"""Shared benchmark helpers: timing + a loopback echo rig."""
from __future__ import annotations

import time
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FabricConfig
from repro.core import serdes
from repro.core.engine import (LoopbackEngine, ShardedTenantEngine,
                               TenantEngine, stack_states)
from repro.core.fabric import DaggerFabric, make_loopback_step
from repro.core.load_balancer import LB_ROUND_ROBIN

Row = Tuple[str, float, str]          # (name, us_per_call, derived)


def tenant_sweep_sizes(n_tenants: int) -> List[int]:
    """Power-of-two ladder up to ``n_tenants``, endpoint included."""
    if n_tenants < 1:
        raise ValueError(f"n_tenants must be >= 1, got {n_tenants}")
    sizes = [1]
    while sizes[-1] * 2 <= n_tenants:
        sizes.append(sizes[-1] * 2)
    if sizes[-1] != n_tenants:
        sizes.append(n_tenants)
    return sizes


def whole_tier_mesh(n_tiers: int):
    """Tenant mesh over ALL of the host's devices, each holding whole
    tiers.  Raises when ``n_tiers`` does not divide over them: a rig
    never runs on fewer devices than the host has without being told
    (pass ``mesh=`` to choose)."""
    from repro.core.transport import make_tenant_mesh
    mesh = make_tenant_mesh()
    d = mesh.shape["tenant"]
    if n_tiers % d:
        raise ValueError(f"{n_tiers} tiers do not divide over {d} devices; "
                         f"pass mesh= to choose the devices")
    return mesh


def timeit(fn: Callable, iters: int, warmup: int = 3) -> float:
    """Mean seconds per call, blocking on fn()'s result.

    ``jax.block_until_ready`` on the returned value is what makes this
    measure compute, not async dispatch: without it every µs row
    under-reports by the device queue depth.  Closures must therefore
    return (one of) the arrays they produce.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / iters


class EchoRig:
    """Client/server fabric pair with an echo handler (paper loopback).

    Two drive modes:

    * ``pump_until`` — the legacy host loop: one jit dispatch + one
      device->host sync per step (kept as the kernel-stack-style baseline
      the engine rows are compared against);
    * ``pump_k`` / ``run_until`` — the scan-fused ``LoopbackEngine``:
      K pipeline iterations per dispatch, done-counting on device,
      donated state.
    """

    def __init__(self, n_flows: int = 4, batch: int = 4,
                 ring_entries: int = 64, dynamic: bool = False):
        cfg = FabricConfig(n_flows=n_flows, ring_entries=ring_entries,
                           batch_size=batch, dynamic_batching=dynamic)
        self.cfg = cfg
        self.client = DaggerFabric(cfg)
        self.server = DaggerFabric(cfg)
        self.cst = self.client.init_state()
        self.sst = self.server.init_state()
        self.cst = self.client.open_connection(self.cst, 1, 0, 1,
                                               LB_ROUND_ROBIN)
        self.sst = self.server.open_connection(self.sst, 1, 0, 0,
                                               LB_ROUND_ROBIN)

        def echo(recs, valid):
            out = dict(recs)
            out["payload"] = recs["payload"] + 1
            return out

        self.step = jax.jit(make_loopback_step(self.client, self.server,
                                               echo))
        self.engine = LoopbackEngine(self.client, self.server, echo)
        self.enqueue = jax.jit(self.client.host_tx_enqueue)
        self.pw = self.client.slot_words - serdes.HEADER_WORDS

    def records(self, n: int, rpc_base: int = 0, timestamp=0):
        pay = jnp.tile(jnp.arange(self.pw, dtype=jnp.int32)[None], (n, 1))
        return serdes.make_records(
            jnp.full((n,), 1, jnp.int32),
            jnp.arange(n, dtype=jnp.int32) + rpc_base,
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32), pay,
            timestamp=timestamp)

    # ------------------------------------------------- engine drive mode
    def pump_k(self, k: int):
        """K fused steps, one dispatch; returns the done count (device
        scalar — block/int() it to sync)."""
        self.cst, self.sst, done = self.engine.run_steps(self.cst, self.sst,
                                                         k)
        return done

    def run_until(self, want: int, max_steps: int = 64) -> int:
        """Device-resident drain: steps until ``want`` completions without
        any per-step host sync (one sync total, for the return value)."""
        self.cst, self.sst, done, _ = self.engine.run_until(
            self.cst, self.sst, want, max_steps)
        return int(done)

    def drain_tel(self, want: int, max_steps: int, tel):
        """Telemetry drain: like ``run_until`` but carrying the latency
        histogram; returns (got, steps, tel')."""
        self.cst, self.sst, done, steps, tel = self.engine.run_until(
            self.cst, self.sst, want, max_steps, tel=tel)
        return int(done), int(steps), tel

    # ------------------------------------------------- legacy host loop
    def pump_until(self, want: int, max_steps: int = 64) -> int:
        """Python pump loop: dispatch + numpy sync per step (baseline)."""
        done = 0
        for _ in range(max_steps):
            self.cst, self.sst, _, dvalid = self.step(self.cst, self.sst)
            done += int(np.asarray(dvalid).sum())
            if done >= want:
                break
        return done


class TenantEchoRig:
    """N independent client/server echo pairs behind ONE TenantEngine.

    The tenant analogue of ``EchoRig``: per-tenant states (own rings,
    FIFOs, connection tables) stacked along a leading axis, all driven by
    a single vmapped dispatch — the paper's §5.7 virtual NIC slots.
    """

    def __init__(self, n_tenants: int, n_flows: int = 4, batch: int = 4,
                 ring_entries: int = 64, use_pallas: bool = False,
                 request_buffer_slots: int = 0):
        cfg = FabricConfig(n_flows=n_flows, ring_entries=ring_entries,
                           batch_size=batch, dynamic_batching=False,
                           use_pallas=use_pallas,
                           request_buffer_slots=request_buffer_slots)
        self.cfg = cfg
        self.n_tenants = n_tenants
        self.client = DaggerFabric(cfg)
        self.server = DaggerFabric(cfg)
        self.cst, self.sst = self._fresh_states()

        def echo(recs, valid):
            out = dict(recs)
            out["payload"] = recs["payload"] + 1
            return out

        self.engine = self._make_engine(echo)
        self._enqueue = jax.jit(jax.vmap(self.client.host_tx_enqueue,
                                         in_axes=(0, None, None)))
        self.pw = self.client.slot_words - serdes.HEADER_WORDS

    def _make_engine(self, echo):
        return TenantEngine(self.client, self.server, echo)

    def _fresh_states(self):
        """Freshly-initialized stacked per-tenant state pair (sweep rigs
        rebuild between measurement points — donated buffers are
        consumed per run)."""
        csts, ssts = [], []
        for _ in range(self.n_tenants):
            cst, sst = self.client.init_state(), self.server.init_state()
            cst = self.client.open_connection(cst, 1, 0, 1,
                                              LB_ROUND_ROBIN)
            sst = self.server.open_connection(sst, 1, 0, 0,
                                              LB_ROUND_ROBIN)
            csts.append(cst)
            ssts.append(sst)
        return stack_states(csts), stack_states(ssts)

    def records(self, n: int, rpc_base: int = 0):
        pay = jnp.tile(jnp.arange(self.pw, dtype=jnp.int32)[None], (n, 1))
        return serdes.make_records(
            jnp.full((n,), 1, jnp.int32),
            jnp.arange(n, dtype=jnp.int32) + rpc_base,
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32), pay)

    def enqueue_all(self, n: int):
        """Same request tile into every tenant's client TX rings — one
        vmapped dispatch (each tenant's conn table maps conn 1)."""
        flows = jnp.arange(n) % self.cfg.n_flows
        self.cst, _ = self._enqueue(self.cst, self.records(n), flows)

    def pump_k(self, k: int):
        """K fused steps for ALL tenants, one dispatch; returns per-tenant
        done counts (device array — sync by reading it)."""
        self.cst, self.sst, done = self.engine.run_steps(self.cst,
                                                         self.sst, k)
        return done


class SwitchEchoRig:
    """N-tier sharded L2 switch with sparse cross-tier load: tier 0 fans
    out to the back half of the mesh, everything else serves.

    The rig behind the ``fig11.compacted_exchange`` rows: the same
    prepared state is stepped through ``switch_step_sharded`` with the
    full-tile exchange (ship everything + mask) and the compacted one
    (ship destined rows + count), so the timing difference isolates the
    exchange format.  ``load_per_conn`` requests per connection keeps
    the cross-tier traffic far below the tile capacity — the sparse
    regime where compaction pays.
    """

    def __init__(self, n_tiers: int = 8, n_flows: int = 2,
                 batch: int = 4, ring_entries: int = 32,
                 load_per_conn: int = 1, mesh=None):
        from repro.core.engine import shard_states
        from repro.core.virtualization import Switch
        if mesh is None:
            mesh = whole_tier_mesh(n_tiers)
        self.mesh = mesh
        self.n_tiers = n_tiers
        cfg = FabricConfig(n_flows=n_flows, ring_entries=ring_entries,
                           batch_size=batch, dynamic_batching=False)
        fabrics = [DaggerFabric(cfg) for _ in range(n_tiers)]
        self.sw = Switch(fabrics)
        states = self.sw.init_states()
        conns = []
        for i, dst in enumerate(range(n_tiers // 2, n_tiers)):
            c = 10 + i
            states[0] = fabrics[0].open_connection(states[0], c, 0, dst,
                                                   LB_ROUND_ROBIN)
            states[dst] = fabrics[dst].open_connection(states[dst], c,
                                                       0, 0,
                                                       LB_ROUND_ROBIN)
            conns.append(c)

        def echo(recs, valid):
            out = dict(recs)
            out["payload"] = recs["payload"] + 1
            return out

        self.handlers = [None] * (n_tiers // 2) + \
            [echo] * (n_tiers - n_tiers // 2)
        pw = fabrics[0].slot_words - serdes.HEADER_WORDS
        n = load_per_conn * len(conns)
        pay = jnp.tile(jnp.arange(pw, dtype=jnp.int32)[None], (n, 1))
        recs = serdes.make_records(
            jnp.asarray(conns * load_per_conn, jnp.int32),
            jnp.arange(n, dtype=jnp.int32), jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.int32), pay)
        states[0], _ = jax.jit(fabrics[0].host_tx_enqueue)(
            states[0], recs, jnp.arange(n) % n_flows)
        self.stacked = shard_states(self.sw.stack_states(states),
                                    self.mesh)
        d = self.mesh.shape["tenant"]
        self.n_dev = d
        # local candidate rows per device: tiers/device * flows * batch
        self.local_rows = (n_tiers // d) * n_flows * batch
        self.slot_words = fabrics[0].slot_words

    def step_fn(self, exchange: str = "full", bucket_cap=None):
        """Jitted one-step closure over the prepared state (pure: the
        rig state is NOT advanced, so successive calls time the same
        exchange)."""
        return jax.jit(lambda s: self.sw.switch_step_sharded(
            s, self.handlers, mesh=self.mesh, exchange=exchange,
            bucket_cap=bucket_cap))


class ShardedTenantEchoRig(TenantEchoRig):
    """``TenantEchoRig`` on the mesh: the stacked tenant axis sharded
    over the host's devices (``ShardedTenantEngine``), so each device
    drives its own block of NIC slots.  ``n_tenants`` must divide the
    device count; on a 1-device host this degrades to the batched rig
    plus shard_map overhead (the fig11 ``sharded_scaling`` rows quantify
    both regimes)."""

    def __init__(self, n_tenants: int, mesh=None, **kw):
        from repro.core.transport import make_tenant_mesh
        self.mesh = make_tenant_mesh() if mesh is None else mesh
        super().__init__(n_tenants, **kw)
        self.cst, self.sst = self.engine.shard_states(self.cst, self.sst)

    def _make_engine(self, echo):
        return ShardedTenantEngine(self.client, self.server, echo,
                                   mesh=self.mesh)

    def run_until(self, targets, max_steps: int):
        """Per-lane drain: each lane freezes at ITS target (one sharded
        dispatch; returns per-tenant done)."""
        self.cst, self.sst, done, _ = self.engine.run_until(
            self.cst, self.sst, targets, max_steps)
        return done

    def run_until_global(self, global_target, max_steps: int):
        """Fleet-wide drain: every device pumps until the psum of done
        counters reaches ``global_target`` (the work-stealing sweep);
        returns (per-tenant done, per-device steps)."""
        self.cst, self.sst, done, dev_steps = self.engine.run_until_global(
            self.cst, self.sst, global_target, max_steps)
        return done, dev_steps


class OpenLoopTenantRig(TenantEchoRig):
    """``TenantEchoRig`` driven by the on-device open-loop generator.

    The rig behind the ``fig11.load_sweep.*`` rows: no host enqueue at
    all — per-tenant ``LoadGenState`` rides the engine carry and injects
    at the configured offered rate regardless of completions, so
    sweeping ``rates`` maps out latency vs OFFERED load up to and past
    the saturation knee.  The offered rate is a device register in the
    generator state: every sweep point reuses one compiled program.

    These rigs keep ``dynamic_batching=False`` (force_flush): partial
    batches emit immediately, so the low-load latency floor is flat and
    the p99-vs-load curve is monotone — with batch-fill waiting enabled,
    LOW offered load would queue longer than moderate load (the paper's
    B=4 batching tradeoff) and the CI knee gate would see an inverted
    curve.
    """

    def __init__(self, n_tenants: int, mode=None, tile=None,
                 flow_weights=None, **kw):
        from repro.core import loadgen
        self._mode = loadgen.MODE_DETERMINISTIC if mode is None else mode
        self._tile = tile
        self._flow_weights = flow_weights
        super().__init__(n_tenants, **kw)

    def _make_engine(self, echo):
        from repro.core import loadgen
        self.gen = loadgen.LoadGen(self.client, mode=self._mode,
                                   tile=self._tile,
                                   flow_weights=self._flow_weights)
        return TenantEngine(self.client, self.server, echo,
                            loadgen=self.gen)

    def reset(self):
        """Fresh fabric states for the next sweep point (the previous
        point's states were donated away)."""
        self.cst, self.sst = self._fresh_states()

    def fresh_gen(self, rates, seeds=None):
        """Per-tenant generator states + telemetry for one sweep point
        (both counters start at 0 — the step-stamp alignment
        contract)."""
        from repro.core import telemetry as tlm
        gst = self.gen.init_state_batch(rates, seeds=seeds)
        return gst, tlm.create_batch(self.n_tenants)

    def run_open_loop(self, rates, steps: int, seeds=None, tel=None):
        """ONE fused device window: inject at per-tenant ``rates`` for
        ``steps`` steps, returning (per-tenant done, telemetry,
        generator state with its offered/injected/dropped
        accounting)."""
        gst, tel0 = self.fresh_gen(rates, seeds=seeds)
        tel = tel0 if tel is None else tel
        self.cst, self.sst, done, tel, gst = self.engine.run_steps(
            self.cst, self.sst, steps, tel=tel, gen=gst)
        return done, tel, gst


class OpenLoopShardedRig(OpenLoopTenantRig):
    """``OpenLoopTenantRig`` on the mesh: per-lane generator state
    shards with the fabric states, injection runs device-local inside
    the shard_map — the open-loop analogue of
    ``ShardedTenantEchoRig``."""

    def __init__(self, n_tenants: int, mesh=None, **kw):
        from repro.core.transport import make_tenant_mesh
        self.mesh = make_tenant_mesh() if mesh is None else mesh
        super().__init__(n_tenants, **kw)
        self.cst, self.sst = self.engine.shard_states(self.cst, self.sst)

    def _make_engine(self, echo):
        from repro.core import loadgen
        self.gen = loadgen.LoadGen(self.client, mode=self._mode,
                                   tile=self._tile,
                                   flow_weights=self._flow_weights)
        return ShardedTenantEngine(self.client, self.server, echo,
                                   mesh=self.mesh, loadgen=self.gen)

    def reset(self):
        super().reset()
        self.cst, self.sst = self.engine.shard_states(self.cst, self.sst)

    def fresh_gen(self, rates, seeds=None):
        gst, tel = super().fresh_gen(rates, seeds=seeds)
        return self.engine.shard_states(gst, tel)


class OpenLoopSwitchRig:
    """N-tier sharded L2 switch under open-loop load: every front-half
    tier injects at the offered rate on its cross-tier connection
    (tier i -> tier ``n/2 + i``), the back half echoes — the
    compact-exchange leg of the ``fig11.load_sweep``.  ``run_fn`` scans
    ``switch_step_sharded`` (full or compacted exchange) into one fused
    multi-step device program with per-tier telemetry and generator
    state in the carry."""

    def __init__(self, n_tiers: int = 8, n_flows: int = 2,
                 batch: int = 4, ring_entries: int = 32, mesh=None,
                 mode=None, tile=None):
        from repro.core import loadgen
        from repro.core.virtualization import Switch
        if mesh is None:
            mesh = whole_tier_mesh(n_tiers)
        self.mesh = mesh
        self.n_tiers = n_tiers
        cfg = FabricConfig(n_flows=n_flows, ring_entries=ring_entries,
                           batch_size=batch, dynamic_batching=False)
        self.fabrics = [DaggerFabric(cfg) for _ in range(n_tiers)]
        self.sw = Switch(self.fabrics)
        self.conns = [10 + i for i in range(n_tiers // 2)]

        def echo(recs, valid):
            out = dict(recs)
            out["payload"] = recs["payload"] + 1
            return out

        self.handlers = [None] * (n_tiers // 2) + \
            [echo] * (n_tiers - n_tiers // 2)
        self.gen = loadgen.LoadGen(
            self.fabrics[0],
            mode=loadgen.MODE_DETERMINISTIC if mode is None else mode,
            tile=tile)
        d = self.mesh.shape["tenant"]
        self.n_dev = d
        self.local_rows = (n_tiers // d) * n_flows * batch

    def fresh(self, rate: float, seeds=None):
        """Fresh sharded (stacked states, telemetry, generator state)
        for one sweep point: front-half tiers offer ``rate`` each on
        their cross-tier connection, serving tiers offer 0."""
        from repro.core import telemetry as tlm
        from repro.core.engine import shard_states
        states = self.sw.init_states()
        half = self.n_tiers // 2
        for i, c in enumerate(self.conns):
            dst = half + i
            states[i] = self.fabrics[i].open_connection(
                states[i], c, 0, dst, LB_ROUND_ROBIN)
            states[dst] = self.fabrics[dst].open_connection(
                states[dst], c, 0, i, LB_ROUND_ROBIN)
        rates = [rate] * half + [0.0] * half
        gst = self.gen.init_state_batch(
            rates, seeds=seeds, conns=self.conns + [0] * half)
        tel = tlm.create_batch(self.n_tiers)
        stacked = self.sw.stack_states(states)
        return (shard_states(stacked, self.mesh),
                shard_states(tel, self.mesh),
                shard_states(gst, self.mesh))

    def run_fn(self, exchange: str = "full", bucket_cap=None,
               steps: int = 16):
        """Jitted ``steps``-step open-loop window:
        ``run(stacked, tel, gst) -> (stacked', tel', gst')`` — the
        sharded switch step scanned on device, donating its carry."""

        def body(carry, _):
            st, tel, gst = carry
            st, _, tel, gst = self.sw.switch_step_sharded(
                st, self.handlers, mesh=self.mesh, exchange=exchange,
                bucket_cap=bucket_cap, tel=tel, loadgen=self.gen,
                gen=gst)
            return (st, tel, gst), None

        def run(st, tel, gst):
            (st, tel, gst), _ = jax.lax.scan(body, (st, tel, gst), None,
                                             length=steps)
            return st, tel, gst

        jitted = jax.jit(run, donate_argnums=(0, 1, 2))

        def call(st, tel, gst):
            # freshly-initialized carries share deduped zero buffers;
            # donation requires distinct ones
            from repro.core.engine import unalias
            st, tel, gst = unalias((st, tel, gst))
            return jitted(st, tel, gst)

        return call
