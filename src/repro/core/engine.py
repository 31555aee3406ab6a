"""LoopbackEngine — the device-resident multi-step RPC engine.

Dagger's headline numbers come from keeping the *entire* RPC stack off
the host critical path (§4.4, the offload principle): the CPU's only
per-RPC work is one ring write, everything else — fetch, steer, batch,
dispatch, respond — happens on the NIC without a host round-trip.  Our
previous reproduction broke that principle in software: the benchmark rig
called the jitted loopback step from a Python loop and synced the
completion mask to numpy *every step*, which is the software analogue of
the per-RPC PCIe doorbell the paper eliminates (one dispatch + one
device->host sync per pipeline iteration).

This module is the fix.  It fuses K loopback iterations into a single
device program:

* ``run_steps``   — ``jax.lax.scan`` over the fused loopback step with
  the (client FabricState, server FabricState, handler state) triple as
  the carry.  One host dispatch executes K full pipeline iterations; the
  scan carries an on-device ``done`` counter so draining never syncs
  per step.
* ``run_until``   — ``jax.lax.while_loop`` variant for load-latency runs:
  steps until the done counter reaches ``target`` (or ``max_steps``),
  with *dynamic* device-scalar bounds so changing the target never
  retraces (the paper's soft-configuration register model).
* donated buffers — both entry points are jitted with
  ``donate_argnums`` over the carried states, so steady-state iteration
  updates ring buffers, FIFOs and counters in place instead of copying
  the whole FabricState per call (the functional-update analogue of the
  paper's BRAM-resident rings).

The host round-trip budget drops from O(steps) to O(1) per measurement
window — exactly the CCI-P batched-access argument of §4.4, applied to
the reproduction's own dataplane.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import telemetry as tlm
from repro.core.fabric import (DaggerFabric, FabricState,
                               make_loopback_step_stateful)
from repro.debug import sanitize


def _with_telemetry(step):
    """Wrap a loopback step so latency telemetry rides the carry.

    The wrapped step threads ``(hstate, Telemetry)`` where the base step
    threads ``hstate`` alone — which lets every engine reuse its
    scan/while bodies unchanged (telemetry is just more handler state:
    vmapped per tenant, keep-masked by lane freezing, sharded by the
    mesh specs).  Per fused step it observes the drained completions
    (residency = current step - the record's stamped issue step + 1,
    see ``repro.core.telemetry``) and then ticks the step counter, so
    an RPC completing in its issue step records 1.
    """

    def tstep(cst, sst, ht):
        hstate, tel = ht
        cst, sst, hstate, done, dvalid = step(cst, sst, hstate)
        with jax.named_scope(tlm.SCOPE_TELEMETRY):
            flow = None
            if tel.hist.ndim == 2:
                # per-flow histograms (telemetry.create_flows): attribute
                # by the ORIGIN-flow tag in flags bits 8+ (LoadGen.inject
                # stamps it; handlers echo flags, the response path only
                # ORs FLAG_RESPONSE into the low bits).  The RX flow a
                # response drains on is load-balancer-chosen —
                # position-based attribution would just measure the
                # balancer's spread.  Untagged records (flags bits 8+
                # zero) bin under flow 0.
                flow = jnp.clip(done["flags"] >> 8, 0,
                                tel.hist.shape[0] - 1)
            tel = tlm.observe(tel, done["timestamp"], dvalid, flow=flow)
            tel = tlm.tick(tel)
        return cst, sst, (hstate, tel), done, dvalid

    return tstep


def _with_loadgen(step, gen):
    """Wrap a (possibly telemetry-wrapped) step with open-loop injection.

    The wrapped step threads ``(ht, LoadGenState)`` where the inner step
    threads ``ht`` alone — the same carry-extension trick as
    ``_with_telemetry``, so the scan/while bodies, lane freezing and
    mesh specs all cover the generator state for free.  Injection runs
    BEFORE the pipeline step (arrivals of step k are fetchable in step
    k), and the generator's step counter ticks inside ``inject`` in
    lockstep with ``Telemetry.step`` — a request served the step it
    arrives records the 1-step residency floor.
    """

    def gstep(cst, sst, hg):
        ht, gst = hg
        with jax.named_scope(tlm.SCOPE_LOADGEN):
            cst, gst = gen.inject(cst, gst)
        cst, sst, ht, done, dvalid = step(cst, sst, ht)
        return cst, sst, (ht, gst), done, dvalid

    return gstep


def _dispatch_span(entry):
    """Mark the host side of an engine entry (argument handling,
    ``unalias``, the jitted call) as the profiler span
    ``telemetry.SPAN_ENGINE_DISPATCH``, so an idle gap of the device in a
    trace can be told apart from the caller's own host work."""
    return jax.profiler.annotate_function(entry,
                                          name=tlm.SPAN_ENGINE_DISPATCH)


def _bufptr(leaf):
    # Expected failures only — anything else is a real bug and re-raises:
    #   AttributeError  — non-array leaves (Python ints, (), None)
    #   TypeError       — tracers (ConcretizationTypeError subclasses it)
    #   JaxRuntimeError — deleted/donated buffers and sharded arrays,
    #                     where no single buffer pointer exists
    try:
        return leaf.unsafe_buffer_pointer()
    except (AttributeError, TypeError, jax.errors.JaxRuntimeError):
        return None


def _jit_entry(fn, static_argnums=(), donate_argnums=()):
    """``jax.jit`` an engine entry point, honoring ``FABRIC_SANITIZE``.

    Normal mode: plain jit with the requested buffer donation.  Sanitize
    mode (``FABRIC_SANITIZE=1``): the entry point is functionalized
    through ``jax.experimental.checkify`` so the in-step fabric
    invariant checks, OOB-index checks and NaN checks all run, and every
    call raises on the first violation.  Donation is dropped in that
    mode — the checkify error value must not alias a donated carry, and
    sanitized runs are for debugging/CI, not steady-state throughput.
    """
    if sanitize.enabled():
        return sanitize.checked_jit(fn, static_argnums=static_argnums)
    return jax.jit(fn, static_argnums=static_argnums,
                   donate_argnums=donate_argnums)


def unalias(donated, protected=()):
    """Copy leaves of ``donated`` whose buffer aliases a previous leaf.

    JAX dedupes eagerly-created constants (two ``jnp.zeros`` of the same
    shape can share one device buffer), and XLA rejects donating the same
    buffer twice (``f(donate(a), donate(a))``).  Freshly-initialized
    fabric/KVS/cache states are exactly that case, so every donating
    entry point routes its carried state through here first.  Leaves that
    alias ``protected`` (non-donated args) are copied too.

    Stacked tenant states (``stack_states``) are covered by the same
    pointer walk: ``jnp.stack`` of N identical per-tenant leaves is a
    *single* deduped constant shared between e.g. the client and server
    stacks, so the guard must see the batched leaves, not the per-tenant
    slices they were built from.
    """
    seen = set()
    for leaf in jax.tree.leaves(protected):
        p = _bufptr(leaf)
        if p is not None:
            seen.add(p)
    leaves, treedef = jax.tree.flatten(donated)
    out = []
    for leaf in leaves:
        p = _bufptr(leaf)
        if p is not None and p in seen:
            leaf = jnp.copy(leaf)
        elif p is not None:
            seen.add(p)
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def stack_states(states):
    """Stack per-tenant pytrees into one batched pytree (leading axis 0).

    The per-tenant connection tables, rings, FIFOs and counters become
    batched arrays — the stacked ``FabricState`` is what ``TenantEngine``
    vmaps over (the paper's §5.7 virtual NIC slots, one per tenant).

    Returns a NEW pytree whose every leaf is ``[T, ...]`` for T input
    states; the inputs are not consumed.  Note that stacking N identical
    freshly-initialized states can produce leaves that share one device
    buffer (JAX dedupes eager constants) — the engines' donating entry
    points route stacked states through ``unalias`` for exactly this
    reason, so callers never need to copy manually.
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def unstack_states(stacked, n=None):
    """Split a stacked pytree back into its per-tenant slices.

    Returns a list of ``n`` pytrees (default: the leading-axis size),
    each a gathered copy of tenant i's slice — safe to use after the
    stacked tree is donated to a later engine call.  Inverse of
    ``stack_states``."""
    if n is None:
        n = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n)]


def shard_states(states, mesh, axis: str = "tenant", specs=None):
    """Place stacked tenant states on ``mesh``: leading tenant axis
    sharded over ``axis``, everything else replicated.  ``specs`` (a
    PartitionSpec pytree matching ``states``) overrides the default
    leading-axis placement — e.g. the decode tenant's KV caches, which
    additionally shard their kv-head dim over the model axis
    (``parallel.sharding.decode_cache_specs``).

    Specs run through ``parallel.sharding.legalize_specs`` so leaves
    whose leading dim does not divide the axis size (e.g. scalar
    handler-state leaves without a tenant axis) stay replicated instead
    of tripping pjit's even-divisibility requirement.  Placing states up
    front keeps the donating sharded entry points from paying a host
    reshard on every call.

    Returns the same pytree with every leaf device_put onto ``mesh``
    (shapes unchanged); the inputs are not consumed — donation only
    happens inside the engine ``run_*`` calls that receive the placed
    states.  ``ShardedTenantEngine.shard_states`` is the bound
    convenience wrapper.
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import legalize_specs

    if specs is None:
        specs = jax.tree.map(lambda x: P(axis) if jnp.ndim(x) else P(),
                             states)
    specs = legalize_specs(specs, states, mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        states, specs)


class LoopbackEngine:
    """Scan-fused client/server loopback pair (paper §5.1 topology).

    ``handler(records, valid)`` for stateless services, or
    ``handler(records, valid, hstate) -> (response, hstate)`` with
    ``stateful=True`` (e.g. the KVS backend threading its store through
    the steady-state loop).
    """

    def __init__(self, client: DaggerFabric, server: DaggerFabric,
                 handler: Callable, stateful: bool = False,
                 donate: bool = True, loadgen=None):
        self.client = client
        self.server = server
        self.stateful = stateful
        if stateful:
            h = handler
        else:
            def h(recs, valid, hstate):
                return handler(recs, valid), hstate
        self._step = make_loopback_step_stateful(client, server, h)
        if sanitize.enabled():
            # every fused iteration re-proves the ring/FIFO invariants;
            # donation is forced off (see _jit_entry)
            self._step = sanitize.wrap_step(self._step)
            donate = False
        # buffer donation: steady-state ring/FIFO/counter updates reuse
        # the input buffers instead of allocating a fresh FabricState per
        # call.  Default on; pass donate=False to keep inputs alive.
        self._donate = donate
        dargs = (0, 1, 2) if donate else ()
        self._run_steps = _jit_entry(self._mk_run_steps(self._step),
                                     static_argnums=(3,),
                                     donate_argnums=dargs)
        self._run_until = _jit_entry(self._mk_run_until(self._step),
                                     donate_argnums=dargs)
        # telemetry variants: same bodies over the telemetry-wrapped step
        # ((hstate, Telemetry) carried where hstate alone is otherwise)
        tstep = _with_telemetry(self._step)
        self._run_steps_tel = _jit_entry(self._mk_run_steps(tstep),
                                         static_argnums=(3,),
                                         donate_argnums=dargs)
        self._run_until_tel = _jit_entry(self._mk_run_until(tstep),
                                         donate_argnums=dargs)
        self._step_jit = _jit_entry(self._step)
        # open-loop variants: the loadgen-wrapped step carries
        # ((hstate[, tel]), LoadGenState) — injection fused into the
        # same scan/while bodies (traced lazily on first use)
        self.loadgen = loadgen
        self._gen_fns = {}
        if loadgen is not None:
            for wt, stp in ((False, self._step), (True, tstep)):
                g = _with_loadgen(stp, loadgen)
                self._gen_fns[("steps", wt)] = _jit_entry(
                    self._mk_run_steps(g), static_argnums=(3,),
                    donate_argnums=dargs)
                self._gen_fns[("until", wt)] = _jit_entry(
                    self._mk_run_until(g), donate_argnums=dargs)

    def _steps_entry(self, hstate, tel, gen):
        """The jitted program ``run_steps`` calls for these optional
        arguments, and the carry it threads beside the states."""
        hstate = hstate if self.stateful else ()
        ht = hstate if tel is None else (hstate, tel)
        if gen is None:
            return (self._run_steps if tel is None
                    else self._run_steps_tel), ht
        return self._gen_fn("steps", tel), (ht, gen)

    def lower_steps(self, cst: FabricState, sst: FabricState, n_steps: int,
                    hstate=None, tel=None, gen=None):
        """Lower the program ``run_steps`` runs for these arguments
        (a ``jax.stages.Lowered``; nothing runs and nothing is donated).
        Its compiled text names each instruction as a profiler trace of
        the program does, with the ``telemetry.SCOPE_*`` path it ran
        under in its ``op_name``."""
        fn, ht = self._steps_entry(hstate, tel, gen)
        return fn.lower(cst, sst, ht, n_steps)

    def _gen_fn(self, kind: str, tel):
        if self.loadgen is None:
            raise ValueError(
                "engine was built without loadgen=; construct it with a "
                "core.loadgen.LoadGen to drive open-loop state")
        return self._gen_fns[(kind, tel is not None)]

    # ------------------------------------------------------------------
    def _mk_run_steps(self, step):

        def run_steps(cst, sst, hstate, n_steps: int):
            def body(carry, _):
                cst, sst, hstate, done = carry
                cst, sst, hstate, _, dvalid = step(cst, sst, hstate)
                done = done + jnp.sum(dvalid.astype(jnp.int32))
                return (cst, sst, hstate, done), None
            carry = (cst, sst, hstate, jnp.int32(0))
            (cst, sst, hstate, done), _ = jax.lax.scan(
                body, carry, None, length=n_steps)
            return cst, sst, hstate, done

        return run_steps

    def _mk_run_until(self, step):

        def run_until(cst, sst, hstate, target, max_steps):
            target = jnp.asarray(target, jnp.int32)
            max_steps = jnp.asarray(max_steps, jnp.int32)

            def cond(carry):
                _, _, _, done, steps = carry
                return (done < target) & (steps < max_steps)

            def body(carry):
                cst, sst, hstate, done, steps = carry
                cst, sst, hstate, _, dvalid = step(cst, sst, hstate)
                done = done + jnp.sum(dvalid.astype(jnp.int32))
                return cst, sst, hstate, done, steps + 1

            carry = (cst, sst, hstate, jnp.int32(0), jnp.int32(0))
            cst, sst, hstate, done, steps = jax.lax.while_loop(
                cond, body, carry)
            return cst, sst, hstate, done, steps

        return run_until

    # ---------------------------------------------------------- public
    @_dispatch_span
    def run_steps(self, cst: FabricState, sst: FabricState, n_steps: int,
                  hstate=None, tel=None, gen=None):
        """Run ``n_steps`` fused pipeline iterations in ONE device call.

        Returns (cst, sst, n_done) — or (cst, sst, hstate, n_done) when
        stateful.  ``n_done`` is a device scalar: reading it is the only
        host sync of the whole window.  Inputs are donated: treat the
        passed states as consumed and keep the returned ones.

        Pass ``tel`` (a ``telemetry.Telemetry``, donated like the
        states) to carry the on-device latency histogram through the
        scan: completions drained each step are binned by their fabric
        residency (current step - stamped ``timestamp`` + 1) and the
        updated Telemetry is appended to the returns.

        Pass ``gen`` (a ``loadgen.LoadGenState``; requires the engine to
        be constructed with ``loadgen=``) to drive the open-loop
        generator inside the same fused window — arrivals are injected
        at the configured offered rate regardless of completions, and
        the updated state (with its offered/injected/dropped accounting)
        is appended LAST to the returns.
        """
        fn, ht = self._steps_entry(hstate, tel, gen)
        if self._donate:
            cst, sst, ht = unalias((cst, sst, ht))
        cst, sst, ht, done = fn(cst, sst, ht, n_steps)
        return self._returns(cst, sst, ht, (done,), tel is not None,
                             gen is not None)

    @_dispatch_span
    def run_until(self, cst: FabricState, sst: FabricState, target,
                  max_steps, hstate=None, tel=None, gen=None):
        """Step until ``target`` completions (or ``max_steps``), on device.

        Both bounds are dynamic device scalars — sweeping the offered
        load never retraces.  Returns (cst, sst, n_done, n_steps), with
        ``hstate`` inserted before ``n_done`` when stateful and the
        updated Telemetry appended when ``tel`` is passed (see
        ``run_steps``; ``gen`` likewise appends the open-loop generator
        state last).  Inputs are donated, as in ``run_steps``.
        """
        hstate = hstate if self.stateful else ()
        ht = hstate if tel is None else (hstate, tel)
        if gen is None:
            fn = self._run_until if tel is None else self._run_until_tel
        else:
            fn = self._gen_fn("until", tel)
            ht = (ht, gen)
        if self._donate:
            cst, sst, ht = unalias((cst, sst, ht),
                                   protected=(target, max_steps))
        cst, sst, ht, done, steps = fn(cst, sst, ht, target, max_steps)
        return self._returns(cst, sst, ht, (done, steps), tel is not None,
                             gen is not None)

    def _returns(self, cst, sst, ht, tail, with_tel, with_gen=False):
        """Assemble the public return tuple: states, [hstate,] counters,
        [telemetry][, loadgen state] — shared by every engine entry
        point."""
        if with_gen:
            ht, gst = ht
        if with_tel:
            hstate, tel = ht
            tail = tail + (tel,)
        else:
            hstate = ht
        if with_gen:
            tail = tail + (gst,)
        if self.stateful:
            return (cst, sst, hstate) + tail
        return (cst, sst) + tail

    def step(self, cst: FabricState, sst: FabricState, hstate=None):
        """Single fused step (kept for record-level drains and debugging);
        returns (cst, sst[, hstate], done records, dvalid)."""
        cst, sst, hstate, done, dvalid = self._step_jit(cst, sst,
                                                        () if hstate is None
                                                        else hstate)
        if self.stateful:
            return cst, sst, hstate, done, dvalid
        return cst, sst, done, dvalid


def _per_tenant_done(dvalid):
    t = dvalid.shape[0]
    return jnp.sum(dvalid.reshape(t, -1).astype(jnp.int32), axis=1)


def _batched_run_steps(vstep, cst, sst, hstate, n_steps: int):
    """Shared scan body for the tenant-batched engines: K vmapped steps
    over a stacked tenant axis (the full stack for ``TenantEngine``, one
    device's shard under ``shard_map`` for ``ShardedTenantEngine`` — the
    bit-exactness contract between the two rests on them sharing THIS
    code) with per-tenant done counts."""
    t = jax.tree.leaves(cst)[0].shape[0]

    def body(carry, _):
        cst, sst, hstate, done = carry
        cst, sst, hstate, _, dvalid = vstep(cst, sst, hstate)
        return (cst, sst, hstate, done + _per_tenant_done(dvalid)), None

    carry = (cst, sst, hstate, jnp.zeros((t,), jnp.int32))
    (cst, sst, hstate, done), _ = jax.lax.scan(body, carry, None,
                                               length=n_steps)
    return cst, sst, hstate, done


def _batched_run_until(vstep, cst, sst, hstate, target, max_steps):
    """Shared while body for the tenant-batched engines (same sharing
    contract as ``_batched_run_steps``): each lane steps until ITS
    target then freezes — a frozen lane stops mutating exactly like its
    independent run would, which is also what makes the per-device
    early-stopping loops of the sharded engine invisible in the results.
    ``target``/``max_steps`` must already be [T] vectors."""
    t = jax.tree.leaves(cst)[0].shape[0]

    def lanes(carry):
        _, _, _, done, steps = carry
        return (done < target) & (steps < max_steps)

    def cond(carry):
        return jnp.any(lanes(carry))

    def body(carry):
        cst, sst, hstate, done, steps = carry
        act = lanes(carry)
        ncst, nsst, nh, _, dvalid = vstep(cst, sst, hstate)

        def keep(new, old):
            m = act.reshape((t,) + (1,) * (new.ndim - 1))
            return jnp.where(m, new, old)

        cst = jax.tree.map(keep, ncst, cst)
        sst = jax.tree.map(keep, nsst, sst)
        hstate = jax.tree.map(keep, nh, hstate)
        done = jnp.where(act, done + _per_tenant_done(dvalid), done)
        steps = jnp.where(act, steps + 1, steps)
        return cst, sst, hstate, done, steps

    zeros = jnp.zeros((t,), jnp.int32)
    carry = (cst, sst, hstate, zeros, zeros)
    return jax.lax.while_loop(cond, body, carry)


def _global_run_until(vstep, axis, cst, sst, hstate, global_target,
                      max_steps):
    """Per-device while body for ``ShardedTenantEngine.run_until_global``:
    every local lane keeps stepping until the FLEET-WIDE completion
    total (a ``psum`` over the per-device done counters, recomputed in
    the loop predicate) reaches ``global_target`` — the work-stealing
    analogue: a device whose lanes drained early keeps pumping its
    pipeline (more steps, no new completions) instead of freezing, so
    the loop ends for everyone on the same step the global target is
    met.  The psum in the predicate keeps the D device loops in
    lockstep: one all-reduce per step is the price of the global
    termination test.  Returns per-tenant done [T_local] and the
    device's own step count as a [1] vector (stacking to [D] outside
    the shard_map)."""
    t = jax.tree.leaves(cst)[0].shape[0]

    def cond(carry):
        _, _, _, done, steps = carry
        total = jax.lax.psum(jnp.sum(done), axis)
        return (total < global_target) & (steps < max_steps)

    def body(carry):
        cst, sst, hstate, done, steps = carry
        cst, sst, hstate, _, dvalid = vstep(cst, sst, hstate)
        return (cst, sst, hstate, done + _per_tenant_done(dvalid),
                steps + 1)

    carry = (cst, sst, hstate, jnp.zeros((t,), jnp.int32), jnp.int32(0))
    cst, sst, hstate, done, steps = jax.lax.while_loop(cond, body, carry)
    return cst, sst, hstate, done, steps.reshape(1)


class TenantEngine:
    """``LoopbackEngine`` vmapped over a leading tenant axis (§5.7).

    The paper virtualizes the FPGA into N NIC slots, one per microservice
    tier, sharing the fabric fairly.  Here each tenant is an independent
    client/server ``FabricState`` pair (its own rings, FIFOs, connection
    table, counters); stacking the pairs (``stack_states``) turns the
    per-tenant tables into batched arrays and ``jax.vmap`` of the fused
    loopback step drives ALL tenants in one device dispatch — no
    per-tenant host loop, which is the multiplexing argument of Beehive's
    direct-attached stack applied to our dataplane.

    Tenants share hard configuration (the ``DaggerFabric`` pair — the
    paper's synthesized bitstream) but carry independent soft state.  The
    handler must be vmappable (pure jnp); with ``stateful=True`` its
    ``hstate`` is a stacked pytree with the same leading tenant axis.

    Bit-exactness contract (the differential harness pins this):
    ``run_steps`` / ``run_until`` over N stacked pairs produce exactly
    the states N independent ``LoopbackEngine`` runs would.
    """

    def __init__(self, client: DaggerFabric, server: DaggerFabric,
                 handler: Callable, stateful: bool = False,
                 donate: bool = True, loadgen=None):
        self.client = client
        self.server = server
        self.stateful = stateful
        if stateful:
            h = handler
        else:
            def h(recs, valid, hstate):
                return handler(recs, valid), hstate
        base = make_loopback_step_stateful(client, server, h)
        if sanitize.enabled():
            # checkify composes with vmap: the per-step invariant checks
            # run across ALL stacked tenants (jnp.all reduces the batch
            # axis too); donation is forced off (see _jit_entry)
            base = sanitize.wrap_step(base)
            donate = False
        self._vstep = jax.vmap(base)
        self._vstep_tel = jax.vmap(_with_telemetry(base))
        self._donate = donate
        dargs = (0, 1, 2) if donate else ()
        self._run_steps = _jit_entry(self._mk_run_steps(self._vstep),
                                     static_argnums=(3,),
                                     donate_argnums=dargs)
        self._run_until = _jit_entry(self._mk_run_until(self._vstep),
                                     donate_argnums=dargs)
        self._run_steps_tel = _jit_entry(self._mk_run_steps(self._vstep_tel),
                                         static_argnums=(3,),
                                         donate_argnums=dargs)
        self._run_until_tel = _jit_entry(self._mk_run_until(self._vstep_tel),
                                         donate_argnums=dargs)
        self._vstep_jit = _jit_entry(self._vstep)
        # open-loop variants: per-lane LoadGenState rides the vmapped
        # carry like per-tenant Telemetry does (lane freezing included)
        self.loadgen = loadgen
        self._gen_fns = {}
        if loadgen is not None:
            for wt, stp in ((False, base), (True, _with_telemetry(base))):
                g = jax.vmap(_with_loadgen(stp, loadgen))
                self._gen_fns[("steps", wt)] = _jit_entry(
                    self._mk_run_steps(g), static_argnums=(3,),
                    donate_argnums=dargs)
                self._gen_fns[("until", wt)] = _jit_entry(
                    self._mk_run_until(g), donate_argnums=dargs)

    _gen_fn = LoopbackEngine._gen_fn
    _steps_entry = LoopbackEngine._steps_entry
    lower_steps = LoopbackEngine.lower_steps

    # ------------------------------------------------------------------
    @staticmethod
    def _n_tenants(cst):
        return jax.tree.leaves(cst)[0].shape[0]

    def _mk_run_steps(self, vstep):

        def run_steps(cst, sst, hstate, n_steps: int):
            return _batched_run_steps(vstep, cst, sst, hstate, n_steps)

        return run_steps

    def _mk_run_until(self, vstep):

        def run_until(cst, sst, hstate, target, max_steps):
            t = self._n_tenants(cst)
            target = jnp.broadcast_to(jnp.asarray(target, jnp.int32), (t,))
            max_steps = jnp.broadcast_to(jnp.asarray(max_steps, jnp.int32),
                                         (t,))
            return _batched_run_until(vstep, cst, sst, hstate, target,
                                      max_steps)

        return run_until

    _returns = LoopbackEngine._returns

    # ---------------------------------------------------------- public
    @_dispatch_span
    def run_steps(self, cst: FabricState, sst: FabricState, n_steps: int,
                  hstate=None, tel=None, gen=None):
        """Run ``n_steps`` fused iterations for EVERY tenant in one call.

        ``cst``/``sst`` are stacked states (``stack_states``); returns
        (cst, sst, n_done [T]) — or (cst, sst, hstate, n_done [T]) when
        stateful.  Inputs are donated, as in ``LoopbackEngine``.

        ``tel`` (optional, ``telemetry.create_batch(T)``) carries a
        PER-TENANT latency histogram through the vmapped scan — lane i's
        counters evolve exactly as its independent ``LoopbackEngine``
        run's would (the parity harness pins this) — and the updated
        Telemetry is appended to the returns.

        ``gen`` (optional, ``loadgen.init_state_batch``; requires
        ``loadgen=`` at construction) drives a PER-LANE open-loop
        generator — lane i injects at rates[i] regardless of
        completions, same parity contract — appended last.
        """
        fn, ht = self._steps_entry(hstate, tel, gen)
        if self._donate:
            cst, sst, ht = unalias((cst, sst, ht))
        cst, sst, ht, done = fn(cst, sst, ht, n_steps)
        return self._returns(cst, sst, ht, (done,), tel is not None,
                             gen is not None)

    @_dispatch_span
    def run_until(self, cst: FabricState, sst: FabricState, target,
                  max_steps, hstate=None, tel=None, gen=None):
        """Per-tenant ``run_until``: each lane steps until ITS ``target``
        completions (or ``max_steps``), then freezes; one device call for
        the whole batch.  ``target``/``max_steps`` are scalars or [T]
        device vectors (dynamic — sweeping load never retraces).  Returns
        (cst, sst, n_done [T], n_steps [T]); ``hstate`` inserted before
        ``n_done`` when stateful, Telemetry appended when ``tel`` is
        passed (frozen lanes freeze their telemetry too — step counters
        included — so histograms stay bit-identical to independent
        runs; a per-lane ``gen`` freezes the same way).  Inputs are
        donated.
        """
        hstate = hstate if self.stateful else ()
        target = jnp.asarray(target, jnp.int32)
        max_steps = jnp.asarray(max_steps, jnp.int32)
        ht = hstate if tel is None else (hstate, tel)
        if gen is None:
            fn = self._run_until if tel is None else self._run_until_tel
        else:
            fn = self._gen_fn("until", tel)
            ht = (ht, gen)
        if self._donate:
            cst, sst, ht = unalias((cst, sst, ht),
                                   protected=(target, max_steps))
        cst, sst, ht, done, steps = fn(cst, sst, ht, target, max_steps)
        return self._returns(cst, sst, ht, (done, steps), tel is not None,
                             gen is not None)

    def step(self, cst: FabricState, sst: FabricState, hstate=None):
        """Single vmapped step over all tenants (debug/drain aid)."""
        cst, sst, hstate, done, dvalid = self._vstep_jit(
            cst, sst, () if hstate is None else hstate)
        if self.stateful:
            return cst, sst, hstate, done, dvalid
        return cst, sst, done, dvalid


class ShardedTenantEngine:
    """``TenantEngine`` placed on a device mesh via ``shard_map`` — the
    tenant axis becomes the scale-out axis.

    The paper's §5.7 scaling story (84 Mrps only by spreading flows over
    lanes) applied to our dataplane: the stacked tenant axis is sharded
    over a 1-D mesh (``transport.make_tenant_mesh``), so each device owns
    WHOLE NIC slots — a contiguous block of T/D client/server pairs with
    their rings, FIFOs, connection tables and counters resident on that
    device — and runs the fused vmapped loopback step entirely
    device-local.  No collective sits on the steady-state path: loopback
    tenants never talk across slots, so the D device programs proceed
    independently (the Beehive replicate-the-stack-per-lane argument);
    cross-slot tiers use ``Switch.switch_step_sharded``, which routes
    inter-shard records through the ``transport.all_to_all_tiles`` ToR
    hop.

    Bit-exactness contract (pinned by ``tests/test_sharded_parity.py``):
    on ANY mesh shape — 1 device or an N-virtual-device CPU mesh — the
    results equal ``TenantEngine`` on the same stacked states, and
    transitively N independent ``LoopbackEngine`` runs.  ``run_until``'s
    while loop runs per-device, so a shard whose lanes all hit their
    targets stops stepping early; lane freezing makes this invisible in
    the results.  ``run_until_global`` swaps the per-lane quotas for
    ONE fleet-wide target whose while predicate is a ``psum`` over the
    per-device done counters — fast devices keep pumping until the
    fleet total crosses the target (work-stealing-style sweeps).

    ``n_tenants`` must divide evenly over the mesh axis.  States should
    be placed with ``shard_states`` (the constructors in
    ``runtime.kvs`` / ``runtime.serving`` do this) — unplaced states
    work but pay a reshard per call.  All ``run_*`` entry points donate
    their carried states: treat passed states as consumed.

    ``FABRIC_SANITIZE`` intentionally does NOT apply here: checkify
    under ``shard_map`` with per-lane collectives is unsupported, and
    the bit-exactness contract means ``TenantEngine`` (which IS
    sanitized) executes the identical step code over the same states —
    sanitize there, then run sharded.
    """

    def __init__(self, client: DaggerFabric, server: DaggerFabric,
                 handler: Callable, mesh=None, axis: str = "tenant",
                 stateful: bool = False, donate: bool = True,
                 loadgen=None):
        from jax.sharding import PartitionSpec

        from repro.core.transport import shard_map
        from repro.debug import sanitize
        sanitize.note_unsanitized_sharded("ShardedTenantEngine")
        if mesh is None:
            from repro.core.transport import make_tenant_mesh
            mesh = make_tenant_mesh(axis=axis)
        self.client = client
        self.server = server
        self.mesh = mesh
        self.axis = axis
        self.n_devices = mesh.shape[axis]
        self.stateful = stateful
        if stateful:
            h = handler
        else:
            def h(recs, valid, hstate):
                return handler(recs, valid), hstate
        base = make_loopback_step_stateful(client, server, h)
        self._vstep = jax.vmap(base)
        self._vstep_tel = jax.vmap(_with_telemetry(base))
        self._shard_map = shard_map
        self._P = PartitionSpec
        self._donate = donate
        dargs = (0, 1, 2) if donate else ()
        self._run_steps = jax.jit(self._mk_run_steps(self._vstep),
                                  static_argnums=(3,), donate_argnums=dargs)
        self._run_until = jax.jit(self._mk_run_until(self._vstep),
                                  donate_argnums=dargs)
        self._run_until_global = jax.jit(
            self._mk_run_until_global(self._vstep), donate_argnums=dargs)
        self._run_steps_tel = jax.jit(self._mk_run_steps(self._vstep_tel),
                                      static_argnums=(3,),
                                      donate_argnums=dargs)
        self._run_until_tel = jax.jit(self._mk_run_until(self._vstep_tel),
                                      donate_argnums=dargs)
        self._run_until_global_tel = jax.jit(
            self._mk_run_until_global(self._vstep_tel, with_tel=True),
            donate_argnums=dargs)
        # open-loop variants: per-lane LoadGenState shards with the
        # states (every leaf carries the leading tenant axis, so the
        # P(axis) specs cover it for free)
        self.loadgen = loadgen
        self._gen_fns = {}
        if loadgen is not None:
            for wt, stp in ((False, base), (True, _with_telemetry(base))):
                g = jax.vmap(_with_loadgen(stp, loadgen))
                self._gen_fns[("steps", wt)] = jax.jit(
                    self._mk_run_steps(g), static_argnums=(3,),
                    donate_argnums=dargs)
                self._gen_fns[("until", wt)] = jax.jit(
                    self._mk_run_until(g), donate_argnums=dargs)
                self._gen_fns[("until_global", wt)] = jax.jit(
                    self._mk_run_until_global(g, with_tel=wt,
                                              with_gen=True),
                    donate_argnums=dargs)

    _gen_fn = LoopbackEngine._gen_fn
    _steps_entry = LoopbackEngine._steps_entry

    # ------------------------------------------------------------------
    def _specs(self, tree):
        """P(axis) on every leaf — all engine state carries a leading
        tenant dim (stacked scalars included, as [T] vectors)."""
        return jax.tree.map(lambda _: self._P(self.axis), tree)

    def _check_divisible(self, cst):
        t = jax.tree.leaves(cst)[0].shape[0]
        if t % self.n_devices:
            raise ValueError(
                f"n_tenants={t} must divide over the {self.n_devices}"
                f"-device '{self.axis}' mesh axis (whole NIC slots per "
                f"device)")

    def _mk_run_steps(self, vstep):

        def run_steps(cst, sst, hstate, n_steps: int):
            def local_steps(cst, sst, hstate):
                # the SAME scan body TenantEngine runs, over this
                # device's shard of whole NIC slots
                return _batched_run_steps(vstep, cst, sst, hstate,
                                          n_steps)

            specs = (self._specs(cst), self._specs(sst),
                     self._specs(hstate))
            return self._shard_map(
                local_steps, mesh=self.mesh, in_specs=specs,
                out_specs=(*specs, self._P(self.axis)))(cst, sst, hstate)

        return run_steps

    def _mk_run_until(self, vstep):

        # the SAME while body TenantEngine runs, per device: a device
        # whose local lanes all froze simply stops stepping early, which
        # lane freezing makes invisible in the results
        def local_until(cst, sst, hstate, target, max_steps):
            return _batched_run_until(vstep, cst, sst, hstate, target,
                                      max_steps)

        def run_until(cst, sst, hstate, target, max_steps):
            sspec = (self._specs(cst), self._specs(sst),
                     self._specs(hstate))
            lane = self._P(self.axis)
            return self._shard_map(
                local_until, mesh=self.mesh,
                in_specs=(*sspec, lane, lane),
                out_specs=(*sspec, lane, lane))(cst, sst, hstate, target,
                                                max_steps)

        return run_until

    def _mk_run_until_global(self, vstep, with_tel: bool = False,
                             with_gen: bool = False):
        axis = self.axis

        def local_until(cst, sst, hstate, global_target, max_steps):
            out = _global_run_until(vstep, axis, cst, sst, hstate,
                                    global_target, max_steps)
            if not with_tel:
                return out
            # fleet-wide histogram: sum this device's per-tenant
            # histograms, psum across the mesh — every device returns
            # the same replicated [n_bins] total
            cst, sst, ht, done, steps = out
            tel = ht[0][1] if with_gen else ht[1]
            ghist = tlm.merge_hist(tel.hist, axis)
            return cst, sst, ht, done, steps, ghist

        def run_until_global(cst, sst, hstate, global_target, max_steps):
            sspec = (self._specs(cst), self._specs(sst),
                     self._specs(hstate))
            lane = self._P(self.axis)
            repl = self._P()
            outs = (*sspec, lane, lane)
            if with_tel:
                outs = outs + (repl,)
            return self._shard_map(
                local_until, mesh=self.mesh,
                in_specs=(*sspec, repl, repl),
                out_specs=outs)(cst, sst, hstate, global_target, max_steps)

        return run_until_global

    _returns = LoopbackEngine._returns

    # ---------------------------------------------------------- public
    def shard_states(self, *trees):
        """Place stacked state pytrees on this engine's mesh (leading
        tenant axis sharded; see module-level ``shard_states``)."""
        out = tuple(shard_states(t, self.mesh, self.axis) for t in trees)
        return out if len(out) > 1 else out[0]

    @_dispatch_span
    def run_steps(self, cst: FabricState, sst: FabricState, n_steps: int,
                  hstate=None, tel=None, gen=None):
        """Run ``n_steps`` fused iterations for every tenant, each device
        driving its own NIC-slot shard — ONE sharded dispatch.  Same
        signature/returns as ``TenantEngine.run_steps`` (``tel``
        included: the per-tenant Telemetry shards with the states and
        stays bit-identical to the single-device run; ``gen`` likewise —
        the counter-based PRNG makes the sharded arrival sequences
        bit-identical too); inputs donate.
        """
        self._check_divisible(cst)
        fn, ht = self._steps_entry(hstate, tel, gen)
        if self._donate:
            cst, sst, ht = unalias((cst, sst, ht))
        cst, sst, ht, done = fn(cst, sst, ht, n_steps)
        return self._returns(cst, sst, ht, (done,), tel is not None,
                             gen is not None)

    @_dispatch_span
    def run_until(self, cst: FabricState, sst: FabricState, target,
                  max_steps, hstate=None, tel=None, gen=None):
        """Per-tenant ``run_until`` on the mesh: each lane steps until
        ITS target then freezes; each device's while loop ends when its
        local lanes are done.  Same signature/returns as
        ``TenantEngine.run_until``; inputs donate."""
        self._check_divisible(cst)
        t = jax.tree.leaves(cst)[0].shape[0]
        hstate = hstate if self.stateful else ()
        target = jnp.broadcast_to(jnp.asarray(target, jnp.int32), (t,))
        max_steps = jnp.broadcast_to(jnp.asarray(max_steps, jnp.int32),
                                     (t,))
        ht = hstate if tel is None else (hstate, tel)
        if gen is None:
            fn = self._run_until if tel is None else self._run_until_tel
        else:
            fn = self._gen_fn("until", tel)
            ht = (ht, gen)
        if self._donate:
            cst, sst, ht = unalias((cst, sst, ht),
                                   protected=(target, max_steps))
        cst, sst, ht, done, steps = fn(cst, sst, ht, target, max_steps)
        return self._returns(cst, sst, ht, (done, steps), tel is not None,
                             gen is not None)

    @_dispatch_span
    def run_until_global(self, cst: FabricState, sst: FabricState,
                         global_target, max_steps, hstate=None, tel=None,
                         gen=None):
        """Global-completion sweep: every device keeps pumping ALL its
        lanes until the FLEET-WIDE done total (``psum`` over per-device
        counters, evaluated in each device's while predicate) reaches
        ``global_target`` or ``max_steps`` elapse — the
        work-stealing-style load-latency mode: fast devices don't
        freeze at a per-lane quota, they keep absorbing offered load
        until the fleet as a whole has served the target.

        ``global_target``/``max_steps`` are dynamic device scalars
        (sweeping the target never retraces).  Returns
        ``(cst, sst, n_done [T], dev_steps [D])`` with per-TENANT done
        counts and per-DEVICE step counts (the psum predicate ends all
        device loops on the same step, so ``dev_steps`` entries agree —
        reported per device so sweeps can audit the lockstep); ``hstate``
        is inserted before ``n_done`` when stateful.  Inputs are
        donated, as in ``run_steps``.  Unlike ``run_until`` there is no
        per-lane freezing: a drained lane keeps stepping (harmless
        no-ops for loopback traffic) instead of pinning its state to
        the step its own target was met.

        With ``tel`` (a sharded per-tenant Telemetry), the sweep
        additionally returns the FLEET-WIDE latency histogram — the
        per-device per-tenant histograms summed locally and psum-merged
        across the mesh inside the shard_map, replicated on every
        device — appended after the Telemetry:
        ``(cst, sst, [hstate,] n_done, dev_steps, tel,
        global_hist [n_bins])``.  ``gen`` (per-lane open-loop states)
        appends the updated LoadGenState after everything else."""
        self._check_divisible(cst)
        hstate = hstate if self.stateful else ()
        global_target = jnp.asarray(global_target, jnp.int32)
        max_steps = jnp.asarray(max_steps, jnp.int32)
        ht = hstate if tel is None else (hstate, tel)
        if gen is None:
            fn = (self._run_until_global if tel is None
                  else self._run_until_global_tel)
        else:
            fn = self._gen_fn("until_global", tel)
            ht = (ht, gen)
        if self._donate:
            cst, sst, ht = unalias((cst, sst, ht),
                                   protected=(global_target, max_steps))
        out = fn(cst, sst, ht, global_target, max_steps)
        if tel is None:
            cst, sst, ht, done, steps = out
            return self._returns(cst, sst, ht, (done, steps), False,
                                 gen is not None)
        cst, sst, ht, done, steps, ghist = out
        rets = self._returns(cst, sst, ht, (done, steps), True,
                             gen is not None)
        if gen is not None:
            # keep the LoadGenState last: ... tel, ghist, gen
            return rets[:-1] + (ghist, rets[-1])
        return rets + (ghist,)
