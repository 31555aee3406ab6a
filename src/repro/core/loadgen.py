"""On-device open-loop load generation — offered load as a device process.

Everything the fabric measured before this module was CLOSED-loop: the
host enqueued a wave, the engine drained it, and the next wave waited
for the completions.  Closed loops cannot reproduce Dagger's headline
artifact — the latency-vs-OFFERED-load curves of Fig. 11 climbing to
saturation (84 Mrps) — because a closed loop slows its own arrival rate
exactly when the system congests, which hides the knee.  An open-loop
generator injects at a configured rate REGARDLESS of completions, so
past saturation the queues grow, the drop counters move, and the tail
is measured under the load that caused it.

Design (mirrors the Telemetry pattern of ``repro.core.telemetry``):

* **All state is an int32 pytree** (``LoadGenState``) that rides the
  engine scan/while carry exactly like ``Telemetry`` does — vmapped per
  tenant, keep-masked by lane freezing, sharded by the mesh specs.  The
  host is NOT in the loop: ``LoadGen.inject`` runs INSIDE the fused
  step, packing step-stamped records straight into the client TX rings.
* **Counter-based PRNG** — randomness is a pure hash of
  ``(lane key, step counter, salt)`` (SplitMix-style integer mixing),
  never a mutable RNG stream.  The arrival sequence is therefore a pure
  function of ``(seed, step)``: bit-identical under ``jax.vmap``
  (TenantEngine) and ``shard_map`` (ShardedTenantEngine), which is what
  the Loopback == Tenant == Sharded parity ladder in
  ``tests/test_loadgen.py`` pins.
* **Three arrival processes** (hard config, like a synthesized
  bitstream; the RATE is a soft device register in the state, so
  sweeping offered load never retraces):

  - ``MODE_DETERMINISTIC`` — a Q16.16 fixed-point accumulator emits
    exactly ``floor(steps * rate)`` arrivals over any window (integer
    rates: exactly ``rate * steps``), fractional arrears carried in the
    state;
  - ``MODE_POISSON`` — per-step arrival counts drawn by inverse-CDF
    from a Poisson(rate) truncated at the injection tile width, one
    counter-hash uniform per step;
  - ``MODE_BURSTY`` — a two-state on/off Markov chain (transition
    probabilities in Q0.16, compared against hash bits — integer
    arithmetic only) gating the deterministic accumulator: mean offered
    rate = ``rate * p_on / (p_on + p_off)``.

* **Queue-growth and drop accounting** — the generator never blocks.
  Every arrival is either *injected* (accepted by the TX ring) or
  *dropped* (ring full, or the raw count exceeded the tile width), so

      ``offered == injected + dropped``                 (by construction)
      ``injected == completed + in_flight + fabric_drops``   (conserved)

  with ``in_flight`` the ring/FIFO occupancy of both fabric states and
  ``fabric_drops`` the packet-monitor drop counters downstream of the
  TX ring (``tests/test_properties.py`` pins the invariant past
  saturation).

**Step-stamp alignment contract**: ``inject`` stamps records with the
generator's own step counter, which ticks once per fused step exactly
like ``Telemetry.step``.  Thread a FRESH ``LoadGenState`` together with
a fresh ``Telemetry`` (both counters 0) — or states advanced by the
same engine — and residencies come out exact; the engines inject
BEFORE the pipeline step, so a request served immediately records the
1-step floor.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import serdes
from repro.core.fabric import DaggerFabric, FabricState

MODE_DETERMINISTIC = 0
MODE_POISSON = 1
MODE_BURSTY = 2

RATE_SHIFT = 16                   # offered rate is Q16.16 requests/step
RATE_ONE = 1 << RATE_SHIFT

_SALT_ARRIVAL = 1
_SALT_BURST = 2
_SALT_FLOW = 3

ARR_BINS = 16            # arrival-count histogram width (counts >= 15 bin
                         # together — raw counts are tile-bounded anyway)


@jax.tree_util.register_dataclass
@dataclass
class LoadGenState:
    """Per-lane open-loop generator state (all int32 — vmap/shard/donate
    like every other carry pytree).  ``rate`` is the SOFT register: a
    device scalar swept without retracing, exactly like the engines'
    dynamic ``target``/``max_steps`` bounds."""
    key: jnp.ndarray        # lane seed of the counter PRNG
    step: jnp.ndarray       # generator step (ticks once per fused step)
    rate: jnp.ndarray       # offered rate, Q16.16 requests/step (soft)
    acc: jnp.ndarray        # Q16 fractional arrears (deterministic/bursty)
    burst_on: jnp.ndarray   # on/off Markov state (bursty mode)
    conn: jnp.ndarray       # connection id the lane injects on
    next_rpc: jnp.ndarray   # next rpc_id to assign
    offered: jnp.ndarray    # total arrivals generated
    injected: jnp.ndarray   # accepted into the TX ring
    dropped: jnp.ndarray    # offered - injected (tile clip + ring full)
    arr_hist: jnp.ndarray   # [ARR_BINS] int32 — arrival-count histogram:
                            # arr_hist[k] = steps with k raw arrivals
                            # (last bin overflows); sum == step always


def seed_key(seed: int) -> int:
    """An outside seed (any Python int) as the int32 lane key: reduced
    mod 2**32 and read as two's complement, so every seed has a key
    and seeds equal mod 2**32 share one."""
    k = int(seed) % (1 << 32)
    return k - (1 << 32) if k >= (1 << 31) else k


def rate_q16(rate: float) -> int:
    """Offered rate in requests/step -> the Q16.16 register value."""
    return int(round(rate * RATE_ONE))


# ---------------------------------------------------------------- PRNG
def _mix32(x):
    """SplitMix-style avalanche over uint32 (pure element-wise ops —
    bit-identical under vmap/shard_map on any backend)."""
    x = jnp.asarray(x, jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def counter_hash(key, ctr, salt):
    """uint32 hash of (lane key, step counter, salt) — the counter-based
    PRNG.  No stream state: the value is a pure function of its inputs,
    so every engine derives the SAME arrival randomness from the same
    (seed, step) regardless of batching or sharding."""
    x = (jnp.asarray(key, jnp.uint32) * jnp.uint32(0x9E3779B9)
         ^ jnp.asarray(ctr, jnp.uint32) * jnp.uint32(0x85EBCA6B)
         ^ jnp.asarray(salt, jnp.uint32) * jnp.uint32(0xC2B2AE35))
    return _mix32(x)


def counter_uniform(key, ctr, salt):
    """float32 uniform in [0, 1) from the top 24 hash bits."""
    return (counter_hash(key, ctr, salt) >> jnp.uint32(8)).astype(
        jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _poisson_count(lam, u, tile: int):
    """Inverse-CDF Poisson(lam) sample truncated at ``tile``.

    pmf(k) built by the stable recurrence ``p_k = p_{k-1} * lam / k``;
    the count is the number of CDF entries <= u, so the (negligible for
    ``lam << tile``) tail mass collapses onto ``tile``.  float32
    element-wise ops + a fixed-order cumsum — deterministic and
    vmap-invariant on a given backend.
    """
    k = jnp.arange(tile, dtype=jnp.float32)
    pmf = jnp.exp(-lam) * jnp.cumprod(
        jnp.where(k == 0, 1.0, lam / jnp.maximum(k, 1.0)))
    cdf = jnp.cumsum(pmf)                       # cdf[k] = P(X <= k)
    return jnp.sum((u >= cdf).astype(jnp.int32))


class LoadGen:
    """Hard configuration of the open-loop generator (the bitstream
    half: arrival-process MODE, injection tile width, flow policy).
    Per-lane soft state — rate, seed, connection — lives in
    ``LoadGenState``.

    ``flow_weights`` (optional) skews the per-request flow choice by a
    fixed weight vector (e.g. Zipf over flows — the fig12 z99 skew
    applied to TRAFFIC): each record draws a flow from the Q0.16
    inverse-CDF table with one counter-hash per record lane.  Default is
    deterministic round-robin (``rpc_id % n_flows``).
    """

    def __init__(self, fab: DaggerFabric, mode: int = MODE_DETERMINISTIC,
                 tile: Optional[int] = None, fn_id: int = 0,
                 p_on: float = 0.125, p_off: float = 0.125,
                 flow_weights: Optional[Sequence[float]] = None,
                 payload_fn=None):
        if mode not in (MODE_DETERMINISTIC, MODE_POISSON, MODE_BURSTY):
            raise ValueError(f"unknown loadgen mode {mode}")
        self.fab = fab
        self.mode = mode
        self.tile = (fab.cfg.n_flows * fab.cfg.batch_size
                     if tile is None else int(tile))
        if self.tile < 1:
            raise ValueError("injection tile must be >= 1")
        self.fn_id = int(fn_id)
        self.pw = fab.slot_words - serdes.HEADER_WORDS
        # payload_fn(gst, lane, rpc_id) -> [tile, pw] int32 overrides the
        # default synthetic payload — application tenants (LM decode) use
        # it to encode real request arguments; it must be a pure function
        # of counter-PRNG state so batched/sharded engines stay parity
        self.payload_fn = payload_fn
        # Q0.16 transition probabilities, compared against hash bits
        self.p_on_q16 = int(round(p_on * (1 << 16)))
        self.p_off_q16 = int(round(p_off * (1 << 16)))
        if flow_weights is None:
            self.flow_cdf_q16 = None
        else:
            w = [float(x) for x in flow_weights]
            if len(w) != fab.cfg.n_flows or min(w) < 0 or sum(w) <= 0:
                raise ValueError("flow_weights must be n_flows "
                                 "non-negative weights")
            tot = sum(w)
            acc, cdf = 0.0, []
            for x in w:
                acc += x / tot
                cdf.append(min(int(round(acc * (1 << 16))), 1 << 16))
            # table has n_flows-1 thresholds; flow = #{thresholds <= u}
            self.flow_cdf_q16 = jnp.asarray(cdf[:-1], jnp.int32)

    # ------------------------------------------------------------ state
    def init_state(self, rate: float, seed: int = 0,
                   conn: int = 1) -> LoadGenState:
        """Fresh scalar generator state at ``rate`` requests/step."""
        z = jnp.int32(0)
        return LoadGenState(
            key=jnp.int32(seed_key(seed)), step=z,
            rate=jnp.int32(rate_q16(rate)),
            acc=z, burst_on=jnp.int32(1), conn=jnp.int32(conn),
            next_rpc=z, offered=z, injected=z, dropped=z,
            arr_hist=jnp.zeros((ARR_BINS,), jnp.int32))

    def init_state_batch(self, rates: Sequence[float],
                         seeds: Optional[Sequence[int]] = None,
                         conns: Optional[Sequence[int]] = None
                         ) -> LoadGenState:
        """Stacked per-lane states (leading tenant/tier axis) — lane i
        offers ``rates[i]`` with its own PRNG key, the shape the vmapped
        and sharded engines thread (Zipf-skewed per-tenant rates are
        just a skewed ``rates`` vector)."""
        n = len(rates)
        seeds = list(range(n)) if seeds is None else list(seeds)
        conns = [1] * n if conns is None else list(conns)
        if not (len(seeds) == len(conns) == n):
            raise ValueError("rates/seeds/conns must have equal length")
        z = jnp.zeros((n,), jnp.int32)
        return LoadGenState(
            key=jnp.asarray([seed_key(s) for s in seeds], jnp.int32),
            step=z,
            rate=jnp.asarray([rate_q16(r) for r in rates], jnp.int32),
            acc=z, burst_on=jnp.ones((n,), jnp.int32),
            conn=jnp.asarray(conns, jnp.int32),
            next_rpc=z, offered=z, injected=z, dropped=z,
            arr_hist=jnp.zeros((n, ARR_BINS), jnp.int32))

    # --------------------------------------------------------- arrivals
    def arrivals(self, gst: LoadGenState):
        """One step of the arrival process: ``(raw_count, gst')``.

        Advances ONLY the process state (step, arrears, burst phase) —
        the injection counters move in ``inject``.  ``raw_count`` is the
        number of arrivals this step BEFORE the tile clip, so summing it
        over a window gives the exact offered load.
        """
        step0 = gst.step
        if self.mode == MODE_POISSON:
            lam = gst.rate.astype(jnp.float32) * jnp.float32(1.0 / RATE_ONE)
            u = counter_uniform(gst.key, step0, _SALT_ARRIVAL)
            raw = _poisson_count(lam, u, self.tile)
            acc, burst = gst.acc, gst.burst_on
        else:
            burst = gst.burst_on
            if self.mode == MODE_BURSTY:
                # on/off Markov chain: flip on hash bits vs Q0.16 probs
                u16 = (counter_hash(gst.key, step0, _SALT_BURST)
                       & jnp.uint32(0xFFFF)).astype(jnp.int32)
                p_flip = jnp.where(burst != 0, self.p_off_q16,
                                   self.p_on_q16)
                burst = jnp.where(u16 < p_flip, 1 - burst, burst)
                rate = jnp.where(burst != 0, gst.rate, 0)
            else:
                rate = gst.rate
            # Bresenham accumulation: integer part emits, fraction carries
            acc = gst.acc + rate
            raw = acc >> RATE_SHIFT
            acc = acc & jnp.int32(RATE_ONE - 1)
        # arrival-count histogram: one entry per step at this step's raw
        # count (overflow last bin) — arr_hist.sum() == step invariant
        b = jnp.clip(raw, 0, gst.arr_hist.shape[-1] - 1)
        if gst.arr_hist.ndim == 1:
            ah = gst.arr_hist.at[b].add(1)
        else:           # stacked lanes scanned without vmap
            ah = gst.arr_hist.at[
                jnp.arange(gst.arr_hist.shape[0]), b].add(1)
        gst = dataclasses.replace(gst, step=step0 + 1, acc=acc,
                                  burst_on=burst, arr_hist=ah)
        return raw, gst

    def sample_counts(self, gst: LoadGenState, n_steps: int):
        """Host-side harness: scan the arrival process ALONE (no fabric)
        for ``n_steps`` — returns ``(counts [n_steps], gst')``.  The
        statistical tests (chi-square vs the Poisson pmf, exact
        deterministic totals, burst duty cycles) and the vmap-parity
        checks run on this."""
        def body(g, _):
            raw, g = self.arrivals(g)
            return g, raw
        gst, counts = jax.lax.scan(body, gst, None, length=n_steps)
        return counts, gst

    # -------------------------------------------------------- injection
    def _flows(self, gst: LoadGenState, lane):
        if self.flow_cdf_q16 is None:
            # deterministic round-robin, continuous across steps
            return (gst.next_rpc + lane) % self.fab.cfg.n_flows
        u16 = (counter_hash(gst.key, gst.step * self.tile + lane,
                            _SALT_FLOW) & jnp.uint32(0xFFFF)).astype(
                                jnp.int32)
        return jnp.sum((u16[:, None] >= self.flow_cdf_q16[None, :])
                       .astype(jnp.int32), axis=1)

    def inject(self, cst: FabricState, gst: LoadGenState):
        """One open-loop injection, INSIDE the fused step (pure jnp —
        scan/vmap/shard_map-safe): draw this step's arrival count, pack
        step-stamped records, push them into the client TX rings, and
        account every arrival as injected or dropped.  Returns
        ``(cst', gst')``."""
        step0 = gst.step
        raw, gst = self.arrivals(gst)
        n = jnp.minimum(raw, self.tile)
        lane = jnp.arange(self.tile, dtype=jnp.int32)
        valid = lane < n
        rpc_id = gst.next_rpc + lane
        # distinct payloads so completions are attributable end to end
        if self.payload_fn is None:
            pay = jnp.broadcast_to(lane[:, None] + 1,
                                   (self.tile, self.pw)) + rpc_id[:, None]
        else:
            pay = jnp.asarray(self.payload_fn(gst, lane, rpc_id),
                              jnp.int32)
        flows = self._flows(gst, lane)
        # origin-flow tag in flags bits 8+: the response's RX flow is
        # load-balancer-chosen, so per-flow tail attribution needs the
        # REQUEST flow echoed back (handlers copy flags; the response
        # path only ORs FLAG_RESPONSE into the low bits)
        recs = serdes.make_records(
            jnp.full((self.tile,), 1, jnp.int32) * gst.conn, rpc_id,
            jnp.full((self.tile,), self.fn_id, jnp.int32),
            flows << 8, pay, timestamp=step0)
        cst, accepted = self.fab.host_tx_enqueue(cst, recs, flows, valid)
        n_acc = jnp.sum(accepted.astype(jnp.int32))
        gst = dataclasses.replace(
            gst, next_rpc=gst.next_rpc + n, offered=gst.offered + raw,
            injected=gst.injected + n_acc,
            dropped=gst.dropped + (raw - n_acc))
        return cst, gst


# ------------------------------------------------------------- host side
def snapshot(gst: LoadGenState) -> dict:
    """Host-side readout of the accounting counters (sums lane axes)."""
    import numpy as np
    out = {}
    for k in ("offered", "injected", "dropped", "next_rpc", "step"):
        out[k] = int(np.asarray(jax.device_get(getattr(gst, k))).sum())
    return out


def system_occupancy(*states) -> int:
    """Total in-flight RPCs resident in the given fabric states' rings
    and flow FIFOs — the ``in_flight`` term of the conservation
    invariant ``injected == completed + in_flight + fabric_drops``
    (each in-flight RPC occupies exactly one of TX ring / flow FIFO /
    RX ring per fabric side at a step boundary)."""
    import numpy as np
    tot = 0
    for st in states:
        for ring in (st.tx, st.rx, st.flow_fifo):
            tot += int(np.asarray(jax.device_get(
                ring.occupancy())).sum())
    return tot
