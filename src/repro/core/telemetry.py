"""On-device RPC latency telemetry — the measurement layer (§5.2/§6).

Dagger's headline numbers are µs-scale medians and tails, but a
device-resident dataplane makes per-RPC latency unobservable from the
host: the fused engines sync ONE scalar per measurement window, so a
host wall clock around the dispatch measures dispatch overhead, not
fabric residency (exactly the per-step software overhead §4.4 argues
off the critical path).  This module measures latency the way the
hardware would — with step-stamped records and an on-device histogram:

* the issuer stamps the current fabric step into the record's
  ``timestamp`` header word (``serdes`` word 4 — the IDL's dormant
  ``timestamp`` field promoted to the wire);
* handlers echo the stamp untouched (it is a header field, so
  ``dict(recs)`` responses carry it for free);
* the completion side, INSIDE the fused step, computes the RPC's
  residency ``lat = step - timestamp + 1`` and scatter-adds it into a
  histogram carried through the scan/while loop.

**Step-unit contract.**  ``Telemetry.step`` ticks once per fused
pipeline step.  A recorded latency of L means the RPC was resident for
L fabric steps, COUNTING the completing step — an RPC issued and
drained within one fused step records L=1, never 0.  Bin ``n_bins-1``
is the overflow bin (all L >= n_bins-1 land there); bin 0 only catches
anomalies (a timestamp from the future clips to 0).  Conservation
invariant, pinned by ``tests/test_telemetry.py``:
``hist.sum() == n_done`` always.

Host-side extraction (``quantiles`` / ``summary``) turns the histogram
into median/p90/p99 **in steps**; multiply by the measured per-step
wall cost of the same fused loop to get µs
(``us = q_steps * step_us``).  The histogram itself never leaves the
device until the window ends — one sync per window, like the done
counter.

All state is int32 and pytree-registered, so Telemetry vmaps over a
tenant axis (``create_batch``), shards over a mesh (leading-[T]
leaves), donates, and psum-merges (``ShardedTenantEngine
.run_until_global`` returns the fleet-wide histogram as a ``psum`` over
device-local per-tenant histograms).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

LAT_BINS = 64        # default histogram width (latencies in [0, 62] + ovf)

# Names of the fused step's phases on the device.  Each is a
# ``jax.named_scope``: compile-time metadata that changes no operation,
# kept in every compiled instruction's ``op_name`` as a path such as
# ``jit(run_steps)/while/body/vmap(dagger.client)/dagger.nic.fetch/
# dagger.ring.peek/gather``, which a profiler trace of the program can be
# attributed by.  The names are stable: a reader of a trace names them.
SCOPE_LOADGEN = "dagger.loadgen"            # open-loop injection
SCOPE_TELEMETRY = "dagger.telemetry"        # observe + tick
SCOPE_CLIENT = "dagger.client"              # the client NIC's part
SCOPE_SERVER = "dagger.server"              # the server NIC's part
SCOPE_HANDLER = "dagger.handler"            # the server's RPC handler
SCOPE_QUEUES = "dagger.queues"              # queue counters, step's end
SCOPE_NIC_ENQUEUE = "dagger.nic.enqueue"    # host_tx_enqueue
SCOPE_NIC_FETCH = "dagger.nic.fetch"        # nic_fetch
SCOPE_NIC_DELIVER = "dagger.nic.deliver"    # nic_deliver, both routes
SCOPE_NIC_EMIT = "dagger.nic.emit"          # nic_sched_emit
SCOPE_NIC_DRAIN = "dagger.nic.drain"        # host_rx_drain
SCOPE_NIC_PIPELINE = "dagger.nic.pipeline"  # nic_pipeline's megakernel
SCOPE_RING_PUSH = "dagger.ring.push"
SCOPE_RING_PEEK = "dagger.ring.peek"
SCOPE_RING_ADVANCE = "dagger.ring.advance"
SCOPE_KVS_GET = "dagger.kvs.get"
SCOPE_KVS_SET = "dagger.kvs.set"
SCOPE_SWITCH_FETCH = "dagger.switch.fetch"      # phase A + crossbar
SCOPE_SWITCH_DELIVER = "dagger.switch.deliver"  # phase B
SCOPE_SWITCH_EMIT = "dagger.switch.emit"        # phase C
SCOPE_SWITCH_DRAIN = "dagger.switch.drain"      # phase D
SCOPE_EXCHANGE = "dagger.exchange"              # the ToR all-to-all
SCOPE_DECODE_MODEL = "dagger.decode.model"      # the LM decode step

# The scopes of a loopback engine's window program (``TenantEngine``
# with a generator and telemetry); a stateful KVS handler adds
# ``SCOPE_KVS_GET`` and ``SCOPE_KVS_SET``.
LOOPBACK_SCOPES = (
    SCOPE_LOADGEN, SCOPE_TELEMETRY, SCOPE_CLIENT, SCOPE_SERVER,
    SCOPE_HANDLER, SCOPE_QUEUES, SCOPE_NIC_ENQUEUE, SCOPE_NIC_FETCH,
    SCOPE_NIC_DELIVER, SCOPE_NIC_EMIT, SCOPE_NIC_DRAIN, SCOPE_RING_PUSH,
    SCOPE_RING_PEEK, SCOPE_RING_ADVANCE)

# Host span (``jax.profiler.TraceAnnotation``, on the profiler's clock)
# around the host side of an engine entry: argument handling, ``unalias``
# and the jitted call, up to the return of the dispatch.
SPAN_ENGINE_DISPATCH = "dagger.engine.dispatch"


@jax.tree_util.register_dataclass
@dataclass
class Telemetry:
    step: jnp.ndarray       # int32 — current fabric step (monotonic)
    hist: jnp.ndarray       # [n_bins] int32 — completions by residency
    n_done: jnp.ndarray     # int32 — total completions observed
    sum_steps: jnp.ndarray  # int32 — sum of residencies, floored at 0
                            # (anomalies bin at 0) but NOT capped at the
                            # histogram width, so the mean sees the tail


def create(n_bins: int = LAT_BINS) -> Telemetry:
    """Fresh scalar telemetry (one engine / one tier / one tenant)."""
    z = jnp.int32(0)
    return Telemetry(z, jnp.zeros((n_bins,), jnp.int32), z, z)


def create_batch(n: int, n_bins: int = LAT_BINS) -> Telemetry:
    """Stacked telemetry with a leading tenant/tier axis — the shape the
    vmapped engines and the stacked switch thread through their carries
    (leaf i is lane i's independent counter set)."""
    z = jnp.zeros((n,), jnp.int32)
    return Telemetry(z, jnp.zeros((n, n_bins), jnp.int32), z, z)


def create_flows(n_flows: int, n_bins: int = LAT_BINS) -> Telemetry:
    """Scalar telemetry with a PER-FLOW histogram ``[n_flows, n_bins]``
    — one engine, its tail attributed by flow (the Zipf-skew sweeps bin
    hot vs cold flows separately).  Distinguished from ``create_batch``
    by the scalar ``step``: a batched Telemetry stacks whole counter
    sets ([T] steps), a per-flow one splits ONE lane's histogram by flow
    (``observe`` routes rows via its ``flow`` argument; the conservation
    invariant ``hist.sum() == n_done`` is unchanged).  ``quantiles`` on
    ``hist[f]`` gives flow f's tail, on ``hist`` the aggregate."""
    z = jnp.int32(0)
    return Telemetry(z, jnp.zeros((n_flows, n_bins), jnp.int32), z, z)


def observe(tel: Telemetry, issue_step, valid, flow=None) -> Telemetry:
    """Record completions: residency = step - issue_step + 1 per valid row.

    ``issue_step``: [N] int32 timestamps off the drained records;
    ``valid``: [N] bool completion mask.  Rows past the histogram width
    land in the overflow bin; invalid rows contribute nothing (their
    scatter adds 0).  With a per-flow Telemetry (``create_flows``),
    ``flow`` gives each row's [N] flow index and rows scatter into
    ``hist[flow, bin]``.  Pure — safe inside scan/while/vmap/shard_map.
    """
    valid = jnp.asarray(valid)
    lat = tel.step - jnp.asarray(issue_step, jnp.int32) + 1
    lat = jnp.clip(lat, 0, None)
    n_bins = tel.hist.shape[-1]
    binned = jnp.clip(lat, 0, n_bins - 1)
    v = valid.astype(jnp.int32)
    if flow is None:
        if tel.hist.ndim != 1:
            raise ValueError("per-flow Telemetry needs observe(..., flow=)")
        hist = tel.hist.at[binned].add(v)
    else:
        hist = tel.hist.at[jnp.asarray(flow, jnp.int32), binned].add(v)
    return Telemetry(
        step=tel.step,
        hist=hist,
        n_done=tel.n_done + jnp.sum(v),
        sum_steps=tel.sum_steps + jnp.sum(lat * v))


def observe_count(tel: Telemetry, count) -> Telemetry:
    """Record a per-step COUNT histogram instead of a latency one: bin
    ``count`` (overflow to the last bin) gains one entry per call.  Used
    for arrival-process histograms — call once per fused step with that
    step's raw arrival count and ``hist[k]`` becomes the number of steps
    with k arrivals, the empirical pmf a chi-square test compares
    against the configured process (``poisson_chi2``).  Invariants:
    ``hist.sum() == n_done`` (steps observed) and ``sum_steps`` holds
    the total arrivals, both int32 like every Telemetry counter."""
    c = jnp.clip(jnp.asarray(count, jnp.int32), 0, None)
    n_bins = tel.hist.shape[-1]
    if tel.hist.ndim != 1:
        raise ValueError("observe_count needs a scalar-lane Telemetry")
    return Telemetry(
        step=tel.step,
        hist=tel.hist.at[jnp.clip(c, 0, n_bins - 1)].add(1),
        n_done=tel.n_done + 1,
        sum_steps=tel.sum_steps + c)


def tick(tel: Telemetry) -> Telemetry:
    """Advance the fabric step counter (once per fused pipeline step)."""
    return Telemetry(tel.step + 1, tel.hist, tel.n_done, tel.sum_steps)


def merge_hist(hist, axis_name: str = None):
    """Collapse leading lane axes of a histogram stack to one [n_bins]
    total; with ``axis_name`` (inside shard_map) additionally psum over
    the mesh axis — the fleet-wide histogram of
    ``run_until_global``."""
    h = jnp.asarray(hist)
    if h.ndim > 1:
        h = jnp.sum(h.reshape(-1, h.shape[-1]), axis=0)
    if axis_name is not None:
        h = jax.lax.psum(h, axis_name)
    return h


# ---------------------------------------------------------------- host side
def quantiles(hist, qs=(0.5, 0.9, 0.99)):
    """Histogram -> latency quantiles in STEPS (host-side, one sync).

    Accepts a [n_bins] histogram or any [..., n_bins] stack (lane axes
    are summed).  Returns {q: steps}; an empty histogram returns NaNs.
    The quantile is the smallest residency L with
    ``cdf(L) >= ceil(q * n)`` — exact on the integer distribution.
    """
    import numpy as np
    h = np.asarray(jax.device_get(hist), np.int64)
    if h.ndim > 1:
        h = h.reshape(-1, h.shape[-1]).sum(axis=0)
    c = np.cumsum(h)
    n = int(c[-1]) if c.size else 0
    if n == 0:
        return {q: float("nan") for q in qs}
    return {q: int(np.searchsorted(c, int(np.ceil(q * n)), side="left"))
            for q in qs}


def poisson_chi2(hist, lam: float, min_expected: float = 5.0):
    """Chi-square statistic of a COUNT histogram (``observe_count``)
    against Poisson(``lam``), host-side.

    Bins are merged left-to-right until each merged bin's expected count
    is >= ``min_expected`` (the classic validity rule); the last merged
    bin absorbs the full upper tail so expectations sum to n.  Returns
    ``(stat, dof)`` with ``dof = n_bins_merged - 1`` — compare against
    the caller's critical value.  Degenerate histograms (< 2 merged
    bins) return ``(0.0, 0)``.
    """
    import numpy as np
    h = np.asarray(jax.device_get(hist), np.int64)
    if h.ndim > 1:
        h = h.reshape(-1, h.shape[-1]).sum(axis=0)
    n = int(h.sum())
    if n == 0:
        return 0.0, 0
    k = np.arange(len(h), dtype=np.float64)
    with np.errstate(divide="ignore"):
        logpmf = -lam + k * np.log(max(lam, 1e-300)) - \
            np.cumsum(np.concatenate([[0.0], np.log(np.maximum(k[1:], 1))]))
    pmf = np.exp(logpmf)
    pmf[-1] = max(1.0 - pmf[:-1].sum(), 0.0)   # overflow bin = upper tail
    exp = n * pmf
    # merge adjacent bins until every merged expectation >= min_expected
    m_obs, m_exp, co, ce = [], [], 0.0, 0.0
    for o, e in zip(h, exp):
        co, ce = co + o, ce + e
        if ce >= min_expected:
            m_obs.append(co)
            m_exp.append(ce)
            co = ce = 0.0
    if m_obs:
        m_obs[-1] += co
        m_exp[-1] += ce
    if len(m_obs) < 2:
        return 0.0, 0
    m_obs, m_exp = np.asarray(m_obs), np.asarray(m_exp)
    stat = float(np.sum((m_obs - m_exp) ** 2 / m_exp))
    return stat, len(m_obs) - 1


def summary(tel_or_hist, step_us: float = None, qs=(0.5, 0.9, 0.99)):
    """Host-side readout: quantiles in steps (and µs given the measured
    per-step cost), completion count, and mean residency.

    ``tel_or_hist`` is a Telemetry (possibly batched) or a bare
    histogram.  Key names: 0.5 -> ``median``, else ``p<100q>``, with
    ``_steps`` / ``_us`` suffixes.  ``us = steps * step_us`` — the
    step-unit contract counts the completing step, so one-step RPCs
    cost one step, never zero.
    """
    import numpy as np
    if isinstance(tel_or_hist, Telemetry):
        hist = tel_or_hist.hist
        n = int(np.asarray(jax.device_get(tel_or_hist.n_done)).sum())
        s = int(np.asarray(jax.device_get(tel_or_hist.sum_steps)).sum())
    else:
        hist = tel_or_hist
        h = np.asarray(jax.device_get(hist), np.int64)
        n = int(h.sum())
        s = None
    out = {"n_done": n}
    qd = quantiles(hist, qs)
    for q, steps in qd.items():
        name = "median" if q == 0.5 else f"p{int(round(q * 100))}"
        out[f"{name}_steps"] = steps
        if step_us is not None:
            out[f"{name}_us"] = steps * step_us
    if s is not None and n:
        out["mean_steps"] = s / n
        if step_us is not None:
            out["mean_us"] = out["mean_steps"] * step_us
    return out
