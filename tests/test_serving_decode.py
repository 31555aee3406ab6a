"""Request-level differential ladder for the continuous-batching decode
tenant (``repro.runtime.decode``).

The flagship invariant: continuous batching is a SCHEDULING policy, not
a numerics change — every request's token stream is bit-identical to
the same request decoded alone, regardless of pool size, admission
order, arrival process, tenant batching, or mesh shape.  A request's
content is a pure hash of (generator key, rpc_id), so runs that differ
ONLY in timing still name the same requests and the streams can be
diffed request-by-request:

  1. batched (concurrent pool) == sequential (one request at a time);
  2. invariant across slot-pool sizes and admission orders;
  3. tenant-vmapped run == per-tenant solo runs (tokens + histograms);
  4. 2-D (tenant x model) sharded mesh == vmapped run, including the
     tensor-parallel model path (8-virtual-device CI leg);
  5. uncongested telemetry matches the analytic oracle exactly:
     TTFT = prompt_len + 1, every ITL = 1;
  6. conservation under randomized load (the hypothesis-free fallback
     for the ``test_properties`` property):
     ``admitted == completed + active + rejected``, active slot ids
     unique, generator ledger exact.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.lm_decode import TINY, build_engine
from repro.core import loadgen as lg
from repro.core import telemetry as tlm
from repro.core.transport import make_grid_mesh
from repro.runtime import decode as dec
from repro.runtime.decode import collect_streams

KEY = 5  # generator lane key shared by runs that must name same requests


def _run_single(eng, rate, seed, steps):
    st = eng.init_states(rate, seed=seed)
    st, (c, v) = eng.make_run_steps(steps)(st)
    return st, collect_streams(c, v)


def _done_streams(streams):
    return {r: e["tokens"] for r, e in streams.items()
            if e["done"] and not e["nack"]}


def _plen(key, rid, max_prompt):
    return 1 + int(lg.counter_hash(key, rid, dec._SALT_PLEN)) % max_prompt


# ---------------------------------------------------------------------------
# 1. batched == sequential, request by request
# ---------------------------------------------------------------------------

def test_batched_matches_sequential_per_request():
    """The same rpc_ids decoded concurrently (continuous batching, up
    to the whole pool in flight) and strictly one-at-a-time produce
    IDENTICAL token streams."""
    eng = build_engine(mode=lg.MODE_DETERMINISTIC)
    _, batched = _run_single(eng, rate=0.5, seed=KEY, steps=48)
    # 1 request per 16 steps; prompt+gen lifetime <= 8 -> never overlaps
    _, seq = _run_single(eng, rate=1.0 / 16.0, seed=KEY, steps=16 * 24)
    b, s = _done_streams(batched), _done_streams(seq)
    common = sorted(set(b) & set(s))
    assert len(common) >= 10, (len(b), len(s))
    for rid in common:
        assert b[rid] == s[rid], f"request {rid} diverged"


@pytest.mark.parametrize("n_slots", [2, 8])
def test_pool_size_invariance(n_slots):
    """Shrinking or growing the slot pool reschedules requests but
    never changes any request's tokens (reference pool = 4)."""
    ref_eng = build_engine(n_slots=4, mode=lg.MODE_DETERMINISTIC)
    eng = build_engine(n_slots=n_slots, mode=lg.MODE_DETERMINISTIC)
    _, a = _run_single(ref_eng, rate=0.5, seed=KEY, steps=48)
    _, b = _run_single(eng, rate=0.5, seed=KEY, steps=48)
    da, db = _done_streams(a), _done_streams(b)
    common = sorted(set(da) & set(db))
    assert len(common) >= 8
    for rid in common:
        assert da[rid] == db[rid]


def test_admission_order_invariance():
    """Different arrival processes (same key) admit the same requests
    in different orders/steps — streams still agree request-by-request."""
    det = build_engine(mode=lg.MODE_DETERMINISTIC)
    bur = build_engine(mode=lg.MODE_BURSTY)
    _, a = _run_single(det, rate=0.5, seed=KEY, steps=64)
    _, b = _run_single(bur, rate=1.0, seed=KEY, steps=64)
    da, db = _done_streams(a), _done_streams(b)
    common = sorted(set(da) & set(db))
    assert len(common) >= 6
    for rid in common:
        assert da[rid] == db[rid]


@pytest.mark.requires_pallas
def test_pallas_decode_route_matches_jnp():
    """The flash-decoding kernel route (``use_pallas=True``) serves the
    identical streams as the pure-jnp attention path."""
    a_eng = build_engine(mode=lg.MODE_DETERMINISTIC)
    b_eng = build_engine(mode=lg.MODE_DETERMINISTIC, use_pallas=True)
    _, a = _run_single(a_eng, rate=0.5, seed=KEY, steps=48)
    _, b = _run_single(b_eng, rate=0.5, seed=KEY, steps=48)
    assert _done_streams(a) == _done_streams(b)


# ---------------------------------------------------------------------------
# 2. telemetry vs the analytic oracle
# ---------------------------------------------------------------------------

def test_telemetry_matches_analytic_oracle():
    """Uncongested (wide egress, low rate): every first token lands
    exactly prompt_len + 1 steps after injection and every later token
    exactly 1 step after its predecessor — the whole TTFT histogram is
    reconstructible from the streams alone."""
    eng = build_engine(mode=lg.MODE_DETERMINISTIC)
    st, streams = _run_single(eng, rate=0.25, seed=KEY, steps=64)
    want_ttft = np.zeros_like(np.asarray(st.ttft.hist))
    n_itl = 0
    for rid, ent in streams.items():
        if ent["nack"] or not ent["tokens"]:
            continue
        want_ttft[_plen(KEY, rid, eng.max_prompt) + 1] += 1
        n_itl += len(ent["tokens"]) - 1
    np.testing.assert_array_equal(np.asarray(st.ttft.hist), want_ttft)
    itl = np.asarray(st.itl.hist)
    assert itl[1] == n_itl and itl.sum() == n_itl  # every ITL exactly 1
    assert int(st.itl.n_done) == n_itl


def test_streams_complete_when_pool_outruns_one_flow():
    """More slots generate per step than one client flow drains
    (8 slots > batch_size 4): the server's TX back-pressure holds each
    connection's stream to what the client drains, so the client NIC
    drops nothing and every finished stream carries all its tokens."""
    eng = build_engine(n_slots=8, max_prompt=4, max_new_cap=24,
                       mode=lg.MODE_DETERMINISTIC)
    st, streams = _run_single(eng, rate=0.5, seed=KEY, steps=96)
    key = int(np.asarray(st.gst.key))
    done = _done_streams(streams)
    assert len(done) >= 8
    for rid, toks in done.items():
        mnew = 1 + int(lg.counter_hash(key, rid, dec._SALT_MNEW)) % 24
        assert len(toks) == mnew, f"request {rid}: {len(toks)} of {mnew}"
    assert int(np.asarray(st.cst.mon["drops_no_slot"]).sum()) == 0


def test_fragment_stream_is_mtu_shaped():
    """Tokens return as a fragmented >MTU response: frag indices are
    contiguous from 0 and only the final fragment carries
    LAST_FRAGMENT (``collect_streams`` already orders by frag_idx;
    completed streams must have exactly max_new tokens)."""
    eng = build_engine(mode=lg.MODE_DETERMINISTIC)
    _, streams = _run_single(eng, rate=0.25, seed=KEY, steps=64)
    done = _done_streams(streams)
    assert done
    for rid, toks in done.items():
        mnew = 1 + int(lg.counter_hash(KEY, rid, dec._SALT_MNEW)) \
            % eng.max_new_cap
        assert len(toks) == mnew


# ---------------------------------------------------------------------------
# 3. tenant batching and 2-D mesh parity
# ---------------------------------------------------------------------------

def test_tenant_batched_matches_solo_runs():
    """T vmapped tenants == T independent solo runs: token streams AND
    per-tenant telemetry histograms, bitwise."""
    eng = build_engine(mode=lg.MODE_DETERMINISTIC)
    rates, seeds = [0.25, 0.5, 0.25, 0.5], [3, 4, 5, 6]
    stb = eng.init_states_batch(rates, seeds=seeds)
    stb, (c, v) = eng.make_tenant_run_steps(48)(stb)
    for t in range(4):
        sts, solo = _run_single(eng, rates[t], seeds[t], 48)
        batched = collect_streams(c[:, t], v[:, t])
        assert _done_streams(batched) == _done_streams(solo)
        np.testing.assert_array_equal(np.asarray(stb.ttft.hist[t]),
                                      np.asarray(sts.ttft.hist))
        np.testing.assert_array_equal(np.asarray(stb.itl.hist[t]),
                                      np.asarray(sts.itl.hist))


def _mesh_parity(eng, mesh, n_tenants=4, steps=48):
    rates = [0.5] * n_tenants
    seeds = list(range(7, 7 + n_tenants))
    sta = eng.init_states_batch(rates, seeds=seeds)
    sta, (ca, va) = eng.make_tenant_run_steps(steps)(sta)
    stb = eng.init_states_batch(rates, seeds=seeds)
    stb, (cb, vb) = eng.make_sharded_run_steps(mesh, steps)(stb)
    np.testing.assert_array_equal(np.asarray(sta.slots.completed),
                                  np.asarray(stb.slots.completed))
    np.testing.assert_array_equal(np.asarray(sta.ttft.hist),
                                  np.asarray(stb.ttft.hist))
    np.testing.assert_array_equal(np.asarray(sta.itl.hist),
                                  np.asarray(stb.itl.hist))
    for t in range(n_tenants):
        assert (collect_streams(ca[:, t], va[:, t])
                == collect_streams(cb[:, t], vb[:, t]))


def test_sharded_1x1_mesh_matches_vmapped():
    eng = build_engine(mode=lg.MODE_DETERMINISTIC)
    _mesh_parity(eng, make_grid_mesh(1, 1))


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 (virtual) devices")
@pytest.mark.parametrize("shape", [(8, 1), (4, 2), (2, 4)])
def test_sharded_2d_mesh_matches_vmapped(shape):
    """The 2-D (tenant x model) grid — including the tensor-parallel
    model shards with in-model psum — reproduces the vmapped run
    bitwise: tokens, counters, histograms."""
    t, m = shape
    # 4-way TP needs kv-heads divisible by 4
    cfg = TINY.replace(n_kv_heads=4) if m == 4 else None
    eng = build_engine(cfg=cfg, mode=lg.MODE_DETERMINISTIC)
    _mesh_parity(eng, make_grid_mesh(t, m),
                 n_tenants=max(t, 4), steps=48)


def test_sharded_rejects_nondivisible_tp():
    """TP over a model axis that does not divide the head/ff/vocab dims
    must fail loudly at build time, not silently compute garbage."""
    eng = build_engine(cfg=TINY.replace(n_kv_heads=1))
    mesh = make_grid_mesh(1, 1)
    # mesh axis size 1 is fine ...
    eng.make_sharded_run_steps(mesh, 4)
    if len(jax.devices()) >= 2:
        bad = make_grid_mesh(1, 2)
        with pytest.raises(ValueError, match="divisible"):
            eng.make_sharded_run_steps(bad, 4)


# ---------------------------------------------------------------------------
# 4. scheduler accounting (hypothesis-free conservation fallback)
# ---------------------------------------------------------------------------

def _check_conservation(st):
    active = int(np.asarray(st.slots.req_id >= 0).sum())
    admitted = int(np.asarray(st.slots.admitted).sum())
    completed = int(np.asarray(st.slots.completed).sum())
    rejected = int(np.asarray(st.slots.rejected).sum())
    assert admitted == completed + active + rejected, \
        (admitted, completed, active, rejected)
    # no slot double-occupied: live request ids unique per tenant pool
    rid = np.asarray(st.slots.req_id).reshape(-1, st.slots.req_id.shape[-1])
    for row in rid:
        live = row[row >= 0]
        assert len(live) == len(set(live.tolist()))
    snap = lg.snapshot(st.gst)
    assert snap["offered"] == snap["injected"] + snap["dropped"]
    assert int(np.asarray(st.gst.arr_hist).sum()) == snap["step"]
    return admitted, completed, rejected


@pytest.mark.parametrize("mode,rate,steps,seed", [
    (lg.MODE_DETERMINISTIC, 0.25, 40, 0),
    (lg.MODE_DETERMINISTIC, 2.0, 56, 1),
    (lg.MODE_POISSON, 0.5, 48, 2),
    (lg.MODE_POISSON, 3.0, 40, 3),
    (lg.MODE_BURSTY, 1.5, 64, 4),
])
def test_conservation_randomized_bursts(mode, rate, steps, seed):
    """admitted == completed + active + rejected across arrival modes,
    rates far past saturation included; slot pool never double-books."""
    eng = build_engine(n_slots=2, mode=mode)
    st, _ = _run_single(eng, rate, seed, steps)
    admitted, _, _ = _check_conservation(st)
    assert admitted > 0


def test_overload_rejects_and_nacks():
    """Past pool capacity the scheduler NACKs instead of stalling: the
    rejected counter moves and rejected requests surface client-side as
    NACK responses."""
    eng = build_engine(n_slots=1, mode=lg.MODE_DETERMINISTIC)
    st, streams = _run_single(eng, rate=2.0, seed=KEY, steps=48)
    _, _, rejected = _check_conservation(st)
    assert rejected > 0
    nacks = sum(1 for e in streams.values() if e["nack"])
    assert 0 < nacks <= rejected


def test_conservation_under_tenant_and_mesh_batching():
    """The invariant survives vmapping and the (1,1)-mesh shard_map."""
    eng = build_engine(n_slots=2, mode=lg.MODE_POISSON)
    st = eng.init_states_batch([1.5, 0.5, 2.5, 1.0])
    st, _ = eng.make_tenant_run_steps(48)(st)
    _check_conservation(st)
    st = eng.init_states_batch([1.5, 0.5, 2.5, 1.0])
    st, _ = eng.make_sharded_run_steps(make_grid_mesh(1, 1), 48)(st)
    _check_conservation(st)
