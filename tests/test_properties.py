"""System-level property tests (hypothesis): the fabric's end-to-end
invariants under randomized traffic, the fused-deliver megakernel's
equivalence with the unfused pipeline, record conservation across the
multi-tier switch, and distributed-optim numerics.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.config import FabricConfig
from repro.core import monitor, serdes
from repro.core.fabric import DaggerFabric, make_loopback_step
from repro.core.load_balancer import (LB_OBJECT, LB_ROUND_ROBIN, LB_STATIC)


@given(st.lists(st.integers(1, 6), min_size=1, max_size=6),
       st.sampled_from([LB_ROUND_ROBIN, LB_OBJECT]))
@settings(max_examples=12, deadline=None)
def test_exactly_once_completion(waves, lb):
    """Every accepted RPC completes EXACTLY once, in any traffic pattern,
    under either load balancer — no loss, no duplication."""
    cfg = FabricConfig(n_flows=2, ring_entries=32, batch_size=4,
                       dynamic_batching=True)   # force_flush False
    client, server = DaggerFabric(cfg), DaggerFabric(cfg)
    cst, sst = client.init_state(), server.init_state()
    # dynamic batching ON -> force flush partial batches (low-load mode)
    cst = client.set_soft(cst, force_flush=True)
    sst = server.set_soft(sst, force_flush=True)
    cst = client.open_connection(cst, 3, 1, 1, lb)
    sst = server.open_connection(sst, 3, 1, 0, lb)

    step = jax.jit(make_loopback_step(client, server,
                                      lambda r, v: dict(r)))
    enq = jax.jit(client.host_tx_enqueue)
    sent, completed = 0, {}
    rid = 0
    for n in waves:
        pay = jax.random.randint(jax.random.PRNGKey(rid), (n, 12),
                                 0, 1 << 20, jnp.int32)
        recs = serdes.make_records(
            jnp.full((n,), 3, jnp.int32),
            rid + jnp.arange(n, dtype=jnp.int32),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32), pay)
        rid += n
        cst, acc = enq(cst, recs, jnp.arange(n) % 2)
        sent += int(np.asarray(acc).sum())
        for _ in range(3):
            cst, sst, done, dv = step(cst, sst)
            flat_ids = np.asarray(done["rpc_id"]).reshape(-1)
            for i in np.nonzero(np.asarray(dv).reshape(-1))[0]:
                key = int(flat_ids[i])
                completed[key] = completed.get(key, 0) + 1
    # drain whatever is still in flight
    for _ in range(12):
        cst, sst, done, dv = step(cst, sst)
        flat_ids = np.asarray(done["rpc_id"]).reshape(-1)
        for i in np.nonzero(np.asarray(dv).reshape(-1))[0]:
            key = int(flat_ids[i])
            completed[key] = completed.get(key, 0) + 1
    assert sum(completed.values()) == sent, "lost or stuck RPCs"
    assert all(v == 1 for v in completed.values()), "duplicated RPCs"


# ---------------------------------------------------------------------------
# nic_deliver_fused megakernel ≡ the unfused steer/allocate/scatter pipeline
# ---------------------------------------------------------------------------

def _tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.requires_pallas
@given(st.integers(0, 2 ** 32 - 1),
       st.integers(1, 5),               # n_flows
       st.integers(2, 8),               # ring entries
       st.integers(1, 32),              # tile rows
       st.integers(0, 16),              # pre-occupancy pushes
       st.booleans())                   # any valid rows at all
@settings(max_examples=25, deadline=None)
def test_nic_deliver_fused_equals_unfused(seed, n_flows, entries, n,
                                          n_pre, any_valid):
    """For ANY (records, flow table, valid mask, ring occupancy): the
    Pallas megakernel's output FabricState is bit-identical to the
    unfused FreeFifo.allocate + steer + Ring.push composition — free
    FIFO contents, request table, flow FIFOs, RR cursor, and every
    monitor counter included."""
    rng = np.random.default_rng(seed)
    cfg = FabricConfig(n_flows=n_flows, ring_entries=entries,
                       batch_size=2, dynamic_batching=False)
    fab = DaggerFabric(cfg)
    state = fab.init_state()
    for _ in range(int(rng.integers(1, 5))):
        state = fab.open_connection(
            state, int(rng.integers(0, 600)), int(rng.integers(0, 8)),
            int(rng.integers(0, 4)),
            int(rng.choice([LB_ROUND_ROBIN, LB_STATIC, LB_OBJECT])))
    state = dataclasses.replace(state,
                                rr=jnp.int32(int(rng.integers(0, 100))))
    state = fab.set_soft(state,
                         active_flows=int(rng.integers(1, n_flows + 1)))
    if n_pre:     # randomize FIFO/request-buffer occupancy
        pre = jnp.asarray(rng.integers(0, 2, n_pre) > 0)
        free2, sids, gr = state.free.allocate(pre)
        ffp, _ = state.flow_fifo.push(
            jnp.asarray(rng.integers(0, n_flows, n_pre), jnp.int32),
            sids[:, None], gr)
        state = dataclasses.replace(state, free=free2, flow_fifo=ffp)
    slots = jnp.asarray(rng.integers(-2 ** 31, 2 ** 31,
                                     (n, fab.slot_words), dtype=np.int64),
                        jnp.int32)
    slots = slots.at[:, 0].set(
        jnp.asarray(rng.integers(0, 600, n), jnp.int32))
    valid = jnp.asarray(rng.integers(0, 2, n) > 0) if any_valid \
        else jnp.zeros((n,), bool)
    _tree_equal(fab.nic_deliver(state, slots, valid, use_pallas=False),
                fab.nic_deliver(state, slots, valid, use_pallas=True))


@pytest.mark.requires_pallas
@given(st.integers(0, 2 ** 32 - 1), st.integers(1, 4))
@settings(max_examples=10, deadline=None)
def test_nic_deliver_fused_backpressure_property(seed, n_flows):
    """Saturated flow FIFOs: every granted slot leaks back identically
    in both paths and the free list conserves its net occupancy."""
    rng = np.random.default_rng(seed)
    cfg = FabricConfig(n_flows=n_flows, ring_entries=2, batch_size=2,
                       dynamic_batching=False, request_buffer_slots=8)
    fab = DaggerFabric(cfg)
    state = fab.init_state()
    caps = state.flow_fifo.capacity
    for i in range(caps):
        ffp, _ = state.flow_fifo.push(
            jnp.arange(n_flows, dtype=jnp.int32),
            jnp.full((n_flows, 1), i, jnp.int32),
            jnp.ones((n_flows,), bool))
        state = dataclasses.replace(state, flow_fifo=ffp)
    slots = jnp.asarray(rng.integers(0, 1000, (6, fab.slot_words)),
                        jnp.int32)
    valid = jnp.ones((6,), bool)
    a = fab.nic_deliver(state, slots, valid, use_pallas=False)
    b = fab.nic_deliver(state, slots, valid, use_pallas=True)
    _tree_equal(a, b)
    assert int(a.mon["drops_fifo_full"]) == min(6, 8)
    assert int(a.free.available()) == int(state.free.available())


# ---------------------------------------------------------------------------
# switch_step record conservation (no record created or dropped)
# ---------------------------------------------------------------------------

def _system_occupancy(states):
    """Records held anywhere in the mesh: TX + RX rings + flow FIFOs."""
    tot = 0
    for s in states:
        tot += int(jnp.sum(s.tx.occupancy()))
        tot += int(jnp.sum(s.rx.occupancy()))
        tot += int(jnp.sum(s.flow_fifo.occupancy()))
    return tot


@given(st.lists(st.integers(0, 4), min_size=1, max_size=5),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_switch_step_conserves_records(waves, seed):
    """Across any ``switch_step``, records are neither created nor
    destroyed: each drained request re-enters as exactly one response,
    each drained response leaves through the completions, and with
    roomy rings nothing is dropped.  Occupancy bookkeeping:

        S_before - S_after == (#responses surfaced) - (#fetch-misses)

    where fetch-misses are records whose connection lookup failed at the
    crossbar (they leave the system and are NOT delivered — the
    conn-miss host-fallback path, counted here from the monitors).
    """
    from repro.core.virtualization import Switch
    rng = np.random.default_rng(seed)
    cfg = FabricConfig(n_flows=2, ring_entries=64, batch_size=4,
                       dynamic_batching=False)
    fabrics = [DaggerFabric(cfg) for _ in range(3)]
    sw = Switch(fabrics)
    states = sw.init_states()
    states[0] = fabrics[0].open_connection(states[0], 1, 0, 1,
                                           LB_ROUND_ROBIN)
    states[1] = fabrics[1].open_connection(states[1], 1, 0, 0,
                                           LB_ROUND_ROBIN)

    def echo(recs, valid):
        return dict(recs)

    handlers = [None, echo, None]
    enq = jax.jit(fabrics[0].host_tx_enqueue)
    rid = 0
    for n in waves:
        if n:
            pay = jnp.asarray(rng.integers(0, 1 << 20, (n, 12)), jnp.int32)
            recs = serdes.make_records(
                jnp.full((n,), 1, jnp.int32),
                rid + jnp.arange(n, dtype=jnp.int32),
                jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
                pay)
            rid += n
            states[0], _ = enq(states[0], recs, jnp.arange(n) % 2)
        for _ in range(2):
            before = _system_occupancy(states)
            ing0 = sum(monitor.snapshot(s.mon)["rpcs_ingested"]
                       for s in states)
            del0 = sum(monitor.snapshot(s.mon)["rpcs_delivered"]
                       for s in states)
            states, comps = sw.switch_step(states, handlers)
            after = _system_occupancy(states)
            # no drops anywhere (rings sized for the whole load)
            for s in states:
                snap = monitor.snapshot(s.mon)
                assert snap["drops_no_slot"] == 0
                assert snap["drops_fifo_full"] == 0
            # responses that left the system through the completions
            surfaced = 0
            for recs_i, valid_i in comps:
                is_resp = (np.asarray(recs_i["flags"])
                           & serdes.FLAG_RESPONSE) != 0
                surfaced += int((np.asarray(valid_i) & is_resp).sum())
            ing1 = sum(monitor.snapshot(s.mon)["rpcs_ingested"]
                       for s in states)
            del1 = sum(monitor.snapshot(s.mon)["rpcs_delivered"]
                       for s in states)
            misses = (ing1 - ing0) - (del1 - del0)
            assert before - after == surfaced + misses, \
                (before, after, surfaced, misses)


def test_pod_sync_single_pod_identity():
    """int8-EF pod sync over a 1-pod mesh returns ~the input gradients
    (quantization error bounded by one ulp of the scale)."""
    from repro.optim import pod_sync_step
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = {"w": jnp.asarray(np.random.default_rng(0)
                          .standard_normal(64).astype(np.float32))}
    e = {"w": jnp.zeros((64,), jnp.float32)}
    synced, err = pod_sync_step(g, e, mesh)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    np.testing.assert_allclose(np.asarray(synced["w"]),
                               np.asarray(g["w"]), atol=scale)
    # error feedback captures exactly the quantization residual
    np.testing.assert_allclose(np.asarray(g["w"] - synced["w"]),
                               np.asarray(err["w"]), atol=1e-6)


@given(st.integers(1, 200),             # payload words
       st.integers(0, 2 ** 32 - 1),     # shuffle + content seed
       st.sampled_from([8, 16, 32]))    # slot words
@settings(max_examples=30, deadline=None)
def test_fragment_reassemble_any_order(n_words, seed, slot_words):
    """Fragment/wire/reassemble is the identity for ANY payload length
    and ANY delivery order — bit-exact INCLUDING length (no trailing
    slot padding), with the fragment index surviving serdes.pack's
    word-3 assembly (the wire-format bug regression)."""
    from repro.core.reassembly import Reassembler, pack_fragmented
    rng = np.random.default_rng(seed)
    payload = rng.integers(-2 ** 31, 2 ** 31, n_words,
                           dtype=np.int64).astype(np.int32)
    recs = pack_fragmented(9, 1, 0, payload, slot_words)
    batch = {k: jnp.asarray(np.stack([r[k] for r in recs]))
             for k in recs[0]}
    back = serdes.unpack(serdes.pack(batch, slot_words))
    wired = [jax.tree.map(lambda x: np.asarray(x)[i], back)
             for i in range(len(recs))]
    ra = Reassembler(max_fragments=256)
    outs = [ra.feed(wired[i]) for i in rng.permutation(len(wired))]
    done = [o for o in outs if o is not None]
    assert len(done) == 1, "reassembly must complete exactly once"
    assert done[0].shape == payload.shape
    np.testing.assert_array_equal(done[0], payload)


@given(st.integers(1, 64),              # rows in the local tile
       st.integers(1, 8),               # destination devices
       st.integers(0, 2 ** 32 - 1))     # content seed
@settings(max_examples=40, deadline=None)
def test_compact_buckets_conserve_records(n, n_dev, seed):
    """Compaction never drops or duplicates a record (at full cap) and
    keeps same-destination rows in their original relative order — the
    invariant the compacted sharded switch's parity rests on.  With a
    reduced cap, survivors + dropped counts still conserve the total."""
    from repro.core.transport import bucket_valid, compact_buckets
    rng = np.random.default_rng(seed)
    rows = {"x": jnp.asarray(rng.integers(-2 ** 31, 2 ** 31, (n, 2),
                                          dtype=np.int64), jnp.int32),
            "tag": jnp.arange(n, dtype=jnp.int32)}
    valid = jnp.asarray(rng.random(n) < 0.6)
    dest = jnp.asarray(rng.integers(0, n_dev, n), jnp.int32)

    buckets, counts, dropped, shipped = compact_buckets(rows, valid,
                                                        dest, n_dev, n)
    assert int(np.asarray(dropped).sum()) == 0        # cap=n never drops
    np.testing.assert_array_equal(np.asarray(shipped),
                                  np.asarray(valid))
    bv = np.asarray(bucket_valid(counts, n))
    tags = np.asarray(buckets["tag"])[bv]
    want = np.asarray(rows["tag"])[np.asarray(valid)]
    # exactly-once: the multiset of live rows equals the valid inputs
    assert sorted(tags.tolist()) == sorted(want.tolist())
    x_in = {int(t): np.asarray(rows["x"])[t]
            for t in want.tolist()}
    x_out = np.asarray(buckets["x"])[bv]
    for t, x in zip(tags.tolist(), x_out):
        np.testing.assert_array_equal(x, x_in[int(t)])
    # stable per-destination order
    nd = np.asarray(dest)
    for dev in range(n_dev):
        blk = np.asarray(buckets["tag"])[dev * n:(dev + 1) * n]
        live = blk[np.asarray(bucket_valid(counts, n))
                   [dev * n:(dev + 1) * n]]
        ref = [t for t in range(n)
               if bool(valid[t]) and nd[t] == dev]
        assert live.tolist() == ref

    # reduced cap: survivors are the earliest per destination, and
    # counts + dropped conserve the offered total
    cap = max(1, n // 2)
    b2, c2, d2, s2 = compact_buckets(rows, valid, dest, n_dev, cap)
    assert int((np.asarray(c2) + np.asarray(d2)).sum()) == \
        int(np.asarray(valid).sum())
    # shipped + dropped partition the valid rows
    assert int(np.asarray(s2).sum()) == int(np.asarray(c2).sum())
    assert not bool(np.asarray(s2 & ~valid).any())
    for dev in range(n_dev):
        ref = [t for t in range(n)
               if bool(valid[t]) and nd[t] == dev][:cap]
        blk = np.asarray(b2["tag"])[dev * cap:(dev + 1) * cap]
        live = blk[np.asarray(bucket_valid(c2, cap))
                   [dev * cap:(dev + 1) * cap]]
        assert live.tolist() == ref


@pytest.mark.requires_pallas
@given(st.integers(0, 2 ** 32 - 1),     # traffic seed
       st.sampled_from([LB_ROUND_ROBIN, LB_STATIC, LB_OBJECT]),
       st.lists(st.integers(0, 5), min_size=1, max_size=3))
@settings(max_examples=8, deadline=None)
def test_switch_step_fused_equals_unfused(seed, lb, waves):
    """For ANY wave pattern and steering scheme through a 4-tier switch:
    ``switch_step_stacked(use_pallas=True)`` (the whole front half as
    one ``switch_step_fused`` Pallas megakernel) is bit-identical to the
    jnp composition — states, completions, and telemetry included."""
    from repro.core import telemetry as tlm
    from repro.core.virtualization import Switch
    rng = np.random.default_rng(seed)
    t = 4
    cfg = FabricConfig(n_flows=2, ring_entries=32, batch_size=4,
                       dynamic_batching=False)
    fabrics = [DaggerFabric(cfg) for _ in range(t)]
    sw = Switch(fabrics)
    states = sw.init_states()
    conns = []
    for i, dst in enumerate(range(t // 2, t)):
        c = 10 + i
        states[0] = fabrics[0].open_connection(states[0], c, i % 2, dst,
                                               lb)
        states[dst] = fabrics[dst].open_connection(states[dst], c, i % 2,
                                                   0, lb)
        conns.append(c)

    def echo(recs, valid):
        out = dict(recs)
        out["payload"] = recs["payload"] + 1
        return out

    handlers = [None, None] + [echo] * (t - 2)
    pw = fabrics[0].slot_words - serdes.HEADER_WORDS
    s_un = s_fu = sw.stack_states(states)
    tel_un, tel_fu = tlm.create_batch(t), tlm.create_batch(t)
    step_un = jax.jit(lambda s, tl: sw.switch_step_stacked(
        s, handlers, tel=tl, use_pallas=False))
    step_fu = jax.jit(lambda s, tl: sw.switch_step_stacked(
        s, handlers, tel=tl, use_pallas=True))
    enq = jax.jit(fabrics[0].host_tx_enqueue)
    rid = 0
    for n in waves:
        if n:
            pay = jnp.asarray(rng.integers(0, 1 << 20, (n, pw)),
                              jnp.int32)
            recs = serdes.make_records(
                jnp.asarray(rng.choice(conns, n), jnp.int32),
                rid + jnp.arange(n, dtype=jnp.int32),
                jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
                pay)
            rid += n
            flows = jnp.asarray(rng.integers(0, 2, n), jnp.int32)
            # identical enqueue on both sides (states are equal here)
            new0_un, _ = enq(jax.tree.map(lambda x: x[0], s_un),
                             recs, flows)
            new0_fu, _ = enq(jax.tree.map(lambda x: x[0], s_fu),
                             recs, flows)
            s_un = jax.tree.map(
                lambda full, t0: full.at[0].set(t0), s_un, new0_un)
            s_fu = jax.tree.map(
                lambda full, t0: full.at[0].set(t0), s_fu, new0_fu)
        for _ in range(2):
            s_un, (r_un, v_un), tel_un = step_un(s_un, tel_un)
            s_fu, (r_fu, v_fu), tel_fu = step_fu(s_fu, tel_fu)
            _tree_equal((r_un, v_un), (r_fu, v_fu))
            _tree_equal(s_un, s_fu)
            _tree_equal(tel_un, tel_fu)


@given(st.integers(2, 64), st.integers(1, 8))
@settings(max_examples=20, deadline=None)
def test_idl_char_roundtrip(nbytes, seed):
    """char[N] fields roundtrip for any N and content length <= N."""
    from repro.core import idl
    src = f"Message M {{ char[{nbytes}] s; }}"
    mod = idl.load(src, f"gen_{nbytes}_{seed}")
    rng = np.random.default_rng(seed)
    text = "".join(chr(rng.integers(97, 123))
                   for _ in range(int(rng.integers(0, nbytes + 1))))
    m = mod.M(s=text)
    back = mod.M.unpack(m.pack())
    assert back.s == text


@given(st.integers(0, 2 ** 32 - 1),     # traffic seed
       st.integers(1, 3),               # tenants
       st.integers(1, 8))               # fused steps
@settings(max_examples=15, deadline=None)
def test_telemetry_histogram_conservation(seed, n_tenants, k):
    """Latency-telemetry invariants under randomized traffic: the
    histogram conserves completions (``hist.sum() == n_done`` exactly),
    residency counts the completing step (bin 0 empty), ``sum_steps``
    equals the histogram's weighted sum for in-range residencies, and
    per-tenant histograms equal the independent single-pair runs
    bit-for-bit (the seeded fallback sweep lives in
    ``test_telemetry.py``)."""
    from repro.core import telemetry as tlm
    from repro.core.engine import (LoopbackEngine, TenantEngine,
                                   stack_states)
    from repro.core.load_balancer import LB_ROUND_ROBIN
    rng = np.random.default_rng(seed)
    cfg = FabricConfig(n_flows=int(rng.integers(1, 5)),
                       ring_entries=32,
                       batch_size=int(rng.integers(1, 5)),
                       dynamic_batching=False)
    client, server = DaggerFabric(cfg), DaggerFabric(cfg)
    pw = client.slot_words - serdes.HEADER_WORDS

    def pair(n):
        cst, sst = client.init_state(), server.init_state()
        cst = client.open_connection(cst, 1, 0, 1, LB_ROUND_ROBIN)
        sst = server.open_connection(sst, 1, 0, 0, LB_ROUND_ROBIN)
        pay = jnp.asarray(rng.integers(0, 100, (n, pw)), jnp.int32)
        recs = serdes.make_records(
            jnp.full((n,), 1, jnp.int32), jnp.arange(n, dtype=jnp.int32),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32), pay,
            timestamp=0)
        cst, _ = jax.jit(client.host_tx_enqueue)(
            cst, recs, jnp.arange(n) % cfg.n_flows)
        return cst, sst

    def echo(recs, valid):
        out = dict(recs)
        out["payload"] = recs["payload"] + 1
        return out

    loads = [int(rng.integers(1, 9)) for _ in range(n_tenants)]
    refs = []
    for n in loads:
        cst, sst = pair(n)
        eng = LoopbackEngine(client, server, echo)
        _, _, done, tel = eng.run_steps(cst, sst, k, tel=tlm.create())
        h = np.asarray(tel.hist)
        assert int(done) == int(tel.n_done) == h.sum()
        assert h[0] == 0
        in_range = (h[:-1] * np.arange(len(h) - 1)).sum()
        if h[-1] == 0:
            assert int(tel.sum_steps) == in_range
        refs.append(tel)

    pairs = [pair(n) for n in loads]
    teng = TenantEngine(client, server, echo)
    _, _, tdone, ttel = teng.run_steps(
        stack_states([c for c, _ in pairs]),
        stack_states([s for _, s in pairs]), k,
        tel=tlm.create_batch(n_tenants))
    np.testing.assert_array_equal(
        np.asarray(ttel.hist).sum(axis=1), np.asarray(tdone))
    for t, ref in enumerate(refs):
        np.testing.assert_array_equal(np.asarray(ttel.hist[t]),
                                      np.asarray(ref.hist))
        assert int(ttel.sum_steps[t]) == int(ref.sum_steps)


# ---------------------------------------------------------------------------
# open-loop load generation: arrival conservation past saturation
# ---------------------------------------------------------------------------

@given(st.integers(0, 2 ** 32 - 1),     # generator seed
       st.integers(1, 4),               # n_flows
       st.integers(1, 4),               # batch
       st.sampled_from([4, 8, 16, 32]),  # ring entries
       st.sampled_from([0, 8, 32]),     # request buffer slots
       st.floats(0.1, 3.0),             # offered rate, x tile width
       st.integers(1, 40),              # fused steps
       st.sampled_from([0, 1, 2]))      # arrival mode
@settings(max_examples=10, deadline=None)
# a seed past int32: the generator key is the seed mod 2**32
@example(seed=2_147_483_648, n_flows=1, batch=1, entries=4, slots=0,
         rate_x=0.1, k=1, mode=0)
def test_loadgen_conservation_property(seed, n_flows, batch, entries,
                                       slots, rate_x, k, mode):
    """Open-loop arrival conservation, any config x any rate INCLUDING
    far past saturation:

        offered  == injected + generator drops          (by construction)
        injected == completed + in_flight + fabric_drops    (conserved)

    where in_flight is the ring/FIFO occupancy of both fabric states and
    fabric_drops the monitor drop counters downstream of the TX ring
    (the client's ``drops_tx_full`` stays out — those rejections ARE the
    generator's drop counter).  The open-loop generator never blocks, so
    every arrival must land in exactly one bucket."""
    from repro.core import loadgen as lg
    from repro.core.engine import LoopbackEngine
    from repro.core.load_balancer import LB_ROUND_ROBIN

    cfg = FabricConfig(n_flows=n_flows, ring_entries=entries,
                       batch_size=batch, dynamic_batching=False,
                       request_buffer_slots=slots)
    client, server = DaggerFabric(cfg), DaggerFabric(cfg)
    cst, sst = client.init_state(), server.init_state()
    cst = client.open_connection(cst, 1, 0, 1, LB_ROUND_ROBIN)
    sst = server.open_connection(sst, 1, 0, 0, LB_ROUND_ROBIN)

    gen = lg.LoadGen(client, mode=mode)
    eng = LoopbackEngine(client, server,
                         lambda r, v: dict(r), loadgen=gen)
    cst, sst, done, gst = eng.run_steps(
        cst, sst, k, gen=gen.init_state(rate_x * gen.tile, seed=seed))

    snap = lg.snapshot(gst)
    assert snap["offered"] == snap["injected"] + snap["dropped"]
    fab_drops = 0
    for key in ("drops_no_slot", "drops_fifo_full", "drops_rx_full",
                "drops_exchange"):
        fab_drops += int(np.asarray(cst.mon[key]))
        fab_drops += int(np.asarray(sst.mon[key]))
    fab_drops += int(np.asarray(sst.mon["drops_tx_full"]))
    assert snap["injected"] == (int(np.asarray(done))
                                + lg.system_occupancy(cst, sst)
                                + fab_drops)
    assert snap["step"] == k


# ---------------------------------------------------------------------------
# decode tenant: slot-pool conservation under randomized load
# ---------------------------------------------------------------------------

_DECODE_RIGS = {}


def _decode_rig(mode):
    """One engine + compiled 40-step loop per arrival mode (rate and
    seed are runtime values, so all examples share the compilations)."""
    if mode not in _DECODE_RIGS:
        from repro.apps.lm_decode import build_engine
        eng = build_engine(n_slots=2, mode=mode)
        _DECODE_RIGS[mode] = (eng, eng.make_run_steps(40))
    return _DECODE_RIGS[mode]


@given(st.integers(0, 2),                # arrival mode
       st.floats(0.05, 4.0),             # offered rate (past saturation)
       st.integers(0, 2 ** 20))          # generator seed
@settings(max_examples=10, deadline=None)
def test_decode_slot_conservation(mode, rate, seed):
    """Continuous-batching scheduler accounting under randomized
    arrival bursts and max-token draws: every request that reaches
    admission is in exactly one of {completed, active, rejected}, no
    slot is double-occupied, and the generator ledger stays exact.
    (Mirrored by the seeded fallback in ``test_serving_decode.py`` for
    hypothesis-free environments.)"""
    from repro.core import loadgen as lg

    eng, run = _decode_rig(mode)
    stf, _ = run(eng.init_states(rate, seed=seed))
    active = int(np.asarray(stf.slots.req_id >= 0).sum())
    admitted = int(np.asarray(stf.slots.admitted))
    completed = int(np.asarray(stf.slots.completed))
    rejected = int(np.asarray(stf.slots.rejected))
    assert admitted == completed + active + rejected
    live = np.asarray(stf.slots.req_id)
    live = live[live >= 0]
    assert len(live) == len(set(live.tolist())), "slot double-occupied"
    snap = lg.snapshot(stf.gst)
    assert snap["offered"] == snap["injected"] + snap["dropped"]
    assert int(np.asarray(stf.gst.arr_hist).sum()) == snap["step"] == 40
