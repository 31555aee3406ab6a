"""Differential harness: the tenant-batched dataplane vs N independent
single-pair runs, asserted bit-identical.

``TenantEngine`` (vmapped ``LoopbackEngine``), the fused
``nic_deliver_fused`` megakernel, and the stacked ``Switch`` step must
all be *exact* reproductions of their per-tenant / unfused references —
the whole pipeline is int32, so any drift is a bug, not numerics.  The
randomized sweeps are seeded numpy (hypothesis-free) so they run
everywhere; the hypothesis variants live in ``test_properties.py``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FabricConfig
from repro.core import serdes
from repro.core.engine import (LoopbackEngine, TenantEngine, stack_states,
                               unstack_states)
from repro.core.fabric import DaggerFabric
from repro.core.load_balancer import (LB_OBJECT, LB_ROUND_ROBIN, LB_STATIC)

PALLAS_CASES = [False, pytest.param(True, marks=pytest.mark.requires_pallas)]


def assert_trees_equal(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


def _echo(recs, valid):
    out = dict(recs)
    out["payload"] = recs["payload"] + 1
    return out


def _fabrics(use_pallas=False, n_flows=4, batch=4, ring_entries=32):
    cfg = FabricConfig(n_flows=n_flows, ring_entries=ring_entries,
                       batch_size=batch, dynamic_batching=False,
                       use_pallas=use_pallas)
    return DaggerFabric(cfg), DaggerFabric(cfg)


def _records(fab, n, base=0, conn=1):
    pw = fab.slot_words - serdes.HEADER_WORDS
    pay = jnp.tile(jnp.arange(pw, dtype=jnp.int32)[None], (n, 1)) + base
    return serdes.make_records(
        jnp.full((n,), conn, jnp.int32), jnp.arange(n, dtype=jnp.int32),
        jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32), pay)


def _tenant_pairs(client, server, n_tenants, per_tenant_load):
    """Per-tenant state pairs with distinct traffic + connection tables."""
    enq = jax.jit(client.host_tx_enqueue)
    csts, ssts = [], []
    for t in range(n_tenants):
        cst, sst = client.init_state(), server.init_state()
        cst = client.open_connection(cst, 1 + t, 0, 1, LB_ROUND_ROBIN)
        sst = server.open_connection(sst, 1 + t, 0, 0, LB_ROUND_ROBIN)
        n = per_tenant_load[t]
        cst, acc = enq(cst, _records(client, n, base=100 * t, conn=1 + t),
                       jnp.arange(n) % client.cfg.n_flows)
        assert bool(acc.all())
        csts.append(cst)
        ssts.append(sst)
    return csts, ssts


# ---------------------------------------------------------------------------
# TenantEngine vs N independent LoopbackEngine runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", PALLAS_CASES)
def test_tenant_run_steps_matches_independent(use_pallas):
    """N=4 stacked pairs, K fused steps: exact pytree equality with 4
    separate LoopbackEngine runs (the acceptance-criterion case)."""
    client, server = _fabrics(use_pallas=use_pallas)
    loads = [4, 6, 8, 2]
    csts, ssts = _tenant_pairs(client, server, 4, loads)
    stc, sts = stack_states(csts), stack_states(ssts)

    refs = []
    for t in range(4):
        eng = LoopbackEngine(client, server, _echo)
        c2, s2, done = eng.run_steps(csts[t], ssts[t], 5)
        refs.append((c2, s2, int(done)))

    teng = TenantEngine(client, server, _echo)
    tc, ts, tdone = teng.run_steps(stc, sts, 5)
    assert tdone.shape == (4,)
    for t, (c_ref, s_ref, d_ref) in enumerate(refs):
        assert int(tdone[t]) == d_ref == loads[t]
        assert_trees_equal(jax.tree.map(lambda x: x[t], tc), c_ref,
                           f"client state diverged for tenant {t}")
        assert_trees_equal(jax.tree.map(lambda x: x[t], ts), s_ref,
                           f"server state diverged for tenant {t}")


def test_tenant_run_until_per_lane_targets():
    """Each lane stops at ITS target and freezes — final states equal the
    independent run_until results, including per-lane step counts."""
    client, server = _fabrics()
    loads = [8, 8, 8]
    targets = [4, 6, 8]
    csts, ssts = _tenant_pairs(client, server, 3, loads)
    stc, sts = stack_states(csts), stack_states(ssts)

    refs = []
    for t in range(3):
        eng = LoopbackEngine(client, server, _echo)
        refs.append(eng.run_until(csts[t], ssts[t], targets[t], 16))

    teng = TenantEngine(client, server, _echo)
    tc, ts, tdone, tsteps = teng.run_until(stc, sts,
                                           jnp.asarray(targets), 16)
    for t, (c_ref, s_ref, d_ref, n_ref) in enumerate(refs):
        # a step may complete a whole batch, legitimately overshooting
        # the target — parity is with the independent run, not the target
        assert int(tdone[t]) == int(d_ref) >= targets[t]
        assert int(tsteps[t]) == int(n_ref)
        assert_trees_equal(jax.tree.map(lambda x: x[t], tc), c_ref)
        assert_trees_equal(jax.tree.map(lambda x: x[t], ts), s_ref)


def test_tenant_stateful_handler_parity():
    """Stacked handler state rides the vmapped carry: per-tenant counters
    with distinct initial values match the independent runs exactly."""
    client, server = _fabrics()

    def handler(recs, valid, count):
        out = dict(recs)
        out["payload"] = recs["payload"] + 1
        return out, count + jnp.sum(valid.astype(jnp.int32))

    loads = [4, 8]
    csts, ssts = _tenant_pairs(client, server, 2, loads)
    h0 = [jnp.int32(10), jnp.int32(20)]
    # stack BEFORE the independent runs donate (consume) the per-tenant
    # buffers — jnp.stack copies, so both sides see identical inputs
    stc, sts = stack_states(csts), stack_states(ssts)
    sth = jnp.stack(h0)

    refs = []
    for t in range(2):
        eng = LoopbackEngine(client, server, handler, stateful=True)
        refs.append(eng.run_steps(csts[t], ssts[t], 4, hstate=h0[t]))

    teng = TenantEngine(client, server, handler, stateful=True)
    tc, ts, th, tdone = teng.run_steps(stc, sts, 4, hstate=sth)
    for t, (c_ref, s_ref, h_ref, d_ref) in enumerate(refs):
        assert int(th[t]) == int(h_ref) == 10 * (t + 1) + loads[t]
        assert int(tdone[t]) == int(d_ref)
        assert_trees_equal(jax.tree.map(lambda x: x[t], tc), c_ref)
        assert_trees_equal(jax.tree.map(lambda x: x[t], ts), s_ref)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_tenant_kvs_parity(use_pallas):
    """DeviceKVS.make_tenant_engine == N separate make_engine runs,
    store state included (the stateful-handler acceptance config); with
    ``use_pallas`` the tenants' GETs are one probe of the stacked
    stores."""
    from repro.runtime.kvs import DeviceKVS
    client, server = _fabrics(n_flows=2, batch=4)
    kvs = DeviceKVS(n_buckets=64, ways=4, key_words=2, value_words=4,
                    use_pallas=use_pallas)
    pw = client.slot_words - serdes.HEADER_WORDS
    enq = jax.jit(client.host_tx_enqueue)

    n, n_tenants = 4, 3
    csts, ssts = [], []
    for t in range(n_tenants):
        cst, sst = client.init_state(), server.init_state()
        cst = client.open_connection(cst, 1, 0, 1, LB_ROUND_ROBIN)
        sst = server.open_connection(sst, 1, 0, 0, LB_ROUND_ROBIN)
        # each key SET, then read back by a GET on the same flow
        pay = np.zeros((2 * n, pw), np.int32)
        pay[:, 0] = np.tile(np.arange(n) + 1 + 10 * t, 2)  # per-tenant keys
        pay[:n, 2] = np.arange(n) + 100 + 10 * t      # per-tenant values
        recs = serdes.make_records(
            np.full(2 * n, 1, np.int32), np.arange(2 * n, dtype=np.int32),
            np.repeat(np.int32([1, 0]), n),            # fn_id 1 = SET, 0 = GET
            np.zeros(2 * n, np.int32), jnp.asarray(pay))
        cst, _ = enq(cst, recs, jnp.arange(2 * n) % 2)
        csts.append(cst)
        ssts.append(sst)
    stc, sts = stack_states(csts), stack_states(ssts)

    refs = []
    for t in range(n_tenants):
        eng = kvs.make_engine(client, server)
        refs.append(eng.run_steps(csts[t], ssts[t], 4,
                                  hstate=kvs.init_state()))

    teng = kvs.make_tenant_engine(client, server)
    tc, ts, tdb, tdone = teng.run_steps(
        stc, sts, 4, hstate=kvs.init_state_batch(n_tenants))
    for t, (c_ref, s_ref, db_ref, d_ref) in enumerate(refs):
        assert int(tdone[t]) == int(d_ref) == 2 * n
        assert int(tdb.n_set[t]) == int(tdb.n_hit[t]) == n
        assert_trees_equal(jax.tree.map(lambda x: x[t], tdb), db_ref,
                           f"KVS store diverged for tenant {t}")
        assert_trees_equal(jax.tree.map(lambda x: x[t], tc), c_ref)
        assert_trees_equal(jax.tree.map(lambda x: x[t], ts), s_ref)
    # tenant isolation: tenant 0's keys are absent from tenant 1's store
    keys = jnp.stack([jnp.arange(n, dtype=jnp.int32) + 1,
                      jnp.zeros(n, jnp.int32)], axis=1)
    db1 = jax.tree.map(lambda x: x[1], tdb)
    _, _, hit = kvs.get(db1, keys)
    assert not bool(hit.any())


def test_stack_unstack_roundtrip():
    client, server = _fabrics()
    csts, _ = _tenant_pairs(client, server, 3, [2, 3, 4])
    back = unstack_states(stack_states(csts))
    assert len(back) == 3
    for orig, got in zip(csts, back):
        assert_trees_equal(orig, got)


def test_tenant_serving_smoke():
    """ServingEngine.make_tenant_run_steps: per-tenant served counts and
    (int) session tables match independent make_run_steps runs.  Token
    values are float-model outputs and excluded (vmap may legally change
    reduction order)."""
    from repro.configs import get_config
    from repro.runtime.serving import FLAG_NEW, ServingEngine
    cfg = get_config("repro-100m", reduced=True).replace(
        n_layers=2, d_model=64, d_ff=128, vocab=128, n_heads=4,
        n_kv_heads=4)
    fcfg = FabricConfig(n_flows=2, ring_entries=32, batch_size=4,
                        dynamic_batching=False)
    k, n_sessions, n_tenants = 2, 2, 2
    eng = ServingEngine(cfg, fcfg, n_slots=n_sessions, max_seq=16)
    sw = eng.fabric.slot_words
    pw = sw - serdes.HEADER_WORDS

    def tiles(tenant):
        ts, vs = [], []
        for it in range(k):
            pay = np.zeros((n_sessions, pw), np.int32)
            for i in range(n_sessions):
                pay[i, 0] = 100 + i + 10 * tenant
                pay[i, 1] = 5 + i if it == 0 else -1
                pay[i, 2] = FLAG_NEW if it == 0 else 0
            recs = serdes.make_records(
                np.zeros(n_sessions, np.int32),
                np.arange(n_sessions, dtype=np.int32) + it * n_sessions,
                np.zeros(n_sessions, np.int32),
                np.zeros(n_sessions, np.int32), jnp.asarray(pay))
            ts.append(serdes.pack(recs, sw))
            vs.append(jnp.ones((n_sessions,), bool))
        return jnp.stack(ts), jnp.stack(vs)

    per = [tiles(t) for t in range(n_tenants)]
    refs = []
    for t in range(n_tenants):
        run = eng.make_run_steps()
        fst, cache, sess = eng.init_states()
        _, _, sess, served, _, _ = run(fst, cache, sess, eng.params,
                                       per[t][0], per[t][1])
        refs.append((jax.tree.map(np.asarray, sess), int(served)))

    run_t = eng.make_tenant_run_steps()
    fst, cache, sess = eng.init_states_batch(n_tenants)
    in_slots = jnp.stack([p[0] for p in per], axis=1)   # [K, T, N, W]
    in_valid = jnp.stack([p[1] for p in per], axis=1)
    _, _, sess, served, out_s, out_v = run_t(fst, cache, sess, eng.params,
                                             in_slots, in_valid)
    assert out_s.shape[:2] == (k, n_tenants)
    for t in range(n_tenants):
        assert int(served[t]) == refs[t][1]
        np.testing.assert_array_equal(np.asarray(sess.session_id[t]),
                                      refs[t][0].session_id)
        np.testing.assert_array_equal(np.asarray(sess.pos[t]),
                                      refs[t][0].pos)


# ---------------------------------------------------------------------------
# nic_deliver_fused megakernel vs the unfused jnp pipeline (seeded sweeps;
# the hypothesis variants live in test_properties.py)
# ---------------------------------------------------------------------------

def _random_deliver_state(rng, n_flows, ring_entries, batch):
    cfg = FabricConfig(n_flows=n_flows, ring_entries=ring_entries,
                       batch_size=batch, dynamic_batching=False)
    fab = DaggerFabric(cfg)
    st = fab.init_state()
    for _ in range(int(rng.integers(1, 5))):
        st = fab.open_connection(
            st, int(rng.integers(0, 600)), int(rng.integers(0, 8)),
            int(rng.integers(0, 4)),
            int(rng.choice([LB_ROUND_ROBIN, LB_STATIC, LB_OBJECT])))
    st = dataclasses.replace(st, rr=jnp.int32(int(rng.integers(0, 100))))
    st = fab.set_soft(st, active_flows=int(rng.integers(1, n_flows + 1)))
    # randomize FIFO occupancy: allocate some slots + enqueue their refs
    n_pre = int(rng.integers(0, st.free.capacity + 1))
    if n_pre:
        pre = jnp.asarray(rng.integers(0, 2, n_pre) > 0)
        free2, sids, gr = st.free.allocate(pre)
        ffp, _ = st.flow_fifo.push(
            jnp.asarray(rng.integers(0, n_flows, n_pre), jnp.int32),
            sids[:, None], gr)
        st = dataclasses.replace(st, free=free2, flow_fifo=ffp)
    return fab, st


def _random_tile(rng, fab, n):
    slots = jnp.asarray(
        rng.integers(-2 ** 31, 2 ** 31, (n, fab.slot_words),
                     dtype=np.int64), jnp.int32)
    # bias conn ids into the opened range so hits/misses both occur
    slots = slots.at[:, 0].set(
        jnp.asarray(rng.integers(0, 600, n), jnp.int32))
    valid = jnp.asarray(rng.integers(0, 2, n) > 0)
    return slots, valid


@pytest.mark.requires_pallas
@pytest.mark.parametrize("seed", range(4))
def test_nic_deliver_fused_matches_unfused_randomized(seed):
    rng = np.random.default_rng(200 + seed)
    for _ in range(8):
        fab, st = _random_deliver_state(
            rng, int(rng.integers(1, 6)), int(rng.integers(2, 9)),
            int(rng.integers(1, 5)))
        slots, valid = _random_tile(rng, fab, int(rng.integers(1, 40)))
        a = fab.nic_deliver(st, slots, valid, use_pallas=False)
        b = fab.nic_deliver(st, slots, valid, use_pallas=True)
        assert_trees_equal(a, b, "fused deliver diverged from oracle")


@pytest.mark.requires_pallas
def test_nic_deliver_fused_zero_valid():
    fab, st = _random_deliver_state(np.random.default_rng(0), 2, 4, 2)
    slots = jnp.zeros((6, fab.slot_words), jnp.int32)
    valid = jnp.zeros((6,), bool)
    a = fab.nic_deliver(st, slots, valid, use_pallas=False)
    b = fab.nic_deliver(st, slots, valid, use_pallas=True)
    assert_trees_equal(a, b)
    # and nothing moved: delivery of an empty tile is the identity on the
    # data structures (monitor included — all deltas zero)
    assert_trees_equal(a.flow_fifo, st.flow_fifo)
    assert_trees_equal(a.free, st.free)


@pytest.mark.requires_pallas
def test_nic_deliver_fused_full_ring_backpressure():
    """Flow FIFOs at capacity: every granted slot must leak back to the
    free FIFO identically in both paths (drops_fifo_full counted)."""
    rng = np.random.default_rng(7)
    cfg = FabricConfig(n_flows=2, ring_entries=2, batch_size=2,
                       dynamic_batching=False, request_buffer_slots=8)
    fab = DaggerFabric(cfg)
    st = fab.init_state()
    # saturate both flow FIFOs directly (the free list can never do this
    # organically: per-flow capacity >= request_buffer_slots by design)
    caps = st.flow_fifo.capacity
    for i in range(caps):
        ffp, acc = st.flow_fifo.push(jnp.arange(2, dtype=jnp.int32),
                                     jnp.full((2, 1), i, jnp.int32),
                                     jnp.ones((2,), bool))
        assert bool(acc.all())
        st = dataclasses.replace(st, flow_fifo=ffp)
    assert int(st.flow_fifo.occupancy().min()) == caps
    slots, _ = _random_tile(rng, fab, 8)
    valid = jnp.ones((8,), bool)
    a = fab.nic_deliver(st, slots, valid, use_pallas=False)
    b = fab.nic_deliver(st, slots, valid, use_pallas=True)
    assert_trees_equal(a, b)
    assert int(a.mon["drops_fifo_full"]) > 0
    # leaked slots really returned: free-FIFO net occupancy unchanged
    assert int(a.free.available()) == int(st.free.available())


@pytest.mark.requires_pallas
def test_nic_deliver_fused_free_exhaustion():
    """Request buffer exhausted: grants stop, drops_no_slot counted, both
    paths identical."""
    cfg = FabricConfig(n_flows=2, ring_entries=8, batch_size=2,
                       dynamic_batching=False, request_buffer_slots=3)
    fab = DaggerFabric(cfg)
    st = fab.init_state()
    slots = jnp.asarray(
        np.random.default_rng(1).integers(0, 1000, (8, fab.slot_words)),
        jnp.int32)
    valid = jnp.ones((8,), bool)
    a = fab.nic_deliver(st, slots, valid, use_pallas=False)
    b = fab.nic_deliver(st, slots, valid, use_pallas=True)
    assert_trees_equal(a, b)
    assert int(a.mon["drops_no_slot"]) == 8 - 3
