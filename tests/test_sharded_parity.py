"""Differential harness: the mesh-sharded dataplane vs the tenant-batched
one, asserted bit-identical.

``ShardedTenantEngine`` (``shard_map`` of the vmapped loopback step over
the tenant axis) and ``Switch.switch_step_sharded`` (the stacked switch
with its crossbar routed through the ``all_to_all_tiles`` ToR hop) must
reproduce ``TenantEngine`` / ``switch_step_stacked`` EXACTLY on any mesh
shape — and transitively the N independent ``LoopbackEngine`` runs that
``test_tenant_parity.py`` pins the batched engines to.  The whole
pipeline is int32, so any drift is a routing/arbitration bug, not
numerics.

The mesh spans every visible device: a plain CPU run exercises the
1-lane degenerate mesh; the CI multi-device leg re-runs this module
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so each
device owns one NIC slot and the inter-shard paths really cross device
boundaries.  Tenant counts are multiples of 8 so both shapes divide.
"""
import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FabricConfig
from repro.core import serdes
from repro.core.engine import (LoopbackEngine, ShardedTenantEngine,
                               TenantEngine, shard_states, stack_states)
from repro.core.fabric import DaggerFabric
from repro.core.load_balancer import LB_ROUND_ROBIN
from repro.core.transport import (make_tenant_mesh, mesh_all_to_all,
                                  mesh_shift)
from repro.core.virtualization import Switch

PALLAS_CASES = [False, pytest.param(True, marks=pytest.mark.requires_pallas)]

N_TENANTS = 8            # divides 1/2/4/8-device meshes


def _eqns(jaxpr, loops=()):
    """Every equation of ``jaxpr`` and of the jaxprs it calls, outside
    Pallas kernels, with the loop primitives that enclose it."""
    for e in jaxpr.eqns:
        yield e, loops
        if e.primitive.name == "pallas_call":
            continue
        inner = loops + ((e.primitive.name,)
                         if e.primitive.name in ("while", "scan") else ())
        for p in e.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub, inner)


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


LOADS = [4, 6, 8, 2, 3, 5, 7, 1]


# ---------------------------------------------------------------------------
# ShardedTenantEngine vs TenantEngine (and transitively LoopbackEngine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", PALLAS_CASES)
def test_sharded_run_steps_matches_tenant(use_pallas):
    """8 NIC slots over however many devices exist: exact pytree equality
    with the single-device TenantEngine (the acceptance-criterion case)."""
    client, server = _fabrics(use_pallas=use_pallas)
    csts, ssts = _tenant_pairs(client, server, N_TENANTS, LOADS)
    stc, sts = stack_states(csts), stack_states(ssts)
    stc2, sts2 = stack_states(csts), stack_states(ssts)

    teng = TenantEngine(client, server, _echo)
    tc, ts, tdone = teng.run_steps(stc, sts, 5)

    seng = ShardedTenantEngine(client, server, _echo)
    assert seng.n_devices == len(jax.devices())
    sc, ss, sdone = seng.run_steps(*seng.shard_states(stc2, sts2), 5)
    np.testing.assert_array_equal(np.asarray(tdone), np.asarray(sdone))
    np.testing.assert_array_equal(np.asarray(sdone), LOADS)
    assert_trees_equal(tc, sc, "client states diverged across the mesh")
    assert_trees_equal(ts, ss, "server states diverged across the mesh")


def test_sharded_run_steps_matches_independent_loopback():
    """Transitivity spelled out: the sharded engine equals N independent
    LoopbackEngine runs directly, not just via TenantEngine."""
    client, server = _fabrics()
    csts, ssts = _tenant_pairs(client, server, N_TENANTS, LOADS)
    stc, sts = stack_states(csts), stack_states(ssts)

    refs = []
    for t in range(N_TENANTS):
        eng = LoopbackEngine(client, server, _echo)
        c2, s2, done = eng.run_steps(csts[t], ssts[t], 5)
        refs.append((c2, s2, int(done)))

    seng = ShardedTenantEngine(client, server, _echo)
    sc, ss, sdone = seng.run_steps(*seng.shard_states(stc, sts), 5)
    for t, (c_ref, s_ref, d_ref) in enumerate(refs):
        assert int(sdone[t]) == d_ref == LOADS[t]
        assert_trees_equal(jax.tree.map(lambda x: x[t], sc), c_ref,
                           f"client state diverged for tenant {t}")
        assert_trees_equal(jax.tree.map(lambda x: x[t], ss), s_ref,
                           f"server state diverged for tenant {t}")


def test_sharded_run_until_per_lane_targets():
    """Each lane stops at ITS target and freezes; each device's while
    loop ends with its own lanes — results still bit-match the
    single-device engine, per-lane step counts included."""
    client, server = _fabrics()
    loads = [8] * N_TENANTS
    targets = [4, 6, 8, 2, 5, 3, 7, 8]
    csts, ssts = _tenant_pairs(client, server, N_TENANTS, loads)
    stc, sts = stack_states(csts), stack_states(ssts)
    stc2, sts2 = stack_states(csts), stack_states(ssts)

    teng = TenantEngine(client, server, _echo)
    tc, ts, tdone, tsteps = teng.run_until(stc, sts,
                                           jnp.asarray(targets), 16)

    seng = ShardedTenantEngine(client, server, _echo)
    sc, ss, sdone, ssteps = seng.run_until(
        *seng.shard_states(stc2, sts2), jnp.asarray(targets), 16)
    np.testing.assert_array_equal(np.asarray(tdone), np.asarray(sdone))
    np.testing.assert_array_equal(np.asarray(tsteps), np.asarray(ssteps))
    assert_trees_equal(tc, sc)
    assert_trees_equal(ts, ss)


def test_sharded_stateful_handler_parity():
    """Stacked handler state shards with the tenant axis: per-tenant
    counters with distinct initial values match the batched runs."""
    client, server = _fabrics()

    def handler(recs, valid, count):
        out = dict(recs)
        out["payload"] = recs["payload"] + 1
        return out, count + jnp.sum(valid.astype(jnp.int32))

    csts, ssts = _tenant_pairs(client, server, N_TENANTS, LOADS)
    h0 = jnp.arange(N_TENANTS, dtype=jnp.int32) * 10
    h0b = jnp.copy(h0)                  # both engines donate their hstate
    stc, sts = stack_states(csts), stack_states(ssts)
    stc2, sts2 = stack_states(csts), stack_states(ssts)

    teng = TenantEngine(client, server, handler, stateful=True)
    tc, ts, th, tdone = teng.run_steps(stc, sts, 4, hstate=h0)

    seng = ShardedTenantEngine(client, server, handler, stateful=True)
    sc, ss, sh0 = seng.shard_states(stc2, sts2, h0b)
    sc, ss, sh, sdone = seng.run_steps(sc, ss, 4, hstate=sh0)
    np.testing.assert_array_equal(np.asarray(th), np.asarray(sh))
    np.testing.assert_array_equal(np.asarray(tdone), np.asarray(sdone))
    assert_trees_equal(tc, sc)
    assert_trees_equal(ts, ss)


@pytest.mark.parametrize("use_pallas", PALLAS_CASES)
def test_sharded_kvs_parity(use_pallas):
    """DeviceKVS.make_sharded_tenant_engine == make_tenant_engine, the
    per-tenant stores riding the sharded handler state (the stateful
    acceptance config), with the fused megakernel both ways."""
    from repro.runtime.kvs import DeviceKVS
    client, server = _fabrics(use_pallas=use_pallas, n_flows=2, batch=4)
    kvs = DeviceKVS(n_buckets=64, ways=4, key_words=2, value_words=4)
    pw = client.slot_words - serdes.HEADER_WORDS
    enq = jax.jit(client.host_tx_enqueue)

    n = 4
    csts, ssts = [], []
    for t in range(N_TENANTS):
        cst, sst = client.init_state(), server.init_state()
        cst = client.open_connection(cst, 1, 0, 1, LB_ROUND_ROBIN)
        sst = server.open_connection(sst, 1, 0, 0, LB_ROUND_ROBIN)
        pay = np.zeros((n, pw), np.int32)
        pay[:, 0] = np.arange(n) + 1 + 10 * t          # per-tenant keys
        pay[:, 2] = np.arange(n) + 100 + 10 * t        # per-tenant values
        recs = serdes.make_records(
            np.full(n, 1, np.int32), np.arange(n, dtype=np.int32),
            np.ones(n, np.int32),                      # fn_id 1 = SET
            np.zeros(n, np.int32), jnp.asarray(pay))
        cst, _ = enq(cst, recs, jnp.arange(n) % 2)
        csts.append(cst)
        ssts.append(sst)
    stc, sts = stack_states(csts), stack_states(ssts)
    stc2, sts2 = stack_states(csts), stack_states(ssts)

    teng = kvs.make_tenant_engine(client, server)
    tc, ts, tdb, tdone = teng.run_steps(
        stc, sts, 4, hstate=kvs.init_state_batch(N_TENANTS))

    seng = kvs.make_sharded_tenant_engine(client, server)
    sc, ss, sdb = seng.shard_states(stc2, sts2,
                                    kvs.init_state_batch(N_TENANTS))
    sc, ss, sdb, sdone = seng.run_steps(sc, ss, 4, hstate=sdb)
    np.testing.assert_array_equal(np.asarray(tdone), np.asarray(sdone))
    assert_trees_equal(tdb, sdb, "KVS stores diverged across the mesh")
    assert_trees_equal(tc, sc)
    assert_trees_equal(ts, ss)
    # tenant isolation survives sharding: tenant 0's keys miss store 1
    keys = jnp.stack([jnp.arange(n, dtype=jnp.int32) + 1,
                      jnp.zeros(n, jnp.int32)], axis=1)
    db1 = jax.tree.map(lambda x: x[1], sdb)
    _, _, hit = kvs.get(db1, keys)
    assert not bool(hit.any())


@pytest.mark.requires_pallas
def test_sharded_kvs_probe_kernel_parity():
    """The store's GET probe on the kernel route under the sharded
    engine (``jax.vmap`` inside ``shard_map``, over each device's shard of
    the stacked stores) == ``make_tenant_engine``: every key SET is read
    back by a GET, and the program holds one probe call, inside the step
    loop alone (no loop over the stores around it)."""
    from repro.runtime.kvs import DeviceKVS
    client, server = _fabrics(n_flows=2, batch=4)
    kvs = DeviceKVS(n_buckets=512, ways=4, key_words=2, value_words=4,
                    use_pallas=True)
    pw = client.slot_words - serdes.HEADER_WORDS
    enq = jax.jit(client.host_tx_enqueue)

    n = 4
    csts, ssts = [], []
    for t in range(N_TENANTS):
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
    stc2, sts2 = stack_states(csts), stack_states(ssts)

    teng = kvs.make_tenant_engine(client, server)
    tc, ts, tdb, tdone = teng.run_steps(
        stc, sts, 4, hstate=kvs.init_state_batch(N_TENANTS))

    seng = kvs.make_sharded_tenant_engine(client, server)
    sc, ss, sdb = seng.shard_states(stc2, sts2,
                                    kvs.init_state_batch(N_TENANTS))
    jaxpr = seng._run_steps.trace(sc, ss, sdb, 4).jaxpr.jaxpr
    calls = [loops for e, loops in _eqns(jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1 and len(calls[0]) == 1, calls
    sc, ss, sdb, sdone = seng.run_steps(sc, ss, 4, hstate=sdb)
    np.testing.assert_array_equal(np.asarray(tdone), np.asarray(sdone))
    np.testing.assert_array_equal(np.asarray(sdone), 2 * n)
    np.testing.assert_array_equal(np.asarray(sdb.n_hit), n)
    assert_trees_equal(tdb, sdb, "KVS stores diverged across the mesh")
    assert_trees_equal(tc, sc)
    assert_trees_equal(ts, ss)


# ---------------------------------------------------------------------------
# run_until_global: fleet-wide (psum) completion target
# ---------------------------------------------------------------------------

def test_run_until_global_reaches_fleet_target():
    """The global sweep serves exactly the offered load when the target
    equals it, reports per-device step counts, and — because a drained
    loopback lane's extra steps are no-ops — lands on the same states
    as the equivalent fixed-step batched run."""
    client, server = _fabrics()
    csts, ssts = _tenant_pairs(client, server, N_TENANTS, LOADS)
    stc, sts = stack_states(csts), stack_states(ssts)
    stc2, sts2 = stack_states(csts), stack_states(ssts)

    seng = ShardedTenantEngine(client, server, _echo)
    sc, ss, done, dev_steps = seng.run_until_global(
        *seng.shard_states(stc, sts), sum(LOADS), 64)
    np.testing.assert_array_equal(np.asarray(done), LOADS)
    assert dev_steps.shape == (len(jax.devices()),)
    # the psum predicate ends every device's loop on the same step
    assert len(set(np.asarray(dev_steps).tolist())) == 1
    s = int(dev_steps[0])
    assert 0 < s <= 64

    # no per-lane freezing => the sweep IS s fused steps on every lane
    teng = TenantEngine(client, server, _echo)
    tc, ts, tdone = teng.run_steps(stc2, sts2, s)
    np.testing.assert_array_equal(np.asarray(tdone), np.asarray(done))
    assert_trees_equal(tc, sc, "global sweep diverged from run_steps")
    assert_trees_equal(ts, ss)


def test_run_until_global_hits_max_steps():
    """An unreachable target stops at max_steps on every device."""
    client, server = _fabrics()
    csts, ssts = _tenant_pairs(client, server, N_TENANTS, LOADS)
    seng = ShardedTenantEngine(client, server, _echo)
    _, _, done, dev_steps = seng.run_until_global(
        *seng.shard_states(stack_states(csts), stack_states(ssts)),
        10_000, 7)
    np.testing.assert_array_equal(np.asarray(dev_steps),
                                  [7] * len(jax.devices()))
    assert int(np.asarray(done).sum()) == sum(LOADS)


def test_run_until_global_partial_target_stops_early():
    """A sub-drain target ends the sweep as soon as the fleet total
    crosses it (possibly overshooting within the final step)."""
    client, server = _fabrics()
    loads = [8] * N_TENANTS
    csts, ssts = _tenant_pairs(client, server, N_TENANTS, loads)
    seng = ShardedTenantEngine(client, server, _echo)
    target = 10
    _, _, done, dev_steps = seng.run_until_global(
        *seng.shard_states(stack_states(csts), stack_states(ssts)),
        target, 64)
    total = int(np.asarray(done).sum())
    assert total >= target
    assert int(dev_steps[0]) < 64


def test_run_until_global_kvs_stateful():
    """The DeviceKVS port: per-tenant stores ride the global sweep, and
    the result equals the batched engine run for the same step count."""
    from repro.runtime.kvs import DeviceKVS
    client, server = _fabrics(n_flows=2, batch=4)
    kvs = DeviceKVS(n_buckets=64, ways=4, key_words=2, value_words=4)
    pw = client.slot_words - serdes.HEADER_WORDS
    enq = jax.jit(client.host_tx_enqueue)

    n = 4
    csts, ssts = [], []
    for t in range(N_TENANTS):
        cst, sst = client.init_state(), server.init_state()
        cst = client.open_connection(cst, 1, 0, 1, LB_ROUND_ROBIN)
        sst = server.open_connection(sst, 1, 0, 0, LB_ROUND_ROBIN)
        pay = np.zeros((n, pw), np.int32)
        pay[:, 0] = np.arange(n) + 1 + 10 * t
        pay[:, 2] = np.arange(n) + 100 + 10 * t
        recs = serdes.make_records(
            np.full(n, 1, np.int32), np.arange(n, dtype=np.int32),
            np.ones(n, np.int32), np.zeros(n, np.int32),
            jnp.asarray(pay))
        cst, _ = enq(cst, recs, jnp.arange(n) % 2)
        csts.append(cst)
        ssts.append(sst)
    stc, sts = stack_states(csts), stack_states(ssts)
    stc2, sts2 = stack_states(csts), stack_states(ssts)

    seng = kvs.make_sharded_tenant_engine(client, server)
    sc, ss, sdb = seng.shard_states(stc, sts,
                                    kvs.init_state_batch(N_TENANTS))
    sc, ss, sdb, sdone, dev_steps = seng.run_until_global(
        sc, ss, n * N_TENANTS, 32, hstate=sdb)
    assert int(np.asarray(sdone).sum()) == n * N_TENANTS
    s = int(dev_steps[0])

    teng = kvs.make_tenant_engine(client, server)
    tc, ts, tdb, tdone = teng.run_steps(
        stc2, sts2, s, hstate=kvs.init_state_batch(N_TENANTS))
    np.testing.assert_array_equal(np.asarray(tdone), np.asarray(sdone))
    assert_trees_equal(tdb, sdb, "KVS stores diverged in global sweep")
    assert_trees_equal(tc, sc)
    assert_trees_equal(ts, ss)


def test_serving_run_until_global():
    """The ServingEngine port: the sweep consumes staged ingress tiles
    until the fleet-wide served total crosses the target; a full-drain
    target reproduces make_tenant_run_steps exactly (int fields)."""
    from repro.configs import get_config
    from repro.runtime.serving import FLAG_NEW, ServingEngine
    cfg = get_config("repro-100m", reduced=True).replace(
        n_layers=2, d_model=64, d_ff=128, vocab=128, n_heads=4,
        n_kv_heads=4)
    fcfg = FabricConfig(n_flows=2, ring_entries=32, batch_size=4,
                        dynamic_batching=False)
    k, n_sessions = 3, 2
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

    per = [tiles(t) for t in range(N_TENANTS)]
    in_slots = jnp.stack([p[0] for p in per], axis=1)   # [K, T, N, W]
    in_valid = jnp.stack([p[1] for p in per], axis=1)

    run_t = eng.make_tenant_run_steps()
    fst, cache, sess = eng.init_states_batch(N_TENANTS)
    _, _, sess_t, served_t, _, _ = run_t(fst, cache, sess, eng.params,
                                         in_slots, in_valid)

    mesh = make_tenant_mesh()
    run_g = eng.make_sharded_tenant_run_until_global(mesh=mesh)
    fst, cache, sess = eng.init_states_batch(N_TENANTS)
    fst, cache, sess = eng.shard_tenant_states(fst, cache, sess, mesh)
    # full-drain target: the while loop must run all K staged steps
    _, _, sess_g, served_g, dev_steps, out_s, out_v = run_g(
        fst, cache, sess, eng.params, in_slots, in_valid,
        10_000, k + 5)
    np.testing.assert_array_equal(np.asarray(dev_steps),
                                  [k] * len(jax.devices()))
    np.testing.assert_array_equal(np.asarray(served_t),
                                  np.asarray(served_g))
    np.testing.assert_array_equal(np.asarray(sess_t.session_id),
                                  np.asarray(sess_g.session_id))
    np.testing.assert_array_equal(np.asarray(sess_t.pos),
                                  np.asarray(sess_g.pos))
    assert out_s.shape[:2] == (k, N_TENANTS)

    # early-stop target: first-step traffic alone crosses it
    fst, cache, sess = eng.init_states_batch(N_TENANTS)
    fst, cache, sess = eng.shard_tenant_states(fst, cache, sess, mesh)
    _, _, _, served_e, dev_steps_e, _, out_v_e = run_g(
        fst, cache, sess, eng.params, in_slots, in_valid,
        n_sessions * N_TENANTS, k + 5)
    assert int(dev_steps_e[0]) == 1
    assert int(np.asarray(served_e).sum()) >= n_sessions * N_TENANTS
    # egress tiles of steps the loop never reached stay invalid
    assert not bool(np.asarray(out_v_e[1:]).any())


# ---------------------------------------------------------------------------
# switch_step_sharded vs switch_step_stacked (multi-tier, cross-shard)
# ---------------------------------------------------------------------------

def _switch_topology(n_tiers=N_TENANTS, use_pallas=False):
    """Tier 0 fans out to the BACK half of the mesh (so every request
    crosses a shard boundary on a multi-device mesh), tier 1 calls its
    neighbour tier 2, the rest serve."""
    cfg = FabricConfig(n_flows=2, ring_entries=16, batch_size=4,
                       dynamic_batching=False, use_pallas=use_pallas)
    fabrics = [DaggerFabric(cfg) for _ in range(n_tiers)]
    sw = Switch(fabrics)
    states = sw.init_states()
    conns = []
    for i, dst in enumerate(range(n_tiers // 2, n_tiers)):
        c = 10 + i
        states[0] = fabrics[0].open_connection(states[0], c, 0, dst,
                                               LB_ROUND_ROBIN)
        states[dst] = fabrics[dst].open_connection(states[dst], c, 0, 0,
                                                   LB_ROUND_ROBIN)
        conns.append(c)
    states[1] = fabrics[1].open_connection(states[1], 30, 1, 2,
                                           LB_ROUND_ROBIN)
    states[2] = fabrics[2].open_connection(states[2], 30, 1, 1,
                                           LB_ROUND_ROBIN)

    def add(c):
        def h(recs, valid):
            out = dict(recs)
            out["payload"] = recs["payload"] + c
            return out
        return h

    handlers = [None, None, add(5)] + \
        [add(100 * (i + 1)) for i in range(n_tiers - 3)]

    pw = fabrics[0].slot_words - serdes.HEADER_WORDS
    n = 2 * len(conns)
    pay = jnp.tile(jnp.arange(pw, dtype=jnp.int32)[None], (n, 1))
    recs = serdes.make_records(
        jnp.asarray(conns * 2, jnp.int32), jnp.arange(n, dtype=jnp.int32),
        jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32), pay)
    states[0], acc = jax.jit(fabrics[0].host_tx_enqueue)(
        states[0], recs, jnp.arange(n) % 2)
    assert bool(acc.all())
    recs1 = serdes.make_records(
        jnp.full(3, 30, jnp.int32), jnp.arange(3, dtype=jnp.int32),
        jnp.zeros(3, jnp.int32), jnp.zeros(3, jnp.int32), pay[:3])
    states[1], acc = jax.jit(fabrics[1].host_tx_enqueue)(
        states[1], recs1, jnp.arange(3) % 2)
    assert bool(acc.all())
    return sw, states, handlers


@pytest.mark.parametrize("use_pallas", PALLAS_CASES)
def test_switch_step_sharded_matches_stacked(use_pallas):
    """Inter-shard RPCs through the all_to_all ToR hop: states AND
    completions bit-match the single-device stacked step, every step,
    requests and their responses crossing shard boundaries both ways."""
    sw, states, handlers = _switch_topology(use_pallas=use_pallas)
    mesh = make_tenant_mesh()
    stacked = sw.stack_states(states)
    sharded = shard_states(sw.stack_states(states), mesh)
    step_st = jax.jit(lambda s: sw.switch_step_stacked(s, handlers))
    step_sh = jax.jit(
        lambda s: sw.switch_step_sharded(s, handlers, mesh=mesh))

    for step in range(6):
        stacked, (ra, va) = step_st(stacked)
        sharded, (rb, vb) = step_sh(sharded)
        assert_trees_equal(stacked, sharded,
                           f"switch states diverged at step {step}")
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                      err_msg=f"valid at step {step}")
        assert_trees_equal(ra, rb, f"completions diverged at step {step}")


def test_switch_step_sharded_delivers_cross_shard_responses():
    """End-to-end check that responses actually arrive: tier 0's
    completions contain every handler-stamped response payload."""
    sw, states, handlers = _switch_topology()
    mesh = make_tenant_mesh()
    sharded = shard_states(sw.stack_states(states), mesh)
    step_sh = jax.jit(
        lambda s: sw.switch_step_sharded(s, handlers, mesh=mesh))
    got = {}
    for _ in range(6):
        sharded, (recs, valid) = step_sh(sharded)
        r0 = jax.tree.map(lambda x: np.asarray(x[0]), recs)
        v0 = np.asarray(valid[0])
        for i in np.nonzero(v0)[0]:
            if r0["flags"][i] & serdes.FLAG_RESPONSE:
                got[int(r0["rpc_id"][i])] = int(r0["payload"][i][0])
    # rpc k went to tier n_tiers//2 + (k % 5): payload[0] = 0 + 100*(dst idx+1)
    n_conns = N_TENANTS - N_TENANTS // 2
    want = {k: 100 * (k % n_conns + 1 + (N_TENANTS // 2 - 3))
            for k in range(2 * n_conns)}
    assert got == want


# ---------------------------------------------------------------------------
# serving + guards + transport
# ---------------------------------------------------------------------------

def test_sharded_serving_smoke():
    """make_sharded_tenant_run_steps: per-tenant served counts and (int)
    session tables match make_tenant_run_steps; float token values are
    excluded as in the tenant smoke (vmap may legally reorder float
    reductions)."""
    from repro.configs import get_config
    from repro.runtime.serving import FLAG_NEW, ServingEngine
    cfg = get_config("repro-100m", reduced=True).replace(
        n_layers=2, d_model=64, d_ff=128, vocab=128, n_heads=4,
        n_kv_heads=4)
    fcfg = FabricConfig(n_flows=2, ring_entries=32, batch_size=4,
                        dynamic_batching=False)
    k, n_sessions = 2, 2
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

    per = [tiles(t) for t in range(N_TENANTS)]
    in_slots = jnp.stack([p[0] for p in per], axis=1)   # [K, T, N, W]
    in_valid = jnp.stack([p[1] for p in per], axis=1)

    run_t = eng.make_tenant_run_steps()
    fst, cache, sess = eng.init_states_batch(N_TENANTS)
    _, _, sess_t, served_t, _, _ = run_t(fst, cache, sess, eng.params,
                                         in_slots, in_valid)

    mesh = make_tenant_mesh()
    run_s = eng.make_sharded_tenant_run_steps(mesh=mesh)
    fst, cache, sess = eng.init_states_batch(N_TENANTS)
    fst, cache, sess = eng.shard_tenant_states(fst, cache, sess, mesh)
    _, _, sess_s, served_s, out_s, out_v = run_s(
        fst, cache, sess, eng.params, in_slots, in_valid)
    assert out_s.shape[:2] == (k, N_TENANTS)
    np.testing.assert_array_equal(np.asarray(served_t),
                                  np.asarray(served_s))
    np.testing.assert_array_equal(np.asarray(sess_t.session_id),
                                  np.asarray(sess_s.session_id))
    np.testing.assert_array_equal(np.asarray(sess_t.pos),
                                  np.asarray(sess_s.pos))


def test_sharded_engine_rejects_indivisible_tenants():
    """Whole NIC slots per device: a tenant count that does not divide
    the mesh axis is a configuration error, not silent padding."""
    if len(jax.devices()) == 1:
        pytest.skip("needs a >1-device mesh to be indivisible")
    client, server = _fabrics()
    n = len(jax.devices()) + 1
    csts, ssts = _tenant_pairs(client, server, n, [2] * n)
    seng = ShardedTenantEngine(client, server, _echo)
    with pytest.raises(ValueError, match="divide"):
        seng.run_steps(stack_states(csts), stack_states(ssts), 2)


def test_mesh_transport_roundtrip():
    """The (now-live) mesh transport wrappers: a full rotation returns
    every tile home; all_to_all twice is the identity."""
    mesh = make_tenant_mesh()
    d = mesh.shape["tenant"]
    tile = {"a": jnp.arange(d * 3, dtype=jnp.int32).reshape(d, 3),
            "b": jnp.arange(d, dtype=jnp.int32)[:, None] * 10}
    shifted = tile
    for _ in range(d):
        shifted = mesh_shift(shifted, mesh, "tenant")
    assert_trees_equal(shifted, tile, "full ring rotation != identity")
    # one shift really moves data on a multi-lane mesh
    if d > 1:
        moved = mesh_shift(tile, mesh, "tenant")
        np.testing.assert_array_equal(
            np.asarray(moved["a"]),
            np.roll(np.asarray(tile["a"]), 1, axis=0))
    # all_to_all: every lane holds one bucket per destination lane
    # ([lanes * lanes, ...] globally); the exchange is a transpose of
    # the (src, dst) bucket grid, so applying it twice is the identity
    buckets = jnp.arange(d * d * 2, dtype=jnp.int32).reshape(d * d, 2)
    once = mesh_all_to_all(buckets, mesh, "tenant")
    np.testing.assert_array_equal(
        np.asarray(once).reshape(d, d, 2),
        np.asarray(buckets).reshape(d, d, 2).transpose(1, 0, 2))
    twice = mesh_all_to_all(once, mesh, "tenant")
    np.testing.assert_array_equal(np.asarray(twice), np.asarray(buckets))
