"""Runtime: checkpoint/restart exactness, elasticity, stragglers, KVS."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.config import TrainConfig
from repro.configs import get_config
from repro.data import SyntheticLMData, ZipfKVWorkload
from repro.runtime.kvs import DeviceKVS
from repro.runtime.train_loop import Trainer


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"a": np.arange(12, dtype=np.float32).reshape(4, 3),
            "b": {"c": np.ones((2,), np.int32)}}
    mgr.save(7, tree, n_shards=2)
    like = jax.tree.map(np.zeros_like, tree)
    restored, manifest = mgr.restore(like)
    assert manifest["step"] == 7
    jax.tree.map(np.testing.assert_array_equal, restored, tree)


def test_checkpoint_elastic_reshard(tmp_path):
    """Saved with 4 shards, restored regardless of the new world size."""
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    mgr.save(1, tree, n_shards=4)
    restored, _ = mgr.restore(jax.tree.map(np.zeros_like, tree))
    np.testing.assert_array_equal(restored["w"], tree["w"])


def test_checkpoint_gc_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"x": np.zeros(3)}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    assert mgr.latest_step() == 4
    assert mgr._steps() == [3, 4]


def test_atomic_save_no_partial(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"x": np.zeros(3)}
    mgr.save(5, tree)
    # a leftover tmp dir (simulated crash) must be invisible to restore
    os.makedirs(os.path.join(str(tmp_path), ".tmp_step_9_crash"),
                exist_ok=True)
    assert mgr.latest_step() == 5


def test_data_determinism():
    cfg = get_config("repro-100m", reduced=True)
    d1 = SyntheticLMData(cfg, 4, 32, seed=1)
    d2 = SyntheticLMData(cfg, 4, 32, seed=1)
    b1, b2 = d1.batch_at(17), d2.batch_at(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(d1.batch_at(18)["tokens"], b1["tokens"])
    # shards partition the batch
    s0 = d1.shard_for(17, 0, 2)
    s1 = d1.shard_for(17, 1, 2)
    np.testing.assert_array_equal(
        np.concatenate([s0["tokens"], s1["tokens"]]), b1["tokens"])


def test_failure_restart_reproduces_run(tmp_path):
    """Kill at step 6, restart from checkpoint -> identical final params."""
    cfg = get_config("repro-100m", reduced=True).replace(
        n_layers=2, d_model=64, d_ff=128, vocab=256)
    tc = TrainConfig(lr=1e-3, total_steps=10, warmup_steps=2)

    t_ref = Trainer(cfg, tc, batch=2, seq=16)
    t_ref.run(8)

    ck = str(tmp_path / "ck")
    t1 = Trainer(cfg, tc, batch=2, seq=16, ckpt_dir=ck, ckpt_every=4)
    with pytest.raises(RuntimeError, match="injected node failure"):
        t1.run(8, failure_at=6)
    # "new process": fresh trainer, resume from latest checkpoint (step 4)
    t2 = Trainer(cfg, tc, batch=2, seq=16, ckpt_dir=ck, ckpt_every=4)
    assert t2.maybe_resume() and t2.step == 4
    t2.run(8)

    for a, b in zip(jax.tree.leaves(t_ref.params),
                    jax.tree.leaves(t2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_straggler_detection():
    from repro.runtime.train_loop import StragglerMonitor
    mon = StragglerMonitor(factor=3.0)
    for i in range(10):
        mon.observe(i, 0.1)
    mon.observe(10, 1.0)          # 10x median -> event
    assert mon.n_events == 1
    assert mon.events[0]["step"] == 10


# ---------------------------------------------------------------------------
# KVS
# ---------------------------------------------------------------------------

def test_kvs_set_get_roundtrip():
    kvs = DeviceKVS(n_buckets=64, ways=4, key_words=2, value_words=4)
    st = kvs.init_state()
    n = 32
    keys = jnp.stack([jnp.arange(n, dtype=jnp.int32),
                      jnp.zeros(n, jnp.int32)], axis=1)
    vals = jax.random.randint(jax.random.PRNGKey(0), (n, 4), 0, 1000,
                              jnp.int32)
    st = kvs.set(st, keys, vals)
    st, got, hit = kvs.get(st, keys)
    assert bool(hit.all())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vals))
    # missing keys miss
    st, _, hit2 = kvs.get(st, keys + 10000)
    assert not bool(hit2.any())


def test_kvs_update_in_place():
    kvs = DeviceKVS(n_buckets=16, ways=2, key_words=1, value_words=2)
    st = kvs.init_state()
    k = jnp.array([[42]], jnp.int32)
    st = kvs.set(st, k, jnp.array([[1, 2]], jnp.int32))
    st = kvs.set(st, k, jnp.array([[3, 4]], jnp.int32))
    st, v, hit = kvs.get(st, k)
    assert bool(hit[0]) and v[0].tolist() == [3, 4]
    assert int(st.n_evict) == 0


def test_kvs_eviction_under_pressure():
    kvs = DeviceKVS(n_buckets=2, ways=2, key_words=1, value_words=1)
    st = kvs.init_state()
    keys = jnp.arange(64, dtype=jnp.int32)[:, None]
    for i in range(0, 64, 4):
        st = kvs.set(st, keys[i:i + 4], keys[i:i + 4])
    assert int(st.n_evict) > 0           # table much smaller than keyspace
    st, v, hit = kvs.get(st, keys)
    ok = np.asarray(hit)
    # surviving entries return their own value
    np.testing.assert_array_equal(np.asarray(v[ok, 0]),
                                  np.asarray(keys[ok, 0]))


def test_kvs_get_after_set_property():
    """hypothesis-style randomized get-after-set with unique keys."""
    rng = np.random.default_rng(0)
    kvs = DeviceKVS(n_buckets=256, ways=4, key_words=2, value_words=2)
    st = kvs.init_state()
    keys = rng.choice(10000, size=64, replace=False).astype(np.int32)
    kw = np.stack([keys, keys * 0], axis=1)
    vals = rng.integers(0, 2**31 - 1, size=(64, 2)).astype(np.int32)
    st = kvs.set(st, jnp.asarray(kw), jnp.asarray(vals))
    st, got, hit = kvs.get(st, jnp.asarray(kw))
    # lossy store: any hit must return the exact stored value
    h = np.asarray(hit)
    assert h.mean() > 0.9                 # plenty of room -> few evictions
    np.testing.assert_array_equal(np.asarray(got)[h], vals[h])


@pytest.mark.parametrize("nb,ways,use_pallas", [(8, 4, False), (8, 4, True),
                                                 (64, 2, False)])
def test_kvs_batch_conflicts_keep_last_write(nb, ways, use_pallas):
    """Batches with duplicate keys and more new keys per bucket than
    free ways: a GET hit always returns the value last SET for that key
    (tag, key and value of a slot come from one row), never-set keys
    miss, and every stored key that misses is counted in ``n_evict``."""
    kvs = DeviceKVS(n_buckets=nb, ways=ways, key_words=2, value_words=4,
                    use_pallas=use_pallas)
    st = kvs.init_state()
    rng = np.random.default_rng(nb + ways)
    last = {}
    for _ in range(6):
        k = rng.integers(0, 3 * nb, 40)
        keys = np.stack([k, k * 7 + 1], 1).astype(np.int32)
        vals = rng.integers(0, 1 << 30, (40, 4)).astype(np.int32)
        st = kvs.set(st, jnp.asarray(keys), jnp.asarray(vals))
        for i in range(40):
            last[int(k[i])] = vals[i]
    probe = np.arange(4 * nb)
    keys = np.stack([probe, probe * 7 + 1], 1).astype(np.int32)
    st, got, hit = kvs.get(st, jnp.asarray(keys))
    got, hit = np.asarray(got), np.asarray(hit)
    for i in probe:
        if hit[i]:
            assert int(i) in last
            np.testing.assert_array_equal(got[i], last[int(i)])
    misses = sum(1 for i in last if not hit[i])
    assert 0 < misses <= int(st.n_evict)


def test_zipf_workload_shape():
    wl = ZipfKVWorkload(n_keys=100, skew=0.99, set_fraction=0.5)
    keys, is_set, kw, vw = next(wl.batches(256))
    assert keys.shape == (256,) and kw.shape[0] == 256
    # zipf: the most popular key appears much more than uniform
    assert np.bincount(keys).max() > 2 * (256 / 100)
