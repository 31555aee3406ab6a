"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# every test here drives a Pallas kernel; degrade to skip (not error)
# on backends where even the interpreter is unavailable
pytestmark = pytest.mark.requires_pallas

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("r,w,f,b", [(8, 16, 2, 4), (32, 12, 4, 8),
                                     (64, 16, 1, 16), (5, 4, 3, 2)])
def test_ring_gather_sweep(r, w, f, b):
    table = jax.random.randint(KEY, (r, w), -1000, 1000, jnp.int32)
    refs = jax.random.randint(jax.random.PRNGKey(r), (f, b), 0, r + 1,
                              jnp.int32)     # includes OOB sentinel r
    np.testing.assert_array_equal(
        np.asarray(ops.ring_gather(table, refs)),
        np.asarray(ref.ref_ring_gather(table, refs)))


@pytest.mark.parametrize("r,w,f,b", [(8, 16, 2, 4), (16, 8, 4, 4)])
def test_ring_copy_module_parity(r, w, f, b):
    """Direct kernel-module-vs-oracle parity (FL001 registry pair):
    ``ring_copy.ring_gather`` against ``ref.ref_ring_copy``, bypassing
    the ``ops`` facade so the pallas_call path itself is pinned."""
    from repro.kernels import ring_copy
    table = jax.random.randint(KEY, (r, w), -1000, 1000, jnp.int32)
    refs = jax.random.randint(jax.random.PRNGKey(r * 7 + b), (f, b), 0,
                              r + 1, jnp.int32)  # includes OOB sentinel r
    np.testing.assert_array_equal(
        np.asarray(ring_copy.ring_gather(table, refs, interpret=True)),
        np.asarray(ref.ref_ring_copy(table, refs)))


@pytest.mark.parametrize("n,flows,kw", [(1, 2, 1), (17, 7, 2), (256, 16, 2),
                                        (300, 5, 3)])
def test_hash_steer_sweep(n, flows, kw):
    payload = jax.random.randint(jax.random.PRNGKey(n), (n, 12),
                                 -2**31, 2**31 - 1, jnp.int32)
    a = ops.hash_steer_static(payload, flows, key_words=kw)
    b = ref.ref_hash_steer(payload, flows, key_words=kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hash_steer_dynamic_matches_static():
    payload = jax.random.randint(KEY, (64, 12), -2**31, 2**31 - 1, jnp.int32)
    for flows in (2, 3, 7, 16):
        a = ops.hash_steer(payload, jnp.int32(flows))
        b = ref.ref_hash_steer(payload, flows)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n,f,e,r", [(1, 1, 2, 4), (8, 2, 4, 8),
                                     (24, 4, 8, 16), (40, 3, 4, 12)])
def test_nic_deliver_fused_kernel_sweep(n, f, e, r):
    """Raw-array megakernel vs its jnp oracle (state-level parity lives
    in test_tenant_parity.py / test_properties.py)."""
    rng = np.random.default_rng(n * 131 + f)
    w, c = 12, 16
    slots = jnp.asarray(rng.integers(-1000, 1000, (n, w)), jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, n), jnp.int32)
    # a shuffled free list with a random live window [head, tail)
    fifo = jnp.asarray(rng.permutation(r), jnp.int32)
    head = int(rng.integers(0, r))
    avail = int(rng.integers(0, r + 1))
    req = jnp.asarray(rng.integers(-99, 99, (r, w)), jnp.int32)
    ffbuf = jnp.asarray(rng.integers(-99, 99, (f, e)), jnp.int32)
    tag = jnp.asarray(rng.integers(-1, 40, c), jnp.int32)
    src = jnp.asarray(rng.integers(0, 8, c), jnp.int32)
    lb = jnp.asarray(rng.integers(0, 3, c), jnp.int32)
    fftail = jnp.asarray(rng.integers(0, 100, f), jnp.int32)
    ffspace = jnp.asarray(rng.integers(0, e + 1, f), jnp.int32)
    scal = jnp.asarray([head, avail, head + avail,
                        int(rng.integers(0, 50)),
                        int(rng.integers(1, f + 1))], jnp.int32)
    got = ops.nic_deliver_fused(slots, valid, fifo, req, ffbuf, tag, src,
                                lb, fftail, ffspace, scal)
    want = ref.ref_nic_deliver_fused(slots, valid, fifo, req, ffbuf, tag,
                                     src, lb, fftail, ffspace, scal)
    for g, x in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))


@pytest.mark.parametrize("seed", range(3))
def test_nic_deliver_fused_mixed_scheme_batches(seed):
    """Batches interleaving STATIC/OBJECT and invalid lanes between
    ROUND_ROBIN rows: the kernel's carried RR counter must agree with the
    oracle's cumulative rank (the mixed-batch steering bug regression —
    RR positions are dense over the VALID RR rows, not raw batch
    indices, and invalid lanes never consume a slot)."""
    rng = np.random.default_rng(400 + seed)
    n, w, f, e, r, c = 24, 12, 4, 8, 16, 8
    slots = jnp.asarray(rng.integers(-1000, 1000, (n, w)), jnp.int32)
    # every conn-cache entry hits, with a scheme mix that interleaves
    conn_ids = jnp.asarray(rng.integers(0, c, n), jnp.int32)
    slots = slots.at[:, 0].set(conn_ids)
    slots = slots.at[:, 2].set(0)                 # requests, not responses
    valid = jnp.asarray(rng.integers(0, 4, n) > 0, jnp.int32).astype(
        jnp.int32)                                # ~1/4 invalid lanes
    tag = jnp.arange(c, dtype=jnp.int32)          # tag[i] == i: all hit
    src = jnp.asarray(rng.integers(0, f, c), jnp.int32)
    lb = jnp.asarray(rng.permutation([0, 0, 0, 1, 1, 2, 2, 2]), jnp.int32)
    fifo = jnp.asarray(rng.permutation(r), jnp.int32)
    req = jnp.zeros((r, w), jnp.int32)
    ffbuf = jnp.full((f, e), -1, jnp.int32)
    fftail = jnp.zeros((f,), jnp.int32)
    ffspace = jnp.full((f,), e, jnp.int32)
    scal = jnp.asarray([0, r, 0, int(rng.integers(0, 50)), f], jnp.int32)
    got = ops.nic_deliver_fused(slots, valid, fifo, req, ffbuf, tag, src,
                                lb, fftail, ffspace, scal)
    want = ref.ref_nic_deliver_fused(slots, valid, fifo, req, ffbuf, tag,
                                     src, lb, fftail, ffspace, scal)
    for g, x in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
    # valid RR rows fill slots densely: k-th one -> (rr0 + k) % f
    flow = np.asarray(got[4])
    vrr = (np.asarray(lb)[np.asarray(conn_ids)] == 0) \
        & (np.asarray(valid) != 0)
    rr0 = int(scal[3])
    np.testing.assert_array_equal(
        flow[vrr], (rr0 + np.arange(vrr.sum())) % f)
    # cursor advance == #valid RR rows
    assert int(got[8][2]) == int(vrr.sum())


@pytest.mark.parametrize("n,sw", [(1, 16), (13, 16), (64, 8), (100, 32)])
def test_rpc_pack_sweep(n, sw):
    from repro.core import serdes
    ks = [jax.random.randint(jax.random.PRNGKey(i), (n,), 0, 2**16,
                             jnp.int32) for i in range(7)]
    pay = jax.random.randint(KEY, (n, sw - serdes.HEADER_WORDS),
                             -100, 100, jnp.int32)
    a = ops.rpc_pack(*ks, pay, sw)
    b = ref.ref_rpc_pack(*ks, pay, sw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rpc_pack_matches_serdes_with_fragments():
    """Kernel == serdes.pack on fragment headers: word 3 carries the
    fragment index and word 4 the issue-step timestamp through a full
    pack->unpack round trip (the wire bug regression: the old kernel
    masked word 3 to its low 16 bits; timestamps predate nothing — the
    field was dormant in the IDL until the telemetry layer wired it)."""
    from repro.core import serdes
    n, sw = 8, 16
    recs = serdes.make_records(
        jnp.arange(n, dtype=jnp.int32), jnp.arange(n, dtype=jnp.int32),
        jnp.zeros(n, jnp.int32),
        jnp.full(n, serdes.FLAG_FRAGMENT, jnp.int32),
        jnp.zeros((n, sw - serdes.HEADER_WORDS), jnp.int32),
        payload_len=jnp.full(n, 44, jnp.int32),
        frag_idx=jnp.arange(n, dtype=jnp.int32) * 3,
        timestamp=jnp.arange(n, dtype=jnp.int32) + 1000)
    want = serdes.pack(recs, sw)
    got = ops.rpc_pack(recs["conn_id"], recs["rpc_id"], recs["fn_id"],
                       recs["flags"], recs["payload_len"],
                       recs["frag_idx"], recs["timestamp"],
                       recs["payload"], sw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    back = serdes.unpack(got)
    np.testing.assert_array_equal(np.asarray(back["frag_idx"]),
                                  np.arange(n) * 3)
    np.testing.assert_array_equal(np.asarray(back["payload_len"]),
                                  np.full(n, 44))
    np.testing.assert_array_equal(np.asarray(back["timestamp"]),
                                  np.arange(n) + 1000)


@pytest.mark.parametrize("nb,ways,vw,n,dup", [(8, 2, 4, 4, False),
                                              (64, 4, 8, 16, False),
                                              (16, 8, 2, 33, False),
                                              (64, 4, 8, 130, True)])
def test_kv_probe_sweep(nb, ways, vw, n, dup):
    """Packed-table probe vs the logical-layout oracle.  ``dup`` gives
    every bucket's ways 0 and 1 the same tag with different keys (two
    keys whose 32-bit hashes collide): only the key tells them apart."""
    from repro.kernels.kv_probe import pack
    kw = 2
    tags = jax.random.randint(KEY, (nb, ways), 1, 2**31 - 1,
                              jnp.int32).astype(jnp.uint32)
    if dup:
        tags = tags.at[:, 1].set(tags[:, 0])
    keys = jax.random.randint(jax.random.PRNGKey(5), (nb, ways, kw),
                              -1000, 1000, jnp.int32)
    vals = jax.random.randint(jax.random.PRNGKey(1), (nb, ways, vw),
                              0, 1000, jnp.int32)
    qb = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, nb, jnp.int32)
    qw = jax.random.randint(jax.random.PRNGKey(3), (n,), 0, ways, jnp.int32)
    if dup:
        qw = jnp.ones_like(qw)
    # half the queries hit, half miss
    miss = jax.random.randint(jax.random.PRNGKey(4), (n,), 0, 2,
                              jnp.int32) == 0
    qt = jnp.where(miss, jnp.uint32(0xDEADBEEF), tags[qb, qw])
    qk = keys[qb, qw]
    av, ah = ops.kv_probe(pack(tags), pack(keys.reshape(nb, ways * kw)),
                          pack(vals.reshape(nb, ways * vw)), qb, qt, qk,
                          ways=ways, vw=vw)
    bv, bh = ref.ref_kv_probe(tags, keys, vals, qb, qt, qk)
    np.testing.assert_array_equal(np.asarray(av), np.asarray(bv))
    np.testing.assert_array_equal(np.asarray(ah), np.asarray(bh))
    if dup:
        assert bool(bh.any())
        np.testing.assert_array_equal(np.asarray(av)[np.asarray(bh)],
                                      np.asarray(vals[qb, 1])[np.asarray(bh)])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,nq,nkv,hd,s,blk",
                         [(1, 4, 4, 64, 128, 32), (2, 8, 2, 32, 64, 16),
                          (3, 16, 4, 16, 96, 32), (1, 2, 1, 128, 256, 64)])
def test_decode_attention_sweep(dtype, b, nq, nkv, hd, s, blk):
    q = jax.random.normal(KEY, (b, nq, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd), dtype)
    for length in (1, s // 2 + 1, s):
        a = ops.decode_attention(q, k, v, length, s_blk=blk)
        o = ref.ref_decode_attn(q, k, v, length)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(a), np.asarray(o),
                                   rtol=tol, atol=tol)


def test_decode_attention_matches_model_attention():
    """The kernel agrees with the model-zoo decode attention math."""
    from repro.models import attention as mattn
    from repro.configs import get_config
    cfg = get_config("qwen2-1.5b", reduced=True)
    b, s = 2, 32
    hd, nq, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = jax.random.normal(KEY, (b, 1, nq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd))
    length = 17
    mask = (jnp.arange(s) < length)[None, None, None, None, :]
    want = mattn._sdpa(cfg, q, k, v, mask)[:, 0]
    got = ops.decode_attention(q[:, 0], k, v, length, s_blk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("length_kind",
                         ["zero", "one", "blk-1", "blk", "blk+1", "full"])
def test_decode_attention_edge_lengths(length_kind):
    """Block-boundary edges of the online-softmax scan: lengths that
    leave a block empty, fill exactly one block, or spill one row into
    the next block must all match the oracle (length 0 degrades to
    mean(v) in both — fully-masked softmax is uniform)."""
    b, nq, nkv, hd, s, blk = 2, 4, 2, 32, 96, 32
    q = jax.random.normal(KEY, (b, nq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd))
    length = {"zero": 0, "one": 1, "blk-1": blk - 1, "blk": blk,
              "blk+1": blk + 1, "full": s}[length_kind]
    a = ops.decode_attention(q, k, v, length, s_blk=blk)
    o = ref.ref_decode_attn(q, k, v, length)
    np.testing.assert_allclose(np.asarray(a), np.asarray(o),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_vmap_over_slots():
    """The decode tenant drives the kernel under ``vmap`` with a
    PER-SLOT length vector (each pool slot at its own depth).  The
    composed route must equal slot-by-slot oracle calls."""
    n, nq, nkv, hd, s, blk = 5, 4, 2, 32, 64, 16
    q = jax.random.normal(KEY, (n, nq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (n, s, nkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (n, s, nkv, hd))
    lengths = jnp.array([0, 1, blk, blk + 1, s], jnp.int32)
    got = jax.vmap(
        lambda qi, ki, vi, li: ops.decode_attention(
            qi[None], ki[None], vi[None], li, s_blk=blk)[0]
    )(q, k, v, lengths)
    for i in range(n):
        want = ref.ref_decode_attn(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                   int(lengths[i]))
        np.testing.assert_allclose(np.asarray(got[i]),
                                   np.asarray(want[0]),
                                   rtol=2e-5, atol=2e-5)
