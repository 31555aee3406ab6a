"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.extend
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


def _probe_store(nb, ways, vw, n, dup, seed=0):
    """A store's logical tables, its packed tables, and ``n`` queries:
    half hit, half miss.  ``dup`` gives every bucket's ways 0 and 1 the
    same tag with different keys (two keys whose 32-bit hashes
    collide), and aims the hits at way 1: only the key tells them
    apart."""
    from repro.kernels.kv_probe import pack
    kw = 2
    key = lambda i: jax.random.fold_in(  # noqa: E731
        jax.random.PRNGKey(i), seed) if seed else jax.random.PRNGKey(i)
    tags = jax.random.randint(key(0), (nb, ways), 1, 2**31 - 1,
                              jnp.int32).astype(jnp.uint32)
    if dup:
        tags = tags.at[:, 1].set(tags[:, 0])
    keys = jax.random.randint(key(5), (nb, ways, kw), -1000, 1000, jnp.int32)
    vals = jax.random.randint(key(1), (nb, ways, vw), 0, 1000, jnp.int32)
    qb = jax.random.randint(key(2), (n,), 0, nb, jnp.int32)
    qw = jax.random.randint(key(3), (n,), 0, ways, jnp.int32)
    if dup:
        qw = jnp.ones_like(qw)
    miss = jax.random.randint(key(4), (n,), 0, 2, jnp.int32) == 0
    qt = jnp.where(miss, jnp.uint32(0xDEADBEEF), tags[qb, qw])
    qk = keys[qb, qw]
    packed = (pack(tags), pack(keys.reshape(nb, ways * kw)),
              pack(vals.reshape(nb, ways * vw)))
    return (tags, keys, vals), packed, (qb, qt, qk)


# (stores, which operands carry them under ``jax.vmap``): "both"; the
# queries alone against one store; the stores alone for one query set;
# "nested", a vmap of a vmap over [2, stores]
_PROBE_CASES = [(8, 2, 4, 4, False, None), (64, 4, 8, 16, False, None),
                (16, 8, 2, 33, False, None), (64, 4, 8, 130, True, None),
                (16, 4, 2, 130, True, (1, "both")),
                (16, 4, 2, 130, True, (3, "both")),
                (64, 4, 8, 33, False, (3, "both")),
                (16, 4, 2, 130, True, (3, "queries")),
                (16, 4, 2, 130, True, (3, "tables")),
                (16, 4, 2, 33, True, (3, "nested"))]


@pytest.mark.parametrize(
    "nb,ways,vw,n,dup,vmap", _PROBE_CASES,
    ids=["-".join(map(str, c[:5])) + ("" if c[5] is None
                                      else f"-vmap{c[5][0]}{c[5][1]}")
         for c in _PROBE_CASES])
def test_kv_probe_sweep(nb, ways, vw, n, dup, vmap):
    """Packed-table probe vs the logical-layout oracle, one store or
    ``jax.vmap``-ed over stacked stores (each against its own oracle)."""
    probe = lambda *a: ops.kv_probe(*a, ways=ways, vw=vw)  # noqa: E731

    def check(got, logical, queries):
        want = ref.ref_kv_probe(*logical, *queries)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        return want

    if vmap is None:
        logical, packed, queries = _probe_store(nb, ways, vw, n, dup)
        bv, bh = check(probe(*packed, *queries), logical, queries)
        if dup:
            assert bool(bh.any())
            np.testing.assert_array_equal(
                np.asarray(bv)[np.asarray(bh)],
                np.asarray(logical[2][queries[0], 1])[np.asarray(bh)])
        return
    n_stores, axes = vmap
    stores = [_probe_store(nb, ways, vw, n, dup, seed=s)
              for s in range(n_stores)]
    stack = lambda part: [jnp.stack(x) for x in zip(  # noqa: E731
        *[st[part] for st in stores])]
    tables, queries = stack(1), stack(2)
    if axes == "both":
        got = jax.vmap(probe)(*tables, *queries)
        pairs = [(s, s) for s in range(n_stores)]
    elif axes == "queries":
        got = jax.vmap(probe, in_axes=(None,) * 3 + (0,) * 3)(
            *stores[0][1], *queries)
        pairs = [(0, s) for s in range(n_stores)]
    elif axes == "tables":
        got = jax.vmap(probe, in_axes=(0,) * 3 + (None,) * 3)(
            *tables, *stores[0][2])
        pairs = [(s, 0) for s in range(n_stores)]
    else:
        # outer row 1 holds the stores in reverse order
        flip = lambda xs: [jnp.stack([x, x[::-1]]) for x in xs]  # noqa: E731
        got = jax.vmap(jax.vmap(probe))(*flip(tables), *flip(queries))
        got = [g.reshape((-1,) + g.shape[2:]) for g in got]
        pairs = [(s, s) for s in range(n_stores)] + [
            (n_stores - 1 - s,) * 2 for s in range(n_stores)]
    assert all(g.shape[0] == len(pairs) for g in got)
    for i, (t, q) in enumerate(pairs):
        check((got[0][i], got[1][i]), stores[t][0], stores[q][2])



@pytest.mark.parametrize("axes", ["tables_apart", "queries_over_stacks"])
def test_kv_probe_vmap_refuses(axes):
    """The probe's batching rule folds a vmapped axis into the stores
    only where the three tables carry it together, and into the query
    sets only against one store: tables batched apart, or queries
    batched against stores stacked by an inner ``jax.vmap``, raise
    instead of broadcasting a table."""
    probe = lambda *a: ops.kv_probe(*a, ways=4, vw=2)  # noqa: E731
    stores = [_probe_store(16, 4, 2, 33, False, seed=s) for s in range(2)]
    tables, queries = [[jnp.stack(x) for x in zip(*[st[part]
                                                      for st in stores])]
                       for part in (1, 2)]
    with pytest.raises(NotImplementedError):
        if axes == "tables_apart":
            jax.vmap(probe, in_axes=(0, None, 0, 0, 0, 0))(
                tables[0], stores[0][1][1], tables[2], *queries)
        else:
            jax.vmap(jax.vmap(probe), in_axes=(None,) * 3 + (0,) * 3)(
                *tables, *[jnp.stack([q, q]) for q in queries])

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


def test_kv_probe_vmapped_get_is_one_call_over_the_stacked_tables():
    """``jax.vmap`` of ``DeviceKVS.get`` on the kernel route probes the
    stacked stores with one kernel call and touches the tables only
    there: no loop over the stores, no slice or conversion of a table
    (JAX's own batching of a kernel with a batched scalar-prefetch
    operand loops over the batch and slices every table per trip)."""
    from repro.runtime.kvs import DeviceKVS
    # tables of 256 and 512 rows; 33 queries pad to 128 rows of output
    kvs = DeviceKVS(n_buckets=8192, ways=4, key_words=2, value_words=2,
                    use_pallas=True)
    dbs = kvs.init_state_batch(3)
    keys = jnp.zeros((3, 33, 2), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.vmap(kvs.get))(dbs, keys).jaxpr
    tables = {tuple(x.shape) for x in (dbs.tags, dbs.keys, dbs.vals)}
    calls = [(e, loops) for e, loops in _eqns(jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1 and calls[0][1] == ()
    # the kernel reads the stacks themselves
    assert {tuple(v.aval.shape) for v in calls[0][0].invars} >= tables
    rows = {t[-2:] for t in tables}
    for e, _ in _eqns(jaxpr):
        if e.primitive.name in ("dynamic_slice", "bitcast_convert_type",
                                "gather", "slice", "copy"):
            assert not any(tuple(v.aval.shape[-2:]) in rows
                           for v in e.invars if hasattr(v, "aval")), e


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
