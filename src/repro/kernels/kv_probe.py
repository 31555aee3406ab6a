"""Pallas kernel: set-associative KVS bucket probe (the MICA backend, §5.6).

MICA partitions a lossy/lossless hash index across cores; Dagger steers
requests to the owning partition in hardware (``hash_steer``) and the
store itself does a bucket probe per GET.  On TPU the index lives in HBM
and each query copies in only the rows that hold its bucket, then selects
the matching way with vectorized compares (no CAM — the paper notes CAMs
are too expensive on FPGAs too, §4.7).

Table layout (shared with ``runtime.kvs``): every per-bucket record
array is packed into 128-lane rows.  A bucket's ``n`` words occupy
``stride(n)`` consecutive lanes (``n`` rounded up to a power of two), so
a bucket never straddles a row and ``128 // stride(n)`` buckets share
one row.  Tags (``n = ways``), keys (``n = ways * key_words``) and
values (``n = ways * value_words``) are each ``[rows, 128]``.  The
128-lane minor dim is the TPU's native tile width, so the arrays stay
in HBM with no relayout and each probe is one row DMA per array.

Stores come stacked, ``[S, rows, 128]`` per array, as the tenant-batched
engines hold them, and the probe reads them where they lie: one call
serves every store.  Grid: one program per tile of ``TILE_Q`` queries of
one query set, ``T * ceil(N / TILE_Q)`` in all for ``T`` sets.  Bucket
ids of every set ride in SMEM (scalar prefetch) to address the DMAs;
tags are copied in as stored (``uint32``) and reinterpreted in VMEM;
the way match (tag and key, like the jnp oracle) and the value select
run on the VPU over the whole tile.  ``probe`` is the one-store call;
under ``jax.vmap`` (nested too) it becomes one stacked call, never a
loop over stores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE_Q = 128


def stride(n: int) -> int:
    """Lanes one bucket's ``n`` words occupy in a packed row."""
    s = 1 << max(n - 1, 0).bit_length()
    if s > LANES:
        raise ValueError(f"{n} words per bucket exceed one {LANES}-lane row")
    return s


def packed_rows(n_buckets: int, n: int) -> int:
    """Rows of the packed array holding ``n`` words for every bucket."""
    per_row = LANES // stride(n)
    return -(-n_buckets // per_row)


def locate(bucket, n: int):
    """(row, first lane) of ``bucket``'s ``n``-word record."""
    s = stride(n)
    per_row = LANES // s
    return bucket // per_row, (bucket % per_row) * s


def pack(records):
    """Per-bucket records ``[NB, n]`` -> the packed ``[rows, 128]`` array
    (bucket ``b`` at ``locate(b, n)``)."""
    nb, n = records.shape
    s = stride(n)
    per_row = LANES // s
    out = jnp.pad(records, ((0, (-nb) % per_row), (0, s - n)))
    return out.reshape(-1, LANES)


def _kernel(bucket_sm, tags_hbm, keys_hbm, vals_hbm, bucket_ref, qtag_ref,
            qkey_ref, out_val, out_hit, tag_rows, key_rows, val_rows, sem,
            *, ways: int, kw: int, vw: int, per_store: int):
    tile = pl.program_id(0)
    base = tile * TILE_Q
    arrays = ((tags_hbm, tag_rows, ways), (keys_hbm, key_rows, ways * kw),
              (vals_hbm, val_rows, ways * vw))
    store = tile // per_store

    def copies(i):
        b = bucket_sm[base + i]
        return [pltpu.make_async_copy(
                    hbm.at[store, pl.ds(b // (LANES // stride(n)), 1)],
                    rows.at[pl.ds(i, 1)], sem.at[k])
                for k, (hbm, rows, n) in enumerate(arrays)]

    def start(i, c):
        for cp in copies(i):
            cp.start()
        return c

    def wait(i, c):
        for cp in copies(i):
            cp.wait()
        return c

    jax.lax.fori_loop(0, TILE_Q, start, 0)
    jax.lax.fori_loop(0, TILE_Q, wait, 0)

    b = bucket_ref[...]                                   # [TILE_Q, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (TILE_Q, LANES), 1)
    tags = jax.lax.bitcast_convert_type(tag_rows[...], jnp.int32)

    def word(rows, n, offset):
        """Lane ``offset`` of each query's bucket record -> [TILE_Q, 1]."""
        at = (b % (LANES // stride(n))) * stride(n) + offset
        return jnp.sum(jnp.where(lane == at, rows, 0), axis=1,
                       keepdims=True)

    # first way whose tag AND key match (the jnp oracle's rule)
    way = jnp.full((TILE_Q, 1), ways, jnp.int32)
    for w in reversed(range(ways)):
        m = word(tags, ways, w) == qtag_ref[...]
        for t in range(kw):
            m &= word(key_rows[...], ways * kw, w * kw + t) \
                == qkey_ref[:, t:t + 1]
        way = jnp.where(m, w, way)
    hit = way < ways
    out = jnp.zeros((TILE_Q, LANES), jnp.int32)
    for j in range(vw):
        out = jnp.where(lane == j,
                        word(val_rows[...], ways * vw, way * vw + j), out)
    out_val[...] = jnp.where(hit, out, 0)
    out_hit[...] = hit.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("ways", "vw", "interpret"))
def kv_probe(tags, keys, values, q_bucket, q_tag, q_key, *, ways: int,
             vw: int, interpret: bool):
    """Probe ``S`` stores with ``T`` query sets at once.  tags: packed
    [S, rows_t, 128] uint32 (``ways`` per bucket); keys: packed [S,
    rows_k, 128] int32 (``ways * kw`` per bucket); values: packed [S,
    rows_v, 128] int32 (``ways * vw`` per bucket); q_bucket [T, N] int32,
    q_tag [T, N] uint32, q_key [T, N, kw] int32.  ``S`` divides ``T``,
    and the sets come grouped by store: sets ``j * T / S`` to ``(j + 1)
    * T / S - 1`` probe store ``j``.  -> (val [T, N, vw], hit [T, N]
    bool): the value of the first way whose tag and key both match."""
    t, n, kw = q_key.shape
    s = tags.shape[0]
    if keys.shape[0] != s or values.shape[0] != s or t % s:
        raise ValueError(f"tables of {tags.shape[0]}, {keys.shape[0]} and "
                         f"{values.shape[0]} stores for {t} query sets")
    pad = (-n) % TILE_Q
    tiles = (n + pad) // TILE_Q
    flat = lambda x: jnp.pad(  # noqa: E731
        x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)).reshape(
            (t * (n + pad),) + x.shape[2:])
    qb = flat(q_bucket.astype(jnp.int32))
    qt = flat(jax.lax.bitcast_convert_type(q_tag, jnp.int32))
    qk = flat(q_key.astype(jnp.int32))
    col = pl.BlockSpec((TILE_Q, 1), lambda i, bsm: (i, 0))
    row = pl.BlockSpec((TILE_Q, LANES), lambda i, bsm: (i, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    val, hit = pl.pallas_call(
        functools.partial(_kernel, ways=ways, kw=kw, vw=vw,
                          per_store=tiles * (t // s)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t * tiles,),
            in_specs=[hbm, hbm, hbm, col, col,
                      pl.BlockSpec((TILE_Q, kw), lambda i, bsm: (i, 0))],
            out_specs=[row, col],
            scratch_shapes=[pltpu.VMEM((TILE_Q, LANES), jnp.uint32)]
            + [pltpu.VMEM((TILE_Q, LANES), jnp.int32)] * 2
            + [pltpu.SemaphoreType.DMA((3,))]),
        out_shape=[jax.ShapeDtypeStruct((t * (n + pad), LANES), jnp.int32),
                   jax.ShapeDtypeStruct((t * (n + pad), 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(qb, tags, keys, values, qb[:, None], qt[:, None], qk)
    val = val.reshape(t, n + pad, LANES)[:, :n, :vw]
    return val, hit.reshape(t, n + pad)[:, :n] != 0


def probe(tags, keys, values, q_bucket, q_tag, q_key, *, ways: int,
          vw: int, interpret: bool):
    """Probe one store: tables packed [rows, 128] (``kv_probe``'s dtypes
    and layout), q_bucket [N], q_tag [N], q_key [N, kw] -> (val [N, vw],
    hit [N] bool).  ``jax.vmap`` of it, nested or not, is one
    ``kv_probe`` call over the stacked tables."""
    val, hit = _stacked(ways, vw, interpret)(
        tags[None], keys[None], values[None], q_bucket[None], q_tag[None],
        q_key[None])
    return val[0], hit[0]


def _stacked(ways: int, vw: int, interpret: bool):
    """``kv_probe`` at fixed widths, batched by folding a vmapped axis
    into the query sets and, where the tables carry it, the stores.  The
    kernel's own batching rule would loop over the batch and slice every
    table once per trip."""

    @jax.custom_batching.custom_vmap
    def call(tags, keys, values, q_bucket, q_tag, q_key):
        return kv_probe(tags, keys, values, q_bucket, q_tag, q_key,
                        ways=ways, vw=vw, interpret=interpret)

    @call.def_vmap
    def rule(size, batched, tags, keys, values, q_bucket, q_tag, q_key):
        # batch b's query set j becomes set b * T + j, after its stores
        # (batched tables) or against the one store (unbatched)
        if len(set(batched[:3])) > 1:
            raise NotImplementedError("tags, keys and values batched apart")
        tables = (tags, keys, values)
        if batched[0]:
            tables = [x.reshape((-1,) + x.shape[2:]) for x in tables]
        elif tags.shape[0] > 1:
            raise NotImplementedError("queries batched against stacked "
                                      "stores that are not")
        t, n = q_key.shape[-3:-1]
        qs = [(q if b else jnp.broadcast_to(q, (size,) + q.shape)).reshape(
                  (size * t,) + q.shape[1 + int(b):])
              for q, b in zip((q_bucket, q_tag, q_key), batched[3:])]
        val, hit = call(*tables, *qs)
        return (val.reshape(size, t, n, vw),
                hit.reshape(size, t, n)), (True, True)

    return call
