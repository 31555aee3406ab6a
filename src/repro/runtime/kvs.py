"""MICA-style in-device key-value store (paper §5.6 backend).

A set-associative, lossy hash index: [n_buckets, ways] tag array + full
key/value stores, batched vectorized GET/SET, eviction by hash-picked way
(MICA's lossy mode).  Keys are steered to partitions (flows) by the
object-level load balancer *before* reaching the store — the Dagger NIC's
job — so each lane only ever touches its own partition (MICA's
core-partitioned design; here lane-partitioned).

The GET probe has a Pallas kernel (``repro.kernels.kv_probe``); the jnp
path below is its oracle.  Both read the same packed table layout.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import telemetry as tlm
from repro.core.load_balancer import fnv1a_words
from repro.kernels import kv_probe as kvp


@jax.tree_util.register_dataclass
@dataclass
class KVSState:
    """The index, packed into 128-lane rows (``kernels.kv_probe`` layout:
    bucket ``b``'s record of ``n`` words sits at ``kv_probe.locate(b,
    n)``) so the table stays in HBM in the TPU's native tiling."""
    tags: jnp.ndarray        # [rows, 128] uint32, WAYS per bucket, 0 = empty
    keys: jnp.ndarray        # [rows, 128] int32, WAYS * KW per bucket
    vals: jnp.ndarray        # [rows, 128] int32, WAYS * VW per bucket
    n_set: jnp.ndarray
    n_get: jnp.ndarray
    n_hit: jnp.ndarray
    n_evict: jnp.ndarray     # acknowledged keys the lossy index dropped


class DeviceKVS:
    def __init__(self, n_buckets: int = 1024, ways: int = 4,
                 key_words: int = 2, value_words: int = 8,
                 use_pallas: bool = False):
        self.nb = n_buckets
        self.ways = ways
        self.kw = key_words
        self.vw = value_words
        self.use_pallas = use_pallas
        for n in (ways, ways * key_words, ways * value_words):
            kvp.stride(n)                       # fits one 128-lane row

    def init_state(self) -> KVSState:
        z = jnp.int32(0)
        rows = lambda n: (kvp.packed_rows(self.nb, n), kvp.LANES)
        return KVSState(
            tags=jnp.zeros(rows(self.ways), jnp.uint32),
            keys=jnp.zeros(rows(self.ways * self.kw), jnp.int32),
            vals=jnp.zeros(rows(self.ways * self.vw), jnp.int32),
            n_set=z, n_get=z, n_hit=z, n_evict=z)

    def init_state_batch(self, n_tenants: int) -> KVSState:
        """Stacked per-tenant stores (leading tenant axis) for the
        tenant-batched engine — each tenant owns an isolated partition
        set, mirroring MICA's per-core partitions across NIC slots."""
        from repro.core.engine import stack_states
        return stack_states([self.init_state() for _ in range(n_tenants)])

    # ------------------------------------------------------------------
    def _bucket_tag(self, key_words):
        h = fnv1a_words(key_words, self.kw)
        bucket = (h % jnp.uint32(self.nb)).astype(jnp.int32)
        tag = (h | jnp.uint32(1))                   # nonzero tag
        return bucket, tag, h

    def _cells(self, bucket, n, way=None, width=None):
        """(row [N, 1], lane [N, width]) of the ``n``-word bucket record
        (or of way ``way``'s ``width`` words inside it)."""
        row, lane0 = kvp.locate(bucket, n)
        if way is not None:
            lane0 = lane0 + way * width
        width = n if width is None else width
        return row[:, None], lane0[:, None] + jnp.arange(width)

    def _read(self, arr, bucket, n):
        return arr[self._cells(bucket, n)]

    @jax.named_scope(tlm.SCOPE_KVS_GET)
    def get(self, st: KVSState, key_words, valid=None):
        """key_words: [N, KW] -> (values [N, VW], hit [N])."""
        n = key_words.shape[0]
        valid = jnp.ones((n,), bool) if valid is None else valid
        bucket, tag, _ = self._bucket_tag(key_words)
        if self.use_pallas:
            from repro.kernels import ops
            val, hit = ops.kv_probe(st.tags, st.keys, st.vals, bucket, tag,
                                    key_words, ways=self.ways, vw=self.vw)
            hit = hit & valid
        else:
            match, way = self._match_way(st, bucket, tag, key_words)
            hit = jnp.any(match, axis=1) & valid
            val = st.vals[self._cells(bucket, self.ways * self.vw, way,
                                      self.vw)]
        val = jnp.where(hit[:, None], val, 0)
        st2 = _bump(st, n_get=jnp.sum(valid.astype(jnp.int32)),
                    n_hit=jnp.sum(hit.astype(jnp.int32)))
        return st2, val, hit

    @jax.named_scope(tlm.SCOPE_KVS_SET)
    def set(self, st: KVSState, key_words, val_words, valid=None):
        """Insert/update [N] records.

        Rows are matched against the store as it was before the batch;
        a key SET twice in one batch keeps its last value, and new keys
        of one bucket take its empty ways in turn before evicting.  Each
        slot is written by at most one row, so a key's tag, key and
        value always come from one row.  ``n_evict`` counts every key
        the index lost: a stored key overwritten by a different key, and
        a new key whose write lost its slot to another row.
        """
        n = key_words.shape[0]
        ways = self.ways
        idx = jnp.arange(n, dtype=jnp.int32)
        valid = jnp.ones((n,), bool) if valid is None else valid
        bucket, tag, h = self._bucket_tag(key_words)
        bucket = jnp.where(valid, bucket, self.nb)  # invalid rows sort last
        # batch order -> (bucket, key, batch index) order: equal keys are
        # adjacent, and only the last of them is live
        cols = [idx] + [key_words[:, k] for k in range(self.kw - 1, -1, -1)]
        order = jnp.lexsort(tuple(cols) + (bucket,))
        bs, ks = bucket[order], key_words[order]
        nxt_same = jnp.concatenate(
            [(bs[1:] == bs[:-1]) & jnp.all(ks[1:] == ks[:-1], axis=1),
             jnp.zeros((1,), bool)])
        live = (bs < self.nb) & ~nxt_same
        tag_s, h_s = tag[order], h[order]

        match, way_m = self._match_way(st, bs, tag_s, ks)
        exists = jnp.any(match, axis=1)
        occupied = self._read(st.tags, bs, ways) != 0          # [N, WAYS]
        n_empty = jnp.sum(~occupied, axis=1)
        # k-th live new key of a bucket takes the bucket's k-th empty way
        new = live & ~exists
        seg_start = jnp.concatenate([jnp.ones((1,), bool),
                                     bs[1:] != bs[:-1]])
        c_new = jnp.cumsum(new.astype(jnp.int32))
        first = jax.lax.cummax(jnp.where(seg_start, idx, 0))
        rank = c_new - new.astype(jnp.int32) - (
            c_new[first] - new[first].astype(jnp.int32))
        empty_rank = jnp.cumsum((~occupied).astype(jnp.int32), axis=1) - 1
        way_e = jnp.argmax(~occupied & (empty_rank == rank[:, None]),
                           axis=1)
        way_v = ((h_s >> jnp.uint32(16)) % jnp.uint32(ways)).astype(
            jnp.int32)
        way = jnp.where(exists, way_m, jnp.where(
            rank < n_empty, way_e, (way_v + rank - n_empty) % ways))
        # one writer per slot: among live rows aiming at the same
        # (bucket, way), the last in this order wins
        slot = jnp.where(live, bs * ways + way, self.nb * ways)
        o2 = jnp.lexsort((idx, slot))
        s2 = slot[o2]
        lose2 = jnp.concatenate([s2[1:] == s2[:-1], jnp.zeros((1,), bool)])
        win = live & ~jnp.zeros((n,), bool).at[o2].set(lose2)
        was_full = jnp.take_along_axis(occupied, way[:, None], 1)[:, 0]
        evictions = (win & new & was_full) | (live & ~win & new)

        def put(arr, n_words, width, vals):
            r, lane = self._cells(bs, n_words, way, width)
            r = jnp.where(win[:, None], r, arr.shape[0])   # OOB -> drop
            return arr.at[r, lane].set(vals, mode="drop")

        tags = put(st.tags, ways, 1, tag_s[:, None])
        keys = put(st.keys, ways * self.kw, self.kw, ks)
        vals = put(st.vals, ways * self.vw, self.vw, val_words[order])
        st2 = KVSState(tags, keys, vals, st.n_set, st.n_get, st.n_hit,
                       st.n_evict)
        return _bump(st2, n_set=jnp.sum(valid.astype(jnp.int32)),
                     n_evict=jnp.sum(evictions.astype(jnp.int32)))

    def _match_way(self, st, bucket, tag, key_words):
        bt = self._read(st.tags, bucket, self.ways)          # [N, WAYS]
        bk = self._read(st.keys, bucket, self.ways * self.kw).reshape(
            -1, self.ways, self.kw)                          # [N, WAYS, KW]
        match = (bt == tag[:, None]) & jnp.all(
            bk == key_words[:, None, :], axis=-1)
        return match, jnp.argmax(match, axis=1)

    # ------------------------------------------------- fabric integration
    def make_handler(self):
        """Returns handler(payload [N,W], valid [N], state) for the fabric.

        fn_id 0 = GET (payload: key), 1 = SET (payload: key ++ value).
        Response payload: [status, value...] (status 1 = hit/stored)."""
        kw, vw = self.kw, self.vw

        def handler(payload, valid, st, fn_id):
            key = payload[:, :kw]
            val_in = payload[:, kw:kw + vw]
            is_set = fn_id == 1
            st = self.set(st, key, val_in, valid & is_set)
            st, val, hit = self.get(st, key, valid & ~is_set)
            status = jnp.where(is_set, 1, hit.astype(jnp.int32))
            out = jnp.zeros_like(payload)
            out = out.at[:, 0].set(status)
            out = out.at[:, 1:1 + vw].set(jnp.where(is_set[:, None],
                                                    val_in, val))
            return out, st

        return handler

    def make_engine(self, client, server):
        """Scan-fused loopback engine serving this store (paper §5.6).

        The KVSState is the engine's handler state: GET/SET handling,
        steering and the store update all stay inside the fused device
        step, and the steady-state loop runs K iterations per host
        dispatch (``engine.run_steps(cst, sst, k, hstate=db)``).

        Per-op latency telemetry rides the same carry: pass
        ``tel=telemetry.create()`` (clients stamp request records with
        the step counter via ``serdes.make_records(...,
        timestamp=...)``) and the returned Telemetry histogram holds
        every GET/SET's fabric residency in steps — the paper's
        Fig. 12 µs medians come from this histogram times the measured
        step cost, not from a host wall clock.
        """
        from repro.core.engine import LoopbackEngine
        return LoopbackEngine(client, server, self._record_handler(),
                              stateful=True)

    def make_tenant_engine(self, client, server):
        """Tenant-batched KVS engine (one NIC slot + store per tenant).

        ``engine.run_steps(csts, ssts, k, hstate=dbs)`` drives N
        independent client/server/store triples in one dispatch;
        ``dbs`` is ``init_state_batch(n)`` (or any stacked KVSState).
        Bit-identical to N separate ``make_engine`` runs.
        """
        from repro.core.engine import TenantEngine
        return TenantEngine(client, server, self._record_handler(),
                            stateful=True)

    def make_sharded_tenant_engine(self, client, server, mesh=None,
                                   axis: str = "tenant"):
        """Mesh-sharded KVS engine: each device owns whole NIC slots —
        client/server pairs AND their tenant stores — and runs the fused
        GET/SET loop device-local (MICA's core partitioning lifted to the
        mesh).  Call ``engine.shard_states(csts, ssts, dbs)`` (placement
        via ``parallel.sharding.legalize_specs``) before the first
        ``run_steps``; results are bit-identical to
        ``make_tenant_engine`` on any mesh shape.

        The returned engine also exposes
        ``run_until_global(csts, ssts, global_target, max_steps,
        hstate=dbs)``: a fleet-wide completion sweep whose while
        predicate is a ``psum`` over per-device done counters, so
        devices whose stores drained early keep pumping until the whole
        fleet has served ``global_target`` GET/SET RPCs — returns
        ``(csts, ssts, dbs, n_done [T], dev_steps [D])``; with
        ``tel=telemetry.create_batch(T)`` it additionally returns the
        per-tenant Telemetry and the psum-merged fleet-wide latency
        histogram (bit-identical to the single-device run on any mesh
        shape).
        """
        from repro.core.engine import ShardedTenantEngine
        return ShardedTenantEngine(client, server, self._record_handler(),
                                   mesh=mesh, axis=axis, stateful=True)

    def _record_handler(self):
        h = self.make_handler()

        def handler(recs, valid, db):
            pay, db = h(recs["payload"], valid, db, recs["fn_id"])
            out = dict(recs)
            out["payload"] = pay
            return out, db

        return handler


def _bump(st: KVSState, **kw):
    import dataclasses
    return dataclasses.replace(
        st, **{k: getattr(st, k) + v for k, v in kw.items()})
