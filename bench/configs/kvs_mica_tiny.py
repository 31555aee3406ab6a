"""kvs_mica_tiny: the MICA KVS port of the paper on MICA's "tiny" data.

Sizes are in ``kvs_mica_tiny.json``: 64 partitions, each one NIC lane
(a client/server pair on ``TenantEngine``) whose server handler is the
program's ``DeviceKVS`` (2^20 buckets x 4 ways, 8-byte keys and values)
holding 3 Mi keys.  The tables are made on the device from the seed and
bulk-loaded through ``DeviceKVS.set`` during set-up, coldest keys first.

Requests: 5 % SET, 95 % GET, keys Zipf(0.99) over the partition's own
keys.  Every draw is a pure integer hash of (lane key, rpc id), so the
reference recomputes each request from the rpc id a reply names.  The
Zipf draw inverts a 65,537-entry table of the distribution's quantiles
(integer ranks, built here in float64) and spreads uniformly inside a
quantile's ranks, so the device and numpy draw the same key.

Key ``k`` of a lane is the words ``[k, h(lane, k)]``; its value at
version ``v`` is ``[v, h(lane ^ v, k)]``.  The bulk load writes version
0 and a SET with rpc id ``r`` writes version ``r + 1``.  So a GET's
answer names the version it returns, and the reference can tell whether
that version belongs to this key, was written by the load or by a SET
of the same key, and is not older than a SET that was acknowledged
before the GET was issued.
"""
from __future__ import annotations

import time

import numpy as np

from bench import loadgen as blg
from bench import wire
from bench.rig import LoopbackRig, fabrics

KW = VW = 2
MASK31 = 0x7FFFFFFF
SALT_KEYWORD = 11
SALT_VALUE = 12
TABLE_BITS = 16


def zipf_table(n_keys: int, s: float) -> np.ndarray:
    """Rank at each 1/65536 quantile of Zipf(s) over ``n_keys`` ranks
    (65,537 int32 entries, the last = n_keys - 1)."""
    p = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** s
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    q = np.arange((1 << TABLE_BITS) + 1) / float(1 << TABLE_BITS)
    t = np.minimum(np.searchsorted(cdf, q, side="left"), n_keys - 1)
    t[-1] = n_keys - 1
    if np.diff(t).max() >= 1 << TABLE_BITS:
        raise ValueError("a quantile spans too many ranks for 32-bit math")
    return t.astype(np.int32)


def _draw(u, table_lo, table_hi):
    lo = u & 0xFFFF
    return table_lo + ((lo * (table_hi - table_lo + 1)) >> 16)


def key_index(table, key, rpc_id):
    """The Zipf rank of request ``rpc_id`` (jnp, uint32 math)."""
    import jax.numpy as jnp
    u = blg.hash32(key, rpc_id, blg.SALT_KEY)
    hi = (u >> 16).astype(jnp.int32)
    a = table[hi].astype(jnp.uint32)
    b = table[hi + 1].astype(jnp.uint32)
    return _draw(u, a, b).astype(jnp.int32)


def key_index_np(table, key, rpc_id):
    u = blg.hash32_np(key, rpc_id, blg.SALT_KEY)
    hi = (u >> np.uint32(16)).astype(np.int64)
    a = table[hi].astype(np.uint32)
    b = table[hi + 1].astype(np.uint32)
    with np.errstate(over="ignore"):
        return _draw(u, a, b).astype(np.int64)


def _set_threshold(share: float) -> int:
    return int(round(share * (1 << 16)))


def is_set_np(key, rpc_id, thr: int):
    return (blg.hash32_np(key, rpc_id, blg.SALT_OP) & np.uint32(0xFFFF)) \
        < np.uint32(thr)


def _words(key, k, ver, xp, h):
    """(key words [.., 2], value words [.., 2]) of key ``k`` at version
    ``ver``; ``xp``/``h`` are numpy or jnp and their hash."""
    kw1 = h(key, k, SALT_KEYWORD) & xp.uint32(MASK31)
    ver_u = xp.asarray(ver).astype(xp.uint32)
    vw1 = h(xp.asarray(key).astype(xp.uint32) ^ ver_u, k,
            SALT_VALUE) & xp.uint32(MASK31)
    kw = xp.stack([xp.asarray(k).astype(xp.int32) + xp.zeros_like(
        kw1.astype(xp.int32)), kw1.astype(xp.int32)], axis=-1)
    vw = xp.stack([ver_u.astype(xp.int32) + xp.zeros_like(
        vw1.astype(xp.int32)), vw1.astype(xp.int32)], axis=-1)
    return kw, vw


def requests_fn(table, pw: int, set_share: float):
    import jax.numpy as jnp
    thr = _set_threshold(set_share)

    def requests(key, rpc_id):
        k = key_index(table, key, rpc_id)
        is_set = (blg.hash32(key, rpc_id, blg.SALT_OP)
                  & jnp.uint32(0xFFFF)) < jnp.uint32(thr)
        kw, vw = _words(key, k, rpc_id + 1, jnp, blg.hash32)
        pay = jnp.zeros(rpc_id.shape + (pw,), jnp.int32)
        pay = pay.at[:, 0:KW].set(kw)
        pay = pay.at[:, KW:KW + VW].set(jnp.where(is_set[:, None], vw, 0))
        return is_set.astype(jnp.int32), pay

    return requests


def store_handler(kvs, out_fault=None, lose_sets=False):
    """The program's KVS handler over reply records (fn 0 = GET, 1 =
    SET; reply payload ``[status, value...]``).  ``lose_sets`` plants a
    fault: SETs are acknowledged with their value but never stored."""
    h = kvs.make_handler()

    def handler(recs, valid, db):
        store = valid & (recs["fn_id"] == 0) if lose_sets else valid
        pay, db = h(recs["payload"], store, db, recs["fn_id"])
        if out_fault is not None:
            pay = out_fault(recs, pay)
        out = dict(recs)
        out["payload"] = pay
        return out, db

    return handler


def _control_fault(recs, pay):
    """The control: one GET in 64 answers with its value's second word
    off by one, which breaks the guarantee that a GET returns a value
    written for its key."""
    import jax.numpy as jnp
    bad = ((recs["rpc_id"] % 64 == 0) & (recs["fn_id"] == 0))
    return pay.at[:, 2].set(jnp.where(bad, pay[:, 2] ^ 1, pay[:, 2]))


def _gets_miss(recs, pay):
    """A planted fault: every GET answers "miss"."""
    import jax.numpy as jnp
    return jnp.where((recs["fn_id"] == 0)[:, None], 0, pay)


def control_kw(name: str = "control") -> dict:
    """Builder arguments of the control (``control``) and of the planted
    faults that read the upper ends of ``get_miss_pct`` (``gets_miss``)
    and ``stale_gets`` (``sets_lost``: SETs acknowledged, never
    stored)."""
    if name == "sets_lost":
        return {"lose_sets": True}
    return {"out_fault": {"control": _control_fault,
                          "gets_miss": _gets_miss}[name]}


def load_tables(kvs, keys, n_keys: int, batch: int):
    """Every lane's store, made on the device and bulk-loaded through
    ``DeviceKVS.set`` in batches, coldest keys first."""
    import jax
    import jax.numpy as jnp

    def one_lane(db, key, start):
        idx = start + jnp.arange(batch, dtype=jnp.int32)
        k = n_keys - 1 - idx
        kw, vw = _words(key, k, jnp.zeros_like(k), jnp, blg.hash32)
        return kvs.set(db, kw, vw, idx < n_keys)

    make = jax.jit(jax.vmap(lambda _: kvs.init_state()))
    load = jax.jit(jax.vmap(one_lane, in_axes=(0, 0, None)),
                   donate_argnums=(0,))
    db = make(jnp.arange(len(keys)))
    lane_keys = jnp.asarray(keys)
    for start in range(0, n_keys, batch):
        db = load(db, lane_keys, jnp.int32(start))
    jax.block_until_ready(db)
    return db


def build(sizes: dict, traffic: dict, seed: int, out_fault=None,
          lose_sets: bool = False, abstract: bool = False):
    import jax.numpy as jnp

    from repro.runtime.kvs import DeviceKVS
    client, server = fabrics(sizes)
    pw = sizes["slot_bytes"] // 4 - wire.HEADER_WORDS
    n_lanes, n_keys = sizes["n_partitions"], sizes["keys_per_partition"]
    kvs = DeviceKVS(n_buckets=sizes["n_buckets"], ways=sizes["ways"],
                    key_words=KW, value_words=VW,
                    use_pallas=sizes["store_kernel"])
    table = zipf_table(n_keys, traffic["zipf"])
    keys = blg.lane_keys(seed, n_lanes)
    t = time.perf_counter()
    if abstract:
        import jax
        db = jax.eval_shape(jax.vmap(lambda _: kvs.init_state()),
                            jnp.arange(n_lanes))
    else:
        db = load_tables(kvs, keys, n_keys, sizes["load_batch"])
    load_s = time.perf_counter() - t
    rig = LoopbackRig(abstract=abstract,
        client=client, server=server,
        handler=store_handler(kvs, out_fault, lose_sets), n_lanes=n_lanes,
        requests=requests_fn(jnp.asarray(table), pw, traffic["set_share"]),
        rate=traffic["rate_per_lane"],
        steps_per_window=traffic["steps_per_window"],
        n_bins=traffic["lat_bins"], seed=seed, hstate=db,
        request_kinds=lambda lk, rid: is_set_np(
            lk, rid, _set_threshold(traffic["set_share"])).astype(int))
    rig.setup_detail = {"table_load_s": load_s}
    return rig


# ------------------------------------------------------------- reference
def _steps_of(step_offered, lane, rpc):
    """The step that injected each (lane, rpc id): ids are handed out in
    arrival order, ``step_offered[l, s]`` of them in step ``s``."""
    cum = np.cumsum(step_offered, axis=1)
    out = np.empty(len(lane), np.int64)
    for l in np.unique(lane):
        m = lane == l
        out[m] = np.searchsorted(cum[l], rpc[m], side="right")
    return out


class SetLog:
    """Every SET the lanes issued, by the reference's draw: lane, key,
    the step that injected it, sorted by (lane, key, step)."""

    def __init__(self, table, thr, ukeys, next_rpc, step_offered):
        self.n_keys = int(table[-1]) + 1
        next_rpc = np.asarray(next_rpc, np.int64)
        lane = np.repeat(np.arange(len(next_rpc)), next_rpc)
        rpc = np.concatenate([np.arange(n) for n in next_rpc])
        lk = ukeys[lane]
        is_set = is_set_np(lk, rpc.astype(np.uint32), thr)
        lane, rpc = lane[is_set], rpc[is_set]
        key = key_index_np(table, lk[is_set], rpc.astype(np.uint32))
        step = _steps_of(step_offered, lane, rpc)
        self.span = int(step_offered.shape[1]) + 2
        self.comp = np.sort((lane * self.n_keys + key) * self.span + step)

    def last_before(self, lane, key, step):
        """The injection step of the last SET of (lane, key) injected at
        or before ``step`` (-1 where there is none)."""
        gid = lane * self.n_keys + key
        if not self.comp.size:
            return np.full(np.shape(gid), -1, np.int64)
        t = np.clip(step, -1, self.span - 2)
        i = np.searchsorted(self.comp, gid * self.span + t, side="right") - 1
        hit = (t >= 0) & (i >= 0) & (self.comp[np.maximum(i, 0)]
                                     // self.span == gid)
        return np.where(hit, self.comp[np.maximum(i, 0)] % self.span, -1)


def max_residency(hist):
    """The largest residency in steps any RPC of the run had (its bin),
    or None where some landed in the overflow bin."""
    hist = np.asarray(hist)
    if hist[-1]:
        return None
    nz = np.nonzero(hist)[0]
    return int(nz[-1]) if nz.size else 0


def check_answers(sizes: dict, traffic: dict, keys, ledger, samples):
    """Every reply in the kept samples against the reference.  Returns
    (replies checked, replies that say the wrong thing, GET hits that
    are stale, GETs, GET misses).

    Timing comes from the reference's own draw of the arrivals (the step
    that injected each rpc id) and the run's largest residency ``R``: an
    RPC injected at step ``s`` was served at a step in ``[s, s + R - 1]``.
    So a SET injected ``R`` or more steps before a GET was stored before
    the GET was served, and a SET injected ``R`` or more steps after
    another was stored after it; a hit may return the last such SET of
    its key or one that may have been stored after it, and nothing
    older.  A hit may not return a SET injected after the GET could have
    been served.  Lanes whose generator dropped requests (which ids it
    dropped is not known) are held to the key and the later bound only."""
    table = zipf_table(sizes["keys_per_partition"], traffic["zipf"])
    thr = _set_threshold(traffic["set_share"])
    ukeys = np.asarray(keys).view(np.uint32)
    next_rpc = np.asarray(ledger["next_rpc"], np.int64)
    step_offered = ledger["step_offered"]
    r_max = max_residency(ledger["hist"])
    r_gap = step_offered.shape[1] if r_max is None else r_max
    log = SetLog(table, thr, ukeys, next_rpc, step_offered)
    judged = np.asarray(ledger["lane_dropped"]) == 0
    seen, bad, stale, gets, misses = set(), 0, 0, 0, 0
    for ring, head, tail in samples:
        lane, slots = wire.drained(ring, head, tail)
        r = wire.decode(slots)
        rpc = r["rpc_id"].astype(np.int64)
        fresh = np.array([(int(a), int(b)) not in seen
                          for a, b in zip(lane, rpc)], bool)
        seen.update(zip(lane.tolist(), rpc.tolist()))
        lane, rpc = lane[fresh], rpc[fresh]
        r = {k: v[fresh] for k, v in r.items()}
        lk = ukeys[lane]
        known = (rpc >= 0) & (rpc < next_rpc[lane])
        rid = np.where(known, rpc, 0).astype(np.uint32)
        k = key_index_np(table, lk, rid)
        is_set = is_set_np(lk, rid, thr)
        s_req = _steps_of(step_offered, lane, rid.astype(np.int64))
        status = r["payload"][:, 0]
        v0 = r["payload"][:, 1].astype(np.int64)
        v1 = r["payload"][:, 2].astype(np.int64)
        # SET: stored, and the value it wrote comes back
        _, want = _words(lk, k, rid + np.uint32(1), np, blg.hash32_np)
        set_ok = (status == 1) & (v0 == want[:, 0]) & (v1 == want[:, 1])
        # GET hit: a version of this key, written by the load (0) or by
        # a SET of the same key injected before the GET was served
        src = np.clip(v0 - 1, 0, None)
        src_known = (v0 >= 1) & (src < next_rpc[lane])
        src = np.where(src_known, src, 0)
        s_src = _steps_of(step_offered, lane, src)
        _, got = _words(lk, k, v0.astype(np.uint32), np, blg.hash32_np)
        bound = v1 == got[:, 1]
        by_set = (src_known & is_set_np(lk, src.astype(np.uint32), thr)
                  & (key_index_np(table, lk, src.astype(np.uint32)) == k)
                  & (s_src <= s_req + r_gap - 1))
        hit_ok = (status == 1) & bound & ((v0 == 0) | by_set)
        miss = (status == 0) & (v0 == 0) & (v1 == 0)
        ok = (known & (r["conn_id"] == LoopbackRig.conn)
              & ((r["flags"] & wire.FLAG_RESPONSE) != 0)
              & (r["fn_id"] == is_set.astype(np.int64))
              & np.where(is_set, set_ok, hit_ok | miss))
        # freshness: the last SET of the key stored before the GET was
        # served, and the hit not older than it
        y = log.last_before(lane, k, s_req - r_gap)
        old = (y >= 0) & ((v0 == 0) | (s_src <= y - r_gap))
        if r_max is None:        # no bound on residency: nothing judged
            old = np.ones_like(old)
        is_stale = ok & ~is_set & (status == 1) & judged[lane] & old
        bad += int((~ok).sum())
        stale += int(is_stale.sum())
        gets += int((known & ~is_set).sum())
        misses += int((ok & ~is_set & miss).sum())
    return len(seen), bad, stale, gets, misses


def check(sizes: dict, traffic: dict, ledger: dict, keys, samples) -> dict:
    from bench.rig import ledger_checks
    checked, bad, stale, gets, misses = check_answers(
        sizes, traffic, keys, ledger, samples)
    out = ledger_checks(ledger)
    out["bad_answers"] = {"value": bad, "limit": 0}
    out["stale_gets"] = {"value": stale, "limit": 0}
    out["get_miss_pct"] = {"value": 100.0 * misses / max(gets, 1),
                           "limit": traffic["get_miss_pct_limit"]}
    out["answers_checked"] = {"value": checked,
                              "at_least": traffic["min_answers_checked"]}
    return out
