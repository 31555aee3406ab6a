"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Each ``<name>`` in kernels/ has a matching ``ref_<name>`` here; tests sweep
shapes/dtypes and assert_allclose kernel-vs-oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.load_balancer import fnv1a_words


def ref_ring_copy(table, refs):
    """Oracle for ``kernels/ring_copy.ring_gather`` (the CCI-P transmit
    engine's batched slot copy): table [R, W] int32; refs [F, B] int32;
    out-of-bounds refs (the free-slot sentinel R) yield zero rows."""
    return table.at[refs].get(mode="fill", fill_value=0)


# back-compat name for callers keyed on the op (``ring_gather``) rather
# than the kernel module (``ring_copy``)
ref_ring_gather = ref_ring_copy


def ref_ring_push(buf, queue_ids, pos, slots):
    """buf [Q, E, W]; queue_ids/pos [N] (queue_ids == Q drops); slots
    [N, W] -> new buf.  The pure-jnp scatter ``Ring.push`` uses."""
    return buf.at[queue_ids, pos].set(slots, mode="drop")


def ref_nic_deliver_fused(slots, valid, fifo, req_table, ffbuf, conn_tag,
                          conn_src, conn_lb, fftail, ffspace, scal,
                          key_words: int = 2):
    """Pure-jnp oracle for the fused delivery megakernel.

    Mirrors the unfused ``DaggerFabric.nic_deliver`` composition
    (``FreeFifo.allocate`` + steer + ``Ring.push`` + leak-back) over the
    kernel's raw-array calling convention; same returns.
    """
    from repro.core.load_balancer import (LB_OBJECT, LB_ROUND_ROBIN,
                                          LB_STATIC)
    from repro.core.rings import rank_by_group, rank_within
    from repro.core.serdes import FLAG_RESPONSE, HEADER_WORDS

    n = slots.shape[0]
    r = fifo.shape[0]
    f, d = ffbuf.shape
    free_head, free_avail, free_tail, rr0, active = (scal[i]
                                                     for i in range(5))
    v = valid != 0
    # free-slot allocate
    rank = rank_within(v)
    granted = v & (rank < free_avail)
    sid = jnp.where(granted, fifo[(free_head + rank) % r], r)
    req_out = req_table.at[sid].set(slots, mode="drop")
    # steer (conn read port 2 + FNV-1a / RR / static)
    cid = slots[:, 0]
    c_idx = cid % conn_tag.shape[0]
    hit = conn_tag[c_idx] == cid
    srcf = conn_src[c_idx]
    lbv = conn_lb[c_idx]
    is_resp = (((slots[:, 2] >> 16) & 0xFFFF) & FLAG_RESPONSE) != 0
    h = fnv1a_words(slots[:, HEADER_WORDS:], key_words)
    obj = (h % active.astype(jnp.uint32)).astype(jnp.int32)
    # cumulative positions over the VALID RR rows only (exclusive cumsum:
    # #valid RR rows before row i — mirrors load_balancer.steer)
    vrr = (v & (lbv == LB_ROUND_ROBIN)).astype(jnp.int32)
    rr_seq = (rr0 + jnp.cumsum(vrr) - vrr) % active
    flow = jnp.where(lbv == LB_STATIC, srcf % active,
                     jnp.where(lbv == LB_OBJECT, obj, rr_seq))
    flow = jnp.where(is_resp & hit, srcf % active, flow)
    n_rr = jnp.sum(vrr)
    # flow-FIFO push
    rank2, _ = rank_by_group(flow, f, granted)
    accepted = granted & (rank2 < ffspace[flow])
    pos = (fftail[flow] + rank2) % d
    q = jnp.where(accepted, flow, f)
    ff_out = ffbuf.at[q, pos].set(sid, mode="drop")
    a_counts = jnp.zeros((f,), jnp.int32).at[q].add(
        accepted.astype(jnp.int32), mode="drop")
    # leak-back
    leaked = granted & ~accepted
    l_idx = jnp.where(leaked, (free_tail + rank_within(leaked)) % r, r)
    fifo_out = fifo.at[l_idx].set(sid, mode="drop")
    ctr = jnp.stack([jnp.sum(granted.astype(jnp.int32)),
                     jnp.sum(leaked.astype(jnp.int32)), n_rr])
    return (req_out, ff_out, fifo_out, sid, flow,
            granted.astype(jnp.int32), accepted.astype(jnp.int32),
            a_counts, ctr)


def ref_switch_step_fused(tx_buf, tx_head, tx_tail, rx_buf, rx_head,
                          rx_tail, req_table, fifo, ffbuf, ff_head, ff_tail,
                          conn_tag, conn_src, conn_dest, conn_lb, scal,
                          hist, ext_slots, ext_valid, ext_dest, bmax: int,
                          include_fetch: bool = True, key_words: int = 2):
    """Pure-jnp oracle for the fused switch-step megakernel.

    Reconstructs a stacked ``FabricState`` from the kernel's raw-array
    calling convention and replays the exact unfused composition —
    vmapped ``nic_fetch`` + crossbar dest lookup + ``nic_deliver`` +
    ``nic_sched_emit`` + RX-ring drain + ``telemetry.observe``/``tick``
    — so equivalence to ``Switch.switch_step_stacked`` holds by
    construction.  Same 17-output tuple as the kernel.

    ``scal[:, S_ACTIVE]`` must be pre-clipped to [1, n_flows] (the
    wrapper contract).
    """
    from repro.config import FabricConfig
    from repro.core import monitor
    from repro.core.connection import ConnTable
    from repro.core.fabric import DaggerFabric, FabricState, SoftConfig
    from repro.core.rings import FreeFifo, Ring
    from repro.core.serdes import FLAG_RESPONSE
    from repro.kernels.switch_step import (MON_COLS, S_ACTIVE, S_BATCH,
                                           S_FLUSH, S_FREE_HEAD,
                                           S_FREE_TAIL, S_RR, S_TSTEP)

    t, f, e, w = tx_buf.shape
    r = fifo.shape[1]
    nb = hist.shape[1]
    fab = DaggerFabric(FabricConfig(
        n_flows=f, ring_entries=e, slot_bytes=w * 4,
        conn_cache_entries=conn_tag.shape[1], batch_size=bmax,
        request_buffer_slots=r, use_pallas=False))
    sts = FabricState(
        tx=Ring(tx_buf, tx_head, tx_tail),
        rx=Ring(rx_buf, rx_head, rx_tail),
        req_table=req_table,
        free=FreeFifo(fifo, scal[:, S_FREE_HEAD], scal[:, S_FREE_TAIL]),
        flow_fifo=Ring(ffbuf[..., None], ff_head, ff_tail),
        conn=ConnTable(conn_tag, conn_src, conn_dest, conn_lb),
        rr=scal[:, S_RR],
        soft=SoftConfig(scal[:, S_BATCH], scal[:, S_ACTIVE],
                        scal[:, S_FLUSH] != 0),
        mon=jax.tree.map(lambda x: jnp.zeros((t,), jnp.int32),
                         monitor.create()))

    if include_fetch:
        sts, slots, valid = jax.vmap(fab.nic_fetch)(sts)
        flat = slots.reshape(t, -1, w)
        fval = valid.reshape(t, -1)
        dest, hit = jax.vmap(ConnTable.read_dest)(sts.conn, flat[..., 0])
        cand_slots = flat.reshape(-1, w)
        cand_valid = (fval & hit).reshape(-1).astype(jnp.int32)
        cand_dest = dest.reshape(-1)
    else:
        cand_slots = ext_slots
        cand_valid = ext_valid.astype(jnp.int32)
        cand_dest = ext_dest

    sel = (cand_dest[None, :] == jnp.arange(t)[:, None]) \
        & (cand_valid[None, :] != 0)
    sts = jax.vmap(fab.nic_deliver, in_axes=(0, None, 0))(
        sts, cand_slots, sel)
    sts = jax.vmap(fab.nic_sched_emit)(sts)

    # drain (host_rx_drain on raw slots — keeps the wire words)
    slots_d, valid_d = jax.vmap(lambda rg: rg.peek(bmax))(sts.rx)
    n = jnp.sum(valid_d.astype(jnp.int32), axis=-1)           # [T, F]
    rx2 = Ring(sts.rx.buf, sts.rx.head + n, sts.rx.tail)
    drained = slots_d.reshape(t, -1, w)
    dvalid = valid_d.reshape(t, -1).astype(jnp.int32)

    # telemetry: observe drained responses, then tick
    flags = (drained[..., 2] >> 16) & 0xFFFF
    vv = (dvalid != 0) & ((flags & FLAG_RESPONSE) != 0)
    lat = jnp.clip(scal[:, S_TSTEP, None] - drained[..., 4] + 1, 0, None)
    binned = jnp.clip(lat, 0, nb - 1)
    hist2 = jax.vmap(lambda h, b, v: h.at[b].add(v))(
        hist, binned, vv.astype(jnp.int32))

    scal2 = (scal.at[:, S_FREE_HEAD].set(sts.free.head)
             .at[:, S_FREE_TAIL].set(sts.free.tail)
             .at[:, S_RR].set(sts.rr)
             .at[:, S_TSTEP].add(1)
             .at[:, 7].add(jnp.sum(vv.astype(jnp.int32), axis=1))
             .at[:, 8].add(jnp.sum(lat * vv.astype(jnp.int32), axis=1)))
    mon = jnp.stack(
        [sts.mon["rpcs_ingested"], sts.mon["rpcs_delivered"],
         sts.mon["rpcs_emitted"],
         sts.mon["rpcs_completed"] + jnp.sum(n, axis=1),
         sts.mon["drops_no_slot"], sts.mon["drops_fifo_full"],
         sts.mon["batches_emitted"]], axis=-1)
    assert mon.shape == (t, MON_COLS)
    return (sts.tx.head, sts.rx.buf, rx2.head, sts.rx.tail, sts.req_table,
            sts.free.fifo, sts.flow_fifo.buf[..., 0], sts.flow_fifo.head,
            sts.flow_fifo.tail, scal2, hist2, cand_slots, cand_valid,
            cand_dest, drained, dvalid, mon)


def ref_hash_steer(payload, n_flows, key_words: int = 2):
    """payload [N, W] int32 -> flow [N] int32 via FNV-1a % n_flows."""
    h = fnv1a_words(payload, key_words)
    return (h % jnp.uint32(n_flows)).astype(jnp.int32)


def ref_rpc_pack(conn_id, rpc_id, fn_id, flags, payload_len, frag_idx,
                 timestamp, payload, slot_words: int):
    """Field arrays -> wire slots [N, slot_words] int32."""
    from repro.core.serdes import HEADER_WORDS
    pw = slot_words - HEADER_WORDS
    w2 = (fn_id & 0xFFFF) | (flags << 16)
    w3 = (payload_len & 0xFFFF) | ((frag_idx & 0xFFFF) << 16)
    pl_ = payload[:, :pw]
    if pl_.shape[1] < pw:
        pl_ = jnp.pad(pl_, ((0, 0), (0, pw - pl_.shape[1])))
    return jnp.concatenate(
        [jnp.stack([conn_id, rpc_id, w2, w3, timestamp], axis=-1), pl_],
        axis=-1).astype(jnp.int32)


def ref_kv_probe(tags, keys, values, q_bucket, q_tag, q_key):
    """Set-associative probe.

    tags: [NB, WAYS] uint32 (0 = empty); keys: [NB, WAYS, KW] int32;
    values: [NB, WAYS, VW] int32; q_bucket: [N] int32; q_tag: [N]
    uint32; q_key: [N, KW] int32.  A way matches when its tag and key
    both do.  Returns (value [N, VW] of the first matching way, else 0;
    hit [N] bool).
    """
    match = (tags[q_bucket] == q_tag[:, None]) & jnp.all(
        keys[q_bucket] == q_key[:, None, :], axis=-1)
    hit = jnp.any(match, axis=1)
    way = jnp.argmax(match, axis=1)
    val = values[q_bucket, way]
    return jnp.where(hit[:, None], val, 0), hit


def ref_decode_attn(q, k, v, length):
    """GQA decode attention oracle.

    q: [B, nq, hd]; k,v: [B, S, nkv, hd]; length: scalar int32 (valid
    prefix of the cache).  Returns [B, nq, hd] float32.
    """
    b, nq, hd = q.shape
    s, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    qg = q.reshape(b, nkv, g, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, kf) * (hd ** -0.5)
    mask = jnp.arange(s)[None, None, None, :] < length
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, vf)
    return out.reshape(b, nq, hd)
