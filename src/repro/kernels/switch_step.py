"""Pallas megakernel: ONE kernel for the whole per-device switch step.

``Switch.switch_step_stacked`` is the software L2 switch: every tier's
NIC fetches its host-written tile, the crossbar steers rows to their
destination tier, and each destination runs deliver (free-slot allocate
+ steer + flow-FIFO scatter), emit (flow scheduler + CCI-P transmit)
and drain (completion queues + latency telemetry).  In the pure-jnp
path those are ~10 XLA ops per tier with every intermediate
materialized.  On the Dagger FPGA the same work is one tightly-coupled
pipeline with no intermediate materialization — an RPC goes from TX
ring to completion queue without ever leaving the NIC.

This kernel is that pipeline.  Four phases run back-to-back over the
whole [T]-tier state in one pass:

  A fetch   tx rings -> candidate list + read-port-1 dest lookup
  B deliver candidates -> request buffer + flow FIFOs (per-DEST
            grant/leak/RR/rank arbitration — ``nic_deliver_fused``
            subsumed, generalized over the tier axis)
  C emit    flow FIFOs -> rx rings + free-slot release
  D drain   rx rings -> completions + telemetry histogram scatter

The hardware's per-cycle arbiters assign each concurrent writer its
queue position serially; here every arbitration register is computed in
closed form as an exclusive prefix sum over the global candidate order
(grant rank per destination, RR sequence position, flow-FIFO push rank
per (dest, flow), leak-back rank), so the whole kernel is straight-line
vectorized code — no sequential loop over candidates — while producing
the EXACT register sequence the serial arbiter would.  Each phase
consumes the value arrays its predecessor produced, so the in-call
dataflow equals the unfused stage chaining bit-for-bit (pinned by
``tests/test_switch_fused.py`` against ``ref.py``'s oracle and the live
``switch_step_stacked`` composition).

Scalar register file (``scal`` [T, SCAL_COLS] int32, per tier):
free-FIFO head/tail cursors, RR cursor, soft batch width, active flows
(pre-clipped to [1, F] by the caller), force-flush flag, telemetry
step/n_done/sum_steps.  Monitor deltas come back as ``mon``
[T, MON_COLS] — cursor reconstruction and counter bumps stay outside as
scalar arithmetic (see ``fabric.fused_switch_front``).

With ``include_fetch=False`` phase A is skipped and the candidate list
is taken from ``ext_*`` — the sharded switch fetches + exchanges
tiles over the mesh ToR hop first, then hands the post-exchange global
candidate list (dest already rebased to device-local tier ids; rows
destined elsewhere are simply out of [0, T)) to phases B-D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.load_balancer import LB_OBJECT, LB_ROUND_ROBIN, LB_STATIC
from repro.core.serdes import FLAG_RESPONSE, HEADER_WORDS

FNV_OFFSET = 0x811C9DC5
FNV_PRIME = 0x01000193

# per-tier scalar register file (int32 columns of ``scal``)
(S_FREE_HEAD, S_FREE_TAIL, S_RR, S_BATCH, S_ACTIVE, S_FLUSH,
 S_TSTEP, S_TNDONE, S_TSUM) = range(9)
SCAL_COLS = 9

# per-tier monitor delta columns of the ``mon`` output
(M_INGESTED, M_DELIVERED, M_EMITTED, M_COMPLETED, M_NO_SLOT,
 M_FIFO_FULL, M_BATCHES) = range(7)
MON_COLS = 7

# What the v5e compiler says to this kernel (pinned by
# tests/test_tpu_compile.py): the 4-D ``take_along_axis`` ring reads and
# the ``.at[]`` scatters of phases A-D have no Mosaic lowering.  Until
# the kernel is redesigned around 2-D gathers, a TPU refuses the fused
# switch path instead of silently running the jnp composition.
MOSAIC_REFUSAL = ("switch_step_fused does not compile for TPU — Mosaic: "
                  "'NotImplementedError: Only 2D gather is supported'. "
                  "Run the switch path with use_pallas=False on a TPU.")


def _fnv1a_rows(rows, key_words: int):
    """Vectorized byte-serial FNV-1a over the payload key words [M]."""
    h = jnp.full((rows.shape[0],), FNV_OFFSET, jnp.uint32)
    for k in range(key_words):
        wk = rows[:, HEADER_WORDS + k].astype(jnp.uint32)
        for shift in (0, 8, 16, 24):
            byte = (wk >> shift) & jnp.uint32(0xFF)
            h = (h ^ byte) * jnp.uint32(FNV_PRIME)
    return h


def _rank_at(onehot, d):
    """Exclusive prefix count of ``onehot`` [M, K] rows at column d [M].

    rank_i = number of j < i with onehot[j, d_i] — the queue position a
    serial arbiter would hand row i among the rows contending for the
    same column (destination tier, (dest, flow) pair, ...).
    """
    ex = jnp.cumsum(onehot, axis=0) - onehot
    return jnp.take_along_axis(ex, d[:, None], axis=1)[:, 0]


def _kernel(tx_buf_ref, tx_head_ref, tx_tail_ref, rx_buf_ref, rx_head_ref,
            rx_tail_ref, req_ref, fifo_ref, ffbuf_ref, ffh_ref, fft_ref,
            tag_ref, src_ref, dest_ref, lb_ref, scal_ref, hist_ref,
            ext_slots_ref, ext_valid_ref, ext_dest_ref,
            txh_out, rxbuf_out, rxh_out, rxt_out, req_out, fifo_out,
            ffbuf_out, ffh_out, fft_out, scal_out, hist_out,
            cand_slots_out, cand_valid_out, cand_dest_out,
            drained_out, dvalid_out, mon_out,
            *, bmax: int, include_fetch: bool, key_words: int):
    t, f, e, w = tx_buf_ref.shape
    e_rx = rx_buf_ref.shape[2]
    r_cap = fifo_ref.shape[1]
    n_conn = tag_ref.shape[1]
    d_cap = ffbuf_ref.shape[2]
    n_bins = hist_ref.shape[1]
    m = ext_valid_ref.shape[0]

    # whole-state reads: cursors and registers live as values
    txh = tx_head_ref[...]
    txt = tx_tail_ref[...]
    rxh = rx_head_ref[...]
    rxt = rx_tail_ref[...]
    ffh = ffh_ref[...]
    fft = fft_ref[...]
    sc = scal_ref[...]
    req_in = req_ref[...]
    fifo_in = fifo_ref[...]
    ffbuf_in = ffbuf_ref[...]
    rxbuf_in = rx_buf_ref[...]
    tag = tag_ref[...]
    src = src_ref[...]
    dstt = dest_ref[...]
    lb = lb_ref[...]
    hist_in = hist_ref[...]
    free_head = sc[:, S_FREE_HEAD]
    free_tail = sc[:, S_FREE_TAIL]
    active = sc[:, S_ACTIVE]
    batch = jnp.clip(sc[:, S_BATCH], 1, bmax)
    flush = sc[:, S_FLUSH] != 0

    ti_g = jnp.broadcast_to(jnp.arange(t)[:, None, None], (t, f, bmax))
    fi_g = jnp.broadcast_to(jnp.arange(f)[None, :, None], (t, f, bmax))
    jj = jnp.arange(bmax)[None, None, :]

    # ---- phase A: CCI-P batched fetch + read-port-1 dest lookup ----------
    if include_fetch:
        txbuf = tx_buf_ref[...]
        take_a = jnp.minimum(txt - txh, batch[:, None])          # [T, F]
        idxs = (txh[:, :, None] + jnp.arange(bmax)) % e          # [T,F,bmax]
        rows_a = jnp.take_along_axis(txbuf, idxs[..., None], axis=2)
        cid_a = rows_a[..., 0]
        ci_a = cid_a % n_conn
        hit_a = tag[ti_g, ci_a] == cid_a
        v_a = (jj < take_a[:, :, None]) & hit_a
        cand_slots = rows_a.reshape(m, w)
        cand_valid = v_a.reshape(m).astype(jnp.int32)
        cand_dest = dstt[ti_g, ci_a].reshape(m)
        ingested = jnp.sum(take_a, axis=1)
        txh_out[...] = txh + take_a
    else:
        cand_slots = ext_slots_ref[...]
        cand_valid = ext_valid_ref[...]
        cand_dest = ext_dest_ref[...]
        ingested = jnp.zeros((t,), jnp.int32)
        txh_out[...] = txh
    cand_slots_out[...] = cand_slots
    cand_valid_out[...] = cand_valid
    cand_dest_out[...] = cand_dest

    # ---- phase B: deliver (allocate + steer + flow-FIFO scatter) ---------
    # arbitration over the global candidate order: every serial register
    # (grant count, RR position, push rank, leak rank) becomes an
    # exclusive prefix sum keyed by destination — row order per tier
    # equals the jnp crossbar's masked full-list order, so grants/ranks/
    # RR positions match the serial arbiter exactly
    rows = cand_slots
    d_raw = cand_dest
    in_range = (d_raw >= 0) & (d_raw < t)
    v = (cand_valid != 0) & in_range
    d = jnp.where(in_range, d_raw, 0)
    oh_d = ((d[:, None] == jnp.arange(t)[None, :])
            & v[:, None]).astype(jnp.int32)                      # [M, T]

    # free-slot FIFO allocate: a valid row is granted iff its arrival
    # rank at the destination fits the pre-step availability window
    vrank = _rank_at(oh_d, d)
    avail = (free_tail - free_head)[d]
    granted = v & (vrank < avail)
    a_idx = (free_head[d] + vrank) % r_cap
    sid = jnp.where(granted, fifo_in[d, a_idx], r_cap)   # OOB sentinel

    # request-buffer scatter (granted rows only; slot ids are unique)
    req2 = req_in.at[jnp.where(granted, d, t),
                     jnp.where(granted, sid, 0), :].set(rows, mode="drop")

    # connection lookup on the DEST tier (1W3R read port 2) + steering
    cid = rows[:, 0]
    ci = cid % n_conn
    hit = tag[d, ci] == cid
    srcf = src[d, ci]
    lbv = lb[d, ci]
    flags = (rows[:, 2] >> 16) & 0xFFFF
    is_resp = (flags & FLAG_RESPONSE) != 0
    act_d = active[d]
    obj = (_fnv1a_rows(rows, key_words) %
           act_d.astype(jnp.uint32)).astype(jnp.int32)
    # RR positions are cumulative over THIS tier's valid RR rows only
    oh_rr = oh_d * (lbv == LB_ROUND_ROBIN).astype(jnp.int32)[:, None]
    rr_seq = (sc[:, S_RR][d] + _rank_at(oh_rr, d)) % act_d
    flow = jnp.where(lbv == LB_STATIC, srcf % act_d,
                     jnp.where(lbv == LB_OBJECT, obj, rr_seq))
    # responses return to the flow their request was issued from (SRQ)
    flow = jnp.where(is_resp & hit, srcf % act_d, flow)

    # flow-FIFO push arbitration (space from the PRE-push cursors)
    df = d * f + flow
    oh_df = ((df[:, None] == jnp.arange(t * f)[None, :])
             & granted[:, None]).astype(jnp.int32)               # [M, T*F]
    frank = _rank_at(oh_df, df)
    space = d_cap - (fft.reshape(-1)[df] - ffh.reshape(-1)[df])
    accepted = granted & (frank < space)
    pos = (fft.reshape(-1)[df] + frank) % d_cap
    ffbuf2 = ffbuf_in.at[jnp.where(accepted, d, t),
                         jnp.where(accepted, flow, 0),
                         jnp.where(accepted, pos, 0)].set(sid, mode="drop")

    # flow FIFO full: leak the granted slot back to the free FIFO
    leaked = granted & ~accepted
    oh_lk = oh_d * leaked.astype(jnp.int32)[:, None]
    l_idx = (free_tail[d] + _rank_at(oh_lk, d)) % r_cap
    fifo2 = fifo_in.at[jnp.where(leaked, d, t),
                       jnp.where(leaked, l_idx, 0)].set(sid, mode="drop")

    zt = jnp.zeros((t,), jnp.int32)
    ngr = zt.at[d].add(granted.astype(jnp.int32))
    nlk = jnp.sum(oh_lk, axis=0)
    nrr = jnp.sum(oh_rr, axis=0)
    dns = zt.at[d].add((v & ~granted).astype(jnp.int32))
    act_c = jnp.zeros((t, f), jnp.int32).at[d, flow].add(
        accepted.astype(jnp.int32))
    req_out[...] = req2
    fft2 = fft + act_c
    fft_out[...] = fft2
    ft_mid = free_tail + nlk                 # free tail after leak-backs

    # ---- phase C: emit (flow scheduler + CCI-P transmit + slot release) --
    counts = fft2 - ffh
    ready = (counts >= batch[:, None]) | flush[:, None]
    take_c = jnp.where(ready, jnp.minimum(counts, batch[:, None]), 0)
    # back-pressure: only emit into RX rings with space (flow blocking)
    space_rx = e_rx - (rxt - rxh)
    take_c = jnp.where(space_rx >= take_c, take_c, 0)            # [T, F]
    lv = jj < take_c[:, :, None]                                 # [T,F,bmax]
    ff_idx = (ffh[:, :, None] + jnp.arange(bmax)) % d_cap
    sid_c = jnp.take_along_axis(ffbuf2, ff_idx, axis=2)  # post-deliver
    prow = req2[ti_g, jnp.where(lv, sid_c, 0)]           # [T,F,bmax,W]
    rx_idx = (rxt[:, :, None] + jnp.arange(bmax)) % e_rx
    rxbuf2 = rxbuf_in.at[jnp.where(lv, ti_g, t), fi_g, rx_idx, :].set(
        prow, mode="drop")
    # release the emitted slots: flow-major, lane-minor order continues
    # the free tail after the leak-backs (matches ``rank_within``)
    rel_rank = (jnp.cumsum(take_c, axis=1) - take_c)[:, :, None] + \
        jnp.arange(bmax)
    rel_idx = (ft_mid[:, None, None] + rel_rank) % r_cap
    fifo3 = fifo2.at[jnp.where(lv, ti_g, t),
                     jnp.where(lv, rel_idx, 0)].set(sid_c, mode="drop")
    rxbuf_out[...] = rxbuf2
    fifo_out[...] = fifo3
    ffbuf_out[...] = ffbuf2
    rxt2 = rxt + take_c
    rxt_out[...] = rxt2
    ffh_out[...] = ffh + take_c
    nrel = jnp.sum(take_c, axis=1)
    emitted = nrel
    batches = jnp.sum((take_c > 0).astype(jnp.int32), axis=1)

    # ---- phase D: completion drain + latency telemetry -------------------
    occ = rxt2 - rxh
    n_take = jnp.minimum(occ, bmax)
    idx_d = (rxh[:, :, None] + jnp.arange(bmax)) % e_rx
    srow = jnp.take_along_axis(rxbuf2, idx_d[..., None], axis=2)
    dv = jj < occ[:, :, None]
    # drained rows mirror Ring.peek: stale contents included, masked
    # only by dvalid — required for bit-exact parity
    drained_out[...] = srow.reshape(t, f * bmax, w)
    dvalid_out[...] = dv.reshape(t, f * bmax).astype(jnp.int32)
    # telemetry: a drained RESPONSE completes an RPC this tier issued —
    # residency = step - stamped issue step + 1
    is_resp_d = (((srow[..., 2] >> 16) & 0xFFFF) & FLAG_RESPONSE) != 0
    vv = (dv & is_resp_d).astype(jnp.int32)
    lat = jnp.maximum(sc[:, S_TSTEP][:, None, None] - srow[..., 4] + 1, 0)
    binv = jnp.minimum(lat, n_bins - 1)
    hist_out[...] = hist_in.at[ti_g, binv].add(vv)
    rxh_out[...] = rxh + n_take
    completed = jnp.sum(n_take, axis=1)
    nd = jnp.sum(vv, axis=(1, 2))
    ssum = jnp.sum(lat * vv, axis=(1, 2))

    # ---- register write-back ---------------------------------------------
    scal_out[...] = (sc.at[:, S_FREE_HEAD].add(ngr)
                     .at[:, S_FREE_TAIL].set(ft_mid + nrel)
                     .at[:, S_RR].set((sc[:, S_RR] + nrr) % active)
                     .at[:, S_TSTEP].add(1)
                     .at[:, S_TNDONE].add(nd)
                     .at[:, S_TSUM].add(ssum))
    mon_out[...] = jnp.stack(
        [ingested, jnp.sum(act_c, axis=1), emitted, completed, dns, nlk,
         batches], axis=-1)


@functools.partial(jax.jit, static_argnames=("bmax", "include_fetch",
                                             "key_words", "interpret"))
def switch_step_fused(tx_buf, tx_head, tx_tail, rx_buf, rx_head, rx_tail,
                      req_table, fifo, ffbuf, ff_head, ff_tail,
                      conn_tag, conn_src, conn_dest, conn_lb, scal, hist,
                      ext_slots, ext_valid, ext_dest, bmax: int,
                      include_fetch: bool = True, key_words: int = 2, *,
                      interpret: bool):
    """One fused fetch+steer+deliver+emit+drain pass over a tier stack.

    tx/rx rings [T, F, E, W] with head/tail [T, F]; req_table [T, R, W];
    fifo [T, R] free-slot ids; ffbuf [T, F, D] flow-FIFO slot refs with
    ff_head/ff_tail [T, F]; conn_* [T, C]; scal [T, SCAL_COLS] register
    file; hist [T, n_bins] telemetry histogram; ext_* the [M]-row
    candidate list consumed when ``include_fetch=False`` (with fetch,
    M must equal T*F*bmax and ext_* are ignored inputs).

    Returns (tx_head', rx_buf', rx_head', rx_tail', req_table', fifo',
    ffbuf', ff_head', ff_tail', scal', hist', cand_slots [M, W],
    cand_valid [M], cand_dest [M], drained [T, F*bmax, W],
    dvalid [T, F*bmax], mon [T, MON_COLS]).
    """
    if not interpret:
        raise NotImplementedError(MOSAIC_REFUSAL)
    return fused_call(tx_buf, tx_head, tx_tail, rx_buf, rx_head, rx_tail,
                      req_table, fifo, ffbuf, ff_head, ff_tail, conn_tag,
                      conn_src, conn_dest, conn_lb, scal, hist, ext_slots,
                      ext_valid, ext_dest, bmax=bmax,
                      include_fetch=include_fetch, key_words=key_words,
                      interpret=interpret)


def fused_call(tx_buf, tx_head, tx_tail, rx_buf, rx_head, rx_tail,
               req_table, fifo, ffbuf, ff_head, ff_tail, conn_tag,
               conn_src, conn_dest, conn_lb, scal, hist, ext_slots,
               ext_valid, ext_dest, *, bmax: int, include_fetch: bool,
               key_words: int, interpret: bool):
    """The ``pallas_call`` behind ``switch_step_fused``, unguarded (the
    v5e compile test uses it to confirm Mosaic still refuses it)."""
    t, f, e, w = tx_buf.shape
    e_rx = rx_buf.shape[2]
    r = fifo.shape[1]
    d = ffbuf.shape[2]
    c = conn_tag.shape[1]
    nb = hist.shape[1]
    m = ext_valid.shape[0]
    if include_fetch and m != t * f * bmax:
        raise ValueError(f"include_fetch needs an ext candidate list of "
                         f"T*F*bmax = {t * f * bmax} rows, got {m}")
    whole = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    out_shape = (
        jax.ShapeDtypeStruct((t, f), jnp.int32),          # tx_head'
        jax.ShapeDtypeStruct((t, f, e_rx, w), jnp.int32),  # rx_buf'
        jax.ShapeDtypeStruct((t, f), jnp.int32),          # rx_head'
        jax.ShapeDtypeStruct((t, f), jnp.int32),          # rx_tail'
        jax.ShapeDtypeStruct((t, r, w), jnp.int32),       # req_table'
        jax.ShapeDtypeStruct((t, r), jnp.int32),          # fifo'
        jax.ShapeDtypeStruct((t, f, d), jnp.int32),       # ffbuf'
        jax.ShapeDtypeStruct((t, f), jnp.int32),          # ff_head'
        jax.ShapeDtypeStruct((t, f), jnp.int32),          # ff_tail'
        jax.ShapeDtypeStruct((t, SCAL_COLS), jnp.int32),  # scal'
        jax.ShapeDtypeStruct((t, nb), jnp.int32),         # hist'
        jax.ShapeDtypeStruct((m, w), jnp.int32),          # cand slots
        jax.ShapeDtypeStruct((m,), jnp.int32),            # cand valid
        jax.ShapeDtypeStruct((m,), jnp.int32),            # cand dest
        jax.ShapeDtypeStruct((t, f * bmax, w), jnp.int32),  # drained
        jax.ShapeDtypeStruct((t, f * bmax), jnp.int32),   # dvalid
        jax.ShapeDtypeStruct((t, MON_COLS), jnp.int32),   # monitor deltas
    )
    return pl.pallas_call(
        functools.partial(_kernel, bmax=bmax, include_fetch=include_fetch,
                          key_words=key_words),
        grid=(1,),
        in_specs=[
            whole(t, f, e, w),       # tx ring buf
            whole(t, f),             # tx head
            whole(t, f),             # tx tail
            whole(t, f, e_rx, w),    # rx ring buf
            whole(t, f),             # rx head
            whole(t, f),             # rx tail
            whole(t, r, w),          # request table
            whole(t, r),             # free fifo
            whole(t, f, d),          # flow fifo buf
            whole(t, f),             # flow fifo heads
            whole(t, f),             # flow fifo tails
            whole(t, c),             # conn tag
            whole(t, c),             # conn src_flow
            whole(t, c),             # conn dest_addr
            whole(t, c),             # conn lb
            whole(t, SCAL_COLS),     # scalar register file
            whole(t, nb),            # telemetry histogram
            whole(m, w),             # ext candidate slots
            whole(m,),               # ext candidate valid
            whole(m,),               # ext candidate dest
        ],
        out_specs=tuple(whole(*s.shape) for s in out_shape),
        out_shape=out_shape,
        interpret=interpret,
    )(tx_buf, tx_head, tx_tail, rx_buf, rx_head, rx_tail, req_table, fifo,
      ffbuf, ff_head, ff_tail, conn_tag, conn_src, conn_dest, conn_lb,
      scal, hist, ext_slots, ext_valid, ext_dest)
