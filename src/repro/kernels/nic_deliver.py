"""Pallas megakernel: the fused TX-path delivery stage (paper Fig. 9B).

``DaggerFabric.nic_deliver`` is three separate stages in the pure-jnp
path: free-slot FIFO allocation, connection-table steering (hash / RR /
static), and the flow-FIFO ring scatter — each a handful of XLA ops with
their own HBM round-trips.  On the FPGA these are ONE pipeline: an RPC
arriving from the network is granted a request-buffer slot, steered, and
its slot reference landed in a flow FIFO within the same cycle budget.

This kernel is that pipeline as a single Pallas program.  A scalar
``fori_loop`` walks the request tile once, carrying the arbitration
registers (grant counter, leak counter, per-flow rank counters) exactly
like the hardware's per-cycle arbiter:

  row i:  grant   <- free FIFO head + #grants-so-far   (FIFO order)
          steer   <- conn cache read port 2 + FNV-1a hash / RR cursor
          scatter <- flow_fifo[flow, tail+rank] = slot  (or leak the
                     slot back to the free FIFO on backpressure)

TPU adaptation: every register, index array and slot-id table (free
FIFO, flow FIFOs, connection cache, per-row decisions) lives in SMEM,
the scalar core's memory, where the loop reads and writes single words.
The request table is the only vector state: it sits in VMEM transposed
to ``[W, R]`` (slot ids along lanes, since a 16-word slot is narrower
than the 128-lane tile), and a granted row lands as a lane-masked
select of its slot column, picked out of the transposed ``[W, N]``
request tile by a masked lane reduction.  Rows the arbiter rejects
write nothing.

Reads go against the *input* refs (the pre-write state — the 1W3R model),
writes against the output refs, so in-call allocate/release overlap keeps
the unfused semantics bit-for-bit (verified by the parity suite).

Cursor/counter updates (free head/tail, flow-FIFO tails, RR cursor,
monitor bumps) are cheap scalar arithmetic and stay outside the kernel in
``DaggerFabric.nic_deliver`` — the kernel returns the per-row decisions
(slot id, flow, granted, accepted) plus the count registers it carried.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.load_balancer import LB_OBJECT, LB_ROUND_ROBIN, LB_STATIC
from repro.core.serdes import FLAG_RESPONSE, HEADER_WORDS

FNV_OFFSET = 0x811C9DC5
FNV_PRIME = 0x01000193

# scal vector layout (int32): see nic_deliver_fused wrapper
_FREE_HEAD, _FREE_AVAIL, _FREE_TAIL, _RR0, _ACTIVE = range(5)
SCAL_WORDS = 5


def _shr(x, k: int):
    return jax.lax.shift_right_logical(x, jnp.int32(k))


def _umod(h, a):
    """``h mod a`` with ``h`` read as uint32 — in int32 arithmetic, which
    the scalar core supports (``a`` is small and positive)."""
    return ((_shr(h, 1) % a) * 2 + (h & 1)) % a


def _kernel(hdr_ref, valid_ref, fifo_ref, tag_ref, src_ref, lb_ref,
            fftail_ref, ffspace_ref, scal_ref, ffbuf_ref, slots_ref,
            req_ref, req_out, ffbuf_out, fifo_out, sid_out, flow_out,
            granted_out, accepted_out, acc_out, ctr_out, g_counts, *,
            key_words: int):
    req_out[...] = req_ref[...]
    w, n = slots_ref.shape
    r_cap = fifo_ref.shape[0]                      # request buffer slots
    n_conn = tag_ref.shape[0]
    n_flows = fftail_ref.shape[0]
    d_cap = ffbuf_ref.shape[0] // n_flows
    hw = 2 + key_words                             # header words per row
    free_head = scal_ref[_FREE_HEAD]
    free_avail = scal_ref[_FREE_AVAIL]
    free_tail = scal_ref[_FREE_TAIL]
    rr0 = scal_ref[_RR0]
    active = scal_ref[_ACTIVE]

    def copy(ref_in, ref_out):
        def body(j, c):
            ref_out[j] = ref_in[j]
            return c
        jax.lax.fori_loop(0, ref_in.shape[0], body, 0)

    copy(fifo_ref, fifo_out)
    copy(ffbuf_ref, ffbuf_out)

    def zero(j, c):
        g_counts[j] = 0
        acc_out[j] = 0
        return c

    jax.lax.fori_loop(0, n_flows, zero, 0)
    lane_n = jax.lax.broadcasted_iota(jnp.int32, (w, n), 1)
    lane_r = jax.lax.broadcasted_iota(jnp.int32, (w, r_cap), 1)

    def body(i, carry):
        n_granted, n_leaked, n_rr = carry
        v = valid_ref[i] != 0

        # ---- free-slot FIFO allocate (reads the pre-release contents) --
        granted = v & (n_granted < free_avail)
        sid = jnp.where(granted, fifo_ref[(free_head + n_granted) % r_cap],
                        r_cap)                     # OOB sentinel

        # ---- request-buffer write ---------------------------------------
        @pl.when(granted)
        def _():
            col = jnp.sum(jnp.where(lane_n == i, slots_ref[...], 0),
                          axis=1, keepdims=True)             # [W, 1]
            req_out[...] = jnp.where(lane_r == sid, col, req_out[...])

        # ---- connection lookup (1W3R read port 2) + steering -----------
        cid = hdr_ref[i * hw]
        c_idx = cid % n_conn
        hit = tag_ref[c_idx] == cid
        srcf = src_ref[c_idx]
        lbv = lb_ref[c_idx]
        flags = _shr(hdr_ref[i * hw + 1], 16) & 0xFFFF
        is_resp = (flags & FLAG_RESPONSE) != 0
        h = jnp.int32(FNV_OFFSET - (1 << 32))
        for k in range(key_words):
            wk = hdr_ref[i * hw + 2 + k]
            for shift in (0, 8, 16, 24):
                h = (h ^ (_shr(wk, shift) & 0xFF)) * jnp.int32(FNV_PRIME)
        obj = _umod(h, active)
        # RR positions are cumulative over the VALID ROUND_ROBIN rows
        # only: n_rr is the carried count of such rows before this one,
        # so mixed-scheme batches and partially-valid tiles fill RR
        # slots densely (and the cursor advances by n_rr)
        rr_seq = (rr0 + n_rr) % active
        flow = jnp.where(lbv == LB_STATIC, srcf % active,
                         jnp.where(lbv == LB_OBJECT, obj, rr_seq))
        # responses return to the flow their request was issued from (SRQ)
        flow = jnp.where(is_resp & hit, srcf % active, flow)
        n_rr = n_rr + (v & (lbv == LB_ROUND_ROBIN)).astype(jnp.int32)

        # ---- flow-FIFO push arbitration --------------------------------
        rank = g_counts[flow]
        accepted = granted & (rank < ffspace_ref[flow])

        @pl.when(accepted)
        def _():
            ffbuf_out[flow * d_cap + (fftail_ref[flow] + rank) % d_cap] = sid

        # ---- FIFO full: leak the granted slot back to the free FIFO ----
        leaked = granted & ~accepted

        @pl.when(leaked)
        def _():
            fifo_out[(free_tail + n_leaked) % r_cap] = sid

        # ---- per-row decisions ----------------------------------------
        sid_out[i] = sid
        flow_out[i] = flow
        granted_out[i] = granted.astype(jnp.int32)
        accepted_out[i] = accepted.astype(jnp.int32)
        g_counts[flow] = rank + granted.astype(jnp.int32)
        acc_out[flow] = acc_out[flow] + accepted.astype(jnp.int32)
        return (n_granted + granted.astype(jnp.int32),
                n_leaked + leaked.astype(jnp.int32), n_rr)

    n_granted, n_leaked, n_rr = jax.lax.fori_loop(
        0, n, body, (jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    ctr_out[0] = n_granted
    ctr_out[1] = n_leaked
    ctr_out[2] = n_rr


@functools.partial(jax.jit, static_argnames=("key_words", "interpret"))
def nic_deliver_fused(slots, valid, fifo, req_table, ffbuf, conn_tag,
                      conn_src, conn_lb, fftail, ffspace, scal,
                      key_words: int = 2, *, interpret: bool):
    """One fused steer+allocate+scatter pass over a request tile.

    slots [N, W], valid [N] int32; fifo [R] free-slot ids; req_table
    [R, W]; ffbuf [F, D] flow-FIFO slot refs; conn_* [C]; fftail/ffspace
    [F]; scal [SCAL_WORDS] = (free head, free available, free tail, RR
    cursor, active flows) — all int32.

    Returns (req_table', ffbuf', fifo', slot_ids [N], flow [N],
    granted [N], accepted [N], accepted-per-flow [F],
    counters [3] = (n granted, n leaked, n round-robin)).
    """
    n, w = slots.shape
    r, f, d = fifo.shape[0], ffbuf.shape[0], ffbuf.shape[1]
    hdr = jnp.concatenate(
        [slots[:, :1], slots[:, 2:3],
         slots[:, HEADER_WORDS:HEADER_WORDS + key_words]], axis=1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out_shape = (
        jax.ShapeDtypeStruct((w, r), jnp.int32),       # req_table'^T
        jax.ShapeDtypeStruct((f * d,), jnp.int32),     # ffbuf'
        jax.ShapeDtypeStruct((r,), jnp.int32),         # fifo'
        jax.ShapeDtypeStruct((n,), jnp.int32),         # slot_ids
        jax.ShapeDtypeStruct((n,), jnp.int32),         # flow
        jax.ShapeDtypeStruct((n,), jnp.int32),         # granted
        jax.ShapeDtypeStruct((n,), jnp.int32),         # accepted
        jax.ShapeDtypeStruct((f,), jnp.int32),         # accepted per flow
        jax.ShapeDtypeStruct((3,), jnp.int32),         # counters
    )
    outs = pl.pallas_call(
        functools.partial(_kernel, key_words=key_words),
        in_specs=[smem] * 10 + [vmem, vmem],
        out_specs=(vmem,) + (smem,) * 8,
        out_shape=out_shape,
        scratch_shapes=[pltpu.SMEM((f,), jnp.int32)],   # per-flow ranks
        input_output_aliases={11: 0},
        interpret=interpret,
    )(hdr.reshape(-1), valid, fifo, conn_tag, conn_src, conn_lb, fftail,
      ffspace, scal, ffbuf.reshape(-1), slots.T, req_table.T)
    req_t, ffb = outs[0], outs[1]
    return (req_t.T, ffb.reshape(f, d)) + tuple(outs[2:])
