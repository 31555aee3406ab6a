"""Pallas kernel: batched ring-slot scatter (the CCI-P receive engine).

``Ring.push`` writes up to N arbitrated RPC slots into per-queue circular
buffers in one shot: row i lands at ``buf[q[i], pos[i]]`` unless its queue
id is the out-of-bounds drop sentinel (q[i] == n_queues).  This is the
write half of the paper's Fig. 8 ring datapath — the single fused scatter
that makes the host's critical path "one memory write".

TPU adaptation: a slot (W words, 16 for the 64-byte MTU) is narrower
than the 128-lane tile, so the kernel works on the ring transposed to
``[Q, W, E]`` — ring entries along lanes, slot words along sublanes,
which is also the layout XLA gives the ``[Q, E, W]`` ring on a TPU.  The
whole ring block sits in VMEM; queue ids and positions ride in SMEM
(scalar prefetch), and a ``fori_loop`` lands each accepted row as a
lane-masked select into its queue's ``[W, E]`` tile: the row's slot
column is picked out of the transposed ``[W, N]`` slot block by a masked
lane reduction.  N is soft traffic, not hard configuration, so the loop
is not unrolled.  Dropped rows (sentinel queue id) write nothing,
matching the ``mode="drop"`` jnp reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, pos_ref, slots_ref, buf_ref, out_ref, *, n_queues: int):
    out_ref[...] = buf_ref[...]
    w, n = slots_ref.shape
    e = buf_ref.shape[2]
    lane_n = jax.lax.broadcasted_iota(jnp.int32, (w, n), 1)
    lane_e = jax.lax.broadcasted_iota(jnp.int32, (w, e), 1)

    def body(i, carry):
        q = q_ref[i]

        @pl.when(q < n_queues)
        def _():
            col = jnp.sum(jnp.where(lane_n == i, slots_ref[...], 0),
                          axis=1, keepdims=True)             # [W, 1]
            out_ref[q] = jnp.where(lane_e == pos_ref[i], col, out_ref[q])

        return carry

    jax.lax.fori_loop(0, n, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ring_push(buf, queue_ids, pos, slots, *, interpret: bool):
    """buf: [Q, E, W] int32; queue_ids/pos: [N] int32 (queue_ids == Q is
    the drop sentinel); slots: [N, W] int32 -> new buf [Q, E, W]."""
    qn, e, w = buf.shape
    n = queue_ids.shape[0]
    out = pl.pallas_call(
        functools.partial(_kernel, n_queues=qn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec((w, n), lambda i, q, p: (0, 0)),
                      pl.BlockSpec((qn, w, e), lambda i, q, p: (0, 0, 0))],
            out_specs=pl.BlockSpec((qn, w, e), lambda i, q, p: (0, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((qn, w, e), jnp.int32),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(queue_ids, pos, slots.T, buf.transpose(0, 2, 1))
    return out.transpose(0, 2, 1)
