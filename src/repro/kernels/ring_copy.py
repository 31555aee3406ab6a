"""Pallas kernel: batched ring-slot gather (the CCI-P transmit engine).

``nic_sched_emit`` reads B RPC payloads per flow from the request buffer,
addressed by the slot references popped from the flow FIFO (paper Fig.
9B).  On TPU this is a gather of [B, W] rows per flow out of the
[R, W] request table.

TPU adaptation: instead of a CAM/row-addressed BRAM read, the table tile
lives in VMEM (it is small by construction: R = B x n_flows slots of one
cache line each — the paper sizes it the same way), transposed to
``[W, R]`` so slot ids run along lanes.  Slot references ride in SMEM,
and each reference's W-word column is picked out by a masked lane
reduction and placed at its output lane.  Out-of-bounds references (the
free-slot sentinel R) match no lane and produce zero rows, matching the
``mode="drop"`` semantics of the jnp reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(refs_ref, table_ref, out_ref):
    w, r = table_ref.shape
    m = out_ref.shape[1]
    lane_r = jax.lax.broadcasted_iota(jnp.int32, (w, r), 1)
    lane_m = jax.lax.broadcasted_iota(jnp.int32, (w, m), 1)

    def body(j, acc):
        col = jnp.sum(jnp.where(lane_r == refs_ref[j], table_ref[...], 0),
                      axis=1, keepdims=True)                 # [W, 1]
        return jnp.where(lane_m == j, col, acc)

    out_ref[...] = jax.lax.fori_loop(0, m, body,
                                     jnp.zeros((w, m), jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ring_gather(table, refs, *, interpret: bool):
    """table: [R, W] int32; refs: [F, B] int32 -> [F, B, W] int32."""
    r, w = table.shape
    f, b = refs.shape
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec((w, r), lambda i, refs: (0, 0))],
            out_specs=pl.BlockSpec((w, f * b), lambda i, refs: (0, 0))),
        out_shape=jax.ShapeDtypeStruct((w, f * b), jnp.int32),
        interpret=interpret,
    )(refs.reshape(-1), table.T)
    return out.T.reshape(f, b, w)
