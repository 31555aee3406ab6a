"""Pallas kernel: fused GQA decode attention (flash-decoding).

The serving hot loop: one new query token against a KV cache of S
entries.  This is HBM-bandwidth-bound (the §Roofline memory term for all
``decode_*`` cells), so the kernel streams K/V exactly once.

TPU adaptation of the GPU flash-decoding recipe:
* grid = (batch, kv_head, S_blocks); the S dimension is the *innermost*
  (sequential) grid axis so the online-softmax running state (m, l, acc)
  lives in VMEM scratch across iterations — TPU grid programs on the same
  (b, k) prefix execute in order, which replaces the GPU's cross-block
  reduction pass.
* Block shapes: K/V are viewed as ``[B, S, nkv*hd]`` (a free reshape of
  the cache) and tiled ``[s_blk, hd]`` at lane block ``kv_head`` — both
  minor dims are (8,128)-tile aligned when hd is a multiple of 128, so
  Mosaic streams one head's rows without a relayout; q/out tiles are
  ``[g, hd]`` where g = nq / nkv query heads share this kv head (GQA).
* Per-row valid lengths ride in SMEM as a scalar-prefetch operand, so
  every batch row (a continuous-batching slot) masks its OWN prefix.
  Tiles entirely past a row's length still stream but contribute
  exp(-inf)=0; a production variant would early-exit via grid pruning.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, s_blk: int, blocks: int):
    b_idx = pl.program_id(0)
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # [g, hd]
    k = k_ref[0].astype(jnp.float32)                  # [s_blk, hd]
    v = v_ref[0].astype(jnp.float32)
    hd = q.shape[-1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (hd ** -0.5)                              # [g, s_blk]
    pos = s_idx * s_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[b_idx], s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)        # [g, 1]
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                            # [g, s_blk]
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * alpha + jnp.dot(p, v,
                                         preferred_element_type=jnp.float32)
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(s_idx == blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("s_blk", "interpret"))
def decode_attention(q, k, v, length, s_blk: int = 256, *,
                     interpret: bool):
    """q: [B, nq, hd]; k,v: [B, S, nkv, hd]; length: scalar or [B] int32
    (valid cache prefix of each batch row).

    Returns [B, nq, hd] float32 (flash-decoding, single K/V stream)."""
    b, nq, hd = q.shape
    s, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    s_blk = min(s_blk, s)
    if s % s_blk:
        raise ValueError(f"S={s} not a multiple of s_blk={s_blk}")
    blocks = s // s_blk
    qg = q.reshape(b, nkv, g, hd)
    kf = k.reshape(b, s, nkv * hd)
    vf = v.reshape(b, s, nkv * hd)
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    kv_spec = pl.BlockSpec((1, s_blk, hd),
                           lambda bb, kk, ss, lens: (bb, ss, kk))
    qo_spec = pl.BlockSpec((1, 1, g, hd),
                           lambda bb, kk, ss, lens: (bb, kk, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, s_blk=s_blk, blocks=blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nkv, blocks),
            in_specs=[qo_spec, kv_spec, kv_spec],
            out_specs=qo_spec,
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),      # running max m
                pltpu.VMEM((g, 1), jnp.float32),      # running denom l
                pltpu.VMEM((g, hd), jnp.float32),     # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qg, kf, vf)
    return out.reshape(b, nq, hd)
