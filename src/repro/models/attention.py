"""Attention variants: GQA (full / sliding-window / cross) and MLA.

Three execution paths per variant:

* ``train/prefill`` — full-sequence attention; prefill also returns the KV
  cache for subsequent decode steps.
* ``decode`` — one new token against a cache of ``seq_len`` entries.  GQA
  reads the (masked) cache; sliding-window layers slice only the last
  ``window`` entries (this is what makes gemma-style 5:1 local:global decode
  sub-linear in total cache reads).  MLA decode uses the *absorbed* DeepSeek
  formulation: scores are computed directly in the compressed-KV latent
  space, so per-step work is O(S * kv_lora_rank) instead of
  O(S * n_heads * head_dim).

Sliding-window prefill uses chunked (banded) attention — true O(S * W)
compute, not a masked O(S^2) — so the roofline FLOPs of local layers are
honest.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import dense_init, norm_init, norm_apply, _dtype
from repro.models.rope import apply_rope

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, nq * hd), dt),
        "wk": dense_init(ks[1], (d, nkv * hd), dt),
        "wv": dense_init(ks[2], (d, nkv * hd), dt),
        "wo": dense_init(ks[3], (nq * hd, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
    return p


def _qkv(cfg: ModelConfig, p, x, xkv=None):
    hd = cfg.resolved_head_dim
    # head counts derived from the weight shapes, not the config, so the
    # same code runs on tensor-parallel shards inside shard_map (local
    # wq/wk columns are n_heads/tp * hd wide; cfg keeps global counts)
    nq, nkv = p["wq"].shape[-1] // hd, p["wk"].shape[-1] // hd
    xkv = x if xkv is None else xkv
    q = x @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(*x.shape[:-1], nq, hd)
    k = k.reshape(*xkv.shape[:-1], nkv, hd)
    v = v.reshape(*xkv.shape[:-1], nkv, hd)
    return q, k, v


def _sdpa(cfg: ModelConfig, q, k, v, mask):
    """q: [B,S,nq,hd]; k,v: [B,T,nkv,hd]; mask: broadcastable [B,1,1,S,T]."""
    b, s, nq, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    qg = q.reshape(b, s, nkv, g, hd)
    if cfg.fast_attn:
        # accumulate in f32 WITHOUT materializing f32 copies of K/V —
        # halves the HBM read volume of decode-time cache streaming
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                            preferred_element_type=jnp.float32) * (hd ** -0.5)
    else:
        scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * (hd ** -0.5)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        scores = jnp.tanh(scores / c) * c
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    if cfg.fast_attn:
        out = jnp.einsum("bkgst,btkd->bskgd", w.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.reshape(b, s, nq, hd).astype(q.dtype)


def _causal_mask(s: int, t: int, q_offset: int = 0):
    qpos = jnp.arange(s)[:, None] + q_offset
    kpos = jnp.arange(t)[None, :]
    return (kpos <= qpos)[None, None, None]


def _flash_sdpa(q, k, v, block: int, causal: bool = True,
                softcap: float = 0.0):
    """Online-softmax attention, scanning KV blocks: O(S*block) live
    memory instead of O(S^2) materialized scores.

    q: [B,S,nq,hd]; k,v: [B,T,nkv,hd] (nq % nkv == 0).  Pure-JAX flash —
    on TPU the same schedule fuses into VMEM tiles; here it bounds the
    HLO temp footprint, which is what §Roofline measures.
    """
    b, s, nq, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    vd = v.shape[-1]                       # MLA: v head dim != qk head dim
    g = nq // nkv
    block = min(block, t)
    assert t % block == 0, f"T={t} not a multiple of flash block {block}"
    nb = t // block
    qg = q.reshape(b, s, nkv, g, hd).astype(jnp.float32)
    kb = k.reshape(b, nb, block, nkv, hd).swapaxes(0, 1)
    vb = v.reshape(b, nb, block, nkv, vd).swapaxes(0, 1)
    qpos = jnp.arange(s)

    def body(carry, inp):
        m, l, acc = carry
        idx, kc, vc = inp
        sc = jnp.einsum("bskgd,btkd->bkgst", qg, kc.astype(jnp.float32))
        sc = sc * (hd ** -0.5)
        if softcap > 0:
            sc = jnp.tanh(sc / softcap) * softcap
        if causal:
            kpos = idx * block + jnp.arange(block)
            mask = (kpos[None, :] <= qpos[:, None])[None, None, None]
            sc = jnp.where(mask, sc, NEG_INF)
        m_c = jnp.max(sc, axis=-1)
        m_new = jnp.maximum(m, m_c)
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = (acc * alpha[..., None]
                   + jnp.einsum("bkgst,btkd->bkgsd", p,
                                vc.astype(jnp.float32)))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, nkv, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nkv, g, s), jnp.float32)
    a0 = jnp.zeros((b, nkv, g, s, vd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(nb), kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, nq, vd)
    return out.astype(q.dtype)


def gqa_full(cfg: ModelConfig, p, x, positions, causal=True, xkv=None):
    """Full (global) attention; cross-attention when xkv is given."""
    q, k, v = _qkv(cfg, p, x, xkv)
    if xkv is None:  # self-attention -> rope both
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.flash_block and causal and xkv is None \
            and q.shape[1] > cfg.flash_block:
        out = _flash_sdpa(q, k, v, cfg.flash_block,
                          softcap=cfg.logit_softcap)
    else:
        mask = (_causal_mask(q.shape[1], k.shape[1])
                if causal and xkv is None else None)
        out = _sdpa(cfg, q, k, v, mask)
    return out.reshape(*x.shape[:-1], -1) @ p["wo"], (k, v)


def gqa_local(cfg: ModelConfig, p, x, positions):
    """Sliding-window causal attention, chunked: O(S * 2W) compute."""
    w = cfg.local_window
    b, s_orig, d = x.shape
    if s_orig > w and s_orig % w:          # pad tail to a window multiple
        pad = w - s_orig % w
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        positions = jnp.pad(positions, ((0, 0), (0, pad)))
        out, (k, v) = gqa_local(cfg, p, x, positions)
        return out[:, :s_orig], (k[:, :s_orig], v[:, :s_orig])
    s = x.shape[1]
    q, k, v = _qkv(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if s <= w:  # degenerate: plain causal
        out = _sdpa(cfg, q, k, v, _causal_mask(s, s))
        return out.reshape(b, s, -1) @ p["wo"], (k, v)
    nc = s // w
    nq, nkv, hd = q.shape[2], k.shape[2], q.shape[3]
    qc = q.reshape(b, nc, w, nq, hd)
    # keys/values for chunk i: chunks [i-1, i] (window <= w lookback)
    kc = k.reshape(b, nc, w, nkv, hd)
    vc = v.reshape(b, nc, w, nkv, hd)
    k_prev = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], axis=1)
    v_prev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    k2 = jnp.concatenate([k_prev, kc], axis=2)        # [b,nc,2w,nkv,hd]
    v2 = jnp.concatenate([v_prev, vc], axis=2)
    qpos = jnp.arange(w)[:, None] + w                 # within [w, 2w)
    kpos = jnp.arange(2 * w)[None, :]
    band = (kpos <= qpos) & (kpos > qpos - w)
    first = jnp.arange(nc) == 0                       # first chunk: no prev
    valid = kpos >= w
    mask = jnp.where(first[:, None, None], band & valid, band)
    mask = mask.reshape(1, nc, 1, 1, w, 2 * w)        # -> [b,c,k,g,s,t]
    g = nq // nkv
    qg = qc.reshape(b, nc, w, nkv, g, hd)
    scores = jnp.einsum("bcskgd,bctkd->bckgst", qg.astype(jnp.float32),
                        k2.astype(jnp.float32)) * (hd ** -0.5)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        scores = jnp.tanh(scores / c) * c
    scores = jnp.where(mask, scores, NEG_INF)
    wts = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bckgst,bctkd->bcskgd", wts, v2.astype(jnp.float32))
    out = out.reshape(b, s, nq * hd).astype(x.dtype)
    return out @ p["wo"], (k, v)


def pos_vec(pos, b):
    """Broadcast a scalar or per-row decode position to [B] int32."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))


def gqa_decode(cfg: ModelConfig, p, x, cache_k, cache_v, pos):
    """One-token decode. x: [B,1,d]; cache_[kv]: [B,Smax,nkv,hd];
    pos: scalar or per-row [B] (continuous batching)."""
    b = x.shape[0]
    q, k, v = _qkv(cfg, p, x)
    pv = pos_vec(pos, b)
    q = apply_rope(q, pv[:, None], cfg.rope_theta)
    k = apply_rope(k, pv[:, None], cfg.rope_theta)
    rows = jnp.arange(b)
    cache_k = cache_k.at[rows, pv].set(k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[rows, pv].set(v[:, 0].astype(cache_v.dtype))
    s = cache_k.shape[1]
    if (cfg.use_pallas and cfg.logit_softcap == 0
            and s % min(256, s) == 0):
        # flash-decoding Pallas kernel with a per-row length, so each
        # slot attends its OWN valid prefix (continuous batching); the
        # jnp branch below is the oracle (tests/test_kernels.py)
        from repro.kernels import ops as kops
        out = kops.decode_attention(q[:, 0], cache_k, cache_v, pv + 1)
        out = out[:, None].astype(q.dtype)
    else:
        mask = (jnp.arange(s)[None, :] <= pv[:, None])
        mask = mask[:, None, None, None, :]
        out = _sdpa(cfg, q, cache_k, cache_v, mask)
    return out.reshape(b, 1, -1) @ p["wo"], (cache_k, cache_v)


def gqa_cross_decode(cfg: ModelConfig, p, x, cross_k, cross_v):
    """Decode-time cross attention against precomputed encoder K/V."""
    q, _, _ = _qkv(cfg, p, x)   # recomputing k,v is avoided below
    out = _sdpa(cfg, q, cross_k, cross_v, None)
    return out.reshape(*x.shape[:-1], -1) @ p["wo"]


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig):
    m = cfg.mla
    d, nq = cfg.d_model, cfg.n_heads
    dt = _dtype(cfg)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "w_dq": dense_init(ks[0], (d, m.q_lora_rank), dt),
        "q_norm": norm_init(cfg, m.q_lora_rank),
        "w_uq": dense_init(ks[1], (m.q_lora_rank, nq * qk), dt),
        "w_dkv": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dt),
        "kv_norm": norm_init(cfg, m.kv_lora_rank),
        "w_ukv": dense_init(ks[3], (m.kv_lora_rank,
                                    nq * (m.qk_nope_head_dim + m.v_head_dim)), dt),
        "wo": dense_init(ks[4], (nq * m.v_head_dim, d), dt),
    }


def _mla_q(cfg: ModelConfig, p, x):
    m = cfg.mla
    nq = cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ql = norm_apply(cfg, p["q_norm"], x @ p["w_dq"])
    q = (ql @ p["w_uq"]).reshape(*x.shape[:-1], nq, qk)
    return jnp.split(q, [m.qk_nope_head_dim], axis=-1)  # q_nope, q_pe


def _mla_ckv(cfg: ModelConfig, p, x, positions):
    m = cfg.mla
    dkv = x @ p["w_dkv"]
    c_kv, k_pe = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    c_kv = norm_apply(cfg, p["kv_norm"], c_kv)
    k_pe = apply_rope(k_pe[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return c_kv, k_pe


def mla_full(cfg: ModelConfig, p, x, positions):
    """Train/prefill MLA: expand compressed KV and run standard attention."""
    m = cfg.mla
    nq = cfg.n_heads
    b, s, _ = x.shape
    q_nope, q_pe = _mla_q(cfg, p, x)
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    c_kv, k_pe = _mla_ckv(cfg, p, x, positions)
    kv = (c_kv @ p["w_ukv"]).reshape(b, s, nq, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k_pe_b = jnp.broadcast_to(k_pe[:, :, None, :], (b, s, nq, m.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe_b], axis=-1)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    # NOTE: MLA scale is 1/sqrt(qk); _sdpa/_flash use 1/sqrt(q.shape[-1])
    # which equals qk here, so both paths apply the right scale.
    if cfg.flash_block and s > cfg.flash_block:
        out = _flash_sdpa(q, k, v, cfg.flash_block)
    else:
        scale = qk ** -0.5
        scores = jnp.einsum("bsnd,btnd->bnst", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        scores = jnp.where(_causal_mask(s, s)[0], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bnst,btnd->bsnd", w, v.astype(jnp.float32))
    out = out.reshape(b, s, -1).astype(x.dtype) @ p["wo"]
    return out, (c_kv, k_pe)


def mla_decode(cfg: ModelConfig, p, x, cache_ckv, cache_kpe, pos):
    """Absorbed-matrix MLA decode: score and aggregate in latent space.

    cache_ckv: [B,Smax,r]; cache_kpe: [B,Smax,rope].  Per-step compute is
    O(S * (r + rope) * nq) with NO per-head K/V expansion over S.
    """
    m = cfg.mla
    nq = cfg.n_heads
    b = x.shape[0]
    pv = pos_vec(pos, b)
    positions = pv[:, None]
    q_nope, q_pe = _mla_q(cfg, p, x)                   # [b,1,nq,*]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    c_kv, k_pe = _mla_ckv(cfg, p, x, positions)        # [b,1,r], [b,1,rope]
    rows = jnp.arange(b)
    cache_ckv = cache_ckv.at[rows, pv].set(c_kv[:, 0].astype(cache_ckv.dtype))
    cache_kpe = cache_kpe.at[rows, pv].set(k_pe[:, 0].astype(cache_kpe.dtype))
    w_uk, w_uv = jnp.split(
        p["w_ukv"].reshape(m.kv_lora_rank, nq, -1), [m.qk_nope_head_dim], axis=-1)
    # absorb: q_c[b,1,nq,r] = q_nope @ w_uk^T
    q_c = jnp.einsum("bsnd,rnd->bsnr", q_nope.astype(jnp.float32),
                     w_uk.astype(jnp.float32))
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if cfg.fast_attn:
        # stream the compressed cache once in its storage dtype
        s_c = jnp.einsum("bsnr,btr->bnst", q_c.astype(cache_ckv.dtype),
                         cache_ckv, preferred_element_type=jnp.float32)
        s_pe = jnp.einsum("bsnd,btd->bnst", q_pe, cache_kpe,
                          preferred_element_type=jnp.float32)
    else:
        s_c = jnp.einsum("bsnr,btr->bnst", q_c,
                         cache_ckv.astype(jnp.float32))
        s_pe = jnp.einsum("bsnd,btd->bnst", q_pe.astype(jnp.float32),
                          cache_kpe.astype(jnp.float32))
    scores = (s_c + s_pe) * scale
    mask = (jnp.arange(cache_ckv.shape[1])[None, :]
            <= pv[:, None])[:, None, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    if cfg.fast_attn:
        ctx = jnp.einsum("bnst,btr->bsnr", w.astype(cache_ckv.dtype),
                         cache_ckv, preferred_element_type=jnp.float32)
    else:
        ctx = jnp.einsum("bnst,btr->bsnr", w,
                         cache_ckv.astype(jnp.float32))
    out = jnp.einsum("bsnr,rnd->bsnd", ctx, w_uv.astype(jnp.float32))
    out = out.reshape(b, 1, -1).astype(x.dtype) @ p["wo"]
    return out, (cache_ckv, cache_kpe)
