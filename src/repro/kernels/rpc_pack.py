"""Pallas kernel: RPC serialization (the RPC unit's serdes stage, §4.5).

Packs structured field arrays into wire slots — header word assembly is
bit-twiddling on the VPU; the payload copy is a straight VMEM move.  The
paper's serdes handles "ready-to-use RPC objects" with no pointer chasing
(its stated simplification), which is exactly this fixed-layout pack.

BlockSpec: tile along N, which runs along lanes — the kernel assembles
slots transposed (``[slot_words, tile_n]``: one header field or payload
word per sublane row), since a slot is narrower than the 128-lane tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.serdes import HEADER_WORDS

_LANE_TILE = 128


def _kernel(fields_ref, payload_ref, out_ref):
    conn, rpc, fn, flags, plen, frag, ts = (fields_ref[pl.ds(i, 1), :]
                                            for i in range(7))
    out_ref[pl.ds(0, 1), :] = conn
    out_ref[pl.ds(1, 1), :] = rpc
    out_ref[pl.ds(2, 1), :] = (fn & 0xFFFF) | (flags << 16)
    # word 3 carries BOTH halves: byte length low, fragment index high
    # (masking to the low 16 bits here zeroed every fragment index)
    out_ref[pl.ds(3, 1), :] = (plen & 0xFFFF) | ((frag & 0xFFFF) << 16)
    # word 4: the issue-step timestamp the telemetry layer subtracts
    out_ref[pl.ds(4, 1), :] = ts
    out_ref[pl.ds(HEADER_WORDS, payload_ref.shape[0]), :] = payload_ref[...]


@functools.partial(jax.jit, static_argnames=("slot_words", "tile_n",
                                             "interpret"))
def rpc_pack(conn_id, rpc_id, fn_id, flags, payload_len, frag_idx,
             timestamp, payload, slot_words: int, tile_n: int = 256, *,
             interpret: bool):
    """Field arrays [N] + payload [N, pw] -> slots [N, slot_words]."""
    n = conn_id.shape[0]
    pw = slot_words - HEADER_WORDS
    if payload.shape[1] < pw:
        payload = jnp.pad(payload, ((0, 0), (0, pw - payload.shape[1])))
    payload = payload[:, :pw]
    # one block when N fits a tile; else lane-aligned tiles along N
    tile = n if n <= tile_n else max(_LANE_TILE,
                                     tile_n // _LANE_TILE * _LANE_TILE)
    pad = (-n) % tile
    fields = jnp.stack([conn_id, rpc_id, fn_id, flags, payload_len,
                        frag_idx, timestamp]).astype(jnp.int32)
    fields = jnp.pad(fields, ((0, 0), (0, pad)))
    payload_t = jnp.pad(payload.T, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        _kernel,
        grid=((n + pad) // tile,),
        in_specs=[pl.BlockSpec((7, tile), lambda i: (0, i)),
                  pl.BlockSpec((pw, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((slot_words, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((slot_words, n + pad), jnp.int32),
        interpret=interpret,
    )(fields, payload_t)
    return out[:, :n].T
