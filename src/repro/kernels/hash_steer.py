"""Pallas kernel: FNV-1a object-level load balancer (MICA steering, §5.7).

The paper instantiates an application-specific load balancer inside the
NIC that hashes each request's key so all requests for a key reach the
CPU core owning that MICA partition.  Here the hash runs as a vectorized
VPU kernel over the request tile: 8 multiply-xor rounds per key word,
fully unrolled, no MXU involvement.

BlockSpec: requests are tiled along N, which runs along lanes — each
block loads the transposed key words ``[key_words, tile_n]`` of
``tile_n`` requests into VMEM and emits their flow assignment as one
``[1, tile_n]`` row.  The hash runs in int32 lanes (same bits as the
uint32 FNV-1a, logical shifts), the unsigned modulo by halving.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

FNV_OFFSET = 0x811C9DC5
FNV_PRIME = 0x01000193
_LANE_TILE = 128


def _shr(x, k: int):
    return jax.lax.shift_right_logical(x, jnp.int32(k))


def _kernel(keys_ref, out_ref, *, key_words: int, n_flows: int):
    k = keys_ref[...]                                # [key_words, tile]
    h = jnp.full((1, k.shape[1]), FNV_OFFSET - (1 << 32), jnp.int32)
    for i in range(key_words):
        w = k[i:i + 1, :]
        for shift in (0, 8, 16, 24):
            h = (h ^ (_shr(w, shift) & 0xFF)) * jnp.int32(FNV_PRIME)
    if n_flows == 0:                                 # raw-hash mode
        out_ref[...] = h
    else:                                            # h mod n, h as uint32
        out_ref[...] = ((_shr(h, 1) % n_flows) * 2 + (h & 1)) % n_flows


@functools.partial(jax.jit,
                   static_argnames=("n_flows", "key_words", "tile_n",
                                    "interpret"))
def hash_steer_static(payload, n_flows: int, key_words: int = 2,
                      tile_n: int = 256, *, interpret: bool):
    """payload: [N, W] int32 -> flow [N] int32 (static flow count)."""
    n = payload.shape[0]
    tile = n if n <= tile_n else max(_LANE_TILE,
                                     tile_n // _LANE_TILE * _LANE_TILE)
    pad = (-n) % tile
    keys = jnp.pad(payload[:, :key_words].T, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        functools.partial(_kernel, key_words=key_words, n_flows=n_flows),
        grid=((n + pad) // tile,),
        in_specs=[pl.BlockSpec((key_words, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n + pad), jnp.int32),
        interpret=interpret,
    )(keys)
    return out[0, :n]


def hash_steer(payload, active_flows, *, interpret: bool):
    """Dynamic-flow-count wrapper: raw hash via the kernel, modulo outside
    (active_flows is *soft* configuration — a traced scalar)."""
    h = hash_steer_static(payload, 0, interpret=interpret)  # raw hash
    hu = jax.lax.bitcast_convert_type(h, jnp.uint32)
    return (hu % jnp.asarray(active_flows, jnp.uint32)).astype(jnp.int32)
