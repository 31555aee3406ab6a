"""Public wrappers for the Pallas kernels.

Each call decides how its kernel runs, at call (trace) time and never at
import: compiled by Mosaic when JAX's default backend is a TPU, and
through the Pallas interpreter anywhere else (the CPU test suite).
``interpret()`` is that one decision.
"""
from __future__ import annotations

import jax

from repro.kernels.decode_attn import decode_attention as _decode_attention
from repro.kernels.hash_steer import hash_steer as _hash_steer
from repro.kernels.hash_steer import hash_steer_static as _hash_steer_static
from repro.kernels.kv_probe import probe as _kv_probe
from repro.kernels.nic_deliver import nic_deliver_fused as _nic_deliver_fused
from repro.kernels.ring_copy import ring_gather as _ring_gather
from repro.kernels.ring_push import ring_push as _ring_push
from repro.kernels.rpc_pack import rpc_pack as _rpc_pack
from repro.kernels.switch_step import switch_step_fused as _switch_step_fused


def interpret() -> bool:
    """True unless the kernels will run on a TPU."""
    return jax.default_backend() != "tpu"


def ring_gather(table, refs):
    return _ring_gather(table, refs, interpret=interpret())


def ring_push(buf, queue_ids, pos, slots):
    return _ring_push(buf, queue_ids, pos, slots, interpret=interpret())


def nic_deliver_fused(slots, valid, fifo, req_table, ffbuf, conn_tag,
                      conn_src, conn_lb, fftail, ffspace, scal, **kw):
    return _nic_deliver_fused(slots, valid, fifo, req_table, ffbuf,
                              conn_tag, conn_src, conn_lb, fftail, ffspace,
                              scal, interpret=interpret(), **kw)


def switch_step_fused(tx_buf, tx_head, tx_tail, rx_buf, rx_head, rx_tail,
                      req_table, fifo, ffbuf, ff_head, ff_tail, conn_tag,
                      conn_src, conn_dest, conn_lb, scal, hist, ext_slots,
                      ext_valid, ext_dest, bmax, **kw):
    return _switch_step_fused(tx_buf, tx_head, tx_tail, rx_buf, rx_head,
                              rx_tail, req_table, fifo, ffbuf, ff_head,
                              ff_tail, conn_tag, conn_src, conn_dest,
                              conn_lb, scal, hist, ext_slots, ext_valid,
                              ext_dest, bmax, interpret=interpret(), **kw)


def hash_steer(payload, active_flows):
    return _hash_steer(payload, active_flows, interpret=interpret())


def hash_steer_static(payload, n_flows, **kw):
    return _hash_steer_static(payload, n_flows, interpret=interpret(), **kw)


def kv_probe(tags, keys, values, q_bucket, q_tag, q_key, **kw):
    return _kv_probe(tags, keys, values, q_bucket, q_tag, q_key,
                     interpret=interpret(), **kw)


def rpc_pack(conn_id, rpc_id, fn_id, flags, payload_len, frag_idx,
             timestamp, payload, slot_words, **kw):
    return _rpc_pack(conn_id, rpc_id, fn_id, flags, payload_len, frag_idx,
                     timestamp, payload, slot_words, interpret=interpret(),
                     **kw)


def decode_attention(q, k, v, length, **kw):
    return _decode_attention(q, k, v, length, interpret=interpret(), **kw)
