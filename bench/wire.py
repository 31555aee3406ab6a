"""The RPC slot layout, decoded in numpy for the references.

One RPC is one slot of 32-bit words (the paper's cache-line MTU):
word 0 connection id, word 1 rpc id, word 2 fn_id (low 16 bits) and
flags (high 16; bit 0 = response), word 3 payload bytes and fragment
index, word 4 issue step, words 5.. payload.  The references decode
what the NIC wrote with this, not with the program's own ``serdes``.
"""
from __future__ import annotations

import numpy as np

HEADER_WORDS = 5
FLAG_RESPONSE = 1


def decode(slots) -> dict:
    s = np.asarray(slots)
    w2 = s[..., 2].astype(np.int64)
    return {"conn_id": s[..., 0], "rpc_id": s[..., 1],
            "fn_id": w2 & 0xFFFF, "flags": (w2 >> 16) & 0xFFFF,
            "timestamp": s[..., 4], "payload": s[..., HEADER_WORDS:]}


def drained(ring, head, tail):
    """Completions a client drained that its ring still holds, from a
    kept sample: ring [L, E, W], head/tail [L] cursors of one flow per
    lane.  Slots at logical positions [tail - E, head) were written by
    the NIC and read by the host, and not yet overwritten.  Returns
    (lane [M], slots [M, W])."""
    ring = np.asarray(ring)
    n_lanes, e = ring.shape[:2]
    head = np.asarray(head, np.int64)
    tail = np.asarray(tail, np.int64)
    pos = head[:, None] - e + np.arange(e)[None, :]          # [L, E]
    ok = (pos >= tail[:, None] - e) & (pos >= 0)
    lane = np.broadcast_to(np.arange(n_lanes)[:, None], pos.shape)[ok]
    return lane, ring[lane, pos[ok] % e]
