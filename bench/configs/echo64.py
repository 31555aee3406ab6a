"""echo64: Dagger's loopback echo at its smallest message (64-byte RPCs).

Sizes are in ``echo64.json``.  Every lane (NIC slot) is a client/server
NIC pair on ``TenantEngine``; the server's handler answers each request
with its payload plus one, the paper's echo.  Requests carry 11 payload
words, each a hash of the lane key, the rpc id and the word index, so a
reply can be checked against its request without keeping the request.

The plain reference below recomputes each sampled reply from the lane
key and the rpc id it names, in numpy, from the slot words the client
NIC wrote; it imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from bench import loadgen as blg
from bench import wire
from bench.rig import LoopbackRig, fabrics

PAYLOAD_MASK = 0x3FFFFFFF          # keeps payload + 1 inside int32


def payload_words(slot_bytes: int) -> int:
    return slot_bytes // 4 - wire.HEADER_WORDS


def requests_fn(pw: int):
    import jax.numpy as jnp

    def requests(key, rpc_id):
        j = jnp.arange(pw, dtype=jnp.int32)
        words = blg.hash32(key, rpc_id[:, None] * pw + j[None, :],
                           blg.SALT_WORD)
        pay = (words & jnp.uint32(PAYLOAD_MASK)).astype(jnp.int32)
        return jnp.zeros(rpc_id.shape, jnp.int32), pay

    return requests


def echo_handler(recs, valid):
    out = dict(recs)
    out["payload"] = recs["payload"] + 1
    return out


def control_handler(recs, valid):
    """The control: the echo with its work skipped for one request in
    64 (the request's payload comes back unchanged), which breaks the
    configuration's guarantee that every reply is its request plus one."""
    import jax.numpy as jnp
    out = dict(recs)
    skip = (recs["rpc_id"] % 64 == 0)[:, None]
    out["payload"] = jnp.where(skip, recs["payload"],
                               recs["payload"] + 1)
    return out


def build(sizes: dict, traffic: dict, seed: int, handler=echo_handler,
          abstract: bool = False):
    client, server = fabrics(sizes)
    return LoopbackRig(abstract=abstract,
        client=client, server=server, handler=handler,
        n_lanes=sizes["n_tenants"],
        requests=requests_fn(payload_words(sizes["slot_bytes"])),
        rate=traffic["rate_per_lane"],
        steps_per_window=traffic["steps_per_window"],
        n_bins=traffic["lat_bins"], seed=seed)


# ------------------------------------------------------------- reference
def expected_reply(keys, lane, rpc_id, pw: int):
    """The echo's answer: each request word plus one (numpy)."""
    j = np.arange(pw, dtype=np.int64)
    ctr = (rpc_id.astype(np.int64)[:, None] * pw + j[None, :]) & 0xFFFFFFFF
    words = blg.hash32_np(keys[lane][:, None], ctr, blg.SALT_WORD)
    return (words & np.uint32(PAYLOAD_MASK)).astype(np.int64) + 1


def check_answers(sizes: dict, keys, next_rpc, samples):
    """Compare every reply in the kept samples with the reference.
    Returns (replies checked, replies that differ)."""
    pw = payload_words(sizes["slot_bytes"])
    seen, bad = set(), 0
    for ring, head, tail in samples:
        lane, slots = wire.drained(ring, head, tail)
        r = wire.decode(slots)
        rpc = r["rpc_id"].astype(np.int64)
        fresh = np.array([(int(a), int(b)) not in seen
                          for a, b in zip(lane, rpc)], bool)
        seen.update(zip(lane.tolist(), rpc.tolist()))
        lane, rpc = lane[fresh], rpc[fresh]
        r = {k: v[fresh] for k, v in r.items()}
        known = (rpc >= 0) & (rpc < np.asarray(next_rpc)[lane])
        want = expected_reply(keys, lane, np.where(known, rpc, 0), pw)
        ok = (known & (r["conn_id"] == LoopbackRig.conn)
              & ((r["flags"] & wire.FLAG_RESPONSE) != 0)
              & np.all(r["payload"][:, :pw].astype(np.int64) == want,
                       axis=1))
        bad += int((~ok).sum())
    return len(seen), bad


def check(sizes: dict, traffic: dict, ledger: dict, keys, samples) -> dict:
    from bench.rig import ledger_checks
    checked, bad = check_answers(sizes, keys, ledger["next_rpc"], samples)
    out = ledger_checks(ledger)
    out["bad_answers"] = {"value": bad, "limit": 0}
    out["answers_checked"] = {"value": checked,
                              "at_least": traffic["min_answers_checked"]}
    return out


def control_kw(name: str = "control") -> dict:
    return {"handler": {"control": control_handler}[name]}
