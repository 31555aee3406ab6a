"""The benchmark's own open-loop generator: one arrival process for every
traffic mix, read from the mix's data file.

It runs inside the fused step, through the engines' ``loadgen=`` hook
(any object with ``inject(client_state, gen_state)``), so the host is not
in the loop.  It follows the Poisson arrival process of the program's
``core/loadgen.py`` (a counter-hash draw per step, inverse-CDF Poisson
truncated at the injection tile), kept here so that a change to the
program cannot move the yardstick; unlike it, the inverse CDF is a table
of 32-bit thresholds made on the host, so the draw is integer-only and
the same on every backend and in the reference.  What a request carries (its
``fn_id`` and payload words) comes from the configuration, which also
holds the plain reference that checks the answers.

Every random draw is a pure hash of ``(key, counter, salt)``: the same
``--seed`` gives the same arrivals, keys and payloads in every run, and
the reference recomputes any request from its lane key and ``rpc_id``
alone.  ``hash32_np`` is the same hash in numpy for it.  Arrivals come
from a fixed set of streams that the seed deals out to the lanes
(``stream_keys``), so every seed offers the same load.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

SALT_ARRIVAL = 1
SALT_OP = 2
SALT_KEY = 3
SALT_WORD = 4
SALT_LANE = 5
SALT_STREAM = 6


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def hash32(key, ctr, salt):
    """uint32 hash of (key, counter, salt), elementwise (jnp)."""
    x = (jnp.asarray(key).astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         ^ jnp.asarray(ctr).astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
         ^ jnp.asarray(salt).astype(jnp.uint32) * jnp.uint32(0xC2B2AE35))
    return _mix32(x)


def hash32_np(key, ctr, salt):
    """``hash32`` in numpy, for the references."""
    with np.errstate(over="ignore"):
        x = (np.asarray(key).astype(np.uint32) * np.uint32(0x9E3779B9)
             ^ np.asarray(ctr).astype(np.uint32) * np.uint32(0x85EBCA6B)
             ^ np.asarray(salt).astype(np.uint32) * np.uint32(0xC2B2AE35))
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(0x7FEB352D)
        x = x ^ (x >> np.uint32(15))
        x = x * np.uint32(0x846CA68B)
        return x ^ (x >> np.uint32(16))


def lane_keys(seed: int, n_lanes: int) -> np.ndarray:
    """int32 key of each lane, from the run's seed (any Python int)."""
    s = int(seed) % (1 << 32)
    keys = hash32_np(np.uint32(s), np.arange(n_lanes, dtype=np.uint32),
                     SALT_LANE)
    return keys.view(np.int32)


def stream_keys(seed: int, n_lanes: int) -> np.ndarray:
    """int32 arrival-stream key of each lane.  The streams are one fixed
    set, the same for every seed, and the seed only deals them out to
    the lanes in another order: lanes do not interact, so every seed
    offers the same arrivals and the tails differ from seed to seed by
    run noise alone, while the payloads and keys (``lane_keys``) differ.
    """
    base = hash32_np(np.uint32(0x5EED), np.arange(n_lanes, dtype=np.uint32),
                     SALT_STREAM)
    order = np.random.default_rng(int(seed) % (1 << 64)).permutation(n_lanes)
    return base[order].view(np.int32)


def poisson_thresholds(rate: float, tile: int) -> np.ndarray:
    """The inverse CDF of Poisson(``rate``) as 32-bit thresholds:
    ``t[k] = floor(P(X <= k) * 2**32)``, clipped to 2**32 - 1.  A step
    draws ``u`` (32 hash bits) and its arrival count is the number of
    ``k`` with ``u > t[k]``, so P(count > k) = 1 - P(X <= k), truncated
    at the injection ``tile``.  Made on the host in float64, so the
    device and the reference draw the same counts."""
    k = np.arange(tile, dtype=np.float64)
    if rate <= 0:
        return np.full(tile, 0xFFFFFFFF, np.uint32)
    logp = -rate + k * np.log(rate) - np.cumsum(
        np.concatenate([[0.0], np.log(np.maximum(k[1:], 1.0))]))
    cdf = np.cumsum(np.exp(logp))
    return np.minimum(np.floor(cdf * 2.0 ** 32), 0xFFFFFFFF).astype(
        np.uint32)


def arrival_counts_np(streams, start: int, stop: int,
                      thresholds) -> np.ndarray:
    """Arrival counts [lanes, stop - start] of lanes with arrival
    streams ``streams`` in each of steps ``start..stop-1`` (the
    reference's copy of the device draw)."""
    keys = np.asarray(streams).view(np.uint32)[:, None]
    out = np.zeros((keys.shape[0], max(stop - start, 0)), np.int64)
    for s0 in range(start, stop, 256):
        steps = np.arange(s0, min(s0 + 256, stop), dtype=np.uint32)
        u = hash32_np(keys, steps[None, :], SALT_ARRIVAL)
        out[:, s0 - start:s0 - start + len(steps)] = (
            u[:, :, None] > thresholds[None, None, :]).sum(axis=2)
    return out


def arrivals_np(streams, start: int, stop: int, thresholds) -> np.ndarray:
    """Arrival counts [lanes] summed over steps ``start..stop-1``."""
    return arrival_counts_np(streams, start, stop, thresholds).sum(axis=1)


ARRIVALS = ("poisson",)


def check_traffic(traffic: dict) -> dict:
    """Refuse a traffic mix whose arrival process this generator does
    not draw, instead of running it as another one."""
    if traffic.get("arrivals") not in ARRIVALS:
        raise ValueError(f"traffic {traffic.get('name')!r}: arrivals "
                         f"{traffic.get('arrivals')!r} not in {ARRIVALS}")
    return traffic


@jax.tree_util.register_dataclass
@dataclass
class GenState:
    """One lane's generator registers (stacked per lane)."""
    key: jnp.ndarray         # payload and key draws
    stream: jnp.ndarray      # arrival draws
    step: jnp.ndarray        # ticks once per fused step
    cdf: jnp.ndarray         # [tile] uint32 Poisson thresholds (the rate)
    next_rpc: jnp.ndarray
    offered: jnp.ndarray     # arrivals drawn
    injected: jnp.ndarray    # accepted by the client TX ring
    dropped: jnp.ndarray     # offered - injected


def init_states(keys, streams, rate: float, tile: int) -> GenState:
    n = len(keys)
    z = jnp.zeros((n,), jnp.int32)
    return GenState(key=jnp.asarray(keys, jnp.int32),
                    stream=jnp.asarray(streams, jnp.int32), step=z,
                    cdf=jnp.asarray(np.tile(poisson_thresholds(rate, tile),
                                            (n, 1))),
                    next_rpc=z, offered=z, injected=z, dropped=z)


def with_rate(gst: GenState, rate: float) -> GenState:
    """The same lanes at another offered rate (a device register: the
    window program is not rebuilt)."""
    n, tile = gst.cdf.shape
    return dataclasses.replace(gst, cdf=jnp.asarray(
        np.tile(poisson_thresholds(rate, tile), (n, 1))))


class Generator:
    """Poisson open-loop arrivals into one client fabric.

    ``requests(key, rpc_id) -> (fn_id [tile], payload [tile, pw])`` is
    the configuration's request maker.  Requests leave on connection
    ``conn`` and are spread round-robin over the client's TX flows.
    """

    def __init__(self, fab, requests, conn: int = 1):
        from repro.core import serdes
        self._make_records = serdes.make_records
        self.fab = fab
        self.tile = fab.cfg.n_flows * fab.cfg.batch_size
        self.requests = requests
        self.conn = conn

    def inject(self, cst, gst: GenState):
        step0 = gst.step
        u = hash32(gst.stream, step0, SALT_ARRIVAL)
        raw = jnp.sum((u > gst.cdf).astype(jnp.int32))
        n = raw
        lane = jnp.arange(self.tile, dtype=jnp.int32)
        rpc_id = gst.next_rpc + lane
        fn_id, payload = self.requests(gst.key, rpc_id)
        flows = rpc_id % self.fab.cfg.n_flows
        recs = self._make_records(
            jnp.full((self.tile,), self.conn, jnp.int32), rpc_id, fn_id,
            jnp.zeros((self.tile,), jnp.int32), payload, timestamp=step0)
        cst, accepted = self.fab.host_tx_enqueue(cst, recs, flows,
                                                 lane < n)
        n_acc = jnp.sum(accepted.astype(jnp.int32))
        return cst, dataclasses.replace(
            gst, step=step0 + 1, next_rpc=gst.next_rpc + n,
            offered=gst.offered + raw, injected=gst.injected + n_acc,
            dropped=gst.dropped + (raw - n_acc))
