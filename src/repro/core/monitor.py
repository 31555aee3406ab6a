"""Packet Monitor — networking statistics counters (paper Fig. 6).

Functional counter block threaded through the fabric pipeline.  Counters
are device scalars so they update inside the fused step and can be read
out cheaply by the host for soft-reconfiguration decisions (e.g. the
dynamic batching policy reads the ingest rate).
"""
from __future__ import annotations

import jax.numpy as jnp

COUNTERS = (
    "rpcs_ingested",      # accepted into the TX request buffer
    "rpcs_emitted",       # sent to the transport
    "rpcs_delivered",     # written into RX rings
    "rpcs_completed",     # drained by the host / completion queue
    "drops_no_slot",      # request buffer exhausted
    "drops_fifo_full",    # flow FIFO exhausted
    "drops_rx_full",      # RX ring exhausted
    "drops_tx_full",      # TX ring rejected a host/loadgen enqueue
    "drops_exchange",     # compacted cross-shard bucket overflowed
    "batches_emitted",
    # ring occupancy summed over step ends (``DaggerFabric.count_queues``):
    # each RPC in flight adds one per step it waits in that kind of ring
    "queued_tx",          # host -> NIC TX rings
    "queued_fifo",        # flow FIFOs (request buffer references)
    "queued_rx",          # NIC -> host RX rings
)


def create():
    return {k: jnp.int32(0) for k in COUNTERS}


def bump(mon, **deltas):
    out = dict(mon)
    for k, v in deltas.items():
        out[k] = out[k] + jnp.asarray(v, jnp.int32)
    return out


def snapshot(mon):
    """Host-side readout."""
    return {k: int(v) for k, v in mon.items()}
