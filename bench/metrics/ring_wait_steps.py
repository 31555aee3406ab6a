"""ring_wait_steps (layer: NIC queues): the steps an RPC waits in rings,
on average: the packet monitors' queue counters (``queued_tx``,
``queued_fifo``, ``queued_rx``, each ring's occupancy summed over step
ends) of both NICs of every lane, over the RPCs the client NICs
completed (``rpcs_completed``).  An RPC of residency L waits at L - 1
step ends, each in one ring, so this reads the mean residency less one
(plus the waits of the RPCs still in flight, over all completions).

The counters are read from the rig's state after the traced windows,
as totals since the state was built (the warm windows and the traced
ones): the trace context holds no reading from before the traced
windows.  Counters are int32 per lane and read modulo 2^32.  Returns
nothing where the program keeps no queue counters."""

import numpy as np

QUEUES = ("queued_tx", "queued_fifo", "queued_rx")


def _total(counter) -> int:
    return int(np.asarray(counter).astype(np.uint32).astype(np.int64).sum())


def read(ctx):
    import jax
    rig = ctx["rig"]
    cmon, smon = jax.device_get((rig.cst.mon, rig.sst.mon))
    if not all(q in cmon for q in QUEUES):
        return None
    done = _total(cmon["rpcs_completed"])
    if not done:
        return None
    return sum(_total(mon[q]) for mon in (cmon, smon) for q in QUEUES) \
        / done
