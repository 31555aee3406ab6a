"""ring_push_us (layer: rings): device time per fused step under the
scope ``dagger.ring.push`` (``Ring.push``, the scatter of slots into a
ring), wherever it is nested: the host's enqueue, the flow FIFOs and
the RX rings of both NICs (``bench.scopes``).  Returns nothing where
the program names no such scope."""

from bench import scopes


def read(ctx):
    return scopes.per_step_us(ctx, "dagger.ring.push")
