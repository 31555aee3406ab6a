"""nic_us (layer: NIC pipeline): device time per fused step under the
NIC stages' scopes (``dagger.nic.enqueue``, ``.fetch``, ``.deliver``,
``.emit``, ``.drain``) of both NICs, the rings they write included
(``bench.scopes``).  Returns nothing where the program names no such
scope."""

from bench import scopes


def read(ctx):
    return scopes.per_step_us(ctx, "dagger.nic.")
