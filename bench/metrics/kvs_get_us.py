"""kvs_get_us (layer: store): device time per fused step under the scope
``dagger.kvs.get`` (``DeviceKVS.get``): the probe, on the ``kv_probe``
kernel or on its jnp route, with the conversions and slices around it
(``bench.scopes``).  Returns nothing where the program names no such
scope."""

from bench import scopes


def read(ctx):
    return scopes.per_step_us(ctx, "dagger.kvs.get")
