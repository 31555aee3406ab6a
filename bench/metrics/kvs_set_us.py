"""kvs_set_us (layer: store): device time per fused step under the scope
``dagger.kvs.set`` (``DeviceKVS.set``: the batch's sort, the slot
arbitration and the writes into the tables) (``bench.scopes``).
Returns nothing where the program names no such scope."""

from bench import scopes


def read(ctx):
    return scopes.per_step_us(ctx, "dagger.kvs.set")
