"""step_device_us (layer: fused step): device busy time in the traced
windows divided by the fused steps they ran (busy averaged over the
chips used)."""


def read(ctx):
    if not ctx["steps"]:
        return None
    return ctx["trace"].busy_s() * 1e6 / ctx["steps"]
