"""device_idle_share (layer: device): the share of the traced span in
which no operation ran on the chip, averaged over the chips used."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t.busy_s() / t.window_s())
