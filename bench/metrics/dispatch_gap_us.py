"""dispatch_gap_us (layer: engine): the mean time the chip sat idle
between the end of one window's program and the start of the next
one's, over the traced windows (chip 0)."""

from bench.trace import union


def read(ctx):
    t = ctx["trace"]
    runs = t.module_runs(0, ctx["rig"].window_program)
    if len(runs) < 2:
        return None
    ops = [(s, e) for _, s, e in t.chips[0].ops]
    idle = [(s1 - e0) - union(ops, e0, s1)
            for (_, e0), (s1, _) in zip(runs, runs[1:])]
    return sum(idle) / len(idle) * 1e-3
