"""Find a cell's knee: offered rate against what the cell completes.

    python3 -m bench.sweep --workload echo64.poisson80 --seed 3 \\
        --rates 2,3,3.6,4,4.4 --seconds 3

One process builds the cell's rig once and offers each rate in turn
(the generator's rate is a device register, so nothing recompiles),
draining the queues between rates.  Per rate it prints one JSON line:
offered and completed RPCs per step per lane, drops, what is still in
flight at the end (a queue that grows), and the median and p99
residency in fabric steps.  The knee is the highest rate that completes
what it is offered with no drops and a bounded queue; a cell's fixed
rate is four fifths of it.  Runs on a TPU only.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def sweep(workload, seed, rates, seconds):
    import numpy as np

    from bench import harness, latency
    r = harness.resolve(workload)
    harness.check_chip(r["cell"]["chips"])
    rig = harness.load_module(r["builder"]).build(r["sizes"], r["traffic"],
                                                  seed)
    rig.window()                       # compiles; not one of the rates
    rig.read()
    rows = []
    for rate in rates:
        rig.drain()
        hist = np.asarray(rig.tel.hist, np.int64).sum(axis=0)
        rig.set_rate(rate)
        before = rig.ledger()
        merged = latency.Merged(rig.n_bins)
        t_start = time.perf_counter()
        t_end, n_win = t_start + seconds, 0
        while time.perf_counter() < t_end:
            rig.window()
            _, h = rig.read()
            merged.add(h - hist, 1.0)      # (L + U) in steps
            hist = h
            n_win += 1
        wall = time.perf_counter() - t_start
        after = rig.ledger()
        steps = n_win * rig.k * rig.n_lanes
        row = dict(rate=rate, windows=n_win, wall_s=wall,
                   steps_per_s=n_win * rig.k / wall,
                   offered_per_step=(after["offered"] - before["offered"])
                   / steps,
                   completed_per_step=(after["completed"]
                                       - before["completed"]) / steps,
                   gen_dropped=after["gen_dropped"] - before["gen_dropped"],
                   fabric_drops=after["fabric_drops"]
                   - before["fabric_drops"],
                   in_flight_end=after["in_flight"])
        for q in (0.5, 0.99):
            try:
                row[f"q{q}_steps"] = merged.quantile(q)
            except latency.Overflow:
                row[f"q{q}_steps"] = "overflow"
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness
    try:
        sweep(args.workload, args.seed,
              [float(x) for x in args.rates.split(",")], args.seconds)
    except harness.NoChip as e:
        print(f"bench.sweep: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
