"""Run a cell's control: the configuration's own guarantee broken on
purpose, at the cell's size, on several seeds in one process.

    python3 -m bench.control --workload echo64.poisson80 \\
        --seeds 101,102,103 --seconds 4

The configuration's ``control_kw(name)`` names what its builder swaps
in: ``control`` (for echo64, a handler that skips the +1 for one
request in 64), or a planted fault that reads the upper end of one
number's limit (``--name``).  Every
run prints its result line; each has to come out ``correct: false``,
and the numbers it compared are the control's upper readings.  Runs on
a TPU only.  The benchmark's own runs never run it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--name", default="control",
                    help="which of the configuration's controls")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness
    r = harness.resolve(args.workload)
    builder = harness.load_module(r["builder"])
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = harness.run_cell(args.workload, seed, args.seconds, False,
                                   t0=time.perf_counter(),
                                   build_kw=builder.control_kw(args.name))
        except harness.NoChip as e:
            print(f"bench.control: {e}", file=sys.stderr)
            return 2
        print(json.dumps(dict(seed=seed, correct=out["correct"],
                              checks=out["checks"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
