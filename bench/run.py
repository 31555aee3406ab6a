"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload echo64.poisson80 --seed 7 \\
        --seconds 10 --trace 0

Run from the root of a checkout.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number the correctness check
compared, beside its limit.  The same numbers are the last lines on
standard error.  Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits 2.  JAX's persistent compilation
cache lives in ``<checkout>/.jax_cache``.
"""
import time

_T0 = time.perf_counter()             # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program under test is missing ({src}/repro)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the TPU runtime otherwise logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t0=_T0)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 2
    print(harness.checks_text(result["checks"]), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {json.dumps(c)}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
