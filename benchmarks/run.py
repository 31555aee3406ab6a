"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Absolute numbers are CPU-host
numbers; the paper-claim reproduction lives in the RATIO rows (each row's
``derived`` column cites the paper's value).  Run single suites with
``python -m benchmarks.run --only tab3``.

``--json PATH`` additionally writes/merges a ``{name: us_per_call}``
mapping (e.g. ``BENCH_fabric.json``) so successive PRs have a perf
trajectory to regress against; existing keys from other suites are
preserved, re-run suites overwrite their own rows.  Every merge also
stamps a ``_meta`` block recording which backend produced the run
(``{backend, platform, device_count}``) so CPU and accelerator
trajectories don't silently mix; ``scripts/check_docs.py`` ignores
underscore-prefixed keys.

``--accel-profile {cpu,gpu,tpu}`` applies the matching
``repro.config.ACCEL_PROFILES`` environment (x64 off, platform pin,
GPU latency-hiding scheduler / async-collective XLA flags) BEFORE any
suite imports jax, so the same bench commands run unmodified on
GPU/TPU hosts.  All suites run in this one process (a chip belongs to
one process), sharing JAX's persistent compilation cache
(``repro.config.enable_compile_cache``).
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import traceback

SUITES = ["tab3_rpc_platforms", "fig10_interfaces",
          "fig11_latency_throughput", "fig12_kvs",
          "lm_decode_serving", "tab4_flight", "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on suite name")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="merge {name: us_per_call} into this JSON file")
    ap.add_argument("--n-tenants", type=int, default=None,
                    help="tenant-sweep width for suites that take it "
                         "(fig11/fig12 tenant_scaling rows)")
    ap.add_argument("--accel-profile", default=None, metavar="NAME",
                    help="apply repro.config.ACCEL_PROFILES[NAME] env "
                         "setup (cpu/gpu/tpu) before importing jax")
    args = ap.parse_args()
    if args.accel_profile:
        from repro.config import apply_accel_profile
        apply_accel_profile(args.accel_profile)
    from repro.config import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    results = {}
    for suite in SUITES:
        if args.only and args.only not in suite:
            continue
        try:
            mod = __import__(f"benchmarks.{suite}", fromlist=["main"])
            kw = {}
            if args.n_tenants is not None and \
                    "n_tenants" in inspect.signature(mod.main).parameters:
                kw["n_tenants"] = args.n_tenants
            for name, us, derived in mod.main(**kw):
                print(f"{name},{us:.3f},{derived}", flush=True)
                results[name] = round(float(us), 3)
        # suite-isolation boundary: one broken benchmark must not take
        # down the sweep; failure is printed and recorded
        except Exception:  # fabriclint: allow(FL007)
            traceback.print_exc()
            failed.append(suite)
    if args.json:
        merged = {}
        if os.path.exists(args.json):
            try:
                with open(args.json) as f:
                    merged = json.load(f)
            except (json.JSONDecodeError, OSError):
                merged = {}
        merged.update(results)
        # stamp the producing backend so perf trajectories from different
        # hardware never silently mix (underscore keys are ignored by
        # scripts/check_docs.py and the regression tooling)
        import jax
        merged["_meta"] = {
            "backend": jax.default_backend(),
            "platform": jax.devices()[0].platform,
            "device_count": jax.device_count(),
        }
        with open(args.json, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {len(results)} rows to {args.json}",
              file=sys.stderr)
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
