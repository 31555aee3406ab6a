"""Trace a few windows of a cell on the chip and split their device time
by the program's scopes; optionally keep the trace for the benchmark's
tests.

    python3 -m bench.record --workload echo64.poisson80 --seed 4242 \\
        --windows 3 --steps 2 --out recordings --name echo64_scoped

The cell's rig is built at the configuration's sizes (``--sizes`` JSON
overrides some, ``--steps`` the fused steps per window), runs two warm
windows, then ``--windows`` windows inside a ``bench.trace.Recording``
with the harness's own host spans.  The last line of standard output is
one JSON object: the scope split of chip 0 (``bench.scopes``: busy
seconds, scoped share, the largest self times, inclusive time per scope
and per fused step), the value of each per-layer metric the cell lists,
the seconds of the program's dispatch spans and the longest idle gaps
named by them, and the steps an RPC waited in rings over the traced
windows alone, by the queue counters and by the residency histogram.
With ``--out`` it also writes there ``<name>.xplane.pb.gz`` (the
trace), ``<name>.scopes.json.gz`` (the scope path of every instruction
the trace ran) and ``<name>.json`` (what was run).  Without a TPU it
exits 2.
"""
import argparse
import gzip
import json
import os
import shutil
import sys
from pathlib import Path

from bench import scopes

ROOT = Path(__file__).resolve().parents[1]


def traced_windows(rig, n: int, trace_dir: Path):
    """Run ``n`` windows inside a Recording, spans as the harness's
    measured loop names them; returns the trace file's path."""
    from jax.profiler import TraceAnnotation

    from bench import trace as btrace
    with btrace.Recording(trace_dir) as recording:
        for _ in range(n):
            with TraceAnnotation("bench.dispatch"):
                rig.window()
            with TraceAnnotation("bench.counter_read"):
                rig.read()
    return recording.path()


def program_spans(path: str) -> list:
    """(name, start, end) of the program's own host spans (``dagger.``,
    ``telemetry.SPAN_ENGINE_DISPATCH``) in a trace file."""
    from jax.profiler import ProfileData
    return [(e.name, int(e.start_ns), int(e.end_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("dagger.")]


def idle_gaps_program(reduced, spans, top: int = 10) -> list:
    """The longest idle gaps of chip 0, each named by the program span
    that covers most of it ("untraced host work" where none does)."""
    from bench import trace as btrace
    named = btrace.Reduced(reduced.t0, reduced.t1, reduced.chips, spans)
    gp = sorted(reduced.idle_gaps(0), key=lambda g: g[0] - g[1])[:top]
    return [[named.span_name(s, e), (e - s) * 1e-9] for s, e in gp]


def wait_counts(rig):
    """Totals so far, each modulo 2^32: the queue counters of both NICs,
    the client NICs' completions, and the residency histogram's summed
    residencies and completions."""
    import jax
    import numpy as np
    cmon, smon, tel = jax.device_get((rig.cst.mon, rig.sst.mon, rig.tel))

    def total(x):
        return int(np.asarray(x).astype(np.uint32).astype(np.int64).sum())
    queued = sum(total(m[q]) for m in (cmon, smon)
                 for q in ("queued_tx", "queued_fifo", "queued_rx"))
    return np.array([queued, total(cmon["rpcs_completed"]),
                     total(tel.sum_steps), total(tel.n_done)], np.int64)


def waits(before, after) -> dict:
    """Steps an RPC waited in rings over the traced windows alone, by
    the queue counters and by the residency histogram (mean less one)."""
    queued, done, res, n = (after - before) % (1 << 32)
    return dict(ring_wait_steps=queued / done if done else None,
                mean_residency_less_one=(res - n) / n if n else None)


def split(att, steps: int) -> dict:
    names = {"dagger.nic."} | {p for k in att.self_ns for p in k.split("/")
                               if p != scopes.UNSCOPED}
    return dict(
        busy_s=att.busy_ns * 1e-9, scoped_share=att.scoped_share(),
        ops_from_operand=att.from_operand, ops_from_user=att.from_user,
        missing_ops=att.missing,
        top_self_s=att.top(16),
        inclusive_us_per_step={s: att.inclusive_ns(s) * 1e-3 / steps
                               for s in sorted(names)},
        self_us_per_step={k: ns * 1e-3 / steps
                          for k, ns in att.self_ns.items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--sizes", default="{}")
    ap.add_argument("--out", default=None)
    ap.add_argument("--name", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # where none is set, the benchmark command's compile cache
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import harness
    from bench import trace as btrace
    try:
        harness.check_chip(1)
    except harness.NoChip as e:
        print(f"bench.record: {e}; nothing run", file=sys.stderr)
        return 2
    r = harness.resolve(args.workload)
    sizes = dict(r["sizes"], **json.loads(args.sizes))
    traffic = dict(r["traffic"])
    if args.steps:
        traffic["steps_per_window"] = args.steps
    rig = harness.load_module(r["builder"]).build(sizes, traffic,
                                                  args.seed)
    for _ in range(2):
        rig.window()
        rig.read()
    trace_dir = ROOT / ".bench_trace" / f"record-{args.workload}"
    ids0, w0 = rig.next_rpc(), wait_counts(rig)
    path = traced_windows(rig, args.windows, trace_dir)
    ids1, w1 = rig.next_rpc(), wait_counts(rig)
    reduced = btrace.reduce(path, 1)
    steps = args.windows * rig.k
    ctx = dict(trace=reduced, rig=rig, sizes=sizes, traffic=traffic,
               device_kind=jax.devices()[0].device_kind,
               windows=args.windows, steps=steps,
               requests=rig.offered_kinds(ids0, ids1))
    metrics = {m["name"]: harness.load_module(m["reader"]).read(ctx)
               for m in r["per_layer"]}
    att = scopes.of(ctx)
    out = dict(workload=args.workload, seed=args.seed,
               windows=args.windows, steps_per_window=rig.k,
               device_kind=ctx["device_kind"], metrics=metrics,
               scopes=None if att is None else split(att, steps))
    spans = program_spans(path)
    out["dispatch_span_s"] = sum(
        min(e, reduced.t1) - max(s, reduced.t0) for _, s, e in spans
        if e > reduced.t0 and s < reduced.t1) * 1e-9
    out["idle_gaps_program"] = idle_gaps_program(reduced, spans)
    out["traced_waits"] = waits(w0, w1)
    if args.out:
        name = args.name or args.workload
        dest = Path(args.out)
        dest.mkdir(parents=True, exist_ok=True)
        with open(path, "rb") as f, \
                gzip.open(dest / f"{name}.xplane.pb.gz", "wb") as g:
            shutil.copyfileobj(f, g)
        ran = {n.split(" ")[0] for n, _, _ in reduced.chips[0].ops}
        smap = scopes.window_scope_map(rig)
        with gzip.open(dest / f"{name}.scopes.json.gz", "wt") as g:
            json.dump({"paths": {n: "/".join(p) for n, p in
                                 smap.paths.items() if n in ran},
                       "from_operand": sorted(smap.from_operand & ran),
                       "from_user": sorted(smap.from_user & ran)}, g)
        (dest / f"{name}.json").write_text(json.dumps(dict(
            cell=args.workload, seed=args.seed, windows=args.windows,
            steps_per_window=rig.k, chip=ctx["device_kind"],
            sizes=json.loads(args.sizes))) + "\n")
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
