"""One run of one cell: set-up, the measured windows, the check.

The harness is generic.  It finds the cell in ``BENCHMARK.json``, its
configuration's sizes (``configs/<config>.json``) and builder
(``configs/<config>.py``), its traffic mix (``traffic/<traffic>.json``)
and each per-layer metric's reader (``metrics/<metric>.py``), all by
name, so a later cell, mix or metric is new files and new entries.

The measured time is a loop of windows.  Each window is one call of the
configuration's jitted multi-step entry (K fused steps), and after it
the host reads that window's completions and latency histogram, as an
operator's control loop would.  A window is timed from the end of the
previous one to the end of its own read, so host gaps are inside it.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import time
from pathlib import Path

import numpy as np

from bench import latency
from bench import loadgen as blg

ROOT = Path(__file__).resolve().parents[1]
SALT_SAMPLE = 7


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    name = "bench._by_name." + "-".join(Path(path).parts[-2:]).replace(
        ".", "_")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    bench, bench_dir = spec(root), Path(root) / "bench"
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = configs[cell["config"]]
    with open(root / config["file"]) as f:
        sizes = json.load(f)
    with open(bench_dir / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = blg.check_traffic(json.load(f))

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    return dict(
        cell=cell, config=config, sizes=sizes, traffic=traffic,
        builder=bench_dir / "configs" / f"{cell['config']}.py",
        end_to_end=[m for m in bench["end_to_end"] if listed(m)],
        per_layer=[dict(m, reader=bench_dir / "metrics" / f"{m['name']}.py")
                   for m in bench["per_layer"] if listed(m)])


def check_chip(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees "
                     f"{len(devices)}")


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def sampled(seed: int, i: int, every: int) -> bool:
    """Is window ``i`` one whose replies the check keeps?  Drawn from
    the seed, about one window in ``every``."""
    h = blg.hash32_np(np.uint32(int(seed) % (1 << 32)), np.uint32(i),
                      SALT_SAMPLE)
    return int(h) % every == 0


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events (listeners are registered once per process)."""

    _registered = None

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    @classmethod
    def get(cls) -> "CompileMeter":
        if cls._registered is None:
            from jax import monitoring
            meter = cls._registered = cls()

            def on_duration(event, secs, **_):
                if event == "/jax/core/compile/backend_compile_duration":
                    meter.compiles += 1
                    meter.compile_s += secs

            def on_event(event, **_):
                if event == "/jax/compilation_cache/cache_hits":
                    meter.cache_hits += 1

            monitoring.register_event_duration_secs_listener(on_duration)
            monitoring.register_event_listener(on_event)
        return cls._registered


class Windows:
    """The measured loop's record: per window, seconds and completions,
    where its host time went, and the merged latency distribution."""

    def __init__(self, n_bins: int):
        self.seconds = []
        self.completed = []
        # per window: host seconds from the previous read to this
        # window's dispatch, seconds in the read (waiting for the
        # device), the process's CPU seconds, Python's GC seconds
        self.parts = []
        self.merged = latency.Merged(n_bins)

    def slow(self) -> list:
        """Windows that took over 1.1 times the median, with their
        parts: [index, seconds, host, read, CPU, GC seconds]."""
        if not self.seconds:
            return []
        med = float(np.median(self.seconds))
        return [[i, s, *p] for i, (s, p) in enumerate(
            zip(self.seconds, self.parts)) if s > 1.1 * med]


class GcClock:
    """Seconds Python's garbage collector ran while it is installed."""

    def __init__(self):
        self.total = 0.0
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.total += time.perf_counter() - self._t
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def measure(rig, seconds: float, seed: int, traffic: dict,
            prev_hist) -> Windows:
    """Run windows until ``seconds`` have passed; keep every sampled
    window's replies for the check."""
    from jax.profiler import TraceAnnotation
    rec = Windows(rig.n_bins)
    every, cap = traffic["sample_every"], traffic["max_samples"]
    t_end = time.perf_counter() + seconds
    t_prev = time.perf_counter()
    cpu_prev, i = time.process_time(), 0
    with GcClock() as gc_clock:
        gc_prev = 0.0
        while t_prev < t_end:
            with TraceAnnotation("bench.dispatch"):
                rig.window()
            t_sent = time.perf_counter()
            with TraceAnnotation("bench.counter_read"):
                n, hist = rig.read()
            t_now = time.perf_counter()
            with TraceAnnotation("bench.histogram_merge"):
                dt = t_now - t_prev
                rec.seconds.append(dt)
                rec.completed.append(n)
                rec.merged.add(hist - prev_hist, dt * 1e6 / rig.k)
                prev_hist = hist
                if len(rig.samples) < cap and sampled(seed, i, every):
                    rig.keep_sample()
            cpu = time.process_time()
            rec.parts.append((t_sent - t_prev, t_now - t_sent,
                              cpu - cpu_prev, gc_clock.total - gc_prev))
            cpu_prev, gc_prev = cpu, gc_clock.total
            i += 1
            t_prev = t_now
    return rec


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float, require_chip: bool = True, overrides=None,
             build_kw=None, patch=None) -> dict:
    """One run; returns the result line as a dict.  ``overrides`` (sizes
    for a small run on the CPU), ``build_kw`` (arguments for the
    configuration's builder, such as the control's handler) and
    ``patch`` (a function applied to the built rig, to plant a fault)
    serve the control and the benchmark's own tests."""
    import jax
    r = resolve(workload)
    cell, sizes, traffic = r["cell"], dict(r["sizes"]), dict(r["traffic"])
    sizes.update((overrides or {}).get("sizes", {}))
    traffic.update((overrides or {}).get("traffic", {}))
    if require_chip:
        check_chip(cell["chips"])
    meter = CompileMeter.get()
    c0, s0, h0 = meter.compiles, meter.compile_s, meter.cache_hits
    builder = load_module(r["builder"])

    # ---- set-up: state and tables, compile or cache load, warm windows
    t_init = time.perf_counter()
    rig = builder.build(sizes, traffic, seed, **(build_kw or {}))
    if patch is not None:
        patch(rig)
    t_built = time.perf_counter()
    for _ in range(2):        # the second call finds every program built
        rig.window()
        _, hist0 = rig.read()
        rig.keep_sample()
    rig.samples.clear()
    offered0 = rig.ledger()["offered"]
    t_setup = time.perf_counter()
    setup_s = t_setup - t0
    setup = dict(init_s=t_init - t0, build_s=t_built - t_init,
                 warm_s=t_setup - t_built, compiles=meter.compiles - c0,
                 compile_s=meter.compile_s - s0,
                 cache_hits=meter.cache_hits - h0,
                 **getattr(rig, "setup_detail", {}))
    c1 = meter.compiles

    # ---- the measured windows
    out = {}
    if trace:
        from bench import trace as btrace
        win_s = min(seconds, traffic["trace_seconds"])
        trace_dir = ROOT / ".bench_trace" / workload
        ids0 = rig.next_rpc()
        with btrace.Recording(trace_dir) as recording:
            rec = measure(rig, win_s, seed, traffic, hist0)
        ids1 = rig.next_rpc()
        reduced = btrace.reduce(recording.path(), cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = dict(trace=reduced, rig=rig, sizes=sizes, traffic=traffic,
                   device_kind=jax.devices()[0].device_kind,
                   windows=len(rec.seconds), steps=len(rec.seconds) * rig.k,
                   requests=rig.offered_kinds(ids0, ids1))
        metrics = {}
        for m in r["per_layer"]:
            v = load_module(m["reader"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = reduced.breakdown()
        busy_s, window_s = reduced.busy_s(), reduced.window_s()
    else:
        rec = measure(rig, seconds, seed, traffic, hist0)
        metrics = None
    n_compiles = meter.compiles - c1
    wall = float(sum(rec.seconds))
    done = int(sum(rec.completed))
    ledger_mid = rig.ledger()
    attempted = ledger_mid["offered"] - offered0
    gen_dropped = ledger_mid["gen_dropped"]

    device = device_info(cell["chips"])
    if trace:
        device.update(busy_s=busy_s, window_s=window_s)

    # ---- after the close: late replies, then the reference
    rig.keep_sample()                  # the last window's replies too
    rig.drain()
    ledger = rig.ledger()
    samples = rig.host_samples()
    rig_drain = getattr(rig, "drain_windows", None)
    keys = rig.keys
    rig.free()
    checks = builder.check(sizes, traffic, ledger, keys, samples)
    lost = abs(ledger["injected"] - ledger["completed"])
    correct = all(c["value"] <= c["limit"] if "limit" in c
                  else c["value"] >= c["at_least"] for c in checks.values())

    checks["replies_in_window"] = {"value": rec.merged.n, "at_least": 1}
    correct = correct and rec.merged.n >= 1
    if metrics is None:
        p50 = p99 = None
        try:
            if rec.merged.n:
                p50 = rec.merged.quantile(0.50)
                p99 = rec.merged.quantile(0.99)
        except latency.Overflow:
            checks["p99_overflow"] = {"value": 1, "limit": 0}
            correct = False
        metrics = {"rpc_rate": done / wall / 1e6 if wall else None,
                   "p50_us": p50, "p99_us": p99, "setup_s": setup_s}
        units = {m["name"]: m["unit"] for m in r["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in metrics.items()
                   if k in units and v is not None}
    out = dict(correct=bool(correct), attempted=int(attempted),
               failed=int(gen_dropped + lost), metrics=metrics,
               device=device, **out)
    out["ledger"] = {k: v for k, v in ledger.items()
                     if not isinstance(v, np.ndarray)}
    out["ledger"]["drain_windows"] = rig_drain
    out["setup"] = setup
    out["windows"] = dict(n=len(rec.seconds), steps_per_window=rig.k,
                          wall_s=wall, completed=done,
                          slowest_s=max(rec.seconds) if rec.seconds else None,
                          slow=rec.slow(),
                          compiles=n_compiles)
    out["checks"] = checks
    return out


def checks_text(checks: dict) -> str:
    parts = []
    for name, c in checks.items():
        lim = (f"<= {c['limit']}" if "limit" in c
               else f">= {c['at_least']}")
        parts.append(f"{name} {c['value']} (limit {lim})")
    return "; ".join(parts)
