"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

``Recording`` wraps the traced windows: it starts JAX's profiler, marks
the traced span with the host annotation ``bench.traced_windows``, and
stops the profiler.  ``reduce`` reads the file with
``jax.profiler.ProfileData`` and keeps, per chip, the device operations
(the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane) and the
program executions (``XLA Modules``), and from the host planes the
benchmark's own spans (names starting ``bench.``).  On that the
reduction computes:

* busy time: the union of the chip's operation intervals inside the
  traced span, and the idle share, 1 - busy / span;
* device time per operation and per kernel (summed durations of the
  leaf operations: a loop's own event encloses the ones it runs);
* collective time (all-to-all, all-reduce, all-gather, collective
  permute, reduce-scatter);
* idle gaps, each named by the host span that covers most of it.
"""
from __future__ import annotations

import glob
import re
import shutil
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.traced_windows"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-to-all|all-reduce|all-gather|collective-permute|reduce-scatter"
    r"|send|recv", re.IGNORECASE)


class Recording:
    """``with Recording(dir) as r: <traced windows>`` — then
    ``r.path()`` is the trace file."""

    def __init__(self, directory):
        self.dir = Path(directory)

    def __enter__(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # host spans are enough
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._span.__exit__(*exc)
        jax.profiler.stop_trace()
        return False

    def path(self) -> str:
        found = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"expected one trace under {self.dir}, "
                               f"found {found}")
        return found[0]


@dataclass
class Chip:
    """One chip's timeline inside the traced span (ns)."""
    ops: list = field(default_factory=list)       # (name, start, end)
    modules: list = field(default_factory=list)   # (name, start, end)


def union(intervals, t0, t1) -> int:
    """Length of the union of [start, end) intervals, clipped to
    [t0, t1)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, t0, t1):
    """Idle [start, end) stretches of [t0, t1) between intervals."""
    out, cur = [], t0
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


HLO_TEXT = re.compile(r"^%(?P<name>[^ ]+) = (?P<shape>\([^)]*\)|\S+)")
LAYOUT = re.compile(r"\{[^{}]*\}")
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


PALLAS = re.compile(r"op_name=\"([^\"]*)/pallas_call")
JITTED = re.compile(r"jit\((\w+)\)")


def _kernel_of(text: str):
    """The innermost jitted function above a ``pallas_call`` in an op's
    metadata (``.../jit(kv_probe)/.../pallas_call`` -> ``kv_probe``)."""
    m = PALLAS.search(text)
    names = JITTED.findall(m.group(1)) if m else []
    return names[-1] if names else None


def op_name(text: str, stats=()) -> str:
    """The TPU trace names an operation by its HLO text
    (``%fusion.606 = s32[1048576,16]{...} fusion(...)``); keep the
    instruction name and its result shape, ``fusion.606 s32[1048576,16]``.
    A Pallas kernel's custom call is marked in brackets with the jitted
    function that made it where the op's metadata names it, else with
    ``pallas``: ``closed_call.11 (s32[256,128],s32[256,1]) [pallas]``."""
    m = HLO_TEXT.match(LAYOUT.sub("", text))
    if not m:
        return text
    name = f"{m.group('name')} {m.group('shape').replace(' ', '')}"
    if 'custom_call_target="tpu_custom_call"' in text:
        k = _kernel_of(text) or next(
            (_kernel_of(v) for _, v in stats
             if isinstance(v, str) and _kernel_of(v)), None)
        name += f" [{k or 'pallas'}]"
    return name


def is_leaf(name: str) -> bool:
    """Loops and calls enclose the operations they run; their own
    events are not operations of their own."""
    return not CONTAINERS.match(name.split(" ")[0])


@dataclass
class Reduced:
    t0: int
    t1: int
    chips: list
    host_spans: list          # (name, start, end)

    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_ns(self, chip: int) -> int:
        return union([(s, e) for _, s, e in self.chips[chip].ops],
                     self.t0, self.t1)

    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips used."""
        return sum(self.busy_ns(c) for c in range(len(self.chips))) \
            / len(self.chips) * 1e-9

    def op_ns(self, chip: int, pred) -> int:
        return sum(min(e, self.t1) - max(s, self.t0)
                   for n, s, e in self.chips[chip].ops
                   if pred(n) and e > self.t0 and s < self.t1)

    def collective_ns(self, chip: int) -> int:
        return union([(s, e) for n, s, e in self.chips[chip].ops
                      if COLLECTIVE.search(n)], self.t0, self.t1)

    def idle_gaps(self, chip: int = 0):
        return gaps([(s, e) for _, s, e in self.chips[chip].ops],
                    self.t0, self.t1)

    def module_runs(self, chip: int, pattern: str):
        """(start, end) of each execution of a program whose name
        matches ``pattern``, inside the traced span."""
        rx = re.compile(pattern)
        return sorted((s, e) for n, s, e in self.chips[chip].modules
                      if rx.search(n) and e > self.t0 and s < self.t1)

    def span_name(self, s: int, e: int) -> str:
        """The host span (other than the traced-window span) that covers
        most of [s, e)."""
        best, name = 0, "untraced host work"
        for n, hs, he in self.host_spans:
            if n == WINDOW_SPAN:
                continue
            cover = min(e, he) - max(s, hs)
            if cover > best:
                best, name = cover, n
        return name

    def breakdown(self, top: int = 10) -> dict:
        """Top device operations by time (chip 0) and the longest idle
        gaps, named by what the host was doing."""
        per = {}
        for n, s, e in self.chips[0].ops:
            d = min(e, self.t1) - max(s, self.t0)
            if d > 0:
                per[n] = per.get(n, 0) + d
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gp = sorted(self.idle_gaps(0), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, d * 1e-9] for n, d in ops],
                "idle_gaps": [[self.span_name(s, e), (e - s) * 1e-9]
                              for s, e in gp]}


def reduce(path: str, chips: int) -> Reduced:
    """Read a trace file and keep what the metrics need."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, per_chip = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if int(m.group(1)) >= chips:
                continue
            chip = per_chip.setdefault(int(m.group(1)), Chip())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        n = op_name(e.name, e.stats if "tpu_custom_call"
                                    in e.name else ())
                        if is_leaf(n):
                            chip.ops.append((n, int(e.start_ns),
                                             int(e.end_ns)))
                elif line.name == MODULES_LINE:
                    chip.modules.extend(
                        (e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns), int(e.end_ns))
                             for e in line.events
                             if e.name.startswith("bench."))
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(window) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(window)}")
    if sorted(per_chip) != list(range(chips)):
        raise RuntimeError(f"trace holds device planes {sorted(per_chip)}, "
                           f"expected {chips} chip(s)")
    t0, t1 = window[0]
    return Reduced(t0, t1, [per_chip[c] for c in range(chips)], spans)
