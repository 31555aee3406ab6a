"""The benchmark's own tests, on the CPU and at small sizes.

They pin what decides a number without a chip: that every name in
BENCHMARK.json resolves to its files (and that a new cell, mix or metric
resolves without editing a file that is there), the per-window latency
scaling and merged percentiles, the reduction of a trace recorded on a
TPU v5e, the peak table and the work functions, that the command
refuses to run without a TPU, and that a run with its timed path
broken underneath comes out ``correct: false``.
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness, latency, peaks, work
from bench import trace as btrace

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SMALL = {"sizes": {"n_tenants": 2, "n_flows": 4, "ring_entries": 64,
                   "request_buffer_slots": 256},
         "traffic": {"sample_every": 1, "min_answers_checked": 10}}


# ----------------------------------------------------------- resolution
def test_every_entry_resolves_to_its_files():
    spec = harness.spec()
    for c in spec["configs"]:
        with open(ROOT / c["file"]) as f:
            assert json.load(f)["name"] == c["name"]
    for w in spec["workloads"]:
        r = harness.resolve(w["name"])
        mod = harness.load_module(r["builder"])
        assert callable(mod.build) and callable(mod.check)
        assert callable(mod.control_kw)
        assert r["traffic"]["name"] == w["traffic"]
        assert {m["name"] for m in r["end_to_end"]} >= {"setup_s"}
        assert r["per_layer"], w["name"]
        for m in r["per_layer"]:
            assert callable(harness.load_module(m["reader"]).read)


def test_new_entries_resolve_without_editing_a_file(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    b = tmp_path / "bench"
    (b / "traffic" / "burst9.json").write_text(
        json.dumps(dict(json.loads((b / "traffic" / "poisson80.json")
                                   .read_text()), name="burst9")))
    (b / "configs" / "echo65.json").write_text(
        json.dumps(dict(json.loads((b / "configs" / "echo64.json")
                                   .read_text()), name="echo65")))
    shutil.copy(b / "configs" / "echo64.py", b / "configs" / "echo65.py")
    (b / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="echo65",
                                file="bench/configs/echo65.json"))
    spec["workloads"].append(dict(spec["workloads"][0], name="echo65.burst9",
                                  config="echo65", traffic="burst9"))
    spec["per_layer"].append(dict(spec["per_layer"][0], name="new.metric",
                                  workloads=["echo65.burst9"]))
    # a new BENCHMARK.json is the one file that changes
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    r = harness.resolve("echo65.burst9", root=tmp_path)
    assert r["sizes"]["name"] == "echo65"
    assert r["traffic"]["name"] == "burst9"
    assert [m["name"] for m in r["per_layer"]] == ["new.metric"]
    assert harness.load_module(r["per_layer"][0]["reader"]).read({}) == 1.0
    assert r["builder"] == b / "configs" / "echo65.py"
    assert all(p.read_bytes() == data for p, data in before.items())


# -------------------------------------------------------------- latency
def test_windows_scaled_by_their_own_step_time_then_merged():
    m = latency.Merged(8)
    # window A at 100 us/step: three replies after 1 step, one after 2
    m.add(np.array([0, 3, 1, 0, 0, 0, 0, 0]), 100.0)
    # window B stalled, 250 us/step: two replies after 1 step
    m.add(np.array([0, 2, 0, 0, 0, 0, 0, 0]), 250.0)
    # each reply spread over [L, L + 1) steps of its window: mass 3 on
    # [100, 200) us, 1 on [200, 300), 2 on [250, 500)
    assert m.n == 6
    assert m.quantile(0.25) == pytest.approx(150.0)
    assert m.quantile(0.5) == pytest.approx(200.0)
    # F(300) = 3 + 1 + 2 * 50 / 250 = 4.4, then slope 2 / 250 per us
    assert m.quantile(0.99) == pytest.approx(300 + (5.94 - 4.4) * 125)
    # not a median of the windows' medians (166.7 us and 375 us)
    a, b = latency.Merged(8), latency.Merged(8)
    a.add(np.array([0, 3, 1, 0, 0, 0, 0, 0]), 100.0)
    b.add(np.array([0, 2, 0, 0, 0, 0, 0, 0]), 250.0)
    assert (a.quantile(0.5), b.quantile(0.5)) == (
        pytest.approx(100 + 100 * 2 / 3), pytest.approx(375.0))


def test_percentile_in_the_overflow_bin_is_an_error():
    m = latency.Merged(4)
    m.add(np.array([0, 90, 0, 10]), 10.0)        # bin 3 = overflow
    assert m.quantile(0.5) == pytest.approx(10 + 50 / 90 * 10)
    with pytest.raises(latency.Overflow):
        m.quantile(0.99)


class _SlowReadRig:
    """Stands in for a rig: 2 steps per window, each read takes 20 ms of
    host time, one reply per window at residency 1."""
    k, n_bins = 2, 4

    def __init__(self):
        self.samples, self.total = [], 0

    def window(self):
        pass

    def read(self):
        time.sleep(0.02)
        self.total += 1
        return 1, np.array([0, self.total, 0, 0])

    def keep_sample(self):
        self.samples.append(None)


def test_window_time_includes_the_host_gap():
    rig = _SlowReadRig()
    t = time.perf_counter()
    rec = harness.measure(rig, 0.1, seed=3,
                          traffic={"sample_every": 1, "max_samples": 2},
                          prev_hist=np.zeros(4, np.int64))
    elapsed = time.perf_counter() - t
    assert sum(rec.seconds) == pytest.approx(elapsed, abs=0.01)
    assert all(s >= 0.02 for s in rec.seconds)
    assert rec.merged.n == len(rec.seconds)
    # each reply is one step of its own window: seconds / 2 steps
    assert rec.merged.quantile(0.0) == pytest.approx(
        min(rec.seconds) * 1e6 / 2)
    assert len(rig.samples) == 2


# ------------------------------------------------------ peaks and work
def test_peak_table_has_v5e_and_refuses_unknown_kinds():
    p = peaks.peaks("TPU v5 lite")
    assert p.hbm_bytes_per_s == 819e9 and p.bf16_flops == 197e12
    assert "TPU v5e" in p.source
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")


def test_kv_probe_bytes_from_shapes():
    # 4 ways of (tag 1 + key 2 + value 2) words, query bucket + tag +
    # 2 key words, answer 2 value words + hit
    assert work.kv_probe_bytes(128, ways=4, key_words=2,
                               value_words=2) == 128 * (20 + 4 + 3) * 4
    assert work.kv_probe_bytes(0, 4, 2, 2) == 0


# ---------------------------------------------------------------- trace
def test_union_and_gaps_of_intervals():
    iv = [(0, 10), (5, 20), (30, 40), (38, 45)]
    assert btrace.union(iv, 0, 100) == 35
    assert btrace.union(iv, 8, 32) == 14
    assert btrace.gaps(iv, 0, 50) == [(20, 30), (45, 50)]


def test_op_names_from_hlo_text():
    text = ("%fusion.606 = s32[1048576,16]{0,1:T(8,128)} fusion(s32[1] "
            "%x), kind=kCustom")
    assert btrace.op_name(text) == "fusion.606 s32[1048576,16]"
    assert not btrace.is_leaf("while.5 (s32[])")
    assert btrace.is_leaf("fusion.606 s32[1048576,16]")


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "echo64.xplane.pb"
    with gzip.open(DATA / "echo64_small.xplane.pb.gz") as f:
        path.write_bytes(f.read())
    return btrace.reduce(str(path), chips=1)


def test_reduce_a_trace_recorded_on_the_chip(chip_trace):
    t = chip_trace
    meta = json.loads((DATA / "echo64_small.json").read_text())
    assert t.window_s() > 0
    busy = t.busy_s()
    assert 0 < busy < t.window_s()
    runs = t.module_runs(0, "run_steps")
    assert len(runs) == meta["windows"]
    assert t.collective_ns(0) == 0                    # one chip
    b = t.breakdown()
    assert len(b["device_ops"]) == 10
    secs = [d for _, d in b["device_ops"]]
    assert secs == sorted(secs, reverse=True) and secs[0] > 0
    assert all(n.startswith("bench.") for n, _ in b["idle_gaps"])
    # the window program's device time is the busy time, give or take
    # the sample copies between windows
    in_runs = sum(e - s for s, e in runs) * 1e-9
    assert busy == pytest.approx(in_runs, rel=0.05)


def test_metric_readers_on_the_recorded_trace(chip_trace):
    meta = json.loads((DATA / "echo64_small.json").read_text())

    class Rig:
        window_program = "run_steps"

    ctx = dict(trace=chip_trace, rig=Rig(), windows=meta["windows"],
               steps=meta["windows"] * meta["steps_per_window"])
    read = {m: harness.load_module(ROOT / "bench" / "metrics" / f"{m}.py")
            .read for m in ("device_idle_share", "dispatch_gap_us",
                            "step_device_us")}
    idle = read["device_idle_share"](ctx)
    assert 0 < idle < 100
    step = read["step_device_us"](ctx)
    assert step == pytest.approx(chip_trace.busy_s() * 1e6 / ctx["steps"])
    gap = read["dispatch_gap_us"](ctx)
    assert 0 < gap < chip_trace.window_s() * 1e6


def test_kv_probe_roofline_on_a_kvs_trace(tmp_path):
    """The store's kernel is found by its call signature in a trace of
    the KVS cell recorded on the chip, and its share of the HBM roofline
    is a share (0-100 %)."""
    path = tmp_path / "kvs64.xplane.pb"
    with gzip.open(DATA / "kvs64_small.xplane.pb.gz") as f:
        path.write_bytes(f.read())
    meta = json.loads((DATA / "kvs64_small.json").read_text())
    t = btrace.reduce(str(path), chips=1)
    steps = meta["windows"] * meta["steps_per_window"]
    r = harness.resolve(meta["cell"])
    sizes = dict(r["sizes"], keys_per_partition=meta["keys_per_partition"])
    calls = [n for n, _, _ in t.chips[0].ops if work.KV_PROBE_CALL.search(n)]
    # one call per lane and step (the vmapped kernel runs lane by lane)
    assert len(calls) == steps * sizes["n_partitions"]
    read = harness.load_module(
        ROOT / "bench" / "metrics" / "kv_probe_roofline.py").read
    # the GETs the lanes offered at the cell's rate, not the kernel's
    # fixed batch of 256 slots per lane and step
    gets = round(steps * sizes["n_partitions"] * 3.2 * 0.95)
    ctx = dict(trace=t, sizes=sizes, steps=steps, device_kind=meta["chip"],
               requests={0: gets, 1: 17})
    share = read(ctx)
    assert 0 < share < 100
    assert read(dict(ctx, requests={0: 2 * gets})) == pytest.approx(
        2 * share)
    # no GET offered, or the echo trace with no such call: nothing
    assert read(dict(ctx, requests={1: 17})) is None
    assert read(dict(ctx, trace=btrace.Reduced(0, 1, [btrace.Chip()],
                                               []))) is None


def test_unknown_arrival_process_is_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    mix = tmp_path / "bench" / "traffic" / "poisson80.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                   arrivals="onoff")))
    with pytest.raises(ValueError, match="onoff"):
        harness.resolve("echo64.poisson80", root=tmp_path)


def test_offered_kinds_follow_the_reference_draw():
    kvs = harness.load_module(ROOT / "bench/configs/kvs_mica_tiny.py")
    from bench.rig import LoopbackRig
    rig = LoopbackRig.__new__(LoopbackRig)
    rig.n_lanes, rig.keys = 3, np.array([5, -7, 11], np.int32)
    rig.request_kinds = lambda lk, rid: kvs.is_set_np(
        lk, rid, kvs._set_threshold(0.05)).astype(int)
    lo, hi = np.array([0, 10, 100]), np.array([4000, 10, 2100])
    got = rig.offered_kinds(lo, hi)
    assert got[0] + got[1] == 6000
    assert 0.04 < got[1] / 6000 < 0.06
    rig.request_kinds = None
    assert rig.offered_kinds(lo, hi) == {0: 6000}


def test_set_log_names_the_last_set_before_a_step():
    kvs = harness.load_module(ROOT / "bench/configs/kvs_mica_tiny.py")
    log = kvs.SetLog.__new__(kvs.SetLog)
    log.n_keys, log.span = 10, 102
    # lane 0 key 3 set at steps 5 and 40; lane 1 key 3 at step 7
    log.comp = np.sort(np.array([(0 * 10 + 3) * 102 + 5,
                                 (0 * 10 + 3) * 102 + 40,
                                 (1 * 10 + 3) * 102 + 7]))
    lane, key = np.array([0, 0, 0, 1, 1, 0]), np.array([3, 3, 3, 3, 3, 4])
    step = np.array([4, 39, 100, 6, 7, 100])
    assert log.last_before(lane, key, step).tolist() == [-1, 5, 40, -1, 7,
                                                         -1]


# --------------------------------------------------------- the command
def _run_command(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "echo64.poisson80",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_refuses_without_a_tpu():
    p = _run_command(ROOT, {})
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ------------------------------------------- a broken timed path fails
def _state_unchanged(rig):
    """Every call returns the states it was given."""
    import jax.numpy as jnp

    class Stuck:
        def run_steps(self, cst, sst, k, hstate=None, tel=None, gen=None):
            done = jnp.zeros((rig.n_lanes,), jnp.int32)
            if rig.stateful:
                return cst, sst, hstate, done, tel, gen
            return cst, sst, done, tel, gen

    rig.engine = Stuck()


def _half_batch(rig):
    """Only the first half of the lanes is stepped; the rest is left
    out."""
    import jax
    import jax.numpy as jnp
    eng, h = rig.engine, rig.n_lanes // 2

    def part(tree, sl):
        return jax.tree.map(lambda x: x[sl], tree)

    def join(a, b):
        return jax.tree.map(lambda x, y: jnp.concatenate([x, y]), a, b)

    class Half:
        def run_steps(self, cst, sst, k, hstate=None, tel=None, gen=None):
            lo, hi = slice(0, h), slice(h, None)
            trees = (cst, sst, hstate, tel, gen)
            rest = [part(x, hi) for x in trees]
            out = list(eng.run_steps(part(cst, lo), part(sst, lo), k,
                                     hstate=part(hstate, lo),
                                     tel=part(tel, lo), gen=part(gen, lo)))
            d = out.pop(-3)
            if not rig.stateful:
                out.insert(2, None)
            out = [None if a is None else join(a, b)
                   for a, b in zip(out, rest)]
            d = jnp.concatenate([d, jnp.zeros((rig.n_lanes - h,), d.dtype)])
            c, s, hs, t, g = out
            return ((c, s, hs, d, t, g) if rig.stateful
                    else (c, s, d, t, g))

    rig.engine = Half()


def _answer_plus_two(recs, valid):
    out = dict(recs)
    out["payload"] = recs["payload"] + 2
    return out


def _control_kw(cell, name="control"):
    r = harness.resolve(cell)
    return harness.load_module(r["builder"]).control_kw(name)


KVS_SMALL = {"sizes": {"n_partitions": 2, "n_flows": 4, "ring_entries": 64,
                       "request_buffer_slots": 256, "n_buckets": 1024,
                       "keys_per_partition": 3072, "load_batch": 1024},
             "traffic": {"sample_every": 1, "min_answers_checked": 10}}
FAULTS = {
    "echo64.poisson80": ["none", "state_unchanged", "half_batch",
                         "answer_altered", "control"],
    "kvs_mica_tiny.get95": ["none", "state_unchanged", "half_batch",
                            "control", "sets_lost"],
}


@pytest.mark.parametrize("cell,fault", [(c, f) for c, fs in FAULTS.items()
                                        for f in fs])
def test_run_is_correct_only_when_sound(cell, fault):
    """The harness on the CPU at a small size (its look for a chip
    skipped), with the timed path sound or broken underneath."""
    build_kw = ({"handler": _answer_plus_two} if fault == "answer_altered"
                else _control_kw(cell, fault)
                if fault in ("control", "sets_lost") else None)
    kw = dict(patch={"state_unchanged": _state_unchanged,
                     "half_batch": _half_batch}.get(fault),
              build_kw=build_kw)
    small = SMALL if cell.startswith("echo") else KVS_SMALL
    out = harness.run_cell(cell, 2 ** 31 + 11, 0.2, False,
                           t0=time.perf_counter(), require_chip=False,
                           overrides=small, **kw)
    assert out["correct"] is (fault == "none"), out["checks"]
    assert list(out)[-1] == "checks"
    if fault == "none":
        assert out["attempted"] > 0 and out["failed"] == 0
        assert set(out["metrics"]) == {"rpc_rate", "p50_us", "p99_us",
                                       "setup_s"}
