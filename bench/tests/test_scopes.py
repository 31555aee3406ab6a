"""The program's scopes and queue counters, and the benchmark's readers
of them, on the CPU at small sizes and on traces recorded on the chip.

Each cell's window program names every phase of the fused step in its
compiled ``op_name`` metadata; after a drain the queue counters of both
NICs add up to the residency histogram's waits exactly; the scope split
of a recorded trace adds up to its busy time exactly; the new readers
read on the cells that list them and return nothing on a program
without scopes or counters; the four earlier readers read on the
earlier recordings what they always read.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import harness, scopes
from bench import trace as btrace
from bench.tests.test_bench import KVS_SMALL, SMALL

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELLS = {"echo64.poisson80": SMALL["sizes"],
         "kvs_mica_tiny.get95": KVS_SMALL["sizes"]}
QUEUES = ("queued_tx", "queued_fifo", "queued_rx")


def _rig(cell, seed=7, abstract=False, **sizes):
    r = harness.resolve(cell)
    return harness.load_module(r["builder"]).build(
        dict(r["sizes"], **CELLS[cell], **sizes), r["traffic"], seed,
        abstract=abstract)


def _reader(name):
    return harness.load_module(
        ROOT / "bench" / "metrics" / f"{name}.py").read


# ------------------------------------------------------------ scope map
def test_scope_map_from_hlo_text():
    text = "\n".join([
        'ENTRY %main {',
        '  %p = s32[8]{0} parameter(0), metadata={op_name="cst.tx.buf"}',
        '  %fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(run_steps)/while/body/vmap(dagger.client)'
        '/dagger.nic.fetch/dagger.ring.peek/gather;vmap(dagger.loadgen)/x"}',
        '  %copy.2 = s32[8]{0} copy(%fusion.1)',
        '  %bitcast.3 = s32[8]{0} bitcast(%p)',
        '  %copy.4 = s32[8]{0} copy(%bitcast.3), backend_config={"a":[]}',
        '  ROOT %scatter.5 = s32[8]{0} scatter(%copy.4, %copy.2), '
        'metadata={op_name="jit(run_steps)/dagger.queues/scatter"}',
        '  %add.6 = s32[] add(%c, %c), '
        'metadata={op_name="jit(run_steps)/while/body/add"}',
        '}'])
    m = scopes.scope_map(text)
    peek = ("dagger.client", "dagger.nic.fetch", "dagger.ring.peek")
    assert m.paths["fusion.1"] == peek              # first merged name
    assert m.paths["copy.2"] == peek and "copy.2" in m.from_operand
    # a copy of a parameter takes its reader's path
    assert m.paths["copy.4"] == ("dagger.queues",)
    assert "copy.4" in m.from_user and "bitcast.3" in m.from_user
    assert m.paths["add.6"] == () and m.paths["p"] == ()
    assert m.scoped()


def test_attribution_sums_to_busy_time():
    """Overlapping operations are counted once, by the one that covered
    a nanosecond first; other programs' operations are unscoped."""
    chip = btrace.Chip(
        ops=[("fusion.1 s32[8]", 100, 200), ("copy.2 s32[8]", 150, 260),
             ("add.6 s32[]", 300, 310), ("fusion.1 s32[8]", 500, 520),
             ("fusion.9 s32[8]", 600, 650)],
        modules=[("jit_run_steps(1)", 90, 400),
                 ("jit_other(2)", 590, 700)])
    t = btrace.Reduced(0, 1000, [chip], [])
    m = scopes.ScopeMap({"fusion.1": ("dagger.client", "dagger.nic.fetch"),
                         "copy.2": ("dagger.ring.push",), "add.6": ()},
                        from_operand=frozenset({"copy.2"}))
    a = scopes.attribute(t, m, "run_steps")
    assert a.busy_ns == t.busy_ns(0) == 240
    assert sum(a.self_ns.values()) == a.busy_ns
    # fusion.1 at 500 ran outside the window program: unscoped
    assert a.self_ns == {"dagger.client/dagger.nic.fetch": 100,
                         "dagger.ring.push": 60, scopes.UNSCOPED: 80}
    assert a.inclusive_ns("dagger.nic.") == 100
    assert a.inclusive_ns("dagger.nic.fetch") == 100
    assert a.inclusive_ns("dagger.nic") == 0        # a whole name only
    assert a.from_operand == 1 and a.missing == 0
    assert a.scoped_share() == pytest.approx(160 / 240)


# ------------------------------------------------- the cells' programs
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_window_program_names_every_scope(cell):
    """Each cell's window program, lowered through the engine's entry and
    compiled at a small size, holds every scope of the fused step."""
    from repro.core import telemetry as tlm
    rig = _rig(cell, abstract=True)
    lowered = rig.engine.lower_steps(rig.cst, rig.sst, rig.k,
                                     hstate=rig.hstate, tel=rig.tel,
                                     gen=rig.gst)
    m = scopes.scope_map(lowered.compile().as_text())
    found = {s for p in m.paths.values() for s in p}
    want = set(tlm.LOOPBACK_SCOPES)
    if cell.startswith("kvs"):
        want |= {tlm.SCOPE_KVS_GET, tlm.SCOPE_KVS_SET}
    assert want <= found, sorted(want - found)
    assert found <= set(tlm.LOOPBACK_SCOPES) | {tlm.SCOPE_KVS_GET,
                                                 tlm.SCOPE_KVS_SET}


@pytest.mark.parametrize("cell,use_pallas",
                         [(c, p) for c in sorted(CELLS) for p in (False,
                                                                  True)])
def test_queue_counters_add_up_to_the_waits(cell, use_pallas):
    """At every step's end each RPC in flight waits in one ring of one
    NIC, so after a drain the queue counters of both NICs hold, lane by
    lane, the residency histogram's waits: sum_steps - n_done."""
    import jax
    rig = _rig(cell, use_pallas=use_pallas)
    for _ in range(3):
        rig.window()
        rig.read()
    rig.drain()
    assert rig.in_flight() == 0
    cmon, smon, tel = jax.device_get((rig.cst.mon, rig.sst.mon, rig.tel))
    queued = sum(np.asarray(mon[q], np.int64) for mon in (cmon, smon)
                 for q in QUEUES)
    waits = np.asarray(tel.sum_steps, np.int64) - tel.n_done
    assert waits.sum() > 0
    np.testing.assert_array_equal(queued, waits)
    np.testing.assert_array_equal(cmon["rpcs_completed"], tel.n_done)
    assert _reader("ring_wait_steps")(dict(rig=rig)) == \
        waits.sum() / tel.n_done.sum()


def test_readers_return_nothing_without_scopes_or_counters():
    """A program without the engine's lowering entry, its scopes or the
    queue counters (an older checkout under this benchmark) reads None
    and raises nothing."""
    rig = _rig("echo64.poisson80")
    rig.window()
    rig.read()

    class Engine:
        pass

    class Old:
        window_program = rig.window_program
        engine = Engine()
        cst = dataclasses.replace(rig.cst, mon={
            k: v for k, v in rig.cst.mon.items() if k not in QUEUES})
        sst = rig.sst

    ctx = dict(rig=Old(), trace=btrace.Reduced(0, 1, [btrace.Chip()], []),
               steps=4)
    for name in ("nic_us", "ring_push_us", "kvs_get_us", "kvs_set_us",
                 "ring_wait_steps"):
        assert _reader(name)(dict(ctx)) is None, name
    # a program with the entry and no scope at all
    empty = scopes.scope_map("ENTRY %main {\n  %p = s32[] parameter(0)\n}")
    assert not empty.scoped()


def test_record_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.record", "--workload",
         "echo64.poisson80", "--seed", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "nothing run" in proc.stderr


# --------------------------------------------- recordings from the chip
def _reduced(tmp_path, name):
    path = tmp_path / f"{name}.xplane.pb"
    with gzip.open(DATA / f"{name}.xplane.pb.gz") as f:
        path.write_bytes(f.read())
    return btrace.reduce(str(path), chips=1)


@pytest.mark.parametrize("name,want", [
    ("echo64_small", {"device_idle_share": 17.75261426121376,
                      "dispatch_gap_us": 4893.153,
                      "step_device_us": 12425.001666666665,
                      "kv_probe_roofline": None}),
    ("kvs64_small", {"device_idle_share": 5.737661509947745,
                     "dispatch_gap_us": 3809.882,
                     "step_device_us": 37619.23883333334,
                     "kv_probe_roofline": 0.002499837311537432}),
])
def test_earlier_readers_read_what_they_read(tmp_path, name, want):
    """The four readers the benchmark had read on its recordings what
    they read before the scopes and counters came."""
    meta = json.loads((DATA / f"{name}.json").read_text())
    steps = meta["windows"] * meta["steps_per_window"]
    r = harness.resolve(meta["cell"])
    sizes = dict(r["sizes"])
    if "keys_per_partition" in meta:
        sizes["keys_per_partition"] = meta["keys_per_partition"]

    class Rig:
        window_program = "run_steps"

    gets = round(steps * sizes.get("n_partitions", 64) * 3.2 * 0.95)
    ctx = dict(trace=_reduced(tmp_path, name), rig=Rig(), steps=steps,
               windows=meta["windows"], sizes=sizes,
               device_kind=meta["chip"], requests={0: gets, 1: 17})
    assert {m: _reader(m)(ctx) for m in want} == want


SCOPED = {"echo64_scoped": ("nic_us", "ring_push_us"),
          "kvs64_scoped": ("nic_us", "ring_push_us", "kvs_get_us",
                           "kvs_set_us")}


def _scoped(tmp_path, name):
    """A trace of the program with its scopes, recorded on the chip by
    ``bench.record``, with the scope map of the instructions it ran."""
    meta = json.loads((DATA / f"{name}.json").read_text())
    with gzip.open(DATA / f"{name}.scopes.json.gz", "rt") as f:
        raw = json.load(f)
    smap = scopes.ScopeMap(
        {n: tuple(p.split("/")) if p else () for n, p in
         raw["paths"].items()},
        frozenset(raw["from_operand"]), frozenset(raw["from_user"]))
    return meta, _reduced(tmp_path, name), smap


@pytest.mark.parametrize("name", sorted(SCOPED))
def test_scope_split_of_a_recorded_trace(tmp_path, name):
    """Self times over every scope path and ``unscoped`` are the busy
    time, to the nanosecond; every operation of the window program is in
    the map; the scopes hold most of the time; each new reader of a
    scope reads on the cells that list it."""
    meta, t, smap = _scoped(tmp_path, name)
    att = scopes.attribute(t, smap, "run_steps")
    assert sum(att.self_ns.values()) == att.busy_ns == t.busy_ns(0)
    assert att.missing == 0
    assert att.scoped_share() >= 0.8
    ctx = dict(trace=t, scopes=att,
               steps=meta["windows"] * meta["steps_per_window"])
    read = {m: _reader(m)(dict(ctx)) for m in SCOPED[name]}
    assert all(v is not None and v > 0 for v in read.values()), read
    assert read["ring_push_us"] < read["nic_us"]
    if name.startswith("echo"):
        assert _reader("kvs_get_us")(dict(ctx)) is None


def test_kv_probe_calls_found_under_the_store_scope(tmp_path):
    """``kv_probe_roofline`` still finds the kernel's calls, one per lane
    and step, and every one of them sits under ``dagger.kvs.get``."""
    from bench import work
    meta, t, smap = _scoped(tmp_path, "kvs64_scoped")
    steps = meta["windows"] * meta["steps_per_window"]
    calls = [n.split(" ")[0] for n, _, _ in t.chips[0].ops
             if work.KV_PROBE_CALL.search(n)]
    assert len(calls) == steps * harness.resolve(meta["cell"])[
        "sizes"]["n_partitions"]
    assert all("dagger.kvs.get" in smap.paths[c] for c in calls)
