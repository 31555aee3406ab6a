#!/usr/bin/env bash
# Tier-1 gate: full test suite + a benchmark smoke that emits the
# perf-trajectory JSON (BENCH_fabric.json) future PRs regress against.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

TEST_TIMEOUT="${CI_TEST_TIMEOUT:-1800}"
BENCH_TIMEOUT="${CI_BENCH_TIMEOUT:-900}"
PARITY_TIMEOUT="${CI_PARITY_TIMEOUT:-900}"
SHARDED_TIMEOUT="${CI_SHARDED_TIMEOUT:-1800}"

# The two pytest invocations below partition the tier-1 suite (running
# `python -m pytest -x -q` plain is equivalent): the parity/property
# modules get their own fast-fail block + timeout, the remainder follows.
# test_properties.py needs hypothesis (requirements-dev.txt); naming it
# explicitly would BYPASS conftest's collect_ignore and error, so it only
# joins the list when hypothesis imports.  The seeded fallbacks in
# test_tenant_parity.py / test_kernels.py always run.
PARITY_SUITES=(tests/test_tenant_parity.py tests/test_sharded_parity.py
               tests/test_compact_exchange.py
               tests/test_reassembly.py tests/test_virtualization.py
               tests/test_kernels.py tests/test_loadgen.py
               tests/test_serving_decode.py)
# Best-effort dev-deps install so the hypothesis property suites REALLY
# run in CI; an offline container falls back to the seeded sweeps in
# test_loadgen.py / test_telemetry.py (same invariants, fixed seeds).
if ! python -c 'import hypothesis' 2>/dev/null; then
    python -m pip install -q -r requirements-dev.txt 2>/dev/null \
        || echo "WARN: could not install requirements-dev.txt (offline?);" \
                "property suites skipped, seeded fallbacks still run"
fi
if python -c 'import hypothesis' 2>/dev/null; then
    PARITY_SUITES+=(tests/test_properties.py)
    # collection gate: hypothesis being importable is not enough — an
    # import-time skip or a collect_ignore regression would silently
    # drop the whole property suite while this leg still "passes"
    N_PROPS="$(python -m pytest --collect-only -q \
        tests/test_properties.py 2>/dev/null | grep -c '::')" \
        || N_PROPS=0
    if [ "${N_PROPS:-0}" -eq 0 ]; then
        echo "ERROR: hypothesis imports but tests/test_properties.py" \
             "collected zero tests — the property suite silently" \
             "vanished" >&2
        exit 1
    fi
    echo "hypothesis property suite: ${N_PROPS} tests collected"
fi
echo "== fabriclint: repo-specific static analysis =="
# the AST gate (docs/STATIC_ANALYSIS.md): kernel-oracle parity registry,
# donation-after-use, tracer purity, wire-bit allocation, collective
# axis hygiene, host syncs in timed regions, broad excepts.  Exit 1 on
# any unsuppressed finding — fix it or pragma it with a justification.
python -m scripts.fabriclint src benchmarks scripts

echo "== jaxprlint: IR-level contract checks over the traced dataplane =="
# the second static tier (docs/STATIC_ANALYSIS.md): every registered
# dataplane entry point is traced abstractly (nothing executes on
# device) and the FLJ contracts checked on the IR — collective
# schedules, donation efficacy, counter bounds, scatter modes, and the
# wire-cost model reconciled against compiled HLO.  __main__ forces an
# 8-virtual-device host mesh so FLJ105 measures a real all_to_all.
# Exit 1 on any unsuppressed finding; the --json artifact must parse.
JAXPRLINT_JSON="$(mktemp)"
python -m scripts.jaxprlint --json "$JAXPRLINT_JSON"
python - "$JAXPRLINT_JSON" <<'EOF'
import json
import sys

findings = json.load(open(sys.argv[1]))
assert isinstance(findings, list), type(findings)
live = [f for f in findings if not f["suppressed"]]
if live:
    print(f"jaxprlint --json disagrees with its exit code: {live}",
          file=sys.stderr)
    sys.exit(1)
print(f"jaxprlint artifact OK: {len(findings)} finding(s), all "
      f"suppressed by pragma")
EOF
rm -f "$JAXPRLINT_JSON"

echo "== tenant parity / megakernel property suites =="
timeout "$PARITY_TIMEOUT" python -m pytest -x -q "${PARITY_SUITES[@]}"

echo "== tier-1 tests (remainder) =="
timeout "$TEST_TIMEOUT" python -m pytest -x -q \
    --ignore=tests/test_tenant_parity.py \
    --ignore=tests/test_sharded_parity.py \
    --ignore=tests/test_compact_exchange.py \
    --ignore=tests/test_reassembly.py \
    --ignore=tests/test_virtualization.py \
    --ignore=tests/test_kernels.py \
    --ignore=tests/test_loadgen.py \
    --ignore=tests/test_serving_decode.py \
    --ignore=tests/test_properties.py

echo "== FABRIC_SANITIZE smoke: checkified engine windows =="
# the runtime half of the contract suite: with FABRIC_SANITIZE=1 the
# loopback/tenant engines rebuild through jax.experimental.checkify.
# tests/test_sanitize.py asserts BOTH directions — clean windows pass
# unchanged, and intentionally corrupted ring/FIFO cursors (rx head past
# tail, free-FIFO double release) raise instead of corrupting silently
FABRIC_SANITIZE=1 timeout "$TEST_TIMEOUT" python -m pytest -x -q \
    tests/test_sanitize.py

echo "== sharded parity + compacted exchange + telemetry on an 8-virtual-device CPU mesh =="
# the single-process run above covered the 1-lane degenerate mesh; this
# leg forces 8 host devices so every shard boundary is a real device
# boundary (whole NIC slots per device, all_to_all ToR hop live — full
# tile AND compacted buckets AND the psum-merged latency histograms)
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
    timeout "$SHARDED_TIMEOUT" python -m pytest -x -q \
    tests/test_sharded_parity.py tests/test_compact_exchange.py \
    tests/test_telemetry.py tests/test_loadgen.py

echo "== serving-decode request-level parity on an 8-virtual-device 2-D mesh =="
# the continuous-batching decode tenant's differential ladder with the
# (tenant x model) grid LIVE: tenants shard over real device boundaries
# and the model halves tensor-parallel with in-model psum — batched,
# sequential, vmapped and 2-D-sharded runs must serve bit-identical
# token streams and telemetry histograms
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
    timeout "$SHARDED_TIMEOUT" python -m pytest -x -q \
    tests/test_serving_decode.py

echo "== fused switch-step parity on an 8-virtual-device CPU mesh =="
# the megakernel parity ladder (tests/test_switch_fused.py) with the
# sharded rider crossing REAL device boundaries: the whole front half
# of switch_step_sharded as one Pallas kernel per device, fed by the
# live all_to_all exchange
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
    timeout "$SHARDED_TIMEOUT" python -m pytest -x -q \
    tests/test_switch_fused.py

echo "== bench smoke: tab3 =="
timeout "$BENCH_TIMEOUT" python -m benchmarks.run --only tab3 \
    --json BENCH_fabric.json

echo "== bench smoke: fig11 (--n-tenants 4) =="
FIG11_CSV="$(mktemp)"
timeout "$BENCH_TIMEOUT" python -m benchmarks.run --only fig11 \
    --n-tenants 4 --json BENCH_fabric.json | tee "$FIG11_CSV"

echo "== validate tenant + sharded rows emitted by THIS run =="
# validate the fresh CSV, not the merged BENCH_fabric.json — stale
# committed rows in the merge target must not mask a silent absence —
# then confirm the sharded keys really landed in the merged JSON
python - "$FIG11_CSV" BENCH_fabric.json <<'EOF'
import json
import math
import sys

rows = {}
for line in open(sys.argv[1]):
    parts = line.strip().split(",")
    if len(parts) >= 2 and parts[0].startswith("fig11."):
        try:
            rows[parts[0]] = float(parts[1])
        except ValueError:
            pass
required = [f"fig11.tenant_scaling.{kind}.n{n}"
            for kind in ("batched_us", "seq_us", "speedup")
            for n in (1, 2, 4)]
required += [f"fig11.sharded_scaling.{kind}.n{n}"
             for kind in ("sharded_us", "tenant_us", "ratio")
             for n in (1, 2, 4)]
required += [f"fig11.compacted_exchange.{kind}"
             for kind in ("full_us", "compact_us", "speedup",
                          "full_words", "compact_words", "words_ratio")]
required += [f"fig11.global_until.{kind}.n4"
             for kind in ("global_us", "per_lane_us", "ratio",
                          "dev_steps")]
SWEEP_RATES = (1, 2, 3, 4, 6, 8, 12, 16)
SWEEP_ENGINES = ("tenant", "sharded", "switch")
required += [f"fig11.load_sweep.{eng}.p99_steps.r{r}"
             for eng in SWEEP_ENGINES for r in SWEEP_RATES]
required += [f"fig11.load_sweep.{eng}.{kind}"
             for eng in SWEEP_ENGINES
             for kind in ("knee_rps", "sat_mrps")]
required += [f"fig11.load_sweep.{tag}.{kind}"
             for tag in ("zipf_z99", "zipf_z9999", "zipf_flows_z99")
             for kind in ("hot_p99_steps", "cold_p99_steps",
                          "tail_ratio")]
missing = [k for k in required if k not in rows]
bad = [k for k in required if k in rows
       and (not math.isfinite(rows[k]) or rows[k] <= 0)]
merged = json.load(open(sys.argv[2]))
absent = [k for k in required if k.startswith("fig11.sharded_scaling.")
          and (k not in merged
               or not math.isfinite(float(merged[k])))]
if missing or bad or absent:
    print(f"fig11 rows missing={missing} invalid={bad} "
          f"not-in-json={absent}", file=sys.stderr)
    sys.exit(1)
wr = rows["fig11.compacted_exchange.words_ratio"]
if wr <= 1.0:
    print(f"compacted exchange must SHRINK the wire cost at sparse "
          f"load: words_ratio = {wr:.3f} <= 1", file=sys.stderr)
    sys.exit(1)
# open-loop knee gate: the p99-vs-offered-load curve must be monotone
# nondecreasing and the knee detectable (> 0) for every engine.  These
# are STEP-COUNT rows from a deterministic arrival replay — any
# violation is a real dataplane change, never timing noise.
for eng in SWEEP_ENGINES:
    curve = [rows[f"fig11.load_sweep.{eng}.p99_steps.r{r}"]
             for r in SWEEP_RATES]
    if any(b < a for a, b in zip(curve, curve[1:])):
        print(f"load_sweep.{eng} p99 curve not monotone vs offered "
              f"load: {curve}", file=sys.stderr)
        sys.exit(1)
    knee = rows[f"fig11.load_sweep.{eng}.knee_rps"]
    if knee <= 0:
        print(f"load_sweep.{eng} knee undetected (knee_rps = {knee}): "
              f"no offered rate was served at >= 95%", file=sys.stderr)
        sys.exit(1)
    if curve[-1] <= curve[0]:
        print(f"load_sweep.{eng} shows no queueing past the knee: "
              f"p99 {curve[0]} -> {curve[-1]}", file=sys.stderr)
        sys.exit(1)
for tag in ("zipf_z99", "zipf_z9999"):
    tr = rows[f"fig11.load_sweep.{tag}.tail_ratio"]
    if tr <= 1.0:
        print(f"load_sweep.{tag}: hot/cold tail ratio = {tr} <= 1 — "
              f"the traffic skew did not land on the hot lane",
              file=sys.stderr)
        sys.exit(1)
print(f"tenant rows OK: batched n4 = "
      f"{rows['fig11.tenant_scaling.batched_us.n4']:.1f}us, "
      f"speedup n4 = {rows['fig11.tenant_scaling.speedup.n4']:.2f}x")
print(f"sharded rows OK: sharded n4 = "
      f"{rows['fig11.sharded_scaling.sharded_us.n4']:.1f}us, "
      f"tenant/sharded n4 = "
      f"{rows['fig11.sharded_scaling.ratio.n4']:.2f}x")
print(f"compacted exchange OK: full/compact words = {wr:.2f}x, "
      f"step speedup = "
      f"{rows['fig11.compacted_exchange.speedup']:.2f}x")
print(f"global until OK: per_lane/global = "
      f"{rows['fig11.global_until.ratio.n4']:.2f}x (~1 expected on "
      f"1 device), dev steps = "
      f"{rows['fig11.global_until.dev_steps.n4']:.0f}")
knees = ", ".join(
    f"{eng}={rows[f'fig11.load_sweep.{eng}.knee_rps']:.0f}"
    for eng in SWEEP_ENGINES)
print(f"load sweep OK: monotone p99 curves, knees (req/step/lane): "
      f"{knees}; zipf hot/cold tail = "
      f"{rows['fig11.load_sweep.zipf_z99.tail_ratio']:.1f}x")
EOF
rm -f "$FIG11_CSV"

echo "== bench smoke: fig12 + tab4 (telemetry latency rows) =="
TELEM_CSV="$(mktemp)"
timeout "$BENCH_TIMEOUT" python -m benchmarks.run --only fig12 \
    --n-tenants 2 --json BENCH_fabric.json | tee "$TELEM_CSV"
timeout "$BENCH_TIMEOUT" python -m benchmarks.run --only tab4 \
    --json BENCH_fabric.json | tee -a "$TELEM_CSV"

echo "== validate telemetry latency rows emitted by THIS run =="
# same policy as the fig11 leg: gate on the FRESH CSV so stale merged
# rows cannot mask an absence; µs/steps rows must be finite and > 0,
# the sharded-histogram parity gate must be EXACTLY 1.0
python - "$TELEM_CSV" <<'EOF'
import math
import sys

rows = {}
for line in open(sys.argv[1]):
    parts = line.strip().split(",")
    if len(parts) >= 2 and (parts[0].startswith("fig12.")
                            or parts[0].startswith("tab4.")):
        try:
            rows[parts[0]] = float(parts[1])
        except ValueError:
            pass
required = [f"tab4.{mode}.{kind}"
            for mode in ("simple", "optimized")
            for kind in ("median_us", "p99_us", "median_steps",
                         "p99_steps")]
required += ["tab4.throughput_gain", "tab4.latency_ratio_opt_vs_simple"]
required += [f"fig12.{store}.{wl}{suffix}"
             for store in ("mica", "memcached")
             for wl in ("tiny_write_z99", "small_read_z9999")
             for suffix in ("", ".median_steps", ".p99_steps")]
required += [f"fig12.kvs_telemetry.{kind}.n{n}"
             for kind in ("median_steps", "p99_steps", "hist_match")
             for n in (1, 2)]
missing = [k for k in required if k not in rows]
bad = [k for k in required if k in rows
       and (not math.isfinite(rows[k]) or rows[k] <= 0)]
if missing or bad:
    print(f"telemetry rows missing={missing} invalid={bad}",
          file=sys.stderr)
    sys.exit(1)
for n in (1, 2):
    hm = rows[f"fig12.kvs_telemetry.hist_match.n{n}"]
    if hm != 1.0:
        print(f"sharded KVS histograms diverged: hist_match.n{n} = "
              f"{hm} != 1.0", file=sys.stderr)
        sys.exit(1)
print(f"tab4 rows OK: simple median = "
      f"{rows['tab4.simple.median_steps']:.0f} steps / "
      f"{rows['tab4.simple.median_us']:.0f}us, opt/simple latency = "
      f"{rows['tab4.latency_ratio_opt_vs_simple']:.2f}x, throughput "
      f"gain = {rows['tab4.throughput_gain']:.2f}x")
print(f"fig12 telemetry OK: mica tiny-write median = "
      f"{rows['fig12.mica.tiny_write_z99.median_steps']:.0f} steps, "
      f"hist_match n2 = "
      f"{rows['fig12.kvs_telemetry.hist_match.n2']:.1f}")
EOF
rm -f "$TELEM_CSV"

echo "== bench smoke: lm_decode (continuous-batching decode tenant) =="
DECODE_CSV="$(mktemp)"
timeout "$BENCH_TIMEOUT" python -m benchmarks.run --only lm_decode \
    --json BENCH_fabric.json | tee "$DECODE_CSV"

echo "== validate lm_decode latency-vs-load rows emitted by THIS run =="
# fresh-CSV policy as above.  The TTFT/ITL p99 rows are step counts
# from a deterministic replay: they must be finite, positive, and
# monotone NONDECREASING in offered load, with the top rate past the
# egress knee (strictly above the bottom) — a flat-to-the-top curve
# means the backpressure fabric stopped constraining and the sweep is
# measuring nothing
python - "$DECODE_CSV" <<'EOF'
import math
import sys

rows = {}
for line in open(sys.argv[1]):
    parts = line.strip().split(",")
    if len(parts) >= 2 and parts[0].startswith("fig12.lm_decode."):
        try:
            rows[parts[0]] = float(parts[1])
        except ValueError:
            pass
RATES = (25, 50, 100, 200)
required = [f"fig12.lm_decode.{kind}.r{r}"
            for kind in ("ttft_p99_steps", "itl_p99_steps",
                         "completed", "rejected")
            for r in RATES]
missing = [k for k in required if k not in rows]
bad = [k for k in required if k in rows
       and not math.isfinite(rows[k])]
bad += [k for k in required if k in rows and "p99" in k
        and rows[k] <= 0]
if missing or bad:
    print(f"lm_decode rows missing={missing} invalid={bad}",
          file=sys.stderr)
    sys.exit(1)
for kind in ("ttft_p99_steps", "itl_p99_steps"):
    curve = [rows[f"fig12.lm_decode.{kind}.r{r}"] for r in RATES]
    if any(b < a for a, b in zip(curve, curve[1:])):
        print(f"lm_decode {kind} not monotone vs offered load: "
              f"{curve}", file=sys.stderr)
        sys.exit(1)
    if curve[-1] <= curve[0]:
        print(f"lm_decode {kind} shows no queueing past the egress "
              f"knee: p99 {curve[0]} -> {curve[-1]}", file=sys.stderr)
        sys.exit(1)
done = sum(rows[f"fig12.lm_decode.completed.r{r}"] for r in RATES)
if done <= 0:
    print("lm_decode completed no requests across the sweep",
          file=sys.stderr)
    sys.exit(1)
ttft = [rows[f"fig12.lm_decode.ttft_p99_steps.r{r}"] for r in RATES]
itl = [rows[f"fig12.lm_decode.itl_p99_steps.r{r}"] for r in RATES]
print(f"lm_decode rows OK: ttft p99 {ttft[0]:.0f} -> {ttft[-1]:.0f} "
      f"steps, itl p99 {itl[0]:.0f} -> {itl[-1]:.0f} steps across "
      f"rates {[r / 100 for r in RATES]} req/step/tenant; "
      f"{done:.0f} requests completed")
EOF
rm -f "$DECODE_CSV"

echo "== bench: sharded scaling on the 8-virtual-device mesh =="
# the fig11 leg above timed the 1-lane degenerate mesh; this records the
# REAL mesh numbers (each device owning one NIC slot at n8) under
# distinct mesh8_ keys so both regimes live in the perf trajectory
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
    timeout "$BENCH_TIMEOUT" python - <<'EOF'
import json
import math

from benchmarks.fig11_latency_throughput import (_compacted_exchange,
                                                 _global_until,
                                                 _sharded_scaling)
from benchmarks.fig12_kvs import _kvs_telemetry

rows = {}
for name, us, derived in _sharded_scaling(8, iters=5):
    kind = name.split(".")[2]            # sharded_us | tenant_us | ratio
    n = name.rsplit(".", 1)[1]
    rows[f"fig11.sharded_scaling.mesh8_{kind}.{n}"] = round(float(us), 3)
    print(f"{name} [8-dev mesh],{us:.3f},{derived}", flush=True)
# the compacted exchange with a REAL all_to_all (one tier per device)
for name, us, derived in _compacted_exchange(iters=5):
    kind = name.rsplit(".", 1)[1]
    rows[f"fig11.compacted_exchange.mesh8_{kind}"] = round(float(us), 3)
    print(f"{name} [8-dev mesh],{us:.3f},{derived}", flush=True)
# the global sweep in the regime it exists for: one NIC slot per device
for name, us, derived in _global_until(8, iters=5):
    kind = name.split(".")[2]            # global_us | per_lane_us | ...
    rows[f"fig11.global_until.mesh8_{kind}.n8"] = round(float(us), 3)
    print(f"{name} [8-dev mesh],{us:.3f},{derived}", flush=True)
# the sharded latency histograms with REAL device boundaries: tenant
# vs sharded KVS telemetry must stay bit-identical, psum merge exact
# (sizes=[8]: only the full-mesh point — the 1/2/4-tenant ladder was
# already recorded by the single-process fig12 leg)
for name, us, derived in _kvs_telemetry(8, sizes=[8]):
    kind = name.split(".")[2]        # median_steps | p99_steps | ...
    rows[f"fig12.kvs_telemetry.mesh8_{kind}.n8"] = round(float(us), 3)
    print(f"{name} [8-dev mesh],{us:.3f},{derived}", flush=True)
bad = [k for k, v in rows.items()
       if not math.isfinite(v) or v <= 0]
if bad:
    raise SystemExit(f"mesh8 sharded rows invalid: {bad}")
if rows["fig12.kvs_telemetry.mesh8_hist_match.n8"] != 1.0:
    raise SystemExit(
        "sharded KVS latency histograms diverged on the 8-device mesh: "
        f"hist_match = {rows['fig12.kvs_telemetry.mesh8_hist_match.n8']}")
if rows["fig11.compacted_exchange.mesh8_words_ratio"] <= 1.0:
    raise SystemExit("mesh8 compacted exchange words_ratio <= 1")
if rows["fig11.global_until.mesh8_ratio.n8"] <= 0.5:
    raise SystemExit(
        "run_until_global regressed far past cost parity with per-lane "
        f"freezing: mesh8 per_lane/global = "
        f"{rows['fig11.global_until.mesh8_ratio.n8']:.3f} <= 0.5")
with open("BENCH_fabric.json") as f:
    merged = json.load(f)
merged.update(rows)
with open("BENCH_fabric.json", "w") as f:
    json.dump(merged, f, indent=2, sort_keys=True)
    f.write("\n")
r = rows["fig11.sharded_scaling.mesh8_ratio.n8"]
print(f"mesh8 rows OK: tenant/sharded at n8 over 8 devices = {r:.2f}x "
      f"(accept: ~>=1)")
w = rows["fig11.compacted_exchange.mesh8_words_ratio"]
s = rows["fig11.compacted_exchange.mesh8_speedup"]
print(f"mesh8 compacted exchange OK: full/compact words = {w:.2f}x, "
      f"step speedup = {s:.2f}x on a real 8-lane all_to_all")
g = rows["fig11.global_until.mesh8_ratio.n8"]
print(f"mesh8 global until OK: per_lane/global = {g:.2f}x "
      f"(accept: ~1 — cost parity for fleet-target semantics)")
h = rows["fig12.kvs_telemetry.mesh8_median_steps.n8"]
print(f"mesh8 telemetry OK: KVS median {h:.0f} steps, histograms "
      f"bit-identical across 8 device shards (hist_match = 1.0)")
EOF

echo "== bench: fused switch step vs jnp composition + roofline =="
# the megakernel perf contract: one fused Pallas switch step must beat
# the materialized XLA-op chain (gate below), and the static HLO
# roofline rows must land in the trajectory.  The device-bound rows
# (bound_us / attained_frac) need a device kind with published peaks,
# so they are not required here.  Gate on the FRESH CSV, same policy
# as the fig11 leg.
FUSED_CSV="$(mktemp)"
timeout "$BENCH_TIMEOUT" python -m benchmarks.run --only roofline \
    --json BENCH_fabric.json | tee "$FUSED_CSV"
CI_FUSED_MIN_SPEEDUP="${CI_FUSED_MIN_SPEEDUP:-1.0}" \
    python - "$FUSED_CSV" <<'EOF'
import math
import os
import sys

rows = {}
for line in open(sys.argv[1]):
    parts = line.strip().split(",")
    if len(parts) >= 2 and parts[0].startswith("fig11."):
        try:
            rows[parts[0]] = float(parts[1])
        except ValueError:
            pass
required = [f"fig11.switch_fused.{kind}.n{n}"
            for kind in ("unfused_us", "fused_us", "speedup")
            for n in (1, 4)]
required += [f"fig11.roofline.{tag}.{kind}"
             for tag in ("switch_step", "switch_fused")
             for kind in ("flops", "bytes", "intensity")]
missing = [k for k in required if k not in rows]
bad = [k for k in required if k in rows
       and (not math.isfinite(rows[k]) or rows[k] <= 0)]
if missing or bad:
    print(f"fused-switch rows missing={missing} invalid={bad}",
          file=sys.stderr)
    sys.exit(1)
floor = float(os.environ.get("CI_FUSED_MIN_SPEEDUP", "1.0"))
sp = rows["fig11.switch_fused.speedup.n4"]
if sp < floor:
    print(f"fused switch step regressed: speedup.n4 = {sp:.3f} < "
          f"{floor} (unfused {rows['fig11.switch_fused.unfused_us.n4']:.1f}us, "
          f"fused {rows['fig11.switch_fused.fused_us.n4']:.1f}us)",
          file=sys.stderr)
    sys.exit(1)
print(f"fused switch OK: n4 {rows['fig11.switch_fused.unfused_us.n4']:.0f}us"
      f" -> {rows['fig11.switch_fused.fused_us.n4']:.0f}us "
      f"({sp:.2f}x, floor {floor}); HLO bytes "
      f"{rows['fig11.roofline.switch_step.bytes']:.2e} -> "
      f"{rows['fig11.roofline.switch_fused.bytes']:.2e}")
EOF
rm -f "$FUSED_CSV"

echo "== docs vs benchmark trajectory + README quickstart =="
# every row name cited in docs/ + README must exist in BENCH_fabric.json
# (freshly re-merged above) and the README quickstart blocks must run —
# docs cannot silently rot.  The --list-rules smoke keeps the documented
# linter CLIs importable without a jax backend.
python -m scripts.fabriclint --list-rules >/dev/null
python -m scripts.jaxprlint --list-rules >/dev/null
timeout "$BENCH_TIMEOUT" python scripts/check_docs.py

echo "CI OK"
