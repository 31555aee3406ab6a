"""§Roofline: the fused-switch perf contract + dry-run aggregation.

Two row families:

* ``fig11.switch_fused.{unfused_us,fused_us,speedup}.nN`` — measured
  wall time of one ``Switch.switch_step_stacked`` over an N-tier echo
  rig, jnp composition vs the ``switch_step_fused`` Pallas megakernel.
  The megakernel does not compile for a TPU yet (``switch_step.
  MOSAIC_REFUSAL``), so these rows exist only from the Pallas
  interpreter on the CPU, a timing no deployment runs.

* ``fig11.roofline.{switch_step,switch_fused}.*`` — static
  bytes/flops of the compiled step via ``repro.launch.hlo_cost``
  (arithmetic intensity), plus — only on a device kind with published
  peaks in ``repro.config.HW_BY_KIND`` — the roofline bound and the
  attained fraction of it.
  These make the fusion claim quantitative: the fused kernel's win
  must show up as fewer HBM bytes per step, not just lower dispatch
  overhead.

When ``results/dryrun/*.json`` exist (``repro.launch.dryrun --all``)
the legacy per-arch aggregation rows are appended as before; a fresh
checkout no longer emits ``roofline.missing`` — the fabric rows above
are computed live.
"""
from __future__ import annotations

import glob
import json
import os

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results",
                       "dryrun")

ITERS = 30
TIER_SIZES = (1, 4)


def _switch_rig(n_tiers: int, n_flows: int = 2, batch: int = 4,
                ring_entries: int = 32, use_pallas: bool = False):
    """Single-device stacked switch rig: tier 0 fans out to the back
    half of the mesh (itself when n_tiers == 1), echo handlers serve.
    Returns (switch, stacked state, handlers)."""
    import jax
    import jax.numpy as jnp

    from repro.config import FabricConfig
    from repro.core import serdes
    from repro.core.fabric import DaggerFabric
    from repro.core.load_balancer import LB_ROUND_ROBIN
    from repro.core.virtualization import Switch

    cfg = FabricConfig(n_flows=n_flows, ring_entries=ring_entries,
                       batch_size=batch, dynamic_batching=False,
                       use_pallas=use_pallas)
    fabrics = [DaggerFabric(cfg) for _ in range(n_tiers)]
    sw = Switch(fabrics)
    states = sw.init_states()
    serve_lo = n_tiers // 2          # 0 for n_tiers == 1: self-loop
    conns = []
    for i, dst in enumerate(range(serve_lo, n_tiers)):
        c = 10 + i
        states[0] = fabrics[0].open_connection(states[0], c, 0, dst,
                                               LB_ROUND_ROBIN)
        states[dst] = fabrics[dst].open_connection(states[dst], c, 0, 0,
                                                   LB_ROUND_ROBIN)
        conns.append(c)

    def echo(recs, valid):
        out = dict(recs)
        out["payload"] = recs["payload"] + 1
        return out

    handlers = [None] * serve_lo + [echo] * (n_tiers - serve_lo)
    pw = fabrics[0].slot_words - serdes.HEADER_WORDS
    n = 2 * len(conns)
    pay = jnp.tile(jnp.arange(pw, dtype=jnp.int32)[None], (n, 1))
    recs = serdes.make_records(
        jnp.asarray(conns * 2, jnp.int32), jnp.arange(n, dtype=jnp.int32),
        jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32), pay)
    states[0], _ = jax.jit(fabrics[0].host_tx_enqueue)(
        states[0], recs, jnp.arange(n) % n_flows)
    return sw, sw.stack_states(states), handlers


def _roofline_rows(tag: str, fn, stacked, measured_us: float, hw):
    """hlo_cost rows for one compiled step closure.  The bound and the
    attained share need the device's published peaks (``hw``): they are
    emitted only when the step ran on a device kind in
    ``repro.config.HW_BY_KIND``, never against a CPU time."""
    from repro.launch import hlo_cost

    hlo = fn.lower(stacked).compile().as_text()
    cost = hlo_cost.analyze(hlo)
    flops = max(cost["flops"], 1)
    bts = max(cost["bytes"], 1)
    pre = f"fig11.roofline.{tag}"
    rows = [
        (f"{pre}.flops", float(flops), "HLO flops per switch step"),
        (f"{pre}.bytes", float(bts), "HLO HBM bytes per switch step"),
        (f"{pre}.intensity", flops / bts, "flop/byte"),
    ]
    if hw is None:
        return rows
    compute_s = flops / hw.peak_flops_bf16
    memory_s = bts / hw.hbm_bw
    bound_us = max(compute_s, memory_s) * 1e6
    return rows + [
        (f"{pre}.bound_us", bound_us,
         f"roofline bound on {hw.name}: "
         f"{'memory' if memory_s >= compute_s else 'compute'}-bound"),
        (f"{pre}.attained_frac", bound_us / measured_us,
         f"bound_us / measured_us on {hw.name}"),
    ]


def fabric_rows() -> list:
    """Measured fused-vs-unfused switch step + static roofline rows."""
    import jax

    from benchmarks.common import timeit
    from repro.config import hw_spec

    dev = jax.devices()[0]
    hw = hw_spec(dev.device_kind) if dev.platform == "tpu" else None
    out = []
    hlo_targets = {}
    for n in TIER_SIZES:
        sw, stacked, handlers = _switch_rig(n)
        step_un = jax.jit(lambda s, _sw=sw, _h=handlers:
                          _sw.switch_step_stacked(s, _h, use_pallas=False))
        step_fu = jax.jit(lambda s, _sw=sw, _h=handlers:
                          _sw.switch_step_stacked(s, _h, use_pallas=True))
        un_us = timeit(lambda: step_un(stacked), ITERS) * 1e6
        fu_us = timeit(lambda: step_fu(stacked), ITERS) * 1e6
        speed = un_us / fu_us if fu_us > 0 else 0.0
        out.append((f"fig11.switch_fused.unfused_us.n{n}", un_us,
                    f"{n}-tier stacked switch step, jnp composition"))
        out.append((f"fig11.switch_fused.fused_us.n{n}", fu_us,
                    f"{n}-tier stacked switch step, one Pallas megakernel"))
        out.append((f"fig11.switch_fused.speedup.n{n}", speed,
                    "unfused_us / fused_us (>1.0 = fusion wins; "
                    "CI-gated at n4)"))
        hlo_targets[n] = (step_un, step_fu, stacked, un_us, fu_us)

    # static roofline terms at the largest rig
    n = TIER_SIZES[-1]
    step_un, step_fu, stacked, un_us, fu_us = hlo_targets[n]
    out += _roofline_rows("switch_step", step_un, stacked, un_us, hw)
    out += _roofline_rows("switch_fused", step_fu, stacked, fu_us, hw)
    return out


def load_all():
    rows = []
    for path in sorted(glob.glob(os.path.join(RESULTS, "*.json"))):
        with open(path) as f:
            rows.append(json.load(f))
    return rows


def dryrun_rows() -> list:
    """Legacy aggregation of ``repro.launch.dryrun --all`` outputs."""
    out = []
    for r in load_all():
        name = f"roofline.{r['arch']}.{r['shape']}.{r.get('mesh', '-')}"
        if "skipped" in r:
            out.append((name, 0.0, "skipped: " + r["skipped"][:40]))
            continue
        t = r["roofline"]
        dom = r["dominant"].replace("_s", "")
        step_s = max(t.values())
        out.append((name, step_s * 1e6,
                    f"dom={dom} c={t['compute_s']:.2e} "
                    f"m={t['memory_s']:.2e} n={t['collective_s']:.2e} "
                    f"useful={r['useful_ratio']:.2f}"))
    return out


def main() -> list:
    return fabric_rows() + dryrun_rows()


if __name__ == "__main__":
    for r in main():
        print(",".join(str(x) for x in r))
