"""LM-decode tenant application: engine builders + load sweeps.

The shared rig behind ``benchmarks/lm_decode_serving.py`` and the
serving-decode test ladder — a deliberately tiny dense-GQA LM (the
fabric and scheduler are under test, not the model) served by
``runtime.decode.DecodeEngine`` under open-loop load.

Two fabric shapes matter:

* ``default_fabric_config()`` (runtime.decode) — wide egress, used by
  the parity tests so telemetry matches the uncongested analytic oracle
  (TTFT = prompt_len + 1, ITL = 1);
* ``backpressure_fabric_config()`` — ``batch_size=1`` egress, so the
  NIC drains at most one token per flow (one connection) per step.
  Offered load beyond that capacity queues in the rings: TTFT/ITL
  tails CLIMB with rate, which is what the fig12 lm_decode
  latency-vs-load rows (and their CI monotonicity gate) measure.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

from repro.config import FabricConfig
from repro.core import loadgen as lg
from repro.core import telemetry as tlm
from repro.runtime.decode import DecodeEngine

# tiny dense GQA: 2 layers, TP-divisible heads/ff/vocab for 2- and
# 4-way model axes
from repro.configs.repro_100m import REDUCED

TINY = REDUCED.replace(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                       d_ff=128, vocab=128, max_seq=32)


def backpressure_fabric_config(**overrides) -> FabricConfig:
    """Egress-constrained decode fabric: one slot per flow per step
    leaves the NIC, and a connection's tokens share one flow, so token
    streaming saturates at one token per step per tenant and offered
    load beyond it queues (visible latency knee)."""
    kw = dict(n_flows=2, ring_entries=32, batch_size=1,
              dynamic_batching=False)
    kw.update(overrides)
    return FabricConfig(**kw)


def build_engine(cfg=None, fabric_cfg: Optional[FabricConfig] = None,
                 n_slots: int = 4, max_prompt: int = 4,
                 max_new_cap: int = 4, mode: int = lg.MODE_POISSON,
                 seed: int = 0, use_pallas: bool = False,
                 **kw) -> DecodeEngine:
    cfg = TINY if cfg is None else cfg
    if use_pallas:
        cfg = cfg.replace(use_pallas=True)
    return DecodeEngine(cfg, fabric_cfg=fabric_cfg, n_slots=n_slots,
                        max_prompt=max_prompt, max_new_cap=max_new_cap,
                        mode=mode, seed=seed, **kw)


def sweep_rates(engine: DecodeEngine, rates: Sequence[float],
                n_tenants: int = 4, n_steps: int = 192,
                mesh=None) -> Dict[float, dict]:
    """Latency-vs-offered-load sweep: for each rate, run ``n_tenants``
    tenants at that rate for ``n_steps`` fused steps and read the
    per-tenant TTFT/ITL histograms.  The rate is a soft register and
    the tenant count is fixed, so every point reuses one compiled
    loop.  Returns ``{rate: {ttft_p99_steps, itl_p99_steps, ttft_done,
    itl_done, completed, rejected}}``."""
    run = (engine.make_tenant_run_steps(n_steps) if mesh is None
           else engine.make_sharded_run_steps(mesh, n_steps))
    out = {}
    for i, rate in enumerate(rates):
        st = engine.init_states_batch(
            [rate] * n_tenants,
            seeds=[100 * i + t for t in range(n_tenants)])
        st, _ = run(st)
        import numpy as np
        out[rate] = {
            "ttft_p99_steps": tlm.quantiles(st.ttft.hist,
                                            (0.99,))[0.99],
            "itl_p99_steps": tlm.quantiles(st.itl.hist, (0.99,))[0.99],
            "ttft_done": int(np.asarray(st.ttft.n_done).sum()),
            "itl_done": int(np.asarray(st.itl.n_done).sum()),
            "completed": int(np.asarray(st.slots.completed).sum()),
            "rejected": int(np.asarray(st.slots.rejected).sum()),
        }
    return out
