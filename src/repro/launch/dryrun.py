import os
# MUST precede every other import (jax locks platform and device count on
# first init).  The dry run compiles a modelled mesh on 512 host devices
# and never touches an accelerator: with ``--all`` it starts one child
# per cell, and a parent holding a TPU would lock the children out.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512"
                           ).strip()
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract the roofline terms from the compiled artifact.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape decode_32k --multi-pod
  python -m repro.launch.dryrun --all            # orchestrate every cell
                                                 # (subprocess per cell)

Each cell writes results/dryrun/<arch>__<shape>__<mesh>.json with:
  memory_analysis (bytes/device), cost_analysis (per-device FLOPs/bytes),
  per-kind collective bytes parsed from the optimized HLO, roofline terms,
  MODEL_FLOPS and the useful-compute ratio.
"""
import argparse
import json
import re
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import SHAPES, ModelConfig, ShapeCell, TrainConfig, hw_spec
from repro.configs import all_arch_names, get_config
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models import Model
from repro.optim import adamw_init
from repro.parallel import (batch_specs, cache_specs, legalize_specs,
                            opt_specs, param_specs)
from repro.launch.analysis import model_flops
from repro.runtime.train_loop import make_train_step

# the modelled production mesh is a v5e pod (this host only compiles)
HW = hw_spec("TPU v5 lite")
RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Sum bytes of every typed buffer in an HLO result type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-kind payload bytes of every collective in the optimized HLO.

    Bytes = result-shape bytes (operand==result for all-reduce /
    collective-permute; ring wire traffic ~= result for all-gather and
    all-to-all; reduce-scatter's wire bytes ~= operand = result x group,
    which we approximate with the group multiplier)."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\d]+)\s+"
                     r"([\w\-]+)", line)
        if not m:
            continue
        opname = m.group(2)
        kind = next((k for k in _COLLECTIVES
                     if opname == k or opname.startswith(k + "-")), None)
        if kind is None or "-start" in opname and False:
            continue
        if opname.endswith("-done"):
            continue                      # counted at -start
        nbytes = _shape_bytes(m.group(1))
        if kind == "reduce-scatter":
            g = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
            mult = len(g.group(1).split(",")) if g else 1
            nbytes *= mult
        out[kind] += nbytes
        out["count"] += 1
    return out


# ---------------------------------------------------------------------------
# abstract inputs per cell
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, cell: ShapeCell) -> dict:
    """ShapeDtypeStruct stand-ins for the model inputs of this cell."""
    b, s = cell.global_batch, cell.seq_len
    i32 = jnp.int32
    f32 = jnp.float32
    if cell.kind == "train" or cell.kind == "prefill":
        s_text = s - (cfg.frontend_tokens
                      if cfg.frontend and not cfg.enc_layers else 0)
        batch = {"tokens": jax.ShapeDtypeStruct((b, s_text), i32)}
        if cell.kind == "train":
            batch["labels"] = jax.ShapeDtypeStruct((b, s_text), i32)
        if cfg.frontend and not cfg.enc_layers:
            batch["frontend_feats"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_tokens, cfg.frontend_dim), f32)
        if cfg.enc_layers:
            batch["enc_feats"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_tokens, cfg.frontend_dim), f32)
        return batch
    # decode: one new token against a cache of seq_len
    return {"tokens": jax.ShapeDtypeStruct((b, 1), i32),
            "pos": jax.ShapeDtypeStruct((b,), i32)}


def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

def apply_overrides(cfg: ModelConfig, overrides) -> ModelConfig:
    """--override key=value (dotted keys reach nested configs).

    e.g. fast_attn=True  moe.decode_mode=gather  ssm.chunk=64
    """
    import dataclasses

    def coerce(v):
        for cast in (int, float):
            try:
                return cast(v)
            except ValueError:
                pass
        return {"True": True, "False": False}.get(v, v)

    for ov in overrides or []:
        key, val = ov.split("=", 1)
        val = coerce(val)
        if "." in key:
            head, sub = key.split(".", 1)
            inner = getattr(cfg, head)
            inner = dataclasses.replace(inner, **{sub: val})
            cfg = cfg.replace(**{head: inner})
        else:
            cfg = cfg.replace(**{key: val})
    return cfg


def run_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True,
             overrides=None, profile_top: int = 0):
    cfg = apply_overrides(get_config(arch), overrides)
    cell = SHAPES[shape]
    if cell.name == "long_500k" and not cfg.supports_long_context:
        return {"arch": arch, "shape": shape, "skipped":
                "pure full-attention arch; long_500k not applicable "
                "(see DESIGN.md)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = dp_axes(mesh)
    model = Model(cfg)
    # wall clock measures host-side compile latency for the report
    t0 = time.time()  # fabriclint: allow(FL003)

    a_params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_specs = legalize_specs(param_specs(cfg, a_params), a_params, mesh)
    if cell.kind == "train":
        tc = TrainConfig(opt_dtype="bfloat16" if cfg.fsdp else "float32",
                         microbatches=1)
        a_opt = jax.eval_shape(partial(adamw_init, opt_dtype=tc.opt_dtype),
                               a_params)
        o_m = legalize_specs(opt_specs(cfg, a_params), a_params, mesh)
        o_specs = {"m": o_m, "v": o_m, "step": P()}
        a_batch = input_specs(cfg, cell)
        b_specs = legalize_specs(batch_specs(a_batch, dp=dp), a_batch, mesh)
        step = make_train_step(model, tc)
        in_sh = (_ns(mesh, p_specs), _ns(mesh, o_specs), _ns(mesh, b_specs))
        args = (a_params, a_opt, a_batch)
        jitted = jax.jit(step, in_shardings=in_sh, donate_argnums=(0, 1))
    elif cell.kind == "prefill":
        a_batch = input_specs(cfg, cell)
        b_specs = legalize_specs(batch_specs(a_batch, dp=dp), a_batch, mesh)
        a_cache = jax.eval_shape(
            partial(model.cache_init, cell.global_batch, cell.seq_len))
        c_specs = legalize_specs(
            cache_specs(cfg, a_cache, mesh.shape["model"], dp=dp),
            a_cache, mesh)

        def prefill_step(params, batch, cache):
            return model.prefill(params, batch, cache)

        in_sh = (_ns(mesh, p_specs), _ns(mesh, b_specs),
                 _ns(mesh, c_specs))
        args = (a_params, a_batch, a_cache)
        jitted = jax.jit(prefill_step, in_shardings=in_sh,
                         donate_argnums=(2,))
    else:  # decode
        a_in = input_specs(cfg, cell)
        a_cache = jax.eval_shape(
            partial(model.cache_init, cell.global_batch, cell.seq_len))
        c_specs = legalize_specs(
            cache_specs(cfg, a_cache, mesh.shape["model"], dp=dp),
            a_cache, mesh)
        tok_spec = legalize_specs(P(dp, None), a_in["tokens"], mesh)
        pos_spec = legalize_specs(P(dp), a_in["pos"], mesh)

        def serve_step(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos)

        in_sh = (_ns(mesh, p_specs),
                 _ns(mesh, c_specs),
                 NamedSharding(mesh, tok_spec),
                 NamedSharding(mesh, pos_spec))
        args = (a_params, a_cache, a_in["tokens"], a_in["pos"])
        jitted = jax.jit(serve_step, in_shardings=in_sh,
                         donate_argnums=(1,))

    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    print(mem)
    print({k: v for k, v in cost.items() if "{" not in k})
    hlo = compiled.as_text()
    # cache the optimized HLO so cost-model refinements re-analyze for free
    hlo_dir = os.path.join(os.path.dirname(RESULTS_DIR), "hlo")
    os.makedirs(hlo_dir, exist_ok=True)
    import gzip
    tag = f"{arch}__{cell.name}__{'multi' if multi_pod else 'single'}"
    if overrides:
        tag += "__" + "_".join(o.replace("=", "-").replace(".", "_")
                               for o in overrides)
    with gzip.open(os.path.join(hlo_dir, tag + ".hlo.gz"), "wt") as f:
        f.write(hlo)
    coll = collective_bytes(hlo)

    # loop-corrected static cost model (XLA's cost_analysis counts scan
    # bodies ONCE — see repro/launch/hlo_cost.py; the corrected numbers
    # are the roofline source, raw numbers kept for reference)
    from repro.launch import hlo_cost
    corrected = hlo_cost.analyze(hlo)
    if profile_top:
        print(f"--- top {profile_top} byte contributors (loop-scaled) ---")
        for c_, comp_, op_, rtype_, meta_ in hlo_cost.top_contributors(
                hlo, profile_top, by="bytes"):
            print(f"  {c_ / 1e9:10.2f} GB  {op_:24s} {rtype_[:48]:48s} "
                  f"{meta_[:60]}")
        print(f"--- top {profile_top} flop contributors ---")
        for c_, comp_, op_, rtype_, meta_ in hlo_cost.top_contributors(
                hlo, profile_top, by="flops"):
            print(f"  {c_ / 1e9:10.2f} GF  {op_:24s} {rtype_[:48]:48s} "
                  f"{meta_[:60]}")

    chips = int(np.prod(list(mesh.shape.values())))
    flops_dev = float(corrected["flops"])
    bytes_dev = float(corrected["bytes"])
    # collectives in the corrected model are per-device payloads already
    coll_dev = float(corrected["collective_bytes"])
    mf = model_flops(cfg, cell)
    terms = {
        "compute_s": flops_dev / HW.peak_flops_bf16,
        "memory_s": bytes_dev / HW.hbm_bw,
        "collective_s": coll_dev / HW.ici_bw_per_link,
    }
    dominant = max(terms, key=terms.get)
    result = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "compile_s": round(time.time() - t0, 1),  # fabriclint: allow(FL003)
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_live_bytes": (mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes),
        },
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "raw_flops_per_device": float(cost.get("flops", 0.0)),
        "raw_bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "collectives": {k: float(v)
                        for k, v in corrected["collectives"].items()},
        "collectives_uncorrected": coll,
        "collective_bytes_per_device": coll_dev,
        "loop_bodies": corrected["loop_bodies"],
        "roofline": terms,
        "dominant": dominant,
        "model_flops_global": mf,
        "useful_ratio": mf / max(flops_dev * chips, 1.0),
        "params_total": cfg.param_count(),
        "params_active": cfg.param_count(active_only=True),
    }
    if verbose:
        print(json.dumps(result, indent=2, default=str))
    return result


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

def run_all(meshes=("single", "multi"), archs=None, shapes=None,
            timeout: int = 1800):
    import subprocess
    os.makedirs(RESULTS_DIR, exist_ok=True)
    archs = archs or all_arch_names()
    shapes = shapes or list(SHAPES)
    failures = []
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                out = os.path.join(
                    RESULTS_DIR,
                    f"{arch}__{shape}__{mesh_kind}.json".replace("/", "_"))
                if os.path.exists(out):
                    print(f"[skip] {out}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", out]
                if mesh_kind == "multi":
                    cmd.append("--multi-pod")
                print("[run]", " ".join(cmd), flush=True)
                try:
                    r = subprocess.run(cmd, capture_output=True, text=True,
                                       timeout=timeout)
                    rc, err = r.returncode, r.stderr[-2000:]
                except subprocess.TimeoutExpired:
                    rc, err = -1, f"timeout after {timeout}s"
                if rc != 0:
                    failures.append((arch, shape, mesh_kind, err))
                    print(f"[FAIL] {arch} {shape} {mesh_kind}\n{err}",
                          flush=True)
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (repeatable; dotted keys "
                         "for nested configs, e.g. moe.decode_mode=gather)")
    ap.add_argument("--profile-top", type=int, default=0,
                    help="print the N heaviest instructions (the dry-run "
                         "profiler for §Perf iterations)")
    args = ap.parse_args()
    if args.all:
        failures = run_all()
        if failures:
            sys.exit(1)
        return
    result = run_cell(args.arch, args.shape, args.multi_pod,
                      overrides=args.override,
                      profile_top=args.profile_top)
    result["overrides"] = args.override
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2, default=str)


if __name__ == "__main__":
    main()
