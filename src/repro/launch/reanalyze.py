"""Re-derive dry-run JSONs from cached HLO (results/hlo/*.hlo.gz) with the
current cost model — no recompilation.

  PYTHONPATH=src python -m repro.launch.reanalyze            # all cached
  PYTHONPATH=src python -m repro.launch.reanalyze --tag qwen2-1.5b__decode
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os

from repro.config import SHAPES, hw_spec
from repro.configs import get_config
from repro.launch.analysis import model_flops
from repro.launch.hlo_cost import analyze

ROOT = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results")
# the production mesh these HLOs were compiled for is a v5e pod
HW = hw_spec("TPU v5 lite")


def reanalyze_file(path: str):
    name = os.path.basename(path)[:-len(".hlo.gz")]
    parts = name.split("__")
    arch, shape, mesh_kind = parts[0], parts[1], parts[2]
    overrides = parts[3] if len(parts) > 3 else None
    with gzip.open(path, "rt") as f:
        hlo = f.read()
    corrected = analyze(hlo)
    chips = 512 if mesh_kind == "multi" else 256
    cfg = get_config(arch)
    mf = model_flops(cfg, SHAPES[shape])
    flops_dev = corrected["flops"]
    bytes_dev = corrected["bytes"]
    coll_dev = corrected["collective_bytes"]
    terms = {
        "compute_s": flops_dev / HW.peak_flops_bf16,
        "memory_s": bytes_dev / HW.hbm_bw,
        "collective_s": coll_dev / HW.ici_bw_per_link,
    }
    out = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if mesh_kind == "multi" else "16x16",
        "chips": chips,
        "overrides": overrides,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collectives": {k: float(v)
                        for k, v in corrected["collectives"].items()},
        "collective_bytes_per_device": coll_dev,
        "loop_bodies": corrected["loop_bodies"],
        "roofline": terms,
        "dominant": max(terms, key=terms.get),
        "model_flops_global": mf,
        "useful_ratio": mf / max(flops_dev * chips, 1.0),
    }
    return name, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", default="")
    ap.add_argument("--update-json", action="store_true",
                    help="merge the recomputed terms back into the "
                         "matching results/dryrun JSONs")
    args = ap.parse_args()
    for path in sorted(glob.glob(os.path.join(ROOT, "hlo", "*.hlo.gz"))):
        if args.tag and args.tag not in path:
            continue
        name, out = reanalyze_file(path)
        print(json.dumps({name: out["roofline"],
                          "dominant": out["dominant"]}, default=str))
        if args.update_json and out["overrides"] is None:
            jpath = os.path.join(ROOT, "dryrun", name + ".json")
            if os.path.exists(jpath):
                with open(jpath) as f:
                    old = json.load(f)
                old.update({k: out[k] for k in
                            ("flops_per_device", "bytes_per_device",
                             "collectives", "collective_bytes_per_device",
                             "loop_bodies", "roofline", "dominant",
                             "model_flops_global", "useful_ratio")})
                with open(jpath, "w") as f:
                    json.dump(old, f, indent=2, default=str)


if __name__ == "__main__":
    main()
