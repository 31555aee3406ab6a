"""Compile each cell's window program for a described TPU v5e, at the
cell's own size, without a chip.

    JAX_PLATFORMS=cpu python3 -m bench.rehearse [--workload NAME ...]

For each cell the configuration's builder makes its rig with abstract
state (shapes only; no table is loaded), and the window program (K
fused steps of every lane, through the engine's public entry) is
lowered and compiled for one chip of a described ``v5e:2x2`` topology.
A refusal of the chip's compiler shows here at no chip time.  It prints
per cell the compiled program's memory analysis and the Pallas kernels
the program holds.  Kept as a script, not a test: a compile at cell
size takes tens of seconds.
"""
import argparse
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def rehearse(workload: str, topo) -> dict:
    import jax
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    r = harness.resolve(workload)
    rig = harness.load_module(r["builder"]).build(
        r["sizes"], r["traffic"], 0, abstract=True)
    one = SingleDeviceSharding(topo.devices[0])
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        rig.window_args())
    t = time.perf_counter()
    compiled = jax.jit(rig.window_call).lower(*args).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    return dict(workload=workload, compile_s=time.perf_counter() - t,
                kernels=sorted(set(re.findall(
                    r'custom_call_target="(tpu_custom_call)"', text))),
                kernel_lines=[ln.strip()[:300] for ln in text.splitlines()
                              if "tpu_custom_call" in ln][:2],
                argument_bytes=getattr(mem, "argument_size_in_bytes", None),
                temp_bytes=getattr(mem, "temp_size_in_bytes", None))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    # the program picks Mosaic or the interpreter by the backend it
    # sees, which here is the CPU; the compile is for the chip
    from repro.kernels import ops
    ops.interpret = lambda: False
    from bench import harness
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.workload or [w["name"] for w in harness.spec()["workloads"]
                              if w["chips"] == 1]
    for name in names:
        print(rehearse(name, topo), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
