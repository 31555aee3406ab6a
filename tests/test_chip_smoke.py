"""``chip_smoke.py`` at tiny sizes on the CPU, kernels interpreted.

Each phase of the chip smoke takes its sizes as arguments, so these
tests and the chip run go through the same code and the same reference
checks.  Off the chip, ``main()`` must refuse: non-zero exit and no
``"ok": true`` line — also when the script is alone in a directory.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("use_pallas", [False, True])
def test_echo_phase(use_pallas):
    out = chip_smoke.phase_echo(n_tenants=2, n_flows=4, ring_entries=16,
                                steps=24, rate=4.0, seed=3,
                                use_pallas=use_pallas)
    assert out["payload_checked"] == 2 * 4
    assert out["completed"] > 0


def test_fabric_kernel_oracles():
    assert chip_smoke.fabric_kernel_oracles(
        n_flows=4, ring_entries=16, batch=4, seed=1) == [
            "ring_push", "ring_gather", "nic_deliver_fused", "rpc_pack",
            "hash_steer_static", "hash_steer"]


@pytest.mark.parametrize("use_pallas", [False, True])
def test_kvs_phase(use_pallas):
    # 2.5 keys per bucket slot offered at the table: evictions happen,
    # and every miss must be covered by one
    out = chip_smoke.phase_kvs(n_buckets=64, n_keys=600, n_ops=64,
                               n_flows=4, seed=2, use_pallas=use_pallas,
                               load_batch=256)
    assert out["hits"] > 0
    assert out["misses"] <= out["n_evict"]


def test_flight_phase():
    out = chip_smoke.phase_flight(total=8, per_step=4, seed=0,
                                  use_pallas=False)
    assert out["chain_checked"] == 8


@pytest.mark.parametrize("use_pallas", [False, True])
def test_decode_phase(use_pallas):
    from repro.apps.lm_decode import TINY
    out = chip_smoke.phase_decode(cfg=TINY, n_slots=4, max_seq=32,
                                  max_prompt=4, max_new=4, steps=40,
                                  rate=0.25, min_requests=4, seed=1,
                                  use_pallas=use_pallas)
    assert out["served"] >= 4


def test_sharded_phases_on_one_device_mesh():
    from repro.core.transport import make_tenant_mesh
    mesh = make_tenant_mesh(1)
    sw = chip_smoke.phase_sharded_switch(mesh=mesh, n_tiers=4, n_flows=2,
                                         ring_entries=64, steps=32, seed=0)
    assert sw["bit_exact"] and len(sw["shard_devices"]) == 1
    eng = chip_smoke.phase_sharded_engine(mesh=mesh, n_tenants=2,
                                          n_flows=4, ring_entries=16,
                                          seed=0)
    assert eng["bit_exact"] and eng["steps"] > 0


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout


def test_report_line_is_json(capsys):
    """One phase report per line, parseable, with the fields the chip
    run's log is read for."""
    meter = chip_smoke.Meter()
    chip_smoke.run_phase(meter, "flight", chip_smoke.phase_flight,
                         total=4, per_step=4, seed=0, use_pallas=False)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rep = json.loads(line)
    for key in ("phase", "sizes", "steps", "compile_s",
                "peak_bytes_in_use", "verdict"):
        assert key in rep
    assert rep["verdict"] == "pass"


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from jax import monitoring
from repro.config import enable_compile_cache
hits = []
monitoring.register_event_listener(
    lambda e, **_: hits.append(e) if e.endswith("cache_hits") else None)
print(enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(len(hits))
"""


def test_compile_cache_dir_from_env(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` is the cache when set: the first run
    writes there, a second run hits.  Unset, the helper names the fixed
    ``<repo>/.jax_cache``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    runs = [subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                           capture_output=True, text=True, timeout=300)
            for _ in range(2)]
    for r in runs:
        assert r.returncode == 0, r.stderr
    (d1, h1), (d2, h2) = (r.stdout.split() for r in runs)
    assert d1 == d2 == str(tmp_path / "cc")
    assert any((tmp_path / "cc").iterdir())
    assert int(h1) == 0 and int(h2) >= 1
    env.pop("JAX_COMPILATION_CACHE_DIR")
    probe = ("from repro.config import enable_compile_cache; "
             "print(enable_compile_cache())")
    r = subprocess.run([sys.executable, "-c", probe], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.stdout.strip() == os.path.join(ROOT, ".jax_cache")
