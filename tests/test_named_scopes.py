"""Named scopes of the paths no benchmark cell runs yet.

The fused step's phases carry ``jax.named_scope`` names
(``repro.core.telemetry.SCOPE_*``) that a profiler trace of the program
is attributed by.  The loopback cells' scopes are checked on their
compiled window programs by ``bench/tests/test_scopes.py``; here the
switch's phases A-D (stacked and sharded jnp routes), the ToR exchange
(full and compact) and the LM decode model step are found in the
locations of their lowered programs, at small sizes.  The sharded
switch lowers on four virtual CPU devices, in a process of its own.
"""
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro.config import FabricConfig
from repro.core import telemetry as tlm
from repro.core.fabric import DaggerFabric
from repro.core.virtualization import Switch

ROOT = Path(__file__).resolve().parents[1]
SCOPE = re.compile(r"dagger\.[A-Za-z0-9_.]*[A-Za-z0-9_]")
SWITCH = {tlm.SCOPE_SWITCH_FETCH, tlm.SCOPE_SWITCH_DELIVER,
          tlm.SCOPE_SWITCH_EMIT, tlm.SCOPE_SWITCH_DRAIN}
NIC = {tlm.SCOPE_NIC_FETCH, tlm.SCOPE_NIC_DELIVER, tlm.SCOPE_NIC_EMIT,
       tlm.SCOPE_NIC_DRAIN, tlm.SCOPE_NIC_ENQUEUE}


def _scopes(lowered) -> set:
    return set(SCOPE.findall(lowered.as_text(debug_info=True)))


def _switch(n_tiers: int):
    cfg = FabricConfig(n_flows=2, ring_entries=16, batch_size=4,
                       dynamic_batching=False)
    sw = Switch([DaggerFabric(cfg) for _ in range(n_tiers)])
    return sw, sw.stack_states(sw.init_states())


def test_stacked_switch_names_its_phases():
    sw, stacked = _switch(2)
    found = _scopes(jax.jit(sw.switch_step_stacked).lower(stacked))
    assert SWITCH | NIC <= found, sorted((SWITCH | NIC) - found)
    assert tlm.SCOPE_EXCHANGE not in found


SHARDED = textwrap.dedent("""
    import json, re, jax
    from tests.test_named_scopes import SCOPE, _switch
    from repro.core.transport import make_tenant_mesh
    sw, stacked = _switch(4)
    mesh = make_tenant_mesh()
    out = {"devices": len(jax.devices())}
    for ex in ("full", "compact"):
        low = jax.jit(lambda s: sw.switch_step_sharded(
            s, None, mesh=mesh, exchange=ex)).lower(stacked)
        out[ex] = sorted(set(SCOPE.findall(low.as_text(debug_info=True))))
    print(json.dumps(out))
""")


def test_sharded_switch_names_its_phases_and_the_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    for ex in ("full", "compact"):
        found = set(out[ex])
        want = SWITCH | NIC | {tlm.SCOPE_EXCHANGE}
        assert want <= found, (ex, sorted(want - found))


def test_decode_names_its_model_step():
    from repro.apps.lm_decode import build_engine
    from repro.core import loadgen as lg
    eng = build_engine(mode=lg.MODE_DETERMINISTIC)
    st = eng.init_states(1.0, seed=3)
    run = eng.make_run_steps(2)
    found = _scopes(run._jitted.lower(st, eng.params))
    assert tlm.SCOPE_DECODE_MODEL in found
    assert tlm.SCOPE_NIC_DELIVER in found
