"""v5e compile rehearsals: every Pallas kernel on a chip path, compiled
for a TPU v5e that is described, not attached, at the widths the chip
smoke runs.  Nothing executes; what Mosaic would refuse on the chip is
refused here, at no chip time.

The topology is described inside a module-scoped fixture (never at
import, in ``skipif`` or in ``parametrize``): only one process may hold
the TPU compiler library, and the workers of a parallel run must all
collect the same tests.  The persistent compilation cache is off around
the compiles — entries written for a described chip cannot be read back.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import kv_probe as kvp
from repro.kernels import switch_step
from repro.kernels.decode_attn import decode_attention
from repro.kernels.hash_steer import hash_steer_static
from repro.kernels.nic_deliver import nic_deliver_fused
from repro.kernels.ring_copy import ring_gather
from repro.kernels.ring_push import ring_push
from repro.kernels.rpc_pack import rpc_pack

# echo widths: 64 flows x 256-entry rings of 16-word (64-byte) slots,
# batch 4; decode: qwen2-1.5b heads (12 q / 2 kv, head dim 128), 8 slots,
# 256-entry cache; kvs: 2^22 buckets x 4 ways, 8-word values
F, E, W, B = 64, 256, 16, 4
N = F * B
NB = 1 << 22


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # fabriclint: allow(FL007)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kw):
    compiled = fn.lower(*args, interpret=False, **kw).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


def test_decode_attention_qwen2_widths(one_chip):
    s = lambda *shape: _sds(one_chip, shape, jnp.bfloat16)  # noqa: E731
    _compile(decode_attention, s(8, 12, 128), s(8, 256, 2, 128),
             s(8, 256, 2, 128), _sds(one_chip, (8,)))


def test_kv_probe_hbm_table(one_chip):
    rows = lambda n: _sds(one_chip, (1, kvp.packed_rows(NB, n),  # noqa: E731
                                     kvp.LANES))
    c = _compile(kvp.kv_probe, _sds(one_chip, (1, kvp.packed_rows(NB, 4),
                                                kvp.LANES), jnp.uint32),
                 rows(8), rows(32), _sds(one_chip, (1, N)),
                 _sds(one_chip, (1, N), jnp.uint32),
                 _sds(one_chip, (1, N, 2)), ways=4, vw=8)
    # the table stays in HBM: no relayout copy of it in the program
    assert c.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_kv_probe_stacked_stores(one_chip):
    """``jax.vmap`` of the one-store probe over 64 stores at the
    kvs_mica_tiny cell's size (2^20 buckets x 4 ways, 2 + 2 words, 256
    queries each): one kernel call over the stacked tables, with no
    copy, slice or conversion of them in the program."""
    t, nb = 64, 1 << 20
    rows = lambda n, dt=jnp.int32: _sds(  # noqa: E731
        one_chip, (t, kvp.packed_rows(nb, n), kvp.LANES), dt)
    args = (rows(4, jnp.uint32), rows(8), rows(8), _sds(one_chip, (t, N)),
            _sds(one_chip, (t, N), jnp.uint32), _sds(one_chip, (t, N, 2)))
    probe = functools.partial(kvp.probe, ways=4, vw=2, interpret=False)
    c = jax.jit(jax.vmap(probe)).lower(*args).compile()
    text = c.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert c.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_ring_push_echo_widths(one_chip):
    _compile(ring_push, _sds(one_chip, (F, E, W)), _sds(one_chip, (N,)),
             _sds(one_chip, (N,)), _sds(one_chip, (N, W)))


def test_ring_gather_echo_widths(one_chip):
    _compile(ring_gather, _sds(one_chip, (F * B, W)), _sds(one_chip, (F, B)))


def test_nic_deliver_echo_widths(one_chip):
    r, c = F * B, 256
    s = lambda *shape: _sds(one_chip, shape)  # noqa: E731
    _compile(nic_deliver_fused, s(N, W), s(N), s(r), s(r, W),
             s(F, max(E, r)), s(c), s(c), s(c), s(F), s(F), s(5))


def test_rpc_pack_and_hash_steer(one_chip):
    s = lambda *shape: _sds(one_chip, shape)  # noqa: E731
    _compile(rpc_pack, *[s(1024)] * 7, s(1024, W - 5), slot_words=W)
    _compile(hash_steer_static, s(1024, W - 5), 0)
    _compile(hash_steer_static, s(1024, W - 5), 7)


def test_switch_step_fused_still_refused(one_chip):
    """The fused switch megakernel has no Mosaic lowering; the TPU path
    raises ``MOSAIC_REFUSAL`` instead of running.  When this test fails
    because the kernel compiles, drop the refusal."""
    t, bmax, c, nb = 2, B, 256, 64
    s = lambda *shape: _sds(one_chip, shape)  # noqa: E731
    args = (s(t, F, E, W), s(t, F), s(t, F), s(t, F, E, W), s(t, F),
            s(t, F), s(t, F * B, W), s(t, F * B), s(t, F, E), s(t, F),
            s(t, F), s(t, c), s(t, c), s(t, c), s(t, c),
            s(t, switch_step.SCAL_COLS), s(t, nb), s(t * F * bmax, W),
            s(t * F * bmax), s(t * F * bmax))
    fn = jax.jit(lambda *a: switch_step.fused_call(
        *a, bmax=bmax, include_fetch=True, key_words=2, interpret=False))
    with pytest.raises(Exception, match="Only 2D gather"):
        fn.lower(*args).compile()
    with pytest.raises(NotImplementedError,
                       match="switch_step_fused does not compile"):
        switch_step.switch_step_fused.lower(*args, bmax=bmax,
                                            interpret=False)
