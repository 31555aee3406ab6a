"""jaxprlint entry-point registry.

Every public dataplane entry point is declared here as an :class:`Entry`
mapping the engine/switch/decode/loadgen factory to *abstract*
``ShapeDtypeStruct`` inputs — ``build()`` constructs the engine and
returns the callable + args, the driver then runs ``jax.make_jaxpr`` /
``.lower()`` over them, so NOTHING executes on device (engine
construction does run host-side Python, including tiny-model weight
init for the LM entries).

Shapes are deliberately tiny: the FLJ contracts are structural (which
collectives, which scatter modes, which buffers alias), not numeric,
and they are invariant under the tile sizes.

The registry is itself linted:

* **FLJ100** (registry drift) walks :data:`SCAN_CLASSES` for public
  factory names matching :data:`PATTERNS` and fails for any name not
  claimed by an Entry's ``covers`` or excused in :data:`EXEMPT` (with a
  reason) — a new engine cannot dodge the linter;
* findings attribute to the ``Entry(...)`` line in THIS file, so the
  standard ``# jaxprlint: allow(FLJxxx)`` pragma placed there (same
  line or line above) suppresses a finding for that entry only.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp

I32 = jnp.int32


def _sds(shape, dtype=I32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree)


@dataclass
class Entry:
    """One traced dataplane entry point.

    ``build()`` -> dict with keys:

    * ``fn`` — the callable to trace (jitted where donation applies);
    * ``args`` — abstract example args (``ShapeDtypeStruct`` pytrees;
      static args concrete);
    * ``static_argnums`` — forwarded to ``jax.make_jaxpr``;
    * ``expect_donation`` — FLJ102 requires the lowered computation to
      alias every donated input to an output;
    * ``wire`` — FLJ105 spec (see :func:`_wire_exchange`) or None.
    """
    name: str
    build: Callable
    covers: tuple = ()
    #: declared loop bound for FLJ103's overflow proof: no fused window
    #: (scan length or while trip count) exceeds this many steps.  The
    #: default is generous — benchmarks run windows of <= 2**12 steps.
    max_steps: int = 1 << 20
    skip: tuple = field(default=())   # rule ids statically inapplicable


# ---------------------------------------------------------------- fixtures
_FAB_KW = dict(n_flows=2, ring_entries=8, batch_size=2,
               dynamic_batching=False)
_N_TENANTS = 8        # divides 1/2/4/8-device meshes


def _echo(recs, valid):
    out = dict(recs)
    out["payload"] = recs["payload"] + 1
    return out


def _fabrics():
    from repro.config import FabricConfig
    from repro.core.fabric import DaggerFabric
    cfg = FabricConfig(**_FAB_KW)
    return DaggerFabric(cfg), DaggerFabric(cfg)


def _loadgen(fab):
    from repro.core import loadgen as lg
    return lg.LoadGen(fab, mode=lg.MODE_POISSON)


def _stacked_states(fab, n=_N_TENANTS):
    from repro.core.engine import stack_states
    return jax.eval_shape(lambda: stack_states([fab.init_state()] * n))


# ------------------------------------------------------------- engine.py
def _loopback(kind):
    def build():
        cl, sv = _fabrics()
        from repro.core.engine import LoopbackEngine
        gen = _loadgen(cl) if kind == "gen_steps" else None
        eng = LoopbackEngine(cl, sv, _echo, loadgen=gen)
        cst = jax.eval_shape(cl.init_state)
        sst = jax.eval_shape(sv.init_state)
        if kind == "steps":
            return dict(fn=eng._run_steps, args=(cst, sst, (), 4),
                        static_argnums=(3,), expect_donation=True)
        if kind == "gen_steps":
            gst = jax.eval_shape(lambda: gen.init_state(1.5))
            return dict(fn=eng._gen_fns[("steps", False)],
                        args=(cst, sst, ((), gst), 4),
                        static_argnums=(3,), expect_donation=True)
        return dict(fn=eng._run_until,
                    args=(cst, sst, (), _sds(()), _sds(())),
                    expect_donation=True)
    return build


def _tenant(kind):
    def build():
        cl, sv = _fabrics()
        from repro.core.engine import TenantEngine
        eng = TenantEngine(cl, sv, _echo)
        cst, sst = _stacked_states(cl), _stacked_states(sv)
        t = _sds((_N_TENANTS,))
        if kind == "steps":
            return dict(fn=eng._run_steps, args=(cst, sst, (), 4),
                        static_argnums=(3,), expect_donation=True)
        return dict(fn=eng._run_until, args=(cst, sst, (), t, t),
                    expect_donation=True)
    return build


def _sharded(kind):
    def build():
        cl, sv = _fabrics()
        from repro.core import telemetry as tlm
        from repro.core.engine import ShardedTenantEngine
        from repro.core.transport import make_tenant_mesh
        mesh = make_tenant_mesh()
        gen = _loadgen(cl) if kind.startswith("gen_") else None
        eng = ShardedTenantEngine(cl, sv, _echo, mesh=mesh, loadgen=gen)
        cst, sst = _stacked_states(cl), _stacked_states(sv)
        t = _sds((_N_TENANTS,))
        s = _sds(())
        if kind == "steps":
            return dict(fn=eng._run_steps, args=(cst, sst, (), 4),
                        static_argnums=(3,), expect_donation=True)
        if kind == "until":
            return dict(fn=eng._run_until, args=(cst, sst, (), t, t),
                        expect_donation=True)
        if kind == "until_global":
            return dict(fn=eng._run_until_global, args=(cst, sst, (), s, s),
                        expect_donation=True)
        if kind == "until_global_tel":
            tel = jax.eval_shape(lambda: tlm.create_batch(_N_TENANTS))
            return dict(fn=eng._run_until_global_tel,
                        args=(cst, sst, ((), tel), s, s),
                        expect_donation=True)
        # gen_until_global_tel: open-loop + telemetry, the fig11/fig12
        # load-sweep workhorse — LoadGen counters ride the while carry
        tel = jax.eval_shape(lambda: tlm.create_batch(_N_TENANTS))
        gst = jax.eval_shape(
            lambda: gen.init_state_batch([1.5] * _N_TENANTS))
        return dict(fn=eng._gen_fns[("until_global", True)],
                    args=(cst, sst, (((), tel), gst), s, s),
                    expect_donation=True)
    return build


# ----------------------------------------------------- virtualization.py
def _switch(kind):
    def build():
        from repro.config import FabricConfig
        from repro.core.fabric import DaggerFabric
        from repro.core.transport import make_tenant_mesh
        from repro.core.virtualization import Switch
        cfg = FabricConfig(**_FAB_KW)
        t = _N_TENANTS
        sw = Switch([DaggerFabric(cfg) for _ in range(t)])
        handlers = [_echo] * t
        stacked = jax.eval_shape(
            lambda: sw.stack_states(sw.init_states()))
        if kind == "stacked":
            fn = lambda st: sw.switch_step_stacked(st, handlers)  # noqa: E731
        else:
            mesh = make_tenant_mesh()
            exch = "compact" if kind == "compact" else "full"
            cap = 4 if kind == "compact" else None
            fn = lambda st: sw.switch_step_sharded(    # noqa: E731
                st, handlers, mesh=mesh, exchange=exch, bucket_cap=cap)
        return dict(fn=fn, args=(stacked,), expect_donation=False)
    return build


# ------------------------------------------------------ runtime/decode.py
def _decode(kind):
    def build():
        from repro.apps.lm_decode import build_engine
        from repro.core.transport import make_grid_mesh
        eng = build_engine()
        params = _abstract(eng.params)
        if kind == "run_steps":
            st = jax.eval_shape(lambda: eng.init_states(1.5))
            fn = eng.make_run_steps(2)._jitted
            return dict(fn=fn, args=(st, params), expect_donation=True)
        n_dev = len(jax.devices())
        gm = 2 if (kind == "sharded" and n_dev >= 2) else 1
        gt = max(n_dev // gm, 1) if kind == "sharded" else 1
        n_t = max(gt, 2)
        st = jax.eval_shape(
            lambda: eng.init_states_batch([1.5] * n_t))
        if kind == "tenant":
            fn = eng.make_tenant_run_steps(2)._jitted
        else:
            fn = eng.make_sharded_run_steps(make_grid_mesh(gt, gm),
                                            2)._jitted
        return dict(fn=fn, args=(st, params), expect_donation=True)
    return build


# --------------------------------------------------------- runtime/kvs.py
def _kvs(kind):
    def build():
        cl, sv = _fabrics()
        from repro.runtime.kvs import DeviceKVS
        kvs = DeviceKVS(n_buckets=16, ways=2)
        if kind == "engine":
            eng = kvs.make_engine(cl, sv)
            cst = jax.eval_shape(cl.init_state)
            sst = jax.eval_shape(sv.init_state)
            kst = jax.eval_shape(kvs.init_state)
        elif kind == "tenant":
            eng = kvs.make_tenant_engine(cl, sv)
            cst, sst = _stacked_states(cl), _stacked_states(sv)
            kst = jax.eval_shape(lambda: kvs.init_state_batch(_N_TENANTS))
        else:
            eng = kvs.make_sharded_tenant_engine(cl, sv)
            cst, sst = _stacked_states(cl), _stacked_states(sv)
            kst = jax.eval_shape(lambda: kvs.init_state_batch(_N_TENANTS))
        return dict(fn=eng._run_steps, args=(cst, sst, kst, 4),
                    static_argnums=(3,), expect_donation=True)
    return build


# ----------------------------------------------------- runtime/serving.py
def _serving(kind):
    def build():
        from repro.apps.lm_decode import TINY
        from repro.config import FabricConfig
        from repro.core.transport import make_tenant_mesh
        from repro.runtime.serving import ServingEngine
        fcfg = FabricConfig(n_flows=2, ring_entries=32, batch_size=2,
                            dynamic_batching=False)
        eng = ServingEngine(TINY, fcfg, n_slots=2, max_seq=16)
        params = _abstract(eng.params)
        k, n = 2, 2
        w = eng.fabric.slot_words
        if kind == "run_steps":
            fst, cache, sess = jax.eval_shape(eng.init_states)
            fn = eng.make_run_steps()._jitted
            args = (fst, cache, sess, params, _sds((k, n, w)),
                    _sds((k, n), jnp.bool_))
            return dict(fn=fn, args=args, expect_donation=True)
        t = _N_TENANTS
        fst, cache, sess = jax.eval_shape(
            lambda: eng.init_states_batch(t))
        tiles = (_sds((k, t, n, w)), _sds((k, t, n), jnp.bool_))
        if kind == "tenant":
            fn = eng.make_tenant_run_steps()._jitted
            args = (fst, cache, sess, params) + tiles
        elif kind == "sharded":
            fn = eng.make_sharded_tenant_run_steps(
                make_tenant_mesh())._jitted
            args = (fst, cache, sess, params) + tiles
        else:   # sharded_until_global: psum-predicate while loop
            fn = eng.make_sharded_tenant_run_until_global(
                make_tenant_mesh())._jitted
            args = (fst, cache, sess, params) + tiles + (_sds(()),
                                                         _sds(()))
        return dict(fn=fn, args=args, expect_donation=True)
    return build


# --------------------------------------------------- FLJ105 wire entries
def _wire_exchange():
    """The ToR-hop exchange pair, exactly as ``switch_step_sharded``
    composes it, with the committed words models attached — FLJ105
    compiles these (still nothing executes) and reconciles the HLO
    all-to-all bytes against ``full/compact_exchange_words``."""
    from jax.sharding import PartitionSpec as P

    from repro.core import transport
    from repro.core.transport import shard_map

    mesh = transport.make_tenant_mesh()
    d = mesh.shape["tenant"]
    nb, w, cap = 32, 18, 8

    def full_local(slots, valid, dest):
        owner = jnp.arange(d, dtype=dest.dtype)[:, None]
        mask = dest[None, :] == owner
        bucket = {
            "slots": jnp.broadcast_to(slots[None],
                                      (d, nb, w)).reshape(d * nb, w),
            "valid": (valid[None, :] & mask).reshape(d * nb),
            "dest": jnp.broadcast_to(dest[None],
                                     (d, nb)).reshape(d * nb),
        }
        return transport.all_to_all_tiles(bucket, "tenant")

    def compact_local(slots, valid, dest):
        rows, av, counts, _ = transport.exchange_compact(
            {"slots": slots, "dest": dest}, valid, dest, "tenant", d,
            cap)
        return rows, av, counts

    args = (_sds((nb, w)), _sds((nb,), jnp.bool_), _sds((nb,)))
    sm = lambda f, outs: jax.jit(shard_map(    # noqa: E731
        f, mesh=mesh, in_specs=(P(), P(), P()), out_specs=outs))
    return {
        "n_dev": d,
        "paths": {
            "full": (sm(full_local, P()), args,
                     transport.full_exchange_words(d, nb, w)),
            "compact": (sm(compact_local, (P(), P(), P())), args,
                        transport.compact_exchange_words(d, cap, w)),
        },
    }


def _wire(build_spec):
    def build():
        return dict(fn=None, args=(), expect_donation=False,
                    wire=build_spec())
    return build


# ---------------------------------------------------------------- registry
ENTRIES = [
    Entry("engine.LoopbackEngine.run_steps", _loopback("steps"),
          covers=("LoopbackEngine.run_steps",)),
    Entry("engine.LoopbackEngine.run_until", _loopback("until"),
          covers=("LoopbackEngine.run_until",)),
    Entry("engine.LoopbackEngine.run_steps[loadgen]",
          _loopback("gen_steps")),
    Entry("engine.TenantEngine.run_steps", _tenant("steps"),
          covers=("TenantEngine.run_steps",)),
    Entry("engine.TenantEngine.run_until", _tenant("until"),
          covers=("TenantEngine.run_until",)),
    Entry("engine.ShardedTenantEngine.run_steps", _sharded("steps"),
          covers=("ShardedTenantEngine.run_steps",)),
    Entry("engine.ShardedTenantEngine.run_until", _sharded("until"),
          covers=("ShardedTenantEngine.run_until",)),
    Entry("engine.ShardedTenantEngine.run_until_global",
          _sharded("until_global"),
          covers=("ShardedTenantEngine.run_until_global",)),
    Entry("engine.ShardedTenantEngine.run_until_global[tel]",
          _sharded("until_global_tel")),
    Entry("engine.ShardedTenantEngine.run_until_global[loadgen,tel]",
          _sharded("gen_until_global_tel")),
    Entry("virtualization.Switch.switch_step_stacked", _switch("stacked"),
          covers=("Switch.switch_step_stacked",)),
    Entry("virtualization.Switch.switch_step_sharded[full]",
          _switch("full"), covers=("Switch.switch_step_sharded",)),
    Entry("virtualization.Switch.switch_step_sharded[compact]",
          _switch("compact")),
    Entry("decode.DecodeEngine.make_run_steps", _decode("run_steps"),
          covers=("DecodeEngine.make_run_steps",
                  "DecodeEngine.make_decode_step")),
    Entry("decode.DecodeEngine.make_tenant_run_steps", _decode("tenant"),
          covers=("DecodeEngine.make_tenant_run_steps",)),
    Entry("decode.DecodeEngine.make_sharded_run_steps",
          _decode("sharded"),
          covers=("DecodeEngine.make_sharded_run_steps",)),
    Entry("kvs.DeviceKVS.make_engine", _kvs("engine"),
          covers=("DeviceKVS.make_engine",)),
    Entry("kvs.DeviceKVS.make_tenant_engine", _kvs("tenant"),
          covers=("DeviceKVS.make_tenant_engine",)),
    Entry("kvs.DeviceKVS.make_sharded_tenant_engine", _kvs("sharded"),
          covers=("DeviceKVS.make_sharded_tenant_engine",)),
    Entry("serving.ServingEngine.make_run_steps", _serving("run_steps"),
          covers=("ServingEngine.make_run_steps",
                  "ServingEngine.make_serve_step",
                  "ServingEngine.make_serve_step_telemetry")),
    Entry("serving.ServingEngine.make_tenant_run_steps",
          _serving("tenant"),
          covers=("ServingEngine.make_tenant_run_steps",)),
    Entry("serving.ServingEngine.make_sharded_tenant_run_steps",
          _serving("sharded"),
          covers=("ServingEngine.make_sharded_tenant_run_steps",)),
    Entry("serving.ServingEngine.make_sharded_tenant_run_until_global",
          _serving("sharded_until_global"),
          covers=("ServingEngine.make_sharded_tenant_run_until_global",)),
    Entry("transport.exchange[wire-cost]", _wire(_wire_exchange)),
]

#: discovered names excused from registration, WITH the reason — shown
#: by ``--list-entries`` so exemptions stay auditable
EXEMPT = {
    "Switch.switch_step":
        "host-side list-of-states convenience loop; delegates to the "
        "registered switch_step_stacked for the traced dataplane",
}

#: factory-name shapes that make something a public dataplane entry
#: point (the drift gate's net)
PATTERNS = (
    re.compile(r"^switch_step\w*$"),
    re.compile(r"^make_\w*(engine|run|serve|step)\w*$"),
    re.compile(r"^run_(steps|until\w*)$"),
)


def _scan_classes():
    from repro.core import engine, loadgen, virtualization
    from repro.runtime import decode, kvs, serving
    return [
        ("LoopbackEngine", engine.LoopbackEngine),
        ("TenantEngine", engine.TenantEngine),
        ("ShardedTenantEngine", engine.ShardedTenantEngine),
        ("Switch", virtualization.Switch),
        ("DecodeEngine", decode.DecodeEngine),
        ("DeviceKVS", kvs.DeviceKVS),
        ("ServingEngine", serving.ServingEngine),
        ("LoadGen", loadgen.LoadGen),
    ]


def required_entry_points():
    """Every public factory name the drift gate expects to see covered,
    as ``Class.method`` strings."""
    out = []
    for cls_name, cls in _scan_classes():
        for name in sorted(vars(cls)):
            if name.startswith("_"):
                continue
            if any(p.match(name) for p in PATTERNS):
                out.append(f"{cls_name}.{name}")
    return out


def covered_entry_points():
    cov = set()
    for e in ENTRIES:
        cov.update(e.covers)
    return cov


def coverage_gaps():
    """Required entry points neither covered by an Entry nor exempt."""
    cov = covered_entry_points()
    return [q for q in required_entry_points()
            if q not in cov and q not in EXEMPT]
