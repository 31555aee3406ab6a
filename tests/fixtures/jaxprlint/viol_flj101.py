"""Mutation fixture: FLJ101 must fire.

Two schedule corruptions that *trace fine* (jax itself only rejects
unbound axis names, not divergent schedules):

* a ``cond`` that runs a psum on one branch only — the classic
  fleet-desynchronizing divergence;
* a ``while`` whose body psums every iteration but whose predicate is
  device-local, so trip counts can differ and the rendezvous hangs.
"""
import jax
import jax.numpy as jnp
from repro.core.transport import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from scripts.jaxprlint.registry import Entry


def _divergent_cond():
    mesh = Mesh(jax.devices(), ("tenant",))

    def local(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jax.lax.psum(v, "tenant"),
                            lambda v: v + 1,
                            x)

    fn = jax.jit(shard_map(local, mesh=mesh, in_specs=(P(),),
                           out_specs=P()))
    return dict(fn=fn, args=(jax.ShapeDtypeStruct((4,), jnp.int32),),
                expect_donation=False)


def _local_predicate_while():
    mesh = Mesh(jax.devices(), ("tenant",))

    def local(x):
        def body(c):
            return jax.lax.psum(c + 1, "tenant")

        return jax.lax.while_loop(lambda c: c[0] < 5, body, x)

    fn = jax.jit(shard_map(local, mesh=mesh, in_specs=(P(),),
                           out_specs=P()))
    return dict(fn=fn, args=(jax.ShapeDtypeStruct((4,), jnp.int32),),
                expect_donation=False)


ENTRIES = [
    Entry("fixture.divergent_cond_schedule", _divergent_cond),
    Entry("fixture.local_predicate_while", _local_predicate_while),
]
