"""ServingEngine: LM serving *through* the Dagger fabric.

This is the paper's thesis applied to model serving: the entire request
dataplane — ring drain, session lookup (the connection-manager analogue),
steering, batching, the decode step itself, sampling, and response
enqueue — runs as ONE fused device step.  The host's per-request work is
a single ring write (``request()``), exactly Dagger's "single memory
write in the critical RPC path".

Request wire format (payload words):
  [0] session_id    (client-chosen, pins the stream: static LB/affinity)
  [1] token         (next prompt token, or -1 = "sample for me")
  [2] flags         (bit0: NEW session)
Response payload:
  [0] session_id  [1] next_token  [2] position

Sessions own a *slot* (row) of the decode batch + KV cache; per-slot
positions make this continuous batching — streams at different depths
decode in the same step.  Slot allocation/lookup is vectorized (argsort
free-list + match matrix), mirroring the connection cache's role.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FabricConfig, ModelConfig
from repro.core import serdes
from repro.core import telemetry as tlm
from repro.core.fabric import DaggerFabric, FabricState
from repro.models import Model

FLAG_NEW = 1


@jax.tree_util.register_dataclass
@dataclass
class SessionState:
    session_id: jnp.ndarray     # [Nslots] int32, -1 = free
    pos: jnp.ndarray            # [Nslots] int32 next decode position
    last_token: jnp.ndarray     # [Nslots] int32


class ServingEngine:
    def __init__(self, cfg: ModelConfig, fabric_cfg: FabricConfig,
                 n_slots: int, max_seq: int, params=None, seed: int = 0):
        self.cfg = cfg
        self.model = Model(cfg)
        self.fabric = DaggerFabric(fabric_cfg)
        self.n_slots = n_slots
        self.max_seq = max_seq
        key = jax.random.PRNGKey(seed)
        self.params = params if params is not None else self.model.init(key)

    def init_states(self):
        fst = self.fabric.init_state()
        cache = self.model.cache_init(self.n_slots, self.max_seq)
        sess = SessionState(jnp.full((self.n_slots,), -1, jnp.int32),
                            jnp.zeros((self.n_slots,), jnp.int32),
                            jnp.zeros((self.n_slots,), jnp.int32))
        return fst, cache, sess

    # ------------------------------------------------------------------
    def make_serve_step(self):
        """The fused dataplane+model step (server side).

        (fabric_state, cache, sessions, params, in_slots, in_valid)
          -> (fabric_state, cache, sessions, served, out_slots, out_valid)

        ``in_*`` is the wire-ingress tile (requests arriving from client
        NICs / the switch); ``out_*`` is the wire-egress tile (responses
        fetched from the server TX rings).  The whole body — deliver,
        steer, batch, session lookup, decode, sample, respond — is one
        device step."""
        model, fab, n_slots = self.model, self.fabric, self.n_slots

        def step(fst: FabricState, cache, sess: SessionState, params,
                 in_slots, in_valid):
            # 1. wire -> NIC: request buffer, steer, flow FIFOs, RX rings
            fst, recs, rvalid = fab.nic_pipeline(fst, in_slots, in_valid)
            req = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]),
                               recs)
            rv = rvalid.reshape(-1)                        # [N]
            sid = req["payload"][:, 0]
            tok_in = req["payload"][:, 1]
            is_new = (req["payload"][:, 2] & FLAG_NEW) != 0

            # 2. session lookup (connection-manager analogue)
            match = (sid[:, None] == sess.session_id[None, :]) \
                & (sess.session_id[None, :] >= 0)           # [N, Nslots]
            has_slot = jnp.any(match, axis=1)
            slot_of = jnp.argmax(match, axis=1)
            # allocate free slots to NEW sessions (rank -> kth free slot)
            free = sess.session_id < 0
            order = jnp.argsort(jnp.where(free, jnp.arange(n_slots),
                                          n_slots + 1))
            n_free = jnp.sum(free.astype(jnp.int32))
            want_new = rv & is_new & ~has_slot
            rank = jnp.cumsum(want_new.astype(jnp.int32)) - 1
            alloc_ok = want_new & (rank < n_free)
            new_slot = order[jnp.clip(rank, 0, n_slots - 1)]
            slot = jnp.where(alloc_ok, new_slot, slot_of)
            active_req = rv & (alloc_ok | has_slot)
            slot_safe = jnp.where(active_req, slot, n_slots)  # OOB drop

            # 3. update session table + stage tokens
            sess_id2 = sess.session_id.at[slot_safe].set(sid, mode="drop")
            pos2 = sess.pos.at[slot_safe].set(
                jnp.where(alloc_ok, 0, sess.pos.at[slot_safe].get(
                    mode="fill", fill_value=0)), mode="drop")
            tok_stage = sess.last_token.at[slot_safe].set(
                jnp.where(tok_in >= 0, tok_in,
                          sess.last_token.at[slot_safe].get(
                              mode="fill", fill_value=0)), mode="drop")
            slot_has_req = jnp.zeros((n_slots,), bool).at[slot_safe].set(
                True, mode="drop")

            # 4. decode every active slot at its own position
            tokens = tok_stage[:, None]                     # [Nslots, 1]
            logits, cache2 = model.decode_step(params, cache, tokens, pos2)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

            run = slot_has_req
            sess2 = SessionState(
                sess_id2,
                jnp.where(run, pos2 + 1, pos2),
                jnp.where(run, next_tok, tok_stage))
            # only slots that ran keep their cache writes; others keep old
            # (the decode wrote at pos2 rows regardless — harmless, those
            # rows' pos pointer did not advance)

            # 5. responses: [sid, next_token, position] back through fabric
            n = rv.shape[0]
            pw = fab.slot_words - serdes.HEADER_WORDS
            resp_payload = jnp.zeros((n, pw), jnp.int32)
            resp_payload = resp_payload.at[:, 0].set(sid)
            resp_payload = resp_payload.at[:, 1].set(
                next_tok.at[slot_safe].get(mode="fill", fill_value=-1))
            resp_payload = resp_payload.at[:, 2].set(
                pos2.at[slot_safe].get(mode="fill", fill_value=-1))
            resp = dict(req)
            resp["payload"] = resp_payload
            resp["flags"] = req["flags"] | serdes.FLAG_RESPONSE
            flow_of = jnp.repeat(
                jnp.arange(fab.cfg.n_flows, dtype=jnp.int32),
                fab.cfg.batch_size)
            fst, _ = fab.host_tx_enqueue(fst, resp, flow_of, active_req)
            served = jnp.sum(active_req.astype(jnp.int32))
            # 6. NIC -> wire: responses leave through the TX path
            fst, out_slots, out_valid = fab.nic_fetch(fst)
            w = out_slots.shape[-1]
            return (fst, cache2, sess2, served,
                    out_slots.reshape(-1, w), out_valid.reshape(-1))

        return step

    def make_serve_step_telemetry(self):
        """The fused serve step with latency telemetry threaded through.

        ``tstep(fst, cache, sess, tel, params, in_slots, in_valid)``
        wraps ``make_serve_step``: the egress tile's RESPONSES —
        requests served and put back on the wire this step — are
        observed against their stamped issue step (clients stamp
        ``serdes`` word 4 with the telemetry step counter), then the
        step counter ticks.  Residency therefore covers the whole NIC
        path: deliver, flow FIFOs, decode, respond, TX fetch.
        Returns ``(fst, cache, sess, tel, served, out_slots,
        out_valid)``.
        """
        step = self.make_serve_step()

        def tstep(fst, cache, sess, tel, params, in_slots, in_valid):
            fst, cache, sess, served, out_s, out_v = step(
                fst, cache, sess, params, in_slots, in_valid)
            recs = serdes.unpack(out_s)
            is_resp = (recs["flags"] & serdes.FLAG_RESPONSE) != 0
            tel = tlm.observe(tel, recs["timestamp"], out_v & is_resp)
            tel = tlm.tick(tel)
            return fst, cache, sess, tel, served, out_s, out_v

        return tstep

    # ------------------------------------------------------------------
    def make_run_steps(self):
        """Scan-fused steady-state serving loop (the engine treatment).

        ``run_steps(fst, cache, sess, params, in_slots [K, N, W],
        in_valid [K, N], tel=None)`` executes K serve steps in ONE
        device dispatch: the (fabric, cache, sessions) triple is the
        ``lax.scan`` carry with donated buffers, the per-step
        wire-ingress tiles are the scanned xs, and the egress tiles come
        back stacked.  The host stages K tiles up front and syncs once —
        the §4.4 offload principle applied to model serving (vs. one
        dispatch + sync per decode step).

        With ``tel`` (``telemetry.create()``, donated) the latency
        histogram rides the carry (see
        ``make_serve_step_telemetry``) and the updated Telemetry is
        appended to the returns.
        """
        step = self.make_serve_step()
        tstep = self.make_serve_step_telemetry()

        def run_steps(fst, cache, sess, params, in_slots, in_valid):
            def body(carry, x):
                fst, cache, sess, served = carry
                s, v = x
                fst, cache, sess, n, out_s, out_v = step(
                    fst, cache, sess, params, s, v)
                return (fst, cache, sess, served + n), (out_s, out_v)

            carry = (fst, cache, sess, jnp.int32(0))
            (fst, cache, sess, served), (out_slots, out_valid) = \
                jax.lax.scan(body, carry, (in_slots, in_valid))
            return fst, cache, sess, served, out_slots, out_valid

        def run_steps_tel(fst, cache, sess, tel, params, in_slots,
                          in_valid):
            def body(carry, x):
                fst, cache, sess, tel, served = carry
                s, v = x
                fst, cache, sess, tel, n, out_s, out_v = tstep(
                    fst, cache, sess, tel, params, s, v)
                return (fst, cache, sess, tel, served + n), (out_s, out_v)

            carry = (fst, cache, sess, tel, jnp.int32(0))
            (fst, cache, sess, tel, served), (out_slots, out_valid) = \
                jax.lax.scan(body, carry, (in_slots, in_valid))
            return fst, cache, sess, served, out_slots, out_valid, tel

        fn = jax.jit(run_steps, donate_argnums=(0, 1, 2))
        fn_tel = jax.jit(run_steps_tel, donate_argnums=(0, 1, 2, 3))

        def wrapped(fst, cache, sess, params, in_slots, in_valid,
                    tel=None):
            from repro.core.engine import unalias
            fst, cache, sess, tel = unalias(
                (fst, cache, sess, tel),
                protected=(params, in_slots, in_valid))
            if tel is None:
                return fn(fst, cache, sess, params, in_slots, in_valid)
            return fn_tel(fst, cache, sess, tel, params, in_slots,
                          in_valid)

        # jaxprlint registry hook: the inner jitted callable, so the
        # IR linter can lower/trace the donating entry point directly
        wrapped._jitted = fn
        wrapped._jitted_tel = fn_tel
        return wrapped

    # ------------------------------------------------------------------
    def init_states_batch(self, n_tenants: int):
        """Stacked (fabric, cache, sessions) triples — one virtual NIC
        slot + decode batch per tenant, leading tenant axis."""
        from repro.core.engine import stack_states
        return stack_states([self.init_states()
                             for _ in range(n_tenants)])

    def make_tenant_run_steps(self):
        """Tenant-batched serving loop: ``jax.vmap`` of the fused serve
        step over a leading tenant axis, scanned over K ingress tiles.

        ``run_steps(fst, cache, sess, params, in_slots [K, T, N, W],
        in_valid [K, T, N], tel=None)`` serves T independent tenants
        (each with its own fabric, KV cache and session table, sharing
        one set of model weights) for K steps in ONE device dispatch;
        ``served`` comes back per-tenant [T].  States come from
        ``init_states_batch``; ``tel`` is
        ``telemetry.create_batch(T)`` — per-tenant histograms, appended
        to the returns.
        """
        step = self.make_serve_step()
        vstep = jax.vmap(step, in_axes=(0, 0, 0, None, 0, 0))
        vtstep = jax.vmap(self.make_serve_step_telemetry(),
                          in_axes=(0, 0, 0, 0, None, 0, 0))

        def run_steps(fst, cache, sess, params, in_slots, in_valid):
            t = in_slots.shape[1]

            def body(carry, x):
                fst, cache, sess, served = carry
                s, v = x
                fst, cache, sess, n, out_s, out_v = vstep(
                    fst, cache, sess, params, s, v)
                return (fst, cache, sess, served + n), (out_s, out_v)

            carry = (fst, cache, sess, jnp.zeros((t,), jnp.int32))
            (fst, cache, sess, served), (out_slots, out_valid) = \
                jax.lax.scan(body, carry, (in_slots, in_valid))
            return fst, cache, sess, served, out_slots, out_valid

        def run_steps_tel(fst, cache, sess, tel, params, in_slots,
                          in_valid):
            t = in_slots.shape[1]

            def body(carry, x):
                fst, cache, sess, tel, served = carry
                s, v = x
                fst, cache, sess, tel, n, out_s, out_v = vtstep(
                    fst, cache, sess, tel, params, s, v)
                return (fst, cache, sess, tel, served + n), (out_s, out_v)

            carry = (fst, cache, sess, tel, jnp.zeros((t,), jnp.int32))
            (fst, cache, sess, tel, served), (out_slots, out_valid) = \
                jax.lax.scan(body, carry, (in_slots, in_valid))
            return fst, cache, sess, served, out_slots, out_valid, tel

        fn = jax.jit(run_steps, donate_argnums=(0, 1, 2))
        fn_tel = jax.jit(run_steps_tel, donate_argnums=(0, 1, 2, 3))

        def wrapped(fst, cache, sess, params, in_slots, in_valid,
                    tel=None):
            from repro.core.engine import unalias
            fst, cache, sess, tel = unalias(
                (fst, cache, sess, tel),
                protected=(params, in_slots, in_valid))
            if tel is None:
                return fn(fst, cache, sess, params, in_slots, in_valid)
            return fn_tel(fst, cache, sess, tel, params, in_slots,
                          in_valid)

        # jaxprlint registry hook: the inner jitted callable, so the
        # IR linter can lower/trace the donating entry point directly
        wrapped._jitted = fn
        wrapped._jitted_tel = fn_tel
        return wrapped

    # ------------------------------------------------------------------
    def shard_tenant_states(self, fst, cache, sess, mesh,
                            axis: str = "tenant"):
        """Place stacked (fabric, cache, sessions) triples on the mesh:
        tenant axis sharded, placement legalized via
        ``parallel.sharding.legalize_specs`` (see ``engine.shard_states``).
        """
        from repro.core.engine import shard_states
        return (shard_states(fst, mesh, axis),
                shard_states(cache, mesh, axis),
                shard_states(sess, mesh, axis))

    def _sharded_runner(self, mesh, axis: str, local,
                        n_scalar_args: int, n_device_outs: int):
        """Shared shard_map/donation plumbing for the sharded serving
        entry points — ``make_sharded_tenant_run_steps`` and
        ``make_sharded_tenant_run_until_global`` differ ONLY in their
        per-device loop body, so the spec wiring, jit donation,
        ``unalias`` guard and divisibility check live here once.

        ``local(fst, cache, sess, params, in_slots, in_valid,
        *scalars)`` is the per-device body returning ``(fst, cache,
        sess, served, <n_device_outs per-device lane outputs>,
        out_slots, out_valid)``; ``n_scalar_args`` replicated int32
        scalars are appended to the public signature.  States donate,
        weights stay replicated, tiles are sharded on their tenant dim.
        """
        from jax.sharding import PartitionSpec as P

        from repro.core.transport import shard_map
        from repro.debug import sanitize
        sanitize.note_unsanitized_sharded("ServingEngine (sharded)")

        def run(fst, cache, sess, params, in_slots, in_valid, *scalars):
            shard = lambda t: jax.tree.map(lambda _: P(axis), t)
            repl = jax.tree.map(lambda _: P(), params)
            tile = P(None, axis)
            return shard_map(
                local, mesh=mesh,
                in_specs=(shard(fst), shard(cache), shard(sess), repl,
                          tile, tile) + (P(),) * n_scalar_args,
                out_specs=(shard(fst), shard(cache), shard(sess),
                           P(axis)) + (P(axis),) * n_device_outs
                          + (tile, tile))(fst, cache, sess, params,
                                          in_slots, in_valid, *scalars)

        fn = jax.jit(run, donate_argnums=(0, 1, 2))

        def wrapped(fst, cache, sess, params, in_slots, in_valid,
                    *scalars):
            from repro.core.engine import unalias
            t = in_slots.shape[1]
            if t % mesh.shape[axis]:
                raise ValueError(
                    f"n_tenants={t} must divide over the "
                    f"{mesh.shape[axis]}-device '{axis}' mesh axis")
            scalars = tuple(jnp.asarray(s, jnp.int32) for s in scalars)
            fst, cache, sess = unalias(
                (fst, cache, sess),
                protected=(params, in_slots, in_valid) + scalars)
            return fn(fst, cache, sess, params, in_slots, in_valid,
                      *scalars)

        # jaxprlint registry hook: the inner jitted callable, so the
        # IR linter can lower/trace the donating entry point directly
        wrapped._jitted = fn
        return wrapped

    def make_sharded_tenant_run_steps(self, mesh=None,
                                      axis: str = "tenant"):
        """Mesh-sharded serving loop: the tenant axis of
        ``make_tenant_run_steps`` sharded over ``mesh`` with
        ``shard_map``, so each device owns whole NIC slots — fabric, KV
        cache and session table shards — while the model weights stay
        replicated (in_spec ``P()``).  Ingress/egress tiles ride the
        same placement ([K, T, N, W] sharded on the tenant dim).  Same
        signature as ``make_tenant_run_steps``; ``n_tenants`` must
        divide over the mesh axis.
        """
        if mesh is None:
            from repro.core.transport import make_tenant_mesh
            mesh = make_tenant_mesh(axis=axis)
        step = self.make_serve_step()
        vstep = jax.vmap(step, in_axes=(0, 0, 0, None, 0, 0))

        def local(fst, cache, sess, params, in_slots, in_valid):
            tl = in_slots.shape[1]

            def body(carry, x):
                fst, cache, sess, served = carry
                s, v = x
                fst, cache, sess, n, out_s, out_v = vstep(
                    fst, cache, sess, params, s, v)
                return (fst, cache, sess, served + n), (out_s, out_v)

            carry = (fst, cache, sess, jnp.zeros((tl,), jnp.int32))
            (fst, cache, sess, served), (out_slots, out_valid) = \
                jax.lax.scan(body, carry, (in_slots, in_valid))
            return fst, cache, sess, served, out_slots, out_valid

        return self._sharded_runner(mesh, axis, local,
                                    n_scalar_args=0, n_device_outs=0)

    def make_sharded_tenant_run_until_global(self, mesh=None,
                                             axis: str = "tenant"):
        """Global-completion serving sweep on the mesh (the
        ``ShardedTenantEngine.run_until_global`` treatment ported to LM
        serving): every device keeps running serve steps — consuming its
        staged ingress tiles in order — until the FLEET-WIDE served
        total (``psum`` over per-device counters in the while
        predicate) reaches ``global_target``, or ``max_steps`` elapse.

        ``run(fst, cache, sess, params, in_slots [K, T, N, W], in_valid
        [K, T, N], global_target, max_steps)`` returns ``(fst, cache,
        sess, served [T], dev_steps [D], out_slots [K, T, ...],
        out_valid [K, T, ...])``.  ``max_steps`` is clipped to K (only K
        ingress tiles are staged); egress tiles of steps the loop never
        reached come back zeroed/invalid.  ``dev_steps`` entries agree
        across devices (the psum predicate ends every device's loop on
        the same step).  States donate; weights stay replicated.
        """
        if mesh is None:
            from repro.core.transport import make_tenant_mesh
            mesh = make_tenant_mesh(axis=axis)
        step = self.make_serve_step()
        vstep = jax.vmap(step, in_axes=(0, 0, 0, None, 0, 0))

        def local(fst, cache, sess, params, in_slots, in_valid,
                  global_target, max_steps):
            k, tl = in_slots.shape[0], in_slots.shape[1]
            max_steps = jnp.minimum(jnp.asarray(max_steps, jnp.int32),
                                    jnp.int32(k))
            o_s, o_v = jax.eval_shape(
                lambda *a: vstep(*a)[4:6], fst, cache, sess, params,
                in_slots[0], in_valid[0])
            outs = jnp.zeros((k,) + o_s.shape, o_s.dtype)
            outv = jnp.zeros((k,) + o_v.shape, o_v.dtype)

            def cond(c):
                served, steps = c[3], c[4]
                total = jax.lax.psum(jnp.sum(served), axis)
                return (total < global_target) & (steps < max_steps)

            def body(c):
                fst, cache, sess, served, steps, outs, outv = c
                s = jax.lax.dynamic_index_in_dim(in_slots, steps, 0,
                                                 keepdims=False)
                v = jax.lax.dynamic_index_in_dim(in_valid, steps, 0,
                                                 keepdims=False)
                fst, cache, sess, n, os_, ov_ = vstep(fst, cache, sess,
                                                      params, s, v)
                outs = jax.lax.dynamic_update_index_in_dim(outs, os_,
                                                           steps, 0)
                outv = jax.lax.dynamic_update_index_in_dim(outv, ov_,
                                                           steps, 0)
                return fst, cache, sess, served + n, steps + 1, outs, outv

            carry = (fst, cache, sess, jnp.zeros((tl,), jnp.int32),
                     jnp.int32(0), outs, outv)
            fst, cache, sess, served, steps, outs, outv = \
                jax.lax.while_loop(cond, body, carry)
            return fst, cache, sess, served, steps.reshape(1), outs, outv

        return self._sharded_runner(mesh, axis, local,
                                    n_scalar_args=2, n_device_outs=1)

    # ------------------------------------------------------------------
    def prefill_sessions(self, cache, sess: SessionState, prompts,
                         session_ids):
        """Batch-prefill ``prompts`` [Nslots, S] into fresh sessions."""
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        logits, cache = self.model.prefill(self.params, batch, cache)
        s = prompts.shape[1]
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sess = SessionState(jnp.asarray(session_ids, jnp.int32),
                            jnp.full((self.n_slots,), s, jnp.int32),
                            next_tok)
        return cache, sess, next_tok
