"""NIC virtualization: multiple Dagger NIC instances + L2 switch (§5.7).

The paper instantiates one NIC per microservice tier on a single FPGA,
arbitrates CCI-P access round-robin, and connects the NICs through a
static-table L2 switch model.  Here:

* each tier owns a ``DaggerFabric`` + ``FabricState``;
* tiers sharing one hard configuration (the synthesized bitstream) are
  *stacked*: their states become one ``FabricState`` pytree with a
  leading tier axis, and ``switch_step_stacked`` drives every NIC's
  fetch/deliver/emit as ``jax.vmap``-ed batched array ops — one fused,
  jit-able, ``lax.scan``-able device step for the whole mesh of tiers;
* the round-robin *arbiter* is the step scheduler itself: every NIC's
  pipeline runs once per switch step, which is exactly fair round-robin
  sharing of the (single) device;
* EVERY tier's RX rings are drained each step and surfaced through the
  returned completions — a tier without a dispatch handler (``None``,
  i.e. a pure client) hands its in-flight responses to the caller
  instead of letting them pile up until the rings overflow and the
  delivery stage drops them (the silent-drop bug the regression test in
  ``tests/test_virtualization.py`` pins down);
* on a device mesh, ``switch_step_sharded`` routes the crossbar's
  inter-shard records through the ``transport`` all-to-all ToR hop —
  full-tile buckets (the bit-exact oracle) or compacted
  destined-rows-plus-count buckets (``exchange="compact"``), whose
  completions are record-set-identical under the
  ``canonicalize_completions`` comparator below.

Destination lookup uses connection-table read port 1 (read_dest) on the
sending NIC — the 1W3R concurrent read the paper's cache layout enables.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.config import FabricConfig
from repro.core import monitor, serdes
from repro.core import telemetry as tlm
from repro.core.connection import ConnTable
from repro.core.engine import stack_states, unstack_states
from repro.core.fabric import DaggerFabric, FabricState, fused_switch_front


def raw_handler(fn):
    """Mark a switch dispatch handler as a RAW-record handler.

    A plain handler sees only the tier's drained REQUESTS
    (``valid = drained & ~RESPONSE``) and its returned records are
    force-flagged as responses.  A ``raw_handler`` instead receives
    EVERY drained row (responses included — the drain mask itself) and
    must return ``(records, out_valid)`` with fully-formed ``flags``:
    nothing is forced, rows it does not emit must be masked out of
    ``out_valid``.  This is what proxy/forwarding tiers need — e.g. the
    flight-registration Check-in tier, which consumes a response from
    one hop and re-emits it as a fresh REQUEST for the next hop
    (``repro.apps.flight``).  Any handler may also return the
    ``(records, valid)`` tuple to override the emit mask without the
    raw drain semantics.
    """
    fn.full_drain = True
    return fn


def _dispatch(h, recs, drained, is_req):
    """Run one tier's dispatch handler under the switch contract.

    Returns (response records, emit valid).  ``None`` = pure client
    (nothing emitted); plain handlers get requests only and are
    response-flagged; tuple-returning handlers own their flags/mask.
    """
    v_req = drained & is_req
    if h is None:
        return recs, jnp.zeros_like(v_req)
    full = getattr(h, "full_drain", False)
    out = h(recs, drained if full else v_req)
    if out is None:                    # consume-only dispatch
        return recs, jnp.zeros_like(v_req)
    if isinstance(out, tuple):
        return out
    out["flags"] = out["flags"] | serdes.FLAG_RESPONSE
    return out, v_req


def canonicalize_completions(recs, valid):
    """Sort a completion batch into canonical per-tier order.

    recs: record dict with [T, N, ...] leaves; valid: [T, N] bool.
    Within each tier, valid records are sorted by ``(conn_id, rpc_id,
    frag_idx)`` and moved to the front; invalid rows are zeroed so they
    cannot leak arbitrary ring contents into comparisons.  Returns
    ``(recs', valid')`` with the same shapes.

    This is the reordering-tolerant parity mode for the compacted
    sharded switch: the compacted exchange may place a record at a
    different position of the receive tile than the full-tile path does,
    so completions can come off the RX rings at different batch slots.
    Canonicalizing both sides turns positional equality into
    set-equality + per-RPC bit-exactness — the contract
    ``tests/test_compact_exchange.py`` pins.
    """
    valid = jnp.asarray(valid, bool)
    inv = (~valid).astype(jnp.int32)
    # lexsort: last key is primary -> invalid rows last, then the
    # (conn_id, rpc_id, frag_idx) canonical order among valid rows
    order = jnp.lexsort((recs["frag_idx"], recs["rpc_id"],
                         recs["conn_id"], inv), axis=-1)

    def gather(x):
        idx = order.reshape(order.shape + (1,) * (x.ndim - 2))
        return jnp.take_along_axis(x, idx, axis=1)

    sval = jnp.take_along_axis(valid, order, axis=1)

    def mask(x):
        m = sval.reshape(sval.shape + (1,) * (x.ndim - 2))
        return jnp.where(m, x, 0)

    return jax.tree.map(lambda x: mask(gather(x)), recs), sval


class Switch:
    """Static L2 switch over N virtual NICs on one device."""

    def __init__(self, fabrics: List[DaggerFabric]):
        self.fabrics = fabrics
        self.n = len(fabrics)
        # tiers with one hard configuration stack into batched arrays;
        # heterogeneous meshes fall back to the per-tier loop
        self.homogeneous = all(f.cfg == fabrics[0].cfg for f in fabrics)

    def init_states(self) -> List[FabricState]:
        return [f.init_state() for f in self.fabrics]

    # ------------------------------------------------- stacked representation
    def stack_states(self, states: List[FabricState]) -> FabricState:
        """Per-tier states -> one batched FabricState (leading tier axis)."""
        return stack_states(states)

    def unstack_states(self, stacked: FabricState) -> List[FabricState]:
        return unstack_states(stacked, self.n)

    def switch_step_stacked(self, stacked: FabricState,
                            handlers: Optional[List[Callable]] = None,
                            tel=None, use_pallas: Optional[bool] = None,
                            loadgen=None, gen=None):
        """One fused step over the stacked tier axis: vmapped fetch from
        every NIC, switch, vmapped deliver + emit, per-tier dispatch
        handlers, vmapped response enqueue, vmapped completion drain.

        handlers[i]: (records, valid) -> response records, or None for
        pure-client tiers; ``raw_handler``-marked handlers see every
        drained row and return ``(records, valid)`` with their own
        flags (proxy tiers).  Pure function of ``stacked`` — jit it,
        scan it.  Returns (stacked', (records [T, N, ...], valid
        [T, N])); the completions cover EVERY tier (see module
        docstring).

        ``tel`` (``telemetry.create_batch(T)``) threads PER-TIER latency
        telemetry: each tier observes the RESPONSES it drains this step
        (residency = step - the record's stamped issue step + 1), then
        every tier's step counter ticks — appended as a third return.

        ``use_pallas`` (default: the fabric's ``cfg.use_pallas``) routes
        the whole front half — fetch, crossbar, deliver, emit, drain,
        telemetry observe — through the single ``switch_step_fused``
        Pallas megakernel; this jnp composition is its bit-exact oracle
        (dispatch handlers + response enqueue stay host-composed either
        way, preserving the ``raw_handler`` contract).

        ``loadgen`` + ``gen`` (a ``core.loadgen.LoadGen`` and a stacked
        per-TIER ``LoadGenState``, passed together) run open-loop
        injection before the fetch: tier i offers ``gen.rate[i]``
        requests/step into its own TX rings regardless of completions
        (serving tiers use rate 0).  Injection rides BOTH switch paths
        outside the fused kernel, so Pallas/jnp parity is unaffected.
        The updated ``gen`` is appended as the LAST return.
        """
        if not self.homogeneous:
            raise ValueError("stacked switch step needs homogeneous tiers")
        if (loadgen is None) != (gen is None):
            raise ValueError("loadgen and gen must be passed together")
        fab = self.fabrics[0]
        t = self.n
        fused = fab.cfg.use_pallas if use_pallas is None else use_pallas
        if loadgen is not None:
            stacked, gen = jax.vmap(loadgen.inject)(stacked, gen)

        if fused:
            sts, flat_r, fv, ntel = fused_switch_front(fab, stacked, tel)
        else:
            with jax.named_scope(tlm.SCOPE_SWITCH_FETCH):
                # every NIC fetches its host-written tile (CCI-P batched
                # read)
                sts, slots, valid = jax.vmap(fab.nic_fetch)(stacked)
                w = slots.shape[-1]
                flat = slots.reshape(t, -1, w)
                fval = valid.reshape(t, -1)
                # read port 1: destination credentials for outgoing RPCs;
                # responses travel back to the connection's *client* NIC
                # which is also stored as dest on the serving side's conn
                # entry
                cid = flat[..., 0]
                dest, hit = jax.vmap(ConnTable.read_dest)(sts.conn, cid)

                # the L2 crossbar: all tiers' tiles against all
                # destinations
                all_slots = flat.reshape(-1, w)
                all_valid = (fval & hit).reshape(-1)
                all_dest = dest.reshape(-1)
                sel = (all_dest[None, :] == jnp.arange(t)[:, None]) \
                    & all_valid[None, :]                       # [T, T*N]
            with jax.named_scope(tlm.SCOPE_SWITCH_DELIVER):
                sts = jax.vmap(fab.nic_deliver, in_axes=(0, None, 0))(
                    sts, all_slots, sel)
            with jax.named_scope(tlm.SCOPE_SWITCH_EMIT):
                sts = jax.vmap(fab.nic_sched_emit)(sts)

            # dispatch: EVERY tier drains its RX rings (completion queues)
            with jax.named_scope(tlm.SCOPE_SWITCH_DRAIN):
                sts, recs, rvalid = jax.vmap(
                    lambda s: fab.host_rx_drain(s, fab.cfg.batch_size))(sts)
            flat_r = jax.tree.map(
                lambda x: x.reshape((t, -1) + x.shape[3:]), recs)
            fv = rvalid.reshape(t, -1)

        is_req = (flat_r["flags"] & serdes.FLAG_RESPONSE) == 0

        # per-tier dispatch handlers (T is small hard configuration, so the
        # unrolled Python loop is trace-time only; the array ops stay batched)
        resps, rvalids = [], []
        for i in range(t):
            h = handlers[i] if handlers else None
            out, ov = _dispatch(h, jax.tree.map(lambda x: x[i], flat_r),
                                fv[i], is_req[i])
            resps.append(out)
            rvalids.append(ov)
        resp = jax.tree.map(lambda *xs: jnp.stack(xs), *resps)
        rv = jnp.stack(rvalids)
        flow_of = jnp.repeat(jnp.arange(fab.cfg.n_flows, dtype=jnp.int32),
                             fab.cfg.batch_size)
        sts, _ = jax.vmap(fab.host_tx_enqueue, in_axes=(0, 0, None, 0))(
            sts, resp, flow_of, rv)
        if tel is None:
            out = (sts, (flat_r, fv))
        elif fused:
            out = (sts, (flat_r, fv), ntel)
        else:
            # per-tier telemetry: a drained RESPONSE is a completion of
            # an RPC this tier issued — observe it against the stamped
            # issue step, then tick every tier's fabric-step counter
            tel = jax.vmap(tlm.observe)(tel, flat_r["timestamp"],
                                        fv & ~is_req)
            tel = jax.vmap(tlm.tick)(tel)
            out = (sts, (flat_r, fv), tel)
        if gen is not None:
            out = out + (gen,)
        return out

    # ------------------------------------------------- sharded representation
    def switch_step_sharded(self, stacked: FabricState,
                            handlers: Optional[List[Callable]] = None,
                            mesh=None, axis: str = "tenant",
                            exchange: str = "full",
                            bucket_cap: Optional[int] = None,
                            tel=None, use_pallas: Optional[bool] = None,
                            loadgen=None, gen=None):
        """``switch_step_stacked`` on a device mesh: each device owns a
        contiguous block of T/D whole tiers (NIC slots) of the stacked
        state, runs fetch/deliver/emit/dispatch device-local, and the L2
        crossbar's inter-shard records ride the mesh ToR hop —
        ``transport.all_to_all_tiles`` buckets, one per destination
        device (the paper's top-of-rack switch mapped onto the
        interconnect; Beehive's explicit inter-lane transport).

        Two exchange formats (``exchange``):

        * ``"full"`` (default, the oracle) — every source ships its full
          fetched tile to every destination with a per-destination valid
          mask, so after the exchange each device sees the GLOBAL
          candidate list in tier order — delivery arbitration therefore
          processes valid slots in exactly the order
          ``switch_step_stacked`` does, and the results are
          bit-identical on any mesh shape (pinned by
          ``tests/test_sharded_parity.py``).  Wire cost grows with the
          mesh (``transport.full_exchange_words``), not with offered
          load.
        * ``"compact"`` — per-destination buckets carry ONLY destined
          rows plus a count (``transport.exchange_compact``); wire cost
          is ``transport.compact_exchange_words`` with ``bucket_cap``
          rows per bucket (default: the whole local tile, which can
          never overflow — shrink it toward the expected cross-shard
          burst to shrink the exchange).  The stable compaction keeps
          same-destination rows in full-tile order, so delivered records
          are identical; only RX-batch POSITIONS of completions may
          differ.  Parity contract: set-equality + per-RPC
          bit-exactness under ``canonicalize_completions`` (pinned by
          ``tests/test_compact_exchange.py``).  Rows exceeding
          ``bucket_cap`` are dropped ON THE WIRE (unlike ring-full
          backpressure there is no leak-back retry); the default cap
          never drops, and when a shrunken cap does, each source
          tier's packet monitor counts its losses in
          ``mon["drops_exchange"]``.

        ``handlers[i]`` may differ per GLOBAL tier (selected with
        ``lax.switch`` on the device-local tier's global id); every
        handler must return a record dict structurally identical to its
        input (``None`` tiers are pure clients, and ``raw_handler`` /
        tuple-returning handlers work as in the stacked step).
        Returns (stacked', (records [T, N, ...], valid [T, N])) with the
        leading tier axis sharded over ``axis``.

        ``tel`` (``telemetry.create_batch(T)``, sharded with the
        states) threads per-tier telemetry exactly as
        ``switch_step_stacked`` does — observed device-local on each
        tier's drained responses, appended as a third return.

        ``use_pallas`` (default: ``cfg.use_pallas``) fuses each device's
        post-exchange back half — deliver, emit, drain, telemetry — into
        the ``switch_step_fused`` megakernel (fetch and the collective
        exchange cannot fuse across devices and stay composed).

        ``loadgen`` + ``gen`` (per-TIER ``LoadGenState``, sharded with
        the states) inject open-loop arrivals device-local before the
        fetch, exactly as in ``switch_step_stacked``; the updated
        ``gen`` is appended as the LAST return.
        """
        from jax.sharding import PartitionSpec as P

        from repro.core import transport
        from repro.core.transport import shard_map

        if not self.homogeneous:
            raise ValueError("sharded switch step needs homogeneous tiers")
        if exchange not in ("full", "compact"):
            raise ValueError(f"exchange must be 'full' or 'compact', "
                             f"got {exchange!r}")
        if (loadgen is None) != (gen is None):
            raise ValueError("loadgen and gen must be passed together")
        if mesh is None:
            mesh = transport.make_tenant_mesh(axis=axis)
        fab = self.fabrics[0]
        t = self.n
        d = mesh.shape[axis]
        if t % d:
            raise ValueError(f"n_tiers={t} must divide over the {d}-device "
                             f"'{axis}' mesh axis")
        tl = t // d

        def branch(i):
            h = handlers[i] if handlers else None

            def run(r_i, drained, is_req_i):
                return _dispatch(h, r_i, drained, is_req_i)
            return run

        branches = [branch(i) for i in range(t)]
        with_tel = tel is not None
        with_gen = gen is not None
        fused = fab.cfg.use_pallas if use_pallas is None else use_pallas

        def local(sts, *extra):
            ltel = extra[0] if with_tel else None
            lgen = extra[-1] if with_gen else None
            if with_gen:
                # open-loop injection, device-local, before the fetch
                sts, lgen = jax.vmap(loadgen.inject)(sts, lgen)
            dev = jax.lax.axis_index(axis)
            with jax.named_scope(tlm.SCOPE_SWITCH_FETCH):
                sts, slots, valid = jax.vmap(fab.nic_fetch)(sts)
                w = slots.shape[-1]
                flat = slots.reshape(tl, -1, w)
                fval = valid.reshape(tl, -1)
                cid = flat[..., 0]
                dest, hit = jax.vmap(ConnTable.read_dest)(sts.conn, cid)

            # ToR hop: one bucket per destination device, exchanged
            # all-to-all — full tile + mask (order-exact oracle) or
            # compacted destined-rows-plus-count buckets
            loc_slots = flat.reshape(-1, w)
            loc_valid = (fval & hit).reshape(-1)
            loc_dest = dest.reshape(-1)
            nb = loc_slots.shape[0]
            if exchange == "compact":
                cap = nb if bucket_cap is None else bucket_cap
                rows, all_valid, _, shipped = transport.exchange_compact(
                    {"slots": loc_slots, "dest": loc_dest}, loc_valid,
                    loc_dest // tl, axis, d, cap)
                all_slots, all_dest = rows["slots"], rows["dest"]
                # bucket overflow loses rows ON THE WIRE (no free-FIFO
                # leak-back to retry): charge each source tier's packet
                # monitor so an undersized cap is auditable
                tier_drops = jnp.sum(
                    (loc_valid & ~shipped).reshape(tl, -1)
                    .astype(jnp.int32), axis=1)
                sts = dataclasses.replace(
                    sts, mon=monitor.bump(sts.mon,
                                          drops_exchange=tier_drops))
            else:
                owner = jnp.arange(d, dtype=loc_dest.dtype)[:, None]
                mask = (loc_dest[None, :] // tl) == owner      # [D, nb]
                bucket = {
                    "slots": jnp.broadcast_to(
                        loc_slots[None], (d, nb, w)).reshape(d * nb, w),
                    "valid": (loc_valid[None, :] & mask).reshape(d * nb),
                    "dest": jnp.broadcast_to(loc_dest[None],
                                             (d, nb)).reshape(d * nb),
                }
                g = transport.all_to_all_tiles(bucket, axis)
                # block j of the exchange = device j's tile:
                # concatenated, that is the global candidate list in
                # tier order
                all_slots, all_valid, all_dest = (g["slots"], g["valid"],
                                                  g["dest"])

            if fused:
                # fused back half: dest rebased to device-local tier ids
                # (rows destined elsewhere fall out of [0, tl) and the
                # kernel's range mask reproduces the ``sel`` crossbar)
                sts, flat_r, fv, ltel = fused_switch_front(
                    fab, sts, ltel,
                    ext=(all_slots, all_valid, all_dest - dev * tl))
            else:
                gids = dev * tl + jnp.arange(tl, dtype=jnp.int32)
                sel = (all_dest[None, :] == gids[:, None]) \
                    & all_valid[None, :]
                with jax.named_scope(tlm.SCOPE_SWITCH_DELIVER):
                    sts = jax.vmap(fab.nic_deliver, in_axes=(0, None, 0))(
                        sts, all_slots, sel)
                with jax.named_scope(tlm.SCOPE_SWITCH_EMIT):
                    sts = jax.vmap(fab.nic_sched_emit)(sts)

                # dispatch: every local tier drains; handlers are selected
                # by the tier's GLOBAL id so heterogeneous handler lists
                # work
                with jax.named_scope(tlm.SCOPE_SWITCH_DRAIN):
                    sts, recs, rvalid = jax.vmap(
                        lambda s: fab.host_rx_drain(
                            s, fab.cfg.batch_size))(sts)
                flat_r = jax.tree.map(
                    lambda x: x.reshape((tl, -1) + x.shape[3:]), recs)
                fv = rvalid.reshape(tl, -1)
            is_req = (flat_r["flags"] & serdes.FLAG_RESPONSE) == 0

            resps, rvalids = [], []
            for j in range(tl):
                r_j = jax.tree.map(lambda x: x[j], flat_r)
                out, ov = jax.lax.switch(dev * tl + j, branches, r_j,
                                         fv[j], is_req[j])
                resps.append(out)
                rvalids.append(ov)
            resp = jax.tree.map(lambda *xs: jnp.stack(xs), *resps)
            rv = jnp.stack(rvalids)
            flow_of = jnp.repeat(
                jnp.arange(fab.cfg.n_flows, dtype=jnp.int32),
                fab.cfg.batch_size)
            sts, _ = jax.vmap(fab.host_tx_enqueue, in_axes=(0, 0, None, 0))(
                sts, resp, flow_of, rv)
            if with_tel and not fused:
                ltel = jax.vmap(tlm.observe)(ltel, flat_r["timestamp"],
                                             fv & ~is_req)
                ltel = jax.vmap(tlm.tick)(ltel)
            outs = (sts, flat_r, fv)
            if with_tel:
                outs = outs + (ltel,)
            if with_gen:
                outs = outs + (lgen,)
            return outs

        sspec = jax.tree.map(lambda _: P(axis), stacked)
        lane = P(axis)
        in_specs, args = [sspec], [stacked]
        out_specs = [sspec, lane, lane]
        if with_tel:
            tspec = jax.tree.map(lambda _: P(axis), tel)
            in_specs.append(tspec)
            args.append(tel)
            out_specs.append(tspec)
        if with_gen:
            gspec = jax.tree.map(lambda _: P(axis), gen)
            in_specs.append(gspec)
            args.append(gen)
            out_specs.append(gspec)
        outs = shard_map(
            local, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=tuple(out_specs))(*args)
        sts, flat_r, fv = outs[:3]
        ret = (sts, (flat_r, fv))
        if with_tel:
            ret = ret + (outs[3],)
        if with_gen:
            ret = ret + (outs[-1],)
        return ret

    # --------------------------------------------------------- list API
    def switch_step(self, states: List[FabricState],
                    handlers: Optional[List[Callable]] = None):
        """One fused step: fetch from every NIC, switch, deliver, emit,
        run per-tier dispatch handlers, enqueue their responses.

        handlers[i]: (records, valid) -> response records, or None for
        tiers that only consume.  Contract: every tier is drained each
        step; completions[i] is ``(records, valid)`` for ALL tiers (a
        ``None``-handler tier's responses arrive here instead of rotting
        in its RX rings until the fabric drops them).
        """
        if self.homogeneous:
            stacked, (recs, fv) = self.switch_step_stacked(
                self.stack_states(states), handlers)
            completions = [(jax.tree.map(lambda x: x[i], recs), fv[i])
                           for i in range(self.n)]
            return self.unstack_states(stacked), completions
        return self._switch_step_loop(states, handlers)

    def _switch_step_loop(self, states: List[FabricState],
                          handlers: Optional[List[Callable]] = None):
        """Per-tier reference path (heterogeneous hard configurations)."""
        tiles = []
        new_states = list(states)
        for i, fab in enumerate(self.fabrics):
            st, slots, valid = fab.nic_fetch(new_states[i])
            new_states[i] = st
            flat_slots = slots.reshape(-1, slots.shape[-1])
            flat_valid = valid.reshape(-1)
            rec = serdes.unpack(flat_slots)
            dest, hit = st.conn.read_dest(rec["conn_id"])
            tiles.append((flat_slots, flat_valid & hit, dest))

        all_slots = jnp.concatenate([s for s, _, _ in tiles], axis=0)
        all_valid = jnp.concatenate([v for _, v, _ in tiles], axis=0)
        all_dest = jnp.concatenate([d for _, _, d in tiles], axis=0)

        for i, fab in enumerate(self.fabrics):
            sel = all_valid & (all_dest == i)
            st = fab.nic_deliver(new_states[i], all_slots, sel)
            st = fab.nic_sched_emit(st)
            new_states[i] = st

        completions = []
        for i, fab in enumerate(self.fabrics):
            h = handlers[i] if handlers else None
            st, recs, rvalid = fab.host_rx_drain(new_states[i],
                                                 fab.cfg.batch_size)
            flat = jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), recs)
            fvalid = rvalid.reshape(-1)
            is_req = (flat["flags"] & serdes.FLAG_RESPONSE) == 0
            if h is not None:
                resp, ov = _dispatch(h, flat, fvalid, is_req)
                if resp is not None:
                    flow_of = jnp.repeat(
                        jnp.arange(fab.cfg.n_flows, dtype=jnp.int32),
                        fab.cfg.batch_size)
                    st, _ = fab.host_tx_enqueue(st, resp, flow_of, ov)
            completions.append((flat, fvalid))
            new_states[i] = st
        return new_states, completions
