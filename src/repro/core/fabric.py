"""DaggerFabric — the full NIC pipeline (paper Fig. 6/8/9), functional JAX.

Directions follow the paper's naming (as seen FROM the NIC):

* **RX path** (§4.4.1, NIC receiving from the host): host threads write
  ready-to-use RPC objects into per-flow TX rings — the "single memory
  write" critical path — and ``nic_fetch`` drains up to B slots per flow
  per step (the CCI-P batched read; B is *soft* configuration).

* **TX path** (§4.4.2, NIC transmitting to the host): RPCs arriving from
  the network are stored in the *request buffer* (slot table) with a
  *free-slot FIFO*; the load balancer pushes slot references into per-flow
  *flow FIFOs*; the *flow scheduler* picks flows holding a full batch and
  the CCI-P transmitter copies payloads into the host RX rings, with
  back-pressure (flow blocking) instead of loss when an RX ring is full.

Connection lookup is 1W3R against the pre-write table state; response
steering returns responses to the flow their request came from (SRQ
model).  All stages are pure functions over ``FabricState`` so the whole
pipeline fuses into a single device step — the Dagger analogue of running
the RPC stack "on the NIC" instead of on the host CPU.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import FabricConfig
from repro.core import load_balancer as lb
from repro.core import monitor, serdes
from repro.core import telemetry as tlm
from repro.core.connection import ConnTable
from repro.core.rings import FreeFifo, Ring


@jax.tree_util.register_dataclass
@dataclass
class SoftConfig:
    """Runtime-tunable registers (paper: CSR writes; here device scalars)."""
    batch: jnp.ndarray          # CCI-P batching width B
    active_flows: jnp.ndarray   # number of live flows
    force_flush: jnp.ndarray    # emit partial batches (dynamic-B low-load)


@jax.tree_util.register_dataclass
@dataclass
class FabricState:
    tx: Ring                    # host -> NIC rings [F, E, W]
    rx: Ring                    # NIC -> host rings [F, E, W]
    req_table: jnp.ndarray      # [R, W] request buffer (paper Fig. 9B)
    free: FreeFifo              # free-slot FIFO over req_table
    flow_fifo: Ring             # [F, D, 1] slot-id references
    conn: ConnTable
    rr: jnp.ndarray             # round-robin cursor
    soft: SoftConfig
    mon: dict


class DaggerFabric:
    """Hard configuration + the pipeline stage functions.

    Changing any ``FabricConfig`` field is *hard* reconfiguration (new
    traces); mutating ``state.soft`` fields is *soft* reconfiguration.
    """

    def __init__(self, cfg: FabricConfig):
        self.cfg = cfg
        self.slot_words = cfg.slot_bytes // 4
        if cfg.use_pallas:
            from repro.kernels import ops as kops
            self._gather_slots = kops.ring_gather
        else:
            self._gather_slots = None

    # ------------------------------------------------------------------
    def init_state(self) -> FabricState:
        c = self.cfg
        w = self.slot_words
        r = c.resolved_request_buffer_slots
        return FabricState(
            tx=Ring.create(c.n_flows, c.ring_entries, w),
            rx=Ring.create(c.n_flows, c.ring_entries, w),
            req_table=jnp.zeros((r, w), jnp.int32),
            free=FreeFifo.create(r),
            flow_fifo=Ring.create(c.n_flows, max(c.ring_entries, r), 1),
            conn=ConnTable.create(c.conn_cache_entries),
            rr=jnp.int32(0),
            soft=SoftConfig(jnp.int32(c.batch_size),
                            jnp.int32(c.active_flows or c.n_flows),
                            jnp.bool_(not c.dynamic_batching)),
            mon=monitor.create(),
        )

    # ---------------------------------------------------------- host side
    @jax.named_scope(tlm.SCOPE_NIC_ENQUEUE)
    def host_tx_enqueue(self, st: FabricState, records, flow_ids,
                        valid=None) -> Tuple[FabricState, jnp.ndarray]:
        """The host's single memory write: pack records into TX ring slots."""
        slots = serdes.pack(records, self.slot_words)
        if valid is None:
            valid = jnp.ones((slots.shape[0],), bool)
        tx, accepted = st.tx.push(jnp.asarray(flow_ids, jnp.int32) %
                                  self.cfg.n_flows, slots, valid,
                                  use_pallas=self.cfg.use_pallas)
        rejected = jnp.sum((jnp.asarray(valid) & ~accepted)
                           .astype(jnp.int32))
        mon = monitor.bump(st.mon, drops_tx_full=rejected)
        return _replace(st, tx=tx, mon=mon), accepted

    @jax.named_scope(tlm.SCOPE_NIC_DRAIN)
    def host_rx_drain(self, st: FabricState, max_n: int):
        """Completion-queue drain: read + consume RX ring entries."""
        slots, valid = st.rx.peek(max_n)
        n = jnp.sum(valid.astype(jnp.int32), axis=1)
        rx = st.rx.advance(n)
        mon = monitor.bump(st.mon, rpcs_completed=jnp.sum(n))
        recs = serdes.unpack(slots)
        return _replace(st, rx=rx, mon=mon), recs, valid

    # ----------------------------------------------------------- NIC side
    @jax.named_scope(tlm.SCOPE_NIC_FETCH)
    def nic_fetch(self, st: FabricState):
        """CCI-P batched fetch from host TX rings (paper RX path).

        Returns (state, slots [F, Bmax, W], valid [F, Bmax])."""
        bmax = self.cfg.batch_size
        b = jnp.clip(st.soft.batch, 1, bmax)
        counts = st.tx.occupancy()
        take = jnp.minimum(counts, b)
        slots, _ = st.tx.peek(bmax)
        valid = jnp.arange(bmax)[None, :] < take[:, None]
        tx = st.tx.advance(take)
        mon = monitor.bump(st.mon, rpcs_ingested=jnp.sum(take))
        return _replace(st, tx=tx, mon=mon), slots, valid

    @jax.named_scope(tlm.SCOPE_NIC_DELIVER)
    def nic_deliver(self, st: FabricState, slots, valid, use_pallas=None):
        """Network -> request buffer -> steer -> flow FIFOs (paper TX path).

        slots: [N, W]; valid: [N].  With ``use_pallas`` (default: the
        fabric's ``cfg.use_pallas``) the whole stage — free-slot
        allocation, connection steering, and the flow-FIFO scatter — runs
        as the single fused ``nic_deliver_fused`` Pallas megakernel; the
        jnp composition below is its oracle."""
        c = self.cfg
        fused = c.use_pallas if use_pallas is None else use_pallas
        if fused:
            return self._nic_deliver_fused(st, slots, valid)
        free, slot_ids, granted = st.free.allocate(valid)
        drops_no_slot = jnp.sum((valid & ~granted).astype(jnp.int32))
        req_table = st.req_table.at[slot_ids].set(slots, mode="drop")

        rec = serdes.unpack(slots)
        is_resp = (rec["flags"] & serdes.FLAG_RESPONSE) != 0
        # 1W3R read port 2 (pre-write state; there is no conn write here)
        src_flow, lb_scheme, hit = st.conn.read_flow(rec["conn_id"])
        active = jnp.clip(st.soft.active_flows, 1, c.n_flows)
        # invalid lanes (partially-filled tiles, stale peeked slots) must
        # not consume round-robin positions or advance the cursor
        flow, rr = lb.steer(lb_scheme, rec["payload"], src_flow, st.rr,
                            active, valid=jnp.asarray(valid))
        # responses return to the flow their request was issued from (SRQ)
        flow = jnp.where(is_resp & hit, src_flow % active, flow)

        ff, accepted = st.flow_fifo.push(flow, slot_ids[:, None], granted)
        leaked = granted & ~accepted            # FIFO full -> give slot back
        free = free.release(slot_ids, leaked)
        mon = monitor.bump(
            st.mon, drops_no_slot=drops_no_slot,
            drops_fifo_full=jnp.sum(leaked.astype(jnp.int32)),
            rpcs_delivered=jnp.sum(accepted.astype(jnp.int32)))
        return _replace(st, req_table=req_table, free=free, flow_fifo=ff,
                        rr=rr, mon=mon)

    def _nic_deliver_fused(self, st: FabricState, slots, valid):
        """The megakernel path: one Pallas call for the whole TX delivery
        stage (steer + FIFO-allocate + ring scatter); cursor/counter
        updates stay outside as scalar arithmetic."""
        from repro.kernels import ops as kops
        c = self.cfg
        valid = jnp.asarray(valid)
        active = jnp.clip(st.soft.active_flows, 1, c.n_flows)
        ff = st.flow_fifo
        ffspace = ff.capacity - (ff.tail - ff.head)
        scal = jnp.stack([st.free.head, st.free.available(), st.free.tail,
                          st.rr, active]).astype(jnp.int32)
        (req_table, ffbuf, fifo, _, flow, granted_i, accepted_i,
         acc_counts, ctr) = kops.nic_deliver_fused(
            slots, valid.astype(jnp.int32), st.free.fifo, st.req_table,
            ff.buf[..., 0], st.conn.tag, st.conn.src_flow, st.conn.lb,
            ff.tail, ffspace, scal)
        granted = granted_i != 0
        accepted = accepted_i != 0
        free = FreeFifo(fifo, st.free.head + ctr[0], st.free.tail + ctr[1])
        ff2 = Ring(ffbuf[..., None], ff.head, ff.tail + acc_counts)
        rr = (st.rr + ctr[2]) % active
        mon = monitor.bump(
            st.mon,
            drops_no_slot=jnp.sum((valid & ~granted).astype(jnp.int32)),
            drops_fifo_full=ctr[1],
            rpcs_delivered=jnp.sum(accepted.astype(jnp.int32)))
        return _replace(st, req_table=req_table, free=free, flow_fifo=ff2,
                        rr=rr, mon=mon)

    @jax.named_scope(tlm.SCOPE_NIC_EMIT)
    def nic_sched_emit(self, st: FabricState):
        """Flow scheduler + CCI-P transmitter: flow FIFOs -> host RX rings."""
        c = self.cfg
        bmax = c.batch_size
        b = jnp.clip(st.soft.batch, 1, bmax)
        counts = st.flow_fifo.occupancy()
        ready = (counts >= b) | st.soft.force_flush
        take = jnp.where(ready, jnp.minimum(counts, b), 0)
        # back-pressure: only emit into RX rings with space (flow blocking)
        space = st.rx.capacity - st.rx.occupancy()
        take = jnp.where(space >= take, take, 0)

        refs, _ = st.flow_fifo.peek(bmax)               # [F, Bmax, 1]
        lane_valid = jnp.arange(bmax)[None, :] < take[:, None]
        refs = jnp.where(lane_valid[..., None], refs,
                         st.req_table.shape[0])         # OOB sentinel
        if self._gather_slots is not None:
            payload = self._gather_slots(st.req_table, refs[..., 0])
        else:
            payload = st.req_table.at[refs[..., 0]].get(
                mode="fill", fill_value=0)              # [F, Bmax, W]

        f = c.n_flows
        flow_ids = jnp.repeat(jnp.arange(f, dtype=jnp.int32), bmax)
        rx, accepted = st.rx.push(flow_ids, payload.reshape(f * bmax, -1),
                                  lane_valid.reshape(-1),
                                  use_pallas=c.use_pallas)
        ff = st.flow_fifo.advance(take)
        free = st.free.release(refs[..., 0].reshape(-1),
                               lane_valid.reshape(-1))
        mon = monitor.bump(
            st.mon, rpcs_emitted=jnp.sum(take),
            batches_emitted=jnp.sum((take > 0).astype(jnp.int32)))
        return _replace(st, rx=rx, flow_fifo=ff, free=free, mon=mon)

    def nic_pipeline(self, st: FabricState, slots, valid, use_pallas=None):
        """Fused deliver -> emit -> drain over one wire-ingress tile.

        Semantically ``nic_deliver; nic_sched_emit; host_rx_drain(B)``;
        with ``use_pallas`` (default: ``cfg.use_pallas``) the whole
        back-half runs as the single ``switch_step_fused`` megakernel
        (a one-tier stack with every row destined here).  Returns
        ``(state', records [F, B, ...], valid [F, B])`` exactly like
        ``host_rx_drain``."""
        c = self.cfg
        fused = c.use_pallas if use_pallas is None else use_pallas
        if not fused:
            st = self.nic_deliver(st, slots, valid, use_pallas=False)
            st = self.nic_sched_emit(st)
            return self.host_rx_drain(st, c.batch_size)
        stacked = jax.tree.map(lambda x: x[None], st)
        ext = (slots, jnp.asarray(valid).astype(jnp.int32),
               jnp.zeros((slots.shape[0],), jnp.int32))
        with jax.named_scope(tlm.SCOPE_NIC_PIPELINE):
            sts, flat_r, fv, _ = fused_switch_front(self, stacked, None,
                                                    ext=ext)
        st2 = jax.tree.map(lambda x: x[0], sts)
        bmax = c.batch_size
        recs = jax.tree.map(
            lambda x: x[0].reshape((c.n_flows, bmax) + x.shape[2:]),
            flat_r)
        return st2, recs, fv[0].reshape(c.n_flows, bmax)

    @jax.named_scope(tlm.SCOPE_QUEUES)
    def count_queues(self, st: FabricState) -> FabricState:
        """Add each kind of ring's total occupancy to the monitor's
        ``queued_*`` counters; called once per fused step, at its end.

        At a step's end every RPC in flight waits in exactly one ring of
        one NIC, so summed over both NICs of a loopback pair the counters
        grow by one per RPC and step it waits: an RPC of residency L
        (``telemetry``) adds L - 1, and after a drain the sum equals
        ``Telemetry.sum_steps - Telemetry.n_done``."""
        mon = monitor.bump(st.mon, queued_tx=jnp.sum(st.tx.occupancy()),
                           queued_fifo=jnp.sum(st.flow_fifo.occupancy()),
                           queued_rx=jnp.sum(st.rx.occupancy()))
        return _replace(st, mon=mon)

    # ------------------------------------------------------ connection mgmt
    def open_connection(self, st: FabricState, c_id, src_flow, dest_addr,
                        lb_scheme) -> FabricState:
        return _replace(st, conn=st.conn.open(
            jnp.int32(c_id), jnp.int32(src_flow), jnp.int32(dest_addr),
            jnp.int32(lb_scheme)))

    def close_connection(self, st: FabricState, c_id) -> FabricState:
        return _replace(st, conn=st.conn.close(jnp.int32(c_id)))

    # ------------------------------------------------------- soft config
    def set_soft(self, st: FabricState, batch=None, active_flows=None,
                 force_flush=None) -> FabricState:
        s = st.soft
        return _replace(st, soft=SoftConfig(
            jnp.int32(batch) if batch is not None else s.batch,
            jnp.int32(active_flows) if active_flows is not None
            else s.active_flows,
            jnp.bool_(force_flush) if force_flush is not None
            else s.force_flush))


def _replace(st: FabricState, **kw) -> FabricState:
    import dataclasses
    return dataclasses.replace(st, **kw)


def fused_switch_front(fab: DaggerFabric, stacked: FabricState, tel,
                       ext=None):
    """Run the fused switch-step front half as ONE Pallas megakernel.

    ``stacked`` is a tier-stacked ``FabricState`` (leading [T] axis on
    every leaf).  With ``ext=None`` the kernel also performs fetch +
    crossbar dest lookup (the stacked single-device step); with
    ``ext=(slots, valid, dest)`` it consumes a pre-exchanged candidate
    list (the sharded step's post-ToR-hop global list, dest rebased to
    device-local tier ids).  ``tel`` is a per-tier ``Telemetry`` (or
    ``None`` — the kernel still carries the registers, against a dummy
    2-bin histogram that is discarded).

    Returns ``(stacked', records [T, F*B, ...], valid [T, F*B],
    telemetry')`` with the histogram observed over the drained
    responses and the step counter ticked; dispatch handlers and the
    response enqueue stay OUTSIDE (the ``raw_handler`` contract is
    host-side Python).
    """
    from repro.kernels import ops as kops
    from repro.kernels.switch_step import (S_FREE_HEAD, S_FREE_TAIL, S_RR,
                                           S_TNDONE, S_TSTEP, S_TSUM)
    c = fab.cfg
    s = stacked
    t = s.req_table.shape[0]
    f = c.n_flows
    bmax = c.batch_size
    w = fab.slot_words
    active = jnp.clip(s.soft.active_flows, 1, f)
    if tel is None:
        zt = jnp.zeros((t,), jnp.int32)
        tstep, tnd, tsum = zt, zt, zt
        hist = jnp.zeros((t, 2), jnp.int32)
    else:
        tstep, hist, tnd, tsum = (tel.step, tel.hist, tel.n_done,
                                  tel.sum_steps)
    scal = jnp.stack([s.free.head, s.free.tail, s.rr, s.soft.batch,
                      active, s.soft.force_flush.astype(jnp.int32),
                      tstep, tnd, tsum], axis=-1).astype(jnp.int32)
    if ext is None:
        m = t * f * bmax
        ext_slots = jnp.zeros((m, w), jnp.int32)
        ext_valid = jnp.zeros((m,), jnp.int32)
        ext_dest = jnp.zeros((m,), jnp.int32)
        include_fetch = True
    else:
        ext_slots, ext_valid, ext_dest = ext
        ext_valid = jnp.asarray(ext_valid).astype(jnp.int32)
        include_fetch = False
    (txh, rxbuf, rxh, rxt, req, fifo, ffbuf, ffh, fft, scal2, hist2,
     _, _, _, drained, dvalid, mond) = kops.switch_step_fused(
        s.tx.buf, s.tx.head, s.tx.tail, s.rx.buf, s.rx.head, s.rx.tail,
        s.req_table, s.free.fifo, s.flow_fifo.buf[..., 0],
        s.flow_fifo.head, s.flow_fifo.tail, s.conn.tag, s.conn.src_flow,
        s.conn.dest_addr, s.conn.lb, scal, hist, ext_slots, ext_valid,
        ext_dest, bmax=bmax, include_fetch=include_fetch)
    mon = monitor.bump(
        s.mon, rpcs_ingested=mond[:, 0], rpcs_delivered=mond[:, 1],
        rpcs_emitted=mond[:, 2], rpcs_completed=mond[:, 3],
        drops_no_slot=mond[:, 4], drops_fifo_full=mond[:, 5],
        batches_emitted=mond[:, 6])
    sts = _replace(
        s, tx=Ring(s.tx.buf, txh, s.tx.tail), rx=Ring(rxbuf, rxh, rxt),
        req_table=req,
        free=FreeFifo(fifo, scal2[:, S_FREE_HEAD], scal2[:, S_FREE_TAIL]),
        flow_fifo=Ring(ffbuf[..., None], ffh, fft),
        rr=scal2[:, S_RR], mon=mon)
    flat_r = serdes.unpack(drained)
    fv = dvalid != 0
    ntel = None if tel is None else tlm.Telemetry(
        scal2[:, S_TSTEP], hist2, scal2[:, S_TNDONE], scal2[:, S_TSUM])
    return sts, flat_r, fv, ntel


# ---------------------------------------------------------------------------
# Loopback composition (paper §5.1: two NICs on one FPGA, loopback network)
# ---------------------------------------------------------------------------

def make_loopback_step_stateful(client: DaggerFabric, server: DaggerFabric,
                                handler: Callable):
    """One fused device step for a client/server NIC pair with server
    state threaded through the handler.

    handler(records, valid, hstate) -> (response records, hstate'), run in
    the dispatch thread (paper's low-latency threading model).  The
    returned ``step(cst, sst, hstate)`` is jit-able, scan-able and fully
    device-resident — the host's only per-RPC work is writing into the
    client TX ring beforehand.  This is the building block of
    ``repro.core.engine.LoopbackEngine``.
    """

    def step(cst: FabricState, sst: FabricState, hstate):
        # client NIC fetches host-written requests and puts them on the wire
        with jax.named_scope(tlm.SCOPE_CLIENT):
            cst, slots, valid = client.nic_fetch(cst)
        n = slots.shape[0] * slots.shape[1]
        w = slots.shape[2]
        # wire -> server NIC -> dispatch threads (deliver/emit/drain — the
        # fused megakernel back-half when the server runs use_pallas)
        with jax.named_scope(tlm.SCOPE_SERVER):
            sst, reqs, rvalid = server.nic_pipeline(
                sst, slots.reshape(n, w), valid.reshape(n))
        flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), reqs)
        fvalid = rvalid.reshape(-1)
        with jax.named_scope(tlm.SCOPE_HANDLER):
            resp, hstate = handler(flat, fvalid, hstate)
        resp["flags"] = resp["flags"] | serdes.FLAG_RESPONSE
        with jax.named_scope(tlm.SCOPE_SERVER):
            # server host writes responses to its TX rings (single
            # memory write)
            flow_of = jnp.repeat(
                jnp.arange(server.cfg.n_flows, dtype=jnp.int32),
                server.cfg.batch_size)
            sst, _ = server.host_tx_enqueue(sst, resp, flow_of, fvalid)
            # server NIC sends responses back over the wire
            sst, rslots, rvalid2 = server.nic_fetch(sst)
        m = rslots.shape[0] * rslots.shape[1]
        # wire -> client NIC -> completion queues
        with jax.named_scope(tlm.SCOPE_CLIENT):
            cst, done, dvalid = client.nic_pipeline(
                cst, rslots.reshape(m, w), rvalid2.reshape(m))
        # what every ring holds at the step's end: the waits of the
        # RPCs still in flight
        cst = client.count_queues(cst)
        sst = server.count_queues(sst)
        return cst, sst, hstate, done, dvalid

    return step


def make_loopback_step(client: DaggerFabric, server: DaggerFabric,
                       handler: Callable):
    """One fused device step for a client/server NIC pair.

    handler(records, valid) -> response records (same leading shape).
    Stateless wrapper over ``make_loopback_step_stateful``.
    """
    inner = make_loopback_step_stateful(
        client, server, lambda recs, valid, _: (handler(recs, valid), _))

    def step(cst: FabricState, sst: FabricState):
        cst, sst, _, done, dvalid = inner(cst, sst, ())
        return cst, sst, done, dvalid

    return step
