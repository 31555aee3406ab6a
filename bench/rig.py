"""The window loop's rig, shared by the loopback cells.

A rig owns one ``TenantEngine`` (one client/server NIC pair per lane,
``jax.vmap``-ed over the lanes, K fused steps per ``lax.scan`` call),
the benchmark's generator riding its ``loadgen=`` hook, and the
program's on-device latency histogram (``tel=``).  The harness drives
it: ``window()`` dispatches one call of K steps, ``read()`` waits for
it and returns what an operator's control loop would read (the window's
completions and the cumulative histogram), ``keep_sample()`` keeps a
device copy of the client completion rings for the reference check.

Configurations differ only in the handler, its state, the requests and
the reference; they build a rig with those.
"""
from __future__ import annotations

import numpy as np

from bench import loadgen as blg


def fabrics(sizes: dict):
    """The client and server NIC of one lane, at the configuration's
    sizes (fixed batches: every step flushes what it holds)."""
    from repro.config import FabricConfig
    from repro.core.fabric import DaggerFabric
    cfg = FabricConfig(n_flows=sizes["n_flows"],
                       ring_entries=sizes["ring_entries"],
                       slot_bytes=sizes["slot_bytes"],
                       batch_size=sizes["batch_size"],
                       dynamic_batching=False,
                       request_buffer_slots=sizes.get(
                           "request_buffer_slots", 0),
                       use_pallas=sizes["use_pallas"])
    return DaggerFabric(cfg), DaggerFabric(cfg)


def _stack_pairs(client, server, n_lanes: int, conn: int):
    """Per-lane client/server states with connection ``conn`` open: the
    client's replies come back on its flow 0, the server's requests are
    spread round-robin over its flows."""
    from repro.core.engine import stack_states
    from repro.core.load_balancer import LB_ROUND_ROBIN
    cst = client.open_connection(client.init_state(), conn, 0, 1,
                                 LB_ROUND_ROBIN)
    sst = server.open_connection(server.init_state(), conn, 0, 0,
                                 LB_ROUND_ROBIN)
    return stack_states([cst] * n_lanes), stack_states([sst] * n_lanes)


def fabric_drops(st) -> int:
    """Drops the program's packet monitors counted past the client's TX
    ring (whose refusals are the generator's own ``dropped``)."""
    keys = ("drops_no_slot", "drops_fifo_full", "drops_rx_full",
            "drops_exchange")
    return int(sum(np.asarray(st.mon[k]).sum() for k in keys))


def ring_occupancy(st) -> int:
    return int(sum((np.asarray(r.tail) - np.asarray(r.head)).sum()
                   for r in (st.tx, st.rx, st.flow_fifo)))


class LoopbackRig:
    """One cell's engine, state and generator at the cell's sizes."""

    conn = 1
    window_program = r"run_steps"       # the engine's jitted entry

    def __init__(self, *, client, server, handler, n_lanes: int,
                 requests, rate: float, steps_per_window: int,
                 n_bins: int, seed: int, hstate=None,
                 request_kinds=None, abstract: bool = False):
        import jax

        from repro.core import telemetry
        from repro.core.engine import TenantEngine
        self.k = int(steps_per_window)
        self.n_lanes = n_lanes
        self.n_bins = n_bins
        self.stateful = hstate is not None
        self.keys = blg.lane_keys(seed, n_lanes)
        self.streams = blg.stream_keys(seed, n_lanes)
        self.engine = TenantEngine(
            client, server, handler, stateful=self.stateful,
            loadgen=blg.Generator(client, requests, conn=self.conn))

        def states():
            cst, sst = _stack_pairs(client, server, n_lanes, self.conn)
            return (cst, sst, telemetry.create_batch(n_lanes, n_bins),
                    blg.init_states(self.keys, self.streams, rate,
                                     client.cfg.n_flows
                                     * client.cfg.batch_size))

        # abstract: shapes only, for compiling the window program for a
        # chip that is described and not attached (bench/rehearse.py)
        self.cst, self.sst, self.tel, self.gst = (
            jax.eval_shape(states) if abstract else states())
        self.hstate = hstate
        # the reference's fn_id of a request (lane keys, rpc ids; numpy)
        self.request_kinds = request_kinds
        self.tile = client.cfg.n_flows * client.cfg.batch_size
        self.schedule = [(0, rate)]       # (from step, offered rate)
        self.done = None
        self.completed = 0          # every completion since set-up
        self.steps = 0              # fused steps run since set-up
        self.samples = []

    # ------------------------------------------------------- the window
    def window_args(self):
        return self.cst, self.sst, self.hstate, self.tel, self.gst

    def window_call(self, cst, sst, hstate, tel, gst):
        """The window program: K fused steps of every lane."""
        return self.engine.run_steps(cst, sst, self.k, hstate=hstate,
                                     tel=tel, gen=gst)

    def window(self):
        """Dispatch one call of K fused steps (returns before the device
        finishes)."""
        out = self.engine.run_steps(self.cst, self.sst, self.k,
                                    hstate=self.hstate, tel=self.tel,
                                    gen=self.gst)
        if self.stateful:
            self.cst, self.sst, self.hstate, self.done, self.tel, \
                self.gst = out
        else:
            self.cst, self.sst, self.done, self.tel, self.gst = out
        self.steps += self.k

    def read(self):
        """Wait for the window; return (completions in it, cumulative
        latency histogram over all lanes [n_bins])."""
        import jax
        done, hist = jax.device_get((self.done, self.tel.hist))
        n = int(np.sum(done))
        self.completed += n
        return n, np.asarray(hist, np.int64).sum(axis=0)

    def keep_sample(self):
        """Keep a device copy of every lane's client completion ring
        (flow 0, where the connection's replies land) and its cursors.
        Entries in [tail - entries, head) are completions the client has
        already drained, as the NIC wrote them."""
        rx = self.cst.rx
        self.samples.append((rx.buf[:, 0], rx.head[:, 0], rx.tail[:, 0]))

    # ------------------------------------------------------ after close
    def drain(self, max_windows: int = 64) -> int:
        """Stop arrivals and run windows until nothing is in flight (a
        reply that comes late is late, not lost).  Returns windows run."""
        self.set_rate(0.0)
        for i in range(max_windows):
            if self.in_flight() == 0:
                self.drain_windows = i
                return i
            self.window()
            self.read()
        self.drain_windows = max_windows
        return max_windows

    def set_rate(self, rate: float):
        self.gst = blg.with_rate(self.gst, rate)
        self.schedule.append((self.steps, rate))

    def in_flight(self) -> int:
        return ring_occupancy(self.cst) + ring_occupancy(self.sst)

    def ledger(self) -> dict:
        import jax
        g = jax.device_get(self.gst)
        return dict(
            offered=int(np.sum(g.offered)), injected=int(np.sum(g.injected)),
            gen_dropped=int(np.sum(g.dropped)), completed=self.completed,
            in_flight=self.in_flight(),
            fabric_drops=fabric_drops(self.cst) + fabric_drops(self.sst)
            + int(np.asarray(self.sst.mon["drops_tx_full"]).sum()),
            drops={f"{side}.{k}": int(np.asarray(st.mon[k]).sum())
                   for side, st in (("client", self.cst),
                                    ("server", self.sst))
                   for k in st.mon if k.startswith("drops_")},
            hist_total=int(np.asarray(self.tel.hist).sum()),
            hist=np.asarray(self.tel.hist, np.int64).sum(axis=0),
            lane_steps=np.asarray(g.step), steps=self.steps,
            next_rpc=np.asarray(g.next_rpc),
            lane_offered=np.asarray(g.offered),
            lane_dropped=np.asarray(g.dropped),
            step_offered=self.step_offered())

    def step_offered(self) -> np.ndarray:
        """Arrivals [lanes, steps] the host asked for in each step run:
        the reference's draw at the rate of each stretch.  Request ids
        are handed out in arrival order, so this also names the step
        that injected each rpc id."""
        bounds = [s for s, _ in self.schedule[1:]] + [self.steps]
        out = np.zeros((self.n_lanes, self.steps), np.int64)
        for (start, rate), stop in zip(self.schedule, bounds):
            if rate > 0 and stop > start:
                out[:, start:stop] = blg.arrival_counts_np(
                    self.streams, start, stop,
                    blg.poisson_thresholds(rate, self.tile))
        return out

    def next_rpc(self) -> np.ndarray:
        import jax
        return np.asarray(jax.device_get(self.gst.next_rpc), np.int64)

    def offered_kinds(self, lo, hi) -> dict:
        """{fn_id: requests} among rpc ids ``lo[l] .. hi[l] - 1`` of
        each lane, by the reference's draw."""
        lane = np.repeat(np.arange(self.n_lanes), hi - lo)
        rpc = np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)])
        if self.request_kinds is None:
            return {0: int(rpc.size)}
        fn = np.asarray(self.request_kinds(
            np.asarray(self.keys).view(np.uint32)[lane],
            rpc.astype(np.uint32)), np.int64)
        return {int(f): int(n) for f, n in enumerate(np.bincount(fn))
                if n}

    def host_samples(self):
        import jax
        return [tuple(np.asarray(a) for a in s)
                for s in jax.device_get(self.samples)]

    def free(self):
        for name in ("cst", "sst", "hstate", "tel", "gst", "done",
                     "samples", "engine"):
            setattr(self, name, None)


def ledger_checks(ledger: dict) -> dict:
    """The guarantees every loopback cell states, as numbers with their
    limits: every lane drew the arrivals the reference draws for the
    steps the host asked for, each accepted request was answered exactly
    once (after the drain nothing is in flight and completions equal
    injections), every lane stepped as often as the host asked, and the
    latency histogram counts every completion."""
    return {
        "offered_gap": {"value": int(np.abs(
            ledger["lane_offered"] - ledger["step_offered"].sum(axis=1))
            .sum()),
            "limit": 0},
        "lost_or_extra": {"value": abs(ledger["injected"]
                                       - ledger["completed"]),
                          "limit": 0},
        "stalled_lanes": {"value": int(np.sum(ledger["lane_steps"]
                                              != ledger["steps"])),
                          "limit": 0},
        "hist_gap": {"value": abs(ledger["hist_total"]
                                  - ledger["completed"]), "limit": 0},
    }
