"""Chip smoke: the dataplane's main paths, once each, on a TPU.

    python chip_smoke.py [--seed N]     # one chip: echo, kvs, flight, decode
    python chip_smoke.py --chips 4      # four chips: the sharded paths only

Every phase drives a normal entry point at deployment size, checks what
comes out against a plain reference, and prints one JSON line: sizes,
steps run, compile seconds, device bytes in use, and the verdict.  A
phase that serves a Pallas kernel runs twice, with ``use_pallas`` off
and on.  The fused switch megakernel has no Mosaic lowering yet
(``repro.kernels.switch_step.MOSAIC_REFUSAL``), so on a TPU the echo and
flight phases check that ``use_pallas=True`` is refused, and the echo
phase instead checks the per-stage fabric kernels against their oracles
at echo widths.

  echo    64 tenants x 64 flows x 256-entry rings of 64-byte slots
          (~256 MiB of rings) on ``TenantEngine``: payload = request + 1
          for every completion, then open-loop load for a few hundred
          fused steps with the ledger
          ``injected == completed + in_flight + fabric_drops``.  The
          load (64 RPC/step/tenant) overruns what a one-connection lane
          drains (``batch_size`` = 4 per step), so the back-pressure and
          drop paths run and the ledger counts their drops.
  kvs     ``DeviceKVS`` at 2^22 buckets x 4 ways, 8-byte keys, 32-byte
          values (~700 MB), 10 M keys bulk-loaded, then GET/SET RPCs
          through ``DeviceKVS.make_engine``: every GET hit returns the
          value last SET, never-set keys miss, misses <= ``n_evict``.
  flight  the 8-tier check-in DAG (``FlightRegistrationApp.run_load``)
          for a few hundred registrations, checked as its tests do.
  decode  ``DecodeEngine`` at the full width of qwen2-1.5b (28 layers,
          random bf16 weights from the seed), 8 slots, 256-entry KV
          cache, prompts and outputs of up to 128 tokens each so rows
          reach the end of the cache; every streamed token's logit
          under a plain per-request greedy decode is within a bf16
          tolerance of that step's max.

With ``--chips 4`` only the paths that exist across chips run:
``Switch.switch_step_sharded`` (full and compact exchange) against
``switch_step_stacked`` on one device, and
``ShardedTenantEngine.run_until_global`` against ``TenantEngine``; both
bit-exact, with each shard's device printed.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line.  Weights and data come from ``--seed``.  JAX's
persistent compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR`` or
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if os.path.isdir(_SRC) and _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# bf16 keeps 8 significant bits: a streamed token is accepted when its
# reference logit is within 4 bf16 ulps (at the max's magnitude, floor
# 1.0) of the reference step's maximum
DECODE_TOL_ULPS = 4
BF16_EPS = 2.0 ** -7


class SmokeFailure(AssertionError):
    """A phase's result disagreed with its reference."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class Meter:
    """Compile seconds and persistent-cache hits, from JAX's own
    monitoring events (listeners registered once, in ``main``)."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0

    def register(self):
        from jax import monitoring

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _echo_handler(recs, valid):
    out = dict(recs)
    out["payload"] = recs["payload"] + 1
    return out


def _echo_fabrics(n_flows, ring_entries, use_pallas):
    from repro.config import FabricConfig
    from repro.core.fabric import DaggerFabric
    cfg = FabricConfig(n_flows=n_flows, ring_entries=ring_entries,
                       batch_size=4, dynamic_batching=False,
                       use_pallas=use_pallas)
    return DaggerFabric(cfg), DaggerFabric(cfg)


def _tenant_pairs(client, server, n_tenants):
    from repro.core.engine import stack_states
    from repro.core.load_balancer import LB_ROUND_ROBIN
    cst = client.open_connection(client.init_state(), 1, 0, 1,
                                 LB_ROUND_ROBIN)
    sst = server.open_connection(server.init_state(), 1, 0, 0,
                                 LB_ROUND_ROBIN)
    return stack_states([cst] * n_tenants), stack_states([sst] * n_tenants)


def _request_payload(tenant, rpc_id, pw, seed):
    """Deterministic request payload words (numpy, host reference)."""
    import numpy as np
    j = np.arange(pw, dtype=np.int64)
    x = (np.asarray(tenant, np.int64)[..., None] * 1_000_003
         + np.asarray(rpc_id, np.int64)[..., None] * 7919 + j * 131 + seed)
    return (x % (1 << 30)).astype(np.int32)


def _enqueue(fab, cst, conn, rpc_ids, payload, flows):
    """Vmapped host write of per-tenant request tiles into the client
    TX rings (the host's single memory write, for every tenant)."""
    import jax
    import jax.numpy as jnp

    from repro.core import serdes
    t, n = rpc_ids.shape

    def one(st, rid, pay):
        recs = serdes.make_records(
            jnp.full((n,), conn, jnp.int32), rid,
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32), pay)
        return fab.host_tx_enqueue(st, recs, flows)

    return jax.jit(jax.vmap(one))(cst, jnp.asarray(rpc_ids),
                                  jnp.asarray(payload))


def _mon_sum(mon, key):
    import numpy as np
    return int(np.asarray(mon[key]).sum())


def _fabric_drops(cst, sst):
    """Monitor drops downstream of the generator's own accounting (the
    client's ``drops_tx_full`` are the generator's ``dropped``)."""
    tot = 0
    for key in ("drops_no_slot", "drops_fifo_full", "drops_rx_full",
                "drops_exchange"):
        tot += _mon_sum(cst.mon, key) + _mon_sum(sst.mon, key)
    return tot + _mon_sum(sst.mon, "drops_tx_full")


def _nbytes(tree):
    import jax
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


def _switch_refused(run):
    """On a TPU the fused switch path must refuse, not fall back."""
    from repro.kernels.switch_step import MOSAIC_REFUSAL
    try:
        run()
    except NotImplementedError as e:
        check(MOSAIC_REFUSAL in str(e), f"unexpected refusal: {e}")
        return "refused"
    raise SmokeFailure("use_pallas=True ran the switch path on a TPU, "
                       "which has no Mosaic lowering for it")


# ---------------------------------------------------------------------------
# echo
# ---------------------------------------------------------------------------

def fabric_kernel_oracles(n_flows, ring_entries, batch, seed):
    """ring_push, ring_gather, nic_deliver_fused, rpc_pack and
    hash_steer against their jnp oracles at one fabric's widths (the
    per-stage kernels of the ``use_pallas`` fabric path, the wire
    packer and the FNV-1a steering hash)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    rng = np.random.default_rng(seed)
    w = 16
    n = n_flows * batch
    r = n_flows * batch
    d = max(ring_entries, r)
    c = 256
    # ring_push: unique targets, a quarter dropped by the sentinel
    buf = jnp.asarray(rng.integers(-99, 99, (n_flows, ring_entries, w)),
                      jnp.int32)
    cells = rng.permutation(n_flows * ring_entries)[:n]
    q = np.where(rng.random(n) < 0.25, n_flows, cells // ring_entries)
    pos = cells % ring_entries
    slots = jnp.asarray(rng.integers(-1000, 1000, (n, w)), jnp.int32)
    args = (buf, jnp.asarray(q, jnp.int32), jnp.asarray(pos, jnp.int32),
            slots)
    check(bool(jnp.array_equal(ops.ring_push(*args),
                               ref.ref_ring_push(*args))),
          "ring_push != oracle")
    # ring_gather: refs include the free-slot sentinel r
    table = jnp.asarray(rng.integers(-1000, 1000, (r, w)), jnp.int32)
    refs = jnp.asarray(rng.integers(0, r + 1, (n_flows, batch)), jnp.int32)
    check(bool(jnp.array_equal(ops.ring_gather(table, refs),
                               ref.ref_ring_gather(table, refs))),
          "ring_gather != oracle")
    # nic_deliver_fused: random free window, conn cache, scheme mix
    head = int(rng.integers(0, r))
    avail = int(rng.integers(0, r + 1))
    dargs = (
        jnp.asarray(rng.integers(-1000, 1000, (n, w)), jnp.int32),
        jnp.asarray(rng.integers(0, 2, n), jnp.int32),
        jnp.asarray(rng.permutation(r), jnp.int32),
        jnp.asarray(rng.integers(-99, 99, (r, w)), jnp.int32),
        jnp.asarray(rng.integers(-99, 99, (n_flows, d)), jnp.int32),
        jnp.asarray(rng.integers(-1, 40, c), jnp.int32),
        jnp.asarray(rng.integers(0, 8, c), jnp.int32),
        jnp.asarray(rng.integers(0, 3, c), jnp.int32),
        jnp.asarray(rng.integers(0, 100, n_flows), jnp.int32),
        jnp.asarray(rng.integers(0, d + 1, n_flows), jnp.int32),
        jnp.asarray([head, avail, head + avail, int(rng.integers(0, 50)),
                     int(rng.integers(1, n_flows + 1))], jnp.int32))
    got = ops.nic_deliver_fused(*dargs)
    want = ref.ref_nic_deliver_fused(*dargs)
    for i, (g, x) in enumerate(zip(got, want)):
        check(bool(jnp.array_equal(g, x)),
              f"nic_deliver_fused output {i} != oracle")
    # rpc_pack: one record per ring slot (a multi-tile grid), header
    # fields full-range, fragment indices included
    m = n_flows * ring_entries
    fields = [jnp.asarray(rng.integers(0, 1 << 16, m), jnp.int32)
              for _ in range(7)]
    pay = jnp.asarray(rng.integers(-(1 << 31), 1 << 31, (m, w - 5)),
                      jnp.int32)
    check(bool(jnp.array_equal(ops.rpc_pack(*fields, pay, w),
                               ref.ref_rpc_pack(*fields, pay, w))),
          "rpc_pack != oracle")
    # hash_steer: static and dynamic flow counts over full-range keys
    check(bool(jnp.array_equal(ops.hash_steer_static(pay, n_flows),
                               ref.ref_hash_steer(pay, n_flows))),
          "hash_steer_static != oracle")
    active = int(rng.integers(1, n_flows + 1))
    check(bool(jnp.array_equal(ops.hash_steer(pay, jnp.int32(active)),
                               ref.ref_hash_steer(pay, active))),
          "hash_steer != oracle")
    return ["ring_push", "ring_gather", "nic_deliver_fused", "rpc_pack",
            "hash_steer_static", "hash_steer"]


def phase_echo(n_tenants, n_flows, ring_entries, steps, rate, seed,
               use_pallas):
    import numpy as np

    from repro.core import loadgen as lg
    from repro.core import serdes
    from repro.core.engine import TenantEngine
    from repro.kernels.ops import interpret

    sizes = dict(tenants=n_tenants, flows=n_flows, ring_entries=ring_entries,
                 slot_bytes=64)
    client, server = _echo_fabrics(n_flows, ring_entries, use_pallas)
    if use_pallas and not interpret():
        checked = fabric_kernel_oracles(n_flows, ring_entries, 4, seed)
        cst, sst = _tenant_pairs(client, server, n_tenants)
        eng = TenantEngine(client, server, _echo_handler)
        engine = _switch_refused(lambda: eng.run_steps(cst, sst, 1))
        return dict(sizes=sizes, kernels_vs_oracle=checked,
                    engine=engine, steps=0)

    # 1. payload check: host-written requests, stepped to completion
    cst, sst = _tenant_pairs(client, server, n_tenants)
    ring_bytes = _nbytes((cst.tx.buf, cst.rx.buf, sst.tx.buf, sst.rx.buf))
    per = n_flows                      # one request per flow per tenant
    rid = (np.arange(n_tenants)[:, None] * per + np.arange(per)[None]
           ).astype(np.int32)
    pw = client.slot_words - serdes.HEADER_WORDS
    pay = _request_payload(np.arange(n_tenants)[:, None], rid, pw, seed)
    flows = np.arange(per, dtype=np.int32) % n_flows
    cst, acc = _enqueue(client, cst, 1, rid, pay, flows)
    check(bool(np.asarray(acc).all()), "echo: TX ring refused a request")
    eng = TenantEngine(client, server, _echo_handler)
    seen = {}
    n_step = 0
    while len(seen) < rid.size and n_step < 64:
        cst, sst, done, dvalid = eng.step(cst, sst)
        n_step += 1
        v = np.asarray(dvalid).reshape(n_tenants, -1)
        d_rid = np.asarray(done["rpc_id"]).reshape(n_tenants, -1)
        d_pay = np.asarray(done["payload"]).reshape(n_tenants, -1, pw)
        for t, i in zip(*np.nonzero(v)):
            seen[(int(t), int(d_rid[t, i]))] = d_pay[t, i]
    check(len(seen) == rid.size,
          f"echo: {len(seen)} of {rid.size} requests completed")
    for (t, r), got in seen.items():
        want = _request_payload(t, r, pw, seed) + 1
        check(np.array_equal(got, want),
              f"echo: tenant {t} rpc {r} payload != request + 1")
    del cst, sst

    # 2. open-loop load: the conservation ledger
    gen = lg.LoadGen(client, mode=lg.MODE_POISSON)
    eng = TenantEngine(client, server, _echo_handler, loadgen=gen)
    cst, sst = _tenant_pairs(client, server, n_tenants)
    gst = gen.init_state_batch([rate] * n_tenants,
                               seeds=[seed * 1000 + t
                                      for t in range(n_tenants)])
    cst, sst, done, gst = eng.run_steps(cst, sst, steps, gen=gst)
    snap = lg.snapshot(gst)
    completed = int(np.asarray(done).sum())
    in_flight = lg.system_occupancy(cst, sst)
    drops = _fabric_drops(cst, sst)
    check(snap["offered"] == snap["injected"] + snap["dropped"],
          f"echo ledger: offered != injected + dropped ({snap})")
    check(snap["injected"] == completed + in_flight + drops,
          f"echo ledger: injected {snap['injected']} != completed "
          f"{completed} + in_flight {in_flight} + drops {drops}")
    check(completed > 0, "echo: open-loop run completed nothing")
    return dict(sizes=sizes, ring_state_bytes=ring_bytes,
                payload_checked=len(seen), payload_steps=n_step,
                steps=steps, rate_per_tenant=rate, injected=snap["injected"],
                completed=completed, in_flight=in_flight, fabric_drops=drops,
                gen_dropped=snap["dropped"])


# ---------------------------------------------------------------------------
# kvs
# ---------------------------------------------------------------------------

def _kv_key(idx, seed):
    """8-byte key of item ``idx`` (two int32 words, distinct per idx)."""
    import jax.numpy as jnp
    idx = jnp.asarray(idx, jnp.int32)
    return jnp.stack([idx, (idx * 1_000_003 + seed) & 0x7FFFFFFF], axis=-1)


def _kv_val(idx, version, seed, vw):
    """Value words of item ``idx`` at ``version`` (device or host)."""
    import jax.numpy as jnp
    j = jnp.arange(vw, dtype=jnp.int32)
    x = (jnp.asarray(idx, jnp.int32)[..., None] * 65_537
         + jnp.asarray(version, jnp.int32)[..., None] * 8_191
         + j * 131 + seed)
    return x & 0x3FFFFFFF


def phase_kvs(n_buckets, n_keys, n_ops, n_flows, seed, use_pallas,
              load_batch=1 << 20):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import FabricConfig
    from repro.core import serdes
    from repro.core.engine import unalias
    from repro.core.fabric import DaggerFabric
    from repro.core.load_balancer import LB_ROUND_ROBIN
    from repro.runtime.kvs import DeviceKVS

    kw, vw, ways = 2, 8, 4
    kvs = DeviceKVS(n_buckets=n_buckets, ways=ways, key_words=kw,
                    value_words=vw, use_pallas=use_pallas)
    db = kvs.init_state()
    table_bytes = _nbytes(db)

    # bulk load: keys and values made on the device, batch by batch
    def load_batch_at(db, start):
        idx = start + jnp.arange(load_batch, dtype=jnp.int32)
        return kvs.set(db, _kv_key(idx, seed),
                       _kv_val(idx, jnp.zeros_like(idx), seed, vw),
                       idx < n_keys)

    load = jax.jit(load_batch_at, donate_argnums=(0,))
    db = unalias(db)              # fresh state shares its zero scalars
    for start in range(0, n_keys, load_batch):
        db = load(db, jnp.int32(start))
    check(int(db.n_set) == n_keys, "kvs: bulk load lost SETs")

    # a request buffer as deep as the rings: every RPC the TX rings
    # accept has a slot, so the fabric drops nothing and each GET/SET
    # gets its response
    cfg = FabricConfig(n_flows=n_flows, ring_entries=256, batch_size=4,
                       dynamic_batching=False,
                       request_buffer_slots=256 * n_flows)
    client, server = DaggerFabric(cfg), DaggerFabric(cfg)
    # one connection per client flow: a response returns to the flow its
    # request left from, so replies spread over every client flow
    cst, sst = client.init_state(), server.init_state()
    for f in range(n_flows):
        cst = client.open_connection(cst, 1 + f, f, 1, LB_ROUND_ROBIN)
        sst = server.open_connection(sst, 1 + f, 0, 0, LB_ROUND_ROBIN)
    eng = kvs.make_engine(client, server)
    pw = client.slot_words - serdes.HEADER_WORDS
    rng = np.random.default_rng(seed)

    def serve(cst, sst, db, fn, idx, version, rid0):
        """One batch of GET (fn 0) / SET (fn 1) RPCs through the engine;
        returns (states, {rpc_id: response payload}, steps)."""
        n = idx.shape[0]
        pay = np.zeros((n, pw), np.int32)
        pay[:, :kw] = np.asarray(_kv_key(idx, seed))
        if fn == 1:
            pay[:, kw:kw + vw] = np.asarray(_kv_val(idx, version, seed, vw))
        rid = rid0 + np.arange(n, dtype=np.int32)
        flow = jnp.arange(n, dtype=jnp.int32) % n_flows
        recs = serdes.make_records(
            1 + flow, jnp.asarray(rid), jnp.full((n,), fn, jnp.int32),
            jnp.zeros((n,), jnp.int32), jnp.asarray(pay))
        cst, acc = jax.jit(client.host_tx_enqueue)(cst, recs, flow)
        check(bool(np.asarray(acc).all()), "kvs: TX ring refused an RPC")
        got, steps = {}, 0
        while len(got) < n and steps < 256:
            cst, sst, db, done, dvalid = eng.step(cst, sst, db)
            steps += 1
            v = np.asarray(dvalid).reshape(-1)
            d_rid = np.asarray(done["rpc_id"]).reshape(-1)
            d_pay = np.asarray(done["payload"]).reshape(v.shape[0], -1)
            for i in np.nonzero(v)[0]:
                got[int(d_rid[i])] = d_pay[i]
        check(len(got) == n, f"kvs: {len(got)} of {n} RPCs completed "
              f"({_fabric_drops(cst, sst)} dropped by the fabric)")
        return cst, sst, db, got, steps

    n_set = n_ops // 2
    old = rng.choice(n_keys, n_set // 2, replace=False).astype(np.int32)
    new = (n_keys + np.arange(n_set - n_set // 2)).astype(np.int32)
    set_idx = np.concatenate([old, new])
    cst, sst, db, got, s1 = serve(cst, sst, db, 1, set_idx,
                                  np.ones_like(set_idx), 0)
    check(all(p[0] == 1 for p in got.values()), "kvs: a SET was not acked")
    untouched = np.setdiff1d(rng.choice(n_keys, n_set, replace=False),
                             old)[:n_ops // 4].astype(np.int32)
    never = (n_keys + n_set + np.arange(n_ops // 4)).astype(np.int32)
    get_idx = np.concatenate([set_idx, untouched, never])
    ver = np.concatenate([np.ones_like(set_idx), np.zeros_like(untouched),
                          np.zeros_like(never)])
    cst, sst, db, got, s2 = serve(cst, sst, db, 0, get_idx, ver, 1 << 20)
    want = np.asarray(_kv_val(get_idx, ver, seed, vw))
    hits = misses = 0
    for i in range(get_idx.shape[0]):
        p = got[(1 << 20) + i]
        stored = i < set_idx.shape[0] + untouched.shape[0]
        if p[0] == 1:
            check(stored, f"kvs: never-set key {get_idx[i]} hit")
            check(np.array_equal(p[1:1 + vw], want[i]),
                  f"kvs: GET {get_idx[i]} != value last SET")
            hits += 1
        elif stored:
            misses += 1
    evict = int(db.n_evict)
    check(misses <= evict, f"kvs: {misses} misses > {evict} evictions")
    return dict(sizes=dict(buckets=n_buckets, ways=ways, key_bytes=4 * kw,
                           value_bytes=4 * vw, loaded=n_keys,
                           flows=n_flows),
                table_bytes=table_bytes, sets=int(set_idx.shape[0]),
                gets=int(get_idx.shape[0]), hits=hits, misses=misses,
                n_evict=evict, steps=s1 + s2)


# ---------------------------------------------------------------------------
# flight
# ---------------------------------------------------------------------------

def phase_flight(total, per_step, seed, use_pallas):
    import numpy as np

    from repro.apps.flight import (PAY_AIRPORT, PAY_BAGGAGE, PAY_CITIZEN,
                                   PAY_RESULT, PAY_STAGE, PAY_TAG, TIER_ID,
                                   FlightRegistrationApp)
    from repro.core import serdes
    from repro.kernels.ops import interpret

    sizes = dict(tiers=len(TIER_ID), registrations=total, per_step=per_step)
    if use_pallas and not interpret():
        app = FlightRegistrationApp(threading="simple", batch=8,
                                    seed=seed, use_pallas=True)
        rng = np.random.default_rng(seed)
        engine = _switch_refused(
            lambda: app.run_window(*app.make_tiles(1, per_step, rng)))
        return dict(sizes=sizes, engine=engine, steps=0)

    app = FlightRegistrationApp(threading="simple", batch=8, seed=seed,
                                use_pallas=use_pallas)
    res = app.run_load(total=total, per_step=per_step, seed=seed,
                       max_steps=64 * total)
    fe = TIER_ID["passenger"]
    check(res["completed"] == total,
          f"flight: {res['completed']} of {total} registrations completed")
    check(int(np.asarray(app.tel.hist[fe]).sum()) == total,
          "flight: latency histogram does not count every registration")
    check(res["p99_steps"] >= res["median_steps"] >= 12,
          "flight: a registration finished faster than its 12 hops")
    check(res["worker_dropped"] == 0, "flight: worker ring dropped work")

    # every completed registration walked the whole DAG
    chain = FlightRegistrationApp(threading="simple", batch=8, seed=seed,
                                  use_pallas=use_pallas)
    rng = np.random.default_rng(seed + 1)
    n_chain = min(total, 32)
    recs, valid = chain.run_window(*chain.make_tiles(
        64, per_step, rng, n_submit=n_chain))
    flags = np.asarray(recs["flags"])
    pay = np.asarray(recs["payload"])
    v = np.asarray(valid) & ((flags & serdes.FLAG_RESPONSE) != 0)
    check(int(v.sum()) == n_chain,
          f"flight: {int(v.sum())} of {n_chain} chain checks completed")
    for p in pay[v]:
        check(p[PAY_STAGE] == 5 and p[PAY_BAGGAGE] == 1
              and p[PAY_CITIZEN] == 1 and p[PAY_AIRPORT] == 1
              and p[PAY_RESULT] != 0 and p[PAY_TAG] == TIER_ID["checkin"],
              f"flight: a registration skipped a tier: {p}")
    return dict(sizes=sizes, steps=res["steps"],
                median_steps=res["median_steps"], p99_steps=res["p99_steps"],
                chain_checked=n_chain)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def phase_decode(cfg, n_slots, max_seq, max_prompt, max_new, steps, rate,
                 min_requests, seed, use_pallas):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import loadgen as lg
    from repro.models import Model
    from repro.runtime import decode as dec

    cfg = cfg.replace(use_pallas=use_pallas)
    eng = dec.DecodeEngine(cfg, n_slots=n_slots, max_prompt=max_prompt,
                           max_new_cap=max_new, max_seq=max_seq,
                           mode=lg.MODE_DETERMINISTIC, seed=seed)
    st = eng.init_states(rate, seed=seed)
    st, (comp, cvalid) = eng.make_run_steps(steps)(st)
    streams = dec.collect_streams(comp, cvalid)
    done = {r: e["tokens"] for r, e in streams.items()
            if e["done"] and not e["nack"]}
    check(len(done) >= min_requests,
          f"decode: {len(done)} requests served, want >= {min_requests}")

    # reference: a plain greedy decode of each request alone (teacher-
    # forced on the streamed tokens), one batch row per request
    key = int(np.asarray(st.gst.key))
    rids = sorted(done)
    seqs, gen_at = [], []
    for r in rids:
        s = int(lg.counter_hash(key, r, dec._SALT_SEED)) & 0x7FFFFFFF
        plen = 1 + int(lg.counter_hash(key, r, dec._SALT_PLEN)) % max_prompt
        mnew = 1 + int(lg.counter_hash(key, r, dec._SALT_MNEW)) % max_new
        check(len(done[r]) == mnew,
              f"decode: request {r} streamed {len(done[r])} of {mnew}")
        prompt = [int(dec.prompt_token(s, j, cfg.vocab))
                  for j in range(plen)]
        seqs.append(prompt + done[r])
        gen_at.append(plen - 1)
    model = Model(cfg.replace(use_pallas=False))
    length = max(len(q) for q in seqs)
    toks = np.zeros((len(rids), length), np.int32)
    for i, q in enumerate(seqs):
        toks[i, :len(q)] = q
    step = jax.jit(model.decode_step)
    cache = model.cache_init(len(rids), max_seq)
    worst = 0.0
    for p in range(length - 1):
        logits, cache = step(eng.params, cache, jnp.asarray(toks[:, p:p + 1]),
                             jnp.full((len(rids),), p, jnp.int32))
        lg_np = np.asarray(logits.astype(jnp.float32)).reshape(len(rids), -1)
        for i in range(len(rids)):
            j = p - gen_at[i]
            if 0 <= j < len(seqs[i]) - gen_at[i] - 1:
                top = lg_np[i].max()
                tol = DECODE_TOL_ULPS * BF16_EPS * max(1.0, abs(top))
                gap = top - lg_np[i, seqs[i][p + 1]]
                worst = max(worst, gap / tol)
                check(gap <= tol,
                      f"decode: request {rids[i]} token {j} logit "
                      f"{top - gap} is {gap} below the max {top}")
    return dict(sizes=dict(model=cfg.name, layers=cfg.n_layers,
                           d_model=cfg.d_model, heads=cfg.n_heads,
                           kv_heads=cfg.n_kv_heads, vocab=cfg.vocab,
                           slots=n_slots, max_seq=max_seq),
                param_bytes=_nbytes(eng.params), steps=steps,
                served=len(done), longest_seq=length,
                tokens=int(sum(len(t) for t in done.values())),
                worst_gap_over_tol=round(float(worst), 4))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _shard_devices(tree):
    import jax
    leaf = jax.tree.leaves(tree)[0]
    return sorted({str(s.device) for s in leaf.addressable_shards})


def _trees_equal(a, b):
    """Leaf-by-leaf bit equality, compared on the device: each leaf of
    ``b`` is moved to the placement of its ``a`` leaf, and only the
    verdicts cross to the host."""
    import jax
    import jax.numpy as jnp
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb) or any(x.dtype != y.dtype or x.shape != y.shape
                                 for x, y in zip(la, lb)):
        return False
    eqs = [jnp.array_equal(x, jax.device_put(y, x.sharding))
           for x, y in zip(la, lb)]
    return all(bool(e) for e in jax.device_get(eqs))


def phase_sharded_switch(mesh, n_tiers, n_flows, ring_entries, steps, seed):
    import jax
    import jax.numpy as jnp

    from repro.config import FabricConfig
    from repro.core import serdes
    from repro.core.engine import shard_states
    from repro.core.fabric import DaggerFabric
    from repro.core.load_balancer import LB_ROUND_ROBIN
    from repro.core.virtualization import Switch, canonicalize_completions

    cfg = FabricConfig(n_flows=n_flows, ring_entries=ring_entries,
                       batch_size=4, dynamic_batching=False,
                       request_buffer_slots=ring_entries * n_flows)
    fabrics = [DaggerFabric(cfg) for _ in range(n_tiers)]
    sw = Switch(fabrics)
    states = sw.init_states()
    # tier 0 calls every tier of the back half: each request crosses a
    # shard boundary on a multi-device mesh
    conns = []
    for i, dst in enumerate(range(n_tiers // 2, n_tiers)):
        c = 10 + i
        states[0] = fabrics[0].open_connection(states[0], c, 0, dst,
                                               LB_ROUND_ROBIN)
        states[dst] = fabrics[dst].open_connection(states[dst], c, 0, 0,
                                                   LB_ROUND_ROBIN)
        conns.append(c)

    def add(k):
        def h(recs, valid):
            out = dict(recs)
            out["payload"] = recs["payload"] + k
            return out
        return h

    handlers = [None] + [add(100 * (i + 1)) for i in range(n_tiers - 1)]
    pw = fabrics[0].slot_words - serdes.HEADER_WORDS
    n = 2 * n_flows * len(conns)
    pay = (jnp.arange(n * pw, dtype=jnp.int32).reshape(n, pw) + seed)
    recs = serdes.make_records(
        jnp.asarray(conns * (n // len(conns)), jnp.int32),
        jnp.arange(n, dtype=jnp.int32), jnp.zeros(n, jnp.int32),
        jnp.zeros(n, jnp.int32), pay)
    states[0], acc = jax.jit(fabrics[0].host_tx_enqueue)(
        states[0], recs, jnp.arange(n) % n_flows)
    check(bool(acc.all()), "sharded switch: TX ring refused a request")

    one = jax.jit(lambda s: sw.switch_step_stacked(s, handlers))
    full = jax.jit(lambda s: sw.switch_step_sharded(s, handlers, mesh=mesh))
    comp = jax.jit(lambda s: sw.switch_step_sharded(
        s, handlers, mesh=mesh, exchange="compact"))
    s1 = sw.stack_states(states)
    sf = shard_states(sw.stack_states(states), mesh)
    sc = shard_states(sw.stack_states(states), mesh)
    devices = _shard_devices(sf)
    completed, k = 0, 0
    while completed < n and k < steps:
        s1, (r1, v1) = one(s1)
        sf, (rf, vf) = full(sf)
        sc, (rc, vc) = comp(sc)
        check(_trees_equal((s1, r1, v1), (sf, rf, vf)),
              f"sharded switch (full) != stacked at step {k}")
        check(_trees_equal(s1, sc),
              f"sharded switch (compact) states != stacked at step {k}")
        check(_trees_equal(canonicalize_completions(r1, v1),
                           canonicalize_completions(rc, vc)),
              f"sharded switch (compact) completions != stacked at {k}")
        completed += int(jnp.sum(v1[0]))
        k += 1
    check(completed == n, f"sharded switch: {completed} of {n} completed")
    return dict(sizes=dict(tiers=n_tiers, flows=n_flows,
                           ring_entries=ring_entries, requests=n),
                steps=k, shard_devices=devices,
                exchanges=["full", "compact"], bit_exact=True)


def phase_sharded_engine(mesh, n_tenants, n_flows, ring_entries, seed):
    import numpy as np

    from repro.core import serdes
    from repro.core.engine import ShardedTenantEngine, TenantEngine

    client, server = _echo_fabrics(n_flows, ring_entries, False)
    per = 2 * n_flows
    rid = (np.arange(n_tenants)[:, None] * per + np.arange(per)[None]
           ).astype(np.int32)
    pw = client.slot_words - serdes.HEADER_WORDS
    pay = _request_payload(np.arange(n_tenants)[:, None], rid, pw, seed)
    flows = np.arange(per, dtype=np.int32) % n_flows

    def fresh():
        cst, sst = _tenant_pairs(client, server, n_tenants)
        cst, acc = _enqueue(client, cst, 1, rid, pay, flows)
        check(bool(np.asarray(acc).all()), "sharded engine: TX refused")
        return cst, sst

    target = rid.size
    seng = ShardedTenantEngine(client, server, _echo_handler, mesh=mesh)
    cst, sst = seng.shard_states(*fresh())
    devices = _shard_devices(cst)
    cst, sst, done_s, dev_steps = seng.run_until_global(cst, sst, target,
                                                        256)
    dev_steps = np.asarray(dev_steps)
    check(len(set(dev_steps.tolist())) == 1,
          f"sharded engine: devices out of lockstep {dev_steps}")
    check(int(np.asarray(done_s).sum()) >= target,
          "sharded engine: global target not reached")
    teng = TenantEngine(client, server, _echo_handler)
    c1, s1 = fresh()
    c1, s1, done_1 = teng.run_steps(c1, s1, int(dev_steps[0]))
    check(_trees_equal((c1, s1, done_1), (cst, sst, done_s)),
          "ShardedTenantEngine.run_until_global != TenantEngine")
    return dict(sizes=dict(tenants=n_tenants, flows=n_flows,
                           ring_entries=ring_entries, requests=target),
                steps=int(dev_steps[0]), shard_devices=devices,
                bit_exact=True)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_phase(meter, name, fn, **kw):
    """Run one phase, print its JSON line, and return it."""
    import gc

    import jax
    gc.collect()                  # drop the previous phase's buffers
    c0, t0 = meter.compile_s, time.perf_counter()
    report = {"phase": name, "use_pallas": kw.get("use_pallas")}
    report.update(fn(**kw))
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    report.update(
        compile_s=round(meter.compile_s - c0, 3),
        wall_s=round(time.perf_counter() - t0, 3),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_in_use=stats.get("bytes_in_use"),
        verdict="pass")
    print(json.dumps(report, default=str), flush=True)
    return report


def one_chip_phases(seed):
    """(name, phase function, sizes) at deployment size on one chip."""
    from repro.configs.qwen2_1_5b import CONFIG
    return [
        ("echo", phase_echo, dict(n_tenants=64, n_flows=64,
                                  ring_entries=256, steps=300, rate=64.0,
                                  seed=seed)),
        ("kvs", phase_kvs, dict(n_buckets=1 << 22, n_keys=10_000_000,
                                n_ops=4096, n_flows=64, seed=seed)),
        ("flight", phase_flight, dict(total=256, per_step=4, seed=seed)),
        ("decode", phase_decode, dict(cfg=CONFIG, n_slots=8, max_seq=256,
                                      max_prompt=128, max_new=128,
                                      steps=384, rate=0.25, min_requests=8,
                                      seed=seed)),
    ]


def four_chip_phases(seed, mesh):
    return [
        ("sharded_switch", phase_sharded_switch,
         dict(mesh=mesh, n_tiers=8, n_flows=64, ring_entries=256,
              steps=192, seed=seed)),
        ("sharded_engine", phase_sharded_engine,
         dict(mesh=mesh, n_tenants=64, n_flows=64, ring_entries=256,
              seed=seed)),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.config import enable_compile_cache
    cache_dir = enable_compile_cache()
    meter = Meter()
    meter.register()
    print(json.dumps({"compile_cache": cache_dir,
                      "devices": [str(d) for d in devices]}), flush=True)
    if args.chips == 4:
        from repro.core.transport import make_tenant_mesh
        phases = [(n, f, kw, (None,))
                  for n, f, kw in four_chip_phases(args.seed,
                                                   make_tenant_mesh(4))]
    else:
        phases = [(n, f, kw, (False, True))
                  for n, f, kw in one_chip_phases(args.seed)]
    failed = []
    for name, fn, kw, variants in phases:
        for use_pallas in variants:
            extra = {} if use_pallas is None else {"use_pallas": use_pallas}
            try:
                run_phase(meter, name, fn, **kw, **extra)
            # a failed phase is reported and the run goes on to the next;
            # the exit code carries the failure
            except Exception as e:  # fabriclint: allow(FL007)
                import traceback
                traceback.print_exc()
                print(json.dumps({"phase": name, "use_pallas": use_pallas,
                                  "verdict": "fail",
                                  "error": f"{type(e).__name__}: {e}"[:2000]}),
                      flush=True)
                failed.append(f"{name}[use_pallas={use_pallas}]")
    print(json.dumps({"compile_cache_hits": meter.cache_hits,
                      "compile_s_total": round(meter.compile_s, 3)}),
          flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
