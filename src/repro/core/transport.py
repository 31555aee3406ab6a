"""Transport layer: loopback, switch, and mesh (ICI) transports.

The paper's transport is a simplified UDP/IP pipe (the Protocol unit is
idle, §4.5) evaluated over a loopback wire.  We provide three transports
matching the three deployment scales:

* ``loopback``   — client/server NIC pair on one device (the paper's
  evaluation setup; used by ``make_loopback_step``).
* ``Switch``     — N virtual NICs + static L2 table on one device
  (``repro.core.virtualization``; the paper's 8-tier experiment).
* mesh transport — tiles move between *mesh lanes* with ``lax.ppermute``
  / ``lax.all_to_all`` under ``shard_map`` — the scale-out transport that
  maps the paper's ToR hop onto the device interconnect.  This is LIVE:
  ``repro.core.engine.ShardedTenantEngine`` places the tenant axis on a
  mesh, and ``Switch.switch_step_sharded`` routes inter-shard RPCs
  through ``all_to_all_tiles`` buckets (every NIC sends a batch to every
  other NIC through the switch in one step).

Two API levels:

* ``shift_tiles`` / ``all_to_all_tiles`` run INSIDE an enclosing
  ``shard_map`` (per-lane view) — these are what the sharded dataplane
  steps compose with their local pipeline stages;
* ``mesh_shift`` / ``mesh_all_to_all`` are standalone wrappers that
  apply the ``shard_map`` themselves (global-array view) for one-shot
  exchanges and tests.

Two exchange formats ride ``all_to_all_tiles``:

* **full-tile** — every lane ships its whole local tile to every
  destination plus a per-destination valid mask.  Order-exact and
  overflow-free, but the wire cost is ``D x n_rows`` rows per lane
  regardless of how many rows actually cross lanes — cross-device
  bandwidth grows with the mesh, not with offered load (the overhead
  RPCAcc attributes to non-compacted PCIe-attached datapaths).
* **compacted** (``compact_buckets`` / ``exchange_compact``) — each
  per-destination bucket carries ONLY the rows destined there
  (argsort-compaction, original order preserved) plus a per-bucket
  count; the receive side re-expands validity from the counts.  Wire
  cost is ``D x bucket_cap`` rows with ``bucket_cap`` chosen from the
  expected cross-lane burst (the paper's fabric moves only flits that
  have a destination; Beehive's per-lane message steering).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import telemetry as tlm


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` as every dataplane caller uses it: per-lane
    state is device-varying by design, so the varying-manual-axes check
    is off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# per-lane collectives (call INSIDE shard_map)
# ---------------------------------------------------------------------------

def shift_tiles(tile, axis: str, n_lanes: int, offset: int = 1):
    """Rotate per-lane tiles along a mesh axis (ring transport).

    Per-lane view: each lane's tile moves to lane+offset — the Dagger
    wire between NIC i and NIC i+offset.  ``n_lanes`` is the (static)
    mesh axis size."""
    perm = [(i, (i + offset) % n_lanes) for i in range(n_lanes)]
    return jax.tree.map(lambda x: jax.lax.ppermute(x, axis, perm), tile)


@jax.named_scope(tlm.SCOPE_EXCHANGE)
def all_to_all_tiles(tile, axis: str):
    """All-to-all exchange of per-destination tile buckets along a mesh
    axis.  Per-lane view: leaf shape [n_lanes * bucket, ...] where block
    j is this lane's bucket for lane j; afterwards block j holds lane j's
    bucket for this lane.  The Dagger analogue: every NIC sends a batch
    to every other NIC through the ToR switch in one step."""
    return jax.tree.map(
        lambda x: jax.lax.all_to_all(x, axis, split_axis=0,
                                     concat_axis=0, tiled=True), tile)


# ---------------------------------------------------------------------------
# compacted exchange (per-destination buckets: destined rows + count)
# ---------------------------------------------------------------------------

def compact_buckets(rows, valid, dest_dev, n_dev: int, cap: int):
    """Compact a local tile into per-destination-device buckets.

    rows: pytree of [N, ...] leaves (one row per local candidate);
    valid: [N] bool; dest_dev: [N] int32 destination device per row.
    Returns ``(buckets, counts, dropped, shipped)`` where every
    ``buckets`` leaf is [n_dev * cap, ...] (block j = the bucket for
    device j), ``counts`` [n_dev] is the number of live rows in each
    bucket, ``dropped`` [n_dev] counts rows lost to bucket overflow (0
    whenever ``cap >= N`` — the safe default the sharded switch uses),
    and ``shipped`` [N] marks, in the ORIGINAL row order, which valid
    rows made it into a bucket (``valid & ~shipped`` = the dropped
    rows, for per-source attribution).

    The compaction is one stable argsort by destination device, so rows
    sharing a destination keep their original relative order — the
    property that lets the compacted sharded switch reproduce the
    full-tile arbitration outcomes record-for-record (only bucket
    *positions* differ, which the canonical-order comparator absorbs).
    """
    n = dest_dev.shape[0]
    valid = jnp.asarray(valid, bool)
    key = jnp.where(valid, dest_dev.astype(jnp.int32), n_dev)
    order = jnp.argsort(key)              # stable: ties keep row order
    skey = key[order]
    counts = jnp.zeros((n_dev,), jnp.int32).at[key].add(1, mode="drop")
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(n, dtype=jnp.int32) - start[
        jnp.clip(skey, 0, n_dev - 1)]
    live = (skey < n_dev) & (pos < cap)
    tgt = jnp.where(live, skey * cap + pos, n_dev * cap)  # OOB -> drop

    def scatter(x):
        out = jnp.zeros((n_dev * cap,) + x.shape[1:], x.dtype)
        return out.at[tgt].set(x[order], mode="drop")

    buckets = jax.tree.map(scatter, rows)
    sent = jnp.minimum(counts, cap)
    shipped = jnp.zeros((n,), bool).at[order].set(live)
    return buckets, sent, counts - sent, shipped


def bucket_valid(counts, cap: int):
    """counts [n_dev] -> row-validity [n_dev * cap] for compacted
    buckets: the first ``counts[j]`` rows of block j are live."""
    lane = jnp.arange(cap, dtype=jnp.int32)[None, :] < counts[:, None]
    return lane.reshape(-1)


@jax.named_scope(tlm.SCOPE_EXCHANGE)
def exchange_compact(rows, valid, dest_dev, axis: str, n_dev: int,
                     cap: int):
    """Compacted all-to-all (call INSIDE shard_map): compact the local
    tile, exchange buckets + counts, re-expand validity by count.

    Returns ``(rows', valid', dropped, shipped)``: leaves
    [n_dev * cap, ...] where block j now holds the rows device j sent
    here (in j's local order), ``valid'`` [n_dev * cap], ``dropped``
    [n_dev] counting local rows lost to bucket overflow (all zero when
    ``cap`` covers the worst-case burst, e.g. ``cap = N``), and
    ``shipped`` [N] the per-LOCAL-row survival mask (original order —
    what the sharded switch feeds its ``drops_exchange`` monitor
    counter).  Wire cost per lane is ``compact_exchange_words`` vs the
    full-tile path's ``full_exchange_words`` — the bytes the Dagger
    fabric never ships because the flits had no destination."""
    buckets, counts, dropped, shipped = compact_buckets(
        rows, valid, dest_dev, n_dev, cap)
    g = all_to_all_tiles({"rows": buckets, "counts": counts}, axis)
    return g["rows"], bucket_valid(g["counts"], cap), dropped, shipped


def full_exchange_words(n_dev: int, n_rows: int, slot_words: int) -> int:
    """Words one lane puts on the wire per full-tile exchange: n_dev
    copies of the whole tile (slot words + dest) + per-destination valid
    masks."""
    return n_dev * n_rows * (slot_words + 2)


def compact_exchange_words(n_dev: int, cap: int, slot_words: int) -> int:
    """Words one lane puts on the wire per compacted exchange: n_dev
    buckets of cap rows (slot words + dest) + one count each."""
    return n_dev * (cap * (slot_words + 1) + 1)


# ---------------------------------------------------------------------------
# global-array wrappers (apply shard_map themselves)
# ---------------------------------------------------------------------------

def mesh_shift(tile, mesh, axis: str, offset: int = 1):
    """Rotate per-lane tiles along a mesh axis (ring transport).

    tile: any pytree whose leaves have a leading lane (sharded) dim equal
    to the axis size.  Each lane sends its tile to lane+offset."""
    n = mesh.shape[axis]
    specs = jax.tree.map(lambda _: P(axis), tile)
    return shard_map(lambda t: shift_tiles(t, axis, n, offset), mesh=mesh,
                     in_specs=(specs,), out_specs=specs)(tile)


def mesh_all_to_all(tile, mesh, axis: str):
    """All-to-all exchange of per-destination tile buckets along a mesh
    axis: leaf shape [lanes, lanes_per_dest, ...] -> same, transposed
    across lanes (global-array view of ``all_to_all_tiles``)."""
    specs = jax.tree.map(lambda _: P(axis), tile)
    return shard_map(lambda t: all_to_all_tiles(t, axis), mesh=mesh,
                     in_specs=(specs,), out_specs=specs)(tile)


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------

def _make_mesh(shape, names):
    """``jax.make_mesh`` (device order from the physical topology) with
    the classic auto-sharded axes the dataplane's specs are written
    for; ``jax.make_mesh`` raises when the host has too few devices."""
    from jax.sharding import AxisType
    n = 1
    for d in shape:
        n *= d
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=jax.devices()[:n])


def make_tenant_mesh(n_devices: int | None = None, axis: str = "tenant"):
    """1-D mesh over the host's devices with the tenant (NIC-slot) axis.

    The sharded dataplane puts the stacked tenant axis on this mesh so
    each device owns whole NIC slots; on a single-device host this is a
    1-lane mesh and the sharded engines degrade to the batched ones."""
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    return _make_mesh((n,), (axis,))


def make_grid_mesh(n_tenant: int | None = None, n_model: int | None = None,
                   tenant_axis: str = "tenant", model_axis: str = "model"):
    """2-D (tenant, model) mesh for the serving dataplane: tenants shard
    over the first axis (whole NIC slots per device group, as in
    ``make_tenant_mesh``), and each tenant's model weights/KV heads
    tensor-parallel over the second.  Defaults split the host's devices
    as evenly as possible, favoring the tenant axis: ``n_model`` is the
    largest divisor of the device count that is <= sqrt(count)."""
    n = len(jax.devices())
    if n_tenant is None and n_model is None:
        n_model = max(d for d in range(1, int(n ** 0.5) + 1) if n % d == 0)
        n_tenant = n // n_model
    elif n_model is None:
        n_model = n // int(n_tenant)
    elif n_tenant is None:
        n_tenant = n // int(n_model)
    return _make_mesh((int(n_tenant), int(n_model)),
                      (tenant_axis, model_axis))
