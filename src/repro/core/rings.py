"""Ring buffers, free-slot FIFOs and rank helpers (all functional).

These primitives implement the paper's Fig. 8/9 data structures:

* ``Ring``   — per-flow circular RX/TX buffers of fixed-size slots with
  head/tail cursors (head = consumer, tail = producer).
* ``FreeFifo`` — the TX-path free-slot FIFO tracking unused entries of the
  request buffer (paper Fig. 9B).
* rank helpers — vectorized "position within my group" computations used to
  assign FIFO/ring positions to a batch of concurrent writes (the hardware
  analogue: per-cycle arbitration among parallel agents).

All cursors are monotonically increasing int32; physical index = cursor %
capacity.  Occupancy = tail - head, free = capacity - occupancy.  This is
the standard lock-free single-producer/single-consumer ring construction;
the paper gets lock-freedom from the 1:1 flow<->ring<->thread mapping, and
we inherit it because each mesh lane owns its ring shard.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import telemetry as tlm


def rank_within(mask):
    """mask [..., N] bool -> rank of each True among Trues (along last dim).

    rank[i] = number of True entries strictly before i.  False entries get
    the rank they *would* have (useful with mode="drop" scatters).
    """
    c = jnp.cumsum(mask.astype(jnp.int32), axis=-1)
    return c - mask.astype(jnp.int32)


def rank_by_group_onehot(groups, n_groups: int, valid):
    """Reference O(N * n_groups) one-hot + cumsum arbitration.

    Kept as the parity oracle for ``rank_by_group`` (and for readers: this
    is the textbook formulation).  Materializes an [N, n_groups] matrix on
    every call, which made it the hot spot of ``Ring.push``.
    """
    onehot = (groups[:, None] == jnp.arange(n_groups)[None, :]) & valid[:, None]
    c = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    rank = jnp.take_along_axis(
        c - onehot.astype(jnp.int32), groups[:, None], axis=1)[:, 0]
    counts = c[-1] if groups.shape[0] else jnp.zeros((n_groups,), jnp.int32)
    return jnp.where(valid, rank, 0), counts


def rank_by_group(groups, n_groups: int, valid):
    """groups [N] int32, valid [N] -> (rank within own group, group counts).

    Vectorized multi-queue arbitration: for each request, its insertion
    position in its target queue; plus per-group totals.

    O(N log N) sort-based segmented rank: stable-argsort by group (invalid
    entries pushed to a sentinel segment), then rank-within-segment =
    sorted position - segment start, scattered back to request order.
    Replaces the one-hot + cumsum O(N * n_groups) formulation
    (``rank_by_group_onehot``) which built an [N, n_groups] matrix on every
    ``Ring.push`` / ``nic_deliver``.
    """
    n = groups.shape[0]
    if n == 0:
        return (jnp.zeros((0,), jnp.int32),
                jnp.zeros((n_groups,), jnp.int32))
    g = jnp.where(valid, groups, n_groups).astype(jnp.int32)
    order = jnp.argsort(g)                    # stable: ties keep index order
    sg = g[order]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sg[1:] != sg[:-1]])
    seg_start = jax.lax.cummax(jnp.where(is_start, pos, 0))
    rank = jnp.zeros((n,), jnp.int32).at[order].set(pos - seg_start)
    counts = jnp.zeros((n_groups,), jnp.int32).at[g].add(
        1, mode="drop")                       # sentinel segment drops
    return jnp.where(valid, rank, 0), counts


# ---------------------------------------------------------------------------
# Ring
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass
class Ring:
    """[n_queues, entries, slot_words] circular buffer with cursors."""
    buf: jnp.ndarray          # [Q, E, W] int32
    head: jnp.ndarray         # [Q] int32 (consumer cursor)
    tail: jnp.ndarray         # [Q] int32 (producer cursor)

    @staticmethod
    def create(n_queues: int, entries: int, slot_words: int) -> "Ring":
        return Ring(jnp.zeros((n_queues, entries, slot_words), jnp.int32),
                    jnp.zeros((n_queues,), jnp.int32),
                    jnp.zeros((n_queues,), jnp.int32))

    @property
    def capacity(self) -> int:
        return self.buf.shape[1]

    def occupancy(self):
        return self.tail - self.head

    @jax.named_scope(tlm.SCOPE_RING_PUSH)
    def push(self, queue_ids, slots, valid, use_pallas: bool = False):
        """Push slots [N, W] to queues [N]; returns (ring, accepted [N]).

        Entries that would overflow their queue are dropped (the paper's
        ring-full packet drop, counted by the Packet Monitor).  With
        ``use_pallas`` the row scatter runs through the fused
        ``ring_push`` kernel (interpret mode on CPU).
        """
        e = self.capacity
        rank, counts = rank_by_group(queue_ids, self.buf.shape[0], valid)
        free = e - (self.tail - self.head)
        accepted = valid & (rank < free[queue_ids])
        pos = (self.tail[queue_ids] + rank) % e
        q = jnp.where(accepted, queue_ids, self.buf.shape[0])     # OOB -> drop
        if use_pallas:
            from repro.kernels import ops as kops
            buf = kops.ring_push(self.buf, q, pos, slots)
        else:
            buf = self.buf.at[q, pos].set(slots, mode="drop")
        n_acc_per_q = jnp.zeros_like(self.tail).at[q].add(
            accepted.astype(jnp.int32), mode="drop")
        return Ring(buf, self.head, self.tail + n_acc_per_q), accepted

    @jax.named_scope(tlm.SCOPE_RING_PEEK)
    def peek(self, max_n: int):
        """Read up to max_n slots from every queue head.

        Returns (slots [Q, max_n, W], valid [Q, max_n]) without consuming.
        """
        e = self.capacity
        offs = jnp.arange(max_n)
        idx = (self.head[:, None] + offs[None, :]) % e
        slots = jnp.take_along_axis(self.buf, idx[:, :, None], axis=1)
        valid = offs[None, :] < (self.tail - self.head)[:, None]
        return slots, valid

    @jax.named_scope(tlm.SCOPE_RING_ADVANCE)
    def advance(self, n_per_queue):
        return Ring(self.buf, self.head + n_per_queue, self.tail)


# ---------------------------------------------------------------------------
# Free-slot FIFO (paper Fig. 9B)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass
class FreeFifo:
    """Circular FIFO of free request-buffer slot ids."""
    fifo: jnp.ndarray         # [R] int32
    head: jnp.ndarray         # scalar int32 (next to allocate)
    tail: jnp.ndarray         # scalar int32 (next to release into)

    @staticmethod
    def create(n_slots: int) -> "FreeFifo":
        return FreeFifo(jnp.arange(n_slots, dtype=jnp.int32),
                        jnp.int32(0), jnp.int32(n_slots))

    @property
    def capacity(self) -> int:
        return self.fifo.shape[0]

    def available(self):
        return self.tail - self.head

    def allocate(self, want_mask):
        """want_mask [N] bool -> (fifo', slot_ids [N], granted [N]).

        Grants slots FIFO-order to the first ``available`` requesters.
        Non-granted entries get slot_id == capacity (safe OOB sentinel).
        """
        r = self.capacity
        rank = rank_within(want_mask)
        granted = want_mask & (rank < self.available())
        idx = (self.head + rank) % r
        slot_ids = jnp.where(granted, self.fifo[idx], r)
        n = jnp.sum(granted.astype(jnp.int32))
        return (FreeFifo(self.fifo, self.head + n, self.tail),
                slot_ids, granted)

    def release(self, slot_ids, mask):
        """Return slots to the FIFO. mask [N] selects live entries."""
        r = self.capacity
        rank = rank_within(mask)
        idx = (self.tail + rank) % r
        idx = jnp.where(mask, idx, r)                    # OOB -> drop
        fifo = self.fifo.at[idx].set(slot_ids, mode="drop")
        n = jnp.sum(mask.astype(jnp.int32))
        return FreeFifo(fifo, self.head, self.tail + n)
