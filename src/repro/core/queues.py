"""Fixed-capacity circular FIFOs, the RTL Decoupled-queue analogue.

Two flavours:

* ``Fifo``        — a single queue: ``buf[Q, F]`` plus scalar head/count.
* ``BankedFifo``  — a batch of B independent queues ``buf[B, Q, F]`` with
  vectorized per-bank pop (every bank may pop in the same cycle) and
  single-bank push (the controller dispatches one request per cycle).

All fields are int32; ``F`` packs the request fields
``(addr, is_write, data, req_id)``. Operations are branchless (masked) so
they can live inside a ``lax.scan`` cycle step, mirroring how an RTL queue
always computes its next state and the enable wire decides commitment.

Each queue carries a runtime ``limit`` (occupancy cap <= static capacity):
``full()`` compares ``count`` against ``limit`` instead of the buffer shape,
so a queue-depth sweep can reuse one compiled program — the buffer is sized
for the largest depth and the limit is a traced scalar. With
``limit == capacity`` (the default) behaviour is identical to the plain
circular queue.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import Array

REQ_FIELDS = 4  # addr, is_write, data, req_id
F_ADDR, F_WRITE, F_DATA, F_ID = 0, 1, 2, 3


class Fifo(NamedTuple):
    buf: Array    # [Q, F] int32
    head: Array   # scalar int32
    count: Array  # scalar int32
    limit: Array  # scalar int32 runtime occupancy cap (<= capacity)

    @staticmethod
    def make(capacity: int, fields: int = REQ_FIELDS, limit=None) -> "Fifo":
        return Fifo(
            buf=jnp.zeros((capacity, fields), jnp.int32),
            head=jnp.int32(0),
            count=jnp.int32(0),
            limit=jnp.asarray(capacity if limit is None else limit, jnp.int32),
        )

    @property
    def capacity(self) -> int:
        return self.buf.shape[0]

    def full(self) -> Array:
        return self.count >= self.limit

    def empty(self) -> Array:
        return self.count == 0

    def peek(self) -> Array:
        """Head item [F]; garbage if empty (callers must mask)."""
        return self.buf[self.head]

    def peek_valid(self) -> Tuple[Array, Array]:
        """Masked head-of-queue peek without pop: ``(item [F], valid)``.

        ``valid`` is the occupancy bit the raw :meth:`peek` leaves to the
        caller; the item is garbage when ``valid`` is False. The cycle
        stepper and the event-horizon bound both read queue heads through
        this, so "is there a request to act on" has one definition.
        """
        return self.peek(), ~self.empty()

    def push(self, item: Array, enable: Array) -> "Fifo":
        # RTL ready & valid commitment: a push into a full queue does not
        # commit, even if the caller forgot to gate its enable — otherwise
        # ``count`` would exceed ``limit`` and the write index would wrap
        # onto the head entry, corrupting the oldest in-flight request.
        enable = jnp.logical_and(enable, ~self.full())
        q = self.capacity
        idx = (self.head + self.count) % q
        cur = self.buf[idx]
        new = jnp.where(enable, item, cur)
        # dynamic_update_slice (not scatter): alias-friendly, so the buffer
        # stays in-place across scan/while iterations even at large capacity
        return Fifo(
            buf=jax.lax.dynamic_update_slice(self.buf, new[None, :],
                                             (idx, jnp.int32(0))),
            head=self.head,
            count=self.count + enable.astype(jnp.int32),
            limit=self.limit,
        )

    def pop(self, enable: Array) -> Tuple["Fifo", Array]:
        item = self.peek()
        en = enable.astype(jnp.int32)
        return (
            Fifo(buf=self.buf, head=(self.head + en) % self.capacity,
                 count=self.count - en, limit=self.limit),
            item,
        )


class BankedFifo(NamedTuple):
    buf: Array    # [B, Q, F] int32
    head: Array   # [B] int32
    count: Array  # [B] int32
    limit: Array  # scalar int32 runtime occupancy cap (<= capacity, all banks)

    @staticmethod
    def make(banks: int, capacity: int, fields: int = REQ_FIELDS,
             limit=None) -> "BankedFifo":
        return BankedFifo(
            buf=jnp.zeros((banks, capacity, fields), jnp.int32),
            head=jnp.zeros((banks,), jnp.int32),
            count=jnp.zeros((banks,), jnp.int32),
            limit=jnp.asarray(capacity if limit is None else limit, jnp.int32),
        )

    @property
    def capacity(self) -> int:
        return self.buf.shape[1]

    def full(self) -> Array:           # [B] bool
        return self.count >= self.limit

    def empty(self) -> Array:          # [B] bool
        return self.count == 0

    def peek(self) -> Array:
        """Per-bank head items [B, F]; garbage where empty."""
        b = self.buf.shape[0]
        return self.buf[jnp.arange(b), self.head]

    def peek_valid(self) -> Tuple[Array, Array]:
        """Masked per-bank head peek without pop: ``(items [B, F],
        valid bool[B])``. Items are garbage where ``valid`` is False."""
        return self.peek(), ~self.empty()

    def push_at(self, bank: Array, item: Array, enable: Array) -> "BankedFifo":
        """Push ``item`` [F] into queue ``bank`` (scalar index), masked.

        Like :meth:`Fifo.push`, the enable is gated on the target bank not
        being at its runtime limit (RTL ready & valid), so an ungated push
        can never overrun the queue and wrap onto its head entry."""
        enable = jnp.logical_and(enable, ~self.full()[bank])
        q = self.capacity
        idx = (self.head[bank] + self.count[bank]) % q
        cur = self.buf[bank, idx]
        new = jnp.where(enable, item, cur)
        en = enable.astype(jnp.int32)
        return BankedFifo(
            buf=jax.lax.dynamic_update_slice(
                self.buf, new[None, None, :], (bank, idx, jnp.int32(0))),
            head=self.head,
            count=self.count.at[bank].add(en),
            limit=self.limit,
        )

    def pop_mask(self, enable: Array) -> Tuple["BankedFifo", Array]:
        """Vectorized pop: every bank whose ``enable`` bit is set pops its head.

        Returns (new_fifo, items[B, F]).
        """
        items = self.peek()
        en = enable.astype(jnp.int32)
        return (
            BankedFifo(
                buf=self.buf,
                head=(self.head + en) % self.capacity,
                count=self.count - en,
                limit=self.limit,
            ),
            items,
        )

    def promote_rowhit(self, open_row: Array, rows: Array) -> "BankedFifo":
        """FR-FCFS (first-ready, first-come-first-serve): swap the oldest
        row-hit entry into the head slot so the scheduler issues it next.

        ``open_row`` int32[B] (-1 = no open row); ``rows`` int32[B, Q] row
        index of every queue slot where it lies in the ring (physical slot
        order, not age order). An entry is only promoted if no older entry
        touches the same address (program order per address must hold —
        real controllers enforce the same dependency check).

        The search works on the ring in place: every slot knows its age
        ``(slot - head) % Q`` and the chosen entry's address is read by a
        masked reduction, so nothing rotates the ring into age order (a
        [B, Q] gather, and under ``vmap`` an [L, B, Q] one). Without a
        promotable hit ``sel`` is 0 and the swap is the identity.
        """
        q = self.capacity
        slot = jnp.arange(q, dtype=jnp.int32)[None, :]               # [1, Q]
        age = (slot - self.head[:, None]) % q                        # [B, Q]
        valid = age < self.count[:, None]
        hit = valid & (rows == open_row[:, None]) & (open_row >= 0)[:, None]
        first = jnp.min(jnp.where(hit, age, q), axis=1)              # [B]
        has = first < q
        # dependency guard: an older same-address entry blocks promotion
        addr = self.buf[..., F_ADDR]
        addr_sel = jnp.sum(jnp.where(age == first[:, None], addr, 0), axis=1)
        # (older than a hit means valid: first < count wherever has)
        older = age < first[:, None]
        conflict = (older & (addr == addr_sel[:, None])).any(axis=1)
        sel = jnp.where(has & ~conflict, first, 0)
        pos = (self.head + sel) % q
        buf = _swap_into_head(self.buf, self.head, pos)
        return BankedFifo(buf, self.head, self.count, self.limit)


def _swap_rows(buf: Array, head: Array, pos: Array) -> Array:
    """Swap slot ``pos[b]`` with slot ``head[b]`` of every bank queue by
    per-bank row reads and writes."""
    ar_b = jnp.arange(buf.shape[0])
    head_items = buf[ar_b, head]
    sel_items = buf[ar_b, pos]
    buf = buf.at[ar_b, head].set(sel_items)
    return buf.at[ar_b, pos].set(head_items)


def _swap_onehot(buf: Array, head: Array, pos: Array) -> Array:
    """:func:`_swap_rows` as a dense one-hot select over the ring."""
    slot = jnp.arange(buf.shape[1], dtype=jnp.int32)[None, :]
    at_head = (slot == head[:, None])[..., None]                  # [B, Q, 1]
    at_pos = (slot == pos[:, None])[..., None]
    head_item = jnp.sum(jnp.where(at_head, buf, 0), axis=1)       # [B, F]
    sel_item = jnp.sum(jnp.where(at_pos, buf, 0), axis=1)
    # pos == head takes the at_head arm, where sel_item is the head item
    return jnp.where(at_head, sel_item[:, None, :],
                     jnp.where(at_pos, head_item[:, None, :], buf))


@jax.custom_batching.custom_vmap
def _swap_into_head(buf: Array, head: Array, pos: Array) -> Array:
    """The promotion's swap, in the form that suits how it is batched.

    One lane swaps rows: a dense select over the ring makes XLA lay the
    carried queue buffer out slot-minor, and the single-lane step then
    copies it back for the head peek on every step, FR-FCFS or not (8 % of
    the one-lane decode rate on a TPU v5e). Under ``vmap`` the row writes
    become scatters over the whole [L, B, Q, F] buffer; the one-hot select,
    which fuses with the policy's select, runs a 64-lane sweep 1.17x and
    8 serving lanes 1.5x as fast end to end on a TPU v5e."""
    return _swap_rows(buf, head, pos)


@_swap_into_head.def_vmap
def _swap_into_head_vmap(axis_size, in_batched, buf, head, pos):
    args = [x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, batched in zip((buf, head, pos), in_batched)]
    return jax.vmap(_swap_onehot)(*args), True


def rr_arbiter(bids: Array, ptr: Array) -> Tuple[Array, Array, Array]:
    """Rotating-priority round-robin arbiter (paper §5.3).

    ``bids`` bool[B]; ``ptr`` int32 rotating priority pointer. Returns
    ``(winner_index, any_grant, new_ptr)``. The bank at ``ptr`` has highest
    priority; on a grant the pointer moves one past the winner, giving every
    requester a bounded-latency guarantee — identical semantics to the RTL
    ``RRArbiter``.
    """
    n = bids.shape[0]
    rot = (jnp.arange(n, dtype=jnp.int32) - ptr) % n
    key = jnp.where(bids, rot, n)
    winner = jnp.argmin(key).astype(jnp.int32)
    any_grant = bids.any()
    new_ptr = jnp.where(any_grant, (winner + 1) % n, ptr)
    return winner, any_grant, new_ptr


def rr_arbiter_grouped(bids: Array, ptrs: Array, groups: int) -> Tuple[Array, Array, Array]:
    """Per-channel round-robin: one grant per group of ``B//groups`` banks.

    ``bids`` bool[B] flattened channel-major; ``ptrs`` int32[groups].
    Returns (grant_mask bool[B], winners int32[groups], new_ptrs).

    ``B`` must divide evenly into ``groups``: the reshape below would
    otherwise silently truncate the trailing ``B % groups`` banks out of
    arbitration (those banks could bid forever and never be granted), so a
    non-divisible shape is a configuration error, not a best-effort case.
    """
    b = bids.shape[0]
    if b % groups != 0:
        raise ValueError(
            f"rr_arbiter_grouped: {b} banks do not divide into {groups} "
            f"groups; the trailing {b % groups} banks would never arbitrate")
    per = b // groups
    bids2 = bids.reshape(groups, per)
    rot = (jnp.arange(per, dtype=jnp.int32)[None, :] - ptrs[:, None]) % per
    key = jnp.where(bids2, rot, per)
    winners = jnp.argmin(key, axis=1).astype(jnp.int32)
    any_grant = bids2.any(axis=1)
    new_ptrs = jnp.where(any_grant, (winners + 1) % per, ptrs)
    grant = jnp.zeros((groups, per), bool)
    grant = grant.at[jnp.arange(groups), winners].set(any_grant)
    return grant.reshape(b), winners, new_ptrs
