"""FR-FCFS row-hit promotion on the physical ring against the age-ordered
formulation.

``BankedFifo.promote_rowhit`` searches each bank queue where its slots lie
and swaps the chosen entry into the head slot: by per-bank row updates on
one lane, by a one-hot select under ``vmap``. The oracle is the benchmark's
frozen reference (``bench/reference/queues.py``), which rotates the ring
into age order with a gather and searches there. Both swaps must return
the reference's buffer bit for bit on every ring.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import queues as ref_queues
from repro.core.params import SCHED_FCFS, SCHED_FRFCFS, Topology
from repro.core.queues import F_ADDR, BankedFifo
from repro.core.simulator import _promote_frfcfs

TOPO = Topology()
ROW_SHIFT = TOPO.addr_low_bits + TOPO.column_bits


def _ring(rng, b, q, *, wrap=True, full=False, limit=None, no_open=False,
          rows=3, cols=4, free_rows=False):
    """One random banked ring: few rows and columns, so row-hits and
    repeated addresses are common. Returns the promotion's inputs with the
    row of every physical slot last.

    With rows taken from the addresses, an older same-address entry is
    itself a row-hit and so the first one: the dependency guard cannot
    fire. ``free_rows`` draws the rows apart from the addresses, the input
    on which the guard decides."""
    head = rng.integers(0, q, b) if wrap else np.zeros(b, np.int64)
    cap = q if limit is None else limit
    count = np.full(b, cap) if full else rng.integers(0, cap + 1, b)
    row = rng.integers(0, rows, (b, q))
    col = rng.integers(0, cols, (b, q))
    addr = (row << ROW_SHIFT) | col
    buf = rng.integers(-(1 << 20), 1 << 20, (b, q, 4))
    buf[..., F_ADDR] = addr
    open_row = rng.integers(-1, rows, b)
    if no_open:
        open_row[:] = -1
    if free_rows:
        row = rng.integers(0, rows, (b, q))
    return (jnp.asarray(buf, jnp.int32), jnp.asarray(head, jnp.int32),
            jnp.asarray(count, jnp.int32), jnp.int32(cap),
            jnp.asarray(open_row, jnp.int32), jnp.asarray(row, jnp.int32))


def _promote(buf, head, count, limit, open_row, rows):
    fifo = BankedFifo(buf, head, count, limit)
    return fifo.promote_rowhit(open_row, rows).buf


_promote_new = jax.jit(_promote)
_promote_lanes = jax.jit(jax.vmap(_promote))


@jax.jit
def _promote_ref(buf, head, count, limit, open_row, rows):
    """The age-ordered formulation: rotate the rows into age order."""
    q = buf.shape[1]
    offs = (head[:, None] + jnp.arange(q)[None, :]) % q
    fifo = ref_queues.BankedFifo(buf, head, count, limit)
    return fifo.promote_rowhit(
        open_row, jnp.take_along_axis(rows, offs, axis=1)).buf


def _check(ring):
    got = np.asarray(_promote_new(*ring))
    want = np.asarray(_promote_ref(*ring))
    np.testing.assert_array_equal(got, want)
    return got


def _check_lanes(rings):
    """The batched swap: all rings as lanes of one vmapped call."""
    got = np.asarray(_promote_lanes(*(jnp.stack(x) for x in zip(*rings))))
    for lane, ring in zip(got, rings):
        np.testing.assert_array_equal(lane, np.asarray(_promote_ref(*ring)))


CASES = {
    "wrapped_heads": dict(wrap=True),
    "head_at_zero": dict(wrap=False),
    "full_queues": dict(full=True),
    "limit_below_capacity": dict(limit=5),
    "no_open_row": dict(no_open=True),
    "one_row_repeated_addresses": dict(rows=1, cols=2),
    "distinct_addresses": dict(rows=8, cols=64),
    "rows_apart_from_addresses": dict(rows=2, cols=2, free_rows=True),
}


@pytest.mark.parametrize("q", [1, 8, 16])
@pytest.mark.parametrize("case", list(CASES))
def test_matches_age_ordered_promotion(case, q):
    rng = np.random.default_rng(1000 + q)
    kw = dict(CASES[case])
    if kw.get("limit", 0) > q:
        kw["limit"] = q
    rings = [_ring(rng, 16, q, **kw) for _ in range(40)]
    for ring in rings:
        _check(ring)
    _check_lanes(rings)


def test_covers_promotion_guard_and_identity():
    """The draws reach all three outcomes: an entry promoted, a hit held
    back by an older same-address entry, and no hit (identity)."""
    rng = np.random.default_rng(7)
    promoted = blocked = unchanged = 0
    for _ in range(60):
        ring = _ring(rng, 16, 8, rows=2, cols=2, free_rows=True)
        buf, head, count, _, open_row, row = (np.asarray(x) for x in ring)
        got = _check(ring)
        for bk in range(buf.shape[0]):
            ages = [(head[bk] + a) % 8 for a in range(count[bk])]
            addrs = [buf[bk, s, F_ADDR] for s in ages]
            hits = [i for i, s in enumerate(ages)
                    if open_row[bk] >= 0 and row[bk, s] == open_row[bk]]
            if not np.array_equal(got[bk], buf[bk]):
                promoted += 1
            elif hits and hits[0] > 0 and addrs[hits[0]] in addrs[:hits[0]]:
                blocked += 1
            else:
                unchanged += 1
    assert promoted and blocked and unchanged


def test_batched_promotion_has_no_gather():
    """Under vmap the search and swap are dense over the ring: no gather
    or scatter (the age-order rotation was an [L, B, Q] gather)."""
    rng = np.random.default_rng(0)
    rings = [_ring(rng, 64, 128) for _ in range(2)]
    text = str(jax.make_jaxpr(_promote_lanes)(
        *(jnp.stack(x) for x in zip(*rings))))
    assert "gather" not in text and "scatter" not in text


def test_vmapped_batch_mixing_policies():
    """Under vmap the policy's cond is a select: FCFS lanes come back
    unchanged, FR-FCFS lanes as the reference promotes them."""
    rng = np.random.default_rng(11)
    lanes = 8
    rings = [_ring(rng, 16, 16, rows=2, cols=3) for _ in range(lanes)]
    stacked = [jnp.stack(xs) for xs in zip(*rings)]
    pol = jnp.asarray([SCHED_FRFCFS, SCHED_FCFS] * (lanes // 2), jnp.int32)

    def lane(p, buf, head, count, limit, open_row, _rows):
        rp = types.SimpleNamespace(sched_policy=p)
        return _promote_frfcfs(TOPO, rp, BankedFifo(buf, head, count, limit),
                               open_row).buf

    got = np.asarray(jax.jit(jax.vmap(lane))(pol, *stacked))
    changed = 0
    for i, ring in enumerate(rings):
        if pol[i] == SCHED_FCFS:
            np.testing.assert_array_equal(got[i], np.asarray(ring[0]))
        else:
            want = np.asarray(_promote_ref(*ring))
            np.testing.assert_array_equal(got[i], want)
            changed += not np.array_equal(want, np.asarray(ring[0]))
    assert changed, "no FR-FCFS lane promoted anything: the draw is too easy"


try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests need hypothesis (requirements-dev.txt)
    st = None

if st is not None:
    @settings(max_examples=60, deadline=None)
    @given(seed=st.integers(0, 2**32 - 1), q=st.sampled_from([4, 16]),
           full=st.booleans(), no_open=st.booleans(), free_rows=st.booleans(),
           rows=st.integers(1, 4), cols=st.integers(1, 4))
    def test_matches_age_ordered_promotion_property(seed, q, full, no_open,
                                                    free_rows, rows, cols):
        rng = np.random.default_rng(seed)
        ring = _ring(rng, 8, q, full=full, no_open=no_open, rows=rows,
                     cols=cols, free_rows=free_rows)
        _check(ring)
        _check_lanes([ring])
