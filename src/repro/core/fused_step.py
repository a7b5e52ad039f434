"""Glue for the fused hot-loop kernel: ONE Pallas call per executed cycle.

``fused_cycle_step`` is the ``fsm_backend == "fused"`` twin of
``repro.core.simulator.cycle_step`` *plus* the event-horizon bound of
``repro.core.engine._next_event``, in a single ``pallas_call``
(:mod:`repro.kernels.bank_fsm.fused`). The scalar front-end phases
(trace admission + dispatch), the FR-FCFS promotion network, and the
per-request record/memory scatters stay in XLA glue around the kernel —
they are the literal shared helpers of ``cycle_step``, so the fused path
cannot drift from the reference semantics there by construction.

``fused_cycle_step_batch`` is the vmap-mode twin: the per-lane XLA glue
is vmapped (it vectorizes cleanly), but the kernel operands are laid
out lanes-on-sublanes (:func:`_kernel_layout`) and dispatched as ONE
lane-batched ``pallas_call`` for the whole batch — ``jax.vmap`` over a ``pallas_call``
would instead serialize the kernel per lane through the interpret grid.

Both return ``(new_state, delta)`` where ``delta`` is the exact
event-horizon skip the unfused engine would compute with a second kernel
dispatch: 0 unless the whole machine is provably inert through
``cycle + 1 + delta`` (every bank waiting/blocked/idle-with-empty-queue,
req/resp queues empty, no arrival, no schedule boundary, horizon cap).
The skip engines apply it via ``engine._apply_skip``; the per-cycle scan
engine discards it (it passes ``horizon = cycle + 1`` so the bound clamps
to 0 anyway).

The glue's device ops carry named scopes, which a profiler trace maps to
through the compiled program's ``op_name`` metadata:
``memsim.glue.pre`` (``admit``: trace admission and dispatch into the
bank queues; ``gather``: the FR-FCFS promotion, which searches each bank
ring where it lies, then the queue-head gather with the operand packing)
and ``memsim.glue.post`` (``records``: the start / complete record
scatters; ``memory``: the backing-store write and read). Scopes change op
metadata only, never the compiled step.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import Array

from repro.core import power as power_lib
from repro.core.dram_model import TimingState
from repro.core.params import Topology, as_schedule, tier_of_bank
from repro.core.queues import BankedFifo, Fifo
from repro.core.simulator import (
    SimState,
    Trace,
    _frontend_phases,
    _memory_phase,
    _promote_frfcfs,
)
from repro.kernels.bank_fsm.fused import (
    NUM_BANK_ROWS_OUT,
    NUM_JEDEC_SCAL_OUT,
    NUM_SCAL_OUT,
    fused_step_pallas,
)
from repro.kernels.bank_fsm.ops import default_interpret
from repro.kernels.bank_fsm.ref import pack_state, unpack_state

# plain int, not a jnp constant (see ops.py: no trace-context leakage)
_INF = 0x3FFFFFFF


@jax.named_scope("memsim.glue.pre")
def _pre(topo: Topology, sched, trace: Trace, state: SimState, cycle: Array,
         horizon):
    """Per-lane front-end glue + kernel operand packing (single-lane
    shapes; the batch path vmaps this and :func:`_kernel_layout` moves the
    leading lane axis to where the kernel ABI wants it)."""
    seg = sched.segment_at(cycle)
    # the kernel re-resolves every timing/policy param in-kernel; the only
    # glue consumers are the FR-FCFS promote flag and (tiered topologies)
    # the placement decode flags, so resolve those leaves instead of
    # gathering the full RuntimeParams through params_at
    rp = sched.values._replace(
        sched_policy=jnp.asarray(sched.values.sched_policy, jnp.int32)[seg],
        tier_interleave_log2=jnp.asarray(
            sched.values.tier_interleave_log2, jnp.int32)[seg],
        tier_cxl_frac_log2=jnp.asarray(
            sched.values.tier_cxl_frac_log2, jnp.int32)[seg])
    n = trace.num_requests
    b = topo.num_banks
    nxt = cycle + 1

    with jax.named_scope("admit"):
        (req_q, bank_q, t_admit, t_dispatch, next_arrival, blocked_arrival,
         blocked_dispatch) = _frontend_phases(topo, trace, state, cycle, rp)
    with jax.named_scope("gather"):
        bank_q = _promote_frfcfs(topo, rp, bank_q, state.bank.open_row)
        packed = pack_state(state.bank)
        rob = jnp.arange(b, dtype=jnp.int32) // topo.banks_per_rank
        aw = state.timing.act_win[rob]                   # [B, 4]
        # head PEEK in glue (the split kernel's ABI does the same); the pop
        # bookkeeping runs in-kernel on the qmeta rows
        pop_items, _ = bank_q.peek_valid()
        rows = [
            packed,
            jnp.stack([
                bank_q.head, bank_q.count,
                state.timing.last_act[rob], aw[:, 0], aw[:, 1], aw[:, 2],
                aw[:, 3], state.timing.last_rd[rob],
                state.timing.last_wr[rob],
            ]),
            pop_items.T,
        ]
        if topo.jedec:
            # each bank's group registers, then its own gate and bid start
            gob = jnp.arange(b, dtype=jnp.int32) // topo.banks_per_group
            tm = state.timing
            rows.append(jnp.stack([
                tm.bg_act.reshape(-1)[gob], tm.bg_rd.reshape(-1)[gob],
                tm.bg_wr.reshape(-1)[gob], state.bank.pre_ready,
                state.bank.bid_since]))
        bank_rows = jnp.concatenate(rows)
    bounds, rp_mat = sched.pack()
    # next-arrival distance from nxt, post-admission (what the unfused
    # engine's _next_event reads off the post-edge state)
    idx = jnp.minimum(next_arrival, n - 1)
    arrival_rel = jnp.where(next_arrival < n,
                            trace.t[idx] - nxt, jnp.int32(_INF))
    scal = jnp.concatenate([
        jnp.stack([
            cycle, arrival_rel, jnp.asarray(horizon, jnp.int32),
            req_q.count, state.resp_q.head, state.resp_q.count,
            state.resp_q.limit, state.resp_rr,
        ]),
        state.cmd_rr,
    ]).reshape(1, -1)

    ops = (bank_rows, state.resp_q.buf, rp_mat, bounds, scal)
    ctx = (req_q, bank_q, t_admit, t_dispatch, next_arrival, blocked_arrival,
           blocked_dispatch, seg)
    return ops, ctx


@jax.named_scope("memsim.glue.post")
def _post(topo: Topology, n: int, state: SimState, cycle: Array, ctx,
          outs) -> Tuple[SimState, Array]:
    """Per-lane unpack of the kernel outputs + the remaining scalar glue
    (record/memory scatters, counters). ``outs`` carries single-lane
    shapes with the scalar block as a flat [9+2C] row."""
    (req_q, bank_q, t_admit, t_dispatch, next_arrival, blocked_arrival,
     blocked_dispatch, seg) = ctx
    bank2, resp_buf2, scal_row = outs
    new_packed = bank2[:10]
    flags = bank2[10:13]
    qmeta2 = bank2[13:15]
    timing2 = bank2[15:22]

    new_bank = unpack_state(new_packed)
    want_pop = flags[0] == 1
    rw_done = flags[1] == 1
    bank_q = BankedFifo(buf=bank_q.buf, head=qmeta2[0], count=qmeta2[1],
                        limit=bank_q.limit)
    sel = timing2[:, ::topo.banks_per_rank]              # [7, R] rank-uniform
    groups = {}
    if topo.jedec:
        jrows = bank2[NUM_BANK_ROWS_OUT:]
        # [3, R*BG] group-uniform -> each register's [R, BG]
        gsel = jrows[:3, ::topo.banks_per_group].reshape(
            3, topo.num_ranks, topo.bankgroups)
        groups = dict(bg_act=gsel[0], bg_rd=gsel[1], bg_wr=gsel[2])
        new_bank = new_bank._replace(pre_ready=jrows[3], bid_since=jrows[4])
    timing = TimingState(
        last_act=sel[0],
        act_win=jnp.stack([sel[1], sel[2], sel[3], sel[4]], axis=1),
        last_rd=sel[5], last_wr=sel[6], **groups,
    )
    delta = scal_row[0]
    resp_rr = scal_row[1]
    resp_q = Fifo(buf=resp_buf2, head=scal_row[2], count=scal_row[3],
                  limit=state.resp_q.limit)
    ack_valid = scal_row[4] == 1
    fitem_id = scal_row[8]
    channels = topo.channels
    cmd_rr = scal_row[NUM_SCAL_OUT:NUM_SCAL_OUT + channels]
    issued_cmds = scal_row[NUM_SCAL_OUT + channels:
                           NUM_SCAL_OUT + 2 * channels]

    with jax.named_scope("records"):
        # where a bank popped, the FSM latched the popped item into its
        # cur_* registers this edge, so new cur_id IS the popped request id
        t_start = state.t_start.at[
            jnp.where(want_pop, new_bank.cur_id, n)
        ].set(cycle, mode="drop")
    with jax.named_scope("memory"):
        mem, rdata = _memory_phase(topo, n, state.bank, state.mem,
                                   state.rdata, rw_done)
    with jax.named_scope("records"):
        t_complete = state.t_complete.at[
            jnp.where(ack_valid, fitem_id, n)
        ].set(cycle, mode="drop")
    stats = None
    if topo.jedec:
        at = NUM_SCAL_OUT + 2 * channels
        stats = tuple(scal_row[at + k] for k in range(NUM_JEDEC_SCAL_OUT))
    counters = power_lib.update_counters(
        state.counters, issued_cmds, state.bank.st, seg,
        tier_idx=tier_of_bank(topo) if topo.tiers > 1 else None,
        grant_stats=stats)

    new_state = SimState(
        next_arrival=next_arrival,
        req_q=req_q,
        bank_q=bank_q,
        bank=new_bank,
        timing=timing,
        cmd_rr=cmd_rr,
        resp_rr=resp_rr,
        resp_q=resp_q,
        mem=mem,
        t_admit=t_admit,
        t_dispatch=t_dispatch,
        t_start=t_start,
        t_complete=t_complete,
        rdata=rdata,
        counters=counters,
        blocked_arrival=blocked_arrival,
        blocked_dispatch=blocked_dispatch,
    )
    return new_state, delta


def _kernel_layout(ops):
    """Lane-stacked per-lane operands ([L, ...] leading axis, as ``_pre``
    under vmap produces them) -> the kernel ABI of
    :mod:`repro.kernels.bank_fsm.fused`: bank rows [23, L, B] ([28, L, B]
    under ``"jedec"``), resp_buf [F, L, Qr], rp_mat [L, T*S*NP], bounds
    [L, S], scal [L, 8+C]."""
    bank_rows, resp_buf, rp_mat, bounds, scal = ops
    lanes = bank_rows.shape[0]
    return (jnp.moveaxis(bank_rows, 0, 1),
            jnp.transpose(resp_buf, (2, 0, 1)),
            rp_mat.reshape(lanes, -1),
            bounds.reshape(lanes, -1),
            scal.reshape(lanes, -1))


def _lane_outputs(outs):
    """Kernel outputs -> lane-stacked per-lane shapes for ``_post``:
    [L, 22, B], [L, Qr, F] and the [L, 9+2C] scalar rows."""
    bank2, resp_buf2, scal2 = outs
    return (jnp.moveaxis(bank2, 1, 0), jnp.transpose(resp_buf2, (1, 2, 0)),
            scal2)


def fused_cycle_step(topo: Topology, sched, trace: Trace, state: SimState,
                     cycle: Array, horizon) -> Tuple[SimState, Array]:
    """One synchronous clock edge + the event bound at ``cycle + 1``.

    Bit-exact against ``cycle_step`` followed by ``engine._next_event``
    (enforced by tests/test_kernels.py and tests/test_engine_equivalence.py)
    while issuing exactly one Pallas dispatch. ``horizon`` caps the skip
    (the engine's ``num_cycles``); pass ``cycle + 1`` to force ``delta=0``.
    """
    sched = as_schedule(sched)
    cycle = jnp.asarray(cycle, jnp.int32)
    ops, ctx = _pre(topo, sched, trace, state, cycle, horizon)
    outs = fused_step_pallas(
        topo, *_kernel_layout(tuple(x[None] for x in ops)),
        interpret=default_interpret())
    outs = tuple(x[0] for x in _lane_outputs(outs))
    return _post(topo, trace.num_requests, state, cycle, ctx, outs)


def fused_cycle_step_batch(topo: Topology, scheds, traces, states,
                           cycle: Array, horizon) -> Tuple[SimState, Array]:
    """Lane-batched twin of :func:`fused_cycle_step` for the vmap-mode
    skip engine: per-lane glue under ``jax.vmap``, kernel operands laid
    out lanes-on-sublanes, ONE lane-batched dispatch per executed cycle
    for the whole batch. Returns stacked states and per-lane deltas (the
    engine skips by their min, same as the unfused vmap path)."""
    cycle = jnp.asarray(cycle, jnp.int32)
    ops, ctx = jax.vmap(
        lambda tr, sc, st: _pre(topo, sc, tr, st, cycle, horizon)
    )(traces, scheds, states)
    outs = fused_step_pallas(topo, *_kernel_layout(ops),
                             interpret=default_interpret())
    n = traces.t.shape[-1]               # per-lane request count (uniform)
    return jax.vmap(
        lambda st, ctx_l, out_l: _post(topo, n, st, cycle, ctx_l, out_l)
    )(states, ctx, _lane_outputs(outs))
