"""High-throughput simulation engine: compile-once sweeps, batching, skipping.

The reference engine (:func:`repro.core.simulator.simulate`) runs one
``lax.scan`` step per clock and bakes ``queue_size`` into the compiled
program, so the paper's Fig 7/8/9 queue sweeps pay one full XLA compile per
sweep point and a fully serial 100k-step scan per run. This module removes
those bottlenecks while staying **bit-exact** against the reference:

1. **Compile-once sweeps** — queue occupancy is a *runtime* limit against a
   static max capacity (``Fifo.limit`` / ``BankedFifo.limit``,
   ``SimState.effective_queue_size``). Every sweep point shares one compiled
   program; only the limit scalar changes.

2. **Batched simulation** — :func:`simulate_batch` runs (trace,
   runtime-config) lanes through one compile. In ``"vmap"`` mode the lanes
   are stacked on a leading axis and ``jax.vmap``-ed through the cycle step
   as ONE device program on a shared clock, sharded across devices via the
   ``repro.distributed.shard`` mesh helpers (right on accelerators, whose
   hardware lanes absorb the batch axis). In ``"lanes"`` mode (CPU default)
   one compiled single-lane executable per device serves every lane, and
   lanes execute concurrently from worker threads with *independent*
   cycle-skipping (XLA releases the GIL; ``jax.vmap`` cannot amortize a
   batch across CPU cores, and a shared clock would hold every lane to the
   busiest lane's pace).

3. **Event-horizon cycle-skipping** — after every executed cycle the
   engine computes the next-event cycle as a vectorized min over *per-bank*
   bounds and jumps straight to it: WAIT timer expiries (``timer - 1``),
   blocked command-bus bids becoming legal (the tRRDL/tFAW/tCCDL/tWTR/tRTW
   windows from ``RuntimeParams``, via the same
   :func:`repro.core.simulator.issue_eligibility` predicate the stepper
   grants from), idle banks' refresh windows (``refresh_due - tRFC``) and
   SREF-entry thresholds, the next trace arrival, and the horizon. A cycle
   is provably inert — skippable — when every bank is mid-WAIT, parked in
   SREF, idle with an empty scheduler queue, or bidding a command that is
   not yet legal, and the global request/response queues are empty; unlike
   the PR-1 engine this holds *during* active phases, while banks sit in
   staggered WAIT states or blocked bids, not just when the whole system
   has drained. ``_apply_skip`` advances timers, idle counters and the
   power/state cycle counters by exactly the skipped delta (closed form of
   ``delta`` per-cycle updates), so results (``t_complete``, ``rdata``,
   counters, blocked-cycle totals — the full ``SimState``) are
   bit-identical to the per-cycle engine; only inert cycles are collapsed.
   One ``cycle_step`` executes per event, one skip evaluation per executed
   cycle — WAIT-heavy phases (LLM decode traffic) collapse to their event
   count.

4. **Runtime parameter grids** — every Table-1 timing value, the page
   policy and the scheduler are a traced :class:`RuntimeParams` pytree (the
   static :class:`Topology` carries only shapes), so :func:`sweep_grid`
   runs a whole (timing x page-policy x scheduler x refresh x queue-depth)
   Cartesian grid as batch lanes of ONE compiled XLA program.

   Parameters are further a function of *time*: a lane may carry a whole
   :class:`ParamSchedule` (piecewise-constant DVFS / thermal-throttle /
   refresh-stepping operating points) instead of one constant point — the
   ``"schedule"`` grid axis. Every consumer resolves through the single
   ``params_at(schedule, cycle)`` resolver; the event horizon additionally
   mins in the next segment boundary (an operating-point change is an
   event), so skipping stays bit-exact vs a per-cycle reference that
   re-resolves ``params_at`` every cycle, and ``counters["seg_cycles"]``
   attributes executed+skipped cycles to operating points exactly.

5. **Multi-topology sweeps** — the one axis that genuinely forces new
   programs (the hardware *shape*: channels/ranks/bankgroups/banks) is
   orchestrated by :func:`sweep_topologies`: the (topology x runtime) grid
   is grouped by distinct :class:`Topology`, one batched program per shape
   is AOT-compiled **concurrently** on a thread pool (compile wall-clock
   overlaps instead of summing), the per-topology programs run round-robin
   across visible devices, and the per-lane results merge into one
   :class:`TopoGridResult` table keyed by the full config point.

6. **Streaming mega-sweeps** — above a lane threshold (or whenever a
   checkpoint directory is given) :func:`sweep_grid` and
   :func:`sweep_topologies` hand the grid to
   :mod:`repro.core.sweep_stream`: the lane space is chunked into
   fixed-size batches that stream through a configurable memory budget,
   chunk N+1's host-side prep and any pending topology compiles overlap
   chunk N's device execution, completed chunks checkpoint their reduced
   results (``repro.checkpoint.store.SweepCheckpoint``) so a killed sweep
   resumes from the last committed chunk, and compiled executables persist
   *across processes* via the on-disk cache (:mod:`repro.core.exec_cache`,
   ``MEMSIM_EXEC_CACHE_DIR``) — a warm re-invoke of the same topology set
   does zero recompiles. Bit-exact vs the materializing path.

Exactness contract: for any ``cfg`` with capacity ``C``, trace, horizon and
runtime limit ``q <= C``,

    simulate_fast(cfg[C], trace, n, queue_size=q)
        == simulate(cfg[queue_size=q], trace, n)

field-for-field, and likewise per lane for any RuntimeParams point of a
grid. ``tests/test_engine_equivalence.py`` enforces this for all seed
traces, both page policies, both schedulers, both FSM backends, and
randomized RuntimeParams draws.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import logging
import os
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core import exec_cache
from repro.core import tracing
from repro.core import power as power_lib
from repro.core.bank_fsm import cycles_until_actionable, wait_mask
from repro.core.params import (
    CMD_NOP,
    MemSimConfig,
    ParamSchedule,
    RuntimeParams,
    S_IDLE,
    S_SREF,
    Topology,
    as_schedule,
    rp_for_banks,
    tier_of_bank,
)
from repro.core.simulator import (
    SimResult,
    SimState,
    Trace,
    cycle_step,
    init_state,
    issue_eligibility,
    state_to_result,
)

_INF = jnp.int32(0x3FFFFFFF)
_PAD_T = 0x3FFFFFFF  # arrival time for padded trace slots: never due


# --------------------------------------------------------------------------
# event-horizon cycle-skipping (device ops named ``memsim.engine.skip``)
# --------------------------------------------------------------------------

@jax.named_scope("memsim.engine.skip")
def _next_event(topo: Topology, sched: ParamSchedule, trace: Trace,
                state: SimState, nxt: Array, horizon: Array) -> Array:
    """Number of provably-inert cycles starting at cycle ``nxt`` — the
    distance to the event horizon.

    A cycle is inert when executing it would change nothing but countdown
    timers, idle counters and per-cycle statistics. Per bank that means one
    of: a timed WAIT state (timer merely decrements), parked in SREF, idle
    with an empty scheduler queue, or holding an ISSUE-state bid whose
    command is not yet legal under the rank timing windows
    (tRRDL/tFAW/tCCDL/tWTR/tRTW; under ``timing_model="jedec"`` the S
    windows over the rank, the L windows over the bank group, and a PRE's
    precharge gate) — judged by the same
    :func:`issue_eligibility` predicate ``cycle_step`` grants from, so
    "blocked" here and "not granted" there can never disagree. Globally the
    request and response queues must be empty (a dispatch, admission or ack
    would change state) and no RESP_PEND bank may exist (the response
    arbiter would drain it).

    The returned delta is a vectorized min over every upcoming event, so it
    never swallows a cycle in which a timer expires, a blocked bid becomes
    legal, an arrival lands, a refresh window opens, or a self-refresh
    threshold is crossed — those cycles run through ``cycle_step``. All
    bounds are data (traced ``ParamSchedule``), so one compiled program
    serves every parameter point and every schedule of a given segment
    count. FR-FCFS head promotion needs no bound: it is idempotent on a
    frozen queue/open-row state, so deferring it to the next executed
    cycle is observationally identical.

    Time-varying params: every bound here is a closed form of per-cycle
    updates under the operating point governing the jumped-*from* range —
    ``params_at(nxt)``, the segment containing every cycle the skip could
    cover. A DVFS boundary invalidates those closed forms (a shrunk tRFC
    opens refresh windows earlier, re-priced tRRDL/tFAW/tCCDL/tWTR/tRTW
    windows move every blocked bid's legality), so the **next segment
    boundary is itself an event**: it joins the vectorized min, no skip
    ever crosses it, and the boundary cycle executes through
    ``cycle_step`` — whose own resolver then reads the new segment's
    params. The next ``_next_event`` evaluation after that jump resolves
    ``params_at`` in the jumped-to segment, so WAIT-expiry and blocked-bid
    legality bounds are always evaluated against the params active where
    the clock actually stands. This is what keeps the engine bit-exact vs
    a per-cycle reference that re-resolves ``params_at`` every cycle.
    """
    maybe = state.req_q.empty() & state.resp_q.empty()
    return jax.lax.cond(
        maybe,
        lambda _: _event_bound(topo, sched, trace, state, nxt, horizon),
        lambda _: jnp.int32(0), None)


def _event_bound(topo: Topology, sched: ParamSchedule, trace: Trace,
                 state: SimState, nxt: Array, horizon: Array) -> Array:
    """The full event-horizon bound of :func:`_next_event`, without its
    cheap global-queue pre-gate. Module-level so the batched bodies can
    hoist that gate to ONE scalar ``lax.cond`` over all lanes (the joint
    min is 0 whenever any lane has queued work, so the whole vectorized
    bound — eligibility gathers, per-bank mins — can be skipped for the
    batch at once; a per-lane cond would lower to a select under vmap and
    evaluate it every executed cycle)."""
    rp = sched.params_at(nxt)
    bank = state.bank
    st = bank.st
    in_wait = wait_mask(st)
    is_idle = st == S_IDLE
    is_sref = st == S_SREF

    eligible, cmds, legal_at = issue_eligibility(topo, sched,
                                                 state.timing, bank, nxt)
    blocked_bid = (cmds != CMD_NOP) & ~eligible

    # gate: nothing can happen at cycle `nxt` except timer/counter ticks
    _, bq_valid = state.bank_q.peek_valid()
    inert = in_wait | blocked_bid | ((is_idle | is_sref) & ~bq_valid)
    gate = inert.all()

    # per-bank FSM-local bound: WAIT expiry, refresh window, SREF entry
    # (the Pallas backend computes it with the packed-ABI kernel twin so
    # both backends share one definition each, validated against the
    # other)
    if topo.fsm_backend == "pallas":
        from repro.kernels.bank_fsm.ops import (
            bank_event_bound,
            default_interpret,
        )
        from repro.kernels.bank_fsm.ref import pack_state

        local = bank_event_bound(pack_state(bank), nxt, sched, True,
                                 default_interpret(), topo=topo)
    else:
        local = cycles_until_actionable(rp_for_banks(topo, rp), bank,
                                        nxt)
    # a blocked bid becomes actionable the cycle its command turns legal
    per_bank = jnp.where(blocked_bid, legal_at - nxt, local).min()

    n = trace.num_requests
    idx = jnp.minimum(state.next_arrival, n - 1)
    arrival = jnp.where(state.next_arrival < n, trace.t[idx] - nxt, _INF)
    b = jnp.minimum(jnp.minimum(per_bank, arrival), horizon - nxt)
    # the next operating-point change is an event: no closed-form bound
    # computed under this segment's params may outlive the segment
    b = jnp.minimum(b, sched.next_boundary(nxt) - nxt)
    return jnp.where(gate, jnp.maximum(b, 0), 0).astype(jnp.int32)


@jax.named_scope("memsim.engine.skip")
def _batch_event_deltas(topo: Topology, traces: Trace,
                        scheds: ParamSchedule, states: SimState,
                        nxt: Array, horizon: Array) -> Array:
    """Per-lane event bounds for the shared-clock batch bodies, with the
    global-queue pre-gate hoisted to ONE scalar cond: whenever any lane
    has queued work its own bound is 0, hence the joint min is 0 — so the
    vectorized bound only runs when every lane might skip. This restores
    the single-lane engine's saturated-phase fast path (two compares per
    lane per executed cycle) that a vmapped per-lane cond would lose to
    select-lowering."""
    maybe = jax.vmap(
        lambda st: st.req_q.empty() & st.resp_q.empty())(states)
    lanes = maybe.shape[0]
    return jax.lax.cond(
        maybe.all(),
        lambda: jax.vmap(
            lambda tr, sc, st: _event_bound(topo, sc, tr, st, nxt, horizon)
        )(traces, scheds, states),
        lambda: jnp.zeros((lanes,), jnp.int32))


@jax.named_scope("memsim.engine.skip")
def _apply_skip(topo: Topology, sched: ParamSchedule, state: SimState,
                delta: Array, nxt: Array) -> SimState:
    """Fast-forward ``delta`` inert cycles starting at ``nxt``, replicating
    exactly what the per-cycle engine would have accumulated over them
    (identity at ``delta == 0``).

    ``_next_event`` caps every delta at the next schedule boundary, so all
    skipped cycles share one segment — ``segment_at(nxt)`` — and the whole
    delta's counter contribution attributes to that operating point (see
    :func:`repro.core.power.skip_counters`)."""
    st = state.bank.st
    in_wait = wait_mask(st)
    is_idle = st == S_IDLE
    skipped = delta > 0

    timer = jnp.where(in_wait, state.bank.timer - delta, state.bank.timer)
    # per-cycle semantics: truly-idle banks count up, all others reset to 0
    idle_ctr = jnp.where(
        skipped,
        jnp.where(is_idle, state.bank.idle_ctr + delta, 0),
        state.bank.idle_ctr,
    ).astype(jnp.int32)
    bank = state.bank._replace(timer=timer.astype(jnp.int32),
                               idle_ctr=idle_ctr)
    counters = power_lib.skip_counters(
        state.counters, st, delta, topo.channels, sched.segment_at(nxt),
        tier_idx=tier_of_bank(topo) if topo.tiers > 1 else None)
    return state._replace(bank=bank, counters=counters)


# --------------------------------------------------------------------------
# single-lane runners
# --------------------------------------------------------------------------

def _run_skip_core(topo: Topology, trace: Trace, num_cycles: Array,
                   sched: ParamSchedule, queue_limit: Array,
                   resp_limit: Array) -> Tuple[SimState, Array]:
    """Event-driven while-loop engine: execute one ``cycle_step`` per
    event, then jump the clock to the next event horizon. ``num_cycles``
    and every ParamSchedule value/boundary are traced, so one compiled
    program serves every horizon, parameter point and schedule (of a given
    segment count). Returns (final state, number of cycle_step executions
    actually performed).

    The loop condition is a scalar, so XLA keeps the carried buffers
    in-place — no per-iteration state copies (this is why the batched
    variant below shares one clock across lanes instead of vmapping the
    whole while loop, whose batching rule would select-copy the full state
    every step)."""
    state0 = init_state(topo, sched, trace.num_requests, queue_limit,
                        resp_limit)
    num_cycles = jnp.asarray(num_cycles, jnp.int32)

    def cond(carry):
        _, t, _ = carry
        return t < num_cycles

    def body(carry):
        state, t, steps = carry
        if topo.fsm_backend == "fused":
            # the fused kernel returns the edge AND the event bound from
            # ONE pallas dispatch — no separate _next_event evaluation
            from repro.core.fused_step import fused_cycle_step

            state, delta = fused_cycle_step(topo, sched, trace, state, t,
                                            num_cycles)
        else:
            state = cycle_step(topo, sched, trace, state, t)
            delta = _next_event(topo, sched, trace, state, t + 1, num_cycles)
        state = _apply_skip(topo, sched, state, delta, t + 1)
        return (state, t + 1 + delta, steps + 1)

    state, _, steps = jax.lax.while_loop(
        cond, body, (state0, jnp.int32(0), jnp.int32(0)))
    return state, steps


def _run_skip_batch_core(topo: Topology, traces: Trace, num_cycles: Array,
                         scheds: ParamSchedule, queue_limits: Array,
                         resp_limits: Array) -> Tuple[SimState, Array]:
    """Batched event-horizon skipping on a SHARED clock (vmap mode).

    Lanes carry heterogeneous ParamSchedules (``scheds`` has a leading
    batch axis on every boundary/value leaf): timings, policies, refresh
    intervals, queue limits and whole DVFS schedules all differ per lane
    inside ONE device program. All lanes see the same cycle counter; after
    each jointly-executed cycle the clock jumps by the *joint* event
    horizon ``delta = min over lanes`` of each lane's inert bound (each of
    which already mins in that lane's next schedule boundary), so a jump
    happens only when every lane is provably quiescent and each lane's
    skipped cycles are inert for it — per-lane exactness is untouched.
    Sharing the clock keeps the while condition scalar: no per-lane
    live-masking of the carry (which would copy every queue/memory buffer
    each step) and in-place buffer updates survive."""
    states = jax.vmap(
        lambda tr, sc, ql, rl: init_state(topo, sc, tr.num_requests, ql, rl)
    )(traces, scheds, queue_limits, resp_limits)
    num_cycles = jnp.asarray(num_cycles, jnp.int32)

    def cond(carry):
        _, t, _ = carry
        return t < num_cycles

    def body(carry):
        states, t, steps = carry
        if topo.fsm_backend == "fused":
            from repro.core.fused_step import fused_cycle_step_batch

            # lane-batched kernel: ONE dispatch for the whole batch (vmap
            # over a pallas_call would serialize the kernel per lane)
            states, deltas = fused_cycle_step_batch(topo, scheds, traces,
                                                    states, t, num_cycles)
        else:
            states = jax.vmap(
                lambda tr, sc, st: cycle_step(topo, sc, tr, st, t)
            )(traces, scheds, states)
            deltas = _batch_event_deltas(topo, traces, scheds, states,
                                         t + 1, num_cycles)
        delta = deltas.min()
        states = jax.vmap(
            lambda sc, st: _apply_skip(topo, sc, st, delta, t + 1)
        )(scheds, states)
        return (states, t + 1 + delta, steps + 1)

    states, _, steps = jax.lax.while_loop(
        cond, body, (states, jnp.int32(0), jnp.int32(0)))
    return states, steps


def _run_window_core(topo: Topology, trace: Trace, t_start: Array,
                     t_end: Array, sched: ParamSchedule,
                     state: SimState) -> Tuple[SimState, Array]:
    """Re-entrant windowed variant of :func:`_run_skip_core`: advance a
    *carried* ``SimState`` from ``t_start`` to exactly ``t_end``, with the
    event horizon additionally capped at the window boundary.

    This is the engine half of :class:`repro.core.session.SimSession`. The
    state is not initialized here — it arrives as an argument (queues,
    per-tier power counters and schedule segment attribution all ride
    inside the pytree, and the runtime queue limits live in ``Fifo.limit``,
    so no extra arguments are needed) and leaves the same way, staying
    on-device between calls. ``t_start`` / ``t_end`` are traced scalars and
    the trace buffer has a fixed (session-capacity) shape, so ONE compiled
    program serves every window of every session of a given
    ``(topology, capacity, segment count)``.

    Bit-exactness vs the monolithic run: a window boundary only *caps* the
    skip delta, so the windowed engine executes ``cycle_step`` on boundary
    cycles the monolithic engine would have skipped. Executing a provably
    inert cycle is bit-identical to skipping it (``_apply_skip`` is the
    closed form of the per-cycle updates — the same property that makes
    the shared-clock joint-min skipping of :func:`_run_skip_batch_core`
    exact per lane), so the final state after the last window equals the
    monolithic final state field-for-field; only the executed-step count
    (metadata) differs."""
    t_end = jnp.asarray(t_end, jnp.int32)

    def cond(carry):
        _, t, _ = carry
        return t < t_end

    def body(carry):
        state, t, steps = carry
        if topo.fsm_backend == "fused":
            from repro.core.fused_step import fused_cycle_step

            state, delta = fused_cycle_step(topo, sched, trace, state, t,
                                            t_end)
        else:
            state = cycle_step(topo, sched, trace, state, t)
            delta = _next_event(topo, sched, trace, state, t + 1, t_end)
        state = _apply_skip(topo, sched, state, delta, t + 1)
        return (state, t + 1 + delta, steps + 1)

    state, _, steps = jax.lax.while_loop(
        cond, body, (state, jnp.asarray(t_start, jnp.int32), jnp.int32(0)))
    return state, steps


@functools.partial(jax.jit, static_argnums=(0,))
def _run_window_jit(topo, trace, t_start, t_end, sched, state):
    return _run_window_core(topo, trace, t_start, t_end, sched, state)


def _run_window_batch_core(topo: Topology, traces: Trace, t_start: Array,
                           t_end: Array, scheds: ParamSchedule,
                           states: SimState) -> Tuple[SimState, Array]:
    """Windowed variant of :func:`_run_skip_batch_core`: advance L carried
    lane states from ``t_start`` to exactly ``t_end`` on a SHARED clock.

    This is the engine half of
    :class:`repro.core.session_batch.SessionBatch` — L independent
    sessions (each with its own arrival buffer, ParamSchedule, queue
    limits and cumulative counters stacked on a leading lane axis) advance
    through the same window as lanes of ONE program. The skip delta is the
    joint min over each lane's inert bound, additionally capped at the
    window boundary; both caps only ever *shrink* the jump, and executing
    a provably inert cycle is bit-identical to skipping it, so every lane's
    state after any window partition equals its single-session
    (:func:`_run_window_core`) state field-for-field. The while condition
    stays scalar, so XLA keeps the stacked carried buffers in-place."""
    t_end = jnp.asarray(t_end, jnp.int32)

    def cond(carry):
        _, t, _ = carry
        return t < t_end

    def body(carry):
        states, t, steps = carry
        if topo.fsm_backend == "fused":
            from repro.core.fused_step import fused_cycle_step_batch

            states, deltas = fused_cycle_step_batch(topo, scheds, traces,
                                                    states, t, t_end)
        else:
            states = jax.vmap(
                lambda tr, sc, st: cycle_step(topo, sc, tr, st, t)
            )(traces, scheds, states)
            deltas = _batch_event_deltas(topo, traces, scheds, states,
                                         t + 1, t_end)
        delta = deltas.min()
        states = jax.vmap(
            lambda sc, st: _apply_skip(topo, sc, st, delta, t + 1)
        )(scheds, states)
        return (states, t + 1 + delta, steps + 1)

    states, _, steps = jax.lax.while_loop(
        cond, body, (states, jnp.asarray(t_start, jnp.int32), jnp.int32(0)))
    return states, steps


@functools.partial(jax.jit, static_argnums=(0,))
def _run_window_batch_jit(topo, traces, t_start, t_end, scheds, states):
    return _run_window_batch_core(topo, traces, t_start, t_end, scheds,
                                  states)


def _run_window_lanes_core(topo: Topology, traces: Trace, t_start: Array,
                           t_end: Array, scheds: ParamSchedule,
                           states: SimState) -> Tuple[SimState, Array]:
    """Windowed lane batch in "lanes" mode: ``lax.map`` the single-lane
    window engine over the stacked lanes inside ONE device program.

    The counterpart of :func:`_run_window_batch_core` with the same
    mode split as :func:`simulate_batch`: the shared-clock vmap body pays
    select-lowered conds and a joint skip held back by the busiest lane —
    a good trade on accelerators, where the lane axis vectorizes into
    hardware lanes, and a bad one on CPU. Here each lane runs the exact
    single-lane op stream (scalar while condition, in-place carried
    buffers, *independent* cycle skipping) sequentially on-device, so the
    whole batch still costs one dispatch, one compile and one stacked
    report fetch per window, while per-lane step counts — not just final
    states — match :func:`_run_window_core` exactly. Unlike vmapping the
    while loop itself, the scan over lanes needs no live-masking of the
    carry: each iteration's loop is already scalar.

    Returns (stacked states, per-lane executed-step counts ``[L]``)."""

    def one(args):
        tr, sc, st = args
        return _run_window_core(topo, tr, t_start, t_end, sc, st)

    return jax.lax.map(one, (traces, scheds, states))


@functools.partial(jax.jit, static_argnums=(0,))
def _run_window_lanes_jit(topo, traces, t_start, t_end, scheds, states):
    return _run_window_lanes_core(topo, traces, t_start, t_end, scheds,
                                  states)


def _run_scan_core(topo: Topology, trace: Trace, num_cycles: int,
                   sched: ParamSchedule, queue_limit: Array,
                   resp_limit: Array) -> Tuple[SimState, Array]:
    """Plain per-cycle scan, but with runtime limits/params (compile-once)."""
    state0 = init_state(topo, sched, trace.num_requests, queue_limit,
                        resp_limit)

    def step(carry, cycle):
        return cycle_step(topo, sched, trace, carry, cycle), None

    final, _ = jax.lax.scan(step, state0,
                            jnp.arange(num_cycles, dtype=jnp.int32))
    return final, jnp.int32(num_cycles)


@functools.partial(jax.jit, static_argnums=(0,))
def _run_skip_jit(topo, trace, num_cycles, sched, queue_limit, resp_limit):
    return _run_skip_core(topo, trace, num_cycles, sched, queue_limit,
                          resp_limit)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _run_scan_jit(topo, trace, num_cycles, sched, queue_limit, resp_limit):
    return _run_scan_core(topo, trace, num_cycles, sched, queue_limit,
                          resp_limit)


@functools.partial(jax.jit, static_argnums=(0,))
def _run_skip_batch_jit(topo, traces, num_cycles, scheds, queue_limits,
                        resp_limits):
    return _run_skip_batch_core(topo, traces, num_cycles, scheds,
                                queue_limits, resp_limits)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _run_scan_batch_jit(topo, traces, num_cycles, scheds, queue_limits,
                        resp_limits):
    fn = lambda tr, sc, ql, rl: _run_scan_core(topo, tr, num_cycles, sc,
                                               ql, rl)
    return jax.vmap(fn)(traces, scheds, queue_limits, resp_limits)


# --------------------------------------------------------------------------
# trace batching
# --------------------------------------------------------------------------

def _pad_trace(tr: Trace, n_max: int) -> Trace:
    """Pad one trace to ``n_max`` requests with inert slots: arrival time
    ``_PAD_T`` is never due inside any horizon, so padded requests are
    never admitted and their records stay -1 (padding with 0 would alias a
    real cycle-0 arrival and corrupt every shorter lane of the batch).

    Rejects traces whose real arrivals reach the sentinel: such a request
    would be indistinguishable from padding (``t`` is sorted, so checking
    the last entry suffices)."""
    n = int(tr.num_requests)
    if n and int(np.asarray(tr.t)[n - 1]) >= _PAD_T:
        raise ValueError(
            f"trace arrival t={int(np.asarray(tr.t)[n - 1])} reaches the "
            f"padding sentinel {_PAD_T}; arrivals must stay below it")
    if n == n_max:
        return tr

    def pad(x, fill):
        out = np.full((n_max,), fill, np.int32)
        out[:n] = np.asarray(x, np.int32)
        return jnp.asarray(out)

    return Trace(t=pad(tr.t, _PAD_T), addr=pad(tr.addr, 0),
                 is_write=pad(tr.is_write, 0), wdata=pad(tr.wdata, 0))


def _sentinel_trace(n_max: int) -> Trace:
    """An all-padding lane: every arrival sits at the ``_PAD_T`` sentinel,
    so no request is ever due and the lane idles bit-inertly for the whole
    horizon. Used to pad a batch up to a device multiple so awkward grid
    sizes still shard (the padding lanes are dropped on the way out)."""
    zeros = jnp.zeros((n_max,), jnp.int32)
    return Trace(t=jnp.full((n_max,), _PAD_T, jnp.int32), addr=zeros,
                 is_write=zeros, wdata=zeros)


def stack_traces(traces: Sequence[Trace],
                 pad_lanes: int = 0) -> Tuple[Trace, List[int]]:
    """Pad traces to a common length (see :func:`_pad_trace`) and stack on
    a leading batch axis, appending ``pad_lanes`` all-sentinel lanes (see
    :func:`_sentinel_trace`). Returns the stacked trace and the real
    per-lane request counts (padding lanes excluded)."""
    ns = [int(tr.num_requests) for tr in traces]
    n_max = max(ns)
    padded = [_pad_trace(tr, n_max) for tr in traces]
    padded += [_sentinel_trace(n_max)] * pad_lanes
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *padded)
    return stacked, ns


def _lane_executable(topo: Topology, n_max: int, num_segments: int,
                     num_cycles: int, cycle_skip: bool, device
                     ) -> Tuple[object, float]:
    """AOT-compile the single-lane runner for one device (cached).

    Lowering uses ShapeDtypeStructs committed to ``device``, so each device
    gets its own executable once and every lane dispatched to that device
    reuses it — including across horizons, RuntimeParams points and whole
    ParamSchedules (``num_cycles`` and every boundary/value of the
    schedule pytree are runtime values for the skipping engine; only the
    segment count ``num_segments`` is a shape). Returns (executable,
    compile seconds — 0.0 on cache hit)."""
    from jax.sharding import SingleDeviceSharding

    sharding = SingleDeviceSharding(device)
    key = ("lane", topo, n_max, num_segments,
           None if cycle_skip else num_cycles, cycle_skip, device.id)
    with _aot_lock:
        cached = _aot_cache.get(key)
    if cached is not None:
        return cached, 0.0
    disk_key = (exec_cache.make_key("lane_executable", key, ())
                if exec_cache.cache_dir() is not None else None)
    if disk_key is not None:
        cached = exec_cache.load(disk_key)
        if cached is not None:
            with _aot_lock:
                _aot_cache[key] = cached
            return cached, 0.0

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    tr_s = Trace(t=sds((n_max,)), addr=sds((n_max,)),
                 is_write=sds((n_max,)), wdata=sds((n_max,)))
    scal = sds(())
    seg = sds((num_segments,))
    # tiered topologies carry [S, T] value leaves (one params row per tier)
    val = seg if topo.tiers == 1 else sds((num_segments, topo.tiers))
    sched_s = ParamSchedule(
        boundaries=seg,
        values=RuntimeParams(*([val] * len(RuntimeParams._fields))))
    with tracing.span("memsim.engine.compile") as sp:
        tracing.count("compiles")
        if cycle_skip:
            compiled = _run_skip_jit.lower(topo, tr_s, scal, sched_s, scal,
                                           scal).compile()
        else:
            compiled = _run_scan_jit.lower(topo, tr_s, num_cycles, sched_s,
                                           scal, scal).compile()
    compile_s = sp.seconds
    with _aot_lock:
        _aot_cache[key] = compiled
    if disk_key is not None:
        exec_cache.store(disk_key, compiled)
    return compiled, compile_s


def _run_lanes(topo: Topology, trace_list: List[Trace], num_cycles: int,
               scheds: List[ParamSchedule], qs: List[int], rs: List[int],
               cycle_skip: bool, shard: bool,
               timings: Optional[dict]) -> Tuple[List[SimState], List[int]]:
    """Lanes mode: each lane runs the single-lane engine; lanes round-robin
    over devices and execute concurrently from worker threads (XLA releases
    the GIL during execution). Unlike the vmap mode this keeps per-lane
    *independent* cycle-skipping — a drained lane fast-forwards even while
    another is still saturated — and each lane's op stream is identical to
    ``simulate_fast``. One compiled executable per device serves every
    lane, horizon, RuntimeParams point and ParamSchedule (of the common
    padded segment count). ``timings`` (if given) additionally gains
    ``per_lane``: one ``{lane, device, steps, run_s}`` record per lane —
    the per-device throughput attribution the multi-device scale-out
    benchmarks report."""
    from concurrent.futures import ThreadPoolExecutor

    n_max = max(int(tr.num_requests) for tr in trace_list)
    padded = [_pad_trace(tr, n_max) for tr in trace_list]
    devices = jax.devices() if shard else jax.devices()[:1]
    d_count = min(len(devices), len(padded))
    num_segments = scheds[0].num_segments

    compile_s = 0.0
    compiles = 0
    compiled = []
    for di in range(d_count):
        exe, c_s = _lane_executable(topo, n_max, num_segments, num_cycles,
                                    cycle_skip, devices[di])
        compiled.append(exe)
        compile_s += c_s
        compiles += int(c_s > 0.0)

    def work(i: int):
        dev = devices[i % d_count]
        t_l0 = time.perf_counter()
        tr = jax.device_put(padded[i], dev)
        sc = jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(x, jnp.int32), dev),
            scheds[i])
        ql = jax.device_put(jnp.int32(qs[i]), dev)
        rl = jax.device_put(jnp.int32(rs[i]), dev)
        if cycle_skip:
            nc = jax.device_put(jnp.int32(num_cycles), dev)
            final, steps = compiled[i % d_count](tr, nc, sc, ql, rl)
        else:
            final, steps = compiled[i % d_count](tr, sc, ql, rl)
        jax.block_until_ready(final)
        return final, int(steps), {"lane": i, "device": dev.id,
                                   "steps": int(steps),
                                   "run_s": time.perf_counter() - t_l0}

    t0 = time.perf_counter()
    if d_count > 1 and len(padded) > 1:
        with ThreadPoolExecutor(max_workers=d_count) as pool:
            outs = list(pool.map(work, range(len(padded))))
    else:
        outs = [work(i) for i in range(len(padded))]
    run_s = time.perf_counter() - t0

    if timings is not None:
        timings["compile_s"] = timings.get("compile_s", 0.0) + compile_s
        timings["run_s"] = timings.get("run_s", 0.0) + run_s
        timings["compiles"] = timings.get("compiles", 0) + compiles
        timings.setdefault("per_lane", []).extend(o[2] for o in outs)
    return [o[0] for o in outs], [o[1] for o in outs]


def _shard_pad(batch: int) -> int:
    """Sentinel lanes needed to round ``batch`` up to a device multiple.

    GSPMD can only split an evenly-divisible batch axis, so without padding
    any ``batch % len(devices) != 0`` sweep would silently fall back to ONE
    device; callers append this many :func:`_sentinel_trace` lanes before
    stacking and drop them on the way out."""
    devices = jax.devices()
    if len(devices) <= 1:
        return 0
    return (-batch) % len(devices)


def _maybe_shard(tree, batch: int):
    """Shard the leading batch axis across visible devices.

    Returns ``(tree, mesh)``: the mesh (one ``"data"`` axis over every
    device) the batch now lives on, or None on a single device, which
    leaves it unsharded. Callers pad ``batch`` to a device multiple via
    :func:`_shard_pad`. There is no fallback: a batch that cannot be
    placed across the devices raises instead of silently running on one."""
    devices = jax.devices()
    if len(devices) <= 1:
        return tree, None
    if batch % len(devices) != 0:
        raise ValueError(f"batch of {batch} lanes does not divide over "
                         f"{len(devices)} devices (pad with _shard_pad)")
    from jax.sharding import Mesh

    from repro.distributed import shard as shard_lib

    mesh = Mesh(np.asarray(devices), ("data",))
    sharding = shard_lib.named(mesh, "data")
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), tree), mesh


# A sharded batch is split over the devices explicitly: XLA cannot
# partition a Pallas kernel, so each device runs the batched engine on its
# own block of lanes. In the event-horizon engine each device then keeps
# its own shared clock, which leaves every lane bit-exact (executing an
# inert cycle is bit-identical to skipping it) and needs no collective.

@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_skip_batch_split_jit(mesh, topo, traces, num_cycles, scheds,
                              queue_limits, resp_limits):
    from jax.sharding import PartitionSpec as P

    def per_device(tr, nc, sc, ql, rl):
        states, steps = _run_skip_batch_core(topo, tr, nc, sc, ql, rl)
        return states, steps[None]

    lanes = P("data")
    return jax.shard_map(per_device, mesh=mesh,
                         in_specs=(lanes, P(), lanes, lanes, lanes),
                         out_specs=lanes, check_vma=False)(
        traces, num_cycles, scheds, queue_limits, resp_limits)


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _run_scan_batch_split_jit(mesh, topo, traces, num_cycles, scheds,
                              queue_limits, resp_limits):
    from jax.sharding import PartitionSpec as P

    lanes = P("data")
    return jax.shard_map(
        lambda tr, sc, ql, rl: _run_scan_batch_jit(topo, tr, num_cycles, sc,
                                                   ql, rl),
        mesh=mesh, in_specs=lanes, out_specs=lanes, check_vma=False)(
        traces, scheds, queue_limits, resp_limits)


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

_logger = logging.getLogger(__name__)


class _AotLruCache:
    """Bounded LRU of AOT-compiled executables, keyed like the old dict.

    Compiled XLA executables pin host and device memory for as long as they
    are referenced; a long-lived process sweeping many topologies, horizons
    or segment counts would otherwise grow its executable set without
    bound. Capacity comes from ``MEMSIM_AOT_CACHE_SIZE`` (default 64,
    clamped to >= 1), re-read on every insert so a live process can be
    resized; the least-recently-used entry is dropped on overflow and each
    eviction is logged AND counted — ``stats()`` exposes lifetime
    hits/misses/evictions so cache thrash is observable in the BENCH JSON
    ``engine.*`` sections, not just the log. Not internally locked —
    every call site already holds ``_aot_lock``."""

    _DEFAULT = 64

    def __init__(self) -> None:
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def maxsize(self) -> int:
        raw = os.environ.get("MEMSIM_AOT_CACHE_SIZE", "").strip()
        try:
            size = int(raw) if raw else self._DEFAULT
        except ValueError:
            size = self._DEFAULT
        return max(1, size)

    def get(self, key, default=None):
        if key in self._entries:
            self.hits += 1
            self._entries.move_to_end(key)
            return self._entries[key]
        self.misses += 1
        return default

    def __getitem__(self, key):
        value = self._entries[key]
        self._entries.move_to_end(key)
        return value

    def __contains__(self, key) -> bool:
        # a presence probe precedes every reuse, so it refreshes recency too
        if key in self._entries:
            self.hits += 1
            self._entries.move_to_end(key)
            return True
        self.misses += 1
        return False

    def __setitem__(self, key, value) -> None:
        self._entries[key] = value
        self._entries.move_to_end(key)
        limit = self.maxsize()
        while len(self._entries) > limit:
            old_key, _ = self._entries.popitem(last=False)
            self.evictions += 1
            _logger.info(
                "AOT cache evicted %r (%d executables > MEMSIM_AOT_CACHE_SIZE"
                "=%d); evicted programs recompile on next use", old_key,
                len(self._entries) + 1, limit)

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        # lifetime hit/miss/eviction counters survive a clear() on purpose:
        # benches snapshot-and-diff them around each leg, and tests clear
        # the entries to re-count compiles without losing the trajectory
        self._entries.clear()

    def stats(self) -> Dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "entries": len(self._entries),
                "maxsize": self.maxsize()}


_aot_cache = _AotLruCache()
#: guards _aot_cache: sweep_topologies compiles distinct-topology programs
#: from worker threads, and _run_lanes/_timed may race with them.
_aot_lock = threading.Lock()


def _rp_i32(rp: RuntimeParams, timing_model: str = "table1") -> RuntimeParams:
    """Coerce every RuntimeParams leaf to a committed int32 scalar so AOT
    cache keys and lowered signatures are stable regardless of whether the
    caller passed Python ints or device arrays. The cross-field constraints
    the seed config path enforces (``MemSimConfig.validate``) are checked
    here through the same shared predicate, so a bad ``params=`` override
    fails with the same clear error as config construction — checked per
    leaf, skipping only traced leaves, which cannot be inspected
    host-side (the caller inside the trace owns those)."""
    from repro.core.params import runtime_constraint_violations

    vals = {}
    for f in RuntimeParams._fields:
        try:
            vals[f] = int(getattr(rp, f))
        except (TypeError, jax.errors.TracerIntegerConversionError,
                jax.errors.ConcretizationTypeError):
            vals[f] = None  # traced leaf
    bad = runtime_constraint_violations(vals, timing_model)
    if bad:
        raise ValueError("; ".join(bad))
    return RuntimeParams(*[_i32(v) for v in rp])


def _i32(x):
    """``x`` as an int32 array: a numpy one for a host value
    (``params.on_host``), so preparing a lane's parameters dispatches
    nothing to the device; else a jax one."""
    from repro.core.params import on_host

    return np.asarray(x, np.int32) if on_host(x) else jnp.asarray(x, jnp.int32)


def _sched_i32(params, timing_model: str = "table1") -> ParamSchedule:
    """Canonicalize a ``params=`` override to a validated int32
    :class:`ParamSchedule`: a bare :class:`RuntimeParams` lifts to the S=1
    degenerate schedule through :func:`_rp_i32` (same committed-leaf and
    validation contract as before); a schedule validates every segment
    through the same shared predicate — plus sorted/unique boundary checks
    — so a bad segment fails with the same ValueError text as config
    construction (traced leaves are skipped; the caller inside the trace
    owns those)."""
    if isinstance(params, RuntimeParams):
        return ParamSchedule.constant(_rp_i32(params, timing_model))
    sched = as_schedule(params)  # raises TypeError on anything else
    sched.validate(timing_model)
    return ParamSchedule(boundaries=_i32(sched.boundaries),
                         values=RuntimeParams(*[_i32(v)
                                                for v in sched.values]))


def _jit_name(jitted) -> str:
    """Stable cross-process identifier of a jitted runner (``id()`` is
    process-local, so the persistent cache cannot key on it)."""
    fn = getattr(jitted, "__wrapped__", None)
    return getattr(fn, "__qualname__", None) or repr(jitted)


_dtype_str: Dict = {}


def _dtype_name(dt) -> str:
    """``str(dtype)`` memoized on the dtype object. The AOT probe runs
    once per *window* on the session paths — ~70 pytree leaves each — and
    numpy's dtype ``__str__`` costs microseconds per call, which profiled
    as the third-largest host cost of a windowed advance."""
    s = _dtype_str.get(dt)
    if s is None:
        s = _dtype_str[dt] = str(dt)
    return s


def _aot_lower(jitted, all_args: tuple, dyn_args: tuple, static_key: tuple):
    """Phase one of the split AOT pipeline: trace + lower (holds the GIL,
    so callers run it sequentially). Returns ``(key, lowered, lower_s,
    cached)``; on a cache hit ``lowered`` is None and ``cached`` carries
    the executable itself — a strong reference, because the bounded LRU
    may evict the entry between this probe and the caller's use.

    Misses in the in-memory LRU fall through to the persistent on-disk
    executable cache (:mod:`repro.core.exec_cache`, enabled via
    ``MEMSIM_EXEC_CACHE_DIR``): a previously compiled program — from an
    earlier *process* — deserializes in milliseconds, is published to the
    in-memory cache, and counts as a cache hit, not a fresh compile
    (``timings["compiles"]`` stays 0; the load wall is accounted in
    ``exec_cache.stats()["load_s"]``)."""
    shapes = tuple((tuple(x.shape), _dtype_name(x.dtype))
                   for x in jax.tree_util.tree_leaves(dyn_args))
    mem_key = (id(jitted), static_key, shapes)
    disk_key = (exec_cache.make_key(_jit_name(jitted), static_key, shapes)
                if exec_cache.cache_dir() is not None else None)
    key = (mem_key, disk_key)
    with _aot_lock:
        cached = _aot_cache.get(mem_key)
    if cached is not None:
        return key, None, 0.0, cached
    if disk_key is not None:
        cached = exec_cache.load(disk_key)
        if cached is not None:
            with _aot_lock:
                _aot_cache[mem_key] = cached
            return key, None, 0.0, cached
    t0 = time.perf_counter()
    lowered = jitted.lower(*all_args)
    return key, lowered, time.perf_counter() - t0, None


def _aot_finish(key: tuple, lowered) -> Tuple[object, float]:
    """Phase two: XLA compilation (releases the GIL — safe and profitable
    to run from worker threads), then publish to the in-memory cache and,
    when enabled, the persistent on-disk executable cache."""
    mem_key, disk_key = key
    with tracing.span("memsim.engine.compile") as sp:
        tracing.count("compiles")
        compiled = lowered.compile()
    compile_s = sp.seconds
    with _aot_lock:
        _aot_cache[mem_key] = compiled
    if disk_key is not None:
        exec_cache.store(disk_key, compiled)
    return compiled, compile_s


def aot_cache_stats() -> Dict:
    """Lifetime observability of both executable-cache layers: the
    in-process bounded LRU (hits / misses / evictions / entries) and the
    persistent on-disk cache (hits / misses / writes / load wall). The
    benches snapshot-and-diff this around each leg and export the deltas
    into the BENCH JSON ``engine.*`` sections, so cache-thrash regressions
    show up in the perf trajectory, not just the log."""
    with _aot_lock:
        mem = _aot_cache.stats()
    return {"memory": mem, "disk": exec_cache.stats()}


def compiled_texts() -> List[str]:
    """The optimized HLO text (``Compiled.as_text()``) of every program in
    the in-memory AOT cache: what a profiler trace's device ops are named
    after, each with the ``op_name`` metadata that carries its named
    scope (``memsim.glue.pre`` ...)."""
    with _aot_lock:
        programs = list(_aot_cache._entries.values())
    return [p.as_text() for p in programs]


def _aot_compile(jitted, all_args: tuple, dyn_args: tuple,
                 static_key: tuple) -> Tuple[object, float, int]:
    """Lower + compile a jitted runner ahead of time, cached.

    ``all_args`` is the full positional argument list (statics interleaved,
    as the jit signature expects; dynamic slots may be ShapeDtypeStructs);
    ``dyn_args`` the dynamic subset the compiled executable takes. The
    cache key is (fn, statics, dynamic-arg shapes), so re-requesting the
    same program returns instantly with ``compile_s == 0``. Thread-safe:
    concurrent requests for *distinct* keys compile in parallel (XLA
    releases the GIL during compilation — this is what lets
    :func:`sweep_topologies` overlap one compile per topology; it splits
    the two phases via :func:`_aot_lower` / :func:`_aot_finish`, which
    this composes). Returns ``(compiled, compile_seconds, fresh)``."""
    key, lowered, lower_s, cached = _aot_lower(jitted, all_args, dyn_args,
                                               static_key)
    if lowered is None:
        return cached, 0.0, 0
    compiled, compile_s = _aot_finish(key, lowered)
    return compiled, lower_s + compile_s, 1


def _timed(jitted, all_args: tuple, dyn_args: tuple, static_key: tuple,
           timings: Optional[dict]):
    """Invoke a jitted runner, optionally splitting compile vs run wall time
    via AOT lowering (see :func:`_aot_compile` for the cache contract).
    ``timings`` (if given) gains ``compile_s`` / ``run_s`` / ``compiles``,
    read off the ``memsim.engine.compile`` and ``memsim.engine.run`` spans
    (:mod:`repro.core.tracing`); the run span covers the dispatch and, with
    ``timings``, the wait for the result."""
    if timings is None:
        with tracing.span("memsim.engine.run"):
            return jitted(*all_args)
    compiled, compile_s, fresh = _aot_compile(jitted, all_args, dyn_args,
                                              static_key)
    with tracing.span("memsim.engine.run") as sp:
        out = compiled(*dyn_args)
        jax.block_until_ready(out)
    timings["compile_s"] = timings.get("compile_s", 0.0) + compile_s
    timings["run_s"] = timings.get("run_s", 0.0) + sp.seconds
    timings["compiles"] = timings.get("compiles", 0) + fresh
    return out


def simulate_fast(cfg: MemSimConfig, trace: Trace, num_cycles: int = 100_000,
                  *, queue_size: Optional[int] = None,
                  resp_queue_size: Optional[int] = None,
                  cycle_skip: bool = True,
                  params=None,
                  timings: Optional[dict] = None) -> SimResult:
    """Single-trace run on the fast engine; bit-exact vs :func:`simulate`.

    ``cfg.queue_size`` is the static *capacity*; ``queue_size`` (default:
    capacity) is the runtime depth actually enforced. ``params`` (default:
    ``cfg.runtime()``) carries every timing value and policy flag as traced
    data — a constant :class:`RuntimeParams` point or a time-varying
    :class:`ParamSchedule` (DVFS/thermal operating points; the event
    horizon then also mins in the next segment boundary, staying bit-exact
    vs the per-cycle reference that re-resolves ``params_at`` every
    cycle). Successive calls with different depths, horizons, parameter
    points or schedules (of one segment count) all reuse one compiled
    program per ``cfg.topology()``. With ``cycle_skip`` the engine
    fast-forwards through provably inert cycles (exact — see module
    docstring); pass ``cycle_skip=False`` for the plain compile-once scan.
    ``timings`` (optional dict) receives ``compile_s``, ``run_s``,
    ``compiles`` and ``steps`` (cycle_step executions; < num_cycles when
    skipping helped). ``compile_s`` and ``run_s`` are filled through
    :func:`repro.core.tracing.span`: the call records the spans
    ``memsim.simulate_fast`` > ``memsim.engine.prepare`` /
    ``memsim.engine.compile`` / ``memsim.engine.run`` /
    ``memsim.engine.to_result`` (``tracing.recent``).
    """
    with tracing.span("memsim.simulate_fast"):
        with tracing.span("memsim.engine.prepare"):
            cfg.validate()
            topo = cfg.topology()
            sched = _sched_i32(cfg.runtime() if params is None else params,
                               cfg.timing_model)
            ql = cfg.queue_size if queue_size is None else queue_size
            rl = (cfg.resp_queue_size if resp_queue_size is None
                  else resp_queue_size)
            if not (1 <= ql <= cfg.queue_size):
                raise ValueError(
                    f"queue_size={ql} not in [1, {cfg.queue_size}]")
            if not (1 <= rl <= cfg.resp_queue_size):
                raise ValueError(
                    f"resp_queue_size={rl} not in [1, {cfg.resp_queue_size}]")
            ql = jnp.int32(ql)
            rl = jnp.int32(rl)
            nc = jnp.int32(num_cycles) if cycle_skip else None
        if cycle_skip:
            final, steps = _timed(_run_skip_jit,
                                  (topo, trace, nc, sched, ql, rl),
                                  (trace, nc, sched, ql, rl), (topo,),
                                  timings)
        else:
            final, steps = _timed(_run_scan_jit,
                                  (topo, trace, num_cycles, sched, ql, rl),
                                  (trace, sched, ql, rl), (topo, num_cycles),
                                  timings)
        if timings is not None:
            timings["steps"] = int(steps)
        with tracing.span("memsim.engine.to_result"):
            res = state_to_result(cfg, trace, final, num_cycles)
            label = cfg if params is None else sched.apply_to(cfg)
            res.cfg = dataclasses.replace(label, queue_size=int(ql),
                                          resp_queue_size=int(rl))
            count_grants([res])
        return res


def simulate_batch(cfg: MemSimConfig,
                   traces: Union[Trace, Sequence[Trace]],
                   num_cycles: int = 100_000,
                   *, queue_sizes: Optional[Sequence[int]] = None,
                   resp_queue_sizes: Optional[Sequence[int]] = None,
                   params=None,
                   lane_cfgs: Optional[Sequence[MemSimConfig]] = None,
                   cycle_skip: bool = True,
                   shard: bool = True,
                   batch_mode: str = "auto",
                   timings: Optional[dict] = None) -> List[SimResult]:
    """Run a batch of (trace, runtime-config) lanes through one compile.

    ``traces`` may be a list of traces (a multi-trace workload) or a single
    trace that is broadcast across the lanes implied by ``queue_sizes`` /
    ``params`` (a parameter sweep). ``params`` gives each lane its own
    :class:`RuntimeParams` point or time-varying :class:`ParamSchedule` —
    timings, page policy, scheduler, refresh interval, whole DVFS/thermal
    schedules — all traced data inside the one compiled program (default:
    every lane runs ``cfg.runtime()``; mixed constant/schedule lanes are
    padded to a common segment count). Lanes are padded to a common
    request count; each lane is bit-exact vs an individual
    :func:`simulate` run at its queue depth and parameter point/schedule.
    ``lane_cfgs`` (optional, one per lane) labels each returned
    ``SimResult.cfg``; by default the label is ``cfg`` with the lane's
    queue depths substituted.

    ``batch_mode``:
      * ``"vmap"``  — stack lanes on a leading axis and ``vmap`` the cycle
        step: the whole batch is ONE device program on a shared clock
        (joint cycle-skipping); the batch axis is sharded across devices
        when more than one is visible and ``shard``. Best on accelerators,
        where the batch axis vectorizes into the hardware lanes.
      * ``"lanes"`` — one compiled single-lane executable per device,
        reused by every lane; lanes round-robin over devices and run
        concurrently from worker threads, each with independent
        cycle-skipping. Best on CPU, where vmap cannot amortize across the
        batch and joint skipping is held back by the busiest lane.
      * ``"auto"``  — ``"lanes"`` on the CPU backend, ``"vmap"`` otherwise.

    The call records the span ``memsim.simulate_batch`` with
    ``memsim.engine.prepare``, ``memsim.engine.compile`` (fresh compiles
    only), ``memsim.engine.run`` and ``memsim.engine.to_result`` under it
    (``tracing.recent``).
    """
    with tracing.span("memsim.simulate_batch"):
        with tracing.span("memsim.engine.prepare"):
            (topo, batch_mode, trace_list, qs, rs, scheds, ns,
             batched) = _prepare_batch(cfg, traces, queue_sizes,
                                       resp_queue_sizes, params, lane_cfgs,
                                       shard, batch_mode, timings)
        if not trace_list:
            return []
        if batch_mode == "lanes":
            with tracing.span("memsim.engine.run"):
                finals, lane_steps = _run_lanes(topo, trace_list, num_cycles,
                                                scheds, qs, rs, cycle_skip,
                                                shard, timings)
            if timings is not None:
                timings["steps"] = max(lane_steps)
        else:
            stacked, sched_stack, ql, rl, mesh = batched
            if cycle_skip:
                dyn = (stacked, jnp.int32(num_cycles), sched_stack, ql, rl)
                if mesh is None:
                    finals, steps = _timed(_run_skip_batch_jit,
                                           (topo,) + dyn, dyn, (topo,),
                                           timings)
                else:
                    finals, steps = _timed(_run_skip_batch_split_jit,
                                           (mesh, topo) + dyn, dyn,
                                           (mesh, topo), timings)
            else:
                dyn = (stacked, sched_stack, ql, rl)
                statics = (topo, num_cycles)
                if mesh is None:
                    finals, steps = _timed(_run_scan_batch_jit,
                                           (topo, stacked, num_cycles)
                                           + dyn[1:], dyn, statics, timings)
                else:
                    finals, steps = _timed(_run_scan_batch_split_jit,
                                           (mesh, topo, stacked, num_cycles)
                                           + dyn[1:], dyn, (mesh,) + statics,
                                           timings)
            if timings is not None:
                timings["steps"] = int(np.max(np.asarray(steps)))
                # the devices the lane results actually live on
                timings["devices_used"] = sorted(
                    d.id for d in finals.t_complete.sharding.device_set)
        with tracing.span("memsim.engine.to_result"):
            results = _batch_results(cfg, num_cycles, trace_list, scheds,
                                     qs, rs, ns, lane_cfgs, finals,
                                     batch_mode == "lanes")
            count_grants(results)
            return results


def _prepare_batch(cfg, traces, queue_sizes, resp_queue_sizes, params,
                   lane_cfgs, shard, batch_mode, timings):
    """Host preparation of :func:`simulate_batch`: validated per-lane
    depths and schedules, and in ``"vmap"`` mode the stacked, padded and
    (with several devices) sharded operands ``(stacked traces, stacked
    schedules, queue limits, resp limits, mesh)``; None in ``"lanes"``
    mode."""
    cfg.validate()
    topo = cfg.topology()
    if batch_mode not in ("auto", "vmap", "lanes"):
        raise ValueError(f"unknown batch_mode {batch_mode!r}")
    if batch_mode == "auto":
        batch_mode = "lanes" if jax.default_backend() == "cpu" else "vmap"
    if isinstance(traces, Trace):
        n_lanes = (len(queue_sizes) if queue_sizes is not None
                   else len(params) if params is not None else None)
        if n_lanes is None:
            raise ValueError(
                "broadcasting a single trace requires queue_sizes or params")
        trace_list = [traces] * n_lanes
    else:
        trace_list = list(traces)
    lanes = len(trace_list)
    if lanes == 0:
        return topo, batch_mode, trace_list, [], [], [], [], None

    def _broadcast(vals, default, name, cap):
        if vals is None:
            vals = [default] * lanes
        vals = list(vals)
        if len(vals) != lanes:
            raise ValueError(f"{name} must have one entry per lane")
        for v in vals:
            if not (1 <= v <= cap):
                raise ValueError(f"{name} entry {v} not in [1, {cap}]")
        return vals

    qs = _broadcast(queue_sizes, cfg.queue_size, "queue_sizes",
                    cfg.queue_size)
    rs = _broadcast(resp_queue_sizes, cfg.resp_queue_size,
                    "resp_queue_sizes", cfg.resp_queue_size)
    if params is None:
        scheds = [_sched_i32(cfg.runtime(), cfg.timing_model)] * lanes
    else:
        scheds = [_sched_i32(p, cfg.timing_model) for p in params]
        if len(scheds) != lanes:
            raise ValueError("params must have one entry per lane")
    # mixed constant/schedule lanes share one compiled program: pad every
    # lane's schedule to the common segment count (inert SCHEDULE_INF rows)
    s_max = max(sc.num_segments for sc in scheds)
    scheds = [sc.pad_to(s_max) for sc in scheds]
    if lane_cfgs is not None and len(lane_cfgs) != lanes:
        raise ValueError("lane_cfgs must have one entry per lane")

    ns = [int(tr.num_requests) for tr in trace_list]
    if batch_mode == "lanes":
        return topo, batch_mode, trace_list, qs, rs, scheds, ns, None
    # pad the batch to a device multiple with sentinel lanes so awkward
    # grid sizes still shard (GSPMD cannot split a ragged batch axis;
    # without padding a 5-lane sweep on 4 devices would silently run on
    # ONE device). Sentinel lanes are inert by construction and dropped
    # by _batch_results, which reads lanes [0, lanes) only.
    pad_lanes = _shard_pad(lanes) if shard else 0
    stacked, _ = stack_traces(trace_list, pad_lanes=pad_lanes)
    sched_stack = ParamSchedule.stack(scheds + [scheds[0]] * pad_lanes)
    ql = jnp.asarray(qs + [qs[0]] * pad_lanes, jnp.int32)
    rl = jnp.asarray(rs + [rs[0]] * pad_lanes, jnp.int32)
    mesh = None
    if shard:
        (stacked, sched_stack, ql, rl), mesh = _maybe_shard(
            (stacked, sched_stack, ql, rl), lanes + pad_lanes)
    if timings is not None:
        timings["pad_lanes"] = timings.get("pad_lanes", 0) + pad_lanes
        timings["sharded"] = mesh is not None
        timings["devices"] = len(jax.devices())
    return (topo, batch_mode, trace_list, qs, rs, scheds, ns,
            (stacked, sched_stack, ql, rl, mesh))


#: span counter -> the result counter it sums over a call's lanes
GRANT_SPAN_COUNTERS = {"jedec_l_bound": "grants_l_bound",
                       "jedec_windowed": "grants_windowed",
                       "jedec_pre_held": "pre_held"}


def count_grants(results: Sequence[SimResult]) -> None:
    """Add the ``"jedec"`` grant counters of ``results``, summed over the
    lanes, to the open span (``tracing.count``): ``jedec_l_bound``,
    ``jedec_windowed``, ``jedec_pre_held`` and, as their bases,
    ``jedec_grants`` (granted ACTs, RDs and WRs) and ``jedec_pre_grants``
    (granted PREs). A ``"table1"`` result has none."""
    from repro.core.params import CMD_ACT, CMD_PRE, CMD_RD, CMD_WR

    results = [r for r in results if "grants_windowed" in r.counters]
    if not results:
        return
    for name, key in GRANT_SPAN_COUNTERS.items():
        tracing.count(name, sum(int(r.counters[key]) for r in results))
    cmds = sum(np.asarray(r.counters["cmd_counts"], np.int64)
               for r in results)
    tracing.count("jedec_grants",
                  int(cmds[CMD_ACT] + cmds[CMD_RD] + cmds[CMD_WR]))
    tracing.count("jedec_pre_grants", int(cmds[CMD_PRE]))


def _batch_results(cfg, num_cycles, trace_list, scheds, qs, rs, ns,
                   lane_cfgs, finals, per_lane: bool) -> List[SimResult]:
    """The host :class:`SimResult` of every real lane: one ``device_get``
    of each lane's final state (``per_lane``, ``"lanes"`` mode) or of the
    stacked batch."""
    if per_lane:
        hosts = [jax.device_get(f) for f in finals]

        def lane_field(i, name):
            return np.asarray(getattr(hosts[i], name))[: ns[i]]

        def lane_counters(i):
            return {k: np.asarray(v) for k, v in hosts[i].counters.items()}

        def lane_scalar(i, name):
            return int(getattr(hosts[i], name))
    else:
        host = jax.device_get(finals)

        def lane_field(i, name):
            return np.asarray(getattr(host, name))[i, : ns[i]]

        def lane_counters(i):
            return {k: np.asarray(v)[i] for k, v in host.counters.items()}

        def lane_scalar(i, name):
            return int(np.asarray(getattr(host, name))[i])

    results = []
    for i in range(len(trace_list)):
        if lane_cfgs is not None:
            lane_cfg = lane_cfgs[i]
        else:
            lane_cfg = dataclasses.replace(scheds[i].apply_to(cfg),
                                           queue_size=qs[i],
                                           resp_queue_size=rs[i])
        results.append(SimResult(
            cfg=lane_cfg,
            num_cycles=num_cycles,
            t_intended=np.asarray(trace_list[i].t),
            is_write=np.asarray(trace_list[i].is_write),
            t_admit=lane_field(i, "t_admit"),
            t_dispatch=lane_field(i, "t_dispatch"),
            t_start=lane_field(i, "t_start"),
            t_complete=lane_field(i, "t_complete"),
            rdata=lane_field(i, "rdata"),
            counters=lane_counters(i),
            blocked_arrival=lane_scalar(i, "blocked_arrival"),
            blocked_dispatch=lane_scalar(i, "blocked_dispatch"),
        ))
    return results


def sweep_queue_sizes(cfg: MemSimConfig, trace: Trace,
                      queue_sizes: Sequence[int],
                      num_cycles: int = 100_000,
                      *, capacity: Optional[int] = None,
                      cycle_skip: bool = True,
                      batch_mode: str = "auto",
                      timings: Optional[dict] = None) -> List[SimResult]:
    """The paper's queue sweep as one compile + one batched device program.

    A one-axis special case of :func:`sweep_grid`. ``capacity`` (default
    ``max(queue_sizes)``) sizes the static buffers; pass the largest depth
    you will ever sweep so later sweeps with the same trace shape and lane
    count reuse the compiled program (``num_cycles`` is already a runtime
    value for the skipping engine).
    """
    return sweep_grid(cfg, trace, {"queue_size": list(queue_sizes)},
                      num_cycles, capacity=capacity, cycle_skip=cycle_skip,
                      batch_mode=batch_mode, timings=timings)


#: grid axes resolvable by :func:`sweep_grid`: every RuntimeParams field
#: (policies given as their config strings), the runtime queue depths, and
#: ``"schedule"`` — whose values are time-varying parameter schedules (see
#: :func:`lane_schedule` for the accepted forms), each a lane of the same
#: single compiled program.
GRID_AXES = tuple(RuntimeParams._fields) + ("queue_size", "resp_queue_size",
                                            "schedule")


def lane_schedule(cfg: MemSimConfig, spec) -> ParamSchedule:
    """Resolve a ``"schedule"`` grid-axis value against a lane's base
    config.

    Accepted forms:
      * ``None`` — the constant degenerate schedule (``cfg.runtime()``);
      * a :class:`ParamSchedule` — used as-is (already fully resolved, so
        it does NOT compose with the lane's other runtime axes);
      * a :class:`RuntimeParams` — a constant override point;
      * a sequence of ``(start_cycle, override_dict)`` segments — each
        segment's parameters are ``cfg`` with the overrides substituted
        (``dataclasses.replace(cfg, **overrides).validate()``), so
        schedules COMPOSE with the other grid axes (a swept ``tCL`` value
        applies to every segment that doesn't override it) and a bad
        segment fails with the exact ValueError config construction
        raises.
    """
    if spec is None:
        return ParamSchedule.constant(cfg.runtime())
    if isinstance(spec, ParamSchedule):
        return spec
    if isinstance(spec, RuntimeParams):
        return ParamSchedule.constant(spec)
    segs = []
    for start, ov in spec:
        seg_cfg = dataclasses.replace(cfg, **dict(ov)).validate()
        segs.append((int(start), seg_cfg.runtime()))
    return ParamSchedule.from_segments(segs)


def _stream_threshold() -> int:
    """Lane count at which :func:`sweep_grid` / :func:`sweep_topologies`
    switch to the streaming executor by default (``MEMSIM_STREAM_THRESHOLD``,
    default 4096, re-read per call)."""
    raw = os.environ.get("MEMSIM_STREAM_THRESHOLD", "").strip()
    try:
        v = int(raw) if raw else 4096
    except ValueError:
        v = 4096
    return max(1, v)


def grid_points(grid: Mapping[str, Sequence]) -> List[Dict]:
    """Expand an axis dict into the Cartesian product of override dicts,
    last axis fastest (``itertools.product`` order, deterministic)."""
    keys = list(grid)
    for k in keys:
        if k not in GRID_AXES:
            raise ValueError(f"unknown grid axis {k!r}; valid: {GRID_AXES}")
        if len(grid[k]) == 0:
            raise ValueError(f"grid axis {k!r} is empty")
    return [dict(zip(keys, vals))
            for vals in itertools.product(*(grid[k] for k in keys))]


def sweep_grid(cfg: MemSimConfig, trace: Trace,
               grid: Mapping[str, Sequence],
               num_cycles: int = 100_000,
               *, capacity: Optional[int] = None,
               resp_capacity: Optional[int] = None,
               cycle_skip: bool = True,
               shard: bool = True,
               batch_mode: str = "auto",
               stream: Optional[bool] = None,
               chunk_lanes: Optional[int] = None,
               memory_budget_bytes: Optional[int] = None,
               checkpoint_dir: Optional[str] = None,
               resume: bool = True,
               timings: Optional[dict] = None) -> List[SimResult]:
    """Run a full runtime-parameter grid through ONE compiled program.

    ``grid`` maps axis names to value lists; axes may be any Table-1
    timing parameter (``tRP``, ``tREFI``, ...), ``page_policy`` /
    ``sched_policy`` (config strings, lowered to flags),
    ``sref_idle_cycles``, the runtime queue depths ``queue_size`` /
    ``resp_queue_size``, and ``"schedule"`` — time-varying DVFS/thermal
    parameter schedules (see :func:`lane_schedule` for the accepted value
    forms; segment-spec lists compose with the other axes). One batch lane
    runs per point of the Cartesian product (:func:`grid_points` order);
    every lane is bit-exact vs an individual :func:`simulate` run of its
    config (with ``params=`` its schedule, re-resolved every cycle), and
    the whole grid — timings x policies x refresh x depth x schedules —
    shares a single compiled XLA program because all axes are traced
    data.

    ``capacity`` / ``resp_capacity`` (defaults: the largest swept depth,
    falling back to ``cfg``) size the static queue buffers. Returns one
    :class:`SimResult` per point with ``result.cfg`` set to that point's
    full ``MemSimConfig``.

    Streaming: grids at or above :func:`_stream_threshold` lanes (env
    ``MEMSIM_STREAM_THRESHOLD``, default 4096) — or any call that gives a
    ``checkpoint_dir`` or sets ``stream=True`` — run through the streaming
    executor (:func:`repro.core.sweep_stream.stream_sweep`): the lane
    space is chunked (``chunk_lanes``, or derived from
    ``memory_budget_bytes``), each chunk executes as one batched device
    program while the next chunk's host prep overlaps, completed chunks
    checkpoint to ``checkpoint_dir`` (kill/resume), and compiled
    executables persist across processes via ``MEMSIM_EXEC_CACHE_DIR``.
    Results are bit-exact vs this materializing path; ``batch_mode`` /
    ``shard`` do not apply to the streamed chunks (each chunk is a
    vmap-style batched program on its topology's device). Pass
    ``stream=False`` to force the materializing path.

    The call records the span ``memsim.sweep_grid``, with
    ``memsim.engine.prepare`` (per-point configs and schedules) and
    :func:`simulate_batch`'s spans under it (``tracing.recent``).

    Example::

        sweep_grid(MemSimConfig(), trace, {
            "tCL": [14, 18],
            "page_policy": ["closed", "open"],
            "sched_policy": ["fcfs", "frfcfs"],
            "queue_size": [16, 64],
        })
    """
    with tracing.span("memsim.sweep_grid"):
        points = grid_points(grid)
        if stream is None:
            stream = (checkpoint_dir is not None
                      or len(points) >= _stream_threshold())
        if stream:
            from repro.core.sweep_stream import stream_sweep

            return list(stream_sweep(
                cfg, trace, grid, num_cycles, capacity=capacity,
                resp_capacity=resp_capacity, cycle_skip=cycle_skip,
                chunk_lanes=chunk_lanes,
                memory_budget_bytes=memory_budget_bytes,
                checkpoint_dir=checkpoint_dir, resume=resume,
                timings=timings).results)
        with tracing.span("memsim.engine.prepare"):
            # per-point full configs: __post_init__ validates the policy
            # strings, validate() the cross-field constraints (e.g. tREFI >
            # tRFC) the seed path would enforce — a bad grid point fails
            # here, not silently in-trace. The "schedule" axis is not a
            # config field: it resolves per lane against that lane's config
            # (lane_schedule), every segment validated the same way.
            lane_cfgs = [dataclasses.replace(
                cfg, **{k: v for k, v in ov.items() if k != "schedule"}
            ).validate() for ov in points]
            lane_scheds = [lane_schedule(c, ov.get("schedule"))
                           for c, ov in zip(lane_cfgs, points)]
            qs = [c.queue_size for c in lane_cfgs]
            rs = [c.resp_queue_size for c in lane_cfgs]
            cap = max(qs) if capacity is None else capacity
            rcap = max(rs) if resp_capacity is None else resp_capacity
            if cap < max(qs):
                raise ValueError("capacity below largest swept queue size")
            if rcap < max(rs):
                raise ValueError(
                    "resp_capacity below largest swept resp queue size")
            cfg_cap = dataclasses.replace(cfg, queue_size=cap,
                                          resp_queue_size=rcap)
        return simulate_batch(cfg_cap, trace, num_cycles,
                              queue_sizes=qs, resp_queue_sizes=rs,
                              params=lane_scheds,
                              lane_cfgs=lane_cfgs,
                              cycle_skip=cycle_skip, shard=shard,
                              batch_mode=batch_mode, timings=timings)


# --------------------------------------------------------------------------
# multi-topology sweeps: one concurrent compile per hardware shape
# --------------------------------------------------------------------------

#: structural grid axes resolvable by :func:`sweep_topologies` on top of the
#: runtime ``GRID_AXES``: every shape-determining :class:`Topology` field.
#: Each distinct topology in a grid costs one compile (overlapped on a
#: thread pool); ``queue_size`` / ``resp_queue_size`` stay *runtime* depths
#: against a grid-wide static capacity, so a depth value never forces its
#: own program.
TOPO_AXES = tuple(f.name for f in dataclasses.fields(Topology)
                  if f.name not in ("queue_size", "resp_queue_size"))


def topo_grid_points(grid: Mapping[str, Sequence]) -> List[Dict]:
    """Expand a mixed (topology x runtime) axis dict into the Cartesian
    product of override dicts, last axis fastest (:func:`grid_points`
    order). Valid axes are :data:`TOPO_AXES` (structural — channels, ranks,
    bankgroups, banks_per_group, column_bits, mem_words, fsm_backend) plus
    every runtime axis of :data:`GRID_AXES`."""
    keys = list(grid)
    for k in keys:
        if k not in TOPO_AXES and k not in GRID_AXES:
            raise ValueError(
                f"unknown grid axis {k!r}; valid: {TOPO_AXES + GRID_AXES}")
        if len(grid[k]) == 0:
            raise ValueError(f"grid axis {k!r} is empty")
    return [dict(zip(keys, vals))
            for vals in itertools.product(*(grid[k] for k in keys))]


@dataclasses.dataclass
class TopoGridResult:
    """Merged result table of a multi-topology sweep, keyed by the full
    config point.

    Per-lane :class:`SimResult`\\ s of different topologies carry different
    bank counts (and therefore different per-bank internals); the merge is
    on the shape-independent surface every lane shares — per-request
    records, power/state counters, blocked totals — with each result's
    ``cfg`` labelling its exact grid point. ``points[i]`` is the axis
    override dict of ``results[i]`` (grid order);
    ``topologies[topo_of_point[i]]`` its compiled hardware shape.
    ``timings`` records per-topology compile/run seconds plus the
    concurrent (``compile_s_wall``) vs sequential-sum (``compile_s``)
    compile wall-clock."""

    points: List[Dict]
    results: List[SimResult]
    topologies: List[Topology]
    topo_of_point: List[int]
    timings: Dict

    def __len__(self) -> int:
        return len(self.results)

    def __iter__(self):
        return iter(self.results)

    def __getitem__(self, i: int) -> SimResult:
        return self.results[i]

    def table(self) -> List[Dict]:
        """One row per grid point: ``{point, topology, result}``."""
        return [{"point": dict(p), "topology": self.topologies[ti],
                 "result": r}
                for p, ti, r in zip(self.points, self.topo_of_point,
                                    self.results)]

    def result_at(self, **axes) -> SimResult:
        """The unique grid point matching every given axis value."""
        hits = [i for i, p in enumerate(self.points)
                if all(p.get(k) == v for k, v in axes.items())]
        if len(hits) != 1:
            raise KeyError(
                f"{axes} matches {len(hits)} grid points (need exactly 1)")
        return self.results[hits[0]]


def sweep_topologies(cfg: MemSimConfig,
                     trace: Union[Trace, Sequence[Trace]],
                     grid: Mapping[str, Sequence],
                     num_cycles: int = 100_000,
                     *, capacity: Optional[int] = None,
                     resp_capacity: Optional[int] = None,
                     cycle_skip: bool = True,
                     max_workers: Optional[int] = None,
                     stream: Optional[bool] = None,
                     chunk_lanes: Optional[int] = None,
                     memory_budget_bytes: Optional[int] = None,
                     checkpoint_dir: Optional[str] = None,
                     resume: bool = True,
                     timings: Optional[dict] = None) -> TopoGridResult:
    """Run a full (topology x runtime-params x policy x depth) grid with
    ONE overlapped compile per distinct hardware shape.

    Runtime axes batch as lanes of a shared program (exactly
    :func:`sweep_grid`); the structural :data:`TOPO_AXES` cannot — each
    distinct :class:`Topology` sets array shapes, so it needs its own XLA
    program. This orchestrator makes that cost scale with the number of
    *shapes*, not points, and overlaps it:

    1. expand the grid (:func:`topo_grid_points`) and group points by the
       distinct ``Topology`` they resolve to (queue depths are unified to a
       grid-wide static capacity first, so depth values never split a
       group);
    2. AOT-lower each topology's batched event-horizon program
       sequentially (tracing holds the GIL), then compile them
       **concurrently** on a thread pool — XLA releases the GIL, so the
       compile wall-clock overlaps instead of summing
       (``timings["compile_s_wall"]`` vs the sequential sum
       ``timings["compile_s"]``);
    3. dispatch each topology's lanes through its compiled batch runner,
       topologies round-robin across visible devices
       (``repro.distributed.shard.round_robin_devices``) and concurrent
       from worker threads;
    4. merge the per-lane results into one :class:`TopoGridResult` keyed
       by the full config point.

    Every lane is bit-exact vs a per-config seed :func:`simulate` run of
    its point. ``trace`` is one Trace broadcast to every point, or a
    sequence with one Trace per point. ``capacity`` / ``resp_capacity``
    (defaults: the largest swept depth, falling back to ``cfg``) size the
    static queue buffers of every topology. ``max_workers`` bounds both
    thread pools — concurrent compiles and concurrent dispatches —
    (default: enough to cover the host cores and the visible devices;
    pass 1 for fully sequential execution). Re-invoking with the same
    shapes reuses every compiled program (``timings["compiles"] == 0``).

    Streaming: grids at or above :func:`_stream_threshold` points — or any
    call giving ``checkpoint_dir`` or ``stream=True`` — route through
    :func:`repro.core.sweep_stream.stream_sweep` (chunked lane execution
    under a memory budget, kill/resume checkpointing, persistent
    cross-process executable cache via ``MEMSIM_EXEC_CACHE_DIR``);
    bit-exact vs this materializing path. ``stream=False`` forces the
    materializing path.

    Example::

        sweep_topologies(MemSimConfig(), trace, {
            "channels": [1, 2],
            "banks_per_group": [2, 4],      # 4 distinct topologies
            "tREFI": [3600, 7200],          # runtime lanes within each
            "queue_size": [16, 64],
        })
    """
    from concurrent.futures import ThreadPoolExecutor

    from jax.sharding import SingleDeviceSharding

    from repro.distributed.shard import round_robin_devices

    points = topo_grid_points(grid)
    if stream is None:
        stream = (checkpoint_dir is not None
                  or len(points) >= _stream_threshold())
    if stream:
        from repro.core.sweep_stream import stream_sweep

        return stream_sweep(
            cfg, trace, grid, num_cycles, capacity=capacity,
            resp_capacity=resp_capacity, cycle_skip=cycle_skip,
            max_workers=max_workers, chunk_lanes=chunk_lanes,
            memory_budget_bytes=memory_budget_bytes,
            checkpoint_dir=checkpoint_dir, resume=resume, timings=timings)
    lane_cfgs = [dataclasses.replace(
        cfg, **{k: v for k, v in ov.items() if k != "schedule"}).validate()
        for ov in points]
    n_points = len(points)
    if isinstance(trace, Trace):
        trace_list = [trace] * n_points
    else:
        trace_list = list(trace)
        if len(trace_list) != n_points:
            raise ValueError(
                f"got {len(trace_list)} traces for {n_points} grid points")

    qs = [c.queue_size for c in lane_cfgs]
    rs = [c.resp_queue_size for c in lane_cfgs]
    cap = max(qs) if capacity is None else capacity
    rcap = max(rs) if resp_capacity is None else resp_capacity
    if cap < max(qs):
        raise ValueError("capacity below largest swept queue size")
    if rcap < max(rs):
        raise ValueError("resp_capacity below largest swept resp queue size")
    # per-point schedules (the "schedule" runtime axis rides along exactly
    # like in sweep_grid), padded to one grid-wide segment count so every
    # topology's batched program takes the same schedule shapes
    scheds = [_sched_i32(lane_schedule(c, ov.get("schedule")))
              for c, ov in zip(lane_cfgs, points)]
    s_max = max(sc.num_segments for sc in scheds)
    scheds = [sc.pad_to(s_max) for sc in scheds]

    # group grid points by the distinct static topology they compile to
    topologies: List[Topology] = []
    topo_of_point: List[int] = []
    for c in lane_cfgs:
        t = dataclasses.replace(c, queue_size=cap,
                                resp_queue_size=rcap).topology()
        if t not in topologies:
            topologies.append(t)
        topo_of_point.append(topologies.index(t))
    n_topos = len(topologies)
    groups = [[i for i, ti in enumerate(topo_of_point) if ti == gi]
              for gi in range(n_topos)]
    devices = round_robin_devices(n_topos)
    if max_workers is None:
        # one knob bounds both thread pools: compiles are CPU-bound
        # (cores), dispatches device-bound (distinct devices) — cover both
        import os
        n_dev = len({d.id for d in devices})
        max_workers = max(1, min(n_topos, max(os.cpu_count() or 1, n_dev)))

    # ---- phase 1: one batched program per topology, compiles overlapped --
    t_c0 = time.perf_counter()
    lowered = []
    for gi, topo in enumerate(topologies):
        idxs = groups[gi]
        n_max_g = max(int(trace_list[i].num_requests) for i in idxs)
        sharding = SingleDeviceSharding(devices[gi])

        def sds(shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

        tr_s = Trace(t=sds((len(idxs), n_max_g)),
                     addr=sds((len(idxs), n_max_g)),
                     is_write=sds((len(idxs), n_max_g)),
                     wdata=sds((len(idxs), n_max_g)))
        scal, vec = sds(()), sds((len(idxs),))
        seg = sds((len(idxs), s_max))
        val = (seg if topo.tiers == 1
               else sds((len(idxs), s_max, topo.tiers)))
        sched_s = ParamSchedule(
            boundaries=seg,
            values=RuntimeParams(*([val] * len(RuntimeParams._fields))))
        if cycle_skip:
            lowered.append(_aot_lower(
                _run_skip_batch_jit, (topo, tr_s, scal, sched_s, vec, vec),
                (tr_s, scal, sched_s, vec, vec), (topo, devices[gi].id)))
        else:
            lowered.append(_aot_lower(
                _run_scan_batch_jit, (topo, tr_s, num_cycles, sched_s, vec,
                                      vec),
                (tr_s, sched_s, vec, vec), (topo, num_cycles,
                                            devices[gi].id)))

    def finish(gi: int) -> Tuple[object, float, int]:
        key, low, lower_s, cached = lowered[gi]
        if low is None:
            return cached, 0.0, 0
        compiled, c_s = _aot_finish(key, low)
        return compiled, lower_s + c_s, 1

    if n_topos > 1 and max_workers > 1:
        with ThreadPoolExecutor(max_workers=max_workers) as pool:
            built = list(pool.map(finish, range(n_topos)))
    else:
        built = [finish(gi) for gi in range(n_topos)]
    compile_wall = time.perf_counter() - t_c0
    compiled = [b[0] for b in built]
    compile_seq = [b[1] for b in built]
    fresh_total = sum(b[2] for b in built)

    # ---- phase 2: stage + dispatch each topology's lanes concurrently ----
    def run_group(gi: int):
        idxs = groups[gi]
        dev = devices[gi]
        stacked, _ = stack_traces([trace_list[i] for i in idxs])
        sched_stack = ParamSchedule.stack([scheds[i] for i in idxs])
        ql = jnp.asarray([qs[i] for i in idxs], jnp.int32)
        rl = jnp.asarray([rs[i] for i in idxs], jnp.int32)
        stacked, sched_stack, ql, rl = jax.device_put(
            (stacked, sched_stack, ql, rl), dev)
        t0 = time.perf_counter()
        if cycle_skip:
            nc = jax.device_put(jnp.int32(num_cycles), dev)
            finals, steps = compiled[gi](stacked, nc, sched_stack, ql, rl)
        else:
            finals, steps = compiled[gi](stacked, sched_stack, ql, rl)
        jax.block_until_ready(finals)
        return finals, int(np.max(np.asarray(steps))), \
            time.perf_counter() - t0

    t_r0 = time.perf_counter()
    if n_topos > 1 and max_workers > 1:
        with ThreadPoolExecutor(max_workers=min(n_topos, max_workers)) \
                as pool:
            outs = list(pool.map(run_group, range(n_topos)))
    else:
        outs = [run_group(gi) for gi in range(n_topos)]
    run_wall = time.perf_counter() - t_r0

    # ---- merge: one result table keyed by the full config point ----------
    results: List[Optional[SimResult]] = [None] * n_points
    for gi, (finals, _, _) in enumerate(outs):
        host = jax.device_get(finals)
        for k, i in enumerate(groups[gi]):
            n_i = int(trace_list[i].num_requests)
            results[i] = SimResult(
                cfg=lane_cfgs[i],
                num_cycles=num_cycles,
                t_intended=np.asarray(trace_list[i].t),
                is_write=np.asarray(trace_list[i].is_write),
                t_admit=np.asarray(host.t_admit)[k, :n_i],
                t_dispatch=np.asarray(host.t_dispatch)[k, :n_i],
                t_start=np.asarray(host.t_start)[k, :n_i],
                t_complete=np.asarray(host.t_complete)[k, :n_i],
                rdata=np.asarray(host.rdata)[k, :n_i],
                counters={c: np.asarray(v)[k]
                          for c, v in host.counters.items()},
                blocked_arrival=int(np.asarray(host.blocked_arrival)[k]),
                blocked_dispatch=int(np.asarray(host.blocked_dispatch)[k]),
            )

    own = {
        "compiles": fresh_total,
        "compile_s": sum(compile_seq),
        "compile_s_wall": compile_wall,
        "run_s": run_wall,
        "steps": max(o[1] for o in outs),
        "topologies": n_topos,
        "per_topology": [
            {"topology": dataclasses.asdict(topologies[gi]),
             "lanes": len(groups[gi]),
             "compile_s": compile_seq[gi],
             "run_s": outs[gi][2],
             "steps": outs[gi][1],
             "device": devices[gi].id}
            for gi in range(n_topos)],
    }
    if timings is not None:
        for k in ("compiles", "topologies"):
            timings[k] = timings.get(k, 0) + own[k]
        for k in ("compile_s", "compile_s_wall", "run_s"):
            timings[k] = timings.get(k, 0.0) + own[k]
        timings["steps"] = max(timings.get("steps", 0), own["steps"])
        timings.setdefault("per_topology", []).extend(own["per_topology"])
    return TopoGridResult(points=points, results=results,
                          topologies=topologies,
                          topo_of_point=topo_of_point, timings=own)
