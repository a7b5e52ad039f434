"""The plain reference of the ``"jedec"`` timing model: the per-cycle
MemorySim circuit with bank-group windows and a per-bank precharge gate.

A frozen copy of the per-cycle ``jnp`` path of ``repro.core.simulator``
with ``params``, ``dram_model`` and ``bank_fsm`` beside it (the modules
the bank-group registers, the S/L windows and the precharge gate change),
and ``power`` and ``queues`` taken from :mod:`bench.reference`, so that
no change to the program can move the yardstick. Only the per-cycle
``jnp`` FSM is kept: no kernel, no event horizon, no batching. The
``"jedec"`` grant counters are kept here, beside the power counters, and
:func:`simulate` can return the issued-command log that
:mod:`bench.reference_jedec.protocol` checks.

MemorySim top level (paper §5.1): trace front-end -> controller -> banks.

The whole memory subsystem is one synchronous circuit: ``cycle_step`` is the
combinational logic, the ``SimState`` NamedTuple is the register file, and
``jax.lax.scan`` is the clock. Request life-cycle (paper's numbered path):

  1. trace lists R = {addr, t}
  2. at cycle t, R is pushed into the global reqQueue (stall = backpressure)
  3. the controller classifies R by (rank, bankgroup, bank) and forwards it
     to that bank scheduler's local queue
  4. the bank FSM drives ACTIVATE -> READ/WRITE -> PRECHARGE against the
     DRAM timing model (closed-page policy, refresh deadlines)
  5. the completion token is round-robin collected into respQueue and acked
     to the front-end; latency = ack_cycle - t.

Per-request dispatch/start/complete cycles are recorded so the benchmark
harness can reproduce the paper's Table 2 / Fig 6-9 analyses exactly.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from bench.reference import power as power_lib
from bench.reference.queues import BankedFifo, Fifo, rr_arbiter, rr_arbiter_grouped
from bench.reference_jedec.bank_fsm import BankState, compute_bids, fsm_update
from bench.reference_jedec.dram_model import (
    TimingState,
    decode_address,
    jedec_windows,
    legal_issue_cycle,
    record_issue,
)
from bench.reference_jedec.params import (
    CMD_ACT,
    CMD_NOP,
    CMD_PRE,
    CMD_RD,
    CMD_WR,
    SCHED_FRFCFS,
    MemSimConfig,
    ParamSchedule,
    RuntimeParams,
    S_RESP_PEND,
    Topology,
    as_schedule,
    rp_for_banks,
    tier_of_bank,
)

#: the grant counters of the ``"jedec"`` model, kept beside the power ones
JEDEC_COUNTERS = ("grants_l_bound", "grants_windowed", "pre_held")


class Trace(NamedTuple):
    """A standalone memory trace: request i must issue at cycle t[i]."""

    t: Array         # [N] int32, sorted non-decreasing
    addr: Array      # [N] int32 word address
    is_write: Array  # [N] int32 {0, 1}
    wdata: Array     # [N] int32 payload for writes

    @property
    def num_requests(self) -> int:
        return self.t.shape[0]

    @staticmethod
    def from_numpy(t, addr, is_write, wdata=None) -> "Trace":
        t = np.asarray(t, np.int32)
        if wdata is None:
            wdata = np.zeros_like(t)
        order = np.argsort(t, kind="stable")
        return Trace(
            t=jnp.asarray(t[order]),
            addr=jnp.asarray(np.asarray(addr, np.int32)[order]),
            is_write=jnp.asarray(np.asarray(is_write, np.int32)[order]),
            wdata=jnp.asarray(np.asarray(wdata, np.int32)[order]),
        )


class SimState(NamedTuple):
    next_arrival: Array       # scalar: index of next trace entry to admit
    req_q: Fifo               # global request queue
    bank_q: BankedFifo        # per-bank scheduler queues
    bank: BankState
    timing: TimingState
    cmd_rr: Array             # [C] per-channel command arbiter pointers
    resp_rr: Array            # scalar response arbiter pointer
    resp_q: Fifo
    mem: Array                # [mem_words] int32 backing store (bit-true)
    # per-request records, [N]; -1 = not yet
    t_admit: Array
    t_dispatch: Array
    t_start: Array
    t_complete: Array
    rdata: Array
    # aggregate counters
    counters: Dict[str, Array]
    blocked_arrival: Array    # cycles an arrival stalled on full reqQueue
    blocked_dispatch: Array   # cycles dispatch stalled on a full bank queue

    @property
    def effective_queue_size(self) -> Array:
        """Runtime depth enforced on the req/bank queues (the paper's
        ``queueSize`` as a data value — see ``Fifo.limit``). The global
        reqQueue and every bank queue share one limit by construction."""
        return self.req_q.limit


@dataclasses.dataclass
class SimResult:
    """Host-side result bundle (numpy)."""

    cfg: MemSimConfig
    num_cycles: int
    t_intended: np.ndarray
    is_write: np.ndarray
    t_admit: np.ndarray
    t_dispatch: np.ndarray
    t_start: np.ndarray
    t_complete: np.ndarray
    rdata: np.ndarray
    counters: Dict[str, int]
    blocked_arrival: int
    blocked_dispatch: int

    @property
    def completed(self) -> np.ndarray:
        return self.t_complete >= 0

    @property
    def latency(self) -> np.ndarray:
        """In-system latency (admission -> ack), the paper's accounting:
        a request blocked outside a full reqQueue is not yet 'in' the
        system (its wait shows up as lost throughput, Fig 9, not latency).
        """
        return np.where(self.completed, self.t_complete - self.t_admit, -1)

    @property
    def e2e_latency(self) -> np.ndarray:
        """Intended-issue -> ack (includes pre-admission stall)."""
        return np.where(self.completed, self.t_complete - self.t_intended, -1)


def init_state(topo: Topology, sched, num_requests: int,
               queue_limit=None, resp_queue_limit=None) -> SimState:
    """Initial register file.

    Shapes come from the static ``topo`` plus the schedule's segment count
    (the per-segment cycle counters); the only runtime value consumed here
    is the cycle-0 ``tREFI`` (initial refresh deadlines, resolved through
    ``params_at(0)``). ``sched`` is a :class:`ParamSchedule` or a bare
    :class:`RuntimeParams` (lifted to the S=1 degenerate schedule).
    ``queue_limit`` / ``resp_queue_limit`` are optional *runtime* occupancy
    caps (traced scalars) on the statically-sized queues: the paper's
    ``queueSize`` becomes a data value instead of a compiled shape, so a
    queue-depth sweep reuses one XLA program (see ``repro.core.engine``).
    Defaults reproduce the static behaviour (limit == capacity).
    """
    sched = as_schedule(sched)
    rp0 = sched.params_at(jnp.int32(0))
    neg = jnp.full((num_requests,), -1, jnp.int32)
    return SimState(
        next_arrival=jnp.int32(0),
        req_q=Fifo.make(topo.queue_size, limit=queue_limit),
        bank_q=BankedFifo.make(topo.num_banks, topo.queue_size, limit=queue_limit),
        bank=BankState.make(topo, rp0),
        timing=TimingState.make(topo),
        cmd_rr=jnp.zeros((topo.channels,), jnp.int32),
        resp_rr=jnp.int32(0),
        resp_q=Fifo.make(topo.resp_queue_size, limit=resp_queue_limit),
        mem=jnp.zeros((topo.mem_words,), jnp.int32),
        t_admit=neg,
        t_dispatch=neg,
        t_start=neg,
        t_complete=neg,
        rdata=jnp.zeros((num_requests,), jnp.int32),
        counters=dict(power_lib.make_counters(topo.num_banks,
                                              sched.num_segments,
                                              topo.tiers),
                      **({k: jnp.int32(0) for k in JEDEC_COUNTERS}
                         if topo.jedec else {})),
        blocked_arrival=jnp.int32(0),
        blocked_dispatch=jnp.int32(0),
    )


def issue_eligibility(topo: Topology, sched, timing: TimingState,
                      bank: BankState, cycle: Array
                      ) -> Tuple[Array, Array, Array]:
    """The ONE issue-eligibility predicate: which banks may be granted the
    command bus this cycle.

    ``sched`` is a :class:`ParamSchedule` (or bare :class:`RuntimeParams`);
    legality is judged under ``params_at(cycle)`` — the operating point
    governing *this* cycle — so a DVFS boundary re-prices every pending bid
    the cycle it lands, exactly as the per-cycle reference does.

    Returns ``(eligible bool[B], cmds int32[B], legal_at int32[B])`` where
    ``eligible = bidding & (cycle >= legal_at)``. ``cycle_step`` feeds
    ``eligible`` to the per-channel arbiters; the event-horizon engine
    (:mod:`repro.core.engine`) reuses ``legal_at`` as the "cycles until the
    queue head becomes issuable" bound (valid within the current schedule
    segment — the engine caps skips at the next boundary) — sharing this
    definition is what makes skipping through blocked ISSUE states provably
    exact.
    """
    rp = rp_for_banks(topo, as_schedule(sched).params_at(cycle))
    bids, cmds = compute_bids(bank.st, bank.cur_write)
    banks = jnp.arange(topo.num_banks, dtype=jnp.int32)
    rank_of_bank = banks // topo.banks_per_rank
    if topo.jedec:
        legal_at = legal_issue_cycle(rp, timing, cmds, rank_of_bank,
                                     banks // topo.banks_per_group,
                                     bank.pre_ready)
    else:
        legal_at = legal_issue_cycle(rp, timing, cmds, rank_of_bank)
    eligible = bids & (cycle >= legal_at)
    return eligible, cmds, legal_at


def grant_counts(topo: Topology, rp: RuntimeParams, timing: TimingState,
                 bank: BankState, cmds: Array, grant: Array) -> Dict:
    """This edge's increments of :data:`JEDEC_COUNTERS`: a grant was held
    by a window when its legal cycle lies after the cycle its bid began;
    it was bound by its bank group when the same-group (L) windows alone
    set that cycle."""
    banks = jnp.arange(topo.num_banks, dtype=jnp.int32)
    s_at, l_at = jedec_windows(rp, timing, cmds, banks // topo.banks_per_rank,
                               banks // topo.banks_per_group, bank.pre_ready)
    held = grant & (jnp.maximum(s_at, l_at) > bank.bid_since)
    act_col = (cmds == CMD_ACT) | (cmds == CMD_RD) | (cmds == CMD_WR)
    return {"grants_l_bound": (held & act_col & (l_at > s_at)).sum(),
            "grants_windowed": (held & act_col).sum(),
            "pre_held": (held & (cmds == CMD_PRE)).sum()}


def _frontend_phases(topo: Topology, trace: Trace, state: SimState,
                     cycle: Array, rp: RuntimeParams = None):
    """Phases 1-2 of the clock edge: trace admission into the global
    reqQueue and dispatch of its head into the target bank queue. Shared
    verbatim between :func:`cycle_step` and the fused hot-loop step
    (:mod:`repro.core.fused_step`). ``rp`` carries the cycle's resolved
    parameter point for the tier-placement decode on tiered topologies
    (unused — and the graph untouched — on a single tier). Returns
    ``(req_q, bank_q, t_admit, t_dispatch, next_arrival, blocked_arrival,
    blocked_dispatch)``."""
    n = trace.num_requests

    # ---- phase 1: front-end arrival into reqQueue (1 request / cycle) -----
    idx = jnp.minimum(state.next_arrival, n - 1)
    due = (state.next_arrival < n) & (trace.t[idx] <= cycle)
    can_admit = due & ~state.req_q.full()
    item = jnp.stack(
        [trace.addr[idx], trace.is_write[idx], trace.wdata[idx], idx.astype(jnp.int32)]
    )
    req_q = state.req_q.push(item, can_admit)
    t_admit = state.t_admit.at[
        jnp.where(can_admit, idx, n)
    ].set(cycle.astype(jnp.int32), mode="drop")
    next_arrival = state.next_arrival + can_admit.astype(jnp.int32)
    blocked_arrival = state.blocked_arrival + (due & ~can_admit).astype(jnp.int32)

    # ---- phase 2: dispatch reqQueue head -> bank scheduler queue -----------
    head = req_q.peek()
    tgt_bank, _, _ = decode_address(topo, head[0], rp)
    have_req = ~req_q.empty()
    tgt_full = state.bank_q.full()[tgt_bank]
    do_dispatch = have_req & ~tgt_full
    req_q, ditem = req_q.pop(do_dispatch)
    bank_q = state.bank_q.push_at(tgt_bank, ditem, do_dispatch)
    t_dispatch = state.t_dispatch.at[
        jnp.where(do_dispatch, ditem[3], n)
    ].set(cycle.astype(jnp.int32), mode="drop")
    blocked_dispatch = state.blocked_dispatch + (have_req & tgt_full).astype(jnp.int32)
    return (req_q, bank_q, t_admit, t_dispatch, next_arrival,
            blocked_arrival, blocked_dispatch)


def _promote_frfcfs(topo: Topology, rp, bank_q: BankedFifo,
                    open_row: Array) -> BankedFifo:
    """FR-FCFS (a traced policy flag): promote the oldest row-hit to each
    bank queue's head. lax.cond keeps the promotion network off the
    runtime path for FCFS lanes on the single-lane engines (under vmap it
    lowers to a select, which is the price of a shared program). Shared by
    :func:`cycle_step` and the fused step."""
    from bench.reference.bank_fsm import row_of

    def _promoted_buf():
        q = bank_q.capacity
        offs = (bank_q.head[:, None] + jnp.arange(q)[None, :]) % q
        addrs = jnp.take_along_axis(bank_q.buf[..., 0], offs, axis=1)
        return bank_q.promote_rowhit(open_row, row_of(topo, addrs)).buf

    pol = jnp.asarray(rp.sched_policy)
    if topo.tiers > 1:
        pol = pol.reshape(-1)[0]  # tier-uniform by construction -> scalar
    return bank_q._replace(buf=jax.lax.cond(
        pol == SCHED_FRFCFS,
        _promoted_buf, lambda: bank_q.buf))


def _memory_phase(topo: Topology, n: int, old_bank: BankState, mem: Array,
                  rdata: Array, rw_done: Array) -> Tuple[Array, Array]:
    """Phase 6: bit-true memory access on column completion, on the
    PRE-edge bank registers (the request the completing column command
    belongs to). Shared by :func:`cycle_step` and the fused step."""
    maddr = old_bank.cur_addr & (topo.mem_words - 1)
    is_wr = old_bank.cur_write == 1
    widx = jnp.where(rw_done & is_wr, maddr, topo.mem_words)
    # read through the scatter OUTPUT: banks never alias a word in-cycle,
    # so the post-write image equals the pre-write one at every read
    # address — and chaining the gather after the scatter gives ``mem``
    # a single linear def-use chain, so XLA's scatter expander mutates
    # the carried backing store in place instead of copying the full
    # array (twice) every executed cycle to keep a pre-write image live
    mem2 = mem.at[widx].set(old_bank.cur_data, mode="drop")
    rvals = mem2[maddr]
    ridx = jnp.where(rw_done & ~is_wr, old_bank.cur_id, n)
    rdata2 = rdata.at[ridx].set(rvals, mode="drop")
    return mem2, rdata2


def cycle_step(topo: Topology, sched, trace: Trace,
               state: SimState, cycle: Array) -> SimState:
    """One synchronous clock edge (:func:`edge` without its commands)."""
    return edge(topo, sched, trace, state, cycle)[0]


def edge(topo: Topology, sched, trace: Trace, state: SimState,
         cycle: Array) -> Tuple[SimState, Array, Array]:
    """One synchronous clock edge. ``sched`` is a :class:`ParamSchedule`
    (or bare :class:`RuntimeParams`): every parameter consumed this cycle
    is resolved through ``params_at(cycle)`` — the per-cycle reference
    semantics time-varying runs are defined by. The FSM is always the
    ``jnp`` one, whatever ``topo.fsm_backend`` names. Returns the new
    state and each channel's issued command (``CMD_NOP`` where none) and
    the flat bank it went to."""
    sched = as_schedule(sched)
    rp = sched.params_at(cycle)
    rp_b = rp_for_banks(topo, rp)  # per-bank leaves on tiered topologies
    seg = sched.segment_at(cycle)
    n = trace.num_requests
    b = topo.num_banks

    (req_q, bank_q, t_admit, t_dispatch, next_arrival, blocked_arrival,
     blocked_dispatch) = _frontend_phases(topo, trace, state, cycle, rp)

    # ---- phase 3: command bids, timing legality, per-channel RR grant ------
    eligible, cmds, _ = issue_eligibility(topo, sched, state.timing,
                                          state.bank, cycle)
    rank_of_bank = (jnp.arange(b, dtype=jnp.int32) // topo.banks_per_rank)
    grant_mask, winners, cmd_rr = rr_arbiter_grouped(eligible, state.cmd_rr, topo.channels)

    timing = state.timing
    issued_cmds, issued_banks = [], []
    for ch in range(topo.channels):  # static unroll; channels is small
        flat_w = ch * topo.banks_per_channel + winners[ch]
        granted = eligible.reshape(topo.channels, -1)[ch].any()
        cmd_w = jnp.where(granted, cmds[flat_w], CMD_NOP)
        group_w = ((flat_w // topo.banks_per_group) % topo.bankgroups
                   if topo.jedec else None)
        timing = record_issue(timing, cycle, cmd_w, rank_of_bank[flat_w],
                              granted, group_w)
        issued_cmds.append(cmd_w)
        issued_banks.append(flat_w)
    issued_cmds = jnp.stack(issued_cmds)

    # ---- phase 4: response arbitration into respQueue ----------------------
    resp_bids = (state.bank.st == S_RESP_PEND) & ~state.resp_q.full()
    resp_w, any_resp, resp_rr = rr_arbiter(resp_bids, state.resp_rr)
    resp_accept = jnp.zeros((b,), bool).at[resp_w].set(any_resp)
    resp_item = jnp.stack(
        [
            state.bank.cur_addr[resp_w],
            state.bank.cur_write[resp_w],
            state.bank.cur_data[resp_w],
            state.bank.cur_id[resp_w],
        ]
    )
    resp_q = state.resp_q.push(resp_item, any_resp)

    # ---- phase 5: synchronous FSM update + bank queue pops -----------------
    bank_q = _promote_frfcfs(topo, rp, bank_q, state.bank.open_row)
    pop_items, queue_nonempty = bank_q.peek_valid()
    new_bank, outs = fsm_update(
        topo, rp_b, state.bank, grant_mask, resp_accept, queue_nonempty,
        pop_items, cycle
    )
    bank_q, popped = bank_q.pop_mask(outs.want_pop)
    t_start = state.t_start.at[
        jnp.where(outs.want_pop, pop_items[:, 3], n)
    ].set(cycle.astype(jnp.int32), mode="drop")

    # ---- phase 6: bit-true memory access on column completion --------------
    mem, rdata = _memory_phase(topo, n, state.bank, state.mem, state.rdata,
                               outs.rw_done)

    # ---- phase 7: respQueue -> front-end ack (stats close out) -------------
    # The pop reads the post-push queue: a response pushed into an empty
    # respQueue this cycle is acked this cycle (flow-through queue, standard
    # RTL Decoupled passthrough). Front-end is always ready (1 ack / cycle).
    ack_valid = ~resp_q.empty()
    resp_q, fitem = resp_q.pop(ack_valid)
    t_complete = state.t_complete.at[
        jnp.where(ack_valid, fitem[3], n)
    ].set(cycle.astype(jnp.int32), mode="drop")

    # ---- phase 8: counters ---------------------------------------------------
    counters = power_lib.update_counters(
        state.counters, issued_cmds, state.bank.st, seg,
        tier_idx=tier_of_bank(topo) if topo.tiers > 1 else None)
    if topo.jedec:
        counts = grant_counts(topo, rp_b, state.timing, state.bank, cmds,
                              grant_mask)
        counters.update({k: (state.counters[k] + v).astype(jnp.int32)
                         for k, v in counts.items()})

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
    return new_state, issued_cmds, jnp.stack(issued_banks).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(0,))
def run_cycles(topo: Topology, sched: ParamSchedule, trace: Trace,
               state: SimState, t0, t1) -> SimState:
    """Clock ``state`` through every cycle of ``[t0, t1)``, one
    ``cycle_step`` per cycle. Both bounds are traced: one compiled program
    per (topology, trace capacity, segment count)."""
    return jax.lax.fori_loop(
        jnp.asarray(t0, jnp.int32), jnp.asarray(t1, jnp.int32),
        lambda c, st: cycle_step(topo, sched, trace, st, c), state)


@functools.partial(jax.jit, static_argnums=(0, 5))
def run_cycles_logged(topo: Topology, sched: ParamSchedule, trace: Trace,
                      state: SimState, t0, num_cycles: int):
    """:func:`run_cycles` over ``[t0, t0 + num_cycles)`` that also stacks
    each edge's issued commands and banks (``[num_cycles, C]`` each)."""
    def step(st, c):
        new, cmds, banks = edge(topo, sched, trace, st, c)
        return new, (cmds, banks)

    return jax.lax.scan(step, state, jnp.asarray(t0, jnp.int32)
                        + jnp.arange(num_cycles, dtype=jnp.int32))


def command_log(cmds, banks) -> np.ndarray:
    """``int32[K, 4]`` rows ``(cycle, channel, flat bank, command)``, one
    per issued command, in issue order."""
    cmds, banks = np.asarray(cmds), np.asarray(banks)
    cycle, ch = np.nonzero(cmds != CMD_NOP)
    return np.stack([cycle, ch, banks[cycle, ch], cmds[cycle, ch]],
                    axis=1).astype(np.int32)


def state_to_result(cfg: MemSimConfig, trace: Trace, final: SimState,
                    num_cycles: int) -> SimResult:
    """Pull a device-side final state into the host-side result bundle."""
    counters = {k: np.asarray(v) for k, v in final.counters.items()}
    return SimResult(
        cfg=cfg,
        num_cycles=num_cycles,
        t_intended=np.asarray(trace.t),
        is_write=np.asarray(trace.is_write),
        t_admit=np.asarray(final.t_admit),
        t_dispatch=np.asarray(final.t_dispatch),
        t_start=np.asarray(final.t_start),
        t_complete=np.asarray(final.t_complete),
        rdata=np.asarray(final.rdata),
        counters=counters,
        blocked_arrival=int(final.blocked_arrival),
        blocked_dispatch=int(final.blocked_dispatch),
    )


def simulate(cfg: MemSimConfig, trace: Trace, num_cycles: int,
             *, params=None, queue_size=None, log: bool = False):
    """Run the per-cycle reference for ``num_cycles`` over ``trace``.

    ``params`` is a :class:`RuntimeParams` point or a tier-stacked one
    (default lifted from ``cfg``); ``queue_size`` the runtime depth of the
    request and bank queues (default: ``cfg.queue_size``). With ``log``
    returns ``(result, command log)`` (:func:`command_log`)."""
    sched = as_schedule(cfg.runtime() if params is None else params)
    topo = cfg.topology()
    ql = cfg.queue_size if queue_size is None else queue_size
    state = init_state(topo, sched, trace.num_requests, jnp.int32(ql),
                       jnp.int32(cfg.resp_queue_size))
    if log:
        final, (cmds, banks) = run_cycles_logged(topo, sched, trace, state,
                                                 0, num_cycles)
        return (state_to_result(cfg, trace, final, num_cycles),
                command_log(cmds, banks))
    final = run_cycles(topo, sched, trace, state, 0, num_cycles)
    return state_to_result(cfg, trace, final, num_cycles)
