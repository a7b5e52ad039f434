"""Bank-scheduler FSM (paper §5.2, Fig 2), vectorized over banks.

RTL semantics: every bank's FSM register updates exactly once per clock from
the cycle-start state — no intra-cycle forwarding. All decisions below read
the *current* state; the controller applies queue pops / memory accesses the
FSM requests. ``fsm_update`` is the per-cycle hot loop; the Pallas kernel in
``repro.kernels.bank_fsm`` implements the identical function blocked over the
bank axis for TPU, validated against this implementation.

Every timing value and the page policy come from the traced
:class:`RuntimeParams` pytree — the page-policy selection is branchless
``jnp.where`` on the ``PAGE_OPEN`` flag, so a single compiled program
serves both policies (and any Table-1 timing point); only the data differs.

Time-varying parameters (DVFS/thermal schedules): ``fsm_update`` is the
*instantaneous* combinational network — its ``rp`` argument is the
operating point governing THIS cycle, resolved by the caller through
``ParamSchedule.params_at(cycle)`` (``bench.reference_jedec.simulator.cycle_step``
does the one resolve per cycle; the Pallas kernel twin resolves the packed
``[S, NP]`` schedule in-kernel). WAIT timers latch their duration from the
params active at the grant cycle and merely count down across schedule
boundaries — an in-flight command completes at its issued timing, exactly
the per-cycle reference semantics.

Closed-page transitions (the paper's policy; write identical with WR):

  IDLE --pop--> ACT_ISSUE --grant--> ACT_WAIT(tRCD) --> RW_ISSUE
       --grant--> RW_WAIT(tCL) --> PRE_ISSUE --grant--> PRE_WAIT(tRP)
       --> RESP_PEND --resp-accept--> IDLE

  IDLE --refresh window--> REF_ISSUE --grant--> REF_WAIT(tRFC) --> IDLE
  IDLE --1000 idle cycles--> SREF_ISSUE --grant--> SREF
  SREF --queue nonempty--> SREF_EXIT_ISSUE --grant--> SREF_EXIT_WAIT(tXS) --> IDLE

Open-page transitions (the paper's future-work extension): rows stay open
after a column access; RW_WAIT goes straight to RESP_PEND; a pop that hits
the open row enters RW_ISSUE directly; a conflict (other row open) or a
refresh/self-refresh on an open row precharges first — the ``pending``
register records what to do after PRE_WAIT expires (1 = activate for the
current request, 2 = refresh, 3 = self-refresh entry).

Under ``timing_model="jedec"`` each bank also keeps its precharge gate,
``pre_ready``: an ACT grant sets it to ``cycle + tRAS``, a RD or WR grant
raises it to ``cycle + tRTP`` or ``cycle + tWTP``, and every PRE bid
(closed-page precharge, open-page conflict, precharge before refresh or
self refresh) is legal from it on
(:func:`bench.reference_jedec.dram_model.legal_issue_cycle`). ``bid_since`` holds the
cycle the bank's current bid began, so a grant can tell whether a window
held it (the ``grants_windowed`` / ``pre_held`` counters). The
``"table1"`` model carries neither register.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp
from jax import Array

from bench.reference_jedec.params import (
    CMD_ACT,
    CMD_NOP,
    CMD_PRE,
    CMD_RD,
    CMD_REF,
    CMD_SREF_ENTER,
    CMD_SREF_EXIT,
    CMD_WR,
    PAGE_OPEN,
    RuntimeParams,
    S_ACT_ISSUE,
    S_ACT_WAIT,
    S_IDLE,
    S_PRE_ISSUE,
    S_PRE_WAIT,
    S_REF_ISSUE,
    S_REF_WAIT,
    S_RESP_PEND,
    S_RW_ISSUE,
    S_RW_WAIT,
    S_SREF,
    S_SREF_EXIT_ISSUE,
    S_SREF_EXIT_WAIT,
    S_SREF_ISSUE,
    Topology,
)

# pending-after-precharge codes (open-page mode)
P_NONE, P_RW, P_REF, P_SREF = 0, 1, 2, 3


class BankState(NamedTuple):
    """Per-bank scheduler registers, all [B] int32."""

    st: Array           # FSM state
    timer: Array        # countdown for WAIT states
    idle_ctr: Array     # consecutive idle cycles (self-refresh entry)
    refresh_due: Array  # absolute cycle of next refresh deadline
    cur_addr: Array     # in-flight request fields
    cur_write: Array
    cur_data: Array
    cur_id: Array
    open_row: Array     # open-page: currently open row (-1 = closed)
    pending: Array      # open-page: action after PRE_WAIT (P_* codes)
    pre_ready: Array = None  # "jedec": first cycle a PRE is legal
    bid_since: Array = None  # "jedec": first cycle of the current bid

    @staticmethod
    def make(topo: Topology, rp: RuntimeParams) -> "BankState":
        from bench.reference_jedec.dram_model import _NEG
        from bench.reference_jedec.params import rp_for_banks

        b = topo.num_banks
        rp = rp_for_banks(topo, rp)  # [T] leaves -> per-bank (T=1: identity)
        z = jnp.zeros((b,), jnp.int32)
        jedec = {}
        if topo.jedec:
            jedec = dict(pre_ready=jnp.full((b,), _NEG, jnp.int32),
                         bid_since=z)
        return BankState(
            st=z,
            timer=z,
            idle_ctr=z,
            refresh_due=jnp.broadcast_to(
                jnp.asarray(rp.tREFI, jnp.int32), (b,)),
            cur_addr=z,
            cur_write=z,
            cur_data=z,
            cur_id=jnp.full((b,), -1, jnp.int32),
            open_row=jnp.full((b,), -1, jnp.int32),
            pending=z,
            **jedec,
        )


class FsmOutputs(NamedTuple):
    """What the FSM asks the controller to do this cycle."""

    want_pop: Array      # bool[B]: pop my local queue head into cur_*
    rw_done: Array       # bool[B]: column access completed -> touch memory
    completed: Array     # bool[B]: response accepted -> request finished
    started: Array       # bool[B]: service began (for latency breakdown)


def row_of(topo: Topology, addr: Array) -> Array:
    return (addr >> (topo.addr_low_bits + topo.column_bits)).astype(jnp.int32)


def wait_mask(st: Array) -> Array:
    """bool[B]: bank is in a timed WAIT state (timer counts down, no bus
    activity until expiry). Shared by ``fsm_update`` and the cycle-skipping
    engine, which fast-forwards these timers."""
    return (
        (st == S_ACT_WAIT)
        | (st == S_RW_WAIT)
        | (st == S_PRE_WAIT)
        | (st == S_REF_WAIT)
        | (st == S_SREF_EXIT_WAIT)
    )


#: sentinel bound for banks that only an external event can unblock (plain
#: int on purpose: a module-level jnp constant materialized during tracing
#: would leak that trace's context into later traces)
EVENT_INF = 0x3FFFFFFF


def cycles_until_actionable(rp: RuntimeParams, bank: BankState,
                            cycle: Array) -> Array:
    """Branchless per-bank bound: cycles from ``cycle`` until this bank's
    FSM would do anything besides count (WAIT timer decrement / idle
    counter increment), absent external events.

    * WAIT states transition when the timer expires — during cycle
      ``cycle + timer - 1`` (the ``timer - 1`` convention: the per-cycle
      engine decrements first, then fires on ``timer2 == 0``).
    * IDLE banks act when the refresh window opens (cycle
      ``refresh_due - tRFC``) or the self-refresh threshold is crossed
      (``idle_ctr + 1`` reaches ``sref_idle_cycles``), whichever first.
    * SREF banks wake only on external queue activity: ``EVENT_INF``.
    * ISSUE / RESP_PEND banks are actionable now (0) from the FSM's view;
      command-bus legality is the timing model's domain
      (:func:`bench.reference_jedec.dram_model.legal_issue_cycle`).

    This is the FSM-local half of the event-horizon bound the skipping
    engine takes a vectorized min over. ``rp`` is the operating point of
    the segment containing ``cycle``; the bound is a closed form of
    constant-``rp`` per-cycle updates, so it is valid exactly up to the
    next ``ParamSchedule`` boundary — the engine mins that boundary into
    the horizon, guaranteeing no skip outlives the segment this bound was
    computed under. The Pallas backend has a packed-ABI twin
    (``repro.kernels.bank_fsm``) that must agree bank-for-bank — the
    kernel tests enforce it.
    """
    st = bank.st
    in_wait = wait_mask(st)
    is_idle = st == S_IDLE
    is_sref = st == S_SREF
    refresh_in = bank.refresh_due - rp.tRFC - cycle
    sref_in = rp.sref_idle_cycles - 1 - bank.idle_ctr
    bound = jnp.zeros_like(st)
    bound = jnp.where(in_wait, bank.timer - 1, bound)
    bound = jnp.where(is_idle, jnp.minimum(refresh_in, sref_in), bound)
    bound = jnp.where(is_sref, EVENT_INF, bound)
    return bound.astype(jnp.int32)


def is_bid_state(st: Array) -> Array:
    """bool[B]: the state bids an ACT, a column command or a PRE."""
    return (st == S_ACT_ISSUE) | (st == S_RW_ISSUE) | (st == S_PRE_ISSUE)


def jedec_bank_update(rp: RuntimeParams, bank: BankState, st2: Array,
                      grant: Array, cycle: Array) -> Tuple[Array, Array]:
    """The ``"jedec"`` bank registers after one edge: ``(pre_ready,
    bid_since)``. A granted ACT sets the precharge gate to ``cycle +
    tRAS``; a granted RD or WR raises it to ``cycle + tRTP`` or ``cycle +
    tWTP``. ``bid_since`` moves to ``cycle + 1`` when the edge enters a
    bidding state (``st2``) the bank was not in."""
    st = bank.st
    col = grant & (st == S_RW_ISSUE)
    col_gate = cycle + jnp.where(bank.cur_write == 1, rp.tWTP, rp.tRTP)
    pre_ready = jnp.where(col, jnp.maximum(bank.pre_ready, col_gate),
                          bank.pre_ready)
    pre_ready = jnp.where(grant & (st == S_ACT_ISSUE), cycle + rp.tRAS,
                          pre_ready)
    bid_since = jnp.where(is_bid_state(st2) & (st2 != st), cycle + 1,
                          bank.bid_since)
    return pre_ready.astype(jnp.int32), bid_since.astype(jnp.int32)


def compute_bids(st: Array, cur_write: Array) -> Tuple[Array, Array]:
    """Current-state command bids for the shared command bus.

    Returns (bids bool[B], cmds int32[B]); cmds is CMD_NOP where not bidding.
    """
    cmd = jnp.full_like(st, CMD_NOP)
    cmd = jnp.where(st == S_ACT_ISSUE, CMD_ACT, cmd)
    rw = jnp.where(cur_write == 1, CMD_WR, CMD_RD)
    cmd = jnp.where(st == S_RW_ISSUE, rw, cmd)
    cmd = jnp.where(st == S_PRE_ISSUE, CMD_PRE, cmd)
    cmd = jnp.where(st == S_REF_ISSUE, CMD_REF, cmd)
    cmd = jnp.where(st == S_SREF_ISSUE, CMD_SREF_ENTER, cmd)
    cmd = jnp.where(st == S_SREF_EXIT_ISSUE, CMD_SREF_EXIT, cmd)
    return cmd != CMD_NOP, cmd


def fsm_update(
    topo: Topology,
    rp: RuntimeParams,
    bank: BankState,
    grant: Array,           # bool[B] command-bus grant (timing-checked)
    resp_accept: Array,     # bool[B] response arbiter accepted our token
    queue_nonempty: Array,  # bool[B] local bank queue has a request
    pop_item: Array,        # [B, 4] head items (addr, is_write, data, id)
    cycle: Array,           # scalar int32
) -> Tuple[BankState, FsmOutputs]:
    """One synchronous clock edge for all bank FSMs (pure, branchless).

    ``rp.page_policy`` is a traced flag: the open-page deviations are merged
    in with ``jnp.where`` masks gated on ``is_open``, so closed- and
    open-page lanes share one compiled program and each reproduces the
    original per-policy semantics bit-for-bit.
    """
    is_open = jnp.asarray(rp.page_policy) == PAGE_OPEN  # traced scalar
    st, timer = bank.st, bank.timer
    open_row = bank.open_row
    pending = bank.pending

    refresh_needed = cycle >= (bank.refresh_due - rp.tRFC)

    # ---- WAIT states: tick timers, transition on expiry -------------------
    in_wait = wait_mask(st)
    timer2 = jnp.where(in_wait, jnp.maximum(timer - 1, 0), timer)
    expired = in_wait & (timer2 == 0)

    nxt = st
    nxt = jnp.where(expired & (st == S_ACT_WAIT), S_RW_ISSUE, nxt)
    # activation opens the row (tracked in both modes; used by open mode)
    open_row = jnp.where(expired & (st == S_ACT_WAIT),
                         row_of(topo, bank.cur_addr), open_row)
    # RW_WAIT expiry: open page responds directly, closed page precharges
    nxt = jnp.where(expired & (st == S_RW_WAIT),
                    jnp.where(is_open, S_RESP_PEND, S_PRE_ISSUE), nxt)
    # PRE_WAIT expiry: closed page responds; open page dispatches on the
    # pending code latched when the precharge was scheduled
    pre_done = expired & (st == S_PRE_WAIT)
    nxt = jnp.where(pre_done & ~is_open, S_RESP_PEND, nxt)
    nxt = jnp.where(pre_done & is_open & (pending == P_RW), S_ACT_ISSUE, nxt)
    nxt = jnp.where(pre_done & is_open & (pending == P_REF), S_REF_ISSUE, nxt)
    nxt = jnp.where(pre_done & is_open & (pending == P_SREF), S_SREF_ISSUE, nxt)
    open_row = jnp.where(pre_done, -1, open_row)
    pending = jnp.where(pre_done, P_NONE, pending)
    nxt = jnp.where(expired & (st == S_REF_WAIT), S_IDLE, nxt)
    nxt = jnp.where(expired & (st == S_SREF_EXIT_WAIT), S_IDLE, nxt)
    rw_done = expired & (st == S_RW_WAIT)
    ref_done = expired & (st == S_REF_WAIT)

    # ---- ISSUE states: on grant, enter the corresponding WAIT -------------
    is_wr = bank.cur_write == 1
    act_dur = jnp.where(is_wr, rp.tRCDWR, rp.tRCDRD).astype(jnp.int32)
    nxt = jnp.where(grant & (st == S_ACT_ISSUE), S_ACT_WAIT, nxt)
    timer2 = jnp.where(grant & (st == S_ACT_ISSUE), act_dur, timer2)
    nxt = jnp.where(grant & (st == S_RW_ISSUE), S_RW_WAIT, nxt)
    timer2 = jnp.where(grant & (st == S_RW_ISSUE), rp.tCL, timer2)
    nxt = jnp.where(grant & (st == S_PRE_ISSUE), S_PRE_WAIT, nxt)
    timer2 = jnp.where(grant & (st == S_PRE_ISSUE), rp.tRP, timer2)
    nxt = jnp.where(grant & (st == S_REF_ISSUE), S_REF_WAIT, nxt)
    timer2 = jnp.where(grant & (st == S_REF_ISSUE), rp.tRFC, timer2)
    nxt = jnp.where(grant & (st == S_SREF_ISSUE), S_SREF, nxt)
    nxt = jnp.where(grant & (st == S_SREF_EXIT_ISSUE), S_SREF_EXIT_WAIT, nxt)
    timer2 = jnp.where(grant & (st == S_SREF_EXIT_ISSUE), rp.tXS, timer2)

    # ---- RESP_PEND: drained by the response arbiter ------------------------
    completed = resp_accept & (st == S_RESP_PEND)
    nxt = jnp.where(completed, S_IDLE, nxt)

    # ---- IDLE: refresh > new request > self-refresh countdown --------------
    idle = st == S_IDLE
    row_open = open_row >= 0
    go_ref = idle & refresh_needed
    # open page with a row open must precharge before refreshing
    ref_pre = is_open & row_open
    nxt = jnp.where(go_ref, jnp.where(ref_pre, S_PRE_ISSUE, S_REF_ISSUE), nxt)
    pending = jnp.where(go_ref & ref_pre, P_REF, pending)

    want_pop = idle & ~refresh_needed & queue_nonempty
    pop_row = row_of(topo, pop_item[:, 0])
    hit = is_open & want_pop & row_open & (open_row == pop_row)
    conflict = is_open & want_pop & row_open & (open_row != pop_row)
    # default: activate (closed page always; open page when no row is open)
    nxt = jnp.where(want_pop, S_ACT_ISSUE, nxt)
    nxt = jnp.where(hit, S_RW_ISSUE, nxt)          # row hit: CAS only
    nxt = jnp.where(conflict, S_PRE_ISSUE, nxt)    # conflict: close first
    pending = jnp.where(conflict, P_RW, pending)

    truly_idle = idle & ~refresh_needed & ~queue_nonempty
    idle_ctr2 = jnp.where(truly_idle, bank.idle_ctr + 1, jnp.zeros_like(bank.idle_ctr))
    go_sref = truly_idle & (idle_ctr2 >= rp.sref_idle_cycles)
    sref_pre = is_open & row_open
    nxt = jnp.where(go_sref,
                    jnp.where(sref_pre, S_PRE_ISSUE, S_SREF_ISSUE), nxt)
    pending = jnp.where(go_sref & sref_pre, P_SREF, pending)

    # ---- SREF: wake on pending work ----------------------------------------
    wake = (st == S_SREF) & queue_nonempty
    nxt = jnp.where(wake, S_SREF_EXIT_ISSUE, nxt)

    # ---- refresh bookkeeping ------------------------------------------------
    refresh_due2 = jnp.where(ref_done, bank.refresh_due + rp.tREFI, bank.refresh_due)
    # Self-refresh internally maintains the cells: push the deadline forward.
    exiting = expired & (st == S_SREF_EXIT_WAIT)
    refresh_due2 = jnp.where(exiting, cycle + rp.tREFI, refresh_due2)

    # ---- latch popped request -------------------------------------------------
    cur_addr = jnp.where(want_pop, pop_item[:, 0], bank.cur_addr)
    cur_write = jnp.where(want_pop, pop_item[:, 1], bank.cur_write)
    cur_data = jnp.where(want_pop, pop_item[:, 2], bank.cur_data)
    cur_id = jnp.where(want_pop, pop_item[:, 3], bank.cur_id)

    jedec = {}
    if bank.pre_ready is not None:
        pre_ready, bid_since = jedec_bank_update(
            rp, bank, nxt.astype(jnp.int32), grant, cycle)
        jedec = dict(pre_ready=pre_ready, bid_since=bid_since)
    new = BankState(
        st=nxt.astype(jnp.int32),
        timer=timer2.astype(jnp.int32),
        idle_ctr=idle_ctr2.astype(jnp.int32),
        refresh_due=refresh_due2.astype(jnp.int32),
        cur_addr=cur_addr,
        cur_write=cur_write,
        cur_data=cur_data,
        cur_id=cur_id,
        open_row=open_row.astype(jnp.int32),
        pending=pending.astype(jnp.int32),
        **jedec,
    )
    outs = FsmOutputs(
        want_pop=want_pop,
        rw_done=rw_done,
        completed=completed,
        started=want_pop,
    )
    return new, outs
