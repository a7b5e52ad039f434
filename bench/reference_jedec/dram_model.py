"""DRAM timing model (paper §5.5).

The paper's timing model is a mirror FSM controlled by the bank scheduler:
it holds each command in a timing-parameter state (tRCD, tRP, tRFC, ...)
and acks on expiry, while also enforcing the *rank-level* constraints the
scheduler cannot see locally (tRRDL, tFAW) plus column-bus turnarounds
(tCCDL, tWTR, tRTW).

Bank-level sequencing constraints (tRP before ACT, tRCD before RW) are
enforced structurally by the closed-page FSM: each WAIT state's duration is
the corresponding timing parameter, and the FSM cannot skip states — the
same "correct by construction" property the paper claims for RTL.

Under ``timing_model="jedec"`` (DDR4/DDR5 bank groups) the timing state
also holds ``[R, BG]`` last-ACT / last-RD / last-WR registers per bank
group: a command is held by the short (S) gaps against the last command
anywhere in its rank and by the long (L) gaps against the last one in its
own bank group, and a PRE by its bank's precharge gate (``pre_ready``,
kept by :mod:`bench.reference_jedec.bank_fsm`). The ``"table1"`` model carries none
of that state and traces today's ops.

State layout is vectorized: one entry per flattened rank for rank-scoped
registers, one per flattened bank for bank-scoped ones. Structure (rank and
bank counts, address decode) comes from the static :class:`Topology`; every
timing value comes from the traced :class:`RuntimeParams` pytree, so one
compiled program serves any Table-1 parameter point.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp
from jax import Array

from bench.reference_jedec.params import (
    CMD_ACT,
    CMD_PRE,
    CMD_RD,
    CMD_WR,
    RuntimeParams,
    Topology,
)

_NEG = jnp.int32(-(1 << 20))  # "long ago" initializer for last-command times


class TimingState(NamedTuple):
    """Rank-scoped DRAM timing registers, and under ``"jedec"`` the
    bank-group ones (None under ``"table1"``: no state, no ops)."""

    last_act: Array    # [R] cycle of most recent ACTIVATE per rank (tRRDL)
    act_win: Array     # [R, 4] cycles of the last four ACTIVATEs (tFAW)
    last_rd: Array     # [R] most recent READ column command
    last_wr: Array     # [R] most recent WRITE column command
    bg_act: Array = None   # [R, BG] most recent ACTIVATE per bank group
    bg_rd: Array = None    # [R, BG] most recent READ per bank group
    bg_wr: Array = None    # [R, BG] most recent WRITE per bank group

    @staticmethod
    def make(topo: Topology) -> "TimingState":
        r = topo.num_ranks
        groups = {}
        if topo.jedec:
            groups = {k: jnp.full((r, topo.bankgroups), _NEG, jnp.int32)
                      for k in ("bg_act", "bg_rd", "bg_wr")}
        return TimingState(
            last_act=jnp.full((r,), _NEG, jnp.int32),
            act_win=jnp.full((r, 4), _NEG, jnp.int32),
            last_rd=jnp.full((r,), _NEG, jnp.int32),
            last_wr=jnp.full((r,), _NEG, jnp.int32),
            **groups,
        )


def bank_to_rank(topo: Topology, bank_idx: Array) -> Array:
    """Map flattened bank index -> flattened rank index.

    Banks are flattened channel-major: ``bank = ((ch * R + rank) * BG + bg) * BA + ba``.
    """
    return bank_idx // topo.banks_per_rank


def jedec_windows(
    rp: RuntimeParams,
    timing: TimingState,
    cmd: Array,          # [B] int32 command each bank wants to issue
    rank_of_bank: Array,  # [B] int32
    group_of_bank: Array,  # [B] int32 rank * BG + bank group
    pre_ready: Array,    # [B] int32 each bank's precharge gate
) -> Tuple[Array, Array]:
    """The two halves of a bid's legal cycle under ``"jedec"``:
    ``(s_at, l_at)``, whose max is the legal cycle.

    ``s_at`` holds the windows against the rank (tRRDS and tFAW for ACT;
    tCCDS and tWTRS for RD; tCCDS and tRTW for WR) and a PRE's gate
    (``pre_ready``); ``l_at`` the same-bank-group windows (tRRDL; tCCDL and
    tWTR; tCCDL), ``_NEG`` for commands without one. Other commands
    (REF/SREF*) are legal since long ago in both."""
    la = timing.last_act[rank_of_bank]
    oldest_act = timing.act_win[rank_of_bank].min(axis=-1)
    lr = timing.last_rd[rank_of_bank]
    lw = timing.last_wr[rank_of_bank]
    gla = timing.bg_act.reshape(-1)[group_of_bank]
    glr = timing.bg_rd.reshape(-1)[group_of_bank]
    glw = timing.bg_wr.reshape(-1)[group_of_bank]

    s_at = jnp.full_like(cmd, _NEG)
    s_at = jnp.where(cmd == CMD_ACT,
                     jnp.maximum(la + rp.tRRDS, oldest_act + rp.tFAW), s_at)
    s_at = jnp.where(cmd == CMD_RD,
                     jnp.maximum(lr + rp.tCCDS, lw + rp.tWTRS), s_at)
    s_at = jnp.where(cmd == CMD_WR,
                     jnp.maximum(lw + rp.tCCDS, lr + rp.tRTW), s_at)
    s_at = jnp.where(cmd == CMD_PRE, pre_ready, s_at)
    l_at = jnp.full_like(cmd, _NEG)
    l_at = jnp.where(cmd == CMD_ACT, gla + rp.tRRDL, l_at)
    l_at = jnp.where(cmd == CMD_RD,
                     jnp.maximum(glr + rp.tCCDL, glw + rp.tWTR), l_at)
    l_at = jnp.where(cmd == CMD_WR, glw + rp.tCCDL, l_at)
    return s_at.astype(jnp.int32), l_at.astype(jnp.int32)


def legal_issue_cycle(
    rp: RuntimeParams,
    timing: TimingState,
    cmd: Array,          # [B] int32 command each bank wants to issue
    rank_of_bank: Array,  # [B] int32
    group_of_bank: Array = None,  # [B] int32 ("jedec" only)
    pre_ready: Array = None,      # [B] int32 ("jedec" only)
) -> Array:
    """Earliest cycle at which each bank's bid command satisfies the rank
    constraints (tRRDL/tFAW for ACT, tCCDL/tWTR/tRTW for column commands).

    Returns int32[B] absolute cycles. Non-column, non-ACT commands
    (PRE/REF/SREF*) have no rank-level constraint here — their bank-level
    sequencing is structural — and report "legal since long ago" (``_NEG``).

    This is the ONE definition of command-bus readiness: the per-cycle
    stepper's :func:`bench.reference_jedec.simulator.issue_eligibility` grants on
    ``cycle >= legal_issue_cycle(...)``, and the event-horizon engine uses
    the same value as the "cycles until the queue head becomes issuable"
    bound — the two can never disagree.
    The windows only move when a command is granted (:func:`record_issue`),
    so between grants the returned cycle is a constant of the state *and
    the operating point*: ``rp`` is the params of the schedule segment
    governing the evaluation cycle (``ParamSchedule.params_at``), and the
    returned absolute cycle is only meaningful within that segment — a
    DVFS boundary re-prices every window, which is why the event-horizon
    engine caps skips at the next boundary and re-evaluates there.

    Under ``"jedec"`` (``timing.bg_act`` set) the legal cycle is the max
    of :func:`jedec_windows`: the S windows over the rank, the L windows
    over the bank's group, and for PRE the bank's ``pre_ready``.
    """
    if timing.bg_act is not None:
        s_at, l_at = jedec_windows(rp, timing, cmd, rank_of_bank,
                                   group_of_bank, pre_ready)
        return jnp.maximum(s_at, l_at)
    la = timing.last_act[rank_of_bank]           # [B]
    aw = timing.act_win[rank_of_bank]            # [B, 4]
    lr = timing.last_rd[rank_of_bank]
    lw = timing.last_wr[rank_of_bank]

    oldest_act = aw.min(axis=-1)
    act_at = jnp.maximum(la + rp.tRRDL, oldest_act + rp.tFAW)
    rd_at = jnp.maximum(lr + rp.tCCDL, lw + rp.tWTR)
    wr_at = jnp.maximum(lw + rp.tCCDL, lr + rp.tRTW)

    at = jnp.full_like(cmd, _NEG)
    at = jnp.where(cmd == CMD_ACT, act_at, at)
    at = jnp.where(cmd == CMD_RD, rd_at, at)
    at = jnp.where(cmd == CMD_WR, wr_at, at)
    return at.astype(jnp.int32)




def record_issue(
    timing: TimingState,
    cycle: Array,
    cmd: Array,        # scalar int32: the command granted this cycle (per channel
    rank: Array,       # scalar int32 flattened rank of the granted bank
    granted: Array,    # scalar bool
    group: Array = None,  # scalar int32 bank group within the rank ("jedec")
) -> TimingState:
    """Update rank registers after the arbiter grants one command (and
    under ``"jedec"`` the granted bank's group registers)."""
    is_act = granted & (cmd == CMD_ACT)
    is_rd = granted & (cmd == CMD_RD)
    is_wr = granted & (cmd == CMD_WR)

    last_act = jnp.where(
        is_act, timing.last_act.at[rank].set(cycle), timing.last_act
    )
    # tFAW window: replace the oldest entry with the new ACT time.
    win = timing.act_win[rank]
    oldest_slot = jnp.argmin(win)
    act_win = jnp.where(
        is_act, timing.act_win.at[rank, oldest_slot].set(cycle), timing.act_win
    )
    last_rd = jnp.where(is_rd, timing.last_rd.at[rank].set(cycle), timing.last_rd)
    last_wr = jnp.where(is_wr, timing.last_wr.at[rank].set(cycle), timing.last_wr)
    if timing.bg_act is None:
        return TimingState(last_act, act_win, last_rd, last_wr)

    def group_set(reg, hit):
        return jnp.where(hit, reg.at[rank, group].set(cycle), reg)

    return TimingState(last_act, act_win, last_rd, last_wr,
                       bg_act=group_set(timing.bg_act, is_act),
                       bg_rd=group_set(timing.bg_rd, is_rd),
                       bg_wr=group_set(timing.bg_wr, is_wr))


def wait_duration(rp: RuntimeParams, cmd: Array, is_write: Array) -> Array:
    """Duration of the WAIT state entered after a command is issued.

    ACT  -> tRCDRD / tRCDWR (activate-to-column delay, paper Table 1)
    RD/WR-> tCL (data return; documented addition)
    PRE  -> tRP
    REF  -> tRFC
    SREF_EXIT -> tXS

    Under a time-varying :class:`~bench.reference_jedec.params.ParamSchedule`, ``rp``
    is the operating point of the *grant* cycle: the duration is latched
    into the bank's timer at issue and counts down unchanged across
    schedule boundaries (in-flight commands complete at their issued
    timing).
    """
    from bench.reference_jedec.params import CMD_PRE, CMD_REF, CMD_SREF_ENTER, CMD_SREF_EXIT

    dur = jnp.zeros_like(cmd)
    act_dur = jnp.where(is_write, rp.tRCDWR, rp.tRCDRD)
    dur = jnp.where(cmd == CMD_ACT, act_dur, dur)
    dur = jnp.where((cmd == CMD_RD) | (cmd == CMD_WR), rp.tCL, dur)
    dur = jnp.where(cmd == CMD_PRE, rp.tRP, dur)
    dur = jnp.where(cmd == CMD_REF, rp.tRFC, dur)
    dur = jnp.where(cmd == CMD_SREF_ENTER, 1, dur)
    dur = jnp.where(cmd == CMD_SREF_EXIT, rp.tXS, dur)
    return dur


def tier_select(topo: Topology, addr: Array, rp: RuntimeParams) -> Array:
    """Host-side placement decode: which tier owns ``addr`` (bool, True =
    CXL). Addresses are split into ``2^tier_interleave_log2`` word blocks;
    the CXL expander owns 1 of every ``2^tier_cxl_frac_log2`` blocks (the
    all-ones residue), a DRAM:CXL capacity split of ``(2^k - 1):1``. Both
    flags are traced tier-uniform data, so placement is a sweep axis."""
    il = jnp.asarray(rp.tier_interleave_log2, jnp.int32).reshape(-1)[0]
    k = jnp.asarray(rp.tier_cxl_frac_log2, jnp.int32).reshape(-1)[0]
    frac_mask = (jnp.int32(1) << k) - 1
    return ((addr >> il) & frac_mask) == frac_mask


def decode_address(topo: Topology, addr: Array,
                   rp: RuntimeParams = None) -> Tuple[Array, Array, Array]:
    """Address -> (flat_bank, flat_rank, row), paper §5.2 fixed mapping.

    Low bits: {channel? no — paper: remaining|rank|bankgroup|bank}. We extend
    with channel above rank when channels > 1.

    Tiered topologies (``topo.tiers > 1``) remap the channel slice through
    the placement decode: CXL-owned interleave blocks (:func:`tier_select`)
    land on the ``cxl_channels`` channels above ``dram_channels``, the rest
    spread over the DRAM channels — the channel *bits* of the address pick
    the channel within the owning tier. Single-tier topologies never touch
    ``rp`` and keep the exact pre-tier decode graph.
    """
    ba = addr & (topo.banks_per_group - 1)
    bg = (addr >> topo.bank_bits) & (topo.bankgroups - 1)
    rk = (addr >> (topo.bank_bits + topo.bankgroup_bits)) & (topo.ranks - 1)
    ch = (addr >> (topo.bank_bits + topo.bankgroup_bits + topo.rank_bits)) & (
        topo.channels - 1
    )
    if topo.tiers > 1 and rp is not None:
        is_cxl = tier_select(topo, addr, rp)
        ch = jnp.where(is_cxl,
                       topo.dram_channels + (ch & (topo.cxl_channels - 1)),
                       ch & (topo.dram_channels - 1))
    flat_bank = ((ch * topo.ranks + rk) * topo.bankgroups + bg) * topo.banks_per_group + ba
    flat_rank = ch * topo.ranks + rk
    row = addr >> (topo.addr_low_bits + topo.column_bits)
    return flat_bank.astype(jnp.int32), flat_rank.astype(jnp.int32), row.astype(jnp.int32)
