"""Fused hot-loop kernel: ONE Pallas call per executed cycle.

The event-horizon engine executes few cycles, but each executed cycle used
to pay two ``pallas_call`` dispatches (the bank-FSM kernel and its
event-bound twin) plus XLA glue for the queue peeks, the response push and
both round-robin arbiters. This kernel fuses phases 3-7 of
``repro.core.simulator.cycle_step`` *and* the engine's
``_next_event`` bound into a single invocation:

  * command bids + rank timing legality (``issue_eligibility``),
  * the per-channel rotating-priority arbiters (``rr_arbiter_grouped``),
  * rank timing-window updates (``record_issue``, vectorized per-bank),
  * response arbitration + respQueue push with ready&valid gating,
  * the post-FSM bank-queue head/count pop bookkeeping (the head PEEK —
    one gather, ``BankedFifo.peek_valid`` — stays in glue and feeds the
    kernel as pop rows, exactly like the split FSM kernel's ABI),
  * the FSM clock edge itself (the *shared* ``_fsm_combinational``
    where-chain — the exact network the split kernel lowers),
  * the flow-through respQueue ack (``Fifo.pop`` on the post-push buffer),
  * the event-horizon bound at ``cycle + 1`` on the post-edge state (the
    shared ``_event_bound_combinational`` plus blocked-bid legality and
    the next-schedule-boundary cap).

Phases 1-2 (trace admission + dispatch, inherently scalar) and the
record/memory scatters stay in XLA glue (``repro.core.fused_step``); the
acceptance metric is pallas dispatches per executed cycle, which drops
from 2 to 1 with the remaining glue absorbed into the same jitted body.

The kernel is natively LANE-BATCHED: ``lanes`` independent sweeps (each
its own trace position, queues, schedule and arbiter pointers, all on the
engine's shared batch clock) share one dispatch per executed cycle — not
one per lane, which is what ``jax.vmap`` over a ``pallas_call`` would
serialize into via the grid.

Layout. Every per-bank quantity is an [L, B] block: lanes on sublanes,
banks on the lane axis. Per-lane scalars are [L, 1] columns. Nothing is
ever reshaped across the lane axis (Mosaic refuses such shape casts), so
every cross-bank reduction — both arbiters, the inert gate, the event
bound — is a lane-axis reduction of an [L, B] block; the per-channel
command arbiter masks each channel's static bank segment. The op count
is independent of the lane count.

ABI (all int32; L = lanes, B = banks per lane, Qr = resp capacity,
F = 4 request fields, S = schedule segments, T = schedule tiers,
NP = NUM_RUNTIME_PARAMS, C = channels):

  inputs   bank rows [23, L, B]: state 0-9 | qmeta 10-11 (head,count) |
           timing 12-18 (last_act, act_win0..3, last_rd, last_wr gathered
           per-bank) | pop 19-22 (head items; garbage where empty) —
           plus resp_buf [F, L, Qr] (field-major) | rp_mat [L, T*S*NP]
           (each lane's tier-major ``ParamSchedule.pack`` matrix,
           flattened) | bounds [L, S] | scal [L, 8+C] = (cycle,
           arrival_rel, horizon, req_count, resp_head, resp_count,
           resp_limit, resp_rr, cmd_rr[C]) per lane (cycle/horizon are
           the shared clock)
  outputs  bank rows [22, L, B]: new_state 0-9 | flags 10-12 | qmeta2
           13-14 | timing2 15-21 (rank-uniform; glue reduces back to [R])
           — plus resp_buf2 [F, L, Qr] | scal2 [L, 9+2C] = (delta,
           resp_rr2, resp_head2, resp_count2, ack_valid, fitem_addr,
           fitem_write, fitem_data, fitem_id, cmd_rr2[C], issued_cmd[C])
           per lane

The queue head PEEK (a gather the split path already does in glue) feeds
the kernel as 4 pop rows instead of shipping the whole queue buffer
through the ABI; the pop BOOKKEEPING stays in-kernel.

Bit-exactness against the unfused path is a structural property wherever
possible (the FSM edge and local event bound are the *same* functions the
split kernels call) and enforced by tests/test_kernels.py +
tests/test_engine_equivalence.py everywhere else (arbiters, timing
windows, queue ops, gate logic).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.params import (
    CMD_ACT,
    CMD_NOP,
    CMD_PRE,
    CMD_RD,
    CMD_REF,
    CMD_SREF_ENTER,
    CMD_SREF_EXIT,
    CMD_WR,
    NUM_RUNTIME_PARAMS,
    RP_INDEX,
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
    SCHEDULE_INF,
    Topology,
)
from repro.kernels.bank_fsm.bank_fsm import (
    _count_invocation,
    _event_bound_combinational,
    _fsm_combinational,
)

# plain int (no module-level jnp constants — see ops.py): the dram_model
# "legal since long ago" default
_NEG = -(1 << 20)

NUM_TIMING_ROWS = 7      # last_act, act_win0..3, last_rd, last_wr
NUM_BANK_ROWS_IN = 23    # state 10 + qmeta 2 + timing 7 + pop 4
NUM_BANK_ROWS_OUT = 22   # state 10 + flags 3 + qmeta 2 + timing 7
NUM_SCAL_IN = 8          # + channels
NUM_SCAL_OUT = 9         # + 2 * channels


def _compute_cmds(st, cur_write):
    """Lanewise :func:`repro.core.bank_fsm.compute_bids` (cmds only; a lane
    bids iff its cmd != CMD_NOP)."""
    cmd = jnp.full_like(st, CMD_NOP)
    cmd = jnp.where(st == S_ACT_ISSUE, CMD_ACT, cmd)
    rw = jnp.where(cur_write == 1, CMD_WR, CMD_RD)
    cmd = jnp.where(st == S_RW_ISSUE, rw, cmd)
    cmd = jnp.where(st == S_PRE_ISSUE, CMD_PRE, cmd)
    cmd = jnp.where(st == S_REF_ISSUE, CMD_REF, cmd)
    cmd = jnp.where(st == S_SREF_ISSUE, CMD_SREF_ENTER, cmd)
    cmd = jnp.where(st == S_SREF_EXIT_ISSUE, CMD_SREF_EXIT, cmd)
    return cmd


def _legal_at(rp, cmd, la, aw0, aw1, aw2, aw3, lr, lw):
    """Lanewise :func:`repro.core.dram_model.legal_issue_cycle` on the
    per-bank expanded timing rows."""
    oldest = jnp.minimum(jnp.minimum(aw0, aw1), jnp.minimum(aw2, aw3))
    act_at = jnp.maximum(la + rp("tRRDL"), oldest + rp("tFAW"))
    rd_at = jnp.maximum(lr + rp("tCCDL"), lw + rp("tWTR"))
    wr_at = jnp.maximum(lw + rp("tCCDL"), lr + rp("tRTW"))
    at = jnp.full_like(cmd, _NEG)
    at = jnp.where(cmd == CMD_ACT, act_at, at)
    at = jnp.where(cmd == CMD_RD, rd_at, at)
    at = jnp.where(cmd == CMD_WR, wr_at, at)
    return at.astype(jnp.int32)


def _resolve_rp_lanes(rp_ref, bnd_ref, cycle, lanes, width,
                      tier_split: int):
    """Per-lane in-kernel ParamSchedule resolution: serve ``rp(name)`` as
    an [L, width] block (lanes on sublanes, banks on lanes — the layout of
    every bank row the shared combinational networks consume).

    ``rp_ref`` is [L, T*S*NP]: each lane's own tier-major
    ``ParamSchedule.pack`` values matrix flattened on the lane axis, so
    parameter ``j`` of tier ``t`` in segment ``s`` is column
    ``(t*S + s)*NP + j`` — a static column slice. The active segment per
    lane is the last one whose start boundary is <= cycle (boundaries
    sorted; SCHEDULE_INF padding never activates), found branchlessly:
    count satisfied boundaries, then a select chain over the S static
    columns (exactly the one-hot row sum, without a reshape). S == 1 (the
    constant degenerate schedule) reads the column directly. A tier-stacked
    schedule (T = 2) selects per bank at the static ``tier_split``; a
    single-tier one serves every bank, as the jnp reference does. Accessed
    parameters are memoized so each broadcasts once per resolve."""
    s = bnd_ref.shape[1]
    tiers = rp_ref.shape[1] // (s * NUM_RUNTIME_PARAMS)
    seg = (jnp.sum((bnd_ref[...] <= cycle).astype(jnp.int32), axis=1,
                   keepdims=True) - 1) if s > 1 else None      # [L, 1]
    cache: Dict[str, jax.Array] = {}
    bi = (jax.lax.broadcasted_iota(jnp.int32, (lanes, width), 1)
          if tiers > 1 else None)

    def col(t, j):
        if s == 1:
            c = t * NUM_RUNTIME_PARAMS + j
            return rp_ref[:, c:c + 1]
        val = jnp.zeros((lanes, 1), jnp.int32)
        for k in range(s):
            c = (t * s + k) * NUM_RUNTIME_PARAMS + j
            val = jnp.where(seg == k, rp_ref[:, c:c + 1], val)
        return val

    def rp(name):
        if name not in cache:
            j = RP_INDEX[name]
            val = jnp.broadcast_to(col(0, j), (lanes, width))
            for t in range(1, tiers):
                # two tiers max (Topology.validate): one static threshold
                val = jnp.where(bi >= tier_split, col(t, j), val)
            cache[name] = val
        return cache[name]

    return rp


def _fused_kernel(topo: Topology, bank_ref, resp_ref, rp_ref, bnd_ref,
                  scal_ref, bank_out_ref, resp_out_ref, scal_out_ref):
    lanes = bank_ref.shape[1]
    b = topo.num_banks              # banks per lane
    shape = (lanes, b)
    nf = resp_ref.shape[0]          # request fields (4)
    qr = resp_ref.shape[2]          # resp queue capacity per lane
    q_cap = topo.queue_size         # bank queue capacity
    per = topo.banks_per_channel
    channels = topo.channels

    # ---- per-lane scalars: [L, 1] columns ----------------------------------
    def scol(k):
        return scal_ref[:, k:k + 1]

    cycle = scol(0)                 # shared batch clock (same in every lane)
    horizon = scol(2)
    arrival_rel = scol(1)
    req_count = scol(3)
    resp_head = scol(4)
    resp_count = scol(5)
    resp_limit = scol(6)
    resp_rr = scol(7)
    cmd_rr = [scol(NUM_SCAL_IN + c) for c in range(channels)]
    nxt = cycle + 1

    split = topo.tier_split_bank
    rp = _resolve_rp_lanes(rp_ref, bnd_ref, cycle, lanes, b, split)
    rp2 = _resolve_rp_lanes(rp_ref, bnd_ref, nxt, lanes, b, split)

    # ---- loads (one [23, L, B] operand; row map in the module docstring) ---
    rows = tuple(bank_ref[i] for i in range(10))
    st = rows[0]
    cur_addr, cur_write, cur_data, cur_id = rows[4], rows[5], rows[6], rows[7]
    qhead = bank_ref[10]
    qcount = bank_ref[11]
    la, aw0, aw1, aw2, aw3, lr, lw = (bank_ref[12 + i] for i in range(7))
    # head items peeked by glue (garbage where the queue is empty, exactly
    # like the unfused peek — the FSM masks on queue_nonempty)
    pop_rows = tuple(bank_ref[19 + f] for f in range(nf))
    queue_nonempty = qcount > 0

    # ---- phase 3: bids, legality, per-channel RR grant, record_issue -------
    cmds = _compute_cmds(st, cur_write)
    bids = cmds != CMD_NOP
    legal = _legal_at(rp, cmds, la, aw0, aw1, aw2, aw3, lr, lw)
    eligible = bids & (cycle >= legal)

    # per-channel arbitration on the [L, B] block: banks stay on the lane
    # axis, each channel is a static lane segment. The per-channel
    # reductions are masked lane reductions ([L, 1] results) broadcast
    # back over the channel's banks, so nothing is reshaped across the
    # lane axis (Mosaic cannot) and the op count is independent of the
    # lane count.
    bank = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    chan = bank // per
    wi = bank % per                 # bank index within its channel
    in_chan = [chan == c for c in range(channels)]

    def per_channel(vals):
        """[L, B] block holding each bank's channel's [L, 1] value."""
        out = jnp.broadcast_to(vals[0], shape)
        for c in range(1, channels):
            out = jnp.where(in_chan[c], vals[c], out)
        return out

    def chan_reduce(fn, x, fill):
        if channels == 1:
            return [fn(x, axis=1, keepdims=True)]
        return [fn(jnp.where(in_chan[c], x, fill), axis=1, keepdims=True)
                for c in range(channels)]

    ptr = per_channel(cmd_rr)
    rot = (wi - ptr) % per
    key = jnp.where(eligible, rot, per)
    m_c = chan_reduce(jnp.min, key, per)                        # C x [L, 1]
    grant = eligible & (rot == per_channel(m_c))
    g_i = grant.astype(jnp.int32)
    # CMD_NOP (0) where the channel granted nothing
    cmd_w_c = chan_reduce(jnp.sum, g_i * cmds, 0)
    cmd_rr2 = [jnp.where(m_c[c] < per, (cmd_rr[c] + m_c[c] + 1) % per,
                         cmd_rr[c]) for c in range(channels)]
    # record_issue, vectorized: every bank of the winner's rank holds the
    # same register value, so a masked elementwise update is the scalar
    # .at[rank] update broadcast per-bank (ranks are channel-disjoint)
    rank_in = wi // topo.banks_per_rank
    rank_w = per_channel(chan_reduce(jnp.sum, g_i * rank_in, 0))
    cmd_w = per_channel(cmd_w_c)
    any_g = per_channel(m_c) < per
    upd = rank_in == rank_w
    is_act = any_g & (cmd_w == CMD_ACT)
    is_rd = any_g & (cmd_w == CMD_RD)
    is_wr = any_g & (cmd_w == CMD_WR)
    la2 = jnp.where(is_act & upd, cycle, la)
    # tFAW window: replace the first-minimum slot (jnp.argmin ties to the
    # first occurrence; this select chain reproduces that exactly)
    awm = jnp.minimum(jnp.minimum(aw0, aw1), jnp.minimum(aw2, aw3))
    s0 = aw0 == awm
    s1 = (aw1 == awm) & ~s0
    s2 = (aw2 == awm) & ~s0 & ~s1
    s3 = ~s0 & ~s1 & ~s2
    hit_act = is_act & upd
    aw0_2 = jnp.where(hit_act & s0, cycle, aw0)
    aw1_2 = jnp.where(hit_act & s1, cycle, aw1)
    aw2_2 = jnp.where(hit_act & s2, cycle, aw2)
    aw3_2 = jnp.where(hit_act & s3, cycle, aw3)
    lr2 = jnp.where(is_rd & upd, cycle, lr)
    lw2 = jnp.where(is_wr & upd, cycle, lw)

    # ---- phase 4: response arbitration + respQueue push --------------------
    # resp_buf is field-major [F, L, Qr]: lanes on sublanes, slots on lanes
    resp_full = resp_count >= resp_limit                        # [L, 1]
    bids_r = (st == S_RESP_PEND) & ~resp_full
    rot_r = (bank - resp_rr) % b
    key_r = jnp.where(bids_r, rot_r, b)
    m_r = jnp.min(key_r, axis=1, keepdims=True)                 # [L, 1]
    any_resp = m_r < b
    accept = bids_r & (rot_r == m_r)
    resp_rr2 = jnp.where(any_resp, (resp_rr + m_r + 1) % b, resp_rr)
    a_i = accept.astype(jnp.int32)
    item = [jnp.sum(a_i * v, axis=1, keepdims=True)
            for v in (cur_addr, cur_write, cur_data, cur_id)]   # F x [L, 1]
    widx = (resp_head + resp_count) % qr                        # [L, 1]
    qi = jax.lax.broadcasted_iota(jnp.int32, (lanes, qr), 1)
    at_w = (qi == widx) & any_resp
    head_oh = qi == resp_head
    head_row = []
    for f in range(nf):
        old = resp_ref[f]                                       # [L, Qr]
        resp_out_ref[f] = jnp.where(at_w, item[f], old)
        head_row.append(jnp.sum(jnp.where(head_oh, old, 0), axis=1,
                                keepdims=True))
    resp_count1 = resp_count + any_resp.astype(jnp.int32)

    # ---- phase 5: FSM clock edge + bank-queue pop bookkeeping --------------
    new_rows, (want_pop, rw_done, completed) = _fsm_combinational(
        topo, rp, cycle, rows, grant, accept, queue_nonempty, pop_rows)
    wp = want_pop.astype(jnp.int32)
    qhead2 = (qhead + wp) % q_cap
    qcount2 = qcount - wp

    # ---- phase 7: flow-through respQueue ack (Fifo.pop post-push) ----------
    ack = resp_count1 > 0                                       # [L, 1]
    flow = any_resp & (widx == resp_head)
    fitem = [jnp.where(flow, item[f], head_row[f]) for f in range(nf)]
    resp_head2 = (resp_head + ack.astype(jnp.int32)) % qr
    resp_count2 = resp_count1 - ack.astype(jnp.int32)

    # ---- event-horizon bound at nxt on the post-edge state -----------------
    st2, timer2, idle2, rdue2 = new_rows[0], new_rows[1], new_rows[2], new_rows[3]
    cur_write2 = new_rows[5]
    local = _event_bound_combinational(rp2, nxt, st2, timer2, idle2, rdue2)
    cmds_n = _compute_cmds(st2, cur_write2)
    bids_n = cmds_n != CMD_NOP
    legal_n = _legal_at(rp2, cmds_n, la2, aw0_2, aw1_2, aw2_2, aw3_2, lr2,
                        lw2)
    eligible_n = bids_n & (nxt >= legal_n)
    blocked_n = bids_n & ~eligible_n
    # wait mask must match repro.core.bank_fsm.wait_mask exactly
    in_wait_n = ((st2 == S_ACT_WAIT) | (st2 == S_RW_WAIT)
                 | (st2 == S_PRE_WAIT) | (st2 == S_REF_WAIT)
                 | (st2 == S_SREF_EXIT_WAIT))
    idle_n = st2 == S_IDLE
    sref_n = st2 == S_SREF
    bq_valid_n = qcount2 > 0
    inert = in_wait_n | blocked_n | ((idle_n | sref_n) & ~bq_valid_n)
    gate = jnp.min(inert.astype(jnp.int32), axis=1, keepdims=True) == 1
    per_bank = jnp.min(jnp.where(blocked_n, legal_n - nxt, local), axis=1,
                       keepdims=True)
    # next operating-point boundary is an event (ParamSchedule.next_boundary)
    bnd = bnd_ref[...]
    nb = jnp.min(jnp.where(bnd > nxt, bnd, SCHEDULE_INF), axis=1,
                 keepdims=True)
    b_val = jnp.minimum(jnp.minimum(per_bank, arrival_rel), horizon - nxt)
    b_val = jnp.minimum(b_val, nb - nxt)
    maybe = (req_count == 0) & (resp_count2 == 0)
    delta = jnp.where(maybe & gate, jnp.maximum(b_val, 0), 0)   # [L, 1]

    # ---- stores (one [22, L, B] output; row map in the module docstring) ---
    outs = (list(new_rows)
            + [want_pop.astype(jnp.int32), rw_done.astype(jnp.int32),
               completed.astype(jnp.int32), qhead2, qcount2,
               la2, aw0_2, aw1_2, aw2_2, aw3_2, lr2, lw2])
    for i, row in enumerate(outs):
        bank_out_ref[i] = row
    # scalar outputs packed on the lane axis by a select chain (a lane
    # concatenate of [L, 1] columns is a relayout Mosaic refuses)
    cols = ([delta, resp_rr2, resp_head2, resp_count2, ack.astype(jnp.int32)]
            + fitem + cmd_rr2 + cmd_w_c)
    ko = jax.lax.broadcasted_iota(jnp.int32, scal_out_ref.shape, 1)
    acc = jnp.zeros(scal_out_ref.shape, jnp.int32)
    for k, v in enumerate(cols):
        acc = jnp.where(ko == k, v, acc)
    scal_out_ref[...] = acc


def fused_step_pallas(topo: Topology, bank_rows, resp_buf, rp_mat, bounds,
                      scal, interpret: bool):
    """Invoke the fused hot-loop kernel (whole-array blocks, no grid).

    All shape/ordering contracts are in the module docstring; the lane
    count L is ``bank_rows.shape[1]``. Returns ``(bank_rows2 [22, L, B],
    resp_buf2 [F, L, Qr], scal2 [L, 9+2C])``."""
    _count_invocation()
    assert bank_rows.shape[0] == NUM_BANK_ROWS_IN
    assert bank_rows.shape[2] == topo.num_banks, (
        f"bank width {bank_rows.shape[2]} != banks {topo.num_banks}")
    lanes = bank_rows.shape[1]
    kernel = functools.partial(_fused_kernel, topo)
    out_shape = [
        jax.ShapeDtypeStruct((NUM_BANK_ROWS_OUT,) + bank_rows.shape[1:],
                             jnp.int32),
        jax.ShapeDtypeStruct(resp_buf.shape, jnp.int32),
        jax.ShapeDtypeStruct((lanes, NUM_SCAL_OUT + 2 * topo.channels),
                             jnp.int32),
    ]
    return pl.pallas_call(kernel, out_shape=out_shape, interpret=interpret,
                          name="fused_fsm")(
        bank_rows, resp_buf, rp_mat, bounds, scal)
