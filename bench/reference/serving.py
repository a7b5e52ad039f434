"""The serving reference: the closed loop on the per-cycle circuit.

A frozen copy of the continuous-batching scheduler and the KV pager
(``repro.serving.scheduler``, ``repro.serving.kv_pager``) and of the tier
placement maps (``repro.traces.llm_workload.dram_words`` /
``cxl_words``), closed over :class:`Session`, a windowed session on the
per-cycle reference circuit (:mod:`bench.reference.simulator`).
:func:`run_serving` is the plain twin of one lane of the program's
``run_serving_batched``: the same requests, scheduler seed, window and
cycle cap give the same emissions, records and counters.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, List, Optional, Set

import jax.numpy as jnp
import numpy as np

from bench.reference.simulator import (
    SimResult,
    Trace,
    as_schedule,
    init_state,
    run_cycles,
)

_PAD_T = 0x3FFFFFFF  # arrival time of an unfilled slot: never due


def dram_words(idx, interleave_log2: int, cxl_frac_log2: int):
    """Word address of the ``idx``-th word of the *DRAM-resident* sequential
    space under block placement (``repro.core.dram_model.tier_select``):
    addresses are split into ``2^interleave_log2``-word blocks and the CXL
    expander owns the all-ones residue of every ``2^cxl_frac_log2`` blocks,
    so a DRAM stream walks the remaining ``2^k - 1`` of each group.
    Vectorized numpy; inverse of the placement decode (every returned
    address satisfies ``tier_select == False``)."""
    idx = np.asarray(idx, np.int64)
    il, k = interleave_log2, cxl_frac_log2
    m = (1 << k) - 1  # DRAM blocks per group
    blk = idx >> il
    off = idx & ((1 << il) - 1)
    phys = (blk // m) * (1 << k) + (blk % m)
    return (phys << il) | off


def cxl_words(idx, interleave_log2: int, cxl_frac_log2: int):
    """Word address of the ``idx``-th word of the *CXL-resident* sequential
    space: the all-ones block residue of every ``2^cxl_frac_log2``-block
    group (``tier_select == True``). Vectorized numpy twin of
    :func:`dram_words`."""
    idx = np.asarray(idx, np.int64)
    il, k = interleave_log2, cxl_frac_log2
    blk = idx >> il
    off = idx & ((1 << il) - 1)
    phys = (blk << k) | ((1 << k) - 1)
    return (phys << il) | off



@dataclasses.dataclass(frozen=True)
class PageState:
    """Immutable pool-occupancy snapshot (the MaxText ``page_state``
    threading idiom): the scheduler reads this to gate admission."""

    num_blocks: int
    free_blocks: int
    used_blocks: int
    sequences: int

    @property
    def occupancy(self) -> float:
        return self.used_blocks / max(self.num_blocks, 1)


class KVPager:
    """Block-granular KV-cache manager for one device's KV pool.

    ``block_words`` words per block, ``words_per_token`` KV words appended
    per generated token. ``tiered=True`` routes block addresses through
    the DRAM/CXL placement maps (``interleave_log2`` / ``cxl_frac_log2``
    must then match the simulated lane's placement flags).
    """

    def __init__(self, num_blocks: int = 64, block_words: int = 256,
                 words_per_token: int = 32, *, hot_blocks: int = 2,
                 tiered: bool = False, interleave_log2: int = 6,
                 cxl_frac_log2: int = 1, kv_base: int = 1 << 22,
                 addr_mask: int = 0x3FFFFFFF):
        if block_words % words_per_token:
            raise ValueError("block_words must be a words_per_token multiple")
        self.num_blocks = num_blocks
        self.block_words = block_words
        self.words_per_token = words_per_token
        self.hot_blocks = max(1, hot_blocks)
        self.tiered = tiered
        self.interleave_log2 = interleave_log2
        self.cxl_frac_log2 = cxl_frac_log2
        self.kv_base = kv_base
        self.addr_mask = addr_mask
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._chains: Dict[int, List[int]] = {}
        self._fill: Dict[int, int] = {}  # words filled in the tail block

    # ---- occupancy ---------------------------------------------------------

    def page_state(self) -> PageState:
        used = self.num_blocks - len(self._free)
        return PageState(num_blocks=self.num_blocks,
                         free_blocks=len(self._free), used_blocks=used,
                         sequences=len(self._chains))

    def blocks_for_tokens(self, tokens: int) -> int:
        words = tokens * self.words_per_token
        return -(-words // self.block_words)

    def can_admit(self, prompt_tokens: int) -> bool:
        """Enough free blocks to hold the prompt's KV plus one growth
        block for the first generated token?"""
        return (self.blocks_for_tokens(prompt_tokens) + 1
                <= len(self._free))

    # ---- sequence lifecycle ------------------------------------------------

    def admit(self, rid: int) -> None:
        if rid in self._chains:
            raise ValueError(f"sequence {rid} already admitted")
        self._chains[rid] = []
        self._fill[rid] = 0

    def free_seq(self, rid: int) -> None:
        """Sequence-boundary eviction: the whole chain returns to the
        pool."""
        for bid in self._chains.pop(rid):
            self._free.append(bid)
        self._fill.pop(rid)

    # ---- address generation ------------------------------------------------

    def append_addrs(self, rid: int, tokens: int = 1) -> np.ndarray:
        """Word addresses of ``tokens`` new tokens' KV writes at the
        sequence tail, allocating blocks as the tail fills. Raises if the
        pool is dry — schedulers gate on :meth:`can_admit` /
        :meth:`page_state` first. Vectorized: one block-sized chunk per
        allocation instead of a per-word Python loop (same addresses)."""
        chain = self._chains[rid]
        remaining = tokens * self.words_per_token
        chunks = []
        while remaining:
            if not chain or self._fill[rid] == self.block_words:
                if not self._free:
                    raise RuntimeError(
                        f"KV pool exhausted ({self.num_blocks} blocks); "
                        "admission must gate on can_admit()")
                chain.append(self._free.pop())
                self._fill[rid] = 0
            take = min(remaining, self.block_words - self._fill[rid])
            # the tail block is by definition inside the hot window
            chunks.append(self.kv_base + chain[-1] * self.block_words
                          + self._fill[rid]
                          + np.arange(take, dtype=np.int64))
            self._fill[rid] += take
            remaining -= take
        idx = (np.concatenate(chunks) if chunks
               else np.zeros(0, np.int64))
        if self.tiered:
            idx = np.asarray(dram_words(idx, self.interleave_log2,
                                        self.cxl_frac_log2), np.int64)
        return idx & self.addr_mask

    def gather_addrs(self, rid: int, n: int,
                     rng: np.random.Generator) -> np.ndarray:
        """Word addresses of an ``n``-read attention gather over the
        sequence's KV: recency-weighted — most reads hit the hot tail
        window (DRAM on tiered topologies), the rest the demoted cold
        blocks (CXL). Vectorized: the hot/cold choices, block positions
        and in-block offsets are batched draws (still deterministic per
        ``rng`` state)."""
        chain = self._chains[rid]
        if not chain:
            return np.zeros(0, np.int64)
        n_chain = len(chain)
        hot_lo = max(0, n_chain - self.hot_blocks)
        if n_chain > self.hot_blocks:
            cold = rng.random(n) < 0.25
            pos = np.where(cold,
                           rng.integers(0, n_chain - self.hot_blocks,
                                        size=n),
                           rng.integers(hot_lo, n_chain, size=n))
        else:
            pos = rng.integers(hot_lo, n_chain, size=n)
        limit = np.where(pos == n_chain - 1,
                         max(self._fill[rid], 1), self.block_words)
        off = (rng.random(n) * limit).astype(np.int64)
        idx = (self.kv_base
               + np.asarray(chain, np.int64)[pos] * self.block_words + off)
        if self.tiered:
            hot = pos >= n_chain - self.hot_blocks
            idx = np.where(
                hot,
                np.asarray(dram_words(idx, self.interleave_log2,
                                      self.cxl_frac_log2), np.int64),
                np.asarray(cxl_words(idx, self.interleave_log2,
                                     self.cxl_frac_log2), np.int64))
        return idx & self.addr_mask


@dataclasses.dataclass
class ServingConfig:
    """Scheduler knobs (memory-side; model shapes are abstracted into
    reads/writes per token)."""

    max_batch: int = 8                 # admitted-batch hard cap
    weight_reads_per_token: int = 8    # sequential weight-shard reads/step
    kv_reads_per_token: int = 4        # KV gather reads per decode step
    prefill_tokens_per_step: int = 8   # prompt tokens written per prefill step
    occupancy_high: float = 0.5        # reqQueue high-water fraction (AIMD)
    stall_high: float = 0.34           # stalled-sequence fraction high-water
    additive_increase: float = 1.0
    multiplicative_decrease: float = 0.5


@dataclasses.dataclass
class _SeqState:
    req: Request
    joined: int
    phase: str = "prefill"             # "prefill" -> "decode"
    prefill_done: int = 0
    decode_done: int = 0
    outstanding: Set[int] = dataclasses.field(default_factory=set)
    last_complete: int = -1
    first_token: int = -1
    done_at: int = -1


class ContinuousBatchScheduler:
    """See the module docstring. ``queue_limit`` is the simulated
    reqQueue's runtime depth (the AIMD high-water reference)."""

    def __init__(self, cfg: ServingConfig, pager: KVPager,
                 requests: List[Request], queue_limit: int, seed: int = 0):
        self.cfg = cfg
        self.pager = pager
        self.queue_limit = max(int(queue_limit), 1)
        self.waiting = deque(sorted(requests, key=lambda r: r.arrival))
        self.running: Dict[int, _SeqState] = {}
        self.target = float(cfg.max_batch)
        self.admitted_batch: List[int] = []
        self.batch_target: List[float] = []
        self.finished: List[_SeqState] = []
        self.tokens = 0
        self._rng = np.random.default_rng(seed)
        self._owner: Dict[int, int] = {}   # trace slot -> rid
        self._next_slot = 0
        self._wcursor = 0                  # sequential weight-stream cursor
        self._blocked_seen = 0
        self._waited: Set[int] = set()  # rids that emitted nothing all window
        self._tiered = pager.tiered

    # ---- emission ----------------------------------------------------------

    def _weight_addrs(self, n: int) -> List[int]:
        idx = (self._wcursor + np.arange(n)) % (1 << 21)
        self._wcursor += n
        if self._tiered:  # weights always stay DRAM-resident
            idx = dram_words(idx, self.pager.interleave_log2,
                             self.pager.cxl_frac_log2)
        return [int(a) & 0x3FFFFFFF for a in idx]

    def _step_requests(self, s: _SeqState):
        """(addr, is_write) list of the sequence's next step, advancing its
        phase bookkeeping. The step is emitted atomically or not at all."""
        c = self.cfg
        reqs = []
        if s.phase == "prefill":
            tokens = min(c.prefill_tokens_per_step,
                         s.req.prompt_tokens - s.prefill_done)
            for a in self._weight_addrs(c.weight_reads_per_token):
                reqs.append((a, 0))
            for a in self.pager.append_addrs(s.req.rid, tokens):
                reqs.append((a, 1))
            s.prefill_done += tokens
            if s.prefill_done >= s.req.prompt_tokens:
                s.phase = "decode"
        else:
            for a in self._weight_addrs(c.weight_reads_per_token):
                reqs.append((a, 0))
            for a in self.pager.gather_addrs(s.req.rid, c.kv_reads_per_token,
                                             self._rng):
                reqs.append((a, 0))
            for a in self.pager.append_addrs(s.req.rid, 1):
                reqs.append((a, 1))
        return reqs

    def plan_window(self, t0: int, t1: int):
        """Admissions + one step per ready sequence, as (t, addr, is_write)
        arrival arrays inside ``[t0, t1)`` — or ``None`` when the window
        emits nothing. Feed the result to ``session.advance``."""
        # join at sequence boundaries: open slots only (nothing preempts)
        while (self.waiting and self.waiting[0].arrival <= t0
               and len(self.running) < min(int(self.target),
                                           self.cfg.max_batch)
               and self.pager.can_admit(self.waiting[0].prompt_tokens)):
            req = self.waiting.popleft()
            self.pager.admit(req.rid)
            self.running[req.rid] = _SeqState(req=req, joined=t0)

        budget = t1 - t0
        streams = []
        self._waited = set()
        for s in self.running.values():
            if s.outstanding:
                # previous step still in the memory system: if it is STILL
                # there when this window closes, the step outlived a full
                # window — the persistent-stall backpressure signal
                self._waited.add(s.req.rid)
                continue
            need = (self.cfg.weight_reads_per_token
                    + (self.cfg.kv_reads_per_token + self.pager.words_per_token
                       if s.phase == "decode"
                       else min(self.cfg.prefill_tokens_per_step,
                                s.req.prompt_tokens - s.prefill_done)
                       * self.pager.words_per_token))
            if need > budget:
                continue  # deferred: front-end bandwidth exhausted
            budget -= need
            streams.append((s, self._step_requests(s)))

        self.admitted_batch.append(len(self.running))
        self.batch_target.append(self.target)
        if not streams:
            return None

        # round-robin interleave across sequences, one request per cycle
        ts, addrs, writes = [], [], []
        t = t0
        queues = deque((s, deque(reqs)) for s, reqs in streams)
        while queues:
            s, q = queues.popleft()
            a, w = q.popleft()
            slot = self._next_slot
            self._next_slot += 1
            self._owner[slot] = s.req.rid
            s.outstanding.add(slot)
            ts.append(t)
            addrs.append(a)
            writes.append(w)
            t += 1
            if q:
                queues.append((s, q))
        return (np.asarray(ts, np.int64), np.asarray(addrs, np.int64),
                np.asarray(writes, np.int64))

    # ---- feedback ----------------------------------------------------------

    def observe(self, report: WindowReport) -> None:
        """Fold one window's completions and occupancy back into the
        batch: finished steps unblock their sequences, finished sequences
        leave (freeing their KV blocks), and the AIMD target reacts to
        memory backpressure."""
        for slot, at in zip(report.completed_ids, report.completed_at):
            rid = self._owner.pop(int(slot))
            s = self.running.get(rid)
            if s is None:
                continue
            s.outstanding.discard(int(slot))
            s.last_complete = max(s.last_complete, int(at))
            if not s.outstanding:
                if s.phase == "decode":
                    s.decode_done += 1
                    self.tokens += 1
                    if s.first_token < 0:
                        s.first_token = s.last_complete
                    if s.decode_done >= s.req.decode_tokens:
                        s.done_at = s.last_complete
                        self.pager.free_seq(rid)
                        self.finished.append(self.running.pop(rid))

        blocked_new = report.blocked_arrival - self._blocked_seen
        self._blocked_seen = report.blocked_arrival
        stalled = sum(1 for rid in self._waited
                      if rid in self.running and self.running[rid].outstanding)
        pressured = (stalled > self.cfg.stall_high
                     * max(len(self.running), 1)
                     or report.req_q_len > self.cfg.occupancy_high
                     * self.queue_limit
                     or blocked_new > 0)
        if pressured:
            self.target = max(1.0,
                              self.target * self.cfg.multiplicative_decrease)
        else:
            self.target = min(float(self.cfg.max_batch),
                              self.target + self.cfg.additive_increase)

    def idle(self) -> bool:
        return not self.running and not self.waiting


@dataclasses.dataclass
class WindowReport:
    """The fields of one window that the scheduler reads."""

    completed_ids: np.ndarray
    completed_at: np.ndarray
    req_q_len: int
    blocked_arrival: int


class Session:
    """A fixed-capacity arrival buffer and a carried per-cycle state."""

    def __init__(self, cfg, capacity: int, params=None):
        self.cfg = cfg
        self.topo = cfg.topology()
        self.sched = as_schedule(cfg.runtime() if params is None else params)
        self.capacity = int(capacity)
        self.t = np.full((self.capacity,), _PAD_T, np.int32)
        self.addr = np.zeros((self.capacity,), np.int32)
        self.is_write = np.zeros((self.capacity,), np.int32)
        self.n = 0
        self.cycle = 0
        self.state = init_state(self.topo, self.sched, self.capacity,
                                jnp.int32(cfg.queue_size),
                                jnp.int32(cfg.resp_queue_size))

    def advance(self, window: int, arrivals=None) -> WindowReport:
        if arrivals is not None:
            t, addr, wr = (np.asarray(a, np.int64) for a in arrivals)
            if self.n + t.size > self.capacity:
                raise ValueError(f"{self.n + t.size} arrivals overflow the "
                                 f"capacity {self.capacity}")
            sl = slice(self.n, self.n + t.size)
            self.t[sl] = t
            self.addr[sl] = addr & 0x3FFFFFFF
            self.is_write[sl] = wr
            self.n += t.size
        t0, t1 = self.cycle, self.cycle + int(window)
        self.state = run_cycles(self.topo, self.sched, self._trace(),
                                self.state, t0, t1)
        self.cycle = t1
        st = self.state
        done = np.asarray(st.t_complete)[: self.n]
        ids = np.nonzero((done >= t0) & (done < t1))[0].astype(np.int64)
        return WindowReport(completed_ids=ids, completed_at=done[ids],
                            req_q_len=int(st.req_q.count),
                            blocked_arrival=int(st.blocked_arrival))

    def _trace(self) -> Trace:
        return Trace(t=jnp.asarray(self.t), addr=jnp.asarray(self.addr),
                     is_write=jnp.asarray(self.is_write),
                     wdata=jnp.zeros((self.capacity,), jnp.int32))

    def result(self) -> SimResult:
        n, st = self.n, self.state
        return SimResult(
            cfg=self.cfg, num_cycles=self.cycle,
            t_intended=self.t[:n].copy(), is_write=self.is_write[:n].copy(),
            t_admit=np.asarray(st.t_admit)[:n],
            t_dispatch=np.asarray(st.t_dispatch)[:n],
            t_start=np.asarray(st.t_start)[:n],
            t_complete=np.asarray(st.t_complete)[:n],
            rdata=np.asarray(st.rdata)[:n],
            counters={k: np.asarray(v) for k, v in st.counters.items()},
            blocked_arrival=int(st.blocked_arrival),
            blocked_dispatch=int(st.blocked_dispatch))


def run_serving(cfg, requests, serving: ServingConfig, *, params,
                window_cycles: int, capacity: int, max_cycles: int,
                seed: int, end_cycle: Optional[int] = None):
    """One closed loop until it drains past its last arrival or reaches
    ``max_cycles``; then, with nothing more emitted, on to ``end_cycle``
    (the cycle at which a lane-batched run stopped). Returns ``(summary,
    session)``: the summary holds the fields of the program's
    ``ServingResult`` that the comparison reads."""
    pager = KVPager(tiered=cfg.tiers > 1,
                    interleave_log2=cfg.tier_interleave_log2,
                    cxl_frac_log2=cfg.tier_cxl_frac_log2)
    session = Session(cfg, capacity, params)
    sched = ContinuousBatchScheduler(serving, pager, requests,
                                     queue_limit=cfg.queue_size, seed=seed)
    last_arrival = max((r.arrival for r in requests), default=0)
    while session.cycle < max_cycles:
        if sched.idle() and session.cycle > last_arrival:
            break
        t0 = session.cycle
        arrivals = sched.plan_window(t0, t0 + window_cycles)
        sched.observe(session.advance(window_cycles, arrivals))
    cycles = session.cycle
    if end_cycle is not None and end_cycle > session.cycle:
        session.advance(end_cycle - session.cycle)
    done = [s for s in sched.finished if s.done_at >= 0]
    summary = dict(
        offered=len(requests), completed=len(done), tokens=sched.tokens,
        cycles=cycles, admitted_batch=list(sched.admitted_batch),
        batch_target=list(sched.batch_target),
        queueing=np.asarray([s.joined - s.req.arrival for s in done],
                            np.int64),
        service=np.asarray([s.done_at - s.joined for s in done], np.int64))
    return summary, session
