"""Re-entrant windowed engine sessions: the closed-loop co-simulation API.

The batch engines in :mod:`repro.core.engine` keep the original "whole
trace in, stats out" contract: every arrival is fixed before the first
cycle runs, so memory backpressure can never change what the workload does
next. :class:`SimSession` breaks that open-loop assumption without giving
up any of the engine's throughput machinery:

* ``SimSession.open(cfg, params=...)`` builds the initial ``SimState``
  once and keeps it **on-device** between calls — queues, per-tier power
  counters and the schedule's segment-attribution counters all ride inside
  the state pytree, and the runtime queue depths live in ``Fifo.limit``,
  so nothing needs re-threading per window.
* ``session.advance(window_cycles, new_arrivals=...)`` runs the
  event-horizon skip engine with the horizon additionally capped at the
  window boundary (:func:`repro.core.engine._run_window_core`) and returns
  a :class:`WindowReport` — the completions and queue occupancies a
  closed-loop scheduler (``repro.serving``) feeds back into its next
  admission/batch-size decision.
* New arrivals append into a fixed-capacity host buffer pre-filled with
  the engine's ``_PAD_T`` sentinel (never due inside any horizon, never
  admitted), so every window reuses ONE AOT-compiled program per
  ``(topology, capacity, segment count)`` — across windows *and* across
  sessions. ``session.timings["compiles"]`` stays 1 no matter how many
  windows run.

Exactness contract (enforced by ``tests/test_session.py`` on every FSM
backend): replaying identical arrivals through any window partition —
including window=1 and windows cutting refresh/SREF/DVFS-boundary seams —
yields a final :class:`SimResult` bit-identical to one monolithic
:func:`repro.core.engine.simulate_fast` run over the concatenated trace.
A window boundary only caps the skip delta; executing a provably inert
cycle is bit-identical to skipping it (the same closed-form property the
shared-clock batch engine's joint-min skipping relies on).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.engine import (
    _PAD_T,
    _run_window_jit,
    _sched_i32,
    _timed,
    count_grants,
)
from repro.core.params import MemSimConfig
from repro.core.simulator import SimResult, SimState, Trace, init_state


@dataclasses.dataclass
class WindowReport:
    """What one ``advance`` window observably did — the feedback signal.

    ``completed_ids`` are the request indices (slots of the session's
    realized trace, emission order) acked inside ``[t_start, t_end)``,
    with ``completed_at`` their ack cycles. ``req_q_len`` /
    ``resp_q_len`` are the end-of-window global queue occupancies, and
    ``blocked_arrival`` the *cumulative* cycles an arrival has stalled on
    a full reqQueue — the memory-backpressure signals a scheduler turns
    into its next admission decision.
    """

    t_start: int
    t_end: int
    completed_ids: np.ndarray
    completed_at: np.ndarray
    req_q_len: int
    resp_q_len: int
    admitted: int          # arrivals admitted into the reqQueue so far
    arrivals_total: int    # trace slots filled so far
    blocked_arrival: int
    steps: int             # cycle_step executions this window

    @property
    def n_completed(self) -> int:
        return int(self.completed_ids.size)


def _as_arrival_arrays(new_arrivals):
    """Normalize an arrivals payload to host numpy (t, addr, is_write,
    wdata). Accepts a :class:`Trace` or a 3/4-tuple of array-likes."""
    if isinstance(new_arrivals, Trace):
        t = np.asarray(new_arrivals.t, np.int64)
        addr = np.asarray(new_arrivals.addr, np.int64)
        wr = np.asarray(new_arrivals.is_write, np.int64)
        wd = np.asarray(new_arrivals.wdata, np.int64)
    else:
        parts = tuple(new_arrivals)
        if len(parts) == 3:
            t, addr, wr = (np.asarray(p, np.int64) for p in parts)
            wd = np.zeros_like(t)
        elif len(parts) == 4:
            t, addr, wr, wd = (np.asarray(p, np.int64) for p in parts)
        else:
            raise ValueError(
                "new_arrivals must be a Trace or (t, addr, is_write[, "
                f"wdata]); got {len(parts)} components")
    if not (t.shape == addr.shape == wr.shape == wd.shape):
        raise ValueError("arrival component shapes disagree")
    return t, addr, wr, wd


def report_fetch(state: SimState):
    """The on-device pytree a :class:`WindowReport` is built from — every
    field the serving feedback loop reads, fetched in ONE ``device_get``
    per window (not one per field). Shared with the lane-batched session,
    where the same tuple carries a leading lane axis."""
    return (state.t_complete, state.req_q.count, state.resp_q.count,
            state.next_arrival, state.blocked_arrival)


def _build_report(t0: int, t1: int, n_filled: int, steps: int,
                  t_complete, req_q_len, resp_q_len, admitted,
                  blocked) -> WindowReport:
    t_complete = np.asarray(t_complete)[:n_filled]
    in_window = (t_complete >= t0) & (t_complete < t1)
    ids = np.nonzero(in_window)[0].astype(np.int64)
    return WindowReport(
        t_start=t0, t_end=t1,
        completed_ids=ids,
        completed_at=t_complete[ids],
        req_q_len=int(req_q_len),
        resp_q_len=int(resp_q_len),
        admitted=int(admitted),
        arrivals_total=n_filled,
        blocked_arrival=int(blocked),
        steps=steps,
    )


class SimSession:
    """A re-entrant windowed simulation of one memory device.

    Use :meth:`open` to construct. The session owns a fixed-capacity
    arrival buffer (slots beyond the filled prefix sit at the engine's
    never-due padding sentinel) and the on-device ``SimState``; repeated
    :meth:`advance` calls move the clock forward window by window, feeding
    in arrivals as they become known. See the module docstring for the
    exactness and compile-sharing contracts.
    """

    def __init__(self, cfg: MemSimConfig, capacity: int, sched,
                 state: SimState, timings: Dict):
        self.cfg = cfg
        self.topo = cfg.topology()
        self.capacity = int(capacity)
        self._sched = sched
        self._state = state
        self.timings = timings
        self._t = np.full((self.capacity,), _PAD_T, np.int32)
        self._addr = np.zeros((self.capacity,), np.int32)
        self._is_write = np.zeros((self.capacity,), np.int32)
        self._wdata = np.zeros((self.capacity,), np.int32)
        self._n_filled = 0
        self._last_t = 0
        self._cycle = 0
        self._dev_trace: Optional[Trace] = None

    # ---- construction -----------------------------------------------------

    @classmethod
    def open(cls, cfg: MemSimConfig, *, capacity: int = 4096,
             params=None, queue_size: Optional[int] = None,
             resp_queue_size: Optional[int] = None,
             timings: Optional[Dict] = None) -> "SimSession":
        """Open a session on ``cfg``'s topology.

        ``capacity`` is the static arrival-buffer size — the one shape
        (besides the topology and the schedule's segment count) the
        compiled windowed program keys on; every arrival ever appended
        must fit. ``params`` is a constant :class:`RuntimeParams` point or
        a :class:`ParamSchedule` (absolute boundaries — a window cutting a
        DVFS segment seam stays bit-exact). ``queue_size`` /
        ``resp_queue_size`` are the runtime occupancy limits (default:
        the static capacities), carried inside the state like everywhere
        else in the engine. ``timings`` receives the shared
        compile/run-wall accounting of every window (``compiles`` counts
        fresh XLA compiles — 1 for the first session of a topology, 0
        after).
        """
        cfg.validate()
        if capacity < 1:
            raise ValueError(f"capacity={capacity} must be >= 1")
        topo = cfg.topology()
        sched = _sched_i32(cfg.runtime() if params is None else params,
                           cfg.timing_model)
        ql = cfg.queue_size if queue_size is None else queue_size
        rl = (cfg.resp_queue_size if resp_queue_size is None
              else resp_queue_size)
        if not (1 <= ql <= cfg.queue_size):
            raise ValueError(f"queue_size={ql} not in [1, {cfg.queue_size}]")
        if not (1 <= rl <= cfg.resp_queue_size):
            raise ValueError(
                f"resp_queue_size={rl} not in [1, {cfg.resp_queue_size}]")
        state = init_state(topo, sched, capacity, jnp.int32(ql),
                           jnp.int32(rl))
        return cls(cfg, capacity, sched, state,
                   {} if timings is None else timings)

    # ---- arrivals ----------------------------------------------------------

    @property
    def cycle(self) -> int:
        """The session clock: every cycle < ``cycle`` has been simulated."""
        return self._cycle

    @property
    def arrivals_total(self) -> int:
        return self._n_filled

    def append(self, new_arrivals) -> int:
        """Append arrivals to the realized trace; returns the index of the
        first appended slot. Arrival times must be non-decreasing within
        the payload AND not precede any already-appended arrival (the
        concatenated trace must satisfy the sorted :class:`Trace`
        contract, which is also what makes the windowed run comparable to
        one monolithic run over it)."""
        t, addr, wr, wd = _as_arrival_arrays(new_arrivals)
        n = int(t.size)
        if n == 0:
            return self._n_filled
        if np.any(np.diff(t) < 0):
            raise ValueError("arrival times must be non-decreasing")
        if self._n_filled and int(t[0]) < self._last_t:
            raise ValueError(
                f"arrival t={int(t[0])} precedes already-appended "
                f"t={self._last_t}; the concatenated trace must stay "
                "sorted")
        if int(t[-1]) >= _PAD_T:
            raise ValueError(
                f"arrival t={int(t[-1])} reaches the padding sentinel "
                f"{_PAD_T}; arrivals must stay below it")
        if self._n_filled + n > self.capacity:
            raise ValueError(
                f"appending {n} arrivals overflows session capacity "
                f"{self.capacity} ({self._n_filled} filled); open the "
                "session with a larger capacity")
        first = self._n_filled
        sl = slice(first, first + n)
        self._t[sl] = t.astype(np.int32)
        self._addr[sl] = (addr & 0x3FFFFFFF).astype(np.int32)
        self._is_write[sl] = wr.astype(np.int32)
        self._wdata[sl] = wd.astype(np.int32)
        self._n_filled += n
        self._last_t = int(t[-1])
        self._dev_trace = None  # host buffer changed: re-upload next window
        return first

    def trace(self) -> Trace:
        """The realized arrival stream so far (filled slots only) — what a
        monolithic run replaying this session would be fed, and what
        :func:`repro.traces.io.save_session_trace` exports."""
        n = self._n_filled
        return Trace(t=jnp.asarray(self._t[:n]),
                     addr=jnp.asarray(self._addr[:n]),
                     is_write=jnp.asarray(self._is_write[:n]),
                     wdata=jnp.asarray(self._wdata[:n]))

    # ---- the windowed run --------------------------------------------------

    def _device_trace(self) -> Trace:
        # the upload is cached between windows: drain phases (no appends
        # since the last window) re-dispatch on the same device buffers
        # instead of re-transferring 4 x capacity words every window
        if self._dev_trace is None:
            host = (self._t, self._addr, self._is_write, self._wdata)
            self._dev_trace = Trace(*(jnp.asarray(a) for a in host))
            tracing.count("upload_bytes", sum(a.nbytes for a in host))
        return self._dev_trace

    def advance(self, window_cycles: int,
                new_arrivals=None) -> WindowReport:
        """Simulate ``[cycle, cycle + window_cycles)`` and report back.

        ``new_arrivals`` (optional) is appended first — the closed loop:
        a scheduler reads the previous window's :class:`WindowReport`,
        decides what traffic to emit, and hands it in here. The state
        stays on-device; the one host transfer per window is the
        completion-record slice the report is built from.

        Spans: ``memsim.session.upload`` (the append and the arrival
        buffer's upload, counter ``upload_bytes``: 0 where the cached
        device copy is reused), ``memsim.engine.run`` and
        ``memsim.session.fetch`` (the report's transfer and build).
        """
        if window_cycles < 0:
            raise ValueError(f"window_cycles={window_cycles} must be >= 0")
        t0 = self._cycle
        t1 = t0 + int(window_cycles)
        with tracing.span("memsim.session.upload"):
            if new_arrivals is not None:
                self.append(new_arrivals)
            trace = self._device_trace() if t1 > t0 else None
        steps = 0
        if t1 > t0:
            jt0, jt1 = jnp.int32(t0), jnp.int32(t1)
            args = (trace, jt0, jt1, self._sched, self._state)
            state, steps = _timed(_run_window_jit, (self.topo,) + args,
                                  args, (self.topo,), self.timings)
            self._state = state
            self._cycle = t1
            steps = int(steps)
        with tracing.span("memsim.session.fetch"):
            # ONE host transfer for the whole report: a stacked device_get
            # of every field the feedback loop reads, not one get per field
            t_complete, req_q_len, resp_q_len, admitted, blocked = \
                jax.device_get(report_fetch(self._state))
            return _build_report(t0, t1, self._n_filled, steps, t_complete,
                                 req_q_len, resp_q_len, admitted, blocked)

    def run_until(self, t_end: int,
                  window_cycles: int) -> Sequence[WindowReport]:
        """Advance in fixed windows until the clock reaches ``t_end``."""
        reports = []
        while self._cycle < t_end:
            w = min(window_cycles, t_end - self._cycle)
            reports.append(self.advance(w))
        return reports

    # ---- results -----------------------------------------------------------

    def result(self) -> SimResult:
        """Host-side result bundle over the filled arrival slots — the
        same surface a monolithic :func:`repro.core.engine.simulate_fast`
        run over :meth:`trace` for ``cycle`` cycles returns (bit-identical
        to it, per the session exactness contract). Span:
        ``memsim.engine.to_result``."""
        with tracing.span("memsim.engine.to_result"):
            res = self._result()
            count_grants([res])
            return res

    def _result(self) -> SimResult:
        n = self._n_filled
        host = jax.device_get(self._state)
        return SimResult(
            cfg=dataclasses.replace(
                self.cfg,
                queue_size=int(np.asarray(host.req_q.limit)),
                resp_queue_size=int(np.asarray(host.resp_q.limit))),
            num_cycles=self._cycle,
            t_intended=self._t[:n].copy(),
            is_write=self._is_write[:n].copy(),
            t_admit=np.asarray(host.t_admit)[:n],
            t_dispatch=np.asarray(host.t_dispatch)[:n],
            t_start=np.asarray(host.t_start)[:n],
            t_complete=np.asarray(host.t_complete)[:n],
            rdata=np.asarray(host.rdata)[:n],
            counters={k: np.asarray(v) for k, v in host.counters.items()},
            blocked_arrival=int(host.blocked_arrival),
            blocked_dispatch=int(host.blocked_dispatch),
        )
