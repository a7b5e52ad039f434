"""Lane-batched re-entrant sessions: L closed-loop sessions as ONE program.

:class:`SessionBatch` is the many-session twin of
:class:`repro.core.session.SimSession`. PR 9's serving study advanced one
session per scenario point in a Python loop, so every (offered load x
mixture x topology) point paid its own per-window dispatch and host<->device
round-trips — scenario count was a wall-clock multiplier. Here concurrent
sessions become a **lane axis of the windowed engine**, the same move the
sweep layer made for parameter points, topologies and lane counts:

* Per-lane ``SimState`` (queues, banks, memory image, counters), per-lane
  arrival buffers and per-lane :class:`~repro.core.params.ParamSchedule`
  all stack on a leading lane axis and stay **on-device** between windows.
* One :meth:`advance` call advances every lane through the window and
  returns one :class:`~repro.core.session.WindowReport` per lane, built
  from a SINGLE ``jax.device_get`` of the stacked report pytree (one host
  transfer per window for the whole batch, not one per lane per field).
* Every window of every batch reuses ONE AOT-compiled program per
  ``(topology, capacity, lane count, segment count)``; lanes on the same
  topology with different ``RuntimeParams``/``ParamSchedule`` or runtime
  queue limits ride as traced data, exactly like ``sweep_grid`` lanes.

``batch_mode`` picks how the window itself executes, with the same split
(and the same CPU/accelerator trade) as
:func:`repro.core.engine.simulate_batch`:

* ``"vmap"`` — :func:`repro.core.engine._run_window_batch_core`: the
  cycle step vmaps over lanes on a SHARED clock whose skip delta is the
  joint min over lanes. Best where the lane axis vectorizes into hardware
  lanes (accelerators); on CPU every select-lowered cond and the joint
  clock held back by the busiest lane make it *slower* than sequential.
* ``"lanes"`` — :func:`repro.core.engine._run_window_lanes_core`:
  ``lax.map`` of the single-lane window engine over the stacked lanes,
  still one dispatch/compile/report-fetch per window but each lane keeps
  the exact single-lane op stream and *independent* cycle skipping (even
  per-lane ``steps`` counts match a standalone session).
* ``"auto"`` (default) — ``"lanes"`` on the CPU backend, ``"vmap"``
  otherwise.

Exactness contract (``tests/test_session_batch.py``, all three FSM
backends): lane ``i`` of a batch fed some arrival stream is bit-identical
— records, counters, blocked totals — to a standalone ``SimSession``
replaying the same stream through the same window partition. The window
boundary and the other lanes' activity only ever *shrink* the skip delta,
and executing a provably inert cycle equals skipping it (the closed-form
property shared with ``_run_skip_batch_core``), so per-lane exactness
survives the shared clock.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.engine import (
    _PAD_T,
    _run_window_batch_jit,
    _run_window_lanes_jit,
    _sched_i32,
    _timed,
    count_grants,
)
from repro.core.params import MemSimConfig, ParamSchedule, RuntimeParams
from repro.core.session import WindowReport, _as_arrival_arrays, \
    _build_report, report_fetch
from repro.core.simulator import SimResult, Trace, init_state


def _per_lane(value, lanes: int, what: str) -> list:
    """Broadcast a scalar-or-sequence option to a per-lane list. A
    RuntimeParams/ParamSchedule is a NamedTuple, so the single-value case
    is detected by type, not by iterability."""
    if isinstance(value, (list, tuple)) and not isinstance(
            value, (RuntimeParams, ParamSchedule)):
        if len(value) != lanes:
            raise ValueError(
                f"per-lane {what} has {len(value)} entries for {lanes} lanes")
        return list(value)
    return [value] * lanes


class SessionBatch:
    """L re-entrant windowed sessions advancing in lock-step windows.

    Use :meth:`open`. All lanes share the topology, the arrival-buffer
    ``capacity`` and the window clock (those are the compiled program's
    shape keys); everything else — schedules, queue limits, arrival
    streams — is per-lane traced data. See the module docstring for the
    exactness and compile-sharing contracts.
    """

    def __init__(self, cfg: MemSimConfig, lanes: int, capacity: int,
                 scheds: ParamSchedule, states, timings: Dict,
                 batch_mode: str = "auto"):
        if batch_mode == "auto":
            batch_mode = ("lanes" if jax.default_backend() == "cpu"
                          else "vmap")
        self.cfg = cfg
        self.topo = cfg.topology()
        self.lanes = int(lanes)
        self.capacity = int(capacity)
        self.batch_mode = batch_mode
        self._scheds = scheds
        self._states = states
        self.timings = timings
        self._dev_traces: Optional[Trace] = None
        self._t = np.full((self.lanes, self.capacity), _PAD_T, np.int32)
        self._addr = np.zeros((self.lanes, self.capacity), np.int32)
        self._is_write = np.zeros((self.lanes, self.capacity), np.int32)
        self._wdata = np.zeros((self.lanes, self.capacity), np.int32)
        self._n_filled = [0] * self.lanes
        self._last_t = [0] * self.lanes
        self._cycle = 0

    # ---- construction -----------------------------------------------------

    @classmethod
    def open(cls, cfg: MemSimConfig, lanes: int, *, capacity: int = 4096,
             params=None, queue_size=None, resp_queue_size=None,
             batch_mode: str = "auto",
             timings: Optional[Dict] = None) -> "SessionBatch":
        """Open ``lanes`` sessions on ``cfg``'s topology.

        ``params`` is a single RuntimeParams/ParamSchedule applied to all
        lanes, or a per-lane sequence (entries may be ``None`` for the
        config default; heterogeneous segment counts pad to the common S,
        which joins the program key). ``queue_size`` / ``resp_queue_size``
        likewise broadcast or go per-lane. ``capacity`` is shared — lanes
        needing *different* capacities need separate (sequential)
        sessions, since capacity is a compiled shape. ``batch_mode`` is
        ``"vmap"``, ``"lanes"`` or ``"auto"`` (see the module docstring);
        both modes satisfy the same per-lane exactness contract.
        """
        cfg.validate()
        if lanes < 1:
            raise ValueError(f"lanes={lanes} must be >= 1")
        if capacity < 1:
            raise ValueError(f"capacity={capacity} must be >= 1")
        if batch_mode not in ("auto", "vmap", "lanes"):
            raise ValueError(f"unknown batch_mode {batch_mode!r}")
        topo = cfg.topology()
        scheds = [_sched_i32(cfg.runtime() if p is None else p,
                             cfg.timing_model)
                  for p in _per_lane(params, lanes, "params")]
        sched_stack = ParamSchedule.stack(scheds)
        qls, rls = [], []
        for ql in _per_lane(queue_size, lanes, "queue_size"):
            ql = cfg.queue_size if ql is None else ql
            if not (1 <= ql <= cfg.queue_size):
                raise ValueError(
                    f"queue_size={ql} not in [1, {cfg.queue_size}]")
            qls.append(ql)
        for rl in _per_lane(resp_queue_size, lanes, "resp_queue_size"):
            rl = cfg.resp_queue_size if rl is None else rl
            if not (1 <= rl <= cfg.resp_queue_size):
                raise ValueError(
                    f"resp_queue_size={rl} not in [1, {cfg.resp_queue_size}]")
            rls.append(rl)
        states = jax.vmap(
            lambda sc, ql, rl: init_state(topo, sc, capacity, ql, rl)
        )(sched_stack, jnp.asarray(qls, jnp.int32),
          jnp.asarray(rls, jnp.int32))
        return cls(cfg, lanes, capacity, sched_stack, states,
                   {} if timings is None else timings, batch_mode)

    # ---- arrivals ----------------------------------------------------------

    @property
    def cycle(self) -> int:
        """The shared batch clock: every lane has simulated every cycle
        below it."""
        return self._cycle

    def arrivals_total(self, lane: int) -> int:
        return self._n_filled[lane]

    def append(self, lane: int, new_arrivals) -> int:
        """Append arrivals to one lane's realized trace; returns the index
        of the first appended slot. Same sortedness/sentinel/capacity
        contract as :meth:`SimSession.append`, enforced per lane."""
        if not (0 <= lane < self.lanes):
            raise ValueError(f"lane={lane} not in [0, {self.lanes})")
        t, addr, wr, wd = _as_arrival_arrays(new_arrivals)
        n = int(t.size)
        if n == 0:
            return self._n_filled[lane]
        if np.any(np.diff(t) < 0):
            raise ValueError("arrival times must be non-decreasing")
        if self._n_filled[lane] and int(t[0]) < self._last_t[lane]:
            raise ValueError(
                f"lane {lane}: arrival t={int(t[0])} precedes "
                f"already-appended t={self._last_t[lane]}; the concatenated "
                "trace must stay sorted")
        if int(t[-1]) >= _PAD_T:
            raise ValueError(
                f"arrival t={int(t[-1])} reaches the padding sentinel "
                f"{_PAD_T}; arrivals must stay below it")
        if self._n_filled[lane] + n > self.capacity:
            raise ValueError(
                f"lane {lane}: appending {n} arrivals overflows capacity "
                f"{self.capacity} ({self._n_filled[lane]} filled); open the "
                "batch with a larger capacity")
        first = self._n_filled[lane]
        sl = slice(first, first + n)
        self._t[lane, sl] = t.astype(np.int32)
        self._addr[lane, sl] = (addr & 0x3FFFFFFF).astype(np.int32)
        self._is_write[lane, sl] = wr.astype(np.int32)
        self._wdata[lane, sl] = wd.astype(np.int32)
        self._n_filled[lane] += n
        self._last_t[lane] = int(t[-1])
        self._dev_traces = None  # host buffers changed: re-upload
        return first

    def trace(self, lane: int) -> Trace:
        """Lane ``lane``'s realized arrival stream so far (filled slots)."""
        n = self._n_filled[lane]
        return Trace(t=jnp.asarray(self._t[lane, :n]),
                     addr=jnp.asarray(self._addr[lane, :n]),
                     is_write=jnp.asarray(self._is_write[lane, :n]),
                     wdata=jnp.asarray(self._wdata[lane, :n]))

    # ---- the windowed run --------------------------------------------------

    def _device_traces(self) -> Trace:
        # cached between windows: windows with no new appends on any lane
        # (drain phases) re-dispatch on the same device buffers instead of
        # re-uploading 4 x lanes x capacity words
        if self._dev_traces is None:
            host = (self._t, self._addr, self._is_write, self._wdata)
            self._dev_traces = Trace(*(jnp.asarray(a) for a in host))
            tracing.count("upload_bytes", sum(a.nbytes for a in host))
        return self._dev_traces

    def advance(self, window_cycles: int,
                new_arrivals: Optional[Sequence] = None
                ) -> List[WindowReport]:
        """Simulate ``[cycle, cycle + window_cycles)`` on every lane and
        report back per lane.

        ``new_arrivals`` (optional) is a length-``lanes`` sequence of
        per-lane payloads (entries may be ``None``) appended before the
        window runs — ragged per-lane arrival counts are the normal case.
        One batched dispatch advances all lanes; ONE stacked
        ``device_get`` fetches every lane's report fields.

        Spans: ``memsim.session.upload`` (the appends and the arrival
        buffers' upload, counter ``upload_bytes``: 16 B x lanes x capacity
        where any lane appended since the last upload, else 0),
        ``memsim.engine.run`` and ``memsim.session.fetch`` (the stacked
        transfer and the reports' build).
        """
        if window_cycles < 0:
            raise ValueError(f"window_cycles={window_cycles} must be >= 0")
        t0 = self._cycle
        t1 = t0 + int(window_cycles)
        with tracing.span("memsim.session.upload"):
            if new_arrivals is not None:
                if len(new_arrivals) != self.lanes:
                    raise ValueError(
                        f"new_arrivals has {len(new_arrivals)} entries for "
                        f"{self.lanes} lanes")
                for lane, payload in enumerate(new_arrivals):
                    if payload is not None:
                        self.append(lane, payload)
            traces = self._device_traces() if t1 > t0 else None
        steps = jnp.int32(0)
        if t1 > t0:
            jt0, jt1 = jnp.int32(t0), jnp.int32(t1)
            args = (traces, jt0, jt1, self._scheds, self._states)
            jitted = (_run_window_lanes_jit if self.batch_mode == "lanes"
                      else _run_window_batch_jit)
            states, steps = _timed(jitted, (self.topo,) + args, args,
                                   (self.topo,), self.timings)
            self._states = states
            self._cycle = t1
        with tracing.span("memsim.session.fetch"):
            # ONE stacked host transfer for every lane's report fields AND
            # the step counts ("lanes" mode: per-lane counts, exactly the
            # numbers the L standalone sessions would report; "vmap" mode:
            # the shared joint-clock count, same for every lane)
            (t_complete, req_q, resp_q, admitted, blocked), steps = \
                jax.device_get((report_fetch(self._states), steps))
            steps = np.asarray(steps)
            per_steps = (steps.astype(np.int64).tolist() if steps.ndim
                         else [int(steps)] * self.lanes)
            self.timings["steps"] = (self.timings.get("steps", 0)
                                     + max(per_steps))
            return [
                _build_report(t0, t1, self._n_filled[i], per_steps[i],
                              t_complete[i], req_q[i], resp_q[i],
                              admitted[i], blocked[i])
                for i in range(self.lanes)
            ]

    def run_until(self, t_end: int,
                  window_cycles: int) -> List[List[WindowReport]]:
        """Advance in fixed windows until the clock reaches ``t_end``;
        returns one report list per window."""
        reports = []
        while self._cycle < t_end:
            w = min(window_cycles, t_end - self._cycle)
            reports.append(self.advance(w))
        return reports

    # ---- results -----------------------------------------------------------

    def lane_result(self, lane: int,
                    num_cycles: Optional[int] = None) -> SimResult:
        """Lane ``lane``'s host-side result bundle — bit-identical to a
        standalone :meth:`SimSession.result` over the same arrivals and
        the same final clock. ``num_cycles`` relabels the cycle count for
        lanes that went idle before the batch clock stopped (the state
        past that point is inert for them). Span:
        ``memsim.engine.to_result``."""
        with tracing.span("memsim.engine.to_result"):
            res = self._lane_result(lane, num_cycles)
            count_grants([res])
            return res

    def _lane_result(self, lane: int,
                     num_cycles: Optional[int]) -> SimResult:
        n = self._n_filled[lane]
        host = jax.device_get(
            jax.tree_util.tree_map(lambda x: x[lane], self._states))
        return SimResult(
            cfg=dataclasses.replace(
                self.cfg,
                queue_size=int(np.asarray(host.req_q.limit)),
                resp_queue_size=int(np.asarray(host.resp_q.limit))),
            num_cycles=self._cycle if num_cycles is None else int(num_cycles),
            t_intended=self._t[lane, :n].copy(),
            is_write=self._is_write[lane, :n].copy(),
            t_admit=np.asarray(host.t_admit)[:n],
            t_dispatch=np.asarray(host.t_dispatch)[:n],
            t_start=np.asarray(host.t_start)[:n],
            t_complete=np.asarray(host.t_complete)[:n],
            rdata=np.asarray(host.rdata)[:n],
            counters={k: np.asarray(v) for k, v in host.counters.items()},
            blocked_arrival=int(host.blocked_arrival),
            blocked_dispatch=int(host.blocked_dispatch),
        )

    def results(self) -> List[SimResult]:
        return [self.lane_result(i) for i in range(self.lanes)]

    def lane_view(self, lane: int, cycle: Optional[int] = None
                  ) -> "SessionLane":
        return SessionLane(self, lane, self._cycle if cycle is None
                           else int(cycle))


class SessionLane:
    """Read-only single-lane view over a :class:`SessionBatch` with the
    same surface downstream consumers read off a ``SimSession`` —
    ``trace()``, ``result()``, ``cycle``, ``arrivals_total`` — so e.g.
    :func:`repro.traces.io.save_session_trace` and
    :class:`repro.serving.ServingResult` work unchanged on batched runs."""

    def __init__(self, batch: SessionBatch, lane: int, cycle: int):
        self._batch = batch
        self._lane = int(lane)
        self.cycle = int(cycle)

    @property
    def cfg(self) -> MemSimConfig:
        return self._batch.cfg

    @property
    def arrivals_total(self) -> int:
        return self._batch.arrivals_total(self._lane)

    def trace(self) -> Trace:
        return self._batch.trace(self._lane)

    def result(self) -> SimResult:
        return self._batch.lane_result(self._lane, num_cycles=self.cycle)
