"""The program's spans and counters (``repro.core.tracing``): the ring of
root calls, nesting, counters, and the spans a closed-loop serving run
records per window."""

from __future__ import annotations

import threading

import numpy as np
import pytest

from repro.core import MemSimConfig, SessionBatch, tracing
from repro.serving import ServingConfig, run_serving_batched
from repro.serving.workload import generate_request_batch
from repro.traces import trace_example

WINDOW_CHILDREN = ["memsim.serve.plan", "memsim.session.upload",
                   "memsim.engine.run", "memsim.session.fetch",
                   "memsim.serve.observe"]


def _children(call, index):
    return [s for s in call if s.parent == index]


def test_nested_spans_are_children_of_one_call():
    with tracing.span("test.root", job=3):
        with tracing.span("test.child"):
            tracing.count("items", 2)
            tracing.count("items")
            with tracing.span("test.grandchild"):
                pass
        with tracing.span("test.child"):
            pass
    (call,) = tracing.recent(1, "test.root")
    assert [s.name for s in call] == ["test.root", "test.child",
                                      "test.grandchild", "test.child"]
    assert [s.parent for s in call] == [None, 0, 1, 0]
    assert len({s.call for s in call}) == 1
    assert call[0].ids == {"job": 3}
    assert call[1].counters == {"items": 3} and call[3].counters == {}
    assert all(s.start_ns <= s.end_ns for s in call)
    assert call[0].start_ns <= call[1].start_ns
    assert call[1].end_ns <= call[0].end_ns


def test_count_outside_a_span_does_nothing():
    tracing.count("orphan", 5)
    with tracing.span("test.after_orphan"):
        pass
    (call,) = tracing.recent(1, "test.after_orphan")
    assert call[0].counters == {}


def test_ring_keeps_the_last_calls_and_recent_returns_whole_calls():
    first = tracing.RING_CALLS + 10
    for i in range(first):
        with tracing.span("test.ring", i=i):
            with tracing.span("test.ring.child"):
                pass
        if i % 3 == 0:
            with tracing.span("test.other"):
                pass
    # every root counts against the ring, whatever its name
    kept = tracing.recent(10 * tracing.RING_CALLS, "test.ring")
    others = tracing.recent(10 * tracing.RING_CALLS, "test.other")
    assert len(kept) + len(others) == tracing.RING_CALLS
    assert [c[0].ids["i"] for c in kept] == list(
        range(first - len(kept), first))
    last = tracing.recent(4, "test.ring")
    assert [c[0].ids["i"] for c in last] == list(range(first - 4, first))
    for call in last:
        assert [s.name for s in call] == ["test.ring", "test.ring.child"]
        assert call[1].call == call[0].call
    assert tracing.recent(0, "test.ring") == []
    assert tracing.recent(3, "test.no_such_root") == []


def test_a_span_on_another_thread_is_a_root_of_its_own():
    def work():
        with tracing.span("test.thread_root"):
            pass

    with tracing.span("test.main_root"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
    (main,) = tracing.recent(1, "test.main_root")
    assert [s.name for s in main] == ["test.main_root"]
    assert len(tracing.recent(1, "test.thread_root")) == 1


def test_an_exception_closes_the_span():
    with pytest.raises(ValueError):
        with tracing.span("test.raises"):
            with tracing.span("test.raises.child"):
                raise ValueError("boom")
    (call,) = tracing.recent(1, "test.raises")
    assert all(s.end_ns is not None for s in call)
    with tracing.span("test.after_raise"):
        pass
    (after,) = tracing.recent(1, "test.after_raise")
    assert after[0].parent is None


def test_a_nested_api_call_is_no_new_root():
    from repro.core import sweep_grid

    cfg = MemSimConfig(fsm_backend="jnp", queue_size=8)
    sweep_grid(cfg, trace_example(n=20, gap=5), {"tCL": [14, 18]}, 600,
               timings={})
    (call,) = tracing.recent(1, "memsim.sweep_grid")
    names = [s.name for s in call]
    assert names.count("memsim.simulate_batch") == 1
    batch = names.index("memsim.simulate_batch")
    assert call[batch].parent == 0
    kids = [s.name for s in _children(call, batch)]
    assert kids[0] == "memsim.engine.prepare"
    assert kids[-1] == "memsim.engine.to_result"
    assert "memsim.engine.run" in kids


def test_simulate_fast_timings_come_from_its_spans():
    from repro.core import simulate_fast

    tm = {}
    simulate_fast(MemSimConfig(fsm_backend="jnp", queue_size=8),
                  trace_example(n=20, gap=5), 600, timings=tm)
    (call,) = tracing.recent(1, "memsim.simulate_fast")
    runs = [s for s in call if s.name == "memsim.engine.run"]
    assert len(runs) == 1 and runs[0].parent == 0
    assert tm["run_s"] == runs[0].seconds
    compiles = [s for s in call if s.name == "memsim.engine.compile"]
    assert tm["compiles"] == sum(s.counters["compiles"] for s in compiles)
    assert [s.name for s in call if s.parent == 0] == (
        ["memsim.engine.prepare"] + ["memsim.engine.compile"] * len(compiles)
        + ["memsim.engine.run", "memsim.engine.to_result"])


def _tiny_serving(lanes=2, capacity=2048, window=400):
    cfg = MemSimConfig(fsm_backend="jnp", queue_size=16)
    lists = generate_request_batch(
        [dict(process="poisson", mixture="chat", rate_per_kcycle=r,
              horizon=1200) for r in (1.0, 3.0)][:lanes], seed=3)
    res = run_serving_batched(cfg, lists, ServingConfig(),
                              window_cycles=window, capacity=capacity,
                              max_cycles=3200, batch_mode="vmap")
    (call,) = tracing.recent(1, "memsim.serve")
    return res, call


def test_serving_records_each_window_phase_once():
    lanes, capacity, window = 2, 2048, 400
    res, call = _tiny_serving(lanes, capacity, window)
    assert call[0].name == "memsim.serve"
    # opening the lanes' sessions and schedulers comes first
    assert call[1].name == "memsim.engine.prepare" and call[1].parent == 0
    windows = [(i, s) for i, s in enumerate(call)
               if s.name == "memsim.serve.window"]
    assert len(windows) == max(r.cycles for r in res) // window
    assert [s.ids for _, s in windows] == [{"window": k}
                                           for k in range(len(windows))]
    for i, w in windows:
        assert w.parent == 0
        kids = [s.name for s in _children(call, i)]
        # a fresh compile may open before the first window's run
        assert [k for k in kids if k != "memsim.engine.compile"] == \
            WINDOW_CHILDREN, (w.ids, kids)

    # the arrival buffers go up on the first window and on every window in
    # which some lane appended requests; the cached copy serves the rest
    appended = {int(t) // window for r in res
                for t in np.asarray(r.session.trace().t)}
    whole = 16 * lanes * capacity
    for i, w in windows:
        (up,) = [s for s in _children(call, i)
                 if s.name == "memsim.session.upload"]
        k = w.ids["window"]
        want = whole if (k == 0 or k in appended) else 0
        assert up.counters.get("upload_bytes", 0) == want, k


def test_session_batch_uploads_only_after_an_append():
    cfg = MemSimConfig(fsm_backend="jnp", queue_size=8)
    lanes, capacity = 2, 64
    batch = SessionBatch.open(cfg, lanes, capacity=capacity,
                              batch_mode="vmap")

    def one(t):
        return np.array([t]), np.array([64]), np.array([0])

    # windows of 50 cycles: lane 0 appends in window 0, lane 1 in window 2
    plan = [[one(5), None], [None, None], [None, one(105)], [None, None]]
    with tracing.span("test.session_batch"):
        for arrivals in plan:
            batch.advance(50, arrivals)
    (call,) = tracing.recent(1, "test.session_batch")
    sent = [s.counters.get("upload_bytes", 0) for s in call
            if s.name == "memsim.session.upload"]
    assert sent == [16 * lanes * capacity, 0, 16 * lanes * capacity, 0]
