"""The readers of the program's spans, counters and glue scopes, on tiny
CPU runs of each cell's entry point (the device metrics need a TPU trace:
the glue-scope readers are fed a reduced trace built from the compiled
program's own op keys, and the host-span times one device plane, since
off the chip they are left out)."""

from __future__ import annotations

import numpy as np
import pytest

from bench import entries, harness
from bench.metrics import _scopes, _spans
from bench.tests import tiny
from bench.trace_reduce import OPCODE

SEED = 2**33 + 11
CELLS = ["ddr4-2ch.decode.l1", "ddr4-2ch.decode.sweep64",
         "ddr4-cxl.serve.l8"]
#: the metrics that read the program's spans, counters and glue scopes
NEW = {
    "ddr4-2ch.decode.l1": ["prep_ms_per_job.l1", "result_ms_per_job.l1",
                           "pre_us_per_step.l1", "post_us_per_step.l1"],
    "ddr4-2ch.decode.sweep64": ["prep_ms_per_job.sweep",
                                "result_ms_per_job.sweep",
                                "pre_us_per_step.sweep",
                                "post_us_per_step.sweep"],
    "ddr4-cxl.serve.l8": ["plan_ms_per_window.serve",
                          "upload_ms_per_window.serve",
                          "fetch_ms_per_window.serve",
                          "observe_ms_per_window.serve",
                          "upload_mb_per_window.serve",
                          "pre_us_per_step.serve",
                          "post_us_per_step.serve"],
}

#: the host-span time readers: read only where the trace holds the chip
TIMES = [n for names in NEW.values() for n in names
         if "_ms_per_" in n]
#: a reduced trace that holds one device plane
CHIP = {"window_s": 0.02, "devices": [
    {"device": 0, "busy_s": 0.01, "span_s": 0.02, "idle_s": 0.01,
     "ops": {}, "kernels": {"fused_fsm": {"seconds": 0.0, "events": 0}}}]}


def _ctx(cell, jobs, trace=CHIP):
    config, traffic = tiny.cell(cell)
    return dict(config=config, traffic=traffic, jobs=jobs, trace=trace,
                peaks=None, window_s=1.0, setup_s=1.0)


@pytest.fixture(scope="module")
def runs():
    """Two jobs of each cell after a warm-up, run one cell after another
    (each reader reads the last calls of its own entry point's root)."""
    out = {}
    for cell in CELLS:
        config, traffic = tiny.cell(cell)
        entry = entries.get(traffic["entry"])(config, traffic)
        entry.warm_up(SEED)
        out[cell] = [entry.job(SEED + k) for k in (1, 2)]
    return out


def _self_ms_per(cell, jobs, name, per_window):
    found = _spans.calls(_ctx(cell, jobs))
    total = 0
    for call in found:
        for i, s in enumerate(call):
            if s.name == name:
                kids = sum(c.end_ns - c.start_ns for c in call
                           if c.parent == i)
                total += s.end_ns - s.start_ns - kids
    n = (sum(s.name == "memsim.serve.window" for c in found for s in c)
         if per_window else len(found))
    return 1e-6 * total / n


@pytest.mark.parametrize("cell,metric,span", [
    ("ddr4-2ch.decode.l1", "prep_ms_per_job.l1", "memsim.engine.prepare"),
    ("ddr4-2ch.decode.l1", "result_ms_per_job.l1",
     "memsim.engine.to_result"),
    ("ddr4-2ch.decode.sweep64", "prep_ms_per_job.sweep",
     "memsim.engine.prepare"),
    ("ddr4-2ch.decode.sweep64", "result_ms_per_job.sweep",
     "memsim.engine.to_result"),
    ("ddr4-cxl.serve.l8", "plan_ms_per_window.serve", "memsim.serve.plan"),
    ("ddr4-cxl.serve.l8", "upload_ms_per_window.serve",
     "memsim.session.upload"),
    ("ddr4-cxl.serve.l8", "fetch_ms_per_window.serve",
     "memsim.session.fetch"),
    ("ddr4-cxl.serve.l8", "observe_ms_per_window.serve",
     "memsim.serve.observe"),
])
def test_span_reader_reads_self_time(runs, cell, metric, span):
    jobs = runs[cell]
    value = harness.reader(metric).read(_ctx(cell, jobs))
    assert value is not None and value > 0
    want = _self_ms_per(cell, jobs, span, metric.endswith(".serve"))
    assert value == pytest.approx(want, rel=1e-12)


def test_span_readers_take_the_traced_jobs_only(runs):
    cell = "ddr4-cxl.serve.l8"
    found = _spans.calls(_ctx(cell, runs[cell]))
    assert len(found) == 2
    # the warm-up job's call comes first in the ring and is left out
    assert all(c[0].name == "memsim.serve" for c in found)
    assert found[0][0].call < found[1][0].call


def test_upload_mb_is_whole_buffers_over_windows(runs):
    cell = "ddr4-cxl.serve.l8"
    _, traffic = tiny.cell(cell)
    window, capacity = traffic["window_cycles"], traffic["capacity"]
    jobs = runs[cell]
    uploading = windows = 0
    for job in jobs:
        lanes = job.lanes
        appended = {int(t) // window for res in job.outputs
                    for t in np.asarray(res.session.trace().t)}
        windows += job.windows
        uploading += len(appended | {0})
    value = harness.reader("upload_mb_per_window.serve").read(
        _ctx(cell, jobs))
    assert value == 1e-6 * (16 * lanes * capacity * uploading) / windows


@pytest.mark.parametrize("cell", CELLS)
def test_every_new_reader_gives_none_without_jobs(cell):
    names = NEW[cell]
    manifest = harness.load_manifest()
    assert set(names) <= {m["name"] for m in manifest["per_layer"]
                          if cell in m.get("workloads", [])}
    for name in names:
        assert harness.reader(name).read(_ctx(cell, [])) is None, name


@pytest.mark.parametrize("metric", TIMES)
def test_span_time_is_left_out_off_the_chip(runs, metric):
    cell = next(c for c, names in NEW.items() if metric in names)
    reader = harness.reader(metric)
    assert reader.read(_ctx(cell, runs[cell])) is not None
    for trace in (None, {"window_s": 0.02, "devices": []}):
        assert reader.read(_ctx(cell, runs[cell], trace=trace)) is None


def test_upload_count_is_read_off_the_chip_too(runs):
    cell = "ddr4-cxl.serve.l8"
    reader = harness.reader("upload_mb_per_window.serve")
    off = reader.read(_ctx(cell, runs[cell],
                           trace={"window_s": 0.02, "devices": []}))
    assert off is not None and off == reader.read(_ctx(cell, runs[cell]))


def test_glue_scopes_name_pre_and_post_fusions(runs, monkeypatch):
    """The op -> scope map of the CPU-compiled ``_run_skip_jit`` (the
    ``decode.l1`` cell's program) gives at least one fusion to each glue
    scope."""
    from repro.core import engine

    texts = [t for t in engine.compiled_texts()
             if t.startswith("HloModule jit__run_skip_jit")]
    assert texts
    monkeypatch.setattr(engine, "compiled_texts", lambda: texts)
    scopes = _scopes.glue_scopes()
    fusions = {k: v for k, v in scopes.items()
               if (m := OPCODE.search(k + "(")) and m.group(1) == "fusion"}
    assert "pre" in fusions.values() and "post" in fusions.values()


def test_glue_readers_split_device_time_by_scope(runs):
    scopes = _scopes.glue_scopes()
    pre = next(k for k, v in scopes.items() if v == "pre")
    post = next(k for k, v in scopes.items() if v == "post")
    device = {"device": 0, "busy_s": 0.01, "span_s": 0.02, "idle_s": 0.01,
              "ops": {pre: 2e-3, post: 3e-3, "%other = s32[] add": 1e-3},
              "kernels": {"fused_fsm": {"seconds": 1e-4, "events": 100}}}
    cell = "ddr4-2ch.decode.l1"
    ctx = _ctx(cell, runs[cell], trace={"window_s": 0.02,
                                        "devices": [device]})
    assert harness.reader("pre_us_per_step.l1").read(ctx) == \
        pytest.approx(20.0)
    assert harness.reader("post_us_per_step.l1").read(ctx) == \
        pytest.approx(30.0)
