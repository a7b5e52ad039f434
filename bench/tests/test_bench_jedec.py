"""The DDR5-4800 cell rehearsed on the CPU at a tiny size, past the look
for a chip (the :mod:`bench.tests.tiny` pattern: the cell's own
configuration and entry, a short trace, few lanes, a low cycle cap): a
sound run is correct, the control and every planted fault make it
incorrect; every job is the same work; and the cell's own readers: the
grant-counter shares, the sweep's host-span times and the roofline's byte
count."""

from __future__ import annotations

import copy

import numpy as np
import pytest

from bench import entries, harness
from bench.roofline import step_bytes
from bench.roofline_jedec import step_bytes_jedec
from bench.tests import faults

CELL = "ddr5-4800.decode.dense.sweep64"
SEED = 2**33 + 13
TINY = {"params": {"tokens": 2, "reads_per_token": 32, "compute_gap": 1000,
                   "kv_frac": 0.25, "draw_seed": 0},
        "horizon": 2600, "warmup_horizon": 1200,
        "grid": {"page_policy": ["closed", "open"], "tCCDL": [12, 16]},
        "compare_lanes": 4}


def cell():
    """``(config, traffic)`` of the cell at its tiny size."""
    _, config, traffic = harness.cell_files(harness.load_manifest(), CELL)
    return config, dict(copy.deepcopy(traffic), **TINY)


def entry(**kw):
    config, traffic = cell()
    return entries.get(traffic["entry"])(config, traffic, **kw)


def run(e=None, trace=False):
    config, traffic = cell()
    return harness.run(CELL, SEED, 0.2, trace, config=config,
                       traffic=traffic, entry=e)


@pytest.fixture(scope="module")
def sound():
    return run(trace=True)


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert all(c["value"] == 0 for k, c in sound["checks"].items()
               if k.endswith("_mismatched"))
    assert sound["checks"]["protocol_violations"] == {"value": 0,
                                                      "limit": 0}
    assert sound["checks"]["lanes_compared"]["value"] == 4


def test_traced_run_off_the_chip_reports_the_counts(sound):
    # the CPU trace holds no TPU plane: the device metrics are left out,
    # the program's counters are not
    assert set(sound["metrics"]) == {"steps_per_kcycle.ddr5",
                                     "l_bound_pct.ddr5",
                                     "pre_held_pct.ddr5",
                                     "windowed_pct.ddr5"}
    assert 0 < sound["metrics"]["pre_held_pct.ddr5"]["value"] <= 100
    assert 0 < sound["metrics"]["windowed_pct.ddr5"]["value"] <= 100
    assert 0 <= sound["metrics"]["l_bound_pct.ddr5"]["value"] <= 100


def test_control_is_not_correct():
    out = run(entry(control=True))
    assert not out["correct"]
    assert out["checks"]["records_mismatched"]["value"] > 0


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_is_not_correct(fault):
    out = run(faults.plant(entry(), fault))
    assert not out["correct"], (fault, out["checks"])


def test_a_broken_window_in_the_command_log_is_counted():
    # the reference's log goes through the protocol checker: an ACT moved
    # onto the one before it in its rank breaks tRRDS at least
    e = entry()
    job = e.job(SEED)
    res, log, cfg = e.reference(job, 0)
    assert e.compare(job.outputs[0], (res, log, cfg)) == {
        "records_mismatched": 0, "counters_mismatched": 0,
        "protocol_violations": 0}
    acts = np.nonzero(log[:, 3] == 1)[0]
    bad = log.copy()
    bad[acts[1], 0] = bad[acts[0], 0]
    bad[acts[1], 2] = bad[acts[0], 2] ^ 4      # another group, same rank
    assert e.compare(job.outputs[0], (res, bad, cfg))[
        "protocol_violations"] > 0


def test_every_seed_is_the_same_work():
    # a job's seed relabels rows only: the same steps, records and
    # counters on every lane, other addresses and words read
    from repro.core.dram_model import decode_address

    e = entry()
    a, b = e.job(SEED + 1), e.job(SEED + 2)
    assert a.steps == b.steps
    assert not np.array_equal(a.inputs[1], b.inputs[1])
    topo = e.cfg.topology()
    bank_a, rank_a, row_a = map(np.asarray, decode_address(topo, a.inputs[1]))
    bank_b, rank_b, row_b = map(np.asarray, decode_address(topo, b.inputs[1]))
    assert np.array_equal(bank_a, bank_b) and np.array_equal(rank_a, rank_b)
    same = row_a[:, None] == row_a[None, :]
    assert np.array_equal(same, row_b[:, None] == row_b[None, :])
    col = (1 << (topo.addr_low_bits + topo.column_bits)) - 1
    assert np.array_equal(a.inputs[1] & col, b.inputs[1] & col)
    for ra, rb in zip(a.outputs, b.outputs):
        for f in ("t_admit", "t_dispatch", "t_start", "t_complete"):
            assert np.array_equal(getattr(ra, f), getattr(rb, f)), f
        assert _counters_equal(ra, rb)


def _counters_equal(ra, rb) -> bool:
    return (ra.counters.keys() == rb.counters.keys()
            and all(np.array_equal(ra.counters[k], rb.counters[k])
                    for k in ra.counters))


def _ctx(jobs, trace=None, peaks=None):
    config, traffic = cell()
    return dict(config=config, traffic=traffic, jobs=jobs, trace=trace,
                peaks=peaks, window_s=1.0, setup_s=1.0)


def test_grant_readers_are_the_lanes_counters():
    e = entry()
    e.warm_up(SEED)
    jobs = [e.job(SEED + k) for k in (1, 2)]

    def total(key):
        return sum(int(r.counters[key]) for j in jobs for r in j.outputs)

    def cmds(*kinds):
        return sum(int(np.asarray(r.counters["cmd_counts"])[k])
                   for j in jobs for r in j.outputs for k in kinds)

    pre, grants = cmds(4), cmds(1, 2, 3)
    assert total("grants_windowed") > 0 and pre > 0
    ctx = _ctx(jobs)
    assert harness.reader("l_bound_pct.ddr5").read(ctx) == pytest.approx(
        100.0 * total("grants_l_bound") / total("grants_windowed"))
    assert harness.reader("pre_held_pct.ddr5").read(ctx) == pytest.approx(
        100.0 * total("pre_held") / pre)
    assert harness.reader("windowed_pct.ddr5").read(ctx) == pytest.approx(
        100.0 * total("grants_windowed") / grants)
    for name in ("l_bound_pct.ddr5", "pre_held_pct.ddr5",
                 "windowed_pct.ddr5", "jedec_fsm_roofline.ddr5",
                 "sweep_prep_ms_per_job.ddr5",
                 "sweep_result_ms_per_job.ddr5"):
        assert harness.reader(name).read(_ctx([])) is None, name


@pytest.mark.parametrize("metric,span", [
    ("sweep_prep_ms_per_job.ddr5", "memsim.engine.prepare"),
    ("sweep_result_ms_per_job.ddr5", "memsim.engine.to_result"),
])
def test_sweep_span_readers_read_self_time_on_the_chip(metric, span):
    from repro.core import tracing

    e = entry()
    e.warm_up(SEED)
    jobs = [e.job(SEED + k) for k in (1, 2)]
    found = tracing.recent(2, "memsim.sweep_grid")
    want = 0
    for call in found:
        for i, s in enumerate(call):
            if s.name == span:
                kids = sum(c.end_ns - c.start_ns for c in call
                           if c.parent == i)
                want += s.end_ns - s.start_ns - kids
    chip = {"window_s": 0.02, "devices": [
        {"device": 0, "busy_s": 0.01, "span_s": 0.02, "idle_s": 0.01,
         "ops": {}, "kernels": {}}]}
    value = harness.reader(metric).read(_ctx(jobs, trace=chip))
    assert value is not None and value > 0
    assert value == pytest.approx(1e-6 * want / 2, rel=1e-12)
    # off the chip the host phases time a CPU run: left out
    assert harness.reader(metric).read(_ctx(jobs)) is None


def test_grant_readers_give_none_for_a_table1_sweep():
    from bench.tests import tiny

    config, traffic = tiny.cell("ddr4-2ch.decode.sweep64")
    e = entries.get(traffic["entry"])(config, traffic)
    jobs = [e.job(SEED)]
    ctx = dict(_ctx(jobs), config=config, traffic=traffic)
    assert harness.reader("l_bound_pct.ddr5").read(ctx) is None
    assert harness.reader("pre_held_pct.ddr5").read(ctx) is None
    assert harness.reader("windowed_pct.ddr5").read(ctx) is None


def test_step_bytes_jedec_counts_the_group_windows_and_gates():
    config, _ = cell()
    # on top of the configuration's step: 4 ranks x 8 bank groups x 3
    # windows and 128 banks' precharge gates, each read and written once
    assert step_bytes_jedec(config, 1) == (step_bytes(config, 1)
                                           + 2 * 4 * (4 * 8 * 3 + 128))
    # 128 banks x (10 state + 6 queue head) + 4 ranks x 7 timing words
    # + 64 x 4 response-queue words + 2
    assert step_bytes(config, 1) == 2 * 4 * (128 * 16 + 4 * 7 + 64 * 4 + 2)
    assert step_bytes_jedec(config, 64) == 64 * step_bytes_jedec(config, 1)


def test_roofline_share_is_bytes_over_peak_over_kernel_time():
    config, _ = cell()

    class Job:
        lanes = 64

    peaks = {"hbm_bytes_per_s": 819e9}
    device = {"device": 0, "busy_s": 0.01, "span_s": 0.02, "idle_s": 0.01,
              "ops": {}, "kernels": {"fused_fsm": {"seconds": 1e-2,
                                                   "events": 1000}}}
    trace = {"window_s": 0.02, "devices": [device]}
    reader = harness.reader("jedec_fsm_roofline.ddr5")
    share = reader.read(_ctx([Job()], trace, peaks))
    want = 100.0 * step_bytes_jedec(config, 64) / 819e9 / 1e-5
    assert share == pytest.approx(want)
    device["kernels"]["fused_fsm"]["seconds"] = 1e-9
    with pytest.raises(ValueError, match="over 100%"):
        reader.read(_ctx([Job()], trace, peaks))
