"""The trace reduction on a short trace recorded on a TPU v5 lite: one
``ddr4-2ch.decode.l1`` job of 700 cycles inside the window annotation."""

from pathlib import Path

import pytest

from bench import harness, trace_reduce

TRACE = Path(__file__).resolve().parent / "data" / "tiny_l1.xplane.pb"
# read off this trace when it was recorded (TPU v5 lite, one chip)
WINDOW_S = 0.037091134
BUSY_S = 0.00426755
STEPS = 100


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_xplane(TRACE)


def test_one_device_with_busy_kernel_and_idle(reduced):
    (dev,) = reduced["devices"]
    assert dev["device"] == 0
    assert reduced["window_s"] == pytest.approx(WINDOW_S, abs=1e-9)
    assert dev["busy_s"] == pytest.approx(BUSY_S, abs=1e-9)
    assert dev["busy_s"] + dev["idle_s"] == pytest.approx(dev["span_s"])
    assert dev["span_s"] <= reduced["window_s"]
    k = dev["kernels"]["fused_fsm"]
    # one fused kernel call per executed step
    assert k["events"] == STEPS
    assert 0 < k["seconds"] < dev["busy_s"]


def test_ops_leave_out_the_loop_that_contains_them(reduced):
    ops = reduced["devices"][0]["ops"]
    assert not any(name.startswith("%while") for name in ops)
    assert sum(ops.values()) <= reduced["devices"][0]["busy_s"] * 1.0001


def test_program_runs_are_counted(reduced):
    modules = reduced["devices"][0]["modules"]
    assert sum(n for m, n in modules.items() if "_run_skip_jit" in m) == 1


def test_gaps_are_longest_first_and_named(reduced):
    gaps = reduced["devices"][0]["gaps"]
    assert [g[0] for g in gaps] == sorted((g[0] for g in gaps), reverse=True)
    assert all(isinstance(g[1], str) and g[1] for g in gaps)


def test_breakdown_lists_at_most_ten_of_each(reduced):
    b = harness.breakdown(reduced)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(s, float) for _, s in b["device_ops"])


def test_missing_window_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 9), (0, 2), (1, 3), (8, 12)]) == [
        (0, 3), (5, 12)]


def test_readers_on_the_recorded_trace(reduced):
    from bench.entries import Job
    from bench.roofline import peaks

    config = harness.cell_files(harness.load_manifest(),
                                "ddr4-2ch.decode.l1")[1]
    job = Job(seed=4, inputs=None, outputs=None, lane_cycles=700,
              clock_cycles=700, steps=STEPS, windows=0, devices_used=[0],
              lanes=1)
    ctx = dict(jobs=[job], window_s=reduced["window_s"], setup_s=1.0,
               config=config, traffic={}, trace=reduced,
               peaks=peaks("TPU v5 lite"))
    dev = reduced["devices"][0]
    k = dev["kernels"]["fused_fsm"]["seconds"]
    read = {m: harness.reader(m + ".l1").read(ctx) for m in (
        "kernel_us_per_step", "glue_us_per_step", "fused_fsm_roofline",
        "idle_pct", "steps_per_kcycle")}
    assert read["kernel_us_per_step"] == pytest.approx(1e6 * k / STEPS)
    assert read["glue_us_per_step"] == pytest.approx(
        1e6 * (dev["busy_s"] - k) / STEPS)
    assert 0 < read["fused_fsm_roofline"] < 100
    assert read["idle_pct"] == pytest.approx(
        100 * dev["idle_s"] / dev["span_s"])
    assert read["steps_per_kcycle"] == pytest.approx(1000 * STEPS / 700)
    # no closed-loop window ran here: the serving reader finds nothing
    assert harness.reader("host_gap_ms_per_window.serve").read(ctx) is None
