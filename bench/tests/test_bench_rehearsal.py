"""Each one-chip cell rehearsed on the CPU at a tiny size, past the look
for a chip: a sound run is correct; the control and every fault that the
cell can have make it incorrect."""

import pytest

from bench import entries
from bench.tests import faults
from bench.tests import tiny

CELLS = ["ddr4-2ch.decode.l1", "ddr4-2ch.decode.sweep64",
         "ddr4-cxl.serve.l8"]
CELL_FAULTS = [(c, f) for c in CELLS for f in faults.FAULTS
               if not (c.endswith(".l1") and f == "half_batch")]


def _entry(cell, **kw):
    config, traffic = tiny.cell(cell)
    return entries.get(traffic["entry"])(config, traffic, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = tiny.run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert "setup_s" in out["metrics"] and len(out["metrics"]) == 2
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 for k, c in out["checks"].items()
               if k.endswith("_mismatched"))


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    out = tiny.run(cell, entry=_entry(cell, control=True))
    assert not out["correct"]
    assert out["checks"]["records_mismatched"]["value"] > 0


@pytest.mark.parametrize("cell,fault", CELL_FAULTS)
def test_fault_is_not_correct(cell, fault):
    out = tiny.run(cell, entry=faults.plant(_entry(cell), fault))
    assert not out["correct"], (fault, out["checks"])


def test_traced_run_off_the_chip_reports_only_counts():
    out = tiny.run("ddr4-2ch.decode.l1", trace=True)
    assert out["correct"]
    # the CPU trace holds no TPU plane: every device metric is left out
    assert set(out["metrics"]) == {"steps_per_kcycle.l1"}
    assert out["device"]["busy_s"] == 0.0
