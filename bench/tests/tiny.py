"""Each cell at a size a CPU test holds: the cell's own configuration and
entry, with a short trace, few lanes and a low cycle cap."""

from __future__ import annotations

import copy

from bench import harness

DECODE = {"tokens": 2, "reads_per_token": 16, "compute_gap": 4000,
          "kv_frac": 0.25, "draw_seed": 0}
TRAFFIC = {
    "ddr4-2ch.decode.l1": {"params": DECODE, "horizon": 9000,
                           "warmup_horizon": 2000},
    "ddr4-2ch.decode.sweep64": {
        "params": DECODE, "horizon": 9000, "warmup_horizon": 2000,
        "grid": {"tCL": [14, 18], "queue_size": [16, 128]},
        "compare_lanes": 4},
    "ddr4-cxl.serve.l8": {
        "params": {"process": "poisson", "mixture": ["chat"],
                   "rate_per_kcycle": [1.0, 4.0], "horizon": 2000,
                   "draw_seed": 0},
        "window_cycles": 1000, "capacity": 8192, "warmup_max_cycles": 2000,
        "compare_lanes": 2},
}
MAX_CYCLES = 5000


def cell(name: str):
    """``(config, traffic)`` of cell ``name`` at its tiny size."""
    _, config, traffic = harness.cell_files(harness.load_manifest(), name)
    traffic = dict(copy.deepcopy(traffic), **TRAFFIC[name])
    if "max_cycles" in config:
        config = dict(config, max_cycles=MAX_CYCLES)
    return config, traffic


def run(name: str, entry=None, seed: int = 2**33 + 7, trace: bool = False):
    """One tiny run of ``name`` past the look for a chip."""
    config, traffic = cell(name)
    return harness.run(name, seed, 0.2, trace, config=config,
                       traffic=traffic, entry=entry)
