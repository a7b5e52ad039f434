"""``sweep_grid``: a runtime-parameter grid as lanes of one program."""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional

from bench.entries import Job
from bench.entries.simulate_fast import SimulateFast, ref_trace_of, trace_of


def _devices_used(tm: Dict) -> List[int]:
    """The devices a sweep's lanes ran on, from the engine's timings: the
    batched path names them, the lanes path records each lane's device."""
    if "devices_used" in tm:
        return list(tm["devices_used"])
    return sorted({p["device"] for p in tm.get("per_lane", [])}) or [0]


def grid_points(grid: Dict) -> List[Dict]:
    """Cartesian product of the grid's axes, last axis fastest."""
    keys = list(grid)
    return [dict(zip(keys, vals))
            for vals in itertools.product(*(grid[k] for k in keys))]


class SweepGrid(SimulateFast):
    """A runtime-parameter grid as lanes of one program,
    ``repro.core.sweep_grid``, in the platform's default batch mode."""

    def __init__(self, config, traffic, **kw):
        super().__init__(config, traffic, **kw)
        from repro.core import sweep_grid

        self.program = sweep_grid
        self.grid = {k: list(v) for k, v in traffic["grid"].items()}
        self.points = grid_points(self.grid)
        # the control's break holds on every lane, a swept axis included
        self.run_grid = {k: [self.control.get(k, x) for x in v]
                         for k, v in self.grid.items()}
        if self.params is not None:
            raise ValueError("sweep_grid cells take one-tier configurations")

    def job(self, seed: int, horizon: Optional[int] = None) -> Job:
        horizon = self.horizon if horizon is None else horizon
        arrays = self.inputs(seed)
        tm: Dict = {}
        res = self.program(self.cfg, trace_of(arrays), self.run_grid,
                           horizon, timings=tm)
        return Job(seed=seed, inputs=arrays, outputs=list(res),
                   lane_cycles=horizon * len(self.points),
                   clock_cycles=horizon, steps=int(tm["steps"]), windows=0,
                   devices_used=_devices_used(tm),
                   lanes=len(self.points))

    def reference(self, job: Job, lane: int):
        from bench.reference.simulator import simulate

        point = dict(self.points[lane])
        q = point.pop("queue_size", self.ref_cfg.queue_size)
        cfg = dataclasses.replace(self.ref_cfg, **point)
        return simulate(cfg, ref_trace_of(job.inputs), job.clock_cycles,
                        queue_size=q)


ENTRY = SweepGrid
