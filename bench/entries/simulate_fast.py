"""``simulate_fast``: one lane, one whole trace."""

from __future__ import annotations

from typing import Dict, Optional

from bench import generators
from bench.entries import Entry, Job


def trace_of(arrays):
    """The program's Trace of generated arrays."""
    import jax.numpy as jnp

    from repro.core.simulator import Trace

    return Trace(*(jnp.asarray(a) for a in arrays))


def ref_trace_of(arrays):
    """The reference's Trace of the same arrays."""
    import jax.numpy as jnp

    from bench.reference.simulator import Trace

    return Trace(*(jnp.asarray(a) for a in arrays))


class SimulateFast(Entry):
    """One lane, one whole trace, on ``repro.core.simulate_fast``."""

    def __init__(self, config, traffic, **kw):
        super().__init__(config, traffic, **kw)
        from repro.core import simulate_fast

        self.program = simulate_fast
        self.generate = generators.get(traffic["generator"])
        self.horizon = int(traffic["horizon"])

    def inputs(self, seed: int):
        return self.generate(self.traffic["params"], seed)

    def job(self, seed: int, horizon: Optional[int] = None) -> Job:
        horizon = self.horizon if horizon is None else horizon
        arrays = self.inputs(seed)
        tm: Dict = {}
        res = self.program(self.cfg, trace_of(arrays), horizon,
                           params=self.params, timings=tm)
        return Job(seed=seed, inputs=arrays, outputs=[res],
                   lane_cycles=horizon, clock_cycles=horizon,
                   steps=int(tm["steps"]), windows=0,
                   devices_used=[0], lanes=1)

    def warm_up(self, seed: int) -> Job:
        return self.job(seed, int(self.traffic["warmup_horizon"]))

    def reference(self, job: Job, lane: int):
        from bench.reference.simulator import simulate

        return simulate(self.ref_cfg, ref_trace_of(job.inputs),
                        job.clock_cycles, params=self.ref_params)


ENTRY = SimulateFast
