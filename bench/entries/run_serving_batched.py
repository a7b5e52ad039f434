"""``run_serving_batched``: closed-loop serving lanes to a cycle cap."""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from bench import generators
from bench.entries import (Entry, Job, counter_mismatches,
                           record_mismatches)

#: the serving summary fields compared lane by lane
SERVING_FIELDS = ("offered", "completed", "tokens", "cycles",
                  "admitted_batch", "batch_target", "queueing", "service")


class RunServingBatched(Entry):
    """Closed-loop serving lanes on ``repro.serving.run_serving_batched``:
    one lane per (mixture, load) scenario, each with its own request
    stream and scheduler seed, to the configuration's cycle cap."""

    def __init__(self, config, traffic, **kw):
        super().__init__(config, traffic, **kw)
        from repro.serving import ServingConfig, run_serving_batched

        self.program = run_serving_batched
        self.serving_doc = dict(config.get("serving", {}))
        self.serving = ServingConfig(**self.serving_doc)
        self.generate = generators.get(traffic["generator"])
        self.window = int(traffic["window_cycles"])
        self.capacity = int(traffic["capacity"])
        self.max_cycles = int(config["max_cycles"])
        # at most one arrival per cycle is emitted, so a lane capped at
        # max_cycles never outgrows the arrival buffer, whatever the seed
        if self.capacity < self.max_cycles:
            raise ValueError(f"capacity {self.capacity} below the cycle cap "
                             f"{self.max_cycles}")

    def job(self, seed: int, max_cycles: Optional[int] = None) -> Job:
        max_cycles = self.max_cycles if max_cycles is None else max_cycles
        lists, seeds = self.generate(self.traffic["params"], seed)
        tm: Dict = {}
        res = self.program(self.cfg, lists, self.serving, params=self.params,
                           window_cycles=self.window, capacity=self.capacity,
                           max_cycles=max_cycles, timings=tm, seeds=seeds)
        end = max(r.cycles for r in res)
        return Job(seed=seed, inputs=(lists, seeds, max_cycles, end),
                   outputs=list(res),
                   lane_cycles=sum(r.cycles for r in res), clock_cycles=end,
                   steps=int(tm.get("steps", 0)),
                   windows=-(-end // self.window), devices_used=[0],
                   lanes=len(res))

    def warm_up(self, seed: int) -> Job:
        return self.job(seed, int(self.traffic["warmup_max_cycles"]))

    def reference(self, job: Job, lane: int):
        from bench.reference import serving as ref

        lists, seeds, max_cycles, end = job.inputs
        return ref.run_serving(
            self.ref_cfg, lists[lane], ref.ServingConfig(**self.serving_doc),
            params=self.ref_params, window_cycles=self.window,
            capacity=self.capacity, max_cycles=max_cycles, seed=seeds[lane],
            end_cycle=end)

    def program_side(self, job: Job, lane: int):
        res = job.outputs[lane]
        tr = res.session.trace()
        return dict({f: getattr(res, f) for f in SERVING_FIELDS},
                    trace=[np.asarray(x) for x in (tr.t, tr.addr,
                                                   tr.is_write)],
                    result=res.session.result())

    def compare(self, res, ref) -> Dict[str, int]:
        summary, session = ref
        bad = sum(not np.array_equal(np.asarray(summary[f]),
                                     np.asarray(res[f]))
                  for f in SERVING_FIELDS)
        # the emitted address stream, slot by slot
        n = session.n
        bad += sum(not np.array_equal(mine, theirs) for mine, theirs in zip(
            (session.t[:n], session.addr[:n], session.is_write[:n]),
            res["trace"]))
        ref_res = session.result()
        return {"records_mismatched": record_mismatches(ref_res,
                                                        res["result"]),
                "counters_mismatched": counter_mismatches(ref_res,
                                                          res["result"]),
                "serving_mismatched": int(bad)}


ENTRY = RunServingBatched
