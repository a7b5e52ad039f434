"""The program's entry points as the cells drive them, each with its check.

A traffic file names its ``entry``: a module ``bench/entries/<entry>.py``
whose ``ENTRY`` is a class (a subclass of :class:`Entry`) that builds the
cell's inputs from a seed (with the generator the file names,
:mod:`bench.generators`), runs one job on the program, and compares a
sample of what the job produced with the plain reference
(:mod:`bench.reference`), field for field. :func:`get` finds it by that
name, so a new entry point is a new module and no edit here:

* ``simulate_fast`` — one lane over a whole trace;
* ``sweep_grid`` — a runtime-parameter grid, one lane per point, in the
  platform's default batch mode (split over every visible device);
* ``run_serving_batched`` — closed-loop serving lanes: scheduler, KV
  pager and windowed engine, to a fixed cycle cap.

The program is called through ``self.program`` so that a test can put a
broken one in its place and see the check fail. The program runs on the
default device; the reference runs on the CPU backend, after the window.
"""

from __future__ import annotations

import dataclasses
import importlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

#: the per-request records every comparison covers
RECORD_FIELDS = ("t_admit", "t_dispatch", "t_start", "t_complete", "rdata")
#: reference lanes run side by side on the host's cores
REFERENCE_THREADS = 8
#: the control's broken guarantee: a timing window left unenforced
CONTROL = {"tRRDL": 1}


@dataclasses.dataclass
class Job:
    """One finished job: what it cost and what it produced."""

    seed: int
    inputs: object             # what the reference is given
    outputs: List[object]      # one host result per lane
    lane_cycles: int           # simulated cycles delivered, summed on lanes
    clock_cycles: int          # cycles on the engine's (shared) clock
    steps: int                 # executed steps (busiest device)
    windows: int               # closed-loop windows (0: open loop)
    devices_used: List[int]
    lanes: int


def record_mismatches(ref, res) -> int:
    """Requests whose records differ in any field (a length difference
    counts every request the shorter side lacks)."""
    n = max(len(np.asarray(ref.t_complete)), len(np.asarray(res.t_complete)))
    bad = np.zeros(n, bool)
    for f in RECORD_FIELDS:
        a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(res, f))
        m = min(a.size, b.size)
        bad[:m] |= a[:m] != b[:m]
        bad[m:] = True
    return int(bad.sum())


def counter_mismatches(ref, res) -> int:
    """Counters of the reference that differ, plus the two blocked-cycle
    totals; a counter the program lacks counts as differing."""
    out = 0
    for k, v in ref.counters.items():
        if k not in res.counters or not np.array_equal(
                np.asarray(v), np.asarray(res.counters[k])):
            out += 1
    out += int(ref.blocked_arrival != res.blocked_arrival)
    out += int(ref.blocked_dispatch != res.blocked_dispatch)
    return out


def stratified_sample(n_lanes: int, k: int, rng) -> List[int]:
    """``k`` lanes, one drawn from each of ``k`` equal blocks of the lane
    axis, so that every device's block and both halves of a batch are
    always looked at."""
    k = min(k, n_lanes)
    edges = np.linspace(0, n_lanes, k + 1).astype(int)
    return [int(rng.integers(lo, hi)) for lo, hi in zip(edges, edges[1:])]


def _cpu_device():
    import jax

    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


class Entry:
    """Base: configuration, parameters and the reference's twins of both."""

    def __init__(self, config: Dict, traffic: Dict, *,
                 control: bool = False):
        from bench.reference import params as ref_params
        from repro.core import params as prog_params

        self.config, self.traffic = config, traffic
        timings = dict(config["timings"])
        ref_timings = dict(timings)
        # the control breaks one stated guarantee in the program only: the
        # ACT-to-ACT gap (tRRDL) is no longer enforced
        self.control = CONTROL if control else {}
        timings.update(self.control)
        self.cfg = prog_params.MemSimConfig(**config["topology"], **timings,
                                            fsm_backend=config["backend"])
        self.ref_cfg = ref_params.MemSimConfig(**config["topology"],
                                               **ref_timings,
                                               fsm_backend="jnp")
        self.params = self._tier_params(self.cfg, prog_params)
        self.ref_params = self._tier_params(self.ref_cfg, ref_params)

    def _tier_params(self, cfg, mod):
        """The tier-stacked parameter point of a DRAM + CXL configuration
        (None on one tier): tier 1 is tier 0 plus the config's adders and
        scales."""
        tier = self.config.get("cxl_tier")
        if not tier:
            return None
        dram = cfg.runtime()
        cxl = dram._replace(
            **{k: getattr(dram, k) + v for k, v in tier["add"].items()},
            **{k: getattr(dram, k) * v for k, v in tier["scale"].items()})
        return mod.tiered_params(dram, cxl)

    # ---- the reference ---------------------------------------------------

    def _reference_lanes(self, fn, lanes):
        """Run ``fn(lane)`` for every lane on the CPU backend, side by
        side."""
        import jax

        cpu = _cpu_device()

        def on_cpu(lane):
            with jax.default_device(cpu):
                return fn(lane)

        with ThreadPoolExecutor(REFERENCE_THREADS) as pool:
            return list(pool.map(on_cpu, lanes))

    def check(self, jobs: List[Job], rng) -> Dict[str, int]:
        """Compare a sample, drawn with ``rng``, of what ``jobs`` produced
        with the reference: one job, and lanes spread over its batch. The
        program's side is fetched first and every job's device state let
        go before the reference runs. Returns each number compared."""
        job = jobs[int(rng.integers(len(jobs)))]
        lanes = stratified_sample(len(job.outputs),
                                  self.traffic["compare_lanes"], rng)
        mine = [self.program_side(job, lane) for lane in lanes]
        for j in jobs:
            j.outputs = None
        refs = self._reference_lanes(
            lambda lane: self.reference(job, lane), lanes)
        out = {"lanes_compared": len(lanes)}
        for res, ref in zip(mine, refs):
            for k, v in self.compare(res, ref).items():
                out[k] = out.get(k, 0) + v
        return out

    def program_side(self, job: Job, lane: int):
        return job.outputs[lane]

    def compare(self, res, ref) -> Dict[str, int]:
        return {"records_mismatched": record_mismatches(ref, res),
                "counters_mismatched": counter_mismatches(ref, res)}


def get(name: str):
    """The entry class of the module ``bench/entries/<name>.py``."""
    return importlib.import_module(f"bench.entries.{name}").ENTRY
