"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/readings.py --workload ddr4-2ch.decode.l1 \\
        --seeds 101,102,103 --control-seeds 201,202,203

For each seed, one job at the cell's own size goes through the cell's
entry and the same check a benchmark run makes; then the same again for
the control (the program with one stated guarantee broken, see
``bench.entries.CONTROL``). Each reading is printed as one JSON line:
the seed, ``program`` or ``control``, and every number compared.

A benchmark run's jobs all serve the cell's one request set
(``params["draw_seed"]``) in orders drawn from their seeds, so that every
run does the same work. A reading instead draws a request set of its own
from its seed (``draw_seed`` in its line), so that the readings cover as
many traffic draws as seeds. No window is measured; the lower reading of
a number is the largest sound reading, the upper the smallest control
reading. Runs on the chip, in one process, like a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
#: the job number whose seed a reading's request set is drawn from
DRAW = 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import entries, generators, harness
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import numpy as np

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 3
    _, config, traffic = harness.cell_files(harness.load_manifest(ROOT),
                                            args.workload, ROOT)
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        seeds = [int(s) for s in seeds.split(",") if s]
        if not seeds:
            continue
        for i, seed in enumerate(seeds):
            draw = generators.job_seed(seed, DRAW)
            entry = entries.get(traffic["entry"])(
                config, dict(traffic, params=dict(traffic["params"],
                                                  draw_seed=draw)),
                control=kind == "control")
            if i == 0:
                entry.warm_up(generators.job_seed(seed, 0))
            t0 = time.perf_counter()
            job = entry.job(generators.job_seed(seed, 1))
            job_s = time.perf_counter() - t0
            rng = np.random.default_rng(generators.job_seed(seed, 1 << 30))
            numbers = entry.check([job], rng)
            print(json.dumps({"workload": args.workload, "kind": kind,
                              "seed": seed, "draw_seed": draw,
                              "job_s": job_s,
                              "steps": job.steps,
                              "check_s": time.perf_counter() - t0 - job_s,
                              **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
