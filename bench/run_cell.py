"""Run one benchmark cell on the chip and print its result line.

    python bench/run_cell.py --workload ddr4-2ch.decode.l1 --seed 7 \\
        --seconds 10 --trace 0

The cell, its configuration, traffic and metrics are found by name from
``BENCHMARK.json`` (see :mod:`bench.harness`). The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``, each number compared with the reference beside its limit;
the checks are also the last lines of standard error.

Exit codes: 0 with a result; 1 when the run failed (a compile inside the
window, an error); 2 when the repo's sources are missing; 3 when JAX finds
no TPU, or fewer chips than the cell asks for. Only a run on a TPU prints
a result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    if not (ROOT / "src" / "repro").is_dir():
        print("run_cell: the repo's sources (src/repro) are missing",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    manifest = harness.load_manifest(ROOT)
    cell, _, _ = harness.cell_files(manifest, args.workload, ROOT)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # every program, however quick to compile, goes to the cache, so that
    # only a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run_cell: no TPU (platform {devices[0].platform!r}); no "
              "result", file=sys.stderr)
        return 3
    if len(devices) < int(cell["chips"]):
        print(f"run_cell: {args.workload} needs {cell['chips']} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 3
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START,
                          manifest=manifest)
    except Exception:  # the run failed: say why, print no result
        traceback.print_exc()
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
