"""One run of one cell: set-up, the measured window, the check, the metrics.

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (a file under ``bench/configs``) and its traffic mix
(``bench/traffic/<traffic>.json``, whose ``entry`` names a module under
``bench/entries``, see :func:`bench.entries.get`), and each metric ``<name>`` is read by the module
in ``bench/metrics`` whose name is the longest dotted prefix of it. A new
cell, mix, configuration or metric is new files and new manifest entries.

A run (:func:`run`):

1. builds the cell's entry and warms up its compiled programs with one
   short job of the same shapes (set-up, ``setup_s``);
2. runs jobs back to back, each with its own seed drawn from ``--seed``,
   and closes the window at the first job boundary after ``seconds``;
   compiles inside the window are counted and must be none;
3. reads the device's peak memory, then checks a sample of the window's
   results against the plain reference on the CPU backend;
4. reports the end-to-end metrics, or, with ``trace``, traces the window
   and reports the per-layer metrics from the reduced trace.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from statistics import fmean
from typing import Dict, List, Optional

import numpy as np

from bench import generators

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: the longest a traced window runs before it closes at a job boundary
TRACE_SECONDS = 2.0


def load_manifest(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_files(manifest: Dict, name: str, root: Path = ROOT):
    """``(cell, config, traffic)`` of the workload ``name``."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def _reported_in(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(manifest: Dict, cell: str, traced: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``traced`` its per-layer metrics (those without a ``workloads``
    key go to every cell that reports the end-to-end metric they move)."""
    e2e = [m for m in manifest["end_to_end"] if _reported_in(m, cell)]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def reader(metric: str):
    """The module that reads ``metric``: the longest dotted prefix of its
    name that is a module under ``bench/metrics``."""
    parts = metric.split(".")
    for n in range(len(parts), 0, -1):
        mod = ".".join(parts[:n])
        if (BENCH / "metrics" / f"{mod}.py").is_file():
            return importlib.import_module(f"bench.metrics.{mod}")
    raise KeyError(f"no reader for metric {metric!r} under bench/metrics")


class CompileCounter:
    """Counts the XLA programs this process builds, through jax.monitoring:
    every request (``count``) and those loaded from the persistent
    compile cache (``loaded``); the rest were compiled afresh."""

    def __init__(self):
        import jax

        self.count = self.loaded = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _device_memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _limits(numbers: Dict[str, int], sample: int, chips: int,
            devices_used: int) -> Dict[str, Dict]:
    """Each number compared with its limit. Mismatch counts have the limit
    0 (an exact comparison); ``lanes_compared`` and ``devices_used`` are
    at least their limit."""
    checks = {k: {"value": v, "limit": 0} for k, v in numbers.items()
              if k != "lanes_compared"}
    checks["lanes_compared"] = {"value": numbers["lanes_compared"],
                                "limit": sample}
    if chips > 1:
        checks["devices_used"] = {"value": devices_used, "limit": chips}
    return checks


def _passes(name: str, c: Dict) -> bool:
    if name in ("lanes_compared", "devices_used"):
        return c["value"] >= c["limit"]
    return c["value"] <= c["limit"]


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, manifest: Optional[Dict] = None,
        config: Optional[Dict] = None, traffic: Optional[Dict] = None,
        entry=None, log=sys.stderr) -> Dict:
    """One run of ``cell_name``; returns the result line's object.

    ``config`` / ``traffic`` replace the cell's files (tests run cells at
    tiny sizes); ``entry`` replaces the entry built from them (tests plant
    faults in it). Raises when a compile happens inside the window."""
    import jax

    from bench import entries, trace_reduce
    from bench.roofline import peaks

    t_start = time.perf_counter() if t_start is None else t_start
    manifest = load_manifest() if manifest is None else manifest
    cell, cfg_doc, trf_doc = cell_files(manifest, cell_name)
    config = cfg_doc if config is None else config
    traffic = trf_doc if traffic is None else traffic
    chips = int(cell["chips"])
    devices = jax.devices()
    if entry is None:
        entry = entries.get(traffic["entry"])(config, traffic)

    compiles = CompileCounter()
    entry.warm_up(generators.job_seed(seed, 0))
    setup_s = time.perf_counter() - t_start
    print(f"setup_s={setup_s!r} programs_in_setup={compiles.count} "
          f"loaded_from_cache={compiles.loaded}", file=log, flush=True)

    limit_s = min(seconds, TRACE_SECONDS) if trace else seconds
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    jobs: List = []
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        before = compiles.count
        span = (jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
                if trace else contextlib.nullcontext())
        t0 = time.perf_counter()
        with span:
            while True:
                with (jax.profiler.TraceAnnotation("bench.job") if trace
                      else contextlib.nullcontext()):
                    jobs.append(entry.job(generators.job_seed(
                        seed, len(jobs) + 1)))
                if time.perf_counter() - t0 >= limit_s:
                    break
        window_s = time.perf_counter() - t0
        in_window = compiles.count - before
        if trace:
            jax.profiler.stop_trace()
        print(f"window_s={window_s!r} jobs={len(jobs)} "
              f"steps={sum(j.steps for j in jobs)} "
              f"compiles_in_window={in_window}", file=log, flush=True)
        if in_window:
            raise RuntimeError(f"{in_window} compiles inside the measured "
                               "window: a shape was not warmed up")
        memory_peak = _device_memory_peak(devices)
        reduced = (trace_reduce.reduce_xplane(
            trace_reduce.find_xplane(log_dir)) if trace else None)
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    # the window's facts, read before the check lets go of the results
    facts = dict(jobs=list(jobs), window_s=window_s, setup_s=setup_s)
    devices_used = len({d for j in jobs for d in j.devices_used})
    rng = np.random.default_rng(generators.job_seed(seed, 1 << 30))
    t_ref = time.perf_counter()
    numbers = entry.check(jobs, rng)
    print(f"reference_s={time.perf_counter() - t_ref!r}", file=log,
          flush=True)
    checks = _limits(numbers, min(traffic["compare_lanes"], jobs[0].lanes),
                     chips, devices_used)
    correct = all(_passes(k, c) for k, c in checks.items())

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    ctx = dict(facts, config=config, traffic=traffic, trace=reduced,
               peaks=peaks(dev.device_kind) if dev.platform == "tpu"
               else None)
    metrics = {}
    for m in cell_metrics(manifest, cell_name, trace):
        value = reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": correct, "attempted": len(jobs),
           "failed": 0 if correct else 1, "metrics": metrics,
           "device": device}
    if reduced is not None:
        devs = reduced["devices"]
        for d in devs:
            print(f"trace device {d['device']}: events={d['events']} "
                  f"first_s={d['first_s']!r} span_s={d['span_s']!r} "
                  f"busy_s={d['busy_s']!r} kernels={d['kernels']}",
                  file=log, flush=True)
        # busy time over the span it was measured in (see trace_reduce)
        device["busy_s"] = fmean(d["busy_s"] for d in devs) if devs else 0.0
        device["window_s"] = (fmean(d["span_s"] for d in devs) if devs
                              else reduced["window_s"])
        out["breakdown"] = breakdown(reduced)
    for k, c in checks.items():
        print(f"check {k}={c['value']} limit={c['limit']}", file=log,
              flush=True)
    out["checks"] = checks
    return out


def breakdown(reduced: Dict) -> Dict:
    """The ten device ops that took most time (mean over devices) and the
    ten longest idle gaps, named by what the host was doing."""
    devs = reduced["devices"]
    ops: Dict[str, float] = {}
    for d in devs:
        for name, s in d["ops"].items():
            ops[name] = ops.get(name, 0.0) + s / len(devs)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted((g for d in devs for g in d["gaps"]),
                  key=lambda g: -g[0])[:10]
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[name, s] for s, name in gaps]}
