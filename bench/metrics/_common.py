"""What the readers share: the executed steps and per-device trace means.

Trace numbers are taken per device over its recorded span (see
:mod:`bench.trace_reduce`) and averaged over the devices. A step's device
time is divided by the fused kernel's events on that device: the engine
makes one fused call per executed step, so they count the steps the span
holds, also where the profiler dropped the end of a long job."""

from __future__ import annotations

from statistics import fmean
from typing import Callable, Dict, Optional

KERNEL = "fused_fsm"


def steps(ctx: Dict) -> int:
    """Executed steps of the traced jobs (the busiest device's count where
    lanes are split over devices), from the engine's counters."""
    return sum(j.steps for j in ctx["jobs"])


def device_mean(ctx: Dict, fn: Callable[[Dict], Optional[float]]):
    """Mean over the traced devices of ``fn(device)``, or None where the
    trace holds no device or ``fn`` finds nothing on one."""
    tr = ctx["trace"]
    if tr is None or not tr["devices"]:
        return None
    vals = [fn(d) for d in tr["devices"]]
    return None if any(v is None for v in vals) else fmean(vals)


def kernel(d: Dict):
    """``(seconds, steps)`` of the fused kernel on device ``d``, or None."""
    k = d["kernels"][KERNEL]
    return (k["seconds"], k["events"]) if k["events"] else None
