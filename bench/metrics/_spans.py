"""What the span readers share: the program's own spans of the traced jobs.

The program records spans at its layer boundaries
(``repro.core.tracing``): a root span per API call and children for its
phases. A reader takes the spans of the last ``len(ctx["jobs"])`` calls
whose root names the cell's entry point; those are the window's jobs, the
warm-up comes before them, and spans the correctness check opens after
the window have other roots. A phase's time is its *self* time: the
span's duration less the time its child spans cover.

A time is read only where the run's trace holds the chip: off it the
kernels run in Pallas's interpreter, so the host phases time a CPU run
that says nothing of a chip run, and a traced run there reports counts
only. A program without the tracing module records no spans: every reader
then returns None, and the harness leaves the metric out."""

from __future__ import annotations

from typing import Dict, List, Optional

#: the root span of each entry point (``traffic["entry"]``)
ROOTS = {"simulate_fast": "memsim.simulate_fast",
         "sweep_grid": "memsim.sweep_grid",
         "run_serving_batched": "memsim.serve"}
WINDOW = "memsim.serve.window"


def calls(ctx: Dict) -> Optional[List[list]]:
    """The traced jobs' calls (each its list of spans), or None where the
    program records none or the run has no jobs."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    root = ROOTS.get(ctx["traffic"]["entry"])
    jobs = len(ctx["jobs"])
    if root is None or not jobs:
        return None
    found = tracing.recent(jobs, root)
    return found if len(found) == jobs else None


def on_chip(ctx: Dict) -> bool:
    """Whether the run's reduced trace holds a device plane."""
    return bool(ctx["trace"] and ctx["trace"]["devices"])


def self_ns(call: list) -> List[int]:
    """Self time of each span of one call, in nanoseconds, by index."""
    own = [s.end_ns - s.start_ns for s in call]
    for s in call:
        if s.parent is not None:
            own[s.parent] -= s.end_ns - s.start_ns
    return own


def total_self_ns(found: List[list], name: str) -> int:
    """Summed self time of the spans named ``name`` over the calls."""
    out = 0
    for call in found:
        own = self_ns(call)
        out += sum(t for s, t in zip(call, own) if s.name == name)
    return out


def windows(found: List[list]) -> int:
    """Closed-loop windows in the calls (``memsim.serve.window`` spans)."""
    return sum(s.name == WINDOW for call in found for s in call)


def ms_per_window(ctx: Dict, name: str) -> Optional[float]:
    """Self time of the spans named ``name`` per closed-loop window, in
    milliseconds, on the chip only."""
    found = calls(ctx) if on_chip(ctx) else None
    n = windows(found) if found else 0
    return 1e-6 * total_self_ns(found, name) / n if n else None


def ms_per_job(ctx: Dict, name: str) -> Optional[float]:
    """Self time of the spans named ``name`` per job, in milliseconds, on
    the chip only."""
    found = calls(ctx) if on_chip(ctx) else None
    return 1e-6 * total_self_ns(found, name) / len(found) if found else None
