"""What the readers of a ``"jedec"`` sweep share: the program's spans of
the traced jobs' ``memsim.sweep_grid`` calls.

The readers find the calls themselves: the root of a sweep's call is
``memsim.sweep_grid`` whatever entry drove it, and
:data:`bench.metrics._spans.ROOTS` knows only the entries it was written
for. Two kinds of reading:

* the ``"jedec"`` grant counters the program adds to its
  ``memsim.engine.to_result`` spans (``repro.core.engine.count_grants``),
  summed over the calls; a counter is read off and on the chip alike;
* a host phase's self time per job, on the chip only (see
  :mod:`bench.metrics._spans`).

A program that records no spans, or none of these counters, gives
nothing to read: the readers then return None."""

from __future__ import annotations

from typing import Dict, List, Optional

from bench.metrics._spans import on_chip, total_self_ns

ROOT = "memsim.sweep_grid"


def calls(ctx: Dict) -> Optional[List[list]]:
    """The traced jobs' sweep calls (each its list of spans), or None."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    jobs = len(ctx["jobs"])
    found = tracing.recent(jobs, ROOT) if jobs else []
    return found if jobs and len(found) == jobs else None


def totals(ctx: Dict) -> Optional[Dict[str, int]]:
    """Each span counter summed over the traced jobs' calls, or None."""
    found = calls(ctx)
    if found is None:
        return None
    out: Dict[str, int] = {}
    for call in found:
        for s in call:
            for k, v in s.counters.items():
                out[k] = out.get(k, 0) + v
    return out


def pct(ctx: Dict, part: str, whole: str) -> Optional[float]:
    """``part`` as a percent of ``whole`` over the traced jobs, or None
    where the calls hold neither or ``whole`` is 0."""
    t = totals(ctx)
    if not t or part not in t or not t.get(whole):
        return None
    return 100.0 * t[part] / t[whole]


def ms_per_job(ctx: Dict, name: str) -> Optional[float]:
    """Self time of the spans named ``name`` per job, in milliseconds, on
    the chip only."""
    found = calls(ctx) if on_chip(ctx) else None
    return 1e-6 * total_self_ns(found, name) / len(found) if found else None
