"""Faults planted under a cell's timed path, to show that the check fails.

:func:`plant` wraps an entry's ``program`` so that the run goes on as
usual while the program's results carry one fault:

* ``state_unchanged`` — every step returns its state unchanged: the run
  ends where it started;
* ``half_batch`` — half of the lanes are left out: their results are
  copies of the other half's;
* ``answer_altered`` — one answer is altered where it is produced: a
  request's completion cycle, or a serving lane's token count.

Used by the tests beside it; never by a benchmark run.
"""

from __future__ import annotations

import dataclasses

import numpy as np

FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def _alter(res):
    if hasattr(res, "tokens"):  # a serving lane
        return dataclasses.replace(res, tokens=res.tokens + 1)
    t = np.array(res.t_complete)
    done = np.nonzero(t >= 0)[0]
    t[done[0] if done.size else 0] += 1
    return dataclasses.replace(res, t_complete=t)


def plant(entry, fault: str):
    """Wrap ``entry.program`` with ``fault``; returns ``entry``."""
    from bench.entries.run_serving_batched import RunServingBatched
    from bench.entries.simulate_fast import SimulateFast
    from bench.entries.sweep_grid import SweepGrid

    real = entry.program

    if fault == "state_unchanged":
        def broken(*args, **kw):
            if isinstance(entry, RunServingBatched):
                return real(*args, **dict(kw, max_cycles=0))
            args = list(args)
            at = 3 if isinstance(entry, SweepGrid) else 2
            args[at] = 0
            return real(*args, **kw)
    elif fault == "half_batch":
        if type(entry) is SimulateFast:
            raise ValueError(f"{fault} needs more than one lane")

        def broken(*args, **kw):
            out = list(real(*args, **kw))
            keep = (len(out) + 1) // 2
            return [out[i % keep] for i in range(len(out))]
    elif fault == "answer_altered":
        def broken(*args, **kw):
            out = real(*args, **kw)
            if isinstance(out, list):
                return [_alter(r) for r in out]
            return _alter(out)
    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    entry.program = broken
    return entry
