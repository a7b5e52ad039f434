"""Traffic generators, found by the name a traffic file gives.

A traffic file's ``generator`` names a module here whose ``generate``
turns the file's parameters and a job's seed into the job's inputs. Each
module is the benchmark's own copy of a generator of the program, so that
no change to the program can move the yardstick. :func:`job_seed` draws
the seed of job ``k`` of a run from ``--seed``.
"""

from __future__ import annotations

import importlib

import numpy as np


def job_seed(seed: int, job: int) -> int:
    """Seed of job ``job`` of a run started with ``--seed seed`` (any
    non-negative whole number, however large)."""
    return int(np.random.SeedSequence([int(seed), int(job)])
               .generate_state(1)[0])


def get(name: str):
    """The ``generate`` function of the generator module ``name``."""
    return importlib.import_module(f"bench.generators.{name}").generate
