"""API layer: host preparation per job of a sweep, in milliseconds (host
clock): self time of the program's ``memsim.engine.prepare`` spans under
the ``memsim.sweep_grid`` root, found by the root itself
(:mod:`bench.metrics._sweep_calls`), so any sweep entry is read."""

from bench.metrics._sweep_calls import ms_per_job


def read(ctx):
    return ms_per_job(ctx, "memsim.engine.prepare")
