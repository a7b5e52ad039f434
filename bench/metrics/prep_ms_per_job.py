"""API layer: host preparation per job, in milliseconds (host clock): self
time of the program's ``memsim.engine.prepare`` spans (configuration to
topology, schedules, scalar conversions, the stacked traces and
schedules, sharding: everything up to the dispatch)."""

from bench.metrics._spans import ms_per_job


def read(ctx):
    return ms_per_job(ctx, "memsim.engine.prepare")
