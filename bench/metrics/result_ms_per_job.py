"""API layer: host conversion of the final state per job, in milliseconds
(host clock): self time of the program's ``memsim.engine.to_result``
spans (the final state's transfer and the per-lane results' build)."""

from bench.metrics._spans import ms_per_job


def read(ctx):
    return ms_per_job(ctx, "memsim.engine.to_result")
