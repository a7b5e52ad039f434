"""API layer: host conversion of a sweep's final state per job, in
milliseconds (host clock): self time of the program's
``memsim.engine.to_result`` spans under the ``memsim.sweep_grid`` root,
found by the root itself (:mod:`bench.metrics._sweep_calls`)."""

from bench.metrics._sweep_calls import ms_per_job


def read(ctx):
    return ms_per_job(ctx, "memsim.engine.to_result")
