"""A sweep: simulated lane-cycles per wall second (``sweep_grid``)."""

from bench.metrics._rate import lane_cycles_per_s as read  # noqa: F401
