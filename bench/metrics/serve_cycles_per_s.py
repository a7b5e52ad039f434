"""A closed-loop serving study: simulated lane-cycles per wall second,
each lane counted to the cycle it stopped at (``run_serving_batched``)."""

from bench.metrics._rate import lane_cycles_per_s as read  # noqa: F401
