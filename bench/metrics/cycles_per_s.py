"""One long trace: simulated cycles per wall second of one lane (``simulate_fast``)."""

from bench.metrics._rate import lane_cycles_per_s as read  # noqa: F401
