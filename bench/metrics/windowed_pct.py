"""Timing model (``core/dram_model.py`` under ``"jedec"``): ACT, RD and
WR grants that waited on any timing window, as a percent of all ACT, RD
and WR grants (program counters ``jedec_windowed`` over
``jedec_grants``, see :mod:`bench.metrics._sweep_calls`)."""

from bench.metrics._sweep_calls import pct


def read(ctx):
    return pct(ctx, "jedec_windowed", "jedec_grants")
