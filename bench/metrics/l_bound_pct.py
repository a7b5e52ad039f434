"""Timing model (``core/dram_model.py`` under ``"jedec"``): ACT, RD and
WR grants whose legal cycle a same-bank-group (L) window alone set, as a
percent of the grants a timing window held (program counters
``jedec_l_bound`` over ``jedec_windowed``, see :mod:`bench.metrics._sweep_calls`)."""

from bench.metrics._sweep_calls import pct


def read(ctx):
    return pct(ctx, "jedec_l_bound", "jedec_windowed")
