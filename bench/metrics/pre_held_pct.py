"""Timing model (``core/bank_fsm.py`` under ``"jedec"``): PRE grants the
bank's precharge gate (tRAS, tRTP, tWTP) held, as a percent of all PRE
grants (program counters ``jedec_pre_held`` over ``jedec_pre_grants``,
see :mod:`bench.metrics._sweep_calls`)."""

from bench.metrics._sweep_calls import pct


def read(ctx):
    return pct(ctx, "jedec_pre_held", "jedec_pre_grants")
