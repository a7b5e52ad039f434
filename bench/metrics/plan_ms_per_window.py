"""Serving host loop: host planning per closed-loop window, in
milliseconds (host clock): self time of the program's
``memsim.serve.plan`` span, in which every live lane's scheduler plans
the window's memory requests (``serving.plan_window_batch``)."""

from bench.metrics._spans import ms_per_window


def read(ctx):
    return ms_per_window(ctx, "memsim.serve.plan")
