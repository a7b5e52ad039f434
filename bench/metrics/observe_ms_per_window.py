"""Serving host loop: host bookkeeping per closed-loop window, in
milliseconds (host clock): self time of the program's
``memsim.serve.observe`` span, in which every live lane's scheduler and
KV pager fold in the window's completions (``serving.observe_batch``)."""

from bench.metrics._spans import ms_per_window


def read(ctx):
    return ms_per_window(ctx, "memsim.serve.observe")
