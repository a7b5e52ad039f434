"""Serving host loop: the window reports' transfer and build per
closed-loop window, in milliseconds (host clock): self time of the
program's ``memsim.session.fetch`` span (one stacked ``device_get`` and
the per-lane ``WindowReport`` build in ``SessionBatch.advance``)."""

from bench.metrics._spans import ms_per_window


def read(ctx):
    return ms_per_window(ctx, "memsim.session.fetch")
