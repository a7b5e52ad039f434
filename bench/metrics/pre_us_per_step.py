"""Glue layer: device time per executed step of the ops whose innermost
glue scope is ``memsim.glue.pre``, in microseconds (mean over the
devices used; see :mod:`bench.metrics._scopes`)."""

from bench.metrics._scopes import us_per_step


def read(ctx):
    return us_per_step(ctx, "pre")
