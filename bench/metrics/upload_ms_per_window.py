"""Serving host loop: the arrival buffers' append and upload per
closed-loop window, in milliseconds (host clock): self time of the
program's ``memsim.session.upload`` span (``SessionBatch.append`` and,
where any lane appended, the buffers' transfer to the device)."""

from bench.metrics._spans import ms_per_window


def read(ctx):
    return ms_per_window(ctx, "memsim.session.upload")
