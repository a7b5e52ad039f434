"""Serving host loop (``serving/scheduler.py``, ``core/session_batch.py``):
device idle time per closed-loop window, in milliseconds (mean over the
devices used). Between two windows the device waits while the host
observes the last window's completions and plans the next one; a window
is one run of the windowed engine's program in the traced span."""

from bench.metrics._common import device_mean

WINDOW_PROGRAM = "_run_window"


def read(ctx):
    def one(d):
        runs = sum(n for name, n in d["modules"].items()
                   if WINDOW_PROGRAM in name)
        return 1000.0 * d["idle_s"] / runs if runs else None

    return device_mean(ctx, one)
