"""Glue layer (``core/fused_step.py`` and the engine's loop body): device
busy time that is not the fused kernel, per executed step, in
microseconds (mean over the devices used)."""

from bench.metrics._common import device_mean, kernel


def read(ctx):
    def one(d):
        k = kernel(d)
        return 1e6 * (d["busy_s"] - k[0]) / k[1] if k else None

    return device_mean(ctx, one)
