"""Kernel layer (``kernels/bank_fsm/fused.py``): the fused kernel's device
time per executed step, in microseconds (mean over the devices used)."""

from bench.metrics._common import device_mean, kernel


def read(ctx):
    def one(d):
        k = kernel(d)
        return 1e6 * k[0] / k[1] if k else None

    return device_mean(ctx, one)
