"""Device layer: the share of the traced span in which no operation ran
on the device, in percent (mean over the devices used)."""

from bench.metrics._common import device_mean


def read(ctx):
    return device_mean(ctx, lambda d: 100.0 * d["idle_s"] / d["span_s"]
                       if d["span_s"] > 0 else None)
