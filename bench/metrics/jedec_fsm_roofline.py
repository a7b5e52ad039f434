"""Kernel layer under the ``"jedec"`` timing model: the fused kernel's
share of its memory roofline, in percent: the bytes one executed step
must move (``bench.roofline_jedec``: the step's state with the bank-group
windows and precharge gates, from the configuration and the lanes on a
device) over the HBM peak, over the kernel's device time per step (mean
over the devices used). A share above 100% means the bytes are counted
too high or the time misses part of the work: an error."""

from bench.metrics._common import device_mean, kernel
from bench.roofline_jedec import step_bytes_jedec


def read(ctx):
    devices = ctx["trace"]["devices"] if ctx["trace"] else []
    if not devices or ctx["peaks"] is None:
        return None
    lanes = ctx["jobs"][0].lanes // len(devices)
    need_s = (step_bytes_jedec(ctx["config"], lanes)
              / ctx["peaks"]["hbm_bytes_per_s"])

    def one(d):
        k = kernel(d)
        return 100.0 * need_s * k[1] / k[0] if k else None

    share = device_mean(ctx, one)
    if share is not None and share > 100.0:
        raise ValueError(f"jedec_fsm roofline share {share!r}% is over 100%")
    return share
