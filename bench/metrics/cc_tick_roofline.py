"""The CC-tick kernel's least time per call over its measured time per call.

The least time is the larger of the bytes and the operations one call needs
(roofline.py counts them from the configuration, per live flow, for the
points one device advances per call) over the chip's peaks."""
import roofline
import devtrace


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    ops = devtrace.cc_tick_ops(tr, ctx["kernel_calls_per_device"])
    if ops is None:
        return None
    per_call = sum(e - s for _, s, e in ops) * 1e-9 / len(ops)
    least, _ = roofline.least_seconds(ctx["config"], ctx["points_per_kernel_call"],
                                      ctx["device_kind"])
    return 100.0 * least / per_call
