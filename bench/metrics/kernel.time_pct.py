"""The CC-tick kernel's share of the device's busy time in the traced cut."""
import devtrace


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    ops = devtrace.cc_tick_ops(tr, ctx["kernel_calls_per_device"])
    busy = devtrace.busy_ns(tr) * devtrace.n_devices(tr)
    if ops is None or busy <= 0:
        return None
    return 100.0 * sum(e - s for _, s, e in ops) / busy
