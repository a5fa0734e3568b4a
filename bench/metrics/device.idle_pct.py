"""Share of the traced cut in which no operation ran on the device."""
import devtrace


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    return 100.0 * (1.0 - devtrace.busy_ns(tr) / devtrace.window_ns(tr))
