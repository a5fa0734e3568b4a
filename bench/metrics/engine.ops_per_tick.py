"""Device ops per scan step of the tick loop: the leaf ops inside each
group's outer loop op in the traced cut, over the scan steps each device
ran (one CC-tick call per step)."""
import devtrace
import scopes


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    steps = ctx["kernel_calls_per_device"] * devtrace.n_devices(tr)
    ops = scopes.loop_ops(tr)
    return len(ops) / steps if ops and steps else None
