"""Microseconds per scan step of the tick loop, all K points of a group
advancing together: the groups' device-call wall time over their ticks."""


def read(ctx):
    groups = [g for c in ctx["calls"] for g in c["groups"]]
    ticks = sum(g["n_ticks"] for g in groups)
    return 1e6 * sum(g["wall_s"] for g in groups) / ticks if ticks else None
