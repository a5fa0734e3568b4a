"""Share of a plan call's wall time spent outside its groups' device calls:
grouping, stacking the sweep, dispatch and postprocessing in `run_plan`."""


def read(ctx):
    calls = ctx["calls"]
    wall = sum(c["wall_s"] for c in calls)
    device = sum(g["wall_s"] for c in calls for g in c["groups"])
    return 100.0 * (wall - device) / wall if wall > 0 else None
