"""Share of a plan call's wall time in its groups' ``run_plan.postprocess``
spans (per-point slicing, `metrics.postprocess`, cache save), full-length
calls, read from the program's span log."""
import phases


def read(ctx):
    calls = ctx["calls"]
    tot = phases.window_phases(calls)
    wall = sum(c["wall_s"] for c in calls)
    return None if tot is None or wall <= 0 else 100.0 * tot["postprocess"] / wall
