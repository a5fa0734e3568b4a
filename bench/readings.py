#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip at the cell's size.

    python bench/readings.py --workload <cell> --first-seed <n> \\
        --seeds 12 --controls 3

For each of ``--seeds`` run seeds it runs the first window call of a run with
that seed on the timed path and compares it with the plain reference, as a
run does: the lower readings.  The same call's OFF points, compared with the
reference's WI points of the same seeds and programs, read what a CC update
that computes MLTCP-WI as plain Reno would.  For each of ``--controls``
seeds it puts the control in the program's place, the reference computed in
bfloat16, and compares it the same way: the upper readings.  One JSON line
per reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import HERE, ROOT, cell_entry, load_bench, start_jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    entry = cell_entry(load_bench(), args.workload)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    start_jax(int(entry["chips"]))
    import jax.numpy as jnp

    import check
    import plan
    import reference
    from repro import netsim

    cell = plan.load_cell(args.workload)
    iso = plan.single_iso(cell.config)

    def stats(outs):
        return [check.point_stats(o["iter_times"], o["trace_util"],
                                  o["trace_jobtput"], o["trace_t"])
                for o in outs]

    for i in range(args.seeds + args.controls):
        seed = args.first_seed + i
        draws = plan.draw_calls(seed, 2, cell.points_per_variant, iso)[1]
        points = [(v, d.seed, d.order) for v in cell.variants for d in draws]
        variants = [p[0] for p in points]
        t0 = time.perf_counter()
        ref = stats(reference.simulate(cell.config, points))
        ref_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        if i < args.seeds:
            pr = netsim.run_plan(plan.make_plan(cell, draws))
            by_point = {(r.point["variant"], r.point["point"]): r
                        for r in pr.results}
            got = stats([dict(iter_times=r.iter_times, trace_util=r.trace_util,
                              trace_jobtput=r.trace_jobtput, trace_t=r.trace_t)
                         for v in cell.variants
                         for r in [by_point[v, k] for k in range(len(draws))]])
            kind = "program"
        else:
            got = stats(reference.simulate(cell.config, points,
                                           dtype=jnp.bfloat16))
            kind = "control"
        got_s = time.perf_counter() - t0
        nums = check.numbers(variants, got, ref)
        if kind == "program" and {"OFF", "WI"} <= set(variants):
            off = [g for v, g in zip(variants, got) if v == "OFF"]
            wi = [r for v, r in zip(variants, ref) if v == "WI"]
            print(json.dumps(dict(kind="wi_as_off", seed=seed, numbers=
                                  check.numbers(["WI"] * len(wi), off, wi))),
                  flush=True)
        per_job = [dict(variant=v, iters=g["iters"].tolist(),
                        ref_iters=r["iters"].tolist(),
                        rate=g["rate"].tolist(),
                        ref_rate=r["rate"].tolist(),
                        share=float(g["share"][0]),
                        ref_share=float(r["share"][0]))
                   for v, g, r in zip(variants, got, ref)]
        print(json.dumps(dict(kind=kind, seed=seed, numbers=nums,
                              seconds=dict(reference=ref_s, got=got_s),
                              points=per_job)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
