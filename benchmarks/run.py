"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call = wall time per
simulator tick across the benchmark's simulations) and *merges* the full
derived metrics into results/benchmarks.json keyed by suite name (existing
suites' entries from earlier runs survive — the perf trajectory is append/
update, never overwrite-all).

Each suite returns ``(derived_metrics, n_ticks)`` where n_ticks is summed
from the actual configs it ran (`PlanResult.n_ticks`) — not a hand-kept
constant — so the µs/tick column stays honest as suites grow axes or
change sim times.

Quick mode (default) scales workloads per benchmarks/common.py; set
REPRO_FULL=1 for paper-scale runs.
"""
from __future__ import annotations

from benchmarks import (
    churn,
    circular,
    common,
    convergence,
    diversity,
    kernel_sweep,
    parameters,
    partial_compat,
    speedup_vs_jobs,
    stragglers,
    timeline,
)
from repro import compile_cache


def main() -> None:
    compile_cache.enable()
    suites = [
        ("fig5_timeline", timeline.run),
        ("fig7_9_convergence", convergence.run),
        ("fig10_speedup_vs_jobs", speedup_vs_jobs.run),
        ("fig11_table2_diversity", diversity.run),
        ("fig12_stragglers", stragglers.run),
        ("fig13_partial_compat", partial_compat.run),
        ("fig14_circular_dependency", circular.run),
        ("fig15_agg_functions", parameters.fig15_agg_functions),
        ("fig16_slope_intercept", parameters.fig16_heatmap),
        ("fig17_wi_vs_md", parameters.fig17_wi_vs_md),
        ("kernel_sweep", kernel_sweep.run),
        ("churn_gauntlet", churn.run),
    ]
    done = 0
    for name, fn in suites:
        r = common.timed(name, fn)
        # merge as each suite finishes: a crash in a later suite must not
        # discard the hours the earlier ones already spent; _health records
        # the suite's fusion/cache counters (kernel fallbacks, cache hits,
        # compile groups) so the perf trajectory tracks them per run
        common.merge_results({name: {**r.derived, "_health": r.health}})
        done += 1
        print(r.csv_line(), flush=True)
    print(f"# merged {done} suites into {common.RESULTS_PATH}")


if __name__ == "__main__":
    main()
