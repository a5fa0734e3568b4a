"""Shared helpers for the paper-figure benchmarks.

Every suite declares a `netsim.Plan` — named axes (scheme, F family, job
count, seed, ...) over a config builder — and `netsim.run_plan` partitions
the matrix into compile groups, so job-count grids share one padded program
and every result carries its `SweepPoint` labels.  Suites report their
simulated tick counts from `PlanResult.n_ticks` / `SimResult.cfg`, so the
µs/tick CSV tracks the configs instead of hand-kept constants.

Workload scaling: testbed iterations are O(100 ms); to keep CPU wall-time
tractable the benchmarks run the same phase *ratios* scaled by
``WORK_SCALE`` (interleaving dynamics depend on ratios, not absolutes —
validated by tests/test_netsim.py::test_scale_invariance). Full-scale runs:
``REPRO_FULL=1 PYTHONPATH=src python -m benchmarks.run``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np

from repro import netsim, workload
from repro.core import Algo, CCParams, MLTCPConfig, Variant

FULL = bool(int(os.environ.get("REPRO_FULL", "0")))
SMOKE = bool(int(os.environ.get("REPRO_SMOKE", "0")))  # CI regression smoke
WORK_SCALE = 1.0 if FULL else 0.25
SIM_TIME = 20.0 if FULL else (1.5 if SMOKE else 4.0)
DT = 2e-5
# seed grid for error bars — a free vmap axis via netsim.simulate_sweep
SEEDS = (1, 2, 3) if FULL else ((1,) if SMOKE else (1, 2))

# paper §4.1 defaults per scheme
PARAMS = {
    ("reno", "WI"): (1.75, 0.25),
    ("reno", "MD"): (1.0, 1.0),
    ("cubic", "WI"): (1.0, 0.5),
    ("cubic", "MD"): (0.8, 0.8),
    ("dcqcn", "WI"): (1.067, 0.267),
    ("dcqcn", "MD"): (1.067, 0.267),
}
ALGOS = {"reno": Algo.RENO, "cubic": Algo.CUBIC, "dcqcn": Algo.DCQCN}

# ECN thresholds for the RoCE fabric; RED drop thresholds for TCP
RED_BY_ALGO = {
    "reno": dict(red_qmin=150e3, red_qmax=1.5e6, red_pmax=0.12),
    "cubic": dict(red_qmin=150e3, red_qmax=1.5e6, red_pmax=0.12),
    "dcqcn": dict(red_qmin=50e3, red_qmax=400e3, red_pmax=0.2),
}


def protocol(algo: str, variant: str = "WI", slope=None, intercept=None,
             f_spec: str = "linear", **cfg_kw) -> MLTCPConfig:
    var = {"OFF": Variant.OFF, "WI": Variant.WI, "MD": Variant.MD,
           "BOTH": Variant.BOTH}[variant]
    s_def, i_def = PARAMS.get((algo, "WI" if variant == "OFF" else variant),
                              (1.75, 0.25))
    return MLTCPConfig(
        cc=CCParams(algo=int(ALGOS[algo]), variant=int(var), tick_dt=DT,
                    rtt=100e-6),
        slope=s_def if slope is None else slope,
        intercept=i_def if intercept is None else intercept,
        f_spec=f_spec,
        **cfg_kw)


def build_cfg(topo, profiles, proto, *, sim_time=None, seed=1,
              straggle_prob=None, start_offset=None, cassini=None,
              static_job_factors=None, scale=None, **kw) -> netsim.SimConfig:
    scale = WORK_SCALE if scale is None else scale
    profiles = [p.scaled(scale) for p in profiles]
    jobs = workload.jobspec_from_profiles(profiles,
                                          straggle_prob=straggle_prob,
                                          start_offset=start_offset)
    algo = {int(v): k for k, v in ALGOS.items()}[proto.cc.algo]
    return netsim.SimConfig(
        topo=topo, jobs=jobs, protocol=proto,
        sim_time=SIM_TIME if sim_time is None else sim_time, dt=DT,
        seed=seed, cassini=cassini, static_job_factors=static_job_factors,
        **{**RED_BY_ALGO[algo], **kw})


def plan(build, *, name: str = "", where=None, **axes) -> netsim.Plan:
    """Declare an experiment plan from keyword axes.

    Each ``axes`` value is either a value sequence or a `netsim.Axis`
    (renamed to its keyword); ``build`` maps a point's label dict to its
    `SimConfig`.  Run with `run_plan`.
    """
    resolved = []
    for key, v in axes.items():
        if isinstance(v, netsim.Axis):
            resolved.append(dataclasses.replace(v, name=key))
        else:
            resolved.append(netsim.Axis(key, tuple(v)))
    return netsim.Plan(name=name, axes=tuple(resolved), build=build,
                       where=where)


# Per-suite fusion health, accumulated across every plan a suite runs
# (suites may run several); `timed` resets it per benchmark and attaches the
# totals to the BenchResult so run.py can print + merge them.  The last
# three keys are the static analyzer's verdict: compile groups the plan
# lint predicted before the run, plans whose executed group count diverged
# from that prediction, and non-info plan-lint findings (avoidable splits).
_PLAN_HEALTH = {"n_kernel_fallbacks": 0, "n_compile_groups": 0,
                "n_groups_predicted": 0, "n_group_mispredicts": 0,
                "n_plan_findings": 0, "n_group_errors": 0}


def reset_plan_health() -> None:
    for k in _PLAN_HEALTH:
        _PLAN_HEALTH[k] = 0


def plan_health() -> dict:
    return dict(_PLAN_HEALTH)


def run_plan(p: netsim.Plan, **kw) -> netsim.PlanResult:
    """Execute a plan (thin wrapper so suites share one entry point and
    their fusion health aggregates per suite).

    Each execution is preceded by the plan lint: the predicted compile
    groups and any non-info findings land in the suite's health block, and
    an executed group count that diverges from the prediction is counted
    as a mispredict — the benchmarks continuously cross-validate the
    static analyzer against reality.
    """
    from repro.analysis import plan_lint

    findings, facts = plan_lint.lint_plan(
        p, label=p.name or "plan", pad_jobs=kw.get("pad_jobs", True),
        telemetry=kw.get("telemetry"))
    predicted = facts["groups"]

    pr = netsim.run_plan(p, **kw)
    _PLAN_HEALTH["n_kernel_fallbacks"] += pr.n_kernel_fallbacks
    _PLAN_HEALTH["n_compile_groups"] += pr.n_compile_groups
    # keep_going=True salvage: failed compile groups land here instead of
    # aborting the suite; a nonzero count in _health flags the partial run
    _PLAN_HEALTH["n_group_errors"] += len(pr.group_errors)
    _PLAN_HEALTH["n_groups_predicted"] += predicted
    _PLAN_HEALTH["n_group_mispredicts"] += int(
        predicted != pr.n_compile_groups)
    _PLAN_HEALTH["n_plan_findings"] += sum(
        1 for f in findings if f.effective_severity != "info")
    return pr


def seed_axis(seeds=None) -> netsim.Axis:
    """The shared multi-seed error-bar axis (a free `simulate_sweep` vmap
    lane; every suite appends it to its plan)."""
    return netsim.Axis("seed", tuple(SEEDS if seeds is None else seeds))


def sim(topo, profiles, proto, **kw) -> netsim.SimResult:
    """One simulation as a single-point plan (kept for one-off runs)."""
    pr = run_plan(plan(lambda pt: build_cfg(topo, profiles, proto, **kw),
                       name="single"))
    return pr.results[0]


RESULTS_PATH = os.path.join("results", "benchmarks.json")


def merge_results(new: dict, path: str = RESULTS_PATH) -> dict:
    """Merge suite results into the benchmarks JSON, keyed by suite name.

    Load-if-exists, update, dump — a partial run (one suite, a new suite)
    updates only its own keys instead of destroying the perf trajectory the
    other suites recorded on earlier runs.  The dump goes through a temp
    file + os.replace so a crash mid-write can never leave a truncated
    file that a later run would "recover" from as empty.  Returns the
    merged dict.
    """
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    data: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (json.JSONDecodeError, OSError):
            data = {}          # corrupt/unreadable: rewrite from this run
    data.update(new)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1)
    os.replace(tmp, path)
    return data


@dataclasses.dataclass
class BenchResult:
    name: str
    wall_s: float
    n_ticks: int
    derived: dict
    # fusion health over every plan the suite ran (plan_health())
    health: dict = dataclasses.field(default_factory=dict)

    def csv_line(self) -> str:
        us = 1e6 * self.wall_s / max(self.n_ticks, 1)
        key, val = next(iter(self.derived.items()))
        line = f"{self.name},{us:.3f},{key}={val}"
        if self.health:
            line += f",fallbacks={self.health.get('n_kernel_fallbacks', 0)}"
        return line


def timed(name: str, fn) -> BenchResult:
    reset_plan_health()
    t0 = time.time()
    derived, n_ticks = fn()
    return BenchResult(name, time.time() - t0, n_ticks, derived,
                       health=plan_health())


def gpt2(n: int = 1) -> list[workload.CommProfile]:
    return [workload.profile_for("gpt2") for _ in range(n)]
