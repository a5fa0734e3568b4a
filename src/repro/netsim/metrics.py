"""Metrics over raw simulation outputs — the paper's reported quantities.

* per-job training-iteration times (avg / p99 / CDF)  — Figs 7c, 8c, 9c, 11
* dropped / ECN-marked packets per second             — Figs 7b, 8b, 9b
* link-utilization traces                             — Figs 7a, 8a, 9a, 14
* interleave score: pairwise Jaccard overlap of comm phases on shared links
* speedups vs a baseline run                          — Figs 10, 12, 13
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from repro.netsim import telemetry as telemetry_mod
from repro.netsim.engine import RawSimOutput, SimConfig, SweepPoint


@dataclasses.dataclass
class SimResult:
    """Post-processed, numpy-side view of one simulation.

    ``point`` (when the run came from a sweep or an experiment plan) names
    the grid point this result belongs to — axis name -> value labels plus
    the resolved `SweepParams` — so results are self-describing and can be
    grouped/pivoted by axis name instead of positional bookkeeping.
    """

    cfg: SimConfig
    iter_times: list[np.ndarray]      # per job, valid entries only
    drops_per_s: float
    marks_per_s: float
    trace_t: np.ndarray               # [C]
    trace_util: np.ndarray            # [C, M]
    trace_incomm: np.ndarray          # [C, J]
    trace_drops: np.ndarray           # [C]
    trace_jobtput: np.ndarray         # [C, J] delivered bytes/s per job
    point: Optional[SweepPoint] = None
    # decimated probe series + detector outputs when cfg.telemetry armed
    # the probe subsystem (netsim.telemetry); None otherwise
    telemetry: Optional[telemetry_mod.TelemetryResult] = None

    @property
    def n_jobs(self) -> int:
        return len(self.iter_times)

    def avg_iter(self, job: int, warmup: int = 5) -> float:
        x = self.iter_times[job][warmup:]
        return float(np.mean(x)) if x.size else float("nan")

    def p99_iter(self, job: int, warmup: int = 5) -> float:
        x = self.iter_times[job][warmup:]
        return float(np.percentile(x, 99)) if x.size else float("nan")

    def all_iters(self, warmup: int = 5) -> np.ndarray:
        xs = [x[warmup:] for x in self.iter_times if x.size > warmup]
        return np.concatenate(xs) if xs else np.asarray([])


def postprocess(cfg: SimConfig, raw: RawSimOutput,
                point: Optional[SweepPoint] = None,
                n_jobs: Optional[int] = None) -> SimResult:
    """Numpy-side view of one raw simulation.

    ``point`` attaches the sweep/plan coordinates; ``n_jobs`` trims the
    job-indexed outputs to the first n jobs — the active jobs of a run on a
    padded fabric (`SweepParams.job_active`), whose masked-off trailing jobs
    record no iterations and carry no traffic.
    """
    it = np.asarray(raw.iter_times)
    counts = np.asarray(raw.iter_counts)
    n = it.shape[0] if n_jobs is None else min(n_jobs, it.shape[0])
    per_job = [it[j, : int(min(counts[j], it.shape[1]))] for j in range(n)]
    per_job = [x[~np.isnan(x)] for x in per_job]
    sim_t = float(np.asarray(raw.trace_t)[-1]) if raw.trace_t.size else cfg.sim_time
    telemetry = None
    if raw.telemetry is not None and cfg.telemetry is not None:
        telemetry = telemetry_mod.collect(cfg, raw.telemetry, n_jobs=n)
    return SimResult(
        cfg=cfg,
        iter_times=per_job,
        drops_per_s=float(np.asarray(raw.trace_drops).sum() / max(sim_t, 1e-9)),
        marks_per_s=float(np.asarray(raw.trace_marks).sum() / max(sim_t, 1e-9)),
        trace_t=np.asarray(raw.trace_t),
        trace_util=np.asarray(raw.trace_util),
        trace_incomm=np.asarray(raw.trace_incomm)[:, :n],
        trace_drops=np.asarray(raw.trace_drops),
        trace_jobtput=np.asarray(raw.trace_jobtput)[:, :n],
        point=point,
        telemetry=telemetry,
    )


def host_view(cfg: SimConfig, raw: RawSimOutput) -> RawSimOutput:
    """A `simulate_sweep` output brought to the host in one `jax.device_get`.

    Fetches only the leaves `postprocess` reads: the iteration record and
    its counts, the ``trace_*`` series but ``trace_ratio``, and the
    ``telemetry`` state when ``cfg.telemetry`` arms it.  ``final_state`` and
    ``trace_ratio`` stay on the device and are None in the view.  A point's
    output is then a NumPy slice of each leaf (``leaf[slot]``), where
    slicing the device arrays would dispatch an index transfer, a slice and
    a squeeze per leaf and point.
    """
    return jax.device_get(raw._replace(
        trace_ratio=None, final_state=None,
        telemetry=None if cfg.telemetry is None else raw.telemetry))


def postprocess_sweep(cfg: SimConfig, raw: RawSimOutput,
                      points: Optional[list[SweepPoint]] = None
                      ) -> list[SimResult]:
    """Post-process a `simulate_sweep` output (leading [K] sweep axis) into
    one SimResult per grid point, in sweep order, from its `host_view`.

    Pass the `SweepPoint` list from `grid_sweep` (or hand-built labels) and
    each result carries its own point — downstream grouping then selects by
    axis value instead of relying on positional alignment.
    """
    view = host_view(cfg, raw)
    k = view.iter_counts.shape[0]
    if points is not None and len(points) != k:
        raise ValueError(f"{len(points)} points for a K={k} sweep")
    return [postprocess(cfg, jax.tree_util.tree_map(lambda x, i=i: x[i], view),
                        point=None if points is None else points[i],
                        n_jobs=None if points is None else points[i].n_jobs)
            for i in range(k)]


def iteration_times(cfg: SimConfig, raw: RawSimOutput) -> list[np.ndarray]:
    return postprocess(cfg, raw).iter_times


def interleave_score(res: SimResult, job_a: int, job_b: int,
                     tail_frac: float = 0.5) -> float:
    """Jaccard overlap of two jobs' comm phases over the trace tail.

    0.0 = perfectly interleaved, 1.0 = fully synchronized. The paper's
    convergence claim: MLTCP drives this toward ~0 within ~10 iterations,
    so we score the tail (post-convergence) portion of the run.
    """
    ic = res.trace_incomm
    start = int(ic.shape[0] * (1.0 - tail_frac))
    a = ic[start:, job_a].astype(bool)
    b = ic[start:, job_b].astype(bool)
    union = np.logical_or(a, b).sum()
    if union == 0:
        return 0.0
    return float(np.logical_and(a, b).sum() / union)


def mean_pairwise_interleave(res: SimResult, tail_frac: float = 0.5) -> float:
    j = res.trace_incomm.shape[1]
    scores = [interleave_score(res, a, b, tail_frac)
              for a in range(j) for b in range(a + 1, j)]
    return float(np.mean(scores)) if scores else 0.0


def speedup_stats(base: SimResult, test: SimResult,
                  warmup: int = 5) -> dict[str, float]:
    """Training-iteration-time speedups of ``test`` over ``base`` (paper's
    headline metric): ratio of avg and p99 iteration times across all jobs."""
    b, t = base.all_iters(warmup), test.all_iters(warmup)
    return {
        "avg_speedup": float(np.mean(b) / np.mean(t)),
        "p99_speedup": float(np.percentile(b, 99) / np.percentile(t, 99)),
        "base_avg": float(np.mean(b)), "test_avg": float(np.mean(t)),
        "base_p99": float(np.percentile(b, 99)),
        "test_p99": float(np.percentile(t, 99)),
    }


def sweep_speedup_stats(bases: list[SimResult], tests: list[SimResult],
                        warmup: int = 5) -> dict[str, float]:
    """Seed-paired speedups over a sweep: ``bases``/``tests`` are same-length
    `postprocess_sweep` outputs run with matching seed grids; returns mean
    and (population) std across the sweep — the paper-figure error bars."""
    if len(bases) != len(tests):
        raise ValueError(f"sweep lengths differ: {len(bases)} vs {len(tests)}")
    per = [speedup_stats(b, t, warmup) for b, t in zip(bases, tests)]
    avg = np.asarray([p["avg_speedup"] for p in per])
    p99 = np.asarray([p["p99_speedup"] for p in per])
    return {
        "avg_speedup": float(avg.mean()), "avg_speedup_std": float(avg.std()),
        "p99_speedup": float(p99.mean()), "p99_speedup_std": float(p99.std()),
        "n_points": len(per),
    }


# ---------------------------------------------------------------------------
# Telemetry accessors (probe series + detector outputs; netsim.telemetry)
# ---------------------------------------------------------------------------

def _require_telemetry(res: SimResult) -> telemetry_mod.TelemetryResult:
    if res.telemetry is None:
        raise ValueError(
            "result has no telemetry: run with SimConfig.telemetry set to a "
            "TelemetrySpec (or run_plan(..., telemetry=spec))")
    return res.telemetry


def probe_timeline(res: SimResult, probe: str
                   ) -> tuple[np.ndarray, np.ndarray]:
    """(t, values) of one armed probe's decimated series — e.g.
    ``probe_timeline(res, "flow_cwnd")`` gives the Fig. 5-style [S, N]
    per-flow cwnd timeline at sample times t [S]."""
    return _require_telemetry(res).timeline(probe)


def time_to_interleave(res: SimResult) -> float:
    """Seconds until the EWMA pairwise comm-overlap *permanently* drops
    below the spec's threshold (inf if the run never converged — the
    paper's "stabilizes into an interleaved state" claim, as a number)."""
    return _require_telemetry(res).time_to_interleave_s


def convergence_iteration(res: SimResult) -> float:
    """Training iterations completed when the interleave detector last saw
    overlap above threshold — the paper's "within a few training
    iterations" metric (inf: never converged; 0: interleaved from the
    start)."""
    return _require_telemetry(res).time_to_interleave_iters


def iter_time_quantile(res: SimResult, q: float,
                       job: Optional[int] = None) -> float:
    """Streaming iteration-time quantile from the in-scan log-histogram
    sketch (no dense iteration record needed; ~one-bin resolution)."""
    return _require_telemetry(res).iter_quantile(q, job=job)
