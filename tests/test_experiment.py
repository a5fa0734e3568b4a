"""Experiment-plan API (`netsim.experiment`) — correctness invariants.

The contract: a plan's cartesian product partitions into compile groups
(one trace per distinct static signature), job-count grids merge into one
padded + masked group whose active lanes match unpadded runs exactly, and
every result is self-describing via its `SweepPoint`.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import netsim
from repro.netsim import engine, experiment, metrics
from repro.core import Algo, CCParams, MLTCPConfig, Variant

DT = 2e-5


def _proto(algo=Algo.RENO, variant=Variant.WI, **kw):
    return MLTCPConfig(cc=CCParams(algo=int(algo), variant=int(variant),
                                   tick_dt=DT, rtt=100e-6),
                       slope=1.75, intercept=0.25, **kw)


def _cfg(n_jobs=2, sim_time=0.4, seed=3, **kw):
    topo = netsim.dumbbell(n_jobs, sockets_per_job=2)
    jobs = netsim.JobSpec.simple([0.0075] * n_jobs, [25e6] * n_jobs)
    return netsim.SimConfig(topo=topo, jobs=jobs,
                            protocol=kw.pop("protocol", _proto()),
                            sim_time=sim_time, dt=DT, seed=seed, **kw)


def _jobs_plan(variants=("WI",), job_counts=(2, 3, 4), seeds=(3,),
               sim_time=0.4, name="jobs-plan"):
    def build(pt):
        variant = {"OFF": Variant.OFF, "WI": Variant.WI}[pt["variant"]]
        return _cfg(n_jobs=pt["n_jobs"], sim_time=sim_time,
                    protocol=_proto(variant=variant))
    return netsim.Plan(
        name=name, build=build,
        axes=(netsim.Axis("variant", tuple(variants)),
              netsim.Axis("n_jobs", tuple(job_counts)),
              netsim.Axis("seed", tuple(seeds))))


# ---------------------------------------------------------------------------
# Padded / masked jobs axis
# ---------------------------------------------------------------------------

def test_padded_job_axis_matches_unpadded_runs():
    """A plan over n_jobs in {2,3,4} must match three unpadded `simulate()`
    runs on iteration times (tight tolerance)."""
    counts = (2, 3, 4)
    pr = netsim.run_plan(_jobs_plan(job_counts=counts), shard=False)
    assert pr.n_compile_groups == 1
    for n in counts:
        (res,) = pr.select(n_jobs=n)
        assert res.n_jobs == n            # padded jobs trimmed away
        cfg = _cfg(n_jobs=n)
        seq = netsim.postprocess(cfg, netsim.simulate(cfg))
        assert len(seq.iter_times) == n
        for j in range(n):
            assert res.iter_times[j].shape == seq.iter_times[j].shape
            np.testing.assert_allclose(res.iter_times[j], seq.iter_times[j],
                                       rtol=1e-5, atol=1e-7)


def test_padded_group_compiles_once():
    """The whole job-count grid is one trace of one compile group."""
    before = engine.TRACE_COUNT
    pr = netsim.run_plan(_jobs_plan(job_counts=(2, 3, 4), sim_time=0.1,
                                    name="trace-once"), shard=False)
    assert pr.n_compile_groups == 1
    assert engine.TRACE_COUNT == before + 1


def test_fig10_style_plan_two_compile_groups():
    """Acceptance: job count 2..8 x 3 seeds x {MLTCP, OFF} runs in <= 2
    compile groups (one per variant) instead of >= 14 compiles."""
    before = engine.TRACE_COUNT
    pr = netsim.run_plan(_jobs_plan(variants=("OFF", "WI"),
                                    job_counts=(2, 3, 4, 5, 6, 7, 8),
                                    seeds=(1, 2, 3), sim_time=0.3,
                                    name="fig10-accept"), shard=False)
    assert len(pr) == 2 * 7 * 3
    assert pr.n_compile_groups <= 2
    assert engine.TRACE_COUNT - before <= 2
    # every result is self-describing
    for res in pr:
        assert res.point is not None
        assert set(res.point.axes) == {"variant", "n_jobs", "seed"}
        assert res.n_jobs == res.point["n_jobs"]
        assert res.point.params.job_active is not None
    # seed-paired selections feed the error-bar aggregation directly
    sp = netsim.sweep_speedup_stats(pr.select(variant="OFF", n_jobs=5),
                                    pr.select(variant="WI", n_jobs=5))
    assert sp["n_points"] == 3


def test_pad_jobs_off_forces_exact_groups():
    pr = netsim.run_plan(_jobs_plan(job_counts=(2, 3), sim_time=0.1,
                                    name="no-pad"),
                         shard=False, pad_jobs=False)
    assert pr.n_compile_groups == 2


def test_mismatched_workload_structure_does_not_merge():
    """Points whose jobs differ *structurally* (start offsets, phase counts)
    keep their own compile group — only workload values are traced."""
    def build(pt):
        n = pt["n_jobs"]
        offs = [0.002] * n if n == 3 else None      # structural difference
        topo = netsim.dumbbell(n, sockets_per_job=2)
        jobs = netsim.JobSpec.simple([0.0075] * n, [25e6] * n,
                                     start_offset=offs)
        return netsim.SimConfig(topo=topo, jobs=jobs, protocol=_proto(),
                                sim_time=0.1, dt=DT, seed=0)
    pr = netsim.run_plan(netsim.Plan(
        name="mismatch", build=build,
        axes=(netsim.Axis("n_jobs", (2, 3)),)), shard=False)
    assert pr.n_compile_groups == 2


def test_workload_values_merge_into_one_group():
    """Jobs differing only in compute/comm/straggle *values* are traced
    leaves now: one compile group, results bit-equal to exact grouping."""
    def build(pt):
        n = pt["n_jobs"]
        compute = [0.0075] * n if n == 3 else [0.009] * n   # value-only diff
        topo = netsim.dumbbell(n, sockets_per_job=2)
        jobs = netsim.JobSpec.simple(compute, [25e6] * n,
                                     straggle_prob=[0.05 * (n == 3)] * n)
        return netsim.SimConfig(topo=topo, jobs=jobs, protocol=_proto(),
                                sim_time=0.1, dt=DT, seed=0)
    plan = netsim.Plan(name="value-merge", build=build,
                       axes=(netsim.Axis("n_jobs", (2, 3)),))
    before = engine.TRACE_COUNT
    pr = netsim.run_plan(plan, shard=False)
    assert pr.n_compile_groups == 1
    assert engine.TRACE_COUNT == before + 1
    # bit-identical to per-cell compilation
    pr_exact = netsim.run_plan(plan, shard=False, pad_jobs=False)
    assert pr_exact.n_compile_groups == 2
    for a, b in zip(pr, pr_exact):
        assert a.point.axes == b.point.axes
        for ja, jb in zip(a.iter_times, b.iter_times):
            assert np.array_equal(ja, jb)


# ---------------------------------------------------------------------------
# Axes: dynamic vs static, resolve, where
# ---------------------------------------------------------------------------

def test_dynamic_axes_share_one_group_static_axes_split():
    cfg = _cfg(sim_time=0.1)
    before = engine.TRACE_COUNT
    pr = netsim.run_plan(netsim.Plan(
        name="axes", build=lambda pt: dataclasses.replace(
            cfg, protocol=dataclasses.replace(cfg.protocol,
                                              f_spec=pt["f_spec"])),
        axes=(netsim.Axis("f_spec", ("F1", "F5")),       # static: 2 groups
              netsim.Axis("slope", (0.5, 1.75)),         # dynamic
              netsim.Axis("seed", (0, 1)))), shard=False)
    assert pr.n_compile_groups == 2
    assert engine.TRACE_COUNT == before + 2
    assert len(pr.select(f_spec="F1")) == 4
    # the dynamic value actually reached the sweep
    (res,) = pr.select(f_spec="F1", slope=0.5, seed=1)
    assert float(res.point.params.slope) == 0.5
    assert int(res.point.params.seed) == 1


def test_axis_resolve_maps_labels_to_masks():
    """A label axis can resolve to job_active masks (isolation runs) and
    stay selectable by label."""
    def solo_mask(v):
        if v == "all":
            return np.ones((2,), bool)
        m = np.zeros((2,), bool)
        m[v] = True
        return m
    pr = netsim.run_plan(netsim.Plan(
        name="solo", build=lambda pt: _cfg(sim_time=0.4),
        axes=(netsim.Axis("solo", ("all", 0, 1), field="job_active",
                          resolve=solo_mask),)), shard=False)
    assert pr.n_compile_groups == 1
    (alone,) = pr.select(solo=0)
    assert len(alone.iter_times[0]) > 0
    assert len(alone.iter_times[1]) == 0       # masked job never ran
    (both,) = pr.select(solo="all")
    assert all(len(x) > 0 for x in both.iter_times)
    # isolation is at least as fast as sharing the link
    assert alone.avg_iter(0) <= both.avg_iter(0) * 1.01


def test_where_prunes_points():
    pr = netsim.run_plan(netsim.Plan(
        name="where", build=lambda pt: _cfg(sim_time=0.1),
        axes=(netsim.Axis("a", (0, 1)), netsim.Axis("seed", (0, 1))),
        where=lambda pt: not (pt["a"] == 1 and pt["seed"] == 1)),
        shard=False)
    assert len(pr) == 3
    with pytest.raises(KeyError):
        pr.select(a=1, seed=1)


def test_plan_validation():
    with pytest.raises(ValueError, match="duplicate axis"):
        netsim.Plan(name="dup", build=lambda pt: _cfg(),
                    axes=(netsim.Axis("a", (1,)), netsim.Axis("a", (2,))))
    with pytest.raises(ValueError, match="no values"):
        netsim.Axis("empty", ())
    with pytest.raises(ValueError, match="unknown kind"):
        netsim.Axis("a", (1,), kind="bogus")
    with pytest.raises(ValueError, match="unknown sweep field"):
        netsim.run_plan(netsim.Plan(
            name="bad-field", build=lambda pt: _cfg(sim_time=0.1),
            axes=(netsim.Axis("a", (1,), kind="dynamic"),)), shard=False)


# ---------------------------------------------------------------------------
# Self-describing results / SweepPoint round-trip
# ---------------------------------------------------------------------------

def test_grid_sweep_points_roundtrip_through_postprocess():
    """grid_sweep labels travel attached to results, not positionally."""
    cfg = _cfg(sim_time=0.3)
    slopes = [0.5, 1.75]
    sweep, points = netsim.grid_sweep(cfg, slope=slopes, seed=[0, 1])
    assert all(isinstance(p, netsim.SweepPoint) for p in points)
    results = netsim.postprocess_sweep(cfg, netsim.simulate_sweep(cfg, sweep),
                                       points)
    for res in results:
        assert res.point is not None
        # the label matches the params that actually ran
        assert float(res.point.params.slope) == res.point["slope"]
        assert int(res.point.params.seed) == res.point["seed"]
    assert sorted({r.point["slope"] for r in results}) == slopes
    with pytest.raises(ValueError, match="points for a K="):
        netsim.postprocess_sweep(cfg, netsim.simulate_sweep(cfg, sweep),
                                 points[:1])


def test_sweep_point_matches_and_group_by():
    pr = netsim.run_plan(_jobs_plan(job_counts=(2, 3), seeds=(0, 1),
                                    sim_time=0.1, name="pivot"), shard=False)
    assert pr[0].point.matches(variant="WI")
    assert not pr[0].point.matches(variant="OFF")
    assert not pr[0].point.matches(bogus=1)
    by_n = pr.group_by("n_jobs")
    assert set(by_n) == {(2,), (3,)}
    assert all(len(v) == 2 for v in by_n.values())
    assert pr.n_ticks == sum(r.cfg.n_ticks for r in pr)


def test_restrict_workload_roundtrip():
    cfg4 = _cfg(n_jobs=4)
    cfg2 = _cfg(n_jobs=2)
    topo_r, jobs_r = netsim.restrict_workload(cfg4.topo, cfg4.jobs, 2)
    assert experiment._same_workload(topo_r, jobs_r, cfg2.topo, cfg2.jobs)
    assert not experiment._same_workload(topo_r, jobs_r,
                                         cfg4.topo, cfg4.jobs)


# ---------------------------------------------------------------------------
# Sharding
# ---------------------------------------------------------------------------

def test_shard_auto_is_safe_on_any_device_count():
    """shard="auto" partitions K when devices exist and is a no-op
    otherwise; results are identical either way."""
    pr_on = netsim.run_plan(_jobs_plan(job_counts=(2, 3), seeds=(0, 1, 2),
                                       sim_time=0.1, name="shard-on"),
                            shard=True)
    pr_off = netsim.run_plan(_jobs_plan(job_counts=(2, 3), seeds=(0, 1, 2),
                                        sim_time=0.1, name="shard-off"),
                             shard=False)
    assert len(pr_on) == len(pr_off)
    for a, b in zip(pr_on, pr_off):
        assert a.point.axes == b.point.axes
        np.testing.assert_allclose(np.concatenate(a.iter_times + [[0.0]]),
                                   np.concatenate(b.iter_times + [[0.0]]),
                                   rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# The iteration record sized to the group's bound
# ---------------------------------------------------------------------------

def _same_bytes(a, b, path="result") -> None:
    """Assert two results equal leaf by leaf, byte for byte (NaN payloads
    and signed zeros included)."""
    if dataclasses.is_dataclass(a) and not isinstance(a, type):
        assert type(a) is type(b), path
        for f in dataclasses.fields(a):
            _same_bytes(getattr(a, f.name), getattr(b, f.name),
                        f"{path}.{f.name}")
    elif isinstance(a, dict):
        assert a.keys() == b.keys(), path
        for k in a:
            _same_bytes(a[k], b[k], f"{path}[{k!r}]")
    elif isinstance(a, (list, tuple)):
        assert type(a) is type(b) and len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _same_bytes(x, y, f"{path}[{i}]")
    elif a is None or b is None:
        assert a is None and b is None, path
    else:
        x, y = np.asarray(a), np.asarray(b)
        assert x.dtype == y.dtype and x.shape == y.shape, path
        assert x.tobytes() == y.tobytes(), path


def _record_plan_dumbbell():
    """Reno, a padded job-count group: 2 and 3 jobs on the 3-job fabric."""
    def build(pt):
        n = pt["n_jobs"]
        topo = netsim.dumbbell(n, sockets_per_job=2)
        jobs = netsim.JobSpec.simple([0.003, 0.0045, 0.006][:n],
                                     [2e6, 3e6, 1e6][:n])
        return netsim.SimConfig(topo=topo, jobs=jobs, protocol=_proto(),
                                sim_time=0.06, dt=DT, seed=0)
    return netsim.Plan(name="record-dumbbell", build=build,
                       axes=(netsim.Axis("n_jobs", (2, 3)),
                             netsim.Axis("seed", (4, 5))))


def _record_plan_triangle():
    """DCQCN on three links, a two-phase program, stragglers and an armed
    straggle-burst channel."""
    spec = netsim.FaultSpec(n_events=3, straggle_bursts=True)
    jobs = netsim.JobSpec(
        compute=np.array([[0.002, 0.001], [0.004, 0.0], [0.003, 0.0]]),
        comm_bytes=np.array([[1e6, 2e6], [2e6, 0.0], [3e6, 0.0]]),
        n_phases=np.array([2, 1, 1], np.int32),
        start_offset=np.array([0.0, 0.001, 0.0]),
        straggle_prob=np.array([0.2, 0.0, 0.1]),
        iso_iter_time=np.array([0.0035, 0.0043, 0.0035]))

    def build(pt):
        return netsim.SimConfig(
            topo=netsim.triangle(2), jobs=jobs,
            protocol=_proto(algo=Algo.DCQCN), sim_time=0.05, dt=DT,
            seed=0, faults=spec)
    return netsim.Plan(
        name="record-triangle", build=build,
        axes=(netsim.Axis("burst", (0.0, 0.5), field="*", resolve=(
            lambda p: lambda cfg: netsim.fault_schedule(
                cfg, [netsim.straggle_burst(0.01, 0.03, p)],
                spec=spec).overrides())),
              netsim.Axis("seed", (1, 2))))


def _record_plan_two_tier():
    """Reno on the two-tier fabric, with a job leaving and re-joining
    mid-run (churn) beside a straggle burst."""
    spec = netsim.FaultSpec(n_events=4, churn=True, straggle_bursts=True)

    def build(pt):
        topo = netsim.two_tier([(0, 1), (1, 2), (2, 0)], n_leaves=3)
        jobs = netsim.JobSpec.simple([0.002, 0.003, 0.0025],
                                     [2e6, 1.5e6, 2.5e6])
        return netsim.SimConfig(topo=topo, jobs=jobs, protocol=_proto(),
                                sim_time=0.05, dt=DT, seed=0, faults=spec)
    events = {"none": [],
              "churn": [netsim.job_departs(0.0105, 1),
                        netsim.job_arrives(0.02, 1),
                        netsim.straggle_burst(0.03, None, 0.3)]}
    return netsim.Plan(
        name="record-two-tier", build=build,
        axes=(netsim.Axis("events", tuple(events), field="*", resolve=(
            lambda name: lambda cfg: netsim.fault_schedule(
                cfg, events[name], spec=spec).overrides())),
              netsim.Axis("seed", (6, 7))))


# 127 iterations of 2 ticks fit 254 ticks: the bound is 128, one slot above
TIGHT_TICKS = 254


def _record_plan_tight():
    """Compute of 1.5 ticks and no bytes: every iteration takes 2 ticks,
    so the jobs finish within a slot of the bound."""
    def build(pt):
        jobs = netsim.JobSpec.simple([1.5 * DT] * 2, [0.0] * 2)
        return netsim.SimConfig(topo=netsim.dumbbell(2, sockets_per_job=2),
                                jobs=jobs, protocol=_proto(),
                                sim_time=TIGHT_TICKS * DT, dt=DT, seed=0)
    return netsim.Plan(name="record-tight", build=build,
                       axes=(netsim.Axis("seed", (0, 1)),))


RECORD_PLANS = {"dumbbell": _record_plan_dumbbell,
                "triangle": _record_plan_triangle,
                "two_tier": _record_plan_two_tier,
                "tight": _record_plan_tight}


@pytest.mark.parametrize("case", sorted(RECORD_PLANS))
def test_bounded_record_matches_full_record_bytewise(case, monkeypatch):
    """A plan run with its record sized to the group's bound gives every
    `SimResult` field byte for byte as with the record at its 4,096-slot
    ceiling, and no job finishes more iterations than the bound."""
    plan = RECORD_PLANS[case]()
    _, cfgs, overrides, groups = experiment.resolve_plan(plan)
    bounds = []
    for g in groups:
        sweep = experiment.group_sweep(cfgs, overrides, g)
        active = (None if sweep.job_active is None
                  else np.asarray(sweep.job_active))
        bounds.append(engine.iteration_bound(g.cfg, np.asarray(sweep.compute),
                                             active))
    bounded = netsim.run_plan(plan, shard=False)
    slots = [g.iter_slots for g in bounded.profile.groups]
    assert slots == [g.cfg.max_iters_recorded for g in groups]
    assert all(b <= s < engine.MAX_ITERS for b, s in zip(bounds, slots))
    most = max(len(x) for r in bounded for x in r.iter_times)
    assert 2 <= most <= max(bounds)
    if case == "tight":
        assert most == TIGHT_TICKS // 2 and max(bounds) - most <= 2
    monkeypatch.setattr(experiment, "_bound_record", lambda cfg, sweep: cfg)
    full = netsim.run_plan(plan, shard=False)
    assert ([g.iter_slots for g in full.profile.groups]
            == [engine.MAX_ITERS] * len(groups))
    assert len(bounded) == len(full)
    for a, b in zip(bounded, full):
        _same_bytes(a, b)


def _bench_like_plan(orders, seeds):
    """Distinct single-phase programs, permuted across the jobs per point,
    each point with its own seed, OFF and WI (as the benchmark's calls)."""
    compute = np.array([0.004, 0.0025, 0.006])
    comm = np.array([3e6, 2e6, 4e6])

    def build(pt):
        order = list(orders[pt["point"]])
        variant = {"OFF": Variant.OFF, "WI": Variant.WI}[pt["variant"]]
        return netsim.SimConfig(
            topo=netsim.dumbbell(3, sockets_per_job=2),
            jobs=netsim.JobSpec.simple(compute[order], comm[order]),
            protocol=_proto(variant=variant), sim_time=0.0413, dt=DT,
            seed=seeds[pt["point"]])
    return netsim.Plan(
        name="record-bench-like", build=build,
        axes=(netsim.Axis("variant", ("OFF", "WI"), kind="static"),
              netsim.Axis("point", tuple(range(len(orders))),
                          kind="static")))


def test_bounded_record_does_not_retrace_on_reordered_programs():
    """The bound reads the programs, not their order or the seeds: a re-run
    with the programs permuted and new seeds traces nothing and sizes the
    record alike.  The record-form counter reads "bounded" for the plan and
    "default" for a direct `simulate_sweep` call."""
    from repro.netsim import counters

    with counters.watch() as w:
        first = netsim.run_plan(_bench_like_plan(
            [(0, 1, 2), (2, 0, 1)], [11, 12]), shard=False)
    assert w.traces == 2
    assert w.iter_records == {"bounded": 2, "default": 0}
    before = engine.TRACE_COUNT
    again = netsim.run_plan(_bench_like_plan(
        [(1, 2, 0), (0, 2, 1)], [2**31 - 5, 987654321]), shard=False)
    assert engine.TRACE_COUNT == before
    slots = [g.iter_slots for g in first.profile.groups]
    assert [g.iter_slots for g in again.profile.groups] == slots
    assert all(1 < s < engine.MAX_ITERS for s in slots)
    cfg = _cfg(sim_time=0.0413)
    with counters.watch() as w2:
        netsim.simulate_sweep(cfg, netsim.make_sweep(cfg, seed=[1, 2]))
    assert w2.iter_records == {"bounded": 0, "default": 1}


# ---------------------------------------------------------------------------
# resolve_plan predicts the groups run_plan dispatches
# ---------------------------------------------------------------------------

def _static_mix_plan():
    """Static-factor and adaptive points under a favoritism the fused kernel
    does not implement: with the kernel on, factor presence splits them."""
    def build(pt):
        cfg = _cfg(sim_time=0.02, use_pallas_kernel=True,
                   protocol=_proto(favoritism="smallest_data_remaining"))
        if pt["scheme"] == "static":
            cfg = dataclasses.replace(cfg,
                                      static_job_factors=np.array([1.0, 0.5]))
        return cfg
    return netsim.Plan(name="static-mix", build=build,
                       axes=(netsim.Axis("scheme", ("adaptive", "static")),
                             netsim.Axis("seed", (0, 1))))


DISPATCH_PLANS = {
    "padded_jobs": lambda: _jobs_plan(job_counts=(2, 3, 4), seeds=(3, 4),
                                      sim_time=0.05, name="dispatch-jobs"),
    "faults": _record_plan_triangle,
    "static_mix": _static_mix_plan,
}


@pytest.mark.parametrize("case", sorted(DISPATCH_PLANS))
def test_run_plan_dispatches_resolve_plan_groups(case):
    """`run_plan` runs the groups `resolve_plan` predicts, group by group:
    the same signature, the same K and the same iteration record."""
    from repro.analysis import kernel_expectation

    plan = DISPATCH_PLANS[case]()
    _, cfgs, overrides, groups = experiment.resolve_plan(plan)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the adaptive arm's fallback
        pr = netsim.run_plan(plan, shard=False)
    assert pr.group_errors == []
    assert pr.n_compile_groups == len(groups) == len(pr.profile.groups)
    for g, prof in zip(groups, pr.profile.groups):
        assert prof.signature == experiment._group_signature(g)
        assert prof.n_points == len(g.idxs)
        assert prof.iter_slots == g.cfg.max_iters_recorded
    if case == "static_mix":
        lowerings = [kernel_expectation(
            g.cfg, experiment.group_sweep(cfgs, overrides, g))
            for g in groups]
        assert lowerings == ["fallback", "fused"]


# ---------------------------------------------------------------------------
# Each group's output reaches the host in one fetch
# ---------------------------------------------------------------------------

def _capture_sweeps(monkeypatch) -> list:
    """Record every group output `run_plan` dispatches."""
    raws = []

    def capture(cfg, sweep):
        raws.append(engine.simulate_sweep(cfg, sweep))
        return raws[-1]
    monkeypatch.setattr(experiment, "simulate_sweep", capture)
    return raws


HOST_VIEW_PLANS = {
    "padded_jobs": (lambda: _jobs_plan(job_counts=(2, 3), seeds=(3, 4),
                                       sim_time=0.05, name="view-jobs"),
                    None),
    "telemetry": (lambda: _jobs_plan(job_counts=(2, 3), seeds=(3,),
                                     sim_time=0.05, name="view-telemetry"),
                  netsim.TelemetrySpec(stride=25)),
    "faults": (_record_plan_triangle, None),
}


@pytest.mark.parametrize("case", sorted(HOST_VIEW_PLANS))
def test_host_view_results_match_device_slicing_bytewise(case, monkeypatch):
    """Every `SimResult` field of `run_plan` is byte for byte what slicing
    each point out of the same group output on the device gives."""
    make, spec = HOST_VIEW_PLANS[case]
    plan = make()
    points, cfgs, overrides, groups = experiment.resolve_plan(
        plan, telemetry=spec)
    raws = _capture_sweeps(monkeypatch)
    pr = netsim.run_plan(plan, shard=False, telemetry=spec)
    assert pr.group_errors == [] and len(raws) == len(groups)
    for g, raw in zip(groups, raws):
        per_point = experiment._stack_group(cfgs, overrides, g)[0]
        for slot, i in enumerate(g.idxs):
            n = cfgs[i].jobs.n_jobs
            point = netsim.SweepPoint(axes=dict(points[i]),
                                      params=per_point[slot], n_jobs=n)
            want = metrics.postprocess(
                cfgs[i], jax.tree_util.tree_map(lambda x: x[slot], raw),
                point=point, n_jobs=n)
            _same_bytes(pr[i], want)
            assert (pr[i].telemetry is not None) == (spec is not None)


def test_postprocess_sweep_makes_no_implicit_transfer():
    """A group's results come from one explicit `device_get`: with implicit
    transfers refused, `postprocess_sweep` still runs, telemetry armed, where
    indexing each point out of the device arrays would transfer its index."""
    cfg = _cfg(sim_time=0.05, telemetry=netsim.TelemetrySpec(stride=25))
    raw = jax.block_until_ready(netsim.simulate_sweep(
        cfg, netsim.make_sweep(cfg, seed=[1, 2])))
    with jax.transfer_guard("disallow"):
        results = netsim.postprocess_sweep(cfg, raw)
        view = metrics.host_view(cfg, raw)
    assert len(results) == 2
    assert all(r.telemetry is not None for r in results)
    assert view.final_state is None and view.trace_ratio is None
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(view))


# the leaves of a group's output that `metrics.postprocess` reads
READ_LEAVES = ("iter_times", "iter_counts", "trace_t", "trace_util",
               "trace_incomm", "trace_drops", "trace_marks", "trace_jobtput")


def test_fetched_bytes_are_the_leaves_postprocess_reads(monkeypatch):
    """At the benchmark cell's group shapes (K = 4, seven jobs of eight
    sockets on one link, 400 chunks, 256 record slots) each group fetches
    the eight leaves `postprocess` reads, 110,384 B, and no `final_state`.
    The device call is replaced by zeros of the program's output shapes."""
    n_ticks, n_jobs = 148_032, 7

    def build(pt):
        variant = {"OFF": Variant.OFF, "WI": Variant.WI}[pt["variant"]]
        return netsim.SimConfig(
            topo=netsim.dumbbell(n_jobs, sockets_per_job=8),
            jobs=netsim.JobSpec.simple([0.05] * n_jobs, [1e8] * n_jobs),
            protocol=_proto(variant=variant), sim_time=n_ticks * DT, dt=DT,
            seed=0)
    plan = netsim.Plan(name="cell-shapes", build=build,
                       axes=(netsim.Axis("variant", ("OFF", "WI")),
                             netsim.Axis("seed", (1, 2, 3, 4))))
    raws, fetched = [], []

    def zeros(cfg, sweep):
        shapes = jax.eval_shape(lambda s: engine.simulate_sweep(cfg, s),
                                sweep)
        raws.append(jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes))
        return raws[-1]

    def device_get(tree):
        fetched.append(tree)
        return real_get(tree)
    real_get = jax.device_get
    monkeypatch.setattr(experiment, "simulate_sweep", zeros)
    monkeypatch.setattr(experiment, "_bound_record", lambda cfg, sweep:
                        dataclasses.replace(cfg, max_iters_recorded=256))
    monkeypatch.setattr(jax, "device_get", device_get)
    pr = netsim.run_plan(plan, shard=False)
    assert [g.n_points for g in pr.profile.groups] == [4, 4]
    assert len(fetched) == len(raws) == 2
    for prof, raw, tree in zip(pr.profile.groups, raws, fetched):
        read = sum(getattr(raw, name).nbytes for name in READ_LEAVES)
        assert prof.fetched_bytes == read == 110_384
        assert tree.final_state is None and tree.trace_ratio is None
        assert tree.telemetry is None
        assert sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)) == read
        assert sum(x.nbytes for x in jax.tree_util.tree_leaves(raw)) > read
