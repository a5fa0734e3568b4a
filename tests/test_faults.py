"""Traced fault injection (netsim.faults) + fault-tolerant run_plan.

Pins the three contracts DESIGN.md §8 promises:

* faults off is *free*: ``faults=None`` and an armed-but-identity schedule
  produce bit-identical trajectories, on the fused kernel path, with zero
  fallbacks — and schedule values are data, so new schedules never retrace;
* the fault channels do what they claim at the engine/link level (churn
  freezes a job, blackholes stall the holed job, flaps stretch iterations,
  straggle bursts straggle);
* a poisoned compile group under ``run_plan(keep_going=True)`` is salvaged
  (healthy groups complete + cache, the failure is reported on
  ``group_errors``), and a corrupt cache entry is quarantined, never fatal.
"""
import dataclasses
import os
import warnings

import jax
import numpy as np
import pytest

from repro import netsim
from repro.netsim import engine
from repro.core import Algo, CCParams, MLTCPConfig, Variant

DT = 2e-5

ALGOS = {"reno": Algo.RENO, "cubic": Algo.CUBIC, "dcqcn": Algo.DCQCN}


def _proto(algo=Algo.RENO, variant=Variant.WI, **kw):
    return MLTCPConfig(cc=CCParams(algo=int(algo), variant=int(variant),
                                   tick_dt=kw.pop("tick_dt", DT),
                                   rtt=100e-6),
                       slope=1.75, intercept=0.25, **kw)


def _cfg(n_jobs=2, sim_time=0.5, seed=3, **kw):
    topo = netsim.dumbbell(n_jobs, sockets_per_job=2)
    jobs = netsim.JobSpec.simple([0.0075] * n_jobs, [25e6] * n_jobs)
    return netsim.SimConfig(topo=topo, jobs=jobs,
                            protocol=kw.pop("protocol", _proto()),
                            sim_time=sim_time, dt=DT, seed=seed, **kw)


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(la, lb))


ALL_SPEC = netsim.FaultSpec(n_events=4, churn=True, link_flaps=True,
                            blackholes=True, straggle_bursts=True)


# ---------------------------------------------------------------------------
# Faults off is free
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["reno", "cubic", "dcqcn"])
def test_armed_identity_schedule_is_bitwise_noop(algo):
    """For every CC algorithm, arming a FaultSpec with the identity
    schedule (the default when no overrides arrive) runs bit-identical to
    ``faults=None`` on the fused kernel path, with zero oracle fallbacks —
    every channel's no-op really is exact (`& True`, `* 1.0`, `+ 0.0`,
    `where(False)`)."""
    from repro.kernels import ops

    proto = _proto(algo=ALGOS[algo])
    cfg = _cfg(sim_time=0.25, protocol=proto, use_pallas_kernel=True)
    before = ops.FALLBACK_COUNT
    raw_off = netsim.simulate(cfg)
    raw_armed = netsim.simulate(dataclasses.replace(cfg, faults=ALL_SPEC))
    assert ops.FALLBACK_COUNT == before, \
        f"{algo}: fault channels knocked the CC-tick kernel off the fused path"
    for name in raw_off._fields:
        assert _tree_equal(getattr(raw_off, name), getattr(raw_armed, name)), \
            f"{algo}: identity fault schedule changed RawSimOutput.{name}"


def test_explicit_identity_schedule_matches_default():
    """`identity_schedule` fed through make_sweep == the armed default."""
    cfg = _cfg(sim_time=0.2, faults=ALL_SPEC)
    ident = netsim.identity_schedule(cfg, ALL_SPEC)
    raw_default = netsim.simulate(cfg)
    raw_explicit = jax.tree_util.tree_map(
        lambda x: x[0],
        netsim.simulate_sweep(cfg, netsim.make_sweep(cfg, **ident.overrides())))
    assert _tree_equal(raw_default, raw_explicit)


def test_fault_schedules_are_data_not_structure():
    """Two different non-trivial schedules under one FaultSpec share one
    trace: the schedule rides in SweepParams, so re-running with new fault
    values costs zero retraces (the batched-churn-grid property the churn
    benchmark relies on)."""
    spec = netsim.FaultSpec(n_events=4, churn=True, link_flaps=True)
    cfg = _cfg(sim_time=0.2, faults=spec)
    sched_a = netsim.fault_schedule(
        cfg, [netsim.job_departs(0.05, 1), netsim.job_arrives(0.1, 1)],
        spec=spec)
    sched_b = netsim.fault_schedule(
        cfg, [netsim.link_flap(0.04, 0.12, 0, 0.5)], spec=spec)
    before = engine.TRACE_COUNT
    netsim.simulate_sweep(cfg, netsim.make_sweep(cfg, **sched_a.overrides()))
    assert engine.TRACE_COUNT == before + 1
    netsim.simulate_sweep(cfg, netsim.make_sweep(cfg, **sched_b.overrides()))
    assert engine.TRACE_COUNT == before + 1, \
        "a new fault schedule under the same spec retraced the program"


# ---------------------------------------------------------------------------
# Schedule builder semantics
# ---------------------------------------------------------------------------

def test_schedule_builds_sorted_padded_event_table():
    spec = netsim.FaultSpec(n_events=6, churn=True, link_flaps=True)
    cfg = _cfg(sim_time=0.5, faults=spec)
    sched = netsim.fault_schedule(
        cfg, [netsim.link_flap(0.2, 0.3, 0, 0.5),
              netsim.job_departs(0.1, 1)], spec=spec)
    ticks = sched.values["fault_tick"]
    assert ticks.shape == (6,)
    # boundaries: 0, departure, flap start, flap end — then padding rows
    # that duplicate the last boundary (rank-sum row selection picks the
    # LAST duplicate, so padding shadows nothing)
    expect = [0, round(0.1 / DT), round(0.2 / DT), round(0.3 / DT)]
    assert list(ticks[:4]) == expect
    assert list(ticks[4:]) == [expect[-1]] * 2
    # padding rows carry the final row's channel state verbatim
    assert np.array_equal(sched.values["fault_job_active"][4],
                          sched.values["fault_job_active"][3])
    assert np.array_equal(sched.values["fault_link_scale"][4],
                          sched.values["fault_link_scale"][3])


def test_schedule_churn_forward_fills_and_windows_apply():
    spec = netsim.FaultSpec(n_events=5, churn=True, link_flaps=True)
    cfg = _cfg(sim_time=0.5, faults=spec)
    sched = netsim.fault_schedule(
        cfg, [netsim.job_departs(0.1, 1), netsim.job_arrives(0.3, 1),
              netsim.link_flap(0.1, 0.3, 0, 0.25)], spec=spec)
    active = sched.values["fault_job_active"]
    # rows: t=0 (all in), depart (job 1 out ... persists), arrive (back)
    assert active[:, 0].all()
    assert list(active[:3, 1]) == [True, False, True]
    scale = sched.values["fault_link_scale"][:3, 0]
    np.testing.assert_allclose(scale, [1.0, 0.25, 1.0])


def test_schedule_overlapping_flaps_compose_multiplicatively():
    cfg = _cfg(sim_time=0.5)
    sched = netsim.fault_schedule(
        cfg, [netsim.link_flap(0.1, 0.4, 0, 0.5),
              netsim.link_flap(0.2, 0.3, 0, 0.5)])
    scale = sched.values["fault_link_scale"][:, 0]
    # rows at 0, .1, .2, .3, .4: nested flap windows multiply
    np.testing.assert_allclose(scale, [1.0, 0.5, 0.25, 0.5, 1.0])


def test_schedule_validates():
    cfg = _cfg()  # 2 jobs, 4 flows, 1 bottleneck + leaf links
    with pytest.raises(ValueError, match="indexes"):
        netsim.fault_schedule(cfg, [netsim.job_departs(0.1, 7)])
    with pytest.raises(ValueError, match="does not arm"):
        netsim.fault_schedule(
            cfg, [netsim.job_departs(0.1, 1)],
            spec=netsim.FaultSpec(n_events=4, link_flaps=True))
    with pytest.raises(ValueError, match="event rows"):
        netsim.fault_schedule(
            cfg, [netsim.link_flap(0.1, 0.2, 0, 0.5),
                  netsim.link_flap(0.3, 0.4, 0, 0.5)],
            spec=netsim.FaultSpec(n_events=2, link_flaps=True))
    with pytest.raises(ValueError, match="empty"):
        netsim.link_flap(0.2, 0.2, 0, 0.5)
    with pytest.raises(ValueError, match="at least one flow"):
        netsim.blackhole(0.1, 0.2, [])
    with pytest.raises(ValueError, match="channel"):
        netsim.faults.FaultEvent("gremlin", 0.1, None, (), 1.0)


# ---------------------------------------------------------------------------
# Fault dynamics
# ---------------------------------------------------------------------------

def _iter_counts(cfg, overrides=None):
    sweep = (netsim.make_sweep(cfg, **overrides) if overrides
             else netsim.make_sweep(cfg))
    raw = netsim.simulate_sweep(cfg, sweep)
    return np.asarray(raw.iter_counts)[0]


def test_churn_freezes_and_resumes_a_job():
    # seed 1: under the default (partitionable threefry) random stream the
    # seed-3 baseline is the one seed of 0-7 whose survivor completes more
    # iterations with its competitor present throughout (41 vs 38)
    spec = netsim.FaultSpec(n_events=3, churn=True)
    cfg = _cfg(sim_time=0.6, faults=spec, seed=1)
    base = _iter_counts(cfg)
    gone = netsim.fault_schedule(      # job 1 out for the middle third
        cfg, [netsim.job_departs(0.2, 1), netsim.job_arrives(0.4, 1)],
        spec=spec)
    faulted = _iter_counts(cfg, gone.overrides())
    # the churned job lost roughly its absence window of progress...
    assert faulted[1] < base[1] * 0.85
    # ...but kept running outside it; the survivor never slowed down
    assert faulted[1] > 0
    assert faulted[0] >= base[0]


def test_blackhole_stalls_only_the_holed_job():
    spec = netsim.FaultSpec(n_events=3, blackholes=True)
    cfg = _cfg(sim_time=0.6, faults=spec)
    base = _iter_counts(cfg)
    flows = [int(f) for f in
             np.nonzero(np.asarray(cfg.topo.flow_to_job) == 1)[0]]
    holed = netsim.fault_schedule(
        cfg, [netsim.blackhole(0.2, 0.4, flows)], spec=spec)
    faulted = _iter_counts(cfg, holed.overrides())
    assert faulted[1] < base[1] * 0.85   # null-routed: no delivery, no progress
    assert faulted[0] >= base[0] * 0.9   # the other job rides through


def test_link_flap_stretches_iterations():
    spec = netsim.FaultSpec(n_events=3, link_flaps=True)
    cfg = _cfg(sim_time=0.6, faults=spec)
    base = _iter_counts(cfg)
    flapped = netsim.fault_schedule(    # bottleneck at quarter capacity
        cfg, [netsim.link_flap(0.2, 0.5, 0, 0.25)], spec=spec)
    faulted = _iter_counts(cfg, flapped.overrides())
    assert faulted.sum() < base.sum() * 0.9


def test_straggle_burst_slows_progress():
    """An uncontended job under a prob-1.0 straggle burst loses the
    straggle surcharge (5-10% of its isolated iteration time, sampled per
    iteration) on every iteration of the window — measurable directly as
    lost iterations, with no contention noise in the way."""
    spec = netsim.FaultSpec(n_events=3, straggle_bursts=True)
    cfg = _cfg(n_jobs=1, sim_time=0.8, faults=spec)
    base = _iter_counts(cfg)
    bursty = netsim.fault_schedule(
        cfg, [netsim.straggle_burst(0.0, None, 1.0)], spec=spec)
    faulted = _iter_counts(cfg, bursty.overrides())
    assert faulted.sum() < base.sum() - 1


def test_reinterleave_detector_reports_every_event_window():
    """The per-event verdict machinery: one report per schedule row, with
    start ticks matching the table and finite re-interleave iters only
    where re-convergence happened."""
    spec = netsim.FaultSpec(n_events=3, churn=True)
    sched_events = [netsim.job_departs(0.25, 1), netsim.job_arrives(0.45, 1)]
    tel = netsim.TelemetrySpec(
        probes=("interleave_overlap", "job_iter"),
        detectors=("interleave", "iter_sketch", "reinterleave"),
        stride=8)

    def build(pt):
        return _cfg(sim_time=0.8, faults=spec, telemetry=tel)

    plan = netsim.Plan(
        name="reinterleave-smoke",
        axes=(netsim.Axis(
            "schedule", ("gauntlet",), field="*",
            resolve=lambda label: (lambda cfg: netsim.fault_schedule(
                cfg, sched_events, spec=spec).overrides())),),
        build=build)
    res = netsim.run_plan(plan).results[0]
    reports = res.telemetry.fault_events
    assert len(reports) == spec.n_events
    cfg = build(None)
    table = netsim.fault_schedule(cfg, sched_events, spec=spec)
    assert [r.start_tick for r in reports] == \
        list(table.values["fault_tick"])
    for r in reports:
        assert r.reconverged in (True, False)
        if r.reconverged:
            assert np.isfinite(r.reinterleave_iters)


# ---------------------------------------------------------------------------
# Fault-tolerant run_plan + cache quarantine
# ---------------------------------------------------------------------------

def _poisonable_plan(sim_time=0.15):
    def build(pt):
        # the poisoned point builds a config whose protocol tick grid
        # disagrees with the simulator's — simulate_sweep rejects it at
        # group-run time, inside run_plan's per-group isolation
        tick = DT * 2 if pt["cell"] == "poison" else DT
        return _cfg(sim_time=sim_time, protocol=_proto(tick_dt=tick))
    return netsim.Plan(name="salvage",
                       axes=(netsim.Axis("cell", ("ok-a", "poison", "ok-b")),
                             netsim.Axis("seed", (0, 1))),
                       build=build)


def test_keep_going_false_reraises():
    with pytest.raises(ValueError, match="tick_dt"):
        netsim.run_plan(_poisonable_plan())


def test_keep_going_salvages_healthy_groups(tmp_path):
    cache = str(tmp_path / "cache")
    pr = netsim.run_plan(_poisonable_plan(), keep_going=True,
                         cache_dir=cache)
    # the poisoned group is reported, not raised...
    assert len(pr.group_errors) == 1
    err = pr.group_errors[0]
    assert "ValueError" in err.error and "tick_dt" in err.error
    assert all("cell=poison" in lbl for lbl in err.point_labels)
    assert "algo=" in err.signature
    # ...its members' slots stay None, every healthy cell completed
    missing = [r for r in pr.results if r is None]
    assert len(missing) == 2
    assert len(pr.select(cell="ok-a")) == 2
    assert len(pr.select(cell="ok-b")) == 2
    with pytest.raises(KeyError):
        pr.select(cell="poison")
    # healthy cells were cached: a re-run simulates nothing new
    pr2 = netsim.run_plan(_poisonable_plan(), keep_going=True,
                          cache_dir=cache)
    assert pr2.n_cache_hits == 4
    assert len(pr2.group_errors) == 1


def test_corrupt_cache_entry_quarantined_and_recomputed(tmp_path):
    cache = str(tmp_path / "cache")
    cfg = _cfg(sim_time=0.15)
    plan = netsim.Plan(name="cache-roundtrip",
                       axes=(netsim.Axis("seed", (0, 1, 2)),),
                       build=lambda pt: cfg)
    netsim.run_plan(plan, cache_dir=cache)
    entries = sorted(f for f in os.listdir(cache) if f.endswith(".pkl"))
    assert len(entries) == 3
    # truncate one entry mid-pickle, zero out another
    with open(os.path.join(cache, entries[0]), "wb") as f:
        f.write(b"\x80\x04corrupt")
    with open(os.path.join(cache, entries[1]), "wb"):
        pass
    with pytest.warns(RuntimeWarning, match="quarantined"):
        pr = netsim.run_plan(plan, cache_dir=cache)
    # the two damaged points were re-simulated, the healthy one served
    assert pr.n_cache_hits == 1
    assert all(r is not None for r in pr.results)
    quarantined = [f for f in os.listdir(cache) if f.endswith(".corrupt")]
    assert len(quarantined) >= 1
    # the re-run rewrote healthy entries: a third run is all hits, silently
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        pr3 = netsim.run_plan(plan, cache_dir=cache)
    assert pr3.n_cache_hits == 3


def test_prune_cache_evicts_quarantine_and_debris(tmp_path):
    cache = tmp_path / "cache"
    cache.mkdir()
    keep = cache / "v2-deadbeef.pkl"
    keep.write_bytes(b"x" * 16)
    debris = [cache / "v1-old.pkl",          # stale schema
              cache / "v2-torn.pkl.tmp",     # torn write
              cache / "v2-bad.pkl.corrupt",  # quarantined
              cache / "v2-empty.pkl"]        # zero-byte
    for p in debris[:-1]:
        p.write_bytes(b"x")
    debris[-1].write_bytes(b"")
    assert netsim.prune_cache(str(cache)) == len(debris)
    assert sorted(os.listdir(cache)) == [keep.name]
    assert netsim.prune_cache(str(tmp_path / "nonexistent")) == 0
