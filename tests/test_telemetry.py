"""On-device probe subsystem (`netsim.telemetry`) — the off-is-free
invariant, decimation correctness, detector == NumPy replay, trace-count
pinning, and the plan-layer plumbing (telemetry=, phase profile, cache
versioning, per-plan fallback-warning reset)."""
import dataclasses
import math

import jax
import numpy as np
import pytest

from repro import netsim
from repro.netsim import counters, engine, telemetry
from repro.core import Algo, CCParams, MLTCPConfig, Variant

DT = 2e-5


def _proto(algo=Algo.RENO, variant=Variant.WI, **kw):
    return MLTCPConfig(cc=CCParams(algo=int(algo), variant=int(variant),
                                   tick_dt=DT, rtt=100e-6),
                       slope=1.75, intercept=0.25, **kw)


def _cfg(n_jobs=2, sim_time=0.2, seed=3, **kw):
    topo = netsim.dumbbell(n_jobs, sockets_per_job=2)
    jobs = netsim.JobSpec.simple([0.004] * n_jobs, [2e6] * n_jobs)
    return netsim.SimConfig(topo=topo, jobs=jobs,
                            protocol=kw.pop("protocol", _proto()),
                            sim_time=sim_time, dt=DT, seed=seed, **kw)


ALL_PROBES = ("flow_cwnd", "flow_rate", "flow_ratio", "link_queue",
              "link_mark_rate", "job_incomm", "job_phase", "job_iter",
              "job_f", "interleave_overlap")


# ---------------------------------------------------------------------------
# (a) telemetry off is free
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", [Algo.RENO, Algo.CUBIC, Algo.DCQCN])
def test_off_bit_identical_and_armed_changes_nothing(algo):
    """Arming every probe + detector must not perturb a single bit of the
    pre-existing outputs, and the unarmed config's telemetry stays None."""
    cfg = _cfg(protocol=_proto(algo=algo))
    raw_off = netsim.simulate(cfg)
    assert raw_off.telemetry is None
    assert raw_off.final_state.telemetry is None

    cfg_on = dataclasses.replace(
        cfg, telemetry=telemetry.TelemetrySpec(probes=ALL_PROBES, stride=40))
    raw_on = netsim.simulate(cfg_on)
    assert raw_on.telemetry is not None
    for f in engine.RawSimOutput._fields:
        if f in ("final_state", "telemetry"):
            continue
        assert np.array_equal(np.asarray(getattr(raw_off, f)),
                              np.asarray(getattr(raw_on, f)),
                              equal_nan=True), f


def test_off_output_has_no_extra_leaves():
    """None telemetry contributes zero pytree leaves: an unarmed run's
    output tree is leaf-identical to the pre-subsystem layout."""
    cfg = _cfg(sim_time=0.05)
    raw = netsim.simulate(cfg)
    stripped = raw._replace(final_state=None, telemetry=None)
    n_chunk_fields = len(telemetry.CHUNK_PROBES)
    # iter_times + iter_counts + the chunk trace channels
    assert len(jax.tree_util.tree_leaves(stripped)) == 2 + n_chunk_fields


def test_off_rerun_does_not_retrace():
    cfg = _cfg(sim_time=0.05)
    sweep = netsim.make_sweep(cfg, seed=(1, 2))
    netsim.simulate_sweep(cfg, sweep)
    before = engine.TRACE_COUNT
    netsim.simulate_sweep(cfg, netsim.make_sweep(cfg, seed=(3, 4)))
    assert engine.TRACE_COUNT == before


# ---------------------------------------------------------------------------
# (b) decimated series == dense stride-1 reference at the sampled ticks
# ---------------------------------------------------------------------------

def test_decimated_equals_dense_restriction():
    stride = 37            # deliberately not a divisor of anything
    probes = ("flow_cwnd", "link_queue", "job_incomm", "job_f")
    base = _cfg(sim_time=0.05)
    dense_cfg = dataclasses.replace(
        base, telemetry=telemetry.TelemetrySpec(probes=probes, stride=1,
                                                detectors=()))
    dec_cfg = dataclasses.replace(
        base, telemetry=telemetry.TelemetrySpec(probes=probes, stride=stride,
                                                detectors=()))
    dense = telemetry.collect(dense_cfg, netsim.simulate(dense_cfg).telemetry)
    dec = telemetry.collect(dec_cfg, netsim.simulate(dec_cfg).telemetry)
    assert np.array_equal(dec.ticks, dense.ticks[::stride])
    for name in probes:
        assert np.array_equal(dec.series[name], dense.series[name][::stride]), name


def test_ring_buffer_wraps_chronologically():
    """capacity < samples: the ring keeps the *latest* window, and collect
    returns it in tick order."""
    cfg = _cfg(sim_time=0.05)
    cap = 13
    cfg = dataclasses.replace(
        cfg, telemetry=telemetry.TelemetrySpec(probes=("job_iter",),
                                               stride=10, capacity=cap,
                                               detectors=()))
    res = telemetry.collect(cfg, netsim.simulate(cfg).telemetry)
    n_ticks = cfg.n_ticks
    sampled = np.arange(0, n_ticks, 10)
    assert np.array_equal(res.ticks, sampled[-cap:])
    assert res.n_samples == len(sampled)


# ---------------------------------------------------------------------------
# (c) in-scan detectors == NumPy post-hoc replay
# ---------------------------------------------------------------------------

def test_interleave_detector_matches_numpy_replay():
    spec = telemetry.TelemetrySpec(probes=("job_incomm", "job_iter"),
                                   stride=1)
    cfg = dataclasses.replace(_cfg(), telemetry=spec)
    raw = netsim.simulate(cfg)
    ic = np.asarray(raw.telemetry.series["job_incomm"]) > 0.5
    ji = np.asarray(raw.telemetry.series["job_iter"])

    # float32 replay of the streaming EWMA both/either ratio
    alpha = np.float32(-math.expm1(-cfg.dt / spec.overlap_tau))
    a, b = ic[:, 0], ic[:, 1]
    eb = ee = np.float32(0.0)
    last_bad, iters_at = -1, 0
    for t in range(len(a)):
        eb = eb + alpha * (np.float32(a[t] & b[t]) - eb)
        ee = ee + alpha * (np.float32(a[t] | b[t]) - ee)
        ov = eb / max(ee, np.float32(1e-6))
        if ov > spec.overlap_threshold:
            last_bad, iters_at = t, ji[t].max()
    assert int(raw.telemetry.last_bad_tick) == last_bad
    assert int(raw.telemetry.iters_at_last_bad) == int(iters_at)

    res = telemetry.collect(cfg, raw.telemetry)
    hold = int(round(spec.hold_frac * cfg.n_ticks))
    if last_bad < cfg.n_ticks - hold:
        assert res.converged
        assert res.time_to_interleave_s == pytest.approx((last_bad + 1) * cfg.dt)
        assert res.time_to_interleave_iters == float(iters_at)
    else:
        assert not res.converged
        assert res.time_to_interleave_s == float("inf")


def test_iter_sketch_quantiles_match_percentile():
    """Streaming p50/p99 from the log-histogram sketch lands within one
    bin width of the exact percentile over the recorded iterations."""
    spec = telemetry.TelemetrySpec(probes=(), detectors=("iter_sketch",))
    cfg = dataclasses.replace(_cfg(sim_time=0.4), telemetry=spec)
    res = netsim.postprocess(cfg, netsim.simulate(cfg))
    exact = np.concatenate(res.iter_times)
    assert int(res.telemetry.iter_hist.sum()) == exact.size
    ratio = spec.sketch_hi / spec.sketch_lo
    bin_w = ratio ** (1.0 / spec.sketch_bins)     # geometric bin width
    for q in (0.5, 0.99):
        sk = res.telemetry.iter_quantile(q)
        ex = float(np.quantile(exact, q))
        assert ex / bin_w <= sk <= ex * bin_w


# ---------------------------------------------------------------------------
# (d) trace accounting: armed probes cost exactly one trace per group
# ---------------------------------------------------------------------------

def test_armed_plan_one_trace_per_group_and_rerun_free():
    spec = telemetry.TelemetrySpec(stride=50)
    plan = netsim.Plan(
        name="tele-trace",
        axes=(netsim.Axis("variant", ("OFF", "WI")),
              netsim.Axis("seed", (1, 2))),
        build=lambda pt: _cfg(sim_time=0.05, protocol=_proto(
            variant=Variant[pt["variant"]])))
    before = engine.TRACE_COUNT
    pr = netsim.run_plan(plan, telemetry=spec)
    assert pr.n_compile_groups == 2
    assert engine.TRACE_COUNT - before == 2
    assert all(r.telemetry is not None for r in pr)
    # rerun: jit cache holds both armed programs — zero new traces
    before = engine.TRACE_COUNT
    netsim.run_plan(plan, telemetry=spec)
    assert engine.TRACE_COUNT == before
    # profile per group recorded on the default path
    assert len(pr.profile.groups) == 2
    assert all(g.wall_s > 0 for g in pr.profile.groups)


def test_padded_group_trims_point_telemetry():
    """On a padded-jobs group, each point's series trim to its own fabric."""
    spec = telemetry.TelemetrySpec(probes=("flow_cwnd", "job_incomm"),
                                   stride=50)

    def build(pt):
        n = pt["n_jobs"]
        topo = netsim.dumbbell(n, sockets_per_job=2)
        jobs = netsim.JobSpec.simple([0.004] * n, [2e6] * n)
        return netsim.SimConfig(topo=topo, jobs=jobs, protocol=_proto(),
                                sim_time=0.05, dt=DT, seed=3)

    plan = netsim.Plan(name="tele-pad",
                       axes=(netsim.Axis("n_jobs", (2, 3)),), build=build)
    pr = netsim.run_plan(plan, telemetry=spec)
    assert pr.n_compile_groups == 1          # padded into one group
    for r in pr:
        n = r.point["n_jobs"]
        assert r.telemetry.series["job_incomm"].shape[1] == n
        assert r.telemetry.series["flow_cwnd"].shape[1] == 2 * n


# ---------------------------------------------------------------------------
# registry & spec validation
# ---------------------------------------------------------------------------

def test_unknown_probe_rejected_and_custom_probe_captured():
    cfg = dataclasses.replace(
        _cfg(), telemetry=telemetry.TelemetrySpec(probes=("no_such",)))
    with pytest.raises(ValueError, match="no_such"):
        netsim.simulate(cfg)

    name = "test_q_sq"
    telemetry.register_probe(name, "link", lambda s: s.q_len ** 2,
                             overwrite=True)
    spec = telemetry.TelemetrySpec(probes=(name, "link_queue"), stride=25,
                                   detectors=())
    cfg = dataclasses.replace(_cfg(sim_time=0.05), telemetry=spec)
    res = telemetry.collect(cfg, netsim.simulate(cfg).telemetry)
    assert np.array_equal(res.series[name], res.series["link_queue"] ** 2)


def test_probe_timeline_accessors():
    spec = telemetry.TelemetrySpec(stride=50)
    cfg = dataclasses.replace(_cfg(), telemetry=spec)
    res = netsim.postprocess(cfg, netsim.simulate(cfg))
    t, cw = netsim.probe_timeline(res, "flow_cwnd")
    assert t.shape[0] == cw.shape[0] and cw.shape[1] == cfg.topo.n_flows
    assert np.isfinite(netsim.time_to_interleave(res)) in (True, False)
    with pytest.raises(KeyError, match="job_f"):
        netsim.probe_timeline(res, "job_f")    # not armed by default
    off = netsim.postprocess(_cfg(sim_time=0.05), netsim.simulate(
        _cfg(sim_time=0.05)))
    with pytest.raises(ValueError, match="telemetry"):
        netsim.time_to_interleave(off)


# ---------------------------------------------------------------------------
# plan layer: profiling, cache versioning, warning reset
# ---------------------------------------------------------------------------

def _mini_plan(**build_kw):
    kw = {"sim_time": 0.05, **build_kw}
    return netsim.Plan(name="mini",
                       axes=(netsim.Axis("seed", (1, 2)),),
                       build=lambda pt: _cfg(**kw))


def test_profile_split_fields():
    pr = netsim.run_plan(_mini_plan())
    prof = pr.profile
    (g,) = prof.groups
    assert g.stack_s > 0 and g.wall_s > 0 and g.postprocess_s > 0
    assert g.n_points == 2 and g.n_ticks == 2500
    assert prof.prepare_s > 0
    assert set(prof.jit_s) == set(counters.JIT_KINDS)
    s = prof.summary()
    assert s["n_groups"] == 1 and "compile_s" in s and "trace_s" in s
    assert s["stack_s"] > 0 and s["postprocess_s"] > 0
    assert prof.total_ticks == 2 * 2500


def test_fallback_warning_rearmed_per_plan():
    """A plan whose kernel-enabled config falls back must warn even when an
    earlier plan already warned for the same reason."""
    pytest.importorskip("repro.kernels.ops")
    kw = dict(protocol=_proto(favoritism="smallest_data_remaining"),
              use_pallas_kernel=True)
    with pytest.warns(UserWarning, match="favoritism"):
        pr = netsim.run_plan(_mini_plan(**kw))
    assert pr.n_kernel_fallbacks >= 1
    # a *different static config* (new trace) with the same fallback reason:
    # without the per-plan reset, the process-global once-guard would
    # swallow this plan's warning
    with pytest.warns(UserWarning, match="favoritism"):
        netsim.run_plan(_mini_plan(sim_time=0.06, **kw))
