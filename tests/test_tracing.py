"""Where the time goes, measured inside the program: a `jax.named_scope` per
tick stage, `counters.span` phases in `run_plan`, and the jit-seconds
counter (`counters.jit_seconds`)."""
import glob
import os
import re
import threading
import time

import jax
import pytest

from repro import netsim
from repro.core import Algo, CCParams, MLTCPConfig, Variant
from repro.netsim import counters, engine, telemetry

DT = 2e-5
TICK_SCOPES = ("tick.rng", "tick.faults", "tick.phase", "tick.inject",
               "tick.links", "tick.feedback", "tick.accounting",
               "tick.cc_update", "tick.accumulate", "tick.telemetry")
CHUNK_SCOPES = ("chunk.reset", "chunk.capture")
CC_SCOPES = ("cc.pack", "cc.unpack")
PHASES = ("run_plan.prepare", "run_plan.stack", "run_plan.device",
          "run_plan.postprocess")


def _cfg(sim_time=0.05, seed=3, **kw):
    proto = MLTCPConfig(cc=CCParams(algo=int(Algo.RENO),
                                    variant=int(Variant.WI), tick_dt=DT,
                                    rtt=100e-6),
                        slope=1.75, intercept=0.25)
    return netsim.SimConfig(topo=netsim.dumbbell(2, sockets_per_job=2),
                            jobs=netsim.JobSpec.simple([0.004] * 2,
                                                       [2e6] * 2),
                            protocol=proto, sim_time=sim_time, dt=DT,
                            seed=seed, **kw)


def _plan(sim_time):
    return netsim.Plan(name="tracing", axes=(netsim.Axis("seed", (1, 2)),),
                       build=lambda pt: _cfg(sim_time=sim_time,
                                             seed=pt["seed"]))


def _scopes(text: str) -> set[str]:
    """Every tick/chunk/cc scope named in a lowered module's locations."""
    names = re.findall(r'loc\("([^"]*)"', text)
    return {part for n in names for part in n.split("/")
            if re.fullmatch(r"(tick|chunk|cc)\.[a-z_]+", part)}


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "oracle"])
def test_every_stage_scope_is_in_the_lowered_sweep(kernel):
    """Faults and telemetry armed, so that no stage's block vanishes; the
    oracle path has no pack/unpack around a kernel."""
    spec = netsim.FaultSpec(n_events=4, churn=True, link_flaps=True,
                            blackholes=True, straggle_bursts=True)
    cfg = _cfg(sim_time=0.01, use_pallas_kernel=kernel, faults=spec,
               telemetry=telemetry.TelemetrySpec(probes=("flow_cwnd",
                                                         "job_f"),
                                                 stride=40))
    lowered = engine.lower_sweep(cfg, engine.make_sweep(cfg))
    want = set(TICK_SCOPES + CHUNK_SCOPES + (CC_SCOPES if kernel else ()))
    assert _scopes(lowered.as_text(debug_info=True)) == want


def test_phase_spans_cover_the_call():
    t0 = time.perf_counter()
    pr = netsim.run_plan(_plan(0.0421))
    wall = time.perf_counter() - t0
    prof = pr.profile
    (g,) = prof.groups
    assert g.traced and min(prof.prepare_s, g.stack_s, g.wall_s,
                            g.postprocess_s) > 0
    inside = prof.prepare_s + g.stack_s + g.wall_s + g.postprocess_s
    assert 0.95 * wall <= inside <= wall


def test_phase_spans_land_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    plan = _plan(0.0422)
    netsim.run_plan(plan)                    # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        netsim.run_plan(plan)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    spans = [(e.name, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("run_plan.")]
    assert [n for n, _ in spans].count("run_plan.prepare") == 1
    for phase in PHASES[1:]:
        (args,) = [a for n, a in spans if n == phase]
        assert args["group"] == 0 and args["points"] == 2


def test_jit_seconds_cold_then_warm():
    plan = _plan(0.0423)
    cold = netsim.run_plan(plan).profile.jit_s
    assert set(cold) == set(counters.JIT_KINDS)
    assert cold["trace"] > 0 and cold["compile"] > 0
    warm = netsim.run_plan(plan).profile.jit_s
    assert warm["trace"] == 0 and warm["compile"] == 0
    summary = netsim.run_plan(plan).profile.summary()
    assert summary["trace_s"] == 0 and summary["compile_s"] == 0


def test_jit_seconds_count_nested_spans_once():
    """A jit traced inside another's trace is counted once, as the outer's,
    and the persistent-cache load inside a backend compile goes to
    ``cache_load``.  Recorded on a thread of its own, whose span records no
    other test's compile can swallow."""
    def record():
        base = time.time() - 100.0
        span = jax.monitoring.record_event_time_span
        trace = "/jax/core/compile/jaxpr_trace_duration"
        span(trace, base + 0.1, base + 0.4)           # inner
        span(trace, base + 0.5, base + 0.7)           # inner
        span(trace, base + 0.0, base + 1.0)           # outer
        span(trace, base + 2.0, base + 2.5)           # next, top level
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        span("/jax/core/compile/backend_compile_duration",
             base + 3.0, base + 4.0)

    w = counters.CounterWatch()
    t = threading.Thread(target=record)
    t.start()
    t.join()
    got = w.jit_s
    assert got["trace"] == pytest.approx(1.5)
    assert got["compile"] == pytest.approx(0.75)
    assert got["cache_load"] == pytest.approx(0.25)
    assert got["lower"] == 0.0


def test_span_times_its_block_and_survives_an_exception():
    with counters.span("test.span", k=1) as sp:
        time.sleep(0.01)
    assert sp.seconds >= 0.01
    with pytest.raises(RuntimeError):
        with counters.span("test.span") as sp2:
            raise RuntimeError("boom")
    assert sp2.seconds >= 0.0


def test_span_log_keeps_finished_spans_in_order():
    """`recent_spans` holds each finished span's name, args, seconds and
    jit seconds, numbered in the order the spans finished; a `run_plan`
    call logs its prepare span first, then its groups' three phases."""
    with counters.span("test.outer"):
        with counters.span("test.inner", k=1):
            pass
    *_, inner, outer = counters.recent_spans()
    assert (inner.name, inner.args, outer.name) == (
        "test.inner", {"k": 1}, "test.outer")
    assert outer.seq == inner.seq + 1
    assert outer.seconds >= inner.seconds >= 0.0
    assert set(outer.jit_s) == set(counters.JIT_KINDS)

    pr = netsim.run_plan(_plan(0.0421))
    log = counters.recent_spans()
    n = 1 + 3 * len(pr.profile.groups)
    assert [sp.name for sp in log[-n:]] == ["run_plan.prepare"] + [
        p for _ in pr.profile.groups for p in PHASES[1:]]
    devices = [sp for sp in log[-n:] if sp.name == "run_plan.device"]
    assert [sp.seconds for sp in devices] == [g.wall_s
                                             for g in pr.profile.groups]
    assert counters.SPAN_LOG >= 1000
