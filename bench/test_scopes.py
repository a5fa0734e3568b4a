"""Checks of the scope-aware trace reduction (scopes.py): stage times inside
the outer loop, idle time by `run_plan` phase, the HLO op_name table; of the
window's phases and set-up's jit seconds from the span log (phases.py); and
of the readers of the metrics built on them, including on a program that
has neither scopes nor a span log.

    PYTHONPATH=src python -m pytest bench
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "src"), str(HERE)]

import devtrace  # noqa: E402
import phases  # noqa: E402
import scopes  # noqa: E402

P = "jit(_run_sweep)/vmap()/while/body/closed_call/"


def metric(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def scoped_trace() -> tuple[devtrace.Trace, scopes.Scopes]:
    # window [0, 1000); one group's outer loop [100, 600) holding two scan
    # steps; a set-up op before it and a postprocess op after it
    ops = [("%fusion.1", 20, 30, P[:-12] + "broadcast"),       # outside
           ("%while.5", 100, 500, ""),                        # outer loop
           ("%fusion.2", 110, 40, P + "tick.links/add"),
           ("%closed_call.3 " + devtrace.KERNEL_MATCH, 150, 10,
            P + "tick.cc_update/pallas_call"),
           ("%copy.4", 160, 10, P + "tick.cc_update/cc.pack/pad"),
           ("%fusion.6", 170, 20, P + "chunk.capture/reduce_sum"),
           ("%fusion.7", 190, 10, P + "add"),                  # unscoped
           ("%fusion.2", 300, 40, P + "tick.links/add"),
           ("%closed_call.3 " + devtrace.KERNEL_MATCH, 340, 10,
            P + "tick.cc_update/pallas_call"),
           ("%fusion.8", 350, 20, P + "tick.accounting/scatter"),
           ("%fusion.9", 700, 50, "jit(dynamic_slice)/dynamic_slice")]
    host = [(devtrace.WINDOW_SPAN, 0, 1000),
            ("run_plan.prepare", 0, 20), ("run_plan.stack", 50, 40),
            ("run_plan.device", 90, 610), ("run_plan.postprocess", 700, 290),
            ("PjitFunction(_run_sweep)", 95, 10)]
    tr = devtrace.Trace(
        ops={"/device:TPU:0": [(n, s, d) for n, s, d, _ in ops]},
        host=host, window=(0, 1000))
    return tr, {"/device:TPU:0": [sc for *_, sc in ops]}


def test_loop_ops_are_the_leaves_inside_the_outer_loop():
    tr, sc = scoped_trace()
    ops = scopes.loop_ops(tr, sc)
    assert [o[0] for o in ops][:2] == ["%fusion.2", "%closed_call.3 "
                                       + devtrace.KERNEL_MATCH]
    assert len(ops) == 8          # not the loop itself, nor ops outside it
    assert ops[0][3] == P + "tick.links/add"
    assert [o[:3] for o in scopes.loop_ops(tr)] == [o[:3] for o in ops]


def test_stage_times_sum_to_the_loop_and_count_unscoped():
    tr, sc = scoped_trace()
    times = scopes.stage_times(tr, sc)
    assert times == pytest.approx({"tick.links": 80e-9,
                                   "tick.cc_update": 30e-9,
                                   "tick.accounting": 20e-9,
                                   "chunk.capture": 20e-9,
                                   "unscoped": 10e-9})
    shares = {s: scopes.stage_share(tr, sc, s) for s in times}
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares["unscoped"] == pytest.approx(100 * 10 / 160)
    cc = scopes.stage_times(tr, sc, prefixes=("cc.",))
    assert cc["cc.pack"] == pytest.approx(10e-9)


def test_idle_by_span_goes_to_the_innermost_run_plan_phase():
    # busy [20,50) [100,600) (the loop op) [700,750); each gap goes to the
    # phase covering its middle
    idle = dict(scopes.idle_by_span(scoped_trace()[0]))
    assert idle == pytest.approx({"run_plan.prepare": 20e-9,
                                  "run_plan.stack": 50e-9,
                                  "run_plan.device": 100e-9,
                                  "run_plan.postprocess": 250e-9})


class FakeSpan:
    def __init__(self, seq, name, seconds, jit=0.0):
        self.seq, self.name, self.seconds = seq, name, seconds
        self.args = {}
        self.jit_s = {"trace": jit, "lower": 0.0, "compile": 2 * jit,
                      "cache_load": 0.0}


def span_log(*calls, first_seq=0):
    """A log of plan calls, each ``(prepare_s, [(stack_s, wall_s,
    postprocess_s) per group], jit)``."""
    log = []
    for prepare, groups, jit in calls:
        log.append(FakeSpan(first_seq + len(log), "run_plan.prepare",
                            prepare, jit))
        for stack, wall, post in groups:
            for name, secs in (("stack", stack), ("device", wall),
                               ("postprocess", post)):
                log.append(FakeSpan(first_seq + len(log), "run_plan." + name,
                                    secs, jit))
    return log


WINDOW_CALL = {"wall_s": 4.0, "groups": [
    {"wall_s": 1.8, "n_ticks": 2}, {"wall_s": 1.7, "n_ticks": 2}]}
LOG = span_log((0.5, [(0.1, 9.0, 0.3)], 1.0),                  # warm call
               (0.01, [(0.02, 1.8, 0.1), (0.02, 1.7, 0.1)], 0.0),
               (0.01, [(0.02, 1.9, 0.1)], 0.0))                # traced cut


def test_window_phases_are_the_calls_whose_device_spans_match():
    tot = phases.window_phases([WINDOW_CALL], LOG)
    assert tot == pytest.approx({"prepare": 0.01, "stack": 0.04,
                                 "device": 3.5, "postprocess": 0.2})
    # a call the log no longer holds reads nothing
    lost = {"wall_s": 4.0, "groups": [{"wall_s": 1.75, "n_ticks": 2}]}
    assert phases.window_phases([WINDOW_CALL, lost], LOG) is None


def test_first_call_jit_is_the_warm_call_by_phase():
    jit = phases.first_call_jit(LOG)
    assert set(jit) == {"prepare", "stack", "device", "postprocess"}
    assert sum(sum(p.values()) for p in jit.values()) == pytest.approx(12.0)
    # a log that has dropped the process's first span reads nothing
    assert phases.first_call_jit(LOG[1:]) is None


def test_a_program_without_scopes_or_span_log_reads_nothing(monkeypatch):
    tr, _ = scoped_trace()
    scopes.remember(tr, {})
    assert scopes.stage_share(tr, {}, "tick.links") is None
    assert set(scopes.stage_times(tr, {})) == {"unscoped"}
    monkeypatch.setattr(phases, "_log", lambda: None)
    ctx = {"trace": tr, "kernel_calls_per_device": 2, "calls": [WINDOW_CALL]}
    for name in ("engine.links_pct", "engine.accounting_pct",
                 "engine.cc_update_pct", "experiment.postprocess_pct",
                 "setup.jit_s"):
        assert metric(name)(ctx) is None, name
    # counting ops needs no scope
    assert metric("engine.ops_per_tick")(ctx) == 4.0


def test_metric_readers_on_a_scoped_run(monkeypatch):
    tr, sc = scoped_trace()
    scopes.remember(tr, sc)
    monkeypatch.setattr(phases, "_log", lambda: LOG)
    ctx = {"trace": tr, "kernel_calls_per_device": 2, "calls": [WINDOW_CALL]}
    assert metric("engine.links_pct")(ctx) == pytest.approx(50.0)
    assert metric("engine.cc_update_pct")(ctx) == pytest.approx(18.75)
    assert metric("engine.accounting_pct")(ctx) == pytest.approx(12.5)
    assert metric("experiment.postprocess_pct")(ctx) == pytest.approx(5.0)
    assert metric("setup.jit_s")(ctx) == pytest.approx(12.0)


def test_readers_without_a_trace_read_nothing():
    ctx = {"trace": None, "kernel_calls_per_device": 0, "calls": []}
    for name in ("engine.links_pct", "engine.ops_per_tick",
                 "experiment.postprocess_pct"):
        assert metric(name)(ctx) is None, name


def test_traced_plan_is_run_py_s_cut():
    cut = scopes.traced_plan(["--workload", "dumbbell_reno.sweep",
                              "--seed", "4294970001", "--trace", "1"])
    assert cut is not None and cut.name == "cut"
    assert scopes.traced_plan(["-q"]) is None


HLO = """HloModule jit__run_sweep, entry_computation_layout={()->f32[4]}

%fused_computation.2 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="a/tick.links/add"}
}

%fused_computation.3 (param_0.1: f32[4]) -> (f32[4], f32[4]) {
  %param_0.1 = f32[4]{0} parameter(0)
  %neg.1 = f32[4]{0} negate(%param_0.1), metadata={op_name="a/tick.phase/neg"}
  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}) tuple(%neg.1, %neg.1)
}

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.2, metadata={op_name="a/tick.inject/mul"}
  %fusion.3 = (f32[4]{0}, f32[4]{0}) fusion(%p), kind=kLoop, calls=%fused_computation.3, metadata={op_name="a/tick.feedback/sub"}
  ROOT %closed_call.4 = f32[4]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="a/tick.cc_update/pallas_call"}
}
"""


def test_hlo_scopes_take_the_fusion_root():
    table = scopes.hlo_scopes(HLO)
    assert table["fusion.2"] == "a/tick.links/add"      # root's op_name
    assert table["fusion.3"] == "a/tick.feedback/sub"   # tuple root: own
    assert table["closed_call.4"] == "a/tick.cc_update/pallas_call"
    tr = devtrace.Trace(ops={"/device:TPU:0": [("%fusion.2 ...", 0, 1),
                                               ("%closed_call.4", 1, 1),
                                               ("%copy.9", 2, 1)]},
                        host=[], window=(0, 3))
    sc, conflicts = scopes.scopes_from_hlo(tr, [HLO, HLO])
    assert conflicts == 0
    assert sc["/device:TPU:0"] == [
        "a/tick.links/add", "a/tick.cc_update/pallas_call", ""]


def test_hlo_scopes_of_a_compiled_module():
    """The table holds the scopes of a real compiled module's text."""
    import jax
    import jax.numpy as jnp

    def f(x):
        def body(c, _):
            with jax.named_scope("tick.links"):
                c = jnp.sin(c) * 2.0
            with jax.named_scope("tick.accounting"):
                c = c + jnp.cumsum(c)
            return c, None
        return jax.lax.scan(body, x, None, length=3)[0]

    text = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
    stages = {scopes.stage_of(s) for s in scopes.hlo_scopes(text).values()}
    assert {"tick.links", "tick.accounting"} <= stages


def test_recorded_slice_has_no_scopes_and_412_loop_ops():
    rec = json.loads((HERE / "testdata" / "v5e_trace_slice.json").read_text())
    tr = devtrace.Trace(ops={k: [tuple(e) for e in v]
                             for k, v in rec["ops"].items()},
                        host=[tuple(e) for e in rec["host"]],
                        window=tuple(rec["window"]))
    assert len(scopes.loop_ops(tr)) == 412
    assert scopes.stage_share(tr, None, "tick.links") is None


def test_hlo_scopes_go_by_the_group_whose_loop_holds_the_op():
    """Two group programs that give one instruction name two scopes: an op
    inside the k-th outer loop takes the k-th program's."""
    second = HLO.replace("a/tick.links/add", "a/tick.phase/add")
    ops = [("%while.1", 0, 10), ("%fusion.2", 1, 2),
           ("%while.1", 20, 10), ("%fusion.2", 21, 2)]
    tr = devtrace.Trace(ops={"/device:TPU:0": ops}, host=[], window=(0, 30))
    # the fusion and its root both differ
    sc, conflicts = scopes.scopes_from_hlo(tr, [HLO, second])
    assert conflicts == 2
    assert sc["/device:TPU:0"][1] == "a/tick.links/add"
    assert sc["/device:TPU:0"][3] == "a/tick.phase/add"
