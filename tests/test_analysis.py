"""Tests for the three-layer static verifier (repro.analysis).

Each lint rule is demonstrated to fire on a deliberately-broken fixture —
an injected f64 upcast in a scan body, a config that statically forces the
kernel->oracle fallback, a plan axis that needlessly splits compile groups,
source fixtures for every AST rule — and the real repo programs (reno /
cubic / dcqcn lowerings, armed telemetry, the benchmark plans' structure)
are asserted clean.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import netsim
from repro.analysis import (RULES, analyze_plan, kernel_expectation,
                            lint_closed_jaxpr, lint_plan, lint_sources,
                            lint_sweep, predict_compile_groups)
from repro.core import Algo, CCParams, MLTCPConfig, Variant
from repro.netsim import counters, engine

DT = 2e-5


def _proto(algo=Algo.RENO, variant=Variant.WI, **kw):
    return MLTCPConfig(cc=CCParams(algo=int(algo), variant=int(variant),
                                   tick_dt=DT, rtt=100e-6),
                       slope=1.75, intercept=0.25, **kw)


def _cfg(n_jobs=2, sim_time=0.3, seed=3, **kw):
    topo = netsim.dumbbell(n_jobs, sockets_per_job=2)
    jobs = netsim.JobSpec.simple([0.0075] * n_jobs, [25e6] * n_jobs)
    return netsim.SimConfig(topo=topo, jobs=jobs,
                            protocol=kw.pop("protocol", _proto()),
                            sim_time=sim_time, dt=DT, seed=seed, **kw)


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# IR lint: real lowerings are clean, broken fixtures fire
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", [Algo.RENO, Algo.CUBIC, Algo.DCQCN])
def test_real_lowerings_are_clean(algo):
    cfg = _cfg(protocol=_proto(algo=algo))
    findings, facts = lint_sweep(cfg, engine.make_sweep(cfg), label=str(algo))
    assert findings == []
    assert facts["expectation"] == "off"
    assert facts["pallas_calls"] == 0
    assert facts["f64_ops"] == 0
    assert facts["eqns"] > 0


def test_kernel_presence_statically_proven():
    cfg = _cfg(use_pallas_kernel=True)
    sweep = engine.make_sweep(cfg)
    assert kernel_expectation(cfg, sweep) == "fused"
    findings, facts = lint_sweep(cfg, sweep, label="fused")
    assert findings == []
    assert facts["pallas_calls"] >= 1


def test_kernel_fallback_config_fires():
    """Non-linear F without static factors is outside the kernel's
    specialization: requesting use_pallas_kernel must be flagged."""
    cfg = _cfg(use_pallas_kernel=True, protocol=_proto(f_spec="F3"))
    sweep = engine.make_sweep(cfg)
    assert kernel_expectation(cfg, sweep) == "fallback"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")      # ops.py's loud fallback warning
        findings, facts = lint_sweep(cfg, sweep, label="fb")
    assert "ir/kernel-fallback" in _rules(findings)
    assert facts["pallas_calls"] == 0        # and it really lowered unfused


# (protocol options, Static factors, use_pallas_kernel, reason, lowering)
_OTHER_FAV = {"favoritism": "smallest_data_remaining"}
ELIGIBILITY = {
    "default": ({}, False, True, None, "fused"),
    "favoritism": (_OTHER_FAV, False, True,
                   "favoritism='smallest_data_remaining'", "fallback"),
    "f_spec": ({"f_spec": "F3"}, False, True, "f_spec='F3'", "fallback"),
    "default+static": ({}, True, True, None, "fused"),
    "favoritism+static": (_OTHER_FAV, True, True, None, "fused"),
    "f_spec+static": ({"f_spec": "F3"}, True, True, None, "fused"),
    "kernel_off": ({}, False, False, None, "off"),
    "kernel_off+favoritism": (_OTHER_FAV, False, False,
                              "favoritism='smallest_data_remaining'", "off"),
}


@pytest.mark.parametrize("case", sorted(ELIGIBILITY))
def test_kernel_eligibility_has_one_owner(case):
    """`ops.cc_tick_fallback_reason` decides the fused kernel's eligibility;
    the group split and the IR lint's expectation agree with it."""
    from repro.kernels import ops
    from repro.netsim import experiment

    proto_kw, factors, kernel, reason, lowering = ELIGIBILITY[case]
    cfg = _cfg(use_pallas_kernel=kernel, protocol=_proto(**proto_kw))
    if factors:
        cfg = dataclasses.replace(cfg,
                                  static_job_factors=np.array([1.0, 0.5]))
    got = ops.cc_tick_fallback_reason(cfg.protocol, factors)
    assert got == reason
    assert kernel_expectation(cfg, engine.make_sweep(cfg)) == lowering
    assert lowering == ("off" if not kernel
                        else "fused" if got is None else "fallback")
    bare = ops.cc_tick_fallback_reason(cfg.protocol, False)
    assert experiment._factors_need_split(cfg) == (kernel
                                                   and bare is not None)


def test_armed_telemetry_lowering_clean():
    spec = netsim.TelemetrySpec(probes=("flow_cwnd", "link_queue"),
                                stride=16)
    cfg = _cfg(telemetry=spec)
    findings, facts = lint_sweep(cfg, engine.make_sweep(cfg), label="armed")
    assert findings == []
    assert facts["f64_ops"] == 0


def test_f64_upcast_in_scan_body_fires():
    """A convert to float64 injected into a scan body must be caught (the
    x64 context synthesizes what jax_enable_x64 leakage would produce)."""
    def body(c, _):
        return c + jnp.float64(1.0), None

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: jax.lax.scan(body, x, None, length=3))(
                jnp.zeros((), jnp.float64))
    findings, facts = lint_closed_jaxpr(jaxpr, label="f64-fixture")
    assert "ir/f64-promotion" in _rules(findings)
    assert facts["f64_ops"] > 0


def test_host_callback_in_scan_fires():
    def body(c, _):
        jax.debug.print("tick {}", c)
        return c + 1.0, None

    jaxpr = jax.make_jaxpr(
        lambda x: jax.lax.scan(body, x, None, length=2))(jnp.float32(0.0))
    findings, _ = lint_closed_jaxpr(jaxpr, label="cb-fixture")
    assert "ir/host-callback" in _rules(findings)


def test_nested_control_fires_and_whitelists():
    def body(c, _):
        c = jax.lax.cond(c > 0, lambda v: v + 1.0, lambda v: v - 1.0, c)
        return c, None

    jaxpr = jax.make_jaxpr(
        lambda x: jax.lax.scan(body, x, None, length=2))(jnp.float32(0.0))
    findings, _ = lint_closed_jaxpr(jaxpr, label="cond-fixture")
    assert "ir/nested-control" in _rules(findings)
    ok, _ = lint_closed_jaxpr(jaxpr, label="cond-ok",
                              whitelist=frozenset({"cond"}))
    assert "ir/nested-control" not in _rules(ok)


def test_kernel_unexpected_fires():
    """A pallas_call in a lowering that expected the oracle is flagged."""
    cfg = _cfg(use_pallas_kernel=True)
    traced = engine.trace_sweep(cfg, engine.make_sweep(cfg))
    findings, _ = lint_closed_jaxpr(traced.jaxpr, label="unexpected",
                                    expectation="off")
    assert "ir/kernel-unexpected" in _rules(findings)


# ---------------------------------------------------------------------------
# Plan lint: split explainers, avoidable splits, prediction == execution
# ---------------------------------------------------------------------------

def _variant_plan(**cfg_kw):
    def build(pt):
        var = {"OFF": Variant.OFF, "WI": Variant.WI}[pt["variant"]]
        return _cfg(protocol=_proto(variant=var), **cfg_kw)
    return netsim.Plan(name="variant-plan", build=build,
                       axes=(netsim.Axis("variant", ("OFF", "WI")),
                             netsim.Axis("seed", (3,))))


def test_group_split_explainer_names_the_field():
    findings, facts = lint_plan(_variant_plan(), label="vp")
    assert facts["groups"] == 2
    splits = [f for f in findings if f.rule == "plan/group-split"]
    assert len(splits) == 1
    assert "protocol.cc.variant" in splits[0].message
    # a structural split is not avoidable
    assert "plan/avoidable-split" not in _rules(findings)
    assert facts["wasted_traces_estimate"] == 0


def test_avoidable_split_fires_on_value_axis():
    """An axis over buffer_bytes (a plain float the canonicalizer keeps
    static) needlessly splits groups — flagged with a wasted-trace count."""
    def build(pt):
        return _cfg(buffer_bytes=pt["bb"])
    plan = netsim.Plan(name="bb-plan", build=build,
                       axes=(netsim.Axis("bb", (2e6, 4e6)),
                             netsim.Axis("seed", (3,))))
    findings, facts = lint_plan(plan, label="bb")
    assert facts["groups"] == 2
    avoid = [f for f in findings if f.rule == "plan/avoidable-split"]
    assert len(avoid) == 1
    assert "buffer_bytes" in avoid[0].message
    assert facts["wasted_traces_estimate"] == 1


def test_prediction_matches_execution():
    plan = _variant_plan()
    predicted = predict_compile_groups(plan)
    pr = netsim.run_plan(plan, shard=False)
    assert predicted == pr.n_compile_groups == 2


# ---------------------------------------------------------------------------
# Source lint fixtures (lint_sources): every AST rule fires, pragmas work
# ---------------------------------------------------------------------------

_SCANNED = """
import jax
import numpy as np
import jax.numpy as jnp

def body(c, x):
{body}
    return c, None

def run(xs):
    return jax.lax.scan(body, jnp.float32(0.0), xs)
"""


def _scan_fixture(body_lines):
    src = _SCANNED.format(body="\n".join("    " + l for l in body_lines))
    return lint_sources({"fix/mod.py": src})


def test_np_in_scan_fires_and_pragma_suppresses():
    findings, facts = _scan_fixture(["c = np.sin(c)"])
    assert "src/np-in-scan" in _rules(findings)
    assert facts["scan_reachable"] >= 1
    ok, _ = _scan_fixture(["c = np.sin(c)  # lint: allow(np-in-scan)"])
    assert "src/np-in-scan" not in _rules(ok)


def test_stale_pragma_unknown_rule_fires():
    findings, facts = _scan_fixture(
        ["c = c + 1  # lint: allow(no-such-rule)"])
    stale = [f for f in findings if f.rule == "src/stale-pragma"]
    assert stale and "unknown rule" in stale[0].message
    assert facts["pragmas"] == 1


def test_stale_pragma_unused_suppression_fires():
    # the named rule exists but nothing fires on that line
    findings, _ = _scan_fixture(["c = c + 1  # lint: allow(np-in-scan)"])
    stale = [f for f in findings if f.rule == "src/stale-pragma"]
    assert stale and "outlived" in stale[0].message


def test_stale_pragma_quiet_when_suppression_is_live():
    findings, _ = _scan_fixture(
        ["c = np.sin(c)  # lint: allow(np-in-scan)"])
    assert "src/stale-pragma" not in _rules(findings)


def test_float_cast_on_traced_fires():
    findings, _ = _scan_fixture(["y = jnp.sum(c)", "c = c + float(y)"])
    assert "src/float-cast-traced" in _rules(findings)
    # casting a static python value stays legal
    ok, _ = _scan_fixture(["n = len(x)", "c = c + float(n)"])
    assert "src/float-cast-traced" not in _rules(ok)


def test_branch_on_traced_fires():
    findings, _ = _scan_fixture(["y = jnp.sum(c)",
                                 "if y > 0:",
                                 "    c = c + 1"])
    assert "src/branch-on-traced" in _rules(findings)
    # `is None` tests and static-attribute branches stay legal
    ok, _ = _scan_fixture(["y = jnp.sum(c)",
                           "if y is not None and y.ndim == 0:",
                           "    c = c + 1"])
    assert "src/branch-on-traced" not in _rules(ok)


def test_f64_literal_rules():
    # jnp.float64 fires anywhere, even outside scan-reachable code
    findings, _ = lint_sources({"fix/a.py": (
        "import jax.numpy as jnp\n"
        "def helper(x):\n"
        "    return jnp.float64(x)\n")})
    assert "src/f64-literal" in _rules(findings)
    # np.float64 is legal numpy-side plumbing when not scan-reachable...
    ok, _ = lint_sources({"fix/b.py": (
        "import numpy as np\n"
        "def plumbing(x):\n"
        "    return np.float64(x)\n")})
    assert "src/f64-literal" not in _rules(ok)
    # ...but fires inside a scan-reachable function
    findings, _ = _scan_fixture(["c = c + np.float64(1.0)"])
    assert "src/f64-literal" in _rules(findings)


def test_unit_suffix_conflict_fires():
    findings, _ = lint_sources({"fix/u.py": (
        "def f(q_bytes, delay_s, rate_bps, n_ticks):\n"
        "    total = q_bytes + delay_s\n"
        "    return total\n")})
    assert "src/unit-suffix" in _rules(findings)
    ok, _ = lint_sources({"fix/u2.py": (
        "def f(q_bytes, extra_bytes, delay_s, rate_bps):\n"
        "    total = q_bytes + extra_bytes\n"
        "    secs = q_bytes / rate_bps + delay_s   # divide converts\n"
        "    return total, secs\n")})
    assert "src/unit-suffix" not in _rules(ok)


def test_indirect_scan_body_via_partial_and_alias():
    """Reachability follows `partial(...)` bindings and function-valued
    reassignments (the engine's tick_fn pattern)."""
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from functools import partial\n"
        "def helper(c):\n"
        "    return np.cos(c)\n"
        "def tick(scale, c, x):\n"
        "    fn = helper\n"
        "    return fn(c) * scale, None\n"
        "def run(xs):\n"
        "    body = partial(tick, 2.0)\n"
        "    return jax.lax.scan(body, 0.0, xs)\n")
    findings, facts = lint_sources({"fix/ind.py": src})
    assert "src/np-in-scan" in _rules(findings)
    assert facts["scan_reachable"] >= 2      # tick and helper


# ---------------------------------------------------------------------------
# Counters + end-to-end runner
# ---------------------------------------------------------------------------

def test_counters_watch_counts_traces():
    cfg = _cfg(seed=101, sim_time=0.32)      # unique shape-free signature
    sweep = engine.make_sweep(cfg)
    with counters.watch() as w:
        engine.trace_sweep(cfg, sweep)
    first = w.traces
    with counters.watch() as w2:
        engine.trace_sweep(cfg, sweep)       # cache hit: no new trace
    assert first <= 1
    assert w2.traces == 0
    assert isinstance(w2.fallbacks, int)


def test_analyze_plan_end_to_end():
    report = analyze_plan("vp", _variant_plan())
    assert report.ok()
    proof = report.proofs["vp"]
    assert proof["groups_predicted"] == 2
    assert proof["groups_traced"] <= 2       # warm process may cache-hit
    assert proof["f64_ops"] == 0
    assert proof["kernel_fallbacks"] == 0
    rendered = report.render(verbose=True)
    assert "PASS" in rendered and "PROOF" in rendered


def test_rule_catalog_is_complete():
    expected = {
        "ir/kernel-missing", "ir/kernel-fallback", "ir/kernel-unexpected",
        "ir/f64-promotion", "ir/host-callback", "ir/nested-control",
        "plan/group-split", "plan/avoidable-split", "plan/group-mismatch",
        "plan/retrace",
        "src/np-in-scan", "src/float-cast-traced", "src/branch-on-traced",
        "src/f64-literal", "src/unit-suffix", "src/stale-pragma",
        "kernel/dyn-not-smem", "kernel/dyn-written", "kernel/state-not-vmem",
        "kernel/block-misaligned", "kernel/grid-remainder",
        "kernel/operand-mismatch", "kernel/f64-in-body",
        "kernel/gather-scatter", "kernel/nested-control",
        "kernel/vmem-budget",
        "budget/drift", "budget/missing-baseline", "budget/stale-baseline",
        "budget/env-mismatch", "budget/unknown-dtype",
    }
    assert set(RULES) == expected
    for r in RULES.values():
        assert r.summary and r.rationale


def test_severity_profiles():
    from repro.analysis import severity_for

    # ci promotes baseline-hygiene warnings; bench = declared defaults;
    # notebook demotes errors to advisory warnings unless overridden
    assert severity_for("src/stale-pragma") == "warning"
    assert severity_for("src/stale-pragma", "ci") == "error"
    assert severity_for("budget/missing-baseline", "ci") == "error"
    assert severity_for("budget/drift", "bench") == "error"
    assert severity_for("budget/drift", "notebook") == "warning"
    assert severity_for("kernel/dyn-not-smem", "ci") == "error"


def test_report_profile_gates_ok():
    from repro.analysis import AnalysisReport, make_finding

    f = make_finding("src/stale-pragma", "x.py:1", "stale")
    ci = AnalysisReport(profile="ci")
    ci.extend([f])
    assert not ci.ok() and ci.errors()
    nb = AnalysisReport(profile="notebook")
    nb.extend([f])
    assert nb.ok() and not nb.errors()
    js = ci.to_json()
    assert js["profile"] == "ci" and js["findings"][0]["severity"] == "error"
