"""IR lint: walk the *traced* sweep program and prove lowering invariants
without executing anything.

`engine.trace_sweep` gives us the ``jax.stages.Traced`` for a (cfg, sweep)
pair — same jaxpr cache as ``.lower()``/execution, so the program we lint
is byte-for-byte the program a later ``simulate_sweep`` runs.  The walk
recurses through every sub-jaxpr (scan bodies, pjit calls, cond branches,
the pallas_call kernel body) and checks, per compile group:

* the fused ``mltcp_cc_tick`` ``pallas_call`` is present exactly when the
  config statically entitles it (``kernel_expectation``) — the static
  proof that the PR-3 silent-fallback bug stays dead;
* no value or ``convert_element_type`` lands in float64 anywhere in the
  program (bit-stable f32 pipeline);
* no host callbacks / debug prints in the hot path;
* no non-whitelisted ``while``/``cond`` inside the tick-scan body.

We lint at the jaxpr level rather than StableHLO on purpose: on the CPU
backend (interpret mode, `ops.resolve_interpret`) the Pallas custom call
never reaches HLO (interpret mode lowers to plain HLO ops), but the ``pallas_call`` primitive is always
visible in the jaxpr, so the same proof holds on CPU CI and on device.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Optional

import jax
from jax.extend import core as jax_core

from repro.analysis.findings import Finding, make_finding

__all__ = ["kernel_expectation", "lint_closed_jaxpr", "lint_sweep",
           "HOST_CALLBACK_PRIMITIVES"]

# Primitives that round-trip through the host.  Any of these inside the
# sweep program stalls the device once per tick.
HOST_CALLBACK_PRIMITIVES = frozenset({
    "debug_callback", "pure_callback", "io_callback", "outside_call",
    "infeed", "outfeed", "debug_print", "host_local_array_to_global_array",
})

# Control-flow primitives that must not appear inside the tick-scan body
# unless whitelisted by name.
_NESTED_CONTROL = frozenset({"while", "cond"})

# Equation params whose values carry sub-jaxprs we must recurse into.
_F64 = "float64"


def kernel_expectation(cfg, sweep) -> str:
    """What the lowering *must* contain: "fused" | "fallback" | "off".

    Mirrors the static fallback decision in ``kernels.ops.mltcp_cc_tick``
    (and nothing else — that's the point: if ops.py and this function ever
    disagree, the kernel-missing / kernel-unexpected rules catch it on the
    next lint run).
    """
    if not cfg.use_pallas_kernel:
        return "off"
    if sweep.static_job_factors is not None:
        # Static-baseline factors ride in as operands; favoritism/F moot.
        return "fused"
    proto = cfg.protocol
    if proto.favoritism != "largest_data_sent" or proto.f_spec != "linear":
        return "fallback"
    return "fused"


@dataclasses.dataclass
class _WalkState:
    pallas_calls: int = 0
    f64_ops: int = 0
    eqns: int = 0
    findings: list = dataclasses.field(default_factory=list)


def _sub_jaxprs(params) -> Iterable:
    """Yield every (Closed)Jaxpr reachable from an eqn's params."""
    for val in params.values():
        stack = [val]
        while stack:
            v = stack.pop()
            if isinstance(v, (jax_core.ClosedJaxpr, jax_core.Jaxpr)):
                yield v
            elif isinstance(v, (list, tuple)):
                stack.extend(v)


def _aval_dtype(v) -> Optional[str]:
    aval = getattr(v, "aval", None)
    dtype = getattr(aval, "dtype", None)
    return None if dtype is None else str(dtype)


def _walk(jaxpr, state: _WalkState, label: str, whitelist: frozenset,
          in_scan: bool, in_kernel: bool) -> None:
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        for c in jaxpr.consts:
            if str(getattr(c, "dtype", "")) == _F64:
                state.f64_ops += 1
                state.findings.append(make_finding(
                    "ir/f64-promotion", label,
                    f"float64 constant {getattr(c, 'shape', ())} captured "
                    f"by the program"))
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        state.eqns += 1
        name = eqn.primitive.name

        for v in eqn.outvars:
            if _aval_dtype(v) == _F64:
                state.f64_ops += 1
                state.findings.append(make_finding(
                    "ir/f64-promotion", label,
                    f"`{name}` produces a float64 value "
                    f"{getattr(v.aval, 'shape', ())}"
                    + (" inside the tick scan" if in_scan else "")))
                break   # one finding per eqn is enough
        if (name == "convert_element_type"
                and str(eqn.params.get("new_dtype", "")) == _F64):
            # outvar check above already fired; this branch only matters
            # for exotic converts whose outvar aval lies (shouldn't
            # happen, kept as a belt-and-braces count)
            pass

        if name in HOST_CALLBACK_PRIMITIVES:
            state.findings.append(make_finding(
                "ir/host-callback", label,
                f"host callback primitive `{name}`"
                + (" inside the tick scan" if in_scan else "")))

        if (in_scan and not in_kernel and name in _NESTED_CONTROL
                and name not in whitelist):
            state.findings.append(make_finding(
                "ir/nested-control", label,
                f"`{name}` inside the tick-scan body (whitelist via "
                f"the lint whitelist= option if intentional)"))

        if name == "pallas_call":
            state.pallas_calls += 1

        sub_in_scan = in_scan or name == "scan"
        sub_in_kernel = in_kernel or name == "pallas_call"
        for sub in _sub_jaxprs(eqn.params):
            _walk(sub, state, label, whitelist, sub_in_scan, sub_in_kernel)


def lint_closed_jaxpr(closed_jaxpr, *, label: str = "<jaxpr>",
                      expectation: str = "off",
                      whitelist: frozenset = frozenset(),
                      ) -> tuple[list[Finding], dict]:
    """Lint one ClosedJaxpr against `expectation` ("fused"/"fallback"/"off").

    Returns (findings, facts); facts = {"pallas_calls", "f64_ops", "eqns"}.
    """
    state = _WalkState()
    _walk(closed_jaxpr, state, label, frozenset(whitelist),
          in_scan=False, in_kernel=False)

    if expectation == "fused" and state.pallas_calls == 0:
        state.findings.append(make_finding(
            "ir/kernel-missing", label,
            "use_pallas_kernel config lowered with no pallas_call in the "
            "program — the CC tick is running the jnp oracle"))
    elif expectation in ("off", "fallback") and state.pallas_calls > 0:
        state.findings.append(make_finding(
            "ir/kernel-unexpected", label,
            f"{state.pallas_calls} pallas_call(s) in a lowering that "
            f"expected the jnp oracle (expectation={expectation})"))
    if expectation == "fallback":
        state.findings.append(make_finding(
            "ir/kernel-fallback", label,
            "config requests use_pallas_kernel but statically forces the "
            "jnp-oracle fallback (non-default favoritism or non-linear F "
            "without static factors); drop the flag or fix the config"))

    facts = {"pallas_calls": state.pallas_calls, "f64_ops": state.f64_ops,
             "eqns": state.eqns}
    return state.findings, facts


def lint_sweep(cfg, sweep, *, label: str,
               whitelist: frozenset = frozenset(),
               ) -> tuple[list[Finding], dict]:
    """Trace (never execute) the sweep program for (cfg, sweep) and lint it.

    Tracing shares the jit cache with execution, so calling this before a
    run costs one trace total, and calling it after a run costs zero.
    """
    from repro.netsim import engine

    traced = engine.trace_sweep(cfg, sweep)
    expectation = kernel_expectation(cfg, sweep)
    findings, facts = lint_closed_jaxpr(
        traced.jaxpr, label=label, expectation=expectation,
        whitelist=whitelist)
    facts["expectation"] = expectation
    return findings, facts
