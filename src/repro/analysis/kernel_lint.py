"""Kernel lint (layer 4): prove the Pallas CC-tick kernel *body*'s
invariants, per (algo, variant, factors) specialization.

The IR lint (layer 1) proves the ``pallas_call`` is present exactly when
the config entitles it; this layer walks *into* that equation — its
``grid_mapping`` (operand memory spaces, block shapes, grid) and its body
jaxpr — and checks the claims the perf story rests on:

* the `DynamicParams` operand is an f32[1, NDYN] **SMEM** ref and the body
  never writes it (``kernel/dyn-not-smem`` / ``kernel/dyn-written``);
* every flow-state operand is a default/VMEM ref with the
  ``(min(SUBLANES, rows), LANES)`` block tile ops.py packs
  (``kernel/state-not-vmem`` / ``kernel/block-misaligned``);
* the grid covers exactly ``rows`` with no remainder step
  (``kernel/grid-remainder``), and operand/result counts match the
  specialization (``kernel/operand-mismatch``);
* the body is straight-line elementwise f32: no f64 values, no
  gather/scatter, no while/cond/scan (``kernel/f64-in-body``,
  ``kernel/gather-scatter``, ``kernel/nested-control``);
* a static VMEM-bytes estimate per grid step (all in/out blocks, x2 for
  double buffering) stays under a configurable ceiling
  (``kernel/vmem-budget``).

The expectation comes from `kernels.ops.kernel_layout` — the same padding
math the dispatch uses — and the kernel equation is located in the
*already-traced* sweep jaxpr (`engine.trace_sweep` shares the jit cache),
so the whole layer costs zero extra traces.  Under a vmapped sweep the
pallas batching rule prepends batch dims: the grid gains leading axes
and blocks gain squeezed leading dims — `_normalize` and `_tile` strip
both, so one expectation covers K=1 and K>1 programs.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Optional

import jax
from jax.extend import core as jax_core

from repro.analysis.findings import Finding, make_finding
from repro.kernels import mltcp_step as ms

__all__ = ["find_kernel_eqns", "lint_kernel_eqn", "lint_kernel",
           "DEFAULT_VMEM_CEILING_BYTES"]

# Per-grid-step VMEM ceiling for the static estimate.  The real kernel's
# working set is ~44 blocks x 8x128 x 4 B ~= 180 KiB; 4 MiB leaves room
# for growth while still catching a runaway block shape long before the
# ~16 MiB physical VMEM (and its double-buffering halves) would.
DEFAULT_VMEM_CEILING_BYTES = 4 * 1024 * 1024

# Primitives that break the elementwise one-pass property.
_GATHER_SCATTER = frozenset({
    "gather", "scatter", "scatter-add", "scatter_add", "scatter_mul",
    "scatter_min", "scatter_max", "dynamic_gather",
})
_CONTROL = frozenset({"while", "cond", "scan"})
_F64 = "float64"


def _sub_jaxprs(params) -> Iterable:
    for val in params.values():
        stack = [val]
        while stack:
            v = stack.pop()
            if isinstance(v, (jax_core.ClosedJaxpr, jax_core.Jaxpr)):
                yield v
            elif isinstance(v, (list, tuple)):
                stack.extend(v)


def find_kernel_eqns(jaxpr) -> list:
    """Every CC-tick ``pallas_call`` eqn reachable from a (Closed)Jaxpr,
    matched by the kernel body's function name (`ms.KERNEL_NAME`, read
    from the body jaxpr's debug info; vmap keeps it)."""
    out = []
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            debug = getattr(eqn.params["jaxpr"], "debug_info", None)
            if getattr(debug, "func_name", None) == ms.KERNEL_NAME:
                out.append(eqn)
        for sub in _sub_jaxprs(eqn.params):
            out.extend(find_kernel_eqns(sub))
    return out


def _tile(block_mapping) -> tuple:
    """The per-grid-step tile shape: the block's ref shape, with the batch
    dims a vmap prepends (squeezed out of the block) already gone."""
    return tuple(block_mapping.transformed_block_aval.shape)


def _space(block_mapping) -> str:
    """"smem" | "default" (ANY/VMEM) | other, from the transformed aval."""
    space = getattr(block_mapping.transformed_block_aval,
                    "memory_space", None)
    return "default" if space is None else str(space)


@dataclasses.dataclass
class _Normalized:
    grid: tuple                  # trailing (non-batch) grid dims
    n_batch_dims: int
    in_mappings: list            # BlockMapping per input operand
    out_mappings: list
    body: object                 # the body Jaxpr


def _normalize(eqn, expected: ms.KernelLayout) -> _Normalized:
    gm = eqn.params["grid_mapping"]
    n_batch = max(len(gm.grid) - len(expected.grid), 0)
    bms = list(gm.block_mappings)
    return _Normalized(
        grid=tuple(gm.grid[n_batch:]), n_batch_dims=n_batch,
        in_mappings=bms[:gm.num_inputs],
        out_mappings=bms[gm.num_inputs:gm.num_inputs + gm.num_outputs],
        body=eqn.params["jaxpr"])


def _walk_body(jaxpr, watched: frozenset, state: dict, label: str,
               findings: list) -> None:
    """Recurse the kernel body (and its pjit sub-jaxprs, threading which
    sub-invars alias the watched dyn ref) for f64 / gather-scatter /
    control-flow / dyn-write violations."""
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        for v in eqn.outvars:
            dtype = getattr(getattr(v, "aval", None), "dtype", None)
            if dtype is not None and str(dtype) == _F64:
                if not state["f64"]:
                    findings.append(make_finding(
                        "kernel/f64-in-body", label,
                        f"`{name}` produces a float64 value "
                        f"{getattr(v.aval, 'shape', ())} inside the "
                        f"kernel body"))
                state["f64"] += 1
                break
        if name in _GATHER_SCATTER:
            findings.append(make_finding(
                "kernel/gather-scatter", label,
                f"`{name}` inside the kernel body — every op must be "
                f"elementwise over the block tile"))
        if name in _CONTROL:
            findings.append(make_finding(
                "kernel/nested-control", label,
                f"`{name}` inside the kernel body — the specialization "
                f"is static, the body must be straight-line"))
        if (name == "swap" and eqn.invars
                and isinstance(eqn.invars[0], jax_core.Var)
                and eqn.invars[0] in watched):
            findings.append(make_finding(
                "kernel/dyn-written", label,
                "kernel body writes to the DynamicParams SMEM operand "
                "(read-only by contract)"))
        if name == "pjit":
            sub = eqn.params.get("jaxpr")
            if isinstance(sub, jax_core.ClosedJaxpr):
                # positional call: thread the watched-ref aliasing through
                sub_watched = frozenset(
                    sv for ov, sv in zip(eqn.invars, sub.jaxpr.invars)
                    if isinstance(ov, jax_core.Var) and ov in watched)
                _walk_body(sub, sub_watched, state, label, findings)
        else:
            for sub in _sub_jaxprs(eqn.params):
                _walk_body(sub, frozenset(), state, label, findings)


def lint_kernel_eqn(eqn, expected: ms.KernelLayout, *, label: str,
                    vmem_ceiling_bytes: int = DEFAULT_VMEM_CEILING_BYTES,
                    ) -> tuple[list[Finding], dict]:
    """Check one CC-tick pallas_call eqn against a specialization layout.

    Returns (findings, facts); facts = {"vmem_bytes_per_step",
    "body_eqns", "n_batch_dims"}.
    """
    findings: list[Finding] = []
    n = _normalize(eqn, expected)

    # --- operand/result counts mirror the specialization ----------------
    if (len(n.in_mappings) != expected.n_inputs
            or len(n.out_mappings) != expected.n_outputs):
        findings.append(make_finding(
            "kernel/operand-mismatch", label,
            f"{len(n.in_mappings)} inputs / {len(n.out_mappings)} outputs "
            f"!= specialization's {expected.n_inputs}/{expected.n_outputs} "
            f"(static_factors={expected.use_static_factors})"))

    # --- the dyn SMEM operand -------------------------------------------
    dyn_var = None
    if expected.dyn_index < len(n.in_mappings):
        dyn_bm = n.in_mappings[expected.dyn_index]
        dyn_shape = _tile(dyn_bm)
        if _space(dyn_bm) != "smem" or dyn_shape != expected.dyn_shape:
            findings.append(make_finding(
                "kernel/dyn-not-smem", label,
                f"DynamicParams operand is {_space(dyn_bm)}{dyn_shape}, "
                f"expected smem{expected.dyn_shape}"))
        else:
            body = (n.body.jaxpr if isinstance(n.body, jax_core.ClosedJaxpr)
                    else n.body)
            dyn_var = body.invars[expected.dyn_index]

    # --- flow-state refs: VMEM, aligned tiles ---------------------------
    vmem_bytes = 0
    state_mappings = (list(enumerate(n.in_mappings)) +
                      list(enumerate(n.out_mappings)))
    for i, bm in state_mappings:
        if bm in n.in_mappings and i == expected.dyn_index:
            continue                       # the SMEM scalars, checked above
        dims = _tile(bm)
        aval = bm.transformed_block_aval
        itemsize = getattr(getattr(aval, "dtype", None), "itemsize", 4)
        size = itemsize
        for d in dims:
            size *= d
        vmem_bytes += size
        kind = "in" if bm in n.in_mappings else "out"
        if _space(bm) not in ("default", "vmem", "ANY"):
            findings.append(make_finding(
                "kernel/state-not-vmem", label,
                f"state operand {kind}[{i}] lives in {_space(bm)} "
                f"(flow state must be VMEM-resident)"))
        if dims != expected.block:
            findings.append(make_finding(
                "kernel/block-misaligned", label,
                f"state operand {kind}[{i}] block {dims} != the "
                f"{expected.block} (SUBLANES, LANES) tile"))

    # --- grid covers rows exactly ---------------------------------------
    if n.grid != expected.grid or expected.rows % expected.block[0] != 0:
        findings.append(make_finding(
            "kernel/grid-remainder", label,
            f"grid {n.grid} (after {n.n_batch_dims} batch dim(s)) does "
            f"not cover rows={expected.rows} in {expected.block[0]}-row "
            f"blocks exactly (expected grid {expected.grid})"))

    # --- VMEM ceiling (x2: pipelined double buffering) ------------------
    est = 2 * vmem_bytes
    if est > vmem_ceiling_bytes:
        findings.append(make_finding(
            "kernel/vmem-budget", label,
            f"static VMEM estimate {est} B per grid step (2x {vmem_bytes} "
            f"B of blocks) exceeds the {vmem_ceiling_bytes} B ceiling"))

    # --- body: straight-line elementwise f32, dyn read-only -------------
    state = {"f64": 0}
    watched = frozenset() if dyn_var is None else frozenset({dyn_var})
    body_eqns = (n.body.jaxpr.eqns if isinstance(n.body, jax_core.ClosedJaxpr)
                 else n.body.eqns)
    _walk_body(n.body, watched, state, label, findings)

    facts = {"vmem_bytes_per_step": est, "body_eqns": len(body_eqns),
             "n_batch_dims": n.n_batch_dims}
    return findings, facts


def lint_kernel(cfg, sweep, *, label: str,
                vmem_ceiling_bytes: int = DEFAULT_VMEM_CEILING_BYTES,
                ) -> tuple[list[Finding], dict]:
    """Lint the CC-tick kernel body of one compile group's traced program.

    A no-op (empty findings, ``kernel_checked=False``) when the
    specialization does not expect the fused kernel — mirrored from
    `jaxpr_lint.kernel_expectation`, i.e. from ops.py's own dispatch —
    or when the kernel eqn is absent (layer 1's ``ir/kernel-missing``
    already fired for that).  Tracing shares the jit cache with the IR
    lint and execution, so this costs zero extra traces.
    """
    from repro.analysis import jaxpr_lint
    from repro.netsim import engine

    facts = {"kernel_checked": False, "vmem_bytes_per_step": 0}
    if jaxpr_lint.kernel_expectation(cfg, sweep) != "fused":
        return [], facts

    traced = engine.trace_sweep(cfg, sweep)
    eqns = find_kernel_eqns(traced.jaxpr)
    if not eqns:
        return [], facts

    expected = _expected_for(cfg, sweep)
    findings: list[Finding] = []
    for eqn in eqns:
        ef, efacts = lint_kernel_eqn(
            eqn, expected, label=label,
            vmem_ceiling_bytes=vmem_ceiling_bytes)
        findings.extend(ef)
        facts["vmem_bytes_per_step"] = max(facts["vmem_bytes_per_step"],
                                           efacts["vmem_bytes_per_step"])
    facts["kernel_checked"] = True
    facts["n_kernel_eqns"] = len(eqns)
    return findings, facts


def _expected_for(cfg, sweep) -> ms.KernelLayout:
    """The layout ops.py will build for this (cfg, sweep) specialization."""
    from repro.kernels import ops

    return ops.kernel_layout(
        cfg.topo.n_flows,
        use_static_factors=sweep.static_job_factors is not None)
