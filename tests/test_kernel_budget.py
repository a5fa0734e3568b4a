"""Layers 4+5: kernel-body lint fixtures (every rule fires on a broken
kernel, the real kernel lints clean) and HLO budget bookkeeping (drift /
missing / stale / env-mismatch / unknown-dtype), plus the roofline dtype
regression the budget layer depends on."""
from __future__ import annotations

import json
import warnings

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import netsim
from repro.analysis import find_kernel_eqns, lint_kernel, lint_kernel_eqn
from repro.analysis.hlo_budget import (BudgetBook, METRICS, SCHEMA,
                                       env_fingerprint)
from repro.core import Algo, CCParams, MLTCPConfig, Variant
from repro.kernels import mltcp_step as ms
from repro.kernels import ops
from repro.netsim import engine
from repro.roofline import hlo

DT = 2e-5
ROWS, NDYN = 8, 5


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Kernel-lint fixtures: a mini CC-tick-shaped pallas_call per violation.
# The kernel fn is named `_kernel` so find_kernel_eqns prefix-matches it
# exactly as it matches the real kernel (vmapped or not).
# ---------------------------------------------------------------------------

_LAYOUT = ms.KernelLayout(rows=ROWS, block=(ROWS, ms.LANES), grid=(1,),
                          n_inputs=3, n_outputs=1, dyn_index=0,
                          dyn_shape=(1, NDYN), use_static_factors=False)

_STATE = pl.BlockSpec((ROWS, ms.LANES), lambda i: (i, 0))
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _kernel(dyn_ref, a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] * dyn_ref[0, 0] + b_ref[...]


def _args():
    return (jnp.zeros((1, NDYN), jnp.float32),
            jnp.zeros((ROWS, ms.LANES), jnp.float32),
            jnp.zeros((ROWS, ms.LANES), jnp.float32))


def _call(kernel=_kernel, in_specs=(_SMEM, _STATE, _STATE),
          out_specs=_STATE, grid=(1,)):
    def run(dyn, a, b):
        return pl.pallas_call(
            kernel, grid=grid, in_specs=list(in_specs),
            out_specs=out_specs,
            out_shape=jax.ShapeDtypeStruct((ROWS, ms.LANES), jnp.float32),
            interpret=True)(dyn, a, b)
    return run


def _lint(run, layout=_LAYOUT, **kw):
    jaxpr = jax.make_jaxpr(run)(*_args())
    eqns = find_kernel_eqns(jaxpr)
    assert len(eqns) == 1
    return lint_kernel_eqn(eqns[0], layout, label="fix", **kw)


def test_fixture_kernel_is_clean():
    findings, facts = _lint(_call())
    assert findings == []
    assert facts["vmem_bytes_per_step"] == 2 * 3 * ROWS * ms.LANES * 4
    assert facts["body_eqns"] > 0


def test_dyn_not_smem_fires():
    # dyn rides as a full-array VMEM block instead of SMEM scalars
    dyn_vmem = pl.BlockSpec((1, NDYN), lambda i: (0, 0))
    findings, _ = _lint(_call(in_specs=(dyn_vmem, _STATE, _STATE)))
    assert "kernel/dyn-not-smem" in _rules(findings)


def test_state_not_vmem_fires():
    # a flow-state ref pinned to SMEM serializes the vector loads
    def _kernel(dyn_ref, a_ref, b_ref, o_ref):
        o_ref[...] = b_ref[...] * dyn_ref[0, 0] + a_ref[0, 0]

    findings, _ = _lint(_call(kernel=_kernel, in_specs=(_SMEM, _SMEM, _STATE)))
    assert "kernel/state-not-vmem" in _rules(findings)


def test_block_misaligned_and_grid_remainder_fire():
    half = pl.BlockSpec((ROWS // 2, ms.LANES), lambda i: (i, 0))
    findings, _ = _lint(_call(in_specs=(_SMEM, half, half),
                              out_specs=half, grid=(2,)))
    got = _rules(findings)
    assert "kernel/block-misaligned" in got
    assert "kernel/grid-remainder" in got


def test_operand_mismatch_fires():
    wrong = ms.KernelLayout(rows=ROWS, block=(ROWS, ms.LANES), grid=(1,),
                            n_inputs=4, n_outputs=2, dyn_index=0,
                            dyn_shape=(1, NDYN), use_static_factors=True)
    findings, _ = _lint(_call(), layout=wrong)
    assert "kernel/operand-mismatch" in _rules(findings)


def test_f64_in_body_fires():
    def _kernel(dyn_ref, a_ref, b_ref, o_ref):
        v = a_ref[...].astype(jnp.float64) * 2.0
        o_ref[...] = v.astype(jnp.float32) + b_ref[...]

    with jax.enable_x64(True):
        findings, _ = _lint(_call(kernel=_kernel))
    assert "kernel/f64-in-body" in _rules(findings)


def test_gather_scatter_fires():
    def _kernel(dyn_ref, a_ref, b_ref, o_ref):
        idx = dyn_ref[0, 0].astype(jnp.int32) % (ROWS * ms.LANES)
        o_ref[...] = b_ref[...] + jnp.take(a_ref[...].ravel(), idx,
                                           mode="clip")

    findings, _ = _lint(_call(kernel=_kernel))
    assert "kernel/gather-scatter" in _rules(findings)


def test_nested_control_fires():
    def _kernel(dyn_ref, a_ref, b_ref, o_ref):
        o_ref[...] = jax.lax.cond(dyn_ref[0, 0] > 0.0,
                                  lambda: a_ref[...] + b_ref[...],
                                  lambda: a_ref[...] - b_ref[...])

    findings, _ = _lint(_call(kernel=_kernel))
    assert "kernel/nested-control" in _rules(findings)


def test_dyn_written_fires():
    def _kernel(dyn_ref, a_ref, b_ref, o_ref):
        dyn_ref[0, 0] = jnp.float32(1.0)
        o_ref[...] = a_ref[...] + b_ref[...]

    findings, _ = _lint(_call(kernel=_kernel))
    assert "kernel/dyn-written" in _rules(findings)


def test_vmem_budget_fires():
    findings, _ = _lint(_call(), vmem_ceiling_bytes=1024)
    assert "kernel/vmem-budget" in _rules(findings)


def test_grid_remainder_fires_on_uncoverable_rows():
    # an expectation whose rows are not block-divisible can never be
    # covered exactly — the rule fires on the layout itself
    ragged = ms.KernelLayout(rows=12, block=(8, ms.LANES), grid=(1,),
                             n_inputs=3, n_outputs=1, dyn_index=0,
                             dyn_shape=(1, NDYN), use_static_factors=False)
    findings, _ = _lint(_call(), layout=ragged)
    assert "kernel/grid-remainder" in _rules(findings)


# ---------------------------------------------------------------------------
# The real kernel lints clean — per specialization, through the real
# trace path, including the vmapped (K>1) program
# ---------------------------------------------------------------------------

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


@pytest.mark.parametrize("variant", [Variant.WI, Variant.MD, Variant.BOTH])
def test_real_kernel_body_is_clean(variant):
    cfg = _cfg(use_pallas_kernel=True,
               protocol=_proto(variant=variant))
    sweep = engine.make_sweep(cfg)
    findings, facts = lint_kernel(cfg, sweep, label="real")
    assert findings == []
    assert facts["kernel_checked"]
    assert facts["vmem_bytes_per_step"] > 0


def test_real_kernel_body_clean_under_vmap():
    cfg = _cfg(use_pallas_kernel=True)
    sweep = engine.make_sweep(cfg, seed=[1, 2, 3])
    findings, facts = lint_kernel(cfg, sweep, label="vmapped")
    assert findings == []
    assert facts["kernel_checked"]


def test_kernel_lint_skips_oracle_configs():
    cfg = _cfg()                               # use_pallas_kernel=False
    findings, facts = lint_kernel(cfg, engine.make_sweep(cfg), label="off")
    assert findings == [] and not facts["kernel_checked"]


def test_expected_layout_matches_ops_packing():
    lay = ops.kernel_layout(100)
    assert lay.rows == ops.packed_rows(100)
    assert lay.rows % lay.block[0] == 0
    assert lay.grid == (lay.rows // lay.block[0],)
    assert lay.n_inputs == 1 + len(ms.IN_ORDER)
    assert ops.kernel_layout(100, use_static_factors=True).n_inputs == \
        2 + len(ms.IN_ORDER)


# ---------------------------------------------------------------------------
# HLO budget bookkeeping
# ---------------------------------------------------------------------------

_SIG = "group0|jobs=2 flows=4 algo=0 dt=2e-05 kernel=True faults=False"


def _envelope(**over):
    env = {m: 100.0 for m in METRICS}
    env.update(over)
    return env


def _write_baseline(path, groups, env=None, tolerances=None):
    path.write_text(json.dumps({
        "schema": SCHEMA,
        "env": env or env_fingerprint(),
        "tolerances": tolerances or {},
        "plans": {"p": {"groups": groups}},
    }))


def test_budget_clean_when_within_tolerance(tmp_path):
    bp = tmp_path / "budgets.json"
    _write_baseline(bp, [dict(signature=_SIG, **_envelope())])
    book = BudgetBook(path=bp)
    book.observe("p", _SIG, _envelope(flops=101.0))     # within 2%
    assert book.finish() == []


def test_tampered_baseline_trips_drift_with_group_and_metric(tmp_path):
    bp = tmp_path / "budgets.json"
    _write_baseline(bp, [dict(signature=_SIG, **_envelope())])
    book = BudgetBook(path=bp)
    book.observe("p", _SIG, _envelope(flops=150.0, output_bytes=101.0))
    findings = book.finish()
    assert _rules(findings) == {"budget/drift"}
    drifted = {f.message.split(":")[0] for f in findings}
    assert drifted == {"flops", "output_bytes"}         # leaf-level diff
    assert all(f.where == f"p :: {_SIG}" for f in findings)


def test_missing_and_stale_baseline(tmp_path):
    bp = tmp_path / "budgets.json"
    _write_baseline(bp, [dict(signature="group9|gone", **_envelope())])
    book = BudgetBook(path=bp)
    book.observe("p", _SIG, _envelope())
    got = _rules(book.finish())
    assert got == {"budget/missing-baseline", "budget/stale-baseline"}


def test_env_mismatch_skips_drift(tmp_path):
    bp = tmp_path / "budgets.json"
    _write_baseline(bp, [dict(signature=_SIG, **_envelope())],
                    env={"jax": "0.0.0"})
    book = BudgetBook(path=bp)
    book.observe("p", _SIG, _envelope(flops=1e9))       # huge drift...
    got = _rules(book.finish())
    assert got == {"budget/env-mismatch"}               # ...but skipped


def test_no_baseline_file_warns(tmp_path):
    book = BudgetBook(path=tmp_path / "nope.json")
    book.observe("p", _SIG, _envelope())
    findings = book.finish()
    assert _rules(findings) == {"budget/missing-baseline"}
    assert "does not exist" in findings[0].message


def test_unknown_dtype_surfaces_as_finding(tmp_path):
    bp = tmp_path / "budgets.json"
    _write_baseline(bp, [dict(signature=_SIG, **_envelope())])
    book = BudgetBook(path=bp)
    book.observe("p", _SIG, dict(_envelope(), unknown_dtypes=["q7"]))
    got = _rules(book.finish())
    assert "budget/unknown-dtype" in got


def test_update_mode_roundtrips(tmp_path):
    bp = tmp_path / "budgets.json"
    book = BudgetBook(path=bp, update=True)
    book.observe("p", _SIG, _envelope(flops=42.0))
    book.save()
    data = json.loads(bp.read_text())
    assert data["schema"] == SCHEMA
    assert data["env"] == env_fingerprint()
    (group,) = data["plans"]["p"]["groups"]
    assert group["signature"] == _SIG and group["flops"] == 42.0
    # and a check-mode book against it is clean
    book2 = BudgetBook(path=bp)
    book2.observe("p", _SIG, _envelope(flops=42.0))
    assert book2.finish() == []


def test_matches_any_cross_check(tmp_path):
    bp = tmp_path / "budgets.json"
    _write_baseline(bp, [dict(signature=_SIG, **_envelope())])
    book = BudgetBook(path=bp)
    bare_sig = _SIG.split("|", 1)[1]
    assert book.matches_any(bare_sig, _envelope()) is True
    assert book.matches_any(bare_sig, _envelope(flops=999.0)) is False
    assert book.matches_any("jobs=9 flows=9", _envelope()) is None


def test_committed_budgets_schema_is_current():
    from repro.analysis.hlo_budget import DEFAULT_PATH

    data = json.loads(DEFAULT_PATH.read_text())
    assert data["schema"] == SCHEMA
    assert set(data["plans"])  # at least one plan pinned
    for plan in data["plans"].values():
        for g in plan["groups"]:
            assert set(METRICS) <= set(g)


# ---------------------------------------------------------------------------
# roofline dtype regression (satellite): fabricated HLO lines
# ---------------------------------------------------------------------------

def test_f8_collective_bytes_counted_exactly():
    txt = "%ar = f8e4m3[128]{0} all-reduce(%x), replica_groups={}"
    out = hlo.collective_bytes_from_text(txt)
    assert out["total_bytes"] == 128.0                  # 1 B/elem, not 4
    assert out["unknown_dtypes"] == []


def test_unknown_dtype_warns_once_and_is_reported():
    hlo._warned_dtypes.discard("q7")
    txt = ("%a = q7[64]{0} all-gather(%x)\n"
           "%b = q7[64]{0} all-gather(%y)\n")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = hlo.collective_bytes_from_text(txt)
    assert out["unknown_dtypes"] == ["q7"]
    assert out["total_bytes"] == 2 * 64 * 4             # documented default
    assert sum("q7" in str(x.message) for x in w) == 1  # once, not per line
