"""The comparison that decides ``correct`` must reject what it is there to
reject.  These tests skip the harness's look for a chip and drive the rest of
a run on the CPU at a small simulated length, with the cell's own limits:

* a sound run keeps the iteration rates and link utilization within their
  limits (its per-job bytes are not held here: over a run of a few
  iterations, one iteration more or less is a large share);
* the control, the plain reference computed in bfloat16 in the program's
  place, comes out not correct;
* with the timed path broken underneath, the run comes out not correct: a
  tick that returns its state unchanged, half of each call's points left
  out (their slots filled from the other half), and answers altered where
  they are produced (each job handed the next job's iteration times);
* a CC update that computes MLTCP-WI as plain Reno (F = 1) comes out not
  correct, caught by how unevenly the jobs share the link.

    PYTHONPATH=src python -m pytest bench
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "src"), str(HERE)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import check  # noqa: E402
import plan  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

CELL = "dumbbell_reno.sweep"
# simulated seconds: long enough for every job to finish a few iterations
SECONDS = 0.6


@pytest.fixture(autouse=True)
def fresh_programs():
    """Every test compiles its own programs: a fault planted under the jit
    cache must not be served a sound program, nor the next test a broken
    one."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def two_tier_cell() -> plan.Cell:
    """The dumbbell's jobs on the program's two-tier fabric (4 leaves, 50
    Gbps leaf links, a 200 Gbps core), so that paths of three hops are
    held to the program too."""
    c = plan.load_cell("dumbbell_reno.sweep")
    config = dict(c.config, fabric={"kind": "two_tier", "leaves": 4,
                                    "leaf_gbps": 50.0, "core_gbps": 200.0},
                  jobs=dict(c.config["jobs"], n=14))
    return plan.Cell("two_tier.sweep", config, c.traffic)


@pytest.mark.parametrize("cell,seconds", [("dumbbell_reno.sweep", 0.18),
                                          ("two_tier.sweep", 0.15)])
def test_reference_follows_the_program_on_cpu(cell, seconds):
    """On the CPU, before rounding differences have grown, the reference
    and the program's jnp path agree: every iteration to within two ticks,
    link utilization to 0.1% and each job's bytes to 1%."""
    import dataclasses

    import numpy as np
    from repro import netsim

    c = two_tier_cell() if cell == "two_tier.sweep" else plan.load_cell(cell)
    draws = plan.draw_calls(2**32 + 10, 1, c.points_per_variant,
                            plan.single_iso(c.config))[0]
    p = plan.make_plan(c, draws, seconds=seconds)
    build = p.build
    p = dataclasses.replace(p, build=lambda pt: dataclasses.replace(
        build(pt), use_pallas_kernel=False))
    got = netsim.run_plan(p).results
    points = [(r.point["variant"], draws[r.point["point"]].seed,
               draws[r.point["point"]].order) for r in got]
    ref = reference.simulate(c.config, points, seconds=seconds)
    for r, f in zip(got, ref):
        assert [len(x) for x in r.iter_times] == [len(x) for x in
                                                  f["iter_times"]]
        for x, y in zip(r.iter_times, f["iter_times"]):
            np.testing.assert_allclose(x, y, rtol=0,
                                       atol=2 * c.config["dt_s"] + 1e-9)
        np.testing.assert_allclose(r.trace_util.mean(axis=0),
                                   f["trace_util"].mean(axis=0), rtol=1e-3)
        np.testing.assert_allclose(r.trace_jobtput.sum(axis=0),
                                   f["trace_jobtput"].sum(axis=0), rtol=1e-2)


def drive(seed: int) -> dict:
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.0, trace=0)
    return run.run(args, bench, require_chip=False, sim_seconds=SECONDS)


def test_sound_run_is_within_the_rate_and_utilization_limits():
    result = drive(2**32 + 11)
    assert result["attempted"] == 8 and result["failed"] == 0
    for name in ("iter_gap", "iter_bias", "util_gap", "share_gap"):
        v = result["compared"][name]
        assert v["value"] <= v["limit"], (name, v)


def test_control_in_bfloat16_is_rejected():
    cell = plan.load_cell(CELL)
    draws = plan.draw_calls(2**32 + 12, 1, cell.points_per_variant,
                            plan.single_iso(cell.config))[0]
    points = [(v, d.seed, d.order) for v in cell.variants for d in draws]

    def stats(dtype):
        return [check.point_stats(o["iter_times"], o["trace_util"],
                                  o["trace_jobtput"], o["trace_t"])
                for o in reference.simulate(cell.config, points, dtype=dtype,
                                            seconds=SECONDS)]

    nums = check.numbers([p[0] for p in points], stats(jnp.bfloat16),
                         stats(jnp.float32))
    ok, _ = check.judge(nums, check.load_limits(CELL))
    assert not ok, nums


def _state_unchanged(monkeypatch):
    from repro.netsim import engine

    monkeypatch.setattr(engine, "_tick",
                        lambda cfg, statics, sweep, wl, st, _: (st, None))


def _half_the_batch(monkeypatch):
    from repro.netsim import experiment

    real = experiment.simulate_sweep

    def half(cfg, sweep):
        k = sweep.slope.shape[0]
        keep = jax.tree_util.tree_map(lambda x: x[: k // 2], sweep)
        raw = real(cfg, keep)
        return jax.tree_util.tree_map(
            lambda x: jnp.concatenate([x, x[: k - k // 2]]), raw)

    monkeypatch.setattr(experiment, "simulate_sweep", half)


def _answers_altered(monkeypatch):
    from repro.netsim import experiment

    real = experiment.simulate_sweep

    def shifted(cfg, sweep):
        raw = real(cfg, sweep)
        return raw._replace(iter_times=jnp.roll(raw.iter_times, 1, axis=1),
                            iter_counts=jnp.roll(raw.iter_counts, 1, axis=1))

    monkeypatch.setattr(experiment, "simulate_sweep", shifted)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_batch,
                                   _answers_altered])
def test_broken_timed_path_is_rejected(fault, monkeypatch):
    fault(monkeypatch)
    result = drive(2**32 + 13)
    assert not result["correct"], result["compared"]
    # caught by the jobs' iteration rates, which a sound run keeps
    gap = result["compared"]["iter_gap"]
    assert gap["value"] > gap["limit"], gap


def test_wi_computed_as_reno_is_rejected(monkeypatch):
    """MLTCP-WI's F = slope * ratio + intercept, computed as Reno's 1: the
    jobs then share the link as evenly as under OFF."""
    from repro.netsim import experiment

    real = experiment.simulate_sweep

    def as_reno(cfg, sweep):
        return real(cfg, sweep._replace(
            slope=jnp.zeros_like(sweep.slope),
            intercept=jnp.ones_like(sweep.intercept)))

    monkeypatch.setattr(experiment, "simulate_sweep", as_reno)
    result = drive(2**32 + 14)
    assert not result["correct"], result["compared"]
    gap = result["compared"]["share_gap"]
    assert gap["value"] > gap["limit"], gap
