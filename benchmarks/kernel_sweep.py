"""Fused CC-tick kernel vs jnp oracle on a plan-shaped K>1 sweep (µs/tick).

The CC tick is the simulator's hot loop (MLTCP §4: per-iteration byte-scaled
window updates across all flows, every tick).  Since the protocol scalars
became kernel *operands* (DESIGN.md §4) the fused Pallas kernel stays
engaged under real `run_plan` sweeps, so this suite times exactly that
shape: a job-count x seed plan run twice — once through the jnp oracle,
once with ``use_pallas_kernel=True`` — and reports µs/tick for both plus
the ratio.  Each mode is compiled by a warm-up run first, so the numbers
are steady-state execution, not trace+compile.

Interpretation note: on the CPU backend the kernel body runs through the
Pallas *interpreter* (chosen automatically, `ops.resolve_interpret`), which
emulates the TPU grid and is expected to be slower than the oracle — the
suite is then a regression harness for the dispatch overhead and a
correctness gate (``n_kernel_fallbacks == 0``), and its timings are not
device numbers.  On a TPU the same entry point runs the kernel compiled
and measures the genuine fused speedup.  Results merge into results/benchmarks.json under
``kernel_sweep`` (existing suites' entries survive — see
`common.merge_results`).
"""
from __future__ import annotations

import dataclasses
import time

from benchmarks import common
from repro import netsim
from repro.kernels import ops as kernel_ops


def _plan(use_kernel: bool, job_counts) -> netsim.Plan:
    def build(pt):
        n = pt["n_jobs"]
        cfg = common.build_cfg(netsim.dumbbell(n, sockets_per_job=2),
                               common.gpt2(n),
                               common.protocol("reno", "WI"))
        return dataclasses.replace(cfg, use_pallas_kernel=use_kernel)
    return common.plan(build, name=f"kernel-sweep-{use_kernel}",
                       n_jobs=tuple(job_counts),
                       seed=common.seed_axis())


def make_plan(use_kernel: bool = True, job_counts=(2, 3)) -> netsim.Plan:
    """The kernel-mode plan (default: fused).  `repro.analysis --plan
    kernel_sweep` lints this lowering to prove the pallas_call is present —
    the static form of the suite's `n_kernel_fallbacks == 0` assert."""
    return _plan(use_kernel, job_counts)


def _timed_plan(use_kernel: bool, job_counts) -> tuple[float, int, int]:
    """(steady-state seconds, total ticks, kernel fallbacks) for one mode.

    Fallbacks are read off the *warm-up* run: FALLBACK_COUNT increments at
    trace time, and the timed run hits the jit cache (trace count 0), so
    its delta is always zero — only the run that traces can tell whether
    the kernel actually engaged.
    """
    plan = _plan(use_kernel, job_counts)
    warmup = common.run_plan(plan)              # warm-up: trace + compile
    t0 = time.time()
    pr = common.run_plan(plan)                  # same jit cache entries
    wall = time.time() - t0
    return wall, pr.n_ticks, warmup.n_kernel_fallbacks


def run(job_counts=(2, 3)) -> tuple[dict, int]:
    oracle_s, n_ticks, _ = _timed_plan(False, job_counts)
    fused_s, fused_ticks, fallbacks = _timed_plan(True, job_counts)
    assert fallbacks == 0, (
        f"use_pallas_kernel=True fell back to the jnp oracle {fallbacks} "
        f"times — the sweep did not run fused")
    oracle_us = 1e6 * oracle_s / max(n_ticks, 1)
    fused_us = 1e6 * fused_s / max(fused_ticks, 1)
    out = {
        "oracle_us_per_tick": round(oracle_us, 3),
        "fused_us_per_tick": round(fused_us, 3),
        "fused_over_oracle": round(fused_us / max(oracle_us, 1e-9), 3),
        "kernel_fallbacks": fallbacks,
        "interpret": kernel_ops.resolve_interpret(),
    }
    # each mode executed its plan twice (warm-up + timed) — report all the
    # ticks actually simulated so the harness's us/tick CSV stays honest
    return out, 2 * (n_ticks + fused_ticks)


if __name__ == "__main__":
    import json
    derived, _ = run()
    common.merge_results({"kernel_sweep": derived})
    print(json.dumps(derived, indent=1))
