#!/usr/bin/env python3
"""Chip smoke test: the netsim sweep with the fused CC-tick kernel compiled
on a TPU, driven through the benchmark entry points.

    python3 chip_smoke.py               # one chip: phases A, B and C
    python3 chip_smoke.py --four-chips  # four chips: phase A's kernel plan,
                                        # sharded vs unsharded, nothing else

Phase A is the paper's deployment (§4.1): six Table 1 jobs on a 50 Gbps
dumbbell at 100 us RTT, Reno with 8 sockets per job, MLTCP off and WI, four
seeds, at the benchmarks' quick-mode workload scale, run once through the
fused kernel and once through the jnp oracle.  Phase B runs the same
deployment for CUBIC (4 sockets per job) and DCQCN (1 QP per job, ECN
fabric) through the kernel.  Phase C is the two-tier leaf/spine fabric at
cluster size: 128 jobs x 8 sockets = 1,024 flows, Reno, kernel on.

Every phase checks: no kernel fallback to the oracle, ``tpu_custom_call``
in the compiled program of every kernel-enabled compile group, finite
statistics, every job through its iterations, and that a second run
reproduces the first bit for bit.  The timings it prints are smoke
timings (compile, and one warm call ending in ``block_until_ready``), not
benchmark numbers.  The last line of standard output is one JSON object
naming the device; it is printed only when every check passed.  Without a
TPU the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SEEDS = (0, 1, 2, 3)
# Six of the seven Table 1 jobs (workload.PAPER_MODELS), the multi-peak
# gpt3_hybrid program among them; RoBERTa is left out because its
# iterations are twice the others' and would double every run's length.
PAPER_JOBS = ("vgg16", "wideresnet101", "camembert", "gpt1", "gpt2",
              "gpt3_hybrid")
SOCKETS = {"reno": 8, "cubic": 4, "dcqcn": 1}     # paper §4.1
MIN_ITERS_PAPER = 10
MIN_ITERS_CLUSTER = 3
CLUSTER_JOBS, CLUSTER_LEAVES = 128, 4
# Kernel vs oracle on the chip: per-job mean iteration time over the four
# seeds (after the warm-up iterations) must agree within this relative
# tolerance.  Programs that round one tick apart diverge like reseeded
# runs; on a TPU v5e the oracle's per-seed means of one job spread by up
# to 5.3% (sample std), so two independent 4-seed means differ with a std
# of at most 5.3% x sqrt(2/4) = 3.7%, and 12% is a 3-sigma bound.
MEAN_ITER_RTOL = 0.12


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def sim_time_for(profiles, n_iters: int, link_bytes_per_s: float,
                 jobs_per_link: int, scale: float) -> float:
    """Simulated seconds for every job to finish ``n_iters`` iterations.

    At a fair share of its busiest link, a job's iteration takes its
    compute plus its bytes at 1/jobs_per_link of the link's capacity.  The
    1.6x margin covers links that run below capacity: DCQCN's ECN fabric
    holds the dumbbell near 70% utilization."""
    worst = max(p.total_compute + jobs_per_link * p.total_bytes
                / link_bytes_per_s for p in profiles)
    return 1.6 * n_iters * worst * scale


def paper_plan(algo: str, use_kernel: bool):
    """Phase A/B: the paper's dumbbell deployment, OFF and WI x 4 seeds."""
    from benchmarks import common
    from repro import netsim, workload

    profiles = [workload.profile_for(m) for m in PAPER_JOBS]
    topo = netsim.dumbbell(len(profiles), sockets_per_job=SOCKETS[algo])
    sim_time = sim_time_for(profiles, MIN_ITERS_PAPER, topo.cap[0],
                            len(profiles), common.WORK_SCALE)

    def build(pt):
        cfg = common.build_cfg(topo, profiles,
                               common.protocol(algo, pt["variant"]),
                               sim_time=sim_time)
        return dataclasses.replace(cfg, use_pallas_kernel=use_kernel)

    name = f"smoke-{algo}-{'kernel' if use_kernel else 'oracle'}"
    return common.plan(build, name=name, variant=("OFF", "WI"), seed=SEEDS)


def cluster_plan():
    """Phase C: two-tier fabric, 128 jobs x 8 sockets spread over 4 leaves
    (each leaf uplink, downlink and the core carry 32 / 32 / 128 jobs)."""
    from benchmarks import common
    from repro import netsim, workload

    profiles = [workload.profile_for(PAPER_JOBS[j % len(PAPER_JOBS)])
                for j in range(CLUSTER_JOBS)]
    pairs = []
    for j in range(CLUSTER_JOBS):
        src = j % CLUSTER_LEAVES
        hop = 1 + (j // CLUSTER_LEAVES) % (CLUSTER_LEAVES - 1)
        pairs.append((src, (src + hop) % CLUSTER_LEAVES))
    topo = netsim.two_tier(pairs, n_leaves=CLUSTER_LEAVES,
                           sockets_per_job=SOCKETS["reno"])
    sim_time = sim_time_for(profiles, MIN_ITERS_CLUSTER, topo.cap[0],
                            CLUSTER_JOBS // CLUSTER_LEAVES, common.WORK_SCALE)

    def build(pt):
        cfg = common.build_cfg(topo, profiles, common.protocol("reno", "WI"),
                               sim_time=sim_time)
        return dataclasses.replace(cfg, use_pallas_kernel=True)

    return common.plan(build, name="smoke-cluster", seed=SEEDS)


def compile_groups(plan, use_kernel: bool) -> float:
    """AOT-compile every compile group of ``plan``; returns the seconds.

    For kernel plans, the compiled text must hold the Mosaic kernel: a
    missing ``tpu_custom_call`` means it ran as plain XLA (interpreted).
    """
    from repro.netsim import engine, experiment

    _, cfgs, overrides, groups = experiment.resolve_plan(plan)
    t0 = time.perf_counter()
    for gi, group in enumerate(groups):
        sweep = experiment.group_sweep(cfgs, overrides, group)
        compiled = engine.lower_sweep(group.cfg, sweep).compile()
        if use_kernel:
            check("tpu_custom_call" in compiled.as_text(),
                  f"{plan.name}: compile group {gi} has no tpu_custom_call")
    return time.perf_counter() - t0


def check_results(pr, min_iters: int) -> None:
    import numpy as np

    check(pr.n_kernel_fallbacks == 0,
          f"{pr.plan.name}: {pr.n_kernel_fallbacks} kernel fallbacks")
    check(not pr.group_errors, f"{pr.plan.name}: {pr.group_errors}")
    for r in pr.results:
        label = f"{pr.plan.name} {r.point.label()}"
        for name in ("drops_per_s", "marks_per_s", "trace_t", "trace_util",
                     "trace_drops", "trace_jobtput"):
            check(bool(np.all(np.isfinite(getattr(r, name)))),
                  f"{label}: non-finite {name}")
        for j, it in enumerate(r.iter_times):
            check(bool(np.all(np.isfinite(it))),
                  f"{label}: job {j} non-finite iteration times")
            check(it.size >= min_iters,
                  f"{label}: job {j} finished {it.size} < {min_iters} "
                  f"iterations")


FIELDS = ("trace_util", "trace_drops", "trace_jobtput", "trace_incomm")


def first_difference(a, b):
    """None if two SimResults are bitwise equal, else where they first
    differ: the earliest chunk of a chunk trace, else a per-run field."""
    import numpy as np

    first = None
    for name in FIELDS:
        x, y = getattr(a, name), getattr(b, name)
        diff = ~np.all((x == y).reshape(x.shape[0], -1), axis=1)
        if diff.any() and (first is None or np.argmax(diff) < first[0]):
            first = (int(np.argmax(diff)), name)
    if first is not None:
        chunk, name = first
        tick = round(a.trace_t[chunk] / a.cfg.dt)
        return f"{name} in the chunk ending at tick {tick}"
    for j, (x, y) in enumerate(zip(a.iter_times, b.iter_times)):
        if not np.array_equal(x, y):
            return f"iter_times of job {j}"
    for name in ("drops_per_s", "marks_per_s"):
        if getattr(a, name) != getattr(b, name):
            return name
    return None


def assert_reproduces(first, second) -> None:
    for a, b in zip(first.results, second.results):
        where = first_difference(a, b)
        check(where is None, f"{first.plan.name} {a.point.label()}: a second "
              f"run differs from the first at {where}")


def run_phase(label: str, plan, use_kernel: bool, min_iters: int,
              shard="auto"):
    """Compile, run twice (the second warm), check; returns the results.

    The ahead-of-time compile (and its ``tpu_custom_call`` check) covers
    the unsharded program, which is what one chip runs; a run with
    ``shard=True`` compiles its sharded program inside its first call."""
    from benchmarks import common

    compile_s = None if shard is True else compile_groups(plan, use_kernel)
    t0 = time.perf_counter()
    first = common.run_plan(plan, shard=shard)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    second = common.run_plan(plan, shard=shard)
    warm_s = time.perf_counter() - t0
    check_results(first, min_iters)
    assert_reproduces(first, second)
    aot = "n/a" if compile_s is None else f"{compile_s:.3f}"
    print(f"[smoke timing] {label}: groups={first.n_compile_groups} "
          f"points={len(first)} ticks/point={first.n_ticks // len(first)} "
          f"aot_compile_s={aot} first_call_s={first_s:.3f} "
          f"warm_call_s={warm_s:.3f}", flush=True)
    return first


def compare_runs(tag: str, test_pr, ref_pr) -> None:
    """Report whether two runs of one plan agree bit for bit (and where
    they first part), and gate on their per-job mean iteration times,
    averaged over the seeds, agreeing within MEAN_ITER_RTOL."""
    import numpy as np

    bitwise = True
    for a, b in zip(test_pr.results, ref_pr.results):
        where = first_difference(a, b)
        if where is not None:
            if bitwise:
                print(f"[{tag}] first difference: {a.point.label()} {where}")
            bitwise = False
    print(f"[{tag}] bitwise identical: {bitwise}", flush=True)
    worst = spread = 0.0
    for variant in ("OFF", "WI"):
        tests = test_pr.select(variant=variant)
        refs = ref_pr.select(variant=variant)
        for j in range(tests[0].n_jobs):
            per_seed = [r.avg_iter(j) for r in refs]
            tm = np.mean([r.avg_iter(j) for r in tests])
            rm = np.mean(per_seed)
            rel = abs(tm - rm) / rm
            worst = max(worst, rel)
            spread = max(spread, float(np.std(per_seed, ddof=1)) / rm)
            check(rel <= MEAN_ITER_RTOL,
                  f"{tag}: {variant} job {j} mean iteration {tm:.6f} s vs "
                  f"{rm:.6f} s ({rel:.3%} > {MEAN_ITER_RTOL:.0%})")
    print(f"[{tag}] worst per-job mean iteration time difference: "
          f"{worst:.4%} (limit {MEAN_ITER_RTOL:.0%}); worst seed-to-seed "
          f"spread of the reference's per-seed means: {spread:.4%}",
          flush=True)


def one_chip() -> None:
    kernel_a = run_phase("A reno kernel", paper_plan("reno", True), True,
                         MIN_ITERS_PAPER)
    oracle_a = run_phase("A reno oracle", paper_plan("reno", False), False,
                         MIN_ITERS_PAPER)
    compare_runs("kernel vs oracle", kernel_a, oracle_a)
    run_phase("B cubic kernel", paper_plan("cubic", True), True,
              MIN_ITERS_PAPER)
    run_phase("B dcqcn kernel", paper_plan("dcqcn", True), True,
              MIN_ITERS_PAPER)
    run_phase("C two-tier 1024 flows kernel", cluster_plan(), True,
              MIN_ITERS_CLUSTER)


def four_chips() -> None:
    plan = paper_plan("reno", True)
    sharded = run_phase("A reno kernel sharded", plan, True,
                        MIN_ITERS_PAPER, shard=True)
    single = run_phase("A reno kernel unsharded", plan, True,
                       MIN_ITERS_PAPER, shard=False)
    compare_runs("sharded vs unsharded", sharded, single)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only phase A's kernel plan sharded over four "
                         "chips and unsharded, and compare the two")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    from repro import compile_cache
    from repro.kernels import ops

    cache_dir = compile_cache.enable()
    print(f"device: {devices[0].device_kind} x{len(devices)}; "
          f"compile cache: {cache_dir}; "
          f"kernel interpret mode: {ops.resolve_interpret()}", flush=True)
    try:
        check(ops.resolve_interpret() is False,
              "the kernels would run interpreted")
        four_chips() if args.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
