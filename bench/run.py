#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <config>.<traffic> --seed <n> \\
        --seconds <s> --trace <0|1>

Set-up builds the cell's plan, runs one whole plan call to compile (or load
from the persistent cache) and warm every program the window uses, then the
window runs whole `repro.netsim.run_plan` calls back to back, each with
fresh seeds drawn from ``--seed``.  A call is started only while it would
end nearer to ``--seconds`` than stopping does, so the window ends at a
call boundary.  With ``--trace 1`` the window is followed by one profiled
call of a cut of the same plan (same shapes, fewer ticks, compiled in
set-up), from which the device metrics come.

Once the window has closed and the peak device memory is read, the plain
reference (reference.py) simulates every point the window simulated, and
check.py decides ``correct``.  The last line of standard output is one JSON
object; every number compared is printed beside its limit as the last lines
of standard error and under the result's last key.  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero before any
result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# JAX's persistent compilation cache: a fixed directory in the checkout, so
# that only a cell's first run in a checkout compiles
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """The run cannot be measured (no chip, a trace inside the window ...)."""


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads while ``armed``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self, jax):
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_):
        if self.armed and name in self.EVENTS:
            self.count += 1


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's records."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


def load_metric(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def start_jax(chips: int):
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"needs {chips} chips, found {len(devices)}")
    return jax, devices


def timed_call(cell, draws, seconds):
    """One plan call on the timed path; returns its record and results."""
    import plan
    from repro import netsim

    p = plan.make_plan(cell, draws, seconds=seconds)
    t0 = time.perf_counter()
    pr = netsim.run_plan(p, keep_going=True)
    wall = time.perf_counter() - t0
    record = {
        "wall_s": wall,
        # configured ticks of every point, so that an early exit would show
        # as a gain and not as lost work
        "ticks": len(pr.results) * plan.n_ticks(cell.config, seconds),
        "groups": [{"wall_s": g.wall_s, "n_ticks": g.n_ticks,
                    "n_points": g.n_points} for g in pr.profile.groups],
        "fallbacks": pr.n_kernel_fallbacks,
    }
    return record, pr


def run(args, bench: dict, *, require_chip: bool = True,
        sim_seconds: float | None = None) -> dict:
    """One run of one cell; returns the result object (and prints the
    earlier lines).  ``require_chip=False`` and ``sim_seconds`` (a shorter
    simulated length) exist for the tests, which drive the rest of a run on
    the CPU at a small size."""
    entry = cell_entry(bench, args.workload)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    if require_chip:
        jax, devices = start_jax(int(entry["chips"]))
    else:
        import jax
        devices = jax.devices()
    import check
    import devtrace
    import plan
    import reference
    import roofline
    from repro import netsim
    from repro.netsim import counters

    kind = devices[0].device_kind
    if require_chip:
        roofline.peaks(kind)
    cell = plan.load_cell(args.workload)
    plan.check_programs(cell.config)
    limits = check.load_limits(cell.name)
    iso = plan.single_iso(cell.config)
    draws = plan.draw_calls(args.seed, 256, cell.points_per_variant, iso)

    # --- set-up: compile and warm every program the window uses ---------
    imports_s = process_age_s()
    t0 = time.perf_counter()
    netsim.run_plan(plan.make_plan(cell, draws[0], seconds=sim_seconds))
    warm_s = time.perf_counter() - t0
    cut = None
    if args.trace:
        cut_s = int(cell.traffic["trace_cut_ticks"]) * float(cell.config["dt_s"])
        cut = plan.make_plan(cell, draws[0], seconds=cut_s, name="cut")
        netsim.run_plan(cut)
        # once more, warm, to price the profiler against the traced call
        t0 = time.perf_counter()
        netsim.run_plan(cut)
        cut_s = time.perf_counter() - t0
    setup_s = process_age_s()
    print(f"setup: start_and_imports_s={imports_s:.3f} warm_call_s={warm_s:.3f} "
          f"setup_s={setup_s:.3f}", flush=True)

    # --- the window: whole plan calls ------------------------------------
    calls, results = [], []
    compiles = CompileCounter(jax)
    with counters.watch() as watch:
        compiles.armed = True
        start = time.perf_counter()
        for c in range(1, len(draws)):
            record, pr = timed_call(cell, draws[c], sim_seconds)
            calls.append(record)
            results += [(c, r) for r in pr.results]
            elapsed = time.perf_counter() - start
            if elapsed + 0.5 * record["wall_s"] >= args.seconds:
                break
        compiles.armed = False
    fallbacks = sum(c["fallbacks"] for c in calls)
    print(f"window: calls={len(calls)} traces={watch.traces} "
          f"compiles={compiles.count} kernel_fallbacks={fallbacks} "
          f"platform={devices[0].platform} device_kind={kind} "
          f"count={len(devices)}", flush=True)
    if watch.traces or compiles.count:
        raise BenchError(f"{watch.traces} traces and {compiles.count} "
                         f"compiles inside the window")
    if fallbacks:
        raise BenchError(f"{fallbacks} kernel fallbacks to the oracle")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    trace, kernel_calls = None, 0
    if args.trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(tmp)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
                traced = netsim.run_plan(cut)
            traced_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
            trace = devtrace.load(tmp)
            # one CC-tick call per scan step of each group, on every device
            kernel_calls = sum(g.n_ticks for g in traced.profile.groups)
            mosaic = len(devtrace.kernel_ops(trace, devtrace.KERNEL_MATCH))
            print(f"trace: cut_call_s={cut_s:.3f} (warm, untraced) "
                  f"traced_call_s={traced_s:.3f} mosaic_calls={mosaic} "
                  f"cc_tick_calls_expected="
                  f"{kernel_calls * devtrace.n_devices(trace)}", flush=True)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    # --- correctness: the reference over every point of the window -------
    points, prog, failed = [], [], 0
    for c, r in results:
        if r is None:
            failed += 1
            continue
        d = draws[c][r.point["point"]]
        stats = check.point_stats(r.iter_times, r.trace_util,
                                  r.trace_jobtput, r.trace_t)
        if not check.finite(stats):
            failed += 1
        points.append((r.point["variant"], d.seed, d.order))
        prog.append(stats)
    attempted = len(results)
    del results
    gc.collect()
    ref = []
    for b in range(0, len(points), cell.n_points):
        block = points[b:b + cell.n_points]
        pad = cell.n_points - len(block)
        out = reference.simulate(cell.config, block + block[-1:] * pad,
                                 seconds=sim_seconds)
        ref += [check.point_stats(o["iter_times"], o["trace_util"],
                                  o["trace_jobtput"], o["trace_t"])
                for o in out[:len(block)]]
    nums = check.numbers([p[0] for p in points], prog, ref)
    ok, compared = check.judge(nums, limits)
    correct = ok and failed == 0 and attempted > 0

    # --- metrics ---------------------------------------------------------
    metrics = {}
    if not args.trace:
        wall = sum(c["wall_s"] for c in calls)
        metrics["sim_ticks_per_s"] = {
            "value": sum(c["ticks"] for c in calls) / wall, "unit": "ticks/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        k_local = max(1, cell.points_per_variant // len(devices))
        ctx = {"calls": calls, "trace": trace, "config": cell.config,
               "kernel_calls_per_device": kernel_calls, "device_kind": kind,
               "points_per_kernel_call": k_local}
        for m in bench["per_layer"]:
            value = load_metric(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = devtrace.busy_ns(trace) * 1e-9
        device["window_s"] = devtrace.window_ns(trace) * 1e-9
        result["breakdown"] = {"device_ops": devtrace.top_ops(trace),
                               "idle_gaps": devtrace.idle_gaps(trace)}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_bench()
    try:
        result = run(args, bench)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, v in result["compared"].items():
        print(f"compared {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
