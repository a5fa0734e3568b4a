"""The traced cut by name scope: which stage of the tick each device op
belongs to, read from the program's ``jax.named_scope`` metadata.

Neither an op's text nor its event stats (``device_offset_ps``,
``device_duration_ps``) in a TPU trace hold the HLO ``op_name`` metadata
that ``jax.named_scope`` writes (``.../tick.links/add``).  So `scopes_from_hlo`
maps each op by instruction name to the ``op_name`` in the compiled module's
HLO text.  A fusion takes the scope of its fused computation's root
instruction, which is XLA's own metadata for it.  The stage of an op is the
innermost ``tick.*`` or ``chunk.*`` part of its path (`stage_times`).

The metric readers get the trace that ``devtrace.load`` made; `scoped`
rebuilds the traced cut's plan from the run's own ``--workload`` and
``--seed``, as run.py builds it, compiles its group programs (found in the
persistent cache that set-up filled) and keeps the op scopes it maps, once a
trace.  A program without scopes maps every op to "" and its stage shares
read nothing.
"""
from __future__ import annotations

import argparse
import re
import sys

import devtrace

STAGE_PREFIXES = ("tick.", "chunk.")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

# per device of a trace, each op's name-scope path ("" where none is
# known), in the order of ``Trace.ops``
Scopes = dict[str, list[str]]


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` of a compiled module's HLO text.  A
    fusion (or any op that calls a computation) takes its called
    computation's root's op_name, its own where the root has none."""
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    roots: dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)", line)
            comp = head.group(1) if head and line.rstrip().endswith("{") \
                else None
            continue
        m = re.match(r"\s*(ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if not m:
            continue
        name = m.group(2)
        meta = _OP_NAME.search(line)
        own[name] = meta.group(1) if meta else ""
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if called:
            calls[name] = called.group(1)
        if m.group(1) and comp is not None:
            roots[comp] = own[name]
    return {name: roots.get(calls.get(name, ""), "") or scope
            for name, scope in own.items()}


def scopes_from_hlo(trace: devtrace.Trace,
                    hlo_texts: list[str]) -> tuple[Scopes, int]:
    """Each op's scope from the compiled modules' HLO text, by instruction
    name.  ``hlo_texts`` are the groups' programs in the order they ran:
    where a device shows one outer loop per program, an op inside the k-th
    loop takes the k-th program's table; any other op takes the first
    program that names it.  Returns the scopes and how many names the
    programs map to different scopes."""
    tables = [hlo_scopes(text) for text in hlo_texts]
    merged: dict[str, str] = {}
    conflicts = 0
    for table in tables:
        for name, scope in table.items():
            if name in merged and merged[name] != scope:
                conflicts += 1
            merged.setdefault(name, scope)
    scopes: Scopes = {}
    for dev, evs in trace.ops.items():
        loops = _outer_loops([(s, s + d, name) for name, s, d in evs])
        by_loop = len(loops) == len(tables)
        out = []
        for name, s, d in evs:
            table = merged
            if by_loop:
                table = next((t for (ls, le), t in zip(loops, tables)
                              if ls <= s and s + d <= le), merged)
            out.append(table.get(devtrace.short_name(name).lstrip("%"), ""))
        scopes[dev] = out
    return scopes, conflicts


def _outer_loops(intervals) -> list[tuple[int, int]]:
    """The ``(start, end)`` of each loop op that no other op holds, in time
    order; ``intervals`` are ``(start, end, name)``."""
    loops, top_end = [], None
    for s, e, name in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        if top_end is not None and s < top_end and e <= top_end:
            continue
        top_end = e
        if "while" in devtrace.short_name(name):
            loops.append((s, e))
    return loops


def stage_of(scope: str, prefixes=STAGE_PREFIXES) -> str:
    """The innermost part of a scope path that starts with one of
    ``prefixes``, or "unscoped"."""
    for part in reversed(scope.split("/")):
        if part.startswith(prefixes):
            return part
    return "unscoped"


def loop_ops(trace: devtrace.Trace,
             scopes: Scopes | None = None) -> list[tuple[str, int, int, str]]:
    """Leaf device ops inside an outer loop op (a ``while`` that no other op
    holds: the chunk scan of each group's program), on every device, as
    ``(name, start, end, scope)`` clipped to the window.  A leaf holds no
    other op, as in ``devtrace.leaves``."""
    lo, hi = trace.window
    out = []
    for dev, evs in trace.ops.items():
        dev_scopes = (scopes or {}).get(dev) or [""] * len(evs)
        ops = sorted(((name, max(s, lo), min(s + d, hi), sc)
                      for (name, s, d), sc in zip(evs, dev_scopes)
                      if min(s + d, hi) > max(s, lo)),
                     key=lambda op: (op[1], -op[2]))
        loops = _outer_loops([(s, e, name) for name, s, e, _ in ops])
        for i, op in enumerate(ops):
            s, e = op[1], op[2]
            if i + 1 < len(ops) and ops[i + 1][1] < e and ops[i + 1][2] <= e:
                continue
            if any(ls <= s and e <= le for ls, le in loops):
                out.append(op)
    return out


def stage_times(trace: devtrace.Trace, scopes: Scopes | None,
                prefixes=STAGE_PREFIXES) -> dict[str, float]:
    """Loop leaf-op seconds per stage (`stage_of`), averaged over the
    devices that ran anything; ops of no stage are ``unscoped``."""
    tot: dict[str, float] = {}
    for _, s, e, scope in loop_ops(trace, scopes):
        stage = stage_of(scope, prefixes)
        tot[stage] = tot.get(stage, 0.0) + (e - s)
    n_dev = max(1, devtrace.n_devices(trace))
    return {k: ns / n_dev * 1e-9 for k, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])}


def stage_share(trace: devtrace.Trace, scopes: Scopes | None, stage: str):
    """Percent of loop leaf-op time in ``stage``, or None where no op of
    the loop carries a ``tick.*`` scope (a program without the scopes)."""
    times = stage_times(trace, scopes)
    total = sum(times.values())
    if total <= 0 or not any(k.startswith("tick.") for k in times):
        return None
    return 100.0 * times.get(stage, 0.0) / total


def idle_by_span(trace: devtrace.Trace,
                 prefix: str = "run_plan.") -> list[list]:
    """Idle time of the first device inside the window, by the innermost
    host span named ``prefix...`` that covers each gap's middle (gaps that
    none covers go to "(no <prefix> span)")."""
    dev = next((evs for evs in trace.ops.values() if evs), [])
    lo, hi = trace.window
    gaps, t = [], lo
    clipped = [(max(s, lo), min(s + d, hi)) for _, s, d in dev
               if min(s + d, hi) > max(s, lo)]
    for s, e in devtrace.union(clipped):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = [h for h in trace.host if h[0].startswith(prefix)]
    outside = f"(no {prefix} span)"
    tot: dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        covering = [(dur, name) for name, start, dur in spans
                    if start <= mid < start + dur]
        name = min(covering)[1] if covering else outside
        tot[name] = tot.get(name, 0.0) + (e - s)
    return [[name, ns * 1e-9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])]


# ---------------------------------------------------------------------------
# The traced cut's programs, for the metric readers
# ---------------------------------------------------------------------------

def traced_plan(argv=None):
    """The plan of the traced cut, built from ``--workload`` and ``--seed``
    of the command line as run.py builds it, or None where the command line
    names no cell."""
    import plan

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    args, _ = ap.parse_known_args(sys.argv[1:] if argv is None else argv)
    if args.workload is None or args.seed is None:
        return None
    cell = plan.load_cell(args.workload)
    draws = plan.draw_calls(args.seed, 256, cell.points_per_variant,
                            plan.single_iso(cell.config))
    cut_s = int(cell.traffic["trace_cut_ticks"]) * float(cell.config["dt_s"])
    return plan.make_plan(cell, draws[0], seconds=cut_s, name="cut")


def hlo_texts(cut) -> list[str]:
    """The compiled HLO text of each group program of a plan, as `run_plan`
    dispatches it (compiled in set-up, so loaded from the persistent
    cache)."""
    from repro.netsim import engine, experiment

    _, cfgs, overrides, groups = experiment.resolve_plan(cut)
    texts = []
    for g in groups:
        sweep = experiment.group_sweep(cfgs, overrides, g)
        sweep, _ = experiment._shard_sweep(sweep, len(g.idxs), "auto")
        texts.append(engine.lower_sweep(g.cfg, sweep).compile().as_text())
    return texts


_last: tuple = (None, None)        # (trace, its scopes), `scoped`'s memo


def remember(trace: devtrace.Trace, scopes: Scopes | None) -> None:
    """Set the scopes `scoped` gives for ``trace``."""
    global _last
    _last = (trace, scopes)


def scoped(ctx: dict) -> Scopes | None:
    """The op scopes of the run's traced cut (None where there is no trace
    or the command line names no cell), mapped once a trace; the first
    mapping prints the cut by stage to standard error."""
    trace = ctx.get("trace")
    if trace is None:
        return None
    if _last[0] is not trace:
        cut = traced_plan()
        scopes = None
        if cut is not None:
            scopes, conflicts = scopes_from_hlo(trace, hlo_texts(cut))
            print(f"scopes: mapped from the compiled HLO ({conflicts} names "
                  f"in conflict)", file=sys.stderr)
            print_stages(trace, scopes, ctx["kernel_calls_per_device"])
        remember(trace, scopes)
    return _last[1]


def print_stages(trace: devtrace.Trace, scopes: Scopes,
                 kernel_calls: int) -> None:
    """The traced cut by name scope, to standard error: loop leaf-op time by
    tick stage, the kernel wrapper's pack and unpack, device idle time by
    `run_plan` phase, and the CC-tick calls found under ``tick.cc_update``
    beside the count that ``devtrace.cc_tick_ops`` expects."""
    ops = loop_ops(trace, scopes)
    stages = stage_times(trace, scopes)
    total = sum(stages.values())
    n_dev = max(1, devtrace.n_devices(trace))
    print(f"stages: {len(ops)} loop leaf ops, {total * 1e3:.3f} ms a device, "
          f"{len(ops) / max(1, kernel_calls * n_dev):.1f} ops a scan step",
          file=sys.stderr)
    for stage, secs in stages.items():
        print(f"  {stage:<18} {secs * 1e3:9.3f} ms "
              f"{100 * secs / total if total else 0:6.2f}%", file=sys.stderr)
    cc = stage_times(trace, scopes, prefixes=("cc.",))
    print("  cc: " + " ".join(f"{k}={v * 1e3:.3f}ms" for k, v in cc.items()
                              if k != "unscoped"), file=sys.stderr)
    print("idle_by_span: " + " ".join(
        f"{k}={v * 1e3:.3f}ms" for k, v in idle_by_span(trace)),
        file=sys.stderr)
    under = sum(1 for name, _, _, scope in ops
                if devtrace.KERNEL_MATCH in name
                and stage_of(scope) == "tick.cc_update")
    print(f"cc_tick: mosaic_calls_under_tick.cc_update={under} "
          f"cc_tick_calls_expected={kernel_calls * n_dev}", file=sys.stderr)


def share(ctx: dict, stage: str):
    """A metric reader's value: ``stage``'s percent of the traced cut's
    loop leaf-op time, or None where the program has no scopes."""
    trace = ctx.get("trace")
    if trace is None:
        return None
    scopes = scoped(ctx)
    return None if scopes is None else stage_share(trace, scopes, stage)
