"""The window's `run_plan` phases and set-up's jit seconds, read from the
program's log of finished spans (`repro.netsim.counters.recent_spans`).

A plan call logs its ``run_plan.prepare`` span first, then per group
``run_plan.stack``, ``run_plan.device`` and ``run_plan.postprocess``; a call
is a prepare span and the spans after it up to the next.  run.py's call
records carry each group's ``wall_s``, which is the group's
``run_plan.device`` span's seconds, so a window call is found in the log as
the call whose device spans read exactly those seconds.  Set-up's first
warm call is the process's first call.  A program that logs no spans (or a
log that no longer holds a call) reads nothing.
"""
from __future__ import annotations

import sys

PHASES = ("prepare", "stack", "device", "postprocess")


def plan_calls(spans) -> list[list]:
    """The ``run_plan.*`` spans of a log, cut into plan calls."""
    calls: list[list] = []
    for sp in spans:
        if sp.name == "run_plan.prepare":
            calls.append([sp])
        elif sp.name.startswith("run_plan.") and calls:
            calls[-1].append(sp)
    return calls


def _log():
    from repro.netsim import counters

    read = getattr(counters, "recent_spans", None)
    return None if read is None else read()


_printed: set = set()


def window_phases(calls: list[dict], log=None) -> dict[str, float] | None:
    """Seconds of each phase (``PHASES``) summed over the window's calls
    (run.py's call records), or None where the log (the program's, unless
    given) does not hold them all; prints the phases as shares of the
    calls' wall time once a window."""
    log = _log() if log is None else log
    if not log or not calls:
        return None
    by_walls = {tuple(sp.seconds for sp in call
                      if sp.name == "run_plan.device"): call
                for call in plan_calls(log)}
    tot = dict.fromkeys(PHASES, 0.0)
    for c in calls:
        found = by_walls.get(tuple(g["wall_s"] for g in c["groups"]))
        if found is None:
            return None
        for sp in found:
            tot[sp.name.removeprefix("run_plan.")] += sp.seconds
    if id(calls) not in _printed:
        _printed.add(id(calls))
        wall = sum(c["wall_s"] for c in calls)
        host = wall - sum(g["wall_s"] for c in calls for g in c["groups"])
        inside = tot["prepare"] + tot["stack"] + tot["postprocess"]
        print("phases: " + " ".join(
            f"{p}_pct={100 * tot[p] / wall:.3f}" for p in PHASES)
            + f" host_pct={100 * host / wall:.3f}"
            f" uncovered_pct={100 * (host - inside) / wall:.3f}",
            file=sys.stderr)
    return tot


def first_call_jit(log=None) -> dict[str, dict[str, float]] | None:
    """The jit seconds (``counters.JIT_KINDS``) of the process's first plan
    call, by phase, or None where the log (the program's, unless given) no
    longer starts at the process's first span; prints them once."""
    log = _log() if log is None else log
    if not log or log[0].seq != 0:
        return None
    calls = plan_calls(log)
    if not calls:
        return None
    out: dict[str, dict[str, float]] = {}
    for sp in calls[0]:
        phase = out.setdefault(sp.name.removeprefix("run_plan."), {})
        for kind, secs in sp.jit_s.items():
            phase[kind] = phase.get(kind, 0.0) + secs
    if "first_call_jit" not in _printed:
        _printed.add("first_call_jit")
        kinds: dict[str, float] = {}
        for phase in out.values():
            for kind, secs in phase.items():
                kinds[kind] = kinds.get(kind, 0.0) + secs
        print("setup_jit (warm call): " + " ".join(
            f"{k}_s={v:.3f}" for k, v in kinds.items()) + " by phase: "
            + " ".join(f"{p}_s={sum(v.values()):.3f}"
                       for p, v in out.items()), file=sys.stderr)
    return out
