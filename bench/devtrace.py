"""Reduce a profiler trace of the traced window to device metrics.

A trace is reduced from a neutral form, so that the reduction can be checked
on a small recorded trace: ``ops`` maps each device to its operations as
``(name, start_ns, duration_ns)``, ``host`` lists the host's spans the same
way (the Python tracer's function spans and the benchmark's own
annotations), and ``window`` is the traced call's ``(start_ns, end_ns)`` on
the same clock.

On a TPU v5e the profiler's "XLA Ops" line names each op by its HLO
instruction text.  A loop op (``%while``) spans the ops of its body, so the
breakdown counts leaf ops only.  A Pallas kernel appears as a call whose
text holds ``custom_call_target="tpu_custom_call"``; its Pallas name is not
kept there.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os

WINDOW_SPAN = "bench.traced_call"
# how a Mosaic kernel shows in a TPU trace; the CC tick's calls are told
# from any other's by their number (cc_tick_ops)
KERNEL_MATCH = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[tuple[str, int, int]]]
    host: list[tuple[str, int, int]]
    window: tuple[int, int]


def load(profile_dir: str) -> Trace:
    """Read the ``.xplane.pb`` the JAX profiler wrote under ``profile_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {profile_dir}, "
                           f"found {files}")
    data = ProfileData.from_file(files[0])
    ops: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [(_compact(e.name), int(e.start_ns),
                                        int(e.duration_ns))
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in line.events]
                # the thread that ran the traced call: its Python spans
                # say what the host was doing
                if any(ev[0] == WINDOW_SPAN for ev in events):
                    host += events
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span, found "
                           f"{len(spans)}")
    _, start, dur = spans[0]
    return Trace(ops=ops, host=host, window=(start, start + dur))


def _compact(hlo: str) -> str:
    """An op's HLO name, with the kernel's mark kept: the full instruction
    text runs to kilobytes, and a trace holds 10^5 ops."""
    head = hlo.split(" ", 1)[0]
    return f"{head} {KERNEL_MATCH}" if KERNEL_MATCH in hlo else head


def _clip(events, window):
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(trace: Trace) -> float:
    """Union of operation intervals inside the window, averaged over the
    devices that ran anything."""
    per = [sum(e - s for s, e in union((s, e) for _, s, e in
                                      _clip(evs, trace.window)))
           for evs in trace.ops.values() if evs]
    return sum(per) / len(per) if per else 0.0


def n_devices(trace: Trace) -> int:
    """Devices that ran anything in the trace."""
    return sum(1 for evs in trace.ops.values() if evs)


def window_ns(trace: Trace) -> int:
    return trace.window[1] - trace.window[0]


def kernel_ops(trace: Trace, kernel: str) -> list[tuple[str, int, int]]:
    """Every device operation whose name carries the kernel's name."""
    return [ev for evs in trace.ops.values() for ev in _clip(evs, trace.window)
            if kernel in ev[0]]


def cc_tick_ops(trace: Trace, calls_per_device: int):
    """The CC-tick kernel's calls in the window, or None where the Mosaic
    calls there are not exactly one per scan step on each device: the
    trace does not name a Mosaic kernel, so another kernel in the tick would
    otherwise be counted as the CC tick's."""
    ops = kernel_ops(trace, KERNEL_MATCH)
    if not ops or len(ops) != calls_per_device * n_devices(trace):
        return None
    return ops


def leaves(events):
    """The operations that hold no other: a loop or call op whose interval
    covers the ops of its body is left out, so that time is not counted
    twice."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = []
    for i, (name, s, e) in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][1] < e and evs[i + 1][2] <= e:
            continue
        out.append((name, s, e))
    return out


def short_name(hlo: str) -> str:
    """An op's HLO instruction name (``%fusion.12``) without its text."""
    return hlo.split(" ", 1)[0]


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """Leaf device operations by total time (seconds, averaged over
    devices), named by their HLO instruction name."""
    tot: dict[str, float] = {}
    for evs in trace.ops.values():
        for name, s, e in leaves(_clip(evs, trace.window)):
            key = short_name(name)
            tot[key] = tot.get(key, 0.0) + (e - s)
    n_dev = max(1, sum(1 for evs in trace.ops.values() if evs))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / n_dev * 1e-9] for name, ns in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """Idle time of the first device inside the window, by what the host was
    doing: each gap goes to the shortest host span (other than the window's
    own) that covers its middle, and gaps are summed per span name."""
    dev = next((evs for evs in trace.ops.values() if evs), [])
    lo, hi = trace.window
    busy = union((s, e) for _, s, e in _clip(dev, trace.window))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    host = sorted((h for h in trace.host if h[0] != WINDOW_SPAN),
                  key=lambda h: h[1])
    tot: dict[str, float] = {}
    active: list = []          # heap of (duration, end, name), lazily pruned
    i = 0
    for s, e in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (s + e) / 2
        while i < len(host) and host[i][1] <= mid:
            name, start, dur = host[i]
            heapq.heappush(active, (dur, start + dur, name))
            i += 1
        while active and active[0][1] <= mid:
            heapq.heappop(active)
        name = active[0][2] if active else "(no host span)"
        tot[name] = tot.get(name, 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]
