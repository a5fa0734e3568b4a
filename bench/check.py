"""The comparison that decides ``correct``.

Every point a timed call simulated is simulated again by the plain
reference (reference.py) with the same seed and program order, and the two
are compared by statistics that a float32 trajectory keeps while its ticks
part (see reference.py): per job, the iteration rate (one over the mean
iteration time) and the bytes delivered over the run, per link the mean
utilization, and per point how unevenly the jobs that send at once share
the link.  That last one is what MLTCP-WI is for: Algorithm 1's byte ratio
scales each job's additive increase by F = slope * ratio + intercept, so a
job further into its communication phase takes more than a fair share, and
a WI run shares more unevenly than Reno's fair share would.  A job with no
iteration to measure has the rate 0, so every number stays finite however
far off a run is.  Each number has
a limit per cell in ``limits/<cell>.json``, set from the readings that
PERF.md lists.
"""
from __future__ import annotations

import json

import numpy as np

from plan import HERE

# iterations skipped at the start of every job: the first one starts with
# every job in step and measures the synchronized start, not the sharing
WARMUP_ITERS = 1
# a job sends in a trace chunk where it delivers at least this share of the
# chunk's busiest job's bytes
SENDING = 1e-3


def load_limits(cell: str) -> dict:
    path = HERE / "limits" / f"{cell}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no limits for cell {cell!r} ({path})")
    return json.loads(path.read_text())["limits"]


def _iter_rate(iters) -> float:
    x = np.asarray(iters, np.float64)[WARMUP_ITERS:]
    return float(1.0 / np.mean(x)) if x.size else 0.0


def share_spread(trace_jobtput) -> float:
    """Mean over trace chunks of the coefficient of variation (standard
    deviation over mean) of the throughputs of the jobs sending in that
    chunk, over the chunks where two or more send.  A job sends in a chunk
    where it delivers at least ``SENDING`` of the chunk's busiest job.  NaN
    where no chunk has two jobs sending."""
    x = np.asarray(trace_jobtput, np.float64)
    top = x.max(axis=1, keepdims=True)
    sending = (x > SENDING * top) & (top > 0)
    n = sending.sum(axis=1)
    rows = n >= 2
    if not rows.any():
        return float("nan")
    x, sending, n = x[rows], sending[rows], n[rows]
    mean = np.where(sending, x, 0).sum(axis=1) / n
    var = np.where(sending, (x - mean[:, None]) ** 2, 0).sum(axis=1) / n
    return float(np.mean(np.sqrt(var) / mean))


def point_stats(iter_times, trace_util, trace_jobtput, trace_t) -> dict:
    """What is compared of one point, from either side."""
    t = np.asarray(trace_t, np.float64)
    chunk_s = t[1] - t[0] if t.size > 1 else float(t[0])
    return dict(
        iters=np.asarray([len(x) for x in iter_times]),
        rate=np.asarray([_iter_rate(x) for x in iter_times]),
        bytes=np.asarray(trace_jobtput, np.float64).sum(axis=0) * chunk_s,
        util=np.asarray(trace_util, np.float64).mean(axis=0),
        share=np.asarray([share_spread(trace_jobtput)]))


def finite(stats: dict) -> bool:
    return all(np.all(np.isfinite(v)) for v in stats.values())


def _rel(a, b):
    """Relative gap of ``a`` from ``b``; two equal values (two zero rates:
    no iteration to measure on either side) have none."""
    return np.where(a == b, 0.0, np.abs(a - b) / np.abs(b))


@np.errstate(divide="ignore", invalid="ignore")
def numbers(variants: list[str], prog: list[dict], ref: list[dict]) -> dict:
    """The compared numbers over all points: the widest relative gap of a
    job's iteration rate, the mean signed gap of the rates per scheme
    variant (the larger in size), the widest relative gap of a job's bytes
    delivered, the widest gap of a link's mean utilization, and the widest
    relative gap of a point's share spread (`share_spread`).  A non-finite
    gap (a reference job with no iteration where the program has some)
    reads as infinitely far off."""
    gaps, signed, bgaps, ugaps, sgaps = [], {}, [], [], []
    for v, p, r in zip(variants, prog, ref):
        g = _rel(p["rate"], r["rate"])
        gaps.append(np.where(np.isfinite(g), g, np.inf))
        s = np.where(p["rate"] == r["rate"], 0.0,
                     (p["rate"] - r["rate"]) / r["rate"])
        signed.setdefault(v, []).append(np.where(np.isfinite(s), s, np.inf))
        b = _rel(p["bytes"], r["bytes"])
        bgaps.append(np.where(np.isfinite(b), b, np.inf))
        u = np.abs(p["util"] - r["util"])
        ugaps.append(np.where(np.isfinite(u), u, np.inf))
        sh = _rel(p["share"], r["share"])
        sgaps.append(np.where(np.isfinite(sh), sh, np.inf))
    return {
        "iter_gap": float(np.max(np.concatenate(gaps))),
        "iter_bias": float(max(abs(np.mean(np.concatenate(x)))
                               for x in signed.values())),
        "bytes_gap": float(np.max(np.concatenate(bgaps))),
        "util_gap": float(np.max(np.concatenate(ugaps))),
        "share_gap": float(np.max(np.concatenate(sgaps))),
    }


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(every number within its limit, {name: {value, limit}})."""
    table = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
