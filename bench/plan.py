"""Build a cell's experiment plan from its configuration and traffic files.

A cell is ``<config>.<traffic>``: ``configs/<config>.json`` describes the
deployment (fabric, jobs and their Table 1 phase programs, transport, RED,
tick), ``traffic/<traffic>.json`` the sweep run on it (scheme variants,
points per variant, the traced cut's length).  This module holds the arithmetic that turns
the two into a `repro.netsim.Plan`; it is the benchmark's own copy, so a
change to the program's benchmark suites cannot move the yardstick.

Every plan call draws fresh per-point seeds from the run's ``--seed``.  The
seed also permutes which job index carries which of the five single-phase
(data-parallel) programs, so each point's per-job answers are its own: a
result handed to the wrong point reads as wrong, not as noise.  The set of
programs is the same for every seed, only their order moves, so every call
does the same work.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
GBPS = 1e9 / 8.0
# the least relative difference in isolation iteration time that makes two
# program orders of one call tell apart (see draw_calls)
DISTINCT = 0.3


def load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict

    @property
    def variants(self) -> tuple[str, ...]:
        return tuple(self.traffic["variants"])

    @property
    def points_per_variant(self) -> int:
        return int(self.traffic["points_per_variant"])

    @property
    def n_points(self) -> int:
        return len(self.variants) * self.points_per_variant


def load_cell(name: str) -> Cell:
    config, sep, traffic = name.partition(".")
    if not sep:
        raise ValueError(f"cell {name!r} is not <config>.<traffic>")
    return Cell(name, load_json("configs", config), load_json("traffic", traffic))


# ---------------------------------------------------------------------------
# Deployment arithmetic
# ---------------------------------------------------------------------------

def job_classes(config: dict) -> list[str]:
    """The program name each job index runs before the per-point shuffle:
    job j runs ``cycle[j mod len(cycle)]``."""
    cycle = config["jobs"]["cycle"]
    return [cycle[j % len(cycle)] for j in range(int(config["jobs"]["n"]))]


def shuffled_programs(config: dict, order: tuple[int, ...]) -> list[str]:
    """Per-job program names under one point's permutation ``order`` of the
    single-phase programs (multi-phase programs keep their job indices)."""
    classes = job_classes(config)
    single = [p for p in config["jobs"]["cycle"]
              if len(config["programs"][p]["compute_s"]) == 1]
    remap = {p: single[i] for p, i in zip(single, order)}
    return [remap.get(p, p) for p in classes]


def leaf_pairs(config: dict) -> list[tuple[int, int]]:
    """Two-tier placement: job j sends from leaf j mod L to the leaf
    1 + (j div L) mod (L-1) further on, so every leaf pair is used and each
    leaf uplink and downlink carries n/L jobs."""
    n_leaves = int(config["fabric"]["leaves"])
    pairs = []
    for j in range(int(config["jobs"]["n"])):
        src = j % n_leaves
        hop = 1 + (j // n_leaves) % (n_leaves - 1)
        pairs.append((src, (src + hop) % n_leaves))
    return pairs


def job_paths(config: dict) -> tuple[list[float], list[list[int]]]:
    """Link capacities (bytes/s) and each job's ordered link ids.  Link ids
    follow the program's topology builders: the dumbbell has one link; the
    two-tier fabric has uplinks 0..L-1, downlinks L..2L-1 and the core 2L."""
    fab = config["fabric"]
    if fab["kind"] == "dumbbell":
        return [fab["link_gbps"] * GBPS], [[0]] * int(config["jobs"]["n"])
    if fab["kind"] == "two_tier":
        n = int(fab["leaves"])
        caps = ([fab["leaf_gbps"] * GBPS] * (2 * n) + [fab["core_gbps"] * GBPS])
        return caps, [[a, 2 * n, n + b] for a, b in leaf_pairs(config)]
    raise ValueError(f"unknown fabric kind {fab['kind']!r}")


def jobs_per_link(config: dict) -> int:
    """Jobs sharing the busiest bottleneck link."""
    _, paths = job_paths(config)
    counts: dict[int, int] = {}
    for path in paths:
        for link in path:
            counts[link] = counts.get(link, 0) + 1
    caps, _ = job_paths(config)
    slowest = min(caps)
    return max(c for l, c in counts.items() if caps[l] == slowest)


def sim_time(config: dict) -> float:
    """Simulated seconds for every job to finish ``iterations`` iterations.

    At a fair share of its busiest link a job's iteration takes its compute
    plus its bytes at 1/jobs_per_link of the link; the margin covers links
    that run below capacity."""
    caps, _ = job_paths(config)
    k = jobs_per_link(config)
    scale = float(config["work_scale"])
    programs = [config["programs"][p] for p in config["jobs"]["cycle"]]
    worst = max(sum(p["compute_s"]) + k * sum(p["comm_bytes"]) / min(caps)
                for p in programs)
    return float(config["sim_margin"]) * int(config["iterations"]) * worst * scale


def n_ticks(config: dict, seconds: float | None = None) -> int:
    return int(round((sim_time(config) if seconds is None else seconds)
                     / float(config["dt_s"])))


# ---------------------------------------------------------------------------
# Per-call seeds
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PointDraw:
    """What one plan point is run with: its simulator seed (a traced int32
    leaf) and its permutation of the single-phase programs."""

    seed: int
    order: tuple[int, ...]


def draw_calls(run_seed: int, n_calls: int, n_points: int,
               single_iso: list[float]) -> list[list[PointDraw]]:
    """Per-call point draws, a pure function of the run's seed.

    ``single_iso`` is each single-phase program's isolation iteration time.
    Within one call the ``n_points`` points of a variant get program orders
    that differ at some job index by a program at least ``DISTINCT`` slower
    or faster, where the programs allow it, so that one point's answers
    handed to another read as wrong.  The OFF and WI point of one index
    share their order, so the two schemes stay paired."""
    rng = np.random.default_rng(int(run_seed))
    perms = list(itertools.permutations(range(len(single_iso))))
    iso = np.asarray(single_iso, np.float64)

    def apart(a, b) -> bool:
        x, y = iso[list(a)], iso[list(b)]
        return bool(np.max(np.abs(x - y) / np.minimum(x, y)) >= DISTINCT)

    calls = []
    for _ in range(n_calls):
        seeds = rng.integers(1, 2**31 - 1, size=n_points)
        chosen: list[tuple[int, ...]] = []
        for i in rng.permutation(len(perms)):
            if all(apart(perms[i], c) for c in chosen):
                chosen.append(perms[i])
            if len(chosen) == n_points:
                break
        while len(chosen) < n_points:          # the programs allow no more
            chosen.append(perms[int(rng.integers(len(perms)))])
        calls.append([PointDraw(int(s), o) for s, o in zip(seeds, chosen)])
    return calls


def single_iso(config: dict) -> list[float]:
    """Isolation iteration time of each single-phase program (compute plus
    bytes at the slowest link's rate), in the order of ``jobs.cycle``."""
    link = min(job_paths(config)[0])
    return [sum(p["compute_s"]) + sum(p["comm_bytes"]) / link
            for name in config["jobs"]["cycle"]
            for p in [config["programs"][name]] if len(p["compute_s"]) == 1]


# ---------------------------------------------------------------------------
# The program's side: a repro.netsim.Plan
# ---------------------------------------------------------------------------

def check_programs(config: dict) -> None:
    """The program's Table 1 table must hold the configuration's programs."""
    from repro import workload

    for name, want in config["programs"].items():
        have = workload.profile_for(name)
        if (list(have.compute_s) != want["compute_s"]
                or list(have.comm_bytes) != want["comm_bytes"]):
            raise ValueError(f"program {name!r}: the program's Table 1 entry "
                             f"{have} differs from the configuration {want}")


def topology(config: dict):
    from repro import netsim

    fab = config["fabric"]
    spj = int(config["transport"]["sockets_per_job"])
    if fab["kind"] == "dumbbell":
        return netsim.dumbbell(int(config["jobs"]["n"]), sockets_per_job=spj,
                               cap_gbps=float(fab["link_gbps"]))
    return netsim.two_tier(leaf_pairs(config), n_leaves=int(fab["leaves"]),
                           sockets_per_job=spj,
                           leaf_up_gbps=float(fab["leaf_gbps"]),
                           core_gbps=float(fab["core_gbps"]))


def protocol(config: dict, variant: str):
    from repro.core import Algo, CCParams, MLTCPConfig, Variant

    tr, ml = config["transport"], config["mltcp"]
    wi = ml["WI"]
    return MLTCPConfig(
        cc=CCParams(algo=int(Algo[tr["algo"].upper()]),
                    variant=int(Variant[variant]),
                    mss=float(tr["mss_bytes"]), rtt=float(tr["rtt_s"]),
                    tick_dt=float(config["dt_s"]),
                    min_cwnd=float(tr["min_cwnd"]),
                    init_cwnd=float(tr["init_cwnd"]),
                    reno_beta=float(tr["reno_beta"])),
        f_spec=ml["f"], slope=float(wi["slope"]),
        intercept=float(wi["intercept"]), favoritism=ml["favoritism"],
        aggregate_by_job=bool(ml["aggregate_by_job"]),
        init_comm_gap=float(ml["init_comm_gap_s"]), g=float(ml["g"]),
        gamma=float(ml["gamma"]))


def make_plan(cell: Cell, draws: list[PointDraw], *,
              seconds: float | None = None, name: str = ""):
    """The `repro.netsim.Plan` of one call: variants x points, each point
    with its own seed and program order.  ``seconds`` overrides the
    simulated length (the traced run's cut); everything else, shapes
    included, stays as in the timed calls."""
    from repro import netsim, workload

    config = cell.config
    topo = topology(config)
    scale = float(config["work_scale"])
    length = sim_time(config) if seconds is None else seconds
    red = config["red"]
    link_bps = min(job_paths(config)[0])
    kernel = {"use_pallas_kernel": True} if any(
        f.name == "use_pallas_kernel"
        for f in dataclasses.fields(netsim.SimConfig)) else {}

    def build(pt):
        draw = draws[pt["point"]]
        names = shuffled_programs(config, draw.order)
        profiles = [workload.profile_for(n).scaled(scale) for n in names]
        jobs = workload.jobspec_from_profiles(profiles,
                                              link_bytes_per_s=link_bps)
        return netsim.SimConfig(
            topo=topo, jobs=jobs, protocol=protocol(config, pt["variant"]),
            sim_time=length, dt=float(config["dt_s"]), seed=draw.seed,
            red_qmin=float(red["qmin_bytes"]), red_qmax=float(red["qmax_bytes"]),
            red_pmax=float(red["pmax"]), buffer_bytes=float(red["buffer_bytes"]),
            **kernel)

    return netsim.Plan(
        name=name or cell.name,
        axes=(netsim.Axis("variant", cell.variants, kind="static"),
              netsim.Axis("point", tuple(range(len(draws))), kind="static")),
        build=build)
