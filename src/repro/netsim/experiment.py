"""Declarative experiment plans — one sweep surface over every axis.

The paper's evaluation is a matrix of sweeps: Fig. 10 varies job count x
seed, Figs. 15-17 vary aggressiveness functions and protocol scalars, the
baselines add scheme axes (OFF / WI / MD / Static / Cassini).  Some of those
axes are *dynamic* (traced scalars the batched sweep engine already vmaps
over — slope, intercept, g, gamma, RED thresholds, seeds, per-job factors,
the `job_active` mask) and some are *static* (they shape the traced program
— algorithm, variant, F family, topology, workload).  Before this module
every benchmark hand-wired that split; now callers declare a `Plan`:

    plan = Plan(
        name="fig10-reno",
        axes=(Axis("variant", ("OFF", "WI")),
              Axis("n_jobs", (2, 3, 4, 5, 6, 7, 8)),
              Axis("seed", (1, 2, 3))),
        build=lambda pt: build_cfg_for(pt["variant"], pt["n_jobs"]),
    )
    result = run_plan(plan)
    sweep_speedup_stats(result.select(variant="OFF", n_jobs=4),
                        result.select(variant="WI", n_jobs=4))

and `run_plan` does the partitioning (DESIGN.md §5):

  1. enumerate the cartesian product of the axes (minus `where`-filtered
     points) and build each point's `SimConfig`;
  2. group points by *static signature* — the config with every dynamic
     field canonicalized, workload *values* (phase programs, straggle
     probabilities, Cassini schedules, Static factors) included — so
     points that only differ dynamically share one compile group;
  3. merge groups that differ only in workload *shape*: if a point's
     (topology, job structure) equal the *restriction* of a larger
     point's to its first n jobs, the smaller point runs on the larger
     fabric with a `job_active` mask (the padded-jobs axis), joining its
     compile group; phase programs are column-padded to the group's
     P_max (zero columns are inert under the `n_phases` mask);
  4. lower each group's points onto the `simulate_sweep` K axis — one
     trace, one compile, K simulations per group — optionally sharding K
     across local devices; the group's iteration record is sized to the
     most iterations its points can finish (`_bound_record`);
  5. post-process each point with its own (unpadded) config and attach a
     `SweepPoint`, so every `SimResult` names its axis coordinates.

A Fig. 10-style plan (7 job counts x 3 seeds x {OFF, WI}) thus compiles
*two* programs (one per variant) instead of 14+, and the straggler /
partial-compat grids (which sweep workload values) collapse the same way.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.netsim import counters
from repro.netsim import metrics
from repro.netsim.engine import (
    SimConfig,
    JobSpec,
    SweepParams,
    SweepPoint,
    iteration_bound,
    simulate_sweep,
    sweep_of,
)
from repro.netsim.telemetry import TelemetrySpec
from repro.netsim.topology import Topology

__all__ = ["Axis", "Plan", "PlanResult", "GroupError", "GroupProfile",
           "PlanProfile", "run_plan", "restrict_workload",
           "resolve_plan", "group_sweep"]

_DYNAMIC_FIELDS = frozenset(SweepParams._fields)


# ---------------------------------------------------------------------------
# Plan declaration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Axis:
    """One named dimension of an experiment plan.

    ``values`` are the labels enumerated into the cartesian product; every
    point's full label dict is passed to `Plan.build`.

    kind:
      * "dynamic" — the axis targets a `SweepParams` field and rides the
        batched sweep (no recompilation across its values);
      * "static"  — the axis only shapes the config via `Plan.build`
        (algorithm, variant, F family, workload, ...);
      * "auto"    — dynamic iff the target field names a SweepParams field.

    ``field`` overrides the targeted SweepParams field (default: the axis
    name), and ``resolve`` maps a label to the field's actual value — e.g.
    an axis named "solo" with values ("all", 0, 1) can resolve to
    `job_active` masks while results stay selectable by the human label.

    ``field="*"`` targets *several* sweep fields at once: the resolved
    value must be a ``{sweep field: value}`` dict — or a callable taking
    the point's built `SimConfig` and returning one, for values whose
    shapes depend on the config (a fault schedule's blackhole table is
    [E, n_flows], and n_flows varies with the point's socket counts).
    The fault-schedule axis of `benchmarks/churn.py` is the canonical use:
    one human label resolves to the whole ``faults.FaultSchedule
    .overrides()`` dict, so schedules ride the batched sweep.
    """

    name: str
    values: tuple
    kind: str = "auto"
    field: Optional[str] = None
    resolve: Optional[Callable[[object], object]] = None

    def __post_init__(self):
        if self.kind not in ("auto", "dynamic", "static"):
            raise ValueError(f"axis {self.name!r}: unknown kind {self.kind!r}")
        if not len(self.values):
            raise ValueError(f"axis {self.name!r} has no values")
        object.__setattr__(self, "values", tuple(self.values))

    @property
    def target(self) -> str:
        return self.field if self.field is not None else self.name

    def is_dynamic(self) -> bool:
        if self.kind == "auto":
            return self.target == "*" or self.target in _DYNAMIC_FIELDS
        return self.kind == "dynamic"


@dataclasses.dataclass(frozen=True)
class Plan:
    """A declarative experiment: named axes x a config builder.

    ``build`` receives one point's ``{axis name: value}`` dict and returns
    that point's `SimConfig`.  It may ignore dynamic axes entirely —
    `run_plan` threads their (resolved) values into the sweep afterwards —
    but static axes (job count, scheme, F family, ...) must be reflected in
    the returned config.  ``where`` optionally prunes points from the
    cartesian product (e.g. baseline points that only need one slope).
    """

    axes: tuple[Axis, ...]
    build: Callable[[dict], SimConfig]
    name: str = ""
    where: Optional[Callable[[dict], bool]] = None

    def __post_init__(self):
        names = [ax.name for ax in self.axes]
        if len(set(names)) != len(names):
            raise ValueError(f"plan {self.name!r}: duplicate axis names {names}")

    def points(self) -> list[dict]:
        """The cartesian product of axis values (last axis fastest), minus
        `where`-filtered points, as one label dict per point."""
        pts = [{}]
        for ax in self.axes:
            pts = [{**p, ax.name: v} for p in pts for v in ax.values]
        if self.where is not None:
            pts = [p for p in pts if self.where(p)]
        if not pts:
            raise ValueError(f"plan {self.name!r} has no points")
        return pts


# ---------------------------------------------------------------------------
# Workload restriction — the padded-jobs merge test
# ---------------------------------------------------------------------------

def restrict_workload(topo: Topology, jobs: JobSpec,
                      n_jobs: int) -> tuple[Topology, JobSpec]:
    """The sub-workload on the first ``n_jobs`` jobs of a fabric.

    A smaller plan point may run on a larger point's fabric (with trailing
    jobs masked off) exactly when its own (topo, jobs) equal this
    restriction — same links, same flows for the kept jobs, same phase
    programs.  Flows of kept jobs must form a prefix of the flow axis so
    the lane-stable RNG draws identical randomness (see `_lane_uniform`).
    """
    keep = topo.flow_to_job < n_jobs
    topo_r = Topology(cap=topo.cap, hops=topo.hops[keep],
                      flow_to_job=topo.flow_to_job[keep], names=topo.names)
    jobs_r = JobSpec(compute=jobs.compute[:n_jobs],
                     comm_bytes=jobs.comm_bytes[:n_jobs],
                     n_phases=jobs.n_phases[:n_jobs],
                     start_offset=jobs.start_offset[:n_jobs],
                     straggle_prob=jobs.straggle_prob[:n_jobs],
                     iso_iter_time=jobs.iso_iter_time[:n_jobs])
    return topo_r, jobs_r


def _pad_cols(a: np.ndarray, width: int, fill) -> np.ndarray:
    if a.shape[1] >= width:
        return a
    pad = np.full((a.shape[0], width - a.shape[1]), fill, a.dtype)
    return np.concatenate([a, pad], axis=1)


def _same_workload(ta: Topology, ja: JobSpec, tb: Topology, jb: JobSpec) -> bool:
    """Value equality modulo behaviour-neutral padding (zero phase columns,
    -1 hop columns)."""
    if ta.names != tb.names or not np.array_equal(ta.cap, tb.cap):
        return False
    if not np.array_equal(ta.flow_to_job, tb.flow_to_job):
        return False
    h = max(ta.hops.shape[1], tb.hops.shape[1])
    if not np.array_equal(_pad_cols(ta.hops, h, -1), _pad_cols(tb.hops, h, -1)):
        return False
    p = max(ja.compute.shape[1], jb.compute.shape[1])
    return (np.array_equal(_pad_cols(ja.compute, p, 0.0),
                           _pad_cols(jb.compute, p, 0.0))
            and np.array_equal(_pad_cols(ja.comm_bytes, p, 0.0),
                               _pad_cols(jb.comm_bytes, p, 0.0))
            and np.array_equal(ja.n_phases, jb.n_phases)
            and np.array_equal(ja.start_offset, jb.start_offset)
            and np.array_equal(ja.straggle_prob, jb.straggle_prob)
            and np.array_equal(ja.iso_iter_time, jb.iso_iter_time))


def _flows_are_job_prefix(topo: Topology, n_jobs: int) -> bool:
    """Flows of the first n_jobs jobs occupy the first flow lanes."""
    keep = topo.flow_to_job < n_jobs
    return bool(np.all(np.nonzero(keep)[0] == np.arange(int(keep.sum()))))


# ---------------------------------------------------------------------------
# Static signatures & compile groups
# ---------------------------------------------------------------------------

def _canonical_jobs(jobs: JobSpec) -> JobSpec:
    """The job structure with every traced workload value zeroed.

    Phase-program values, straggle probabilities and isolation times ride
    the sweep (`SweepParams.compute` / `comm_bytes` / `straggle_prob` /
    `iso_iter`); only the array shapes, `n_phases` and `start_offset`
    remain structural.
    """
    return JobSpec(compute=np.zeros_like(jobs.compute),
                   comm_bytes=np.zeros_like(jobs.comm_bytes),
                   n_phases=jobs.n_phases,
                   start_offset=jobs.start_offset,
                   straggle_prob=np.zeros_like(jobs.straggle_prob),
                   iso_iter_time=np.zeros_like(jobs.iso_iter_time))


def _canonical_cfg(cfg: SimConfig) -> SimConfig:
    """The config with every dynamic field pinned to a canonical value.

    Two points share a compile group iff their canonical configs are equal
    (after workload-shape merging); using the canonical config as the jit
    static argument also means re-running a plan with different seeds,
    scalars or workload values hits the exact same jit cache entry.

    The Static factors and the Cassini schedule canonicalize to None —
    their values are `SweepParams` leaves and their *presence* is
    normalized per group at lowering time (`_point_params`): a point
    without factors gets the all-negative "adaptive" sentinel, a point
    without a schedule gets all-zero periods (per-job off), both exact
    value-level no-ops in the traced program.
    """
    proto = dataclasses.replace(cfg.protocol, slope=0.0, intercept=0.0,
                                g=0.0, gamma=0.0, init_comm_gap=0.0)
    return dataclasses.replace(
        cfg, protocol=proto, seed=0,
        red_qmin=0.0, red_qmax=1.0, red_pmax=0.0,
        jobs=_canonical_jobs(cfg.jobs),
        static_job_factors=None, cassini=None)


def _no_workload(cfg: SimConfig) -> SimConfig:
    return dataclasses.replace(cfg, topo=None, jobs=None)


def _fabric_key(topo: Topology):
    return (topo.names, topo.cap.tobytes())


def _factors_need_split(cfg: SimConfig) -> bool:
    """True when Static-factor presence may not be mixed in one group.

    The fused kernel's adaptive branch (which sentinel factor entries
    select) runs only where the kernel would run the config without
    factors (`ops.cc_tick_fallback_reason`); otherwise a kernel-enabled
    group must keep factor-bearing and adaptive points apart so no
    sentinel ever reaches the kernel (pure-Static groups stay fused — all
    entries >= 0 mask the branch exactly).  The jnp oracle computes the
    true adaptive F, so non-kernel configs always mix.
    """
    if not cfg.use_pallas_kernel:
        return False
    from repro.kernels import ops
    return ops.cc_tick_fallback_reason(cfg.protocol, False) is not None


@dataclasses.dataclass
class _Group:
    """One compile group: a shared static config + its member points."""

    cfg: SimConfig               # canonical static config (largest fabric,
    #                              phase programs padded to the group P_max)
    idxs: list[int]              # plan-point indices, in plan order
    masked: bool                 # True iff job_active masks are needed
    factors: bool = False        # some member carries Static factors
    cassini: bool = False        # some member carries a Cassini schedule


def _pad_group_jobs(jobs: JobSpec, p_max: int) -> JobSpec:
    if jobs.compute.shape[1] >= p_max:
        return jobs
    return JobSpec(compute=_pad_cols(jobs.compute, p_max, 0.0),
                   comm_bytes=_pad_cols(jobs.comm_bytes, p_max, 0.0),
                   n_phases=jobs.n_phases,
                   start_offset=jobs.start_offset,
                   straggle_prob=jobs.straggle_prob,
                   iso_iter_time=jobs.iso_iter_time)


def _finish_group(cfgs: list[SimConfig], cfg_g: SimConfig,
                  members: list[int], masked: bool) -> _Group:
    p_max = max(cfgs[i].jobs.compute.shape[1] for i in members)
    if cfg_g.jobs.compute.shape[1] < p_max:
        cfg_g = dataclasses.replace(
            cfg_g, jobs=_pad_group_jobs(cfg_g.jobs, p_max))
    return _Group(cfg=cfg_g, idxs=sorted(members), masked=masked,
                  factors=any(cfgs[i].static_job_factors is not None
                              for i in members),
                  cassini=any(cfgs[i].cassini is not None for i in members))


def _compile_groups(cfgs: list[SimConfig], pad_jobs: bool) -> list[_Group]:
    canon = [_canonical_cfg(c) for c in cfgs]
    # Bucket by everything except the workload, then merge by workload
    # *shape* (the canonical jobs' zeroed values make `_same_workload` a
    # structural comparison).  Factor presence joins the key only when the
    # kernel cannot take the adaptive sentinel (_factors_need_split).
    buckets: dict = {}
    for i, c in enumerate(canon):
        fp = (cfgs[i].static_job_factors is not None
              if _factors_need_split(c) else None)
        if pad_jobs:
            key = ("pad", _no_workload(c), _fabric_key(c.topo), fp)
        else:
            key = ("exact", c, fp)
        buckets.setdefault(key, []).append(i)

    groups: list[_Group] = []
    for key, idxs in buckets.items():
        if key[0] == "exact":
            groups.append(_finish_group(cfgs, canon[idxs[0]], idxs,
                                        masked=False))
            continue
        remaining = list(idxs)
        while remaining:
            ref = max(remaining,
                      key=lambda i: (cfgs[i].jobs.n_jobs, cfgs[i].topo.n_flows))
            ref_topo, ref_jobs = cfgs[ref].topo, canon[ref].jobs
            members, rest = [], []
            for i in remaining:
                n = cfgs[i].jobs.n_jobs
                if (n <= ref_jobs.n_jobs
                        and _flows_are_job_prefix(ref_topo, n)
                        and _same_workload(*restrict_workload(ref_topo,
                                                              ref_jobs, n),
                                           cfgs[i].topo, canon[i].jobs)):
                    members.append(i)
                else:
                    rest.append(i)
            masked = any(cfgs[i].jobs.n_jobs < ref_jobs.n_jobs
                         for i in members)
            groups.append(_finish_group(cfgs, canon[ref], members, masked))
            remaining = rest
    # deterministic group order: by first member point
    groups.sort(key=lambda g: g.idxs[0])
    return groups


# ---------------------------------------------------------------------------
# Lowering a group onto the sweep axis
# ---------------------------------------------------------------------------

def _bound_record(cfg: SimConfig, sweep: SweepParams) -> SimConfig:
    """The group config with its iteration record cut to the power of two
    at or above the most iterations a point of ``sweep`` (the group's
    stacked values) can finish (`engine.iteration_bound`), and never above
    the config's own ``max_iters_recorded``.

    The bound reads only the phase programs and the job mask, so a re-run
    of the same programs in another order or with other seeds sizes the
    record alike and reuses the compiled program.
    """
    active = (None if sweep.job_active is None
              else np.asarray(sweep.job_active))
    bound = iteration_bound(cfg, np.asarray(sweep.compute), active)
    slots = 1 << max(bound - 1, 0).bit_length()
    return dataclasses.replace(
        cfg, max_iters_recorded=min(cfg.max_iters_recorded, slots))


def _pad_rows(x: np.ndarray, j: int, fill) -> np.ndarray:
    if x.shape[0] >= j:
        return x
    pad = np.full((j - x.shape[0],) + x.shape[1:], fill, x.dtype)
    return np.concatenate([x, pad], axis=0)


def _point_params(cfg: SimConfig, overrides: dict, group: _Group) -> SweepParams:
    """Resolve one point's unbatched SweepParams on the group's fabric.

    Scalar overrides of per-job fields broadcast across the point's own
    jobs; the workload leaves are then padded to the group's [J_ref, P_max]
    shape (zero rows for masked-off jobs, zero columns beyond `n_phases`);
    Static-factor / Cassini presence is normalized group-wide with exact
    value-level no-ops (the adaptive sentinel, zero periods).
    """
    from repro.netsim.engine import (  # single source of dtypes/shapes
        _FIELD_DTYPE,
        _point_shape,
    )

    params = sweep_of(cfg)
    for field, value in overrides.items():
        dtype = _FIELD_DTYPE.get(field, jnp.float32)
        a = np.asarray(value)
        shape = _point_shape(field, cfg)
        if a.ndim < len(shape):
            a = np.broadcast_to(a, shape)
        params = params._replace(**{field: jnp.asarray(a, dtype)})
    j_ref = group.cfg.jobs.n_jobs
    p_max = group.cfg.jobs.compute.shape[1]
    n = cfg.jobs.n_jobs

    def pad(x, fill=0.0, cols=False):
        x = np.asarray(x, np.float32)
        if cols:
            x = _pad_cols(x, p_max, 0.0)
        return jnp.asarray(_pad_rows(x, j_ref, fill))

    params = params._replace(
        compute=pad(params.compute, cols=True),
        comm_bytes=pad(params.comm_bytes, cols=True),
        straggle_prob=pad(params.straggle_prob),
        iso_iter=pad(params.iso_iter),
    )
    if group.factors:
        f = params.static_job_factors
        f = (np.full((n,), -1.0, np.float32) if f is None  # adaptive sentinel
             else np.asarray(f, np.float32))
        params = params._replace(static_job_factors=pad(f, fill=1.0))
    if group.cassini:
        off = params.cassini_offset
        per = params.cassini_period
        eps = params.cassini_eps
        off = np.zeros((n,), np.float32) if off is None else np.asarray(off)
        per = np.zeros((n,), np.float32) if per is None else np.asarray(per)
        params = params._replace(
            cassini_offset=pad(off), cassini_period=pad(per),
            cassini_eps=jnp.asarray(0.0 if eps is None else eps, jnp.float32))
    if params.job_active is not None:
        m = np.asarray(params.job_active, bool)
        if m.shape[0] < j_ref:     # caller mask on the point's own fabric
            m = np.concatenate([m, np.zeros((j_ref - m.shape[0],), bool)])
        params = params._replace(job_active=jnp.asarray(m))
    elif group.masked:
        mask = np.zeros((j_ref,), bool)
        mask[:n] = True
        params = params._replace(job_active=jnp.asarray(mask))
    if cfg.faults is not None:
        # fault tables are built on the point's own fabric; pad the job /
        # flow axis to the group's with identity values for the padded
        # lanes (inactive jobs stay inactive, padded flows never
        # blackhole).  Links are never padded — the pad-merge requires an
        # identical link fabric.  cfg.faults rides the canonical config,
        # so presence is uniform within a group.
        n_flows_g = group.cfg.topo.n_flows
        for fname, width, fill in (("fault_job_active", j_ref, False),
                                   ("fault_straggle", j_ref, 0.0),
                                   ("fault_blackhole", n_flows_g, False)):
            v = getattr(params, fname)
            if v is not None:
                a = _pad_cols(np.asarray(v), width, fill)
                params = params._replace(**{fname: jnp.asarray(a)})
    return params


def _stack_params(per_point: list[SweepParams]) -> SweepParams:
    out = {}
    for name in SweepParams._fields:
        vals = [getattr(p, name) for p in per_point]
        if all(v is None for v in vals):
            out[name] = None
        elif any(v is None for v in vals):
            raise ValueError(f"sweep field {name!r} set on only some points "
                             f"of one compile group")
        else:
            out[name] = jnp.stack([jnp.asarray(v) for v in vals])
    return SweepParams(**out)


def _shard_sweep(sweep: SweepParams, k: int,
                 shard) -> tuple[SweepParams, int]:
    """Optionally lay the K axis out across local devices.

    Pads K up to a multiple of the device count (repeating the last point;
    the surplus results are dropped after the run) and commits every leaf
    to a NamedSharding over a 1-D device mesh, so the jitted sweep program
    partitions the vmapped simulations across devices.  shard="auto" turns
    this on whenever more than one local device exists; single-device runs
    are returned untouched (identical jit cache keys to unsharded calls).
    """
    n_dev = jax.local_device_count()
    if shard == "auto":
        shard = n_dev > 1
    if not shard or n_dev <= 1:
        return sweep, k
    pad = (-k) % n_dev
    if pad:
        sweep = jax.tree_util.tree_map(
            lambda x: jnp.concatenate(
                [x, jnp.repeat(x[-1:], pad, axis=0)], axis=0), sweep)
    # local devices only: the pad above is computed from the local count,
    # and the sweep pytree is host-local data
    mesh = jax.sharding.Mesh(np.asarray(jax.local_devices()), ("k",))
    ns = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("k"))
    return jax.device_put(sweep, ns), k + pad


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GroupProfile:
    """Runtime profile of one compile group's sweep execution.

    Host seconds of the group's three `run_plan` phases, each a
    `counters.span` of the same name: stacking the points' parameters
    (``stack_s``, ``run_plan.stack``), the device call with its
    ``block_until_ready`` (``wall_s``, ``run_plan.device``; trace and
    compile included when the group traced a new program, ``traced``) and
    the per-point postprocess (``postprocess_s``, ``run_plan.postprocess``).  ``iter_slots`` is the length of the
    group's iteration record.
    """

    n_points: int                     # K lowered onto the sweep axis
    n_jobs: int                       # group fabric size (padded)
    n_flows: int
    n_ticks: int                      # per simulation
    wall_s: float                     # device call (trace+compile+execute)
    traced: bool
    signature: Optional[str] = None     # _group_signature
    stack_s: float = 0.0
    postprocess_s: float = 0.0
    iter_slots: int = 0


@dataclasses.dataclass
class PlanProfile:
    """Per-group runtime profiles of one `run_plan` call.

    ``prepare_s`` is the call's ``run_plan.prepare`` span (points, builds,
    overrides, grouping); ``jit_s`` the call's
    `counters.jit_seconds` deltas (trace, lower, compile, cache_load).
    The costing input for scheduling follow-ons (ROADMAP: sharding
    *across* compile groups needs per-group cost estimates).
    """

    groups: list[GroupProfile] = dataclasses.field(default_factory=list)
    prepare_s: float = 0.0
    jit_s: dict = dataclasses.field(default_factory=dict)

    @property
    def total_wall_s(self) -> float:
        return sum(g.wall_s for g in self.groups)

    @property
    def total_ticks(self) -> int:
        """Simulator ticks across every group (K * n_ticks summed)."""
        return sum(g.n_points * g.n_ticks for g in self.groups)

    def summary(self) -> dict:
        out = {"n_groups": len(self.groups),
               "wall_s": round(self.total_wall_s, 3),
               "n_traced": sum(g.traced for g in self.groups),
               "prepare_s": round(self.prepare_s, 3),
               "stack_s": round(sum(g.stack_s for g in self.groups), 3),
               "postprocess_s": round(sum(g.postprocess_s
                                          for g in self.groups), 3)}
        for kind, secs in self.jit_s.items():
            out[f"{kind}_s"] = round(secs, 3)
        return out


@dataclasses.dataclass
class GroupError:
    """One compile group's failure under ``run_plan(keep_going=True)``.

    ``signature`` names the group structurally (fabric size, algorithm,
    kernel flag, dt) and ``point_labels`` carry the member points' axis
    coordinates, so a salvaged run's report says exactly which cells are
    missing and why; ``error`` is the stringified exception.
    """

    group_index: int
    signature: str
    point_labels: list[str]
    error: str


def _group_signature(group: _Group) -> str:
    c = group.cfg
    return (f"jobs={c.jobs.n_jobs} flows={c.topo.n_flows} "
            f"algo={c.protocol.cc.algo} dt={c.dt} "
            f"kernel={c.use_pallas_kernel} faults={c.faults is not None}")


@dataclasses.dataclass
class PlanResult:
    """All of a plan's results, each self-describing via its `SweepPoint`.

    Results are in plan-point order (cartesian product, last axis fastest).
    ``select`` filters by axis values *preserving that order*, so two
    selections that differ only in a scheme axis stay seed-paired for
    `sweep_speedup_stats`.

    Under ``run_plan(keep_going=True)`` a failed compile group leaves its
    members' slots as None and appends a `GroupError` to ``group_errors``;
    ``select`` / ``group_by`` skip the missing cells.
    """

    plan: Plan
    results: list[metrics.SimResult]
    n_compile_groups: int
    # jnp-oracle fallbacks of the fused CC-tick kernel traced while running
    # this plan (0 unless a config asked for use_pallas_kernel with options
    # outside the kernel's specialization — see repro.kernels.ops).  Like
    # engine.TRACE_COUNT this counts at *trace* time: a plan whose compile
    # groups are already in the jit cache reports 0 — read it off the first
    # run of a given static config.
    n_kernel_fallbacks: int = 0
    # host seconds of the call's phases, per group, and its jit seconds
    profile: PlanProfile = dataclasses.field(default_factory=PlanProfile)
    # compile groups that failed under keep_going=True (empty otherwise —
    # the default keep_going=False re-raises at the failing group)
    group_errors: list[GroupError] = dataclasses.field(default_factory=list)

    def __len__(self) -> int:
        return len(self.results)

    def __iter__(self):
        return iter(self.results)

    def __getitem__(self, i):
        return self.results[i]

    def select(self, **axis_values) -> list[metrics.SimResult]:
        """Results whose SweepPoint matches every given axis=value."""
        out = [r for r in self.results
               if r is not None and r.point.matches(**axis_values)]
        if not out:
            raise KeyError(f"no plan point matches {axis_values} "
                           f"(axes: {[a.name for a in self.plan.axes]})")
        return out

    def group_by(self, *names) -> dict[tuple, list[metrics.SimResult]]:
        """Pivot results by the given axis names -> ordered result lists."""
        out: dict[tuple, list[metrics.SimResult]] = {}
        for r in self.results:
            if r is None:
                continue
            key = tuple(r.point[n] for n in names)
            out.setdefault(key, []).append(r)
        return out

    @property
    def n_ticks(self) -> int:
        """Total simulator ticks executed (for µs/tick accounting)."""
        return sum(r.cfg.n_ticks for r in self.results if r is not None)


# ---------------------------------------------------------------------------
# The runner
# ---------------------------------------------------------------------------

def _resolve_overrides(plan: Plan, points: list[dict],
                       cfgs: list[SimConfig]) -> list[dict]:
    """Each point's resolved dynamic-axis overrides ({sweep field: value}).

    A ``field="*"`` axis resolves to a dict of sweep-field overrides (or a
    callable from the point's built config to one — see `Axis`); its
    entries merge into the point's override dict like so many single-field
    axes.
    """
    dyn_axes = [ax for ax in plan.axes if ax.is_dynamic()]
    for ax in dyn_axes:
        if ax.target != "*" and ax.target not in _DYNAMIC_FIELDS:
            raise ValueError(f"axis {ax.name!r} is dynamic but targets "
                             f"unknown sweep field {ax.target!r}")
    overrides = []
    for pt, cfg in zip(points, cfgs):
        ov = {}
        for ax in dyn_axes:
            v = pt[ax.name]
            r = ax.resolve(v) if ax.resolve is not None else v
            if ax.target != "*":
                ov[ax.target] = r
                continue
            if callable(r):
                r = r(cfg)
            if not isinstance(r, dict):
                raise ValueError(
                    f"axis {ax.name!r} targets field='*' so each label "
                    f"must resolve to a dict of sweep-field overrides "
                    f"(or a callable(cfg) -> dict); "
                    f"label {pt[ax.name]!r} gave {type(r).__name__}")
            for fname, val in r.items():
                if fname not in _DYNAMIC_FIELDS:
                    raise ValueError(
                        f"axis {ax.name!r} (field='*') override names "
                        f"unknown sweep field {fname!r}")
                ov[fname] = val
        overrides.append(ov)
    return overrides


def _prepare(plan: Plan, pad_jobs: bool,
             telemetry: Optional[TelemetrySpec]
             ) -> tuple[list[dict], list[SimConfig], list[dict], list[_Group]]:
    """The plan's points, each point's built config (telemetry stamped on
    if given), its resolved dynamic overrides, and the compile groups
    (before `_stack_group` bounds their iteration records)."""
    points = plan.points()
    cfgs = [plan.build(dict(pt)) for pt in points]
    if telemetry is not None:
        cfgs = [dataclasses.replace(c, telemetry=telemetry) for c in cfgs]
    overrides = _resolve_overrides(plan, points, cfgs)
    return points, cfgs, overrides, _compile_groups(cfgs, pad_jobs)


def _stack_group(cfgs: list[SimConfig], overrides: list[dict],
                 group: _Group
                 ) -> tuple[list[SweepParams], SweepParams, SimConfig]:
    """One group's per-point params, their stacked sweep, and the group
    config with its iteration record bounded (`_bound_record`)."""
    per_point = [_point_params(cfgs[i], overrides[i], group)
                 for i in group.idxs]
    sweep = _stack_params(per_point)
    return per_point, sweep, _bound_record(group.cfg, sweep)


def resolve_plan(plan: Plan, *, pad_jobs: bool = True,
                 telemetry: Optional[TelemetrySpec] = None
                 ) -> tuple[list[dict], list[SimConfig], list[dict],
                            list[_Group]]:
    """The static partitioning stage of `run_plan`, without executing.

    Returns ``(points, cfgs, overrides, groups)``: the plan's label dicts,
    each point's built config (telemetry stamped on if given), its resolved
    dynamic overrides, and the compile groups (each group's ``idxs`` index
    into ``points``/``cfgs``; its ``cfg`` has the iteration record
    `run_plan` gives it, `_bound_record`).  Both run the same `_prepare`
    and `_stack_group`, so this is exactly the grouping `run_plan`
    executes — the static analyzer (`repro.analysis`) lints these groups'
    lowerings before anything runs, and benchmark health checks compare
    the prediction against what a run actually compiled.
    """
    points, cfgs, overrides, groups = _prepare(plan, pad_jobs, telemetry)
    for g in groups:
        _, _, g.cfg = _stack_group(cfgs, overrides, g)
    return points, cfgs, overrides, groups


def group_sweep(cfgs: list[SimConfig], overrides: list[dict],
                group: _Group) -> SweepParams:
    """One compile group's batched SweepParams, exactly as `run_plan` would
    stack it (point params resolved on the group fabric, K = len(idxs))."""
    return _stack_group(cfgs, overrides, group)[1]


def run_plan(plan: Plan, *, shard="auto", pad_jobs: bool = True,
             telemetry: Optional[TelemetrySpec] = None,
             keep_going: bool = False) -> PlanResult:
    """Execute a plan: one `simulate_sweep` per compile group.

    shard:     "auto" | True | False — lay each group's K axis across local
               devices (see `_shard_sweep`).
    pad_jobs:  merge workload-size variants into one padded + masked compile
               group where possible (disable to force exact grouping).
    telemetry: arm the probe subsystem (netsim.telemetry) on every point:
               the spec is stamped onto each built config (joining its
               static signature), and each `SimResult` gains a
               `.telemetry` with the probe series and detector outputs.
               None leaves the built configs untouched — a build function
               may still arm points itself.
    keep_going: isolate per-group failures — a compile group that raises
               (bad config, OOM, compile error) is recorded on
               `PlanResult.group_errors` (its members' result slots stay
               None) and the remaining groups still run, so one poisoned
               cell cannot torch a long benchmark run.  The default
               (False) re-raises at the failing group.
    """
    group_errors: list[GroupError] = []
    with counters.watch(reset_warnings=True) as plan_watch:
        with counters.span("run_plan.prepare") as prepare:
            points, cfgs, overrides, groups = _prepare(plan, pad_jobs,
                                                       telemetry)
            results: list[Optional[metrics.SimResult]] = [None] * len(points)
        plan_profile = PlanProfile(prepare_s=prepare.seconds)
        for gi, group in enumerate(groups):
            idxs = group.idxs
            k = len(idxs)
            try:
                with counters.span("run_plan.stack", group=gi,
                                   points=k) as stack:
                    per_point, sweep, group.cfg = _stack_group(
                        cfgs, overrides, group)
                    sweep, _ = _shard_sweep(sweep, k, shard)
                prof = GroupProfile(n_points=k, n_jobs=group.cfg.jobs.n_jobs,
                                    n_flows=group.cfg.topo.n_flows,
                                    n_ticks=group.cfg.n_ticks,
                                    wall_s=0.0, traced=False,
                                    signature=_group_signature(group),
                                    stack_s=stack.seconds,
                                    iter_slots=group.cfg.max_iters_recorded)
                with counters.watch() as w, counters.span(
                        "run_plan.device", group=gi, points=k) as device:
                    raw = simulate_sweep(group.cfg, sweep)
                    jax.block_until_ready(raw)
                prof.wall_s = device.seconds
                prof.traced = w.traces > 0
                plan_profile.groups.append(prof)
                with counters.span("run_plan.postprocess", group=gi,
                                   points=k) as post:
                    for slot, i in enumerate(idxs):
                        point = SweepPoint(axes=dict(points[i]),
                                           params=per_point[slot],
                                           n_jobs=cfgs[i].jobs.n_jobs)
                        raw_i = jax.tree_util.tree_map(
                            lambda x, s=slot: x[s], raw)
                        results[i] = metrics.postprocess(cfgs[i], raw_i,
                                                         point=point,
                                                         n_jobs=point.n_jobs)
                prof.postprocess_s = post.seconds
            except Exception as exc:
                if not keep_going:
                    raise
                group_errors.append(GroupError(
                    group_index=gi,
                    signature=_group_signature(group),
                    point_labels=[SweepPoint(axes=dict(points[i])).label()
                                  for i in idxs],
                    error=f"{type(exc).__name__}: {exc}"))
    plan_profile.jit_s = plan_watch.jit_s
    return PlanResult(plan=plan, results=results,
                      n_compile_groups=len(groups),
                      n_kernel_fallbacks=plan_watch.fallbacks,
                      profile=plan_profile,
                      group_errors=group_errors)
