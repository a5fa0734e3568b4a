"""Fluid network simulation engine.

One `jax.lax.scan` steps the whole fabric: job phase machines, flow injection,
store-and-forward link queues with RED/ECN, RTT-delayed ack/loss/CNP feedback,
and the MLTCP-augmented congestion-control update (`repro.core.cc_tick`).

Configuration is split (DESIGN.md §3): `SimConfig` is the *static* half —
topology, job-array *shapes*, algorithm/variant choices, everything that
shapes the traced program — and `SweepParams` is the *dynamic* half:
protocol scalars (slope, intercept, g, gamma, INIT_COMM_GAP), RED
thresholds, the per-job workload values (phase programs `compute` /
`comm_bytes`, `straggle_prob`, `iso_iter`), the Static-baseline job factors,
the Cassini schedule values, the PRNG seed and the `job_active` padding
mask, carried as traced values.  `simulate_sweep` vmaps the whole chunked
scan over a leading sweep axis, so a K-point parameter / seed / workload
grid is one trace, one compile, and one device program instead of K.  The
experiment layer (`netsim.experiment`, DESIGN.md §5) lowers whole
evaluation matrices — static axes included — onto this sweep axis, one
compile group per static signature.

Model summary (hardware-adaptation notes in DESIGN.md §2):
  * fluid flows: each tick a flow injects ``min(rate*dt, bytes_left)``;
  * store-and-forward: bytes advance one link per tick; per-link service is
    ``cap*dt`` split proportionally across queued flows (FIFO-fair fluid);
  * RED at enqueue: mark/drop probability ramps linearly on queue length
    between ``red_qmin`` and ``red_qmax``; drop mode feeds Reno/CUBIC loss
    events (Bernoulli on expected dropped packets) and retransmits the bytes;
    ECN mode feeds DCQCN CNPs;
  * feedback (acks = delivered bytes, loss, CNP) returns after ``rtt`` via a
    ring buffer — the ack clock MLTCP's Algorithm 1 listens to;
  * jobs: a phase *program* (compute_s, comm_bytes) pairs per iteration —
    on/off for data-parallel jobs, multi-peak for hybrid DP/PP/TP jobs —
    with optional stragglers and Cassini-style start-time enforcement.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mltcp as core
from repro.netsim import faults as faults_mod
from repro.netsim import telemetry as telem
from repro.netsim.topology import HashableConfig, Topology

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class JobSpec(HashableConfig):
    """Per-job workload description (numpy, static).

    compute[J, P] seconds and comm_bytes[J, P] bytes define each iteration's
    sub-phase program (P >= 1; unused phases zero-padded with n_phases[J]).
    """

    compute: np.ndarray          # [J, P] seconds
    comm_bytes: np.ndarray       # [J, P] bytes
    n_phases: np.ndarray         # [J] int
    start_offset: np.ndarray     # [J] seconds
    straggle_prob: np.ndarray    # [J] probability per iteration
    iso_iter_time: np.ndarray    # [J] isolation iteration time (s)

    @staticmethod
    def simple(compute_s, comm_bytes, start_offset=None, straggle_prob=None,
               cap_bytes_per_s: float = 50e9 / 8) -> "JobSpec":
        """On/off jobs: one compute phase + one comm phase per iteration."""
        compute_s = np.asarray(compute_s, np.float64)
        comm_bytes_a = np.asarray(comm_bytes, np.float64)
        j = compute_s.shape[0]
        iso = compute_s + comm_bytes_a / cap_bytes_per_s
        return JobSpec(
            compute=compute_s[:, None],
            comm_bytes=comm_bytes_a[:, None],
            n_phases=np.ones((j,), np.int32),
            start_offset=(np.zeros((j,)) if start_offset is None
                          else np.asarray(start_offset, np.float64)),
            straggle_prob=(np.zeros((j,)) if straggle_prob is None
                           else np.asarray(straggle_prob, np.float64)),
            iso_iter_time=iso,
        )

    @property
    def n_jobs(self) -> int:
        return int(self.compute.shape[0])

    @property
    def total_bytes(self) -> np.ndarray:
        """[J] bytes per iteration (Algorithm 1's total_bytes input)."""
        return self.comm_bytes.sum(axis=1)

@dataclasses.dataclass(frozen=True, eq=False)
class CassiniSchedule(HashableConfig):
    """Centralized time-shift baseline [66]: align each job's comm-phase start
    to ``offset + k*period``; the end-host agent delays a job that deviates by
    more than ``eps`` until the next slot (which is how stragglers hurt it)."""

    offset: np.ndarray           # [J] seconds
    period: np.ndarray           # [J] seconds
    eps: float = 2e-3


@dataclasses.dataclass(frozen=True, eq=False)
class SimConfig(HashableConfig):
    topo: Topology
    jobs: JobSpec
    protocol: core.MLTCPConfig
    sim_time: float = 10.0
    dt: float = 2e-5
    # RED / buffer parameters (per link, bytes)
    red_qmin: float = 150e3
    red_qmax: float = 1.5e6
    red_pmax: float = 0.12
    buffer_bytes: float = 4e6         # taildrop ceiling
    ecn_mode: Optional[bool] = None   # default: True iff DCQCN
    # Static [67] baseline: per-JOB constant aggressiveness factors
    static_job_factors: Optional[np.ndarray] = None
    cassini: Optional[CassiniSchedule] = None
    cubic_epoch_reset_on_comm_start: bool = True
    max_iters_recorded: int = 4096
    n_chunks: int = 400               # trace resolution
    seed: int = 0
    use_pallas_kernel: bool = False   # route CC tick through kernels/ops.py
    # On-device probe subsystem (netsim.telemetry, DESIGN.md §6).  None is
    # the zero-cost default: every telemetry hook is gated on a python-level
    # `cfg.telemetry is not None`, so an unarmed config traces the exact
    # program this engine emitted before probes existed (bit-identical
    # RawSimOutput, no extra traces — pinned by tests/test_telemetry.py).
    telemetry: Optional[telem.TelemetrySpec] = None
    # Fault-injection structure (netsim.faults, DESIGN.md §8).  Like
    # `telemetry`, the spec is static (row count + armed channels shape the
    # traced program) while the schedule *values* ride in as SweepParams
    # leaves — and None is the zero-cost default: every fault hook is gated
    # on a python-level `cfg.faults is not None`, so an un-faulted config
    # traces the exact pre-fault program (bit-identical RawSimOutput,
    # pinned by tests/test_faults.py).
    faults: Optional[faults_mod.FaultSpec] = None

    @property
    def n_ticks(self) -> int:
        return int(round(self.sim_time / self.dt))

    @property
    def rtt_ticks(self) -> int:
        return max(1, int(round(self.protocol.cc.rtt / self.dt)))

    def is_ecn(self) -> bool:
        if self.ecn_mode is not None:
            return self.ecn_mode
        return self.protocol.cc.algo == int(core.Algo.DCQCN)


# ---------------------------------------------------------------------------
# Sweep axis — the dynamic (traced) half of the configuration
# ---------------------------------------------------------------------------

class SweepParams(NamedTuple):
    """Traced per-simulation parameters (one sweep grid point per entry).

    Every field the paper's evaluation sweeps — the aggressiveness function's
    slope/intercept (Fig. 16), Algorithm 1's g/gamma/INIT_COMM_GAP, the RED /
    ECN thresholds, the Static [67] per-job factors and the PRNG seed — lives
    here as a JAX value rather than a static jit argument, so
    ``simulate_sweep`` can vmap one compiled program over a whole grid.

    Unbatched (scalar) instances describe a single simulation; batched
    instances carry a leading [K] axis on every non-None leaf.

    The *workload* is traced too (the straggler / partial-compat axis):
    ``compute`` / ``comm_bytes`` are each job's per-iteration phase program,
    padded to a shared [J, P_max] shape — only ``n_phases`` (a static shape
    mask in `JobSpec`) decides which columns are live, so padding columns
    with zeros never changes a trajectory — and ``straggle_prob`` /
    ``iso_iter`` drive the per-iteration straggler sampling.  Plans that
    sweep batch size or straggle probability therefore share one compile
    group instead of compiling per workload value.

    ``job_active`` is the padded-jobs axis (DESIGN.md §5): a [J] bool mask
    that deactivates trailing jobs of an over-provisioned fabric, so a
    job-count grid (Fig. 10's 2..8 jobs) runs every point on the *largest*
    topology inside one compile group instead of one compile per count.
    Inactive jobs never start, so their flows inject nothing and are inert
    (lane-stable RNG keeps the active lanes bit-comparable to an unpadded
    run).  None means "all jobs active" and adds no masking ops.

    ``cassini_offset`` / ``cassini_period`` / ``cassini_eps`` carry the
    Cassini [66] baseline's schedule as values: a job with period <= 0 is
    simply un-scheduled, which lets Cassini and non-Cassini points of a
    plan share one compile group (the branch exists in the program, the
    per-job gate decides).  All three are None when no point needs them.

    The ``fault_*`` leaves are the fault-injection *schedule* (DESIGN.md
    §8): an event table whose row count and armed channels are fixed by
    ``cfg.faults`` (a static `FaultSpec`), whose *values* — event start
    ticks, per-event job-activity masks, link-capacity multipliers,
    blackhole masks, straggle boosts — are traced, so a churn grid
    (schedule x seed x variant) shares one compile group.  All None when
    ``cfg.faults`` is None; `faults.identity_schedule` gives exact-no-op
    values for an armed spec.
    """

    slope: Array                # F(x) = slope * x + intercept      (Eq. 3)
    intercept: Array
    g: Array                    # Algorithm 1 noise tolerance
    gamma: Array                # Algorithm 1 iter_gap EWMA factor
    init_comm_gap: Array        # Algorithm 1 INIT_COMM_GAP (s)
    red_qmin: Array             # RED ramp start (bytes)
    red_qmax: Array             # RED ramp knee (bytes)
    red_pmax: Array             # RED mark/drop probability at the knee
    seed: Array                 # int32 PRNG seed
    compute: Array              # [J, P] per-phase compute seconds
    comm_bytes: Array           # [J, P] per-phase comm bytes
    straggle_prob: Array        # [J] straggle probability per iteration
    iso_iter: Array             # [J] isolation iteration time (s)
    static_job_factors: Optional[Array]  # [J] Static-baseline factors or None
    job_active: Optional[Array] = None   # [J] bool mask (padded-jobs axis)
    cassini_offset: Optional[Array] = None  # [J] slot-grid offsets (s)
    cassini_period: Optional[Array] = None  # [J] slot periods; <=0 = off
    cassini_eps: Optional[Array] = None     # scalar agent tolerance (s)
    fault_tick: Optional[Array] = None        # [E] int32 event start ticks
    fault_job_active: Optional[Array] = None  # [E, J] bool churn masks
    fault_link_scale: Optional[Array] = None  # [E, M] capacity multipliers
    fault_blackhole: Optional[Array] = None   # [E, N] bool null-route masks
    fault_straggle: Optional[Array] = None    # [E, J] straggle-prob boosts

    def dyn(self) -> core.DynamicParams:
        """The protocol-layer slice, for `core.cc_tick`."""
        return core.DynamicParams(slope=self.slope, intercept=self.intercept,
                                  g=self.g, gamma=self.gamma,
                                  init_comm_gap=self.init_comm_gap)


# Per-sweep-point shapes/dtypes: most fields are scalars; the per-job
# fields carry a [J] axis per point ([K, J] batched) and the phase
# programs a [J, P] axis pair ([K, J, P] batched).
_POINT_NDIM = {
    "static_job_factors": 1, "job_active": 1,
    "compute": 2, "comm_bytes": 2,
    "straggle_prob": 1, "iso_iter": 1,
    "cassini_offset": 1, "cassini_period": 1,
    "fault_tick": 1, "fault_job_active": 2, "fault_link_scale": 2,
    "fault_blackhole": 2, "fault_straggle": 2,
}
_FIELD_DTYPE = {"seed": jnp.int32, "job_active": jnp.bool_,
                "fault_tick": jnp.int32, "fault_job_active": jnp.bool_,
                "fault_blackhole": jnp.bool_}


def _point_shape(name: str, cfg: SimConfig) -> tuple[int, ...]:
    """The per-point (unbatched) shape of a sweep field on cfg's fabric."""
    if name.startswith("fault_"):
        if cfg.faults is None:
            raise ValueError(
                f"sweep field {name!r} needs cfg.faults (a FaultSpec) — "
                f"fault schedule values have no meaning on an un-faulted "
                f"config")
        e = cfg.faults.n_events
        if name == "fault_tick":
            return (e,)
        if name == "fault_link_scale":
            return (e, cfg.topo.n_links)
        if name == "fault_blackhole":
            return (e, cfg.topo.n_flows)
        return (e, cfg.jobs.n_jobs)       # fault_job_active / fault_straggle
    nd = _POINT_NDIM.get(name, 0)
    if nd == 0:
        return ()
    j, p = cfg.jobs.compute.shape
    return (j,) if nd == 1 else (j, p)


def _unknown_field_error(name: str) -> ValueError:
    return ValueError(
        f"unknown sweep field {name!r}: not a SweepParams leaf "
        f"(it would silently compile per-point instead of riding the "
        f"batched sweep); valid leaves: {', '.join(SweepParams._fields)}")


def sweep_of(cfg: SimConfig) -> SweepParams:
    """Lift a config's dynamic values into an (unbatched) SweepParams."""
    sf = None
    if cfg.static_job_factors is not None:
        sf = jnp.asarray(np.asarray(cfg.static_job_factors), jnp.float32)
    cas_off = cas_per = cas_eps = None
    if cfg.cassini is not None:
        cas_off = jnp.asarray(cfg.cassini.offset, jnp.float32)
        cas_per = jnp.asarray(cfg.cassini.period, jnp.float32)
        cas_eps = jnp.asarray(cfg.cassini.eps, jnp.float32)
    # an armed FaultSpec defaults to the identity schedule (exact no-op
    # values); real schedules arrive as make_sweep overrides
    fault_vals = {name: None for name in faults_mod.FIELDS}
    if cfg.faults is not None:
        ident = faults_mod.identity_schedule(cfg, cfg.faults).values
        for name, v in ident.items():
            fault_vals[name] = jnp.asarray(
                v, _FIELD_DTYPE.get(name, jnp.float32))
    p = cfg.protocol
    jobs = cfg.jobs
    return SweepParams(
        slope=jnp.asarray(p.slope, jnp.float32),
        intercept=jnp.asarray(p.intercept, jnp.float32),
        g=jnp.asarray(p.g, jnp.float32),
        gamma=jnp.asarray(p.gamma, jnp.float32),
        init_comm_gap=jnp.asarray(p.init_comm_gap, jnp.float32),
        red_qmin=jnp.asarray(cfg.red_qmin, jnp.float32),
        red_qmax=jnp.asarray(cfg.red_qmax, jnp.float32),
        red_pmax=jnp.asarray(cfg.red_pmax, jnp.float32),
        seed=jnp.asarray(cfg.seed, jnp.int32),
        compute=jnp.asarray(jobs.compute, jnp.float32),
        comm_bytes=jnp.asarray(jobs.comm_bytes, jnp.float32),
        straggle_prob=jnp.asarray(jobs.straggle_prob, jnp.float32),
        iso_iter=jnp.asarray(jobs.iso_iter_time, jnp.float32),
        static_job_factors=sf,
        cassini_offset=cas_off,
        cassini_period=cas_per,
        cassini_eps=cas_eps,
        **fault_vals,
    )


def make_sweep(cfg: SimConfig, **overrides) -> SweepParams:
    """Build a batched SweepParams from a config plus per-field overrides.

    Each override is a scalar (held constant — per-job fields broadcast it
    across the point shape) or a length-K sequence (the sweep values); the
    per-job fields (``straggle_prob``, ``iso_iter``, ``job_active``,
    ``static_job_factors``, ``cassini_*``) also take [J] or [K, J], and the
    phase programs (``compute``, ``comm_bytes``) take [J, P] or [K, J, P].
    All length-K overrides must agree on K; unswept fields are broadcast
    from the config.
    """
    base = sweep_of(cfg)
    lens = []
    for name, v in overrides.items():
        if name not in SweepParams._fields:
            raise _unknown_field_error(name)
        nd = _POINT_NDIM.get(name, 0)
        a = np.asarray(v)
        if a.ndim == nd + 1:
            lens.append(a.shape[0])
        elif a.ndim not in (0, nd):
            raise ValueError(
                f"sweep field {name!r} has shape {a.shape}; expected a "
                f"scalar, the point shape {_point_shape(name, cfg)}, or a "
                f"[K]-leading batch of point shapes")
    k = lens[0] if lens else 1
    if any(l != k for l in lens):
        raise ValueError(f"sweep fields disagree on length: {lens}")
    out = {}
    for name in SweepParams._fields:
        v = overrides.get(name, getattr(base, name))
        if v is None:
            out[name] = None
            continue
        a = jnp.asarray(v, _FIELD_DTYPE.get(name, jnp.float32))
        nd = _POINT_NDIM.get(name, 0)
        if a.ndim == 0 and nd > 0:
            a = jnp.broadcast_to(a, _point_shape(name, cfg))
        if a.ndim == nd:
            a = jnp.broadcast_to(a[None], (k,) + a.shape)
        out[name] = a
    return SweepParams(**out)


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """Self-describing label for one grid point of a sweep/plan.

    ``axes`` maps axis name -> that point's value (the *label* the caller
    enumerated — e.g. ``{"slope": 1.75, "seed": 2}`` or
    ``{"variant": "WI", "n_jobs": 4}``); ``params`` is the resolved
    unbatched SweepParams actually run, so results carry both the
    human-facing coordinates and the exact dynamic values.  ``n_jobs`` is
    the point's *active* job count on a padded fabric (None: all jobs).

    Travels with its `SimResult` (``metrics.postprocess(..., point=...)``),
    so aggregation never relies on positional alignment with a label list.
    """

    axes: dict
    params: Optional[SweepParams] = None
    n_jobs: Optional[int] = None

    def __getitem__(self, name: str):
        return self.axes[name]

    def get(self, name: str, default=None):
        return self.axes.get(name, default)

    def matches(self, **axis_values) -> bool:
        """True iff every given axis name exists and equals the value."""
        for name, want in axis_values.items():
            if name not in self.axes:
                return False
            have = self.axes[name]
            if isinstance(have, np.ndarray) or isinstance(want, np.ndarray):
                if not np.array_equal(np.asarray(have), np.asarray(want)):
                    return False
            elif have != want:
                return False
        return True

    def label(self) -> str:
        return ",".join(f"{k}={v}" for k, v in self.axes.items())


def sweep_slice(sweep: SweepParams, i: int) -> SweepParams:
    """The i-th unbatched point of a batched SweepParams."""
    return jax.tree_util.tree_map(lambda x: x[i], sweep)


def grid_sweep(cfg: SimConfig, **axes) -> tuple[SweepParams, list[SweepPoint]]:
    """Cartesian-product sweep over the given scalar axes.

    Returns the batched SweepParams (K = product of axis lengths) plus one
    `SweepPoint` per grid point carrying that point's axis values *and* its
    resolved params, so labels round-trip through
    `metrics.postprocess_sweep(cfg, raw, points)` attached to each result
    instead of relying on positional alignment.
    """
    names = list(axes)
    for n in names:
        if n not in SweepParams._fields:
            raise _unknown_field_error(n)
    grids = np.meshgrid(*[np.asarray(axes[n], np.float64) for n in names],
                        indexing="ij")
    flat = {n: g.reshape(-1) for n, g in zip(names, grids)}
    # per-job / per-phase fields: each scalar axis label broadcasts to the
    # point shape, so e.g. straggle_prob=[0.0, 0.1] sweeps a uniform
    # probability across jobs ([K] labels -> [K, J] values)
    values = {}
    for n in names:
        nd = _POINT_NDIM.get(n, 0)
        v = flat[n]
        if nd:
            pshape = _point_shape(n, cfg)
            v = np.broadcast_to(v.reshape((-1,) + (1,) * nd),
                                (v.shape[0],) + pshape)
        values[n] = v
    sweep = make_sweep(cfg, **values)
    n_jobs = cfg.jobs.n_jobs
    k = sweep_len(sweep)
    points = [SweepPoint(axes={n: flat[n][i].item() for n in names},
                         params=sweep_slice(sweep, i), n_jobs=n_jobs)
              for i in range(k)] if names else \
        [SweepPoint(axes={}, params=sweep_slice(sweep, 0), n_jobs=n_jobs)]
    return sweep, points


def sweep_len(sweep: SweepParams) -> int:
    """K, the number of grid points in a batched SweepParams."""
    return int(sweep.slope.shape[0])


# ---------------------------------------------------------------------------
# Engine state
# ---------------------------------------------------------------------------

class EngineState(NamedTuple):
    proto: core.MLTCPState
    backlog: Array        # [M+1, N] queued bytes (row M = trash)
    transit: Array        # [M+1, N] bytes arriving next tick
    ring_del: Array       # [D, N] delivered bytes (feedback delay line)
    ring_loss: Array      # [D, N] bool
    ring_cnp: Array       # [D, N] bool
    ring_ptr: Array       # int32
    to_send: Array        # [N] bytes not yet injected (this comm sub-phase)
    to_deliver: Array     # [N] bytes not yet delivered
    comm_start: Array     # [N] time current comm sub-phase started
    phase_idx: Array      # [J]
    in_comm: Array        # [J] bool
    t_rem: Array          # [J] remaining compute seconds
    iter_idx: Array       # [J]
    iter_start: Array     # [J]
    hold_until: Array     # [J]
    iter_times: Array     # [J, MAX_ITERS]
    straggle_extra: Array # [J] sampled straggle time for current iteration
    key: Array
    tick: Array           # int32
    # accumulators for trace chunks
    acc_util: Array       # [M]
    acc_drops: Array      # scalar (packets)
    acc_marks: Array      # scalar (packets)
    acc_jobbytes: Array   # [J] delivered bytes per job
    # armed-probe ring buffers + detector state; None (zero pytree leaves)
    # unless cfg.telemetry arms the subsystem
    telemetry: Optional[telem.TelemetryState] = None


class TickStatics(NamedTuple):
    """Device-resident static arrays used by the tick function.

    Only *structural* data lives here — routing, fan-out, phase counts,
    start offsets.  The workload values (phase programs, straggle
    probabilities, Cassini schedules) are traced `SweepParams` leaves and
    the per-job totals derived from them (`_workload_view`) are computed
    per sweep point.
    """

    cap: Array            # [M]
    first_link: Array     # [N]
    next_link: Array      # [M+1, N] (M = trash/delivered)
    f2j: Array            # [N]
    spj_inv: Array        # [N] 1/flows-in-job
    n_phases: Array       # [J]
    start_offset: Array   # [J]


def _build_statics(cfg: SimConfig) -> TickStatics:
    topo, jobs = cfg.topo, cfg.jobs
    M, N = topo.n_links, topo.n_flows
    hops = topo.hops
    first_link = hops[:, 0].astype(np.int32)
    # next_link[l, n]: link after l on n's path; M (trash) means "delivered".
    nxt = np.full((M + 1, N), M, np.int32)
    for n in range(N):
        path = [l for l in hops[n] if l >= 0]
        for i, l in enumerate(path):
            nxt[l, n] = path[i + 1] if i + 1 < len(path) else M
    f2j = topo.flow_to_job.astype(np.int32)
    spj = np.bincount(f2j, minlength=jobs.n_jobs).astype(np.float64)
    return TickStatics(
        cap=jnp.asarray(topo.cap, jnp.float32),
        first_link=jnp.asarray(first_link),
        next_link=jnp.asarray(nxt),
        f2j=jnp.asarray(f2j),
        spj_inv=jnp.asarray(1.0 / spj[f2j], jnp.float32),
        n_phases=jnp.asarray(jobs.n_phases, jnp.int32),
        start_offset=jnp.asarray(jobs.start_offset, jnp.float32),
    )


class _WorkloadView(NamedTuple):
    """Per-point values derived from the traced workload leaves."""

    job_total_bytes: Array  # [J] bytes per iteration (Algorithm 1 input)
    period: Array           # [J] nominal iteration period (normalizer)


def _workload_view(cfg: SimConfig, sweep: SweepParams) -> _WorkloadView:
    total = sweep.comm_bytes.sum(axis=-1)
    # 1/cap.min() folds to a python float so the division-by-constant is a
    # reciprocal multiply in every program that computes it (bit-equality
    # between compile groups; DESIGN.md §4)
    inv_cap = float(1.0 / np.asarray(cfg.topo.cap, np.float64).min())
    period = sweep.compute.sum(axis=-1) + total * jnp.float32(inv_cap)
    return _WorkloadView(job_total_bytes=total, period=period)


def _init_state(cfg: SimConfig, statics: TickStatics,
                sweep: SweepParams) -> EngineState:
    topo, jobs = cfg.topo, cfg.jobs
    M, N, J = topo.n_links, topo.n_flows, jobs.n_jobs
    D = cfg.rtt_ticks
    z = jnp.zeros
    return EngineState(
        proto=core.init_state(N, cfg.protocol, dyn=sweep.dyn()),
        backlog=z((M + 1, N), jnp.float32),
        transit=z((M + 1, N), jnp.float32),
        ring_del=z((D, N), jnp.float32),
        ring_loss=z((D, N), bool),
        ring_cnp=z((D, N), bool),
        ring_ptr=jnp.asarray(0, jnp.int32),
        to_send=z((N,), jnp.float32),
        to_deliver=z((N,), jnp.float32),
        comm_start=z((N,), jnp.float32),
        phase_idx=z((J,), jnp.int32),
        in_comm=z((J,), bool),
        t_rem=sweep.compute[:, 0],            # start in compute of phase 0
        iter_idx=z((J,), jnp.int32),
        iter_start=statics.start_offset,
        hold_until=z((J,), jnp.float32),
        iter_times=jnp.full((J, cfg.max_iters_recorded), jnp.nan, jnp.float32),
        straggle_extra=z((J,), jnp.float32),
        key=jax.random.PRNGKey(sweep.seed),
        tick=jnp.asarray(0, jnp.int32),
        acc_util=z((M,), jnp.float32),
        acc_drops=jnp.asarray(0.0, jnp.float32),
        acc_marks=jnp.asarray(0.0, jnp.float32),
        acc_jobbytes=z((J,), jnp.float32),
        telemetry=(telem.init_state(cfg, cfg.telemetry)
                   if cfg.telemetry is not None else None),
    )


# ---------------------------------------------------------------------------
# One tick
# ---------------------------------------------------------------------------

def _mix32(x: Array) -> Array:
    """murmur3's 32-bit finalizer — a cheap full-avalanche bijection."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _lane_uniform(key: Array, n: int) -> Array:
    """Per-lane U[0,1) draws where lane i depends only on (key, i).

    `jax.random.uniform(key, (n,))` has *no* prefix property — its counter
    layout depends on n, so a padded fabric would draw different randomness
    than an unpadded one.  Hashing (key, lane index) counter-style instead
    makes the first n lanes of a padded run bit-identical to an unpadded
    run, which is what lets the padded-jobs axis (`SweepParams.job_active`)
    share one compile group across job counts without changing any
    trajectory.  Two keyed murmur3 finalizer rounds stay ~10 ALU ops per
    lane — a per-lane `jax.random.fold_in` costs a threefry hash each and
    ~3x the whole engine's tick rate.
    """
    lanes = jnp.arange(n, dtype=jnp.uint32)
    h = _mix32(lanes ^ key[0].astype(jnp.uint32))
    h = _mix32(h ^ key[1].astype(jnp.uint32))
    # top 24 bits -> [0, 1) at float32 resolution
    return (h >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(1 / (1 << 24))


def _red_prob(sweep: SweepParams, q: Array) -> Array:
    """Gentle RED: 0 -> pmax on [qmin, qmax], pmax -> 1 on [qmax, 2*qmax]."""
    ramp1 = jnp.clip((q - sweep.red_qmin)
                     / (sweep.red_qmax - sweep.red_qmin),
                     0.0, 1.0) * sweep.red_pmax
    ramp2 = jnp.clip((q - sweep.red_qmax) / sweep.red_qmax, 0.0, 1.0) \
        * (1.0 - sweep.red_pmax)
    return ramp1 + ramp2


def _tick(cfg: SimConfig, statics: TickStatics, sweep: SweepParams,
          wl: _WorkloadView, st: EngineState,
          _unused) -> tuple[EngineState, None]:
    dt = jnp.float32(cfg.dt)
    t = st.tick.astype(jnp.float32) * dt
    M = cfg.topo.n_links
    N = cfg.topo.n_flows
    J = cfg.jobs.n_jobs
    mss = cfg.protocol.cc.mss
    arange_n = jnp.arange(N)

    key, k_loss, k_cnp, k_strag, k_samt = jax.random.split(st.key, 5)

    # ------------------------------------------------------------------
    # 0. Fault-event gather (cfg.faults is None -> this block vanishes)
    # ------------------------------------------------------------------
    fault_idx = None
    if cfg.faults is not None:
        # event rows are sorted by start tick; row e is in effect on
        # [fault_tick[e], fault_tick[e+1]) and row 0 is the identity
        # baseline at tick 0, so the current row is a rank over the tick
        # column — one reduce + gather per tick, no control flow, and
        # nothing reaches the CC-tick kernel (DESIGN.md §8)
        fault_idx = jnp.clip(
            jnp.sum((sweep.fault_tick <= st.tick).astype(jnp.int32)) - 1,
            0, cfg.faults.n_events - 1)

    # ------------------------------------------------------------------
    # 1. Job phase machine: compute countdown -> comm-phase entry
    # ------------------------------------------------------------------
    started = t >= statics.start_offset
    if sweep.job_active is not None:
        # padded-jobs axis: masked-off jobs never start, so their flows
        # stay inert (no injection, no iterations) for this sweep point
        started = started & sweep.job_active
    churn_row = None
    if cfg.faults is not None and cfg.faults.churn:
        # churn: a departed job's compute clock freezes (`started` gate)
        # and its comm phase is force-exited below, so its flows stop
        # injecting; on re-arrival the stale t_rem <= 0 re-enters the
        # interrupted comm sub-phase with a fresh quota.  The identity
        # row is all-True — `& True` is an exact no-op.
        churn_row = sweep.fault_job_active[fault_idx]            # [J]
        started = started & churn_row
    t_rem = jnp.where(~st.in_comm & started, st.t_rem - dt, st.t_rem)
    compute_done = ~st.in_comm & started & (t_rem <= 0.0)

    if sweep.cassini_period is not None:
        # Cassini agent: comm may only start on its slot grid (+/- eps).
        # The schedule is a traced per-job value; period <= 0 disables the
        # agent for that job (value-identical to the no-Cassini program),
        # so scheduled and unscheduled plan points share one compile group.
        on = sweep.cassini_period > 0.0
        per = jnp.maximum(sweep.cassini_period, 1e-6)
        k = jnp.ceil((t - sweep.cassini_offset) / per)
        next_slot = sweep.cassini_offset + k * per
        near = jnp.abs(jnp.round((t - sweep.cassini_offset) / per) * per
                       + sweep.cassini_offset - t) <= sweep.cassini_eps
        hold = jnp.where(compute_done & on & ~near & (st.hold_until <= t),
                         next_slot, st.hold_until)
        enter_comm = compute_done & (~on | near | (t >= hold))
        hold_until = hold
    else:
        enter_comm = compute_done
        hold_until = st.hold_until

    in_comm = st.in_comm | enter_comm
    if churn_row is not None:
        in_comm = in_comm & churn_row

    # flows of entering jobs pick up their sub-phase quota
    phase_bytes_job = sweep.comm_bytes[jnp.arange(J), st.phase_idx]  # [J]
    enter_f = enter_comm[statics.f2j]
    quota_f = (phase_bytes_job[statics.f2j] * statics.spj_inv)
    to_send = jnp.where(enter_f, quota_f, st.to_send)
    to_deliver = jnp.where(enter_f, quota_f, st.to_deliver)
    comm_start = jnp.where(enter_f, t, st.comm_start)

    # ------------------------------------------------------------------
    # 2. Injection at current CC rate
    # ------------------------------------------------------------------
    rate = core.send_rate(cfg.protocol.cc, st.proto.cc)          # [N] bytes/s
    active = in_comm[statics.f2j] & (to_send > 0.0)
    inj = jnp.where(active, jnp.minimum(rate * dt, to_send), 0.0)
    to_send = to_send - inj
    inj_lost = None
    if cfg.faults is not None and cfg.faults.blackholes:
        # blackholed flows are null-routed at the first hop: injected
        # bytes vanish as drops (folded into dropped_f below, so they
        # loss-signal after the usual feedback delay and retransmit when
        # the hole closes).  Identity row is all-False: inj - 0.0 exact.
        bh_row = sweep.fault_blackhole[fault_idx]                # [N]
        inj_lost = jnp.where(bh_row, inj, 0.0)
        inj = inj - inj_lost

    # ------------------------------------------------------------------
    # 3. Links: enqueue (RED) -> serve -> route departures
    # ------------------------------------------------------------------
    incoming = st.transit
    incoming = incoming.at[statics.first_link, arange_n].add(inj)
    incoming = incoming.at[M].set(0.0)                           # trash row

    q_len = st.backlog[:M].sum(axis=1)                           # [M]
    p_red = _red_prob(sweep, q_len)                              # [M]
    p_full = jnp.concatenate([p_red, jnp.zeros((1,), p_red.dtype)])
    # taildrop on buffer overflow (both modes)
    overflow = jnp.concatenate([
        (q_len >= cfg.buffer_bytes).astype(jnp.float32), jnp.zeros((1,))])

    if cfg.is_ecn():
        marked = incoming * p_full[:, None]
        drop_frac = overflow[:, None]
    else:
        marked = jnp.zeros_like(incoming)
        drop_frac = jnp.minimum(p_full[:, None] + overflow[:, None], 1.0)

    dropped = incoming * drop_frac
    kept = incoming - dropped
    backlog = st.backlog + kept

    tot = backlog[:M].sum(axis=1)
    cap_eff = statics.cap
    if cfg.faults is not None and cfg.faults.link_flaps:
        # link flaps scale the *service* capacity only; acc_util keeps the
        # nominal cap as its normalizer (utilization stays comparable
        # across the flap, and scale=0.0 never divides by zero).  The
        # identity row is all-ones: cap * 1.0 is bit-exact.
        cap_eff = cap_eff * sweep.fault_link_scale[fault_idx]    # [M]
    serve_ratio = jnp.where(tot > 0.0,
                            jnp.minimum(1.0, cap_eff * dt / jnp.maximum(tot, 1e-9)),
                            0.0)
    serve_full = jnp.concatenate([serve_ratio, jnp.zeros((1,))])
    dep = backlog * serve_full[:, None]
    backlog = backlog - dep
    backlog = backlog.at[M].set(0.0)

    # route departures: next_link == M means delivered
    is_final = statics.next_link == M                            # [M+1, N]
    delivered = jnp.sum(dep * is_final, axis=0)                  # [N]
    fwd = dep * (~is_final)
    transit = jnp.zeros_like(st.transit).at[
        statics.next_link.reshape(-1), jnp.tile(arange_n, M + 1)
    ].add(fwd.reshape(-1))
    transit = transit.at[M].set(0.0)

    # per-flow drop / mark signals.  The barrier pins the flow vector as a
    # materialized value: otherwise XLA may merge `dropped_f.sum()` below
    # into one reduce over `dropped` in one program and not in another
    # (the blackhole add sits between them), summing in a different order
    # — armed-identity faults must stay bitwise equal to faults off.
    dropped_f = jax.lax.optimization_barrier(dropped.sum(axis=0))  # [N] B
    if inj_lost is not None:
        dropped_f = dropped_f + inj_lost       # blackholed first-hop bytes
    marked_f = marked.sum(axis=0)
    loss_evt = _lane_uniform(k_loss, N) < -jnp.expm1(-dropped_f / mss)
    cnp_evt = _lane_uniform(k_cnp, N) < -jnp.expm1(-marked_f / mss)
    # dropped bytes must be retransmitted
    to_send = to_send + dropped_f

    # ------------------------------------------------------------------
    # 4. Feedback delay line (acks/loss/CNP arrive one RTT later)
    # ------------------------------------------------------------------
    ptr = st.ring_ptr
    fb_del = st.ring_del[ptr]
    fb_loss = st.ring_loss[ptr]
    fb_cnp = st.ring_cnp[ptr]
    ring_del = st.ring_del.at[ptr].set(delivered)
    ring_loss = st.ring_loss.at[ptr].set(loss_evt)
    ring_cnp = st.ring_cnp.at[ptr].set(cnp_evt)
    ring_ptr = (ptr + 1) % cfg.rtt_ticks

    # ------------------------------------------------------------------
    # 5. Byte accounting & comm-phase completion
    # ------------------------------------------------------------------
    to_deliver = jnp.maximum(to_deliver - delivered, 0.0)
    # float32 byte accounting drifts by ulps of the quota on every tick, so
    # `to_deliver` can end a few bytes above the half-packet tolerance.  A
    # flow with nothing left to send and nothing left in the network can
    # deliver no more: it is done, or its job would wait forever.
    in_network = backlog[:M].sum(axis=0) + transit[:M].sum(axis=0)  # [N]
    drained = (to_send <= 0.0) & (in_network <= 0.5 * mss)
    flow_done = ((to_deliver <= 0.5 * mss) | drained).astype(jnp.int32)
    job_all_done = jnp.ones((J,), jnp.int32).at[statics.f2j].min(flow_done) > 0
    comm_done = in_comm & job_all_done

    last_phase = st.phase_idx >= (statics.n_phases - 1)
    iter_done = comm_done & last_phase
    phase_idx = jnp.where(comm_done, jnp.where(last_phase, 0, st.phase_idx + 1),
                          st.phase_idx)
    in_comm = in_comm & ~comm_done

    # iteration bookkeeping + straggler sampling for the next iteration
    iter_time = t - st.iter_start
    iter_times = st.iter_times.at[
        jnp.arange(J), jnp.minimum(st.iter_idx, cfg.max_iters_recorded - 1)
    ].set(jnp.where(iter_done, iter_time,
                    st.iter_times[jnp.arange(J),
                                  jnp.minimum(st.iter_idx,
                                              cfg.max_iters_recorded - 1)]))
    iter_idx = st.iter_idx + iter_done.astype(jnp.int32)
    iter_start = jnp.where(iter_done, t, st.iter_start)

    strag_p = sweep.straggle_prob
    if cfg.faults is not None and cfg.faults.straggle_bursts:
        # additive boost, clipped back to a probability; identity row is
        # all-zeros (p + 0.0 and clip-to-[0,1] of a probability are exact)
        strag_p = jnp.clip(strag_p + sweep.fault_straggle[fault_idx],
                           0.0, 1.0)
    straggles = _lane_uniform(k_strag, J) < strag_p
    strag_amt = (0.05 + 0.05 * _lane_uniform(k_samt, J)) * sweep.iso_iter
    straggle_extra = jnp.where(iter_done,
                               jnp.where(straggles, strag_amt, 0.0),
                               st.straggle_extra)

    next_compute = sweep.compute[jnp.arange(J), phase_idx]
    t_rem = jnp.where(comm_done,
                      next_compute + jnp.where(iter_done, straggle_extra, 0.0),
                      t_rem)

    # ------------------------------------------------------------------
    # 6. Protocol update (MLTCP / baselines) on delayed feedback
    # ------------------------------------------------------------------
    fb = core.Feedback(num_acks=fb_del / mss, loss=fb_loss, cnp=fb_cnp, now=t)
    flow_total = jnp.where(
        jnp.asarray(cfg.protocol.aggregate_by_job),
        wl.job_total_bytes[statics.f2j],
        wl.job_total_bytes[statics.f2j] * statics.spj_inv)
    comm_elapsed = jnp.clip((t - comm_start) / wl.period[statics.f2j],
                            0.0, 1.0)
    est_finish = jnp.clip(to_deliver / jnp.maximum(rate, 1.0)
                          / wl.period[statics.f2j], 0.0, 1.0)

    # the kernel path takes the same traced DynamicParams as the oracle:
    # protocol scalars are operands of the fused kernel (DESIGN.md §4), so
    # K=1 and K>1 sweeps share this one dispatch
    tick_fn = core.cc_tick
    dyn = sweep.dyn()
    if cfg.use_pallas_kernel:
        from repro.kernels import ops as kernel_ops
        tick_fn = kernel_ops.mltcp_cc_tick
    static_factors = (sweep.static_job_factors[statics.f2j]
                      if sweep.static_job_factors is not None else None)
    proto, _ = tick_fn(
        cfg.protocol, st.proto, fb, flow_total,
        flow_to_job=statics.f2j, n_jobs=J,
        static_factors=static_factors,
        comm_elapsed=comm_elapsed, est_finish=est_finish,
        dyn=dyn)

    # CUBIC epoch reset on comm start (idle handling; see DESIGN.md)
    if (cfg.cubic_epoch_reset_on_comm_start
            and cfg.protocol.cc.algo == int(core.Algo.CUBIC)):
        cc = proto.cc._replace(
            epoch_start=jnp.where(enter_f, t, proto.cc.epoch_start),
            w_max=jnp.where(enter_f, proto.cc.cwnd, proto.cc.w_max))
        proto = proto._replace(cc=cc)

    # ------------------------------------------------------------------
    # 7. Trace accumulators
    # ------------------------------------------------------------------
    acc_util = st.acc_util + dep[:M].sum(axis=1) / (statics.cap * dt)
    acc_drops = st.acc_drops + dropped_f.sum() / mss
    acc_marks = st.acc_marks + marked_f.sum() / mss
    acc_jobbytes = st.acc_jobbytes.at[statics.f2j].add(delivered)

    # ------------------------------------------------------------------
    # 8. Telemetry probes + streaming detectors (off = this block vanishes)
    # ------------------------------------------------------------------
    tstate = st.telemetry
    if cfg.telemetry is not None:
        spec = cfg.telemetry
        f_job = None
        if spec.wants("job_f"):
            # recompute the factor stage from the post-update detection
            # state (the kernel path doesn't return per-flow F), then
            # average socket factors per job
            f_flow = core.f_values(cfg.protocol, proto.det, fb,
                                   comm_elapsed, est_finish, dyn,
                                   static_factors=static_factors)
            f_job = (jnp.zeros((J,), jnp.float32).at[statics.f2j]
                     .add(f_flow * statics.spj_inv))
        # a churn-departed job leaves the interleave statistic exactly like
        # a padded-out job: fold the current churn row into the activity
        # mask (identity row is all-True -> an exact no-op `&`)
        telem_active = sweep.job_active
        if churn_row is not None:
            telem_active = (churn_row if telem_active is None
                            else telem_active & churn_row)
        sig = telem.TickSignals(
            tick=st.tick, t=t,
            cwnd=proto.cc.cwnd, rate=rate,
            bytes_ratio=proto.det.bytes_ratio,
            q_len=q_len, red_prob=p_red,
            in_comm=in_comm, phase_idx=phase_idx, iter_idx=iter_idx,
            iter_done=iter_done, iter_time=iter_time,
            f_job=f_job, job_active=telem_active,
            fault_idx=fault_idx,
            fault_ticks=(sweep.fault_tick if cfg.faults is not None
                         else None))
        tstate = telem.tick_update(cfg, spec, st.telemetry, sig)

    return EngineState(
        proto=proto, backlog=backlog, transit=transit,
        ring_del=ring_del, ring_loss=ring_loss, ring_cnp=ring_cnp,
        ring_ptr=ring_ptr,
        to_send=to_send, to_deliver=to_deliver, comm_start=comm_start,
        phase_idx=phase_idx, in_comm=in_comm, t_rem=t_rem,
        iter_idx=iter_idx, iter_start=iter_start, hold_until=hold_until,
        iter_times=iter_times, straggle_extra=straggle_extra,
        key=key, tick=st.tick + 1,
        acc_util=acc_util, acc_drops=acc_drops, acc_marks=acc_marks,
        acc_jobbytes=acc_jobbytes, telemetry=tstate,
    ), None


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

class RawSimOutput(NamedTuple):
    iter_times: Array     # [J, MAX_ITERS] seconds (nan where unset)
    iter_counts: Array    # [J]
    trace_util: Array     # [n_chunks, M] mean utilization per chunk
    trace_drops: Array    # [n_chunks] packets per chunk
    trace_marks: Array    # [n_chunks]
    trace_incomm: Array   # [n_chunks, J] bool snapshot
    trace_t: Array        # [n_chunks] chunk end times
    trace_jobtput: Array  # [n_chunks, J] delivered bytes/s per job
    trace_ratio: Array    # [n_chunks, J] mean bytes_ratio snapshot per job
    final_state: EngineState
    # final TelemetryState (ring buffers + detector scalars) when
    # cfg.telemetry is armed; None (zero extra leaves) otherwise
    telemetry: Optional[telem.TelemetryState] = None


def _run_single(cfg: SimConfig, statics: TickStatics,
                sweep: SweepParams) -> RawSimOutput:
    """One simulation as a pure traced function of an unbatched sweep point."""
    st = _init_state(cfg, statics, sweep)
    ticks_per_chunk = max(1, cfg.n_ticks // cfg.n_chunks)
    n_chunks = cfg.n_ticks // ticks_per_chunk
    tick = partial(_tick, cfg, statics, sweep, _workload_view(cfg, sweep))

    def chunk(st: EngineState, _):
        st = st._replace(acc_util=jnp.zeros_like(st.acc_util),
                         acc_drops=jnp.asarray(0.0, jnp.float32),
                         acc_marks=jnp.asarray(0.0, jnp.float32),
                         acc_jobbytes=jnp.zeros_like(st.acc_jobbytes))
        st, _ = jax.lax.scan(tick, st, None, length=ticks_per_chunk)
        # the legacy chunk-averaged channels, via the built-in chunk-probe
        # registry (telemetry.CHUNK_PROBES — same expressions, same order)
        return st, telem.chunk_capture(cfg, statics, st, ticks_per_chunk)

    st, (u, d, m, ic, tt, jt, rj) = jax.lax.scan(chunk, st, None,
                                                 length=n_chunks)
    return RawSimOutput(iter_times=st.iter_times, iter_counts=st.iter_idx,
                        trace_util=u, trace_drops=d, trace_marks=m,
                        trace_incomm=ic, trace_t=tt, trace_jobtput=jt,
                        trace_ratio=rj, final_state=st,
                        telemetry=st.telemetry)


# Incremented once per (re)trace of the sweep program; tests pin "a K-point
# sweep costs exactly one trace" on this counter.
TRACE_COUNT = 0


@partial(jax.jit, static_argnums=(0, 2))
def _run_sweep(cfg: SimConfig, sweep: SweepParams,
               mesh: Optional[jax.sharding.Mesh]) -> RawSimOutput:
    global TRACE_COUNT
    TRACE_COUNT += 1
    statics = _build_statics(cfg)
    run = jax.vmap(lambda s: _run_single(cfg, statics, s))
    if mesh is not None:
        # Mosaic kernels cannot be partitioned automatically: each device
        # runs the same vmapped program over its own K/n sweep points.
        # check_vma is off because the kernel's pallas_call out_shapes carry
        # no varying-mesh-axes annotation (every value varies along "k").
        spec = jax.sharding.PartitionSpec(mesh.axis_names[0])
        run = jax.shard_map(run, mesh=mesh, in_specs=spec, out_specs=spec,
                            check_vma=False)
    return run(sweep)


def _sweep_mesh(sweep: SweepParams) -> Optional[jax.sharding.Mesh]:
    """The 1-D device mesh the sweep's K axis is laid out over, if any.

    `experiment._shard_sweep` commits every leaf to one NamedSharding over
    a ``("k",)`` mesh; the sweep program then runs under `shard_map` on
    that mesh.  Anything else (host arrays, one device) runs unsharded,
    under the same jit cache key as any unsharded call.
    """
    leaf = jax.tree_util.tree_leaves(sweep)[0]
    sharding = getattr(leaf, "sharding", None)
    if (isinstance(sharding, jax.sharding.NamedSharding)
            and sharding.mesh.size > 1):
        return sharding.mesh
    return None


def _check_cfg(cfg: SimConfig) -> None:
    if abs(cfg.protocol.cc.tick_dt - cfg.dt) > 1e-12:
        raise ValueError(
            f"protocol.cc.tick_dt ({cfg.protocol.cc.tick_dt}) must equal the "
            f"simulator dt ({cfg.dt}); build CCParams with tick_dt=dt")


def _validate_sweep(cfg: SimConfig, sweep: SweepParams) -> None:
    _check_cfg(cfg)
    if sweep.slope.ndim < 1:
        raise ValueError("sweep is unbatched; every field needs a leading "
                         "sweep axis (use make_sweep / grid_sweep)")
    k = sweep_len(sweep)
    for name in SweepParams._fields:
        v = getattr(sweep, name)
        if v is not None and (v.ndim < 1 or v.shape[0] != k):
            raise ValueError(
                f"sweep field {name!r} has shape {v.shape}; expected a "
                f"leading sweep axis of length {k} (use make_sweep)")
    cas = (sweep.cassini_offset, sweep.cassini_period, sweep.cassini_eps)
    if any(c is not None for c in cas) and any(c is None for c in cas):
        raise ValueError("cassini_offset / cassini_period / cassini_eps "
                         "must be set together (or all None)")
    if cfg.faults is None:
        for name in faults_mod.FIELDS:
            if getattr(sweep, name) is not None:
                raise ValueError(
                    f"sweep carries {name!r} but cfg.faults is None — set a "
                    f"FaultSpec on the config so the fault gather is traced")
    else:
        required = cfg.faults.leaves()
        for name in faults_mod.FIELDS:
            v = getattr(sweep, name)
            if name in required and v is None:
                raise ValueError(
                    f"cfg.faults arms {name!r} but the sweep leaf is None "
                    f"(use faults.schedule / faults.identity_schedule)")
            if name not in required and v is not None:
                raise ValueError(
                    f"sweep carries {name!r} but cfg.faults does not arm "
                    f"that channel")
        e = cfg.faults.n_events
        if sweep.fault_tick.shape[-1] != e:
            raise ValueError(
                f"fault_tick has {sweep.fault_tick.shape[-1]} event rows; "
                f"cfg.faults.n_events = {e}")


def simulate_sweep(cfg: SimConfig, sweep: SweepParams) -> RawSimOutput:
    """Run K simulations batched over the sweep axis — one trace, one compile.

    ``sweep`` is a batched SweepParams (see `make_sweep` / `grid_sweep`):
    every non-None leaf carries a leading [K] axis.  The whole chunked
    `lax.scan` is vmapped over that axis, so the returned RawSimOutput's
    leaves all gain a leading [K] dimension (postprocess with
    `metrics.postprocess_sweep`).  Retraces only when the *static* config
    (topology, jobs, algorithm, K) changes — never per grid point.
    """
    _validate_sweep(cfg, sweep)
    return _run_sweep(cfg, sweep, _sweep_mesh(sweep))


def lower_sweep(cfg: SimConfig, sweep: SweepParams):
    """AOT-lower the sweep program (`jax.stages.Lowered`) without running it.

    The profiling hook behind `run_plan(..., profile=True)`: callers split
    wall time into trace (`lower_sweep`), compile (`.compile()`) and execute
    (calling the compiled object), and read `.memory_analysis()` for the
    device footprint.  Shares `_run_sweep`'s jit/lowering cache (pin with
    `TRACE_COUNT` if retrace behavior matters), but `.compile()` on the
    returned object re-runs XLA, so the compile_s split is only meaningful
    for cold groups.
    """
    _validate_sweep(cfg, sweep)
    return _run_sweep.lower(cfg, sweep, _sweep_mesh(sweep))


def trace_sweep(cfg: SimConfig, sweep: SweepParams):
    """Trace the sweep program (`jax.stages.Traced`) without lowering it.

    The static analyzer's entry point (repro.analysis.jaxpr_lint): the
    returned object's ``.jaxpr`` is the exact program `simulate_sweep`
    would run for this (cfg, sweep shape) — same jit entry, same jaxpr
    cache, one `TRACE_COUNT` bump for a cold config and zero for a warm
    one — so IR-level invariants (kernel presence, no f64, no callbacks)
    are proved about the real program, not a re-traced imitation.
    """
    _validate_sweep(cfg, sweep)
    return _run_sweep.trace(cfg, sweep, _sweep_mesh(sweep))


def simulate(cfg: SimConfig) -> RawSimOutput:
    """Run one simulation (a K=1 `simulate_sweep`, kept for compatibility).

    Shares `_run_sweep`'s jit cache entry with K=1 sweeps of the same
    config — there is no separate single-run program anymore (the fused
    kernel takes its protocol scalars as operands, so the old "specialize
    on the config's concrete floats" path is gone; DESIGN.md §4).
    """
    _check_cfg(cfg)
    raw = _run_sweep(cfg, make_sweep(cfg), None)
    return jax.tree_util.tree_map(lambda x: x[0], raw)
