"""netsim — vectorized discrete-time fluid network simulator.

The evaluation substrate replacing the paper's 12-server testbed: links with
FIFO queues and RED/ECN, per-flow multi-hop routing, RTT-delayed feedback,
and periodic DNN-job traffic — all stepped by a single `jax.lax.scan`.
Parameter/seed sweeps batch over a leading vmap axis (`simulate_sweep`):
one trace, one compile, K simulations per device program.  The experiment
layer (`Axis`/`Plan`/`run_plan`) declares whole evaluation matrices over
static *and* dynamic axes and lowers them onto that sweep axis, one compile
group per distinct static signature.  Workload *values* — phase programs,
straggle probabilities, Cassini schedules, Static factors — are traced
sweep leaves, so straggler/compat grids fold into one group per variant;
job-count grids pad + mask into a single group; and K optionally shards
across local devices.
"""

from repro.netsim.topology import Topology, dumbbell, triangle, two_tier
from repro.netsim.engine import (
    CassiniSchedule,
    JobSpec,
    SimConfig,
    SweepParams,
    SweepPoint,
    grid_sweep,
    make_sweep,
    simulate,
    simulate_sweep,
    sweep_len,
    sweep_of,
    sweep_slice,
)
from repro.netsim.experiment import (
    Axis,
    GroupError,
    GroupProfile,
    Plan,
    PlanProfile,
    PlanResult,
    restrict_workload,
    run_plan,
)
from repro.netsim.faults import (
    FaultEvent,
    FaultSchedule,
    FaultSpec,
    blackhole,
    identity_schedule,
    job_arrives,
    job_departs,
    link_flap,
    straggle_burst,
)
from repro.netsim.faults import schedule as fault_schedule
from repro.netsim.metrics import (
    SimResult,
    convergence_iteration,
    interleave_score,
    iter_time_quantile,
    iteration_times,
    mean_pairwise_interleave,
    postprocess,
    postprocess_sweep,
    probe_timeline,
    speedup_stats,
    sweep_speedup_stats,
    time_to_interleave,
)
from repro.netsim.telemetry import (
    TelemetryResult,
    TelemetrySpec,
    register_probe,
)

__all__ = [
    "Topology", "dumbbell", "triangle", "two_tier",
    "CassiniSchedule", "SimConfig", "JobSpec", "simulate",
    "SweepParams", "SweepPoint", "simulate_sweep", "make_sweep",
    "grid_sweep", "sweep_len", "sweep_of", "sweep_slice",
    "Axis", "Plan", "PlanResult", "GroupError", "GroupProfile",
    "PlanProfile", "restrict_workload", "run_plan",
    "FaultSpec", "FaultEvent", "FaultSchedule", "fault_schedule",
    "identity_schedule", "job_arrives", "job_departs", "link_flap",
    "blackhole", "straggle_burst",
    "SimResult", "interleave_score", "iteration_times",
    "mean_pairwise_interleave", "postprocess", "postprocess_sweep",
    "speedup_stats", "sweep_speedup_stats",
    "TelemetrySpec", "TelemetryResult", "register_probe",
    "probe_timeline", "time_to_interleave", "convergence_iteration",
    "iter_time_quantile",
]
