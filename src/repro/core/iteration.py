"""Algorithm 1 — distributed iteration-boundary detection (paper §3.5).

Each flow tracks, purely from its own ack arrivals:

  bytes_sent        successfully delivered bytes in the current iteration
  bytes_ratio       min(1, bytes_sent / total_bytes)
  prev_ack_tstamp   timestamp of the previous ack
  iter_gap          EWMA estimate of the inter-iteration communication gap
  max_gap           max ack gap observed within the current iteration

On every ack: if the gap since the previous ack exceeds ``g * iter_gap`` the
flow declares a new training iteration, folds ``max_gap`` into the EWMA
estimate ``iter_gap`` (factor γ) and resets its byte counters.  This is how
MLTCP stays fully distributed: no controller tells a sender where iteration
boundaries are — it infers them from its own traffic, which also makes the
mechanism robust to multi-peak (pipeline/tensor-parallel) patterns, stragglers
and parameter updates landing mid-iteration (§5 Discussion).

Implemented as a pure function over a NamedTuple state so it can run (a)
vectorized over all flows inside the netsim `lax.scan`, (b) inside the Pallas
fused CC-tick kernel, and (c) standalone on recorded ack traces in tests.

Defaults follow Algorithm 1: g = 0.75, γ = 0.5, MTU = 1500.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray

def ack_bytes(num_acks: Array, mtu) -> Array:
    """``num_acks * mtu`` — the bytes acked this tick — as a materialized
    product.

    The optimization barrier stops XLA from contracting the product into a
    neighbouring add (FMA).  XLA makes that choice *per program*, so without
    the barrier the fused-kernel program and the jnp-oracle program can
    round the same byte counter 1 ulp apart on some tick and drift
    irrecoverably over a simulation.  Single source of truth for every
    Algorithm 1 byte increment: `update_mltcp_params`, the job aggregation
    in `core.cc_tick`, and the fused-kernel wrapper (which passes it to the
    kernel as the precomputed ``ack_bytes`` operand); bit-equality of
    kernel and oracle sweeps is pinned by tests/test_sweep.py.
    """
    return jax.lax.optimization_barrier(num_acks * mtu)


def byte_ratio(numer: Array, total_bytes: Array) -> Array:
    """Algorithm 1 line 20: ``min(1, bytes_sent / total_bytes)``.

    Written as reciprocal-then-multiply deliberately: a literal division
    whose divisor is a trace-time constant (total_bytes usually is) invites
    XLA's divide-by-constant → multiply-by-reciprocal rewrite, and XLA
    makes that choice *per program* — the fused-kernel program and the
    jnp-oracle program could round the same tick 1 ulp apart and drift
    irrecoverably over a simulation.  An explicit reciprocal multiply is
    rewrite-proof (a multiply has no cheaper form), so both programs round
    identically.  The single source of truth for the ratio: used by
    ``update_mltcp_params`` below and inside the fused kernel body
    (`repro.kernels.mltcp_step`), pinned bit-equal by tests/test_sweep.py.
    """
    return jnp.minimum(1.0, numer * (1.0 / jnp.maximum(total_bytes, 1.0)))


class IterDetectParams(NamedTuple):
    """Static parameters of Algorithm 1 (per flow, broadcastable)."""

    total_bytes: Array          # total bytes per training iteration
    init_comm_gap: Array        # INIT_COMM_GAP: min gap for boundary detection (s)
    g: float = 0.75             # noise tolerance for gap detection
    gamma: float = 0.5          # EWMA factor for iter_gap
    mtu: float = 1500.0         # bytes per ack'd packet


class IterDetectState(NamedTuple):
    """Mutable per-flow state of Algorithm 1 (all arrays of shape [n_flows])."""

    bytes_sent: Array
    bytes_ratio: Array
    prev_ack_tstamp: Array
    iter_gap: Array
    max_gap: Array
    n_boundaries: Array         # number of boundaries detected (for metrics)


def init_state(n_flows: int, params: IterDetectParams,
               dtype=jnp.float32) -> IterDetectState:
    z = jnp.zeros((n_flows,), dtype)
    gap = jnp.broadcast_to(jnp.asarray(params.init_comm_gap, dtype), (n_flows,))
    return IterDetectState(
        bytes_sent=z,
        bytes_ratio=z,
        prev_ack_tstamp=z,
        iter_gap=gap,
        max_gap=gap,
        n_boundaries=jnp.zeros((n_flows,), jnp.int32),
    )


def boundary_mask(prev_ack_tstamp: Array, iter_gap: Array, g,
                  num_acks: Array, now: Array) -> Array:
    """Algorithm 1 line 16: does this ack open a new training iteration?

    The single source of truth for the boundary predicate — used by
    ``update_mltcp_params`` below and by the fused-kernel wrapper
    (`repro.kernels.ops.mltcp_cc_tick`) to maintain the ``n_boundaries``
    metrics counter, so the two paths cannot drift.
    """
    has_ack = num_acks > 0
    curr_gap = now - prev_ack_tstamp
    return has_ack & (curr_gap > g * iter_gap)


def update_mltcp_params(state: IterDetectState, params: IterDetectParams,
                        num_acks: Array, now: Array,
                        job_bytes_sent: Array | None = None) -> IterDetectState:
    """One invocation of UPDATE_MLTCP_PARAMS (Algorithm 1, lines 11-27).

    Vectorized over flows. ``num_acks`` is the number of acks received at time
    ``now`` for each flow (0 => no ack; the state is left untouched for those
    flows, as the hook only runs on ack receipt).

    ``job_bytes_sent``: optional job-aggregated bytes (the paper aggregates
    statistics across all sockets of a job — §4.1); when given it replaces the
    per-flow counter in the bytes_ratio computation.
    """
    has_ack = num_acks > 0

    bytes_sent = state.bytes_sent + ack_bytes(num_acks, params.mtu)  # line 12
    curr_gap = now - state.prev_ack_tstamp                         # line 14
    max_gap = jnp.maximum(state.max_gap, curr_gap)                 # line 15

    boundary = boundary_mask(state.prev_ack_tstamp, state.iter_gap,
                             params.g, num_acks, now)              # line 16
    # line 19: iter_gap EWMA folds in this iteration's max observed gap
    iter_gap_upd = (1.0 - params.gamma) * state.iter_gap + params.gamma * max_gap

    numer = job_bytes_sent if job_bytes_sent is not None else bytes_sent
    ratio_mid = byte_ratio(numer, params.total_bytes)

    return IterDetectState(
        # lines 21-22 (reset) vs line 12 (accumulate)
        bytes_sent=jnp.where(boundary, 0.0,
                             jnp.where(has_ack, bytes_sent, state.bytes_sent)),
        bytes_ratio=jnp.where(boundary, 0.0,
                              jnp.where(has_ack, ratio_mid, state.bytes_ratio)),
        prev_ack_tstamp=jnp.where(has_ack, now, state.prev_ack_tstamp),  # line 26
        iter_gap=jnp.where(boundary, iter_gap_upd, state.iter_gap),
        max_gap=jnp.where(boundary,
                          jnp.broadcast_to(params.init_comm_gap, max_gap.shape),
                          jnp.where(has_ack, max_gap, state.max_gap)),
        n_boundaries=state.n_boundaries + boundary.astype(jnp.int32),
    )


def run_on_trace(ack_times: Array, ack_counts: Array,
                 params: IterDetectParams) -> IterDetectState:
    """Run Algorithm 1 over a recorded (time, num_acks) trace for one flow.

    Returns the final state; used by unit/property tests to validate boundary
    detection against synthetic traffic with known iteration structure.
    """
    import jax

    st = init_state(1, params)

    def body(st, inp):
        t, n = inp
        return update_mltcp_params(st, params, jnp.atleast_1d(n),
                                   jnp.atleast_1d(t)), None

    st, _ = jax.lax.scan(body, st, (ack_times, ack_counts))
    return st
