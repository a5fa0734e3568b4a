"""TCP CUBIC +/- MLTCP (paper §3.4, Eqs. 8-11).

Window growth:
    default:  cwnd = CUBIC(t)                                (Eq. 8)
    MLTCP-WI: cwnd = CUBIC(F(bytes_ratio) * t)               (Eq. 9)

where t is the time since the last multiplicative-decrease event and
CUBIC(t) = C*(t - K)^3 + w_max with K = cbrt(w_max * (1 - beta) / C).
A smaller F dilates time for the less-favored flow, so it climbs back toward
w_max more slowly — exactly the paper's mechanism.

Multiplicative decrease:
    default:  cwnd = beta * cwnd                             (Eq. 10)
    MLTCP-MD: cwnd = F(bytes_ratio) * beta * cwnd            (Eq. 11)

The paper scales ``bic_scale`` to make CUBIC responsive at testbed (~100 us)
RTTs; we expose the same knob as ``cubic_scale`` multiplying C.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.cc.types import CCParams, Feedback, FlowCCState

Array = jnp.ndarray


def cube_root(x: Array) -> Array:
    """``x ** (1/3)`` for ``x >= 0``, as ``exp(log(x) / 3)``.

    Mosaic (the Pallas TPU compiler) has no lowering for ``cbrt``, so the
    fused kernel cannot use ``jnp.cbrt``; this form lowers on both paths.
    The single source of truth for CUBIC's K: used by `_cubic_target`
    below and inside the fused kernel body (`repro.kernels.mltcp_step`),
    so kernel and oracle round identically.  ``x = 0`` (an unset
    ``w_max``) gives exactly 0.
    """
    return jnp.where(x > 0.0, jnp.exp(jnp.log(x) * (1.0 / 3.0)), 0.0)


def _cubic_target(params: CCParams, w_max: Array, t: Array) -> Array:
    c = params.cubic_c * params.cubic_scale
    # (1-beta)/c folds to one python-float constant (no constant-divisor
    # division in the graph — keeps kernel/oracle programs bit-identical)
    k = cube_root(w_max * ((1.0 - params.cubic_beta) / c))
    return c * (t - k) ** 3 + w_max


def update(params: CCParams, state: FlowCCState, fb: Feedback,
           f_wi: Array, f_md: Array) -> FlowCCState:
    cwnd = state.cwnd

    # ---- growth toward the cubic target (on acks) ----
    t = jnp.maximum(fb.now - state.epoch_start, 0.0)
    target = _cubic_target(params, state.w_max, f_wi * t)       # Eq. 9
    # per-ack growth (cwnd += (target-cwnd)/cwnd per ack), vectorized over the
    # tick's ack batch; clipped to at most ~50% growth per tick for stability.
    grow = fb.num_acks * jnp.maximum(target - cwnd, 0.0) / jnp.maximum(cwnd, 1e-6)
    # slow start below ssthresh (untouched by MLTCP, §3.4), cubic above.
    in_ss = cwnd < state.ssthresh
    cwnd_inc = cwnd + jnp.where(in_ss, fb.num_acks,
                                jnp.minimum(grow, 0.5 * cwnd + 1.0))

    # ---- multiplicative decrease (once per RTT) ----
    can_cut = state.cooldown <= 0.0
    do_cut = fb.loss & can_cut
    # Eq. 11, with F*beta clipped at 1 (a decrease never increases cwnd).
    cwnd_cut = jnp.maximum(jnp.minimum(f_md * params.cubic_beta, 1.0) * cwnd,
                           params.min_cwnd)

    new_cwnd = jnp.where(do_cut, cwnd_cut, cwnd_inc)
    return state._replace(
        cwnd=new_cwnd,
        w_max=jnp.where(do_cut, cwnd, state.w_max),
        epoch_start=jnp.where(do_cut, fb.now, state.epoch_start),
        ssthresh=jnp.where(do_cut, jnp.maximum(cwnd_cut, 2.0), state.ssthresh),
        cooldown=jnp.where(do_cut, params.rtt,
                           jnp.maximum(state.cooldown - params.tick_dt, 0.0)),
    )
