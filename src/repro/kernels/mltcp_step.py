"""Fused MLTCP congestion-control tick as a Pallas TPU kernel.

One kernel invocation advances *all* flows one simulator tick: Algorithm 1
(iteration-boundary detection + bytes_ratio), the bandwidth-aggressiveness
function F, and the selected congestion-control update (Reno / CUBIC /
DCQCN, WI/MD variants) — 17 state arrays updated in a single VMEM-resident
pass.  This is the netsim hot loop when simulating cluster-scale fabrics
(10^4-10^5 flows x 10^6+ ticks): the unfused jnp path round-trips ~20
arrays through HBM per tick, while the fused kernel reads each once.

Flow state is reshaped to [rows, 128] lanes (TPU vector width); every op is
elementwise, so blocks tile (8, 128) and the grid parallelizes over rows.
Algorithm and MLTCP variant are *static* (one fabric runs one CC), so the
kernel specializes at trace time with zero runtime branching — but the
protocol *scalars* (DYN_FIELDS: F's slope/intercept, Algorithm 1's
g/gamma/INIT_COMM_GAP) arrive as an f32[1, NDYN] SMEM operand, and the
Static-baseline per-flow factors as an optional [R, 128] lanes operand, so
traced sweep values (`simulate_sweep`'s vmapped K axis) keep the kernel
fused instead of forcing a retrace or an oracle fallback (DESIGN.md §4).
The SMEM ref is a plain operand rather than a `PrefetchScalarGridSpec`
scalar-prefetch argument deliberately: the pallas batching rule lowers a
*batched* prefetch operand to a serial `lax.scan` over the batch, which
would run a K-point sweep one simulation at a time.  It is 2-D because
under vmap the batching rule prepends a squeezed batch dim to its block,
and Mosaic accepts that only when the trailing two block dims equal the
array's; a 1-D ``f32[NDYN]`` would leave a single trailing dim that is
neither the full array nor (8, 128)-aligned.

Oracle: repro.core.cc_tick (via ref.py) — the exact module the netsim
engine uses — fuzz-tested field-by-field (including under traced
DynamicParams and vmapped sweeps) in tests/test_kernels.py.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import iteration
from repro.core.cc.cubic import cube_root
from repro.core.cc.types import Algo, Variant

LANES = 128
SUBLANES = 8

DET_FIELDS = ("bytes_sent", "prev_ack_tstamp", "iter_gap", "max_gap")
CC_FIELDS = ("cwnd", "ssthresh", "cooldown", "w_max", "epoch_start",
             "rate_cur", "rate_target", "alpha", "t_last_cnp", "t_last_inc",
             "t_last_alpha")
IN_ORDER = (list(DET_FIELDS) + list(CC_FIELDS)
            + ["stage", "prev_ratio", "num_acks", "ack_bytes", "loss", "cnp",
               "now", "total_bytes", "job_numer"])
OUT_ORDER = list(DET_FIELDS) + list(CC_FIELDS) + ["stage", "ratio", "rate"]

# Layout of the dyn SMEM operand (== core.DynamicParams field order).
DYN_FIELDS = ("slope", "intercept", "g", "gamma", "init_comm_gap")
NDYN = len(DYN_FIELDS)

# The kernel body's function name in traced programs (the pallas_call
# body jaxpr's debug info, kept under vmap): the CC-tick pallas_call is
# located by matching this.  This is the static analyzer's handle onto
# the body jaxpr (analysis/kernel_lint.py): the body is reachable from the
# already-traced sweep jaxpr via the pallas_call eqn's `jaxpr` param, so
# linting it costs zero extra traces.
KERNEL_NAME = "_kernel"


@dataclasses.dataclass(frozen=True)
class KernelLayout:
    """The operand/grid layout a (rows, factors) specialization must lower
    to — the contract between `mltcp_tick_arrays` (which builds the
    pallas_call) and `analysis.kernel_lint` (which proves the traced
    program matches).  Everything here is static: if ops.py's packing and
    this expectation ever diverge, the kernel lint fires on the next run.
    """

    rows: int                       # [rows, 128]-packed flow state
    block: tuple                    # (min(SUBLANES, rows), LANES)
    grid: tuple                     # (rows // block[0],) — exact cover
    n_inputs: int                   # dyn + optional factors + IN_ORDER
    n_outputs: int                  # OUT_ORDER
    dyn_index: int                  # position of the SMEM scalars operand
    dyn_shape: tuple                # (1, NDYN)
    use_static_factors: bool


def expected_layout(rows: int, use_static_factors: bool = False
                    ) -> KernelLayout:
    """The layout `mltcp_tick_arrays` produces for `rows` packed rows."""
    block = (min(SUBLANES, rows), LANES)
    return KernelLayout(
        rows=rows, block=block, grid=(rows // block[0],),
        n_inputs=1 + int(use_static_factors) + len(IN_ORDER),
        n_outputs=len(OUT_ORDER),
        dyn_index=0, dyn_shape=(1, NDYN),
        use_static_factors=use_static_factors)


def _kernel(p, dyn_ref, *refs):
    # protocol scalars, read from SMEM (operand-carried — possibly traced
    # sweep values; see module docstring)
    slope, intercept = dyn_ref[0, 0], dyn_ref[0, 1]
    g, gamma, init_comm_gap = dyn_ref[0, 2], dyn_ref[0, 3], dyn_ref[0, 4]
    if p["use_static_factors"]:
        factors_r, refs = refs[0], refs[1:]
    n_in = len(IN_ORDER)
    (bytes_sent_r, prev_ack_r, iter_gap_r, max_gap_r,
     cwnd_r, ssthresh_r, cooldown_r, w_max_r, epoch_r,
     rate_cur_r, rate_tgt_r, alpha_r, t_cnp_r, t_inc_r, t_alpha_r,
     stage_r, prev_ratio_r, acks_r, ackb_r, loss_r, cnp_r, now_r, tb_r,
     jobnum_r) = refs[:n_in]
    (o_bytes_sent, o_prev_ack, o_iter_gap, o_max_gap,
     o_cwnd, o_ssthresh, o_cooldown, o_w_max, o_epoch,
     o_rate_cur, o_rate_tgt, o_alpha, o_t_cnp, o_t_inc, o_t_alpha,
     o_stage, o_ratio, o_rate) = refs[n_in:]

    now = now_r[...]
    acks = acks_r[...]
    has_ack = acks > 0.0

    # ---------------- Algorithm 1 (core.iteration semantics) --------------
    # acked bytes arrive pre-multiplied (iteration.ack_bytes operand) so the
    # product's rounding is pinned outside the kernel (bit-stable vs oracle)
    bytes_sent = bytes_sent_r[...] + ackb_r[...]
    curr_gap = now - prev_ack_r[...]
    max_gap = jnp.maximum(max_gap_r[...], curr_gap)
    new_iter = curr_gap > g * iter_gap_r[...]
    iter_gap_upd = (1.0 - gamma) * iter_gap_r[...] + gamma * max_gap
    numer = jobnum_r[...] if p["aggregate"] else bytes_sent
    ratio_mid = iteration.byte_ratio(numer, tb_r[...])

    boundary = has_ack & new_iter
    o_bytes_sent[...] = jnp.where(boundary, 0.0,
                                  jnp.where(has_ack, bytes_sent,
                                            bytes_sent_r[...]))
    ratio = jnp.where(boundary, 0.0,
                      jnp.where(has_ack, ratio_mid, prev_ratio_r[...]))
    o_ratio[...] = ratio
    o_prev_ack[...] = jnp.where(has_ack, now, prev_ack_r[...])
    o_iter_gap[...] = jnp.where(boundary, iter_gap_upd, iter_gap_r[...])
    o_max_gap[...] = jnp.where(boundary,
                               jnp.broadcast_to(init_comm_gap, max_gap.shape),
                               jnp.where(has_ack, max_gap, max_gap_r[...]))

    # ---------------- F(bytes_ratio), variant routing ----------------
    if p["variant"] == int(Variant.OFF):
        adaptive = jnp.ones_like(ratio)
    else:
        adaptive = slope * ratio + intercept
    if p["use_static_factors"]:
        # Static [67] with the adaptive sentinel (mirrors core.cc_tick):
        # factor >= 0 replaces F for that flow, factor < 0 keeps the
        # computed F — an exact elementwise select, so mixed Static /
        # adaptive sweep points share this one fused program
        f_vals = jnp.where(factors_r[...] >= 0.0, factors_r[...], adaptive)
    else:
        f_vals = adaptive
    one = jnp.ones_like(f_vals)
    f_wi = f_vals if p["variant"] in (int(Variant.WI), int(Variant.BOTH)) \
        else one
    f_md = f_vals if p["variant"] in (int(Variant.MD), int(Variant.BOTH)) \
        else one

    loss = loss_r[...] > 0.0
    cnp_sig = cnp_r[...] > 0.0
    algo = p["algo"]

    if algo in (int(Algo.RENO), int(Algo.CUBIC)):
        cwnd = cwnd_r[...]
        in_ss = cwnd < ssthresh_r[...]
        if algo == int(Algo.RENO):
            grow_ca = f_wi * acks / jnp.maximum(cwnd, 1e-6)       # Eq. 5
            beta = p["reno_beta"]
        else:
            c = p["cubic_c"] * p["cubic_scale"]
            tt = jnp.maximum(now - epoch_r[...], 0.0)
            # (1-beta)/c is a python-float constant, as in core.cc.cubic
            kk = cube_root(w_max_r[...] * ((1.0 - p["cubic_beta"]) / c))
            target = c * (f_wi * tt - kk) ** 3 + w_max_r[...]     # Eq. 9
            grow = acks * jnp.maximum(target - cwnd, 0.0) \
                / jnp.maximum(cwnd, 1e-6)
            grow_ca = jnp.minimum(grow, 0.5 * cwnd + 1.0)
            beta = p["cubic_beta"]
        cwnd_inc = cwnd + jnp.where(in_ss, acks, grow_ca)
        do_cut = loss & (cooldown_r[...] <= 0.0)
        cwnd_cut = jnp.maximum(jnp.minimum(f_md * beta, 1.0) * cwnd,  # Eq. 7/11
                               p["min_cwnd"])
        o_cwnd[...] = jnp.where(do_cut, cwnd_cut, cwnd_inc)
        o_ssthresh[...] = jnp.where(do_cut, jnp.maximum(cwnd_cut, 2.0),
                                    ssthresh_r[...])
        o_cooldown[...] = jnp.where(
            do_cut, p["rtt"],
            jnp.maximum(cooldown_r[...] - p["tick_dt"], 0.0))
        if algo == int(Algo.CUBIC):
            o_w_max[...] = jnp.where(do_cut, cwnd, w_max_r[...])
            o_epoch[...] = jnp.where(do_cut, now, epoch_r[...])
        else:
            o_w_max[...] = w_max_r[...]
            o_epoch[...] = epoch_r[...]
        o_rate_cur[...] = rate_cur_r[...]
        o_rate_tgt[...] = rate_tgt_r[...]
        o_alpha[...] = alpha_r[...]
        o_t_cnp[...] = t_cnp_r[...]
        o_t_inc[...] = t_inc_r[...]
        o_t_alpha[...] = t_alpha_r[...]
        o_stage[...] = stage_r[...]
        o_rate[...] = o_cwnd[...] * (p["mss"] / p["rtt"])  # == core send_rate
    else:  # ---------------- DCQCN ----------------
        cnp = cnp_sig & ((now - t_cnp_r[...]) >= p["cnp_interval"])
        alpha_on_cnp = (1.0 - p["dcqcn_g"]) * alpha_r[...] + p["dcqcn_g"]
        md_mult = jnp.minimum(f_md * (1.0 - alpha_r[...] / 2.0), 1.0)  # Eq. 15
        rate_cut = jnp.clip(md_mult * rate_cur_r[...], p["rate_min"],
                            p["line_rate"])
        alpha_fired = (now - t_alpha_r[...]) >= p["alpha_timer"]
        alpha_dec = jnp.where(alpha_fired,
                              (1.0 - p["dcqcn_g"]) * alpha_r[...],
                              alpha_r[...])
        inc_fired = (now - t_inc_r[...]) >= p["inc_timer"]
        stage = stage_r[...] + inc_fired.astype(jnp.int32)
        in_ai = stage > p["fast_recovery_stages"]
        tgt_inc = jnp.where(inc_fired & in_ai,
                            rate_tgt_r[...] + f_wi * p["rate_ai"],  # Eq. 13
                            rate_tgt_r[...])
        tgt_inc = jnp.minimum(tgt_inc, p["line_rate"])
        step_up = jnp.minimum(f_wi, 2.0) * 0.5 * (tgt_inc - rate_cur_r[...])
        rate_inc = jnp.where(inc_fired, rate_cur_r[...] + step_up,
                             rate_cur_r[...])
        o_rate_cur[...] = jnp.clip(jnp.where(cnp, rate_cut, rate_inc),
                                   p["rate_min"], p["line_rate"])
        o_rate_tgt[...] = jnp.clip(jnp.where(cnp, rate_cur_r[...], tgt_inc),
                                   p["rate_min"], p["line_rate"])
        o_alpha[...] = jnp.clip(jnp.where(cnp, alpha_on_cnp, alpha_dec),
                                0.0, 1.0)
        o_stage[...] = jnp.where(cnp, jnp.zeros_like(stage), stage)
        o_t_cnp[...] = jnp.where(cnp, now, t_cnp_r[...])
        o_t_inc[...] = jnp.where(cnp | inc_fired, now, t_inc_r[...])
        o_t_alpha[...] = jnp.where(cnp | alpha_fired, now, t_alpha_r[...])
        o_cwnd[...] = cwnd_r[...]
        o_ssthresh[...] = ssthresh_r[...]
        o_cooldown[...] = cooldown_r[...]
        o_w_max[...] = w_max_r[...]
        o_epoch[...] = epoch_r[...]
        o_rate[...] = o_rate_cur[...]


def mltcp_tick_arrays(cfg_static: dict, dyn: jnp.ndarray, arrays: dict, *,
                      static_factors: jnp.ndarray | None = None,
                      interpret: bool = True) -> dict:
    """Run the fused tick.

    ``dyn``: [NDYN] protocol scalars per DYN_FIELDS, carried as an SMEM
    operand (values may be traced — a sweep point — without retracing the
    kernel).  ``arrays``: {field: [R, 128]} per IN_ORDER ("stage" int32,
    rest f32); ``static_factors``: optional [R, 128] per-flow Static [67]
    factors (their *presence* is static, the values are an operand).
    Returns {field: [R, 128]} per OUT_ORDER.
    """
    r = arrays["cwnd"].shape[0]
    block = (min(SUBLANES, r), LANES)
    spec = pl.BlockSpec(block, lambda i: (i, 0))
    ins = [jnp.asarray(dyn, jnp.float32).reshape(1, NDYN)]
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    if static_factors is not None:
        ins.append(static_factors)
        in_specs.append(spec)
    ins += [arrays[k] for k in IN_ORDER]
    in_specs += [spec] * len(IN_ORDER)
    out_shapes = [jax.ShapeDtypeStruct((r, LANES),
                                       jnp.int32 if f == "stage"
                                       else jnp.float32)
                  for f in OUT_ORDER]
    p = dict(cfg_static, use_static_factors=static_factors is not None)
    outs = pl.pallas_call(
        functools.partial(_kernel, p),
        grid=(r // block[0],),
        in_specs=in_specs,
        out_specs=[spec] * len(OUT_ORDER),
        out_shape=out_shapes,
        interpret=interpret,
    )(*ins)
    return dict(zip(OUT_ORDER, outs))
