"""Jit-ready wrappers around the Pallas kernels.

  flash_attention  — pads to block/lane multiples, custom_vjp whose backward
                     recomputes through the jnp oracle (standard recompute);
  rg_lru           — same pattern for the linear-recurrence scan;
  mltcp_cc_tick    — drop-in replacement for repro.core.cc_tick: packs the
                     protocol state into [R, 128] lanes and the protocol
                     scalars (slope/intercept/g/gamma/INIT_COMM_GAP, plus
                     the Static-baseline per-flow factors) into kernel
                     *operands*, runs the fused tick kernel, unpacks.
                     Traced sweep values therefore stay fused; only the
                     structural options the kernel does not implement
                     (non-default favoritism policy, non-linear F family)
                     fall back to the jnp oracle — loudly, via
                     ``FALLBACK_COUNT`` and a one-time warning.

Interpret mode follows the platform (`resolve_interpret`): the CPU
backend runs the kernel bodies through the Pallas interpreter, which
executes them exactly as the TPU grid would (the tests' validation mode);
the TPU backend always runs them compiled.  Every wrapper takes a per-call
``interpret`` override (None = the platform's choice), which ahead-of-time
compiles for a described TPU use from a CPU process.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import iteration
from repro.core import mltcp as core
from repro.kernels import flash_attention as fa
from repro.kernels import mltcp_step as ms
from repro.kernels import ref
from repro.kernels import rg_lru as rl

Array = jnp.ndarray


# Incremented once per trace that routes mltcp_cc_tick through the jnp
# oracle instead of the fused kernel (mirrors engine.TRACE_COUNT); tests pin
# "a kernel-enabled sweep falls back zero times" on this counter.
FALLBACK_COUNT = 0
_FALLBACK_WARNED: set = set()


def reset_fallback_warnings() -> None:
    """Re-arm the once-per-reason fallback warning.

    The guard is process-global, which is right within one plan (a K-point
    sweep traces the same reason once) but wrong across plans: a later
    `run_plan` that newly falls back would bump FALLBACK_COUNT without the
    named-reason warning.  `run_plan` calls this at entry so each plan
    warns at most once per reason.
    """
    _FALLBACK_WARNED.clear()


_INTERPRET_BY_BACKEND = {"cpu": True, "tpu": False}


def resolve_interpret(override: Optional[bool] = None) -> bool:
    """Whether a kernel call runs through the Pallas interpreter.

    ``override`` wins when given.  Otherwise the default backend decides:
    the CPU interprets, the TPU compiles, and any other backend raises —
    the kernels are written for the TPU, and silently interpreting them on
    an accelerator would hide the device behind the interpreter.
    """
    if override is not None:
        return override
    backend = jax.default_backend()
    try:
        return _INTERPRET_BY_BACKEND[backend]
    except KeyError:
        raise RuntimeError(
            f"no Pallas kernel path for backend {backend!r}: the kernels "
            f"compile for the TPU and interpret on the CPU") from None


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q: Array, k: Array, v: Array, causal: bool = True,
                    window: int = 0, softcap: Optional[float] = None,
                    interpret: Optional[bool] = None) -> Array:
    return _flash_fwd_impl(q, k, v, causal, window, softcap, interpret)


def _flash_fwd_impl(q, k, v, causal, window, softcap, interpret=None):
    t, s = q.shape[1], k.shape[1]
    bq = min(fa.DEFAULT_BLOCK_Q, 1 << max((t - 1).bit_length(), 7))
    bk = min(fa.DEFAULT_BLOCK_K, 1 << max((s - 1).bit_length(), 7))
    qp, _ = _pad_to(q, 1, bq)
    kp, _ = _pad_to(k, 1, bk)
    vp, _ = _pad_to(v, 1, bk)
    qp, pad_d = _pad_to(qp, 3, 128)
    kp, _ = _pad_to(kp, 3, 128)
    vp, _ = _pad_to(vp, 3, 128)
    out = fa.flash_attention_fwd(
        qp, kp, vp, causal=causal, window=window, softcap=softcap,
        s_real=s, scale=1.0 / (q.shape[3] ** 0.5),
        block_q=bq, block_k=bk, interpret=resolve_interpret(interpret))
    if pad_d:
        out = out[..., : q.shape[3]]
    if out.shape[1] != t:
        out = out[:, :t]
    return out


def _flash_vjp_fwd(q, k, v, causal, window, softcap, interpret):
    return _flash_fwd_impl(q, k, v, causal, window, softcap,
                           interpret), (q, k, v)


def _flash_vjp_bwd(causal, window, softcap, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: ref.ref_attention(
        q_, k_, v_, causal=causal, window=window, softcap=softcap), q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rg_lru(a: Array, b: Array, interpret: Optional[bool] = None) -> Array:
    return _rg_lru_impl(a, b, interpret)


def _rg_lru_impl(a, b, interpret=None):
    ap, pad = _pad_to(a, 2, rl.BLOCK_D)
    bp, _ = _pad_to(b, 2, rl.BLOCK_D)
    out = rl.rg_lru_scan(ap, bp, interpret=resolve_interpret(interpret))
    return out[..., : a.shape[2]] if pad else out


def _rg_lru_vjp_fwd(a, b, interpret):
    return _rg_lru_impl(a, b, interpret), (a, b)


def _rg_lru_vjp_bwd(interpret, res, g):
    a, b = res
    _, vjp = jax.vjp(ref.ref_rg_lru, a, b)
    return vjp(g)


rg_lru.defvjp(_rg_lru_vjp_fwd, _rg_lru_vjp_bwd)


# ---------------------------------------------------------------------------
# fused protocol tick
# ---------------------------------------------------------------------------

_ROW = ms.LANES * ms.SUBLANES


def packed_rows(n_flows: int) -> int:
    """[rows, 128] rows `mltcp_cc_tick` packs ``n_flows`` flow-state
    vectors into (flows pad to a SUBLANESxLANES multiple, so rows is
    always a multiple of SUBLANES and the grid divides evenly)."""
    return (-(-n_flows // _ROW) * _ROW) // ms.LANES


def kernel_layout(n_flows: int, use_static_factors: bool = False
                  ) -> ms.KernelLayout:
    """The specialization expectation for an ``n_flows``-flow fabric.

    This is the packing contract `analysis.kernel_lint` checks the traced
    pallas_call against — derived from the same `_ROW` padding
    `mltcp_cc_tick` applies, so the expectation and the dispatch can
    never drift apart silently.
    """
    return ms.expected_layout(packed_rows(n_flows),
                              use_static_factors=use_static_factors)


def _pack(x, n_pad, fill=0.0, dtype=jnp.float32):
    x = jnp.asarray(x, dtype)
    x = jnp.pad(x, (0, n_pad - x.shape[0]), constant_values=fill)
    return x.reshape(n_pad // ms.LANES, ms.LANES)


def cc_tick_fallback_reason(cfg: core.MLTCPConfig,
                            static_factors: bool) -> Optional[str]:
    """Why the fused kernel cannot run the CC tick of ``cfg``, or None.

    The one owner of the kernel's specialization: `mltcp_cc_tick` falls
    back on it, the experiment layer splits compile groups on it
    (``experiment._factors_need_split``) and the IR lint predicts the
    lowering from it (``analysis.jaxpr_lint.kernel_expectation``).

    Static [67] factors replace F(score) per flow (negative entries are
    the "adaptive" sentinel — see core.cc_tick), so with factors present
    favoritism/f_spec are moot and must not force a fallback for a
    Static-baseline arm of an ablation plan.  Sentinel entries reuse the
    kernel's adaptive branch, which implements only the default linear F
    over largest_data_sent; the experiment layer therefore never merges
    Static and adaptive points into one kernel-enabled group unless this
    returns None without factors.
    """
    if static_factors:
        return None
    if cfg.favoritism != "largest_data_sent":
        return f"favoritism={cfg.favoritism!r}"
    if cfg.f_spec != "linear":
        return f"f_spec={cfg.f_spec!r}"
    return None


def mltcp_cc_tick(cfg: core.MLTCPConfig, state: core.MLTCPState,
                  fb: core.Feedback, total_bytes: Array,
                  flow_to_job: Optional[Array] = None, n_jobs: int = 0,
                  static_factors: Optional[Array] = None,
                  comm_elapsed: Optional[Array] = None,
                  est_finish: Optional[Array] = None,
                  dyn: Optional[core.DynamicParams] = None,
                  interpret: Optional[bool] = None
                  ) -> tuple[core.MLTCPState, Array]:
    """core.cc_tick drop-in backed by the fused Pallas kernel.

    The protocol scalars (``dyn``, default: the config's floats) and the
    Static-baseline per-flow ``static_factors`` travel into the kernel as
    *operands* — an f32[1, NDYN] SMEM ref and an [R, 128] lanes ref — so
    traced sweep values (`simulate_sweep`'s vmapped K axis) run fused, one
    program per compile group.  Only structural options the kernel does not
    implement (non-default favoritism, non-linear F family) fall back to
    the jnp oracle; the fallback is loud (``FALLBACK_COUNT`` + one-time
    warning) so ``use_pallas_kernel=True`` can never silently run unfused.
    """
    reason = cc_tick_fallback_reason(cfg, static_factors is not None)
    if reason is not None:
        global FALLBACK_COUNT
        FALLBACK_COUNT += 1
        if reason not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(reason)
            warnings.warn(
                f"mltcp_cc_tick: option {reason} is outside the fused "
                f"kernel's static specialization; falling back to the jnp "
                f"oracle (use_pallas_kernel has no effect for this config)",
                stacklevel=2)
        return core.cc_tick(cfg, state, fb, total_bytes,
                            flow_to_job=flow_to_job, n_jobs=n_jobs,
                            static_factors=static_factors,
                            comm_elapsed=comm_elapsed,
                            est_finish=est_finish, dyn=dyn)
    if dyn is None:
        dyn = core.DynamicParams.from_config(cfg)
    # operand-carried protocol scalars, packed per ms.DYN_FIELDS (==
    # DynamicParams order); concrete floats and traced sweep values take
    # the same path
    dyn_vec = jnp.stack([jnp.asarray(v, jnp.float32) for v in dyn])

    n = state.cc.cwnd.shape[0]
    # Per-flow operands must be rank-1 [N]: the engine-level layers above
    # (fault injection most recently — netsim.faults applies its event
    # tables *before* the CC tick) gather/reduce to flow vectors, and a
    # table leaking through unreduced (e.g. [E, N]) would silently pack
    # garbage rows into lanes.  Fail structurally instead.
    for op_name, op in (("total_bytes", total_bytes),
                        ("static_factors", static_factors),
                        ("comm_elapsed", comm_elapsed),
                        ("est_finish", est_finish)):
        if op is None:
            continue
        shape = jnp.shape(op)
        # a static shape tuple, not a traced value:
        if shape not in ((), (n,)):  # lint: allow(branch-on-traced)
            raise ValueError(
                f"mltcp_cc_tick: operand {op_name!r} has shape {shape}, "
                f"expected scalar or [N]={n} per-flow; an engine-level "
                f"layer (fault event table?) leaked an unreduced array "
                f"into the CC tick")
    n_pad = -(-n // _ROW) * _ROW

    # job-aggregated numerator (paper §4.1: stats aggregated per job);
    # iteration.ack_bytes pins the product's rounding (see its docstring) —
    # the same materialized array feeds the kernel's ack_bytes operand
    ackb = iteration.ack_bytes(fb.num_acks, cfg.cc.mss)
    per_flow_bytes = state.det.bytes_sent + ackb
    if cfg.aggregate_by_job and flow_to_job is not None and n_jobs > 0:
        job_tot = jnp.zeros((n_jobs,), per_flow_bytes.dtype
                            ).at[flow_to_job].add(per_flow_bytes)
        job_numer = job_tot[flow_to_job]
        aggregate = True
    else:
        job_numer = per_flow_bytes
        aggregate = False

    cc = cfg.cc
    p = {
        "algo": int(cc.algo), "variant": int(cc.variant),
        "mss": cc.mss, "rtt": cc.rtt, "tick_dt": cc.tick_dt,
        "min_cwnd": cc.min_cwnd, "reno_beta": cc.reno_beta,
        "cubic_c": cc.cubic_c, "cubic_beta": cc.cubic_beta,
        "cubic_scale": cc.cubic_scale, "line_rate": cc.line_rate,
        "rate_ai": cc.rate_ai, "rate_min": cc.rate_min,
        "dcqcn_g": cc.dcqcn_g, "alpha_timer": cc.alpha_timer,
        "inc_timer": cc.inc_timer, "cnp_interval": cc.cnp_interval,
        "fast_recovery_stages": cc.fast_recovery_stages,
        "aggregate": aggregate,
    }

    with jax.named_scope("cc.pack"):
        d, c = state.det, state.cc
        now_arr = jnp.broadcast_to(jnp.asarray(fb.now, jnp.float32), (n,))
        arrays = {
            "bytes_sent": _pack(d.bytes_sent, n_pad),
            "prev_ack_tstamp": _pack(d.prev_ack_tstamp, n_pad),
            "iter_gap": _pack(d.iter_gap, n_pad, fill=1.0),
            "max_gap": _pack(d.max_gap, n_pad, fill=1.0),
            "cwnd": _pack(c.cwnd, n_pad, fill=1.0),
            "ssthresh": _pack(c.ssthresh, n_pad, fill=1.0),
            "cooldown": _pack(c.cooldown, n_pad),
            "w_max": _pack(c.w_max, n_pad, fill=1.0),
            "epoch_start": _pack(c.epoch_start, n_pad),
            "rate_cur": _pack(c.rate_cur, n_pad, fill=cc.rate_min),
            "rate_target": _pack(c.rate_target, n_pad, fill=cc.rate_min),
            "alpha": _pack(c.alpha, n_pad),
            "t_last_cnp": _pack(c.t_last_cnp, n_pad),
            "t_last_inc": _pack(c.t_last_inc, n_pad),
            "t_last_alpha": _pack(c.t_last_alpha, n_pad),
            "stage": _pack(c.inc_stage, n_pad, dtype=jnp.int32),
            "prev_ratio": _pack(d.bytes_ratio, n_pad),
            "num_acks": _pack(fb.num_acks, n_pad),
            "ack_bytes": _pack(ackb, n_pad),
            "loss": _pack(fb.loss, n_pad),
            "cnp": _pack(fb.cnp, n_pad),
            "now": _pack(now_arr, n_pad),
            "total_bytes": _pack(total_bytes, n_pad, fill=1.0),
            "job_numer": _pack(job_numer, n_pad),
        }
        factors = (None if static_factors is None
                   else _pack(static_factors, n_pad, fill=1.0))
    out = ms.mltcp_tick_arrays(p, dyn_vec, arrays, static_factors=factors,
                               interpret=resolve_interpret(interpret))

    def unpack(x, dtype=jnp.float32):
        return x.reshape(-1)[:n].astype(dtype)

    # boundary counter (metrics-only) maintained outside the kernel, via the
    # same predicate helper the jnp oracle uses (single source of truth)
    boundary = iteration.boundary_mask(d.prev_ack_tstamp, d.iter_gap, dyn.g,
                                       fb.num_acks, fb.now)

    with jax.named_scope("cc.unpack"):
        det = core.MLTCPState(
            cc=state.cc, det=state.det).det._replace(
            bytes_sent=unpack(out["bytes_sent"]),
            bytes_ratio=unpack(out["ratio"]),
            prev_ack_tstamp=unpack(out["prev_ack_tstamp"]),
            iter_gap=unpack(out["iter_gap"]),
            max_gap=unpack(out["max_gap"]),
            n_boundaries=d.n_boundaries + boundary.astype(jnp.int32),
        )
        ccs = state.cc._replace(
            cwnd=unpack(out["cwnd"]),
            ssthresh=unpack(out["ssthresh"]),
            cooldown=unpack(out["cooldown"]),
            w_max=unpack(out["w_max"]),
            epoch_start=unpack(out["epoch_start"]),
            rate_cur=unpack(out["rate_cur"]),
            rate_target=unpack(out["rate_target"]),
            alpha=unpack(out["alpha"]),
            t_last_cnp=unpack(out["t_last_cnp"]),
            t_last_inc=unpack(out["t_last_inc"]),
            t_last_alpha=unpack(out["t_last_alpha"]),
            inc_stage=unpack(out["stage"], jnp.int32),
        )
        rate = unpack(out["rate"])
    return core.MLTCPState(cc=ccs, det=det), rate
