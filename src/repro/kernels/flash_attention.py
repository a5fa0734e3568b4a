"""Flash attention (forward) as a Pallas TPU kernel.

Tiling: grid = (batch*q_heads, T/BLOCK_Q, S/BLOCK_K); the innermost grid
dimension is sequential ("arbitrary") so VMEM scratch (running max m,
normalizer l, f32 accumulator) persists across K/V blocks — the online
softmax never materializes the [T, S] matrix.  GQA is handled in the
BlockSpec index maps (query head -> kv head = h // group), so KV heads are
never repeated in memory.  Causal + sliding-window masks and the Gemma-2
logit softcap are applied in-kernel.

MXU alignment: BLOCK_Q/BLOCK_K default 512 with head_dim padded to a
multiple of 128 by the wrapper (ops.py).  Validated on CPU in interpret
mode against ref.py; the backward pass recomputes through the jnp oracle
(custom_vjp in ops.py), the standard recompute strategy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  softcap: float | None, block_q: int, block_k: int,
                  n_k: int, s_real: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # [bq, dh]
    k = k_ref[0].astype(jnp.float32)                    # [bk, dh]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = kpos < s_real          # padded keys never attended
    if causal:
        mask &= kpos <= qpos
    if window and window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                 # [bq, 1]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == n_k - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float | None = None, s_real: int = 0,
                        scale: float | None = None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = True):
    """q: [B, T, H, D]; k/v: [B, S, K, D] -> [B, T, H, D].

    Requires T % block_q == 0, S % block_k == 0 and D % 128 == 0 (the ops.py
    wrapper pads); GQA group = H // K resolved in the index maps. ``s_real``
    masks padded key positions (0 = all real).
    """
    b, t, h, dh = q.shape
    s_len, kh = k.shape[1], k.shape[2]
    g = h // kh
    block_q = min(block_q, t)
    block_k = min(block_k, s_len)
    assert t % block_q == 0 and s_len % block_k == 0, (t, s_len)
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    n_q, n_k = t // block_q, s_len // block_k

    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, t, dh)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * kh, s_len, dh)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * kh, s_len, dh)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, n_k=n_k,
        s_real=s_real or s_len)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, iq, ik: (bh // g, ik, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, iq, ik: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return jnp.moveaxis(out.reshape(b, h, t, dh), 1, 2)
