"""Pallas kernel validation (interpret mode) against the pure-jnp oracles.

Per the brief: sweep shapes/dtypes per kernel and assert_allclose vs ref.py.
The CC-tick kernel is additionally exercised with *traced* DynamicParams
and under vmap (the sweep-engine shapes), where the operand-carried
protocol scalars must keep it fused — FALLBACK_COUNT pins that no case
silently routes through the jnp oracle.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Algo,
    CCParams,
    DynamicParams,
    Feedback,
    MLTCPConfig,
    Variant,
    cc_tick,
    init_state,
)
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (b, t, s, h, kv, dh, causal, window, softcap, dtype)
    (2, 128, 128, 4, 4, 64, True, 0, None, jnp.float32),
    (1, 256, 256, 4, 2, 64, True, 0, None, jnp.float32),
    (2, 128, 128, 4, 1, 32, True, 0, None, jnp.float32),     # MQA + pad dh
    (1, 256, 256, 2, 2, 128, True, 64, None, jnp.float32),   # sliding window
    (1, 128, 128, 2, 2, 64, True, 0, 50.0, jnp.float32),     # softcap
    (2, 128, 128, 4, 4, 64, False, 0, None, jnp.float32),    # bidirectional
    (1, 192, 192, 2, 2, 64, True, 0, None, jnp.float32),     # non-pow2 T pad
    (2, 128, 128, 4, 4, 64, True, 0, None, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_ref(case):
    b, t, s, h, kv, dh, causal, window, softcap, dtype = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, t, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, dh), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, dh), dtype)
    out = ops.flash_attention(q, k, v, causal, window, softcap)
    want = ref.ref_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_grad_matches_ref():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))

    def loss_kernel(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, True, 0, None) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref.ref_attention(q, k, v) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

RGLRU_CASES = [
    (2, 64, 128, jnp.float32),
    (1, 128, 256, jnp.float32),
    (3, 33, 130, jnp.float32),     # ragged D -> pad
    (2, 64, 128, jnp.bfloat16),
]


@pytest.mark.parametrize("case", RGLRU_CASES)
def test_rg_lru_matches_ref(case):
    b, t, d, dtype = case
    ks = jax.random.split(KEY, 2)
    a = jax.random.uniform(ks[0], (b, t, d), dtype, 0.2, 0.99)
    x = jax.random.normal(ks[1], (b, t, d), dtype)
    out = ops.rg_lru(a, x)
    want = ref.ref_rg_lru(a, x)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_rg_lru_grad_matches_ref():
    ks = jax.random.split(KEY, 2)
    a = jax.random.uniform(ks[0], (2, 32, 128), jnp.float32, 0.2, 0.99)
    x = jax.random.normal(ks[1], (2, 32, 128))
    gk = jax.grad(lambda a, x: jnp.sum(ops.rg_lru(a, x) ** 2),
                  argnums=(0, 1))(a, x)
    gr = jax.grad(lambda a, x: jnp.sum(ref.ref_rg_lru(a, x) ** 2),
                  argnums=(0, 1))(a, x)
    for g1, g2 in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused protocol tick
# ---------------------------------------------------------------------------

def _random_protocol_state(n, cfg, key):
    st = init_state(n, cfg)
    ks = jax.random.split(key, 12)
    det = st.det._replace(
        bytes_sent=jax.random.uniform(ks[0], (n,)) * 1e8,
        bytes_ratio=jax.random.uniform(ks[1], (n,)),
        prev_ack_tstamp=jax.random.uniform(ks[2], (n,)) * 0.01,
        iter_gap=jax.random.uniform(ks[3], (n,), minval=1e-3, maxval=0.05),
        max_gap=jax.random.uniform(ks[4], (n,), minval=1e-3, maxval=0.05),
    )
    cc = st.cc._replace(
        cwnd=jax.random.uniform(ks[5], (n,), minval=1.0, maxval=500.0),
        ssthresh=jax.random.uniform(ks[6], (n,), minval=10.0, maxval=1e4),
        cooldown=jax.random.uniform(ks[7], (n,)) * 2e-4,
        w_max=jax.random.uniform(ks[8], (n,), minval=1.0, maxval=500.0),
        epoch_start=jax.random.uniform(ks[9], (n,)) * 0.01,
        rate_cur=jax.random.uniform(ks[10], (n,), minval=1e6, maxval=6e9),
        rate_target=jax.random.uniform(ks[11], (n,), minval=1e6, maxval=6e9),
        alpha=jax.random.uniform(ks[0], (n,)),
        t_last_cnp=jax.random.uniform(ks[1], (n,)) * 0.01,
        t_last_inc=jax.random.uniform(ks[2], (n,)) * 0.01,
        t_last_alpha=jax.random.uniform(ks[3], (n,)) * 0.01,
        inc_stage=jax.random.randint(ks[4], (n,), 0, 10),
    )
    return st._replace(det=det, cc=cc)


PROTO_CASES = [
    (Algo.RENO, Variant.WI, 1.75, 0.25),
    (Algo.RENO, Variant.MD, 1.0, 1.0),
    (Algo.RENO, Variant.OFF, 1.75, 0.25),
    (Algo.CUBIC, Variant.WI, 1.0, 0.5),
    (Algo.CUBIC, Variant.MD, 0.8, 0.8),
    (Algo.DCQCN, Variant.WI, 1.067, 0.267),
    (Algo.DCQCN, Variant.MD, 1.067, 0.267),
    (Algo.DCQCN, Variant.BOTH, 1.067, 0.267),
]


@pytest.mark.parametrize("case", PROTO_CASES)
@pytest.mark.parametrize("n", [7, 64, 300])
def test_mltcp_tick_kernel_matches_core(case, n):
    algo, variant, slope, intercept = case
    cfg = MLTCPConfig(cc=CCParams(algo=int(algo), variant=int(variant)),
                      slope=slope, intercept=intercept)
    key = jax.random.PRNGKey(n)
    st = _random_protocol_state(n, cfg, key)
    ks = jax.random.split(key, 4)
    fb = Feedback(
        num_acks=jnp.where(jax.random.uniform(ks[0], (n,)) < 0.7,
                           jax.random.uniform(ks[1], (n,)) * 40.0, 0.0),
        loss=jax.random.uniform(ks[2], (n,)) < 0.2,
        cnp=jax.random.uniform(ks[3], (n,)) < 0.3,
        now=jnp.asarray(0.0123),
    )
    total = jnp.full((n,), 1e8)
    f2j = jnp.arange(n) % 3

    want_st, want_rate = cc_tick(cfg, st, fb, total, flow_to_job=f2j,
                                 n_jobs=3)
    got_st, got_rate = ops.mltcp_cc_tick(cfg, st, fb, total, flow_to_job=f2j,
                                         n_jobs=3)
    np.testing.assert_allclose(np.asarray(got_rate), np.asarray(want_rate),
                               rtol=1e-6)
    for name in want_st.cc._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got_st.cc, name)),
            np.asarray(getattr(want_st.cc, name)), rtol=1e-6,
            err_msg=f"cc.{name}")
    for name in want_st.det._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got_st.det, name)),
            np.asarray(getattr(want_st.det, name)), rtol=1e-6,
            err_msg=f"det.{name}")


def _random_feedback(n, key, now=0.0123):
    ks = jax.random.split(key, 4)
    return Feedback(
        num_acks=jnp.where(jax.random.uniform(ks[0], (n,)) < 0.7,
                           jax.random.uniform(ks[1], (n,)) * 40.0, 0.0),
        loss=jax.random.uniform(ks[2], (n,)) < 0.2,
        cnp=jax.random.uniform(ks[3], (n,)) < 0.3,
        now=jnp.asarray(now),
    )


def _assert_states_equal(got, want, exact=False):
    assert_fn = (np.testing.assert_array_equal if exact else
                 lambda a, b, err_msg: np.testing.assert_allclose(
                     a, b, rtol=1e-6, err_msg=err_msg))
    for grp in ("cc", "det"):
        for name in getattr(want, grp)._fields:
            assert_fn(np.asarray(getattr(getattr(got, grp), name)),
                      np.asarray(getattr(getattr(want, grp), name)),
                      err_msg=f"{grp}.{name}")


@pytest.mark.parametrize("case", [(Algo.RENO, Variant.WI),
                                  (Algo.CUBIC, Variant.MD),
                                  (Algo.DCQCN, Variant.BOTH)])
def test_mltcp_tick_kernel_traced_dyn_stays_fused(case):
    """Traced DynamicParams (the sweep axis) run through the fused kernel —
    operand-carried scalars, no oracle fallback, bit-equal to core."""
    algo, variant = case
    n = 70
    cfg = MLTCPConfig(cc=CCParams(algo=int(algo), variant=int(variant)))
    st = _random_protocol_state(n, cfg, jax.random.PRNGKey(5))
    fb = _random_feedback(n, jax.random.PRNGKey(6))
    total = jnp.full((n,), 1e8)
    f2j = jnp.arange(n) % 4

    def run(tick_fn, dyn_vals):
        dyn = DynamicParams(*dyn_vals)
        st2, rate = tick_fn(cfg, st, fb, total, flow_to_job=f2j, n_jobs=4,
                            dyn=dyn)
        return st2, rate

    dyn_vals = tuple(jnp.asarray(v, jnp.float32)
                     for v in (1.3, 0.4, 0.8, 0.45, 2e-3))
    before = ops.FALLBACK_COUNT
    got_st, got_rate = jax.jit(lambda dv: run(ops.mltcp_cc_tick, dv))(dyn_vals)
    want_st, want_rate = jax.jit(lambda dv: run(cc_tick, dv))(dyn_vals)
    assert ops.FALLBACK_COUNT == before
    _assert_states_equal(got_st, want_st, exact=True)
    np.testing.assert_array_equal(np.asarray(got_rate), np.asarray(want_rate))


def test_mltcp_tick_kernel_vmaps_over_dyn():
    """A batched DynamicParams axis (K sweep points) vmaps over the kernel
    call — one fused program, K results matching core point-for-point."""
    n, k = 40, 5
    cfg = MLTCPConfig(cc=CCParams(algo=int(Algo.RENO),
                                  variant=int(Variant.WI)))
    st = _random_protocol_state(n, cfg, jax.random.PRNGKey(8))
    # every flow in congestion avoidance, where the WI slope scales growth
    # (slow start ignores F, so a random state may not depend on it)
    st = st._replace(cc=st.cc._replace(ssthresh=0.5 * st.cc.cwnd))
    fb = _random_feedback(n, jax.random.PRNGKey(9))
    total = jnp.full((n,), 1e8)
    f2j = jnp.arange(n) % 3
    slopes = jnp.linspace(0.5, 2.5, k, dtype=jnp.float32)
    base = DynamicParams.from_config(cfg)
    dyns = DynamicParams(slope=slopes,
                         intercept=jnp.broadcast_to(base.intercept, (k,)),
                         g=jnp.broadcast_to(base.g, (k,)),
                         gamma=jnp.broadcast_to(base.gamma, (k,)),
                         init_comm_gap=jnp.broadcast_to(base.init_comm_gap,
                                                        (k,)))

    def one(tick_fn, dyn):
        st2, rate = tick_fn(cfg, st, fb, total, flow_to_job=f2j, n_jobs=3,
                            dyn=dyn)
        return st2.cc.cwnd, st2.det.bytes_ratio, rate

    before = ops.FALLBACK_COUNT
    got = jax.jit(jax.vmap(lambda d: one(ops.mltcp_cc_tick, d)))(dyns)
    want = jax.jit(jax.vmap(lambda d: one(cc_tick, d)))(dyns)
    assert ops.FALLBACK_COUNT == before
    for g, w in zip(got, want):
        assert g.shape[0] == k
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the sweep axis must actually vary the outcome
    assert np.unique(np.asarray(got[0]), axis=0).shape[0] > 1


def test_mltcp_tick_kernel_static_factors():
    """The Static [67] per-flow factors ride into the kernel as an operand
    (they used to force an unconditional oracle fallback) — and since they
    replace F(score) entirely, even a non-linear f_spec stays fused."""
    n = 33
    cfg = MLTCPConfig(cc=CCParams(algo=int(Algo.RENO),
                                  variant=int(Variant.WI)),
                      f_spec="F3")
    st = _random_protocol_state(n, cfg, jax.random.PRNGKey(11))
    fb = _random_feedback(n, jax.random.PRNGKey(12))
    total = jnp.full((n,), 1e8)
    f2j = jnp.arange(n) % 3
    factors = jnp.asarray(0.5 + 1.5 * (jnp.arange(n) % 3) / 2.0, jnp.float32)

    before = ops.FALLBACK_COUNT
    got_st, got_rate = ops.mltcp_cc_tick(cfg, st, fb, total, flow_to_job=f2j,
                                         n_jobs=3, static_factors=factors)
    assert ops.FALLBACK_COUNT == before
    want_st, want_rate = cc_tick(cfg, st, fb, total, flow_to_job=f2j,
                                 n_jobs=3, static_factors=factors)
    _assert_states_equal(got_st, want_st)
    np.testing.assert_allclose(np.asarray(got_rate), np.asarray(want_rate),
                               rtol=1e-6)


def test_mltcp_tick_fallback_is_loud():
    """Structural options outside the kernel's specialization fall back to
    the oracle — incrementing FALLBACK_COUNT and warning once."""
    n = 16
    cfg = MLTCPConfig(cc=CCParams(algo=int(Algo.RENO),
                                  variant=int(Variant.WI)),
                      favoritism="earliest_iter_start")
    st = _random_protocol_state(n, cfg, jax.random.PRNGKey(13))
    fb = _random_feedback(n, jax.random.PRNGKey(14))
    total = jnp.full((n,), 1e8)

    before = ops.FALLBACK_COUNT
    ops._FALLBACK_WARNED.discard("favoritism='earliest_iter_start'")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got_st, _ = ops.mltcp_cc_tick(cfg, st, fb, total)
    assert ops.FALLBACK_COUNT == before + 1
    assert any("favoritism" in str(x.message) for x in w)
    # one-time: a second call with the same reason stays silent
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        ops.mltcp_cc_tick(cfg, st, fb, total)
    assert ops.FALLBACK_COUNT == before + 2
    assert not any("favoritism" in str(x.message) for x in w2)
    # and the fallback result is the oracle's
    want_st, _ = cc_tick(cfg, st, fb, total)
    _assert_states_equal(got_st, want_st)


def test_interpret_default_follows_cpu_backend(monkeypatch):
    """With no per-call override the CPU backend runs the kernels through
    the Pallas interpreter (the TPU compiles them; no env var decides),
    and a backend with no kernel path raises instead of interpreting."""
    assert jax.default_backend() == "cpu"
    assert ops.resolve_interpret() is True
    assert ops.resolve_interpret(False) is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret() is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.resolve_interpret()
    assert ops.resolve_interpret(True) is True


def test_interpret_per_call_override():
    """Every kernel wrapper takes a per-call interpret override (None =
    the platform's choice); interpret=True must behave exactly like the
    default on the CPU backend."""
    n = 24
    cfg = MLTCPConfig(cc=CCParams(algo=int(Algo.RENO),
                                  variant=int(Variant.WI)))
    st = _random_protocol_state(n, cfg, jax.random.PRNGKey(15))
    fb = _random_feedback(n, jax.random.PRNGKey(16))
    total = jnp.full((n,), 1e8)
    a_st, a_rate = ops.mltcp_cc_tick(cfg, st, fb, total, interpret=True)
    b_st, b_rate = ops.mltcp_cc_tick(cfg, st, fb, total)
    _assert_states_equal(a_st, b_st, exact=True)
    np.testing.assert_array_equal(np.asarray(a_rate), np.asarray(b_rate))

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    np.testing.assert_array_equal(
        np.asarray(ops.flash_attention(q, k, v, True, 0, None, True)),
        np.asarray(ops.flash_attention(q, k, v)))
    a = jax.random.uniform(ks[0], (2, 32, 128), jnp.float32, 0.2, 0.99)
    x = jax.random.normal(ks[1], (2, 32, 128))
    np.testing.assert_array_equal(np.asarray(ops.rg_lru(a, x, True)),
                                  np.asarray(ops.rg_lru(a, x)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_n_boundaries_match_over_fuzzed_sequences(seed):
    """Fuzz Algorithm 1's boundary counter across many ticks: the kernel
    wrapper's out-of-kernel counter (via `iteration.boundary_mask`) must
    track the jnp oracle exactly — one source of truth, no drift."""
    n, n_ticks, dt = 33, 120, 2e-5
    cfg = MLTCPConfig(cc=CCParams(algo=int(Algo.RENO),
                                  variant=int(Variant.WI), tick_dt=dt),
                      slope=1.75, intercept=0.25, init_comm_gap=3 * dt)
    st_ref = init_state(n, cfg)
    st_ker = init_state(n, cfg)
    total = jnp.full((n,), 2e6)
    f2j = jnp.arange(n) % 4
    rng = np.random.default_rng(seed)
    for i in range(n_ticks):
        # bursty on/off ack pattern so gaps straddle g * iter_gap
        burst = rng.uniform(size=n) < (0.9 if (i // 10) % 2 == 0 else 0.05)
        fb = Feedback(
            num_acks=jnp.asarray(burst * rng.uniform(1, 20, n), jnp.float32),
            loss=jnp.asarray(rng.uniform(size=n) < 0.03),
            cnp=jnp.zeros((n,), bool),
            now=jnp.asarray(i * dt, jnp.float32))
        st_ref, _ = cc_tick(cfg, st_ref, fb, total, flow_to_job=f2j, n_jobs=4)
        st_ker, _ = ops.mltcp_cc_tick(cfg, st_ker, fb, total,
                                      flow_to_job=f2j, n_jobs=4)
        np.testing.assert_array_equal(
            np.asarray(st_ker.det.n_boundaries),
            np.asarray(st_ref.det.n_boundaries),
            err_msg=f"n_boundaries drift at tick {i}")
    assert int(np.asarray(st_ref.det.n_boundaries).max()) > 0
