"""Unit + property tests for the MLTCP core (protocol invariants)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ModuleNotFoundError:  # optional test extra; fall back to a fixed grid
    from _hypothesis_fallback import given, settings
    from _hypothesis_fallback import strategies as st

from repro.core import (
    Algo,
    CCParams,
    Feedback,
    IterDetectParams,
    MLTCPConfig,
    Variant,
    cc_tick,
    init_state,
    make_fn,
    paper_functions,
    run_on_trace,
)
from repro.core.aggressiveness import is_srpt_reinforcing


# ---------------------------------------------------------------------------
# aggressiveness functions (paper §3.3 requirements)
# ---------------------------------------------------------------------------

def test_paper_functions_shapes():
    fns = paper_functions()
    xs = jnp.linspace(0, 1, 101)
    for name in ("F1", "F2", "F3", "F4"):
        assert is_srpt_reinforcing(fns[name]), name      # increasing
    for name in ("F5", "F6"):
        assert not is_srpt_reinforcing(fns[name]), name  # decreasing
    # all six share the range [0.25, 2] on [0, 1] (paper §4.8)
    for name, fn in fns.items():
        ys = np.asarray(fn(xs))
        assert ys.min() >= 0.24 and ys.max() <= 2.01, (name, ys.min(), ys.max())


@given(slope=st.floats(0.0, 4.0), intercept=st.floats(0.01, 2.0))
@settings(max_examples=50, deadline=None)
def test_linear_f_requirements(slope, intercept):
    f = make_fn("linear", slope, intercept)
    assert is_srpt_reinforcing(f)
    xs = jnp.linspace(0, 1, 33)
    assert bool(jnp.all(f(xs) > 0))      # aggressiveness must stay positive


# ---------------------------------------------------------------------------
# Algorithm 1 — iteration-boundary detection
# ---------------------------------------------------------------------------

def _trace_for_iterations(n_iters, comm_ticks, gap_ticks, dt=1e-4):
    """Synthetic ack trace: bursts of acks separated by silent gaps."""
    times, counts = [], []
    t = 0.0
    for _ in range(n_iters):
        for _ in range(comm_ticks):
            times.append(t)
            counts.append(10.0)
            t += dt
        t += gap_ticks * dt      # compute-phase silence
        times.append(t)          # first ack of next burst
        counts.append(10.0)
        t += dt
    return jnp.asarray(times), jnp.asarray(counts)


def test_algorithm1_detects_boundaries():
    n_iters = 8
    times, counts = _trace_for_iterations(n_iters, comm_ticks=50,
                                          gap_ticks=200)
    params = IterDetectParams(total_bytes=jnp.asarray([1e6]),
                              init_comm_gap=jnp.asarray(1e-3))
    final = run_on_trace(times, counts, params)
    # one boundary per gap (first ack after silence), +- the warmup one
    assert abs(int(final.n_boundaries[0]) - n_iters) <= 1
    # iter_gap EWMA converged near the true gap (200 * 1e-4 = 20 ms)
    assert 5e-3 < float(final.iter_gap[0]) < 40e-3


@given(gap_ticks=st.integers(100, 2000), comm_ticks=st.integers(20, 200))
@settings(max_examples=15, deadline=None)
def test_algorithm1_no_false_positives_within_comm(gap_ticks, comm_ticks):
    """Within a comm burst (uniform ack cadence) no boundaries fire after
    the initial one."""
    times, counts = _trace_for_iterations(4, comm_ticks, gap_ticks)
    params = IterDetectParams(total_bytes=jnp.asarray([1e6]),
                              init_comm_gap=jnp.asarray(1e-3))
    final = run_on_trace(times, counts, params)
    assert int(final.n_boundaries[0]) <= 5   # 4 gaps + possible warmup


def test_bytes_ratio_bounded():
    params = IterDetectParams(total_bytes=jnp.asarray([1e4]),
                              init_comm_gap=jnp.asarray(1.0))
    times = jnp.arange(100, dtype=jnp.float32) * 1e-4
    counts = jnp.full((100,), 100.0)  # sends far more than total_bytes
    final = run_on_trace(times, counts, params)
    assert 0.0 <= float(final.bytes_ratio[0]) <= 1.0


# ---------------------------------------------------------------------------
# congestion-control invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", [Algo.RENO, Algo.CUBIC, Algo.DCQCN])
@pytest.mark.parametrize("variant", [Variant.OFF, Variant.WI, Variant.MD])
def test_cc_state_stays_positive_and_bounded(algo, variant):
    cfg = MLTCPConfig(cc=CCParams(algo=int(algo), variant=int(variant)))
    n = 16
    st = init_state(n, cfg)
    total = jnp.full((n,), 1e7)
    rng = np.random.default_rng(0)
    for i in range(200):
        fb = Feedback(
            num_acks=jnp.asarray(rng.uniform(0, 30, n) *
                                 (rng.uniform(size=n) < 0.8), jnp.float32),
            loss=jnp.asarray(rng.uniform(size=n) < 0.05),
            cnp=jnp.asarray(rng.uniform(size=n) < 0.1),
            now=jnp.asarray(i * 2e-5, jnp.float32))
        st, rate = cc_tick(cfg, st, fb, total)
        assert bool(jnp.all(st.cc.cwnd >= cfg.cc.min_cwnd))
        assert bool(jnp.all(rate > 0))
        assert bool(jnp.all(st.cc.rate_cur <= cfg.cc.line_rate + 1))
        assert bool(jnp.all((st.cc.alpha >= 0) & (st.cc.alpha <= 1)))
        assert bool(jnp.all(jnp.isfinite(st.cc.cwnd)))


def test_md_never_increases_window():
    """A decrease step must never raise cwnd, even with F > 1 (MD clips)."""
    cfg = MLTCPConfig(cc=CCParams(algo=int(Algo.RENO),
                                  variant=int(Variant.MD)),
                      slope=1.0, intercept=1.0)   # F in [1, 2]
    st = init_state(4, cfg)
    st = st._replace(cc=st.cc._replace(cwnd=jnp.full((4,), 100.0)),
                     det=st.det._replace(bytes_ratio=jnp.asarray(
                         [0.0, 0.5, 0.9, 1.0])))
    fb = Feedback(num_acks=jnp.zeros(4), loss=jnp.ones(4, bool),
                  cnp=jnp.zeros(4, bool), now=jnp.asarray(1.0))
    st2, _ = cc_tick(cfg, st, fb, jnp.full((4,), 1e6))
    assert bool(jnp.all(st2.cc.cwnd <= 100.0))


def test_off_variant_ignores_bytes_ratio():
    cfg = MLTCPConfig(cc=CCParams(algo=int(Algo.RENO),
                                  variant=int(Variant.OFF)))
    st = init_state(2, cfg)
    st = st._replace(
        cc=st.cc._replace(cwnd=jnp.asarray([50.0, 50.0]),
                          ssthresh=jnp.asarray([1.0, 1.0])),
        det=st.det._replace(bytes_ratio=jnp.asarray([0.0, 1.0]),
                            prev_ack_tstamp=jnp.asarray([0.999, 0.999]),
                            iter_gap=jnp.asarray([10.0, 10.0])))
    fb = Feedback(num_acks=jnp.asarray([10.0, 10.0]),
                  loss=jnp.zeros(2, bool), cnp=jnp.zeros(2, bool),
                  now=jnp.asarray(1.0))
    st2, _ = cc_tick(cfg, st, fb, jnp.full((2,), 1e6))
    # same acks, different bytes_ratio -> identical growth when OFF
    assert float(st2.cc.cwnd[0]) == float(st2.cc.cwnd[1])


def test_cube_root_matches_cbrt_and_is_zero_at_zero():
    """CUBIC's K uses exp(log(x)/3) (Mosaic has no cbrt lowering): exactly
    0 at x=0, and within f32 rounding of cbrt over 1e-6..1e6.  The bound:
    log's absolute error eps*|log x| <= 1.2e-7 * 13.8 becomes a relative
    error of a third of that after exp, plus an ulp each for log and exp,
    so under 1e-6."""
    from repro.core.cc.cubic import cube_root

    x = jnp.concatenate([jnp.zeros((1,)),
                         jnp.logspace(-6, 6, 241, dtype=jnp.float32)])
    got = np.asarray(cube_root(x))
    assert got[0] == 0.0
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got[1:], np.cbrt(np.asarray(x[1:], np.float64)),
                               rtol=1e-6)
