"""One accessor over the runtime health counters.

Two process-global counters guard the repo's fusion story: the engine bumps
``engine.TRACE_COUNT`` once per (re)trace of the sweep program, and the
kernel wrapper bumps ``ops.FALLBACK_COUNT`` once per trace that routed the
CC tick through the jnp oracle instead of the fused Pallas kernel.  Before
this module every consumer re-implemented the same fragile pokes —
``getattr(sys.modules.get("repro.kernels.ops"), "FALLBACK_COUNT", 0)`` in
`experiment.py`, in ci.yml heredocs, in benchmark suites.  Now there is one
surface:

    from repro.netsim import counters

    with counters.watch() as w:
        run_plan(plan)
    assert w.traces == 2 and w.fallbacks == 0

A third, ``routes()``, counts the sweep-program traces by how their link
stage routes departures (``engine.ROUTE_COUNT``): ``single_hop`` where
every path is one link, ``select`` where some flow is forwarded.  A
fourth, ``iter_records()``, counts them by the iteration record's length
(``engine.RECORD_COUNT``): ``default`` with ``engine.MAX_ITERS`` slots,
``bounded`` where `run_plan` sized it to its group.

``watch()`` snapshots the counters at entry; the returned handle's
``.traces`` / ``.routes`` / ``.iter_records`` / ``.fallbacks`` are live
deltas (they keep counting after the ``with`` block exits, so reading them
post-exit sees everything the block did).  Reading never imports
``repro.kernels`` — a plan that never enables ``use_pallas_kernel``
shouldn't pay the kernel import.

Two more pieces measure where time goes, on the profiler's clock:

* ``jit_seconds()`` — process totals of the seconds JAX spent tracing
  (``trace``), lowering to MLIR (``lower``), compiling in XLA
  (``compile``) and loading executables from the persistent compilation
  cache (``cache_load``), from one `jax.monitoring` listener installed at
  import.  ``CounterWatch.jit_s`` holds the same four as deltas.
* ``span(name, **args)`` — a `jax.profiler.TraceAnnotation` (a host span
  on the device trace's clock when the profiler runs, a no-op otherwise)
  that also times its block with ``perf_counter`` for the caller:

      with counters.span("run_plan.device", group=0) as sp:
          ...
      sp.seconds, sp.jit_s

  Each finished span also goes to a log of the last ``SPAN_LOG``
  (`recent_spans`), for a reader that does not hold the caller's result:
  a harness that times `run_plan` calls from outside reads their phases
  and their jit seconds there after the fact.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time

import jax

__all__ = ["traces", "routes", "iter_records", "fallbacks",
           "reset_fallback_warnings",
           "jit_seconds", "span", "recent_spans", "watch", "CounterWatch"]

# jax.monitoring events -> jit_seconds() kinds.  Trace, lower and compile
# come as time spans; a jit traced inside another's trace records a span
# nested in the outer one, which is counted once, as the outer's.  The
# backend-compile span encloses the persistent-cache lookup, whose seconds
# go to ``cache_load`` and not to ``compile``.
_JIT_SPANS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
JIT_KINDS = ("trace", "lower", "compile", "cache_load")
_jit_total = dict.fromkeys(JIT_KINDS, 0.0)
_jit_lock = threading.Lock()
_jit_local = threading.local()


def _thread_state():
    st = _jit_local
    if not hasattr(st, "spans"):
        # per kind, the (start, seconds counted) of finished spans that a
        # later, enclosing span of the same kind may still swallow
        st.spans = {kind: [] for kind in _JIT_SPANS.values()}
        st.cache_load = 0.0      # cache loads inside the open compile span
    return st


def _on_span(event: str, start: float, end: float, **_) -> None:
    kind = _JIT_SPANS.get(event)
    if kind is None:
        return
    st = _thread_state()
    seconds = end - start
    if kind == "compile":
        seconds -= st.cache_load
        st.cache_load = 0.0
    spans = st.spans[kind]
    nested = 0.0
    while spans and spans[-1][0] >= start:
        nested += spans.pop()[1]
    spans.append((start, seconds))
    del spans[:-4096]
    with _jit_lock:
        _jit_total[kind] += seconds - nested


def _on_duration(event: str, seconds: float, **_) -> None:
    if event != _CACHE_LOAD:
        return
    _thread_state().cache_load += seconds
    with _jit_lock:
        _jit_total["cache_load"] += seconds


# installed once, when this module is first imported
jax.monitoring.register_event_time_span_listener(_on_span)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def traces() -> int:
    """Current engine.TRACE_COUNT (sweep-program traces this process)."""
    from repro.netsim import engine

    return engine.TRACE_COUNT


def routes() -> dict[str, int]:
    """Sweep-program traces this process, by the link stage's routing form
    (``engine.ROUTE_COUNT``)."""
    from repro.netsim import engine

    return dict(engine.ROUTE_COUNT)


def iter_records() -> dict[str, int]:
    """Sweep-program traces this process, by the iteration record's length
    (``engine.RECORD_COUNT``)."""
    from repro.netsim import engine

    return dict(engine.RECORD_COUNT)


def fallbacks() -> int:
    """Current ops.FALLBACK_COUNT without importing the kernels package
    (0 when repro.kernels.ops was never imported — nothing can have fallen
    back if the wrapper never loaded)."""
    mod = sys.modules.get("repro.kernels.ops")
    return getattr(mod, "FALLBACK_COUNT", 0) if mod is not None else 0


def reset_fallback_warnings() -> None:
    """Re-arm ops.py's once-per-reason fallback warning (no-op when the
    kernels were never imported).  `run_plan` calls this per plan so each
    plan warns at most once per fallback reason."""
    mod = sys.modules.get("repro.kernels.ops")
    if mod is not None:
        mod.reset_fallback_warnings()


def jit_seconds() -> dict[str, float]:
    """Seconds this process has spent in JAX's trace, lower, compile and
    persistent-cache load, by kind (``JIT_KINDS``)."""
    with _jit_lock:
        return dict(_jit_total)


# how many finished spans `recent_spans` keeps
SPAN_LOG = 4096
_span_log: collections.deque = collections.deque(maxlen=SPAN_LOG)
_span_seq = itertools.count()


class Span:
    """One `span`: its ``name`` and ``args``; when its block exits,
    ``seconds``, ``jit_s`` (the `jit_seconds()` deltas over the block) and
    ``seq`` (the process's finished spans numbered from 0)."""

    __slots__ = ("name", "args", "seconds", "jit_s", "seq")

    def __init__(self, name: str, args: dict) -> None:
        self.name, self.args = name, args
        self.seconds = 0.0
        self.jit_s = dict.fromkeys(JIT_KINDS, 0.0)
        self.seq = -1


@contextlib.contextmanager
def span(name: str, **args):
    """A named host span: a profiler `TraceAnnotation` carrying ``args``,
    which also times the block; yields a `Span` that is filled in and
    logged (`recent_spans`) when the block exits, normally or by an
    exception."""
    sp = Span(name, args)
    with jax.profiler.TraceAnnotation(name, **args):
        jit0 = jit_seconds()
        t0 = time.perf_counter()
        try:
            yield sp
        finally:
            sp.seconds = time.perf_counter() - t0
            jit1 = jit_seconds()
            sp.jit_s = {k: jit1[k] - jit0[k] for k in JIT_KINDS}
            sp.seq = next(_span_seq)
            _span_log.append(sp)


def recent_spans() -> list[Span]:
    """The last ``SPAN_LOG`` finished spans of this process, oldest first;
    the log is whole while the first one's ``seq`` is 0."""
    return list(_span_log)


class CounterWatch:
    """Live deltas of the counters since construction."""

    def __init__(self) -> None:
        self._traces0 = traces()
        self._routes0 = routes()
        self._records0 = iter_records()
        self._fallbacks0 = fallbacks()
        self._jit0 = jit_seconds()

    @property
    def traces(self) -> int:
        return traces() - self._traces0

    @property
    def routes(self) -> dict[str, int]:
        """`routes()` deltas, by routing form."""
        return {k: n - self._routes0[k] for k, n in routes().items()}

    @property
    def iter_records(self) -> dict[str, int]:
        """`iter_records()` deltas, by record form."""
        return {k: n - self._records0[k] for k, n in iter_records().items()}

    @property
    def fallbacks(self) -> int:
        return fallbacks() - self._fallbacks0

    @property
    def jit_s(self) -> dict[str, float]:
        """`jit_seconds()` deltas, by kind."""
        now = jit_seconds()
        return {k: now[k] - self._jit0[k] for k in JIT_KINDS}


@contextlib.contextmanager
def watch(*, reset_warnings: bool = False):
    """Context manager yielding a `CounterWatch` over the enclosed work.

    ``reset_warnings=True`` additionally re-arms the once-per-reason kernel
    fallback warning at entry (the per-plan semantics `run_plan` wants).
    """
    if reset_warnings:
        reset_fallback_warnings()
    yield CounterWatch()
