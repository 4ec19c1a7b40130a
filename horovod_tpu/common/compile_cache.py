"""Where compiled programs are kept between processes, and the process's
own account of what it traced, lowered and compiled.

JAX's persistent compilation cache is the difference between a process
that compiles a 24-layer step from cold and one that loads it. The
directory is part of the cache's key, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` wins when the operator (or the machine)
sets it — JAX reads that variable itself and this module then sets
nothing — and otherwise the cache lives at one fixed, git-ignored path
under the checkout. ``common/exe_cache.py`` (opt-in
``HOROVOD_EXE_CACHE``) is a different store and is not touched here.

**The compile ledger.** :func:`ensure` also puts listeners on
``jax.monitoring`` (once a process) that turn JAX's own events into
process spans of ``common/tracing.py``'s ring: ``hvd.init.jit_trace``
(function to closed jaxpr), ``hvd.init.jit_lower`` (jaxpr to StableHLO,
the Mosaic lowering of the Pallas kernels inside it) and
``hvd.init.jit_compile`` (the backend compile, or the persistent cache's
answer in its place), each with ``fun``, the name JAX gives (the
function's for a trace, the module's, ``jit(<function>)``, for the other
two). They run only while JAX traces, lowers or compiles: a compiled
step calls none of this.

* An event that starts while none is open on its thread is *outermost*:
  a live span from its start (a child of the thread's active span, and
  in a running profiler session like any other), recorded if it lasted
  ``SMALL_S`` or more. A shorter one (eager one-operation programs
  compile by the dozen in any JAX process) is added to a tally that the
  next recorded span of its kind carries as ``small`` / ``small_s``
  (and ``small_misses`` / ``small_miss_s`` on a compile span).
* An event that starts inside another, of any of the three kinds (a
  ``jit`` traced inside a ``jit``'s trace, an operation run eagerly
  while a function is traced, what a lowering rule traces), is counted
  in the enclosing span's ``inner`` (events, at any depth) and
  ``inner_s`` (seconds of those directly inside it); one of
  ``INNER_SPAN_S`` or more is also a span of its own, a child with
  ``depth``, written when it ends.
* ``cache`` on a compile span is the persistent cache's answer, from
  JAX's own events on that thread while the span was open: ``hit``
  (with ``retrieval_s`` and ``saved_s``), ``miss`` (compiled, and
  written for the next process), ``uncached`` (looked up and compiled,
  but under JAX's floors for keeping, ``jax_persistent_cache_min_*``:
  every process compiles these) or ``off`` (no cache key: the cache was
  not asked).

The registry's ``jit.*`` counters (``common/metrics.py``:
``JIT_METRICS``) count every compile, the small and the inner too.
"""

from __future__ import annotations

import os
import threading

from . import tracing
from .metrics import registry as _metrics

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")

# an outermost event under SMALL_S is tallied and not a span; an inner
# one of INNER_SPAN_S or more is a span of its own
SMALL_S = 0.020
INNER_SPAN_S = 0.100

_COMPILE = "hvd.init.jit_compile"
_SPAN_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "hvd.init.jit_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "hvd.init.jit_lower",
    "/jax/core/compile/backend_compile_duration": _COMPILE,
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}


def ensure() -> str:
    """Point JAX's persistent compilation cache at its directory, start
    the compile ledger, and return that directory. Idempotent; called
    from ``hvd.init()`` and ``hvd.serve()``."""
    _ledger.install()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        # A process that already compiled something decided "no cache"
        # at that first compile; make it look again.
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    return DEFAULT_DIR


class _Open:
    """One event of JAX's between its start and its end."""

    __slots__ = ("name", "start", "fun", "span", "inner", "inner_s",
                 "cache", "cache_s")

    def __init__(self, name, start, fun, span):
        self.name, self.start, self.fun, self.span = name, start, fun, span
        self.inner, self.inner_s = 0, 0.0
        self.cache, self.cache_s = "off", {}


class CompileLedger:
    """The listeners and what they keep between an event's start and its
    end: a stack of open events for each thread, and the process's tally
    of outermost events too short to be spans."""

    def __init__(self) -> None:
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._installed = False
        # span name -> [events, seconds, cache misses, their seconds]
        self._small = {name: [0, 0.0, 0, 0.0] for name in _SPAN_OF.values()}

    def install(self) -> None:
        with self._lock:
            if self._installed:
                return
            self._installed = True
        from jax import monitoring

        monitoring.register_scalar_listener(self._on_start)
        monitoring.register_event_time_span_listener(self._on_end)
        monitoring.register_event_listener(self._on_cache_event)
        monitoring.register_event_duration_secs_listener(
            self._on_cache_seconds
        )

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _on_start(self, event, value, fun_name="", **_) -> None:
        name = _SPAN_OF.get(event)
        if name is None:
            return
        stack = self._stack()
        span = None
        if not stack:
            span = tracing.span(name, fun=fun_name)
            span.__enter__()
        stack.append(_Open(name, value, fun_name, span))

    def _on_end(self, event, start, end, **_) -> None:
        name = _SPAN_OF.get(event)
        if name is None:
            return
        stack = self._stack()
        # whatever lies above the event that ends never will (JAX skips
        # the end where another listener's start raised); an event that
        # began before the ledger did is not there at all
        while stack and (stack[-1].name, stack[-1].start) != (name, start):
            stale = stack.pop()
            if stale.span is not None:
                stale.span.discard()
        if not stack:
            return
        top = stack.pop()
        seconds = end - start
        tags = {}
        if name == _COMPILE:
            tags["cache"] = top.cache
            tags.update(top.cache_s)
            _metrics.counter("jit.compiles")
            _metrics.counter("jit.compile_s", seconds)
        if top.inner:
            tags.update(inner=top.inner, inner_s=round(top.inner_s, 6))
        if stack:
            stack[-1].inner += 1 + top.inner
            stack[-1].inner_s += seconds
            if seconds >= INNER_SPAN_S:
                tracing.record(
                    name, start, end, fun=top.fun, depth=len(stack), **tags
                )
        elif seconds < SMALL_S:
            top.span.discard()
            self._tally(name, seconds, top.cache == "miss")
        else:
            top.span.tag(**tags, **self._take_tally(name))
            top.span.__exit__(None, None, None)

    def _tally(self, name, seconds, missed) -> None:
        with self._lock:
            small = self._small[name]
            small[0] += 1
            small[1] += seconds
            if missed:
                small[2] += 1
                small[3] += seconds

    def _take_tally(self, name) -> dict:
        """The tally of ``name`` as the tags of the span that carries it,
        and a new tally."""
        with self._lock:
            events, seconds, misses, miss_s = self._small[name]
            self._small[name] = [0, 0.0, 0, 0.0]
        tags = {}
        if events:
            tags.update(small=events, small_s=round(seconds, 6))
        if misses:
            tags.update(small_misses=misses, small_miss_s=round(miss_s, 6))
        return tags

    def _open_compile(self):
        stack = self._stack()
        return stack[-1] if stack and stack[-1].name == _COMPILE else None

    def _on_cache_event(self, event, **_) -> None:
        if event == _CACHE_ASKED:
            answer = "uncached"  # until a hit or a write says otherwise
        elif event == _CACHE_HIT:
            answer = "hit"
            _metrics.counter("jit.cache_hits")
        elif event == _CACHE_MISS:
            answer = "miss"
            _metrics.counter("jit.cache_misses")
        else:
            return
        top = self._open_compile()
        if top is not None:
            top.cache = answer

    def _on_cache_seconds(self, event, seconds, **_) -> None:
        tag = _CACHE_SECONDS.get(event)
        if tag is not None:
            top = self._open_compile()
            if top is not None:
                top.cache_s[tag] = round(seconds, 6)


_ledger = CompileLedger()
