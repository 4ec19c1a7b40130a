"""Host spans recorded by the benchmark around its calls into the program.
With ``annotate`` they are also written into the profiler's trace
(``jax.profiler.TraceAnnotation``), so that a gap in the device's work can be
given the name of what the host was doing."""

import contextlib
import threading
import time


class Recorder:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        # (name, start, end, attrs, index) on time.perf_counter; the index is
        # also in the annotation's name ("name#index"), so that a span of
        # the trace finds its attributes here
        self.spans = []
        self._lock = threading.Lock()
        self._next = 0

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        ann = None
        with self._lock:
            index = self._next
            self._next += 1
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"{name}#{index}")
            ann.__enter__()
        start = time.perf_counter()
        try:
            yield attrs
        finally:
            end = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                self.spans.append((name, start, end, attrs, index))

    def named(self, name: str):
        with self._lock:
            return [s for s in self.spans if s[0] == name]

    def by_index(self) -> dict:
        with self._lock:
            return {s[4]: s for s in self.spans}

    def wrap(self, obj, method: str, name: str, attrs=None):
        """Replace ``obj.method`` by a version that runs inside a span.
        ``attrs(obj)`` is evaluated before the call."""
        inner = getattr(obj, method)

        def wrapped(*a, **kw):
            with self.span(name, **(attrs(obj) if attrs else {})):
                return inner(*a, **kw)

        setattr(obj, method, wrapped)
        return inner
