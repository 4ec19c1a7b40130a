"""Share of the traced window that the scheduler thread spent waiting for
work (the program's span ``hvd.batcher.idle_wait`` on the profiler's clock).
None where the trace holds no such span: the program records it under
``HOROVOD_TRACE`` only, and ``lib/xtrace.py: load`` keeps a host span only if
its prefix is among ``span_prefixes`` (``"hvd."`` is not among the defaults
yet, see PERF.md section 7)."""


def read(r):
    trace = r.get("trace")
    if trace is None or not trace.window_s:
        return None
    start, end = trace.window
    waits = [(max(s, start), min(e, end))
             for name, s, e, _ in trace.host_spans
             if name == "hvd.batcher.idle_wait" and e > start and s < end]
    if not waits:
        return None
    return 100.0 * sum(e - s for s, e in waits) / (end - start)
