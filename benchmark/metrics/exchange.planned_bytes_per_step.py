"""Bytes a chip hands to the step's gradient collectives, by the program's
own count (tag ``bytes`` of the step's span ``hvd.exchange.plan``, after
compression); None on one chip, where ``world`` is 1 and nothing is
exchanged."""

from benchmark.lib import jit_spans


def read(r):
    plans = [p for p in jit_spans.in_step_trace(r, jit_spans.PLAN)
             if jit_spans.tag(p, "world") > 1]
    if not plans:
        return None
    return sum(jit_spans.tag(p, "bytes") for p in plans)
