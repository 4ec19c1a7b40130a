"""Seconds of the programs that are not the step, by the process's own
account: the outermost ``hvd.init.jit_trace`` / ``jit_lower`` /
``jit_compile`` spans that end before the step's trace begins, with their
tallies of small events (the step's own three spans carry the tallies of
what ran before them): the weights program, the state's placement, eager
operations."""

from benchmark.lib import jit_spans


def read(r):
    step = jit_spans.step_events(r)
    trace = step["trace"]
    if trace is None:
        return None
    total = 0.0
    for record in jit_spans.outermost(r, *jit_spans.JIT):
        if jit_spans.end(record) <= trace["ts"] + jit_spans.SLACK_S:
            total += jit_spans.seconds(record)
            total += jit_spans.tag(record, "small_s", 0.0)
    for record in step.values():
        if record is not None:
            total += jit_spans.tag(record, "small_s", 0.0)
    return total
