"""Seconds this process compiled from cold on its way to a built step: the
program's ``hvd.init.jit_compile`` spans whose ``cache`` is ``miss`` (and
the misses in their tallies of small compiles) that end no later than the
step's own compile. 0 in a run that found everything in the persistent
cache; what follows the step (the reference's programs) is left out."""

from benchmark.lib import jit_spans


def read(r):
    step = jit_spans.step_events(r)["compile"]
    if step is None:
        return None
    total = 0.0
    for record in jit_spans.named(r, jit_spans.COMPILE):
        if jit_spans.end(record) <= jit_spans.end(step) + jit_spans.SLACK_S:
            if jit_spans.tag(record, "cache", "") == "miss":
                total += jit_spans.seconds(record)
            total += jit_spans.tag(record, "small_miss_s", 0.0)
    return total
