"""Seconds JAX took to trace the training step, by its own clock (the
program's span ``hvd.init.jit_trace`` of the step: from the first line of
the step function to the closed jaxpr: forward, backward, remat, the
optimizer)."""

from benchmark.lib import jit_spans


def read(r):
    return jit_spans.step_seconds(r, "trace")
