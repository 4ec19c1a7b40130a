"""Seconds JAX took to lower the traced step (the program's span
``hvd.init.jit_lower`` of the step: jaxpr to StableHLO, the Mosaic lowering
of every Pallas kernel inside it)."""

from benchmark.lib import jit_spans


def read(r):
    return jit_spans.step_seconds(r, "lower")
