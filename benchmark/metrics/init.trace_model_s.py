"""Seconds of Python in ``Transformer.__call__`` while JAX traces it (the
program's span ``hvd.trainer.trace_model``): the shapes of the parameters
and the step's forward."""

from benchmark.lib import program_spans


def read(r):
    return program_spans.seconds(r, "hvd.trainer.trace_model")
