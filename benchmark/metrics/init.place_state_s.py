"""Seconds placing parameters and optimizer state: the program's spans
``hvd.init.broadcast_parameters``, ``hvd.init.broadcast_optimizer_state``
and ``hvd.init.optimizer_init``."""

from benchmark.lib import program_spans


def read(r):
    return program_spans.seconds(
        r, "hvd.init.broadcast_parameters",
        "hvd.init.broadcast_optimizer_state", "hvd.init.optimizer_init")
