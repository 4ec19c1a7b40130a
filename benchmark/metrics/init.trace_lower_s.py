"""Seconds spent tracing and lowering the cell's programs during set-up: the
host clock around ``.lower()`` of the training step, or JAX's own trace and
lowering durations summed over the server's programs."""


def read(r):
    return r.get("trace_lower_s")
