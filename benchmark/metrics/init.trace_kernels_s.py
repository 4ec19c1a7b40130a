"""Seconds JAX spent in the flash kernels' ``pallas_call``s while it traced
them (the program's spans ``hvd.kernels.flash_call``, all in the ring; the
kernel body's jaxpr is made inside the call). Forward calls lie inside
``hvd.trainer.trace_model`` (``init.trace_model_s``), backward calls
outside it: the two sums go to standard error."""

import json
import sys

from benchmark.lib import jit_spans


def read(r):
    calls = jit_spans.named(r, jit_spans.FLASH_CALL)
    if not calls:
        return None
    models = jit_spans.named(r, "hvd.trainer.trace_model")
    inside = sum(jit_spans.seconds(c) for c in calls
                 if any(jit_spans.holds(m, c) for m in models))
    total = sum(jit_spans.seconds(c) for c in calls)
    print("flash_call spans (s): " + json.dumps({
        "calls": len(calls), "inside_trace_model": round(inside, 3),
        "outside": round(total - inside, 3)}), file=sys.stderr)
    return total
