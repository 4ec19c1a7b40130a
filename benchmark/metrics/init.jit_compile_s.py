"""Seconds of the step's backend compile, or of the persistent cache's
retrieval in its place (the program's span ``hvd.init.jit_compile`` of the
step; its tag ``cache`` says which and goes to standard error)."""

import json
import sys

from benchmark.lib import jit_spans


def read(r):
    record = jit_spans.step_events(r)["compile"]
    if record is None:
        return None
    print("the step's compile: " + json.dumps(record.get("tags", {})),
          file=sys.stderr)
    return jit_spans.seconds(record)
