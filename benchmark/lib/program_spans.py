"""What the program recorded about itself: the process spans of
``horovod_tpu.common.tracing`` (``hvd.init``, ``hvd.init.broadcast_*``,
``hvd.trainer.trace_*``), read from the program's own span ring. The only
file of ``benchmark/lib`` besides ``program.py`` that imports the program;
a program without such spans (the parent of the PR that brought them) gives
an empty list, and every reader over it gives None."""

import json
import sys

KEY = "program_spans"


def snapshot(readings: dict) -> list:
    """The ring's records as they were when a run's readings were first
    asked for them, kept in the readings under ``KEY`` (the metrics are
    read after the run; a reader that traces the step once more asks
    first, so that its own tracing is not counted)."""
    if KEY not in readings:
        try:
            from horovod_tpu.common import tracing

            records = list(tracing.recorder().spans())
        except Exception:  # noqa: BLE001 - a reader never fails the run
            records = []
        readings[KEY] = records
        by_name = {}
        for r in records:
            by_name.setdefault(r.get("name"), []).append(
                round(r.get("dur_ms", 0.0) / 1e3, 3))
        print("program spans (s, each one in order): " + json.dumps(
            {k: v[:8] for k, v in by_name.items()}), file=sys.stderr)
    return readings[KEY]


def seconds(readings: dict, *names):
    """Total seconds of the process spans with one of ``names``; None
    where the program recorded none of them."""
    durations = [r["dur_ms"] / 1e3 for r in snapshot(readings)
                 if r.get("name") in names and "dur_ms" in r]
    return sum(durations) if durations else None
