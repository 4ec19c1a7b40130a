"""Median time of ``InferenceEngine.decode_step`` (it returns host tokens, so
the span ends when the device has)."""

import statistics


def read(r):
    spans = r["spans"].named("engine.decode_step")
    return (1e3 * statistics.median(s[2] - s[1] for s in spans)
            if spans else None)
