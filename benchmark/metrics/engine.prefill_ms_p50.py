"""Median time of ``InferenceEngine.prefill`` (it returns the first token as
a host number, so the span ends when the device has)."""

import statistics


def read(r):
    spans = r["spans"].named("engine.prefill")
    return (1e3 * statistics.median(s[2] - s[1] for s in spans)
            if spans else None)
