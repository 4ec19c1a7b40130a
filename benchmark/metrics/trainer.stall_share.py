"""Share of the window that is not median steps: 1 - p50 x steps / window."""

import statistics


def read(r):
    stamps = r["stamps"]
    if len(stamps) < 3:
        return None
    p50 = statistics.median(b - a for a, b in zip(stamps, stamps[1:]))
    return 100.0 * (1.0 - p50 * len(stamps) / r["window_s"])
