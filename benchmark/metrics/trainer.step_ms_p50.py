"""Median gap between the arrivals of successive steps' losses."""

import statistics


def read(r):
    stamps = r["stamps"]
    if len(stamps) < 3:
        return None
    return 1e3 * statistics.median(b - a for a, b in zip(stamps, stamps[1:]))
