"""Per step, the collective time during which no compute operation runs on
the same device (device trace, averaged over the chips)."""

from benchmark.lib import xtrace


def read(r):
    trace = r.get("trace")
    if trace is None or not trace.devices or r["chips"] < 2:
        return None
    steps = xtrace.step_count(trace)
    return 1e3 * trace.exposed_collective_s() / steps if steps else None
