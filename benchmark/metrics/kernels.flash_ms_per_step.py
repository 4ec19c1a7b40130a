"""Device time of the three flash kernels per step."""

from benchmark.lib import work, xtrace


def read(r):
    trace = r.get("trace")
    if trace is None or not trace.devices:
        return None
    steps = xtrace.step_count(trace)
    seconds = trace.op_seconds()
    total = sum(seconds.get(k, 0.0) for k in work.FLASH_KERNELS)
    return 1e3 * total / steps if steps and total else None
