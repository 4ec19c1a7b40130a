"""1 - union of the device's operation intervals over the traced window."""


def read(r):
    trace = r.get("trace")
    if trace is None or not trace.devices:
        return None
    return 100.0 * trace.idle_share
