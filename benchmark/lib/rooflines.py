"""Shares of a roofline, from a run's readings. A reader that finds nothing
to read returns None, never 0."""

from . import chip, work


def flash_share(r, kernel: str):
    trace = r.get("trace")
    if trace is None or not trace.devices:
        return None
    calls = trace.op_calls(kernel)
    if not calls:
        return None
    peak, bw = chip.peaks(r["device_kind"])
    flops, nbytes = work.flash_call_work(
        r["cfg"], kernel, r["traffic"]["batch_per_chip"], r["traffic"]["seq"])
    least = work.roofline_seconds(flops, nbytes, peak, bw)
    return 100.0 * least * len(calls) / sum(calls)


def decode_share(r):
    trace = r.get("trace")
    if trace is None or not trace.devices:
        return None
    _, bw = chip.peaks(r["device_kind"])
    # the recorder's spans hold the live tokens of each step; the trace's
    # copies of the same spans hold the device's busy time inside them
    recorded = r["spans"].by_index()
    least = busy = 0.0
    for idx, busy_ns in trace.busy_within_spans("engine.decode_step"):
        if idx in recorded and busy_ns:
            live = recorded[idx][3]["live_tokens"]
            least += work.decode_step_bytes(r["cfg"], live) / bw
            busy += busy_ns / 1e9
    return 100.0 * least / busy if busy else None
