"""Forward FLOPs of the prompt and output tokens of every request completed
in the window, over the window and the chip's peak."""

from benchmark.lib import chip, work


def read(r):
    if r["device_kind"] not in chip.CHIP_PEAKS:
        return None
    peak, _ = chip.peaks(r["device_kind"])
    flops = sum(
        work.serve_flops(r["cfg"], len(req["tokens"]), len(reply["tokens"]))
        for req, reply in r["summary"]["completed"])
    return 100.0 * flops / (r["seconds"] * r["chips"] * peak)
