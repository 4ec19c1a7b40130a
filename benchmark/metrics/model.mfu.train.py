"""Forward and backward FLOPs the model needs per token (no recomputation)
x tokens/s over chips x peak."""

from benchmark.lib import chip, work


def read(r):
    if r["device_kind"] not in chip.CHIP_PEAKS:
        return None
    peak, _ = chip.peaks(r["device_kind"])
    flops = work.train_flops_per_token(r["cfg"], r["traffic"]["seq"])
    return 100.0 * flops * r["tokens_per_s"] / (r["chips"] * peak)
