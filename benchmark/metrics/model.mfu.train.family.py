"""The step's share of the chip's peak: forward and backward FLOPs a token
needs (no recomputation) by the configuration's family work file
(``benchmark/work/<model>.py``) x tokens/s over chips x peak. None where the
family has no work file."""

from benchmark.lib import chip, manifest


def read(r):
    family = manifest.load_module("work", r["cfg"].get("model", ""))
    if family is None or r["device_kind"] not in chip.CHIP_PEAKS:
        return None
    peak, _ = chip.peaks(r["device_kind"])
    flops = family.train_flops_per_token(r["cfg"], r["traffic"]["seq"])
    return 100.0 * flops * r["tokens_per_s"] / (r["chips"] * peak)
