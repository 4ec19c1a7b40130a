"""The step's share of the chip's peak in block-diffusion training: forward
and backward FLOPs a data token needs (no recomputation; both copies of the
row through the layers, the kept pairs of the mask, the head over one) by
``benchmark/work/sdar_moe.py`` x data tokens/s over chips x peak. Read as
``model.mfu.train.family`` is."""

from benchmark.lib import manifest

read = manifest.load_module("metrics", "model.mfu.train.family").read
