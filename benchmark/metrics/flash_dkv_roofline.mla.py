"""Share of its roofline that ``flash_dkv`` reaches over a step's calls in a
model of latent attention: the key 192 wide, the value 128, every head its
own. Read as ``flash_dkv_roofline.gqa_window`` is, from the family's own
count (``benchmark/work/<family>.py: flash_share``; nothing is counted at a
padded width)."""

from benchmark.lib import manifest

read = manifest.load_module("metrics", "flash_dkv_roofline.gqa_window").read
