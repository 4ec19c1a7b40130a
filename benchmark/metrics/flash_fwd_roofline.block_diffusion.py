"""Share of its roofline that ``flash_fwd`` reaches over a step's calls
under the block-diffusion mask: the products of the ``L^2 + L x B`` pairs
the mask keeps, the tensors over the ``2L`` positions, by the family's own
count (``benchmark/work/<family>.py: flash_share``). Read as
``flash_fwd_roofline.gqa_window`` is."""

from benchmark.lib import manifest

read = manifest.load_module("metrics", "flash_fwd_roofline.gqa_window").read
