"""Share of its roofline that ``flash_dkv`` reaches over a step's calls in a
model whose layers differ: each layer's call counted with its own mask and
the grouped heads' shapes (``benchmark/work/<family>.py: flash_share``)."""

from benchmark.lib import manifest


def read(r):
    family = manifest.load_module("work", r["cfg"].get("model", ""))
    if family is None or not hasattr(family, "flash_share"):
        return None
    return family.flash_share(r, "flash_dkv")
