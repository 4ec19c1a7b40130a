"""Share of its roofline that ``flash_dkv`` reaches: the least time the chip
could take for one call (``benchmark/lib/work.py``) over the call's mean
device time."""

from benchmark.lib import rooflines


def read(r):
    return rooflines.flash_share(r, "flash_dkv")
