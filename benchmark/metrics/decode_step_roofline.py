"""Share of its roofline that the decode step reaches: the bytes it has to
read (every multiplied weight and the live keys and values, once each) over
peak bandwidth, over the device's busy time inside the step's span."""

from benchmark.lib import rooflines


def read(r):
    return rooflines.decode_share(r)
