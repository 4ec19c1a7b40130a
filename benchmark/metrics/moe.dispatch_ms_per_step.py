"""Per step, device time of an expert layer's feed-forward part that is no
matmul: router scores and top-k, the sort, the gather into sorted rows, the
activation, the un-sort and the weighted sum (class ``moe``)."""

from benchmark.lib import scopes


def read(r):
    return scopes.ms_per_step(r, "moe")
