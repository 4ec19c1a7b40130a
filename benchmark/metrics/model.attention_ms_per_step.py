"""Per step, device time under ``MultiHeadAttention`` outside remat's
second forward: projections, the flash kernels, their backward."""

from benchmark.lib import scopes


def read(r):
    return scopes.ms_per_step(r, "attention")
