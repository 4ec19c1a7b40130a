"""Per step, device time of the rest of each ``block_N``: layer norms,
the two dense layers, GELU, residuals, forward and backward."""

from benchmark.lib import scopes


def read(r):
    return scopes.ms_per_step(r, "mlp")
