"""Per step, all device time of the expert layers' feed-forward part outside
remat's second forward: routing, dispatch, the grouped matmuls, the shared
expert, the combine (classes ``moe``, ``moe_experts``, ``moe_shared`` of
``benchmark/scopes/<family>.py``)."""

from benchmark.lib import scopes


def read(r):
    return scopes.ms_per_step(r, "moe", "moe_experts", "moe_shared")
