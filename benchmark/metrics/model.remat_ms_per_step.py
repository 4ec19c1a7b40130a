"""Per step, device time of remat's second forward
(``rematted_computation``); None where the traffic runs without remat."""

from benchmark.lib import scopes


def read(r):
    if not r.get("traffic", {}).get("remat"):
        return None
    return scopes.ms_per_step(r, "remat")
