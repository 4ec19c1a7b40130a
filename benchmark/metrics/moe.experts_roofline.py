"""Share of its roofline that the held experts' grouped matmul reaches: the
least time for the rows expected here through the three matmuls, forward and
backward (``benchmark/work/<family>.py``), over the device time under the
scope ``moe_experts`` outside remat's second forward."""

from benchmark.lib import manifest, scopes


def read(r):
    family = manifest.load_module("work", r["cfg"].get("model", ""))
    if family is None or not hasattr(family, "experts_share"):
        return None
    return family.experts_share(r, scopes.ms_per_step(r, "moe_experts"))
