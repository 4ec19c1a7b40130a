"""Per step, all device time in operations under the program's scope
``hvd_exchange`` (device trace over the compiled step's ``op_name``s,
averaged over the chips). Listed for the cells with an exchange: on one
chip the world-1 ``psum`` and the division by 1 fold away and nothing is
left under the scope. None where the program does not name its work."""

from benchmark.lib import scopes


def read(r):
    return scopes.ms_per_step(r, "exchange", program_scope=True)
