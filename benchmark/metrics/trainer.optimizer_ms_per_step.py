"""Per step, device time under ``hvd_update`` and ``hvd_accumulate`` (the
wrapped optimizer's update, the guard, the accumulation) and in the step
function's own top-level operations (``apply_updates``, which the compiler
fuses with the update). None where the program does not name its work."""

from benchmark.lib import scopes


def read(r):
    return scopes.ms_per_step(r, "optimizer", program_scope=True)
