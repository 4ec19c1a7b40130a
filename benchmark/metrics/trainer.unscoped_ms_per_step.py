"""Per step, device time in operations that no scope names: what the
compiler made on its own (``copy-done``, ``slice-done``) and what the
program still leaves anonymous. None where the program does not name its
work."""

from benchmark.lib import scopes


def read(r):
    return scopes.ms_per_step(r, "unscoped", program_scope=True)
