"""Grid steps of the blocked dK/dV calls of one trace of the step that
compute nothing: ``grid_steps - kept_tiles`` summed over the program's
``hvd.kernels.flash_call`` spans with ``staging`` = ``blocked`` (0 since
the grid lists only the tiles the mask keeps; a mask that brought empty
steps back would show here)."""

from benchmark.lib import jit_spans


def read(r):
    blocked = [c for c in jit_spans.in_step_trace(r, jit_spans.FLASH_CALL)
               if jit_spans.tag(c, "staging", "") == "blocked"]
    if not blocked:
        return None
    return sum(jit_spans.tag(c, "grid_steps") - jit_spans.tag(c, "kept_tiles")
               for c in blocked)
