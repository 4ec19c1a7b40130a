"""The most any flash kernel call stages whole-sequence in VMEM, in MiB:
the largest ``staged_vmem_bytes`` of the program's
``hvd.kernels.flash_call`` spans (K and V in the forward and dQ kernels, the
q group in the whole-sequence dK/dV kernel, each twice buffered)."""

from benchmark.lib import jit_spans


def read(r):
    calls = jit_spans.named(r, jit_spans.FLASH_CALL)
    if not calls:
        return None
    return max(jit_spans.tag(c, "staged_vmem_bytes") for c in calls) / 2**20
