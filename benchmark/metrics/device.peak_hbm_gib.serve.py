"""Peak bytes held on the fullest device after the window: the allocator's
``peak_bytes_in_use`` plus ``peak_bytes_reserved`` (a loaded program's
temporaries), as ``lib/chip.py: memory_peak_bytes`` reads them."""

from benchmark.lib import chip


def read(r):
    peak = r.get("memory_peak_bytes")
    if not peak or r["device_kind"] not in chip.CHIP_PEAKS:
        return None
    return peak / 2**30
