"""The device the run is on: its published peaks, the check that it is the
chip the cell asks for, a dependent host transfer, and its memory peak."""

import numpy as np

# Per-chip peaks keyed by ``device_kind``: (bf16 TFLOP/s, HBM GB/s).
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
# 819 GB/s per chip; the v5e reports the kind "TPU v5 lite" (chip run, PR 21).
# A kind that is not here is an error, never a default.
CHIP_PEAKS = {
    "TPU v5 lite": (197.0e12, 819.0e9),
}


class NoChip(SystemExit):
    """Exit non-zero: JAX has not the accelerator the cell needs."""


def peaks(device_kind: str):
    """``(peak FLOP/s, peak bytes/s)`` of one chip of ``device_kind``."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}: add it to "
            "benchmark/lib/chip.py CHIP_PEAKS with the source of each number"
        ) from None


def require_chips(chips: int):
    """The devices of this process, or exit non-zero with nothing printed on
    stdout when the platform is the CPU or the count is not the cell's."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip("benchmark: JAX found no accelerator (platform=cpu)")
    if len(devices) != chips:
        raise NoChip(
            f"benchmark: the cell asks for {chips} chip(s), JAX sees "
            f"{len(devices)}"
        )
    peaks(devices[0].device_kind)
    return devices


def describe(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def fetch_scalar(x) -> float:
    """Wait for ``x`` by moving it, one scalar, to the host. A host transfer
    of a value that depends on the step cannot return before the step has
    ended, whatever the runtime does with ``block_until_ready``."""
    return float(np.asarray(x))


def memory_peak_bytes(devices) -> int:
    """Peak bytes held on the fullest device, from its ``memory_stats()``:
    ``peak_bytes_in_use`` (the buffers JAX holds: arguments, code, results)
    plus ``peak_bytes_reserved`` (what the runtime sets aside for a loaded
    program's temporaries, which ``peak_bytes_in_use`` does not count: on
    the v5e BERT-large's step has 9.0 GB of them, PERF.md section 6). 0
    where the backend does not report it, as the CPU."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def program_footprint(compiled) -> dict:
    """What XLA says one device holds while ``compiled`` runs, in bytes: its
    arguments, the outputs that alias none of them, its temporaries and its
    code. Printed beside the allocator's numbers as a cross-check; empty
    where the backend gives no analysis."""
    try:
        m = compiled.memory_analysis()
    except Exception:  # a backend without the analysis
        m = None
    if m is None:
        return {}
    parts = {
        "arguments": int(m.argument_size_in_bytes),
        "outputs_not_aliased": int(m.output_size_in_bytes)
        - int(m.alias_size_in_bytes),
        "temporaries": int(m.temp_size_in_bytes),
        "code": int(m.generated_code_size_in_bytes),
    }
    parts["total"] = sum(parts.values())
    return parts
