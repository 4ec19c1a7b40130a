"""Shared bench-harness helpers: AOT compile + XLA FLOP counting + MFU.

One place owns the MFU methodology for every bench (bench.py,
bench_lm.py): compile the jitted step ONCE ahead of time (the same
compiled object runs the timed loop — no second trace/compile), read
the step's FLOPs from XLA cost analysis, and divide measured FLOP/s by
the chip's peak bf16 FLOP/s.

It also owns the bench RUN ID: one id per bench process (or one per
sweep when the driver exports ``BENCH_RUN_ID``), stamped onto every
JSON artifact line AND into the flight-recorder step records
(``telemetry.set_run_id``) — a bench number and the step telemetry
that produced it join on ``run_id`` instead of on filename archaeology.
"""

import os
import uuid

_RUN_ID = None


def run_id() -> str:
    """This bench process's run id. ``BENCH_RUN_ID`` wins (a sweep
    driver threads one id through every bench it launches); otherwise
    a fresh 16-hex id. First call also stamps it into the telemetry
    hub so flight-recorder records carry the same id."""
    global _RUN_ID
    if _RUN_ID is None:
        _RUN_ID = os.environ.get("BENCH_RUN_ID") or uuid.uuid4().hex[:16]
        try:
            from horovod_tpu.common import telemetry

            telemetry.set_run_id(_RUN_ID)
        except Exception:  # bench without the package on path
            pass
    return _RUN_ID


def stamp(line: dict) -> dict:
    """Add ``run_id`` to a bench JSON record (in place, returned for
    chaining). Never overwrites — a parent re-emitting a child's
    already-stamped line keeps the child's id."""
    line.setdefault("run_id", run_id())
    return line

# Per-chip peaks, keyed by what ``jax.devices()[0].device_kind`` reports:
# (bf16 TFLOP/s — the MFU denominator, HBM GB/s — the roofline's).
# A kind that is not here is an error, never a default: add it with the
# source of each number beside it.
CHIP_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s per chip. "TPU v5 lite" is the kind the v5e reports
    # (chip run, PR 21).
    "TPU v5 lite": (197.0, 819.0),
}


def require_accelerator():
    """Select ``BENCH_PLATFORM`` when it is set and return the first
    device — or exit 1 when JAX found only the CPU and a CPU run was not
    asked for by name (``BENCH_PLATFORM=cpu``): a CPU number is never
    printed under a device metric's name. Call before anything touches
    a backend."""
    import jax

    asked = os.environ.get("BENCH_PLATFORM")
    if asked:
        jax.config.update("jax_platforms", asked)
    dev = jax.devices()[0]
    if dev.platform == "cpu" and asked != "cpu":
        raise SystemExit(
            "bench: JAX found no accelerator (platform=cpu). Set "
            "BENCH_PLATFORM=cpu to ask for a CPU run by name."
        )
    return dev


def chip_peaks(device=None):
    """``(peak bf16 TFLOP/s, peak HBM GB/s)`` of ``device`` (default: the
    first one); ``(None, None)`` on the CPU, which has no roofline worth
    dividing by. An unknown ``device_kind`` raises."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return None, None
    try:
        return CHIP_PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device_kind {device.device_kind!r}; "
            "add it to _benchlib.CHIP_PEAKS with the source of each number"
        ) from None


def sync(x):
    """Wait for ``x`` by moving one scalar of it to the host, and return
    that scalar as a float. A host transfer of a value that data-depends
    on the whole timed loop cannot return early, whatever the runtime
    does with ``block_until_ready`` (on the v5e the two agree:
    ``chip_smoke.py`` checks it on every run). Call it on the final loss
    BEFORE starting the timer too, to drain the warm-up queue. Only ONE
    scalar crosses: the leaf is sliced on-device first, so syncing on a
    128 MB allreduce buffer doesn't pay a 128 MB transfer."""
    import jax
    import numpy as np

    leaf = jax.tree.leaves(x)[0]
    if hasattr(leaf, "reshape"):
        leaf = leaf.reshape(-1)[:1]
    return float(np.asarray(leaf).ravel()[0])


def aot_compile(step_fn, *args):
    """AOT-compile a jitted fn once; returns (compiled, flops_or_None).
    A compile error propagates: a step that does not compile must not
    be timed as something else. The step's XLA-estimated HBM traffic
    (the roofline numerator) is read separately with
    :func:`bytes_accessed`."""
    compiled = step_fn.lower(*args).compile()
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0)) or None
    except Exception:
        flops = None
    return compiled, flops


def bytes_accessed(compiled):
    """XLA's 'bytes accessed' estimate for a compiled step, or None
    (its own failure domain — a missing bytes field must never cost
    the FLOPs number)."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return float(ca.get("bytes accessed", 0.0)) or None
    except Exception:
        return None


def mfu_fields(flops, iters, dt, step_bytes=None):
    """The tflops_per_sec / mfu keys for a bench JSON line (empty dict
    when FLOPs are unknown). ``step_bytes`` (from
    :func:`bytes_accessed` on the SAME compiled step) adds the
    roofline side: XLA's bytes estimate over the measured step time vs
    the chip's HBM peak — an mbu near 1.0 with mfu well below 1.0 is
    the quantified bandwidth-bound argument VERDICT r3 asked for
    (XLA assumes perfect fusion, so read mbu as a lower bound)."""
    if flops is None or dt <= 0:
        return {}
    tflops = flops * iters / dt / 1e12
    out = {"tflops_per_sec": round(tflops, 2)}
    peak, peak_bw = chip_peaks()
    if peak:
        out["mfu"] = round(tflops / peak, 4)
    if step_bytes and peak_bw:
        gbs = step_bytes * iters / dt / 1e9
        out["hbm_gb_per_sec"] = round(gbs, 1)
        out["mbu"] = round(gbs / peak_bw, 4)
    return out
