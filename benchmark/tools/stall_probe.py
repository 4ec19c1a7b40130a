"""A training cell's timed window with the host's own account of it: where
a window that lost steps lost them. Drives the window of ``loops/train.py``
(the same compiled step, two steps in flight) and prints, beside the rate,
every gap between loss arrivals that is over 1.5 times the median with
what the driving thread did inside it (seconds dispatching, seconds waiting
in the fetch), and the host's counters over the window: this process's CPU
time and context switches, the machine's ``/proc/stat`` (with ``steal``:
time the hypervisor gave to someone else), its load and CPU pressure. A
tool for the chip, not a measurement of a cell.

    python3 benchmark/tools/stall_probe.py --workload gpt2m-train-1chip \
        --seconds 45
"""

import argparse
import contextlib
import json
import os
import resource
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CPU_FIELDS = ("user", "nice", "system", "idle", "iowait", "irq", "softirq",
              "steal")


def _read(path):
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return ""


def host_account() -> dict:
    usage = resource.getrusage(resource.RUSAGE_SELF)
    cpu = _read("/proc/stat").split("\n", 1)[0].split()[1:1 + len(CPU_FIELDS)]
    return {
        "process": {"user_s": usage.ru_utime, "system_s": usage.ru_stime,
                    "voluntary_switches": usage.ru_nvcsw,
                    "involuntary_switches": usage.ru_nivcsw},
        "machine_jiffies": dict(zip(CPU_FIELDS, map(int, cpu))),
        "loadavg": _read("/proc/loadavg").split()[:3],
        "cpu_pressure": _read("/proc/pressure/cpu").strip(),
    }


def delta(before: dict, after: dict) -> dict:
    return {
        "process": {k: round(after["process"][k] - before["process"][k], 3)
                    for k in before["process"]},
        "machine_jiffies": {
            k: after["machine_jiffies"][k] - v
            for k, v in before["machine_jiffies"].items()},
        "loadavg": [before["loadavg"], after["loadavg"]],
        "cpu_pressure": [before["cpu_pressure"], after["cpu_pressure"]],
        "cpus": os.cpu_count(),
        "cpus_allowed": len(os.sched_getaffinity(0)),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=2147487300)
    p.add_argument("--seconds", type=float, default=45.0)
    p.add_argument("--any-device", action="store_true",
                   help="do not ask for the chip (a rehearsal on the CPU)")
    p.add_argument("--manifest", default=None,
                   help="another BENCHMARK.json (the tests' one)")
    p.add_argument("--data-dir", default=None,
                   help="where traffic/ and limits/ are (the tests' data)")
    args = p.parse_args(argv)

    import numpy as np

    from benchmark.lib import chip, manifest
    from benchmark.loops import train
    from horovod_tpu.common import compile_cache

    compile_cache.ensure()
    cell = manifest.Cell(manifest.load_manifest(args.manifest), args.workload,
                         args.data_dir or manifest.BENCH_DIR)
    trainer = train.Trainer(cell, args.seed, not args.any_device)
    trainer.build()
    trainer.warm_up()
    phases = []

    @contextlib.contextmanager
    def span(name):
        t = time.perf_counter()
        yield
        phases.append((name, t, time.perf_counter()))

    before = host_account()
    t0, stamps, _, _ = train.drive_window(
        trainer._advance, chip.fetch_scalar, args.seconds, span=span)
    after = host_account()

    t = cell.traffic
    tokens = trainer.world * t["batch_per_chip"] * t["seq"]
    gaps = np.diff(np.asarray([t0] + stamps))
    p50 = float(np.median(gaps[train.IN_FLIGHT:]))
    stalls = []
    for i in np.flatnonzero(gaps > 1.5 * p50):
        if i < train.IN_FLIGHT:  # the queue fills
            continue
        lo, hi = stamps[i - 1], stamps[i]
        inside = {"bench.dispatch": 0.0, "bench.fetch": 0.0}
        for name, s, e in phases:
            if name in inside and e > lo and s < hi:
                inside[name] += min(e, hi) - max(s, lo)
        stalls.append({
            "step": int(i), "at_s": round(lo - t0, 3),
            "gap_ms": round(float(gaps[i]) * 1e3, 3),
            "dispatching_ms": round(inside["bench.dispatch"] * 1e3, 3),
            "fetching_ms": round(inside["bench.fetch"] * 1e3, 3)})
    print(json.dumps({
        "device_kind": trainer.devices[0].device_kind,
        "workload": args.workload, "seed": args.seed,
        "steps": len(stamps), "step_ms_p50": p50 * 1e3,
        "train_tokens_per_s": len(stamps) * tokens / (stamps[-1] - t0),
        "steps_at_the_median_pace": (stamps[-1] - t0) / p50,
        "stalled_gaps": len(stalls),
        "lost_ms": round(sum(s["gap_ms"] - p50 * 1e3 for s in stalls), 3),
        "stalls": stalls[:40],
        "host": delta(before, after),
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
