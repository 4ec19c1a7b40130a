"""One run of one cell of the benchmark:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (traced runs) and, last, ``compared``: every number that
decided ``correct`` beside its limit. See benchmark/README.md.
"""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def result_line(cell, result: dict, traced: bool) -> dict:
    """The run's last line from what the loop returned."""
    from benchmark.lib import manifest

    readings = result["readings"]
    metrics = {}
    if traced:
        for m in cell.per_layer:
            reader = manifest.load_module("metrics", m["name"])
            if reader is None:
                raise SystemExit(
                    f"benchmark: no reader benchmark/metrics/{m['name']}.py")
            value = reader.read(readings)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {
                "value": float(result["end_to_end"][m["name"]]),
                "unit": m["unit"],
            }
    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": dict(result["device"]),
    }
    trace = readings.get("trace")
    if traced and trace is not None and trace.devices:
        line["device"]["busy_s"] = trace.busy_s
        line["device"]["window_s"] = trace.window_s
        line["breakdown"] = trace.breakdown()
    line["compared"] = result["compared"]
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmark.lib import compare, manifest

    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    loop = cell.loop()
    # the program's persistent compile cache, at its fixed path in the
    # checkout (or where JAX_COMPILATION_CACHE_DIR says), before anything
    # compiles: only a cell's first run in a checkout compiles
    from horovod_tpu.common import compile_cache

    compile_cache.ensure()
    result = loop.run(cell, args, PROCESS_START)
    trace = result["readings"].get("trace")
    if args.trace and (trace is None or trace.busy_s <= 0):
        raise SystemExit("benchmark: the trace holds no device operation")
    line = result_line(cell, result, bool(args.trace))
    sys.stdout.flush()
    compare.print_compared(result["compared"], line["correct"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
