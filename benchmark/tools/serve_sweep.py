"""Find the highest rate a serving cell sustains: one process, one set-up,
one short window per rate.

    python3 benchmark/tools/serve_sweep.py --workload <cell> --rates 1.5,2,2.5,3 --seconds 30

A rate is sustained when the window completes what it was offered (the
backlog at its close is what is in flight anyway, not a growing queue) and
the first-token tail stays near the unloaded one. The cell then runs at
about four fifths of it (the traffic file's ``rate_rps``).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True,
                   type=lambda s: [float(x) for x in s.split(",")])
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)

    from benchmark.lib import manifest
    from benchmark.loops import serve_open as so
    from horovod_tpu.common import compile_cache

    compile_cache.ensure()
    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    server = so.Server(cell, args.seed)
    try:
        avoid = set()
        for i, rate in enumerate(args.rates):
            schedule = server.schedule(args.seed + i, args.seconds, rate,
                                       avoid)
            avoid |= {len(r["tokens"]) for r in schedule}
            if i == 0:
                server.warm_up(schedule, args.seed)
            before = server.engine.stats()
            _, records = so.run_window(server, schedule, args.seconds)
            after = server.engine.stats()
            s = so.summarise(schedule, records, args.seconds)
            offered = sum(r["max_tokens"] for r in schedule) / args.seconds
            done_late = sum(1 for r in records
                            if r.get("done", 1e9) > args.seconds)
            print(json.dumps({
                "rate_rps": rate, "requests": len(schedule),
                "offered_tokens_per_s": offered,
                "serve_tokens_per_s": s["serve_tokens_per_s"],
                "ttft_p50_ms": s["ttft_p50_ms"],
                "ttft_p90_ms": s["ttft_p90_ms"],
                "tpot_p90_ms": s["tpot_p90_ms"], "failed": s["failed"],
                "finished_after_window": done_late,
                "last_done_s": max(r.get("done", 0) for r in records),
                "decode_steps": after["decode_steps"] - before["decode_steps"],
                "compiles": server.compiles_between(before, after),
            }), flush=True)
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
