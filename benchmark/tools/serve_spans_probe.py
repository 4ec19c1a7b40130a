"""One serving window at one rate with the program's own spans on
(``HOROVOD_TRACE=1``) and a profiler session over part of it: shows that the
spans of the serving loop sit in the profiler's host plane on the device's
clock, what they say about the device's idle gaps, that every reply carries
a stamp per token, and that the decode step is still one program. A tool
for the chip, not a cell (there is no serving cell yet, PERF.md section 7).

    python3 benchmark/tools/serve_spans_probe.py --rate 3.4 --seconds 30
    python3 benchmark/tools/serve_spans_probe.py --rate 0.3 --seconds 30 \
        --trace-after 6.5 --trace-seconds 9

The second line leaves the scheduler idle between about 8 and 14 s of the
window (the arrivals' skeleton is fixed by the traffic file), so a whole
``hvd.batcher.idle_wait`` falls inside the session: a span is written to
the profiler when it ends, and only if it began inside the session.

The serving mix is ``traffic/serve-chat-poisson.json`` on ``gpt2-medium``,
as ``tools/serve_sweep.py`` ran it for PR 24.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TRACE_SECONDS = 5.0
PREFIXES = ("bench.", "batcher.", "engine.", "loadgen.", "hvd.")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="gpt2-medium")
    p.add_argument("--traffic", default="serve-chat-poisson")
    p.add_argument("--rate", type=float, default=3.4)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=2147487100)
    p.add_argument("--trace-after", type=float, default=None,
                   help="seconds into the window at which the profiler "
                        "session opens (default: a third, at most 10)")
    p.add_argument("--trace-seconds", type=float, default=TRACE_SECONDS)
    p.add_argument("--any-device", action="store_true",
                   help="do not ask for the chip (a rehearsal on the CPU)")
    p.add_argument("--manifest", default=None,
                   help="another BENCHMARK.json (the tests' one)")
    p.add_argument("--data-dir", default=None,
                   help="where traffic/ and limits/ are (the tests' data)")
    args = p.parse_args(argv)

    # the program's switch for spans per round and per request; set before
    # the program reads its configuration
    os.environ["HOROVOD_TRACE"] = "1"

    import jax

    from benchmark.lib import manifest, program_spans, spans, xtrace
    from benchmark.loops import serve_open as so
    from horovod_tpu.common import compile_cache

    compile_cache.ensure()
    m = manifest.load_manifest(args.manifest)
    m["workloads"] = m["workloads"] + [{
        "name": "probe-serve", "config": args.config,
        "traffic": args.traffic, "chips": 1, "why": "probe"}]
    cell = manifest.Cell(m, "probe-serve",
                         args.data_dir or manifest.BENCH_DIR)
    server = so.Server(cell, args.seed, not args.any_device)
    box = {}
    try:
        schedule = server.schedule(args.seed, args.seconds, args.rate)
        server.warm_up(schedule, args.seed)
        recorder = spans.Recorder(annotate=True)
        server.instrument(recorder)  # the benchmark's wraps beside them

        open_at = (min(10.0, args.seconds / 3)
                   if args.trace_after is None else args.trace_after)

        def traced(t0):
            time.sleep(max(open_at - (time.monotonic() - t0), 0.0))
            directory = tempfile.mkdtemp(prefix="serve-spans-probe-")
            try:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.enable_hlo_proto = False
                jax.profiler.start_trace(directory, profiler_options=options)
                try:
                    time.sleep(min(args.trace_seconds, args.seconds))
                finally:
                    jax.profiler.stop_trace()
                path = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                                 recursive=True)[0]
                box["trace"] = xtrace.load(path, span_prefixes=PREFIXES)
            finally:
                shutil.rmtree(directory, ignore_errors=True)

        before = server.engine.stats()
        _, records = so.run_window(server, schedule, args.seconds, traced)
        after = server.engine.stats()
        summary = so.summarise(schedule, records, args.seconds)
    finally:
        server.stop()

    trace = box["trace"]
    replies = [reply for _, reply in summary["completed"]]
    in_plane = {}
    for name, s, e, idx in trace.host_spans:
        if name.startswith("hvd."):
            row = in_plane.setdefault(name, {"n": 0, "s": 0.0, "seq": 0})
            row["n"] += 1
            row["s"] += (e - s) / 1e9
            row["seq"] += idx is not None
    readings = {"kind": "serve", "summary": summary, "trace": trace}
    ring = {r["seq"]: r for r in program_spans.snapshot(readings)
            if "seq" in r}
    joined = sum(1 for name, _, _, idx in trace.host_spans
                 if name.startswith("hvd.") and idx in ring
                 and ring[idx]["name"] == name)
    read = {name: manifest.load_module("metrics", name).read(readings)
            for name in ("batcher.idle_wait_share",
                         "batcher.queue_wait_ms_p95",
                         "serving.tpot_gap_p95_ms", "pages.live_share")}
    print(json.dumps({
        "device_kind": jax.devices()[0].device_kind,
        "rate_rps": args.rate, "requests": len(schedule),
        "completed": len(replies), "failed": summary["failed"],
        "serve_tokens_per_s": summary["serve_tokens_per_s"],
        "ttft_p90_ms": summary["ttft_p90_ms"],
        "tpot_p90_ms": summary["tpot_p90_ms"],
        "replies_with_a_stamp_per_token": sum(
            1 for r in replies
            if len(r.get("token_ms", ())) == len(r["tokens"])),
        "decode_compiles": after["decode_compiles"],
        "compiles_in_window": server_compiles(before, after),
        "traced_window_s": trace.window_s, "device_busy_s": trace.busy_s,
        "device_planes": len(trace.devices),
        "hvd_spans_in_host_plane": in_plane,
        "hvd_spans_joined_to_ring_by_seq": joined,
        "idle_gaps_s": trace.breakdown()["idle_gaps"],
        "readers": read,
    }, indent=1))
    return 0


def server_compiles(before: dict, after: dict) -> int:
    return sum(after[k] - before[k] for k in (
        "prefill_compiles", "decode_compiles", "prefill_promotions",
        "prefill_bg_promotions"))


if __name__ == "__main__":
    sys.exit(main())
