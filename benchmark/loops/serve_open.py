"""Loop kind ``serve-open``: ``hvd.serve(model, params)`` in this process,
which holds the chip, under an open loop of requests from a child process
(``benchmark/loadgen.py``) that follows a schedule made from the seed.

Clocks. A request is timed from when it was *due*. ``/generate`` replies
when the request is done and carries the batcher's ``ttft_ms`` (submit to
first token) and ``gen_ms`` (first token to last), so the first-token time is
(sent - due) + ``ttft_ms``; a reply whose ``ttft_ms + gen_ms`` exceeds what
the client itself timed is counted as failed. Arrivals stop at ``--seconds``;
requests in flight then may finish within the traffic file's
``drain_limit_s`` and their latencies count in the tails; one that does not
is attempted and failed. ``serve_tokens_per_s`` counts the output tokens of
requests completed inside the window.
"""

import functools
import gc
import json
import os
import statistics
import subprocess
import sys
import time
import urllib.request

import numpy as np

from ..lib import chip, compare, compiles, manifest, spans
from ..lib import traffic as gen
from ..lib import weights, xtrace

WARMUP_OUTPUT_TOKENS = 4
TRACE_START_S = 8.0
TRACE_SECONDS = 5.0
# a request that failed or never came counts with this latency in a tail
MISSING_MS = 1e9


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between the two nearest ranks."""
    return float(np.percentile(np.asarray(values, float), q))


class Server:
    """The serving plane with the benchmark's weights, warmed up."""

    def __init__(self, cell, seed: int, require_chip: bool = True):
        import jax

        self.cell, self.cfg, self.traffic = cell, cell.config, cell.traffic
        self.devices = (chip.require_chips(cell.chips) if require_chip
                        else jax.devices()[:cell.chips])
        self.readings = {}
        self.compile_log = compiles.CompileLog()
        family = cell.model()
        self.model = family.build_model(self.cfg)
        serve = self.traffic["serve"]
        self.make_params = jax.jit(weights.make_params(
            family.param_shapes(self.model, 8)))
        self.params = self.make_params(weights.seed_key(seed))
        import horovod_tpu as hvd

        self.handle = hvd.serve(
            self.model, self.params, port=0, addr="127.0.0.1",
            handle_sigterm=False, **serve)
        self.engine = self.handle.engine
        self.batcher = self.handle.batcher
        self.port = self.handle.port

    def post(self, tokens, max_tokens: int, timeout: float = 300.0) -> dict:
        body = json.dumps({"tokens": list(map(int, tokens)),
                           "max_tokens": int(max_tokens)}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())

    def warm_up(self, schedule, seed: int):
        """One request through the server's own path for every prefill
        width the schedule can hit; each also decodes, which warms the one
        decode program. Lengths are ones the schedule does not use."""
        used = {len(r["tokens"]) for r in schedule}
        widths = gen.prefill_buckets(
            used, self.engine.min_bucket, self.engine.prefill_ceiling)
        rng = np.random.default_rng([int(seed), 0x7761726D])
        t0 = time.perf_counter()
        room = self.engine.max_len - WARMUP_OUTPUT_TOKENS
        for width in widths:
            n = min(width, room)
            while n in used and n > width // 2 + 1:
                n -= 1
            reply = self.post(
                rng.integers(0, self.cfg["vocab_size"], n),
                WARMUP_OUTPUT_TOKENS)
            if reply.get("status") != "done":
                raise RuntimeError(f"warm-up request failed: {reply}")
        self.engine.drain_promotions()
        self.readings["warmup_s"] = time.perf_counter() - t0
        self.readings["warm_widths"] = widths

    def instrument(self, recorder):
        """Benchmark spans around the batcher's phases and the engine's two
        calls (traced runs only)."""
        b, e = self.batcher, self.engine
        recorder.wrap(b, "_expire_queued", "batcher.expire")
        recorder.wrap(b, "_admit", "batcher.admit")
        recorder.wrap(b, "_decode", "batcher.decode")
        recorder.wrap(b, "_retire", "batcher.retire")
        recorder.wrap(e, "prefill", "engine.prefill")
        recorder.wrap(
            e, "decode_step", "engine.decode_step",
            attrs=lambda eng: {
                "active": len(b._slot_req),
                "live_tokens": int(sum(
                    r.prompt.size + len(r.out_tokens)
                    for r in list(b._slot_req.values()))),
            })

    def schedule(self, seed: int, seconds: float, rate=None, avoid=()):
        """The window's requests: no prompt as long as a warm-up's (or as
        one in ``avoid``), so that the engine sees no length twice."""
        p = self.traffic["prompt_len"]
        warm = set(gen.prefill_buckets(
            range(p["min"], p["max"] + 1), self.engine.min_bucket,
            self.engine.prefill_ceiling))
        return gen.open_loop_schedule(
            self.traffic, self.cfg["vocab_size"], seed, seconds, rate=rate,
            avoid_prompt_lens=warm | set(avoid))

    def compiles_between(self, before: dict, after: dict) -> int:
        """Programs the engine compiled between two ``engine.stats()``,
        background promotions included."""
        return sum(after[k] - before[k] for k in (
            "prefill_compiles", "decode_compiles", "prefill_promotions",
            "prefill_bg_promotions"))

    def stop(self):
        self.handle.stop()
        for obj, names in ((self.batcher, ("_expire_queued", "_admit",
                                           "_decode", "_retire")),
                           (self.engine, ("prefill", "decode_step"))):
            for name in names:
                obj.__dict__.pop(name, None)
        self.handle = self.engine = self.batcher = None
        self.params = None
        gc.collect()


def start_loadgen(port: int, schedule, seconds: float, drain_limit_s: float):
    """The child process, holding the schedule and waiting for ``go``."""
    child = subprocess.Popen(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    job = {
        "port": port, "timeout_s": seconds + drain_limit_s,
        "requests": [dict(r, timeout_s=seconds + drain_limit_s - r["due"])
                     for r in schedule],
    }
    child.stdin.write(json.dumps(job) + "\n")
    child.stdin.flush()
    if child.stdout.readline().strip() != "ready":
        child.kill()
        child.wait()
        raise RuntimeError("the load generator did not start")
    return child


def run_window(server: Server, schedule, seconds: float, traced_fn=None):
    """Open the window: returns ``(t0, records)`` with ``t0`` on
    ``time.monotonic()``. ``traced_fn(t0)`` runs in this thread meanwhile."""
    drain = float(server.traffic["drain_limit_s"])
    child = start_loadgen(server.port, schedule, seconds, drain)
    try:
        t0 = time.monotonic() + 0.25
        child.stdin.write(f"go {t0!r}\n")
        child.stdin.flush()
        if traced_fn is not None:
            traced_fn(t0)
        out, _ = child.communicate(timeout=seconds + drain + 60.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"the load generator exited {child.returncode}")
    return t0, json.loads(out.strip().splitlines()[-1])


def summarise(schedule, records, seconds: float) -> dict:
    """End-to-end numbers of one window from the client's records."""
    by_id = {r["id"]: r for r in records}
    ttft, tpot, late, done_tokens, failed = [], [], [], 0, 0
    completed = []
    for req in schedule:
        rec = by_id.get(req["id"])
        reply = (rec or {}).get("reply") or {}
        ok = (
            rec is not None and rec.get("code") == 200
            and reply.get("status") == "done"
            and len(reply.get("tokens", ())) == req["max_tokens"]
        )
        if rec is not None and "sent" in rec:
            late.append((rec["sent"] - req["due"]) * 1e3)
        if ok:
            client_ms = (rec["done"] - rec["sent"]) * 1e3
            ok = reply["ttft_ms"] + reply["gen_ms"] <= client_ms + 1.0
        if not ok:
            failed += 1
            ttft.append(MISSING_MS)
            continue
        ttft.append((rec["sent"] - req["due"]) * 1e3 + reply["ttft_ms"])
        n_out = len(reply["tokens"])
        if n_out > 1:
            tpot.append(reply["gen_ms"] / (n_out - 1))
        if rec["done"] <= seconds:
            done_tokens += n_out
        completed.append((req, reply))
    return {
        "attempted": len(schedule), "failed": failed,
        "serve_tokens_per_s": done_tokens / seconds,
        "ttft_p90_ms": percentile(ttft, 90),
        "tpot_p90_ms": percentile(tpot, 90) if tpot else MISSING_MS,
        "ttft_p50_ms": statistics.median(ttft),
        "late_ms": late, "completed": completed,
    }


def sample_for_check(completed, seed: int, count: int):
    """A sample of finished requests drawn from the seed, the longest
    (prompt and output together) always in it."""
    if not completed:
        return []
    order = sorted(range(len(completed)), key=lambda i: -(
        len(completed[i][0]["tokens"]) + len(completed[i][1]["tokens"])))
    rng = np.random.default_rng([int(seed), 0x636865636B])
    rest = list(rng.permutation(order[1:]))[:max(count - 1, 0)]
    return [completed[i] for i in [order[0]] + [int(i) for i in rest]]


@functools.lru_cache(maxsize=None)
def _gaps_program(ref, cfg_json, precision, pick_precision):
    import jax
    import jax.numpy as jnp

    cfg = json.loads(cfg_json)

    @jax.jit
    def gaps_of(params, tokens, nxt):
        logits = ref.forward(params, tokens, cfg, precision)[0]
        if pick_precision is not None:
            nxt = jnp.argmax(
                ref.forward(params, tokens, cfg, pick_precision)[0], axis=-1)
        picked = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - picked

    return gaps_of


def reference_gaps(cell, make_params, seed, sample, precision="float32",
                   pick_precision=None):
    """For every served token of the sampled requests: by how much its
    logit lies below the reference's best at that position (0 where the
    served token is the reference's own choice). One forward pass of the
    plain reference over each prompt with its served tokens. With
    ``pick_precision`` the token judged is not the served one but the one
    that the reference computed in that precision puts first (the control).
    Returns the list of gaps, one per served token."""
    ref, cfg = cell.reference(), cell.config
    width = cfg["max_len"]
    params = make_params(weights.seed_key(seed))
    gaps_of = _gaps_program(
        ref, ref.program_key(cfg), precision, pick_precision)

    out = []
    for req, reply in sample:
        prompt, served = req["tokens"], reply["tokens"]
        seq = (prompt + served)[:width + 1]
        tokens = np.zeros((1, width), np.int32)
        nxt = np.zeros((width,), np.int32)
        tokens[0, :len(seq) - 1] = seq[:-1]
        nxt[:len(seq) - 1] = seq[1:]
        gaps = np.asarray(gaps_of(params, tokens, nxt))
        out.extend(gaps[len(prompt) - 1:len(seq) - 1].tolist())
    return out


def run(cell, args, process_start: float, require_chip: bool = True) -> dict:
    t = cell.traffic
    marks = {"imports": time.perf_counter() - process_start}
    server = Server(cell, args.seed, require_chip)
    marks["weights_and_serve"] = (
        time.perf_counter() - process_start - marks["imports"])
    try:
        schedule = server.schedule(args.seed, args.seconds)
        server.warm_up(schedule, args.seed)
        marks["warm_up"] = server.readings["warmup_s"]
        print("setup phases (s): " + json.dumps(
            {k: round(v, 2) for k, v in marks.items()}), file=sys.stderr)
        recorder = spans.Recorder(annotate=bool(args.trace))
        trace_box = {}
        traced_fn = None
        if args.trace:
            server.instrument(recorder)

            def traced_fn(t0):
                def body():
                    time.sleep(min(TRACE_SECONDS, args.seconds))
                time.sleep(max(min(TRACE_START_S, args.seconds / 3)
                               - (time.monotonic() - t0), 0.0))
                trace_box["trace"] = xtrace.record(body, len(server.devices))

        stats_before = server.engine.stats()
        log = server.compile_log
        server.readings["trace_lower_s"] = log.seconds(
            compiles.TRACE, compiles.LOWER)
        server.readings["compile_s"] = log.seconds(compiles.COMPILE)
        events_before = len(log.events)
        to_perf_counter = time.perf_counter() - time.monotonic()
        t0, records = run_window(server, schedule, args.seconds, traced_fn)
        setup_s = t0 + to_perf_counter - process_start
        stats_after = server.engine.stats()
        summary = summarise(schedule, records, args.seconds)
        # the engine's own counts (promotions included), and whatever else
        # JAX traced, lowered or compiled in this process meanwhile
        in_window = log.count_since(events_before) + server.compiles_between(
            stats_before, stats_after)
        memory_peak = chip.memory_peak_bytes(server.devices)
        readings = dict(server.readings)
        readings.update(
            kind="serve", cfg=cell.config, traffic=t, chips=cell.chips,
            seconds=args.seconds, summary=summary, spans=recorder,
            compiles_in_window=in_window, slots=t["serve"]["slots"],
            device_kind=server.devices[0].device_kind,
            memory_peak_bytes=memory_peak, trace=trace_box.get("trace"),
        )
        device = chip.describe(server.devices)
        device["memory_peak_bytes"] = memory_peak
        make_params = server.make_params
    finally:
        server.stop()
    sample = sample_for_check(summary["completed"], args.seed,
                              int(t["check_requests"]))
    gaps = reference_gaps(cell, make_params, args.seed, sample)
    numbers = {
        "logit_gap_max": max(gaps) if gaps else float("inf"),
        "compiles_in_window": in_window,
        "unanswered": summary["failed"],
    }
    readings["checked_tokens"] = len(gaps)
    correct, compared = compare.judge(numbers, cell.limits())
    return {
        "correct": correct, "attempted": summary["attempted"],
        "failed": summary["failed"],
        "end_to_end": {
            "serve_tokens_per_s": summary["serve_tokens_per_s"],
            "ttft_p90_ms": summary["ttft_p90_ms"],
            "tpot_p90_ms": summary["tpot_p90_ms"],
            "setup_s": setup_s,
        },
        "readings": readings, "device": device, "compared": compared,
    }
