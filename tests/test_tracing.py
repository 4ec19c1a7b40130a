"""Trace-plane tests: common/tracing.py + analysis/trace_merge.py.

Covers the ISSUE 20 acceptance surfaces that don't need a serving
fleet: context minting/adoption (W3C traceparent round-trip, malformed
headers, sampling), the span ring bound under concurrent emitters
(property test), the NTP offset estimator on synthetic two-host stamp
pairs — including the asymmetric-RTT error bound — multi-hop offset
composition, and skew-corrected assembly ordering.
"""

import json
import os
import threading
import time

import pytest

from horovod_tpu.analysis import trace_merge
from horovod_tpu.common import tracing


@pytest.fixture
def traced(monkeypatch):
    """Tracing ON at sample rate 1.0, fresh recorder + settings."""
    monkeypatch.setenv("HOROVOD_TRACE", "1")
    monkeypatch.setenv("HOROVOD_TRACE_SAMPLE", "1.0")
    tracing._reset()
    yield
    tracing._reset()


# --------------------------------------------------------------- context


class TestContext:
    def test_traceparent_round_trip(self):
        ctx = tracing.TraceContext("ab" * 16, "cd" * 8, True)
        parsed = tracing.parse_traceparent(ctx.to_traceparent())
        assert parsed.trace_id == ctx.trace_id
        assert parsed.span_id == ctx.span_id
        assert parsed.sampled

    @pytest.mark.parametrize(
        "header",
        [
            None,
            "",
            "garbage",
            "00-short-cd" + "cd" * 7 + "-01",
            "00-" + "zz" * 16 + "-" + "cd" * 8 + "-01",  # non-hex
            "00-" + "00" * 16 + "-" + "cd" * 8 + "-01",  # zero trace
            "00-" + "ab" * 16 + "-" + "00" * 8 + "-01",  # zero span
        ],
    )
    def test_malformed_headers_rejected(self, header):
        assert tracing.parse_traceparent(header) is None

    def test_off_by_default(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_TRACE", raising=False)
        tracing._reset()
        assert not tracing.enabled()
        assert tracing.mint() is None
        assert tracing.adopt("00-" + "ab" * 16 + "-" + "cd" * 8 + "-01") \
            is None
        # None propagates: no span, no cost
        assert tracing.start_span("x", None) is None
        tracing._reset()

    def test_mint_and_children(self, traced):
        ctx = tracing.mint()
        assert ctx is not None and ctx.sampled
        child = tracing.start_span("op", ctx, k=1)
        assert child.ctx.trace_id == ctx.trace_id
        assert child.ctx.span_id != ctx.span_id
        assert child.parent_id == ctx.span_id

    def test_adopt_keeps_caller_decision(self, traced):
        hdr = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        ctx = tracing.adopt(hdr)
        assert ctx.trace_id == "ab" * 16
        # explicit sampled=0 stays untraced even with tracing on
        assert tracing.adopt(hdr[:-2] + "00") is None

    def test_sample_zero_mints_nothing(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_TRACE", "1")
        monkeypatch.setenv("HOROVOD_TRACE_SAMPLE", "0.0")
        tracing._reset()
        assert all(tracing.mint() is None for _ in range(20))
        tracing._reset()

    def test_wire_dict_round_trip(self, traced):
        ctx = tracing.mint()
        back = tracing.TraceContext.from_dict(
            json.loads(json.dumps(ctx.to_dict()))
        )
        assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)
        assert tracing.TraceContext.from_dict(None) is None
        assert tracing.TraceContext.from_dict({"trace_id": ""}) is None


# ----------------------------------------------------------------- spans


class TestSpans:
    def test_span_records_into_ring(self, traced):
        ctx = tracing.mint()
        span = tracing.start_span("op", ctx, slot=3)
        span.end(outcome="ok")
        span.end(outcome="twice")  # idempotent: second end is a no-op
        recs = tracing.recorder().spans()
        assert len(recs) == 1
        rec = recs[0]
        assert rec["name"] == "op"
        assert rec["trace_id"] == ctx.trace_id
        assert rec["tags"]["outcome"] == "ok"
        assert rec["host"] and rec["pid"] == os.getpid()
        assert rec["dur_ms"] >= 0

    def test_retry_annotation_lands_on_active_span(self, traced):
        span = tracing.root_span("hop", tracing.mint())
        with span:
            tracing.annotate("retry:site#1@40ms")
        rec = tracing.recorder().spans()[-1]
        assert rec["tags"]["notes"] == ["retry:site#1@40ms"]

    def test_active_adopts_span_across_threads(self, traced):
        span = tracing.root_span("handoff", tracing.mint())
        seen = []

        def worker():
            with tracing.active(span):
                seen.append(tracing.current())

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert seen == [span]
        assert tracing.current() is None

    def test_ring_bound_under_concurrent_emitters(self, traced):
        """Property: whatever N threads emit, the ring NEVER exceeds
        its bound and every surviving record is intact."""
        rec = tracing.recorder()
        rec.configure(capacity=64)
        ctx = tracing.mint()
        stop = threading.Event()
        errors = []

        def emitter(tid):
            try:
                for i in range(500):
                    s = tracing.start_span("burst", ctx, tid=tid, i=i)
                    s.end()
            except Exception as e:  # pragma: no cover - the failure
                errors.append(e)

        def watcher():
            while not stop.is_set():
                assert len(rec) <= 64
                for r in rec.spans():
                    assert r["name"] == "burst"

        threads = [
            threading.Thread(target=emitter, args=(t,)) for t in range(8)
        ]
        w = threading.Thread(target=watcher)
        w.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        w.join()
        assert not errors
        assert len(rec) <= 64
        assert len(rec.spans()) <= 64

    def test_dump_json_lines(self, traced, tmp_path):
        ctx = tracing.mint()
        for i in range(3):
            tracing.start_span("s", ctx, i=i).end()
        path = str(tmp_path / "ring.spans")
        assert tracing.recorder().dump(path) == path
        lines = [json.loads(x) for x in open(path)]
        assert [r["tags"]["i"] for r in lines] == [0, 1, 2]


# -------------------------------------------------------- offset estimation


class TestNtpOffset:
    def test_symmetric_delay_exact(self):
        # host B runs 250 ms ahead; 10 ms each way
        true_off, d = 0.250, 0.010
        t_send = 100.0
        peer_recv = t_send + d + true_off
        peer_send = peer_recv + 0.002
        t_recv = peer_send - true_off + d
        off, err = trace_merge.ntp_offset(
            t_send, peer_recv, peer_send, t_recv
        )
        assert off == pytest.approx(true_off, abs=1e-9)
        assert err == pytest.approx(d, abs=1e-9)

    def test_asymmetric_rtt_error_bound(self):
        """Asymmetric delay skews the estimate but the TRUE offset
        always stays within ±err (half-RTT) of it — the NTP bound the
        assembler's weighting relies on."""
        true_off = -0.120
        for d_fwd, d_back in [(0.001, 0.030), (0.040, 0.002),
                              (0.0, 0.050), (0.025, 0.025)]:
            t_send = 500.0
            peer_recv = t_send + d_fwd + true_off
            peer_send = peer_recv + 0.001
            t_recv = peer_send - true_off + d_back
            off, err = trace_merge.ntp_offset(
                t_send, peer_recv, peer_send, t_recv
            )
            assert abs(off - true_off) <= err + 1e-12, (d_fwd, d_back)
            # and the skew is exactly half the asymmetry
            assert off - true_off == pytest.approx(
                (d_fwd - d_back) / 2, abs=1e-9
            )

    def test_offsets_compose_across_hops(self):
        """router→prefill→decode: decode never talked to the router,
        yet lands on its clock through the prefill edge."""
        edges = [
            {"a": ("router", 1), "b": ("prefill", 2),
             "offset": 0.100, "err": 0.002},
            {"a": ("prefill", 2), "b": ("decode", 3),
             "offset": -0.040, "err": 0.003},
        ]
        offs = trace_merge.host_offsets(
            edges, reference=("router", 1)
        )
        assert offs[("router", 1)] == 0.0
        assert offs[("prefill", 2)] == pytest.approx(0.100)
        assert offs[("decode", 3)] == pytest.approx(0.060)

    def test_parallel_edges_weighted_by_error(self):
        """A tight edge dominates a sloppy (retried) one between the
        same pair — inverse-error fusion."""
        edges = [
            {"a": ("a", 1), "b": ("b", 2), "offset": 0.100,
             "err": 0.001},
            {"a": ("a", 1), "b": ("b", 2), "offset": 0.900,
             "err": 1.000},
        ]
        offs = trace_merge.host_offsets(edges, reference=("a", 1))
        assert abs(offs[("b", 2)] - 0.100) < 0.005

    def test_dijkstra_prefers_tight_path(self):
        """Two routes to the same host: the low-error relay path must
        beat the direct-but-sloppy edge."""
        edges = [
            {"a": ("a", 1), "b": ("c", 3), "offset": 5.0, "err": 2.0},
            {"a": ("a", 1), "b": ("b", 2), "offset": 1.0,
             "err": 0.001},
            {"a": ("b", 2), "b": ("c", 3), "offset": 1.0,
             "err": 0.001},
        ]
        offs = trace_merge.host_offsets(edges, reference=("a", 1))
        # relay path says 2.0; direct sloppy edge said 5.0 but only
        # perturbs the fused direct estimate, it can't win the path
        assert abs(offs[("c", 3)] - 2.0) < 0.1


# --------------------------------------------------------------- assembly


def _span(host, pid, role, name, ts, dur_ms=1.0, trace_id="t" * 32,
          **tags):
    return {
        "trace_id": trace_id, "span_id": os.urandom(8).hex(),
        "parent_id": None, "name": name, "ts": ts, "dur_ms": dur_ms,
        "tags": tags, "host": host, "pid": pid, "role": role,
    }


class TestAssembly:
    def test_skew_corrected_monotonic_order(self):
        """A decode host 10 s behind makes raw timestamps lie; the
        assembled order must still read router → prefill → decode."""
        skew = -10.0  # decode clock = true - 10s
        spans = [
            _span("h1", 1, "router", "route", 100.0, dur_ms=50.0),
            _span("h1", 2, "prefill", "serve.prefill", 100.010),
            # the hop span carries the NTP stamps for the skewed host
            _span(
                "h1", 2, "prefill", "kv.stream", 100.020,
                t_send=100.020, t_recv=100.024,
                peer_recv=100.021 + skew, peer_send=100.023 + skew,
                peer="h1:3",
            ),
            _span("h1", 3, "decode", "serve.decode", 100.030 + skew),
        ]
        corrected, offsets = trace_merge.assemble(spans)
        assert offsets[("h1", 3)] == pytest.approx(skew, abs=0.003)
        names = [r["name"] for r in corrected]
        assert names == [
            "route", "serve.prefill", "kv.stream", "serve.decode"
        ]
        ts = [r["ts_corrected"] for r in corrected]
        assert ts == sorted(ts)

    def test_to_chrome_one_row_per_host_role(self):
        spans = [
            _span("h1", 1, "router", "route", 1.0),
            _span("h1", 2, "prefill", "serve.prefill", 1.1),
            _span("h2", 3, "decode", "serve.decode", 1.2),
        ]
        corrected, offsets = trace_merge.assemble(spans)
        chrome = trace_merge.to_chrome(corrected, offsets)
        meta = [
            e for e in chrome["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        ]
        assert sorted(m["args"]["name"] for m in meta) == [
            "h1 [prefill]", "h1 [router]", "h2 [decode]"
        ]
        events = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
        assert len(events) == 3
        assert all(e["ts"] >= 0 for e in events)
        assert all(e["args"]["trace_id"] == "t" * 32 for e in events)

    def test_traces_in_and_filter(self):
        spans = [
            _span("h", 1, "r", "a", 1.0, trace_id="x" * 32),
            _span("h", 1, "r", "b", 2.0, trace_id="x" * 32),
            _span("h", 1, "r", "c", 3.0, trace_id="y" * 32),
        ]
        assert trace_merge.traces_in(spans) == {
            "x" * 32: 2, "y" * 32: 1
        }
        assert len(trace_merge.filter_trace(spans, "y" * 32)) == 1


# -------------------------------------------------------------- exemplars


class TestExemplars:
    def test_p95_exemplar_witness(self):
        from horovod_tpu.serving.slo import LatencyRecorder

        rec = LatencyRecorder(capacity=128)
        for i in range(100):
            rec.record_ttft(float(i), trace_id=f"trace-{i}")
        s = rec.summaries()["ttft_ms"]
        # nearest-rank p95 witness over 0..99 is sample 94
        assert s["p95_exemplar"] == "trace-94"
        text = "\n".join(rec.render_prometheus_summaries())
        assert '# {trace_id="trace-94"}' in text
        assert 'serve_ttft_p95_exemplar{trace_id="trace-94"} 1' in text

    def test_untraced_samples_leave_no_exemplar(self):
        from horovod_tpu.serving.slo import LatencyRecorder

        rec = LatencyRecorder(capacity=16)
        rec.record_tpot(5.0)
        s = rec.summaries()["tpot_ms"]
        assert s["p95_exemplar"] == ""
        text = "\n".join(rec.render_prometheus_summaries())
        assert "tpot_p95_exemplar" not in text


# -------------------------------------------------------- compile ledger


TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def ledger(monkeypatch):
    """The compile ledger on a fresh ring, every event a span (the 20 ms
    floor off; a test that wants it sets it back)."""
    from horovod_tpu.common import compile_cache

    tracing._reset()
    compile_cache._ledger.install()
    monkeypatch.setattr(compile_cache, "SMALL_S", 0.0)
    yield compile_cache
    tracing._reset()


def _jit_spans():
    return [r for r in tracing.recorder().spans()
            if r["name"].startswith("hvd.init.jit_")]


def _jax_event(event, seconds, fun):
    """One event as ``jax._src.dispatch.log_elapsed_time`` reports it."""
    from jax import monitoring

    start = time.time()
    monitoring.record_scalar(event, start, fun_name=fun)
    monitoring.record_event_duration_secs(event, seconds, fun_name=fun)
    monitoring.record_event_time_span(
        event, start, start + seconds, fun_name=fun)


class TestCompileLedger:
    def test_a_jit_leaves_one_span_of_each_kind_with_its_name(self, ledger):
        import jax
        import jax.numpy as jnp

        def ledger_outer(x):
            return jnp.sin(x) * 2

        with tracing.span("hvd.init.place_state") as parent:
            jax.jit(ledger_outer).lower(jnp.ones(7)).compile()
        mine = [r for r in _jit_spans() if "ledger_outer" in r["tags"]["fun"]]
        assert [(r["name"], r["tags"]["fun"]) for r in mine] == [
            ("hvd.init.jit_trace", "ledger_outer"),
            ("hvd.init.jit_lower", "jit(ledger_outer)"),
            ("hvd.init.jit_compile", "jit(ledger_outer)")]
        # live spans: children of the thread's active span, serials in order
        assert {r["parent_id"] for r in mine} == {parent.ctx.span_id}
        assert [r["seq"] for r in mine] == sorted(r["seq"] for r in mine)
        # the suite runs with the persistent cache off
        assert mine[2]["tags"]["cache"] == "off"
        assert all(r["dur_ms"] > 0 for r in mine)

    def test_a_jit_inside_a_jit_is_counted_in_the_outer_span(
            self, ledger, monkeypatch):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def ledger_inner(x):
            return jnp.cos(x) + 1

        def ledger_outer2(x):
            return ledger_inner(x) * 2

        jax.jit(ledger_outer2).lower(jnp.ones(5))
        names = [r["tags"]["fun"] for r in _jit_spans()]
        assert "ledger_inner" not in names
        (outer,) = [r for r in _jit_spans()
                    if r["tags"]["fun"] == "ledger_outer2"]
        assert outer["tags"]["inner"] >= 1
        assert 0 < outer["tags"]["inner_s"] <= outer["dur_ms"] / 1e3
        # an inner event of 100 ms or more is a child span, written when
        # it ends (the floor lowered to stand for a long one)
        monkeypatch.setattr(ledger, "INNER_SPAN_S", 0.0)

        def ledger_outer3(x):
            return ledger_inner(x + 1) * 3  # traced anew: another shape

        jax.jit(ledger_outer3).lower(jnp.ones(6))
        spans = {r["tags"]["fun"]: r for r in _jit_spans()}
        inner, outer = spans["ledger_inner"], spans["ledger_outer3"]
        assert inner["parent_id"] == outer["span_id"]
        assert inner["tags"]["depth"] == 1 and "depth" not in outer["tags"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur_ms"] / 1e3 <= (
            outer["ts"] + outer["dur_ms"] / 1e3 + 1e-3)

    def test_the_persistent_caches_answer_is_on_the_compile_span(
            self, ledger, tmp_path):
        import jax
        import jax.numpy as jnp
        from jax.experimental.compilation_cache import compilation_cache

        from horovod_tpu.common.metrics import registry

        settings = {
            "jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1,
        }
        before = {k: getattr(jax.config, k) for k in settings}
        counted = registry.snapshot()

        def ledger_cached(x):
            return jnp.tanh(x) @ x.T

        def compile_span():
            jax.jit(ledger_cached).lower(jnp.ones((9, 9))).compile()
            return [r["tags"] for r in _jit_spans()
                    if r["name"] == "hvd.init.jit_compile"
                    and r["tags"]["fun"] == "jit(ledger_cached)"][-1]

        try:
            for k, v in settings.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()
            first = compile_span()
            jax.clear_caches()  # what a second process would start with
            second = compile_span()
        finally:
            for k, v in before.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()
        assert first["cache"] == "miss" and "retrieval_s" not in first
        assert second["cache"] == "hit"
        assert second["retrieval_s"] >= 0 and "saved_s" in second
        now = registry.snapshot()
        delta = {k: now.get(k, 0.0) - counted.get(k, 0.0)
                 for k in ("jit.compiles", "jit.cache_hits",
                           "jit.cache_misses", "jit.compile_s")}
        # (the eager programs that made the inputs count too)
        assert delta["jit.cache_hits"] >= 1 and delta["jit.cache_misses"] >= 1
        assert delta["jit.compiles"] >= 2 and delta["jit.compile_s"] > 0

    def test_a_thousand_small_programs_are_a_tally_not_a_thousand_spans(
            self, ledger, monkeypatch):
        monkeypatch.setattr(ledger, "SMALL_S", 0.020)
        _jax_event(COMPILE_EVENT, 0.05, "jit(flush)")  # empties the tally
        before = len(tracing.recorder())
        for i in range(1000):
            _jax_event(TRACE_EVENT, 0.001, "add")
            _jax_event(COMPILE_EVENT, 0.002, "jit(add)")
        assert len(tracing.recorder()) == before
        assert tracing.current() is None  # every live span was left
        _jax_event(COMPILE_EVENT, 0.05, "jit(step)")
        _jax_event(TRACE_EVENT, 0.03, "step")
        compiled, traced = tracing.recorder().spans()[before:]
        assert compiled["tags"] == {
            "fun": "jit(step)", "cache": "off", "small": 1000,
            "small_s": pytest.approx(2.0, abs=1e-3)}
        assert traced["tags"] == {
            "fun": "step", "small": 1000, "small_s": pytest.approx(1.0, abs=1e-3)}
        # the next span of a kind starts a new tally
        _jax_event(TRACE_EVENT, 0.03, "step2")
        assert tracing.recorder().spans()[-1]["tags"] == {"fun": "step2"}

    def test_a_ledger_span_is_in_a_running_profiler_session(
            self, ledger, tmp_path):
        import glob
        import sys

        import jax
        import jax.numpy as jnp

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from benchmark.lib import xtrace

        def ledger_profiled(x):
            time.sleep(0.02)
            return x + 1

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            jax.jit(ledger_profiled).lower(jnp.ones(3))
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(
            str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
        host = xtrace.load(path, span_prefixes=("hvd.",)).host_spans
        (record,) = [r for r in _jit_spans()
                     if r["tags"]["fun"] == "ledger_profiled"]
        (event,) = [(start, end) for name, start, end, idx in host
                    if name == "hvd.init.jit_trace" and idx == record["seq"]]
        assert event[1] - event[0] >= 15e6  # the sleep, in nanoseconds
