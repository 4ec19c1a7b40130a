"""Process spans (``tracing.span`` / ``hot_span`` / ``trace_time_span``): one
span system for requests and for the process's own work, in one ring, and on
the profiler's clock while a profiler session runs."""

import glob
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import tracing

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.lib import spans as bench_spans  # noqa: E402
from benchmark.lib import xtrace  # noqa: E402
from benchmark.lib.xtrace import DeviceTrace, Trace  # noqa: E402


@pytest.fixture
def ring(monkeypatch):
    """A fresh ring and settings cache, with ``HOROVOD_TRACE`` as given."""
    def make(trace_on, sample=1.0):
        monkeypatch.setenv("HOROVOD_TRACE", "1" if trace_on else "0")
        monkeypatch.setenv("HOROVOD_TRACE_SAMPLE", str(sample))
        tracing._reset()
        return tracing.recorder()
    yield make
    tracing._reset()


def _by_name(rec):
    return {r["name"]: r for r in rec.spans()}


def test_a_process_span_nests_under_the_active_span_with_a_serial(ring):
    rec = ring(False)  # process spans need no switch
    with tracing.span("hvd.init", world=4) as outer:
        assert tracing.current() is outer
        with tracing.span("hvd.init.optimizer_init") as inner:
            inner.tag(local=4)
    with tracing.span("hvd.init.broadcast_parameters", leaves=3):
        pass
    got = _by_name(rec)
    root = tracing.process_root()
    assert got["hvd.init"]["parent_id"] == root.span_id
    assert got["hvd.init.broadcast_parameters"]["parent_id"] == root.span_id
    assert (got["hvd.init.optimizer_init"]["parent_id"]
            == got["hvd.init"]["span_id"])
    assert {r["trace_id"] for r in got.values()} == {root.trace_id}
    assert got["hvd.init"]["tags"] == {"world": 4}
    assert got["hvd.init.optimizer_init"]["tags"] == {"local": 4}
    seqs = [got[n]["seq"] for n in (
        "hvd.init", "hvd.init.optimizer_init", "hvd.init.broadcast_parameters")]
    assert seqs == sorted(seqs) and len(set(seqs)) == 3
    # the same record shape as a request span's
    assert set(got["hvd.init"]) >= {
        "trace_id", "span_id", "parent_id", "name", "seq", "ts", "dur_ms",
        "tags", "host", "pid"}


def test_a_request_span_carries_a_serial_too(ring):
    rec = ring(True)
    ctx = tracing.mint()
    with tracing.start_span("serve.prefill", ctx, slot=1) as s:
        # a process span opened under a request span is its child
        with tracing.span("hvd.engine.decode_step"):
            pass
    got = _by_name(rec)
    assert got["serve.prefill"]["seq"] == s.seq
    assert (got["hvd.engine.decode_step"]["parent_id"]
            == got["serve.prefill"]["span_id"])
    assert got["hvd.engine.decode_step"]["trace_id"] == ctx.trace_id


@pytest.mark.parametrize("trace_on, sample, recorded", [
    (False, 1.0, False), (True, 1.0, True),
    (True, 0.0, False),  # HOROVOD_TRACE_SAMPLE holds for hot spans too
])
def test_hot_spans_follow_the_existing_switches(
        ring, trace_on, sample, recorded):
    rec = ring(trace_on, sample)
    with tracing.hot_span("hvd.engine.decode_step", active=0) as s:
        assert (s is not None) == recorded
    assert ("hvd.engine.decode_step" in _by_name(rec)) == recorded
    # a span that fires a bounded number of times is always recorded
    with tracing.span("hvd.init"):
        pass
    assert "hvd.init" in _by_name(rec)


def test_trace_time_spans_fire_only_while_jax_traces(ring):
    rec = ring(False)

    def f(x):
        with tracing.trace_time_span("hvd.trainer.trace_update", x, leaves=1):
            return x * 2

    f(jnp.ones(3))  # eager: no span
    assert "hvd.trainer.trace_update" not in _by_name(rec)
    step = jax.jit(f)
    step(jnp.ones(3))
    step(jnp.ones(3))  # the second call runs no Python
    named = [r for r in rec.spans() if r["name"] == "hvd.trainer.trace_update"]
    assert len(named) == 1 and named[0]["tags"] == {"leaves": 1}


def test_spans_sit_on_the_profilers_clock_beside_the_benchmarks(
        ring, tmp_path):
    """Inside a profiler session a span of the program is an event of the
    host plane like the benchmark's own ``bench.`` spans, its serial in its
    name; the reduction then gives a gap of the device's work its name."""
    rec = ring(False)
    bench = bench_spans.Recorder(annotate=True)
    with tracing.span("hvd.init.before_the_session"):
        pass  # no session: ring only
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with bench.span("bench.dispatch"):
            with tracing.span("hvd.exchange.test_gap", note=1) as s:
                time.sleep(0.02)
        np.asarray(jnp.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    host = xtrace.load(
        path, span_prefixes=("bench.", "hvd.")).host_spans
    by_name = {name: (start, end, idx) for name, start, end, idx in host}
    assert "hvd.init.before_the_session" not in by_name
    start, end, idx = by_name["hvd.exchange.test_gap"]
    b_start, b_end, _ = by_name["bench.dispatch"]
    assert b_start <= start and end <= b_end  # one clock, nested
    assert end - start >= 15e6  # the sleep, in the profiler's nanoseconds
    # the serial joins the trace event to the ring record and its tags
    record = _by_name(rec)["hvd.exchange.test_gap"]
    assert idx == record["seq"] == s.seq and record["tags"] == {"note": 1}
    # a device that idles inside the span: the gap gets the span's name,
    # the innermost one's
    device = DeviceTrace(
        [("%fusion.1 = f32[] fusion()", b_start - 1000, start + 1000),
         ("%fusion.2 = f32[] fusion()", end - 1000, b_end + 1000)], [])
    gaps = Trace({0: device}, host).idle_gaps()
    assert list(gaps) == ["hvd.exchange.test_gap"]


def test_tracing_stays_stdlib_only_at_import():
    import subprocess

    code = ("import sys; from horovod_tpu.common import tracing; "
            "assert 'jax' not in sys.modules, 'jax imported'; "
            "import horovod_tpu.runner; "
            "assert 'jax' not in sys.modules, 'runner imports jax'")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def test_hvd_init_and_state_placement_record_their_spans(ring):
    import optax

    import horovod_tpu as hvd

    rec = ring(False)
    hvd.shutdown()
    hvd.init()
    try:
        got = _by_name(rec)
        assert (got["hvd.init"]["parent_id"]
                == tracing.process_root().span_id)
        hvd.init()  # idempotent: no second span
        assert sum(r["name"] == "hvd.init" for r in rec.spans()) == 1
        params = {"w": jnp.ones((4, 8)), "b": jnp.zeros((8,))}
        opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
        params = hvd.broadcast_parameters(params)
        state = hvd.broadcast_optimizer_state(opt.init(params))
        got = _by_name(rec)
        assert got["hvd.init.broadcast_parameters"]["tags"] == {
            "leaves": 2, "bytes": 4 * 8 * 4 + 8 * 4, "device_puts": 2}
        n_state = len(jax.tree_util.tree_leaves(state))
        assert got["hvd.init.optimizer_init"]["tags"]["leaves"] == n_state
        tags = got["hvd.init.broadcast_optimizer_state"]["tags"]
        assert tags["leaves"] == n_state == tags["device_puts"]
        # the optimizer-state broadcast is a span of its own, not a child
        assert (got["hvd.init.broadcast_optimizer_state"]["parent_id"]
                == tracing.process_root().span_id)
    finally:
        hvd.shutdown()
