"""Flight-recorder telemetry tests (common/telemetry.py + satellites).

Covers the three faces of the hub — StepStats ring, live /metrics
scrape, cross-rank straggler ledger — plus the observability
satellites: delta-aware metrics dumps, stall gauges, the
timeline stop()-during-emit race, and the SIGTERM post-mortem dump
(the analog of the reference's kill-based elastic tests, SURVEY §4.3).
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh_hub(**kw):
    from horovod_tpu.common.telemetry import TelemetryHub

    return TelemetryHub(**kw)


# ------------------------------------------------------------- the ring


class TestStepRing:
    def test_ring_bounded_and_ordered(self):
        hub = _fresh_hub(capacity=4)
        for _ in range(10):
            hub.step_begin()
            hub.step_end()
        recs = hub.records()
        assert len(recs) == 4  # bounded
        steps = [r["step"] for r in recs]
        assert steps == sorted(steps)
        assert steps == [6, 7, 8, 9]  # the LAST N, not the first
        assert all(r["wall_ms"] >= 0 for r in recs)

    def test_explicit_step_ids_thread_through(self):
        hub = _fresh_hub(capacity=8)
        hub.step_begin(100)
        hub.step_end()
        # auto ids continue monotonically after an explicit id
        hub.step_begin()
        rec = hub.step_end()
        assert rec["step"] == 101

    def test_begin_closes_open_record(self):
        """A loop that misses one step_end degrades to tick semantics
        instead of wedging the hub."""
        hub = _fresh_hub(capacity=8)
        hub.step_begin(0)
        hub.step_begin(1)  # implicitly closes step 0
        hub.step_end()
        assert [r["step"] for r in hub.records()] == [0, 1]

    def test_percentiles(self):
        hub = _fresh_hub(capacity=16)
        for _ in range(5):
            hub.step_begin()
            hub.step_end()
        pct = hub.percentiles()
        assert pct["count"] == 5
        assert 0 <= pct["p50"] <= pct["p95"]

    def test_records_capture_fusion_deltas(self, hvd):
        """The StepStats record carries what THIS step did on the wire
        (snapshot deltas of the fusion counters), not running totals."""
        import horovod_tpu as hvd_mod

        hub = _fresh_hub(capacity=8)
        x = np.stack([np.full((16,), float(r), np.float32) for r in range(8)])
        # one warmup dispatch so cumulative counters are nonzero before
        # the recorded step — a totals-vs-delta confusion would show
        hvd_mod.allreduce(x, op=hvd_mod.Sum, name="warm")
        hub.step_begin()
        hvd_mod.allreduce(x, op=hvd_mod.Sum, name="stepped")
        rec = hub.step_end()
        assert rec["fusion_dispatches"] == 1.0
        assert rec["fusion_cycles"] == 1.0
        assert rec["wire_bytes"] == x.nbytes  # the rank-major payload
        hub.step_begin()
        rec2 = hub.step_end()  # idle step: no wire movement
        assert rec2["fusion_dispatches"] == 0.0
        assert rec2["wire_bytes"] == 0.0

    def test_tick_stands_down_for_explicit_steps(self):
        hub = _fresh_hub(capacity=8)
        hub.step_begin(0)
        hub.step_end()
        hub.tick(99)  # explicit instrumentation closed a record: no-op
        assert [r["step"] for r in hub.records()] == [0]
        # with no other source, ticks record tick-to-tick steps
        hub.tick(10)
        hub.tick(11)
        hub.tick(12)
        steps = [r["step"] for r in hub.records()]
        assert steps == [0, 10, 11]

    def test_duplicate_ticks_after_close_are_noops(self):
        """Per-shard duplicate ticks can drain AFTER step_end closed
        the manual record — they must not insert bogus near-zero
        records (would drag p50 toward zero and corrupt the straggler
        ledger)."""
        hub = _fresh_hub(capacity=16)
        for step in range(3):
            hub.step_begin(step)
            hub.step_end()
            for _ in range(8):  # 8 shard callbacks of the same step
                hub.tick(step)
        steps = [r["step"] for r in hub.records()]
        assert steps == [0, 1, 2]

    def test_tape_tick_source_outranks_optimizer(self):
        """When value_and_grad (threaded hvd_step, source 'tape') and
        DistributedOptimizer (internal counter, source 'opt') both
        tick in one program with diverging ids, only one source may
        drive the recorder — otherwise every step splits into two
        fragment records."""
        hub = _fresh_hub(capacity=16)
        for i in range(4):
            hub.tick(1000 + i, source="tape")  # resumed global step
            hub.tick(i, source="opt")  # fresh optimizer counter
        steps = [r["step"] for r in hub.records()]
        assert steps == [1000, 1001, 1002]  # one record/step, tape ids
        # optimizer-only jobs still adopt "opt"
        hub2 = _fresh_hub(capacity=8)
        hub2.tick(0)
        hub2.tick(1)
        assert [r["step"] for r in hub2.records()] == [0]

    def test_device_step_tick_propagates_stall_escalation(self):
        """The stall inspector's shutdown escalation must not be
        swallowed by the tick's defensive except — it exists to kill a
        wedged job."""
        from horovod_tpu.common import telemetry
        from horovod_tpu.common.basics import HorovodInternalError

        telemetry._reset_hub()
        try:
            hub = telemetry.hub()

            class _Insp:
                def check(self):
                    raise HorovodInternalError("stalled")

            hub.stall_inspector = _Insp()
            hub.tick(0)  # opens
            with pytest.raises(HorovodInternalError):
                telemetry.device_step_tick(1)  # closes 0 -> check fires
        finally:
            telemetry._reset_hub()


# ---------------------------------------------------- flight recorder


class TestFlightRecorder:
    def test_dump_roundtrip(self, tmp_path):
        hub = _fresh_hub(capacity=8)
        for _ in range(3):
            hub.step_begin()
            hub.step_end()
        path = str(tmp_path / "flight.jsonl")
        assert hub.dump(path) == path
        recs = [json.loads(line) for line in open(path)]
        assert len(recs) == 3
        for rec in recs:
            assert {"step", "ts", "wall_ms", "exposed_collective_ms",
                    "hidden_collective_ms", "wire_bytes",
                    "wire_format"} <= set(rec)

    def test_dump_without_path_is_noop(self):
        hub = _fresh_hub(capacity=4)
        hub.step_begin()
        hub.step_end()
        assert hub.dump() is None

    def test_dump_is_signal_safe_under_held_lock(self, tmp_path):
        """The SIGTERM dump runs in a signal handler on the main
        thread; if the signal landed while that thread held the hub
        lock, a blocking acquire would deadlock the handler and eat
        the whole preemption grace window. dump() must complete
        anyway (bounded acquire + lock-free ring copy)."""
        hub = _fresh_hub(capacity=4)
        hub.step_begin()
        hub.step_end()
        path = str(tmp_path / "f.jsonl")
        hub._lock.acquire()  # simulate the interrupted critical section
        try:
            t0 = time.monotonic()
            assert hub.dump(path) == path
            assert time.monotonic() - t0 < 5.0
        finally:
            hub._lock.release()
        assert len([json.loads(l) for l in open(path)]) == 1

    def test_sigterm_dumps_ring(self, tmp_path):
        """Kill a worker mid-loop: the flight-recorder file must exist,
        parse, hold <= ring-size records with monotonically increasing
        step ids, and carry the collective/wire fields."""
        flight = str(tmp_path / "flight.jsonl")
        script = tmp_path / "worker.py"
        script.write_text(
            textwrap.dedent(
                f"""
                import os, sys, time
                os.environ["JAX_PLATFORMS"] = "cpu"
                os.environ["HOROVOD_FLIGHT_RECORDER"] = {flight!r}
                os.environ["HOROVOD_TELEMETRY_STEPS"] = "8"
                import jax
                jax.config.update("jax_platforms", "cpu")
                import horovod_tpu as hvd

                # the first step brings the hub and its SIGTERM hook up:
                # READY only after it, or a loaded machine kills a
                # worker that has no handler yet (rc -15)
                hvd.step_begin()
                hvd.step_end()
                print("READY", flush=True)
                while True:
                    hvd.step_begin()
                    time.sleep(0.01)
                    hvd.step_end()
                """
            )
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("HOROVOD_FLIGHT_RECORDER", None)
        errfile = tmp_path / "worker.err"
        with open(errfile, "w") as errf:
            proc = subprocess.Popen(
                [sys.executable, str(script)],
                env=env, stdout=subprocess.PIPE, stderr=errf, text=True,
            )
            try:
                line = proc.stdout.readline()
                assert "READY" in line, (
                    f"first line {line!r}:\n{errfile.read_text()[-2000:]}"
                )
                time.sleep(1.0)  # let > ring-size steps elapse
                proc.send_signal(signal.SIGTERM)
                rc = proc.wait(timeout=60)
                assert rc == 143, (
                    f"rc={rc}:\n{errfile.read_text()[-2000:]}"
                )
            finally:
                if proc.poll() is None:
                    proc.kill()
        assert os.path.exists(flight), errfile.read_text()[-2000:]
        recs = [json.loads(line) for line in open(flight)]
        assert 0 < len(recs) <= 8
        steps = [r["step"] for r in recs]
        assert steps == sorted(steps)
        assert len(set(steps)) == len(steps)  # strictly increasing
        for rec in recs:
            assert "exposed_collective_ms" in rec
            assert "hidden_collective_ms" in rec
            assert "wire_bytes" in rec

    def test_graceful_shutdown_dumps_ring(self, tmp_path):
        """preemption.GracefulShutdown's drain path persists the ring
        before os._exit — checked via its _drain_and_exit internals
        with exit intercepted."""
        from horovod_tpu.common import telemetry

        flight = str(tmp_path / "flight.jsonl")
        hub = telemetry.hub()
        hub.configure(flight_path=flight)
        try:
            hub.step_begin()
            hub.step_end()

            class _State:
                committed = False

                def persist(self):
                    self.committed = True

                def wait_until_finished(self):
                    pass

            from horovod_tpu.preemption import GracefulShutdown

            gs = GracefulShutdown(_State())
            exits = []
            real_exit = os._exit
            os._exit = lambda code: exits.append(code)
            try:
                gs._drain_and_exit()
            finally:
                os._exit = real_exit
            assert exits == [143]
            assert os.path.exists(flight)
            assert [json.loads(l) for l in open(flight)]
        finally:
            hub.flight_path = None


# ------------------------------------------------------ scrape endpoint


def _minimal_prom_parse(text):
    """Minimal Prometheus text parser: returns ({name: value}, typed
    names). Raises on NaN samples or malformed lines."""
    samples, types = {}, set()
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            types.add(line.split()[2])
            continue
        if line.startswith("#"):
            continue
        name_part, _, value = line.rpartition(" ")
        base = name_part.split("{", 1)[0]
        val = float(value)
        assert val == val, f"NaN sample: {line}"
        samples[name_part] = val
        samples.setdefault(base, val)
    return samples, types


class TestScrapeEndpoint:
    def _server(self, hub):
        from horovod_tpu.common.telemetry import MetricsServer

        return MetricsServer(port=0, hub_instance=hub)

    def test_metrics_prometheus_roundtrip(self, hvd):
        from horovod_tpu.common.metrics import registry

        hub = _fresh_hub(capacity=8)
        for _ in range(4):
            hub.step_begin()
            hub.step_end()
        registry.gauge("smoke.answer", 42.0)
        server = self._server(hub)
        port = server.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as resp:
                ctype = resp.headers.get("Content-Type", "")
                text = resp.read().decode()
        finally:
            server.stop()
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        samples, types = _minimal_prom_parse(text)
        # step summary present with both quantiles
        assert samples['telemetry_step_ms{quantile="0.5"}'] >= 0
        assert samples['telemetry_step_ms{quantile="0.95"}'] >= 0
        assert samples["telemetry_step_ms_count"] == 4
        assert "telemetry_step_ms" in types
        # registry gauges with HELP/TYPE lines
        assert samples["hvd_smoke_answer"] == 42.0
        assert "hvd_smoke_answer" in types
        assert "# HELP hvd_smoke_answer" in text

    def test_nan_gauges_are_dropped(self):
        from horovod_tpu.common.telemetry import render_prometheus

        text = render_prometheus({"bad.gauge": float("nan"),
                                  "good.gauge": 1.0}, {})
        assert "NaN" not in text and "nan" not in text
        assert "hvd_good_gauge 1" in text
        assert "hvd_bad_gauge" not in text

    def test_telemetry_json_and_healthz(self):
        hub = _fresh_hub(capacity=8)
        hub.step_begin(7)
        hub.step_end()
        server = self._server(hub)
        port = server.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/telemetry", timeout=10
            ) as resp:
                tele = json.load(resp)
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            ) as resp:
                assert resp.read() == b"ok\n"
            code = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ).status
            assert code == 200
        finally:
            server.stop()
        assert tele["ring_capacity"] == 8
        assert [r["step"] for r in tele["steps"]] == [7]
        assert "percentiles" in tele and "metrics" in tele

    def test_env_port_starts_server_at_init(self, monkeypatch):
        """HOROVOD_METRICS_PORT wires the endpoint into hvd.init()."""
        import socket

        import horovod_tpu as hvd_mod
        from horovod_tpu.common import basics

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        monkeypatch.setenv("HOROVOD_METRICS_PORT", str(port))
        hvd_mod.shutdown()
        hvd_mod.init()
        try:
            assert basics.state().telemetry_server is not None
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as resp:
                assert resp.status == 200
        finally:
            hvd_mod.shutdown()


# --------------------------------------------------- auto-threading


class TestAutoThreading:
    def test_value_and_grad_opens_steps(self, hvd, monkeypatch):
        """Host-level (non-traced) tape calls open/close an auto record
        per step. The allreduce is stubbed out: eagerly there is no
        axis context, and the hook under test is pure host plumbing."""
        import jax.numpy as jnp

        import horovod_tpu as hvd_mod
        from horovod_tpu import optimizer as opt_mod
        from horovod_tpu.common import telemetry

        monkeypatch.setenv("HOROVOD_TELEMETRY", "1")
        monkeypatch.setattr(
            opt_mod, "_allreduce_grads", lambda grads, *a, **k: grads
        )
        telemetry._reset_hub()
        try:
            hub = telemetry.hub()
            assert hub.enabled
            vg = hvd_mod.value_and_grad(lambda w: jnp.sum(w * w))
            before = len(hub)
            for _ in range(3):
                vg(jnp.ones((4,)))
            assert len(hub) == before + 3
            steps = [r["step"] for r in hub.records()]
            assert steps == sorted(steps)
        finally:
            telemetry._reset_hub()

    def test_value_and_grad_ticks_under_jit_with_step(self, hvd,
                                                      monkeypatch):
        """The real usage shape — vg inside jit/shard_map with a
        threaded hvd_step — ticks the flight recorder per executed
        step with the caller's step ids."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        import horovod_tpu as hvd_mod
        from horovod_tpu.common import telemetry

        monkeypatch.setenv("HOROVOD_TELEMETRY", "1")
        telemetry._reset_hub()
        try:
            hub = telemetry.hub()
            vg = hvd_mod.value_and_grad(lambda w, x: jnp.sum(w * x))
            mesh = hvd_mod.mesh()

            @jax.jit
            @jax.shard_map(
                mesh=mesh, in_specs=(P(), P(hvd_mod.WORLD_AXIS), P()),
                out_specs=(P(), P()), check_vma=False,
            )
            def step(w, x, s):
                return vg(w, x[0], hvd_step=s)

            w = jnp.ones(3)
            x = np.stack([np.full((3,), float(r), np.float32)
                          for r in range(8)])
            for i in range(4):
                out = step(w, x, jnp.asarray(i, jnp.int32))
            jax.block_until_ready(out)
            # the last tick's record is still open → >= 3 closed, with
            # the threaded ids (per-shard duplicates deduped)
            assert len(hub) >= 3
            steps = [r["step"] for r in hub.records()]
            assert steps == sorted(steps)
            assert set(steps) <= {0, 1, 2, 3}
            assert len(set(steps)) == len(steps)
        finally:
            telemetry._reset_hub()

    def test_auto_hooks_off_by_default(self, hvd, monkeypatch):
        import jax.numpy as jnp

        import horovod_tpu as hvd_mod
        from horovod_tpu import optimizer as opt_mod
        from horovod_tpu.common import telemetry

        monkeypatch.setattr(
            opt_mod, "_allreduce_grads", lambda grads, *a, **k: grads
        )
        telemetry._reset_hub()
        try:
            assert not telemetry.auto_enabled()
            hub = telemetry.hub()
            vg = hvd_mod.value_and_grad(lambda w: jnp.sum(w * w))
            vg(jnp.ones((4,)))
            assert len(hub) == 0
        finally:
            telemetry._reset_hub()

    def test_distributed_optimizer_ticks_under_jit(self, hvd, monkeypatch):
        """The debug-callback tick: a FULLY jitted update loop still
        produces flight-recorder records."""
        import jax
        import jax.numpy as jnp
        import optax

        import horovod_tpu as hvd_mod
        from horovod_tpu.common import telemetry
        from horovod_tpu.common.topology import WORLD_AXIS
        from jax.sharding import PartitionSpec as P

        monkeypatch.setenv("HOROVOD_TELEMETRY", "1")
        telemetry._reset_hub()
        try:
            hub = telemetry.hub()
            opt = hvd_mod.DistributedOptimizer(optax.sgd(0.1))
            mesh = hvd_mod.mesh()

            params = jnp.ones((8, 4))

            @jax.jit
            @jax.shard_map(
                mesh=mesh, in_specs=(P(WORLD_AXIS), P(WORLD_AXIS), P()),
                out_specs=(P(WORLD_AXIS), P()), check_vma=False,
            )
            def step(p, g, s):
                updates, s = opt.update(g, s, p)
                return optax.apply_updates(p, updates), s

            state = opt.init(params[:1])
            for _ in range(4):
                params, state = step(params, params, state)
            jax.block_until_ready(params)
            # one tick per executed update (per-shard duplicates are
            # deduped by step id); the last tick's record is still
            # open, so >= 3 closed records with distinct ordered ids
            assert len(hub) >= 3
            steps = [r["step"] for r in hub.records()]
            assert steps == sorted(steps)
            assert len(set(steps)) == len(steps)
        finally:
            telemetry._reset_hub()


# ------------------------------------------- metrics delta-aware dump


class TestMetricsDeltaDump:
    def test_delta_dump_and_seq(self, tmp_path):
        from horovod_tpu.common.metrics import MetricsRegistry

        reg = MetricsRegistry()
        path = str(tmp_path / "m.jsonl")
        reg.configure_export(path)
        reg.gauge("a", 1.0)
        reg.gauge("b", 2.0)
        reg.dump()
        lines = [json.loads(l) for l in open(path)]
        assert {l["name"] for l in lines} == {"a", "b"}  # first: full
        # unchanged: nothing appended
        reg.dump()
        assert len([json.loads(l) for l in open(path)]) == 2
        # one change: one line
        reg.gauge("b", 3.0)
        reg.dump()
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 3
        assert lines[-1]["name"] == "b" and lines[-1]["value"] == 3.0
        # force: full snapshot again
        reg.dump(force=True)
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 5
        # seq strictly monotonic across every line
        seqs = [l["seq"] for l in lines]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)

    def test_explicit_path_gets_full_snapshot(self, tmp_path):
        from horovod_tpu.common.metrics import MetricsRegistry

        reg = MetricsRegistry()
        sink = str(tmp_path / "sink.jsonl")
        reg.configure_export(sink)
        reg.gauge("a", 1.0)
        reg.dump()
        other = str(tmp_path / "other.jsonl")
        # a different explicit path: full snapshot, sink state untouched
        reg.dump(other)
        assert len(open(other).readlines()) == 1
        reg.gauge("a", 2.0)
        reg.dump()
        lines = [json.loads(l) for l in open(sink)]
        assert [l["value"] for l in lines if l["name"] == "a"] == [1.0, 2.0]

    def test_reset_rebaselines(self, tmp_path):
        from horovod_tpu.common.metrics import MetricsRegistry

        reg = MetricsRegistry()
        path = str(tmp_path / "m.jsonl")
        reg.configure_export(path)
        reg.gauge("a", 1.0)
        reg.dump()
        reg.reset()
        reg.gauge("a", 1.0)
        reg.dump()  # after reset the sink re-baselines: full write
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 2


# ------------------------------------------------ stall + stragglers


class TestStallMetricsAndStragglers:
    def test_check_publishes_gauges(self):
        from horovod_tpu.common.metrics import registry
        from horovod_tpu.common.stall_inspector import StallInspector

        insp = StallInspector(warning_seconds=3600.0)
        insp.record_enqueue("t1")
        insp.record_enqueue("t2")
        insp.record_heartbeat(0, time.time() - 7200.0)
        insp.record_heartbeat(1, time.time())
        insp.warning_seconds = 60.0
        insp.check()
        snap = registry.snapshot()
        assert snap["stall.pending"] == 2.0
        assert snap["stall.stale_ranks"] == 1.0
        assert "stall.straggler.count" in snap

    def test_traced_dispatch_runs_stall_check(self, hvd, monkeypatch):
        """Satellite: the stall inspector fires from the traced
        collective dispatch path, not only eager fusion cycles."""
        import jax
        from jax.sharding import PartitionSpec as P

        import horovod_tpu as hvd_mod
        from horovod_tpu.common import basics
        from horovod_tpu.ops import traced

        calls = []
        insp = basics.state().stall_inspector
        assert insp is not None
        monkeypatch.setattr(insp, "check", lambda: calls.append(1))
        monkeypatch.setattr(traced, "_last_stall_check", [0.0])
        mesh = hvd_mod.mesh()

        @jax.jit
        @jax.shard_map(
            mesh=mesh, in_specs=P(hvd_mod.WORLD_AXIS), out_specs=P(),
            check_vma=False,
        )
        def step(x):
            return traced.allreduce(x[0], op=hvd_mod.Sum)

        import jax.numpy as jnp

        step(jnp.ones((8, 4)))
        assert calls  # checked at trace/dispatch time

    def test_straggler_by_p50_multiple(self):
        from horovod_tpu.common.stall_inspector import StallInspector

        insp = StallInspector(straggler_factor=3.0)
        now = time.time()
        for r, p50 in enumerate([10.0, 11.0, 9.0, 100.0]):
            insp.record_heartbeat(r, now, step=50, step_ms_p50=p50)
        assert insp.straggler_ranks() == [3]
        # configurable multiple: at factor 15 nobody is flagged
        assert insp.straggler_ranks(factor=15.0) == []

    def test_straggler_by_step_lag(self):
        from horovod_tpu.common.stall_inspector import StallInspector

        insp = StallInspector()
        now = time.time()
        for r, step in enumerate([100, 101, 99, 2]):
            insp.record_heartbeat(r, now, step=step, step_ms_p50=10.0)
        assert insp.straggler_ranks() == [3]
        assert insp.straggler_ranks(lag_steps=1000) == []

    def test_straggler_needs_a_gang(self):
        from horovod_tpu.common.stall_inspector import StallInspector

        insp = StallInspector()
        insp.record_heartbeat(0, step=5, step_ms_p50=1000.0)
        assert insp.straggler_ranks() == []  # a median of one is itself

    def test_reset_heartbeats_clears_ledger(self):
        from horovod_tpu.common.stall_inspector import StallInspector

        insp = StallInspector()
        insp.record_heartbeat(0, step=5, step_ms_p50=10.0)
        insp.record_heartbeat(1, step=5, step_ms_p50=99.0)
        insp.reset_heartbeats()
        assert insp.straggler_ranks() == []
        assert insp.heartbeat_stats() == {}

    def test_heartbeat_payload_roundtrip(self):
        """Worker stats ride the KV heartbeat; legacy bare-float
        payloads still parse."""
        from horovod_tpu.runner.rendezvous import (
            HEARTBEAT_SCOPE,
            KVStore,
            put_heartbeat,
            read_heartbeat_stats,
            read_heartbeats,
        )

        class _Client:
            def __init__(self, store):
                self.store = store

            def put(self, scope, key, value):
                self.store.put(scope, key, value)

        store = KVStore()
        put_heartbeat(
            _Client(store), 0,
            stats={"step": 17, "step_ms_p50": 12.5, "last_step_ts": 1.0},
        )
        store.put(HEARTBEAT_SCOPE, "1", repr(time.time()).encode())  # legacy
        stats = read_heartbeat_stats(store)
        assert stats[0]["step"] == 17
        assert stats[0]["step_ms_p50"] == 12.5
        assert set(read_heartbeats(store)) == {0, 1}

    def test_multiprocess_straggler_flagged(self, tmp_path):
        """Acceptance: an injected slow rank is flagged through the
        REAL channel — subprocess workers PUT heartbeats over HTTP into
        the driver's rendezvous KV; the elastic driver's poll feeds the
        inspector, which flags the slow rank."""
        from horovod_tpu.elastic.driver import ElasticDriver
        from horovod_tpu.elastic.discovery import HostDiscovery
        from horovod_tpu.runner.hosts import HostInfo
        from horovod_tpu.runner.rendezvous import RendezvousServer

        class _Disc(HostDiscovery):
            def find_available_hosts_and_slots(self):
                return [HostInfo("localhost", 2)]

        server = RendezvousServer(secret_key=None, backend="python")
        port = server.start()
        try:
            worker = tmp_path / "beat.py"
            # stdlib-only worker: no horovod import, so the test stays
            # fast while the payload still crosses a process + socket
            worker.write_text(
                textwrap.dedent(
                    """
                    import json, sys, time, urllib.request
                    port, rank, p50 = sys.argv[1:4]
                    payload = json.dumps({
                        "ts": time.time(), "step": int(sys.argv[4]),
                        "step_ms_p50": float(p50),
                        "last_step_ts": time.time(),
                    }).encode()
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{port}/kv/heartbeat/{rank}",
                        data=payload, method="PUT",
                    )
                    urllib.request.urlopen(req, timeout=10)
                    """
                )
            )
            procs = [
                subprocess.run(
                    [sys.executable, str(worker), str(port), str(rank),
                     str(p50), "40"],
                    capture_output=True, text=True, timeout=60,
                )
                for rank, p50 in [(0, 10.0), (1, 12.0), (2, 95.0)]
            ]
            for p in procs:
                assert p.returncode == 0, p.stderr
            driver = ElasticDriver(_Disc(), ["true"], min_np=1)
            driver._server = server
            driver._last_hb_poll = -1e9
            # no restart reason: stragglers are flagged but one poll is
            # under the quarantine hysteresis (K consecutive polls)
            assert driver._poll_heartbeats(time.monotonic()) is None
            assert driver.stall_inspector.straggler_ranks() == [2]
            stats = driver.stall_inspector.heartbeat_stats()
            assert stats[2]["step_ms_p50"] == 95.0
            from horovod_tpu.common.metrics import registry

            snap = registry.snapshot()
            assert snap["stall.straggler.count"] == 1.0
            assert snap["stall.straggler.worst_ratio"] > 3.0
        finally:
            server.stop()


# --------------------------------------------------- timeline satellite


class TestTimelineRaceAndStepTrack:
    def test_stop_during_emit_loses_nothing(self, tmp_path):
        """Concurrent counter() spam while stop() flushes: every event
        that made it into memory is in the file stop() wrote — the
        final _write can no longer miss a racing emit."""
        from horovod_tpu.common.timeline import Timeline

        path = str(tmp_path / "tl.json")
        tl = Timeline(path)
        stop_evt = threading.Event()
        emitted = []

        def spam():
            i = 0
            while not stop_evt.is_set():
                tl.counter("race.counter", i)
                i += 1
            emitted.append(i)

        threads = [threading.Thread(target=spam) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        tl.stop()
        stop_evt.set()
        for t in threads:
            t.join()
        with open(path) as f:
            on_disk = [
                e for e in json.load(f)["traceEvents"]
                if e.get("name") == "race.counter"
            ]
        in_memory = [
            e for e in tl._events if e.get("name") == "race.counter"
        ]
        # the invariant under test: memory holds nothing the file lacks
        assert len(in_memory) == len(on_disk)

    def test_emit_after_stop_dropped(self, tmp_path):
        from horovod_tpu.common.timeline import Timeline

        path = str(tmp_path / "tl.json")
        tl = Timeline(path)
        tl.counter("c", 1)
        tl.stop()
        tl.counter("c", 2)  # dropped, not deferred
        tl.span("t", "X", 0.0, 1.0)
        tl.close()
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        assert len([e for e in events if e.get("name") == "c"]) == 1

    def test_step_end_emits_telemetry_step_counter(self, tmp_path):
        """Traces align with StepStats: each step boundary lands a
        telemetry.step counter event on the eager timeline."""
        from horovod_tpu.common.timeline import Timeline

        hub = _fresh_hub(capacity=8)
        path = str(tmp_path / "tl.json")
        tl = Timeline(path)
        hub.timeline = tl
        hub.step_begin(3)
        hub.step_end()
        tl.close()
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        track = [e for e in events if e.get("name") == "telemetry.step"]
        assert track and track[0]["ph"] == "C"
        assert track[0]["args"]["telemetry.step"] == 3

    def test_runtime_start_timeline_attaches_hub(self, hvd, tmp_path,
                                                 monkeypatch):
        """hvd.start_timeline() AFTER init must wire the new timeline
        into the telemetry hub, so step boundaries land on the trace
        (found by driving the runtime-activation path)."""
        import horovod_tpu as hvd_mod
        from horovod_tpu.common import telemetry

        monkeypatch.setenv("HOROVOD_TELEMETRY", "1")
        path = str(tmp_path / "tl.json")
        hvd_mod.start_timeline(path)
        hub = telemetry.hub()
        try:
            hub.step_begin(5)
            hub.step_end()
            hvd_mod.stop_timeline()
            with open(path) as f:
                events = json.load(f)["traceEvents"]
            track = [e for e in events
                     if e.get("name") == "telemetry.step"]
            assert track and track[0]["args"]["telemetry.step"] == 5
        finally:
            hub.timeline = None

    def test_step_end_runs_stall_check(self):
        from horovod_tpu.common.stall_inspector import StallInspector

        hub = _fresh_hub(capacity=4)
        insp = StallInspector()
        calls = []
        insp.check = lambda: calls.append(1)
        hub.stall_inspector = insp
        hub.step_begin()
        hub.step_end()
        assert calls == [1]
