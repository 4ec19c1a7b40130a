"""Chaos matrix: every injection site x fault kind must end in one of
the specified outcomes — absorbed by the unified RetryPolicy, degraded
as designed (gang restart / checkpoint fallback / fail-fast circuit),
or fatal on purpose. The reference proves its elastic story by killing
PIDs and flipping discovery files (SURVEY.md §4.3); this suite drives
the same faults through the seeded FaultPlan so CI reproduces them
bit-for-bit."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from horovod_tpu.common.metrics import registry
from horovod_tpu.common.retry import (
    CircuitOpenError,
    RetryError,
    RetryPolicy,
    _reset_breakers,
    backoff_delays,
)
from horovod_tpu.testing import chaos


@pytest.fixture(autouse=True)
def _clean_chaos(monkeypatch):
    """Every test starts with no plan and closed circuits."""
    monkeypatch.delenv("HOROVOD_FAULT_PLAN", raising=False)
    chaos.reset()
    _reset_breakers()
    yield
    chaos.reset()
    _reset_breakers()


def _fast_policy(site, **kw):
    kw.setdefault("attempts", 3)
    kw.setdefault("backoff_ms", 1.0)
    kw.setdefault("backoff_max_ms", 5.0)
    kw.setdefault("deadline_s", 10.0)
    kw.setdefault("circuit_threshold", 2)
    kw.setdefault("circuit_cooldown_s", 0.2)
    return RetryPolicy(site, **kw)


def _delta(name, before):
    return registry.snapshot().get(name, 0.0) - before.get(name, 0.0)


# ---------------------------------------------------------------- FaultPlan


class TestFaultPlan:
    def test_parse_full_syntax(self):
        p = chaos.FaultPlan.parse(
            "seed=9;kv.request@2:reset;heartbeat:p=0.25:delay:ms=50;"
            "svc:5xx:n=3;train.step@4:kill"
        )
        assert p.seed == 9
        kinds = {(r.site, r.kind) for r in p.rules}
        assert kinds == {
            ("kv.request", "reset"), ("heartbeat", "delay"),
            ("svc", "5xx"), ("train.step", "kill"),
        }
        by_site = {r.site: r for r in p.rules}
        assert by_site["kv.request"].at == 2
        assert by_site["kv.request"].remaining == 1  # @N defaults 1-shot
        assert by_site["heartbeat"].p == 0.25
        assert by_site["heartbeat"].ms == 50.0
        assert by_site["heartbeat"].remaining == -1  # unlimited
        assert by_site["svc"].remaining == 3

    def test_parse_rejects_unknown_token_and_kind(self):
        with pytest.raises(ValueError):
            chaos.FaultPlan.parse("kv.request:bogus")
        with pytest.raises(ValueError):
            chaos.FaultPlan.parse("kv.request@1:p=0.5")  # @ and p exclusive

    def test_at_rule_fires_exactly_once_on_the_nth_hit(self):
        plan = chaos.configure("seed=1;site.a@3:reset")
        chaos.inject("site.a")
        chaos.inject("site.a")
        with pytest.raises(ConnectionResetError):
            chaos.inject("site.a")
        for _ in range(5):
            chaos.inject("site.a")  # one-shot: never again
        assert plan.fired() == [{"site": "site.a", "kind": "reset", "hit": 3}]
        assert plan.hits("site.a") == 8

    def test_probability_rules_are_deterministic_per_seed(self):
        def pattern(seed):
            plan = chaos.FaultPlan(
                [chaos.FaultRule("s", kind="timeout", p=0.5, n=1000)],
                seed=seed,
            )
            fired = []
            for i in range(40):
                try:
                    plan.fire("s")
                    fired.append(0)
                except TimeoutError:
                    fired.append(1)
            return fired

        a, b, c = pattern(7), pattern(7), pattern(8)
        assert a == b                      # same seed -> same schedule
        assert a != c                      # seed actually matters
        assert 5 < sum(a) < 35             # p=0.5 is roughly half

    def test_unrelated_site_interleaving_does_not_perturb_schedule(self):
        """Per-site RNG streams: site B's hits cannot shift site A's
        draws — the property that makes multi-site plans reproducible."""
        def run(interleave):
            plan = chaos.FaultPlan(
                [chaos.FaultRule("a", kind="5xx", p=0.5, n=1000)], seed=3
            )
            out = []
            for i in range(20):
                if interleave:
                    plan.fire("b")
                try:
                    plan.fire("a")
                    out.append(0)
                except chaos.InjectedServerError:
                    out.append(1)
            return out

        assert run(False) == run(True)

    def test_env_loading_and_file_indirection(self, monkeypatch, tmp_path):
        monkeypatch.setenv("HOROVOD_FAULT_PLAN", "seed=5;x@1:timeout")
        chaos.reset()
        plan = chaos.active()
        assert plan is not None and plan.seed == 5
        spec_file = tmp_path / "plan.txt"
        spec_file.write_text("seed=6;y@1:reset\n")
        monkeypatch.setenv("HOROVOD_FAULT_PLAN", f"@{spec_file}")
        chaos.reset()
        plan = chaos.active()
        assert plan.seed == 6 and plan.rules[0].site == "y"

    def test_delay_kind_sleeps(self):
        chaos.configure("d@1:delay:ms=120")
        t0 = time.monotonic()
        chaos.inject("d")
        assert time.monotonic() - t0 >= 0.1

    def test_injection_counters(self):
        before = registry.snapshot()
        chaos.configure("c@1:5xx")
        with pytest.raises(chaos.InjectedServerError):
            chaos.inject("c")
        assert _delta("faults_injected", before) == 1
        assert _delta("chaos.c.5xx", before) == 1

    def test_no_plan_inject_is_noop(self):
        for _ in range(3):
            chaos.inject("anything")  # must not raise


# -------------------------------------------------------------- RetryPolicy


class TestRetryPolicy:
    def test_absorbs_transient_failures(self):
        pol = _fast_policy("t.ok")
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise ConnectionResetError("flake")
            return "done"

        before = registry.snapshot()
        assert pol.call(flaky) == "done"
        assert calls["n"] == 3
        assert _delta("retry.t.ok.attempts", before) == 3
        assert _delta("retry.t.ok.retries", before) == 2
        assert _delta("retry.retries_total", before) == 2
        assert _delta("retry.t.ok.exhausted", before) == 0

    def test_non_retryable_raises_immediately(self):
        pol = _fast_policy("t.perm")
        calls = {"n": 0}

        def denied():
            calls["n"] += 1
            raise PermissionError("bad HMAC")

        with pytest.raises(PermissionError):
            pol.call(denied)
        assert calls["n"] == 1

    def test_exhaustion_raises_retry_error_with_cause(self):
        pol = _fast_policy("t.dead")
        before = registry.snapshot()
        with pytest.raises(RetryError) as ei:
            pol.call(lambda: (_ for _ in ()).throw(TimeoutError("slow")))
        assert isinstance(ei.value.__cause__, TimeoutError)
        assert isinstance(ei.value, ConnectionError)  # existing handlers
        assert _delta("retry.t.dead.exhausted", before) == 1

    def test_deadline_stops_the_ladder_early(self):
        pol = _fast_policy(
            "t.deadline", attempts=10, backoff_ms=500.0,
            backoff_max_ms=500.0, deadline_s=0.2,
        )
        calls = {"n": 0}

        def failing():
            calls["n"] += 1
            raise ConnectionResetError("x")

        t0 = time.monotonic()
        with pytest.raises(RetryError) as ei:
            pol.call(failing)
        assert time.monotonic() - t0 < 1.0
        assert calls["n"] < 10  # nowhere near the attempt budget
        # the error reports the attempts that RAN, not the budget
        assert ei.value.attempts == calls["n"]

    def test_circuit_opens_then_half_opens(self):
        pol = _fast_policy("t.circuit")

        def dead():
            raise ConnectionRefusedError("down")

        before = registry.snapshot()
        for _ in range(2):  # threshold=2 consecutive exhausted rounds
            with pytest.raises(RetryError):
                pol.call(dead, peer="host:1")
        assert pol.circuit_state("host:1") == "open"
        t0 = time.monotonic()
        with pytest.raises(CircuitOpenError):
            pol.call(dead, peer="host:1")
        # fail-fast: no attempts, no backoff sleeps
        assert time.monotonic() - t0 < 0.05
        assert _delta("retry.t.circuit.circuit_open", before) == 1
        time.sleep(0.25)  # cooldown=0.2 -> half-open probe allowed
        with pytest.raises(RetryError):
            pol.call(dead, peer="host:1")  # probe ran (and failed)
        # recovery: a successful probe closes the circuit
        time.sleep(0.25)
        assert pol.call(lambda: "up", peer="host:1") == "up"
        assert pol.circuit_state("host:1") == "closed"

    def test_non_retryable_failures_do_not_move_the_breaker(self):
        """An auth/4xx failure is a protocol problem, not peer death:
        however many land, the circuit stays closed."""
        pol = _fast_policy("t.auth")
        for _ in range(5):
            with pytest.raises(PermissionError):
                pol.call(
                    lambda: (_ for _ in ()).throw(PermissionError("hmac")),
                    peer="p:1",
                )
        assert pol.circuit_state("p:1") == "closed"
        assert pol.call(lambda: 1, peer="p:1") == 1

    def test_breaker_is_per_peer(self):
        pol = _fast_policy("t.peers")
        for _ in range(2):
            with pytest.raises(RetryError):
                pol.call(
                    lambda: (_ for _ in ()).throw(ConnectionResetError()),
                    peer="dead:1",
                )
        assert pol.circuit_state("dead:1") == "open"
        assert pol.call(lambda: 1, peer="alive:2") == 1

    def test_backoff_delays_shape(self):
        delays = backoff_delays(0.1, 1.0, jitter=0.25)
        seq = [next(delays) for _ in range(8)]
        assert 0.075 <= seq[0] <= 0.125      # jitter window of initial
        assert all(d <= 1.25 for d in seq)   # cap (+jitter) respected
        assert seq[3] > seq[0]               # it actually grows
        nojit = backoff_delays(0.05, 1.0, jitter=0.0)
        assert [round(next(nojit), 4) for _ in range(6)] == [
            0.05, 0.1, 0.2, 0.4, 0.8, 1.0
        ]


# ------------------------------------------------------ rendezvous KV chaos


@pytest.fixture
def kv(monkeypatch):
    """Python-backend rendezvous server + a fast-retry client."""
    from horovod_tpu.runner.rendezvous import (
        RendezvousClient,
        RendezvousServer,
    )
    from horovod_tpu.runner.secret import make_secret_key

    monkeypatch.setenv("HOROVOD_RENDEZVOUS_BACKEND", "python")
    key = make_secret_key()
    server = RendezvousServer(secret_key=key)
    port = server.start()
    client = RendezvousClient(
        "127.0.0.1", port, secret_key=key,
        retry=_fast_policy("kv.request", attempt_timeout_s=5.0),
    )
    yield server, client
    server.stop()


class TestKVChaos:
    @pytest.mark.parametrize("kind", ["reset", "timeout", "5xx"])
    def test_client_side_fault_absorbed(self, kv, kind):
        _, client = kv
        chaos.configure(f"seed=2;kv.request@1:{kind}")
        before = registry.snapshot()
        client.put("s", "k", b"v")
        assert client.get("s", "k") == b"v"
        assert _delta("retry.kv.request.retries", before) >= 1
        assert _delta("faults_injected", before) == 1

    @pytest.mark.parametrize("kind", ["5xx", "reset"])
    def test_server_side_fault_absorbed(self, kv, kind):
        server, client = kv
        client.put("s", "k", b"v")  # hits 1-2 (put) land clean
        chaos.configure(f"seed=2;kv.server@1:{kind}")
        before = registry.snapshot()
        assert client.get("s", "k") == b"v"
        assert _delta("retry.kv.request.retries", before) >= 1

    def test_exhaustion_then_circuit_fail_fast(self, kv):
        _, client = kv
        chaos.configure("seed=2;kv.request:reset")  # EVERY attempt dies
        with pytest.raises(RetryError):
            client.put("s", "k", b"v")
        with pytest.raises(RetryError):
            client.put("s", "k", b"v")  # threshold=2 -> circuit opens
        t0 = time.monotonic()
        with pytest.raises(CircuitOpenError):
            client.put("s", "k", b"v")
        assert time.monotonic() - t0 < 0.05  # fail-FAST, no ladder

    def test_wait_backoff_cuts_poll_volume(self, kv):
        """The satellite fix: a parked wait() must back off toward the
        ~1s cap instead of hammering at a fixed 50ms — over this 1.2s
        window that is <=9 polls where the old loop fired ~24."""
        _, client = kv
        chaos.configure("seed=1")  # no rules: pure hit counter
        with pytest.raises(TimeoutError):
            client.wait("nope", "missing", timeout=1.2)
        polls = chaos.active().hits("kv.request")
        assert 2 <= polls <= 9, polls

    def test_wait_aborts_on_should_stop(self, kv):
        _, client = kv
        stop = threading.Event()
        threading.Timer(0.15, stop.set).start()
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="shutdown"):
            client.wait(
                "nope", "missing", timeout=30.0, should_stop=stop.is_set
            )
        assert time.monotonic() - t0 < 5.0  # nowhere near the timeout

    def test_wait_still_returns_late_keys(self, kv):
        server, client = kv
        threading.Timer(
            0.3, lambda: server.store.put("s", "late", b"now")
        ).start()
        assert client.wait("s", "late", timeout=10.0) == b"now"

    def test_kill_kind_terminates_a_worker_process(self, tmp_path):
        """The process-death drill actually dies by SIGKILL."""
        script = tmp_path / "victim.py"
        script.write_text(
            "from horovod_tpu.testing import chaos\n"
            "chaos.configure('boom@1:kill')\n"
            "chaos.inject('boom')\n"
            "print('unreachable')\n"
        )
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env["PYTHONPATH"] = os.getcwd() + os.pathsep + env.get(
            "PYTHONPATH", ""
        )
        out = subprocess.run(
            [sys.executable, str(script)], env=env,
            capture_output=True, timeout=60,
        )
        assert out.returncode == -signal.SIGKILL
        assert b"unreachable" not in out.stdout


# ------------------------------------------------------- signed RPC chaos


@pytest.fixture
def rpc():
    from horovod_tpu.runner.secret import make_secret_key
    from horovod_tpu.runner.service import BasicClient, BasicService

    key = make_secret_key()
    service = BasicService("chaos-test", key)
    service.register("ping", lambda req: {"pong": req.get("x")})
    port = service.start()
    client = BasicClient(
        "127.0.0.1", port, key, timeout=5,
        retry=_fast_policy("service.client"),
    )
    yield service, client
    service.stop()


class TestServiceChaos:
    @pytest.mark.parametrize(
        "site,kind",
        [
            ("service.client", "reset"),
            ("service.client", "timeout"),
            ("service.server", "reset"),
            ("service.server", "5xx"),
        ],
    )
    def test_rpc_fault_absorbed(self, rpc, site, kind):
        _, client = rpc
        chaos.configure(f"seed=4;{site}@1:{kind}")
        before = registry.snapshot()
        out = client.request({"type": "ping", "x": 7})
        assert out == {"ok": True, "pong": 7}
        assert _delta("retry.service.client.retries", before) >= 1

    def test_rpc_exhaustion_then_circuit(self, rpc):
        _, client = rpc
        chaos.configure("seed=4;service.client:reset")
        for _ in range(2):
            with pytest.raises(RetryError):
                client.request({"type": "ping"})
        with pytest.raises(CircuitOpenError):
            client.request({"type": "ping"})


# --------------------------------------------------------- heartbeat chaos


class TestHeartbeatChaos:
    def test_heartbeat_survives_kv_flake(self, monkeypatch):
        """The worker's first heartbeat PUT eats an injected reset; the
        KV client's retry absorbs it and the stamp still lands."""
        from horovod_tpu.elastic.worker import WorkerNotificationManager
        from horovod_tpu.runner.rendezvous import RendezvousServer
        from horovod_tpu.runner.secret import make_secret_key

        monkeypatch.setenv("HOROVOD_RENDEZVOUS_BACKEND", "python")
        monkeypatch.setenv("HOROVOD_RETRY_BACKOFF_MS", "5")
        key = make_secret_key()
        server = RendezvousServer(secret_key=key)
        port = server.start()
        monkeypatch.setenv("HOROVOD_GLOO_RENDEZVOUS_ADDR", "127.0.0.1")
        monkeypatch.setenv("HOROVOD_GLOO_RENDEZVOUS_PORT", str(port))
        monkeypatch.setenv("HOROVOD_SECRET_KEY", key.hex())
        monkeypatch.setenv("HOROVOD_RANK", "3")
        monkeypatch.setenv("HOROVOD_HOSTNAME", "localhost")
        # the registration PUT is hit 1; the first heartbeat PUT (hit 2)
        # gets the reset
        chaos.configure("seed=5;kv.request@2:reset")
        before = registry.snapshot()
        mgr = WorkerNotificationManager()
        mgr.init()
        try:
            deadline = time.monotonic() + 10
            hb = None
            while time.monotonic() < deadline:
                hb = server.store.get("heartbeat", "3")
                if hb is not None:
                    break
                time.sleep(0.05)
            assert hb is not None, "heartbeat never landed"
            assert _delta("retry.kv.request.retries", before) >= 1
            assert _delta("faults_injected", before) >= 1
        finally:
            mgr.shutdown()
            server.stop()

    def test_heartbeat_site_delay_does_not_kill_the_loop(self):
        chaos.configure("heartbeat:delay:ms=1:n=5")
        for _ in range(5):
            chaos.inject("heartbeat")  # absorbed as slow beats
        assert chaos.active().hits("heartbeat") == 5


# --------------------------------------------------------- checkpoint chaos


def _corrupt_step_dir(ckdir, step):
    """Damage every array/metadata payload of one committed step —
    post-commit disk damage, the case the atomic-save marker cannot
    guard and the restore fallback must."""
    root = None
    for dirpath, dirnames, filenames in os.walk(ckdir):
        if os.path.basename(dirpath) == str(step):
            root = dirpath
            break
    assert root is not None, f"no step dir {step} under {ckdir}"
    clobbered = 0
    for dirpath, _, filenames in os.walk(root):
        for fn in filenames:
            path = os.path.join(dirpath, fn)
            with open(path, "wb") as f:
                f.write(b"\x00CORRUPT\x00")
            clobbered += 1
    assert clobbered > 0
    return root


class TestCheckpointChaos:
    def test_restore_falls_back_past_corruption(self, hvd, tmp_path):
        import jax.numpy as jnp

        from horovod_tpu.checkpoint import CheckpointManager

        like = {"x": jnp.zeros(4)}
        with CheckpointManager(str(tmp_path / "ck"), max_to_keep=3) as mgr:
            for step in (1, 2):
                mgr.save(step, {"x": jnp.full(4, float(step))})
                mgr.wait_until_finished()
            _corrupt_step_dir(str(tmp_path / "ck"), 2)
            before = registry.snapshot()
            step, out = mgr.restore_latest_good(like=like)
        assert step == 1
        np.testing.assert_allclose(np.asarray(out["x"]), 1.0)
        assert _delta("checkpoint.fallback", before) >= 1

    def test_all_corrupt_raises_instead_of_fresh_start(
        self, hvd, tmp_path
    ):
        import jax.numpy as jnp

        from horovod_tpu.checkpoint import CheckpointManager

        with CheckpointManager(str(tmp_path / "ck")) as mgr:
            mgr.save(1, {"x": jnp.ones(2)})
            mgr.wait_until_finished()
            _corrupt_step_dir(str(tmp_path / "ck"), 1)
            with pytest.raises(Exception):
                mgr.restore_latest_good(like={"x": jnp.zeros(2)})

    def test_durable_state_resumes_from_newest_good(self, hvd, tmp_path):
        import jax.numpy as jnp

        from horovod_tpu.checkpoint import DurableJaxState

        ckdir = str(tmp_path / "ck")
        state = DurableJaxState(
            checkpoint_dir=ckdir, params={"w": jnp.zeros(3)}, step=0,
            max_to_keep=4,
        )
        for i in (1, 2, 3):
            state.params = {"w": jnp.full(3, float(i))}
            state.step = i
            state.commit()
        state.wait_until_finished()
        state.close()
        _corrupt_step_dir(ckdir, 3)

        before = registry.snapshot()
        fresh = DurableJaxState(
            checkpoint_dir=ckdir, params={"w": jnp.zeros(3)}, step=0,
            max_to_keep=4,
        )
        assert fresh.resume_latest()
        assert fresh.step == 2  # newest GOOD, not newest
        np.testing.assert_allclose(np.asarray(fresh.params["w"]), 2.0)
        assert _delta("checkpoint.fallback", before) >= 1
        fresh.close()

    def test_sigkill_mid_save_never_trusts_a_torn_file(
        self, hvd, tmp_path
    ):
        """Regression (satellite 2): a SIGKILL landing while the async
        save of step 2 is in flight must leave either a fully-committed
        step 2 or nothing past step 1 — the restore may fall back but
        can NEVER hand back torn data."""
        ckdir = str(tmp_path / "ck")
        script = tmp_path / "saver.py"
        script.write_text(
            "import os, signal\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            "import jax, jax.numpy as jnp\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "from horovod_tpu.checkpoint import CheckpointManager\n"
            f"mgr = CheckpointManager({ckdir!r}, max_to_keep=3)\n"
            "mgr.save(1, {'x': jnp.full(4096, 1.0)})\n"
            "mgr.wait_until_finished()\n"
            "mgr.save(2, {'x': jnp.full(4096, 2.0)})\n"
            "# no wait: the write is in flight when the kill lands\n"
            "os.kill(os.getpid(), signal.SIGKILL)\n"
        )
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env["PYTHONPATH"] = os.getcwd() + os.pathsep + env.get(
            "PYTHONPATH", ""
        )
        out = subprocess.run(
            [sys.executable, str(script)], env=env,
            capture_output=True, timeout=120,
        )
        assert out.returncode == -signal.SIGKILL, out.stderr

        import jax.numpy as jnp

        from horovod_tpu.checkpoint import CheckpointManager

        with CheckpointManager(ckdir) as mgr:
            step, tree = mgr.restore_latest_good(
                like={"x": jnp.zeros(4096)}
            )
        assert step in (1, 2)
        np.testing.assert_allclose(
            np.asarray(tree["x"]), float(step)
        )  # whichever step won, its data is EXACT — never torn


# ------------------------------------------------------ fusion-path chaos


class TestFusionChaos:
    @pytest.mark.parametrize("kind", ["reset", "timeout", "5xx"])
    def test_dispatch_fault_surfaces_as_internal_error(self, hvd, kind):
        chaos.configure(f"seed=6;fusion.dispatch@1:{kind}")
        with pytest.raises(hvd.HorovodInternalError):
            hvd.allreduce(np.ones((8, 4), np.float32), name="chaos_ar")

    def test_elastic_run_absorbs_dispatch_fault(self, hvd):
        """The degradation contract end to end: fault at the collective
        -> HorovodInternalError -> hvd.elastic.run restores the last
        commit and the retried body completes."""
        from horovod_tpu.elastic import ObjectState
        from horovod_tpu.elastic.worker import run as elastic_run

        chaos.configure("seed=6;fusion.dispatch@1:timeout")
        state = ObjectState(step=0)
        attempts = {"n": 0}

        @elastic_run
        def train(st):
            attempts["n"] += 1
            st.step += 1
            out = hvd.allreduce(
                np.ones((hvd.size(), 4), np.float32),
                op=hvd.Average, name="chaos_elastic",
            )
            return st.step, np.asarray(out)

        step, out = train(state)
        assert attempts["n"] == 2          # failed once, absorbed once
        assert step == 1                   # rollback discarded the bump
        np.testing.assert_allclose(out, 1.0)  # average of ones


# ------------------------------------------------- self-healing driver


class _StoreServer:
    """Duck-typed stand-in for RendezvousServer in driver unit tests."""

    def __init__(self, store):
        self.store = store


def _put_hb(store, rank, p50, step=100):
    from horovod_tpu.runner.rendezvous import HEARTBEAT_SCOPE

    store.put(
        HEARTBEAT_SCOPE, str(rank),
        json.dumps({
            "ts": time.time(), "step": step,
            "step_ms_p50": p50, "last_step_ts": time.time(),
        }).encode(),
    )


class TestDriverSelfHealing:
    def _driver(self, monkeypatch, hosts, polls=3, min_np=1):
        from horovod_tpu.elastic.driver import ElasticDriver
        from horovod_tpu.runner.hosts import HostInfo
        from horovod_tpu.runner.rendezvous import KVStore

        from tests.test_elastic import FakeDiscovery

        monkeypatch.setenv(
            "HOROVOD_STRAGGLER_QUARANTINE_POLLS", str(polls)
        )
        d = ElasticDriver(
            FakeDiscovery([HostInfo(h, s) for h, s in hosts]),
            ["true"], min_np=min_np,
        )
        d.host_manager.refresh()
        d._server = _StoreServer(KVStore())
        # synthetic gang: ranks 0-1 on host a, 2-7 on host b
        d._blocks = [
            {"HOROVOD_RANK": str(r), "HOROVOD_HOSTNAME": h}
            for r, h in enumerate(["a"] * 2 + ["b"] * 6)
        ]
        return d

    def _poll(self, d):
        d._last_hb_poll = -1e9
        return d._poll_heartbeats(time.monotonic())

    def test_quarantine_after_k_consecutive_polls(self, monkeypatch):
        d = self._driver(monkeypatch, [("a", 2), ("b", 6)], polls=3)
        before = registry.snapshot()
        for poll in range(3):
            for r in range(8):
                _put_hb(d._server.store, r, 500.0 if r < 2 else 10.0)
            reason = self._poll(d)
            if poll < 2:
                assert reason is None  # hysteresis: not yet
        assert reason is not None and "quarantine" in reason
        assert d.host_manager.is_blacklisted("a")
        assert not d.host_manager.is_blacklisted("b")
        # re-plan excludes the quarantined host: 8 -> 6
        assert d.compute_assignment().world_size == 6
        assert _delta("driver.quarantined_hosts", before) == 1

    def test_recovery_resets_the_streak(self, monkeypatch):
        d = self._driver(monkeypatch, [("a", 2), ("b", 6)], polls=3)
        for _ in range(2):
            for r in range(8):
                _put_hb(d._server.store, r, 500.0 if r < 2 else 10.0)
            assert self._poll(d) is None
        # the slow ranks recover for one poll -> streak resets
        for r in range(8):
            _put_hb(d._server.store, r, 10.0)
        assert self._poll(d) is None
        for _ in range(2):
            for r in range(8):
                _put_hb(d._server.store, r, 500.0 if r < 2 else 10.0)
            assert self._poll(d) is None  # streak only at 2 again
        assert not d.host_manager.is_blacklisted("a")

    def test_stale_heartbeat_does_not_advance_streak(self, monkeypatch):
        """The driver polls ~10x faster than workers beat: re-judging
        ONE noisy heartbeat payload on every poll must not reach the
        quarantine threshold — streaks only advance on fresh stamps."""
        d = self._driver(monkeypatch, [("a", 2), ("b", 6)], polls=3)
        for r in range(8):  # one noisy observation, stamped once
            _put_hb(d._server.store, r, 500.0 if r < 2 else 10.0)
        for _ in range(6):  # driver re-reads the SAME payloads
            assert self._poll(d) is None
        assert not d.host_manager.is_blacklisted("a")
        assert max(
            d.stall_inspector.straggler_streaks().values(), default=0
        ) == 1

    def test_capacity_guard_keeps_slow_host(self, monkeypatch):
        """Quarantining the straggler would leave < min_np slots: a
        slow gang beats no gang, so the driver keeps it (warning once)."""
        d = self._driver(
            monkeypatch, [("a", 2), ("b", 6)], polls=2, min_np=8
        )
        for _ in range(3):
            for r in range(8):
                _put_hb(d._server.store, r, 500.0 if r < 2 else 10.0)
            assert self._poll(d) is None
        assert not d.host_manager.is_blacklisted("a")
        assert d._quarantine_capacity_warned

    def test_quarantine_disabled_by_zero_polls(self, monkeypatch):
        d = self._driver(monkeypatch, [("a", 2), ("b", 6)], polls=0)
        for _ in range(5):
            for r in range(8):
                _put_hb(d._server.store, r, 500.0 if r < 2 else 10.0)
            assert self._poll(d) is None
        assert not d.host_manager.is_blacklisted("a")


# ------------------------------------------------------- end-to-end drill


def _clean_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    return env


@pytest.mark.slow
class TestChaosDrill:
    """The acceptance scenario as one chained story: KV flake during
    rendezvous (absorbed by retry) -> straggler quarantine (hysteresis)
    -> gang restart 8 -> 6 excluding the slow host -> resume from the
    last GOOD checkpoint past a corrupt newest one."""

    def test_full_drill(self, monkeypatch, tmp_path, hvd):
        import jax.numpy as jnp

        from horovod_tpu.checkpoint import DurableJaxState
        from horovod_tpu.elastic.driver import ElasticDriver
        from horovod_tpu.runner.hosts import HostInfo

        from tests.test_elastic import FakeDiscovery

        # ---- phase 0: two durable checkpoints from the "epoch-0 job"
        ckdir = str(tmp_path / "ck")
        state = DurableJaxState(
            checkpoint_dir=ckdir, params={"w": jnp.zeros(4)}, step=0,
            max_to_keep=4,
        )
        for i in (1, 2):
            state.params = {"w": jnp.full(4, float(i))}
            state.step = i
            state.commit()
        state.wait_until_finished()
        state.close()

        # ---- phase 1: gang of 8 under a seeded KV-flake plan; the
        # workers each hit one injected reset during rendezvous traffic
        # and must absorb it (nonzero retry counters in their metrics
        # dumps), while the driver quarantines the straggler host
        for k, v in _clean_env().items():
            monkeypatch.setenv(k, v)
        monkeypatch.setenv("HOROVOD_STRAGGLER_QUARANTINE_POLLS", "3")
        results = tmp_path / "results"
        results.mkdir()
        script = tmp_path / "w.py"
        script.write_text(
            "import json, os, sys, time\n"
            "sys.path.insert(0, os.getcwd())\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            "from horovod_tpu.common.config import Config\n"
            "from horovod_tpu.common.metrics import registry\n"
            "from horovod_tpu.runner.rendezvous import _client_from_cfg\n"
            "rank = os.environ['HOROVOD_RANK']\n"
            "epoch = int(os.environ.get('HOROVOD_ELASTIC_EPOCH', '0'))\n"
            "cfg = Config.from_env()\n"
            "client = _client_from_cfg(cfg)\n"
            "# rendezvous traffic: the seeded plan resets each\n"
            "# worker's first KV request; the RetryPolicy absorbs it\n"
            "client.put('drill', rank, str(epoch).encode())\n"
            "assert client.get('drill', rank) == str(epoch).encode()\n"
            f"out = os.path.join({str(results)!r}, "
            "f'e{epoch}.r{rank}.json')\n"
            "with open(out, 'w') as f:\n"
            "    json.dump(registry.snapshot(), f)\n"
            "if epoch >= 1:\n"
            "    sys.exit(0)\n"
            "time.sleep(120)\n"  # epoch 0 parks until the restart
        )
        d = ElasticDriver(
            FakeDiscovery(
                [HostInfo("127.0.0.1", 2), HostInfo("localhost", 6)]
            ),
            [sys.executable, str(script)],
            min_np=1,
            discovery_interval=0.2,
            extra_env={
                "HOROVOD_FAULT_PLAN": "seed=11;kv.request@1:reset",
                "HOROVOD_RETRY_BACKOFF_MS": "5",
            },
        )
        try:
            d.host_manager.refresh()
            result = {}
            t = threading.Thread(target=lambda: result.update(rc=d.run()))
            t.start()
            # wait for the epoch-0 gang's 8 result files (all absorbed
            # their KV flake and are parked)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if len(list(results.glob("e0.*.json"))) == 8:
                    break
                time.sleep(0.1)
            assert len(list(results.glob("e0.*.json"))) == 8
            with d._lock:
                rank_to_host = {
                    int(b["HOROVOD_RANK"]): b["HOROVOD_HOSTNAME"]
                    for b in d._blocks
                }
            slow_ranks = {
                r for r, h in rank_to_host.items() if h == "127.0.0.1"
            }
            assert len(slow_ranks) == 2
            # straggler ledger: host 127.0.0.1's ranks report 50x the
            # gang median p50 until the driver quarantines them
            stop_beats = threading.Event()

            def _stamp():
                while not stop_beats.is_set() and d._epoch == 0:
                    for r, h in rank_to_host.items():
                        _put_hb(
                            d._server.store, r,
                            500.0 if r in slow_ranks else 10.0,
                        )
                    time.sleep(0.1)

            beater = threading.Thread(target=_stamp)
            beater.start()
            t.join(timeout=90)
            stop_beats.set()
            beater.join(timeout=5)
            assert not t.is_alive(), "driver did not converge"
            assert result["rc"] == 0
            assert d._resets == 1, "expected exactly one gang restart"
            assert d.host_manager.is_blacklisted("127.0.0.1")
        finally:
            d.shutdown()

        # ---- phase 2 assertions: epoch-1 gang is 6 workers, every
        # worker absorbed its injected KV reset (retry counters > 0)
        e1 = sorted(results.glob("e1.*.json"))
        assert len(e1) == 6, [p.name for p in e1]
        for path in list(results.glob("e0.*.json"))[:1] + e1[:1]:
            snap = json.loads(path.read_text())
            assert snap.get("retry.kv.request.retries", 0) > 0, path.name
            assert snap.get("faults_injected", 0) > 0, path.name

        # ---- phase 3: resume from the last GOOD checkpoint — the
        # newest one is corrupt (the failed epoch's parting gift)
        _corrupt_step_dir(ckdir, 2)
        before = registry.snapshot()
        fresh = DurableJaxState(
            checkpoint_dir=ckdir, params={"w": jnp.zeros(4)}, step=0,
            max_to_keep=4,
        )
        assert fresh.resume_latest()
        assert fresh.step == 1
        np.testing.assert_allclose(np.asarray(fresh.params["w"]), 1.0)
        assert _delta("checkpoint.fallback", before) >= 1
        fresh.close()


# ------------------------------------------------------ serving chaos sites


class TestServeChaos:
    def test_serve_sites_parse_and_fire(self):
        plan = chaos.configure(
            "seed=3;serve.worker_kill@1:reset;serve.migrate@1:timeout"
        )
        with pytest.raises(ConnectionResetError):
            chaos.inject("serve.worker_kill")
        with pytest.raises(TimeoutError):
            chaos.inject("serve.migrate")
        assert {(f["site"], f["kind"]) for f in plan.fired()} == {
            ("serve.worker_kill", "reset"),
            ("serve.migrate", "timeout"),
        }

    def test_worker_kill_transport_fault_crashes_scheduler_to_replay(self):
        """A transport-kind fault at serve.worker_kill lands at the top
        of the batcher's step: the scheduler dies, accepted requests
        fail LOUDLY and new submissions are refused — the dark-worker
        face the Router's replay path keys on. (The ``kill`` kind
        SIGKILLs outright for subprocess drills; its mechanics are
        covered by test_kill_kind_terminates_a_worker_process.)"""
        import jax
        import jax.numpy as jnp

        from horovod_tpu.models.transformer import (
            Transformer,
            TransformerConfig,
        )
        from horovod_tpu.serving.batcher import ContinuousBatcher, Rejected
        from horovod_tpu.serving.engine import InferenceEngine

        cfg = TransformerConfig(
            vocab_size=31, num_layers=1, d_model=8, num_heads=2,
            d_ff=16, max_len=32, causal=True, dtype=jnp.float32,
        )
        model = Transformer(cfg)
        params = model.init(
            jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32),
            train=False,
        )
        eng = InferenceEngine(
            model, params, slots=2, max_len=32, min_bucket=4
        )
        bat = ContinuousBatcher(eng, default_max_new_tokens=4)
        chaos.configure("seed=3;serve.worker_kill@1:reset")
        before = registry.snapshot()
        r = bat.submit([1, 2, 3])  # accepted BEFORE the fault lands
        bat.start()
        try:
            assert r.wait(timeout=30), "waiter stranded after kill fault"
            assert r.status == "error"
            with pytest.raises(Rejected):
                bat.submit([4, 5])
        finally:
            bat.stop()
        assert _delta("chaos.serve.worker_kill.reset", before) == 1


def test_driver_publishes_dead_hosts_to_serve_scope():
    """handle_host_failure/_try_blacklist wiring: the blacklisted host
    set (plus the ranks mapped onto it) lands in the serve scope so the
    Router can evict its announcements without waiting out the TTL."""
    import threading
    import types

    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner.rendezvous import KVStore, read_dead_hosts

    store = KVStore()
    fake = types.SimpleNamespace(
        _server=_StoreServer(store),
        host_manager=types.SimpleNamespace(blacklisted=["a"]),
        _lock=threading.Lock(),
        _blocks=[
            {"HOROVOD_RANK": str(r), "HOROVOD_HOSTNAME": h}
            for r, h in enumerate(["a"] * 2 + ["b"] * 2)
        ],
    )
    ElasticDriver._publish_dead_hosts(fake)
    dead = read_dead_hosts(store)
    assert dead["hosts"] == ["a"]
    assert dead["ranks"] == [0, 1]
