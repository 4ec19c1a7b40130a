"""Elastic tests — the reference's model (SURVEY.md §4.2/§4.3):
driver logic in-process against fake scripted discovery; integration via
real localhost gangs with file-mutation membership changes and failing
workers."""

import os
import sys
import time
from typing import List

import numpy as np
import pytest

import horovod_tpu as hvd_mod
from horovod_tpu.elastic import (
    ElasticDriver,
    HostDiscovery,
    HostDiscoveryScript,
    HostManager,
    JaxState,
    ObjectState,
)
from horovod_tpu.elastic.worker import notification_manager, run as elastic_run
from horovod_tpu.common.basics import (
    HorovodInternalError,
    HostsUpdatedInterrupt,
)
from horovod_tpu.runner.hosts import HostInfo


class FakeDiscovery(HostDiscovery):
    """Scripted host sequences — the reference's fake-discovery test
    pattern (test_elastic_driver.py [V])."""

    def __init__(self, hosts: List[HostInfo]):
        self.hosts = list(hosts)

    def find_available_hosts_and_slots(self):
        return list(self.hosts)


class TestDiscovery:
    def test_script_discovery(self, tmp_path):
        listing = tmp_path / "hosts.txt"
        listing.write_text("a:2\nb:2\n")
        disc = HostDiscoveryScript(f"cat {listing}")
        assert disc.find_available_hosts_and_slots() == [
            HostInfo("a", 2),
            HostInfo("b", 2),
        ]
        # membership driven by mutating the file — §4.3's mechanism
        listing.write_text("a:2\n")
        assert disc.find_available_hosts_and_slots() == [HostInfo("a", 2)]

    def test_script_failure_means_no_hosts(self):
        assert HostDiscoveryScript("exit 1").find_available_hosts_and_slots() == []

    def test_default_slots(self, tmp_path):
        listing = tmp_path / "hosts.txt"
        listing.write_text("a\n")
        disc = HostDiscoveryScript(f"cat {listing}", default_slots=4)
        assert disc.find_available_hosts_and_slots() == [HostInfo("a", 4)]

    def test_host_manager_blacklist(self):
        disc = FakeDiscovery([HostInfo("a", 2), HostInfo("b", 2)])
        mgr = HostManager(disc)
        assert mgr.refresh() is True
        assert [h.hostname for h in mgr.current_hosts()] == ["a", "b"]
        mgr.blacklist("a")
        assert mgr.is_blacklisted("a")
        assert [h.hostname for h in mgr.current_hosts()] == ["b"]
        # blacklisted host keeps being filtered on refresh
        mgr.refresh()
        assert [h.hostname for h in mgr.current_hosts()] == ["b"]

    def test_refresh_reports_change(self):
        disc = FakeDiscovery([HostInfo("a", 2)])
        mgr = HostManager(disc)
        assert mgr.refresh() is True
        assert mgr.refresh() is False
        disc.hosts.append(HostInfo("b", 2))
        assert mgr.refresh() is True


class TestAssignment:
    def _driver(self, disc, **kw):
        kw.setdefault("min_np", 1)
        return ElasticDriver(disc, ["true"], **kw)

    def test_below_min_np_is_none(self):
        d = self._driver(FakeDiscovery([HostInfo("a", 2)]), min_np=4)
        d.host_manager.refresh()
        assert d.compute_assignment() is None

    def test_max_np_clamps(self):
        d = self._driver(
            FakeDiscovery([HostInfo("a", 4), HostInfo("b", 4)]), max_np=6
        )
        d.host_manager.refresh()
        a = d.compute_assignment()
        assert a.world_size == 6
        # ranks dense, reference numbering
        assert [s.rank for s in a.slots] == list(range(6))

    def test_failure_then_reassignment(self):
        d = self._driver(FakeDiscovery([HostInfo("a", 2), HostInfo("b", 2)]))
        d.host_manager.refresh()
        assert d.compute_assignment().world_size == 4
        d.handle_host_failure("a")
        a = d.compute_assignment()
        assert a.world_size == 2
        assert a.hostnames == ["b"]

    def test_slots_per_host_override(self):
        d = self._driver(
            FakeDiscovery([HostInfo("a", 1)]), slots_per_host=4
        )
        d.host_manager.refresh()
        assert d.compute_assignment().world_size == 4


class TestState:
    def test_object_state_commit_restore(self):
        s = ObjectState(step=0, best=1.5)
        s.step = 10
        s.commit()
        s.step = 99
        s.restore()
        assert s.step == 10 and s.best == 1.5

    def test_object_state_initial_save(self):
        s = ObjectState(step=5)
        s.step = 7
        s.restore()  # never committed → back to construction values
        assert s.step == 5

    def test_jax_state_tree_commit_restore(self, hvd):
        import jax.numpy as jnp

        params = {"w": jnp.ones((4, 4)), "b": jnp.zeros(4)}
        s = JaxState(params=params, step=0)
        s.params = {"w": jnp.full((4, 4), 2.0), "b": jnp.ones(4)}
        s.step = 3
        s.commit()
        s.params = {"w": jnp.full((4, 4), -1.0), "b": jnp.ones(4)}
        s.step = 8
        s.restore()
        assert s.step == 3
        np.testing.assert_allclose(np.asarray(s.params["w"]), 2.0)
        np.testing.assert_allclose(np.asarray(s.params["b"]), 1.0)

    def test_jax_state_sync_replicates(self, hvd):
        import jax
        import jax.numpy as jnp

        s = JaxState(params={"w": jnp.arange(8.0)})
        s.sync()
        leaf = s.params["w"]
        assert isinstance(leaf, jax.Array)
        assert leaf.sharding.is_fully_replicated
        np.testing.assert_allclose(np.asarray(leaf), np.arange(8.0))


class TestRunWrapper:
    def test_internal_error_restores_and_retries(self, hvd):
        calls = []

        class S(ObjectState):
            def sync(self):
                calls.append("sync")

        state = S(step=0)
        attempts = {"n": 0}

        @elastic_run
        def train(st):
            attempts["n"] += 1
            if attempts["n"] == 1:
                st.step = 50  # uncommitted progress, must be rolled back
                raise HorovodInternalError("peer died")
            return st.step

        assert train(state) == 0  # rolled back to initial commit
        assert attempts["n"] == 2
        assert calls == ["sync", "sync"]  # re-synced after restore

    def test_hosts_updated_keeps_state(self, hvd):
        state = ObjectState(step=0)
        attempts = {"n": 0}

        @elastic_run
        def train(st):
            attempts["n"] += 1
            if attempts["n"] == 1:
                st.step = 7
                raise HostsUpdatedInterrupt()
            return st.step

        assert train(state) == 7  # progress preserved on membership change
        assert attempts["n"] == 2

    def test_commit_raises_on_pending_update(self, hvd):
        state = ObjectState(step=0)
        notification_manager._updated.set()
        with pytest.raises(HostsUpdatedInterrupt):
            state.commit()
        # flag consumed
        state.commit()


class TestNotificationEndToEnd:
    def test_driver_notifies_worker_manager(self, monkeypatch):
        """Worker manager registers in the KV; driver pings it; the flag
        surfaces as HostsUpdatedInterrupt."""
        from horovod_tpu.elastic.worker import WorkerNotificationManager
        from horovod_tpu.runner.rendezvous import RendezvousServer
        from horovod_tpu.runner.service import BasicClient

        import horovod_tpu.runner.secret as secret_mod

        key = secret_mod.make_secret_key()
        server = RendezvousServer(secret_key=key)
        port = server.start()
        try:
            monkeypatch.setenv("HOROVOD_GLOO_RENDEZVOUS_ADDR", "127.0.0.1")
            monkeypatch.setenv("HOROVOD_GLOO_RENDEZVOUS_PORT", str(port))
            monkeypatch.setenv("HOROVOD_SECRET_KEY", key.hex())
            monkeypatch.setenv("HOROVOD_ELASTIC_EPOCH", "0")
            monkeypatch.setenv("HOROVOD_PROCESS_ID", "0")
            monkeypatch.setenv("HOROVOD_HOSTNAME", "localhost")
            mgr = WorkerNotificationManager()
            mgr.init()
            try:
                addr = server.store.get("workers.0", "0")
                assert addr is not None
                host, _, sport = addr.decode().partition(":")
                out = BasicClient(host, int(sport), key).request(
                    {"type": "hosts_updated", "epoch": 0}
                )
                assert out["ok"] is True
                with pytest.raises(HostsUpdatedInterrupt):
                    mgr.raise_if_updated()
            finally:
                mgr.shutdown()
        finally:
            server.stop()


def _clean_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    return env


@pytest.mark.slow
class TestDriverIntegration:
    """Real localhost gangs (§4.3's chaos style, scaled to CI)."""

    def test_gang_success(self, monkeypatch):
        for k, v in _clean_env().items():
            monkeypatch.setenv(k, v)
        d = ElasticDriver(
            FakeDiscovery([HostInfo("localhost", 2)]),
            [sys.executable, "-c", "import os; assert os.environ['HOROVOD_SIZE']=='2'"],
            min_np=2,
            discovery_interval=0.2,
        )
        try:
            d.host_manager.refresh()
            assert d.run() == 0
        finally:
            d.shutdown()

    def test_worker_failure_blacklists_and_exhausts(self, monkeypatch):
        for k, v in _clean_env().items():
            monkeypatch.setenv(k, v)
        d = ElasticDriver(
            FakeDiscovery([HostInfo("localhost", 1)]),
            [sys.executable, "-c", "raise SystemExit(5)"],
            min_np=1,
            discovery_interval=0.1,
            start_timeout=0.5,
        )
        try:
            d.host_manager.refresh()
            rc = d.run()
            assert rc != 0
            assert d.host_manager.is_blacklisted("localhost")
        finally:
            d.shutdown()

    def test_membership_shrink_restarts_gang(self, monkeypatch, tmp_path):
        """World of 2 sleeps; discovery shrinks to 1; restarted world of
        1 exits 0 — the §3.4 restart-on-change path with a live gang."""
        for k, v in _clean_env().items():
            monkeypatch.setenv(k, v)
        script = tmp_path / "w.py"
        script.write_text(
            "import os, sys, time\n"
            "if os.environ['HOROVOD_SIZE'] == '1':\n"
            "    sys.exit(0)\n"
            "time.sleep(120)\n"
        )
        listing = tmp_path / "hosts.txt"
        listing.write_text("localhost:2\n")
        d = ElasticDriver(
            HostDiscoveryScript(f"cat {listing}"),
            [sys.executable, str(script)],
            min_np=1,
            discovery_interval=0.2,
        )
        try:
            d.host_manager.refresh()
            import threading

            result = {}
            t = threading.Thread(target=lambda: result.update(rc=d.run()))
            t.start()
            time.sleep(1.5)  # let epoch-0 gang come up
            listing.write_text("localhost:1\n")  # shrink membership
            t.join(timeout=60)
            assert not t.is_alive(), "driver did not converge"
            assert result["rc"] == 0
        finally:
            d.shutdown()

    def test_worker_sigkill_triggers_gang_restart(self, monkeypatch,
                                                  tmp_path):
        """§4.3's fault injection: SIGKILL a live worker PID mid-run;
        the driver must detect the dead gang, reset, relaunch, and the
        job must still complete (the reference's integration tests kill
        worker PIDs exactly like this [V])."""
        for k, v in _clean_env().items():
            monkeypatch.setenv(k, v)
        flag = tmp_path / "second_epoch"
        script = tmp_path / "w.py"
        # epoch 0: sleep forever (to be killed); epoch 1+: exit 0
        script.write_text(
            "import os, sys, time, pathlib\n"
            f"flag = pathlib.Path({str(flag)!r})\n"
            "if int(os.environ.get('HOROVOD_ELASTIC_EPOCH', '0')) >= 1:\n"
            "    sys.exit(0)\n"
            "flag.write_text('up')\n"
            "time.sleep(120)\n"
        )
        # Two "hosts" (both local): the failed worker's host gets
        # blacklisted, the surviving host carries the epoch-1 gang —
        # the reference's kill-and-survive scenario shape [V].
        d = ElasticDriver(
            FakeDiscovery(
                [HostInfo("localhost", 1), HostInfo("127.0.0.1", 1)]
            ),
            [sys.executable, str(script)],
            min_np=1,
            discovery_interval=0.2,
        )
        try:
            d.host_manager.refresh()
            import signal as _signal
            import threading

            result = {}
            t = threading.Thread(target=lambda: result.update(rc=d.run()))
            t.start()
            deadline = time.monotonic() + 20
            while not flag.exists() and time.monotonic() < deadline:
                time.sleep(0.05)
            assert flag.exists(), "epoch-0 worker never came up"
            with d._lock:
                procs = list(d._procs)
            assert procs
            procs[0].send_signal(_signal.SIGKILL)
            t.join(timeout=60)
            assert not t.is_alive(), "driver did not recover from SIGKILL"
            assert result["rc"] == 0  # epoch-1 relaunch exited clean
        finally:
            d.shutdown()


@pytest.mark.slow
class TestComposedElasticPath:
    """The composed elastic story as ONE scenario (VERDICT r5 item 6):
    the pieces — gang restart on SIGKILL, ZeRO-1 ``reshard_state``
    across a world change, ``DurableJaxState`` restore from the Orbax
    checkpoint — are individually tested elsewhere; this chains them
    the way a real preempted job experiences them (the reference's
    elastic integration tests tell the same end-to-end story,
    test/integration/test_elastic_torch.py [V])."""

    def test_sigkill_reshard_restore_chain(self, monkeypatch, tmp_path,
                                           hvd):
        import signal as _signal
        import threading

        import jax
        import jax.numpy as jnp
        import optax
        from functools import partial
        from jax.sharding import Mesh, PartitionSpec as P

        from horovod_tpu.checkpoint import DurableJaxState

        rng = np.random.default_rng(0)
        w0 = rng.normal(size=(5, 3)).astype(np.float32)
        params = {
            "w": jnp.asarray(w0),
            "b": jnp.zeros((3,), jnp.float32),
        }
        x = jnp.asarray(rng.normal(size=(8, 16, 5)), jnp.float32)
        y = jnp.asarray(
            np.einsum("wbi,io->wbo", np.asarray(x), w0), jnp.float32
        )

        def _loss(p, xb, yb):
            return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)

        def make_step(opt, mesh):
            @partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P(), opt.state_spec(),
                          P(hvd_mod.WORLD_AXIS), P(hvd_mod.WORLD_AXIS)),
                out_specs=(P(), opt.state_spec(), P()),
                check_vma=False,
            )
            def step(p, st, xb, yb):
                loss, g = jax.value_and_grad(_loss)(p, xb[0], yb[0])
                u, st = opt.update(g, st, p)
                return optax.apply_updates(p, u), st, jax.lax.pmean(
                    loss, hvd_mod.WORLD_AXIS
                )

            return jax.jit(step)

        # ---- phase A: epoch-0 training at world 8, durable commits
        ckdir = str(tmp_path / "ck")
        opt = hvd_mod.ShardedDistributedOptimizer(optax.adam(1e-2))
        ostate = opt.init(params)
        state = DurableJaxState(
            checkpoint_dir=ckdir, params=params, opt_state=ostate,
            step=0,
        )
        step8 = make_step(opt, hvd_mod.mesh())
        losses = []
        for i in range(3):
            state.params, state.opt_state, loss = step8(
                state.params, state.opt_state, x, y
            )
            state.step = i + 1
            losses.append(float(loss))
        state.commit()
        state.wait_until_finished()
        moments_before = [
            np.concatenate(
                [np.asarray(l).reshape(-1)]
            )
            for l in jax.tree_util.tree_leaves(
                jax.device_get(state.opt_state)
            )
        ]
        state.close()

        # ---- phase B: the gang dies (SIGKILL), membership shrinks to
        # 6 slots, the driver restarts; epoch-1 workers report their
        # world size — the size phase C reshards to
        for k, v in _clean_env().items():
            monkeypatch.setenv(k, v)
        flag = tmp_path / "epoch0_up"
        size_file = tmp_path / "epoch1_size"
        script = tmp_path / "w.py"
        script.write_text(
            "import os, sys, time, pathlib\n"
            f"flag = pathlib.Path({str(flag)!r})\n"
            f"size_file = pathlib.Path({str(size_file)!r})\n"
            "if int(os.environ.get('HOROVOD_ELASTIC_EPOCH', '0')) >= 1:\n"
            "    if os.environ.get('HOROVOD_RANK') == '0':\n"
            "        size_file.write_text(os.environ['HOROVOD_SIZE'])\n"
            "    sys.exit(0)\n"
            "flag.write_text('up')\n"
            "time.sleep(120)\n"
        )
        d = ElasticDriver(
            FakeDiscovery(
                [HostInfo("127.0.0.1", 2), HostInfo("localhost", 6)]
            ),
            [sys.executable, str(script)],
            min_np=1,
            discovery_interval=0.2,
        )
        try:
            d.host_manager.refresh()
            result = {}
            t = threading.Thread(target=lambda: result.update(rc=d.run()))
            t.start()
            deadline = time.monotonic() + 20
            while not flag.exists() and time.monotonic() < deadline:
                time.sleep(0.05)
            assert flag.exists(), "epoch-0 gang never came up"
            with d._lock:
                procs = list(d._procs)
            procs[0].send_signal(_signal.SIGKILL)
            t.join(timeout=60)
            assert not t.is_alive(), "driver did not recover"
            assert result["rc"] == 0
        finally:
            d.shutdown()
        new_world = int(size_file.read_text())
        assert new_world == 6  # the blacklisted host's 2 slots are gone

        # ---- phase C: the restarted job restores from the durable
        # checkpoint and reshards the ZeRO-1 state 8 -> new_world,
        # carrying the Adam moments exactly, then keeps learning
        fresh = DurableJaxState(
            checkpoint_dir=ckdir,
            params=jax.tree_util.tree_map(jnp.zeros_like, params),
            opt_state=jax.tree_util.tree_map(jnp.zeros_like, ostate),
            step=0,
        )
        assert fresh.resume_latest()
        assert fresh.step == 3
        r_params = jax.tree_util.tree_map(
            np.asarray, jax.device_get(fresh.params)
        )
        r_ostate = opt.reshard_state(
            jax.device_get(fresh.opt_state), r_params, new_world
        )
        fresh.close()
        moments_after = [
            np.asarray(l).reshape(-1)
            for l in jax.tree_util.tree_leaves(jax.device_get(r_ostate))
        ]
        # moment mass is carried exactly (reshard moves, never resets):
        # sharded leaves keep every nonzero entry, replicated scalars
        # (Adam's count) re-broadcast to the new world unchanged
        for b, a in zip(moments_before, moments_after):
            if np.unique(b).size == 1:
                assert np.unique(a).size == 1 and a.flat[0] == b.flat[0]
            else:
                np.testing.assert_allclose(
                    np.sort(b[np.abs(b) > 0]),
                    np.sort(a[np.abs(a) > 0]),
                    rtol=0, atol=0,
                )

        mesh6 = Mesh(
            np.asarray(jax.devices()[:new_world]),
            (hvd_mod.WORLD_AXIS,),
        )
        step6 = make_step(opt, mesh6)
        p6 = jax.tree_util.tree_map(jnp.asarray, r_params)
        s6 = jax.tree_util.tree_map(jnp.asarray, r_ostate)
        x6, y6 = x[:new_world], y[:new_world]
        for _ in range(5):
            p6, s6, loss = step6(p6, s6, x6, y6)
            losses.append(float(loss))
        assert losses[-1] < losses[2], losses  # still learning post-chain
