"""Runner tests — the reference's `test/single/test_run.py` model
(SURVEY.md §4.2): hostfile parsing, slot math, env construction, command
assembly asserted in-process, no cluster. Plus live KV-rendezvous and
signed-RPC round-trips on localhost."""

import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu.runner import (
    BasicClient,
    BasicService,
    HostInfo,
    RendezvousServer,
    assign_slots,
    make_secret_key,
    parse_hostfile,
    parse_hosts,
)
from horovod_tpu.runner.launch import (
    _runtime_env,
    _ssh_wrap,
    parse_args,
    worker_envs,
)
from horovod_tpu.runner.rendezvous import RendezvousClient
from horovod_tpu.runner.tpu_discovery import chips_per_host, discover_hosts


class TestHosts:
    def test_parse_hosts(self):
        hosts = parse_hosts("a:4, b:2,c")
        assert hosts == [HostInfo("a", 4), HostInfo("b", 2), HostInfo("c", 1)]

    def test_parse_ipv6(self):
        assert HostInfo.from_string("[::1]:4") == HostInfo("::1", 4)
        assert HostInfo.from_string("[fe80::2]") == HostInfo("fe80::2", 1)
        assert HostInfo.from_string("fe80::2") == HostInfo("fe80::2", 1)
        with pytest.raises(ValueError):
            HostInfo.from_string("[::1")
        with pytest.raises(ValueError):
            HostInfo.from_string("[::1]x")

    def test_parse_hosts_rejects_dupes_and_garbage(self):
        with pytest.raises(ValueError):
            parse_hosts("a:4,a:2")
        with pytest.raises(ValueError):
            parse_hosts("a:zero")
        with pytest.raises(ValueError):
            parse_hosts("  ")

    def test_parse_hostfile(self, tmp_path):
        f = tmp_path / "hostfile"
        f.write_text(
            textwrap.dedent(
                """\
                # cluster
                worker-0 slots=4
                worker-1:4
                worker-2   # bare host = 1 slot
                """
            )
        )
        hosts = parse_hostfile(str(f))
        assert hosts == [
            HostInfo("worker-0", 4),
            HostInfo("worker-1", 4),
            HostInfo("worker-2", 1),
        ]

    def test_assign_slots_numbering(self):
        # Reference numbering: rank-major by host, local_rank within host,
        # cross_rank = host index.
        slots = assign_slots([HostInfo("a", 2), HostInfo("b", 2)], np=4)
        assert [(s.rank, s.hostname, s.local_rank, s.cross_rank) for s in slots] == [
            (0, "a", 0, 0),
            (1, "a", 1, 0),
            (2, "b", 0, 1),
            (3, "b", 1, 1),
        ]
        assert all(s.size == 4 and s.cross_size == 2 for s in slots)

    def test_assign_slots_partial_and_overflow(self):
        slots = assign_slots([HostInfo("a", 4), HostInfo("b", 4)], np=3)
        assert [s.hostname for s in slots] == ["a", "a", "a"]
        assert slots[0].cross_size == 1
        with pytest.raises(ValueError):
            assign_slots([HostInfo("a", 2)], np=3)

    def test_slot_env_contract(self):
        (s,) = assign_slots([HostInfo("h", 1)], np=1)
        env = s.to_env()
        for key in (
            "HOROVOD_RANK",
            "HOROVOD_SIZE",
            "HOROVOD_LOCAL_RANK",
            "HOROVOD_LOCAL_SIZE",
            "HOROVOD_CROSS_RANK",
            "HOROVOD_CROSS_SIZE",
        ):
            assert key in env


class TestCLI:
    def test_flag_to_env_translation(self):
        args = parse_args(
            [
                "-np", "4",
                "--fusion-threshold-mb", "32",
                "--cycle-time-ms", "3.5",
                "--timeline-filename", "/tmp/t.json",
                "--autotune",
                "--", "python", "train.py",
            ]
        )
        env = _runtime_env(args)
        assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
        assert env["HOROVOD_CYCLE_TIME"] == "3.5"
        assert env["HOROVOD_TIMELINE"] == "/tmp/t.json"
        assert env["HOROVOD_AUTOTUNE"] == "1"
        assert args.command == ["python", "train.py"]

    def test_worker_envs_per_slot(self):
        slots = assign_slots([HostInfo("localhost", 4)], np=4)
        blocks = worker_envs(
            slots, "per-slot", "127.0.0.1", 1234, 5678, "ab" * 32
        )
        assert len(blocks) == 4
        for i, b in enumerate(blocks):
            assert b["HOROVOD_RANK"] == str(i)
            assert b["HOROVOD_LOCAL_SIZE"] == "1"
            assert b["HOROVOD_PROCESS_ID"] == str(i)
            assert b["HOROVOD_NUM_PROCESSES"] == "4"
            assert b["HOROVOD_COORDINATOR_PORT"] == "5678"
            assert b["HOROVOD_GLOO_RENDEZVOUS_ADDR"] == "127.0.0.1"
            assert b["JAX_PLATFORMS"] == "cpu"

    def test_worker_envs_per_host(self):
        slots = assign_slots([HostInfo("w0", 4), HostInfo("w1", 4)], np=8)
        blocks = worker_envs(slots, "per-host", "w0", 1234, 5678, "ab" * 32)
        assert len(blocks) == 2  # one process per host
        assert blocks[0]["HOROVOD_RANK"] == "0"
        assert blocks[1]["HOROVOD_RANK"] == "4"
        assert blocks[1]["HOROVOD_LOCAL_SIZE"] == "4"
        assert blocks[1]["HOROVOD_PROCESS_ID"] == "1"

    def test_single_process_gets_no_coordinator(self):
        slots = assign_slots([HostInfo("localhost", 1)], np=1)
        (b,) = worker_envs(slots, "per-slot", "127.0.0.1", 1, 2, "00")
        assert "HOROVOD_COORDINATOR_ADDR" not in b

    def test_ssh_command_assembly(self):
        # Reference test_run.py asserts on generated command strings [V].
        cmd = _ssh_wrap(
            "worker-1", 2222,
            {"HOROVOD_RANK": "3", "HOROVOD_SECRET_KEY": "deadbeef"},
            ["python", "t.py"],
        )
        assert cmd[0] == "ssh"
        assert "-p" in cmd and "2222" in cmd
        assert cmd[-2] == "worker-1"
        assert "HOROVOD_RANK=3" in cmd[-1]
        assert "python t.py" in cmd[-1]
        # secret travels over stdin, never the command line
        assert "deadbeef" not in " ".join(cmd)
        assert "read -r HOROVOD_SECRET_KEY" in cmd[-1]

    def test_coordinator_is_first_worker_host(self):
        slots = assign_slots([HostInfo("w0", 4), HostInfo("w1", 4)], np=8)
        blocks = worker_envs(slots, "per-host", "head", 1234, 9874, "00")
        assert all(b["HOROVOD_COORDINATOR_ADDR"] == "w0" for b in blocks)
        assert all(b["HOROVOD_GLOO_RENDEZVOUS_ADDR"] == "head" for b in blocks)


class TestRendezvous:
    def test_kv_round_trip(self):
        server = RendezvousServer()
        port = server.start()
        try:
            client = RendezvousClient("127.0.0.1", port)
            assert client.get("s", "k") is None
            client.put("s", "k", b"value")
            assert client.get("s", "k") == b"value"
            assert client.wait("s", "k", timeout=1) == b"value"
            assert client.keys("s") == ["k"]
            client._request("DELETE", "/kv/s")
            assert client.get("s", "k") is None
        finally:
            server.stop()

    def test_hmac_rejects_unauthenticated(self):
        key = make_secret_key()
        server = RendezvousServer(secret_key=key)
        port = server.start()
        try:
            good = RendezvousClient("127.0.0.1", port, secret_key=key)
            good.put("s", "k", b"v")
            assert good.get("s", "k") == b"v"
            bad = RendezvousClient("127.0.0.1", port)  # no key
            with pytest.raises(RuntimeError):
                bad.put("s", "k2", b"evil")
            assert bad.get("s", "k") is None  # 403 → None
            wrong = RendezvousClient(
                "127.0.0.1", port, secret_key=make_secret_key()
            )
            assert wrong.get("s", "k") is None
        finally:
            server.stop()

    def test_wait_times_out(self):
        server = RendezvousServer()
        port = server.start()
        try:
            client = RendezvousClient("127.0.0.1", port)
            with pytest.raises(TimeoutError):
                client.wait("s", "missing", timeout=0.2)
        finally:
            server.stop()


class TestService:
    def test_rpc_round_trip_and_auth(self):
        key = make_secret_key()
        svc = BasicService("driver", key)
        svc.register("ping", lambda req: {"echo": req.get("payload")})
        port = svc.start()
        try:
            client = BasicClient("127.0.0.1", port, key)
            out = client.request({"type": "ping", "payload": [1, 2, 3]})
            assert out == {"ok": True, "echo": [1, 2, 3]}
            out = client.request({"type": "nope"})
            assert out["ok"] is False and "unknown" in out["error"]
            # wrong key: server drops the frame, client sees closed conn
            evil = BasicClient("127.0.0.1", port, make_secret_key(), timeout=2)
            with pytest.raises((ConnectionError, OSError)):
                evil.request({"type": "ping"})
        finally:
            svc.stop()

    def test_handler_exception_is_reported(self):
        key = make_secret_key()
        svc = BasicService("driver", key)

        def boom(req):
            raise ValueError("bad slot")

        svc.register("boom", boom)
        port = svc.start()
        try:
            client = BasicClient("127.0.0.1", port, key)
            out = client.request({"type": "boom"})
            assert out["ok"] is False and "bad slot" in out["error"]
        finally:
            svc.stop()


class TestBroadcastObject:
    def test_broadcast_via_kv_root_publishes(self, hvd, monkeypatch):
        """Single-process half of the multi-controller broadcast: the
        root-owning process must publish the pickled payload to the
        rendezvous KV (the remote side is covered by the e2e launch)."""
        from horovod_tpu.runner.rendezvous import (
            RendezvousClient,
            broadcast_via_kv,
        )

        key = make_secret_key()
        server = RendezvousServer(secret_key=key)
        port = server.start()
        try:
            monkeypatch.setenv("HOROVOD_GLOO_RENDEZVOUS_ADDR", "127.0.0.1")
            monkeypatch.setenv("HOROVOD_GLOO_RENDEZVOUS_PORT", str(port))
            monkeypatch.setenv("HOROVOD_SECRET_KEY", key.hex())
            hvd.shutdown()
            hvd.init()
            obj = {"step": 7, "lr": 0.1}
            out = broadcast_via_kv(obj, root_rank=0, name="state")
            assert out == obj
            reader = RendezvousClient("127.0.0.1", port, secret_key=key)
            import pickle

            # round counter is folded into the key so a reused name
            # never returns a stale previous-round payload
            assert pickle.loads(reader.wait("broadcast", "state.0", 2)) == obj
            obj2 = {"step": 8}
            assert broadcast_via_kv(obj2, root_rank=0, name="state") == obj2
            assert pickle.loads(reader.wait("broadcast", "state.1", 2)) == obj2
        finally:
            server.stop()


class TestDiscovery:
    def test_explicit_override_wins(self):
        hosts = discover_hosts({"HOROVOD_TPU_HOSTS": "a:4,b:4"})
        assert hosts == [HostInfo("a", 4), HostInfo("b", 4)]

    def test_tpu_metadata(self):
        hosts = discover_hosts(
            {
                "TPU_WORKER_HOSTNAMES": "t0,t1",
                "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1",
            }
        )
        assert hosts == [HostInfo("t0", 4), HostInfo("t1", 4)]

    def test_chips_per_host_bounds(self, monkeypatch):
        monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        assert chips_per_host() == 4

    def test_device_nodes_outrank_the_bounds_variable(self, monkeypatch):
        """A one-chip machine carved from a 2x2 host still says 2,2,1."""
        from horovod_tpu.runner import tpu_discovery

        monkeypatch.setattr(tpu_discovery, "local_chip_count", lambda: 1)
        assert chips_per_host({"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}) == 1

    def test_launcher_parent_stays_off_jax(self):
        """discover_hosts() and the launcher's import chain leave jax
        un-imported: a parent that touches JAX takes the chips its
        workers need."""
        code = (
            "import sys\n"
            "import horovod_tpu.runner.launch\n"
            "from horovod_tpu.runner.tpu_discovery import discover_hosts\n"
            "assert discover_hosts({})[0].hostname == 'localhost'\n"
            "assert discover_hosts()\n"
            "assert 'jax' not in sys.modules, 'launcher imported jax'\n"
        )
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)  # the path that used to ask jax
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, timeout=60,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert proc.returncode == 0, proc.stderr[-2000:]

    def test_auto_placement_never_hides_chips_behind_the_cpu(
        self, monkeypatch
    ):
        from horovod_tpu.runner import launch, tpu_discovery

        local = [HostInfo("localhost", 4)]
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(tpu_discovery, "local_chip_count", lambda: 4)
        assert launch._auto_placement(local, 4) == "per-host"
        with pytest.raises(SystemExit, match="-np must be 4"):
            launch._auto_placement(local, 2)
        assert launch._auto_placement([HostInfo("far", 4)], 4) == "per-host"
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # asked for by name
        assert launch._auto_placement(local, 2) == "per-slot"
        monkeypatch.delenv("JAX_PLATFORMS")
        monkeypatch.setattr(tpu_discovery, "local_chip_count", lambda: 0)
        assert launch._auto_placement(local, 2) == "per-slot"


_LAUNCH_SCRIPT = """
import os
import jax
import horovod_tpu as hvd

hvd.init()
assert hvd.size() == 2, hvd.size()
assert hvd.cross_size() == 2
assert jax.process_count() == 2
rank = hvd.rank()
x = hvd.replicate(float(rank + 1))
out = hvd.allreduce(x, op=hvd.Sum)
assert float(hvd.first(out)) == 3.0, out
print("WORKER_OK", rank)
"""


@pytest.mark.slow
def test_end_to_end_two_process_launch(tmp_path):
    """Live parity with the reference's `horovodrun -np 2 python ...`
    localhost test mode (SURVEY.md §4.1): two real processes, real
    jax.distributed coordination, real collective, exit codes collected."""
    script = tmp_path / "worker.py"
    script.write_text(_LAUNCH_SCRIPT)
    env = dict(os.environ)
    # The workers must not inherit the 8-device test flag: each process
    # is its own 1-chip host.
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    out_dir = tmp_path / "logs"
    proc = subprocess.run(
        [
            sys.executable, "-m", "horovod_tpu.runner",
            "-np", "2", "--placement", "per-slot",
            "--output-filename", str(out_dir),
            "--", sys.executable, str(script),
        ],
        env=env,
        timeout=300,
        capture_output=True,
    )
    logs = "\n".join(
        p.read_text() for p in sorted(out_dir.glob("rank.*"))
    )
    assert proc.returncode == 0, f"launcher failed:\n{proc.stderr.decode()}\n{logs}"
    assert "WORKER_OK 0" in logs and "WORKER_OK 1" in logs


def test_failure_path_kills_all_and_reports(tmp_path):
    """§3.3: on any nonzero exit → terminate all, return the code."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys, time\n"
        "if os.environ['HOROVOD_RANK'] == '0':\n"
        "    sys.exit(3)\n"
        "time.sleep(300)\n"
    )
    env = dict(os.environ)
    # Generous timeout: under full-suite load the driver's jax import
    # alone can take tens of seconds on a loaded single-core box; the
    # sleeping worker is SIGTERMed by the driver, so the real duration
    # is driver startup + ~15 s, not the sleep.
    proc = subprocess.run(
        [
            sys.executable, "-m", "horovod_tpu.runner",
            "-np", "2", "--placement", "per-slot",
            "--", sys.executable, str(script),
        ],
        env=env,
        timeout=240,
        capture_output=True,
    )
    assert proc.returncode == 3


class TestConfigFile:
    """hvdrun --config-file params YAML (ref: horovodrun --config-file,
    upstream runner/launch.py [V]). Precedence: CLI > file > defaults."""

    def _write(self, tmp_path, text):
        f = tmp_path / "params.yaml"
        f.write_text(text)
        return str(f)

    def test_yaml_values_with_nesting(self, tmp_path):
        path = self._write(
            tmp_path,
            "num-proc: 8\n"
            "placement: per-slot\n"
            "fusion:\n"
            "  threshold-mb: 32\n"
            "cycle-time-ms: 3.5\n"
            "autotune: true\n",
        )
        args = parse_args(
            ["--config-file", path, "--", "python", "train.py"]
        )
        assert args.num_proc == 8
        assert args.placement == "per-slot"
        assert args.fusion_threshold_mb == 32.0
        assert args.cycle_time_ms == 3.5
        assert args.autotune is True
        assert args.command == ["python", "train.py"]
        env = _runtime_env(args)
        assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)

    def test_cli_overrides_config_file(self, tmp_path):
        path = self._write(
            tmp_path, "num-proc: 8\ncycle-time-ms: 3.5\n"
        )
        args = parse_args(
            ["--config-file", path, "-np", "2", "--", "x"]
        )
        assert args.num_proc == 2      # CLI wins
        assert args.cycle_time_ms == 3.5  # file still applies

    def test_underscore_keys_and_string_coercion(self, tmp_path):
        path = self._write(
            tmp_path, "num_proc: '4'\nstart_timeout: '30'\n"
        )
        args = parse_args(["--config-file", path, "--", "x"])
        assert args.num_proc == 4
        assert args.start_timeout == 30.0

    def test_unknown_key_fails_fast(self, tmp_path):
        path = self._write(tmp_path, "num-proc: 4\nnot-a-flag: 1\n")
        with pytest.raises(SystemExit):
            parse_args(["--config-file", path, "--", "x"])

    def test_np_still_required_without_config(self):
        with pytest.raises(SystemExit):
            parse_args(["--cycle-time-ms", "3.5", "--", "x"])

    def test_command_not_scanned_for_config_flag(self, tmp_path):
        """--config-file appearing only inside the launched command must
        not be treated as hvdrun's own flag."""
        args = parse_args(
            ["-np", "2", "--", "python", "t.py", "--config-file", "u.yaml"]
        )
        assert args.config_file is None
        assert args.command == [
            "python", "t.py", "--config-file", "u.yaml"
        ]

    def test_command_config_flag_without_separator(self, tmp_path):
        """Same, without the `--` separator: the pre-scan must stop at
        the first positional (start of the command)."""
        args = parse_args(
            ["-np", "2", "python", "t.py", "--config-file", "u.yaml"]
        )
        assert args.config_file is None
        assert args.command == [
            "python", "t.py", "--config-file", "u.yaml"
        ]


class TestVersionConsistency:
    """Same-version gang guard at init (ref: the launch driver's probe
    across hosts, horovod/runner/driver/driver_service.py [V])."""

    class _Cfg:
        def __init__(self, port):
            self.rendezvous_addr = "127.0.0.1"
            self.rendezvous_port = port
            self.secret_key_hex = None
            self.gloo_timeout_seconds = 1.0

    class _Topo:
        def __init__(self, rank):
            self.rank = rank

    def test_same_version_passes_and_rank0_publishes(self):
        from horovod_tpu.runner.rendezvous import check_version_consistency

        server = RendezvousServer()
        port = server.start()
        try:
            cfg = self._Cfg(port)
            check_version_consistency(cfg, self._Topo(0))
            # rank 0 published its version for the others, in the
            # elastic-epoch-keyed scope
            client = RendezvousClient("127.0.0.1", port)
            import horovod_tpu

            assert client.get("version.0", "0").decode() == \
                horovod_tpu.__version__
            check_version_consistency(cfg, self._Topo(1))  # matches
        finally:
            server.stop()

    def test_mismatch_raises_with_both_versions(self):
        from horovod_tpu.runner.rendezvous import check_version_consistency

        server = RendezvousServer()
        port = server.start()
        try:
            client = RendezvousClient("127.0.0.1", port)
            client.put("version.0", "0", b"9.9.9-other")
            with pytest.raises(RuntimeError, match="9.9.9-other"):
                check_version_consistency(
                    self._Cfg(port), self._Topo(2)
                )
        finally:
            server.stop()

    def test_stale_epoch_key_ignored(self, monkeypatch):
        """A previous elastic incarnation's version key must not fake a
        skew: the scope is keyed by HOROVOD_ELASTIC_EPOCH."""
        from horovod_tpu.runner.rendezvous import check_version_consistency

        server = RendezvousServer()
        port = server.start()
        try:
            client = RendezvousClient("127.0.0.1", port)
            client.put("version.0", "0", b"0.0.1-previous-gang")
            monkeypatch.setenv("HOROVOD_ELASTIC_EPOCH", "3")
            # epoch-3 rank 0 publishes the current version; rank 1 then
            # compares within epoch 3 and must NOT see the epoch-0 key
            check_version_consistency(self._Cfg(port), self._Topo(0))
            check_version_consistency(self._Cfg(port), self._Topo(1))
        finally:
            server.stop()

    def test_auth_skew_warns_not_fails(self):
        """A non-200 from the KV (e.g. secret out of sync mid-re-key)
        must warn, never fail init — only a real mismatch raises."""
        from horovod_tpu.runner.rendezvous import check_version_consistency

        key = make_secret_key()
        server = RendezvousServer(secret_key=key)
        port = server.start()
        try:
            cfg = self._Cfg(port)  # client has NO secret → 403 on put
            check_version_consistency(cfg, self._Topo(1))
        finally:
            server.stop()

    def test_timeout_warns_but_passes(self):
        from horovod_tpu.runner.rendezvous import check_version_consistency

        server = RendezvousServer()
        port = server.start()
        try:
            # rank 0 never publishes; non-root must not hard-fail
            check_version_consistency(self._Cfg(port), self._Topo(1))
        finally:
            server.stop()

    def test_no_rendezvous_is_noop(self):
        from horovod_tpu.runner.rendezvous import check_version_consistency

        cfg = self._Cfg(0)
        cfg.rendezvous_addr = None
        check_version_consistency(cfg, self._Topo(1))


class TestCheckBuild:
    """hvdrun --check-build prints the build summary and exits 0 without
    needing -np or a command (ref: horovodrun --check-build [V])."""

    def test_check_build_runs_without_np(self, capsys):
        from horovod_tpu.runner.launch import run_commandline

        assert run_commandline(["--check-build"]) == 0
        out = capsys.readouterr().out
        assert "Available Frameworks" in out
        assert "XLA collectives" in out
        assert "[X] JAX / Flax" in out
        # GPU-era transports must honestly report absent
        assert "[ ] NCCL" in out

    def test_short_flag(self, capsys):
        from horovod_tpu.runner.launch import run_commandline

        assert run_commandline(["-cb"]) == 0
        assert "Available Controllers" in capsys.readouterr().out

    def test_check_build_in_command_not_ours(self):
        """-cb inside the launched command must not trigger the mode."""
        args = parse_args(["-np", "2", "--", "python", "t.py", "-cb"])
        assert args.check_build is False
        assert args.command == ["python", "t.py", "-cb"]
