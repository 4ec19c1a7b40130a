"""``hvdrun`` — the launcher CLI.

TPU-native rebuild of ``horovodrun`` (ref: horovod/runner/launch.py
`run_commandline` + gloo_run.py/mpi_run.py [V] — SURVEY.md §2.5, §3.3;
empty mount, structural citations).

Where the reference picks between mpirun and SSH+Gloo, this launcher has
two placement modes:

* **per-host** (any host with chips): one process per host driving all
  local chips — the JAX single-controller-per-host model, and the only
  one a TPU allows (a chip belongs to one process). Remote hosts are
  reached via ssh exactly like the reference's gloo_run.
* **per-slot** (CPU simulation / tests): one process per rank, each
  seeing one CPU device, wired together with ``jax.distributed`` — the
  moral equivalent of the reference's multi-process localhost testing
  mode (SURVEY.md §4).

The launcher parent never imports JAX (it would take the chips its
workers need): topology comes from env metadata and device nodes
(``tpu_discovery.py``).

Either way the driver: generates a per-job HMAC secret, starts the HTTP
KV rendezvous, exports the ``HOROVOD_*`` env contract + coordinator
address to every worker, watches exit codes, and tears everything down
on first failure (ref §3.3 failure path).

Usage:
    python -m horovod_tpu.runner -np 4 python train.py
    python -m horovod_tpu.runner -np 8 -H host1:4,host2:4 python train.py
"""

from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

from .hosts import HostInfo, SlotInfo, assign_slots, parse_hostfile, parse_hosts
from .rendezvous import RendezvousServer
from .secret import make_secret_key

_LOCAL_NAMES = {"localhost", "127.0.0.1", "::1"}


def _is_local(hostname: str) -> bool:
    return hostname in _LOCAL_NAMES or hostname == socket.gethostname()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _load_config_file(path: str, parser: argparse.ArgumentParser) -> dict:
    """Parse a hvdrun params YAML (ref: horovodrun --config-file,
    upstream runner/launch.py [V]) into argparse defaults.

    Format: a mapping whose keys are the long option names (dashes or
    underscores both accepted); one level of nesting joins section and
    key with a dash, so

        num-proc: 8
        cycle-time-ms: 3.5
        fusion:
          threshold-mb: 32
        autotune: true

    sets --num-proc/--cycle-time-ms/--fusion-threshold-mb/--autotune.
    Precedence (documented contract): explicit CLI flags > config file
    > built-in defaults — the file is applied via parser defaults, so a
    flag given on the command line always wins. Unknown keys fail fast.
    """
    import yaml

    try:
        with open(path) as f:
            data = yaml.safe_load(f) or {}
    except OSError as e:
        raise SystemExit(f"--config-file {path}: {e}") from None
    except yaml.YAMLError as e:
        raise SystemExit(f"--config-file {path}: invalid YAML: {e}") from None
    if not isinstance(data, dict):
        raise SystemExit(
            f"--config-file {path}: expected a YAML mapping, got "
            f"{type(data).__name__}"
        )
    flat: dict = {}
    for k, v in data.items():
        if isinstance(v, dict):
            for k2, v2 in v.items():
                flat[f"{k}-{k2}"] = v2
        else:
            flat[k] = v
    by_dest = {a.dest: a for a in parser._actions}
    out = {}
    for k, v in flat.items():
        dest = str(k).replace("-", "_")
        if dest in ("help", "command", "config_file") or dest not in by_dest:
            raise SystemExit(
                f"--config-file {path}: unknown parameter {k!r} "
                "(keys are hvdrun's long option names)"
            )
        action = by_dest[dest]
        if isinstance(action, argparse._StoreTrueAction):
            v = bool(v)
        elif action.type is not None and v is not None:
            v = action.type(v)
        out[dest] = v
    return out


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    """Flag surface mirrors horovodrun's (launch.py [V]); flags that
    configure the runtime translate into HOROVOD_* env for workers, same
    as the reference."""
    argv = list(sys.argv[1:] if argv is None else argv)
    # Pre-scan so config-file values can satisfy the -np requirement.
    # The scan walks hvdrun's OWN flags only: it stops at "--" or at the
    # first positional (where the REMAINDER command begins), skipping
    # each value-taking flag's argument, so a --config-file belonging to
    # the launched program is never misread as ours.
    no_value_flags = {
        "--verbose", "--timeline-mark-cycles", "--autotune",
        "--hierarchical-allreduce", "--gloo", "--mpi", "-h", "--help",
        "-cb", "--check-build",
    }
    check_build = False
    config_path = None
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--":
            break
        if a in ("-cb", "--check-build"):
            check_build = True
            i += 1
        elif a.startswith("--config-file="):
            config_path = a.split("=", 1)[1]
            i += 1
        elif a == "--config-file":
            if i + 1 < len(argv):
                config_path = argv[i + 1]
            i += 2
        elif a.startswith("-"):
            i += 1 if (a in no_value_flags or "=" in a) else 2
        else:
            break  # first positional = start of the launched command
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu job across hosts/chips.",
        # abbreviations would desync the exact-string pre-scan above
        # (e.g. --config would reach argparse but not the scan)
        allow_abbrev=False,
    )
    p.add_argument("-cb", "--check-build", action="store_true",
                   help="print the framework/controller/op build summary "
                        "and exit (ref: horovodrun --check-build [V])")
    p.add_argument("--config-file", default=None,
                   help="params YAML; CLI flags override its values "
                        "(keys = long option names, one nesting level "
                        "joins with a dash)")
    p.add_argument("-np", "--num-proc", type=int,
                   required=config_path is None and not check_build,
                   help="total number of ranks (chips)")
    p.add_argument("-H", "--hosts", default=None,
                   help="comma-separated host:slots list")
    p.add_argument("--hostfile", default=None,
                   help="file with one 'host slots=N' per line")
    p.add_argument("--placement", choices=("per-host", "per-slot", "auto"),
                   default="auto",
                   help="process placement: per-host (one process per "
                        "host driving its chips), per-slot (localhost CPU "
                        "simulation, one process per rank), auto = "
                        "per-slot iff every host is local and has no "
                        "chips (or JAX_PLATFORMS=cpu)")
    p.add_argument("--start-timeout", type=float, default=600.0)
    p.add_argument("--ssh-port", type=int, default=None)
    p.add_argument("--coordinator-port", type=int, default=9874,
                   help="fixed port for the jax.distributed coordinator "
                        "on the first worker host (multi-host jobs; "
                        "local jobs pick a free port automatically)")
    p.add_argument("--output-filename", default=None,
                   help="redirect each worker's stdout/stderr to "
                        "<output-filename>/rank.<N>.{out,err}")
    p.add_argument("--verbose", action="store_true")
    # runtime knobs forwarded as env (parity with horovodrun flags [V])
    p.add_argument("--fusion-threshold-mb", type=float, default=None)
    p.add_argument("--cycle-time-ms", type=float, default=None)
    p.add_argument("--cache-capacity", type=int, default=None)
    p.add_argument("--timeline-filename", default=None)
    p.add_argument("--timeline-mark-cycles", action="store_true")
    p.add_argument("--autotune", action="store_true")
    p.add_argument("--autotune-log-file", default=None)
    p.add_argument("--log-level", default=None)
    p.add_argument("--stall-timeout", type=float, default=None)
    p.add_argument("--hierarchical-allreduce", action="store_true")
    # elastic mode (ref: horovodrun --host-discovery-script/--min-np/
    # --max-np, horovod/runner/launch.py [V]): supervises gangs through
    # elastic.ElasticDriver instead of a one-shot launch
    p.add_argument("--host-discovery-script", default=None,
                   help="executable printing 'host:slots' per line; "
                        "presence switches hvdrun into elastic mode")
    p.add_argument("--min-np", type=int, default=None,
                   help="elastic: minimum world size (default: -np)")
    p.add_argument("--max-np", type=int, default=None,
                   help="elastic: maximum world size (default: -np)")
    p.add_argument("--slots-per-host", type=int, default=None,
                   help="elastic: override slots per discovered host")
    p.add_argument("--reset-limit", type=int, default=None,
                   help="elastic: max gang restarts before giving up")
    # accepted for script compat; the data plane is always XLA/ICI here
    p.add_argument("--gloo", action="store_true",
                   help="accepted for compatibility (no-op: TPU data "
                        "plane is XLA collectives)")
    p.add_argument("--mpi", action="store_true",
                   help="accepted for compatibility (no-op)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="program and args to launch on every worker")
    if config_path is not None:
        p.set_defaults(**_load_config_file(config_path, p))
    args = p.parse_args(argv)
    if args.num_proc is None and not args.check_build:
        p.error("-np/--num-proc is required (on the CLI or in "
                "--config-file)")
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    return args


def _resolve_hosts(args: argparse.Namespace) -> List[HostInfo]:
    if args.hosts and args.hostfile:
        raise ValueError("use either -H/--hosts or --hostfile, not both")
    if args.hosts:
        return parse_hosts(args.hosts)
    if args.hostfile:
        return parse_hostfile(args.hostfile)
    # No explicit hosts: consult TPU slice metadata (the reference's
    # NIC-probing slot, SURVEY §2.5 → tpu_discovery) before assuming a
    # single local machine.
    from .tpu_discovery import discover_hosts

    hosts = discover_hosts()
    if len(hosts) == 1 and _is_local(hosts[0].hostname):
        # single-host: allow oversubscription up to the requested np
        return [HostInfo(hosts[0].hostname, max(hosts[0].slots, args.num_proc))]
    return hosts


def _auto_placement(hosts: Sequence[HostInfo], num_proc: int) -> str:
    """``--placement auto``. Remote hosts: per-host. All-local: per-slot
    — one CPU process per rank — only where the workers would run on
    the CPU anyway (no chips here, or an explicit ``JAX_PLATFORMS=cpu``).
    A local host WITH chips gets per-host: one process driving every
    local chip, because a chip belongs to one process at a time; per-slot
    there would quietly train on the CPU."""
    from .tpu_discovery import local_chip_count, wants_cpu

    if not all(_is_local(h.hostname) for h in hosts):
        return "per-host"
    chips = 0 if wants_cpu() else local_chip_count()
    if not chips:
        return "per-slot"
    if num_proc != chips:
        raise SystemExit(
            f"hvdrun: this host has {chips} TPU chips and one process "
            f"drives them all, so -np must be {chips} (got {num_proc}). "
            "For a CPU simulation say so: JAX_PLATFORMS=cpu hvdrun "
            "--placement per-slot ..."
        )
    return "per-host"


def _runtime_env(args: argparse.Namespace) -> Dict[str, str]:
    """CLI flags → HOROVOD_* env, the same translation horovodrun does
    (launch.py [V])."""
    env: Dict[str, str] = {}
    if args.fusion_threshold_mb is not None:
        env["HOROVOD_FUSION_THRESHOLD"] = str(
            int(args.fusion_threshold_mb * 1024 * 1024)
        )
    if args.cycle_time_ms is not None:
        env["HOROVOD_CYCLE_TIME"] = str(args.cycle_time_ms)
    if args.cache_capacity is not None:
        env["HOROVOD_CACHE_CAPACITY"] = str(args.cache_capacity)
    if args.timeline_filename:
        env["HOROVOD_TIMELINE"] = args.timeline_filename
    if args.timeline_mark_cycles:
        env["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
    if args.autotune:
        env["HOROVOD_AUTOTUNE"] = "1"
    if args.autotune_log_file:
        env["HOROVOD_AUTOTUNE_LOG"] = args.autotune_log_file
    if args.log_level:
        env["HOROVOD_LOG_LEVEL"] = args.log_level
    if args.stall_timeout is not None:
        env["HOROVOD_STALL_CHECK_TIME_SECONDS"] = str(args.stall_timeout)
    if args.hierarchical_allreduce:
        env["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
    return env


def worker_envs(
    slots: Sequence[SlotInfo],
    placement: str,
    rendezvous_addr: str,
    rendezvous_port: int,
    coordinator_port: int,
    secret_hex: str,
    extra: Optional[Dict[str, str]] = None,
) -> List[Dict[str, str]]:
    """Build the per-process environment blocks.

    per-host: one block per host (lead slot), process drives local_size
    chips. per-slot: one block per rank, each process is its own "host"
    with one device (CPU backend, jax.distributed over localhost).
    """
    extra = dict(extra or {})
    blocks: List[Dict[str, str]] = []
    if placement == "per-host":
        leads = [s for s in slots if s.local_rank == 0]
        n_proc = len(leads)
        for i, s in enumerate(leads):
            env = s.to_env()
            env.update(extra)
            env["HOROVOD_NUM_PROCESSES"] = str(n_proc)
            env["HOROVOD_PROCESS_ID"] = str(i)
            blocks.append(env)
    elif placement == "per-slot":
        n_proc = len(slots)
        for i, s in enumerate(slots):
            # each rank is a standalone 1-chip "host"
            env = SlotInfo(
                hostname=s.hostname,
                rank=s.rank,
                size=s.size,
                local_rank=0,
                local_size=1,
                cross_rank=i,
                cross_size=n_proc,
            ).to_env()
            env.update(extra)
            env["HOROVOD_NUM_PROCESSES"] = str(n_proc)
            env["HOROVOD_PROCESS_ID"] = str(i)
            # Per-slot is the CPU-backend local mode by contract (a chip
            # belongs to one process). Caller-passed env (extra) still
            # overrides.
            env.setdefault("JAX_PLATFORMS", "cpu")
            # One device per slot, whatever the ambient XLA_FLAGS say —
            # an inherited --xla_force_host_platform_device_count=8
            # (e.g. from a test harness) would give every rank 8 local
            # devices and a 8*np-device world. Caller-passed flags (via
            # `extra`) are preserved; only the device-count token is
            # replaced.
            base_flags = env.get(
                "XLA_FLAGS", os.environ.get("XLA_FLAGS", "")
            )
            kept = [
                token
                for token in base_flags.split()
                if "xla_force_host_platform_device_count" not in token
            ]
            env["XLA_FLAGS"] = " ".join(
                kept + ["--xla_force_host_platform_device_count=1"]
            )
            blocks.append(env)
    else:
        raise ValueError(f"unknown placement {placement!r}")
    # The jax.distributed coordinator runs inside process 0, i.e. on the
    # FIRST WORKER's host — not on the driver (which may be a separate
    # head node). Workers must dial that host. Loopback is only valid
    # when EVERY worker is local; in a mixed job remote workers need a
    # routable name for host 0.
    coordinator_host = blocks[0]["HOROVOD_HOSTNAME"]
    if all(_is_local(b["HOROVOD_HOSTNAME"]) for b in blocks):
        coordinator_host = "127.0.0.1"
    for env in blocks:
        env["HOROVOD_CONTROLLER"] = "tpu"
        env["HOROVOD_GLOO_RENDEZVOUS_ADDR"] = rendezvous_addr
        env["HOROVOD_GLOO_RENDEZVOUS_PORT"] = str(rendezvous_port)
        env["HOROVOD_SECRET_KEY"] = secret_hex
        if int(env["HOROVOD_NUM_PROCESSES"]) > 1:
            env["HOROVOD_COORDINATOR_ADDR"] = coordinator_host
            env["HOROVOD_COORDINATOR_PORT"] = str(coordinator_port)
    return blocks


def _ssh_wrap(hostname: str, ssh_port: Optional[int],
              env: Dict[str, str], command: Sequence[str]) -> List[str]:
    """Remote exec via ssh with explicit env exports — the reference's
    gloo_run launch shape (gloo_run.py [V]).

    The HMAC secret is deliberately NOT exported on the command line
    (visible to every local user via /proc/<pid>/cmdline); it is read
    from ssh's stdin instead — launch_processes pipes it in.
    """
    env = {k: v for k, v in env.items() if k != "HOROVOD_SECRET_KEY"}
    exports = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in sorted(env.items())
    )
    remote = (
        "IFS= read -r HOROVOD_SECRET_KEY; export HOROVOD_SECRET_KEY; "
        f"cd {shlex.quote(os.getcwd())} && env {exports} "
        + " ".join(shlex.quote(c) for c in command)
    )
    cmd = ["ssh", "-o", "StrictHostKeyChecking=no"]
    if ssh_port:
        cmd += ["-p", str(ssh_port)]
    cmd += [hostname, remote]
    return cmd


def launch_processes(
    blocks: List[Dict[str, str]],
    command: Sequence[str],
    hostnames: List[str],
    ssh_port: Optional[int] = None,
    output_filename: Optional[str] = None,
    start_timeout: float = 600.0,
    verbose: bool = False,
) -> int:
    """Start every worker, wait, kill the rest on first failure.

    Returns the first non-zero exit code, or 0. (ref §3.3: "driver
    collects exit codes; on any nonzero → terminate all".)
    """
    procs: List[subprocess.Popen] = []
    files = []
    try:
        for env_block, hostname in zip(blocks, hostnames):
            secret_stdin = None
            if _is_local(hostname):
                full_env = dict(os.environ)
                full_env.update(env_block)
                # Workers must resolve the same horovod_tpu the driver
                # runs from, even when launched as `python script.py`
                # (script-dir-only sys.path).
                cwd = os.getcwd()
                prior = full_env.get("PYTHONPATH")
                full_env["PYTHONPATH"] = (
                    cwd if not prior else cwd + os.pathsep + prior
                )
                cmd = list(command)
            else:
                full_env = None
                cmd = _ssh_wrap(hostname, ssh_port, env_block, command)
                secret_stdin = env_block.get("HOROVOD_SECRET_KEY", "")
            stdout = stderr = None
            if output_filename:
                os.makedirs(output_filename, exist_ok=True)
                r = env_block["HOROVOD_RANK"]
                stdout = open(os.path.join(output_filename, f"rank.{r}.out"), "wb")
                stderr = open(os.path.join(output_filename, f"rank.{r}.err"), "wb")
                files += [stdout, stderr]
            if verbose:
                print(f"[hvdrun] rank {env_block['HOROVOD_RANK']} on "
                      f"{hostname}: {' '.join(cmd)}", file=sys.stderr)
            proc = subprocess.Popen(
                cmd, env=full_env, stdout=stdout, stderr=stderr,
                stdin=subprocess.PIPE if secret_stdin is not None else None,
            )
            if secret_stdin is not None:
                proc.stdin.write(secret_stdin.encode() + b"\n")
                proc.stdin.close()
            procs.append(proc)
        deadline = time.monotonic() + start_timeout
        exit_code = 0
        pending = set(range(len(procs)))
        while pending:
            for i in list(pending):
                rc = procs[i].poll()
                if rc is not None:
                    pending.discard(i)
                    if rc != 0 and exit_code == 0:
                        exit_code = rc
                        for j in pending:
                            procs[j].send_signal(signal.SIGTERM)
                        deadline = min(deadline, time.monotonic() + 15)
            if pending:
                if time.monotonic() > deadline:
                    for j in pending:
                        procs[j].kill()
                    if exit_code == 0:
                        exit_code = 124
                    break
                time.sleep(0.05)
        for prc in procs:
            try:
                prc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                prc.kill()
        return exit_code
    finally:
        # A mid-spawn exception must not orphan already-started workers.
        for prc in procs:
            if prc.poll() is None:
                prc.kill()
        for f in files:
            f.close()


def _run_elastic(args: argparse.Namespace) -> int:
    """Elastic mode: hand the job to ElasticDriver (ref: horovodrun's
    elastic launch, gloo_run elastic path [V])."""
    from ..elastic.driver import ElasticDriver
    from ..elastic.discovery import HostDiscoveryScript

    # `is None` (not `or`): --min-np 0 is an explicit value, not unset
    min_np = args.num_proc if args.min_np is None else args.min_np
    max_np = args.num_proc if args.max_np is None else args.max_np
    if min_np < 1 or max_np < min_np:
        raise SystemExit(
            f"hvdrun: inconsistent elastic bounds min_np={min_np} "
            f"max_np={max_np} (need 1 <= min-np <= max-np)"
        )
    driver = ElasticDriver(
        discovery=HostDiscoveryScript(args.host_discovery_script),
        command=args.command,
        min_np=min_np,
        max_np=max_np,
        slots_per_host=args.slots_per_host,
        placement=args.placement,
        start_timeout=args.start_timeout,
        output_filename=args.output_filename,
        reset_limit=args.reset_limit,
        extra_env=_runtime_env(args),
        ssh_port=args.ssh_port,
        verbose=args.verbose,
    )
    try:
        return driver.run()
    finally:
        driver.shutdown()


def _check_build() -> int:
    """Print the build summary (ref: horovodrun --check-build, which
    renders Available Frameworks / Controllers / Tensor Operations from
    the compiled-in feature set [V]). Here the feature set is determined
    at runtime: framework rows probe the shim imports, controller and
    op rows come from the basics predicates — the data plane is always
    XLA collectives over ICI, so the op column reports [X] XLA and [ ]
    for every GPU-era transport the reference could compile in."""
    from horovod_tpu.common import basics

    def _probe(modname):
        try:
            __import__(modname)
            return True
        except Exception:
            return False

    def box(flag):
        return "[X]" if flag else "[ ]"

    lines = [
        "Horovod-TPU v" + getattr(
            __import__("horovod_tpu"), "__version__", "?"),
        "",
        "Available Frameworks:",
        f"    {box(True)} JAX / Flax",
        f"    {box(_probe('torch'))} PyTorch (host bridge)",
        f"    {box(_probe('tensorflow'))} TensorFlow (host bridge)",
        f"    {box(_probe('mxnet'))} MXNet (host bridge)",
        "",
        "Available Controllers:",
        f"    {box(basics.mpi_built())} MPI",
        f"    {box(basics.gloo_built())} Gloo",
        f"    {box(True)} jax.distributed (TPU coordination service)",
        "",
        "Available Tensor Operations:",
        f"    {box(basics.nccl_built())} NCCL",
        f"    {box(basics.ddl_built())} DDL",
        f"    {box(basics.ccl_built())} CCL",
        f"    {box(basics.mpi_built())} MPI",
        f"    {box(basics.gloo_built())} Gloo",
        f"    {box(basics.xla_built())} XLA collectives (ICI/DCN)",
    ]
    print("\n".join(lines))
    return 0


def run_commandline(argv: Optional[Sequence[str]] = None) -> int:
    args = parse_args(argv)
    if args.check_build:
        return _check_build()
    if not args.command:
        print("hvdrun: no command given", file=sys.stderr)
        return 2
    if args.host_discovery_script:
        return _run_elastic(args)
    hosts = _resolve_hosts(args)
    slots = assign_slots(hosts, args.num_proc)
    placement = args.placement
    if placement == "auto":
        placement = _auto_placement(hosts, args.num_proc)
    if placement == "per-slot":
        print(f"[hvdrun] placement per-slot: {args.num_proc} CPU "
              "processes, one device each (JAX_PLATFORMS=cpu)",
              file=sys.stderr)
    secret = make_secret_key()
    server = RendezvousServer(secret_key=secret)
    rendezvous_port = server.start()
    all_local = all(_is_local(h.hostname) for h in hosts)
    addr = "127.0.0.1" if all_local else socket.getfqdn()
    # Local: probe a genuinely free port (driver host == coordinator
    # host). Remote: the coordinator binds on the first worker, which we
    # cannot probe from here — use the fixed, documented port.
    coordinator_port = _free_port() if all_local else args.coordinator_port
    try:
        blocks = worker_envs(
            slots, placement, addr, rendezvous_port, coordinator_port,
            secret.hex(), extra=_runtime_env(args),
        )
        hostnames = [b["HOROVOD_HOSTNAME"] for b in blocks]
        return launch_processes(
            blocks, args.command, hostnames,
            ssh_port=args.ssh_port,
            output_filename=args.output_filename,
            start_timeout=args.start_timeout,
            verbose=args.verbose,
        )
    finally:
        server.stop()


def run(
    command: Sequence[str],
    np: int,
    hosts: Optional[str] = None,
    **cli_kwargs,
) -> int:
    """Programmatic launch — parity with ``horovod.run.run()`` [V]."""
    argv: List[str] = ["-np", str(np)]
    if hosts:
        argv += ["-H", hosts]
    for key, value in cli_kwargs.items():
        flag = "--" + key.replace("_", "-")
        if value is True:
            argv.append(flag)
        elif value is not None and value is not False:
            argv += [flag, str(value)]
    argv += ["--", *command]
    return run_commandline(argv)


def main() -> None:
    sys.exit(run_commandline())
