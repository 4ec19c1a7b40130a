"""Chaos smoke gate (ci.sh): the control plane survives its own medicine.

Runs a short multi-process elastic job under a seeded ``FaultPlan``:

* every worker's FIRST rendezvous-KV request eats an injected
  connection reset (``kv.request@1:reset``) and must absorb it through
  the shared ``RetryPolicy``;
* ONE worker (local rank 0 of the ``127.0.0.1`` "host") SIGKILLs
  itself at training step 3 of epoch 0 (``train.step@3:kill``), so the
  driver must blacklist that host and gang-restart the 8-worker job
  down to 6;
* the restarted gang completes, and rank 0 of the final epoch serves
  ``/metrics`` so this gate asserts — over the live scrape endpoint —
  nonzero ``hvd_retry_*`` counters and ``hvd_faults_injected`` >= 1.

Asserts: driver exit code 0, EXACTLY one gang restart (8 -> 6), the
expected per-epoch result files, and the scraped counters. Exit 0 on
success; any assertion failure is a CI failure.

An **integrity drill** (PR 7) runs first, in its own subprocess: a
guarded training loop on the 8-device CPU mesh eats one injected NaN
step (``train.nan@3:nan`` — the update must be SKIPPED and
``hvd_guard_nonfinite_steps`` counted) and one injected checkpoint
bitflip (``checkpoint.save@2:bitflip`` — ``restore_latest_good`` must
fall back past the digest mismatch), with every counter asserted over
the worker's live ``/metrics`` scrape.

A **serve-failover drill** (PR 19) runs last: a two-worker serving
fleet takes a burst of identical temperature-0 requests through the
Router; one worker is SIGKILLed mid-burst (in-flight requests REPLAYED
on the survivor — zero client-visible errors, every response
bit-identical) and a third worker is then SIGTERMed with a short
``HOROVOD_SERVE_DRAIN_DEADLINE_S`` so its in-flight sequences
live-migrate to the survivor (``hvd_serve_migrations_in`` on the
survivor's live scrape) and still answer the original clients. The
drill runs with the fleet TRACE plane on and asserts its contracts
under chaos: hedge and replay legs surface as tagged SIBLING
``route.attempt`` spans under one route root, and a live-migrated
request assembles (this client's ring + the survivor's live
``/traces`` scrape + the SIGTERMed worker's crash-drained ``.spans``
file) into a single connected trace spanning >= 3 processes.

A **standby-swap drill** (PR 18): the same SIGKILL-a-worker
story, twice — once cold (no cache, no standby) and once with
``HOROVOD_WARM_STANDBY=1`` + a shared ``HOROVOD_EXE_CACHE``. In the
warm pass the kill lands only after the driver's warmer announces
``armed`` over rendezvous KV; the restart swaps the standby host into
the gang (exactly ONE gang restart — the swap-in costs zero additional
resets), every survivor resolves its compile-heavy executable from the
persistent cache (``exe_cache.misses == 0`` — zero new compiles), and
the live-scraped ``hvd_elastic_restart_ms`` beats the cold pass, whose
restarted workers each paid the multi-second XLA recompile.
"""

import itertools
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

# runnable as `python scripts/chaos_smoke.py` from the repo root
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
# the failover drill drives scripts/trace_assemble.py as a library
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

WORKER = """\
import json, os, sys, time
sys.path.insert(0, os.getcwd())
os.environ.setdefault("JAX_PLATFORMS", "cpu")
rank = int(os.environ["HOROVOD_RANK"])
epoch = int(os.environ.get("HOROVOD_ELASTIC_EPOCH", "0"))
host = os.environ.get("HOROVOD_HOSTNAME", "")
workdir = os.environ["CHAOS_SMOKE_DIR"]

from horovod_tpu.common import telemetry
from horovod_tpu.common.config import Config
from horovod_tpu.common.metrics import registry
from horovod_tpu.runner.rendezvous import _client_from_cfg
from horovod_tpu.testing import chaos

# exactly ONE victim: per-slot placement makes every process its own
# "host" (local_rank 0), so the 127.0.0.1 workers elect the victim
# through an exclusive lock file instead
victim = False
if epoch == 0 and host == "127.0.0.1":
    try:
        fd = os.open(
            os.path.join(workdir, "victim.lock"),
            os.O_CREAT | os.O_EXCL | os.O_WRONLY,
        )
        os.close(fd)
        victim = True
    except FileExistsError:
        pass
if victim:
    # the victim: same seeded plan PLUS a mid-run SIGKILL at step 3.
    # It holds its fire until every sibling has written its epoch-0
    # result, so the driver's gang-reap after the kill can never race
    # the survivors' dumps (8 concurrent interpreter starts skew by
    # seconds on a loaded CI box).
    chaos.configure("seed=11;kv.request@1:reset;train.step@3:kill")
    world = int(os.environ["HOROVOD_SIZE"])
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        done = [
            n for n in os.listdir(workdir) if n.startswith("result.e0.")
        ]
        if len(done) >= world - 1:
            break
        time.sleep(0.05)
else:
    assert chaos.active() is not None, "fault plan env did not load"

cfg = Config.from_env()
client = _client_from_cfg(cfg)
# rendezvous traffic: hit 1 eats the injected reset; RetryPolicy absorbs
client.put("smoke", str(rank), b"hello")
assert client.get("smoke", str(rank)) == b"hello"

hub = telemetry.hub()
for step in range(5):
    hub.step_begin(step)
    chaos.inject("train.step")  # the victim dies here at step 3
    time.sleep(0.02)            # "training"
    hub.step_end()

out = os.path.join(workdir, f"result.e{epoch}.r{rank}.json")
with open(out + ".tmp", "w") as f:
    json.dump(
        {"epoch": epoch, "rank": rank, "metrics": registry.snapshot()}, f
    )
os.replace(out + ".tmp", out)

if epoch >= 1 and rank == 0:
    # serve the live scrape endpoint until the gate has read it
    server = telemetry.MetricsServer(port=0)
    port = server.start()
    port_file = os.path.join(workdir, "scrape_port")
    with open(port_file + ".tmp", "w") as f:
        f.write(str(port))
    os.replace(port_file + ".tmp", port_file)
    ack = os.path.join(workdir, "scraped.ok")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not os.path.exists(ack):
        time.sleep(0.1)
if epoch == 0:
    time.sleep(120)  # park; the gang restart reaps us
sys.exit(0)
"""


def _prom_value(text: str, name: str) -> float:
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise AssertionError(f"metric {name} not in scrape:\n{text[:600]}")


def _prom_value_or(text: str, name: str, default: float) -> float:
    """A counter that never incremented is ABSENT from the scrape."""
    try:
        return _prom_value(text, name)
    except AssertionError:
        return default


INTEGRITY_WORKER = """\
import json, os, sys
sys.path.insert(0, os.getcwd())
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
workdir = os.environ["CHAOS_SMOKE_DIR"]

import jax, jax.numpy as jnp, optax
from jax.sharding import PartitionSpec as P
import horovod_tpu as hvd
from jax import shard_map
from horovod_tpu.common.metrics import registry
from horovod_tpu.checkpoint import CheckpointManager
from horovod_tpu.common import telemetry
from horovod_tpu.testing import chaos

# the seeded integrity plan: NaN at training step 3, bitflip on the
# SECOND checkpoint save
chaos.configure("seed=11;train.nan@3:nan;checkpoint.save@2:bitflip")

hvd.init()
world = hvd.size()
mesh = hvd.mesh()
opt = hvd.DistributedOptimizer(
    optax.sgd(0.1), op=hvd.Sum, grad_guard=True, guard_max_skips=0,
    overlap_buckets=2,
)
# non-constant values: a constant array compresses to nothing and
# the bitflip would land in container slack instead of payload
params = {"w": jnp.linspace(1.0, 2.0, 4096, dtype=jnp.float32)}
state = opt.init(params)

@jax.jit
def step(grads, state, params):
    def body(g, s, p):
        g = jax.tree_util.tree_map(lambda x: x[0], g)
        u, s2 = opt.update(g, s, p)
        return jax.tree_util.tree_map(lambda a, b: a + b, p, u), s2
    return shard_map(
        body, mesh=mesh, in_specs=(P(hvd.WORLD_AXIS), P(), P()),
        out_specs=(P(), P()), check_vma=False,
    )(grads, state, params)

ckpt = CheckpointManager(os.path.join(workdir, "ckpt"), async_save=False)
losses = []
for i in range(1, 7):
    g = {"w": jnp.ones((world, 4096), jnp.float32)}
    if chaos.inject("train.nan") == "nan":
        g = {"w": g["w"].at[0, 0].set(jnp.nan)}
    params, state = step(g, state, params)
    jax.block_until_ready(params["w"])
    losses.append(float(params["w"][0]))
    if i in (2, 4):
        # save hit 2 (i == 4) eats the bitflip
        ckpt.save(i, {"params": params, "i": i})
ckpt.wait_until_finished()

# the NaN step was SKIPPED: params advanced 5 times, not 6
assert int(state.guard_skips) == 1, int(state.guard_skips)
assert abs(losses[-1] - (1.0 - 0.1 * 8 * 5)) < 1e-5, losses

# the bitflipped newest checkpoint is bypassed via digest verification
like = {"params": params, "i": 0}
got_step, _ = ckpt.restore_latest_good(like=like)
assert got_step == 2, f"expected fallback to step 2, got {got_step}"
snap = registry.snapshot()
assert snap.get("guard.nonfinite_steps", 0) >= 1, snap
assert snap.get("checkpoint.digest_mismatch", 0) >= 1, snap
assert snap.get("checkpoint.fallback", 0) >= 1, snap

# serve the counters for the gate's live scrape
server = telemetry.MetricsServer(port=0)
port = server.start()
port_file = os.path.join(workdir, "integrity_port")
with open(port_file + ".tmp", "w") as f:
    f.write(str(port))
os.replace(port_file + ".tmp", port_file)
import time
ack = os.path.join(workdir, "integrity.ok")
deadline = time.monotonic() + 30
while time.monotonic() < deadline and not os.path.exists(ack):
    time.sleep(0.1)
sys.exit(0)
"""


def integrity_drill() -> None:
    """One injected NaN step + one injected checkpoint bitflip in a
    guarded training loop; counters asserted over the live scrape."""
    import subprocess

    workdir = tempfile.mkdtemp(prefix="hvd-integrity-smoke-")
    script = os.path.join(workdir, "integrity_worker.py")
    with open(script, "w") as f:
        f.write(INTEGRITY_WORKER)
    env = dict(os.environ)
    env["CHAOS_SMOKE_DIR"] = workdir
    env.pop("HOROVOD_FAULT_PLAN", None)
    proc = subprocess.Popen([sys.executable, script], env=env)
    try:
        port_file = os.path.join(workdir, "integrity_port")
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not os.path.exists(port_file):
            if proc.poll() is not None:
                raise AssertionError(
                    f"integrity worker died rc={proc.returncode}"
                )
            time.sleep(0.1)
        assert os.path.exists(port_file), "integrity worker never served"
        with open(port_file) as f:
            port = int(f.read().strip())
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()
        assert _prom_value(text, "hvd_guard_nonfinite_steps") >= 1
        assert _prom_value(text, "hvd_checkpoint_digest_mismatch") >= 1
        assert _prom_value(text, "hvd_checkpoint_fallback") >= 1
        assert _prom_value(text, "hvd_faults_injected") >= 2
        ack = os.path.join(workdir, "integrity.ok")
        with open(ack + ".tmp", "w") as f:
            f.write("ok")
        os.replace(ack + ".tmp", ack)
        proc.wait(timeout=30)
        assert proc.returncode == 0, f"integrity worker rc={proc.returncode}"
    finally:
        if proc.poll() is None:
            proc.kill()
    print(
        "integrity-drill OK: NaN step skipped, bitflipped checkpoint "
        "bypassed via digest, counters live on /metrics"
    )


STANDBY_WORKER = """\
import json, os, signal, sys, time
sys.path.insert(0, os.getcwd())
os.environ.setdefault("JAX_PLATFORMS", "cpu")
rank = int(os.environ["HOROVOD_RANK"])
epoch = int(os.environ.get("HOROVOD_ELASTIC_EPOCH", "0"))
host = os.environ.get("HOROVOD_HOSTNAME", "")
workdir = os.environ["CHAOS_SMOKE_DIR"]

from horovod_tpu.common import telemetry
from horovod_tpu.common.config import Config
from horovod_tpu.common.metrics import registry
from horovod_tpu.elastic.worker import WorkerNotificationManager
from horovod_tpu.runner.rendezvous import _client_from_cfg

import jax
import jax.numpy as jnp

def chain(x):
    for i in range(220):
        x = jnp.tanh(x @ x.T * (1.0 + 0.01 * i) + i) @ (x * 0.5 + 1.0)
        if i % 7 == 0:
            x = jax.nn.softmax(x, axis=-1) + x
    return x

# resolve the gang's one executable through the persistent cache: a
# cold worker pays the multi-second XLA compile, a warm-restarted one
# deserializes the epoch-0 entry in milliseconds — THE delta the
# restart clock below exists to show
t0 = time.time()
lowered = jax.jit(chain).lower(jnp.ones((48, 48), jnp.float32))
if os.environ.get("HOROVOD_EXE_CACHE"):
    from horovod_tpu.common import exe_cache
    exe, hit = exe_cache.get_or_compile(lowered, "smoke.chain")
    # drain the write-behind BEFORE parking: epoch-0 workers are
    # reaped by SIGTERM, which never runs atexit hooks
    assert exe_cache.flush(60), "exe-cache write-behind did not drain"
else:
    exe, hit = lowered.compile(), False
resolve_ms = (time.time() - t0) * 1e3

# the executable is READY: close the restart clock exactly the way a
# real worker's init does (the driver stamped wall time at teardown)
client = _client_from_cfg(Config.from_env())
WorkerNotificationManager.__new__(
    WorkerNotificationManager
)._publish_restart_ms(client, str(epoch))

out = os.path.join(workdir, f"result.e{epoch}.r{rank}.json")
with open(out + ".tmp", "w") as f:
    json.dump({
        "epoch": epoch, "rank": rank, "host": host, "hit": bool(hit),
        "resolve_ms": resolve_ms, "metrics": registry.snapshot(),
    }, f)
os.replace(out + ".tmp", out)

# exactly ONE victim: the 127.0.0.1 workers elect through an exclusive
# lock file (per-slot placement makes every process its own "host")
victim = False
if epoch == 0 and host == "127.0.0.1":
    try:
        fd = os.open(
            os.path.join(workdir, "victim.lock"),
            os.O_CREAT | os.O_EXCL | os.O_WRONLY,
        )
        os.close(fd)
        victim = True
    except FileExistsError:
        pass
if victim:
    # hold fire until every sibling has dumped its epoch-0 result AND
    # the gate has confirmed the standby is armed (kill.go) — the
    # contract under test is a SIGKILL *with one standby armed*
    world = int(os.environ["HOROVOD_SIZE"])
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        done = [
            n for n in os.listdir(workdir) if n.startswith("result.e0.")
        ]
        if len(done) >= world and os.path.exists(
            os.path.join(workdir, "kill.go")
        ):
            os.kill(os.getpid(), signal.SIGKILL)
        time.sleep(0.05)
    sys.exit(3)  # gate timed out; surface as a worker failure

if epoch >= 1 and rank == 0:
    # serve the live scrape endpoint until the gate has read it
    server = telemetry.MetricsServer(port=0)
    port = server.start()
    port_file = os.path.join(workdir, "standby_port")
    with open(port_file + ".tmp", "w") as f:
        f.write(str(port))
    os.replace(port_file + ".tmp", port_file)
    ack = os.path.join(workdir, "standby.ok")
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and not os.path.exists(ack):
        time.sleep(0.1)
if epoch == 0:
    time.sleep(180)  # park; the gang restart reaps us
sys.exit(0)
"""


def _touch(path: str) -> None:
    with open(path + ".tmp", "w") as f:
        f.write("ok")
    os.replace(path + ".tmp", path)


def standby_swap_drill() -> None:
    """PR 18: SIGKILL a worker with one warm standby armed — the swap-in
    must cost zero additional gang restarts, the survivors must resolve
    their executables with ZERO new compiles, and the live-scraped
    ``elastic.restart_ms`` must beat a cold (no-cache, no-standby)
    baseline of the same drill."""
    import socket

    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner.hosts import HostInfo

    cache = tempfile.mkdtemp(prefix="hvd-standby-exe-cache-")
    # three *local* host labels so both the gang and the warmer launch
    # as plain subprocesses; reservation takes the tail of the sorted
    # list, so the standby is never the victim host (letters sort above
    # "127.0.0.1")
    third = socket.gethostname()
    if third in ("localhost", "127.0.0.1", "::1"):
        third = "::1"

    def phase(warm: bool) -> float:
        workdir = tempfile.mkdtemp(prefix="hvd-standby-smoke-")
        script = os.path.join(workdir, "standby_worker.py")
        with open(script, "w") as f:
            f.write(STANDBY_WORKER)
        extra = {
            "CHAOS_SMOKE_DIR": workdir,
            "HOROVOD_RETRY_BACKOFF_MS": "10",
            # the warmer imports jax to preload cached executables; on
            # this CPU smoke box it must not probe for TPU metadata
            "JAX_PLATFORMS": "cpu",
        }
        if warm:
            extra["HOROVOD_EXE_CACHE"] = cache
            os.environ["HOROVOD_WARM_STANDBY"] = "1"
        else:
            os.environ.pop("HOROVOD_WARM_STANDBY", None)
        driver = ElasticDriver(
            FixedHosts([
                HostInfo("127.0.0.1", 2),
                HostInfo("localhost", 2),
                HostInfo(third, 2),
            ]),
            [sys.executable, script],
            min_np=4,  # epoch 1 (two hosts) must not re-reserve
            discovery_interval=0.2,
            output_filename=(
                os.path.join(workdir, "logs")
                if os.environ.get("CHAOS_SMOKE_LOGS")
                else None
            ),
            extra_env=extra,
        )
        result = {}
        try:
            driver.host_manager.refresh()
            t = threading.Thread(
                target=lambda: result.update(rc=driver.run())
            )
            t.start()
            if warm:
                # the kill lands only once the warmer has announced
                # ``armed`` over rendezvous KV (announce → stage → armed)
                armed = None
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline and not armed:
                    armed = next((
                        hn
                        for hn, ann in driver.standby_status().items()
                        if ann.get("state") == "armed"
                    ), None)
                    time.sleep(0.2)
                assert armed, (
                    f"no armed standby before the kill: "
                    f"{driver.standby_status()}"
                )
                assert armed != "127.0.0.1", "standby on the victim host"
            _touch(os.path.join(workdir, "kill.go"))

            # the post-swap rank 0 publishes its ephemeral scrape port
            port_file = os.path.join(workdir, "standby_port")
            deadline = time.monotonic() + 240
            while (
                time.monotonic() < deadline
                and not os.path.exists(port_file)
            ):
                time.sleep(0.1)
            assert os.path.exists(port_file), (
                "post-swap gang never served /metrics"
            )
            with open(port_file) as f:
                port = int(f.read().strip())
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as resp:
                text = resp.read().decode()

            restart_ms = _prom_value(text, "hvd_elastic_restart_ms")
            assert restart_ms > 0, restart_ms
            assert _prom_value(text, "hvd_elastic_restart_warm") == (
                1.0 if warm else 0.0
            )
            if warm:
                # the scraped survivor resolved from disk: zero compiles
                assert _prom_value(text, "hvd_exe_cache_hits") >= 1
                assert _prom_value_or(
                    text, "hvd_exe_cache_misses", 0
                ) == 0

            _touch(os.path.join(workdir, "standby.ok"))
            t.join(timeout=120)
            assert not t.is_alive(), "driver did not converge"
        finally:
            driver.shutdown()
            os.environ.pop("HOROVOD_WARM_STANDBY", None)

        assert result.get("rc") == 0, f"driver exit {result.get('rc')}"
        # the swap-in cost ZERO additional gang restarts
        assert driver._resets == 1, driver._resets
        assert driver.host_manager.is_blacklisted("127.0.0.1")

        def _results(prefix):
            out = []
            for name in os.listdir(workdir):
                if name.startswith(prefix):
                    with open(os.path.join(workdir, name)) as f:
                        out.append(json.load(f))
            return out

        e0, e1 = _results("result.e0."), _results("result.e1.")
        # cold: all 6 slots active in epoch 0; warm: one host held out
        assert len(e0) == (4 if warm else 6), [r["rank"] for r in e0]
        assert len(e1) == 4, [r["rank"] for r in e1]
        if warm:
            assert driver._standby_swapins == 1, driver._standby_swapins
            # the released standby actually serves in the new gang
            assert driver._standby_released & {
                r["host"] for r in e1
            }, (driver._standby_released, [r["host"] for r in e1])
            for r in e1:  # zero new compiles on ANY survivor
                assert r["hit"], r
                assert r["metrics"].get("exe_cache.misses", 0) == 0, r
        else:
            assert all(not r["hit"] for r in e1)
        return restart_ms

    cold_ms = phase(False)
    warm_ms = phase(True)
    assert warm_ms < cold_ms, (
        f"warm swap-in restart ({warm_ms:.0f} ms) did not beat the "
        f"cold baseline ({cold_ms:.0f} ms)"
    )
    print(
        f"standby-swap OK: armed standby swapped in on 1 gang restart, "
        f"0 new compiles on survivors, restart_ms {warm_ms:.0f} warm "
        f"vs {cold_ms:.0f} cold"
    )


SERVE_WORKER = """\
import os, sys
sys.path.insert(0, os.getcwd())
os.environ.setdefault("JAX_PLATFORMS", "cpu")
workdir = os.environ["CHAOS_SMOKE_DIR"]
rank = int(os.environ["HOROVOD_RANK"])

import jax
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.models.transformer import Transformer, TransformerConfig

cfg = TransformerConfig(
    vocab_size=61, num_layers=1, d_model=16, num_heads=2, d_ff=32,
    max_len=256, causal=True, dtype=jnp.float32,
)
model = Transformer(cfg)
# every worker seeds the SAME params: a temperature-0 request must
# answer bit-identically wherever a replay or migration lands it
params = model.init(
    jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), train=False
)
handle = hvd.serve(
    model, params, port=0, slots=4, max_len=256, max_new_tokens=200,
    addr="127.0.0.1", handle_sigterm=True, paged=True,
)
port_file = os.path.join(workdir, f"serve_port.r{rank}")
with open(port_file + ".tmp", "w") as f:
    f.write(str(handle.port))
os.replace(port_file + ".tmp", port_file)
handle.wait(timeout=600)  # SIGTERM drains (and migrates) via the hook
sys.exit(0)
"""


def serve_failover_drill() -> None:
    """PR 19: SIGKILL a serving worker mid-burst — the Router replays
    its in-flight requests on the survivor with zero client-visible
    errors and bit-identical temperature-0 output; then SIGTERM a
    worker under a short drain deadline — its in-flight sequences
    live-migrate to the survivor and still answer the original
    clients."""
    import signal
    import subprocess

    import trace_assemble
    from horovod_tpu.analysis import trace_merge
    from horovod_tpu.common import tracing
    from horovod_tpu.common.metrics import registry
    from horovod_tpu.runner.rendezvous import (
        RendezvousClient,
        RendezvousServer,
    )
    from horovod_tpu.runner.secret import make_secret_key
    from horovod_tpu.serving.frontend import Router

    os.environ["HOROVOD_RENDEZVOUS_BACKEND"] = "python"
    key = make_secret_key()
    server = RendezvousServer(secret_key=key)
    rdv_port = server.start()
    workdir = tempfile.mkdtemp(prefix="hvd-serve-failover-")
    script = os.path.join(workdir, "serve_worker.py")
    with open(script, "w") as f:
        f.write(SERVE_WORKER)

    def spawn(rank, extra_env=None):
        env = dict(os.environ)
        env.update({
            "CHAOS_SMOKE_DIR": workdir,
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_RANK": str(rank),
            "HOROVOD_RENDEZVOUS_BACKEND": "python",
            "HOROVOD_GLOO_RENDEZVOUS_ADDR": "127.0.0.1",
            "HOROVOD_GLOO_RENDEZVOUS_PORT": str(rdv_port),
            "HOROVOD_SECRET_KEY": key.hex(),
            # crash-safe span drain: a reaped worker leaves its trace
            # ring beside the flight recorder for the assembly below
            "HOROVOD_FLIGHT_RECORDER": os.path.join(
                workdir, f"flight.r{rank}.jsonl"
            ),
        })
        env.update(extra_env or {})
        return subprocess.Popen(
            [sys.executable, script], env=env, cwd=os.getcwd()
        )

    def wait_port(procs, rank):
        pf = os.path.join(workdir, f"serve_port.r{rank}")
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline and not os.path.exists(pf):
            assert procs[rank].poll() is None, (
                f"serve worker {rank} died rc={procs[rank].returncode}"
            )
            time.sleep(0.1)
        assert os.path.exists(pf), f"worker {rank} never served"
        with open(pf) as f:
            return int(f.read().strip())

    prompt = [7, 11, 13]
    procs = {0: spawn(0), 1: spawn(1)}
    try:
        ports = {r: wait_port(procs, r) for r in (0, 1)}
        client = RendezvousClient("127.0.0.1", rdv_port, secret_key=key)
        router = Router(client)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and len(router.snapshot()) < 2:
            time.sleep(0.2)
        assert set(router.snapshot()) == {0, 1}, router.snapshot()

        # ---- hedge leg: one request with an aggressive hedge delay —
        # both arms fire, first writer wins, and the race must be
        # legible as two tagged SIBLING route.attempt spans under one
        # route root in this process's trace ring
        hres = router.route(
            prompt, timeout=240.0, hedge_ms=1.0, request_id="hedge-0"
        )
        assert hres["status"] == "done", hres
        htid = hres.get("trace_id")
        assert htid, f"hedged result carries no trace_id: {hres}"
        # the losing arm closes its leg when its response finally
        # lands — poll until both legs are in the ring
        hlegs = []
        hdeadline = time.monotonic() + 120
        while time.monotonic() < hdeadline:
            hlegs = [
                s for s in tracing.recorder().spans()
                if s["trace_id"] == htid
                and s["name"] == "route.attempt"
            ]
            if len(hlegs) >= 2:
                break
            time.sleep(0.2)
        assert len(hlegs) >= 2, f"hedge fired no backup leg: {hlegs}"
        assert {
            (s.get("tags") or {}).get("hedge") for s in hlegs
        } >= {"primary", "backup"}, hlegs
        assert len({s["parent_id"] for s in hlegs}) == 1, (
            f"hedge arms are not siblings: {hlegs}"
        )
        houtcomes = {
            (s.get("tags") or {}).get("outcome") for s in hlegs
        }
        assert "ok" in houtcomes and "discarded" in houtcomes, hlegs

        # ---- replay leg: SIGKILL worker 0 mid-burst
        results, errors = {}, []

        def one(i):
            try:
                results[i] = router.route(
                    prompt, timeout=240.0, attempts=4,
                    request_id=f"burst-{i}",
                )
            except Exception as e:  # noqa: BLE001 — a failure IS the signal
                errors.append((i, e))

        before = registry.snapshot().get("serve.replays", 0.0)
        threads = [
            threading.Thread(target=one, args=(i,)) for i in range(12)
        ]
        for t in threads:
            t.start()
        time.sleep(0.8)  # mid-burst: first requests still in flight
        os.kill(procs[0].pid, signal.SIGKILL)
        for t in threads:
            t.join(timeout=300)
        assert not errors, f"client-visible failures: {errors[:3]}"
        assert len(results) == 12
        assert all(r["status"] == "done" for r in results.values())
        outs = {tuple(r["tokens"]) for r in results.values()}
        assert len(outs) == 1, (
            f"temp-0 outputs diverged across replay: {len(outs)} variants"
        )
        replays = registry.snapshot().get("serve.replays", 0.0) - before
        assert replays >= 1, "the kill was absorbed without any replay"
        # the replays are visible as tagged sibling spans: the leg that
        # died on the SIGKILLed worker closed outcome="replayed", and a
        # mode="replay" sibling under the same route root won
        ring = tracing.recorder().spans()
        rep_legs = [
            s for s in ring
            if s["name"] == "route.attempt"
            and (s.get("tags") or {}).get("outcome") == "replayed"
        ]
        assert rep_legs, "no route.attempt leg tagged outcome=replayed"
        rep_tids = {s["trace_id"] for s in rep_legs}
        ok_replays = [
            s for s in ring
            if s["name"] == "route.attempt"
            and s["trace_id"] in rep_tids
            and (s.get("tags") or {}).get("mode") == "replay"
            and (s.get("tags") or {}).get("outcome") == "ok"
        ]
        assert ok_replays, (
            "no winning mode=replay sibling beside a replayed leg"
        )
        rep_parent = {s["trace_id"]: s["parent_id"] for s in rep_legs}
        assert any(
            s["parent_id"] == rep_parent[s["trace_id"]]
            for s in ok_replays
        ), "replay legs are not siblings under the same route root"

        # ---- migration leg: SIGTERM worker 2 under a short deadline.
        # A 5ms per-step chaos delay slows decode to ~1s/sequence:
        # without it, CPU decode outruns the 0.25s metrics publish
        # interval and all sequences finish before the SIGTERM gate
        # below can catch them in flight (nothing left to migrate)
        procs[2] = spawn(
            2, {
                "HOROVOD_SERVE_DRAIN_DEADLINE_S": "0.05",
                "HOROVOD_FAULT_PLAN": "serve.worker_kill:p=1:delay:ms=5",
            }
        )
        port2 = wait_port(procs, 2)
        mig_results, mig_errors, mig_traces = {}, [], {}

        def mig_one(i):
            # each migration client mints its own trace root: the
            # traceparent rides to the doomed worker, the migrate
            # frames carry it to the survivor, and the assembly below
            # must stitch all three processes back together
            tctx = tracing.mint()
            span = tracing.root_span(
                "client.generate", tctx, request_id=f"mig-{i}"
            )
            headers = {"Content-Type": "application/json"}
            if tctx is not None:
                headers["traceparent"] = tctx.to_traceparent()
            body = json.dumps(
                {"tokens": prompt, "request_id": f"mig-{i}"}
            ).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port2}/generate", data=body,
                headers=headers,
                method="POST",
            )
            try:
                t_send = time.time()
                with urllib.request.urlopen(req, timeout=300) as resp:
                    mig_results[i] = json.loads(resp.read().decode())
                    tracing.tag_hop(
                        span, t_send, time.time(), resp.headers
                    )
                    mig_traces[i] = resp.headers.get("X-Trace-Id")
            except Exception as e:  # noqa: BLE001 — a failure IS the signal
                mig_errors.append((i, e))
            finally:
                if span is not None:
                    span.end()

        mthreads = [
            threading.Thread(target=mig_one, args=(i,)) for i in range(3)
        ]
        for t in mthreads:
            t.start()
        # SIGTERM only once decode is well under way (>= ~10 tokens per
        # sequence): the drill is about IN-FLIGHT sequences, not queued
        # ones, and the depth makes the history-prefix check meaningful
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port2}/metrics", timeout=10
            ) as resp:
                text = resp.read().decode()
            if _prom_value_or(text, "hvd_serve_tokens_out", 0) >= 30:
                break
            time.sleep(0.1)
        procs[2].send_signal(signal.SIGTERM)
        for t in mthreads:
            t.join(timeout=300)
        assert not mig_errors, f"migration leg failures: {mig_errors[:3]}"
        assert len(mig_results) == 3
        assert all(r["status"] == "done" for r in mig_results.values())
        # migration streams over the default int8 KV wire — lossy, so
        # greedy argmax after the resume point is only approximately
        # stable. The hard guarantees: every client gets its FULL
        # answer, and the generated history carried over the wire is
        # verbatim (>= 8 matching tokens: the >=10/sequence decoded
        # pre-SIGTERM, minus admission stagger) — migrated sequences
        # resume, they are never re-decoded or re-sampled
        ref = list(outs)[0]
        for i, r in sorted(mig_results.items()):
            toks = r["tokens"]
            assert len(toks) == len(ref), (i, len(toks), len(ref))
            shared = sum(
                1 for _ in itertools.takewhile(
                    lambda ab: ab[0] == ab[1], zip(ref, toks)
                )
            )
            assert shared >= 8, (
                f"mig-{i} shares only {shared} leading tokens with the "
                f"uninterrupted reference: carried history was lost"
            )
        # the survivor's LIVE scrape proves where the sequences landed.
        # Engine counters reach /metrics on the batcher's publish
        # interval, so poll rather than one-shot assert
        migrations_in = 0.0
        poll_deadline = time.monotonic() + 60
        while time.monotonic() < poll_deadline:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{ports[1]}/metrics", timeout=10
            ) as resp:
                text = resp.read().decode()
            migrations_in = _prom_value_or(text, "hvd_serve_migrations_in", 0)
            if migrations_in >= 1:
                break
            time.sleep(0.25)
        assert migrations_in >= 1, migrations_in
        procs[2].wait(timeout=60)

        # ---- the migrated request is ONE connected trace spanning
        # >= 3 processes: this client (its own ring), the SIGTERMed
        # worker (crash-drained <flight>.spans file), and the survivor
        # (live /traces scrape — itself an NTP edge)
        w1_spans, w1_edge = trace_assemble.scrape(
            f"http://127.0.0.1:{ports[1]}/traces"
        )
        mig_tids = {
            s["trace_id"] for s in w1_spans if s["name"] == "kv.migrate"
        }
        ours = {t for t in mig_traces.values() if t}
        assert ours, f"no X-Trace-Id echoed: {mig_traces}"
        migrated = mig_tids & ours
        assert migrated, (
            f"no kv.migrate span on the survivor belongs to a drill "
            f"request: {mig_tids} vs {ours}"
        )
        mig_tid = sorted(migrated)[0]
        w2_file = os.path.join(workdir, "flight.r2.jsonl.spans")
        assert os.path.exists(w2_file), (
            "SIGTERMed worker drained no span ring"
        )
        spans = (
            tracing.recorder().spans()
            + w1_spans
            + trace_assemble.load_file(w2_file)
        )
        tspans = trace_merge.filter_trace(spans, mig_tid)
        corrected, offsets = trace_merge.assemble(
            tspans, edges=[w1_edge] if w1_edge else [],
        )
        mprocs = {trace_merge.proc_key(s) for s in tspans}
        assert len(mprocs) >= 3, (
            f"migrated trace spans only {len(mprocs)} process(es): "
            f"{mprocs}"
        )
        assert mprocs <= set(offsets), (
            f"migrated trace not connected on one clock: "
            f"{mprocs - set(offsets)} unreachable"
        )
        mnames = {s["name"] for s in tspans}
        for needle in ("client.generate", "http.generate", "kv.migrate"):
            assert needle in mnames, (needle, sorted(mnames))
        assert all(
            a["ts_corrected"] <= b["ts_corrected"]
            for a, b in zip(corrected, corrected[1:])
        ), "assemble() did not sort by corrected time"
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        server.stop()
    print(
        f"serve-failover OK: {int(replays)} replay(s) after SIGKILL with "
        f"12/12 bit-identical answers, {int(migrations_in)} live "
        f"migration(s) after SIGTERM with 3/3 answered, migrated trace "
        f"assembled across {len(mprocs)} processes"
    )


def main() -> int:
    # fleet trace plane ON (full sampling) for the whole gate: the
    # serve-failover drill asserts the migrated request's assembled
    # trace, and the elastic drills record their cycle spans along the
    # way — chaos with tracing on is exactly the combination to guard
    os.environ["HOROVOD_TRACE"] = "1"
    os.environ["HOROVOD_TRACE_SAMPLE"] = "1.0"
    integrity_drill()
    workdir = tempfile.mkdtemp(prefix="hvd-chaos-smoke-")
    script = os.path.join(workdir, "worker.py")
    with open(script, "w") as f:
        f.write(WORKER)

    os.environ.pop("XLA_FLAGS", None)
    os.environ.pop("JAX_PLATFORMS", None)
    os.environ["HOROVOD_STRAGGLER_QUARANTINE_POLLS"] = "3"

    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner.hosts import HostInfo

    driver = ElasticDriver(
        FixedHosts([HostInfo("127.0.0.1", 2), HostInfo("localhost", 6)]),
        [sys.executable, script],
        min_np=1,
        discovery_interval=0.2,
        # CHAOS_SMOKE_LOGS=1 keeps per-rank worker logs for debugging
        output_filename=(
            os.path.join(workdir, "logs")
            if os.environ.get("CHAOS_SMOKE_LOGS")
            else None
        ),
        extra_env={
            "CHAOS_SMOKE_DIR": workdir,
            # the seeded plan: one KV reset per process, absorbed
            "HOROVOD_FAULT_PLAN": "seed=11;kv.request@1:reset",
            "HOROVOD_RETRY_BACKOFF_MS": "10",
        },
    )
    result = {}
    try:
        driver.host_manager.refresh()
        t = threading.Thread(target=lambda: result.update(rc=driver.run()))
        t.start()

        # the post-restart rank 0 publishes its ephemeral scrape port
        port_file = os.path.join(workdir, "scrape_port")
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not os.path.exists(port_file):
            time.sleep(0.1)
        assert os.path.exists(port_file), "post-restart gang never served"
        with open(port_file) as f:
            port = int(f.read().strip())
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()

        # the acceptance counters, read over the LIVE endpoint
        assert _prom_value(text, "hvd_retry_kv_request_attempts") > 0
        assert _prom_value(text, "hvd_retry_kv_request_retries") > 0, (
            "no absorbed retries on the scraped worker"
        )
        assert _prom_value(text, "hvd_faults_injected") >= 1
        assert _prom_value(text, "telemetry_step_ms_count") == 5

        # release the serving worker, then collect the driver
        ack = os.path.join(workdir, "scraped.ok")
        with open(ack + ".tmp", "w") as f:
            f.write("ok")
        os.replace(ack + ".tmp", ack)
        t.join(timeout=90)
        assert not t.is_alive(), "driver did not converge"
    finally:
        driver.shutdown()

    assert result.get("rc") == 0, f"driver exit {result.get('rc')}"
    assert driver._resets == 1, (
        f"expected exactly one gang restart, got {driver._resets}"
    )
    assert driver.host_manager.is_blacklisted("127.0.0.1")

    # epoch 0: the victim died at step 3 -> 7 of 8 results; epoch 1:
    # all 6 surviving slots (the victim's host lost BOTH) completed
    e0 = [n for n in os.listdir(workdir) if n.startswith("result.e0.")]
    e1 = [n for n in os.listdir(workdir) if n.startswith("result.e1.")]
    assert len(e0) == 7, e0
    assert len(e1) == 6, e1
    # every surviving worker absorbed its injected KV reset
    for name in e0 + e1:
        with open(os.path.join(workdir, name)) as f:
            snap = json.load(f)["metrics"]
        assert snap.get("retry.kv.request.retries", 0) > 0, name
        assert snap.get("faults_injected", 0) >= 1, name

    print(
        f"chaos-smoke OK: 1 gang restart (8->6), "
        f"{len(e0) + len(e1)} workers absorbed their KV flake, "
        f"scrape port {port}"
    )

    standby_swap_drill()
    serve_failover_drill()
    return 0


if __name__ == "__main__":
    sys.exit(main())
