"""The harness end to end on the CPU at the test size, for each loop kind:
the last line's keys, the order of dispatch and fetch, no compile in the
window, cells found by files alone, and no device metric from a CPU run."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import manifest
from benchmark.loops import train
from test_benchmark_correct import MANIFEST, drive

ROOT = manifest.ROOT
DEVICE_METRICS = (
    "model.mfu.train", "model.mfu.serve", "kernels.flash_ms_per_step",
    "flash_fwd_roofline", "flash_dq_roofline", "flash_dkv_roofline",
    "decode_step_roofline", "device.idle_share.train",
    "device.idle_share.serve", "device.peak_hbm_gib.train",
    "device.peak_hbm_gib.serve", "exchange.exposed_collective_ms",
)


def test_window_keeps_two_steps_in_flight_and_divides_by_the_last_stamp():
    log, now = [], [0.0]

    def dispatch():
        log.append(("dispatch", sum(1 for e in log if e[0] == "dispatch")))
        return log[-1][1]

    def fetch(i):
        log.append(("fetch", i))
        now[0] += 1.0  # each step takes one second of the fake clock
        return float(i)

    t0, stamps, losses, drained = train.drive_window(
        dispatch, fetch, seconds=3.5, clock=lambda: now[0])
    # step i+2 is always sent before step i is waited for
    for i in range(3):
        assert log.index(("dispatch", i + 2)) < log.index(("fetch", i))
    in_flight = 0
    for kind, _ in log:
        in_flight += 1 if kind == "dispatch" else -1
        assert in_flight <= train.IN_FLIGHT + 1
    # steps 0, 1, 2 arrived at 1, 2, 3 s; step 3 arrived at 4 s, too late
    assert (t0, stamps, losses) == (0.0, [1.0, 2.0, 3.0], [0.0, 1.0, 2.0])
    assert drained == 3 and log[-1] == ("fetch", 5)


@pytest.mark.parametrize("cell, devices", [
    ("test-train-1", 1), ("test-train-4", 4), ("test-serve", 1)])
def test_loop_end_to_end(cell, devices):
    line, stderr = drive(cell, 0, 1.5, devices=devices)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["count"] == devices
    assert line["compared"]["compiles_in_window"]["value"] == 0
    names = set(line["metrics"])
    assert "setup_s" in names and len(names) >= 2
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert stderr.strip().splitlines()[-1] == "correct = true"


def test_traced_run_on_the_cpu_prints_no_device_metric(tmp_path):
    """A per-layer metric added as a file and an entry alone is found; the
    readers of device metrics find no device in a CPU trace and stay
    silent."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(MANIFEST) as f:
        test = json.load(f)
    test["per_layer"] = [
        dict(m, workloads=["test-train-1"], moves="train_tokens_per_s"
             if m["moves"] != "setup_s" else "setup_s")
        for m in real["per_layer"]
        if "gpt2m-train-1chip" in m["workloads"]
        or m["name"] in DEVICE_METRICS
    ] + [{"name": "test.added_steps", "unit": "count", "better": "higher",
          "source": "program_counter", "layer": "Trainer step",
          "moves": "train_tokens_per_s", "workloads": ["test-train-1"]}]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(test))
    added = os.path.join(manifest.BENCH_DIR, "metrics", "test.added_steps.py")
    with open(added, "w") as f:
        f.write("def read(r):\n    return r['steps']\n")
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
            "--xla_force_host_platform_device_count=1"))
        code = (
            "import sys; sys.path.insert(0, %r); "
            "import drive; drive.MANIFEST = %r; "
            "drive.main(['test-train-1', '1', '1.0'])"
            % (os.path.dirname(os.path.abspath(__file__)), str(path)))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
    finally:
        os.remove(added)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    got = set(line["metrics"])
    assert not got & set(DEVICE_METRICS)
    assert {"init.trace_lower_s", "init.compile_s", "trainer.step_ms_p50",
            "trainer.stall_share", "test.added_steps"} <= got
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_no_chip_exits_non_zero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2m-train-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_every_cell_of_the_manifest_finds_its_files():
    m = manifest.load_manifest()
    for w in m["workloads"]:
        cell = manifest.Cell(m, w["name"])
        assert cell.loop().run
        for metric in cell.per_layer:
            reader = manifest.load_module("metrics", metric["name"])
            assert reader is not None and callable(reader.read), metric
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "limits", cell.name + ".json"))
    assert manifest.load_module("metrics", "no.such.metric") is None


def test_manifest_names_each_pair_of_config_and_traffic_once():
    # The check refuses the file before any run where two cells share a pair
    # (PR 24's first check: gpt2m-train-dp4 used the one-chip mix's name).
    m = manifest.load_manifest()
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs), pairs
    names = [w["name"] for w in m["workloads"]]
    assert len(set(names)) == len(names)
    assert all(len(w["why"]) <= 200 for w in m["workloads"])
