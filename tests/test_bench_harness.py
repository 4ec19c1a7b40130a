"""The bench harnesses are round artifacts — their sweep/efficiency
logic must hold without running a full benchmark (VERDICT r1 #3: a
world-size sweep with scaling_efficiency output, pod-ready)."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from bench_allreduce import (  # noqa: E402
    ring_factor,
    scaling_efficiency,
    sweep_worlds,
)


def test_sweep_worlds_small_box():
    assert sweep_worlds(1) == [1]
    assert sweep_worlds(8) == [1, 2, 4, 8]
    assert sweep_worlds(6) == [1, 2, 4, 6]


def test_sweep_worlds_pod_starts_at_8():
    """On a pod slice the sweep is the north star's 8→256 window."""
    assert sweep_worlds(256) == [8, 16, 32, 64, 128, 256]
    assert sweep_worlds(64) == [8, 16, 32, 64]


def test_ring_factor():
    assert ring_factor(1) == 1.0
    assert ring_factor(2) == 1.0
    assert abs(ring_factor(8) - 1.75) < 1e-12
    assert abs(ring_factor(256) - 2 * 255 / 256) < 1e-12


def test_scaling_efficiency_vs_base():
    base, eff = scaling_efficiency({1: 10.0, 2: 9.0, 4: 8.0})
    assert base == 1
    assert eff[1] == 1.0
    assert abs(eff[2] - 0.9) < 1e-12
    assert abs(eff[4] - 0.8) < 1e-12


def test_scaling_efficiency_empty():
    assert scaling_efficiency({}) == (None, {})


class TestNoHiddenFallback:
    """No chip is an error, not a smaller number: the entry points that
    report device metrics fail without an accelerator, the helpers they
    share raise instead of substituting, and the smoke's body holds its
    invariants on the CPU mesh."""

    @pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
    def test_no_accelerator_exits_nonzero_and_prints_no_result(self, script):
        from _hermetic import hermetic_cpu_env

        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, script)],
            env=hermetic_cpu_env(), cwd=_REPO, capture_output=True,
            text=True, timeout=120,
        )
        assert proc.returncode != 0
        assert not [
            ln for ln in proc.stdout.splitlines() if ln.startswith("{")
        ], proc.stdout
        assert "cpu" in proc.stderr

    def test_smoke_body_on_the_cpu_mesh(self):
        """The trainer chip_smoke.py runs on the chip, at tiny size on 8
        CPU devices: world-spanning all-reduce, one program."""
        import chip_smoke
        import horovod_tpu as hvd
        from horovod_tpu.models import TransformerConfig

        hvd.shutdown()
        try:
            report = chip_smoke.train_smoke(
                TransformerConfig.tiny(), steps=3, batch=2, seq=32
            )
        finally:
            hvd.shutdown()
        assert report["world"] == 8
        assert report["recompiles"] == 0
        assert report["allreduce"]["compiled"] >= 1
        assert report["allreduce"]["bytes"] >= report["param_bytes"]
        assert report["losses"][-1] < report["losses"][0]
        # off the TPU "auto" picks dense attention: nothing to find, and
        # the __main__ path (TPU only) is what insists on the kernels
        assert report["mosaic"]["tpu_custom_call"] == 0

    def test_compiled_allreduce_group_parser(self):
        import chip_smoke

        hlo = (
            "%ar.1 = f32[8] all-reduce(%x), replica_groups={{0,1,2,3}}, "
            "to_apply=%add\n"
            "%ar.2 = f32[8] all-reduce-start(%y), replica_groups=[1,4]<=[4]"
            ", to_apply=%add\n"
            "%ar.3 = f32[8] all-reduce(%z), replica_groups={{0,1},{2,3}}, "
            "to_apply=%add\n"
        )
        assert chip_smoke._spanning_allreduces_compiled(hlo, 4) == 2
        assert chip_smoke._spanning_allreduces_compiled(hlo, 2) == 1

    def test_unknown_device_kind_raises(self):
        import types

        import _benchlib

        v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        assert _benchlib.chip_peaks(v5e) == (197.0, 819.0)
        cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
        assert _benchlib.chip_peaks(cpu) == (None, None)
        other = types.SimpleNamespace(platform="tpu", device_kind="TPU v9")
        with pytest.raises(KeyError, match="TPU v9"):
            _benchlib.chip_peaks(other)

    def test_aot_compile_lets_the_error_out(self):
        import jax
        import jax.numpy as jnp

        import _benchlib

        bad = jax.jit(lambda x: x @ x)  # (3, 4) @ (3, 4) does not trace
        with pytest.raises(TypeError):
            _benchlib.aot_compile(bad, jnp.ones((3, 4)))

    def test_compile_cache_dir(self, monkeypatch, tmp_path):
        """JAX_COMPILATION_CACHE_DIR set: the code sets nothing. Unset:
        the one fixed path under the checkout."""
        import jax

        from horovod_tpu.common import compile_cache

        before = jax.config.jax_compilation_cache_dir
        try:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert compile_cache.ensure() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            fixed = os.path.join(_REPO, ".jax_cache")
            assert compile_cache.ensure() == fixed
            assert jax.config.jax_compilation_cache_dir == fixed
            assert compile_cache.ensure() == fixed  # idempotent
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.slow
def test_bench_allreduce_cpu_sim_end_to_end():
    """The sweep runs on the simulated mesh and emits both per-point
    busbw lines and the scaling summary, parseable."""
    from _hermetic import hermetic_cpu_env

    env = hermetic_cpu_env(n_devices=8)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["BENCH_SIZES"] = "4096,65536"
    env["BENCH_ITERS"] = "3"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench_allreduce.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.strip().startswith("{")]
    busbw = [ln for ln in lines if ln["metric"] == "allreduce_busbw"]
    scaling = [ln for ln in lines if ln["metric"] == "allreduce_scaling"]
    assert {ln["world"] for ln in busbw} == {1, 2, 4, 8}
    assert {ln["world"] for ln in scaling} == {1, 2, 4, 8}
    assert all(ln["base_world"] == 1 for ln in scaling)
    base_line = next(ln for ln in scaling if ln["world"] == 1)
    assert base_line["value"] == 1.0
    # CPU-sim quarantine: every non-TPU scaling line carries the
    # logic-validation-only note (VERDICT r3 weak #8)
    assert all("logic-validation only" in ln["note"] for ln in scaling)


# ------------------------------------------------ round-5 microbenches


def _run_harness(script, env, timeout=420):
    """Run a bench harness as a user would (subprocess, tiny config);
    return its parsed JSON lines, so the harnesses do not rot between
    chip runs. hermetic_cpu_env keeps the child on the CPU."""
    from _hermetic import hermetic_cpu_env

    full_env = hermetic_cpu_env(n_devices=8)
    full_env.update(env)
    full_env.setdefault("BENCH_PLATFORM", "cpu")
    proc = subprocess.run(
        [sys.executable, script],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=full_env,
        cwd=os.path.dirname(os.path.abspath(__file__)) + "/..",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [
        json.loads(ln)
        for ln in proc.stdout.splitlines()
        if ln.startswith("{")
    ]
    assert lines, proc.stdout
    return lines


@pytest.mark.slow
def test_bench_fusion_harness_smoke():
    lines = _run_harness(
        "bench_fusion.py",
        {
            "BENCH_FUSION_N": "8",
            "BENCH_FUSION_BYTES": "16384",
            "BENCH_ITERS": "2",
            "BENCH_AUTOTUNE_TRIALS": "2",
        },
    )
    modes = {l["mode"] for l in lines if l["metric"] == "eager_fusion"}
    # later PRs added modes (host_pack, bucketing_*, gather_*); the
    # original quartet must still be present
    assert modes >= {"unfused", "fused", "default", "traced"}
    assert any(l["metric"] == "eager_fusion_speedup" for l in lines)
    auto = [l for l in lines if l["metric"] == "fusion_autotune"]
    assert auto and auto[0]["trials"] == 2
    # CPU lines must carry the quarantine note
    assert all("note" in l for l in lines)


@pytest.mark.slow
def test_bench_int8_harness_smoke():
    lines = _run_harness(
        "bench_int8.py",
        {"BENCH_SIZES": "65536", "BENCH_ITERS": "2"},
    )
    (line,) = lines
    assert line["metric"] == "int8_compute_tax"
    assert line["quant_ms"] > 0 and line["plain_ms"] > 0
    assert "note" in line


@pytest.mark.slow
def test_bench_overlap_harness_smoke():
    import tempfile

    art = tempfile.mkdtemp()
    lines = _run_harness(
        "bench_overlap.py",
        {
            "BENCH_DRYRUN": "1",
            "BENCH_ITERS": "2",
            "BENCH_ARTIFACT_DIR": art,
        },
    )
    legs = {l["leg"] for l in lines if l["metric"] == "overlap_ab"}
    assert legs == {"ab_monolithic", "ab_bucketed", "ab_bucketed_rs"}
    rs = next(
        l
        for l in lines
        if l["metric"] == "overlap_ab" and l["leg"] == "ab_bucketed_rs"
    )
    tuner = next(l for l in lines if l["metric"] == "overlap_tuner")
    assert tuner["choice"] in tuner["candidates"]
    # compiled-program evidence rides the artifact: bucketed ZeRO-1 leg
    # must carry N independent rs + ag collectives
    assert rs["collectives"]["reduce_scatter"] == rs["n_buckets"]
    assert rs["collectives"]["all_gather"] == rs["n_buckets"]
    # CPU A/B lines carry the quarantine note (the tuner verdict line
    # is a derived summary, not a measurement claim)
    assert all(
        "note" in l for l in lines if l["metric"] == "overlap_ab"
    )
    for leg in legs:
        assert os.path.getsize(
            os.path.join(art, f"overlap_{leg}.json")
        ) > 0


@pytest.mark.slow
def test_bench_seq_harness_smoke():
    lines = _run_harness(
        "bench_seq.py",
        {
            "BENCH_SEQS": "128",
            "BENCH_BATCH": "1",
            "BENCH_HEADS": "2",
            "BENCH_ITERS": "2",
        },
    )
    engines = {l["engine"] for l in lines}
    assert engines == {"flash", "dense"}
    # "tflops" is rounded to 2dp and can legitimately round to 0.0 at
    # this tiny config on a slow host — assert structure, not speed
    assert all(
        "tflops" in l and l["value"] > 0 and "note" in l for l in lines
    )
