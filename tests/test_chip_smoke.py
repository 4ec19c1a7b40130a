"""``chip_smoke.py``, the first-contact trainer: it fails without a
chip, its body holds its invariants on the CPU mesh, and the compile
cache it relies on has one fixed place."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


class TestNoHiddenFallback:
    """No chip is an error, not a smaller number: the smoke fails
    without an accelerator, and its body holds its invariants on the
    CPU mesh."""

    def test_no_accelerator_exits_nonzero_and_prints_no_result(self):
        from _hermetic import hermetic_cpu_env

        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
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
        """A chip that is not in the one table of peaks is an error,
        never a default."""
        from benchmark.lib import chip

        assert chip.peaks("TPU v5 lite") == (197.0e12, 819.0e9)
        with pytest.raises(KeyError, match="TPU v9"):
            chip.peaks("TPU v9")

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
