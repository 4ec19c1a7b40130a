"""``correct`` has to come out false for the control (the reference put in
the program's place, computed in the precision below the configuration's)
and for each fault a run can have, with the timed path broken underneath
the harness. At the test size the configuration is float32, so the control
is bfloat16. On the chip the same readings are taken at the cells' own
sizes by ``tools/limits.py``."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.lib import compare, manifest, weights

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
MANIFEST = os.path.join(DATA, "BENCHMARK.test.json")


def drive(cell, trace, seconds, fault=None, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable, os.path.join(HERE, "drive.py"), cell, str(trace),
           str(seconds)] + ([fault] if fault else [])
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


@pytest.mark.parametrize("cell, fault, devices, over", [
    ("test-train-1", "state_unchanged", 1, "change_gap"),
    ("test-train-1", "half_batch", 1, "grad_gap"),
    ("test-train-4", "no_exchange", 4, "grad_gap"),
    ("test-serve", "token_altered", 1, "logit_gap_max"),
])
def test_a_broken_timed_path_is_not_correct(cell, fault, devices, over):
    line, stderr = drive(cell, 0, 1.0, fault, devices)
    assert line["correct"] is False
    c = line["compared"][over]
    assert c["value"] > c["limit"]
    assert f"compared {over}" in stderr and "correct = false" in stderr
    assert list(line)[-1] == "compared"


def _cell(name):
    return manifest.Cell(manifest.load_manifest(MANIFEST), name, DATA)


def test_training_control_in_lower_precision_is_not_correct():
    import jax

    from benchmark.loops import train

    cell = _cell("test-train-1")
    family = cell.model()
    model = family.build_model(cell.config)
    make = jax.jit(weights.make_params(family.param_shapes(model, 32)))
    batch = family.make_batch(cell.config, cell.traffic, 1, 5)
    ref = train.reference_first_steps(cell, make, 5, batch)
    again = train.reference_first_steps(cell, make, 5, batch)
    control = train.reference_first_steps(cell, make, 5, batch,
                                          precision="bfloat16")
    limits = {k: v for k, v in cell.limits().items()
              if k.endswith("_gap")}
    ok, _ = compare.judge(compare.training_gaps(again, ref), limits)
    assert ok
    ok, compared = compare.judge(compare.training_gaps(control, ref), limits)
    assert not ok, compared


def test_serving_control_in_lower_precision_is_not_correct():
    import jax

    from benchmark.loops import serve_open

    cell = _cell("test-serve")
    family = cell.model()
    model = family.build_model(cell.config)
    make = jax.jit(weights.make_params(family.param_shapes(model, 8)))
    rng = np.random.default_rng(3)
    sample = [({"tokens": rng.integers(0, 256, 28).tolist()},
               {"tokens": rng.integers(0, 256, 100).tolist()})
              for _ in range(8)]
    limit = cell.limits()["logit_gap_max"]
    own = serve_open.reference_gaps(cell, make, 7, sample,
                                    pick_precision="float32")
    assert max(own) <= limit
    # the cell on the chip states bfloat16 and its control is fp8, as here.
    # (At this size a bfloat16 control picks the reference's own token at
    # all 800 positions: its error is below the spacing of 256 logits.)
    control = serve_open.reference_gaps(cell, make, 7, sample,
                                        pick_precision="fp8")
    assert len(control) == 800
    assert max(control) > 3 * limit


def test_zero_gradient_leaves_are_left_out_of_the_change():
    ref = {"losses": [1.0, 1.0, 1.0],
           "grad_norms": np.array([1.0, 2.0, 1e-9, 3.0]),
           "change_norms": np.array([0.1, 0.2, 1e-7, 0.3])}
    prog = {"losses": [1.0, 1.0, 1.0],
            "grad_norms": np.array([1.0, 2.0, 3e-9, 3.0]),
            "change_norms": np.array([0.1, 0.2, 5e-7, 0.33])}
    gaps = compare.training_gaps(prog, ref)
    # the leaf that moved by round-off alone (index 2) decides nothing
    assert gaps["change_gap"] == pytest.approx(0.1)
    assert gaps["_change_leaf"] == 3
    # median of the reference norms is 1.5: the tiny leaf is held to it
    assert gaps["grad_gap"] == pytest.approx(2e-9 / 1.5)
