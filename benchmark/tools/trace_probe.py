"""Record a small device trace of the trainer's step on the chip: the
``.xplane.pb`` that ``tests/benchmark/data`` keeps for the tests of the trace
reduction (a 2-layer, 256-wide model, six traced steps; not a measurement).

    python3 benchmark/tools/trace_probe.py <out_dir>
"""

import glob
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CONFIG = {"vocab_size": 4096, "num_layers": 2, "d_model": 256, "num_heads": 4,
          "d_ff": 1024, "max_len": 1024, "causal": True, "dtype": "bfloat16",
          "flash_block": 512}
TRAFFIC = {"batch_per_chip": 4, "seq": 512, "remat": True,
           "labels": "next-token",
           "optimizer": {"kind": "sgd", "lr": 0.01, "momentum": 0.9,
                         "op": "Average"}}


def main(out_dir: str) -> int:
    import jax

    from benchmark.lib import chip, manifest, program, spans, weights
    from benchmark.loops import train

    family = manifest.load_module("models", "transformer")

    os.makedirs(out_dir, exist_ok=True)
    model = family.build_model(CONFIG, remat=True)
    hvd, mesh, opt = program.init_training(model, TRAFFIC)
    params = jax.jit(weights.make_params(family.param_shapes(model, 512)))(
        weights.seed_key(0))
    state = program.place_training_state(hvd, opt, params)
    batch = [jax.device_put(x, hvd.rank_sharding(mesh)) for x in
             family.make_batch(CONFIG, TRAFFIC, hvd.size(), 0)]
    step = program.make_train_step(hvd, model, opt, mesh)

    def advance():
        nonlocal state
        *state, loss = step(*state, *batch)
        return loss

    for _ in range(3):
        chip.fetch_scalar(advance())
    trace_dir = os.path.join(out_dir, "trace")
    jax.profiler.start_trace(trace_dir)
    recorder = spans.Recorder(annotate=True)
    train.drive_window(advance, chip.fetch_scalar, 1.0, span=recorder.span,
                       clock=train._StepBudget(3))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    shutil.copy(path, os.path.join(out_dir,
                                   f"train_w{hvd.size()}.xplane.pb"))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/probe"))
