"""Where a training cell's set-up goes, phase by phase: the steps of
``loops/train.py: Trainer.build`` one at a time under a clock, beside the
program's own spans. Names what ``init_weights_lower_compile`` (one mark of
the loop) holds besides ``init.hvd_init_s``, ``init.place_state_s``,
``init.trace_lower_s`` and ``init.compile_s``. A tool for the chip, not a
measurement of a cell.

    python3 benchmark/tools/setup_probe.py --workload gpt2m-train-1chip
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=2147487200)
    args = p.parse_args(argv)
    marks, last = {}, [time.perf_counter()]

    def mark(name):
        now = time.perf_counter()
        marks[name] = round(now - last[0], 3)
        last[0] = now

    import jax
    import jax.numpy as jnp

    from benchmark.lib import chip, manifest, program, program_spans, weights
    from horovod_tpu.common import compile_cache

    compile_cache.ensure()
    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    devices = chip.require_chips(cell.chips)
    mark("imports_and_devices")
    t, family = cell.traffic, cell.model()
    model = family.build_model(cell.config, remat=t["remat"])
    hvd, mesh, opt = program.init_training(model, t)
    mark("hvd_init_and_optimizer")
    shapes = family.param_shapes(model, t["seq"])
    mark("param_shapes (eval_shape of model.init)")
    make_params = jax.jit(weights.make_params(shapes))
    params = make_params(weights.seed_key(args.seed))
    jax.block_until_ready(params)
    mark("weights (jit, compile or cache hit, run)")
    params = hvd.broadcast_parameters(params)
    jax.block_until_ready(params)
    mark("broadcast_parameters")
    state = opt.init(params)
    jax.block_until_ready(state)
    mark("opt.init")
    state = hvd.broadcast_optimizer_state(state)
    jax.block_until_ready(state)
    mark("broadcast_optimizer_state")
    state = jax.tree.map(jnp.copy, state)
    jax.block_until_ready(state)
    mark("copy of every state leaf")
    batch = [jax.device_put(x, hvd.rank_sharding(mesh)) for x in
             family.make_batch(cell.config, t, hvd.size(), args.seed)]
    mark("batch")
    step = program.make_train_step(hvd, model, opt, mesh)
    lowered = step.lower(params, state, *batch)
    mark("trace_and_lower")
    compiled = lowered.compile()
    mark("compile")
    if hvd.size() > 1:
        lowered.as_text()
        mark("print the lowered module (allreduce bytes)")
    out = compiled(params, state, *batch)
    chip.fetch_scalar(out[2])
    mark("first step")
    program_spans.snapshot({})
    print(json.dumps({"device_kind": devices[0].device_kind,
                      "workload": args.workload, "phases_s": marks}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
