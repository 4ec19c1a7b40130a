"""Upper readings for the limits of a cell of the ``sdar_moe`` family, on
the chip and at the cell's own size: the plain reference put in the
program's place and made wrong, against the plain reference
(``tools/limits_afmoe.py``'s readings without its half of the batch: the
cell has one row, and ``half_blocks`` stands for it):

    python3 benchmark/tools/limits_sdar_moe.py --workload <cell> \
        --seeds 1,2 [--controls fp8] [--faults leak_own_clean,..] \
        [--out chiprun_out/limits]

For every seed: the controls (the reference computed in the precision below
the configuration's: fp8 for bfloat16) and the faults of this model's own
(``references/sdar_moe.py: FAULTS``), which one compiled program serves: a
noised query that also sees its own block's clean copy, causal in place of
both ways inside a block, the noised-sees-noised part left out, positions
0..2L-1, the weight 1/t left out, the head over the clean half, gates not
renormalised, top-7 for top-8, the second half of the row's blocks left out
of the loss. For the first seed it also reads, and compares with nothing,
the share of (position, choice) pairs on which the program's router and the
float32 reference's choose the same expert, layer by layer. One JSON line
per reading."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import limits_afmoe  # noqa: E402 - puts the repo's root on the path


def readings(cell, seeds, controls, faults, emit):
    import jax

    from benchmark.lib import chip, compare
    from benchmark.loops import train

    chip.require_chips(cell.chips)
    ref, family, t = cell.reference(), cell.model(), cell.traffic
    model = family.build_model(cell.config, remat=t["remat"])
    make_params = jax.jit(family.make_params(
        family.param_shapes(model, t["seq"]), cell.config))
    for seed in seeds:
        host_batch = family.make_batch(cell.config, t, cell.chips, seed)
        if seed == seeds[0]:
            emit({"kind": "routes_alike", "seed": seed,
                  **limits_afmoe.routes_alike(
                      cell, make_params, seed, host_batch)})
        first_steps = limits_afmoe.faulty_first_steps(
            cell, make_params, seed, host_batch)
        base = first_steps(-1)
        emit({"kind": "reference", "seed": seed, "losses": base["losses"]})
        def others():
            for fault in faults:
                yield "fault_" + fault, first_steps(ref.FAULTS.index(fault))
            for precision in controls:
                yield "control_" + precision, train.reference_first_steps(
                    cell, make_params, seed, host_batch, precision=precision)

        for kind, other in others():
            emit({"kind": kind, "seed": seed,
                  **limits_afmoe._public(compare.training_gaps(other, base)),
                  "losses": other["losses"]})


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=limits_afmoe._ints, required=True)
    p.add_argument("--controls", type=limits_afmoe._names, default=["fp8"])
    p.add_argument("--faults", type=limits_afmoe._names, default=None)
    p.add_argument("--out", default="chiprun_out/limits")
    args = p.parse_args(argv)

    from benchmark.lib import manifest
    from horovod_tpu.common import compile_cache

    compile_cache.ensure()
    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    os.makedirs(args.out, exist_ok=True)
    t0 = time.time()
    with open(os.path.join(args.out, cell.name + ".jsonl"), "a") as f:
        def emit(rec):
            rec = {"cell": cell.name, "t": round(time.time() - t0, 1), **rec}
            line = json.dumps(rec)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        faults = args.faults
        if faults is None:
            faults = list(cell.reference().FAULTS)
        readings(cell, args.seeds, args.controls, faults, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
