"""Upper readings for the limits of a cell of the ``afmoe`` family, on the
chip and at the cell's own size: the plain reference put in the program's
place and made wrong, against the plain reference (``tools/limits.py`` does
this for the transformer family; the lower readings, the program against
the reference, are the ``compared`` lines of the cell's own runs):

    python3 benchmark/tools/limits_afmoe.py --workload <cell> --seeds 1,2 \
        [--controls fp8,bfloat16] [--faults top7,no_route_scale,..] \
        [--out chiprun_out/limits]

For every seed: the controls (the reference computed in the precision below
the configuration's: fp8 for bfloat16), half of the batch left out, and the
faults of this model's own (``references/afmoe.py: FAULTS``), which one
compiled program serves: the fault is a number it is handed. For the first
seed it also reads, and compares with nothing, the share of (token, choice)
pairs on which the program's router (in the configuration's compute type)
and the float32 reference's choose the same expert, layer by layer: a
routing flip moves a loss or a gradient without being a fault. One JSON
line per reading.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _ints(text):
    return [int(x) for x in text.split(",") if x]


def _names(text):
    return [x for x in text.split(",") if x]


def _public(gaps: dict) -> dict:
    return {k: v for k, v in gaps.items() if not k.startswith("_")}


def routes_alike(cell, make_params, seed, host_batch) -> dict:
    """Per expert layer, the share of (token, choice) pairs that the
    program's forward and the float32 reference's send to the same
    expert, and the share of the program's pairs whose expert is held
    here (the work file counts the expected ``held / total``)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import weights

    ref, family = cell.reference(), cell.model()
    model = family.build_model(cell.config, remat=False)
    params = make_params(weights.seed_key(seed))
    tokens = host_batch[0].reshape(-1, host_batch[0].shape[-1])

    @jax.jit
    def program_routes(params, tokens):
        _, state = model.apply(params, tokens, train=False,
                               mutable=["intermediates"])
        blocks = state["intermediates"]
        return [blocks[name]["moe"]["chosen"][0]
                for name in sorted(blocks, key=lambda n: int(n.split("_")[1]))]

    ref_routes = jax.jit(lambda p, t: ref.routes(p, t, cell.config))
    first, last = cell.config["experts_held"]
    alike, here = [], []
    for row in tokens:  # a row at a time: the reference's size
        mine = program_routes(params, row[None])
        theirs = ref_routes(params, row[None])
        alike.append([
            float(jnp.mean(jnp.any(a[:, :, None] == b[:, None, :], axis=2)))
            for a, b in zip(mine, theirs)])
        here.append([float(jnp.mean((a >= first) & (a < last)))
                     for a in mine])

    def mean(rows):
        return [sum(col) / len(col) for col in zip(*rows)]

    return {"alike_by_expert_layer": mean(alike),
            "held_here_by_expert_layer": mean(here)}


def faulty_first_steps(cell, make_params, seed, host_batch):
    """``fault index -> what loops/train.py: reference_first_steps reads``,
    from one compiled step that is handed the fault; index -1 is the
    reference itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import weights
    from benchmark.loops import train

    ref = cell.reference()
    cfg = json.loads(ref.program_key(cell.config))
    o = cell.traffic["optimizer"]
    tokens, labels = (x.reshape(-1, x.shape[-1]) for x in host_batch)
    step = jax.jit(lambda p, m, a, b, fault: ref.sgd_momentum_step(
        p, m, a, b, dict(cfg, fault=fault), o["lr"], o["momentum"],
        "float32", min(4, tokens.shape[0])), donate_argnums=(0, 1))
    norms, change = train._norm_programs(ref, make_params)

    def first_steps(fault: int) -> dict:
        params = make_params(weights.seed_key(seed))
        trace = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for i in range(train.COMPARED_STEPS):
            params, trace, loss, grads = step(
                params, trace, tokens, labels, jnp.int32(fault))
            losses.append(float(loss))
            if i == 0:
                grad_norms = np.asarray(norms(grads))
            del grads
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": np.asarray(
                    change(params, weights.seed_key(seed)))}

    return first_steps


def readings(cell, seeds, controls, faults, emit):
    import jax

    from benchmark.lib import chip, compare, weights
    from benchmark.loops import train

    chip.require_chips(cell.chips)
    ref, family, t = cell.reference(), cell.model(), cell.traffic
    model = family.build_model(cell.config, remat=t["remat"])
    make_params = jax.jit(weights.make_params(
        family.param_shapes(model, t["seq"])))
    for seed in seeds:
        host_batch = family.make_batch(cell.config, t, cell.chips, seed)
        if seed == seeds[0]:
            emit({"kind": "routes_alike", "seed": seed, **routes_alike(
                cell, make_params, seed, host_batch)})
        first_steps = faulty_first_steps(cell, make_params, seed, host_batch)
        base = first_steps(-1)
        emit({"kind": "reference", "seed": seed, "losses": base["losses"]})
        for fault in faults:
            other = first_steps(ref.FAULTS.index(fault))
            emit({"kind": "fault_" + fault, "seed": seed,
                  **_public(compare.training_gaps(other, base)),
                  "losses": other["losses"]})
        rows = host_batch[0].shape[0] * host_batch[0].shape[1]
        variants = [("control_" + p, dict(precision=p)) for p in controls]
        variants.append(("fault_half_batch", dict(rows=rows // 2)))
        for kind, kw in variants:
            other = train.reference_first_steps(
                cell, make_params, seed, host_batch, **kw)
            emit({"kind": kind, "seed": seed,
                  **_public(compare.training_gaps(other, base)),
                  "losses": other["losses"]})


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=_ints, required=True)
    p.add_argument("--controls", type=_names, default=["fp8"])
    p.add_argument("--faults", type=_names, default=None)
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
