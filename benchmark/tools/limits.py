"""Read, on the chip and at a cell's own size, what its limits are set from
(benchmark/README.md, "How correct is decided"):

    python3 benchmark/tools/limits.py --workload <cell> --seeds 1,2,.. \
        [--control-seeds 1,2,3] [--seconds 20] [--out chiprun_out/limits]

One process, one set-up. For every seed: the program's numbers against the
plain reference (the lower readings). For every control seed: the reference
put in the program's place and computed in the precision below the
configuration's (fp8 for bfloat16), and, for a training cell, the faults a
run can have, planted in the reference: half of the batch left out, and on
several chips the exchange left out (each chip keeps its own gradient, so
the first chip's rows alone). One JSON line per reading.
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


def _public(gaps: dict) -> dict:
    return {k: v for k, v in gaps.items() if not k.startswith("_")}


def train_readings(cell, seeds, control_seeds, emit, control_precision):
    from benchmark.lib import compare, weights
    from benchmark.loops import train

    trainer = train.Trainer(cell, seeds[0])
    trainer.build()
    names = None
    for seed in seeds:
        if seed != trainer.seed or trainer.params is None:
            trainer.reseed(seed)
        prog = trainer.first_steps()
        host_batch = trainer.host_batch
        trainer.params = trainer.state = None
        ref = train.reference_first_steps(
            cell, trainer.make_params, seed, host_batch)
        gaps = compare.training_gaps(prog, ref)
        if names is None:
            names = weights.leaf_names(
                trainer.make_params(weights.seed_key(seed)))
        emit({"kind": "program", "seed": seed, **_public(gaps),
              "grad_leaf": names[gaps["_grad_leaf"]],
              "change_leaf": names[gaps["_change_leaf"]],
              "losses": prog["losses"], "ref_losses": ref["losses"]})
        if seed not in control_seeds:
            continue
        rows_all = host_batch[0].shape[0] * host_batch[0].shape[1]
        variants = [("control_" + control_precision,
                     dict(precision=control_precision)),
                    ("control_bfloat16", dict(precision="bfloat16")),
                    ("fault_half_batch", dict(rows=rows_all // 2))]
        if cell.chips > 1:
            variants.append(("fault_no_exchange",
                             dict(rows=host_batch[0].shape[1])))
        for kind, kw in variants:
            other = train.reference_first_steps(
                cell, trainer.make_params, seed, host_batch, **kw)
            emit({"kind": kind, "seed": seed,
                  **_public(compare.training_gaps(other, ref)),
                  "losses": other["losses"]})


def serve_readings(cell, seeds, control_seeds, emit, control_precision,
                   seconds):
    from benchmark.lib import weights
    from benchmark.loops import serve_open as so

    t = cell.traffic
    server = so.Server(cell, seeds[0])
    samples = []
    try:
        for i, seed in enumerate(seeds):
            schedule = server.schedule(seed, seconds)
            if i == 0:
                server.warm_up(schedule, seed)
            else:  # the same programs, the next seed's weights
                server.engine._params = server.make_params(
                    weights.seed_key(seed))
            _, records = so.run_window(server, schedule, seconds)
            summary = so.summarise(schedule, records, seconds)
            samples.append((seed, so.sample_for_check(
                summary["completed"], seed, int(t["check_requests"])),
                {k: summary[k] for k in (
                    "attempted", "failed", "serve_tokens_per_s",
                    "ttft_p90_ms", "tpot_p90_ms")}))
        make_params = server.make_params
    finally:
        server.stop()
    for seed, sample, summary in samples:
        gaps = so.reference_gaps(cell, make_params, seed, sample)
        emit({"kind": "program", "seed": seed, "logit_gap_max": max(gaps),
              "checked_tokens": len(gaps),
              "positions_off_best": sum(g > 0 for g in gaps), **summary})
        if seed in control_seeds:
            for precision in (control_precision, "bfloat16"):
                gaps = so.reference_gaps(
                    cell, make_params, seed, sample,
                    pick_precision=precision)
                emit({"kind": "control_" + precision, "seed": seed,
                      "logit_gap_max": max(gaps),
                      "checked_tokens": len(gaps),
                      "positions_off_best": sum(g > 0 for g in gaps)})


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=_ints, required=True)
    p.add_argument("--control-seeds", type=_ints, default=[])
    p.add_argument("--control-precision", default="fp8")
    p.add_argument("--seconds", type=float, default=20.0)
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

        if cell.traffic["loop"] == "train":
            train_readings(cell, args.seeds, set(args.control_seeds), emit,
                           args.control_precision)
        else:
            serve_readings(cell, args.seeds, set(args.control_seeds), emit,
                           args.control_precision, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
