"""Drive one run of a test cell on the CPU, skipping only the harness's look
for a chip, optionally with the timed path broken underneath:

    python3 tests/benchmark/drive.py <cell> <trace 0|1> <seconds> [fault]

Faults: ``state_unchanged`` (the step returns its state as it got it),
``half_batch`` (half of the rows left out, the mean taken over the rest),
``no_exchange`` (each chip applies its own gradient), ``token_altered`` (the
decode step's tokens changed where they are produced). Prints the result
line like ``benchmark/run.py`` does.
"""

import argparse
import json
import os
import sys
import time

START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
DATA = os.path.join(HERE, "data")
MANIFEST = os.path.join(DATA, "BENCHMARK.test.json")


def plant(fault):
    import jax

    from benchmark.lib import program
    from benchmark.loops import serve_open

    if fault == "state_unchanged":
        real = program.make_train_step

        def make(hvd, model, opt, mesh):
            step = real(hvd, model, opt, mesh)

            @jax.jit
            def broken(params, state, tokens, labels):
                _, _, loss = step(params, state, tokens, labels)
                return params, state, loss

            return broken

        program.make_train_step = make
    elif fault == "half_batch":
        real_loss = program.per_chip_loss
        program.per_chip_loss = lambda logits, labels: real_loss(
            logits[: logits.shape[0] // 2], labels[: labels.shape[0] // 2])
    elif fault == "no_exchange":
        real_init = program.init_training

        def init(model, traffic):
            import optax

            hvd, mesh, _ = real_init(model, traffic)
            o = traffic["optimizer"]
            return hvd, mesh, optax.sgd(o["lr"], momentum=o["momentum"])

        program.init_training = init
        program.place_training_state = lambda hvd, opt, params: (
            hvd.broadcast_parameters(params),
            hvd.broadcast_optimizer_state(opt.init(params)))
        program.momentum_trace = lambda state: state[0].trace
    elif fault == "token_altered":
        real_init = serve_open.Server.__init__

        def init(self, *a, **kw):
            real_init(self, *a, **kw)
            decode = self.engine.decode_step
            vocab = self.cfg["vocab_size"]
            self.engine.decode_step = lambda tokens: (
                decode(tokens) + 1) % vocab

        serve_open.Server.__init__ = init
    elif fault:
        raise SystemExit(f"no fault {fault!r}")


def main(argv=None):
    cell_name, trace, seconds, *fault = (argv or sys.argv[1:])
    from benchmark import run as bench_run
    from benchmark.lib import compare, manifest

    plant(fault[0] if fault else None)
    cell = manifest.Cell(manifest.load_manifest(MANIFEST), cell_name, DATA)
    args = argparse.Namespace(seed=2**31 + 11, seconds=float(seconds),
                              trace=int(trace))
    result = cell.loop().run(cell, args, START, require_chip=False)
    line = bench_run.result_line(cell, result, bool(args.trace))
    compare.print_compared(result["compared"], line["correct"])
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
