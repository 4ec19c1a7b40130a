"""Loop kind ``train-block-diffusion``: loop kind ``train`` (its window, its
clocks, its three compared steps, its last line) around a step whose loss is
the model family's own: ``models/<family>.py: per_chip_loss(logits, tokens,
weights)``, where ``train``'s step takes ``lib/program.py``'s unweighted
mean. The batch's second array is the loss's float32 weights, not labels,
and the family's ``make_batch`` carries the noise, so program and reference
see the same draw; the weights are the family's draw too
(``make_params(shapes, cfg)``).

``Trainer.build`` and ``run`` are ``loops/train.py``'s with that one name
changed: ``train.py`` calls ``program.make_train_step`` by name and may not
be edited here (``PERF.md`` section 7 asks for the hook that folds this file
back into it). For the same reason a traced run's table of device time by
scope is made here, from the text of the step that ran
(:func:`scope_table`): ``lib/scopes.py`` would build ``train``'s step once
more to read its names.
"""

import json
import sys
import time
from functools import partial

import numpy as np

from ..lib import chip, compare, program, scopes, spans, xtrace
from . import train
from .train import _StepBudget, drive_window, reference_first_steps


def make_train_step(hvd, model, opt, mesh, per_chip_loss):
    """``lib/program.py: make_train_step`` with the family's loss."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    @partial(jax.jit, donate_argnums=(0, 1))
    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(), P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
        out_specs=(P(), P(), P()), check_vma=False,
    )
    def train_step(params, opt_state, tokens, loss_weights):
        tokens, loss_weights = tokens[0], loss_weights[0]

        def loss_fn(p):
            return per_chip_loss(
                model.apply(p, tokens, train=True), tokens, loss_weights)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, hvd.WORLD_AXIS)

    return train_step


def scope_table(readings: dict, hlo_text: str):
    """``lib/scopes.py: _table_of`` from the optimised text of the step
    that ran: ``(class table, steps, program names its work)``, or None
    where the trace holds no step or its operations are not the text's."""
    trace = readings["trace"]
    steps = xtrace.step_count(trace) if trace.devices else 0
    if not steps:
        return None
    scope_of = scopes.scopes_of_hlo(hlo_text)
    rules = scopes.Rules(readings["cfg"].get("model", ""))
    table = scopes.class_table(trace, scope_of, rules)
    foreign = scopes.foreign_seconds(
        trace, scopes.signatures_of_hlo(hlo_text))
    print("scopes: classes sum to %.6f s of %.6f s busy; %.6f s in "
          "operations that the step's text does not have as the trace has "
          "them" % (sum(table[c]["s"] for c in rules.classes), trace.busy_s,
                    foreign), file=sys.stderr)
    if foreign > 0.005 * trace.busy_s:
        return None
    scopes.print_table(table, rules.classes, steps)
    return table, steps, scopes.has_program_scopes(scope_of)


class Trainer(train.Trainer):
    """``train.Trainer`` with the step above."""

    def build(self):
        import jax

        t = self.traffic
        model = self.family.build_model(self.cfg, remat=t["remat"])
        hvd, mesh, opt = program.init_training(model, t)
        self.world = hvd.size()
        if self.world != self.cell.chips:
            raise RuntimeError(
                f"hvd.size()={self.world}, the cell asks {self.cell.chips}")
        # the family's own draw of the weights (models/<family>.py)
        self.make_params = jax.jit(self.family.make_params(
            self.family.param_shapes(model, t["seq"]), self.cfg))
        self._place = lambda params: program.place_training_state(
            hvd, opt, params)
        self._rank_major = hvd.rank_sharding(mesh)
        params, state = self.reseed(self.seed)
        step = make_train_step(
            hvd, model, opt, mesh, self.family.per_chip_loss)
        t0 = time.perf_counter()
        lowered = step.lower(params, state, self.tokens, self.labels)
        t1 = time.perf_counter()
        self.step = lowered.compile()
        t2 = time.perf_counter()
        self.readings["trace_lower_s"] = t1 - t0
        self.readings["compile_s"] = t2 - t1
        self.readings["allreduce_bytes"] = xtrace.world_allreduce_bytes(
            lowered.as_text(), self.world) if self.world > 1 else 0


def run(cell, args, process_start: float, require_chip: bool = True) -> dict:
    marks = {"imports": time.perf_counter() - process_start}

    def mark(name):
        marks[name] = time.perf_counter() - process_start - sum(
            marks.values())

    trainer = Trainer(cell, args.seed, require_chip)
    trainer.build()
    mark("init_weights_lower_compile")
    prog = trainer.first_steps()
    mark("compared_steps")
    trainer.warm_up()
    mark("warm_up")
    print("setup phases (s): " + json.dumps(
        {k: round(v, 2) for k, v in marks.items()}), file=sys.stderr)
    recorder = spans.Recorder(annotate=bool(args.trace))
    events_before = len(trainer.compile_log.events)
    t0, stamps, losses, _ = drive_window(
        trainer._advance, chip.fetch_scalar, args.seconds,
        span=recorder.span)
    setup_s = t0 - process_start
    compiles_in_window = trainer.compile_log.count_since(events_before)
    steps = len(stamps)
    t = cell.traffic
    # data tokens: a row's noised copy and its clean copy are one row
    tokens_per_step = trainer.world * t["batch_per_chip"] * t["seq"]
    window_s = stamps[-1] - t0 if stamps else float("nan")
    readings = dict(trainer.readings)
    readings.update(
        kind="train", cfg=cell.config, traffic=t, chips=cell.chips,
        stamps=[s - t0 for s in stamps], window_s=window_s, steps=steps,
        tokens_per_s=steps * tokens_per_step / window_s,
        device_kind=trainer.devices[0].device_kind,
        compiles_in_window=compiles_in_window,
    )
    if args.trace:
        trace = xtrace.record(
            lambda: drive_window(
                trainer._advance, chip.fetch_scalar, 1.0,
                span=recorder.span, clock=_StepBudget(train.TRACED_STEPS)),
            len(trainer.devices))
        readings["trace"] = xtrace.steady_steps(trace, train.TRACE_SKIP_STEPS)
        # under the key where lib/scopes.py: table_of keeps its own
        readings[scopes.KEY] = scope_table(readings, trainer.step.as_text())
    memory_peak = chip.memory_peak_bytes(trainer.devices)
    readings["memory_peak_bytes"] = memory_peak
    print("memory (bytes): " + json.dumps({
        "memory_stats": trainer.devices[0].memory_stats(),
        "step_by_xla": chip.program_footprint(trainer.step),
    }), file=sys.stderr)
    make_params, host_batch = trainer.make_params, trainer.host_batch
    device = chip.describe(trainer.devices)
    trainer.free()

    ref = reference_first_steps(cell, make_params, args.seed, host_batch)
    numbers = compare.training_gaps(prog, ref)
    print("read, not compared: " + json.dumps(
        {k: numbers[k] for k in ("loss1_gap", "_grad_gap_own",
                                 "_change_gap_own")}), file=sys.stderr)
    numbers["compiles_in_window"] = compiles_in_window
    numbers["nonfinite_losses"] = int(
        np.sum(~np.isfinite(np.asarray(losses + prog["losses"]))))
    correct, compared = compare.judge(numbers, cell.limits())
    device["memory_peak_bytes"] = memory_peak
    return {
        "correct": correct, "attempted": steps, "failed": 0,
        "end_to_end": {
            "train_tokens_per_s": readings["tokens_per_s"],
            "setup_s": setup_s,
        },
        "readings": readings, "device": device, "compared": compared,
    }
