"""Loop kind ``train``: the quick start's trainer driven for a timed window
with two steps always in flight.

Set-up builds ONE compiled step with its state, drives it from the seed
through its first three steps (what ``correct`` compares with the plain
reference), warms it up, and hands that same object to the window. In the
window the loop dispatches step i+2 before it fetches step i's loss, so the
device never waits for the host; each fetch gives a completion stamp; the
window ends with the last step whose loss arrived within ``--seconds`` and
the rate divides by that stamp.
"""

import contextlib
import functools
import gc
import json
import sys
import time

import numpy as np

from ..lib import chip, compare, compiles, program, spans, weights, xtrace

IN_FLIGHT = 2
WARMUP_STEPS = 10
COMPARED_STEPS = 3
TRACED_STEPS = 24
# the first traced steps refill the queue that starting the profiler drained
TRACE_SKIP_STEPS = 4


def drive_window(dispatch, fetch, seconds: float, clock=time.perf_counter,
                 span=None):
    """The timed window. ``dispatch()`` sends one step and returns its
    pending loss; ``fetch(pending)`` waits for it. Returns ``(t0, stamps,
    losses, drained)``: the time of the first dispatch, the arrival stamp and
    loss of every step that arrived within ``seconds`` of it, and the number
    of steps that were in flight beyond them."""
    span = span or (lambda name: contextlib.nullcontext())
    stamps, losses, queue = [], [], []
    t0 = clock()
    for _ in range(IN_FLIGHT):
        with span("bench.dispatch"):
            queue.append(dispatch())
    while True:
        with span("bench.dispatch"):
            queue.append(dispatch())
        with span("bench.fetch"):
            loss = fetch(queue.pop(0))
        now = clock()
        if now - t0 > seconds:
            break
        stamps.append(now)
        losses.append(loss)
    drained = 1 + len(queue)
    with span("bench.drain"):
        for pending in queue:
            fetch(pending)
    return t0, stamps, losses, drained


class Trainer:
    """The compiled step with its state: built once, compared, then timed."""

    def __init__(self, cell, seed: int, require_chip: bool = True):
        import jax

        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.family = cell.model()
        self.ref = cell.reference()
        self.seed = seed
        self.devices = (chip.require_chips(cell.chips) if require_chip
                        else jax.devices()[:cell.chips])
        self.readings = {}
        self.compile_log = compiles.CompileLog()

    def build(self):
        import jax

        t = self.traffic
        model = self.family.build_model(self.cfg, remat=t["remat"])
        hvd, mesh, opt = program.init_training(model, t)
        self.world = hvd.size()
        if self.world != self.cell.chips:
            raise RuntimeError(
                f"hvd.size()={self.world}, the cell asks {self.cell.chips}")
        self.make_params = jax.jit(weights.make_params(
            self.family.param_shapes(model, t["seq"])))
        self._place = lambda params: program.place_training_state(
            hvd, opt, params)
        self._rank_major = hvd.rank_sharding(mesh)
        params, state = self.reseed(self.seed)
        step = program.make_train_step(hvd, model, opt, mesh)
        t0 = time.perf_counter()
        lowered = step.lower(params, state, self.tokens, self.labels)
        t1 = time.perf_counter()
        self.step = lowered.compile()
        t2 = time.perf_counter()
        self.readings["trace_lower_s"] = t1 - t0
        self.readings["compile_s"] = t2 - t1
        # printing the module takes seconds: only where there is an exchange
        self.readings["allreduce_bytes"] = xtrace.world_allreduce_bytes(
            lowered.as_text(), self.world) if self.world > 1 else 0

    def reseed(self, seed: int):
        """Weights, optimizer state and batch of ``seed`` for the same
        compiled step."""
        import jax

        self.seed = seed
        self.params, self.state = self._place(
            self.make_params(weights.seed_key(seed)))
        self.host_batch = self.family.make_batch(
            self.cfg, self.traffic, self.world, seed)
        self.tokens, self.labels = (
            jax.device_put(x, self._rank_major) for x in self.host_batch)
        return self.params, self.state

    def _advance(self):
        self.params, self.state, loss = self.step(
            self.params, self.state, self.tokens, self.labels)
        return loss

    def first_steps(self) -> dict:
        """Steps 1..3 through the window's own call and feed, with what the
        comparison needs read from the state between them."""
        norms, change = _norm_programs(self.ref, self.make_params)
        losses = [chip.fetch_scalar(self._advance())]
        grad_norms = np.asarray(norms(program.momentum_trace(self.state)))
        for _ in range(COMPARED_STEPS - 1):
            losses.append(chip.fetch_scalar(self._advance()))
        change_norms = np.asarray(
            change(self.params, weights.seed_key(self.seed)))
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": change_norms}

    def warm_up(self):
        loss = None
        for _ in range(WARMUP_STEPS):
            loss = self._advance()
        chip.fetch_scalar(loss)

    def free(self):
        self.params = self.state = self.step = None
        self.tokens = self.labels = None
        gc.collect()


@functools.lru_cache(maxsize=None)
def _norm_programs(ref, make_params):
    """Jitted ``leaf_norms(tree)`` and ``change(params, key)``: the norm,
    leaf by leaf, of ``params`` less the weights that ``key`` gives."""
    import jax

    return (jax.jit(ref.leaf_norms),
            jax.jit(lambda p, key: ref.diff_norms(p, make_params(key))))


@functools.lru_cache(maxsize=None)
def _reference_step(ref, cfg_json, lr, momentum, precision, block_rows):
    import jax

    cfg = json.loads(cfg_json)
    return jax.jit(lambda p, m, a, b: ref.sgd_momentum_step(
        p, m, a, b, cfg, lr, momentum, precision, block_rows),
        donate_argnums=(0, 1))


def reference_first_steps(cell, make_params, seed, host_batch,
                          precision="float32", rows=None) -> dict:
    """What :meth:`Trainer.first_steps` reads, from the configuration's
    plain reference on the whole batch of all chips (``rows``: only the
    first so many rows, the mean over them, to stand for a fault)."""
    import jax
    import jax.numpy as jnp

    ref, cfg = cell.reference(), cell.config
    tokens, labels = (x.reshape(-1, x.shape[-1]) for x in host_batch)
    if rows is not None:
        tokens, labels = tokens[:rows], labels[:rows]
    o = cell.traffic["optimizer"]
    step = _reference_step(
        ref, ref.program_key(cfg), o["lr"], o["momentum"], precision,
        min(4, tokens.shape[0]))
    norms, change = _norm_programs(ref, make_params)
    params = make_params(weights.seed_key(seed))
    trace = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for i in range(COMPARED_STEPS):
        params, trace, loss, grads = step(params, trace, tokens, labels)
        losses.append(float(loss))
        if i == 0:
            grad_norms = np.asarray(norms(grads))
        del grads
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": np.asarray(
                change(params, weights.seed_key(seed)))}


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
        # the traced steps follow the window, through the same loop, so the
        # profiler's start and stop cost the window nothing
        trace = xtrace.record(
            lambda: drive_window(
                trainer._advance, chip.fetch_scalar, 1.0,
                span=recorder.span, clock=_StepBudget(TRACED_STEPS)),
            len(trainer.devices))
        readings["trace"] = xtrace.steady_steps(trace, TRACE_SKIP_STEPS)
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


class _StepBudget:
    """A clock for :func:`drive_window` that ends the loop after a number of
    steps: it reads 0 until ``steps`` stamps were taken, then far later."""

    def __init__(self, steps: int):
        self.left = steps + 1  # the first reading is t0

    def __call__(self) -> float:
        self.left -= 1
        return 0.0 if self.left >= 0 else 1e9
