"""Distributed optimization: the DistributedOptimizer / GradientTape layer.

TPU-native re-design of the reference's per-framework optimizer wrappers
(ref: horovod/torch/optimizer.py `_DistributedOptimizer` — per-parameter
grad hooks firing async allreduces, `backward_passes_per_step` local
aggregation, op=Average/Sum/Adasum, `gradient_predivide_factor`;
horovod/tensorflow/__init__.py `DistributedOptimizer` +
`DistributedGradientTape` [V]; SURVEY.md §2.4, §3.2, §3.5).

The reference hooks autograd to overlap per-tensor allreduces with backprop.
Under XLA that overlap is the *compiler's* job: expressing the gradient
reduction inside the jitted step lets XLA schedule collectives against
backprop compute (latency hiding on ICI) with no hook machinery. So:

* ``DistributedOptimizer(opt)`` wraps any optax ``GradientTransformation``:
  its ``update`` compresses → allreduces → decompresses gradients before the
  inner transform. Use inside ``jit``/``shard_map`` over the world axis.
* ``backward_passes_per_step=k`` accumulates k micro-batch gradients
  locally and communicates once — the reference's local-aggregation
  feature, which on TPU also amortizes ICI latency.
* ``DistributedGradientTape`` parity is ``hvd.value_and_grad`` /
  ``hvd.grad``: autodiff + gradient allreduce in one call.
"""

from __future__ import annotations

import functools
import itertools
import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from .common import guard as _guard
from .common import telemetry as _telemetry
from .common import tracing as _tracing
from .common.process_sets import ProcessSet
from .common.topology import WORLD_AXIS
from .ops import overlap, traced
from .ops.compression import Compression, Compressor
from .ops.reduction_ops import Adasum, Average, ReduceOp, Sum, resolve_op


def _under_trace() -> bool:
    """True while JAX traces the caller (jit, shard_map, grad):
    ``jax.core.trace_state_clean`` of earlier JAX, which 0.9 no longer
    has (its absence read as "not traced", and a jitted tape opened one
    host record at trace time and ticked never)."""
    return not jax.core.trace_ctx.is_top_level()


def _leaf_bytes(x) -> int:
    return int(getattr(x, "nbytes", 0) or 0)


def _allreduce_grads(
    grads,
    op: ReduceOp,
    compression,
    prescale_factor: float,
    postscale_factor: float,
    process_set: Optional[ProcessSet],
    axis_name: str,
    seed=0,
    residuals=None,
    groups=None,
):
    """Compress → allreduce → decompress, leaf-wise over the grad pytree.

    Equivalent of the reference's `_allreduce_grad_async` + synchronize
    loop (horovod/torch/optimizer.py [V]), except the 'async' part is
    XLA's static schedule rather than handles.

    Quantized-wire compressors (Compression.int8) can't go through the
    generic compress→psum→decompress shape — summing raw int8 wraps and
    each rank's scale differs — so they route to the quantized
    collective, which reduces after dequantization on every hop.
    """
    if getattr(compression, "quantized_wire", False):
        if process_set is not None and process_set.process_set_id != 0:
            raise NotImplementedError(
                "Compression.int8 over a process set is not supported; "
                "use fp16/bf16 compression or the global process set"
            )
        # Compression.hier_int8 on the traced/optimizer path: the real
        # two-level recipe (bf16 intra hops, int8 on the inter hop
        # only — the eager placement, no longer a flat degeneration)
        # whenever a slice split is resolvable for this axis. A
        # local-SGD local phase (groups=) has NO inter hop — the
        # quantized wire stays inside the slice instead.
        hier_stages = None
        if (
            groups is None
            and getattr(compression, "wire_format", None) == "int8_hier"
        ):
            from .common import topology as _topo

            hier_stages = _topo.hierarchy_stages(
                world=int(jax.lax.axis_size(axis_name)), mode="on"
            )

        def one_q(g, r=None):
            """One leaf through the quantized wire; with an error-
            feedback carry ``r`` (EF-SGD), last step's quantization
            error joins this step's wire signal and the new residual is
            returned alongside. One body for both paths so the
            prescale/postscale handling can't diverge.

            ``prescale_factor`` is handed to the collective, which
            folds it into the stage-1 wire scales — quantization is
            scale-invariant, so scaling n floats replaces a full HBM
            pre-multiply pass over the tensor (parity-tested against
            the two-pass form in test_fusion_quantized.py). The
            residual contract is input units: the carry joins the RAW
            gradient, before any scaling. A compressor that defines
            ``block_size`` (Compression.int8_block and descendants)
            gets block-wise wire scales on this path too."""
            block = getattr(compression, "block_size", None)
            if hier_stages is not None:
                x = g if r is None else g + r.astype(g.dtype)
                if r is None:
                    out = traced.hierarchical_allreduce_groups(
                        x, op=op, axis_name=axis_name,
                        stages=hier_stages, intra_wire="bf16",
                        inter_wire="int8", seed=seed, block_size=block,
                        prescale_factor=prescale_factor,
                    )
                    new_r = None
                else:
                    out, new_r = traced.hierarchical_allreduce_groups(
                        x, op=op, axis_name=axis_name,
                        stages=hier_stages, intra_wire="bf16",
                        inter_wire="int8", seed=seed, block_size=block,
                        prescale_factor=prescale_factor,
                        return_residual=True,
                    )
                    new_r = new_r.astype(r.dtype)
            elif r is None:
                out = traced.quantized_allreduce(
                    g, op=op, axis_name=axis_name, seed=seed,
                    prescale_factor=prescale_factor, block_size=block,
                    groups=groups,
                )
                new_r = None
            else:
                out, new_r = traced.quantized_allreduce(
                    g + r.astype(g.dtype), op=op, axis_name=axis_name,
                    seed=seed, return_residual=True,
                    prescale_factor=prescale_factor, block_size=block,
                    groups=groups,
                )
                # carry keeps its init dtype: a flip (e.g. bf16 params,
                # f32 grads) would change the state pytree mid-scan
                new_r = new_r.astype(r.dtype)
            if postscale_factor != 1.0:
                out = out * jnp.asarray(postscale_factor, out.dtype)
            return out, new_r

        if residuals is not None:
            # flatten rather than tree_map: grads pytrees containing
            # tuples/NamedTuples would collide with the (out, residual)
            # result pairs under an isinstance(tuple) is_leaf
            g_leaves, treedef = jax.tree_util.tree_flatten(grads)
            r_leaves = treedef.flatten_up_to(residuals)
            out_pairs = [
                one_q(g, r) for g, r in zip(g_leaves, r_leaves)
            ]
            reduced = jax.tree_util.tree_unflatten(
                treedef, [t[0] for t in out_pairs]
            )
            new_residuals = jax.tree_util.tree_unflatten(
                treedef, [t[1] for t in out_pairs]
            )
            return reduced, new_residuals

        return jax.tree_util.tree_map(lambda g: one_q(g)[0], grads)
    if residuals is not None:
        raise ValueError(
            "error_feedback requires a quantized-wire compression "
            "(Compression.int8); lossless/fp16 wires have no residual"
        )

    def one(g):
        wire, ctx = compression.compress(g)
        red = traced.allreduce(
            wire,
            op=op,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            process_set=process_set,
            axis_name=axis_name,
            groups=groups,
        )
        return compression.decompress(red, ctx)

    return jax.tree_util.tree_map(one, grads)


def _exchange_plan(grads, compression, *, world, op, buckets=0,
                   min_bucket_bytes=0):
    """:func:`traced.exchange_plan` for ``compression``'s wire on the
    traced path: the dtype ``compress`` gives each leaf, or int8 for a
    quantized wire; one collective a bucket of ``overlap``'s schedule,
    else one a leaf."""
    quantized = getattr(compression, "quantized_wire", False)

    def wire_dtype(dtype):
        if quantized:
            return jnp.int8
        return jax.eval_shape(
            lambda x: compression.compress(x)[0],
            jax.ShapeDtypeStruct((), dtype),
        ).dtype

    collectives = None
    if buckets:
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        collectives = len(overlap.schedule_for(
            leaves, treedef, buckets, min_bucket_bytes
        ).buckets)
    return traced.exchange_plan(
        grads, world=world, op=op, wire_dtype=wire_dtype,
        wire=compression.wire_format if quantized else None,
        buckets=buckets, collectives=collectives,
    )


class _AccumulationState(NamedTuple):
    inner: Any
    accum: Any  # running local gradient sum
    counter: jnp.ndarray  # micro-steps since last communication
    step: jnp.ndarray  # monotone update count — seeds stochastic rounding
    residual: Any = None  # error-feedback carry (quantized wire only)
    guard_skips: Any = None  # total non-finite skipped steps (guard on)
    guard_streak: Any = None  # CONSECUTIVE skips — escalation trigger
    # local-SGD round state (local_sgd_steps > 1 only; None leaves keep
    # plain jobs' state structure and checkpoints byte-stable):
    local_anchor: Any = None  # params at the last sync round
    local_residual: Any = None  # EF carry of the int8 inter wire


class LocalSGDGradientTransformation(NamedTuple):
    """An optax ``GradientTransformation`` plus the local-SGD sync
    round: ``sync(params, state) -> (new_params, new_state)`` is the
    SEPARATE traced reconciliation body — call it inside the same
    shard_map context as ``update`` but compile it as its OWN program
    (the local-phase step program must carry zero inter-slice replica
    groups; a ``lax.cond`` would bake the inter exchange into every
    step). Drive the cadence with :func:`horovod_tpu.local_sgd
    .maybe_sync`, which owns the retry/defer robustness contract."""

    init: Callable
    update: Callable
    sync: Callable
    local_sgd_steps: int = 1


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    named_parameters=None,  # accepted for API parity; names are pytree paths
    compression: Compressor = Compression.none,
    backward_passes_per_step: int = 1,
    op: Optional[ReduceOp] = None,
    gradient_predivide_factor: float = 1.0,
    average: Optional[bool] = None,
    prescale_factor: Optional[float] = None,
    postscale_factor: Optional[float] = None,
    process_set: Optional[ProcessSet] = None,
    axis_name: str = WORLD_AXIS,
    average_aggregated_gradients: bool = False,
    error_feedback: bool = False,
    overlap_buckets: Optional[int] = None,
    overlap_min_bytes: Optional[int] = None,
    grad_guard: Optional[bool] = None,
    guard_max_skips: Optional[int] = None,
    local_sgd_steps: Optional[int] = None,
    local_sgd_inter_wire: str = "int8",
    local_sgd_intra: Optional[int] = None,
) -> optax.GradientTransformation:
    """Wrap an optax transform with distributed gradient reduction
    (ref: hvd.DistributedOptimizer [V]).

    ``gradient_predivide_factor`` splits the averaging between pre- and
    post-division around the sum exactly like the reference (which uses it
    to keep fp16 sums in range): grads are multiplied by
    ``1/(size·f)`` before and ``f`` after... i.e. prescale=1/(size·f),
    postscale=f with op=Sum (ref: optimizer.py's predivide handling [V]).

    ``error_feedback=True`` (beyond parity; requires
    ``compression=Compression.int8``) carries each step's local
    quantization error into the next step's wire signal — EF-SGD, so
    the int8 wire's cumulative error stays bounded by a constant number
    of quanta instead of growing with the step count.

    ``overlap_buckets=N`` routes the exchange through the bucketed
    layer (``ops/overlap.py``): the gradient tree is partitioned into N
    size-balanced buckets in reverse production order and each bucket
    gets its OWN collective, so the compiled step carries N independent
    collectives XLA can schedule against remaining backward compute
    instead of one terminal exchange — the reference's autograd-hook
    overlap, recovered as compiler-visible dataflow. Bit-exact with the
    monolithic path for op=Sum fp32; within the per-bucket quantum
    bound for quantized wires (EF residuals, the prescale fold and
    block granularity are applied per bucket). ``None`` defers to
    ``HOROVOD_OVERLAP``/``HOROVOD_OVERLAP_BUCKETS``; 0 forces the
    monolithic path. Sum/Average only (Adasum's whole-tensor combine
    does not commute with bucket concat). For overlap of the exchange
    with the backward itself, prefer ``hvd.value_and_grad(...,
    overlap_buckets=N)`` — this wrapper only sees gradients after
    autodiff, so its buckets overlap each other and the update math.

    ``grad_guard=True`` (``None`` defers to ``HOROVOD_GUARD``) folds
    the non-finite sentinel into the compiled update
    (common/guard.py): one ``all(isfinite)`` scalar reduction per
    bucket (per leaf on the monolithic path) over the ALREADY-REDUCED
    gradients — replicated values, so the flag agrees across ranks
    with no extra collective — and a ``lax.cond`` that SKIPS the step
    when the flag trips: zero updates, inner state untouched, EF
    residuals kept at the last applied step's carry, the step counter
    still advancing (stochastic-rounding seeds never repeat). Each
    skip fires a callback counting ``guard.nonfinite_steps``; after
    ``guard_max_skips`` (``HOROVOD_GUARD_MAX_SKIPS``) CONSECUTIVE
    skips the escalation latches and ``State.commit()`` /
    ``hvd.guard_check()`` raise ``HorovodInternalError`` so the
    elastic restore contract fires. The no-skip path pays no host
    sync — the callback lives inside the skip branch only. The guard
    conds the whole inner update, so it requires a dtype-preserving
    inner transform (every elementwise optax chain is).

    ``local_sgd_steps=K`` (``None`` defers to
    ``HOROVOD_LOCAL_SGD_STEPS``; the mode engages at K > 1) switches
    the optimizer into local-SGD mode (horovod_tpu/local_sgd.py,
    ROADMAP item 3): every ``update`` exchanges gradients over the
    INTRA-slice replica groups only — fused, bucketed and monolithic
    paths alike, so the compiled step program carries zero
    inter-slice replica groups and every gradient byte stays on ICI —
    and the returned transformation gains a ``sync`` callable (see
    :class:`LocalSGDGradientTransformation`) that reconciles the
    parameter DELTAS since the last round across the inter (DCN) axis
    with hierarchical Adasum on the ``local_sgd_inter_wire``
    (default ``int8`` — EF residuals carried across rounds in the
    state's ``local_residual`` leaf). Params must ride the training
    loop RANK-MAJOR (``in_specs=P(hvd.WORLD_AXIS)``): slices diverge
    during the local phase, so a replicated ``P()`` spec would be a
    lie. K = 1 IS the existing path (bit-identical by construction).
    Sum/Average only; process sets don't compose. ``local_sgd_intra``
    injects an explicit chips-per-slice for the split (tests/bench on
    single-slice hosts; normal jobs let the topology resolve it).
    """
    op = resolve_op(op, average)
    from . import local_sgd as _local_sgd

    local_k = int(
        local_sgd_steps
        if local_sgd_steps is not None
        else _local_sgd.default_steps()
    )
    local_on = local_k > 1
    if local_on:
        if local_sgd_steps is None:
            # engaged via env: the caller may be an existing loop that
            # never drives the sync round — warn loudly once
            _local_sgd.warn_env_engaged(local_k)
        if op not in (Sum, Average):
            raise ValueError(
                "local_sgd_steps > 1 requires op=Sum/Average for the "
                "local phase (Adasum is the ROUND combiner, not the "
                "per-step gradient op)"
            )
        if process_set is not None and process_set.process_set_id != 0:
            raise NotImplementedError(
                "local_sgd_steps does not compose with process sets"
            )
        if local_sgd_inter_wire not in _local_sgd.INTER_WIRES:
            raise ValueError(
                f"unknown local_sgd_inter_wire {local_sgd_inter_wire!r}"
            )
    if gradient_predivide_factor != 1.0 and op != Average:
        raise ValueError(
            "gradient_predivide_factor requires op=Average (ref parity)"
        )
    if error_feedback and not getattr(compression, "quantized_wire", False):
        raise ValueError(
            "error_feedback=True requires a quantized-wire compression "
            "(Compression.int8)"
        )
    explicit_overlap = overlap_buckets is not None
    if overlap_buckets is None:
        overlap_buckets = overlap.default_buckets()
    overlap_buckets = int(overlap_buckets)
    if overlap_min_bytes is None:
        overlap_min_bytes = overlap.default_min_bytes()
    if overlap_buckets and op not in (Sum, Average):
        if explicit_overlap:
            raise ValueError(
                "overlap_buckets requires op=Sum/Average (Adasum/min/"
                "max/product do not commute with bucket concatenation)"
            )
        # HOROVOD_OVERLAP is a fleet-wide default: a job running an op
        # the bucketed layer can't carry keeps its monolithic path
        # instead of breaking
        overlap_buckets = 0
    k = int(backward_passes_per_step)
    if k < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    guard_on = (
        bool(grad_guard)
        if grad_guard is not None
        else _guard.default_enabled()
    )
    max_skips = int(
        guard_max_skips
        if guard_max_skips is not None
        else _guard.default_max_skips()
    )
    guard_src = _guard.new_source() if guard_on else 0

    def reduce_op_factors(n: int):
        if gradient_predivide_factor != 1.0 and op == Average:
            f = gradient_predivide_factor
            return ReduceOp.SUM, 1.0 / (n * f), f
        pre = prescale_factor if prescale_factor is not None else 1.0
        post = postscale_factor if postscale_factor is not None else 1.0
        return op, pre, post

    def _local_stages():
        """The two-level split for the traced axis (local mode only;
        raises when no split resolves — a one-slice local phase is
        the caller asking for a mode that cannot exist)."""
        return _local_sgd.resolve_stages(
            int(jax.lax.axis_size(axis_name)), intra=local_sgd_intra
        )

    @jax.named_scope(traced.EXCHANGE_SCOPE)  # on all it puts into the step
    def communicate(grads, seed, residuals=None):
        """Exchange + optional guard flag. Returns a uniform
        ``(reduced, new_residuals_or_None, finite_or_None)`` triple so
        the update paths never re-derive the unpacking rules."""
        groups = _local_stages()[0] if local_on else None
        n = (
            process_set.size
            if process_set is not None and process_set.process_set_id != 0
            else (
                len(groups[0]) if groups is not None
                else jax.lax.axis_size(axis_name)
            )
        )
        eff_op, pre, post = reduce_op_factors(n)
        # trace time only: what the step hands to its collectives
        with _exchange_plan(
            grads, compression, world=n, op=eff_op, buckets=overlap_buckets,
            min_bucket_bytes=overlap_min_bytes,
        ):
            return exchange(grads, seed, residuals, groups, eff_op, pre, post)

    def exchange(grads, seed, residuals, groups, eff_op, pre, post):
        if overlap_buckets:
            out = overlap.bucketed_allreduce(
                grads, op=eff_op, n_buckets=overlap_buckets,
                compression=compression, prescale_factor=pre,
                postscale_factor=post, process_set=process_set,
                axis_name=axis_name, seed=seed, residuals=residuals,
                min_bucket_bytes=overlap_min_bytes,
                return_finite=guard_on,
                groups=groups,
            )
            if guard_on:
                if residuals is not None:
                    return out
                reduced, finite = out
                return reduced, None, finite
            if residuals is not None:
                reduced, new_r = out
                return reduced, new_r, None
            return out, None, None
        out = _allreduce_grads(
            grads, eff_op, compression, pre, post, process_set, axis_name,
            seed=seed, residuals=residuals, groups=groups,
        )
        if residuals is not None:
            reduced, new_r = out
        else:
            reduced, new_r = out, None
        finite = traced.tree_finite(reduced) if guard_on else None
        return reduced, new_r, finite

    def guarded_apply(reduced, new_residual, finite, state, params):
        """The skip-step cond (common/guard.py): apply the inner
        update only when the reduced gradients are finite; otherwise
        zero updates, untouched inner state, the LAST APPLIED step's
        EF carry, and a host callback (skip branch only — the healthy
        path never reaches the host). Returns
        ``(updates, inner, residual, skips, streak)``."""
        streak_next = state.guard_streak + 1

        def apply(_):
            updates, inner = optimizer.update(reduced, state.inner, params)
            return (
                updates, inner, new_residual, state.guard_skips,
                jnp.zeros((), jnp.int32),
            )

        def skip(_):
            jax.debug.callback(
                functools.partial(
                    _guard.record_skip, max_skips=max_skips,
                    source=guard_src,
                ),
                streak_next, state.step,
            )
            zeros = jax.tree_util.tree_map(jnp.zeros_like, reduced)
            return (
                zeros, state.inner, state.residual,
                state.guard_skips + 1, streak_next,
            )

        with jax.named_scope(traced.UPDATE_SCOPE):
            return jax.lax.cond(finite, apply, skip, operand=None)

    # the wrapped transform's update, named in the compiled step
    inner_update = jax.named_scope(traced.UPDATE_SCOPE)(optimizer.update)

    def init_fn(params):
        with _tracing.span("hvd.init.optimizer_init") as sp:
            state = _init_state(params)
            leaves = jax.tree_util.tree_leaves(state)
            sp.tag(
                leaves=len(leaves),
                bytes=sum(_leaf_bytes(x) for x in leaves),
            )
            return state

    def _init_state(params):
        inner = optimizer.init(params)
        zero = jnp.zeros((), jnp.int32)
        residual = (
            jax.tree_util.tree_map(jnp.zeros_like, params)
            if error_feedback
            else None
        )
        # guard counters ride the state pytree only when the guard is
        # on — None leaves are empty subtrees, so unguarded jobs keep
        # the exact state structure (and checkpoints) they had
        gskips = zero if guard_on else None
        gstreak = zero if guard_on else None
        # local-SGD round state: the anchor starts AT the initial
        # params (round 0's delta measures from here); the EF carry of
        # the int8 inter wire starts empty
        anchor = (
            jax.tree_util.tree_map(jnp.asarray, params)
            if local_on
            else None
        )
        local_res = (
            jax.tree_util.tree_map(jnp.zeros_like, params)
            if local_on and local_sgd_inter_wire == "int8"
            else None
        )
        if k == 1:
            return _AccumulationState(
                inner=inner, accum=None, counter=zero, step=zero,
                residual=residual, guard_skips=gskips,
                guard_streak=gstreak, local_anchor=anchor,
                local_residual=local_res,
            )
        accum = jax.tree_util.tree_map(jnp.zeros_like, params)
        return _AccumulationState(
            inner=inner, accum=accum, counter=zero, step=zero,
            residual=residual, guard_skips=gskips, guard_streak=gstreak,
            local_anchor=anchor, local_residual=local_res,
        )

    def update_fn(grads, state: _AccumulationState, params=None):
        # trace time only: how long the exchange's and the update's
        # Python takes is part of every cold start (init.trace_optimizer_s)
        with _tracing.trace_time_span(
            "hvd.trainer.trace_update", state.step
        ) as sp:
            if sp is not None:
                sp.tag(leaves=len(jax.tree_util.tree_leaves(grads)))
            return _update(grads, state, params)

    def _update(grads, state: _AccumulationState, params):
        # Flight-recorder auto-threading (common/telemetry.py): one
        # step-boundary tick per compiled update, riding the SAME
        # internal step counter that seeds stochastic rounding — this
        # is how fully-jitted loops (where no host code runs per step)
        # still produce StepStats records. Gated at TRACE time: when
        # telemetry is off the compiled program carries nothing, and
        # enabling telemetry after compile needs a retrace (documented
        # in docs/observability.md).
        if _telemetry.auto_enabled():
            jax.debug.callback(_telemetry.device_step_tick, state.step)
        if k == 1:
            reduced, residual, finite = communicate(
                grads, state.step,
                residuals=state.residual if error_feedback else None,
            )
            if guard_on:
                updates, inner, residual, skips, streak = guarded_apply(
                    reduced, residual, finite, state, params
                )
                return updates, _AccumulationState(
                    inner=inner, accum=None, counter=state.counter,
                    step=state.step + 1, residual=residual,
                    guard_skips=skips, guard_streak=streak,
                    local_anchor=state.local_anchor,
                    local_residual=state.local_residual,
                )
            updates, inner = inner_update(reduced, state.inner, params)
            return updates, _AccumulationState(
                inner=inner, accum=None, counter=state.counter,
                step=state.step + 1, residual=residual,
                local_anchor=state.local_anchor,
                local_residual=state.local_residual,
            )

        # Local aggregation (`backward_passes_per_step` [V]): accumulate k
        # micro-grads, communicate once, step once; off-boundary
        # micro-steps emit zero updates. Like the reference, the SUM of the
        # k micro-grads is applied unless average_aggregated_gradients=True
        # (ref: gradient_aggregation defaults,
        # horovod/tensorflow/gradient_aggregation*.py [V]).
        with jax.named_scope(traced.ACCUMULATE_SCOPE):
            accum = jax.tree_util.tree_map(
                lambda a, g: a + g, state.accum, grads
            )
        counter = state.counter + 1
        boundary = counter >= k

        def do_step(_):
            with jax.named_scope(traced.ACCUMULATE_SCOPE):
                agg = (
                    jax.tree_util.tree_map(lambda a: a / k, accum)
                    if average_aggregated_gradients
                    else accum
                )
            reduced, residual, finite = communicate(
                agg, state.step,
                residuals=state.residual if error_feedback else None,
            )
            with jax.named_scope(traced.ACCUMULATE_SCOPE):
                zeroed = jax.tree_util.tree_map(jnp.zeros_like, accum)
            if guard_on:
                # a skipped boundary still clears the accumulator: the
                # poisoned micro-batch window is discarded, not replayed
                updates, inner, residual, skips, streak = guarded_apply(
                    reduced, residual, finite, state, params
                )
                return (
                    updates, inner, zeroed, jnp.zeros((), jnp.int32),
                    residual, skips, streak,
                )
            updates, inner = inner_update(reduced, state.inner, params)
            return (
                updates, inner, zeroed, jnp.zeros((), jnp.int32),
                residual, state.guard_skips, state.guard_streak,
            )

        def skip_step(_):
            zeros = jax.tree_util.tree_map(jnp.zeros_like, accum)
            return (
                zeros, state.inner, accum, counter, state.residual,
                state.guard_skips, state.guard_streak,
            )

        (
            updates, inner, accum_out, counter_out, residual_out,
            skips_out, streak_out,
        ) = jax.lax.cond(boundary, do_step, skip_step, operand=None)
        return updates, _AccumulationState(
            inner=inner, accum=accum_out, counter=counter_out,
            step=state.step + 1, residual=residual_out,
            guard_skips=skips_out, guard_streak=streak_out,
            local_anchor=state.local_anchor,
            local_residual=state.local_residual,
        )

    if not local_on:
        return optax.GradientTransformation(init_fn, update_fn)

    def sync_fn(params, state: _AccumulationState):
        """The K-step reconciliation round (compile as its OWN program
        — see LocalSGDGradientTransformation): parameter deltas since
        the last anchor merge across slices by hierarchical Adasum on
        the inter wire; params and anchor land on the consensus, the
        EF carry rolls to the next round."""
        stages = _local_stages()
        new_params, new_res = _local_sgd.sync_tree(
            params, state.local_anchor,
            residual=state.local_residual,
            stages=stages, axis_name=axis_name,
            inter_wire=local_sgd_inter_wire, seed=state.step,
            return_residual=local_sgd_inter_wire == "int8",
        )
        return new_params, state._replace(
            local_anchor=new_params, local_residual=new_res
        )

    return LocalSGDGradientTransformation(
        init_fn, update_fn, sync_fn, local_k
    )


# ---------------------------------------------------------------- tape API


def value_and_grad(
    fun: Callable,
    argnums=0,
    has_aux: bool = False,
    op: Optional[ReduceOp] = None,
    average: Optional[bool] = None,
    compression: Compressor = Compression.none,
    process_set: Optional[ProcessSet] = None,
    axis_name: str = WORLD_AXIS,
    overlap_buckets: Optional[int] = None,
    overlap_min_bytes: Optional[int] = None,
    **grad_kwargs,
):
    """jax.value_and_grad + gradient allreduce: the DistributedGradientTape
    equivalent (ref: horovod/tensorflow/__init__.py
    DistributedGradientTape._allreduce_grads [V], SURVEY.md §3.5).

    ``overlap_buckets=N`` is the in-backprop path: the differentiated
    argument passes through :func:`hvd.overlap_boundary` before use, so
    its cotangents leave through N independent per-bucket collectives
    DURING backprop — the returned gradients are already reduced, and
    the compiled step's collectives sit at their buckets' dataflow
    frontiers where XLA overlaps them with the remaining backward
    compute (the reference's autograd-hook latency hiding,
    arXiv 1802.05799 §3, as static dataflow). ``None`` defers to
    ``HOROVOD_OVERLAP``/``HOROVOD_OVERLAP_BUCKETS``; requires a single
    int ``argnums`` and op=Sum/Average.

    With ``compression=Compression.int8``, pass your step counter to the
    wrapped function as ``hvd_step=`` (a traced scalar is fine): it seeds
    the stochastic rounding so quantization noise varies across steps and
    stays unbiased over time. ``DistributedOptimizer`` threads its own
    step automatically; the tape API has no state, so when the caller
    does not provide one an INTERNAL per-wrapper call counter is
    threaded instead — correct in eager use, but constant-folded if the
    caller jits the wrapped function, so a warning (once) nudges jit
    users to thread a real step. Passing the SAME concrete seed twice
    also warns once: a repeated seed re-applies the identical stochastic
    rounding pattern every step, turning the unbiased quantizer into a
    biased one. Other compressors ignore it."""
    op = resolve_op(op, average)
    explicit_overlap = overlap_buckets is not None
    if overlap_buckets is None:
        overlap_buckets = overlap.default_buckets()
    overlap_buckets = int(overlap_buckets)
    if overlap_min_bytes is None:
        overlap_min_bytes = overlap.default_min_bytes()
    if overlap_buckets and (
        op not in (Sum, Average) or not isinstance(argnums, int)
    ):
        if explicit_overlap:
            if not isinstance(argnums, int):
                raise ValueError(
                    "overlap_buckets requires a single int argnums "
                    "(the boundary wraps one argument's pytree)"
                )
            raise ValueError(
                "overlap_buckets requires op=Sum/Average (Adasum/min/"
                "max/product do not commute with bucket concatenation)"
            )
        # env-default overlap: unsupported shapes keep the monolithic
        # path instead of breaking (same rationale as the optimizer)
        overlap_buckets = 0
    vg = jax.value_and_grad(fun, argnums=argnums, has_aux=has_aux, **grad_kwargs)
    auto_step = itertools.count()
    seen = {"last": None, "warned": False}
    quantized = getattr(compression, "quantized_wire", False)

    def _resolve_seed(args, kwargs, hvd_step):
        if not quantized:
            return 0 if hvd_step is None else hvd_step
        if hvd_step is None:
            step = next(auto_step)
            # Tracer detection: a cheap shallow scan on EVERY call —
            # top-level args plus one level into dict/list/tuple args,
            # which covers the params-pytree idiom — catches
            # eager-calls-then-jit (trace at step > 0); a full pytree
            # flatten runs on the FIRST call only, so deeply nested
            # leaves are caught at jit-from-the-start without paying
            # O(n_leaves) per eager step forever.
            def _shallow(objs):
                for a in objs:
                    if isinstance(a, dict):
                        yield from a.values()
                    elif isinstance(a, (list, tuple)):
                        yield from a
                    else:
                        yield a

            traced_call = any(
                isinstance(a, jax.core.Tracer)
                for a in _shallow(list(args) + list(kwargs.values()))
            )
            if not traced_call and step == 0:
                traced_call = any(
                    isinstance(leaf, jax.core.Tracer)
                    for leaf in jax.tree_util.tree_leaves((args, kwargs))
                )
            if not seen["warned"] and traced_call:
                seen["warned"] = True
                warnings.warn(
                    "hvd.value_and_grad(compression=int8) is being traced "
                    "(jit) without hvd_step=; the auto-threaded step "
                    "counter constant-folds into the compiled program, so "
                    "every step reuses one stochastic-rounding pattern. "
                    "Pass your step counter as hvd_step= (a traced scalar "
                    "is fine).",
                    stacklevel=3,
                )
            return step
        if isinstance(hvd_step, int):
            if not seen["warned"] and seen["last"] == hvd_step:
                seen["warned"] = True
                warnings.warn(
                    f"hvd.value_and_grad(compression=int8) received the "
                    f"same hvd_step={hvd_step} twice: a constant seed "
                    f"repeats the stochastic-rounding pattern every step "
                    f"(biased over time). Thread an incrementing step "
                    f"counter.",
                    stacklevel=3,
                )
            seen["last"] = hvd_step
        return hvd_step

    def _auto_telemetry_begin(hvd_step) -> bool:
        """Open a flight-recorder step around this (host-side) call —
        the tape-API half of telemetry auto-threading. Skipped under
        tracing (a jitted wrapper runs this body once, at trace time —
        the optimizer's debug-callback tick owns that case) and when a
        step is already open (explicit hvd.step_begin wins)."""
        if not _telemetry.auto_enabled() or _under_trace():
            return False
        step = hvd_step if isinstance(hvd_step, int) else None
        return _telemetry.hub().auto_step_begin(step)

    def wrapped(*args, hvd_step=None, **kwargs):
        seed = _resolve_seed(args, kwargs, hvd_step)
        opened = _auto_telemetry_begin(hvd_step)
        if (
            not opened
            and hvd_step is not None
            and _telemetry.auto_enabled()
        ):
            # Traced call (the usual shape: vg inside jit/shard_map): a
            # host-side record is impossible — this body runs ONCE, at
            # trace time — but a THREADED step counter lets the
            # compiled program tick the flight recorder instead, same
            # mechanism as the optimizer's auto-threading. A concrete
            # constant hvd_step under jit collapses to one record (the
            # quantized-seed warning above covers that misuse).
            if _under_trace():
                # source "tape": these ids are the CALLER's step
                # counter, so they outrank the optimizer's internal
                # ticks — when both fire in one program only one
                # source drives the recorder (hub.tick dedup)
                jax.debug.callback(
                    functools.partial(
                        _telemetry.device_step_tick, source="tape"
                    ),
                    hvd_step,
                )
        try:
            return _wrapped_body(args, kwargs, seed)
        finally:
            if opened:
                _telemetry.hub().auto_step_end()

    def _wrapped_body(args, kwargs, seed):
        if overlap_buckets:
            # in-backprop exchange: grads come back ALREADY reduced —
            # the boundary's custom_vjp emitted the per-bucket
            # collectives inside the backward pass
            def fun2(*a, **k):
                a = list(a)
                a[argnums] = overlap.overlap_boundary(
                    a[argnums], op=op, n_buckets=overlap_buckets,
                    compression=compression, process_set=process_set,
                    axis_name=axis_name, seed=seed,
                    min_bucket_bytes=overlap_min_bytes,
                )
                return fun(*a, **k)

            vg2 = jax.value_and_grad(
                fun2, argnums=argnums, has_aux=has_aux, **grad_kwargs
            )
            return vg2(*args, **kwargs)
        val, grads = vg(*args, **kwargs)
        with jax.named_scope(traced.EXCHANGE_SCOPE), _exchange_plan(
            grads, compression, op=op, world=lambda: (
                process_set.size
                if process_set is not None and process_set.process_set_id != 0
                else jax.lax.axis_size(axis_name)
            ),
        ):
            grads = _allreduce_grads(
                grads, op, compression, 1.0, 1.0, process_set, axis_name,
                seed=seed,
            )
        return val, grads

    return wrapped


def grad(fun: Callable, **kwargs):
    vg = value_and_grad(fun, **kwargs)

    def wrapped(*args, **kw):
        _, g = vg(*args, **kw)
        return g

    return wrapped


# ------------------------------------------------- parameter broadcast API


def broadcast_parameters(params, root_rank: int = 0):
    """Make every rank hold root_rank's parameters
    (ref: horovod/torch/functions.py broadcast_parameters /
    tensorflow broadcast_variables [V], SURVEY.md §5.4).

    TPU-native semantics, two cases per leaf:

    * **host / replicated leaf** — placing it with a replicated sharding
      sourced from the controller's copy IS the broadcast; XLA moves the
      bytes over ICI (under a single controller there is exactly one
      source copy, so root_rank is moot).
    * **rank-major leaf** (leading dim = world, sharded over the world
      axis — the eager convention for per-rank-divergent state): every
      rank's row is overwritten with ``root_rank``'s, which is the
      reference's actual semantics (rank 0 may have restored a
      checkpoint the others don't have)."""
    with _tracing.span("hvd.init.broadcast_parameters") as sp:
        return _broadcast_tree(params, root_rank, sp)


def _broadcast_tree(params, root_rank: int, sp):
    """:func:`broadcast_parameters` inside the caller's span, which
    gets the leaves, the bytes and the transfers (``device_put`` calls:
    one per leaf, two per rank-major leaf) as tags."""
    from .common import basics
    from .common.topology import WORLD_AXIS, replicated_sharding

    mesh = basics.mesh()
    world = int(mesh.devices.size)
    sharding = replicated_sharding(mesh)

    def _rank_major(x) -> bool:
        if not isinstance(x, jax.Array) or x.ndim == 0:
            return False
        if x.shape[0] != world:
            return False
        spec = getattr(x.sharding, "spec", None)
        return bool(spec) and spec[0] == WORLD_AXIS

    def one(x):
        if _rank_major(x):
            root = jax.device_put(x[root_rank], sharding)
            # All rows = root's; re-place with the ORIGINAL rank-major
            # sharding so per-device memory stays 1/world of the buffer
            # and a second broadcast still recognizes the leaf.
            return jax.device_put(
                jnp.broadcast_to(root[None], x.shape), x.sharding
            )
        return jax.device_put(x, sharding)

    out = jax.tree_util.tree_map(one, params)
    leaves = jax.tree_util.tree_leaves(out)
    sp.tag(
        leaves=len(leaves), bytes=sum(_leaf_bytes(x) for x in leaves),
        device_puts=len(leaves) + sum(map(_rank_major, leaves)),
    )
    return out


def broadcast_optimizer_state(opt_state, root_rank: int = 0):
    """Replicate optimizer state (ref: broadcast_optimizer_state [V]).
    Same mechanism as broadcast_parameters — optax states are pytrees."""
    with _tracing.span("hvd.init.broadcast_optimizer_state") as sp:
        return _broadcast_tree(opt_state, root_rank, sp)


def broadcast_object(obj, root_rank: int = 0, name: Optional[str] = None):
    """Arbitrary-object broadcast (ref: horovod/torch/functions.py
    broadcast_object, pickle-over-collective [V]). Under a single
    controller every rank already shares the controller's Python objects;
    in multi-controller jobs the runner's rendezvous KV store carries the
    pickled payload (runner/rendezvous.py)."""
    import jax as _jax

    if _jax.process_count() == 1:
        return obj
    from .runner.rendezvous import broadcast_via_kv  # pragma: no cover

    return broadcast_via_kv(obj, root_rank, name)  # pragma: no cover


def allgather_object(obj, name: Optional[str] = None):
    """Gather one arbitrary object per rank into a list ordered by rank
    (ref: horovod/torch/functions.py allgather_object,
    pickle-over-allgather [V]). Under the single controller this process
    speaks for every rank, so the list is [obj] * size; multi-controller
    jobs gather pickles through the rendezvous KV like broadcast_object.
    """
    import jax as _jax

    from .common import basics

    if _jax.process_count() == 1:
        world = basics.size() if basics.is_initialized() else 1
        return [obj] * world
    from .runner.rendezvous import allgather_via_kv  # pragma: no cover

    return allgather_via_kv(obj, name)  # pragma: no cover
