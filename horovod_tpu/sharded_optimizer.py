"""ZeRO-style sharded weight update for data-parallel training.

Beyond-parity, TPU-first (the reference has no analog): three sharding
stages over one optimizer, selected by ``zero_stage`` (default
``HOROVOD_ZERO_STAGE``; the staging mirrors DeepSpeed's ZeRO and the
XLA "automatic cross-replica sharding of weight update" recipe —
PAPERS.md: Xu et al., arXiv:2004.13336, pattern reference only):

* ``zero_stage=1`` — optimizer-state sharding (the original contract):
  each rank **reduce-scatters** the gradients (1/N shard each, half the
  wire bytes of a ring allreduce), runs the inner transform on its
  shard only (Adam moments etc. live 1/N-sharded), then **all-gathers**
  the parameter updates. Total communication equals one ring allreduce;
  optimizer math and state memory drop to 1/N.
* ``zero_stage=2`` — gradient sharding on top: grads taken through
  :meth:`value_and_grad` are reduce-scattered **per overlap bucket
  inside backprop** (a ``custom_vjp`` boundary — the mirror of
  ``hvd.overlap_boundary``), so each bucket's reduce-scatter output IS
  the per-rank shard slice and no reduced full-gradient buffer ever
  materializes. The int8/bf16 quantized wire applies to both exchange
  legs (``wire=``, per-bucket resolution via
  ``ops.overlap.resolve_wire``/WireTuner) with error-feedback residual
  rows carried in the optimizer state (``error_feedback=True``).
* ``zero_stage=3`` — parameter sharding: params live as
  ``[world, cols]`` shard rows between steps (:meth:`init_params`,
  layout: ``parallel.fsdp.host_shard_rows``). The forward all-gathers
  each parameter bucket through a ``custom_vjp`` boundary at its
  forward dataflow frontier — the compiled HLO carries N INDEPENDENT
  all-gathers interleaved into compute, not one up-front unshard — and
  the backward's cotangent leaves through the same bucketed
  reduce-scatter, landing gradients directly in shard geometry.
  :meth:`update` then updates the local shard with NO collective (the
  next forward's gathers re-publish the new params), so replicated
  param+grad residency drops world-fold.

Contract (all stages):

* ``opt = ShardedDistributedOptimizer(optax.adam(1e-3), zero_stage=s)``
* ``state = opt.init(params)`` — OUTSIDE jit/shard_map. Every state
  leaf gains a leading ``world`` axis (rank r's shard at index r;
  scalar leaves like Adam's ``count`` are broadcast), so the whole
  state threads through ``jax.shard_map`` with a uniform
  ``P(WORLD_AXIS)`` spec. Stage 3 adds
  ``pstate = opt.init_params(params)`` with the same convention.
* ``updates, state = opt.update(grads, state, params)`` — INSIDE
  ``shard_map`` over the world axis. Stages 1-2 accept full
  (replicated-shape) grads/params and return full updates; grads
  produced by :meth:`value_and_grad` arrive pre-scattered (per-leaf
  shard slices) and skip the internal reduce-scatter. Stage 3 takes
  shard grads + ``opt.local_shards(pstate)`` and returns SHARD
  updates — apply them with ``optax.apply_updates`` on the local
  shards and re-stack with ``opt.as_rows``.

Supported inner transforms: elementwise ones (sgd, momentum, adam,
adamw, rmsprop, ...). Norm-based transforms like
``clip_by_global_norm`` would compute shard-LOCAL norms inside the
sharded update and silently train wrong; apply gradient clipping to
the full gradients BEFORE this wrapper instead. Construction runs a
**differential probe** (VERDICT r3 #5): the inner transform is applied
to a fixed pytree both whole and shard-wise — a mismatch means the
update is not elementwise and raises ``ValueError`` with the
clip-before-wrapper recipe instead of letting training silently
diverge. ``HOROVOD_SHARDED_OPT_PROBE=0`` skips the probe (e.g. for a
deliberately stochastic transform that the probe cannot compare).

Shard layout is owned by ``parallel/fsdp.py`` (ONE source of truth for
the flat pad/split geometry — this module holds no private copy), and
the bucketed exchange legs by ``ops/overlap.py``
(``bucketed_reduce_scatter`` / ``bucketed_shard_all_gather``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .common.topology import WORLD_AXIS
from .ops import traced as _traced
from .ops.reduction_ops import Average, ReduceOp, Sum, resolve_op
from .parallel.fsdp import (
    dyn_shard as _shard_dyn_impl,
    host_shard as _shard_host_impl,
    host_shard_rows,
    host_unshard,
    pad_to as _pad_to_impl,
    reshard_rows,
    shard_cols,
)

_WIRE_FORMATS = ("fp32", "bf16", "int8", "auto")


def _pad_to(flat, n):
    return _pad_to_impl(flat, n)


def _shard_host(x, n, r):
    """Host-side shard r of array x (init path, outside jit)."""
    return _shard_host_impl(x, n, r)


def _shard_dyn(x, n, idx):
    """Traced shard selection by the rank's axis_index (update path)."""
    return _shard_dyn_impl(x, n, idx)


def _probe_nonelementwise(inner: optax.GradientTransformation) -> bool:
    """Differential probe: does `inner` give different updates when its
    inputs are sharded? Applies the transform to a fixed two-leaf pytree
    (values chosen so a global-norm clip at any common max_norm actually
    fires) once whole and once split into 2 shards per leaf — exactly
    the flatten-and-split geometry `update` uses. Elementwise chains
    (sgd/momentum/adam/adamw/rmsprop/weight-decay/schedules) match to
    float tolerance; anything coupling elements across the tree
    (clip_by_global_norm, adaptive_grad_clip, centralization) does not.

    Returns True when a mismatch is detected; False when the transform
    matches or cannot be probed (an inner transform that rejects the
    probe shapes is left to the docstring contract).
    """
    # The (128, 128) leaf exists for SHAPE-GATED couplings: adafactor
    # factors its second moment only when both dims >= 128, and the
    # sharded path always flattens to 1-D (where it falls back to
    # unfactored RMS) — a tiny-leaf probe would let it through.
    _det = np.linspace(-1.0, 1.0, 128 * 128, dtype=np.float32)
    params = {
        "w": jnp.asarray([1.0, -2.0, 3.0, -4.0], jnp.float32),
        "b": jnp.asarray([0.5, 0.25], jnp.float32),
        "m": jnp.asarray(_det.reshape(128, 128)),
    }
    # THREE steps with shard-norm ratios that shift every step: a
    # one-step probe misses transforms whose first update is
    # scale-invariant (clip→adam: Adam's step-1 update is ~sign(g), so
    # shard-local clip factors cancel until the moments carry history).
    # Norms ~10 ensure any realistic clip threshold actually fires.
    gm = jnp.asarray((_det + 0.37).reshape(128, 128))
    # top/bottom row-halves land in different shards after the flatten
    half = jnp.concatenate(
        [
            jnp.full((64, 128), 0.05, jnp.float32),
            jnp.full((64, 128), 6.0, jnp.float32),
        ]
    )
    grad_steps = [
        {
            "w": jnp.asarray([6.0, -8.0, 0.5, 2.0], jnp.float32),
            "b": jnp.asarray([-3.0, 1.5], jnp.float32),
            "m": gm * 3.0,
        },
        {  # shard-norm pattern reversed vs step 1
            "w": jnp.asarray([0.1, 0.2, 9.0, -7.0], jnp.float32),
            "b": jnp.asarray([4.0, -0.05], jnp.float32),
            "m": gm * half,
        },
        {
            "w": jnp.asarray([-5.0, 0.3, 0.4, 6.0], jnp.float32),
            "b": jnp.asarray([0.2, -8.0], jnp.float32),
            "m": gm * half[::-1],
        },
    ]

    def _split(tree, r):
        return jax.tree_util.tree_map(
            lambda x: x.reshape(2, -1)[r], tree
        )

    try:
        full_state = inner.init(params)
        full_upds = []
        for g in grad_steps:
            u, full_state = inner.update(g, full_state, params)
            full_upds.append(u)
        shard_upds = [[] for _ in grad_steps]
        for r in range(2):
            p_r = _split(params, r)
            state_r = inner.init(p_r)
            for step, g in enumerate(grad_steps):
                u_r, state_r = inner.update(_split(g, r), state_r, p_r)
                shard_upds[step].append(u_r)
        recombined = [
            jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate(
                    [a.reshape(-1), b.reshape(-1)]
                ),
                *pair,
            )
            for pair in shard_upds
        ]
    except Exception:
        return False  # unprobeable shapes: fall back to the documented contract
    for full_u, shard_u in zip(full_upds, recombined):
        leaves_f = jax.tree_util.tree_leaves(full_u)
        leaves_s = jax.tree_util.tree_leaves(shard_u)
        if any(
            not np.allclose(
                np.asarray(a, np.float32).reshape(-1),
                np.asarray(b, np.float32).reshape(-1),
                rtol=1e-5,
                atol=1e-6,
            )
            for a, b in zip(leaves_f, leaves_s)
        ):
            return True
    return False


class ShardedDistributedOptimizer:
    """Data-parallel optimizer with reduce-scatter/all-gather weight
    update and ZeRO-1/2/3 sharding stages (module docstring)."""

    def __init__(
        self,
        optimizer: optax.GradientTransformation,
        op: Optional[ReduceOp] = None,
        average: Optional[bool] = None,
        axis_name: str = WORLD_AXIS,
        world: Optional[int] = None,
        overlap_buckets: Optional[int] = None,
        overlap_min_bytes: Optional[int] = None,
        grad_guard: Optional[bool] = None,
        guard_max_skips: Optional[int] = None,
        zero_stage: Optional[int] = None,
        wire: Optional[str] = None,
        wire_block: Optional[int] = None,
        error_feedback: bool = False,
        hierarchical: Optional[bool] = None,
        local_sgd_steps: Optional[int] = None,
        local_sgd_inter_wire: str = "int8",
        local_sgd_intra: Optional[int] = None,
    ):
        """``zero_stage`` selects the sharding stage (module docstring);
        ``None`` defers to ``HOROVOD_ZERO_STAGE`` (default 1). Stage 3
        always runs the bucketed exchange (``overlap_buckets`` floors
        at 1 — its schedule IS the parameter gather plan).

        ``overlap_buckets=N`` buckets the exchange (ops/overlap.py):
        gradients reduce-scatter as N independent per-bucket collectives
        (member leaves' padded [n, ·] panes concatenated column-wise —
        elementwise identical to the per-leaf scatter, so the shard
        values are bit-exact) and parameter updates all-gather the same
        way. Because the inner transform is ELEMENTWISE (the probe
        enforces it), the single ``inner.update`` call decomposes into
        per-leaf dataflow: bucket k's update math depends only on
        bucket k's reduce-scatter output, so XLA overlaps the update
        compute with the tail of the exchange — the shard-by-shard
        interleave of arXiv 2004.13336, with state/checkpoint layout
        unchanged. ``None`` defers to ``HOROVOD_OVERLAP``/
        ``HOROVOD_OVERLAP_BUCKETS``; 0 keeps the per-leaf collectives.

        ``wire`` picks the exchange wire format per bucket
        (``fp32``/``bf16``/``int8``/``auto``; ``None`` defers to
        ``HOROVOD_ZERO_WIRE``, default fp32 — deliberately NOT
        ``HOROVOD_FUSION_WIRE``, the eager fused-wire knob). ``auto``
        resolves per bucket through
        ``ops.overlap.resolve_wire`` (size floor + WireTuner).
        ``error_feedback=True`` (stages 1-2, quantized-capable wire,
        full-gradient update path) carries both legs' quantization
        errors in the optimizer state — ``rs`` rows in full gradient
        geometry, ``ag`` rows in shard geometry (1/N per rank) — plus a
        per-step wire-seed counter, all riding the same
        leading-world-axis convention so ``reshard_state`` carries them
        elastically. Pad positions hold zero residual by construction
        (``parallel.fsdp.pad_to`` contract).

        ``hierarchical`` controls the two-level routing of the exchange
        legs: ``None`` (default) defers to ``HOROVOD_HIERARCHICAL`` —
        when the topology resolves an inter axis, every per-bucket
        reduce-scatter / all-gather decomposes into intra RS -> inter
        hop on the 1/L panes -> intra AG (the ZeRO wire's DCN bytes
        drop L-fold; an int8 ``wire`` quantizes the inter hop only);
        ``False`` pins the flat wire regardless of topology.
        Error-feedback buckets always ride the flat wire (the carry is
        defined against the flat pane quantization).

        ``local_sgd_steps=K`` (``None`` defers to
        ``HOROVOD_LOCAL_SGD_STEPS``; the mode engages at K > 1)
        switches stages 1-2 into local-SGD mode
        (horovod_tpu/local_sgd.py): optimizer state shards over the
        INTRA axis only (each slice's L ranks jointly hold that
        slice's moments — slices' trajectories diverge during the
        local phase), every exchange leg routes over the intra
        replica groups (the compiled step carries zero inter-slice
        groups), and :meth:`sync_round` — a SEPARATE traced program —
        reconciles parameter deltas since the last round across the
        inter axis with hierarchical Adasum on
        ``local_sgd_inter_wire`` (EF residuals carried across rounds
        in the state's ``"local"`` layout family, which
        ``reshard_state`` migrates across world changes). Stage 3 is
        rejected: its parameters shard over the WORLD axis, so a
        slice cannot even hold its own model during an independent
        local phase. Params must ride the training loop rank-major
        (``P(hvd.WORLD_AXIS)``) — slices diverge, so a replicated
        spec would be a lie. ``hierarchical`` two-level routing is
        moot in local mode (there IS no inter hop in the local
        phase). ``local_sgd_intra`` injects an explicit
        chips-per-slice (tests/bench on single-slice hosts).

        ``grad_guard=True`` (``None`` defers to ``HOROVOD_GUARD``)
        adds the non-finite skip-step sentinel (common/guard.py).
        Unlike the replicated optimizer the reduce-scattered shards
        DIVERGE per rank — a NaN lands in exactly one rank's shard —
        so the flag costs one extra 4-byte scalar ``psum`` per step
        (DeepSpeed/AMP's overflow-flag allreduce) to keep the skip
        decision uniform across the gang. Skip semantics are gated by
        ``where`` selects: bad steps feed the inner transform zeroed
        gradients, discard its state delta, and emit zero updates;
        the guard counters ride the state under a ``"guard"`` key —
        an OPT-IN layout change (``reshard_state`` carries it across
        world changes; unguarded jobs keep the flat layout)."""
        self._inner = optimizer
        self._op = resolve_op(op, average)
        if self._op not in (Sum, Average):
            raise NotImplementedError(
                "ShardedDistributedOptimizer supports op=Sum/Average "
                "(Adasum's recursive combine needs full gradients)"
            )
        self._axis = axis_name
        self._world = world
        from .common import basics

        cfg = basics.live_config()
        self._stage = int(
            zero_stage if zero_stage is not None else cfg.zero_stage
        )
        if self._stage not in (1, 2, 3):
            raise ValueError(
                f"zero_stage must be 1, 2 or 3, got {self._stage}"
            )
        # wire=None defers to the DEDICATED sharded-wire knob
        # (HOROVOD_ZERO_WIRE, default fp32) — never to
        # HOROVOD_FUSION_WIRE, which governs the eager fused wire and
        # predates ZeRO-2/3: inheriting it would silently quantize the
        # sharded exchange (and flip the state layout) under existing
        # deployments' env
        self._wire = wire if wire is not None else cfg.zero_wire
        if self._wire not in _WIRE_FORMATS:
            raise ValueError(
                f"wire must be one of {_WIRE_FORMATS}, got {self._wire!r}"
            )
        self._wire_block = int(
            wire_block if wire_block is not None else cfg.fusion_wire_block
        )
        # two-level routing of the exchange legs: "auto" = the
        # HOROVOD_HIERARCHICAL topology decision; None pins flat
        self._hier_arg = None if hierarchical is False else "auto"
        from . import local_sgd as _local_sgd

        self._local_k = int(
            local_sgd_steps
            if local_sgd_steps is not None
            else _local_sgd.default_steps()
        )
        self._local_on = self._local_k > 1
        self._local_wire = local_sgd_inter_wire
        self._local_intra = local_sgd_intra
        if self._local_on:
            if local_sgd_steps is None:
                # engaged via env: warn once — the mode needs a loop
                # that drives sync_round (see local_sgd.maybe_sync)
                _local_sgd.warn_env_engaged(self._local_k)
            if self._stage >= 3:
                raise NotImplementedError(
                    "local_sgd_steps composes with zero_stage<=2 only: "
                    "stage-3 parameters shard over the WORLD axis, so "
                    "a slice cannot hold its own model during an "
                    "independent local phase — run stage 1/2, or keep "
                    "every-step sync at stage 3"
                )
            if local_sgd_inter_wire not in _local_sgd.INTER_WIRES:
                raise ValueError(
                    f"unknown local_sgd_inter_wire "
                    f"{local_sgd_inter_wire!r}"
                )
            # the local phase has no inter hop; two-level routing of
            # the exchange legs would reintroduce one
            self._hier_arg = None
        self._ef = bool(error_feedback)
        if self._ef and self._wire not in ("int8", "auto"):
            raise ValueError(
                "error_feedback requires a quantized-capable wire "
                "(wire='int8' or 'auto'); fp32/bf16 residuals drain to "
                "the exact cast error and buy nothing"
            )
        if self._ef and self._stage >= 3:
            raise ValueError(
                "error_feedback composes with zero_stage<=2 only: the "
                "stage-3 gather/scatter boundary is a stateless "
                "custom_vjp and cannot thread residual carries; run "
                "stage 3 with wire='fp32'/'bf16' or plain int8"
            )
        from .ops import overlap as _overlap

        if overlap_buckets is None:
            overlap_buckets = _overlap.default_buckets()
        self._overlap_buckets = int(overlap_buckets)
        if self._stage >= 3:
            # the schedule IS the parameter gather/scatter plan
            self._overlap_buckets = max(self._overlap_buckets, 1)
        self._overlap_min_bytes = (
            _overlap.default_min_bytes()
            if overlap_min_bytes is None
            else int(overlap_min_bytes)
        )
        from .common import guard as _guard

        self._guard_on = (
            bool(grad_guard)
            if grad_guard is not None
            else _guard.default_enabled()
        )
        self._max_skips = int(
            guard_max_skips
            if guard_max_skips is not None
            else _guard.default_max_skips()
        )
        self._guard_src = _guard.new_source() if self._guard_on else 0
        self._pmeta = None  # stage-3 full-parameter geometry
        import os

        if os.environ.get(
            "HOROVOD_SHARDED_OPT_PROBE", "1"
        ) not in ("0", "false") and _probe_nonelementwise(optimizer):
            raise ValueError(
                "ShardedDistributedOptimizer: the inner optax transform "
                "is not elementwise — its update changes when gradients "
                "are sharded (differential probe mismatch). Norm-based "
                "transforms (clip_by_global_norm, adaptive_grad_clip, "
                "...) would compute shard-LOCAL norms and silently train "
                "wrong. Apply clipping to the FULL gradients before this "
                "wrapper instead, e.g.:\n"
                "    clipped, _ = optax.clip_by_global_norm(c).update("
                "grads, None)\n"
                "    updates, state = sharded_opt.update(clipped, state, "
                "params)\n"
                "or set HOROVOD_SHARDED_OPT_PROBE=0 to accept the risk "
                "for a transform the probe cannot compare (e.g. "
                "stochastic noise)."
            )

    # -- local-SGD topology ------------------------------------------------
    def _local_stages(self, world: int):
        from . import local_sgd as _local_sgd

        return _local_sgd.resolve_stages(
            int(world), intra=self._local_intra
        )

    def _shard_width(self, world: int) -> int:
        """How many ways the flat shard geometry splits: the whole
        world normally; the intra size L in local-SGD mode (each
        slice's L ranks jointly hold that slice's state)."""
        if not self._local_on:
            return int(world)
        return len(self._local_stages(world)[0][0])

    # -- init (outside jit) ------------------------------------------------
    def init(self, params):
        from .common import basics

        n = self._world or basics.size()
        self._world = n
        width = self._shard_width(n)
        shard_states = [
            self._inner.init(
                jax.tree_util.tree_map(
                    lambda p: _shard_host(p, width, r % width), params
                )
            )
            for r in range(n)
        ]
        # stack rank-major: every leaf gets a leading world axis, so the
        # state rides shard_map with ONE spec: P(axis_name)
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
            *shard_states,
        )
        guard_rows = None
        if self._guard_on:
            # guard counters ride the same rank-major convention
            # ([world] rows of replicated scalars) so the whole state
            # still threads through shard_map with the single P(axis)
            # spec
            z = jnp.zeros((n,), jnp.int32)
            guard_rows = {"skips": z, "streak": z, "step": z}
        wire_rows = (
            self._init_wire_rows(params, n, width)
            if self._wants_wire_rows()
            else None
        )
        local_rows = (
            self._init_local_rows(params, n, width)
            if self._local_on
            else None
        )
        return self._compose_state(
            stacked, guard_rows, wire_rows, local_rows
        )

    def _wants_wire_rows(self) -> bool:
        """A quantized-capable wire on the update-internal legs needs
        state: a per-step seed counter (a FIXED stochastic-rounding
        seed would repeat the same realized error every step — a
        directional drift instead of an unbiased walk), plus the EF
        residual rows when error_feedback is on. Stage 3 has no wire
        leg inside update (the boundary carries the exchange), so its
        state stays wire-free."""
        return self._stage <= 2 and (
            self._ef or self._wire in ("int8", "auto")
        )

    def _init_wire_rows(self, params, n, width: Optional[int] = None):
        """Wire-seed counter (+ error-feedback carries when EF is on),
        rank-major: ``rs`` rows mirror the FULL gradient geometry (each
        rank's quantization error is over its own full local
        contribution), ``ag`` rows the shard geometry (the update-leg
        error lives on the shard its rank owns — genuinely 1/N, or 1/L
        in local-SGD mode where the shard splits intra-slice)."""
        if width is None:
            width = n
        rows = {"step": jnp.zeros((n,), jnp.int32)}
        if not self._ef:
            return rows

        # shape/dtype only — a jax.eval_shape template works here too
        def _full_rows(p):
            return jnp.zeros(
                (n,) + tuple(np.shape(p)), jnp.result_type(p)
            )

        def _shard_rows(p):
            shape = tuple(np.shape(p))
            if not shape:
                return jnp.zeros((n,), jnp.result_type(p))
            size = int(np.prod(shape, dtype=np.int64))
            return jnp.zeros(
                (n, shard_cols(size, width)), jnp.result_type(p)
            )

        rows["rs"] = jax.tree_util.tree_map(_full_rows, params)
        rows["ag"] = jax.tree_util.tree_map(_shard_rows, params)
        return rows

    def _init_local_rows(self, params, n, width):
        """The ``"local"`` layout family (local-SGD mode): the anchor —
        params at the last sync round — in intra-position-major shard
        rows (rank ``r`` holds chunk ``r % L``; every slice's L ranks
        jointly hold one full anchor copy, 1/L per rank), the EF
        residual of the int8 inter wire in the same geometry, the
        round counter, and the split width the rows were cut at (the
        ``reshard_state`` migration reads it back — an 8→6 resize may
        change L)."""
        def _rows(p):
            if np.ndim(p) == 0:
                return jnp.stack(
                    [jnp.asarray(p) for _ in range(n)]
                )
            return jnp.stack(
                [_shard_host(jnp.asarray(p), width, r % width)
                 for r in range(n)]
            )

        rows = {
            "anchor": jax.tree_util.tree_map(_rows, params),
            "round": jnp.zeros((n,), jnp.int32),
            "intra": jnp.full((n,), width, jnp.int32),
        }
        if self._local_wire == "int8":
            rows["residual"] = jax.tree_util.tree_map(
                lambda a: jnp.zeros_like(a), rows["anchor"]
            )
        return rows

    # -- state layout ------------------------------------------------------
    @staticmethod
    def _layout(state):
        """Decompose a state into (inner, guard_rows, wire_rows,
        local_rows) without enforcing the optimizer's flags (the
        reshard migration point)."""
        if (
            isinstance(state, dict)
            and "state" in state
            and set(state) <= {"state", "guard", "wire", "local"}
        ):
            return (
                state["state"], state.get("guard"),
                state.get("wire"), state.get("local"),
            )
        return state, None, None, None

    @staticmethod
    def _compose_state(inner, guard_rows, wire_rows, local_rows=None):
        extras = {}
        if guard_rows is not None:
            extras["guard"] = guard_rows
        if wire_rows is not None:
            extras["wire"] = wire_rows
        if local_rows is not None:
            extras["local"] = local_rows
        if not extras:
            return inner
        return {"state": inner, **extras}

    @staticmethod
    def _is_guarded_layout(state) -> bool:
        guard_rows = ShardedDistributedOptimizer._layout(state)[1]
        return guard_rows is not None

    def _split_state(self, state):
        """Layout split + flag validation (update path: mismatches are
        hard errors pointing at the reshard_state migration)."""
        inner, guard_rows, wire_rows, local_rows = self._layout(state)
        if self._local_on and local_rows is None:
            raise ValueError(
                "local_sgd_steps > 1 but the optimizer state has no "
                '"local" layout family (anchor/residual/round rows) — '
                "it was created without local-SGD mode. Migrate it "
                "once with reshard_state(state, params, world) "
                "(params must carry concrete values: the anchor IS "
                "the params), or re-run init(params)."
            )
        if not self._local_on and local_rows is not None:
            raise ValueError(
                'the optimizer state carries a "local" layout family '
                "but local_sgd_steps <= 1 — it was checkpointed by a "
                "local-SGD run. Re-enable local_sgd_steps, or "
                "downgrade the state once with reshard_state(state, "
                "params, world) (which strips the family AND its "
                "intra-width shard geometry — the moments are re-cut "
                "to the flat world split)."
            )
        if self._guard_on and guard_rows is None:
            raise ValueError(
                "grad_guard is on but the optimizer state has the "
                "flat (unguarded) layout — it was created before "
                "the guard was enabled. Migrate it once with "
                "reshard_state(state, params, world) (which "
                "synthesizes zero guard counters), or re-run "
                "init(params)."
            )
        if not self._guard_on and guard_rows is not None:
            raise ValueError(
                "the optimizer state carries guard counters but "
                "grad_guard is off — it was checkpointed by a GUARDED "
                "run. Re-enable the guard, or downgrade the state once "
                "with reshard_state(state, params, world) (which "
                "strips the counters when the guard is off)."
            )
        wants = self._wants_wire_rows()
        if wants and wire_rows is None:
            raise ValueError(
                "the quantized wire needs wire state rows (per-step "
                "seed counter and, with error_feedback, the wire "
                "residual rows) but the optimizer state has none — "
                "migrate it once with reshard_state(state, params, "
                "world) (which synthesizes them), or re-run "
                "init(params)."
            )
        if not wants and wire_rows is not None:
            raise ValueError(
                "the optimizer state carries wire residual/seed rows "
                "but this optimizer's wire is exact (fp32/bf16, no "
                "error_feedback) — re-enable the quantized wire, or "
                "downgrade the state once with reshard_state(state, "
                "params, world)."
            )
        if self._ef and wire_rows is not None and "rs" not in wire_rows:
            raise ValueError(
                "error_feedback is on but the optimizer state carries "
                "no wire residual rows (seed-only wire state from a "
                "plain-int8 run) — migrate it once with "
                "reshard_state(state, params, world)."
            )
        if (
            not self._ef
            and wire_rows is not None
            and "rs" in wire_rows
        ):
            raise ValueError(
                "the optimizer state carries wire residual rows but "
                "error_feedback is off — re-enable it, or downgrade "
                "the state once with reshard_state(state, params, "
                "world)."
            )
        return inner, guard_rows, wire_rows, local_rows

    # -- gradient classification -------------------------------------------
    def _on_wire(self, dtype):
        """The dtype a gradient leaf of ``dtype`` has on this optimizer's
        wire (``auto`` is settled bucket by bucket: counted full width)."""
        narrow = {"bf16": jnp.bfloat16, "int8": jnp.int8}.get(self._wire)
        floating = jnp.issubdtype(dtype, jnp.floating)
        return narrow if narrow is not None and floating else dtype

    def _grads_are_shards(self, grads, params, n) -> bool:
        """Static (trace-time) classification: did ``grads`` come from
        the in-backprop scatter boundary (per-leaf shard slices) or
        from plain backprop (full leaves)? Shapes decide: a shard leaf
        is 1-D of length ``ceil(size/world)``. Leaves where both
        readings coincide (``p.size <= 1``) follow the unambiguous
        majority; an all-ambiguous tree reads as full (legacy)."""
        g_l, g_def = jax.tree_util.tree_flatten(grads)
        p_l = g_def.flatten_up_to(params)
        kinds = []
        for g, p in zip(g_l, p_l):
            if np.ndim(p) == 0:
                continue
            if jnp.result_type(g) == jax.dtypes.float0:
                continue  # non-differentiable leaf: passthrough either way
            gs, ps = tuple(np.shape(g)), tuple(np.shape(p))
            size = int(np.prod(ps, dtype=np.int64))
            sc = (shard_cols(size, n),)
            if gs == ps and gs != sc:
                kinds.append(False)
            elif gs == sc and gs != ps:
                kinds.append(True)
            elif gs == ps == sc:
                continue  # ambiguous corner (size <= 1-ish leaves)
            else:
                raise ValueError(
                    f"gradient leaf shape {gs} matches neither the "
                    f"param shape {ps} nor its shard shape {sc}"
                )
        if not kinds:
            return False
        if all(kinds):
            return True
        if not any(kinds):
            return False
        raise ValueError(
            "gradient tree mixes full and shard leaves — pass either "
            "raw backprop gradients or the tree from opt.value_and_grad"
        )

    # -- update (inside shard_map over axis_name) --------------------------
    def update(self, grads, state, params):
        inner_rows, guard_rows, wire_rows, local_rows = (
            self._split_state(state)
        )
        n = jax.lax.axis_size(self._axis)
        if self._world is not None and n != self._world:
            raise ValueError(
                f"world changed between init ({self._world}) and update "
                f"({n}): call reshard_state(state, params, {n}) after a "
                "topology change — it carries the optimizer moments "
                "over (re-running init would reset them)"
            )
        idx = jax.lax.axis_index(self._axis)
        # local-SGD mode: shard geometry and every collective restrict
        # to the intra groups — the compiled step carries ZERO
        # inter-slice replica groups (hloaudit-asserted)
        if self._local_on:
            from .common.topology import stage_positions

            intra_groups = self._local_stages(n)[0]
            width = len(intra_groups[0])
            pos = jnp.asarray(stage_positions(intra_groups))[idx]
        else:
            intra_groups = None
            width = n
            pos = idx
        # shard_map hands each rank its [1, ...] state slice
        local_state = jax.tree_util.tree_map(lambda x: x[0], inner_rows)
        local_wire = (
            jax.tree_util.tree_map(lambda x: x[0], wire_rows)
            if wire_rows is not None
            else None
        )
        wire_seed = local_wire["step"] if local_wire is not None else 0

        if self._stage >= 3:
            bad = [
                p for p in jax.tree_util.tree_leaves(params)
                if np.ndim(p) > 1
            ]
            if bad:
                raise ValueError(
                    "zero_stage=3 update expects LOCAL parameter shards "
                    "(opt.local_shards(pstate) inside shard_map), got a "
                    f"leaf of shape {np.shape(bad[0])} — full params "
                    "never exist at stage 3"
                )
            p_sh = params
            shard_in = True
        else:
            shard_in = self._grads_are_shards(grads, params, width)
            p_sh = jax.tree_util.tree_map(
                lambda p: p if p.ndim == 0 else _shard_dyn(p, width, pos),
                params,
            )
        if shard_in and self._ef:
            raise ValueError(
                "error_feedback rides the full-gradient update path "
                "(the reduce-scatter happens inside update, where the "
                "residual rows live); grads from opt.value_and_grad "
                "arrive pre-scattered — pass raw backprop gradients "
                "instead, or drop error_feedback"
            )

        from .ops import overlap as _overlap

        new_rs_res = None
        # trace time only: what this reduce-scatter hands to its
        # collectives (grads that arrive as shards were exchanged by
        # ``opt.value_and_grad``: no plan here)
        bucketed = bool(self._overlap_buckets or self._wire != "fp32")
        buckets = max(self._overlap_buckets, 1)
        with _traced.exchange_plan(
            () if shard_in else grads, world=width, op=self._op,
            wire_dtype=self._on_wire, wire=self._wire,
            buckets=self._overlap_buckets,
            collectives=buckets if bucketed else None,
        ):
            if shard_in:
                g_sh = grads
            elif bucketed:
                if self._ef:
                    g_sh, new_rs_res = _overlap.bucketed_reduce_scatter(
                        grads, op=self._op, n_buckets=buckets,
                        axis_name=self._axis, wire=self._wire,
                        wire_block=self._wire_block, seed=wire_seed,
                        residuals=local_wire["rs"],
                        min_bucket_bytes=self._overlap_min_bytes,
                        hier_stages=self._hier_arg,
                        groups=intra_groups,
                    )
                else:
                    g_sh = _overlap.bucketed_reduce_scatter(
                        grads, op=self._op, n_buckets=buckets,
                        axis_name=self._axis, wire=self._wire,
                        wire_block=self._wire_block, seed=wire_seed,
                        min_bucket_bytes=self._overlap_min_bytes,
                        hier_stages=self._hier_arg,
                        groups=intra_groups,
                    )
            else:
                # 0-d leaves (scalar temperature etc.) stay replicated —
                # exactly like init's _shard_host — so state shapes are
                # stable step-over-step (a shape flip would force a retrace
                # and break donation)
                @jax.named_scope(_traced.EXCHANGE_SCOPE)
                def rs(g):
                    if g.ndim == 0:
                        red = _traced.clax.psum(
                            g, self._axis, axis_index_groups=intra_groups
                        )
                        return red / width if self._op == Average else red
                    flat = _pad_to(g.reshape(-1), width).reshape(width, -1)
                    red = _traced.clax.psum_scatter(
                        flat, self._axis, scatter_dimension=0, tiled=False,
                        axis_index_groups=intra_groups,
                    )
                    if self._op == Average:
                        red = red / width
                    return red

                g_sh = jax.tree_util.tree_map(rs, grads)

        finite = None
        if self._guard_on:
            from .ops.traced import tree_finite

            # the scattered shards DIVERGE per rank (a NaN lands in
            # exactly one shard), so the flag must be agreed: one
            # 4-byte scalar psum — the only collective the guard adds
            # Local-SGD mode agrees the flag INTRA-slice only: slices
            # train independently, so a slice skips its own poisoned
            # step without stalling the others (and the local-phase
            # program stays free of inter-slice groups).
            ok_local = tree_finite(g_sh)
            with jax.named_scope(_traced.EXCHANGE_SCOPE):
                bad = _traced.clax.psum(
                    jnp.where(ok_local, 0.0, 1.0).astype(jnp.float32),
                    self._axis,
                    axis_index_groups=intra_groups,
                )
            finite = bad == 0
            # feed the inner transform clean zeros on a bad step; its
            # output and state delta are discarded below anyway, this
            # just keeps NaNs out of user transforms entirely
            g_sh = jax.tree_util.tree_map(
                lambda g: jnp.where(finite, g, jnp.zeros_like(g)), g_sh
            )
        with jax.named_scope(_traced.UPDATE_SCOPE):
            upd_sh, new_local = self._inner.update(g_sh, local_state, p_sh)
        if self._guard_on:
            # skip-step semantics by selection: zero updates, state of
            # the last APPLIED step (where, not multiply — selects are
            # NaN-safe)
            upd_sh = jax.tree_util.tree_map(
                lambda u: jnp.where(finite, u, jnp.zeros_like(u)), upd_sh
            )
            new_local = jax.tree_util.tree_map(
                lambda nl, ol: jnp.where(finite, nl, ol),
                new_local, local_state,
            )

        new_ag_res = None
        if self._stage >= 3:
            # Shard updates out: the next forward's gathers re-publish
            # the new params. Rounding note: XLA contracts the inner
            # transform's final multiply into the caller's
            # `params + update` add as an FMA (one rounding, not two —
            # verified on XLA:CPU, where even optimization_barrier is
            # stripped before fusion), so stage-3 PARAMS can sit 1 ulp
            # from the stage-1 trajectory, whose add consumes an
            # all-gather output and cannot contract. Gradient shards,
            # moments and updates stay bit-exact; the FMA'd apply is
            # the MORE accurate of the two (tests/test_zero.py pins
            # the <=1-ulp bound).
            upd = upd_sh
        elif self._overlap_buckets or self._wire != "fp32":
            buckets = max(self._overlap_buckets, 1)
            if self._ef:
                upd, new_ag_res = _overlap.bucketed_shard_all_gather(
                    upd_sh, params, n_buckets=buckets,
                    axis_name=self._axis, wire=self._wire,
                    wire_block=self._wire_block, seed=wire_seed,
                    residuals=local_wire["ag"],
                    min_bucket_bytes=self._overlap_min_bytes,
                    hier_stages=self._hier_arg,
                    groups=intra_groups,
                )
            else:
                upd = _overlap.bucketed_shard_all_gather(
                    upd_sh, params, n_buckets=buckets,
                    axis_name=self._axis, wire=self._wire,
                    wire_block=self._wire_block, seed=wire_seed,
                    min_bucket_bytes=self._overlap_min_bytes,
                    hier_stages=self._hier_arg,
                    groups=intra_groups,
                )
        else:
            @jax.named_scope(_traced.EXCHANGE_SCOPE)
            def gather(u, p):
                if p.ndim == 0:
                    return u
                full = _traced.clax.all_gather(
                    u, self._axis, axis=0,
                    axis_index_groups=intra_groups,
                ).reshape(-1)
                return full[: p.size].reshape(p.shape).astype(u.dtype)

            upd = jax.tree_util.tree_map(gather, upd_sh, params)
        if self._guard_on and self._stage < 3:
            # a lossy AG leg transmits quantize(0 + residual) on a
            # skipped step; the post-gather gate discards it so skipped
            # steps move nothing (shard updates were gated above)
            upd = jax.tree_util.tree_map(
                lambda u: jnp.where(finite, u, jnp.zeros_like(u)), upd
            )

        new_inner = jax.tree_util.tree_map(lambda x: x[None], new_local)
        new_wire = None
        if local_wire is not None:
            def _gate(new_r, old_r):
                if finite is None:
                    return new_r
                return jnp.where(finite, new_r, old_r)

            # the seed counter advances even on skips — rounding stays
            # decorrelated across retries of a bad region
            new_wire = {
                "step": (local_wire["step"] + jnp.int32(1))[None]
            }
            if self._ef:
                new_wire["rs"] = jax.tree_util.tree_map(
                    lambda a, b: _gate(a, b)[None],
                    new_rs_res, local_wire["rs"],
                )
                new_wire["ag"] = jax.tree_util.tree_map(
                    lambda a, b: _gate(a, b)[None],
                    new_ag_res, local_wire["ag"],
                )
        if not self._guard_on:
            return upd, self._compose_state(
                new_inner, None, new_wire, local_rows
            )
        import functools

        from .common import guard as _guard

        skips = guard_rows["skips"][0]
        streak = guard_rows["streak"][0]
        step = guard_rows["step"][0]
        streak_next = streak + 1

        def _quiet(_):
            return jnp.int32(0)

        def _fire(_):
            # skip branch only: the healthy path never reaches the host
            jax.debug.callback(
                functools.partial(
                    _guard.record_skip, max_skips=self._max_skips,
                    source=self._guard_src,
                ),
                streak_next, step,
            )
            return jnp.int32(0)

        jax.lax.cond(finite, _quiet, _fire, operand=None)
        one = jnp.ones((), jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        new_guard = {
            "skips": jnp.where(finite, skips, skips + one)[None],
            "streak": jnp.where(finite, zero, streak_next)[None],
            "step": (step + one)[None],
        }
        return upd, self._compose_state(
            new_inner, new_guard, new_wire, local_rows
        )

    # -- local-SGD sync round (inside shard_map, its OWN program) ----------
    def sync_round(self, params, state):
        """The K-step reconciliation round for local-SGD mode (stages
        1-2): parameter deltas since the last anchor — computed in the
        intra-shard geometry the ``"local"`` family stores (each
        slice's L ranks jointly hold one delta copy, 1/L per rank) —
        merge across slices by VHDD Adasum over the inter groups
        (:func:`horovod_tpu.local_sgd.adasum_sync_shard`: dots
        completed over intra, ``local_sgd_inter_wire`` on the DCN
        half-exchanges, EF residuals chained across rounds), then one
        intra all-gather reassembles the consensus parameters. Call
        INSIDE shard_map over the world axis, but compile it as a
        SEPARATE program from ``update`` — the local-phase step must
        carry zero inter-slice replica groups. Returns
        ``(new_params, new_state)``; drive the cadence and the
        retry/defer robustness contract with
        :func:`horovod_tpu.local_sgd.maybe_sync`."""
        if not self._local_on:
            raise ValueError(
                "sync_round requires local_sgd_steps > 1"
            )
        from . import local_sgd as _local_sgd
        from .common.topology import stage_positions

        inner_rows, guard_rows, wire_rows, local_rows = (
            self._split_state(state)
        )
        n = jax.lax.axis_size(self._axis)
        stages = self._local_stages(n)
        intra_groups = stages[0]
        L = len(intra_groups[0])
        idx = jax.lax.axis_index(self._axis)
        pos = jnp.asarray(stage_positions(intra_groups))[idx]
        local = jax.tree_util.tree_map(lambda x: x[0], local_rows)
        anchor = local["anchor"]
        residual = local.get("residual")
        rnd = local["round"]
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        a_leaves = treedef.flatten_up_to(anchor)
        r_leaves = (
            treedef.flatten_up_to(residual)
            if residual is not None
            else None
        )
        # per-leaf shard deltas; 0-d leaves ride at intra position 0
        # only (zeros elsewhere — the concat across positions must
        # contain each scalar exactly once, or its dot-product weight
        # would inflate L-fold)
        segs, a_segs, meta = [], [], []
        for p, a in zip(p_leaves, a_leaves):
            if p.ndim == 0:
                d = (p - a).astype(jnp.float32).reshape(1)
                segs.append(jnp.where(pos == 0, d, jnp.zeros_like(d)))
                a_segs.append(a.astype(jnp.float32).reshape(1))
                meta.append((True, 1, 1, (), p.dtype))
            else:
                sh = _shard_dyn(p, L, pos).astype(jnp.float32)
                a_segs.append(a.astype(jnp.float32))
                segs.append(sh - a_segs[-1])
                meta.append(
                    (False, int(a.shape[0]), int(p.size), p.shape,
                     p.dtype)
                )
        flat = jnp.concatenate(segs)
        a_flat = jnp.concatenate(a_segs)
        r_flat = None
        if r_leaves is not None:
            rsegs = []
            for r, m in zip(r_leaves, meta):
                rr = r.astype(jnp.float32).reshape(-1)
                if m[0]:
                    rr = jnp.where(pos == 0, rr, jnp.zeros_like(rr))
                rsegs.append(rr)
            r_flat = jnp.concatenate(rsegs)
        want_res = self._local_wire == "int8"
        if want_res:
            merged, new_r = _local_sgd.adasum_sync_shard(
                flat, stages, axis_name=self._axis,
                inter_wire=self._local_wire, seed=rnd,
                residual=r_flat, return_residual=True,
            )
        else:
            merged = _local_sgd.adasum_sync_shard(
                flat, stages, axis_name=self._axis,
                inter_wire=self._local_wire, seed=rnd,
            )
            new_r = None
        new_anchor_flat = a_flat + merged
        gathered = _traced.clax.all_gather(
            new_anchor_flat, self._axis, axis_index_groups=intra_groups
        )  # [L, C] — position-major chunks of the consensus params
        new_p, new_a, new_res = [], [], []
        off = 0
        for (p, a), m in zip(zip(p_leaves, a_leaves), meta):
            is_scalar, cols, size, shape, dtype = m
            seg = gathered[:, off : off + cols]
            if is_scalar:
                val = seg[0, 0]  # position 0 holds the scalar
                new_p.append(val.astype(dtype))
                new_a.append(val.astype(jnp.result_type(a)))
                if new_r is not None:
                    new_res.append(
                        new_r[off].astype(jnp.result_type(a))
                    )
            else:
                full = seg.reshape(-1)[:size].reshape(shape)
                new_p.append(full.astype(dtype))
                new_a.append(
                    new_anchor_flat[off : off + cols].astype(
                        jnp.result_type(a)
                    )
                )
                if new_r is not None:
                    new_res.append(
                        new_r[off : off + cols].astype(
                            jnp.result_type(a)
                        )
                    )
            off += cols
        new_local = {
            "anchor": jax.tree_util.tree_unflatten(treedef, new_a),
            "round": rnd + jnp.int32(1),
            "intra": local["intra"],
        }
        if residual is not None:
            new_local["residual"] = jax.tree_util.tree_unflatten(
                treedef, new_res
            )
        new_local_rows = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x)[None], new_local
        )
        new_state = self._compose_state(
            inner_rows, guard_rows, wire_rows, new_local_rows
        )
        return jax.tree_util.tree_unflatten(treedef, new_p), new_state

    # -- in-backprop scatter / forward gather boundaries -------------------
    def _traced_intra_groups(self):
        """The intra groups for this trace's axis size (local mode),
        or None — resolved lazily so the boundary kwargs can be built
        inside shard_map where the axis exists."""
        if not self._local_on:
            return None
        return self._local_stages(
            int(jax.lax.axis_size(self._axis))
        )[0]

    def _scatter_kw(self, seed):
        return dict(
            op=self._op,
            n_buckets=max(self._overlap_buckets, 1),
            axis_name=self._axis,
            wire=self._wire,
            wire_block=self._wire_block,
            seed=seed,
            min_bucket_bytes=self._overlap_min_bytes,
            hier_stages=self._hier_arg,
            groups=self._traced_intra_groups(),
        )

    def _gather_kw(self, seed):
        return dict(
            n_buckets=max(self._overlap_buckets, 1),
            axis_name=self._axis,
            wire=self._wire,
            wire_block=self._wire_block,
            seed=seed,
            min_bucket_bytes=self._overlap_min_bytes,
            hier_stages=self._hier_arg,
            groups=self._traced_intra_groups(),
        )

    def _carrier_call(self, psh, pfull, seed):
        """Stage-1/2 boundary: the full params pass through untouched
        on the forward (their shard slices are dead forward values XLA
        DCEs away), and the COTANGENT tree leaves through the bucketed
        reduce-scatter — each overlap bucket's reduce-scatter output IS
        the gradient shard slice, emitted at its backward dataflow
        frontier. The full params ride as an explicit operand (zero
        cotangent) because custom_vjp cannot close over tracers; the
        wire seed rides the same way (an int32 operand whose cotangent
        is float0 — kept integer so step counters never collapse to
        shared float32 values past 2^24), so a TRACED per-step seed
        decorrelates a quantized wire's stochastic rounding across
        steps instead of replaying one fixed realization."""
        from .ops import overlap as _overlap

        kw = self._scatter_kw(0)
        kw.pop("seed")
        s = jnp.asarray(seed, jnp.int32)

        @jax.custom_vjp
        def _carrier(q, pf, sv):
            return pf

        def _fwd(q, pf, sv):
            return pf, sv

        def _bwd(sv, ct):
            g_sh = _overlap.bucketed_reduce_scatter(ct, seed=sv, **kw)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, ct)
            return g_sh, zeros, np.zeros(sv.shape, jax.dtypes.float0)

        _carrier.defvjp(_fwd, _bwd)
        return _carrier(psh, pfull, s)

    def _gather_call(self, psh, seed, differentiable=True):
        """Stage-3 boundary: per-bucket all-gathers reconstruct the full
        params at their forward dataflow frontiers (N INDEPENDENT
        collectives — XLA interleaves each into the compute that first
        consumes its bucket, no monolithic unshard); the backward's
        cotangents leave through the matching bucketed reduce-scatter,
        landing gradients directly in shard geometry. The wire seed is
        a traced int32 operand (see _carrier_call). To re-gather
        instead of keeping the full params live across backward, wrap
        per-layer blocks in ``jax.checkpoint`` — the boundary composes
        with remat (the gathers rerun inside the rematerialized
        block)."""
        from .ops import overlap as _overlap

        self._require_meta()
        meta = self._pmeta
        ag_kw = self._gather_kw(0)
        ag_kw.pop("seed")
        rs_kw = self._scatter_kw(0)
        rs_kw.pop("seed")
        s = jnp.asarray(seed, jnp.int32)

        def _ag(q, sv):
            return _overlap.bucketed_shard_all_gather(
                q, meta, seed=sv, **ag_kw
            )

        if not differentiable:
            return _ag(psh, s)

        @jax.custom_vjp
        def _gather(q, sv):
            return _ag(q, sv)

        def _fwd(q, sv):
            return _ag(q, sv), sv

        def _bwd(sv, ct):
            g_sh = _overlap.bucketed_reduce_scatter(
                ct, seed=sv, **rs_kw
            )
            return g_sh, np.zeros(sv.shape, jax.dtypes.float0)

        _gather.defvjp(_fwd, _bwd)
        return _gather(psh, s)

    def value_and_grad(self, fn, has_aux: bool = False, seed: int = 0):
        """The sharded tape: ``opt.value_and_grad(loss_fn)`` returns a
        function whose gradients arrive as per-leaf SHARD slices,
        reduce-scattered per overlap bucket INSIDE backprop (no reduced
        full-gradient tree ever materializes — the ZeRO-2/3 gradient
        leg). Call INSIDE shard_map:

        * stages 1-2: ``loss, g_sh = vg(params, *args)`` with FULL
          params — forward is untouched; the exchange rides the
          backward.
        * stage 3: ``loss, g_sh = vg(opt.local_shards(pstate), *args)``
          — the forward all-gathers each parameter bucket on demand
          (:meth:`gather_params` dataflow) and ``fn`` receives the full
          params.

        Feed the result straight to :meth:`update` (the shard shapes
        are detected statically and the internal reduce-scatter is
        skipped). Quantized-wire seeding: ``seed`` is the per-trace
        default; the returned function also takes ``wire_seed=`` at
        CALL time, which may be a TRACED value (thread your step
        counter through it) — a fixed seed would replay the identical
        stochastic-rounding realization every step, turning unbiased
        rounding noise into a directional drift. fp32/bf16 wires
        ignore it."""

        def vg(p, *args, wire_seed=None, **kwargs):
            sv = seed if wire_seed is None else wire_seed
            if self._stage >= 3:
                def wrapped(q):
                    return fn(self._gather_call(q, sv), *args, **kwargs)

                return jax.value_and_grad(wrapped, has_aux=has_aux)(p)
            n = jax.lax.axis_size(self._axis)
            idx = jax.lax.axis_index(self._axis)
            if self._local_on:
                from .common.topology import stage_positions

                intra_groups = self._local_stages(n)[0]
                width = len(intra_groups[0])
                pos = jnp.asarray(stage_positions(intra_groups))[idx]
            else:
                width, pos = n, idx
            pc = jax.tree_util.tree_map(jax.lax.stop_gradient, p)
            psh = jax.tree_util.tree_map(
                lambda x: x if x.ndim == 0 else _shard_dyn(x, width, pos),
                pc,
            )

            def wrapped(q):
                return fn(
                    self._carrier_call(q, pc, sv), *args, **kwargs
                )

            return jax.value_and_grad(wrapped, has_aux=has_aux)(psh)

        return vg

    def grad(self, fn, has_aux: bool = False, seed: int = 0):
        vg = self.value_and_grad(fn, has_aux=has_aux, seed=seed)

        def g(*args, **kwargs):
            out = vg(*args, **kwargs)
            return out[1]

        return g

    # -- stage-3 parameter storage -----------------------------------------
    def _bind_meta(self, params) -> None:
        self._pmeta = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(
                np.shape(p), jnp.result_type(p)
            ),
            params,
        )

    def _require_meta(self):
        if self._pmeta is None:
            raise ValueError(
                "stage-3 parameter geometry is unbound: call "
                "init_params(params) (fresh start) or "
                "bind_params_like(params_template) (elastic/checkpoint "
                "resume — shapes only, jax.eval_shape output works) "
                "before gathering"
            )

    def init_params(self, params):
        """Stage-3 parameter storage: every leaf becomes its
        ``[world, cols]`` rank-major shard rows (0-d leaves broadcast
        to ``[world]``), the layout of ``parallel.fsdp.host_shard_rows``
        — the same leading-world-axis convention as the optimizer
        state, so BOTH thread through shard_map with ``state_spec()``
        and checkpoint/reshard with the same machinery. Call OUTSIDE
        jit. Also binds the full-parameter geometry used by
        :meth:`gather_params` and the stage-3 boundary."""
        from .common import basics

        n = self._world or basics.size()
        self._world = n
        self._bind_meta(params)
        return jax.tree_util.tree_map(
            lambda p: host_shard_rows(p, n), params
        )

    def bind_params_like(self, params) -> "ShardedDistributedOptimizer":
        """Record the full-parameter geometry (shapes/dtypes only —
        ``jax.eval_shape`` output is fine) without building storage:
        the elastic-resume path, where the shard rows come back from a
        checkpoint but the optimizer object is fresh. Returns self."""
        self._bind_meta(params)
        return self

    @staticmethod
    def local_shards(pstate):
        """Inside shard_map: strip the ``[1, ...]`` world slice off
        every leaf of the parameter storage (or any state-convention
        tree) — the local shard view ``update`` and
        ``optax.apply_updates`` operate on."""
        return jax.tree_util.tree_map(lambda x: x[0], pstate)

    @staticmethod
    def as_rows(local):
        """Inverse of :meth:`local_shards`: re-add the leading world
        axis so the updated shards flow out through ``state_spec()``."""
        return jax.tree_util.tree_map(lambda x: x[None], local)

    def gather_params(self, shards, seed: int = 0):
        """Traced full-parameter reconstruction from local shard leaves
        (inside shard_map): the stage-3 forward unshard as N
        independent per-bucket all-gathers, without the gradient
        boundary — for eval/inference steps. Pass
        ``opt.local_shards(pstate)``."""
        return self._gather_call(shards, seed, differentiable=False)

    def unshard_params(self, pstate):
        """HOST-side full parameter tree from the ``[world, cols]``
        shard rows (outside jit; export/eval/debug). The training path
        never needs this — checkpoints save the shard rows directly."""
        self._require_meta()
        return jax.tree_util.tree_map(
            lambda rows, m: host_unshard(rows, m.shape, m.dtype),
            pstate, self._pmeta,
        )

    def reshard_params(self, pstate, params, new_world: int):
        """Host-side elastic reshard of the stage-3 parameter storage:
        ``[old_world, cols]`` rows → ``[new_world, cols']`` PRESERVING
        every parameter value bit-exactly (only zero-pad tail is
        re-cut). ``params`` is the full-parameter template (shapes —
        ``jax.eval_shape`` output works). Call OUTSIDE jit after the
        new gang forms, alongside ``reshard_state``."""
        if new_world < 1:
            raise ValueError(f"new_world must be >= 1, got {new_world}")
        self._bind_meta(params)

        def _re(rows, p):
            shape = np.shape(p)
            if len(shape) == 0:
                return jnp.broadcast_to(
                    jnp.asarray(np.asarray(rows).reshape(-1)[0]),
                    (new_world,),
                )
            size = int(np.prod(shape, dtype=np.int64))
            return reshard_rows(
                rows, size, new_world, jnp.result_type(p)
            )

        self._world = new_world
        return jax.tree_util.tree_map(_re, pstate, params)

    def state_spec(self):
        """The single PartitionSpec for the whole state pytree in
        shard_map in_specs/out_specs (the stage-3 parameter storage
        uses the same spec)."""
        from jax.sharding import PartitionSpec as P

        return P(self._axis)

    # -- elastic -----------------------------------------------------------
    def reshard_state(self, state, params, new_world: int):
        """Host-side elastic reshard: convert the [old_world, ...]
        stacked state into [new_world, ...] PRESERVING optimizer
        moments across a gang restart — the elastic alternative to
        the "re-run init(params)" error, which would reset Adam
        moments on every world change. Call OUTSIDE jit, with the
        restored full params (a shape template suffices), after the
        new gang forms::

            state = opt.reshard_state(state, params, hvd.size())

        Mechanics: every sharded leaf is the optimizer moment over the
        param's zero-padded flat vector, split rank-major; resharding
        concatenates the old shards and re-splits at the new padding
        (tail entries beyond the param's size are padding positions —
        zeros that no update ever reads back). Replicated leaves
        (scalars like Adam's ``count``; 0-d params) re-broadcast.

        Layout migration happens HERE: guard counters and wire
        (error-feedback) residual rows are carried when the optimizer
        still wants them, synthesized as zeros when newly enabled, and
        stripped when disabled. ``ag`` residuals are shard-major and
        re-split bit-exactly like the moments; ``rs`` residuals are
        per-rank FULL-geometry errors, so the carry preserves the
        TOTAL un-transmitted signal exactly (summed onto rank 0 — the
        reduction only ever consumes the sum).

        Local-SGD (``"local"`` family): the anchor and EF-residual
        rows are re-cut from the OLD split width (read back from the
        family's ``intra`` leaf) to the new topology's — every
        parameter value carries over bit-exactly (only zero-pad tail
        is re-cut). Optimizer MOMENTS under local mode diverge per
        slice; a resize cannot preserve every slice's trajectory, so
        the new gang seeds every slice from OLD SLICE 0's moments
        (deterministic, and consistent with the post-restart rejoin
        round that re-syncs params from the Adasum consensus —
        docs/design.md)."""
        if new_world < 1:
            raise ValueError(f"new_world must be >= 1, got {new_world}")
        inner, guard_rows, wire_rows, local_rows = self._layout(state)
        _lead = jax.tree_util.tree_leaves(
            (inner, guard_rows, wire_rows, local_rows)
        )
        old_world = (
            int(np.asarray(_lead[0]).shape[0]) if _lead else new_world
        )
        old_width = (
            int(np.asarray(local_rows["intra"]).reshape(-1)[0])
            if local_rows is not None
            else old_world
        )
        new_width = self._shard_width(new_world)

        def _rows_recut(rows, cols_new, dtype):
            """[old_world, cols_old] rows (chunks repeat every
            ``old_width`` rows) → [new_world, cols_new]: slice 0's
            chunks reassemble the full padded vector, re-cut at the
            new width and tiled across the new slices. Bit-exact for
            every real entry (only zero-pad tail moves)."""
            rows = np.asarray(rows)
            full = np.concatenate(
                [np.asarray(rows[i]).reshape(-1) for i in range(old_width)]
            )
            need = int(cols_new) * new_width
            flat = np.zeros((need,), rows.dtype)
            k = min(full.shape[0], need)
            flat[:k] = full[:k]
            chunks = flat.reshape(new_width, int(cols_new))
            return jnp.asarray(
                np.stack([chunks[r % new_width] for r in range(new_world)])
            ).astype(dtype)
        if self._guard_on and guard_rows is None:
            # legacy flat state under a NEWLY-enabled guard: resharding
            # is the migration point — synthesize zero counters so the
            # resumed job starts guarded instead of crashing at its
            # first update
            zero = np.zeros((1,), np.int64)
            guard_rows = {"skips": zero, "streak": zero, "step": zero}
        elif not self._guard_on:
            # guard turned OFF against a guarded checkpoint: the same
            # migration point downgrades — strip the counters
            guard_rows = None
        wants_wire = self._wants_wire_rows()
        synthesize_wire = wants_wire and wire_rows is None
        if not wants_wire:
            wire_rows = None

        # shard-geometry zeros, not a value shard: only leaf
        # size/dtype/structure are read off the template, and zeros
        # keep a jax.eval_shape params template working (the
        # documented elastic-resume path never materializes values)
        def _shard_zeros(p):
            shape = tuple(np.shape(p))
            dt = jnp.result_type(p)
            if not shape:
                return jnp.zeros((), dt)
            size = int(np.prod(shape, dtype=np.int64))
            return jnp.zeros((shard_cols(size, new_width),), dt)

        template = self._inner.init(
            jax.tree_util.tree_map(_shard_zeros, params)
        )
        old_leaves = jax.tree_util.tree_leaves(inner)
        tmpl_leaves, treedef = jax.tree_util.tree_flatten(template)
        if len(old_leaves) != len(tmpl_leaves):
            raise ValueError(
                "state does not match this optimizer's structure "
                f"({len(old_leaves)} leaves vs {len(tmpl_leaves)})"
            )
        out = []
        for o, t in zip(old_leaves, tmpl_leaves):
            o = np.asarray(o)
            t = jnp.asarray(t)
            if t.ndim == 0:
                # replicated leaf, stacked [old_world] -> [new_world]
                out.append(
                    jnp.broadcast_to(
                        jnp.asarray(o.reshape(-1)[0]), (new_world,)
                    )
                )
                continue
            if old_width == old_world and new_width == new_world:
                # flat → flat: per-rank re-split lands exactly on the
                # template's shard size (parallel.fsdp.reshard_rows —
                # the ONE re-split implementation, shared with
                # reshard_params and the ag residuals)
                out.append(
                    reshard_rows(o, t.size * new_world, new_world, t.dtype)
                )
            else:
                # a local-SGD split is involved (either side): re-cut
                # from slice 0's chunks at the new width (moments
                # diverge per slice — see the docstring's policy)
                out.append(_rows_recut(o, t.size, t.dtype))
        self._world = new_world
        resharded = jax.tree_util.tree_unflatten(treedef, out)
        new_guard = None
        if guard_rows is not None:
            new_guard = {
                key: jnp.broadcast_to(
                    jnp.asarray(
                        np.asarray(val).reshape(-1)[0], jnp.int32
                    ),
                    (new_world,),
                )
                for key, val in guard_rows.items()
            }
        new_wire = None
        if synthesize_wire:
            new_wire = self._init_wire_rows(params, new_world, new_width)
        elif wire_rows is not None:
            new_wire = self._reshard_wire_rows(
                wire_rows, params, new_world, new_width, _rows_recut,
                flat_ok=(old_width == old_world and new_width == new_world),
                old_width=old_width,
            )
        new_local = None
        if self._local_on:
            if local_rows is None:
                # local mode newly enabled: the anchor IS the params,
                # so the migration needs concrete values
                if any(
                    not isinstance(l, (jnp.ndarray, np.ndarray))
                    and not hasattr(l, "__array__")
                    for l in jax.tree_util.tree_leaves(params)
                ):
                    raise ValueError(
                        "enabling local_sgd_steps against a state "
                        "without the \"local\" family needs concrete "
                        "parameter VALUES (the anchor is the params); "
                        "a jax.eval_shape template cannot seed it"
                    )
                new_local = self._init_local_rows(
                    params, new_world, new_width
                )
            else:
                new_local = self._reshard_local_rows(
                    local_rows, params, new_world, new_width, _rows_recut
                )
        return self._compose_state(
            resharded, new_guard, new_wire, new_local
        )

    def _reshard_local_rows(
        self, local_rows, params, new_world, new_width, recut
    ):
        """Migrate the ``"local"`` family across a topology change:
        anchor chunks re-cut bit-exactly at the new width (anchors are
        identical across slices by the sync contract — slice 0's rows
        reassemble the one true copy); EF residual chunks re-cut the
        same way, which ADOPTS slice 0's carry (per-slice carries
        cannot survive a re-slicing; the loss is bounded by one
        quantum per element); the round counter re-broadcast; the
        width leaf refreshed."""
        def _leaf(rows, p):
            if np.ndim(p) == 0:
                return jnp.broadcast_to(
                    jnp.asarray(np.asarray(rows).reshape(-1)[0]),
                    (new_world,),
                )
            size = int(np.prod(np.shape(p), dtype=np.int64))
            return recut(
                rows, shard_cols(size, new_width),
                jnp.result_type(np.asarray(rows)),
            )

        out = {
            "anchor": jax.tree_util.tree_map(
                _leaf, local_rows["anchor"], params
            ),
            "round": jnp.broadcast_to(
                jnp.asarray(
                    np.asarray(local_rows["round"]).reshape(-1)[0],
                    jnp.int32,
                ),
                (new_world,),
            ),
            "intra": jnp.full((new_world,), new_width, jnp.int32),
        }
        if self._local_wire == "int8":
            if "residual" in local_rows:
                out["residual"] = jax.tree_util.tree_map(
                    _leaf, local_rows["residual"], params
                )
            else:
                out["residual"] = jax.tree_util.tree_map(
                    lambda a: jnp.zeros_like(a), out["anchor"]
                )
        return out

    def _reshard_wire_rows(
        self, wire_rows, params, new_world: int,
        new_width: Optional[int] = None, recut=None, flat_ok: bool = True,
        old_width: Optional[int] = None,
    ):
        if new_width is None:
            new_width = new_world
        step = jnp.broadcast_to(
            jnp.asarray(
                np.asarray(wire_rows["step"]).reshape(-1)[0], jnp.int32
            ),
            (new_world,),
        )
        if not self._ef:
            return {"step": step}  # seed-only (plain quantized wire)
        if "rs" not in wire_rows:
            # EF newly enabled against a seed-only wire state: the
            # migration point synthesizes zero carries, keeping the
            # seed counter
            out = self._init_wire_rows(params, new_world, new_width)
            out["step"] = step
            return out

        def _re_rs(rows, p):
            # per-rank FULL-geometry error: the future wire only ever
            # consumes the cross-rank SUM, so carrying Σ over the old
            # gang onto rank 0 (zeros elsewhere) preserves the
            # un-transmitted signal exactly across the resize. Under a
            # LOCAL-SGD split the carry is defined against each
            # slice's OWN intra sum — a gang-wide Σ would inject
            # foreign slices' error into slice 0's next reduction —
            # so only slice 0's rows are summed (its total preserved;
            # other slices' carries are dropped like their moments,
            # the documented resize policy).
            rows = np.asarray(rows)
            if np.ndim(p) == 0:
                return jnp.broadcast_to(
                    jnp.asarray(rows.reshape(-1)[0]), (new_world,)
                )
            n_sum = (
                rows.shape[0]
                if flat_ok or old_width is None
                else old_width
            )
            total = rows[:n_sum].sum(axis=0)
            out = np.zeros((new_world,) + total.shape, rows.dtype)
            out[0] = total
            return jnp.asarray(out)

        def _re_ag(rows, p):
            if np.ndim(p) == 0:
                return jnp.broadcast_to(
                    jnp.asarray(np.asarray(rows).reshape(-1)[0]),
                    (new_world,),
                )
            size = int(np.prod(np.shape(p), dtype=np.int64))
            if flat_ok or recut is None:
                return reshard_rows(
                    rows, size, new_world, np.asarray(rows).dtype
                )
            # a local-SGD width is involved: re-cut from slice 0's
            # chunks like the moments (per-slice carries cannot
            # survive a re-slicing; the loss is bounded by one quantum)
            return recut(
                rows, shard_cols(size, new_width),
                np.asarray(rows).dtype,
            )

        return {
            "step": step,
            "rs": jax.tree_util.tree_map(
                _re_rs, wire_rows["rs"], params
            ),
            "ag": jax.tree_util.tree_map(
                _re_ag, wire_rows["ag"], params
            ),
        }
