"""Backward-interleaved gradient exchange: bucketed in-backprop
collectives.

The reference hides communication by firing per-tensor allreduces from
autograd hooks *during* backprop (ref: horovod/torch/optimizer.py
`_DistributedOptimizer` hook machinery [V], Sergeev & Del Balso,
arXiv 1802.05799 §3). Under XLA the equivalent lever is dataflow, not
hooks: the compiler overlaps a collective with remaining backward
compute exactly when the collective's operands do not depend on that
compute. A single exchange over the whole gradient tree (or one fused
buffer concatenating it) is data-dependent on the LAST gradient
produced, so there is structurally nothing to overlap — the exchange
becomes a terminal barrier after backprop.

This module re-creates the hook-style overlap inside one jitted step:

* :func:`build_bucket_schedule` partitions the gradient pytree into
  size-balanced, dtype-homogeneous buckets ordered by REVERSE flatten
  order — the DDP heuristic for backprop production order (the last
  layers' gradients materialize first, so their bucket's collective
  can launch while earlier layers are still differentiating).
* :func:`bucketed_allreduce` emits ONE independent collective per
  bucket (concat members → collective → split), so the compiled HLO
  contains N collectives whose operands are disjoint slices of the
  gradient tree — each launches at its own dataflow frontier, and the
  XLA scheduler runs bucket k's wire time against bucket k-1..0's
  remaining backward compute. Composes with everything the fused wire
  stack built: per-bucket wire format (``Compression.*`` including
  block-scaled int8 with per-bucket stochastic-rounding seeds),
  error-feedback residuals sliced per bucket, the prescale fold,
  process sets, and join masks.
* :func:`overlap_boundary` is the `jax.custom_vjp` marker: identity on
  the forward, bucketed exchange on the cotangents in the backward —
  so ``value_and_grad(..., overlap_buckets=N)`` returns gradients that
  were ALREADY reduced inside backprop, the reference's hook semantics
  with the compiler doing the scheduling (pattern ref: Xu et al.,
  arXiv 2004.13336 — per-shard decomposition is how XLA-era stacks
  recover the overlap).

Why bit-exactness holds for ``op=Sum`` fp32: `psum` over a
concatenation is elementwise identical to per-leaf `psum` (same
cross-replica addition order per element), so bucketing changes the
schedule, never the sum. Quantized wires change block geometry with
bucket geometry; parity there is within the two-stage quantum bound
(tests/test_overlap.py asserts both).

Schedules are cached per (treedef, leaf shapes/dtypes, knobs) with
hit/miss counters — the compile-churn tripwire: a training loop that
rebuilds its schedule (or retraces its step) every iteration shows up
as cache misses, not silence.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common.topology import WORLD_AXIS
from ..common.process_sets import ProcessSet
from ..ops.reduction_ops import Average, Sum, resolve_op
from . import traced
from .compression import Compression, Compressor


class BucketSchedule(NamedTuple):
    """A static partition of the gradient tree's leaves into buckets.

    ``buckets`` holds leaf indices (into the flattened tree) per
    bucket, in EMISSION order — bucket 0's members are produced first
    in backprop (reverse flatten order), so its collective launches
    first. ``passthrough`` are leaves excluded from the exchange
    (float0 cotangents of non-differentiable leaves)."""

    buckets: Tuple[Tuple[int, ...], ...]
    bucket_bytes: Tuple[int, ...]
    total_bytes: int
    passthrough: Tuple[int, ...] = ()

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)


def _leaf_key(leaf) -> Tuple:
    return (tuple(np.shape(leaf)), str(jnp.result_type(leaf)))


def _is_float0(leaf) -> bool:
    return jnp.result_type(leaf) == jax.dtypes.float0


# -- schedule cache ----------------------------------------------------
# One schedule per (structure, geometry, knobs): rebuilt schedules are
# the symptom of retrace churn, so the cache is instrumented. Bounded
# LRU-ish (dict insertion order) so a pathological caller can't grow it.

_CACHE: dict = {}
_CACHE_CAP = 256
_STATS = {"hits": 0, "misses": 0, "disk_hits": 0}

# Persisted beside the executables (HOROVOD_EXE_CACHE sidecar,
# common/exe_cache.py): the partition DECISION that produced each
# persisted bucketed executable. A restarted worker re-derives the
# same buckets from the same inputs today; the sidecar makes the
# decision durable against heuristic drift — a recorded partition is
# replayed verbatim, so its exe-cache entries keep hitting even if
# build_bucket_schedule's balancing rule changes underneath it.
_SIDECAR = "overlap_schedule"


def schedule_cache_stats() -> dict:
    return dict(_STATS, size=len(_CACHE))


def reset_schedule_cache() -> None:
    _CACHE.clear()
    _STATS["hits"] = 0
    _STATS["misses"] = 0
    _STATS["disk_hits"] = 0


def _sidecar_key(key: tuple) -> str:
    import hashlib

    return hashlib.sha256(repr(key).encode()).hexdigest()[:24]


def _schedule_from_record(rec) -> Optional[BucketSchedule]:
    """A sidecar record → BucketSchedule, or None when malformed (a
    corrupt sidecar entry must read as a plain rebuild)."""
    try:
        return BucketSchedule(
            buckets=tuple(tuple(int(i) for i in b) for b in rec["buckets"]),
            bucket_bytes=tuple(int(b) for b in rec["bucket_bytes"]),
            total_bytes=int(rec["total_bytes"]),
            passthrough=tuple(int(i) for i in rec.get("passthrough", ())),
        )
    except (KeyError, TypeError, ValueError):
        return None


def _schedule_record(key: tuple, sched: BucketSchedule) -> dict:
    return {
        "buckets": [list(b) for b in sched.buckets],
        "bucket_bytes": list(sched.bucket_bytes),
        "total_bytes": int(sched.total_bytes),
        "passthrough": list(sched.passthrough),
        "n_leaves": sum(len(b) for b in sched.buckets)
        + len(sched.passthrough),
        "n_buckets": int(key[2]),
        "min_bucket_bytes": int(key[3]),
    }


def build_bucket_schedule(
    leaves: Sequence[Any],
    n_buckets: int,
    min_bucket_bytes: int = 0,
) -> BucketSchedule:
    """Partition ``leaves`` into at most ``n_buckets`` size-balanced
    buckets in reverse flatten order (DDP-style backprop production
    order). Buckets are dtype-homogeneous — a concat buffer carries one
    dtype, so a dtype flip forces a bucket boundary (like DDP's
    per-dtype buckets). Buckets smaller than ``min_bucket_bytes`` are
    merged forward where the dtype allows: below the floor the
    per-collective launch overhead outweighs any overlap win (the
    ``HOROVOD_OVERLAP_MIN_BYTES`` knob)."""
    if n_buckets < 1:
        raise ValueError(f"n_buckets must be >= 1, got {n_buckets}")
    passthrough = tuple(
        i for i, l in enumerate(leaves) if _is_float0(l)
    )
    order = [
        i for i in reversed(range(len(leaves))) if i not in passthrough
    ]
    if not order:
        return BucketSchedule((), (), 0, passthrough)
    nbytes = {
        i: int(np.prod(np.shape(leaves[i]), dtype=np.int64))
        * jnp.result_type(leaves[i]).itemsize
        for i in order
    }
    total = sum(nbytes.values())
    # balanced linear partition: close bucket k before adding a leaf
    # whose MIDPOINT crosses the k-th ideal boundary (k+1)·total/N —
    # the closest-boundary rule, so a large leaf lands on whichever
    # side of the boundary most of it lies
    target = total / n_buckets
    buckets, cur = [], []
    cum, cur_bytes, closed = 0, 0, 0
    cur_dtype = None
    for i in order:
        d = jnp.result_type(leaves[i])
        if cur and (
            cur_dtype != d
            or (
                closed < n_buckets - 1
                and cum + nbytes[i] / 2 >= (closed + 1) * target
            )
        ):
            buckets.append((tuple(cur), cur_bytes))
            closed += 1
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes[i]
        cum += nbytes[i]
        cur_dtype = d
    if cur:
        buckets.append((tuple(cur), cur_bytes))
    if min_bucket_bytes > 0:
        # forward pass: a bucket still under the floor absorbs the
        # next same-dtype bucket (once it clears the floor it stops —
        # no cascade past the target)
        merged = []
        for idxs, b in buckets:
            if (
                merged
                and merged[-1][1] < min_bucket_bytes
                and jnp.result_type(leaves[merged[-1][0][0]])
                == jnp.result_type(leaves[idxs[0]])
            ):
                pi, pb = merged[-1]
                merged[-1] = (pi + idxs, pb + b)
            else:
                merged.append((idxs, b))
        # an under-floor TAIL bucket merges backward
        if (
            len(merged) > 1
            and merged[-1][1] < min_bucket_bytes
            and jnp.result_type(leaves[merged[-2][0][0]])
            == jnp.result_type(leaves[merged[-1][0][0]])
        ):
            pi, pb = merged[-2]
            ti, tb = merged[-1]
            merged[-2:] = [(pi + ti, pb + tb)]
        buckets = merged
    return BucketSchedule(
        buckets=tuple(i for i, _ in buckets),
        bucket_bytes=tuple(b for _, b in buckets),
        total_bytes=total,
        passthrough=passthrough,
    )


def schedule_for(
    leaves: Sequence[Any],
    treedef,
    n_buckets: int,
    min_bucket_bytes: int = 0,
) -> BucketSchedule:
    """Cached :func:`build_bucket_schedule` keyed on tree structure +
    leaf geometry + knobs."""
    key = (
        str(treedef),
        tuple(_leaf_key(l) for l in leaves),
        int(n_buckets),
        int(min_bucket_bytes),
    )
    sched = _CACHE.get(key)
    if sched is not None:
        _STATS["hits"] += 1
        return sched
    _STATS["misses"] += 1
    from ..common import exe_cache as _exe_cache

    disk = _exe_cache.cache_dir()
    if disk:
        rec = _exe_cache.load_json(_SIDECAR).get(_sidecar_key(key))
        if rec is not None:
            sched = _schedule_from_record(rec)
            if sched is not None:
                _STATS["disk_hits"] += 1
                if len(_CACHE) >= _CACHE_CAP:
                    _CACHE.pop(next(iter(_CACHE)))
                _CACHE[key] = sched
                return sched
    sched = build_bucket_schedule(leaves, n_buckets, min_bucket_bytes)
    if disk:
        _exe_cache.persist_json(
            _SIDECAR, {_sidecar_key(key): _schedule_record(key, sched)}
        )
    if len(_CACHE) >= _CACHE_CAP:
        _CACHE.pop(next(iter(_CACHE)))
    _CACHE[key] = sched
    return sched


def default_buckets() -> int:
    """The config-driven default bucket count: ``HOROVOD_OVERLAP_BUCKETS``
    when ``HOROVOD_OVERLAP`` is enabled, else 0 (monolithic path).
    Reads the initialized runtime's config snapshot when there is one."""
    from ..common import basics

    cfg = basics.live_config()
    return cfg.overlap_buckets if cfg.overlap else 0


def default_min_bytes() -> int:
    from ..common import basics

    return basics.live_config().overlap_min_bytes


def _auto_stages(hier_stages, world: int):
    """Resolve a bucketed function's ``hier_stages`` argument:
    ``"auto"`` (the default) consults the HOROVOD_HIERARCHICAL
    topology decision for this axis size — when a real inter axis is
    present, every bucket's collective decomposes into intra RS ->
    inter hop on the 1/L shard -> intra AG (ops/traced.py recipe
    family); an explicit ``(intra_groups, inter_groups)`` tuple is
    used as-is (the test/bench injection point); ``None`` keeps the
    flat wire."""
    if hier_stages == "auto":
        from ..common import topology as _topo

        return _topo.hierarchy_stages(world=world)
    return hier_stages


def _publish(schedule: BucketSchedule) -> None:
    from ..common import metrics

    metrics.publish_overlap(
        schedule.n_buckets, schedule.bucket_bytes, schedule.total_bytes
    )


def bucketed_allreduce(
    grads,
    op=None,
    average: Optional[bool] = None,
    n_buckets: Optional[int] = None,
    compression: Compressor = Compression.none,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
    axis_name: str = WORLD_AXIS,
    seed=0,
    residuals=None,
    mask=None,
    min_bucket_bytes: Optional[int] = None,
    schedule: Optional[BucketSchedule] = None,
    return_finite: bool = False,
    hier_stages="auto",
    groups=None,
):
    """Allreduce a gradient pytree as N independent per-bucket
    collectives (module docstring).

    ``groups`` restricts every bucket's collective to
    ``axis_index_groups`` of the flat axis (the local-SGD local phase:
    each slice reduces among its own ranks, zero inter-slice bytes).
    Mutually exclusive with the two-level routing (``hier_stages`` is
    ignored — there IS no inter hop), with process sets and with join
    masks; ``Average`` divides by the group size. Quantized wires ride
    the grouped two-stage recipe with the same EF residual contract.

    ``hier_stages`` routes each bucket through the TWO-LEVEL recipe
    (``traced.hierarchical_allreduce_groups``: intra RS -> inter
    collective on the 1/L shard -> intra AG) — ``"auto"`` (default)
    engages it exactly when ``HOROVOD_HIERARCHICAL`` resolves an inter
    axis for this topology; pass an explicit ``(intra, inter)`` group
    tuple or ``None`` to force/disable. Process sets and join masks
    degenerate to the flat wire (masked hierarchy has no uniform
    group shape). Quantized compressors place int8 on the INTER hop
    only (``Compression.hier_int8`` additionally rides bf16 intra —
    its documented eager placement, now honored on this path too);
    error-feedback residuals follow the hierarchical input-unit carry
    contract.

    Each bucket: concat its members' flattened leaves → ONE collective
    → split back. For the fp32/bf16 wires the collective is
    :func:`traced.allreduce` (process sets, join ``mask``, pre/post
    scale all compose); for a quantized-wire compression
    (``Compression.int8`` / ``int8_block`` / descendants) it is
    :func:`traced.quantized_allreduce` over the bucket buffer — block
    scales at the compressor's granularity, the prescale fold, and a
    per-bucket-decorrelated stochastic-rounding seed, exactly the PR-2
    monolithic recipe applied per bucket.

    ``residuals`` (error-feedback carry, quantized wires only): each
    bucket's carry joins its wire signal and the new per-bucket
    residual is sliced back to the member leaves; returns
    ``(reduced, new_residuals)``.

    ``mask`` is a [world] bool participation vector (the traced join
    mask): masked-out ranks contribute the identity and ``Average``
    divides by the live count. Sum/Average only — bucketing relies on
    reduction elementwise-ness over the concat (Adasum's whole-tensor
    dot products do not commute with concatenation; use the monolithic
    path for it).

    ``return_finite=True`` appends a scalar bool to the result: the
    non-finite sentinel (common/guard.py), ONE ``all(isfinite)``
    reduction per bucket buffer computed on the already-reduced values
    (replicated, so the flag agrees across ranks with no extra
    collective) AND'd across buckets. The guarded optimizers cond
    their update on it.
    """
    op = resolve_op(op, average)
    if op not in (Sum, Average):
        raise ValueError(
            "bucketed_allreduce supports op=Sum/Average only (Adasum "
            "and min/max/product do not commute with bucket concat); "
            "use the monolithic path for other ops"
        )
    if n_buckets is None:
        n_buckets = default_buckets() or 1
    if min_bucket_bytes is None:
        # same config deferral as n_buckets: the public surface and the
        # optimizer wrappers must build the SAME schedule for the same
        # tree (HOROVOD_OVERLAP_MIN_BYTES; pass 0 to disable merging)
        min_bucket_bytes = default_min_bytes()
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if schedule is None:
        schedule = schedule_for(
            leaves, treedef, n_buckets, min_bucket_bytes
        )
    _publish(schedule)

    quantized = getattr(compression, "quantized_wire", False)
    if groups is not None and (
        mask is not None
        or (process_set is not None and process_set.process_set_id != 0)
    ):
        raise NotImplementedError(
            "bucketed_allreduce(groups=) composes with neither "
            "process sets nor join masks"
        )
    if quantized:
        if process_set is not None and process_set.process_set_id != 0:
            raise NotImplementedError(
                "quantized-wire bucketed exchange over a process set is "
                "not supported (same restriction as the monolithic "
                "path); use fp32/bf16 compression or the global set"
            )
        if mask is not None:
            raise NotImplementedError(
                "join mask over the quantized bucketed wire is not "
                "supported; use fp32/bf16 compression under join"
            )
    elif residuals is not None:
        raise ValueError(
            "error_feedback requires a quantized-wire compression "
            "(Compression.int8); lossless/fp16 wires have no residual"
        )

    r_leaves = (
        treedef.flatten_up_to(residuals) if residuals is not None else None
    )
    out_leaves: list = [None] * len(leaves)
    res_leaves: list = [None] * len(leaves)
    for i in schedule.passthrough:
        out_leaves[i] = leaves[i]
        if r_leaves is not None:
            res_leaves[i] = r_leaves[i]

    stages = (
        None
        if groups is not None
        else _auto_stages(hier_stages, jax.lax.axis_size(axis_name))
    )
    if (
        stages is None
        and groups is None
        and hier_stages == "auto"
        and getattr(compression, "wire_format", None) == "int8_hier"
    ):
        # Compression.hier_int8 is an EXPLICIT per-call request: any
        # resolvable split qualifies, not just auto-mode evidence
        from ..common import topology as _topo

        stages = _topo.hierarchy_stages(
            world=jax.lax.axis_size(axis_name), mode="on"
        )
    if stages is not None and (
        (process_set is not None and process_set.process_set_id != 0)
        or mask is not None
    ):
        stages = None  # masked hierarchy degenerates to flat
    # Compression.hier_int8's eager contract, honored here: bf16 on
    # the intra hops under the int8 inter; plain int8 keeps the intra
    # hops exact (quantize only where bytes are scarce)
    hier_intra = (
        "bf16"
        if getattr(compression, "wire_format", None) == "int8_hier"
        else "fp32"
    )
    block = getattr(compression, "block_size", None)
    finite = None
    for b, idxs in enumerate(schedule.buckets):
        members = [leaves[i] for i in idxs]
        sizes = [int(np.prod(np.shape(m), dtype=np.int64)) for m in members]
        flat = (
            members[0].reshape(-1)
            if len(members) == 1
            else jnp.concatenate([m.reshape(-1) for m in members])
        )
        if quantized:
            # decorrelate rounding across buckets AND steps: stride the
            # caller's step seed by the bucket count (unique per
            # (step, bucket), monotone in the step like the monolithic
            # path's per-step seed)
            bseed = seed * schedule.n_buckets + b
            if r_leaves is not None:
                parts = [
                    r_leaves[i].reshape(-1).astype(flat.dtype)
                    for i in idxs
                ]
                r_flat = (
                    parts[0] if len(parts) == 1 else jnp.concatenate(parts)
                )
                if stages is not None:
                    out_flat, new_r = traced.hierarchical_allreduce_groups(
                        flat + r_flat, op=op, axis_name=axis_name,
                        stages=stages, intra_wire=hier_intra,
                        inter_wire="int8", seed=bseed, block_size=block,
                        prescale_factor=prescale_factor,
                        return_residual=True,
                    )
                else:
                    out_flat, new_r = traced.quantized_allreduce(
                        flat + r_flat, op=op, axis_name=axis_name,
                        seed=bseed, return_residual=True,
                        prescale_factor=prescale_factor, block_size=block,
                        groups=groups,
                    )
            elif stages is not None:
                # the two-level placement: int8 on the DCN hop only
                out_flat = traced.hierarchical_allreduce_groups(
                    flat, op=op, axis_name=axis_name, stages=stages,
                    intra_wire=hier_intra, inter_wire="int8",
                    seed=bseed, block_size=block,
                    prescale_factor=prescale_factor,
                )
                new_r = None
            else:
                out_flat = traced.quantized_allreduce(
                    flat, op=op, axis_name=axis_name, seed=bseed,
                    prescale_factor=prescale_factor, block_size=block,
                    groups=groups,
                )
                new_r = None
            if postscale_factor != 1.0:
                out_flat = out_flat * jnp.asarray(
                    postscale_factor, out_flat.dtype
                )
        elif stages is not None:
            wire, ctx = compression.compress(flat)
            red = traced.hierarchical_allreduce_groups(
                wire,
                op=op,
                axis_name=axis_name,
                stages=stages,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
            )
            out_flat = compression.decompress(red, ctx)
            new_r = None
        else:
            wire, ctx = compression.compress(flat)
            red = traced.allreduce(
                wire,
                op=op,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                process_set=process_set,
                axis_name=axis_name,
                mask=mask,
                groups=groups,
            )
            out_flat = compression.decompress(red, ctx)
            new_r = None
        if return_finite:
            # one scalar reduction over THIS bucket's reduced buffer —
            # the whole guard cost; AND'd into the step flag
            ok = traced.finite_scalar(out_flat)
            finite = ok if finite is None else jnp.logical_and(finite, ok)
        off = 0
        for i, sz in zip(idxs, sizes):
            out_leaves[i] = out_flat[off : off + sz].reshape(
                np.shape(leaves[i])
            )
            if r_leaves is not None:
                # carry keeps its init dtype (see optimizer.one_q)
                res_leaves[i] = (
                    new_r[off : off + sz]
                    .reshape(np.shape(leaves[i]))
                    .astype(r_leaves[i].dtype)
                )
            off += sz
    reduced = jax.tree_util.tree_unflatten(treedef, out_leaves)
    if return_finite and finite is None:  # schedule had no buckets
        finite = jnp.asarray(True)
    if residuals is None:
        return (reduced, finite) if return_finite else reduced
    new_res = jax.tree_util.tree_unflatten(treedef, res_leaves)
    if return_finite:
        return reduced, new_res, finite
    return reduced, new_res


# ----------------------------------------- sharded (ZeRO) bucket wire
#
# The ZeRO-2/3 exchange legs: per-bucket reduce-scatter of a gradient
# pytree INTO per-leaf shard slices, and the dual per-bucket all-gather
# of shard slices back to full leaves. Same schedule machinery and
# pane geometry as bucketed_allreduce (member leaves' padded [n, cols]
# panes concatenated column-wise, ONE collective per bucket), so the
# compiled step carries N independent collectives at their dataflow
# frontiers; the shard slice of each bucket's reduce-scatter output IS
# the per-rank storage slice — no full reduced-gradient buffer exists
# at any point. Wire formats ride per bucket (fp32 / bf16 cast /
# block-scaled int8 with pad exclusion by construction), resolved
# statically at trace time via resolve_wire / the WireTuner.

_WIRE_TUNER = None


def wire_tuner():
    """Process-wide WireTuner consulted by ``wire='auto'`` buckets.
    Trace-time choices freeze into the compiled step, so the tuner's
    explore-then-exploit plays out across RECOMPILES (the step harness
    / bench loop feeds ``record``, exactly like the OverlapTuner)."""
    global _WIRE_TUNER
    if _WIRE_TUNER is None:
        from ..common import basics
        from ..common.autotune import WireTuner

        _WIRE_TUNER = WireTuner(
            min_int8_bytes=basics.live_config().fusion_wire_min_bytes
        )
    return _WIRE_TUNER


def reset_wire_tuner() -> None:
    global _WIRE_TUNER
    _WIRE_TUNER = None


def resolve_wire(
    wire, bucket_bytes: int, itemsize: int = 4, key=None, hop=None
) -> str:
    """Static per-bucket wire-format resolution. Explicit formats pass
    through; ``'auto'`` resolves per bucket at TRACE time: under the
    ``HOROVOD_FUSION_WIRE_MIN_BYTES`` floor the quant tax always wins
    (fp32); above it the PR-2 premise prior picks int8 for 4-byte
    payloads — unless the WireTuner holds measured goodput for this
    bucket key, in which case the bandit's argmax wins (the step
    harness records observations across recompiles, the OverlapTuner
    pattern). Returns one of ``'fp32' | 'bf16' | 'int8'``.

    ``hop`` ∈ {None, 'intra', 'inter'} splits the tuner keyspace per
    hop of the two-level wire — (bucket-tier, hop) — so goodput can
    pick bf16-intra and int8-inter independently; the intra hop's
    candidate menu never includes int8 (ICI is fast: the quant tax
    can't pay for itself inside the slice), and ``bucket_bytes`` for
    the inter hop should be the 1/L shard the DCN actually carries."""
    if wire in (None, "fp32"):
        return "fp32"
    if wire in ("bf16", "int8"):
        if hop == "intra" and wire == "int8":
            return "fp32"  # int8 never rides the intra hop
        return wire
    if wire == "auto":
        tuner = wire_tuner()
        if int(bucket_bytes) < tuner.min_int8_bytes:
            return "fp32"
        candidates = (
            ("fp32", "bf16") if hop == "intra" else tuner.CANDIDATES
        )
        key = key if key is not None else ("bucket", int(bucket_bytes))
        if hop is not None:
            key = tuple(key) + (hop,)
        if any(
            tuner.goodput(key, c) > 0 for c in candidates
        ):
            return tuner.choose(
                key, int(bucket_bytes), itemsize=itemsize,
                candidates=candidates,
            )
        if "int8" in candidates and itemsize >= 4:
            return "int8"
        return "fp32"
    raise ValueError(f"unknown wire format {wire!r}")


def _leaf_panes(leaf, n):
    """One leaf's rank-major pane: flatten, zero-pad, [n, cols]."""
    from ..parallel.fsdp import pad_to

    return pad_to(leaf.reshape(-1), n).reshape(n, -1)


@jax.named_scope(traced.EXCHANGE_SCOPE)
def bucketed_reduce_scatter(
    grads,
    op=None,
    average: Optional[bool] = None,
    n_buckets: Optional[int] = None,
    axis_name: str = WORLD_AXIS,
    wire: str = "fp32",
    wire_block: Optional[int] = None,
    seed=0,
    residuals=None,
    min_bucket_bytes: Optional[int] = None,
    schedule: Optional[BucketSchedule] = None,
    hier_stages="auto",
    groups=None,
):
    """Reduce-scatter a pytree as N independent per-bucket collectives,
    returning per-leaf SHARD slices (nonscalar leaf → its ``[cols]``
    rank shard, ``cols = ceil(size/world)``; 0-d leaf → replicated
    psum) — the ZeRO-2 gradient leg.

    ``groups`` (local-SGD local phase) restricts every collective to
    ``axis_index_groups`` of the flat axis: panes are ``[L, cols]``
    (L = group size), each group scatters among its own members —
    rank r receives the shard of its POSITION within its group — and
    ``Average`` divides by L. ``hier_stages`` is ignored (no inter
    hop exists inside a slice). Elementwise identical to a
    per-leaf ``psum_scatter`` for the fp32 wire (same per-element
    cross-replica sums), so shard values are bit-exact vs the
    monolithic ZeRO-1 path.

    ``wire`` picks the per-bucket format (``resolve_wire``): bf16
    casts the pane buffer, int8 rides
    :func:`~horovod_tpu.ops.traced.quantized_reducescatter` with
    ``wire_block``-scaled stochastic rounding. ``residuals`` (tree
    mirroring ``grads``, input units) is the error-feedback carry for
    lossy buckets: it joins the pane signal before the wire and the new
    per-leaf residual comes back in leaf geometry (exact-wire buckets
    return zero residuals — everything was transmitted). Returns
    ``(shards, new_residuals)`` when ``residuals`` is given.

    ``hier_stages`` (``"auto"`` = the HOROVOD_HIERARCHICAL topology
    decision) routes each bucket through
    :func:`traced.hierarchical_reducescatter` — intra RS of the pane
    buffer, inter hop on the 1/L panes (int8 there when the resolved
    wire is int8), so the ZeRO-2 gradient leg's DCN bytes drop L-fold.
    Error-feedback buckets keep the FLAT wire (the EF carry is defined
    against the flat pane quantization; see docs/design.md)."""
    op = resolve_op(op, average)
    if op not in (Sum, Average):
        raise ValueError(
            "bucketed_reduce_scatter supports op=Sum/Average only"
        )
    if n_buckets is None:
        n_buckets = default_buckets() or 1
    if min_bucket_bytes is None:
        min_bucket_bytes = default_min_bytes()
    if groups is not None:
        n = len(groups[0])
        groups = [list(g) for g in groups]
        stages = None
    else:
        n = jax.lax.axis_size(axis_name)
        stages = _auto_stages(hier_stages, n)
    if residuals is not None:
        stages = None  # EF carries are defined against the flat wire
    hier_L = None if stages is None else len(stages[0][0])
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    nonscalar = [
        i for i, g in enumerate(leaves)
        if np.ndim(g) > 0 and not _is_float0(g)
    ]
    if schedule is None:
        schedule = schedule_for(
            [leaves[i] for i in nonscalar], treedef,
            n_buckets, min_bucket_bytes,
        )
    _publish(schedule)
    r_leaves = (
        treedef.flatten_up_to(residuals) if residuals is not None else None
    )
    out: list = [None] * len(leaves)
    res_out: list = [None] * len(leaves)
    in_schedule = set(nonscalar)
    for i, g in enumerate(leaves):
        if i in in_schedule:
            continue
        if _is_float0(g) or not jnp.issubdtype(
            jnp.result_type(g), jnp.inexact
        ):
            out[i] = g  # passthrough (float0 cotangents etc.)
        else:
            red = traced.clax.psum(g, axis_name, axis_index_groups=groups)
            out[i] = red / n if op == Average else red
        if r_leaves is not None:
            res_out[i] = r_leaves[i]
    for b, idxs in enumerate(schedule.buckets):
        members = [leaves[nonscalar[j]] for j in idxs]
        panes = [_leaf_panes(m, n) for m in members]
        cols = [p.shape[1] for p in panes]
        buf = panes[0] if len(panes) == 1 else jnp.concatenate(
            panes, axis=1
        )
        if r_leaves is not None:
            rparts = [
                _leaf_panes(
                    r_leaves[nonscalar[j]].astype(buf.dtype), n
                )
                for j in idxs
            ]
            buf = buf + (
                rparts[0] if len(rparts) == 1
                else jnp.concatenate(rparts, axis=1)
            )
        if stages is not None:
            # two-level leg: the inter hop sees 1/L of the bucket, so
            # the wire decision is keyed (and sized) per hop
            bw = resolve_wire(
                wire, int(schedule.bucket_bytes[b]) // hier_L,
                itemsize=jnp.result_type(members[0]).itemsize,
                key=("zero_rs", b, buf.shape[1]), hop="inter",
            )
            bseed = seed * schedule.n_buckets + b
            red = traced.hierarchical_reducescatter(
                buf, op=op, axis_name=axis_name, stages=stages,
                intra_wire="bf16" if bw == "bf16" else "fp32",
                inter_wire=bw, seed=bseed, block_size=wire_block,
            )
            off = 0
            for j, c in zip(idxs, cols):
                i = nonscalar[j]
                out[i] = red[off : off + c].astype(
                    jnp.result_type(leaves[i])
                )
                off += c
            continue
        bw = resolve_wire(
            wire, int(schedule.bucket_bytes[b]),
            itemsize=jnp.result_type(members[0]).itemsize,
            key=("zero_rs", b, buf.shape[1]),
        )
        new_r = None
        if bw == "int8":
            bseed = seed * schedule.n_buckets + b
            if r_leaves is not None:
                red, new_r = traced.quantized_reducescatter(
                    buf, op=Sum, axis_name=axis_name, seed=bseed,
                    block_size=wire_block, return_residual=True,
                    groups=groups,
                )
            else:
                red = traced.quantized_reducescatter(
                    buf, op=Sum, axis_name=axis_name, seed=bseed,
                    block_size=wire_block, groups=groups,
                )
            if op == Average:
                red = red / jnp.asarray(n, red.dtype)
        else:
            wire_buf = buf.astype(jnp.bfloat16) if bw == "bf16" else buf
            red = traced.clax.psum_scatter(
                wire_buf, axis_name, scatter_dimension=0, tiled=False,
                axis_index_groups=groups,
            ).astype(buf.dtype)
            if op == Average:
                red = red / jnp.asarray(n, red.dtype)
            if r_leaves is not None:
                # exact wire transmits everything: residual drains;
                # bf16 carries the local cast error (input units)
                new_r = (
                    buf - wire_buf.astype(buf.dtype)
                    if bw == "bf16"
                    else jnp.zeros_like(buf)
                )
        off = 0
        for j, c in zip(idxs, cols):
            i = nonscalar[j]
            out[i] = red[off : off + c].astype(
                jnp.result_type(leaves[i])
            )
            if r_leaves is not None:
                size = int(np.prod(np.shape(leaves[i]), dtype=np.int64))
                res_out[i] = (
                    new_r[:, off : off + c]
                    .reshape(-1)[:size]
                    .reshape(np.shape(leaves[i]))
                    .astype(r_leaves[i].dtype)
                )
            off += c
    shards = jax.tree_util.tree_unflatten(treedef, out)
    if residuals is None:
        return shards
    return shards, jax.tree_util.tree_unflatten(treedef, res_out)


@jax.named_scope(traced.EXCHANGE_SCOPE)
def bucketed_shard_all_gather(
    shards,
    like,
    n_buckets: Optional[int] = None,
    axis_name: str = WORLD_AXIS,
    wire: str = "fp32",
    wire_block: Optional[int] = None,
    seed=0,
    residuals=None,
    min_bucket_bytes: Optional[int] = None,
    schedule: Optional[BucketSchedule] = None,
    hier_stages="auto",
    groups=None,
):
    """The dual of :func:`bucketed_reduce_scatter`: per-leaf shard
    slices → full leaves with ``like``'s shapes, as N independent
    per-bucket all-gathers (concat member shards → ONE collective per
    bucket → per-leaf columns → unpad/reshape). The schedule is keyed
    on ``like``'s (full) leaf geometry, so a matched reduce-scatter /
    all-gather pair shares ONE cached schedule.

    ``groups`` mirrors :func:`bucketed_reduce_scatter`'s local-phase
    contract: shards are the ``[cols = ceil(size/L)]`` group-position
    slices and every gather runs inside its ``axis_index_groups``
    group only (``hier_stages`` ignored).

    ``residuals`` (tree in SHARD geometry — leaf ``[cols]``) is the
    error-feedback carry for lossy buckets on this leg: it joins the
    shard signal before the wire; returns ``(full, new_residuals)``.
    Buckets whose member dtypes diverge fall back to per-leaf fp32
    gathers (an inner transform that changes dtype per leaf)."""
    if n_buckets is None:
        n_buckets = default_buckets() or 1
    if min_bucket_bytes is None:
        min_bucket_bytes = default_min_bytes()
    if groups is not None:
        n = len(groups[0])
        groups = [list(g) for g in groups]
        stages = None
    else:
        n = jax.lax.axis_size(axis_name)
        stages = _auto_stages(hier_stages, n)
    if residuals is not None:
        stages = None  # EF carries are defined against the flat wire
    hier_L = None if stages is None else len(stages[0][0])
    s_leaves, s_def = jax.tree_util.tree_flatten(shards)
    l_leaves = s_def.flatten_up_to(like)
    nonscalar = [
        i for i, l in enumerate(l_leaves)
        if np.ndim(l) > 0 and not _is_float0(l)
    ]
    if schedule is None:
        schedule = schedule_for(
            [l_leaves[i] for i in nonscalar], s_def,
            n_buckets, min_bucket_bytes,
        )
    r_leaves = (
        s_def.flatten_up_to(residuals) if residuals is not None else None
    )
    out: list = [None] * len(s_leaves)
    res_out: list = [None] * len(s_leaves)
    in_schedule = set(nonscalar)
    for i in range(len(s_leaves)):
        if i not in in_schedule:
            out[i] = s_leaves[i]  # replicated scalars pass through
            if r_leaves is not None:
                res_out[i] = r_leaves[i]
    for b, idxs in enumerate(schedule.buckets):
        mem = [s_leaves[nonscalar[j]] for j in idxs]
        if len({m.dtype for m in mem}) > 1:
            for j in idxs:
                i = nonscalar[j]
                l = l_leaves[i]
                full = traced.clax.all_gather(
                    s_leaves[i], axis_name, axis=0,
                    axis_index_groups=groups,
                ).reshape(-1)
                size = int(np.prod(np.shape(l), dtype=np.int64))
                out[i] = (
                    full[:size].reshape(np.shape(l))
                    .astype(s_leaves[i].dtype)
                )
                if r_leaves is not None:
                    res_out[i] = r_leaves[i]
            continue
        cols = [m.shape[0] for m in mem]
        buf = mem[0] if len(mem) == 1 else jnp.concatenate(mem)
        if r_leaves is not None:
            rparts = [
                r_leaves[nonscalar[j]].astype(buf.dtype) for j in idxs
            ]
            buf = buf + (
                rparts[0] if len(rparts) == 1
                else jnp.concatenate(rparts)
            )
        if stages is not None:
            bw = resolve_wire(
                wire, int(schedule.bucket_bytes[b]) // hier_L,
                itemsize=mem[0].dtype.itemsize,
                key=("zero_ag", b, buf.shape[0]), hop="inter",
            )
            bseed = seed * schedule.n_buckets + b
            full = traced.hierarchical_allgather(
                buf, axis_name=axis_name, stages=stages,
                intra_wire="bf16" if bw == "bf16" else "fp32",
                inter_wire=bw, seed=bseed, block_size=wire_block,
            )
            off = 0
            for j, c in zip(idxs, cols):
                i = nonscalar[j]
                l = l_leaves[i]
                size = int(np.prod(np.shape(l), dtype=np.int64))
                out[i] = (
                    full[:, off : off + c]
                    .reshape(-1)[:size]
                    .reshape(np.shape(l))
                    .astype(s_leaves[i].dtype)
                )
                off += c
            continue
        bw = resolve_wire(
            wire, int(schedule.bucket_bytes[b]),
            itemsize=mem[0].dtype.itemsize,
            key=("zero_ag", b, buf.shape[0]),
        )
        new_r = None
        if bw == "int8":
            bseed = seed * schedule.n_buckets + b
            if r_leaves is not None:
                full, new_r = traced.quantized_allgather(
                    buf, axis_name=axis_name, seed=bseed,
                    block_size=wire_block, return_residual=True,
                    groups=groups,
                )
            else:
                full = traced.quantized_allgather(
                    buf, axis_name=axis_name, seed=bseed,
                    block_size=wire_block, groups=groups,
                )
        else:
            wire_buf = buf.astype(jnp.bfloat16) if bw == "bf16" else buf
            full = traced.clax.all_gather(
                wire_buf, axis_name, axis=0, axis_index_groups=groups,
            ).astype(buf.dtype)  # [n, C]
            if r_leaves is not None:
                new_r = (
                    buf - wire_buf.astype(buf.dtype)
                    if bw == "bf16"
                    else jnp.zeros_like(buf)
                )
        off = 0
        for j, c in zip(idxs, cols):
            i = nonscalar[j]
            l = l_leaves[i]
            size = int(np.prod(np.shape(l), dtype=np.int64))
            out[i] = (
                full[:, off : off + c]
                .reshape(-1)[:size]
                .reshape(np.shape(l))
                .astype(s_leaves[i].dtype)
            )
            if r_leaves is not None:
                res_out[i] = new_r[off : off + c].astype(
                    r_leaves[i].dtype
                )
            off += c
    gathered = jax.tree_util.tree_unflatten(s_def, out)
    if residuals is None:
        return gathered
    return gathered, jax.tree_util.tree_unflatten(s_def, res_out)


def overlap_boundary(
    tree,
    op=Average,
    average: Optional[bool] = None,
    n_buckets: Optional[int] = None,
    compression: Compressor = Compression.none,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
    axis_name: str = WORLD_AXIS,
    seed=0,
    mask=None,
    min_bucket_bytes: Optional[int] = None,
    hier_stages="auto",
):
    """The in-backprop boundary marker: identity on the forward; on the
    backward, the cotangent pytree leaves through
    :func:`bucketed_allreduce`.

    Pass the model parameters through this before using them::

        def loss(params, batch):
            params = hvd.overlap_boundary(params, overlap_buckets=4)
            ...

    ``jax.grad`` of such a loss returns gradients that were ALREADY
    reduced during backprop — each bucket's collective sits in the
    backward dataflow at the point its last member gradient
    materializes, which is the reference's autograd-hook overlap
    expressed as compiler-visible dataflow. The custom_vjp body is
    inlined at trace time, so XLA sees N independent collectives, not
    an opaque call."""
    kw = dict(
        op=op,
        average=average,
        n_buckets=n_buckets,
        compression=compression,
        prescale_factor=prescale_factor,
        postscale_factor=postscale_factor,
        process_set=process_set,
        axis_name=axis_name,
        seed=seed,
        mask=mask,
        min_bucket_bytes=min_bucket_bytes,
        hier_stages=hier_stages,
    )

    @jax.custom_vjp
    def _boundary(t):
        return t

    def _fwd(t):
        return t, None

    def _bwd(_, ct):
        with jax.named_scope(traced.EXCHANGE_SCOPE):
            return (bucketed_allreduce(ct, **kw),)

    _boundary.defvjp(_fwd, _bwd)
    return _boundary(tree)
