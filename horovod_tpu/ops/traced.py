"""Traced-mode collectives: the TPU fast path.

These functions are called *inside* ``jit`` / ``shard_map`` over a mesh axis
(default ``'hvd'``). XLA sees the collective, fuses and schedules it, and
overlaps it with compute — statically doing what the reference's background
negotiate-fuse-execute machine (horovod/common/operations.cc RunLoopOnce +
horovod/common/ops/nccl_operations.cc [V], SURVEY.md §3.2) does dynamically.
There is deliberately no fusion buffer here: XLA's combiner pass is the
fusion buffer.

Process-set restriction (ref: per-set communicators in
horovod/common/process_set.cc [V]) is implemented with *masked full-axis
collectives* and static ``ppermute`` routes, NOT ``axis_index_groups``:
XLA's TPU lowering requires every replica group to have the same size,
and a set-plus-singletons partition can never satisfy that. Masking has
no such constraint, lowers on every backend, and costs one full-axis
collective (ICI-cheap) instead of a sub-group one. Ranks outside the
set contribute the reduction identity and get their own input back —
the closest SPMD analog of "non-members don't call the op".
"""

from __future__ import annotations

import types
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import tracing as _tracing
from ..common.topology import WORLD_AXIS
from ..common.process_sets import ProcessSet
from .reduction_ops import Average, Sum, Adasum, Min, Max, Product, resolve_op

# ------------------------------------------------------------- scopes
#
# ``jax.named_scope`` puts a name into the ``op_name`` of every
# operation traced under it: metadata only, the compiled program is the
# same with or without it (tests/test_scopes.py compares the optimised
# HLO). A reader of a device trace sorts the step's time by these names.
# Whoever starts an exchange (``DistributedOptimizer``'s ``communicate``,
# the tape API, ``overlap_boundary``, the ZeRO optimizer) opens
# ``hvd_exchange``, and every collective beneath it carries
# ``collective``. The optimizers put the inner transform's update (and
# the guard's skip) under ``hvd_update`` and the
# ``backward_passes_per_step`` accumulation under ``hvd_accumulate``.
EXCHANGE_SCOPE = "hvd_exchange"
UPDATE_SCOPE = "hvd_update"
ACCUMULATE_SCOPE = "hvd_accumulate"


def exchange_plan(grads, *, world, op, wire_dtype, wire=None, buckets=0,
                  collectives=None):
    """The span ``hvd.exchange.plan`` around an exchange of ``grads``
    while JAX traces it (whoever opens ``EXCHANGE_SCOPE`` opens it),
    else a null context: what the step will hand to its collectives, as
    tags. ``world`` is the size of the axis (or group) reduced over (or
    a function that gives it, called only while tracing: outside a trace
    the axis may be unbound),
    ``wire_dtype(dtype)`` the dtype a leaf of ``dtype`` has on the wire
    and ``wire`` the wire's name where the dtypes do not say it (a
    quantized wire). ``bytes`` is a chip's payload a step: the sum over
    the leaves as they go on the wire (a quantized wire's scales, four
    bytes a block or a leaf, are not counted). ``collectives`` is the
    number of calls the path issues: one a leaf unless given."""
    leaves = jax.tree_util.tree_leaves(grads)
    span = _tracing.trace_time_span(
        "hvd.exchange.plan", leaves[0] if leaves else None
    )
    if isinstance(span, _tracing.Span):
        on_wire = {}
        for leaf in leaves:
            if leaf.dtype not in on_wire:
                on_wire[leaf.dtype] = jnp.dtype(wire_dtype(leaf.dtype))
        span.tag(
            world=int(world() if callable(world) else world),
            op=getattr(op, "name", str(op)).lower(),
            leaves=len(leaves),
            bytes=sum(
                int(np.prod(leaf.shape, dtype=np.int64))
                * on_wire[leaf.dtype].itemsize
                for leaf in leaves
            ),
            wire=wire or "+".join(sorted({d.name for d in on_wire.values()})),
            buckets=int(buckets),
            collectives=len(leaves) if collectives is None else collectives,
        )
    return span


# ``jax.lax``'s collectives, each under the scope ``collective``: the one
# way this package puts a collective into a traced program.
clax = types.SimpleNamespace(**{
    name: jax.named_scope("collective")(getattr(lax, name))
    for name in ("psum", "pmin", "pmax", "psum_scatter", "all_gather",
                 "all_to_all", "ppermute")
})


# The stall inspector used to run only on EAGER fusion cycles, so a
# purely-traced job (the TPU fast path) could stall silently: leaked
# eager handles aged unobserved and stale worker heartbeats never got
# re-checked. Traced collectives have no background loop to hook, but
# their Python entry points ARE the dispatch path (they run at trace /
# retrace time on the host), and the telemetry hub re-checks at every
# step close (common/telemetry.py) for steady state. Rate-limited so a
# per-leaf optimizer trace doesn't pay a check per gradient tensor.
_STALL_CHECK_INTERVAL_S = 0.5
_last_stall_check = [0.0]


def _stall_check() -> None:
    import time as _time

    now = _time.monotonic()
    if now - _last_stall_check[0] < _STALL_CHECK_INTERVAL_S:
        return
    _last_stall_check[0] = now
    from ..common import basics as _basics

    insp = _basics.state().stall_inspector
    if insp is not None:
        insp.check()  # may raise the shutdown escalation — intended


class _SetInfo(NamedTuple):
    """Static per-world lookup tables for a proper-subset process set."""

    mask: np.ndarray  # [world] bool — rank is a member
    pos: np.ndarray  # [world] int32 — rank's index within the set (0 outside)
    size: int
    ranks: Tuple[int, ...]


def _set_info(
    process_set: Optional[ProcessSet], axis_name
) -> Optional[_SetInfo]:
    """None for the global set (or a set covering the whole axis)."""
    if process_set is None or process_set.process_set_id == 0:
        return None
    world = int(lax.axis_size(axis_name))
    if process_set.size == world:
        return None
    mask = np.zeros(world, dtype=bool)
    pos = np.zeros(world, dtype=np.int32)
    for i, r in enumerate(process_set.ranks):
        mask[r] = True
        pos[r] = i
    return _SetInfo(mask, pos, process_set.size, tuple(process_set.ranks))


def _member(info: _SetInfo, axis_name):
    idx = lax.axis_index(axis_name)
    return jnp.asarray(info.mask)[idx], jnp.asarray(info.pos)[idx]


def _masked_gather(tensor, info: _SetInfo, axis_name, member, pos):
    """All-gather over the set's members only: each member drops its
    tensor into its set-slot of a [k·d, ...] buffer, a full-axis psum
    assembles them (outsiders contribute zeros). Every rank — member or
    not — ends up holding the set's gather."""
    d = tensor.shape[0]
    contrib = jnp.where(member, tensor, jnp.zeros_like(tensor))
    buf = jnp.zeros(
        (info.size * d,) + tuple(tensor.shape[1:]), tensor.dtype
    )
    buf = lax.dynamic_update_slice_in_dim(buf, contrib, pos * d, axis=0)
    return clax.psum(buf, axis_name)


def rank(axis_name: str = WORLD_AXIS):
    """Per-chip rank inside a traced region (= hvd.rank() of the owning
    rank in the reference's per-process model)."""
    return lax.axis_index(axis_name)


def size(axis_name: str = WORLD_AXIS) -> int:
    return lax.axis_size(axis_name)


def allreduce(
    tensor,
    average: Optional[bool] = None,
    op=None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
    axis_name: str = WORLD_AXIS,
    mask=None,
    groups=None,
):
    """Allreduce across the mesh axis (ref: hvd.allreduce,
    horovod/torch/mpi_ops.py + MPI/NCCL Allreduce ops [V]).

    pre/postscale mirror HOROVOD's prescale_factor/postscale_factor —
    applied before/after the reduction, fused into the XLA program (the
    reference needs a dedicated ScaleBuffer CUDA kernel; XLA fuses the
    multiply for free, SURVEY.md §2.2 GPU context row).

    With a process set, members reduce among themselves (masked
    full-axis collective — see module docstring) and non-members return
    their input unchanged.

    ``mask`` is the traced join mask (ref: hvd.join / JoinOp [V] —
    the eager layer's `join_ranks` semantics inside a jitted step): a
    [world] bool vector, static numpy or traced, where ``mask[r] ==
    False`` means rank r ran out of data. Masked-out ranks contribute
    the reduction identity, ``Average`` divides by the LIVE count (a
    traced scalar — the mask may change step to step without a
    retrace), and every participating rank receives the live
    reduction. Sum/Average only (a dynamic live-count has no analog
    for min/max/product); composes with a process set by intersection.

    ``groups`` restricts the reduction to ``axis_index_groups`` of the
    flat axis (uniform group sizes — the intra-slice groups of
    ``topology.hierarchy_stages()``): each group reduces among its own
    members and ``Average`` divides by the GROUP size. This is the
    local-SGD local-phase wire (every gradient byte stays on ICI);
    Sum/Average only, and it composes with neither process sets nor
    join masks (a masked subgroup has no uniform replica-group shape).
    """
    _stall_check()
    op = resolve_op(op, average)
    if mask is not None and op not in (Average, Sum):
        raise ValueError(
            "allreduce(mask=) supports op=Sum/Average only"
        )
    if groups is not None:
        if op not in (Average, Sum):
            raise ValueError(
                "allreduce(groups=) supports op=Sum/Average only"
            )
        if mask is not None or (
            process_set is not None and process_set.process_set_id != 0
        ):
            raise NotImplementedError(
                "allreduce(groups=) composes with neither process "
                "sets nor join masks"
            )
        if prescale_factor != 1.0:
            tensor = tensor * jnp.asarray(
                prescale_factor, dtype=tensor.dtype
            )
        out = clax.psum(
            tensor, axis_name, axis_index_groups=[list(g) for g in groups]
        )
        if op == Average:
            out = out / jnp.asarray(len(groups[0]), out.dtype)
        if postscale_factor != 1.0:
            out = out * jnp.asarray(postscale_factor, dtype=out.dtype)
        return out
    info = _set_info(process_set, axis_name)
    n = info.size if info is not None else lax.axis_size(axis_name)
    raw = tensor

    if op == Adasum:
        from .adasum import adasum_allreduce

        if prescale_factor != 1.0:
            tensor = tensor * jnp.asarray(prescale_factor, tensor.dtype)
        if info is not None:
            member, pos = _member(info, axis_name)
            stacked = _masked_gather(
                tensor[None], info, axis_name, member, pos
            )
            from .adasum import _tree_combine

            out = _tree_combine([stacked[i] for i in range(info.size)])
        else:
            out = adasum_allreduce(tensor, axis_name=axis_name)
        if postscale_factor != 1.0:
            out = out * jnp.asarray(postscale_factor, out.dtype)
        if info is not None:
            out = jnp.where(member, out, raw)
        return out

    if prescale_factor != 1.0:
        tensor = tensor * jnp.asarray(prescale_factor, dtype=tensor.dtype)
    member = None
    if info is not None:
        member, _ = _member(info, axis_name)
    live = None
    if mask is not None:
        live = jnp.asarray(mask)[lax.axis_index(axis_name)]
    if op in (Average, Sum):
        gate = member
        if live is not None:
            gate = live if gate is None else jnp.logical_and(gate, live)
        contrib = (
            tensor
            if gate is None
            else jnp.where(gate, tensor, jnp.zeros_like(tensor))
        )
        out = clax.psum(contrib, axis_name)
        if op == Average:
            if live is None:
                out = out / jnp.asarray(n, dtype=out.dtype)
            else:
                # live count is traced: the join mask may differ step
                # to step without forcing a retrace
                n_live = clax.psum(
                    jnp.where(gate, 1.0, 0.0).astype(out.dtype), axis_name
                )
                out = out / jnp.maximum(
                    n_live, jnp.ones((), out.dtype)
                )
    elif op == Min:
        contrib = (
            tensor
            if member is None
            else jnp.where(
                member, tensor, jnp.full_like(tensor, _identity(tensor, Min))
            )
        )
        out = clax.pmin(contrib, axis_name)
    elif op == Max:
        contrib = (
            tensor
            if member is None
            else jnp.where(
                member, tensor, jnp.full_like(tensor, _identity(tensor, Max))
            )
        )
        out = clax.pmax(contrib, axis_name)
    elif op == Product:
        contrib = (
            tensor
            if member is None
            else jnp.where(member, tensor, jnp.ones_like(tensor))
        )
        gathered = clax.all_gather(contrib, axis_name)
        out = jnp.prod(gathered, axis=0)
    else:
        raise ValueError(f"unsupported reduce op {op}")
    if postscale_factor != 1.0:
        out = out * jnp.asarray(postscale_factor, dtype=out.dtype)
    if member is not None:
        out = jnp.where(member, out, raw)
    return out


def _identity(tensor, op):
    """Reduction identity for masking non-members out of pmin/pmax."""
    if jnp.issubdtype(tensor.dtype, jnp.floating):
        fin = jnp.finfo(tensor.dtype)
        return fin.max if op == Min else fin.min
    iin = jnp.iinfo(tensor.dtype)
    return iin.max if op == Min else iin.min


# ------------------------------------------------- non-finite sentinel


def finite_scalar(x):
    """One in-JIT boolean: ``all(isfinite(x))`` — the per-bucket guard
    reduction (common/guard.py). Non-float payloads are finite by
    construction, so the flag folds to a constant and costs nothing.

    Applied to ALREADY-REDUCED values the flag needs no collective: a
    psum/all-gather output is replicated, so every rank computes the
    identical bit and a ``lax.cond`` on it stays uniform across the
    gang (the SPMD-safety requirement for skip-step semantics)."""
    if not jnp.issubdtype(jnp.result_type(x), jnp.floating):
        return jnp.asarray(True)
    return jnp.all(jnp.isfinite(x))


def tree_finite(tree):
    """``finite_scalar`` over a pytree, combined with logical AND —
    one scalar reduction per leaf, one boolean out. Empty trees are
    finite."""
    flags = [
        finite_scalar(leaf)
        for leaf in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(jnp.result_type(leaf), jnp.floating)
    ]
    if not flags:
        return jnp.asarray(True)
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_and(out, f)
    return out


def grouped_allreduce(
    tensors,
    average: Optional[bool] = None,
    op=None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
    axis_name: str = WORLD_AXIS,
):
    """Reduce a list of tensors as one logical op (ref: hvd.grouped_allreduce
    / group_table.cc [V]). In traced mode the group contract — all members
    reduced atomically in one fused collective — is expressed by a single
    psum over the tuple; XLA emits one fused all-reduce."""
    _stall_check()
    op = resolve_op(op, average)
    info = _set_info(process_set, axis_name)
    n = info.size if info is not None else lax.axis_size(axis_name)
    if op == Adasum:
        return [
            allreduce(
                t,
                op=Adasum,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                process_set=process_set,
                axis_name=axis_name,
            )
            for t in tensors
        ]
    raws = list(tensors)
    if prescale_factor != 1.0:
        tensors = [t * jnp.asarray(prescale_factor, t.dtype) for t in tensors]
    member = None
    if info is not None:
        member, _ = _member(info, axis_name)
    if op in (Average, Sum):
        contribs = tuple(
            t if member is None else jnp.where(member, t, jnp.zeros_like(t))
            for t in tensors
        )
        outs = clax.psum(contribs, axis_name)
        if op == Average:
            outs = tuple(o / jnp.asarray(n, o.dtype) for o in outs)
    elif op == Min:
        contribs = tuple(
            t
            if member is None
            else jnp.where(member, t, jnp.full_like(t, _identity(t, Min)))
            for t in tensors
        )
        outs = clax.pmin(contribs, axis_name)
    elif op == Max:
        contribs = tuple(
            t
            if member is None
            else jnp.where(member, t, jnp.full_like(t, _identity(t, Max)))
            for t in tensors
        )
        outs = clax.pmax(contribs, axis_name)
    else:
        raise ValueError(f"unsupported grouped reduce op {op}")
    outs = list(outs)
    if postscale_factor != 1.0:
        outs = [o * jnp.asarray(postscale_factor, o.dtype) for o in outs]
    if member is not None:
        outs = [jnp.where(member, o, r) for o, r in zip(outs, raws)]
    return outs


def allgather(
    tensor,
    process_set: Optional[ProcessSet] = None,
    axis_name: str = WORLD_AXIS,
):
    """Concatenate each rank's tensor along axis 0 (ref: hvd.allgather /
    MPI_Allgatherv path [V]). Traced mode requires equal shapes (static
    shapes under jit); the eager path supports uneven dim0 via padding.

    With a process set, the result is the concatenation of the members'
    tensors in set order — every rank (members and outsiders alike)
    receives it; outsiders contribute nothing."""
    _stall_check()
    info = _set_info(process_set, axis_name)
    if info is None:
        return clax.all_gather(tensor, axis_name, axis=0, tiled=True)
    member, pos = _member(info, axis_name)
    return _masked_gather(tensor, info, axis_name, member, pos)


def broadcast(
    tensor,
    root_rank: int,
    process_set: Optional[ProcessSet] = None,
    axis_name: str = WORLD_AXIS,
):
    """Every rank receives root_rank's value (ref: hvd.broadcast /
    NCCLBroadcast [V]). Implemented as a masked psum — XLA lowers this to a
    broadcast-from-source collective on ICI. With a process set, members
    receive the root's value and outsiders keep their own input."""
    _stall_check()
    info = _set_info(process_set, axis_name)
    idx = lax.axis_index(axis_name)
    contribution = jnp.where(idx == root_rank, tensor, jnp.zeros_like(tensor))
    out = clax.psum(contribution, axis_name)
    if info is not None:
        member, _ = _member(info, axis_name)
        out = jnp.where(member, out, tensor)
    return out


def alltoall(
    tensor,
    process_set: Optional[ProcessSet] = None,
    axis_name: str = WORLD_AXIS,
):
    """Scatter dim-0 blocks to peers, gather their blocks (ref: hvd.alltoall
    / MPI_Alltoallv [V]). Traced mode is the equal-splits case (dim0 %
    participant count == 0); uneven splits are an eager-mode feature.

    With a process set, routing runs over static ``ppermute`` rings among
    the members only — k-1 hops of one block each, the wire-optimal
    (k-1)/k·P, with no replica-group size constraint. Non-members return
    their input unchanged."""
    _stall_check()
    info = _set_info(process_set, axis_name)
    if info is None:
        return clax.all_to_all(
            tensor, axis_name, split_axis=0, concat_axis=0, tiled=True
        )
    k = info.size
    if tensor.shape[0] % k:
        raise ValueError(
            f"alltoall over a {k}-rank process set needs dim0 divisible "
            f"by {k}, got {tensor.shape[0]}"
        )
    d = tensor.shape[0] // k
    member, pos = _member(info, axis_name)
    # Block p stays home: each member keeps its own pos-th block in place.
    own = lax.dynamic_slice_in_dim(tensor, pos * d, d, axis=0)
    out = jnp.zeros_like(tensor)
    out = lax.dynamic_update_slice_in_dim(out, own, pos * d, axis=0)
    for s in range(1, k):
        # Rotation s: the member at set-position q sends its block
        # (q+s)%k to the member at set-position (q+s)%k; equivalently we
        # receive, from position (pos-s)%k, that member's block `pos`.
        perm = [(info.ranks[q], info.ranks[(q + s) % k]) for q in range(k)]
        send_at = ((pos + s) % k) * d
        send = lax.dynamic_slice_in_dim(tensor, send_at, d, axis=0)
        recv = clax.ppermute(send, axis_name, perm)
        recv_slot = ((pos - s) % k) * d
        out = lax.dynamic_update_slice_in_dim(out, recv, recv_slot, axis=0)
    return jnp.where(member, out, tensor)


def reducescatter(
    tensor,
    op=None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
    axis_name: str = WORLD_AXIS,
):
    """Reduce then scatter dim-0 shards (ref: hvd.reducescatter, upstream
    v0.27+ [V]). Maps directly onto the ICI-optimal psum_scatter.

    With a process set, members psum the masked tensor over the full
    axis and slice their set-position's shard (outsiders contribute
    zeros and get the set-position-0 shard — their output, like the
    reference's, is meaningless; its shape must still be uniform under
    SPMD)."""
    _stall_check()
    op = resolve_op(op, None)
    info = _set_info(process_set, axis_name)
    if prescale_factor != 1.0:
        tensor = tensor * jnp.asarray(prescale_factor, tensor.dtype)
    if info is None:
        n = lax.axis_size(axis_name)
        out = clax.psum_scatter(
            tensor, axis_name, scatter_dimension=0, tiled=True
        )
    else:
        k = info.size
        if tensor.shape[0] % k:
            raise ValueError(
                f"reducescatter over a {k}-rank process set needs dim0 "
                f"divisible by {k}, got {tensor.shape[0]}"
            )
        n = k
        member, pos = _member(info, axis_name)
        contrib = jnp.where(member, tensor, jnp.zeros_like(tensor))
        total = clax.psum(contrib, axis_name)
        d = tensor.shape[0] // k
        out = lax.dynamic_slice_in_dim(total, pos * d, d, axis=0)
    if op == Average:
        out = out / jnp.asarray(n, out.dtype)
    if postscale_factor != 1.0:
        out = out * jnp.asarray(postscale_factor, out.dtype)
    return out


def _stochastic_round_rows(x2d, key):
    """Per-row int8 quantization with stochastic rounding (unbiased):
    row-wise absmax scale, floor + bernoulli(frac) up. Plain jnp — XLA
    fuses it into one pass; the per-tensor Pallas kernel
    (pallas_kernels.int8_quantize) covers the single-scale case."""
    absmax = jnp.max(jnp.abs(x2d), axis=1)
    scale = jnp.maximum(absmax, 1e-30) / 127.0
    scaled = x2d / scale[:, None]
    floor = jnp.floor(scaled)
    frac = scaled - floor
    u = jax.random.uniform(key, x2d.shape)
    q = jnp.clip(floor + (u < frac), -128, 127).astype(jnp.int8)
    return q, scale


def _stochastic_round_blocks(x2d, block: int, key):
    """Block-scaled variant of :func:`_stochastic_round_rows`: one
    absmax scale per ``block`` elements within each row, so
    mixed-magnitude regions of a fused buffer never share a dynamic
    range (the block-scaled wire of pallas_kernels.int8_block_quantize,
    expressed as plain jnp for use inside traced programs where XLA
    fuses it into the collective's producer).

    Returns ``(q, scales)`` with ``q`` int8 ``[rows, nb, block]``
    (tail block zero-padded — zeros quantize to zeros and never raise
    a block's absmax, so padding is excluded from the scales by
    construction) and ``scales`` float32 ``[rows, nb]``.
    """
    rows, cols = x2d.shape
    nb = -(-cols // block)
    pad = nb * block - cols
    xb = (
        jnp.pad(x2d, ((0, 0), (0, pad))) if pad else x2d
    ).reshape(rows, nb, block)
    absmax = jnp.max(jnp.abs(xb), axis=2)
    scales = jnp.maximum(absmax, 1e-30) / 127.0
    scaled = xb / scales[:, :, None]
    floor = jnp.floor(scaled)
    frac = scaled - floor
    u = jax.random.uniform(key, scaled.shape)
    q = jnp.clip(floor + (u < frac), -128, 127).astype(jnp.int8)
    return q, scales


def _block_dequant(q, scales):
    """``[rows, nb, block]`` int8 × ``[rows, nb]`` scales → float32
    ``[rows, nb*block]``."""
    rows, nb, block = q.shape
    return (q.astype(jnp.float32) * scales[:, :, None]).reshape(
        rows, nb * block
    )


def quantized_allreduce(
    tensor,
    op=None,
    axis_name: str = WORLD_AXIS,
    seed=0,
    return_residual: bool = False,
    prescale_factor: float = 1.0,
    block_size: Optional[int] = None,
    groups=None,
):
    """Allreduce moving int8 across ICI — the quantized-collective
    recipe of EQuARX (PAPERS.md), built from primitives the reference
    stops short of (its wire compression ends at fp16 [V]).

    Shape: quantized reduce-scatter (all_to_all of per-chunk int8 +
    scales, dequantize-sum locally) then quantized all_gather of the
    reduced shards. Per-device wire bytes ≈ 2·(n-1)/n · P/4 versus
    2·(n-1)/n · P for an fp32 ring allreduce — a true ~4x at every
    world size, with O(P) peak memory (the naive gather-everything
    formulation would move MORE than fp32 psum beyond n=8 and
    materialize an n·P fp32 intermediate).

    Two quantization stages ⇒ error ~2 quanta worst case; stochastic
    rounding (seeded per rank and, when the caller threads a step
    counter in via ``seed``, per step) keeps it unbiased over time.
    Sum/Average only: quantization commutes with neither min/max nor
    product.

    ``return_residual=True`` additionally returns this rank's stage-1
    quantization error (``local − dequant(quant(local))``, same shape
    as ``tensor``) — the carry for error-feedback compression
    (DistributedOptimizer(error_feedback=True)): adding it to the NEXT
    step's gradient keeps the cumulative transmitted signal within a
    constant number of quanta of the true sum instead of a random walk.

    ``prescale_factor`` is FOLDED INTO the stage-1 wire scales rather
    than multiplied through the tensor: quantization is scale-invariant
    (``q = round(x/absmax(x)·127)`` is unchanged by ``x → c·x`` for
    ``c > 0``), so scaling the per-chunk wire scale — n floats — after
    the fact is bit-identical to pre-multiplying the payload, minus one
    full HBM read-write pass over the tensor. The residual stays in
    INPUT (unscaled) units: add it to the next step's raw tensor.

    ``block_size`` switches both stages to block-wise scales (one per
    ``block_size`` elements within each chunk — the wire format of
    ``Compression.int8_block`` and the fused path), so mixed-magnitude
    regions never share a dynamic range; ``None`` keeps the per-chunk
    scale of ``Compression.int8``. The block branch intentionally
    mirrors ``fusion.FusionManager._core_allreduce_q`` (same numeric
    contracts, minus its mask/pset/hier machinery) — a residual-
    contract change must land in both; the fused-vs-unfused parity
    tests are the tripwire.
    """
    _stall_check()
    from .pallas_kernels import int8_quantize

    op = resolve_op(op, None)
    if op not in (Average, Sum):
        raise ValueError("quantized_allreduce supports Sum/Average only")
    if groups is not None:
        # group-limited wire (the local-SGD local phase: int8 that
        # never leaves the slice): the two-stage grouped recipe with
        # the SAME residual contracts as the flat path below —
        # prescale folded into the wire scales, Average's stage-2
        # error surfaced ×n, carry in input units
        gn = len(groups[0])
        shape, dtype = tensor.shape, tensor.dtype
        flat = tensor.reshape(-1).astype(jnp.float32)
        if prescale_factor != 1.0:
            # the grouped core has no scale-fold hook; at group sizes
            # the pre-multiply is one fused producer op, not a
            # separate HBM pass worth optimizing around
            flat = flat * jnp.asarray(prescale_factor, jnp.float32)
        gidx = lax.axis_index(axis_name)
        gkey = jax.random.fold_in(jax.random.PRNGKey(seed), gidx)
        gblock = int(block_size) if block_size else max(
            -(-flat.shape[0] // gn), 1
        )
        out, res = _quantized_sum_groups(
            flat, axis_name, [list(g) for g in groups], gn, gblock,
            gkey, want_residual=return_residual,
        )
        if op == Average:
            out = out / jnp.asarray(gn, out.dtype)
        out = out.reshape(shape).astype(dtype)
        if not return_residual:
            return out
        if prescale_factor == 0.0:
            return out, jnp.zeros(shape, dtype)
        if prescale_factor != 1.0:
            res = res / jnp.asarray(prescale_factor, res.dtype)
        return out, res.reshape(shape).astype(dtype)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    shape, dtype = tensor.shape, tensor.dtype
    flat = tensor.reshape(-1).astype(jnp.float32)
    m = flat.shape[0]
    chunk = -(-m // n)  # ceil
    flat = jnp.pad(flat, (0, chunk * n - m))
    chunks = flat.reshape(n, chunk)  # row j is destined for rank j

    key = jax.random.fold_in(jax.random.PRNGKey(seed), idx)
    prescale = jnp.asarray(prescale_factor, jnp.float32)
    if block_size:
        q, scales = _stochastic_round_blocks(chunks, block_size, key)
        wire_scales = scales * prescale if prescale_factor != 1.0 else scales
        # all_to_all = the scatter half of reduce-scatter: afterwards
        # row r holds the chunk rank r quantized for us, with its scales
        recv = clax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)              # [n, nb, block]
        recv_scales = clax.all_to_all(
            wire_scales, axis_name, split_axis=0, concat_axis=0,
            tiled=True,
        )                                               # [n, nb]
        shard = jnp.sum(_block_dequant(recv, recv_scales), axis=0)  # [cpad]
        if op == Average:
            shard = shard / jnp.asarray(n, shard.dtype)
        q2, s2 = _stochastic_round_blocks(
            shard[None], block_size, jax.random.fold_in(key, 7919)
        )
        all_q = clax.all_gather(q2[0], axis_name)   # [n, nb, block]
        all_s = clax.all_gather(s2[0], axis_name)   # [n, nb]
        out = _block_dequant(all_q, all_s)[:, :chunk].reshape(-1)[:m]
        dequant_local = _block_dequant(q, scales)[:, :chunk]
        e2 = (shard - _block_dequant(q2, s2)[0])[:chunk]
    else:
        q, scales = _stochastic_round_rows(chunks, key)
        wire_scales = (
            scales * prescale if prescale_factor != 1.0 else scales
        )
        # all_to_all = the scatter half of reduce-scatter: afterwards
        # row r holds the chunk rank r quantized for us, with its scale.
        recv = clax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)
        recv_scales = clax.all_to_all(
            wire_scales.reshape(n, 1), axis_name, split_axis=0,
            concat_axis=0, tiled=True,
        ).reshape(n)
        shard = jnp.sum(
            recv.astype(jnp.float32) * recv_scales[:, None], axis=0
        )
        if op == Average:
            shard = shard / jnp.asarray(n, shard.dtype)
        # Second stage: per-tensor Pallas quantizer on the reduced
        # shard, decorrelated from stage one and from other ranks.
        q2, s2 = int8_quantize(shard, seed=seed * 2 + 1 + idx * 7919)
        all_q = clax.all_gather(q2, axis_name)    # [n, chunk] int8
        all_s = clax.all_gather(s2, axis_name)    # [n] f32
        out = (all_q.astype(jnp.float32) * all_s[:, None]).reshape(-1)[:m]
        dequant_local = q.astype(jnp.float32) * scales[:, None]
        e2 = shard - q2.astype(jnp.float32) * s2
    out = out.reshape(shape).astype(dtype)
    if not return_residual:
        return out
    if prescale_factor == 0.0:
        # a zero prescale transmits nothing, so no input correction
        # could ever surface in the output — the carry is zero (the
        # two-pass form's behavior: zeroed chunks quantize to zeros),
        # and dividing e2 by the factor would manufacture NaNs
        return out, jnp.zeros(shape, dtype)
    # Error-feedback carry, BOTH stages, in input units:
    # * stage 1: this rank's local quantization error, elementwise —
    #   against the UNSCALED scales, since the output responds to an
    #   input correction through the folded prescale already;
    # * stage 2: the reduced-shard quantization error of the chunk this
    #   rank owns — adding it to our next-step contribution restores it
    #   in everyone's output (x n under Average, which divides by n;
    #   / prescale, which the input correction will be re-multiplied by).
    res_flat = (chunks - dequant_local).reshape(-1)
    if op == Average:
        e2 = e2 * jnp.asarray(n, jnp.float32)
    if prescale_factor != 1.0:
        e2 = e2 / jnp.asarray(prescale_factor, e2.dtype)
    res_flat = jax.lax.dynamic_update_slice(
        res_flat,
        jax.lax.dynamic_slice(res_flat, (idx * chunk,), (chunk,)) + e2,
        (idx * chunk,),
    )
    residual = res_flat[:m].reshape(shape).astype(dtype)
    return out, residual


def quantized_reducescatter(
    panes,
    op=None,
    axis_name: str = WORLD_AXIS,
    seed=0,
    block_size: Optional[int] = None,
    return_residual: bool = False,
    groups=None,
):
    """Single-stage quantized reduce-scatter of a ``[n, cols]`` pane
    buffer (row ``j`` destined for rank ``j`` — the ``psum_scatter``
    layout the sharded optimizer's bucket panes already use): each rank
    block-quantizes its rows to int8 with stochastic rounding, an
    ``all_to_all`` moves int8 + scales, and the destination dequantizes
    and sums in fp32 — the scatter half of :func:`quantized_allreduce`
    with NO second quantization stage, so the error bound is ONE
    quantum per element (vs two for the full quantized allreduce).

    Pad exclusion by construction: pane pad entries are zeros
    (``parallel.fsdp.pad_to`` contract), zeros quantize to zeros and
    never raise a block's absmax, so a padded pane's block scales equal
    the unpadded pane's and pad positions carry zero residual —
    asserted in tests/test_zero.py.

    Returns the fp32 ``[cols]`` shard. ``return_residual=True``
    additionally returns this rank's local quantization error
    (``panes − dequant(quant(panes))``, input units, ``[n, cols]``) —
    the error-feedback carry: add it to the NEXT step's panes before
    quantizing. Input-unit carry needs no Average rescale: the error
    enters the output pre-division, so a +res input correction restores
    exactly what the quantization cost. Sum/Average only.
    """
    _stall_check()
    op = resolve_op(op, None)
    if op not in (Average, Sum):
        raise ValueError("quantized_reducescatter supports Sum/Average only")
    n = len(groups[0]) if groups is not None else lax.axis_size(axis_name)
    if panes.ndim != 2 or panes.shape[0] != n:
        raise ValueError(
            f"panes must be [world={n}, cols], got {panes.shape}"
        )
    cols = panes.shape[1]
    idx = lax.axis_index(axis_name)
    x = panes.astype(jnp.float32)
    block = int(block_size) if block_size else max(cols, 1)
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
    key = jax.random.fold_in(key, idx)
    q, scales = _stochastic_round_blocks(x, block, key)  # [n, nb, block]
    recv = clax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                          tiled=True, axis_index_groups=groups)
    recv_s = clax.all_to_all(scales, axis_name, split_axis=0,
                            concat_axis=0, tiled=True,
                            axis_index_groups=groups)
    shard = jnp.sum(_block_dequant(recv, recv_s), axis=0)[:cols]
    if op == Average:
        shard = shard / jnp.asarray(n, shard.dtype)
    if not return_residual:
        return shard
    residual = x - _block_dequant(q, scales)[:, :cols]
    return shard, residual


def quantized_allgather(
    shard,
    axis_name: str = WORLD_AXIS,
    seed=0,
    block_size: Optional[int] = None,
    return_residual: bool = False,
    groups=None,
):
    """Quantized all-gather of a per-rank ``[cols]`` shard: block-scaled
    int8 with stochastic rounding on the wire, one quantization stage.
    EVERY rank — the shard's owner included — consumes the dequantized
    wire value, so a gathered parameter-update stays bit-identical
    across replicas (the Horovod replica-consistency contract) at the
    cost of one quantum of update error, which the error-feedback carry
    (``return_residual=True``: ``shard − dequant(quant(shard))``, input
    units, ``[cols]``) cancels cumulatively. Same pad-exclusion-by-
    construction contract as :func:`quantized_reducescatter`.

    Returns the fp32 ``[n, cols]`` gather (row ``r`` = rank r's shard).
    """
    _stall_check()
    n = len(groups[0]) if groups is not None else lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    x = shard.reshape(1, -1).astype(jnp.float32)
    cols = x.shape[1]
    block = int(block_size) if block_size else max(cols, 1)
    key = jax.random.fold_in(jax.random.PRNGKey(1), seed)
    key = jax.random.fold_in(key, idx)
    q, s = _stochastic_round_blocks(x, block, key)  # [1, nb, block]
    all_q = clax.all_gather(
        q[0], axis_name, axis_index_groups=groups
    )  # [n, nb, block]
    all_s = clax.all_gather(
        s[0], axis_name, axis_index_groups=groups
    )  # [n, nb]
    out = _block_dequant(all_q, all_s)[:, :cols]
    if not return_residual:
        return out
    residual = (x - _block_dequant(q, s)[:, :cols])[0]
    return out, residual


def quantized_alltoall(
    tensor,
    axis_name: str = WORLD_AXIS,
    seed=0,
    block_size: Optional[int] = None,
    groups=None,
):
    """Block-scaled int8 alltoall of a ``[n, slots, d]`` dispatch
    buffer (row ``j`` destined for rank ``j`` — the MoE expert-dispatch
    layout of ``parallel/moe.py``): each (destination, slot) row is
    quantized to int8 with one absmax scale per ``block_size`` elements
    of ``d`` and stochastic rounding, an ``all_to_all`` moves int8 +
    scales, and the receiver dequantizes to fp32 — the quantized-MoE
    wire EQuARX motivates (PAPERS.md, arXiv 2506.17615), ~4x fewer
    bytes than the fp32 dispatch at one quantum of error per element.

    Pad exclusion by construction: empty dispatch slots (tokens dropped
    by the capacity gate, slots past a destination's fill) are all-zero
    rows — ``moe.py`` scatters into a zero-initialized buffer and
    carries a ``-1`` expert sentinel per slot — and zeros quantize to
    zeros without ever raising a block's absmax, so a pad slot
    contributes nothing to any scale and arrives as exact zeros.

    ``groups`` restricts the exchange to ``axis_index_groups`` of the
    flat axis (the inter hop of :func:`hierarchical_alltoall`); then
    ``n`` is the group size. Returns fp32 ``[n, slots, d]``.
    """
    _stall_check()
    n = len(groups[0]) if groups is not None else lax.axis_size(axis_name)
    if tensor.ndim != 3 or tensor.shape[0] != n:
        raise ValueError(
            f"dispatch buffer must be [n={n}, slots, d], "
            f"got {tensor.shape}"
        )
    _, slots, d = tensor.shape
    idx = lax.axis_index(axis_name)
    x = tensor.reshape(n * slots, d).astype(jnp.float32)
    # clamp to the row width: a block wider than d would zero-pad every
    # row up to it and the "quantized" wire would move MORE bytes than
    # fp32 (516 vs 256 B/row at d=64 under the default block 512)
    block = min(int(block_size), d) if block_size else max(d, 1)
    block = max(block, 1)
    key = jax.random.fold_in(jax.random.PRNGKey(2), seed)
    key = jax.random.fold_in(key, idx)
    q, scales = _stochastic_round_blocks(x, block, key)
    nb = scales.shape[1]
    recv = clax.all_to_all(
        q.reshape(n, slots, nb, block), axis_name,
        split_axis=0, concat_axis=0, tiled=True, axis_index_groups=groups,
    )
    recv_s = clax.all_to_all(
        scales.reshape(n, slots, nb), axis_name,
        split_axis=0, concat_axis=0, tiled=True, axis_index_groups=groups,
    )
    out = _block_dequant(
        recv.reshape(n * slots, nb, block), recv_s.reshape(n * slots, nb)
    )[:, :d]
    return out.reshape(n, slots, d)


def hierarchical_alltoall(
    tensor,
    axis_name: str = WORLD_AXIS,
    stages=None,
    intra_wire: str = "fp32",
    inter_wire: str = "fp32",
    seed=0,
    block_size: Optional[int] = None,
):
    """Two-level alltoall of a ``[n, slots, d]`` dispatch buffer on the
    FLAT axis (replica groups — ``topology.hierarchy_stages()``),
    elementwise equal to the flat ``lax.all_to_all`` for exact wires:

    1. **inter hop** (DCN): same-position ranks across slices exchange
       whole per-destination-slice sub-buffers — only blocks bound for
       ANOTHER slice cross the wire. ``inter_wire='int8'`` rides
       :func:`quantized_alltoall`; either lossy wire (bf16/int8)
       restores the SELF-slice block from the local fp32 original
       afterwards, so tokens bound for intra-slice experts never pay
       quantization — the PR 10 placement rule (EQuARX: quantize only
       where bytes are scarce) applied to expert dispatch.
    2. **intra hop** (ICI): one alltoall inside each slice delivers
       every block to its destination rank, at ``intra_wire``
       (fp32/bf16 — never int8; ICI is fast).

    The lowered module carries the two-level structure — group-limited
    ``all_to_all`` ops only, never a monolithic world-spanning one
    (tests/bench assert the replica-group text). Non-float payloads
    (the MoE expert-index map) ride both hops unmodified; pass exact
    wires for them. Requires the canonical contiguous-intra ``stages``
    layout. Returns the input dtype (int8 inter returns fp32-rounded
    values cast back).
    """
    if stages is None:
        raise ValueError("stages is required (topology.hierarchy_stages)")
    intra_groups, inter_groups = stages
    L = len(intra_groups[0])
    H = len(inter_groups[0])
    n = L * H
    if tensor.ndim != 3 or tensor.shape[0] != n:
        raise ValueError(
            f"dispatch buffer must be [n={n}, slots, d], "
            f"got {tensor.shape}"
        )
    _, slots, d = tensor.shape
    dtype = tensor.dtype
    lossy = inter_wire in ("bf16", "int8")
    exact = not jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
    idx = lax.axis_index(axis_name)
    # destination blocks, slice-major: xr[h_d] = the [L·slots, d] of
    # everything this rank sends to slice h_d
    xr = tensor.reshape(H, L * slots, d)
    if inter_wire == "int8" and not exact:
        y = quantized_alltoall(
            xr, axis_name=axis_name, seed=seed, block_size=block_size,
            groups=inter_groups,
        ).astype(dtype)
    else:
        wire = "fp32" if exact else inter_wire
        y = clax.all_to_all(
            _stage_cast(xr, wire), axis_name,
            split_axis=0, concat_axis=0, tiled=True,
            axis_index_groups=inter_groups,
        ).astype(dtype)
    if lossy and not exact:
        # the self-slice block never crossed DCN: row h (this rank's
        # position within its inter group) is its own block — restore
        # the fp32 original so intra-bound tokens stay exact
        pos = jnp.asarray(_group_pos_table(inter_groups))[idx]
        own = lax.dynamic_slice_in_dim(xr, pos, 1, axis=0).astype(dtype)
        y = lax.dynamic_update_slice_in_dim(y, own, pos, axis=0)
    # y[h_s] = blocks from (h_s, l_self) for every (h_self, l_d);
    # regroup by destination intra position and deliver inside the slice
    y = y.reshape(H, L, slots, d).transpose(1, 0, 2, 3)  # [L_d, H_s, ...]
    iw = "fp32" if exact else intra_wire
    z = clax.all_to_all(
        _stage_cast(y.reshape(L, H * slots, d), iw), axis_name,
        split_axis=0, concat_axis=0, tiled=True,
        axis_index_groups=intra_groups,
    ).astype(dtype)
    # z[l_s] = blocks from (h_s, l_s) — back to flat rank-major order
    return (
        z.reshape(L, H, slots, d).transpose(1, 0, 2, 3).reshape(
            n, slots, d
        )
    )


# Axis names for the two-level mesh built by hierarchical_mesh()
# (canonical home: common/topology.py — re-bound here for the existing
# import surface).
from ..common.topology import INTRA_AXIS, INTER_AXIS  # noqa: E402,F401


# ------------------------------------------------------------------
# Two-level recipe family ON THE FLAT AXIS (replica groups).
#
# The two-axis forms below (hierarchical_allreduce & co over a
# hierarchical_mesh) prove the dataflow; these group-flavored forms are
# what the DEFAULT wire actually routes through — the fused dispatcher,
# the overlap buckets and the ZeRO legs all trace over the flat "hvd"
# axis, where the slice boundary is expressible only as
# axis_index_groups (common/topology.py hierarchy_stages). Every recipe
# is the same three-hop shape: intra reduce-scatter -> inter collective
# on the 1/L shard -> intra all-gather, each hop with its own wire
# format; zero-pad never reaches a block scale or residual (zeros
# quantize to zeros and never raise an absmax — the standing pad
# contract).
# ------------------------------------------------------------------


def _stage_cast(x, wire):
    """Cast a buffer onto one hop's wire: bf16 halves the bytes (XLA
    fuses the cast into the collective's producer/consumer); fp32 /
    payload width is the identity."""
    return x.astype(jnp.bfloat16) if wire == "bf16" else x


def _group_pos_table(groups):
    """Static [world] int32 table: each rank's index within its group
    (chunk ownership for the grouped quantized recipes)."""
    from ..common.topology import stage_positions

    return stage_positions(groups)


def _quantized_sum_groups(
    row, axis_name, groups, n, block, key, pos=None, want_residual=False,
):
    """The two-stage block-scaled int8 allreduce recipe of
    :func:`quantized_allreduce`, over ``axis_index_groups`` of the flat
    axis (``groups=None`` = the whole axis): chunk the row across the
    ``n`` group members, stochastic-round to int8, all_to_all int8 +
    scales, dequant-sum, re-round the reduced chunk, all_gather.
    SUM semantics (callers divide for Average). Returns ``(out, res)``
    with ``res`` the sum-level input-unit EF carry (both stages, the
    quantized_allreduce contract) or None."""
    m = row.shape[0]
    chunk = -(-m // n)
    flat = jnp.pad(row, (0, chunk * n - m)) if chunk * n != m else row
    chunks = flat.reshape(n, chunk)
    q, scales = _stochastic_round_blocks(chunks, block, key)
    recv = clax.all_to_all(
        q, axis_name, split_axis=0, concat_axis=0, tiled=True,
        axis_index_groups=groups,
    )
    recv_s = clax.all_to_all(
        scales, axis_name, split_axis=0, concat_axis=0, tiled=True,
        axis_index_groups=groups,
    )
    shard = jnp.sum(_block_dequant(recv, recv_s), axis=0)  # [chunk]
    q2, s2 = _stochastic_round_blocks(
        shard[None], block, jax.random.fold_in(key, 7919)
    )
    all_q = clax.all_gather(q2[0], axis_name, axis_index_groups=groups)
    all_s = clax.all_gather(s2[0], axis_name, axis_index_groups=groups)
    out = _block_dequant(all_q, all_s)[:, :chunk].reshape(-1)[:m]
    if not want_residual:
        return out, None
    # which chunk this rank owns = its position within its group
    if pos is None:
        idx = lax.axis_index(axis_name)
        p = (
            jnp.asarray(_group_pos_table(groups))[idx]
            if groups is not None
            else idx
        )
    else:
        p = pos
    res_flat = (chunks - _block_dequant(q, scales)[:, :chunk]).reshape(-1)
    # e2 stays UN-scaled even when the caller averages afterwards:
    # this recipe quantizes the SUM shard (the /n happens outside), so
    # the stage-2 error and an input correction both reach the output
    # through the same later divide — unlike the flat path, which
    # divides BEFORE stage 2 and therefore multiplies its e2 by n
    e2 = (shard - _block_dequant(q2, s2)[0])[:chunk]
    res_flat = lax.dynamic_update_slice(
        res_flat,
        lax.dynamic_slice(res_flat, (p * chunk,), (chunk,)) + e2,
        (p * chunk,),
    )
    return out, res_flat[:m]


def hierarchical_allreduce_groups(
    tensor,
    op=None,
    axis_name: str = WORLD_AXIS,
    stages=None,
    intra_wire: str = "fp32",
    inter_wire: str = "fp32",
    seed=0,
    block_size: Optional[int] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    return_residual: bool = False,
):
    """Two-level allreduce on the FLAT axis: intra reduce-scatter ->
    inter collective on the 1/L shard -> intra all-gather, via the
    replica groups in ``stages`` (``topology.hierarchy_stages()``).
    This is the recipe the fused dispatcher, the overlap buckets and
    the hier_int8 optimizer path ride when an inter axis is present:
    the slow cross-slice hop carries 1/L of the bytes — times another
    ~4x when ``inter_wire='int8'`` (EQuARX's placement: quantize only
    where bytes are scarce).

    ``intra_wire`` ∈ {fp32, bf16} applies to BOTH intra hops;
    ``inter_wire`` ∈ {fp32, bf16, int8}. With everything at fp32 the
    result is the exact two-level sum (bit-exact vs flat for payloads
    whose partial sums are exactly representable — integer-valued
    grids; a few ulp of reassociation otherwise, see docs/perf.md).
    Sum/Average only.

    ``return_residual`` (int8 inter only): the inter-stage EF carry in
    INPUT units — the shard residual re-broadcast over the intra
    groups divided by L, so adding it to the NEXT step's tensor makes
    the intra reduce-scatter reconstruct exactly one copy at the shard
    owner (``hierarchical_quantized_allreduce``'s contract, group
    edition)."""
    op = resolve_op(op, None)
    if op not in (Average, Sum):
        raise ValueError(
            "hierarchical_allreduce_groups supports Sum/Average only"
        )
    if stages is None:
        raise ValueError("stages is required (topology.hierarchy_stages)")
    if return_residual and inter_wire != "int8":
        raise ValueError(
            "return_residual needs inter_wire='int8' (exact hops have "
            "no residual to carry)"
        )
    intra_groups, inter_groups = stages
    L = len(intra_groups[0])
    H = len(inter_groups[0])
    n = L * H
    shape, dtype = tensor.shape, tensor.dtype
    flat = tensor.reshape(-1)
    if inter_wire == "int8":
        flat = flat.astype(jnp.float32)
    m = flat.shape[0]
    pad = (-m) % L
    if pad:
        flat = jnp.pad(flat, (0, pad))
    if prescale_factor != 1.0:
        flat = flat * jnp.asarray(prescale_factor, flat.dtype)
    shard = clax.psum_scatter(
        _stage_cast(flat, intra_wire), axis_name,
        scatter_dimension=0, tiled=True, axis_index_groups=intra_groups,
    ).astype(flat.dtype)
    residual = None
    if inter_wire == "int8":
        idx = lax.axis_index(axis_name)
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
        key = jax.random.fold_in(key, idx)
        block = int(block_size) if block_size else max(shard.shape[0], 1)
        pos = jnp.asarray(_group_pos_table(inter_groups))[idx]
        red, res = _quantized_sum_groups(
            shard, axis_name, inter_groups, H, block, key, pos=pos,
            want_residual=return_residual,
        )
        if res is not None:
            if prescale_factor == 0.0:
                # nothing was transmitted: zero carry (the
                # quantized_allreduce contract), not 0/0 NaNs
                res = jnp.zeros_like(res)
            elif prescale_factor != 1.0:
                # back to INPUT units: the correction will be
                # re-multiplied by the prescale on its way in
                res = res / jnp.asarray(prescale_factor, res.dtype)
            residual = clax.all_gather(
                res / jnp.asarray(L, res.dtype), axis_name,
                tiled=True, axis_index_groups=intra_groups,
            )[:m]
    else:
        red = clax.psum(
            _stage_cast(shard, inter_wire), axis_name,
            axis_index_groups=inter_groups,
        ).astype(shard.dtype)
    out = clax.all_gather(
        _stage_cast(red, intra_wire), axis_name,
        tiled=True, axis_index_groups=intra_groups,
    ).astype(flat.dtype)
    if op == Average:
        out = out / jnp.asarray(n, out.dtype)
    if postscale_factor != 1.0:
        out = out * jnp.asarray(postscale_factor, out.dtype)
    out = out[:m].reshape(shape).astype(dtype)
    if not return_residual:
        return out
    residual = (
        jnp.zeros(shape, dtype)
        if residual is None
        else residual[:m].reshape(shape).astype(dtype)
    )
    return out, residual


def hierarchical_reducescatter(
    panes,
    op=None,
    axis_name: str = WORLD_AXIS,
    stages=None,
    intra_wire: str = "fp32",
    inter_wire: str = "fp32",
    seed=0,
    block_size: Optional[int] = None,
):
    """Two-level reduce-scatter of a ``[n, cols]`` pane buffer (row j
    destined for flat rank j — the ZeRO bucket layout): intra
    reduce-scatter of the destination rows that share this rank's
    slice-local slot -> inter collective on the 1/L-sized ``[H, cols]``
    panes -> this rank's ``[cols]`` shard. The DCN hop moves 1/L of the
    flat reduce-scatter's bytes (int8 inter: ~4x less again).
    Elementwise identical to the flat scatter for exact wires (each
    output element is the same set of addends, summed intra-then-inter).
    Requires the canonical ``stages`` layout (contiguous intra groups —
    ``topology.hierarchy_stages``). Sum/Average only."""
    op = resolve_op(op, None)
    if op not in (Average, Sum):
        raise ValueError(
            "hierarchical_reducescatter supports Sum/Average only"
        )
    if stages is None:
        raise ValueError("stages is required (topology.hierarchy_stages)")
    intra_groups, inter_groups = stages
    L = len(intra_groups[0])
    H = len(inter_groups[0])
    n = L * H
    if panes.ndim != 2 or panes.shape[0] != n:
        raise ValueError(
            f"panes must be [world={n}, cols], got {panes.shape}"
        )
    cols = panes.shape[1]
    dtype = panes.dtype
    buf = panes.reshape(H, L, cols)
    s1 = clax.psum_scatter(
        _stage_cast(buf, intra_wire), axis_name,
        scatter_dimension=1, tiled=True, axis_index_groups=intra_groups,
    ).astype(dtype).reshape(H, cols)
    if inter_wire == "int8":
        shard = quantized_reducescatter(
            s1.astype(jnp.float32), op=Sum, axis_name=axis_name,
            seed=seed, block_size=block_size, groups=inter_groups,
        ).astype(dtype)
    else:
        shard = clax.psum_scatter(
            _stage_cast(s1, inter_wire), axis_name,
            scatter_dimension=0, tiled=True,
            axis_index_groups=inter_groups,
        ).astype(dtype).reshape(cols)
    if op == Average:
        shard = shard / jnp.asarray(n, shard.dtype)
    return shard.reshape(cols)


def hierarchical_allgather(
    shard,
    axis_name: str = WORLD_AXIS,
    stages=None,
    intra_wire: str = "fp32",
    inter_wire: str = "fp32",
    seed=0,
    block_size: Optional[int] = None,
):
    """Two-level all-gather, the dual of
    :func:`hierarchical_reducescatter`: each rank's ``[cols]`` shard ->
    inter all-gather among same-slot peers (1/L of the DCN bytes of a
    flat gather; int8 inter rides
    :func:`quantized_allgather`'s one-stage wire, every rank — owners
    included — consuming the dequantized value so replicas stay
    bit-identical) -> intra all-gather + static reorder back to flat
    rank-major ``[n, cols]``."""
    if stages is None:
        raise ValueError("stages is required (topology.hierarchy_stages)")
    intra_groups, inter_groups = stages
    L = len(intra_groups[0])
    H = len(inter_groups[0])
    n = L * H
    cols = shard.shape[0]
    dtype = shard.dtype
    if inter_wire == "int8":
        g1 = quantized_allgather(
            shard.astype(jnp.float32), axis_name=axis_name, seed=seed,
            block_size=block_size, groups=inter_groups,
        ).astype(dtype)  # [H, cols]
    else:
        g1 = clax.all_gather(
            _stage_cast(shard, inter_wire), axis_name,
            axis_index_groups=inter_groups,
        ).astype(dtype)  # [H, cols]
    g2 = clax.all_gather(
        _stage_cast(g1, intra_wire), axis_name,
        axis_index_groups=intra_groups,
    ).astype(dtype)  # [L, H, cols]
    return jnp.transpose(g2, (1, 0, 2)).reshape(n, cols)


def hierarchical_mesh(local_size: Optional[int] = None):
    """A 2-axis (inter, intra) mesh over the world devices — the TPU
    shape of the reference's node-hierarchy split (NCCL intra-node + MPI
    inter-node, HOROVOD_HIERARCHICAL_ALLREDUCE in nccl_operations.cc
    [V]): ``intra`` rides ICI within a host/slice, ``inter`` rides DCN
    across them. ``local_size`` defaults to the topology's chips-per-host.
    """
    import numpy as np
    from jax.sharding import Mesh

    from ..common import basics

    topo = basics.topology()
    devices = np.asarray(topo.devices)
    if local_size is None:
        # slice-boundary detection incl. the HOROVOD_INTRA_SIZE
        # override (common/topology.py); falls back to chips-per-host
        local_size = topo.intra_size
    if local_size < 1 or devices.size % local_size:
        raise ValueError(
            f"local_size {local_size} must divide world {devices.size}"
        )
    grid = devices.reshape(devices.size // local_size, local_size)
    return Mesh(grid, (INTER_AXIS, INTRA_AXIS))


def hierarchical_allreduce(
    tensor,
    op=None,
    intra_axis: str = INTRA_AXIS,
    inter_axis: str = INTER_AXIS,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
):
    """Two-level allreduce for use inside shard_map over a
    :func:`hierarchical_mesh`: reduce-scatter on the intra (ICI) axis,
    allreduce the 1/L-sized shards on the inter (DCN) axis, all-gather
    back on intra — the reference's exact hierarchical dataflow
    (ReduceScatter→MPI-allreduce→Allgather, nccl_operations.cc [V]),
    which keeps the slow cross-slice hop at 1/local_size of the bytes.

    The tensor is flattened and zero-padded to a multiple of the intra
    size internally; shape is restored on return. Sum/Average only (the
    decomposition relies on reduction associativity over partitions).
    """
    op = resolve_op(op, None)
    if op not in (Average, Sum):
        raise ValueError("hierarchical_allreduce supports Sum/Average only")
    out, _ = _two_level_allreduce(
        tensor, op, intra_axis, inter_axis,
        lambda shard: (clax.psum(shard, inter_axis), None),
        prescale=prescale_factor, postscale=postscale_factor,
    )
    return out


def _two_level_allreduce(
    tensor, op, intra_axis, inter_axis, inter_reduce,
    prescale=1.0, postscale=1.0,
):
    """Shared rs-intra → inter_reduce → ag-intra scaffolding
    (flatten/pad/unpad, Average divisor, scale factors) for
    :func:`hierarchical_allreduce` and its quantized composition —
    one copy of the dataflow, two inter-stage reducers.
    ``inter_reduce(shard) -> (reduced_shard, extra_or_None)``; a
    non-None extra (the EF residual) gets the output's dual transform:
    divided by the intra size, all-gathered, unpadded (see
    hierarchical_quantized_allreduce's carry semantics)."""
    intra_n = lax.axis_size(intra_axis)
    inter_n = lax.axis_size(inter_axis)
    shape, dtype = tensor.shape, tensor.dtype
    flat = tensor.reshape(-1)
    m = flat.shape[0]
    padded = -(-m // intra_n) * intra_n
    if padded != m:
        flat = jnp.pad(flat, (0, padded - m))
    if prescale != 1.0:
        flat = flat * jnp.asarray(prescale, flat.dtype)
    shard = clax.psum_scatter(
        flat, intra_axis, scatter_dimension=0, tiled=True
    )                                       # [padded/L], summed intra
    red, extra = inter_reduce(shard)        # cross-slice hop, 1/L bytes
    out = clax.all_gather(red, intra_axis, tiled=True)  # [padded]
    if op == Average:
        out = out / jnp.asarray(intra_n * inter_n, out.dtype)
    if postscale != 1.0:
        out = out * jnp.asarray(postscale, out.dtype)
    out = out[:m].reshape(shape).astype(dtype)
    if extra is None:
        return out, None
    extra_full = clax.all_gather(
        extra / jnp.asarray(intra_n, extra.dtype), intra_axis,
        tiled=True,
    )
    return out, extra_full[:m].reshape(shape).astype(dtype)


def hierarchical_quantized_allreduce(
    tensor,
    op=None,
    intra_axis: str = INTRA_AXIS,
    inter_axis: str = INTER_AXIS,
    seed=0,
    return_residual: bool = False,
):
    """Hierarchical allreduce with the int8 wire on the CROSS-SLICE hop
    only — EQuARX's placement insight (PAPERS.md, pattern reference)
    composed from this module's two primitives: ICI is fast, so the
    intra reduce-scatter and all-gather stay full-precision; DCN is
    the bottleneck, so the inter-slice allreduce of the 1/L-sized
    shards rides :func:`quantized_allreduce`'s two-stage int8 (~4x
    fewer bytes exactly where bytes are scarcest). Quantization error
    is confined to the inter stage — two stochastic roundings on
    intra-summed shards — so the error bound matches flat
    ``quantized_allreduce`` while the ICI legs contribute none.

    ``return_residual=True``: error-feedback carry in INPUT units.
    The inter-stage residual lives on each rank's intra-shard; it is
    re-broadcast over ``intra_axis`` divided by the intra size, so
    adding it to the NEXT step's tensor makes the intra
    reduce-scatter reconstruct exactly one copy at the shard owner
    (each intra member contributes res/L to the same segment). Use
    with ``DistributedOptimizer(error_feedback=True)`` semantics.
    Sum/Average only.
    """
    op = resolve_op(op, None)
    if op not in (Average, Sum):
        raise ValueError(
            "hierarchical_quantized_allreduce supports Sum/Average only"
        )

    # input-unit carry (the `extra` leg of the shared scaffold): the
    # error enters the output linearly through the final (sum-level)
    # value, so no Average rescale is needed — a +res correction at
    # the input restores the output by res/n, exactly cancelling the
    # -res/n the quantization cost it.
    def inter(shard):
        r = quantized_allreduce(
            shard, op=Sum, axis_name=inter_axis, seed=seed,
            return_residual=return_residual,
        )
        return r if return_residual else (r, None)

    out, residual = _two_level_allreduce(
        tensor, op, intra_axis, inter_axis, inter
    )
    if not return_residual:
        return out
    return out, residual
