"""Expert parallelism: switch-style MoE FFN over the 'ep' axis.

The reference ships only the building block — the alltoall collective
(SURVEY.md §2.6: "the alltoall collective is the EP building block;
reference ships the primitive only"). Here it becomes the real thing:
experts are sharded across the 'ep' mesh axis, tokens are routed top-1
(switch transformer style) with a fixed capacity per expert (static
shapes — XLA requirement), dispatched to their expert's chip with
`lax.all_to_all`, transformed, and returned by the inverse all_to_all.

The dispatch WIRE rides the same stack every other byte family got
(PR 12): ``wire=`` selects fp32 / bf16 / block-scaled int8 with
stochastic rounding (``ops/traced.py quantized_alltoall`` — dropped
and pad slots are all-zero rows with a ``-1`` expert sentinel, so they
are excluded from every block scale by construction), ``hier=`` routes
the exchange through the two-level (intra-ICI / inter-DCN) recipe of
``traced.hierarchical_alltoall`` — tokens bound for intra-slice
experts move bf16/fp32, only the DCN hop rides int8 (the PR 10
placement rule), and ``wire="auto"`` consults the shared WireTuner's
``("alltoall", payload-bucket, dtype, hop)`` keys at trace time.
Routing decisions are computed on fp32 logits BEFORE any wire cast,
so they are identical across wires — the lossy wire moves the same
tokens to the same experts, a few quanta noisier.

Per-device code for use inside shard_map.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name


class MoEParams(NamedTuple):
    router: jnp.ndarray  # [D, E_total]
    w1: jnp.ndarray  # [E_local, D, F]
    b1: jnp.ndarray  # [E_local, F]
    w2: jnp.ndarray  # [E_local, F, D]
    b2: jnp.ndarray  # [E_local, D]


class MoEStats(NamedTuple):
    """Per-step expert-load counters (global — psum'd over the axis),
    the feed for the capacity-factor autotuner (common/autotune.py
    CapacityTuner) and the per-rank expert-load summaries published
    through the rendezvous KV (elastic/worker.py publish_expert_load):
    hot experts ARE stragglers, and these are how the scheduler sees
    them."""

    expert_tokens: jnp.ndarray  # [E_total] f32 — kept tokens per expert
    dropped: jnp.ndarray  # scalar f32 — tokens past capacity (zero out)
    total: jnp.ndarray  # scalar f32 — live tokens routed


def init_moe_params(key, d_model: int, d_ff: int, n_experts_local: int,
                    n_experts_total: int, dtype=jnp.float32) -> MoEParams:
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = 1.0 / jnp.sqrt(d_model)
    s2 = 1.0 / jnp.sqrt(d_ff)
    return MoEParams(
        router=(jax.random.normal(k1, (d_model, n_experts_total)) * s1).astype(dtype),
        w1=(jax.random.normal(k2, (n_experts_local, d_model, d_ff)) * s1).astype(dtype),
        b1=jnp.zeros((n_experts_local, d_ff), dtype),
        w2=(jax.random.normal(k3, (n_experts_local, d_ff, d_model)) * s2).astype(dtype),
        b2=jnp.zeros((n_experts_local, d_model), dtype),
    )


def _resolve_hier(hier, ep: int):
    """The two-level routing decision for the expert wire: explicit
    ``(intra_groups, inter_groups)`` stages pass through; ``None``
    consults the HOROVOD_HIERARCHICAL default tri-state (the same
    decision the fused dispatcher and the overlap buckets ride);
    "on"/True force any resolvable split; "off"/False keep it flat."""
    from ..common import topology as _topo

    if hier is None:
        return _topo.hierarchy_stages(world=ep)
    if hier in ("off", False):
        return None
    if hier in ("on", True):
        return _topo.hierarchy_stages(world=ep, mode="on")
    return hier  # explicit stages


def _resolve_wire(wire, intra_wire, payload_bytes: int, hier):
    """Trace-time wire choice. ``auto`` asks the shared WireTuner's
    ``(alltoall, hop)`` key family — a compile-time decision like the
    OverlapTuner's bucket count: the step's own loop feeds goodput
    across recompiles, the choice is frozen into this trace."""
    from ..common import basics as _basics

    cfg = _basics.live_config()
    if wire is None:
        wire = cfg.moe_wire
    if intra_wire is None:
        intra_wire = cfg.moe_intra_wire
    if wire not in ("fp32", "bf16", "int8", "auto"):
        raise ValueError(
            f"moe wire must be fp32/bf16/int8/auto, got {wire!r}"
        )
    if intra_wire not in ("fp32", "bf16"):
        raise ValueError(
            f"moe intra_wire must be fp32/bf16, got {intra_wire!r}"
        )
    if wire == "auto":
        from ..common.autotune import shared_wire_tuner

        tuner = shared_wire_tuner()
        bucket = 1 << max(int(payload_bytes) - 1, 1).bit_length()
        if hier is not None:
            H = len(hier[1][0])
            wire = tuner.choose(
                ("alltoall", bucket, "float32", "inter"),
                payload_bytes=payload_bytes * (H - 1) // max(H, 1),
                itemsize=4,
            )
            intra_wire = tuner.choose(
                ("alltoall", bucket, "float32", "intra"),
                payload_bytes=payload_bytes,
                itemsize=4,
                candidates=("fp32", "bf16"),
            )
        else:
            wire = tuner.choose(
                ("alltoall", bucket, "float32", "flat"),
                payload_bytes=payload_bytes,
                itemsize=4,
            )
    return wire, intra_wire


def _cast_wire(x, wire):
    return x.astype(jnp.bfloat16) if wire == "bf16" else x


def moe_ffn(
    params: MoEParams,
    x,
    axis_name: str = "ep",
    capacity_factor: Optional[float] = None,
    wire: Optional[str] = None,
    intra_wire: Optional[str] = None,
    hier=None,
    seed: int = 0,
    block_size: Optional[int] = None,
    mask=None,
    process_set=None,
    return_stats: bool = False,
):
    """x: [T_local, D] tokens on this chip → [T_local, D].

    Routing: top-1 over E_total experts; expert e lives on chip
    e // E_local of the 'ep' axis. Tokens over capacity are dropped
    (switch-style; their output is zero and the residual connection
    carries them).

    ``capacity_factor`` (None = HOROVOD_MOE_CAPACITY_FACTOR) sizes the
    static per-destination buffer; for a measured choice drive the
    step harness through ``common.autotune.CapacityTuner`` — capacity
    is a compile-time shape, so tuning happens across recompiles.

    ``wire`` ∈ {fp32, bf16, int8, auto} (None = HOROVOD_MOE_WIRE) is
    the dispatch+return wire; with a two-level split (``hier``) it
    names the INTER hop and ``intra_wire`` ∈ {fp32, bf16} the ICI
    legs. The expert-index map always moves exact int32. ``seed``
    decorrelates the stochastic rounding (thread a step counter for
    unbiasedness over time).

    ``mask`` is the traced join mask ([world] bool, ``mask[r] ==
    False`` = rank r ran out of data): a masked rank contributes no
    tokens (its output rows are zeros) while its EXPERTS keep serving
    the live ranks. ``process_set`` restricts routing to the member
    ranks' experts (non-members return zeros; the wire degenerates to
    the flat masked/ring formulation — hier and int8 need the full
    axis). ``return_stats=True`` additionally returns :class:`MoEStats`.
    """
    from ..ops import traced as _traced
    from ..common import basics as _basics

    ep = lax.axis_size(axis_name)
    t_local, d = x.shape
    e_local = params.w1.shape[0]
    e_total = e_local * ep

    if capacity_factor is None:
        capacity_factor = _basics.live_config().moe_capacity_factor
    if block_size is None:
        block_size = _basics.live_config().moe_wire_block

    info = _traced._set_info(process_set, axis_name)
    member = None
    pos = None
    if info is not None:
        member, pos = _traced._member(info, axis_name)
    live = None
    if mask is not None:
        live = jnp.asarray(mask)[lax.axis_index(axis_name)]

    # participating-rank count and expert universe
    k = info.size if info is not None else ep
    hier_stages = None if info is not None else _resolve_hier(hier, ep)
    capacity = int(max(1, round(float(capacity_factor) * t_local / k)))
    payload_bytes = k * capacity * d * 4
    wire, intra_wire = _resolve_wire(
        wire, intra_wire, payload_bytes, hier_stages
    )
    if info is not None and wire == "int8":
        # the ring formulation moves raw blocks; quantized + pset is
        # not a supported combination — degrade loudly-documented
        wire = "fp32"

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params.router.astype(jnp.float32))
    if info is not None:
        # non-member ranks' experts are outside the set: route over
        # member experts only (set order = member rank order)
        owner = jnp.arange(e_total) // e_local  # [E_total] owning rank
        allowed = jnp.asarray(info.mask)[owner]
        logits = jnp.where(allowed[None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # [T]
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

    owner_rank = expert_idx // e_local  # [T] flat owning rank
    if info is not None:
        # position of the owning rank within the set = dispatch slot
        dest_chip = jnp.asarray(info.pos)[owner_rank]
    else:
        dest_chip = owner_rank

    # position of each token within its destination chip's buffer
    onehot_chip = jax.nn.one_hot(dest_chip, k, dtype=jnp.int32)  # [T, k]
    pos_in_chip = (jnp.cumsum(onehot_chip, axis=0) - 1)  # [T, k]
    my_pos = jnp.take_along_axis(
        pos_in_chip, dest_chip[:, None], axis=1
    )[:, 0]  # [T]
    keep = my_pos < capacity
    if member is not None:
        keep = jnp.logical_and(keep, member)
    if live is not None:
        keep = jnp.logical_and(keep, live)

    # Scatter tokens into the dispatch buffer [k, capacity, D]. Dropped
    # tokens get an out-of-range index → mode='drop' discards them, so
    # empty slots keep their init value (zeros in the payload, -1
    # sentinel in the expert map — the pad-exclusion contract of the
    # quantized wire).
    idx_chip = jnp.where(keep, dest_chip, k)
    idx_pos = jnp.where(keep, my_pos, 0)
    dispatch = (
        jnp.zeros((k, capacity, d), x.dtype)
        .at[idx_chip, idx_pos]
        .set(x, mode="drop")
    )
    token_expert = (
        jnp.full((k, capacity), -1, jnp.int32)
        .at[idx_chip, idx_pos]
        .set((expert_idx % e_local).astype(jnp.int32), mode="drop")
    )

    def _quantized_fwd(b3, step_seed):
        """The int8-bearing exchange of one [k, C, d] float buffer —
        wrapped in a custom_vjp below: stochastic rounding has no
        useful gradient (floor/compare are piecewise-flat, and the
        absmax scales would leak a spurious one), so the cotangent
        rides the EXACT inverse exchange instead — the alltoall's own
        transpose, straight-through on the quantizer. Training with
        the lossy wire therefore costs exactly one extra fp32 exchange
        in backward, never a poisoned gradient."""
        if hier_stages is not None:
            return _traced.hierarchical_alltoall(
                b3, axis_name=axis_name, stages=hier_stages,
                intra_wire=intra_wire, inter_wire=wire,
                seed=step_seed, block_size=block_size,
            )
        return _traced.quantized_alltoall(
            b3, axis_name=axis_name, seed=step_seed,
            block_size=block_size,
        ).astype(b3.dtype)

    @jax.custom_vjp
    def _st_exchange(b3, step_seed):
        return _quantized_fwd(b3, step_seed)

    def _st_fwd(b3, step_seed):
        return _quantized_fwd(b3, step_seed), None

    def _st_bwd(_, ct):
        # the exact exchange is its own transpose for the symmetric
        # [k, C, d] split0/concat0 layout (block (i, j) ↔ (j, i));
        # hierarchical-exact keeps the cotangent's DCN legs two-level
        if hier_stages is not None:
            back = _traced.hierarchical_alltoall(
                ct, axis_name=axis_name, stages=hier_stages
            )
        else:
            back = lax.all_to_all(
                ct, axis_name, split_axis=0, concat_axis=0, tiled=True
            )
        return back, None

    _st_exchange.defvjp(_st_fwd, _st_bwd)

    def exchange(buf, step_seed):
        """One dispatch-shaped hop of the expert wire ([k, C, ·])."""
        floaty = jnp.issubdtype(buf.dtype, jnp.floating)
        if info is not None:
            flat = buf.reshape(k * capacity, -1)
            if wire == "bf16" and floaty:
                flat = _cast_wire(flat, wire)
            out = _traced.alltoall(
                flat, process_set=process_set, axis_name=axis_name
            ).astype(buf.dtype)
            return out.reshape(buf.shape)
        b3 = buf.reshape(k, capacity, -1)
        if hier_stages is not None:
            if wire == "int8" and floaty:
                return _st_exchange(b3, step_seed).reshape(buf.shape)
            return _traced.hierarchical_alltoall(
                b3, axis_name=axis_name, stages=hier_stages,
                intra_wire=intra_wire if floaty else "fp32",
                inter_wire=wire if floaty else "fp32",
                seed=step_seed, block_size=block_size,
            ).reshape(buf.shape)
        if wire == "int8" and floaty:
            return _st_exchange(b3, step_seed).reshape(buf.shape)
        cast = _cast_wire(b3, wire) if floaty else b3
        return lax.all_to_all(
            cast, axis_name, split_axis=0, concat_axis=0, tiled=True
        ).astype(buf.dtype).reshape(buf.shape)

    # To each chip its tokens: [k, C, D] exchanged over the wire; the
    # expert map rides exact int32 alongside.
    recv = exchange(dispatch, seed)
    recv_expert = exchange(token_expert[..., None], seed)[..., 0]
    # recv: [k*C, D] tokens for MY local experts (concat over sources).
    recv = recv.reshape(k * capacity, d)
    which_expert = recv_expert.reshape(k * capacity)

    # Apply each local expert to its tokens (dense einsum over one-hot —
    # MXU-friendly, no gather/scatter in the hot loop).
    sel = jax.nn.one_hot(which_expert, e_local, dtype=recv.dtype)  # [N, E_l]
    h = jnp.einsum("nd,edf,ne->nf", recv, params.w1, sel)
    h = h + jnp.einsum("ef,ne->nf", params.b1, sel)
    h = jax.nn.gelu(h)
    y = jnp.einsum("nf,efd,ne->nd", h, params.w2, sel)
    y = y + jnp.einsum("ed,ne->nd", params.b2, sel)
    # tokens that carried expert=-1 (padding) produce zeros — which
    # also keeps pad slots out of the return wire's block scales
    y = y * (which_expert >= 0)[:, None]

    # Return to origin chips: inverse exchange over the same wire.
    y_back = exchange(
        y.reshape(k, capacity, d), seed + 0x9E37
    ).reshape(k, capacity, d)

    # Un-scatter: token i's result sits at [dest_chip[i], my_pos[i]].
    out = y_back[idx_chip, idx_pos]
    out = jnp.where(keep[:, None], out, 0.0)
    out = (out * gate[:, None]).astype(x.dtype)
    if member is not None:
        out = jnp.where(member, out, jnp.zeros_like(out))
    if live is not None:
        out = jnp.where(live, out, jnp.zeros_like(out))
    if not return_stats:
        return out

    # Expert-load counters, psum'd so every rank holds the global view
    # (the capacity tuner / KV publisher feed). ``total`` counts live
    # routed tokens; ``dropped`` the capacity-gate losses among them.
    routed = jnp.ones((t_local,), jnp.float32)
    if member is not None:
        routed = jnp.where(member, routed, 0.0)
    if live is not None:
        routed = jnp.where(live, routed, 0.0)
    kept = jnp.where(keep, routed, 0.0)
    hist = jnp.sum(
        jax.nn.one_hot(expert_idx, e_total, dtype=jnp.float32)
        * kept[:, None],
        axis=0,
    )
    stats = MoEStats(
        expert_tokens=lax.psum(hist, axis_name),
        dropped=lax.psum(jnp.sum(routed - kept), axis_name),
        total=lax.psum(jnp.sum(routed), axis_name),
    )
    return out, stats


# ---------------------------------------------------------------------------
# Top-k routing and one chip's held experts (models.transformer.ExpertFFN).
# The routing is the one implementation: an exchange across the 'ep' axis
# sends the same sorted rows to their experts' chips and calls the same
# grouped matmuls there. On one chip there is no exchange, and nothing
# stands in for it: the experts held elsewhere add nothing.
# ---------------------------------------------------------------------------


# The names a ``jax.checkpoint`` policy keeps the routing's integer results
# by (``save_only_these_names(*ROUTING_NAMES)``): a token's chosen experts
# and the dispatch's sorted order of the (token, choice) pairs, ``tokens x
# top_k`` int32 each, so that remat's second forward repeats neither
# ``top_k`` nor the sort. The group sizes and the routed count are a
# compare and a sum over the chosen experts, and are computed again. Under
# no policy the names cost nothing.
ROUTING_NAMES = ("moe_chosen", "moe_order")


def route_top_k(logits, select_bias, top_k: int, *, score: str = "sigmoid",
                norm: bool = True, scale: float = 1.0):
    """``(chosen [tokens, k] int32, gates [tokens, k] float32)`` from the
    router's float32 ``logits [tokens, experts]``. The scores are
    ``sigmoid`` or ``softmax`` of the logits; the k experts are chosen on
    score + ``select_bias`` (the load-balancing bias, which selection
    alone reads: no gradient reaches it; None: a router without one, the
    scores alone choose); the gates are the unbiased
    scores of the chosen, divided by their sum where ``norm``, times
    ``scale``."""
    logits = logits.astype(jnp.float32)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"score {score!r} is not sigmoid or softmax")
    ranked = scores
    if select_bias is not None:
        ranked = scores + lax.stop_gradient(select_bias)
    _, chosen = lax.top_k(ranked, top_k)
    chosen = checkpoint_name(chosen.astype(jnp.int32), "moe_chosen")
    # the chosen experts' scores, picked by comparison: the same values as
    # a gather along the experts, which on the chip costs 1 ms a layer for
    # 8 of 128 scores a token (PERF.md section 6, PR 30)
    picked = chosen[..., None] == jnp.arange(
        scores.shape[-1], dtype=chosen.dtype)
    gates = jnp.sum(jnp.where(picked, scores[..., None, :], 0), axis=-1)
    if norm:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return chosen, gates * scale


def _gmm_tiling(rows: int, k: int, n: int):
    """Tile sizes of the grouped matmul: the row tile must divide the
    rows; 512 x 1024 x 1024 stages ~10 MiB of VMEM in bfloat16."""
    tm = 512
    while rows % tm:
        tm //= 2
    if tm < 8 and jax.default_backend() == "tpu":
        raise ValueError(
            f"{rows} routed rows tile no 8-aligned block: tokens x top_k "
            "must be a multiple of 8"
        )
    return tm, min(k, 1024), min(n, 1024)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _grouped_matmul(rows, weights, group_sizes, transpose_rhs=False):
    """``rows [m, k]`` sorted by group times ``weights [groups, k, n]``
    (``[groups, n, k]`` where ``transpose_rhs``), the first
    ``group_sizes[g]`` rows with group 0's matrix and so on (Pallas
    megablox: only tiles that hold a group's rows are visited, so the work
    follows ``sum(group_sizes)``, not m). Rows past the last group are NOT
    written: the caller masks them."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = rows.shape
    n = weights.shape[1 if transpose_rhs else 2]
    return gmm(
        rows, weights, group_sizes, rows.dtype, _gmm_tiling(m, k, n),
        transpose_rhs=transpose_rhs, interpret=_interpret(),
    )


def _grouped_outer(rows, grads, group_sizes, dtype):
    """``rows[group]^T @ grads[group]`` for every group, ``[groups, k, n]``
    in ``dtype``, for ``rows [m, k]`` and ``grads [m, n]`` sorted by group:
    the weights' gradient of :func:`_grouped_matmul` (megablox ``tgmm``;
    rows past the last group are not read)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    (m, k), n = rows.shape, grads.shape[1]
    # the kernel stages a tile of the result twice; in float32 a tile is
    # twice a product's bytes: half the tile keeps the call in ~12 MiB
    tm, tk, tn = _gmm_tiling(m, k, n)
    return tgmm(
        rows.swapaxes(0, 1), grads, group_sizes, dtype,
        (tm, max(tk // 2, 1), tn), interpret=_interpret(),
    )


def window_chunk(rows: int) -> int:
    """Rows of the sorted (token, choice) pairs that a pass of the dispatch
    takes at a time, from the shape alone: a sixteenth of all ``tokens x
    top_k`` rows where that is a whole number of 8-row tiles (the grouped
    matmul's least), else all of them at once."""
    chunk = rows // 16
    return rows if rows % 16 or chunk % 8 else chunk


def _unwritten(rows: int, width: int, dtype, name: str, after):
    """A buffer ``[rows, width]`` that nothing has written: the result of
    a kernel without a body, so no fill runs over rows that no pass will
    produce or read. (Zeros on the CPU's interpreter.) The kernel is
    handed one element of ``after`` and reads nothing: the buffer then
    comes to be when ``after`` is there, not at the start of the step."""
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        lambda after, out: None,
        out_shape=jax.ShapeDtypeStruct((rows, width), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY), name=f"unwritten_{name}",
        interpret=_interpret(),
    )(after[(slice(0, 1),) * after.ndim])


def _over_window(fn, chunks, outs, *rows):
    """``outs`` with the rows of chunk ``i`` set to ``fn(first row of the
    chunk, *chunk i of rows)`` for every ``i < chunks`` (a traced count):
    one pass over a window of the sorted rows. ``rows`` and ``outs`` have
    one row a sorted pair; rows past the window stay as they were."""
    chunk = window_chunk(rows[0].shape[0])

    def one_chunk(i, outs):
        lo = i * chunk
        got = fn(lo, *(lax.dynamic_slice_in_dim(r, lo, chunk) for r in rows))
        return tuple(lax.dynamic_update_slice_in_dim(o, g, lo, 0)
                     for o, g in zip(outs, got))

    return lax.fori_loop(0, chunks, one_chunk, tuple(outs))


def _add_window_to_tokens(fn, chunks, like, *rows):
    """Float32 ``[tokens, d]``: the sum of the window's ``addends`` into
    the rows ``tokens`` that ``fn`` gives them: ``(tokens, addends) =
    fn(first row, *those rows of rows)``. A token's addends meet in
    float32. On the chip a scatter-add costs ~0.8 ms a call before its
    first row (PERF.md section 6, PR 30), so the window's chunks go four
    to a scatter-add in a loop, and the one to three that are left in one
    more, its size chosen by a ``switch``."""
    chunk = window_chunk(rows[0].shape[0])
    most = min(4, rows[0].shape[0] // chunk)

    def add(total, lo, m):
        tokens, addends = fn(lo, *(
            lax.dynamic_slice_in_dim(r, lo, m * chunk) for r in rows))
        return total.at[tokens].add(addends.astype(jnp.float32))

    total = lax.fori_loop(
        0, chunks // most, lambda i, total: add(total, i * most * chunk, most),
        jnp.zeros(like.shape, jnp.float32))
    lo = chunks // most * most * chunk
    return lax.switch(
        chunks % most,
        [lambda total: total] + [
            lambda total, m=m: add(total, lo, m) for m in range(1, most)],
        total)


def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


def _window(order, group_sizes):
    """``(routed, chunks)``: the sorted rows that are routed here (the
    others follow them) and the chunks of the sorted order that hold one."""
    chunk = window_chunk(order.shape[0])
    routed = jnp.sum(group_sizes)
    return routed, (routed + chunk - 1) // chunk


def _is_routed(lo, rows, routed):
    """``[len(rows), 1]``: whether the sorted row, ``lo`` and on, is routed
    here. A row past the routed count is in no group: no grouped matmul
    wrote it, and what is read there must not be used."""
    return (lo + jnp.arange(rows.shape[0]) < routed)[:, None]


@jax.custom_vjp
def _windowed_experts(x, gates, order, group_sizes, w_gate, w_up, w_down):
    """``(y [tokens, d], rows_window)``: the held experts' part of the
    layer's result from ``order``, the (token, choice) pairs sorted by held
    expert with ``group_sizes`` rows each and the pairs held elsewhere
    last, and the rows the passes took. The weights are cast to ``x``'s
    type for the products."""
    return _windowed_experts_fwd(
        x, gates, order, group_sizes, w_gate, w_up, w_down)[0]


# Both rules are jitted: every expert layer of a model has the same shapes,
# so the passes' loops are traced once and not once a layer (1.7 s of the
# cell's set-up where they are not, PERF.md section 6, PR 30).
@jax.jit
def _windowed_experts_fwd(x, gates, order, group_sizes, *weights):
    w_gate, w_up, w_down = (w.astype(x.dtype) for w in weights)
    pairs, top_k = order.shape[0], gates.shape[1]
    routed, chunks = _window(order, group_sizes)
    with jax.named_scope("moe_dispatch"):
        rows, = _over_window(
            lambda lo, pairs: (x[pairs // top_k],), chunks,
            [_unwritten(pairs, x.shape[1], x.dtype, "rows", order)], order)
    with jax.named_scope("moe_experts"):
        gate = _grouped_matmul(rows, w_gate, group_sizes)
        up = _grouped_matmul(rows, w_up, group_sizes)
    hidden, = _over_window(
        lambda lo, gate, up: (_swiglu(gate, up),), chunks,
        [_unwritten(pairs, gate.shape[1], x.dtype, "hidden", up)], gate, up)
    with jax.named_scope("moe_experts"):
        out = _grouped_matmul(hidden, w_down, group_sizes)

    def weighted(lo, pairs, out):
        weight = gates.reshape(-1)[pairs][:, None]
        return pairs // top_k, jnp.where(
            _is_routed(lo, pairs, routed),
            out.astype(jnp.float32) * weight, 0)

    with jax.named_scope("moe_combine"):
        y = _add_window_to_tokens(weighted, chunks, x, order, out)
        y = y.astype(x.dtype)
    return (y, chunks * window_chunk(pairs)), (
        x, gates, order, group_sizes, weights, rows, gate, up, hidden, out)


@jax.jit
def _windowed_experts_bwd(res, cotangents):
    """The transpose over the same window: the combine's is a gather of
    ``dy`` by token, the gather's a scatter-add into ``dx``; a token's
    rows meet in float32, and the weights' gradients leave the grouped
    kernel in float32 for whoever holds the weights in it."""
    x, gates, order, group_sizes, weights, rows, gate, up, hidden, out = res
    w_gate, w_up, w_down = (w.astype(x.dtype) for w in weights)
    dy, _ = cotangents
    pairs, top_k = order.shape[0], gates.shape[1]
    chunk = window_chunk(pairs)
    routed, chunks = _window(order, group_sizes)

    def combine_transposed(lo, pairs, out):
        dweighted = dy[pairs // top_k].astype(jnp.float32)
        dweight = jnp.sum(jnp.where(
            _is_routed(lo, pairs, routed),
            out.astype(jnp.float32) * dweighted, 0), axis=1)
        weight = gates.reshape(-1)[pairs][:, None]
        return (dweighted * weight).astype(x.dtype), dweight

    with jax.named_scope("moe_combine"):
        dout, dweights = _over_window(
            combine_transposed, chunks,
            [_unwritten(pairs, x.shape[1], x.dtype, "dout", dy),
             jnp.zeros((pairs,), jnp.float32)], order, out)

        def to_its_pair(i, dgates):
            at = lax.dynamic_slice_in_dim(order, i * chunk, chunk)
            return dgates.at[at].set(
                lax.dynamic_slice_in_dim(dweights, i * chunk, chunk),
                unique_indices=True)

        dgates = lax.fori_loop(
            0, chunks, to_its_pair, jnp.zeros((pairs,), jnp.float32)
        ).reshape(gates.shape)
    with jax.named_scope("moe_experts"):
        dhidden = _grouped_matmul(dout, w_down, group_sizes, True)
        dw_down = _grouped_outer(hidden, dout, group_sizes, jnp.float32)
    dgate, dup = _over_window(
        lambda lo, dhidden, gate, up: jax.vjp(_swiglu, gate, up)[1](dhidden),
        chunks,
        [_unwritten(pairs, gate.shape[1], x.dtype, name, dhidden)
         for name in ("dgate", "dup")], dhidden, gate, up)
    with jax.named_scope("moe_experts"):
        drows = _grouped_matmul(dgate, w_gate, group_sizes, True)
        drows_up = _grouped_matmul(dup, w_up, group_sizes, True)

        def add_up(i, drows):
            # the sum of the sorted rows' two cotangents, where the first is
            both = (lax.dynamic_slice_in_dim(r, i * chunk, chunk)
                    for r in (drows, drows_up))
            return lax.dynamic_update_slice_in_dim(
                drows, sum(both), i * chunk, 0)

        drows = lax.fori_loop(0, chunks, add_up, drows)
        dw_gate = _grouped_outer(rows, dgate, group_sizes, jnp.float32)
        dw_up = _grouped_outer(rows, dup, group_sizes, jnp.float32)
    with jax.named_scope("moe_dispatch"):
        dx = _add_window_to_tokens(
            lambda lo, pairs, drows: (
                pairs // top_k,
                jnp.where(_is_routed(lo, pairs, routed), drows, 0)),
            chunks, x, order, drows).astype(x.dtype)
    return (dx, dgates.astype(gates.dtype), None, None,
            *(dw.astype(w.dtype)
              for dw, w in zip((dw_gate, dw_up, dw_down), weights)))


_windowed_experts.defvjp(_windowed_experts_fwd, _windowed_experts_bwd)


def held_experts_ffn(x, chosen, gates, w_gate, w_up, w_down, first_held: int):
    """``(y, rows_routed, rows_window)``. ``y`` is the part of an expert
    layer's result that the experts held here give: ``sum over a token's
    chosen experts e in [first_held, first_held + held) of gates_e *
    W_down[e](silu(W_gate[e] x) * W_up[e] x)``, for ``x [tokens, d]``,
    ``chosen``/``gates [tokens, k]`` and weights ``[held, d, f]``,
    ``[held, d, f]``, ``[held, f, d]`` of any float type (the products
    run in ``x``'s).

    Dropless with static shapes: all ``tokens * k`` pairs are sorted by
    held expert (pairs whose expert is held elsewhere last), so every
    choice of every token may land here. The work follows the rows really
    routed here, ``rows_routed``: the grouped matmuls visit only the held
    groups' row tiles, and every other pass (the gather of ``x`` into
    sorted rows, the activation, the weighted add of the rows into their
    tokens' float32 sums, and their transposes) is a loop over
    ``ceil(rows_routed / chunk)`` chunks of ``window_chunk(tokens * k)``
    sorted rows. Rows past that window, ``rows_window`` rows long, are
    never produced or read: the buffers between the passes hold ``tokens *
    k`` rows and are written up to the window. Where every pair is held
    here the window is all of them."""
    held = w_gate.shape[0]
    with jax.named_scope("moe_dispatch"):
        local = chosen - first_held
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(-1)
        order = checkpoint_name(
            jnp.argsort(key, stable=True).astype(jnp.int32), "moe_order")
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(held, dtype=key.dtype), axis=0,
            dtype=jnp.int32,
        )
    y, rows_window = _windowed_experts(
        x, gates, order, group_sizes, w_gate, w_up, w_down)
    return y, jnp.sum(group_sizes), rows_window
