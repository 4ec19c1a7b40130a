"""Blockwise (flash) attention as Pallas TPU kernels, fwd + bwd.

The hot op of the LM benchmark family (BASELINE.json configs #3/#4 —
BERT-large, GPT-2 medium). The reference leans on cuDNN/torch SDPA for
this (its CUDA kernels live outside the framework, cuda_kernels.cu is
only scale/memcpy [V]); the TPU-native answer is a Pallas kernel pair
implementing the FlashAttention-2 formulation:

* forward: one pass over K/V blocks per Q block with the online
  softmax (running max ``m``, running denominator ``l``), emitting the
  output block and the per-row logsumexp. Attention probabilities are
  never materialized in HBM — O(T) memory instead of O(T²).
* backward: the standard two-kernel split — a dQ kernel gridded over Q
  blocks and a dK/dV kernel gridded over K blocks — each recomputing
  P = exp(S − lse) blockwise from the saved logsumexp (recompute beats
  storing T² probabilities on an HBM-bound chip).

Softmax statistics and accumulators run in fp32 regardless of input
dtype (the MXU consumes bf16 operands; the VPU accumulates fp32).
Kernels run in interpret mode off-TPU, so CPU tests exercise the same
code path bit-for-bit (tests/test_flash_attention.py checks fwd+grads
against the dense jnp oracle).

The masks the kernels take, each applied in the kernel with the loops
clamped to the tiles it leaves anything in (forward and dQ loop over K
blocks, dK/dV over Q blocks; where dK/dV stages its q group by block its
grid lists those tiles and no other, :func:`_dkv_steps`;
``flash_attention``'s docstring has the arguments):

* ``causal``: forward/dQ end at the diagonal (``_causal_bound``), dK/dV
  starts there;
* ``window`` (with causal): forward/dQ also start at the band's first block
  (``_window_start``), dK/dV also ends at its last;
* ``lengths`` (right padding): all three end at the valid length
  (``_length_bound``);
* ``block_diffusion`` (a noised and a clean copy of every row under one
  three-part mask; alone): two ranges a program where the others have one
  (``_blockdiff_k_ranges``, ``_blockdiff_q_ranges``).

Any other mask is the dense path's (``TransformerConfig.
flash_decline_reason``).

Used by models.Transformer when ``TransformerConfig.flash_attention``
is on (default: auto — enabled when no padding mask is passed).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import tracing as _tracing


def _lens_spec():
    """Spec for the per-row valid lengths, a ``(rows,)`` int32 array
    held whole in SMEM; each program reads its own entry at
    ``program_id(0)``. The value drives loop bounds, and scalar memory
    is where the official TPU flash kernels keep sequence lengths. (A
    blocked ``(1, 1)`` SMEM spec is refused by the Pallas TPU lowering:
    the last two block dims must be 8x128-divisible or the full array
    dims.)"""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


_NEG_INF = -1e30

# Mosaic requires the last two dims of every block to be (8k, 128k) or
# the full array dims. Row statistics (lse) are per-Q-row scalars, so
# inside the kernels they ride a broadcast lane minor dim — the same
# layout the official jax.experimental.pallas.ops.tpu.flash_attention
# uses (MIN_BLOCK_SIZE trailing dim on l/m). ACROSS kernels, though,
# the lse lives width-1 (minor dim 1 = the full array dim, which
# Mosaic's block rule also accepts): materializing the broadcast as a
# (bh, seq, 128) HBM array made bwd lse traffic and the dkv kernel's
# VMEM footprint 128x larger than needed (ADVICE r3).
# HOROVOD_FLASH_LSE_BROADCAST=1 restores the broadcast interchange
# layout (escape hatch while the width-1 layout awaits real-TPU
# validation; interpret-mode tests cover both).
_STATS_LANES = 128


def _interchange_lanes() -> int:
    import os

    flag = os.environ.get("HOROVOD_FLASH_LSE_BROADCAST", "")
    return _STATS_LANES if flag not in ("", "0", "false", "off") else 1


def _causal_bound(qi, block_q, block_k, n_blocks):
    """K-block iteration bound for causal masking: ceil((qi+1)·BQ / BK)
    covers exactly the unmasked columns."""
    return jnp.minimum(
        n_blocks, ((qi + 1) * block_q + block_k - 1) // block_k
    )


def _apply_causal_mask(s, qi, j, block_q, block_k):
    """Mask scores above the diagonal using global row/col indices."""
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return jnp.where(rows >= cols, s, _NEG_INF)


def _apply_length_mask(s, j, block_k, kv_len):
    """Mask key columns at or beyond the sequence's valid length."""
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1
    )
    return jnp.where(cols < kv_len, s, _NEG_INF)


def _apply_window_mask(s, qi, j, block_q, block_k, window):
    """Causal sliding window: row attends cols in (row-window, row] —
    mask row - col >= window (the >= diagonal side is the causal
    mask's job). Every row keeps its own diagonal, so no row is ever
    fully masked."""
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0
    )
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1
    )
    return jnp.where(rows - cols < window, s, _NEG_INF)


def _window_start(qi, block_q, block_k, window):
    """First K block any row of Q block qi can see: lowest needed col
    is qi*BQ - window + 1."""
    return jnp.maximum(0, (qi * block_q - window + 1) // block_k)


def _length_bound(kv_len, block_k, n_blocks):
    """K-block iteration bound under padding: blocks wholly past the
    valid length contribute nothing."""
    return jnp.minimum(n_blocks, (kv_len + block_k - 1) // block_k)


def _pick(cond, a, b):
    """``a if cond else b`` for a Python ``cond`` (the loop bounds counted
    from plain numbers), ``jnp.where`` for a traced one (in a kernel)."""
    return (a if cond else b) if isinstance(cond, bool) else jnp.where(
        cond, a, b)


# The block-diffusion mask. The sequence is two copies of a row of ``half``
# tokens in blocks of ``blk``: a noised copy (positions < half) and the clean
# one after it. With i, j the tokens' indices in their own copies and b(i) =
# i // blk, query r keeps key c iff
#   noised sees noised:  b(i) == b(j)     noised sees clean:  b(j) < b(i)
#   clean sees clean:    b(j) <= b(i)     clean sees noised:  never
# ``half`` is a whole number of tiles of either size and ``blk`` divides
# both tiles, so a tile lies in one copy and a block in one tile.


def _blockdiff_k_ranges(qi, block_q, block_k, half, blk):
    """``((first, last), (first, last))``: the K tiles that Q tile ``qi``
    keeps anything of. A noised tile: the noised tiles of its own blocks
    (first, so that every row's running maximum is a kept score before a
    tile that holds none of the row's), then the clean tiles of the blocks
    before its last. A clean tile: no noised tile; the clean tiles up to its
    own."""
    q0 = qi * block_q
    noised = q0 < half
    own = q0 // block_k
    own_end = _pick(noised, (q0 + block_q + block_k - 1) // block_k, own)
    # clean tokens seen: those before the last row's block, or through it
    seen = _pick(noised, q0 + block_q - blk, q0 + block_q - half)
    clean = half // block_k
    return (own, own_end), (clean, clean + (seen + block_k - 1) // block_k)


def _blockdiff_q_ranges(ki, block_q, block_k, half, blk):
    """``((first, last), (first, last))``: the Q tiles that keep anything
    of K tile ``ki``. A noised tile: the noised tiles of its own blocks,
    and no other. A clean tile: the noised tiles from the block after its
    first on, and the clean tiles from its own on."""
    k0 = ki * block_k
    noised = k0 < half
    j0 = _pick(noised, k0, k0 - half)
    n_half = half // block_q
    first = _pick(noised, k0 // block_q, (j0 + blk) // block_q)
    last = _pick(noised, (k0 + block_k + block_q - 1) // block_q, n_half)
    clean = n_half + j0 // block_q
    return (first, last), (clean, _pick(noised, clean, 2 * n_half))


def blockdiff_tiles(seq: int, block_q: int, block_k: int, blk: int):
    """``(forward and dQ, dK/dV)``: the (Q tile, K tile) pairs a head's
    loops visit under the block-diffusion mask of a ``seq`` = 2 x half
    sequence, counted from the loop bounds."""
    half = seq // 2
    fwd = sum(
        last - first
        for qi in range(seq // block_q)
        for first, last in _blockdiff_k_ranges(
            qi, block_q, block_k, half, blk)
    )
    dkv = sum(
        last - first
        for ki in range(seq // block_k)
        for first, last in _blockdiff_q_ranges(
            ki, block_q, block_k, half, blk)
    )
    return fwd, dkv


def _apply_blockdiff_mask(s, qi, kj, block_q, block_k, half, blk):
    """The three-part mask on the score tile of Q tile ``qi`` and K tile
    ``kj``: the block of every row and of every column, each from a vector
    of its own, then two comparisons over the tile."""
    q0, k0 = qi * block_q, kj * block_k
    q_noised, k_noised = q0 < half, k0 < half
    bi = jax.lax.div(
        q0 - jnp.where(q_noised, 0, half)
        + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0),
        jnp.int32(blk),
    )
    bj = jax.lax.div(
        k0 - jnp.where(k_noised, 0, half)
        + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1),
        jnp.int32(blk),
    )
    # kept: lowest <= b(j) <= highest. Noised on noised: the row's own
    # block; noised on clean: the blocks before it; clean on clean: up to
    # it; clean on noised: none
    highest = jnp.where(
        q_noised, jnp.where(k_noised, bi, bi - 1),
        jnp.where(k_noised, -1, bi),
    )
    lowest = jnp.where(q_noised & k_noised, bi, 0)
    return jnp.where((bj <= highest) & (bj >= lowest), s, _NEG_INF)


def _loop_ranges(ranges, body, init):
    """``body`` over each ``(first, last)`` of ``ranges`` in turn."""
    for first, last in ranges:
        init = jax.lax.fori_loop(first, last, body, init)
    return init


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal,
                block_q, block_k, padded=False, window=None,
                blockdiff=None):
    if padded:
        len_ref, o_ref, lse_ref = rest
        kv_len = len_ref[pl.program_id(0)]
    else:
        o_ref, lse_ref = rest
        kv_len = None
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # [BQ, D]
    seq_k = k_ref.shape[1]
    n_blocks = seq_k // block_k
    start = 0
    if causal:
        n_blocks = _causal_bound(qi, block_q, block_k, n_blocks)
    if padded:
        n_blocks = _length_bound(kv_len, block_k, n_blocks)
    if window is not None:
        start = _window_start(qi, block_q, block_k, window)
    ranges = ((start, n_blocks),)
    if blockdiff is not None:
        ranges = _blockdiff_k_ranges(
            qi, block_q, block_k, seq_k // 2, blockdiff
        )
    d_v = v_ref.shape[-1]  # the value's width, which may not be the key's

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, BK]
        if causal:
            s = _apply_causal_mask(s, qi, j, block_q, block_k)
        if padded:
            s = _apply_length_mask(s, j, block_k, kv_len)
        if window is not None:
            s = _apply_window_mask(s, qi, j, block_q, block_k, window)
        if blockdiff is not None:
            s = _apply_blockdiff_mask(
                s, qi, j, block_q, block_k, seq_k // 2, blockdiff
            )
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    # stats stay 2-D [BQ, 1] throughout — Mosaic vectorizes 2-D shapes;
    # 1-D vectors hit lowering gaps
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d_v), jnp.float32)
    m, l, acc = _loop_ranges(ranges, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # lane width comes from the out spec: 128 broadcast lanes or the
    # compact width-1 interchange layout (module docstring)
    lse_ref[0] = jnp.broadcast_to(
        m + jnp.log(l_safe), (block_q, lse_ref.shape[-1])
    )


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest,
               scale, causal, block_q, block_k, padded=False,
               window=None, blockdiff=None):
    if padded:
        len_ref, dq_ref = rest
        kv_len = len_ref[pl.program_id(0)]
    else:
        (dq_ref,) = rest
        kv_len = None
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, 0:1]  # [BQ, 1] — lanes are broadcast copies
    # delta[i] = rowsum(dO ⊙ O), computed in-kernel: cheaper than a
    # broadcast [seq, 128] HBM array and the O block is already small
    delta = jnp.sum(
        do * o_ref[0].astype(jnp.float32), axis=-1, keepdims=True
    )
    seq_k = k_ref.shape[1]
    n_blocks = seq_k // block_k
    start = 0
    if causal:
        n_blocks = _causal_bound(qi, block_q, block_k, n_blocks)
    if padded:
        n_blocks = _length_bound(kv_len, block_k, n_blocks)
    if window is not None:
        start = _window_start(qi, block_q, block_k, window)
    ranges = ((start, n_blocks),)
    if blockdiff is not None:
        ranges = _blockdiff_k_ranges(
            qi, block_q, block_k, seq_k // 2, blockdiff
        )
    d = q_ref.shape[-1]

    def body(j, dq):
        k = k_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            s = _apply_causal_mask(s, qi, j, block_q, block_k)
        if padded:
            s = _apply_length_mask(s, j, block_k, kv_len)
        if window is not None:
            s = _apply_window_mask(s, qi, j, block_q, block_k, window)
        if blockdiff is not None:
            s = _apply_blockdiff_mask(
                s, qi, j, block_q, block_k, seq_k // 2, blockdiff
            )
        p = jnp.exp(s - lse)
        if padded:
            # Defense in depth, NOT load-bearing: padded query rows
            # attend finitely over the valid keys (only COLUMNS are
            # masked), so their lse is ordinary and p <= ~1; their
            # contributions already vanish because the wrapper's
            # `where` zeroes the incoming do at padded rows (making
            # do, dp, delta all zero there). Zeroing p keeps dq at
            # padded rows exactly 0 even if a caller bypasses the
            # wrapper. The only degenerate-lse case, kv_len == 0, is
            # excluded by the loop bound clamp (n_blocks == 0).
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, p.shape, 0
            )
            p = jnp.where(rows < kv_len, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        return dq + scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = _loop_ranges(
        ranges, body, jnp.zeros((block_q, d), jnp.float32)
    )
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_members(q_ref, do_ref, o_ref, lse_ref, at, ki, k, v, dk, dv, *,
                 scale, causal, block_q, block_k, i, kv_len, window,
                 blockdiff, half):
    """dk/dv plus the part of Q tile ``i`` in K tile ``ki`` (``k``, ``v``:
    float32) of each query head of the refs' leading dimension in turn (a
    static Python loop: a group is small). The tile's rows are those of
    block ``at`` of the refs' second dimension: ``i`` where they hold the
    whole sequence, 0 where they hold the tile. Both dK/dV kernels' sum."""
    for gm in range(q_ref.shape[0]):
        q = q_ref[gm, pl.dslice(at * block_q, block_q), :].astype(
            jnp.float32
        )
        do = do_ref[gm, pl.dslice(at * block_q, block_q), :].astype(
            jnp.float32
        )
        lse = lse_ref[gm, pl.dslice(at * block_q, block_q), :][:, 0:1]
        delta = jnp.sum(
            do
            * o_ref[gm, pl.dslice(at * block_q, block_q), :].astype(
                jnp.float32
            ),
            axis=-1,
            keepdims=True,
        )
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, BK]
        if causal:
            s = _apply_causal_mask(s, i, ki, block_q, block_k)
        if kv_len is not None:
            # Mask key columns past the length so their dk/dv stay 0.
            s = _apply_length_mask(s, ki, block_k, kv_len)
        if window is not None:
            s = _apply_window_mask(s, i, ki, block_q, block_k, window)
        if blockdiff is not None:
            s = _apply_blockdiff_mask(
                s, i, ki, block_q, block_k, half, blockdiff
            )
        p = jnp.exp(s - lse)
        if kv_len is not None:
            # Same defense-in-depth row zeroing as _dq_kernel (see the
            # comment there — padded-row lse is finite; this guards
            # wrapper-bypassing callers, it does not prevent NaNs).
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, p.shape, 0
            )
            p = jnp.where(rows < kv_len, p, 0.0)
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        dk = dk + scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return dk, dv


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                *rest, scale, causal, block_q, block_k, padded=False,
                window=None, blockdiff=None):
    """dK/dV over one K block. With grouped-query attention the q/do/o/lse
    blocks carry the kv head's whole GROUP of q heads in their leading
    dim, and dk/dv accumulate over the group (:func:`_dkv_members`)."""
    if padded:
        len_ref, dk_ref, dv_ref = rest
        kv_len = len_ref[pl.program_id(0)]
    else:
        dk_ref, dv_ref = rest
        kv_len = None
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)  # [BK, D]
    v = v_ref[0].astype(jnp.float32)
    seq_q = q_ref.shape[1]
    n_blocks = seq_q // block_q
    start = 0
    if causal:
        # Q blocks strictly before this K block see none of it.
        start = ki * block_k // block_q
    if padded:
        # Q blocks wholly past the valid length have do == 0 (zeroed by
        # the wrapper) and masked p — skip them.
        n_blocks = _length_bound(kv_len, block_q, n_blocks)
    if window is not None:
        # Sliding window adds an END bound over Q blocks: the last row
        # that sees any col of this K block is (ki+1)*BK - 1 + W - 1.
        n_blocks = jnp.minimum(
            n_blocks,
            ((ki + 1) * block_k - 1 + window - 1) // block_q + 1,
        )
    ranges = ((start, n_blocks),)
    if blockdiff is not None:
        ranges = _blockdiff_q_ranges(
            ki, block_q, block_k, seq_q // 2, blockdiff
        )
    d, d_v = k_ref.shape[-1], v_ref.shape[-1]

    def body(i, carry):
        return _dkv_members(
            q_ref, do_ref, o_ref, lse_ref, i, ki, k, v, *carry,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            i=i, kv_len=kv_len, window=window, blockdiff=blockdiff,
            half=seq_q // 2,
        )

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d_v), jnp.float32)
    dk, dv = _loop_ranges(ranges, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# The preferred block size everywhere (kernel defaults, model config,
# the ring-flash hop engine): won the r04 on-chip sweep on GPT-2-medium
# seq-512 (MFU 0.563 vs 0.409 at 128). Auto-shrunk per sequence by
# _pick_block; retune HERE so the gate (supports_seq) and every engine
# stay in agreement.
DEFAULT_BLOCK = 512


def _pick_block(seq: int, preferred: int = DEFAULT_BLOCK) -> int:
    b = min(preferred, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


def supports_seq(
    t: int, block_q: int = DEFAULT_BLOCK, block_k: int = DEFAULT_BLOCK
) -> bool:
    """Whether the kernels can tile this sequence length. Mosaic needs
    each block's trailing dims to be (8k, 128k)-aligned or the full
    array dim; we additionally require the chosen block to be 8-aligned
    (sublane) unless the whole sequence is shorter than one sublane —
    full-dim unaligned tiles (e.g. ViT's 14*14+1 = 197 tokens) were
    never validated on hardware and take the dense path. (Before r04
    the check accepted ANY t <= preferred via the full-dim early-out;
    raising the preferred block to 512 would have silently routed 197
    through the kernel.)"""

    def ok(b: int) -> bool:
        return b % 8 == 0 or (b == t and t < 8)

    return ok(_pick_block(t, block_q)) and ok(_pick_block(t, block_k))


# What the dK/dV kernel may stage whole-sequence (:func:`bwd_vmem_bytes`):
# up to it the kernel fetches the kv row's whole q-head group once and
# loops over it in place; past it the q group is staged block by block, a
# grid step for each tile the mask keeps (:func:`_dkv_blocked`), so the
# footprint follows the block and no shape is sent to the dense path for
# VMEM. A guess made before the chip could be asked, and conservative: on
# the v5e (scripts/chip_roster.py --vmem-sweep, d=128 bf16, PR 21) the
# whole-sequence kernel compiled and ran up to an ESTIMATE of 48.8 MiB and
# ran out of VMEM at 97 MiB.
_VMEM_BUDGET_DEFAULT = 12 * 2**20


def _vmem_budget() -> int:
    import os

    return int(
        os.environ.get("HOROVOD_FLASH_VMEM_BUDGET", _VMEM_BUDGET_DEFAULT)
    )


def bwd_vmem_bytes(
    seq: int,
    d: int,
    h_per_kv: int = 1,
    itemsize: int = 2,
    block_k: int = None,
    d_v: int = None,
) -> int:
    """Per-program VMEM staging estimate for the dK/dV backward kernel
    — the family's largest stager. With grouped-query attention it
    fetches the KV row's whole q-head group whole-sequence ((r, seq, d)
    blocks for q/do/o plus an (r, seq, lanes) fp32 lse), so the
    footprint grows r-fold on top of the whole-sequence staging the
    module header documents (ADVICE r4). e.g. r=8, seq=4096, d=128,
    bf16: ~25 MiB. ``d`` is the width of q and k, ``d_v`` that of v, do
    and o (None: ``d``). An estimate in the units of the gate's budget,
    not of what Mosaic allocates (that is :func:`staged_vmem_bytes`, by
    which the call's limit is set): see the budget's note."""
    lanes = _interchange_lanes()
    d_v = d if d_v is None else d_v
    bk = _pick_block(seq, block_k if block_k else DEFAULT_BLOCK)
    # q, do, o and the lse
    stage = h_per_kv * seq * ((d + 2 * d_v) * itemsize + 4 * lanes)
    stage += 2 * bk * (d + d_v) * itemsize  # k/v in-blocks, dk/dv out-blocks
    return stage


def fits_vmem(
    seq: int,
    d: int,
    h_per_kv: int = 1,
    itemsize: int = 2,
    block_k: int = None,
    d_v: int = None,
) -> bool:
    """Whether the dK/dV kernel's whole-sequence staging fits the
    per-core VMEM budget (HOROVOD_FLASH_VMEM_BUDGET bytes, default
    12 MiB). Where it does not, ``flash_attention`` stages the q group
    by block (:func:`_dkv_blocked`); the ulysses/ring auto-gates, whose
    hop engines have no blocked variant, fall back to their dense
    engines, and ``ring_flash_attention`` warns."""
    return (
        bwd_vmem_bytes(seq, d, h_per_kv, itemsize, block_k, d_v)
        <= _vmem_budget()
    )


def _warn_vmem(seq, d, h_per_kv, itemsize, block_k=None, what=""):
    import warnings

    warnings.warn(
        f"{what or 'flash_attention'} backward staging estimate "
        f"{bwd_vmem_bytes(seq, d, h_per_kv, itemsize, block_k) / 2**20:.0f}"
        f" MiB (seq={seq}, head_dim={d}, q-heads-per-kv={h_per_kv}) "
        f"exceeds the VMEM budget ({_vmem_budget() / 2**20:.0f} MiB)"
        f"; Mosaic compilation of the dK/dV kernel will likely fail"
        f" — use ring attention over more chips, more KV heads, or the"
        f" dense path.",
        stacklevel=3,
    )


# What Mosaic holds in VMEM, as the v5e's compiler counts it (compiled for a
# described chip at the shapes of tests/test_tpu_compile.py, PR 31). This is
# the one count of Mosaic's bytes here; :func:`bwd_vmem_bytes` is in the
# units of the gate's budget, which are not bytes of VMEM (ROADMAP A9).
# Unless it is told otherwise a kernel may hold 16 MiB (the "scoped" limit).
# Beside its whole-sequence operands a kernel here held 1.0-4.8 MiB (its
# blocks in and out, the score tile, the float32 temporaries). The largest
# staging that compiled at the default was 10 MiB (dK/dV, 4096 x 128); all
# three kernels were refused at 2 x 8192 x 32 heads of a 192-wide key and a
# 128-wide value (forward and dQ: 12 MiB staged, 16.03 MiB held; dK/dV: 24
# and 25.5), and so was the whole-sequence dK/dV kernel at 4096 x 192/128
# (12 and 16.84) and at 8192 x 128 or x 64 with one head a group (20 and
# 21), which :func:`fits_vmem` has always let through.
_SCOPED_VMEM_DEFAULT = 16 * 2**20
_VMEM_BESIDE_STAGED = 6 * 2**20  # the largest read, 4.84 MiB, and room


def staged_vmem_bytes(rows: int, *operands) -> int:
    """What Mosaic holds for ``rows`` rows of whole-sequence ``operands``,
    each ``(last dimension, itemsize)``: every one twice (the pipeline's
    two buffers), its rows rounded up to 128 lanes, so that a 192-wide
    bf16 row and a width-1 float32 lse row take 512 bytes each."""
    return 2 * rows * sum(
        -(-width // 128) * 128 * itemsize for width, itemsize in operands
    )


def _staging_params(rows: int, *operands):
    """Compiler parameters for a kernel that stages ``operands``
    (:func:`staged_vmem_bytes`). None, the kernel as it always was, while
    Mosaic's default limit holds them: the calls of every shape that ran
    before PR 31 compile to what they were. Past it the limit is raised
    to what is staged and as much again (the chip's VMEM is 128 MiB)."""
    staged = staged_vmem_bytes(rows, *operands)
    if staged + _VMEM_BESIDE_STAGED <= _SCOPED_VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=2 * staged + _VMEM_BESIDE_STAGED
    )


def _call_span(kernel, q, v, grid, *, causal, window, padded, blockdiff,
               block_q, block_k, group, whole=None, params=None, **more):
    """The span ``hvd.kernels.flash_call`` around one ``pl.pallas_call``
    of this file while JAX traces it (the kernel body's jaxpr is made
    inside the call), else a null context: the call's plan as tags.
    ``whole`` is what the call stages whole-sequence, ``(rows,
    *operands)`` as :func:`staged_vmem_bytes` and
    :func:`_staging_params` take them; None for a blocked call, which
    gives the ``params`` it asks for instead."""
    if blockdiff is not None:
        mask = "block_diffusion"
    else:
        mask = "window" if window is not None else (
            "causal" if causal else "none")
        if padded:
            mask = "lengths" if mask == "none" else mask + "+lengths"
    if whole:
        params = _staging_params(*whole)
    return _tracing.trace_time_span(
        "hvd.kernels.flash_call", q, kernel=kernel,
        staging="whole" if whole else "blocked", mask=mask,
        block_q=block_q, block_k=block_k, seq=q.shape[1], heads=q.shape[0],
        group=group, d_qk=q.shape[2], d_v=v.shape[2],
        grid_steps=math.prod(grid),
        staged_vmem_bytes=staged_vmem_bytes(*whole) if whole else 0,
        vmem_limit_bytes=getattr(params, "vmem_limit_bytes", None) or 0,
        **more,
    )


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def _flash_bhtd(q, k, v, causal, block_q, block_k, window):
    o, _ = _flash_fwd(
        q, k, v, causal, block_q, block_k, window=window
    )
    return o


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7)
)
def _flash_bhtd_padded(q, k, v, lens, causal, block_q, block_k, window):
    """Padded variant: ``lens`` is a (bh,) int32 of valid key/query
    lengths. Separate custom_vjp so the unpadded path's compiled
    artifacts are untouched."""
    o, _ = _flash_fwd(
        q, k, v, causal, block_q, block_k, lens=lens, window=window
    )
    return o


def _flash_fwd(q, k, v, causal, block_q, block_k, lens=None, h_per_kv=1,
               window=None, blockdiff=None):
    """``h_per_kv`` > 1 = grouped-query attention: k/v carry bh//r rows
    (r = h_per_kv) and each q row p reads kv row p // r — exact because
    rows are batch-major/head-minor with kv-head groups contiguous.
    ``window`` = causal sliding window width (requires causal). v may be
    narrower or wider than q and k: the scores contract over q's width,
    the output is as wide as v."""
    bh, seq, d = q.shape
    d_v = v.shape[-1]
    scale = 1.0 / (d ** 0.5)
    n_q = seq // block_q
    lanes = _interchange_lanes()
    r = h_per_kv
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, padded=lens is not None,
        window=window, blockdiff=blockdiff,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, seq, d), lambda b, i: (b // r, 0, 0)),
        pl.BlockSpec((1, seq, d_v), lambda b, i: (b // r, 0, 0)),
    ]
    operands = [q, k, v]
    if lens is not None:
        in_specs.append(_lens_spec())
        operands.append(lens)
    kv = (seq, (d, k.dtype.itemsize), (d_v, v.dtype.itemsize))
    with _call_span(
        "flash_fwd", q, v, (bh, n_q), causal=causal, window=window,
        padded=lens is not None, blockdiff=blockdiff, block_q=block_q,
        block_k=block_k, group=r, whole=kv,
    ):
        o, lse = pl.pallas_call(
            kernel,
            grid=(bh, n_q),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, d_v), lambda b, i: (b, i, 0)),
                pl.BlockSpec(
                    (1, block_q, lanes), lambda b, i: (b, i, 0)
                ),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, seq, d_v), q.dtype),
                jax.ShapeDtypeStruct((bh, seq, lanes), jnp.float32),
            ],
            compiler_params=_staging_params(*kv),
            interpret=_interpret(),
            name="flash_fwd",
        )(*operands)
    return o, lse


# The names a ``jax.checkpoint`` policy saves the forward rules'
# residuals by (``save_only_these_names(*RESIDUAL_NAMES)``): a policy on
# primitives does not see through a ``pallas_call``.
RESIDUAL_NAMES = ("flash_q", "flash_k", "flash_v", "flash_o", "flash_lse")
# The forward kernel's outputs among them: a policy that keeps these two
# alone lets the second forward remake q, k and v and run no kernel.
OUTPUT_NAMES = RESIDUAL_NAMES[3:]


def _named_residuals(q, k, v, o, lse):
    """What every forward rule keeps for the backward kernels, ``(q, k,
    v, o, lse_lane)``, each under its name of ``RESIDUAL_NAMES``: a
    rematted caller whose policy saves the names runs neither the
    forward kernel nor the head transposes that made q, k and v a second
    time; outside ``jax.checkpoint`` a name is the identity.

    Keep ONE lane of ``lse`` — the broadcast 128-lane layout is a
    Mosaic in-kernel constraint, not something worth holding across
    the whole forward pass (24 BERT-large layers of (bh, seq, 128)
    fp32 would be ~800 MB); re-broadcast transiently in the bwd."""
    return tuple(
        checkpoint_name(x, name)
        for x, name in zip((q, k, v, o, lse[..., 0]), RESIDUAL_NAMES)
    )


def _flash_fwd_vjp(q, k, v, causal, block_q, block_k, window):
    q, k, v, o, lse_lane = _named_residuals(q, k, v, *_flash_fwd(
        q, k, v, causal, block_q, block_k, window=window
    ))
    return o, (q, k, v, o, lse_lane)


def _flash_bwd_vjp_w(causal, block_q, block_k, window, res, do):
    q, k, v, o, lse_lane = res
    return _flash_bwd_impl(
        q, k, v, o, lse_lane, do, causal, block_q, block_k,
        window=window,
    )


def _flash_fwd_vjp_padded(q, k, v, lens, causal, block_q, block_k,
                          window):
    q, k, v, o, lse_lane = _named_residuals(q, k, v, *_flash_fwd(
        q, k, v, causal, block_q, block_k, lens=lens, window=window
    ))
    return o, (q, k, v, o, lse_lane, lens)


def _flash_bwd_vjp_padded(causal, block_q, block_k, window, res, do):
    q, k, v, o, lse_lane, lens = res
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, o, lse_lane, do, causal, block_q, block_k, lens=lens,
        window=window,
    )
    return dq, dk, dv, None  # int lengths carry no cotangent


def _flash_bwd_impl(
    q, k, v, o, lse_lane, do, causal, block_q, block_k, lens=None,
    h_per_kv=1, window=None, blockdiff=None,
):
    lanes = _interchange_lanes()
    if lanes == 1:
        # compact interchange: (bh, seq, 1) — the kernels' [:, 0:1]
        # slices read it unchanged, at 1/128th the HBM traffic and
        # dkv VMEM of the broadcast layout
        lse = lse_lane[..., None]
    else:
        lse = jnp.broadcast_to(
            lse_lane[..., None], (*lse_lane.shape, lanes)
        )
    bh, seq, d = q.shape
    d_v = v.shape[-1]  # v, o and do; q, k and their gradients are d wide
    scale = 1.0 / (d ** 0.5)
    n_q = seq // block_q
    n_k = seq // block_k
    padded = lens is not None
    r = h_per_kv
    kv_rows = bh // r
    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, seq, d), lambda b, i: (b // r, 0, 0)),
        pl.BlockSpec((1, seq, d_v), lambda b, i: (b // r, 0, 0)),
        pl.BlockSpec((1, block_q, d_v), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, d_v), lambda b, i: (b, i, 0)),
        pl.BlockSpec(
            (1, block_q, lanes), lambda b, i: (b, i, 0)
        ),
    ]
    dq_operands = [q, k, v, do, o, lse]
    # dkv grids over KV rows; with GQA (r > 1) the q/do/o/lse blocks
    # carry the kv row's whole contiguous group of q-head rows (leading
    # block dim r) and the kernel accumulates over the group.
    dkv_in_specs = [
        pl.BlockSpec((r, seq, d), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, block_k, d_v), lambda b, i: (b, i, 0)),
        pl.BlockSpec((r, seq, d_v), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((r, seq, d_v), lambda b, i: (b, 0, 0)),
        pl.BlockSpec(
            (r, seq, lanes), lambda b, i: (b, 0, 0)
        ),
    ]
    dkv_operands = [q, k, v, do, o, lse]
    if padded:
        dq_in_specs.append(_lens_spec())
        dq_operands.append(lens)
        dkv_in_specs.append(_lens_spec())
        # per-KV-row lengths: every r-th q row's entry (lengths are
        # per-batch, so the group's rows all agree)
        dkv_operands.append(lens[::r])
    plan = dict(
        causal=causal, window=window, padded=padded, blockdiff=blockdiff,
        block_q=block_q, block_k=block_k, group=r,
    )
    kv = (seq, (d, k.dtype.itemsize), (d_v, v.dtype.itemsize))
    with _call_span("flash_dq", q, v, (bh, n_q), whole=kv, **plan):
        dq = pl.pallas_call(
            functools.partial(
                _dq_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, padded=padded,
                window=window, blockdiff=blockdiff,
            ),
            grid=(bh, n_q),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec(
                (1, block_q, d), lambda b, i: (b, i, 0)
            ),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            compiler_params=_staging_params(*kv),
            interpret=_interpret(),
            name="flash_dq",
        )(*dq_operands)
    if not fits_vmem(seq, d, r, q.dtype.itemsize, block_k, d_v):
        dk, dv = _dkv_blocked(
            q, k, v, do, o, lse, lens[::r] if padded else None,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            group=r, window=window, blockdiff=blockdiff,
        )
        return dq, dk, dv
    # the group's q, do, o and float32 lse
    group_rows = (
        r * seq, (d, q.dtype.itemsize), (d_v, do.dtype.itemsize),
        (d_v, o.dtype.itemsize), (lanes, 4),
    )
    with _call_span(
        "flash_dkv", q, v, (kv_rows, n_k), whole=group_rows, **plan
    ):
        dk, dv = pl.pallas_call(
            functools.partial(
                _dkv_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, padded=padded,
                window=window, blockdiff=blockdiff,
            ),
            grid=(kv_rows, n_k),
            in_specs=dkv_in_specs,
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_k, d_v), lambda b, i: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            compiler_params=_staging_params(*group_rows),
            interpret=_interpret(),
            name="flash_dkv",
        )(*dkv_operands)
    return dq, dk, dv


_flash_bhtd.defvjp(_flash_fwd_vjp, _flash_bwd_vjp_w)
_flash_bhtd_padded.defvjp(_flash_fwd_vjp_padded, _flash_bwd_vjp_padded)


# Grouped-query attention entry points (additive — the MHA custom_vjps
# above keep their arity so existing callers and compiled paths are
# untouched).


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhtd_gqa(q, k, v, causal, block_q, block_k, h_per_kv, window):
    o, _ = _flash_fwd(
        q, k, v, causal, block_q, block_k, h_per_kv=h_per_kv,
        window=window,
    )
    return o


def _flash_fwd_vjp_gqa(
    q, k, v, causal, block_q, block_k, h_per_kv, window
):
    q, k, v, o, lse_lane = _named_residuals(q, k, v, *_flash_fwd(
        q, k, v, causal, block_q, block_k, h_per_kv=h_per_kv,
        window=window,
    ))
    return o, (q, k, v, o, lse_lane)


def _flash_bwd_vjp_gqa(
    causal, block_q, block_k, h_per_kv, window, res, do
):
    q, k, v, o, lse_lane = res
    return _flash_bwd_impl(
        q, k, v, o, lse_lane, do, causal, block_q, block_k,
        h_per_kv=h_per_kv, window=window,
    )


_flash_bhtd_gqa.defvjp(_flash_fwd_vjp_gqa, _flash_bwd_vjp_gqa)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_bhtd_gqa_padded(
    q, k, v, lens, causal, block_q, block_k, h_per_kv, window
):
    o, _ = _flash_fwd(
        q, k, v, causal, block_q, block_k, lens=lens,
        h_per_kv=h_per_kv, window=window,
    )
    return o


def _flash_fwd_vjp_gqa_padded(
    q, k, v, lens, causal, block_q, block_k, h_per_kv, window
):
    q, k, v, o, lse_lane = _named_residuals(q, k, v, *_flash_fwd(
        q, k, v, causal, block_q, block_k, lens=lens,
        h_per_kv=h_per_kv, window=window,
    ))
    return o, (q, k, v, o, lse_lane, lens)


def _flash_bwd_vjp_gqa_padded(
    causal, block_q, block_k, h_per_kv, window, res, do
):
    q, k, v, o, lse_lane, lens = res
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, o, lse_lane, do, causal, block_q, block_k, lens=lens,
        h_per_kv=h_per_kv, window=window,
    )
    return dq, dk, dv, None


_flash_bhtd_gqa_padded.defvjp(
    _flash_fwd_vjp_gqa_padded, _flash_bwd_vjp_gqa_padded
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bhtd_blockdiff(q, k, v, block_q, block_k, h_per_kv, blockdiff):
    """The block-diffusion mask's entry (any ``h_per_kv``): a rule of its
    own, so that every other mask's keeps its arity."""
    o, _ = _flash_fwd(
        q, k, v, False, block_q, block_k, h_per_kv=h_per_kv,
        blockdiff=blockdiff,
    )
    return o


def _flash_fwd_vjp_blockdiff(q, k, v, block_q, block_k, h_per_kv,
                             blockdiff):
    q, k, v, o, lse_lane = _named_residuals(q, k, v, *_flash_fwd(
        q, k, v, False, block_q, block_k, h_per_kv=h_per_kv,
        blockdiff=blockdiff,
    ))
    return o, (q, k, v, o, lse_lane)


def _flash_bwd_vjp_blockdiff(block_q, block_k, h_per_kv, blockdiff, res,
                             do):
    q, k, v, o, lse_lane = res
    return _flash_bwd_impl(
        q, k, v, o, lse_lane, do, False, block_q, block_k,
        h_per_kv=h_per_kv, blockdiff=blockdiff,
    )


_flash_bhtd_blockdiff.defvjp(
    _flash_fwd_vjp_blockdiff, _flash_bwd_vjp_blockdiff
)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    lengths: Optional[jax.Array] = None,
    window: Optional[int] = None,
    block_diffusion: Optional[int] = None,
) -> jax.Array:
    """Attention over [batch, seq, heads, head_dim] tensors (the model
    layout), softmax scale 1/√d. Differentiable (custom VJP, blockwise
    recompute). Sequence length must be divisible by the chosen block
    sizes; blocks shrink automatically for short sequences.

    v may have a head width of its own (latent attention: a 192-wide key
    with a 128-wide value): d is the width of q and k, over which the
    scores contract and whose root scales them; the output, ``dO`` and
    ``dV`` are as wide as v, and nothing is padded to the wider of the
    two.

    ``lengths`` ([batch] int): per-sequence valid token counts for
    right-padded batches — keys at or beyond a sequence's length are
    masked out of its softmax, outputs at padded query positions are
    zero, and the VJP routes no gradient through padded positions.
    Equivalent to the dense path's key-validity mask
    ``iota(t) < lengths[:, None]``, without leaving the kernel.

    Grouped-query attention: k/v may carry FEWER heads than q
    ([batch, seq, kv_heads, head_dim] with q heads % kv_heads == 0) —
    each group of q heads reads one kv head, Llama/Mistral-style. The
    kernels read the shared kv rows directly (no repeat/broadcast of
    K/V ever materializes), so the HBM savings GQA exists for are
    preserved.

    ``window`` (int, requires ``causal=True``): Mistral-style causal
    sliding window — row r attends cols in (r-window, r], masked
    in-kernel with the block loops clamped to the band on both sides,
    so COMPUTE scales with the window. The forward and dQ kernels
    still stage K/V whole-sequence per program (the BlockSpecs fetch
    (1, seq, d): 2 MiB a tensor at seq 8192, d 128, bf16), so their
    VMEM footprint remains O(seq) — at extreme sequence lengths use
    ring attention for the memory win. The dK/dV kernel stages its q
    group whole-sequence where that fits the budget (:func:`fits_vmem`)
    and block by block where it does not: its grid is then a table, made
    at trace time, of the (K tile, Q tile) steps that this mask keeps a
    pair in (:func:`_dkv_steps`), under any of the masks here, each step
    taking as many of the group's query heads as fit
    (:func:`_members_per_step`). Composes with lengths and GQA.

    ``block_diffusion`` (int, the block length B): the sequence is a
    noised copy of a row of ``t / 2`` tokens and then the clean row, and a
    query keeps the keys that block-diffusion training lets it see
    (:func:`_blockdiff_k_ranges`): in the noised copy its own block, both
    ways, and the clean blocks before it; in the clean copy the clean
    blocks up to its own, both ways inside a block. Masked in-kernel, two
    loop ranges a program (in the blocked dK/dV kernel: the table's steps),
    so the tiles visited follow the ``(t/2)^2 + t/2 x B`` kept pairs
    (:func:`blockdiff_tiles`) and q, k and v are read as they are. It is a
    mask of its own: ``causal``, ``window`` and ``lengths`` are refused
    beside it. ``t / 2`` must be whole tiles and B divide the tiles.
    Composes with GQA and a narrower v."""
    b, t, h, d = q.shape
    if k.shape[-1] != d:
        raise ValueError(
            f"q and k must share their head width: q={d}, k={k.shape[-1]}"
        )
    d_v = v.shape[-1]
    if window is not None:
        if not causal:
            raise ValueError("window= requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        window = int(window)
        if window >= t:
            window = None  # full causal attention; skip the band masks
    kv_h = k.shape[2]
    if v.shape[2] != kv_h or h % kv_h:
        raise ValueError(
            f"kv heads must match and divide q heads: q={h}, "
            f"k={k.shape[2]}, v={v.shape[2]}"
        )
    h_per_kv = h // kv_h
    if block_diffusion is None:
        block_q = _pick_block(t, block_q)
        block_k = _pick_block(t, block_k)

    def to_bhtd(x):
        hh, width = x.shape[2:]
        return x.transpose(0, 2, 1, 3).reshape(b * hh, t, width)

    if block_diffusion is not None:
        if causal or window is not None or lengths is not None:
            raise ValueError(
                "block_diffusion= is a mask of its own: causal, window= "
                "and lengths= do not compose with it"
            )
        blk = int(block_diffusion)
        if blk < 1 or t % 2 or (t // 2) % blk:
            raise ValueError(
                f"block_diffusion={blk} needs a sequence of two copies of "
                f"a whole number of blocks, got {t} positions"
            )
        # tiles of one copy, so that none straddles the two
        block_q = _pick_block(t // 2, block_q)
        block_k = _pick_block(t // 2, block_k)
        if block_q % blk or block_k % blk:
            raise ValueError(
                f"block_diffusion={blk} must divide the kernels' tiles "
                f"({block_q} x {block_k})"
            )
        out = _flash_bhtd_blockdiff(
            to_bhtd(q), to_bhtd(k), to_bhtd(v),
            block_q, block_k, h_per_kv, blk,
        )
        return out.reshape(b, h, t, d_v).transpose(0, 2, 1, 3)
    if lengths is None:
        if h_per_kv == 1:
            out = _flash_bhtd(
                to_bhtd(q), to_bhtd(k), to_bhtd(v),
                causal, block_q, block_k, window,
            )
        else:
            out = _flash_bhtd_gqa(
                to_bhtd(q), to_bhtd(k), to_bhtd(v),
                causal, block_q, block_k, h_per_kv, window,
            )
        return out.reshape(b, h, t, d_v).transpose(0, 2, 1, 3)

    lens = jnp.asarray(lengths, jnp.int32)
    if lens.shape != (b,):
        raise ValueError(
            f"lengths must be [batch]=({b},), got {lens.shape}"
        )
    lens_bh = jnp.repeat(lens, h)  # (bh,)
    if h_per_kv == 1:
        out = _flash_bhtd_padded(
            to_bhtd(q), to_bhtd(k), to_bhtd(v), lens_bh,
            causal, block_q, block_k, window,
        )
    else:
        out = _flash_bhtd_gqa_padded(
            to_bhtd(q), to_bhtd(k), to_bhtd(v), lens_bh,
            causal, block_q, block_k, h_per_kv, window,
        )
    out = out.reshape(b, h, t, d_v).transpose(0, 2, 1, 3)
    # Zero padded QUERY rows OUTSIDE the custom_vjp. The kernel's raw
    # output there is ordinary finite attention over the valid keys
    # (rows are never masked, only columns) — zeroing is the API
    # contract, so padding can't leak downstream. Just as important,
    # this `where`'s transpose zeroes the incoming cotangent at padded
    # rows, which is what makes their dq/dk/dv contributions vanish in
    # the backward kernels.
    valid = jnp.arange(t)[None, :] < lens[:, None]  # [b, t]
    return jnp.where(valid[..., None, None], out, 0.0)


# dK/dV with the q group staged block by block (ROADMAP A9). The
# whole-sequence kernel above fetches (group, seq, d) blocks of q, do and
# o: 48 MiB at group 8, seq 8192, d 128. Here the grid is (kv row, step,
# part of the group): a step is one (K tile, Q tile) that the mask keeps a
# pair in, and fetches a (members, block_q, d) block of q, do, o and lse,
# ``members`` query heads of the kv head's group (all of them where they
# fit, :func:`_members_per_step`), and adds their parts to dk/dv held in
# VMEM scratch, so the footprint follows the blocks and not the sequence.
# Every mask but ``lens`` is known at trace time, so the steps are a table
# (:func:`_dkv_steps`) that the index maps and the kernel read from scalar
# memory (scalar prefetch): a K tile's steps are contiguous, Q tiles
# ascending, the first zeroes the accumulators and the last stores them.
# The grid holds no step that computes nothing, whatever the bands' widths
# (under the block-diffusion mask they run from 1 to 32 Q tiles a K tile).
# The group's parts are the grid's innermost dimension, so that the sums
# run Q tile by Q tile and within one query head by query head, as in the
# whole-sequence kernel (:func:`_dkv_members`, the body of both).
#
# A step is one int32 word, K tile | Q tile | edge bits: the v5e's scalar
# memory is 1 MiB (compiled for a described chip, a table of 262,144 words
# was refused), and columns of their own would take it three times over.

_FIRST, _LAST = 1, 2  # the edge bits: a K tile's first step, its last
_Q_SHIFT, _K_SHIFT = 2, 16
_MAX_TILES = 1 << (_K_SHIFT - _Q_SHIFT)  # the K tile's field is one wider

# What one step of the blocked kernel may hold: its q group's blocks twice
# and the room beside them. A quarter of the v5e's 128 MiB of VMEM, so the
# limit a call asks for (:func:`_staging_params`) stays under half of it.
_STEP_VMEM = 32 * 2**20


def _step_fields(word):
    """``(K tile, Q tile, edge bits)`` of a step's word (of a whole
    table's, given an array)."""
    return (
        word >> _K_SHIFT,
        (word >> _Q_SHIFT) & (_MAX_TILES - 1),
        word & (_FIRST | _LAST),
    )


def _dkv_q_range(ki, block_q, block_k, n_q, causal, window):
    """``[first, last)`` q blocks that see K block ``ki`` (before any
    length bound), as plain numbers: the same bounds as
    :func:`_dkv_kernel`'s loop."""
    first = ki * block_k // block_q if causal else 0
    last = n_q
    if window is not None:
        last = min(n_q, ((ki + 1) * block_k - 1 + window - 1) // block_q + 1)
    return first, last


def _dkv_steps(seq, block_q, block_k, causal, window, blockdiff=None):
    """``(steps, kept)``: the blocked dK/dV kernel's steps a kv row and
    part of its group, an int32 word each (:func:`_step_fields`), and how
    many of them are tiles the mask keeps a pair in (all of them, unless a
    K tile's band is empty: it gets a step on a tile the mask empties, so
    that its zeros are written)."""
    n_q, n_k = seq // block_q, seq // block_k
    if max(n_q, n_k) > _MAX_TILES:
        raise ValueError(
            f"the dK/dV kernel's table of steps holds {_MAX_TILES} tiles a "
            f"sequence: got {max(n_q, n_k)} tiles (seq {seq}, blocks "
            f"{block_q} x {block_k})"
        )
    tiles, kept = [], 0
    for ki in range(n_k):
        if blockdiff is not None:
            ranges = _blockdiff_q_ranges(
                ki, block_q, block_k, seq // 2, blockdiff
            )
        else:
            ranges = (_dkv_q_range(ki, block_q, block_k, n_q, causal, window),)
        band = np.concatenate([np.arange(*r) for r in ranges])
        kept += len(band)
        if not len(band):
            band = np.zeros(1, int)
        steps = ki << _K_SHIFT | band << _Q_SHIFT
        steps[0] |= _FIRST
        steps[-1] |= _LAST
        tiles.append(steps)
    return np.concatenate(tiles).astype(np.int32), kept


def _members_per_step(group, block_q, *operands):
    """How many of a kv head's ``group`` query heads a step of the blocked
    dK/dV kernel takes: the largest divisor of the group whose blocks of
    ``operands`` (q, do, o and lse, as :func:`staged_vmem_bytes` takes
    them), twice buffered, and the room beside them fit ``_STEP_VMEM``."""
    return max(
        (m for m in range(1, group + 1) if group % m == 0
         and staged_vmem_bytes(m * block_q, *operands)
         + _VMEM_BESIDE_STAGED <= _STEP_VMEM),
        default=1,
    )


def _dkv_kernel_blocked(steps_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                        lse_ref, *rest, scale, causal, block_q, block_k,
                        padded, n_q, window, blockdiff=None):
    if padded:
        len_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
        kv_len = len_ref[pl.program_id(0)]
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
        kv_len = None
    ki, i, edge = _step_fields(steps_ref[pl.program_id(1)])
    part = pl.program_id(2)

    @pl.when((edge & _FIRST != 0) & (part == 0))
    def _zero():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _accumulate():
        dk, dv = _dkv_members(
            q_ref, do_ref, o_ref, lse_ref, 0, ki,
            k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            dk_acc[...], dv_acc[...], scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, i=i, kv_len=kv_len,
            window=window, blockdiff=blockdiff, half=n_q * block_q // 2,
        )
        dk_acc[...] = dk
        dv_acc[...] = dv

    if padded:
        # the one bound the table cannot hold: Q tiles wholly past the
        # valid length (do == 0 there, zeroed by the wrapper)
        pl.when(i < _length_bound(kv_len, block_q, n_q))(_accumulate)
    else:
        _accumulate()

    @pl.when((edge & _LAST != 0) & (part == pl.num_programs(2) - 1))
    def _store():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dkv_blocked(q, k, v, do, o, lse, lens, *, scale, causal, block_q,
                 block_k, group, window, blockdiff=None):
    """``(dk, dv)`` through :func:`_dkv_kernel_blocked`; ``lens`` is per
    kv row or None. Same operands and results as the whole-sequence
    call in :func:`_flash_bwd_impl`."""
    bh, seq, d = q.shape
    d_v = v.shape[-1]
    lanes = lse.shape[-1]
    steps, kept = _dkv_steps(seq, block_q, block_k, causal, window, blockdiff)
    # the q group's operands: q, do, o and the float32 lse
    group_rows = (
        (d, q.dtype.itemsize), (d_v, do.dtype.itemsize),
        (d_v, o.dtype.itemsize), (lanes, 4),
    )
    members = _members_per_step(group, block_q, *group_rows)
    parts = group // members
    grid = (bh // group, len(steps), parts)

    def q_block(b, step, part, steps_ref):
        return b * parts + part, _step_fields(steps_ref[step])[1], 0

    def kv_block(b, step, part, steps_ref):
        return b, _step_fields(steps_ref[step])[0], 0

    in_specs = [
        pl.BlockSpec((members, block_q, d), q_block),
        pl.BlockSpec((1, block_k, d), kv_block),
        pl.BlockSpec((1, block_k, d_v), kv_block),
        pl.BlockSpec((members, block_q, d_v), q_block),
        pl.BlockSpec((members, block_q, d_v), q_block),
        pl.BlockSpec((members, block_q, lanes), q_block),
    ]
    operands = [q, k, v, do, o, lse]
    if lens is not None:
        in_specs.append(_lens_spec())
        operands.append(lens)
    limit = _staging_params(members * block_q, *group_rows)
    # what the grid holds beside what the mask keeps
    with _call_span(
        "flash_dkv", q, v, grid, causal=causal,
        window=window, padded=lens is not None, blockdiff=blockdiff,
        block_q=block_q, block_k=block_k, group=group, params=limit,
        kept_tiles=grid[0] * kept * parts, kv_rows=grid[0],
        members_per_step=members,
    ):
        return pl.pallas_call(
            functools.partial(
                _dkv_kernel_blocked, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, padded=lens is not None,
                n_q=seq // block_q, window=window, blockdiff=blockdiff,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=in_specs,
                out_specs=[
                    pl.BlockSpec((1, block_k, d), kv_block),
                    pl.BlockSpec((1, block_k, d_v), kv_block),
                ],
                scratch_shapes=[
                    pltpu.VMEM((block_k, d), jnp.float32),
                    pltpu.VMEM((block_k, d_v), jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=getattr(limit, "vmem_limit_bytes", None),
            ),
            interpret=_interpret(),
            name="flash_dkv",
        )(steps, *operands)
