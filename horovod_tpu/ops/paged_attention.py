"""Paged flash attention: the serving attention read, fused over the
page pool.

The paged memory plane (`serving/paged_kv.py`) stores KV in a physical
block pool ``[num_pages, page_tokens, kv_heads, head_dim]`` per layer,
with each slot mapping its sequence through an int32 page table. Until
this kernel, the attention READ re-assembled every slot's pages into a
transient contiguous ``[slots, max_len, kv_heads, head_dim]`` view
inside the prefill/decode executables (``jnp.take`` over the pool) —
a full-cache-size HBM copy per decode step before a single attention
FLOP ran. This kernel deletes that copy: the Pallas grid walks each
slot's page-table row via scalar prefetch and streams K/V blocks
straight from the pool into VMEM, one page per grid step, with the
FlashAttention-2 online softmax accumulating across pages. The gather
buffer does not exist in the lowered program (asserted by the
``serve_paged_attn`` hlo_audit program), and HBM reads scale with each
slot's LIVE tokens (the loop bound clamps at the slot's page frontier)
instead of ``slots × max_len``.

Layout/contract (the `ops/flash_attention.py` mold):

* grid ``(batch, kv_heads, n_logical_pages)`` — the page axis is the
  innermost (sequential) dimension, so the online-softmax state lives
  in VMEM scratch across page steps. All ``r = heads / kv_heads``
  query heads of a KV head ride one grid step (the GQA analog of the
  flash kernel's ``b // r`` index map: K/V pages are fetched once per
  KV head, never repeated per query head).
* the page table and per-slot lengths are SCALAR-PREFETCH operands
  (``pltpu.PrefetchScalarGridSpec``): the K/V BlockSpec index maps read
  the table to pick each step's physical page, which is exactly how the
  gather disappears — page indirection happens in the DMA descriptor,
  not as a materialized HBM copy.
* steps past a slot's live frontier clamp their index map to the last
  live page (Mosaic elides the re-fetch of an unchanged block) and are
  ``pl.when``-masked out of the accumulation, so ragged multi-slot
  batches pay HBM bytes for live tokens only.
* numerics mirror the dense gather path op-for-op where it is free
  (fp32 scores, the same ``/ sqrt(head_dim)``, the same −1e30 mask);
  the one structural difference is the online softmax's reassociated
  denominator sum, which bounds the divergence at ≤1 ulp of the dense
  ``jax.nn.softmax`` result (greedy tokens are identical — the parity
  tests in tests/test_paged_attention.py pin both).
* RoPE needs nothing here: q and the written k are rotated BEFORE the
  cache write (`models/transformer.py`), so pool contents are already
  position-encoded.

Interpret mode runs the same kernel on CPU (tests + the dryrun bench
leg exercise the real code path). Callers gate through
:func:`unsupported_reason` — the backward-compatible fallback ladder
(non-dividing head dims, oversized pages vs the VMEM budget) falls
back LOUDLY to the gather path and is counted
(``serve.paged_attn_fallbacks``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _NEG_INF, _STATS_LANES, _interpret, _vmem_budget

# Mosaic tile floors on real TPU: lanes (minor dim) and sublanes. The
# interpret path has no layout rules, so CPU tests run any geometry.
_LANES = 128
_SUBLANES = 8


def fwd_vmem_bytes(
    queries: int, head_dim: int, page_tokens: int
) -> int:
    """Worst-case VMEM bytes one grid step stages: the q block and fp32
    accumulator (``queries`` = q rows × grouped query heads), the
    double-buffered K/V page pair, the m/l statistics lanes, and the
    output block. The same budget discipline as the flash backward's
    ``bwd_vmem_bytes`` — shapes whose estimate exceeds
    ``HOROVOD_FLASH_VMEM_BUDGET`` ride the gather path instead."""
    q_rows = max(int(queries), 1)
    d = max(int(head_dim), 1)
    pt = max(int(page_tokens), 1)
    fp32 = 4
    q_block = q_rows * d * fp32
    acc = q_rows * d * fp32
    out = q_rows * d * fp32
    kv = 2 * 2 * pt * d * fp32  # k + v, double-buffered pipeline
    stats = 2 * q_rows * _STATS_LANES * fp32
    return q_block + acc + out + kv + stats


def unsupported_reason(
    head_dim: int,
    page_tokens: int,
    *,
    queries: int = 1,
    backend: Optional[str] = None,
) -> Optional[str]:
    """The fallback ladder, one rung per return: None means the kernel
    path is usable for this geometry; a string names the rung (callers
    log it loudly and count ``serve.paged_attn_fallbacks``)."""
    backend = backend or jax.default_backend()
    if backend == "tpu":
        # Mosaic layout floors apply only on real hardware — interpret
        # mode (CPU tests, dryrun benches) runs any geometry.
        if head_dim % _LANES:
            return (
                f"head_dim {head_dim} does not divide the {_LANES}-lane "
                "MXU tile"
            )
        if page_tokens % _SUBLANES:
            return (
                f"page_tokens {page_tokens} is not {_SUBLANES}-sublane "
                "aligned"
            )
    est = fwd_vmem_bytes(queries, head_dim, page_tokens)
    budget = _vmem_budget()
    if est > budget:
        return (
            f"VMEM estimate {est} B exceeds the budget {budget} B "
            "(oversized page_tokens or prefill chunk; "
            "HOROVOD_FLASH_VMEM_BUDGET)"
        )
    return None


def _kernel(
    tbl_ref,
    lens_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    t: int,
    r: int,
    page_tokens: int,
    causal: bool,
    sqrt_d: float,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    rows = q_ref.shape[2]  # t * r, padded up to whole sublanes
    start = lens_ref[b]
    kv_len = start + t
    n_live = (kv_len + page_tokens - 1) // page_tokens

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < n_live)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)  # [rows, d]
        k = k_ref[0].astype(jnp.float32)  # [page_tokens, d]
        v = v_ref[0].astype(jnp.float32)
        # same op order as the dense oracle: fp32 score matmul, THEN
        # the / sqrt(head_dim) — scaling q first would round differently
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / sqrt_d  # [rows, page_tokens]
        # row i of the packed [t*r] rows is query position start + i//r
        q_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_tokens), 0
        ) // r
        key_pos = j * page_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_tokens), 1
        )
        if causal:
            s = jnp.where(key_pos <= q_pos, s, _NEG_INF)
        s = jnp.where(key_pos < kv_len, s, _NEG_INF)
        m = m_ref[:, :1]  # [rows, 1] — lanes are broadcast copies
        l = l_ref[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l, l_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_attention(
    q,
    k_pool,
    v_pool,
    page_table,
    lengths,
    *,
    causal: bool = True,
):
    """Attention of ``q`` against paged KV, read straight from the pool.

    Args:
      q: ``[batch, t, num_heads, head_dim]`` queries (RoPE already
        applied by the caller). ``t`` is 1 for decode, the chunk width
        for prefill.
      k_pool / v_pool: the physical block pools,
        ``[num_pages, page_tokens, kv_heads, head_dim]`` — this call's
        k/v already scattered in (the write stays pure XLA; only the
        read is fused here).
      page_table: ``[batch, n_logical]`` int32 — each row maps the
        slot's logical pages to physical pool pages. Sentinel /
        out-of-range entries are clamped in the index map; the length
        bound keeps them unattendable, exactly like the gather path's
        ``mode="clip"``.
      lengths: ``[batch]`` int32 — tokens already cached BEFORE this
        call (the engine's ``cache_index``); live KV length is
        ``lengths + t``.
      causal: apply the global causal mask ``key_pos <= query_pos``
        (serving decode is always causal; the flag exists for the
        mold's sake and symmetry with :func:`flash_attention`).

    Returns ``[batch, t, num_heads, head_dim]`` in q's dtype.
    """
    b, t, h, d = q.shape
    num_pages, page_tokens, kvh, dk = k_pool.shape
    if v_pool.shape != k_pool.shape:
        raise ValueError(
            f"k_pool {k_pool.shape} vs v_pool {v_pool.shape} mismatch"
        )
    if dk != d:
        raise ValueError(f"head_dim mismatch: q has {d}, pool has {dk}")
    if h % kvh:
        raise ValueError(
            f"num_heads ({h}) must be a multiple of kv_heads ({kvh})"
        )
    r = h // kvh
    page_table = jnp.asarray(page_table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(b)
    n_logical = page_table.shape[1]
    if page_table.shape[0] != b:
        raise ValueError(
            f"page_table rows ({page_table.shape[0]}) != batch ({b})"
        )
    # Mosaic wants the last two dims of every block 8x128-divisible or
    # equal to the array's, and a block may not pick one head out of
    # the second-minor dim. So the kernel sees 2-D tiles: q/out ride
    # ``[b, kv_heads, t*r, d]`` (a transpose of the small operand; rows
    # are position-major, so row i is query position i // r, padded up
    # to whole sublanes) and the pools are viewed ``[pages, page_tokens,
    # kv_heads*d]`` (free: heads are contiguous), where a ``(1,
    # page_tokens, d)`` block at lane offset ``kv`` IS one head's page.
    rows = -(-t * r // _SUBLANES) * _SUBLANES
    q_rows = q.reshape(b, t, kvh, r, d).transpose(0, 2, 1, 3, 4)
    q_rows = q_rows.reshape(b, kvh, t * r, d)
    if rows != t * r:
        q_rows = jnp.pad(q_rows, ((0, 0), (0, 0), (0, rows - t * r), (0, 0)))
    k_flat = k_pool.reshape(num_pages, page_tokens, kvh * d)
    v_flat = v_pool.reshape(num_pages, page_tokens, kvh * d)
    last_page = num_pages - 1

    def _page(bi, kv, j, tbl, lens):
        # steps past the slot's live frontier re-address the last live
        # page: Mosaic skips the DMA for an unchanged block, so dead
        # grid steps cost no HBM bytes (pl.when masks their compute)
        n_live = (lens[bi] + t + page_tokens - 1) // page_tokens
        jj = jnp.minimum(j, n_live - 1)
        return (jnp.minimum(tbl[bi, jj], last_page), 0, kv)

    def _rows(bi, kv, j, tbl, lens):
        return (bi, kv, 0, 0)

    kernel = functools.partial(
        _kernel,
        r=r,
        page_tokens=page_tokens,
        causal=causal,
        sqrt_d=float(math.sqrt(d)),
        t=t,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, n_logical),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), _rows),
            pl.BlockSpec((1, page_tokens, d), _page),
            pl.BlockSpec((1, page_tokens, d), _page),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d), _rows),
        scratch_shapes=[
            pltpu.VMEM((rows, _STATS_LANES), jnp.float32),
            pltpu.VMEM((rows, _STATS_LANES), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, d), q.dtype),
        interpret=_interpret(),
        name="paged_attention",
    )(page_table, lengths, q_rows, k_flat, v_flat)
    out = out[:, :, : t * r].reshape(b, kvh, t, r, d)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, t, h, d)
