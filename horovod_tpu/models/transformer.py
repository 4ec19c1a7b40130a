"""Transformer family covering the reference's language-model benchmark
configs (BASELINE.json: BERT-large pretraining, GPT-2 medium [V]).

One configurable implementation: ``causal=True`` → GPT-2-style decoder;
``causal=False`` → BERT-style encoder. TPU-first: bfloat16 activations,
fp32 layernorm/softmax accumulation, static shapes, head dims sized for
the MXU (multiples of 128 at real scale). `remat` trades the blocks'
activations for recomputation in the backward; how much it keeps is
decided from the shapes and the device's memory (:func:`remat_plan`):
the matmul and flash-kernel outputs where they fit a quarter of the
device, else only each block's input.

The distributed execution path (tp/sp/pp/ep over a mesh) lives in
horovod_tpu/parallel/ — this module is the single-chip / pure-DP model.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import tracing as _tracing
from ..common.logging import get_logger
from ..common.metrics import registry as _metrics
from ..ops.flash_attention import DEFAULT_BLOCK as _DEFAULT_FLASH_BLOCK
from ..ops.flash_attention import RESIDUAL_NAMES as _FLASH_RESIDUAL_NAMES

_log = get_logger("models.transformer")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    d_ff: int = 3072
    max_len: int = 1024
    causal: bool = True
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    # Rematerialize every block in the backward (training only). What a
    # block keeps besides its input is not a setting: remat_plan()
    # decides it from the per-chip shapes and the device's memory limit.
    remat: bool = False
    # Blockwise Pallas attention (ops/flash_attention.py): True/False,
    # or "auto" = use it on TPU whenever no padding mask is passed (the
    # flash path implements the causal mask itself; arbitrary padding
    # masks stay on the dense path, and off-TPU the interpret-mode
    # kernel would only be overhead). True forces it on any backend.
    flash_attention: Any = "auto"
    # Flash kernel block sizes (tunable: bigger blocks = fewer K/V loop
    # iterations and larger MXU matmuls, more VMEM per program). Auto-
    # shrunk to the sequence length when it is shorter. The default
    # (ops.flash_attention.DEFAULT_BLOCK = 512) won the round-4 on-chip
    # sweep on GPT-2-medium seq-512 (83.0 samp/s / MFU 0.563 vs 60.3 /
    # 0.409 at 128 — bench_results/gpt2_blk*_r04); VMEM per program
    # stays modest because K/V are staged whole-sequence regardless of
    # block_k, so bigger blocks only grow the (block_q, block_k) score
    # tile (512x512 fp32 = 1 MiB).
    flash_block_q: int = _DEFAULT_FLASH_BLOCK
    flash_block_k: int = _DEFAULT_FLASH_BLOCK
    # Rotary position embeddings (Llama/Mistral-style) applied to q/k
    # inside every attention block. When on, the learned absolute
    # position embedding is skipped — RoPE carries all position signal.
    # Orthogonal to the flash kernels (the rotation happens on q/k
    # before they enter attention).
    rope: bool = False
    rope_base: float = 10000.0
    # Mistral-style causal sliding window (requires causal=True): row r
    # attends (r-window, r]. On the flash path the band is masked
    # in-kernel with the block loops clamped to it; the dense path
    # builds the band mask explicitly.
    sliding_window: Optional[int] = None
    # Grouped-query attention (Llama/Mistral-style): number of KV heads
    # (must divide num_heads). None = MHA (one kv head per q head, the
    # fused qkv projection — param-tree-compatible with existing
    # checkpoints). Setting it splits the projection into "q" and "kv"
    # and the kernels read shared KV rows directly (no repeat ever
    # materializes).
    num_kv_heads: Optional[int] = None
    # Switch-MoE FFN (PR 12): 0 = the dense FFN (param-tree-compatible
    # with existing checkpoints). >0 replaces every block's FFN with a
    # top-1-routed expert bank of this many experts — the serving twin
    # of parallel/moe.py's moe_ffn. Routing is DATA (argmax over the
    # router logits), shapes are static (every expert's weights are
    # applied through a one-hot einsum), so the serving engine's
    # zero-retrace invariant holds: decode_compiles==1 across rolling
    # admissions with routing changing per token. Expert weights are
    # stacked on a leading [E] axis — `shard_moe_params` places them
    # over a mesh 'ep' axis for expert-sharded decode (GSPMD partitions
    # the expert einsums; hvd.serve threads it via engine ep_axis=).
    moe_experts: int = 0
    # LM head precision. True (default): bf16 operands on the MXU with
    # fp32 accumulation (preferred_element_type) and fp32 logits out —
    # the standard TPU head recipe; input rounding is bf16-epsilon on
    # logits while softmax/loss stay full fp32. False: the all-fp32
    # head (operands cast up, matmul at fp32 MXU rate — several times
    # slower on a vocab_size-wide projection that is ~15% of forward
    # FLOPs at GPT-2 scale).
    head_mixed_precision: bool = True

    def wants_flash(self) -> bool:
        """The configuration half of the flash gate: ``True``/``False``
        as given, ``"auto"`` = on a TPU backend (off it the interpret-
        mode kernel would only be overhead)."""
        if self.flash_attention == "auto":
            return jax.default_backend() == "tpu"
        return bool(self.flash_attention)

    def flash_decline_reason(self, mask=None, seq=None) -> Optional[str]:
        """The shape half: why this call cannot ride the Pallas flash
        kernels, or None. Pass ``seq`` when known."""
        if mask is not None:
            return (
                "an arbitrary padding mask was passed (the kernels mask "
                "causal and lengths= only; pass lengths for right-padded "
                "batches)"
            )
        if seq is None:
            return None
        from ..ops.flash_attention import (
            bwd_vmem_bytes,
            fits_vmem,
            supports_seq,
        )

        if not supports_seq(seq, self.flash_block_q, self.flash_block_k):
            # untileable lengths (e.g. ViT's 197 tokens) would fail
            # Mosaic's block constraints
            return f"seq {seq} tiles no 8-aligned block"
        head_dim = self.d_model // self.num_heads
        group = self.num_heads // (self.num_kv_heads or self.num_heads)
        itemsize = jnp.dtype(self.dtype).itemsize
        if not fits_vmem(seq, head_dim, group, itemsize, self.flash_block_k):
            # the backward dK/dV kernel stages the whole q-head group
            # whole-sequence
            est = bwd_vmem_bytes(
                seq, head_dim, group, itemsize, self.flash_block_k
            )
            return (
                f"the dK/dV backward would stage ~{est / 2**20:.0f} MiB "
                f"(seq {seq}, head_dim {head_dim}, {group} q heads per kv "
                "head), over the VMEM budget"
            )
        return None

    def uses_flash(self, mask=None, seq=None) -> bool:
        """THE gating rule for the Pallas flash path — single source
        of truth for the model and for bench_lm's FLOPs correction."""
        return (
            self.wants_flash()
            and self.flash_decline_reason(mask, seq) is None
        )

    @staticmethod
    def gpt2_medium() -> "TransformerConfig":
        """BASELINE.json config #4 (GPT-2 medium, 345M)."""
        return TransformerConfig(
            num_layers=24, d_model=1024, num_heads=16, d_ff=4096, causal=True
        )

    @staticmethod
    def bert_large() -> "TransformerConfig":
        """BASELINE.json config #3 (BERT-large, 340M)."""
        return TransformerConfig(
            vocab_size=30522,
            num_layers=24,
            d_model=1024,
            num_heads=16,
            d_ff=4096,
            max_len=512,
            causal=False,
        )

    @staticmethod
    def tiny(causal: bool = True) -> "TransformerConfig":
        """Test-sized config."""
        return TransformerConfig(
            vocab_size=256,
            num_layers=2,
            d_model=64,
            num_heads=4,
            d_ff=128,
            max_len=128,
            causal=causal,
            dtype=jnp.float32,
        )


def apply_rope(x, base: float = 10000.0, offset=0):
    """Rotate [batch, seq, heads, head_dim] q or k by absolute position
    (RoFormer). Pairs are (x[..., :d/2], x[..., d/2:]) — the
    'rotate-half' convention — so the op is two multiplies and one
    concat, fully XLA-fusible. fp32 trig regardless of input dtype;
    ``offset`` shifts positions: a scalar (sequence-parallel shards
    pass their global start — may be a traced value, e.g.
    axis_index·t_local) or a ``[batch]`` array (incremental decode:
    every cache slot sits at its own position)."""
    b, t, h, d = x.shape
    half = d // 2
    if d % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {d}")
    # offset + iota rather than arange(offset, ...) so traced offsets
    # (SP shards, decode cache indices) work
    pos = jnp.asarray(offset, jnp.float32)[..., None] + jnp.arange(
        t, dtype=jnp.float32
    )  # [t] for scalar offsets, [b, t] for per-slot offsets
    inv_freq = base ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = pos[..., :, None] * inv_freq  # [(b,) t, half]
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    if angles.ndim == 2:  # scalar offset: broadcast over batch
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def init_cache(cfg: TransformerConfig, batch: int, max_len=None, dtype=None):
    """Allocate an empty decode KV cache: one ``{"k", "v"}`` dict per
    layer, each ``[batch, max_len, num_kv_heads, head_dim]`` of zeros.

    This is the model half of the serving contract
    (horovod_tpu/serving/): the cache rides
    ``Transformer.__call__(cache=, cache_index=)`` — written in place
    (functionally) at each call's positions and returned updated, so a
    jitted decode step can donate it through successive steps. Slots
    never need re-zeroing on reuse: positions at or beyond a slot's
    ``cache_index`` are masked out of attention and every attended
    position is overwritten by prefill/decode before it first becomes
    attendable."""
    seq = int(max_len) if max_len is not None else cfg.max_len
    if not cfg.rope and seq > cfg.max_len:
        # the learned position table has cfg.max_len rows; a longer
        # cache would let decode feed positions past it, and the jitted
        # gather CLAMPS out-of-range indices instead of raising —
        # silently wrong logits, so refuse here where it is loud
        raise ValueError(
            f"KV cache max_len ({seq}) exceeds the learned position "
            f"table ({cfg.max_len}); raise cfg.max_len or use rope=True"
        )
    kv_heads = cfg.num_kv_heads or cfg.num_heads
    head_dim = cfg.d_model // cfg.num_heads
    dt = cfg.dtype if dtype is None else dtype
    return [
        {
            "k": jnp.zeros((batch, seq, kv_heads, head_dim), dt),
            "v": jnp.zeros((batch, seq, kv_heads, head_dim), dt),
        }
        for _ in range(cfg.num_layers)
    ]


class MultiHeadAttention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None, lengths=None, cache=None,
                 cache_index=None, pages=None, paged_attn=False):
        cfg = self.cfg
        head_dim = cfg.d_model // cfg.num_heads
        if cache is not None:
            if not cfg.causal:
                raise ValueError(
                    "incremental decode (cache=) requires causal=True"
                )
            if mask is not None or lengths is not None:
                raise ValueError(
                    "cache= does not compose with mask=/lengths=: the "
                    "cache_index IS the per-slot length"
                )
        elif pages is not None:
            raise ValueError(
                "pages= (the paged-KV page table) requires cache="
            )
        if cfg.num_kv_heads:
            if cfg.num_heads % cfg.num_kv_heads:
                raise ValueError(
                    f"num_kv_heads ({cfg.num_kv_heads}) must divide "
                    f"num_heads ({cfg.num_heads})"
                )
            q = nn.DenseGeneral(
                (cfg.num_heads, head_dim), dtype=cfg.dtype, name="q"
            )(x)
            kv = nn.DenseGeneral(
                (2, cfg.num_kv_heads, head_dim), dtype=cfg.dtype,
                name="kv",
            )(x)
            k, v = kv[..., 0, :, :], kv[..., 1, :, :]
        else:
            qkv = nn.DenseGeneral(
                (3, cfg.num_heads, head_dim), dtype=cfg.dtype, name="qkv"
            )(x)
            q, k, v = (
                qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
            )
        if cfg.rope:
            rope_offset = 0 if cache is None else cache_index
            q = apply_rope(q, cfg.rope_base, offset=rope_offset)
            k = apply_rope(k, cfg.rope_base, offset=rope_offset)
        if cache is not None:
            return self._cached_attention(cfg, x, q, k, v, cache,
                                          cache_index, head_dim,
                                          pages=pages,
                                          paged_attn=paged_attn)
        # lengths (right-padding) stays on the flash path — the kernels
        # take it natively; only ARBITRARY masks force dense.
        wanted = cfg.wants_flash()
        declined = (
            cfg.flash_decline_reason(mask, seq=x.shape[1]) if wanted else None
        )
        use_flash = wanted and declined is None
        if wanted and not use_flash:
            # the shape dispatch stays, but never silently: a model
            # that was meant to run the kernels and runs dense attention
            # says so and is counted (like serve.paged_attn_fallbacks)
            _log.warning(
                "flash attention is on (flash_attention=%r) but %s; "
                "this call runs dense attention",
                cfg.flash_attention, declined,
            )
            _metrics.counter("flash.dense_fallbacks")
        if use_flash:
            from ..ops.flash_attention import flash_attention

            out = flash_attention(
                q, k, v, causal=cfg.causal,
                block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                lengths=lengths, window=cfg.sliding_window,
            )
            return nn.DenseGeneral(
                cfg.d_model, axis=(-2, -1), dtype=cfg.dtype, name="out"
            )(out)
        if cfg.num_kv_heads and cfg.num_kv_heads != cfg.num_heads:
            # dense fallback materializes the head repeat the flash
            # path avoids
            rep = cfg.num_heads // cfg.num_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        # scores in fp32 for softmax stability
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        ) / jnp.sqrt(head_dim).astype(jnp.float32)
        if cfg.causal:
            t = x.shape[1]
            causal_mask = jnp.tril(jnp.ones((t, t), bool))
            if cfg.sliding_window:
                rows = jnp.arange(t)[:, None]
                cols = jnp.arange(t)[None, :]
                causal_mask = causal_mask & (
                    rows - cols < cfg.sliding_window
                )
            scores = jnp.where(causal_mask[None, None], scores, -1e30)
        elif cfg.sliding_window:
            raise ValueError("sliding_window requires causal=True")
        valid = None
        if lengths is not None:
            # dense twin of the kernel's lengths contract; combined
            # (AND) with an explicit mask rather than ignored, so
            # mask+lengths callers never have valid rows attending to
            # keys past the length
            valid = (
                jnp.arange(x.shape[1])[None, :]
                < jnp.asarray(lengths)[:, None]
            )
            mask = valid if mask is None else (mask & valid)
        if mask is not None:
            scores = jnp.where(mask[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        if valid is not None:
            # match the flash path: padded query rows are zero
            out = jnp.where(valid[:, :, None, None], out, 0.0)
        return nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), dtype=cfg.dtype, name="out"
        )(out)

    def _cached_attention(self, cfg, x, q, k, v, cache, cache_index,
                          head_dim, pages=None, paged_attn=False):
        """Incremental-decode attention: write this call's k/v into the
        per-slot cache at ``cache_index`` (each batch row at its own
        position — prefill passes t=prompt tokens at index 0, decode
        passes t=1 at index=length), then attend q against the FULL
        cache under the global causal mask ``key_pos <= query_pos``.
        Positions at or beyond a slot's write frontier are masked to
        exact −1e30 → exact-zero probabilities, so stale slot contents
        (a reused slot, bucket padding) can never leak into the output
        and the dense path stays bit-comparable with the full-sequence
        forward. Returns ``(out, {"k", "v"})`` — the updated cache.

        Two cache layouts share this math:

        * contiguous slab (``pages=None``): per-slot rows
          ``[batch, max_len, kv_heads, head_dim]``, vmapped
          ``dynamic_update_slice`` writes;
        * paged (``pages=[batch, n_pages]`` int32 page table over a
          ``[num_pages, page_tokens, ...]`` block pool,
          `serving/paged_kv.py`): writes scatter into physical pages
          (``pool.at[phys, offset].set(..., mode="drop")`` — the
          sentinel/out-of-range entries of unallocated logical pages
          drop their writes, exactly the pad positions the slab path
          masks away), reads gather the slot's pages back into a
          transient contiguous view. Because a slot's pages tile
          ``max_len`` exactly, the gathered view has the SAME shape and
          the SAME values at every attendable position as the slab
          row, so the attention below is bit-identical between
          layouts — the serving plane's paged-parity contract.

        ``paged_attn=True`` (paged layout only) replaces the
        gather-then-attend READ with the fused Pallas kernel
        (`ops/paged_attention.py`): the kernel's grid walks the page
        table and streams K/V blocks straight from the pool, so the
        transient contiguous view never exists in the lowered program.
        The write scatter above is unchanged, the gather path stays the
        default-off numerics oracle, and unsupported geometries fall
        back to it loudly (``serve.paged_attn_fallbacks``). Outputs
        agree with the oracle to ≤1 ulp of the fp32 softmax (the online
        softmax reassociates the denominator sum) — greedy argmax
        tokens are identical.
        """
        b, t = x.shape[0], x.shape[1]
        idx = jnp.asarray(cache_index, jnp.int32)

        if pages is None:
            seq = cache["k"].shape[1]

            def _write(buf, new, i):
                return jax.lax.dynamic_update_slice(
                    buf, new.astype(buf.dtype), (i, 0, 0)
                )

            k_cache = jax.vmap(_write)(cache["k"], k, idx)
            v_cache = jax.vmap(_write)(cache["v"], v, idx)
        else:
            pages = jnp.asarray(pages, jnp.int32)
            num_pages, page_tokens = cache["k"].shape[:2]
            n_logical = pages.shape[1]
            seq = n_logical * page_tokens
            pos = idx[:, None] + jnp.arange(t)            # [b, t] global
            lp = pos // page_tokens
            off = pos % page_tokens
            # physical page per written token; positions past the table
            # (bucket-pad overhang) route to the out-of-range sentinel
            # and are dropped — they could never become attendable
            phys = jnp.take_along_axis(
                pages, jnp.clip(lp, 0, n_logical - 1), axis=1
            )
            phys = jnp.where(lp < n_logical, phys, num_pages)

            def _scatter(pool, new):
                return pool.at[phys, off].set(
                    new.astype(pool.dtype), mode="drop"
                )

            k_cache = _scatter(cache["k"], k)
            v_cache = _scatter(cache["v"], v)
        new_cache = {"k": k_cache, "v": v_cache}
        if pages is not None and paged_attn:
            from ..ops import paged_attention as _pa

            r = cfg.num_heads // (cfg.num_kv_heads or cfg.num_heads)
            reason = _pa.unsupported_reason(
                head_dim, page_tokens, queries=t * r
            )
            if reason is None and cfg.sliding_window:
                reason = (
                    "sliding_window is not implemented by the paged "
                    "kernel"
                )
            if reason is None:
                out = _pa.paged_attention(
                    q, k_cache, v_cache, pages, idx, causal=True
                )
                return nn.DenseGeneral(
                    cfg.d_model, axis=(-2, -1), dtype=cfg.dtype,
                    name="out",
                )(out), new_cache
            # loud fallback ladder: requested the kernel, geometry (or
            # backend) can't take it — warn at trace time, count it,
            # and ride the gather oracle below
            import warnings

            from ..common.metrics import registry as _metrics

            warnings.warn(
                f"paged_attn=True but the kernel path is unsupported "
                f"({reason}); falling back to the gather read",
                stacklevel=2,
            )
            _metrics.counter("serve.paged_attn_fallbacks")
        if pages is None:
            kk, vv = k_cache, v_cache
        else:
            # gather-from-pages read: reassemble each row's pages in
            # logical order (sentinel entries clamp into arbitrary
            # garbage the causal mask below zeroes exactly)
            def _gather(pool):
                g = jnp.take(pool, pages, axis=0, mode="clip")
                return g.reshape(b, seq, *pool.shape[2:])

            kk, vv = _gather(k_cache), _gather(v_cache)
        if cfg.num_kv_heads and cfg.num_kv_heads != cfg.num_heads:
            rep = cfg.num_heads // cfg.num_kv_heads
            kk = jnp.repeat(kk, rep, axis=2)
            vv = jnp.repeat(vv, rep, axis=2)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, kk, preferred_element_type=jnp.float32
        ) / jnp.sqrt(head_dim).astype(jnp.float32)
        q_pos = idx[:, None] + jnp.arange(t)          # [b, t] global
        key_pos = jnp.arange(seq)                     # [seq]
        valid = key_pos[None, None, :] <= q_pos[:, :, None]  # [b, t, seq]
        if cfg.sliding_window:
            valid = valid & (
                q_pos[:, :, None] - key_pos[None, None, :]
                < cfg.sliding_window
            )
        scores = jnp.where(valid[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        return nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), dtype=cfg.dtype, name="out"
        )(out), new_cache


class MoEFFN(nn.Module):
    """Switch-style top-1 MoE FFN for decode/serving: router logits in
    fp32, argmax routing (pure DATA — shapes never depend on it), and
    the expert bank applied through dense one-hot einsums over the
    leading ``[E]`` axis (MXU-friendly, no gather/scatter; at decode
    scale — slots tokens per step — the E-fold FLOPs are noise next to
    attention over the cache, and under an 'ep'-sharded bank GSPMD
    partitions the einsum so each shard computes only its experts).
    Dropped-token capacity logic does not exist here: every token is
    served by exactly its routed expert, gated by the router prob —
    exact, static, retrace-free."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        e = cfg.moe_experts
        d, f = cfg.d_model, cfg.d_ff
        logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            x.astype(jnp.float32)
        )  # [b, t, E]
        probs = jax.nn.softmax(logits, axis=-1)
        idx = jnp.argmax(probs, axis=-1)  # [b, t]
        gate = jnp.take_along_axis(probs, idx[..., None], axis=-1)
        sel = jax.nn.one_hot(idx, e, dtype=cfg.dtype)  # [b, t, E]
        scale = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1)
        w1 = self.param("w1", scale, (e, d, f), jnp.float32)
        b1 = self.param("b1", nn.initializers.zeros, (e, f), jnp.float32)
        w2 = self.param("w2", scale, (e, f, d), jnp.float32)
        b2 = self.param("b2", nn.initializers.zeros, (e, d), jnp.float32)
        w1, b1 = w1.astype(cfg.dtype), b1.astype(cfg.dtype)
        w2, b2 = w2.astype(cfg.dtype), b2.astype(cfg.dtype)
        h = jnp.einsum("btd,edf,bte->btf", x, w1, sel)
        h = h + jnp.einsum("ef,bte->btf", b1, sel)
        h = nn.gelu(h)
        y = jnp.einsum("btf,efd,bte->btd", h, w2, sel)
        y = y + jnp.einsum("ed,bte->btd", b2, sel)
        # cfg.dtype, not x.dtype: the input is the fp32 LayerNorm
        # output, and the dense FFN branch this replaces emits
        # cfg.dtype activations — the residual contract must match
        return (y * gate).astype(cfg.dtype)


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None, train: bool = True, lengths=None,
                 cache=None, cache_index=None, pages=None,
                 paged_attn=False):
        cfg = self.cfg
        h = nn.LayerNorm(dtype=jnp.float32)(x)
        new_cache = None
        if cache is None:
            h = MultiHeadAttention(cfg)(h, mask, lengths)
        else:
            h, new_cache = MultiHeadAttention(cfg)(
                h, mask, lengths, cache=cache, cache_index=cache_index,
                pages=pages, paged_attn=paged_attn,
            )
        h = nn.Dropout(cfg.dropout_rate, deterministic=not train)(h)
        x = x + h
        h = nn.LayerNorm(dtype=jnp.float32)(x)
        if cfg.moe_experts:
            h = MoEFFN(cfg, name="moe")(h)
        else:
            h = nn.Dense(cfg.d_ff, dtype=cfg.dtype)(h)
            h = nn.gelu(h)
            h = nn.Dense(cfg.d_model, dtype=cfg.dtype)(h)
        h = nn.Dropout(cfg.dropout_rate, deterministic=not train)(h)
        if cache is None:
            return x + h
        return x + h, new_cache


def shard_moe_params(params, mesh, ep_axis: str = "ep"):
    """Place every MoE expert bank (``.../moe/{w1,b1,w2,b2}`` — the
    leading-``[E]`` stacked leaves of :class:`MoEFFN`) over the mesh's
    ``ep_axis`` with ``NamedSharding(P(ep_axis))``, leaving everything
    else exactly where it is — the serving engine's expert-sharding
    hook (``InferenceEngine(ep_axis=)``): under jit, GSPMD partitions
    the one-hot expert einsums so each shard computes only its local
    experts' FFN — expert-sharded dispatch inside the fixed-shape
    decode step, no shape (and so no retrace) anywhere. The router
    stays replicated (routing is per-token data every shard needs).
    No-op when the mesh lacks the axis, or the axis does not divide
    the expert count (loud — silent replication would quietly undo
    expert parallelism)."""
    import jax as _jax
    from jax.sharding import NamedSharding, PartitionSpec as _P

    if mesh is None or ep_axis not in mesh.axis_names:
        return params
    ep = mesh.shape[ep_axis]
    if ep <= 1:
        return params

    moe_leaves = {"w1", "b1", "w2", "b2"}

    def _walk(node, path):
        if isinstance(node, dict):
            return {k: _walk(v, path + (k,)) for k, v in node.items()}
        if len(path) >= 2 and path[-2] == "moe" and path[-1] in moe_leaves:
            if node.shape[0] % ep:
                raise ValueError(
                    f"moe_experts ({node.shape[0]}) must divide over "
                    f"the '{ep_axis}' mesh axis ({ep})"
                )
            return _jax.device_put(
                node, NamedSharding(mesh, _P(ep_axis))
            )
        return node

    return _walk(params, ())


class LMHead(nn.Module):
    """Vocabulary projection with the TPU mixed-precision recipe (see
    TransformerConfig.head_mixed_precision). Same param tree as the
    nn.Dense it replaces (kernel fp32 [d_model, vocab], bias fp32), so
    checkpoints are layout-compatible either way."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (cfg.d_model, cfg.vocab_size),
            jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (cfg.vocab_size,), jnp.float32
        )
        if cfg.head_mixed_precision:
            y = jax.lax.dot_general(
                x.astype(cfg.dtype),
                kernel.astype(cfg.dtype),
                dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            y = x.astype(jnp.float32) @ kernel
        return y + bias


# The largest share of the device's memory that remat's saved matmul and
# kernel outputs may take (PERF.md section 6, PR 26, has the two chip
# readings it rests on): past it the blocks recompute everything, as a
# user who set ``remat`` because memory is short expects.
REMAT_SAVE_SHARE = 0.25


def remat_plan(cfg: TransformerConfig, tokens: int, bytes_limit):
    """What ``Transformer(remat=True)`` keeps between forward and
    backward, from what the model knows at trace time: ``(mode,
    saved_bytes)`` for ``tokens`` tokens on this chip and a device
    memory of ``bytes_limit`` bytes (None: not known).

    ``save_matmuls``: each block keeps the outputs of its weight matmuls
    (qkv, the attention's output projection, the first feed-forward
    matmul) and of the flash forward (the attention output and one lane
    of ``lse``), and recomputes only the element-wise work; on the
    flash path q, k and v are kept as the kernels take them, in place of
    the projection's output, so the head transposes are not repeated
    either. ``saved_bytes`` is their size over all layers (the
    attention output is counted on the dense path too, which recomputes
    it). Taken when that is at most ``REMAT_SAVE_SHARE`` of the
    device. ``recompute_all``: each block keeps its input alone —
    where the saving would not fit, where the limit cannot be read (CPU)
    and for MoE blocks, whose expert einsums this reckoning does not
    cover. ``off``: ``cfg.remat`` is not set."""
    if not cfg.remat:
        return "off", 0
    if cfg.moe_experts or not bytes_limit:
        return "recompute_all", 0
    head_dim = cfg.d_model // cfg.num_heads
    kv_width = 2 * (cfg.num_kv_heads or cfg.num_heads) * head_dim
    # q + kv, attention output, output projection, first feed-forward
    width = 3 * cfg.d_model + kv_width + cfg.d_ff
    per_token = width * jnp.dtype(cfg.dtype).itemsize + 4 * cfg.num_heads
    saved = cfg.num_layers * int(tokens) * per_token
    if saved > REMAT_SAVE_SHARE * bytes_limit:
        return "recompute_all", 0
    return "save_matmuls", saved


def _device_bytes_limit() -> Optional[int]:
    """``bytes_limit`` of this process's first device; None where the
    backend reports no memory statistics (CPU)."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except jax.errors.JaxRuntimeError:
        return None
    return (stats or {}).get("bytes_limit")


def _save_matmuls_policy():
    """Checkpoint policy of ``save_matmuls``: weight matmuls are the
    ``dot_general``s without batch dimensions (the dense attention
    fallback's einsums have them, and are recomputed); the flash
    kernels' residuals go by name, since a policy on primitives does
    not see through a ``pallas_call``. An output the backward does not
    read (the second feed-forward matmul's; the qkv projection's where
    q, k and v are kept by name) is not kept."""
    policies = jax.checkpoint_policies
    return policies.save_from_both_policies(
        policies.dots_with_no_batch_dims_saveable,
        policies.save_only_these_names(*_FLASH_RESIDUAL_NAMES),
    )


_TRACE_MODEL_SPAN = "hvd.trainer.trace_model"


def _span_at_trace_time(call):
    """``hvd.trainer.trace_model`` around a model's ``__call__`` while
    JAX traces it (``tokens`` is a tracer): how long the model's Python
    takes under jit/grad is part of every cold start. An eager call
    pays one ``isinstance``."""

    @functools.wraps(call)
    def wrapped(self, tokens, *args, **kwargs):
        with _tracing.trace_time_span(
            _TRACE_MODEL_SPAN, tokens, layers=self.cfg.num_layers
        ):
            return call(self, tokens, *args, **kwargs)

    return wrapped


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    @_span_at_trace_time
    def __call__(
        self, tokens, mask=None, train: bool = True,
        return_hidden: bool = False, lengths=None,
        cache=None, cache_index=None, pages=None, paged_attn=False,
    ):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype)(tokens)
        if not cfg.rope:
            if cache is None:
                positions = jnp.arange(tokens.shape[1])[None]
            else:
                # incremental decode: each cache slot sits at its own
                # absolute position (its current length)
                positions = (
                    jnp.asarray(cache_index, jnp.int32)[:, None]
                    + jnp.arange(tokens.shape[1])
                )
            pos = nn.Embed(cfg.max_len, cfg.d_model, dtype=cfg.dtype)(
                positions
            )
            x = x + pos
        if cache is not None:
            # KV-cache-threaded forward (the serving engine's model
            # contract, horovod_tpu/serving/engine.py): same param
            # tree, same block stack, dense attention over the cache.
            # pages= switches the layout to the paged block pool
            # (serving/paged_kv.py) — the table is shared by every
            # layer, each layer's pool is its cache[i] entry.
            # remat is a backward-pass memory trade — inference-only
            # path, so it never wraps here.
            if return_hidden:
                raise ValueError("return_hidden with cache= is not supported")
            new_cache = []
            for i in range(cfg.num_layers):
                x, layer_cache = Block(cfg, name=f"block_{i}")(
                    x, mask, train, lengths,
                    cache=cache[i], cache_index=cache_index,
                    pages=pages, paged_attn=paged_attn,
                )
                new_cache.append(layer_cache)
            x = nn.LayerNorm(dtype=jnp.float32)(x)
            return LMHead(cfg, name="lm_head")(x), new_cache
        block = Block
        mode, saved_bytes = remat_plan(
            cfg, tokens.shape[0] * tokens.shape[1],
            _device_bytes_limit() if cfg.remat else None,
        )
        if mode != "off":
            policy = (
                _save_matmuls_policy() if mode == "save_matmuls" else None
            )
            block = nn.remat(Block, static_argnums=(3,), policy=policy)
        span = _tracing.current()
        if span is not None and span.name == _TRACE_MODEL_SPAN:
            span.tag(remat=mode, remat_saved_bytes=saved_bytes)
        for i in range(cfg.num_layers):
            x = block(cfg, name=f"block_{i}")(x, mask, train, lengths)
        x = nn.LayerNorm(dtype=jnp.float32)(x)
        if return_hidden:
            # pre-head activations for the chunked fused loss
            # (ops/fused_xent.py): callers apply the lm_head params
            # through fused_linear_cross_entropy and never materialize
            # the (tokens, vocab) logits. Param tree is unchanged —
            # init traces the default path below.
            return x
        # fp32 logits; matmul precision per cfg.head_mixed_precision
        return LMHead(cfg, name="lm_head")(x)
