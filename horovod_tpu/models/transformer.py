"""Transformer family covering the reference's language-model benchmark
configs (BASELINE.json: BERT-large pretraining, GPT-2 medium [V]).

One configurable implementation: ``causal=True`` → GPT-2-style decoder;
``causal=False`` → BERT-style encoder. TPU-first: bfloat16 activations,
fp32 layernorm/softmax accumulation, static shapes, head dims sized for
the MXU (multiples of 128 at real scale). `remat` trades the blocks'
activations for recomputation in the backward; how much it keeps is
decided from the shapes and the device's memory (:func:`remat_plan`),
the richest that fits a share of what the parameters' state leaves of
the device: the matmul and flash-kernel outputs, else what the flash
kernels' backward reads, else the flash forward kernel's outputs, else
only each block's input.

The same ``Block`` also builds the sparse long-context decoders
(``layer_kinds``): per layer a window or a full attention, with or
without RoPE, and a dense gated feed-forward or an expert layer that is
told which experts of the deployment it holds (:class:`ExpertFFN`);
RMSNorm in a sandwich, bias-free projections, QK-norm and a gated
attention output are fields of the one ``TransformerConfig``. A third
attention kind, ``latent``, is multi-head latent attention: keys and values
expanded from one low-rank row a token, a rotation on part of each head,
and a key wider than the value.

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
from ..ops.flash_attention import OUTPUT_NAMES as _FLASH_OUTPUT_NAMES
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
    # (ops.flash_attention.DEFAULT_BLOCK = 512) is what every cell of
    # the benchmark runs (PERF_LEDGER.jsonl: the kernels take 15.2 ms
    # of GPT-2 medium's step at 22-32% of their rooflines; whether a
    # smaller causal block does better at seq 512 is ROADMAP A3's open
    # question). VMEM per program stays modest because K/V are staged
    # whole-sequence regardless of block_k, so bigger blocks only grow
    # the (block_q, block_k) score tile (512x512 fp32 = 1 MiB).
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
    # ---- Per-layer kinds and the block's variants. Every default is the
    # GPT-2/BERT block the fields above describe.
    # One "<attention>/<feed-forward>" string per layer (its length must
    # be num_layers); None = every layer alike, as the fields above say.
    # Attention: "window" (the causal band of ``sliding_window``) or
    # "full" (causal, no band), each with "-nope" appended where the
    # layer carries no position rotation although ``rope`` is on; or
    # "latent" (causal, no band; the ``latent`` fields below).
    # Feed-forward: "dense" (``d_ff`` wide, gated where ``ffn_gated``)
    # or "experts" (:class:`ExpertFFN`, the ``moe_*`` fields below).
    # E.g. ("window/dense", "window/experts", "full-nope/experts").
    layer_kinds: Optional[tuple] = None
    # "latent" layers (multi-head latent attention without a low-rank q):
    # q is projected to ``num_heads`` heads of ``qk_nope_head_dim +
    # qk_rope_head_dim``; one joint projection gives ``kv_lora_rank``
    # latent columns, which are normed and expanded to every head's
    # ``qk_nope_head_dim`` of key and ``v_head_dim`` of value, and
    # ``qk_rope_head_dim`` columns of key that all heads share. Only the
    # rope parts are rotated (``rope_base``; ``rope`` must be on), so a
    # head's key is ``qk_nope_head_dim + qk_rope_head_dim`` wide, its
    # value ``v_head_dim``, and the scores' scale is the key width's
    # root. ``head_dim``, ``num_kv_heads``, ``qk_norm`` and
    # ``attn_output_gate`` are the other kinds'.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Rotated pairs are (x0, x1), (x2, x3), ... and not (x[i], x[i + d/2]):
    # apply_rope brings them to the half-split order first (and leaves them
    # there: q and k are permuted alike, so the scores are the same).
    rope_interleave: bool = False
    # Width of one attention head; None = d_model // num_heads. Set where
    # heads x head_dim is not d_model.
    head_dim: Optional[int] = None
    # "layernorm" (mean and bias) or "rmsnorm" (scale alone), in fp32.
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    # A second norm on each branch's output before the residual add:
    # x + norm(attn(norm(x))), x + norm(ffn(norm(x))).
    sandwich_norm: bool = False
    # Biases on the projections, the feed-forward and the head.
    use_bias: bool = True
    # Feed-forward W_d(silu(W_g x) * W_u x) in place of W_2 gelu(W_1 x).
    ffn_gated: bool = False
    # RMSNorm over head_dim on q and on k, before any rotation.
    qk_norm: bool = False
    # o = o * sigmoid(W_gate x) on the attention output, before W_o.
    attn_output_gate: bool = False
    # Multiplier on the token embedding's output (muP: sqrt(d_model)).
    embed_scale: float = 1.0
    # Expert layers ("experts" in layer_kinds). The router scores all
    # ``moe_experts_total`` experts and picks ``moe_top_k`` a token; this
    # chip holds the experts ``[first, last)`` of ``moe_experts_held``
    # (None = all) and computes their part of the result, dropless; what
    # experts held elsewhere would add is left out (the exchange across
    # chips is parallel/moe.py's, not the model's). ``moe_d_ff`` is one
    # routed expert's width, ``moe_shared_d_ff`` that of the shared
    # expert every token passes (0 = none). Gates: ``moe_score``
    # ("sigmoid" or "softmax") of the router's fp32 logits, renormalised
    # over the chosen k where ``moe_route_norm``, times
    # ``moe_route_scale``.
    moe_experts_total: int = 0
    moe_experts_held: Optional[tuple] = None
    moe_top_k: int = 1
    moe_d_ff: int = 0
    moe_shared_d_ff: int = 0
    moe_score: str = "sigmoid"
    moe_route_norm: bool = True
    moe_route_scale: float = 1.0
    # False: a router with no selection bias. The expert layer has no
    # ``select_bias`` leaf and the k experts are chosen on the scores alone.
    moe_select_bias: bool = True
    # Block-diffusion training: the block length B (0 = off). A call takes
    # ``[rows, 2L]`` token ids, a noised copy of every row of L tokens and
    # then the clean row; both copies of token i sit at position i; every
    # attention layer keeps (query, key) under the three-part mask of
    # ``ops.flash_attention`` (noised sees its own noised block and the
    # clean blocks before it, clean sees the clean blocks up to its own),
    # in the kernels; the final norm and the head run over the noised half
    # and the logits are ``[rows, L, vocab]``. B must divide L and the
    # kernels' tiles. Refused beside ``cache=``, ``lengths=``, ``mask=``, a
    # window or a latent layer.
    block_diffusion: int = 0

    def dim_per_head(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def layer_kind(self, layer: int):
        """``(attention kind, feed-forward kind)`` of a layer, or
        ``(None, None)``: the model-wide settings."""
        if self.layer_kinds is None:
            return None, None
        if len(self.layer_kinds) != self.num_layers:
            raise ValueError(
                f"layer_kinds names {len(self.layer_kinds)} layers, "
                f"num_layers is {self.num_layers}"
            )
        attn, _, ffn = self.layer_kinds[layer].partition("/")
        if (
            attn.removesuffix("-nope") not in ("window", "full")
            and attn != "latent"
        ) or ffn not in ("dense", "experts"):
            raise ValueError(
                f"layer kind {self.layer_kinds[layer]!r} is not "
                "'<window|full>[-nope]/<dense|experts>' or "
                "'latent/<dense|experts>'"
            )
        return attn, ffn

    def attention_kind(self, kind: Optional[str]):
        """``(window, rope)`` of an attention kind of ``layer_kinds``
        (None: the model-wide ``sliding_window`` and ``rope``)."""
        if kind is None:
            return self.sliding_window, self.rope
        if kind.startswith("window") and not self.sliding_window:
            raise ValueError("a 'window' layer needs sliding_window")
        if kind == "latent" and not (
            self.rope and self.kv_lora_rank and self.qk_nope_head_dim
            and self.qk_rope_head_dim and self.v_head_dim
        ):
            raise ValueError(
                "a 'latent' layer needs rope, kv_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim"
            )
        return (
            self.sliding_window if kind.startswith("window") else None,
            self.rope and not kind.endswith("-nope"),
        )

    def expert_layers(self) -> int:
        """How many layers' feed-forward is an expert layer."""
        return sum(k.endswith("/experts") for k in self.layer_kinds or ())

    def latent_layers(self) -> int:
        """How many layers' attention is of the kind ``latent``."""
        return sum(k.startswith("latent/") for k in self.layer_kinds or ())

    def head_widths(self, kind: Optional[str] = None):
        """``(key width, value width, key/value heads)`` of a layer of
        an attention kind: q and k are as wide as the key."""
        if kind == "latent":
            return (
                self.qk_nope_head_dim + self.qk_rope_head_dim,
                self.v_head_dim, self.num_heads,
            )
        head_dim = self.dim_per_head()
        return head_dim, head_dim, self.num_kv_heads or self.num_heads

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
                "causal, sliding_window, lengths= and block_diffusion "
                "only; pass lengths for right-padded batches)"
            )
        if seq is None:
            return None
        from ..ops.flash_attention import supports_seq

        if not supports_seq(seq, self.flash_block_q, self.flash_block_k):
            # untileable lengths (e.g. ViT's 197 tokens) would fail
            # Mosaic's block constraints
            return f"seq {seq} tiles no 8-aligned block"
        # no decline for VMEM: where the dK/dV kernel's whole-sequence
        # staging would not fit, it stages by block (ops/flash_attention)
        return None

    def uses_flash(self, mask=None, seq=None) -> bool:
        """THE gating rule for the Pallas flash path — single source
        of truth for the model and for whoever counts its FLOPs."""
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


def apply_rope(x, base: float = 10000.0, offset=0, interleave: bool = False,
               period: Optional[int] = None):
    """Rotate [batch, seq, heads, head_dim] q or k by absolute position
    (RoFormer). Pairs are (x[..., :d/2], x[..., d/2:]) — the
    'rotate-half' convention — so the op is two multiplies and one
    concat, fully XLA-fusible. With ``interleave`` the pairs are (x[...,
    2i], x[..., 2i+1]): the i-th pair still turns by the i-th frequency
    and the result is left in the half-split order (real parts, then
    imaginary parts), as the published latent-attention models do. fp32
    trig regardless of input dtype;
    ``offset`` shifts positions: a scalar (sequence-parallel shards
    pass their global start — may be a traced value, e.g.
    axis_index·t_local) or a ``[batch]`` array (incremental decode:
    every cache slot sits at its own position). ``period``: the positions
    start again every so many tokens (block-diffusion training: two
    copies of a row, token i of either at position i)."""
    b, t, h, d = x.shape
    half = d // 2
    if d % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {d}")
    # offset + iota rather than arange(offset, ...) so traced offsets
    # (SP shards, decode cache indices) work
    # [t] for scalar offsets, [b, t] for per-slot offsets
    pos = jnp.asarray(offset, jnp.float32)[..., None]
    if period is None:
        pos = pos + jnp.arange(t, dtype=jnp.float32)
    else:
        pos = pos + (jnp.arange(t) % period).astype(jnp.float32)
    inv_freq = base ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = pos[..., :, None] * inv_freq  # [(b,) t, half]
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    if angles.ndim == 2:  # scalar offset: broadcast over batch
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    if interleave:
        # strided slices (x[..., 0::2] would be a gather, and its
        # transpose a scatter-add)
        x1, x2 = (
            jax.lax.slice_in_dim(x, first, d, stride=2, axis=3)
            for first in (0, 1)
        )
    else:
        x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def block_diffusion_mask(length: int, block: int):
    """``[2L, 2L]`` bool, the pairs (query, key) that block-diffusion
    training keeps over a noised copy of a row of ``length`` tokens and
    then the clean row, in blocks of ``block``: noised sees its own noised
    block and the clean blocks before it, clean sees the clean blocks up to
    its own, and never a noised key. The dense path's mask; the kernels
    build theirs tile by tile (``ops/flash_attention.py``)."""
    at = jnp.arange(2 * length)
    noised = at < length
    blocks = at % length // block
    bi, bj = blocks[:, None], blocks[None, :]
    return jnp.where(
        noised[:, None],
        jnp.where(noised[None, :], bi == bj, bj < bi),
        ~noised[None, :] & (bj <= bi),
    )


def init_cache(cfg: TransformerConfig, batch: int, max_len=None, dtype=None):
    """Allocate an empty decode KV cache: one ``{"k", "v"}`` dict per
    layer, each ``[batch, max_len, num_kv_heads, head_dim]`` of zeros (a
    ``latent`` layer's hold its expanded heads: the key at its own
    width, the value at its own).

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
    dt = cfg.dtype if dtype is None else dtype
    cache = []
    for layer in range(cfg.num_layers):
        k_width, v_width, kv_heads = cfg.head_widths(
            cfg.layer_kind(layer)[0]
        )
        cache.append({
            "k": jnp.zeros((batch, seq, kv_heads, k_width), dt),
            "v": jnp.zeros((batch, seq, kv_heads, v_width), dt),
        })
    return cache


def _norm(cfg: TransformerConfig, **kwargs):
    """The model's norm, computed in fp32."""
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, **kwargs)
    if cfg.norm != "layernorm":
        raise ValueError(f"norm {cfg.norm!r} is not layernorm or rmsnorm")
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, **kwargs)


class MultiHeadAttention(nn.Module):
    cfg: TransformerConfig
    # this layer's attention kind of cfg.layer_kinds; None = model-wide
    kind: Optional[str] = None

    @nn.compact
    def __call__(self, x, mask=None, lengths=None, cache=None,
                 cache_index=None, pages=None, paged_attn=False):
        cfg = self.cfg
        window, rope = cfg.attention_kind(self.kind)
        scope = "attn_latent" if self.kind == "latent" else (
            "attn_window" if window else "attn_full"
        )
        if cfg.block_diffusion:
            # (cache=, mask= and lengths= are Transformer.__call__'s to refuse)
            if window or self.kind == "latent":
                raise ValueError(
                    "block_diffusion is a full layer's training mask: no "
                    "window or latent layer"
                )
            scope = "attn_blockdiff"
        with jax.named_scope(scope):
            return self._attend(x, mask, lengths, cache, cache_index,
                                pages, paged_attn, window, rope)

    def _latent_qkv(self, x, offset):
        """``(q, k, v)`` of a ``latent`` layer, as wide as the kernels
        take them: q and k ``[.., heads, nope + rope]``, v ``[.., heads,
        v_head_dim]``. What latent attention adds to a plain layer's
        projections runs under the scope ``latent_proj``: the joint
        down-projection, the latent's norm, the up-projection, the
        rotation of the rope parts (the key's is one head that all heads
        share) and the concatenations."""
        cfg = self.cfg
        nope, rope_dim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rank, heads = cfg.kv_lora_rank, cfg.num_heads
        q = nn.DenseGeneral(
            (heads, nope + rope_dim), dtype=cfg.dtype,
            use_bias=cfg.use_bias, name="q",
        )(x)
        with jax.named_scope("latent_proj"):
            c = nn.Dense(
                rank + rope_dim, dtype=cfg.dtype, use_bias=cfg.use_bias,
                name="kv_a",
            )(x)
            latent = _norm(cfg, name="kv_norm")(c[..., :rank])
            kv = nn.DenseGeneral(
                (heads, nope + cfg.v_head_dim), dtype=cfg.dtype,
                use_bias=cfg.use_bias, name="kv_b",
            )(latent.astype(cfg.dtype))
            rotate = functools.partial(
                apply_rope, base=cfg.rope_base, offset=offset,
                interleave=cfg.rope_interleave,
            )
            q = jnp.concatenate(
                [q[..., :nope], rotate(q[..., nope:])], axis=-1
            )
            # [b, t, 1, rope]: one head
            k_rope = rotate(jnp.expand_dims(c[..., rank:], -2))
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope, (*kv.shape[:-1], rope_dim))],
                axis=-1,
            )
        return q, k, kv[..., nope:]

    def _attend(self, x, mask, lengths, cache, cache_index, pages,
                paged_attn, window, rope):
        cfg = self.cfg
        latent = self.kind == "latent"
        # q and k are head_dim wide, v and the heads' output v_dim
        head_dim, v_dim, kv_heads = cfg.head_widths(self.kind)
        if pages is not None and latent:
            raise NotImplementedError(
                "pages= on a latent layer: the page pool holds keys and "
                "values of one head_dim and no compressed latent row, and "
                "the paged kernel no key wider than its value (ROADMAP "
                "B-M4); the dense cached path (pages=None) works"
            )
        if pages is not None and self.kind is not None:
            raise NotImplementedError(
                "pages= with layer_kinds: the paged kernel has no band "
                "and the page allocator holds one kind of layer "
                "(ROADMAP B-M3); the dense cached path (pages=None) works"
            )
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
        if latent:
            q, k, v = self._latent_qkv(
                x, 0 if cache is None else cache_index
            )
        elif cfg.num_kv_heads:
            if cfg.num_heads % cfg.num_kv_heads:
                raise ValueError(
                    f"num_kv_heads ({cfg.num_kv_heads}) must divide "
                    f"num_heads ({cfg.num_heads})"
                )
            q = nn.DenseGeneral(
                (cfg.num_heads, head_dim), dtype=cfg.dtype,
                use_bias=cfg.use_bias, name="q",
            )(x)
            kv = nn.DenseGeneral(
                (2, cfg.num_kv_heads, head_dim), dtype=cfg.dtype,
                use_bias=cfg.use_bias, name="kv",
            )(x)
            k, v = kv[..., 0, :, :], kv[..., 1, :, :]
        else:
            qkv = nn.DenseGeneral(
                (3, cfg.num_heads, head_dim), dtype=cfg.dtype,
                use_bias=cfg.use_bias, name="qkv",
            )(x)
            q, k, v = (
                qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
            )
        if cfg.qk_norm and not latent:
            q = _norm(cfg, name="q_norm")(q).astype(cfg.dtype)
            k = _norm(cfg, name="k_norm")(k).astype(cfg.dtype)
        if rope and not latent:  # a latent layer rotated its rope parts
            rope_offset = 0 if cache is None else cache_index
            # both copies of a block-diffusion row at the row's positions
            period = x.shape[1] // 2 if cfg.block_diffusion else None
            q = apply_rope(q, cfg.rope_base, offset=rope_offset,
                           period=period)
            k = apply_rope(k, cfg.rope_base, offset=rope_offset,
                           period=period)

        def project(out):
            """W_o on the heads' output, gated where the model says."""
            if cfg.attn_output_gate:
                gate = nn.DenseGeneral(
                    (cfg.num_heads, v_dim), dtype=cfg.dtype,
                    use_bias=False, name="gate",
                )(x)
                out = (
                    out * jax.nn.sigmoid(gate.astype(jnp.float32))
                ).astype(cfg.dtype)
            return nn.DenseGeneral(
                cfg.d_model, axis=(-2, -1), dtype=cfg.dtype,
                use_bias=cfg.use_bias, name="out",
            )(out)

        if cache is not None:
            return self._cached_attention(cfg, x, q, k, v, cache,
                                          cache_index, head_dim, project,
                                          window, pages=pages,
                                          paged_attn=paged_attn,
                                          kv_heads=kv_heads)
        # lengths (right-padding) stays on the flash path — the kernels
        # take it natively; only ARBITRARY masks force dense.
        wanted = cfg.wants_flash()
        declined = (
            cfg.flash_decline_reason(mask, seq=x.shape[1]) if wanted else None
        )
        use_flash = wanted and declined is None
        if wanted and not use_flash and cfg.block_diffusion:
            # 2L x 2L dense scores are what the mode exists to avoid
            raise ValueError(
                f"block_diffusion rides the flash kernels or is refused: "
                f"{declined} (flash_attention=False runs the dense path "
                "on purpose)"
            )
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
                q, k, v, causal=cfg.causal and not cfg.block_diffusion,
                block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                lengths=lengths, window=window,
                block_diffusion=cfg.block_diffusion or None,
            )
            return project(out)
        if kv_heads != cfg.num_heads:
            # dense fallback materializes the head repeat the flash
            # path avoids
            rep = cfg.num_heads // kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        # scores in fp32 for softmax stability
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        ) / jnp.sqrt(head_dim).astype(jnp.float32)
        if cfg.block_diffusion:
            scores = jnp.where(
                block_diffusion_mask(x.shape[1] // 2, cfg.block_diffusion),
                scores, -1e30,
            )
        elif cfg.causal:
            t = x.shape[1]
            causal_mask = jnp.tril(jnp.ones((t, t), bool))
            if window:
                rows = jnp.arange(t)[:, None]
                cols = jnp.arange(t)[None, :]
                causal_mask = causal_mask & (rows - cols < window)
            scores = jnp.where(causal_mask[None, None], scores, -1e30)
        elif window:
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
        return project(out)

    def _cached_attention(self, cfg, x, q, k, v, cache, cache_index,
                          head_dim, project, window, pages=None,
                          paged_attn=False, *, kv_heads):
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

            r = cfg.num_heads // kv_heads
            reason = _pa.unsupported_reason(
                head_dim, page_tokens, queries=t * r
            )
            if reason is None and window:
                reason = (
                    "sliding_window is not implemented by the paged "
                    "kernel"
                )
            if reason is None:
                out = _pa.paged_attention(
                    q, k_cache, v_cache, pages, idx, causal=True
                )
                return project(out), new_cache
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
        if kv_heads != cfg.num_heads:
            rep = cfg.num_heads // kv_heads
            kk = jnp.repeat(kk, rep, axis=2)
            vv = jnp.repeat(vv, rep, axis=2)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, kk, preferred_element_type=jnp.float32
        ) / jnp.sqrt(head_dim).astype(jnp.float32)
        q_pos = idx[:, None] + jnp.arange(t)          # [b, t] global
        key_pos = jnp.arange(seq)                     # [seq]
        valid = key_pos[None, None, :] <= q_pos[:, :, None]  # [b, t, seq]
        if window:
            valid = valid & (
                q_pos[:, :, None] - key_pos[None, None, :] < window
            )
        scores = jnp.where(valid[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        return project(out), new_cache


class MoEFFN(nn.Module):
    """The serving engine's top-1 expert bank (``cfg.moe_experts``; the
    training path's expert layer, top-k and share-holding, is
    :class:`ExpertFFN`). Switch-style top-1 MoE FFN: router logits in
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


class GatedMLP(nn.Module):
    """``W_down(silu(W_gate x) * W_up x)`` at ``width``."""

    cfg: TransformerConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg

        def dense(features, name):
            return nn.Dense(features, dtype=cfg.dtype,
                            use_bias=cfg.use_bias, name=name)

        h = nn.silu(dense(self.width, "gate")(x)) * dense(self.width, "up")(x)
        return dense(cfg.d_model, "down")(h)


class ExpertFFN(nn.Module):
    """An expert layer that is told which experts of the deployment it
    holds (``cfg.moe_experts_held``): the router scores all
    ``cfg.moe_experts_total`` experts in float32 and picks ``moe_top_k`` a
    token on score + ``select_bias`` (on the score alone, and no such
    leaf, where ``cfg.moe_select_bias`` is off); the gates are the unbiased
    scores, renormalised over all k chosen (held here or not) and scaled; the
    layer returns ``shared(x) + sum over the chosen experts held here of
    gate_e * expert_e(x)``. Dropless with static shapes: the chosen
    (token, expert) pairs are sorted by held expert; the grouped matmuls
    visit the held groups' rows, and every other pass (the gather into
    sorted rows, the activation, the weighted add into the tokens' rows,
    and their transposes) takes as many chunks of the sorted rows as hold
    a row routed here, so the work follows the rows really routed here,
    forward and backward (parallel/moe.py: :func:`route_top_k`,
    :func:`held_experts_ffn`). What experts held elsewhere would add is
    left out: their exchange is parallel/moe.py's, and no code stands in
    for it here.

    Sown into ``intermediates``: ``chosen`` (the experts of every token),
    ``rows_routed`` (the (token, choice) pairs held here) and
    ``rows_window`` (the rows the passes took: ``rows_routed`` rounded up
    to whole chunks of ``moe.window_chunk(tokens * top_k)``).

    ``select_bias`` is the load-balancing bias: a leaf of the tree that
    only selection reads, so its gradient is zero; its balancing update
    lies outside the gradient step."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from ..parallel import moe as _moe

        cfg = self.cfg
        total, d, f = cfg.moe_experts_total, cfg.d_model, cfg.moe_d_ff
        first, last = cfg.moe_experts_held or (0, total)
        if not 0 <= first < last <= total:
            raise ValueError(
                f"moe_experts_held {cfg.moe_experts_held} is no range of "
                f"the {total} experts"
            )
        tokens = x.reshape(-1, d)
        with jax.named_scope("moe_route"):
            logits = nn.Dense(
                total, dtype=jnp.float32, use_bias=False,
                precision="highest", name="router",
            )(tokens.astype(jnp.float32))
            select_bias = self.param(
                "select_bias", nn.initializers.zeros, (total,), jnp.float32
            ) if cfg.moe_select_bias else None
            chosen, gates = _moe.route_top_k(
                logits, select_bias, cfg.moe_top_k, score=cfg.moe_score,
                norm=cfg.moe_route_norm, scale=cfg.moe_route_scale,
            )
        self.sow("intermediates", "chosen", chosen)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1)
        held = last - first
        w_gate, w_up, w_down = (
            self.param(name, init, shape, jnp.float32)
            for name, shape in (
                ("w_gate", (held, d, f)), ("w_up", (held, d, f)),
                ("w_down", (held, f, d)),
            )
        )
        tokens = tokens.astype(cfg.dtype)
        y, rows_routed, rows_window = _moe.held_experts_ffn(
            tokens, chosen, gates, w_gate, w_up, w_down, first
        )
        self.sow("intermediates", "rows_routed", rows_routed)
        self.sow("intermediates", "rows_window", rows_window)
        if cfg.moe_shared_d_ff:
            with jax.named_scope("moe_shared"):
                y = y + GatedMLP(cfg, cfg.moe_shared_d_ff, name="shared")(
                    tokens
                )
        return y.reshape(x.shape).astype(cfg.dtype)


class Block(nn.Module):
    cfg: TransformerConfig
    # this layer's entry of cfg.layer_kinds; None = the model-wide block
    layer: Optional[int] = None

    @nn.compact
    def __call__(self, x, mask=None, train: bool = True, lengths=None,
                 cache=None, cache_index=None, pages=None,
                 paged_attn=False):
        cfg = self.cfg
        attn_kind, ffn_kind = (
            (None, None) if self.layer is None else cfg.layer_kind(self.layer)
        )
        h = _norm(cfg)(x)
        new_cache = None
        attention = MultiHeadAttention(cfg, kind=attn_kind)
        if cache is None:
            h = attention(h, mask, lengths)
        else:
            h, new_cache = attention(
                h, mask, lengths, cache=cache, cache_index=cache_index,
                pages=pages, paged_attn=paged_attn,
            )
        if cfg.sandwich_norm:
            h = _norm(cfg)(h).astype(cfg.dtype)
        h = nn.Dropout(cfg.dropout_rate, deterministic=not train)(h)
        x = x + h
        h = _norm(cfg)(x)
        if ffn_kind == "experts":
            h = ExpertFFN(cfg, name="moe")(h)
        elif cfg.moe_experts:
            h = MoEFFN(cfg, name="moe")(h)
        elif cfg.ffn_gated:
            h = GatedMLP(cfg, cfg.d_ff, name="mlp")(h)
        else:
            h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, use_bias=cfg.use_bias)(h)
            h = nn.gelu(h)
            h = nn.Dense(
                cfg.d_model, dtype=cfg.dtype, use_bias=cfg.use_bias
            )(h)
        if cfg.sandwich_norm:
            h = _norm(cfg)(h).astype(cfg.dtype)
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
        ) if cfg.use_bias else None
        if cfg.head_mixed_precision:
            y = jax.lax.dot_general(
                x.astype(cfg.dtype),
                kernel.astype(cfg.dtype),
                dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            y = x.astype(jnp.float32) @ kernel
        return y if bias is None else y + bias


# The rungs of remat_plan(), richest first, each with the largest share
# of what the state leaves of the device's memory that its kept bytes may
# take; past it a user who set ``remat`` because memory is short gets the
# next rung down. Each is the largest share measured to run, rounded up
# (PERF.md section 6). ``save_matmuls``: GPT-2 medium at 18 x 512 tokens
# keeps 4.09 GB of the 12.03 GB its state leaves, 34.0%, at a peak of
# 12.31 of 15.74 GiB (PR 26). ``save_attention``: Trinity-Mini's five
# layers at 2 x 8192 keep 1.53 GB of the 8.44 GB left, 18.1% (PR 28).
# ``save_attention_out``: Kanana's six latent layers at 2 x 8192 keep
# 0.82 GB of the 8.65 GB left, 9.5% (PR 32); a larger value needs a chip
# run of its own.
# The poorer rung's share is the smaller because it keeps fewer bytes a
# token: at one share the step would hold more tokens, and the step's
# other temporaries grow with the tokens (0.26-0.30 MB a token in both
# models) and need what the share leaves.
REMAT_SAVE_SHARE = {
    "save_matmuls": 0.35, "save_attention": 0.2, "save_attention_out": 0.1,
}
# What training holds for each parameter, reckoned: float32 weight,
# gradient and one optimizer moment. (The 705.5M parameters of PR 27's
# cell measured 5.644 GB of weights and momentum as the step's
# arguments, 8 bytes each, before the gradients.)
REMAT_STATE_BYTES_PER_PARAM = 12


def _param_count(cfg: TransformerConfig) -> int:
    """The parameters ``Transformer(cfg)`` creates on this chip, from the
    shapes alone: an expert layer counts the experts it holds, not the
    deployment's."""
    d, head_dim = cfg.d_model, cfg.dim_per_head()
    bias = int(cfg.use_bias)
    per_norm = 2 if cfg.norm == "layernorm" else 1  # scale (and bias)
    q_width = cfg.num_heads * head_dim
    kv_width = 2 * (cfg.num_kv_heads or cfg.num_heads) * head_dim

    def gated(width):
        return 3 * d * width + bias * (2 * width + d)

    attention = (
        (d + bias) * (q_width + kv_width)
        + (d * q_width if cfg.attn_output_gate else 0)
        + q_width * d + bias * d
        + (2 * per_norm * head_dim if cfg.qk_norm else 0)
    )
    # a latent layer: q, the joint down-projection, the latent's norm,
    # the up-projection to every head's key and value, the output
    k_width, v_width, heads = cfg.head_widths("latent")
    rank, rope_dim = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    latent = (
        (d + bias) * (heads * k_width + rank + rope_dim)
        + per_norm * rank
        + (rank + bias) * heads * (k_width - rope_dim + v_width)
        + (d * heads * v_width if cfg.attn_output_gate else 0)
        + heads * v_width * d + bias * d
    )
    latent_layers = cfg.latent_layers()
    if cfg.moe_experts:  # MoEFFN: router and bank, biases always
        dense = cfg.moe_experts * (d + 1 + 2 * d * cfg.d_ff + cfg.d_ff + d)
    elif cfg.ffn_gated:
        dense = gated(cfg.d_ff)
    else:
        dense = 2 * d * cfg.d_ff + bias * (cfg.d_ff + d)
    first, last = cfg.moe_experts_held or (0, cfg.moe_experts_total)
    experts = (
        # router and select_bias
        (d + int(cfg.moe_select_bias)) * cfg.moe_experts_total
        + (last - first) * 3 * d * cfg.moe_d_ff
        + (gated(cfg.moe_shared_d_ff) if cfg.moe_shared_d_ff else 0)
    )
    norms = (4 if cfg.sandwich_norm else 2) * per_norm * d
    expert_layers = cfg.expert_layers()
    return (
        cfg.vocab_size * d + (0 if cfg.rope else cfg.max_len * d)
        + (cfg.num_layers - latent_layers) * attention
        + latent_layers * latent + cfg.num_layers * norms
        + expert_layers * experts + (cfg.num_layers - expert_layers) * dense
        + per_norm * d + (d + bias) * cfg.vocab_size
    )


def remat_plan(cfg: TransformerConfig, tokens: int, bytes_limit):
    """What ``Transformer(remat=True)`` keeps between forward and
    backward, from what the model knows at trace time: ``(mode,
    saved_bytes)`` for ``tokens`` tokens on this chip and a device
    memory of ``bytes_limit`` bytes (None: not known). A ladder: the
    richest rung whose bytes, over all layers, are at most its
    ``REMAT_SAVE_SHARE`` of what the state leaves of the device,
    ``bytes_limit - REMAT_STATE_BYTES_PER_PARAM x`` the parameters this
    chip holds (:func:`_param_count`: held experts, not published ones).

    ``save_matmuls``: each block keeps the outputs of its weight matmuls
    (the output gate's where the model has one, the attention's output
    projection, the first feed-forward matmul, or the gate and up
    matmuls of a gated one; in an expert layer the router's float32
    logits and the shared expert's gate and up; where the model has
    them, what QK-norm and the sandwich norm read: the q and k/v
    projections' outputs and the last feed-forward matmul's) and of the
    flash forward (the attention output and one lane of ``lse``), and
    recomputes only the element-wise work and, in an expert layer, the
    dispatch and the grouped matmuls; on the flash path q, k and v are
    kept as the kernels take them, in place of the projection's output
    where no norm reads that, so the head transposes are not repeated
    either. The attention output is counted on the dense path too,
    which recomputes it. Not offered to the serving bank
    (``moe_experts``), whose one-hot einsums make a ``tokens x experts
    x d_ff`` output that the policy would keep.

    ``save_attention``: each block keeps what the flash kernels' backward
    reads and no more, by name (``ops/flash_attention.py:
    RESIDUAL_NAMES``): q, k and v as the kernels take them, the
    attention output and one lane of ``lse``; of an expert layer also
    the routing's integer results (``parallel/moe.py: ROUTING_NAMES``:
    the chosen experts and the sorted order, ``tokens x moe_top_k``
    int32 each). The backward's second forward then runs no flash
    forward, no RoPE or head transpose, no ``top_k`` and no sort of the
    dispatch, and no q/k/v projection unless QK-norm reads its output
    (its backward needs the value before the norm); it recomputes the
    rest: norms, the gate's and the output projection, the
    feed-forward; in an expert layer the router, the dispatch's passes
    over the routed rows' window and the grouped matmuls, whose
    buffers of ``tokens x moe_top_k`` rows stay unkept (2 x 8192
    tokens, top-8, 2048 wide: 512 MiB each, written up to the
    window). Offered where the model rides the kernels
    (``cfg.wants_flash()``): the dense path has no such names.

    A ``latent`` layer is reckoned at its own widths: by name q and k at
    the key's width (the nope and rope parts together), v and the output
    at the value's, every head its own; of its matmuls ``save_matmuls``
    keeps the joint down-projection's output, which the latent's norm
    reads, and the output projection's (q and the up-projection's output
    reach the kernels through slices, a rotation and concatenations,
    whose backward reads no value).

    ``save_attention_out``: each block keeps the flash forward kernel's
    outputs and no more, by name (``ops/flash_attention.py:
    OUTPUT_NAMES``): the attention output and one lane of ``lse``, at
    every head (a latent layer's at the value's width); of an expert
    layer the routing's integer results as above. q, k and v are the
    kernel's inputs: the second forward remakes them as under
    ``recompute_all`` (every projection, norm, rotation and head
    transpose again), and with its outputs kept nothing reads the
    forward kernel, so it is not run a second time, nor ``top_k`` and
    the sort. For a model whose K/V heads are many and whose state
    leaves little (every head of a latent layer has its own 192-wide key
    and 128-wide value: q, k and v are four fifths of the five
    residuals). Offered where ``save_attention`` is.

    ``recompute_all``: each block keeps its input alone, where no rung
    fits and where the limit cannot be read (CPU). ``off``:
    ``cfg.remat`` is not set."""
    if not cfg.remat:
        return "off", 0
    if not bytes_limit:
        return "recompute_all", 0
    itemsize = jnp.dtype(cfg.dtype).itemsize
    q_width = cfg.num_heads * cfg.dim_per_head()
    kv_width = 2 * (cfg.num_kv_heads or cfg.num_heads) * cfg.dim_per_head()
    # the forward kernel's outputs: the attention output and one float32
    # lse lane a head; with its inputs q, k and v the five residuals
    attention_out = q_width * itemsize + 4 * cfg.num_heads
    attention = attention_out + (q_width + kv_width) * itemsize
    # the same of a latent layer: q and k at the key's width, v and the
    # output at the value's, every head its own
    k_width, v_width, heads = cfg.head_widths("latent")
    latent_out = heads * v_width * itemsize + 4 * heads
    latent = latent_out + heads * (2 * k_width + v_width) * itemsize
    latent_layers = cfg.latent_layers()
    plain_layers = cfg.num_layers - latent_layers
    # the output gate's and the output projection's outputs; the q and
    # k/v projections' where a norm reads them (through RoPE and the
    # head transpose alone the backward needs no value); the last
    # feed-forward matmul's where a norm follows it
    matmuls = (
        (q_width if cfg.attn_output_gate else 0) + cfg.d_model
        + (q_width + kv_width if cfg.qk_norm else 0)
        + (cfg.d_model if cfg.sandwich_norm else 0)
    ) * itemsize
    # of a latent layer: the joint down-projection's output, which the
    # latent's norm reads (q and the up-projection's output reach the
    # kernels through slices, a rotation and concatenations alone)
    latent_matmuls = (
        (heads * v_width if cfg.attn_output_gate else 0) + cfg.d_model
        + cfg.kv_lora_rank + cfg.qk_rope_head_dim
        + (cfg.d_model if cfg.sandwich_norm else 0)
    ) * itemsize
    # the first feed-forward matmuls' outputs (gate and up of a gated
    # one); of an expert layer the router's float32 logits and the
    # shared expert's gate and up
    dense = cfg.d_ff * (2 if cfg.ffn_gated else 1) * itemsize
    experts = 4 * cfg.moe_experts_total + 2 * cfg.moe_shared_d_ff * itemsize
    expert_layers = cfg.expert_layers()
    # by name on every saving rung, of an expert layer: the chosen
    # experts and the dispatch's sorted order
    routing = expert_layers * 2 * 4 * cfg.moe_top_k
    # by name on the two richer rungs: the kernels' five residuals
    named = plain_layers * attention + latent_layers * latent + routing
    # by name on the poorest: the forward kernel's outputs alone
    outputs = (
        plain_layers * attention_out + latent_layers * latent_out + routing
    )
    # bytes a token over all layers; 0: the rung is not offered
    rungs = {
        "save_matmuls": 0 if cfg.moe_experts else (
            named + plain_layers * matmuls + latent_layers * latent_matmuls
            + expert_layers * experts
            + (cfg.num_layers - expert_layers) * dense
        ),
        "save_attention": named if cfg.wants_flash() else 0,
        "save_attention_out": outputs if cfg.wants_flash() else 0,
    }
    room = bytes_limit - REMAT_STATE_BYTES_PER_PARAM * _param_count(cfg)
    for mode, per_token in rungs.items():
        saved = int(tokens) * per_token
        if 0 < saved <= REMAT_SAVE_SHARE[mode] * room:
            return mode, saved
    return "recompute_all", 0


def _device_bytes_limit() -> Optional[int]:
    """``bytes_limit`` of this process's first device; None where the
    backend reports no memory statistics (CPU)."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except jax.errors.JaxRuntimeError:
        return None
    return (stats or {}).get("bytes_limit")


def _remat_policy(mode: str):
    """Checkpoint policy of a rung of :func:`remat_plan`. The flash
    kernels' residuals go by name on every saving rung, since a policy
    on primitives does not see through a ``pallas_call``, and with
    them an expert layer's routing (``parallel/moe.py: ROUTING_NAMES``):
    all five on the two richer rungs, the forward kernel's two outputs
    on ``save_attention_out``; ``save_matmuls`` adds the weight
    matmuls, the ``dot_general``s without batch dimensions (the dense
    attention fallback's einsums have them, and are recomputed). An
    output the backward does not read (the second feed-forward
    matmul's, the qkv projection's where q, k and v are kept by name,
    unless a norm follows) is not kept. ``recompute_all``: None,
    nothing but the block's input."""
    from ..parallel.moe import ROUTING_NAMES

    policies = jax.checkpoint_policies
    flash = (
        _FLASH_OUTPUT_NAMES if mode == "save_attention_out"
        else _FLASH_RESIDUAL_NAMES
    )
    names = policies.save_only_these_names(*flash, *ROUTING_NAMES)
    if mode in ("save_attention", "save_attention_out"):
        return names
    if mode == "save_matmuls":
        return policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable, names
        )
    return None


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


def _tag_layer_kinds(span, cfg: TransformerConfig, tokens: int):
    """What the per-layer kinds make of the model, on the trace span."""
    from ..parallel.moe import window_chunk

    first, last = cfg.moe_experts_held or (0, cfg.moe_experts_total)
    span.tag(
        layer_kinds=",".join(cfg.layer_kinds),
        experts_total=cfg.moe_experts_total,
        experts_held=last - first,
        top_k=cfg.moe_top_k,
        # rows of an expert layer's sorted order: every choice of every
        # token can land on this chip; and the rows its dispatch takes at
        # a time, as many times as hold a routed row
        moe_rows_capacity=tokens * cfg.moe_top_k,
        moe_rows_chunk=window_chunk(tokens * cfg.moe_top_k),
    )
    if cfg.latent_layers():
        k_width, v_width, _ = cfg.head_widths("latent")
        span.tag(qk_head_dim=k_width, v_head_dim=v_width,
                 kv_lora_rank=cfg.kv_lora_rank)


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
        # block-diffusion training: L, the row's length (tokens holds a
        # noised copy and then the clean row), else None
        half = None
        if cfg.block_diffusion:
            if cache is not None or lengths is not None or mask is not None:
                raise ValueError(
                    "block_diffusion takes [rows, 2L] token ids alone: no "
                    "cache=, lengths= or mask= (decoding block by block is "
                    "the serving engine's to do, ROADMAP B-M9)"
                )
            half, odd = divmod(tokens.shape[1], 2)
            if odd or half % cfg.block_diffusion:
                raise ValueError(
                    f"block_diffusion={cfg.block_diffusion} takes a noised "
                    f"and a clean copy of whole blocks, got "
                    f"{tokens.shape[1]} positions a row"
                )
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype)(tokens)
        if cfg.embed_scale != 1.0:
            x = x * cfg.embed_scale
        layers = [
            None if cfg.layer_kinds is None else i
            for i in range(cfg.num_layers)
        ]
        if not cfg.rope:
            if half:
                positions = (jnp.arange(2 * half) % half)[None]
            elif cache is None:
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
                x, layer_cache = Block(cfg, layers[i], name=f"block_{i}")(
                    x, mask, train, lengths,
                    cache=cache[i], cache_index=cache_index,
                    pages=pages, paged_attn=paged_attn,
                )
                new_cache.append(layer_cache)
            x = _norm(cfg)(x)
            return LMHead(cfg, name="lm_head")(x), new_cache
        block = Block
        mode, saved_bytes = remat_plan(
            cfg, tokens.shape[0] * tokens.shape[1],
            _device_bytes_limit() if cfg.remat else None,
        )
        if mode != "off":
            block = nn.remat(
                Block, static_argnums=(3,), policy=_remat_policy(mode)
            )
        span = _tracing.current()
        if span is not None and span.name == _TRACE_MODEL_SPAN:
            span.tag(remat=mode, remat_saved_bytes=saved_bytes)
            if cfg.layer_kinds is not None:
                _tag_layer_kinds(
                    span, cfg, tokens.shape[0] * tokens.shape[1]
                )
            if half:
                span.tag(
                    block_length=cfg.block_diffusion, positions=2 * half,
                    head_positions=half,
                    # (query, key) pairs a head keeps of a row
                    score_pairs_kept=half * (half + cfg.block_diffusion),
                )
        for i in range(cfg.num_layers):
            x = block(cfg, layers[i], name=f"block_{i}")(
                x, mask, train, lengths
            )
        if half:
            # the clean copy is there to be seen; the noised half is read
            x = x[:, :half]
        x = _norm(cfg)(x)
        if return_hidden:
            # pre-head activations for the chunked fused loss
            # (ops/fused_xent.py): callers apply the lm_head params
            # through fused_linear_cross_entropy and never materialize
            # the (tokens, vocab) logits. Param tree is unchanged —
            # init traces the default path below.
            return x
        # fp32 logits; matmul precision per cfg.head_mixed_precision
        return LMHead(cfg, name="lm_head")(x)
