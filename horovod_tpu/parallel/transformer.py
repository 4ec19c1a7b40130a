"""The flagship distributed model: a causal transformer trained with
dp x pp x ep x sp x tp parallelism composed over one mesh.

This is the survey build-plan's "exceed parity" layer (SURVEY.md §2.6):
the reference stops at data parallelism; here every axis of
horovod_tpu/parallel/ composes in one SPMD program:

- dp: batch sharded; gradients pmean'd across ('dp','sp') — the
  reference's entire job, one psum here.
- pp: layers split into stages, GPipe microbatch schedule (pipeline.py).
- sp: sequence sharded; exact attention via ring_attention (ppermute ring).
- tp: heads + FFN sharded Megatron-style (tp.py), one psum per block.
- ep: a switch-MoE FFN block after the pipelined stack, tokens routed
  across 'ep' with all_to_all (moe.py).

Everything is per-device code executed under one
``jit(shard_map(step, mesh, ...))`` — XLA sees every collective and
schedules them against compute on ICI.

Data layout: the batch is sharded over ('dp','ep') — the 'ep' axis acts
as additional data parallelism for the dense layers, and the MoE block's
all_to_all then routes each shard's tokens to their experts across 'ep'
(so expert parallelism splits real tokens, not replicas); the sequence is
sharded over 'sp'.

Gradient synchronization (``_sync_grads``) follows one rule derived from
shard_map's transpose semantics (each device's loss output is seeded with
cotangent 1, and every psum/all_to_all edge transposes to a psum of
cotangents, multiplying the upstream cotangent by the replica count):

    for each parameter leaf with partition spec S:
      g ← pmean(g, every mesh axis NOT in S)   # combines per-shard
                                               # partials; replicated-path
                                               # contributions are equal
                                               # so pmean keeps them 1x
      g ← g / Π(size of axes in S ∩ {pp, ep, tp})
           # sharded-axis params received their cotangent through a
           # collective edge once per replica of the downstream loss —
           # uniform over-count by exactly that axis size

This is validated numerically: one train step produces identical
parameters on every mesh factorization (tests/test_parallel.py's
cross-mesh equivalence test).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from .moe import MoEParams, init_moe_params, moe_ffn
from .pipeline import gpipe, pipeline_1f1b
from .ring_attention import ring_attention, ring_flash_attention
from .tp import column_parallel_dense, row_parallel_dense


@dataclasses.dataclass(frozen=True)
class ParallelTransformerConfig:
    vocab_size: int = 256
    num_layers: int = 4  # total; must divide by pp
    d_model: int = 64
    num_heads: int = 4  # must divide by tp
    d_ff: int = 128  # must divide by tp
    max_len: int = 128
    n_experts: int = 4  # total; must divide by ep
    moe_capacity_factor: float = 2.0
    # Expert wire (PR 12, parallel/moe.py): dispatch/return format of
    # the MoE alltoall — None defers to HOROVOD_MOE_WIRE; "int8" rides
    # the block-scaled quantized wire (routing decisions are computed
    # on fp32 logits BEFORE the wire, so they are identical across
    # formats). moe_hier routes the exchange two-level (intra-ICI /
    # inter-DCN; None = the HOROVOD_HIERARCHICAL default decision,
    # "on"/"off" force it, or explicit (intra, inter) stages) — under
    # a split, moe_wire names the INTER hop and moe_intra_wire the
    # ICI legs.
    moe_wire: Any = None
    moe_intra_wire: Any = None
    moe_hier: Any = None
    n_microbatches: int = 2
    dtype: Any = jnp.float32
    learning_rate: float = 1e-2
    # SP attention engine. "auto": Pallas flash-block ring
    # (ring_flash_attention) on TPU when the local sequence shard is
    # flash-tileable, dense ring otherwise. True forces the flash ring
    # on any backend (interpret-mode kernels off-TPU — tests), False
    # forces the dense ring.
    flash_ring: Any = "auto"
    # Rotary position embeddings instead of the learned pos table: the
    # rotation offset is this shard's global start (axis_index("sp") *
    # t_local) — RoPE's relative form is what makes it compose with
    # sequence parallelism without any cross-shard exchange.
    rope: bool = False
    # Pipeline schedule for training. "1f1b" (default): the production
    # path — explicit per-stage backward inside the scan, activation
    # live-set bounded by pp (pipeline.pipeline_1f1b); the MoE+head
    # tail runs per-MICROBATCH (per-micro expert capacity). "gpipe":
    # differentiate through the fill/drain scan — checkpoints
    # O(n_micro) activations; demo/small-model path (VERDICT r4 #7).
    # The composed model runs pipeline_1f1b at virtual_stages=1:
    # Megatron-interleaved chunking needs the L axis pre-permuted so
    # P("pp") hands each device its STRIDED global stages (c*pp+s),
    # which would make the sharded param layout factorization-dependent
    # — use pipeline_1f1b(virtual_stages=...) directly for interleaved
    # custom stacks.
    pipeline_schedule: str = "1f1b"


Params = Dict[str, Any]


def _init_full_params(cfg: ParallelTransformerConfig, key) -> Params:
    """Full (unsharded) parameter pytree; sharding slices it per device."""
    d, f, h = cfg.d_model, cfg.d_ff, cfg.num_heads
    hd = d // h
    L, V = cfg.num_layers, cfg.vocab_size
    ks = jax.random.split(key, 8)
    s = 0.02
    dt = cfg.dtype
    params = {
        "embed": {
            "tok": (jax.random.normal(ks[0], (V, d)) * s).astype(dt),
            "pos": (jax.random.normal(ks[1], (cfg.max_len, d)) * s).astype(dt),
        },
        "stages": {
            # leading axis L: layer-stacked, later split into pp stages
            "ln1_scale": jnp.ones((L, d), dt),
            "ln1_bias": jnp.zeros((L, d), dt),
            "wqkv": (jax.random.normal(ks[2], (L, d, 3, h, hd)) * s).astype(dt),
            "wo": (jax.random.normal(ks[3], (L, h, hd, d)) * s).astype(dt),
            "ln2_scale": jnp.ones((L, d), dt),
            "ln2_bias": jnp.zeros((L, d), dt),
            "w1": (jax.random.normal(ks[4], (L, d, f)) * s).astype(dt),
            "b1": jnp.zeros((L, f), dt),
            "w2": (jax.random.normal(ks[5], (L, f, d)) * s).astype(dt),
            "b2": jnp.zeros((L, d), dt),
        },
        "tail": {
            "lnf_scale": jnp.ones((d,), dt),
            "lnf_bias": jnp.zeros((d,), dt),
            "lm_head": (jax.random.normal(ks[6], (d, V)) * s).astype(dt),
            "moe": init_moe_params(
                ks[7], d, f, cfg.n_experts, cfg.n_experts, dtype=dt
            ),
        },
    }
    return params


def param_specs(cfg: ParallelTransformerConfig) -> Params:
    """PartitionSpecs for every leaf: how the global pytree shards over
    the mesh axes (dp/pp/ep/sp/tp)."""
    return {
        "embed": {"tok": P(), "pos": P()},
        "stages": {
            "ln1_scale": P("pp"),
            "ln1_bias": P("pp"),
            "wqkv": P("pp", None, None, "tp", None),
            "wo": P("pp", "tp", None, None),
            "ln2_scale": P("pp"),
            "ln2_bias": P("pp"),
            "w1": P("pp", None, "tp"),
            "b1": P("pp", "tp"),
            "w2": P("pp", "tp", None),
            "b2": P("pp"),
        },
        "tail": {
            "lnf_scale": P(),
            "lnf_bias": P(),
            "lm_head": P(None, "tp"),  # vocab-parallel head (see loss)
            "moe": MoEParams(
                router=P(),
                w1=P("ep"),
                b1=P("ep"),
                w2=P("ep"),
                b2=P("ep"),
            ),
        },
    }


def make_sharded_params(
    cfg: ParallelTransformerConfig, mesh: Mesh, key
) -> Params:
    full = _init_full_params(cfg, key)
    specs = param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)), full, specs
    )


def _layer_norm(x, scale, bias):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + 1e-5) * scale + bias).astype(x.dtype)


def _block(layer_params, x, use_flash_ring=False, rope=False):
    """One transformer block, per-device view: heads/FFN tp-sharded,
    sequence sp-sharded (ring attention handles the full context)."""
    h = _layer_norm(x, layer_params["ln1_scale"], layer_params["ln1_bias"])
    qkv = jnp.einsum("btd,dchx->btchx", h, layer_params["wqkv"])
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B,T,H/tp,hd]
    if rope:
        from ..models.transformer import apply_rope

        offset = lax.axis_index("sp") * x.shape[1]
        q = apply_rope(q, offset=offset)
        k = apply_rope(k, offset=offset)
    attn_fn = ring_flash_attention if use_flash_ring else ring_attention
    attn = attn_fn(q, k, v, axis_name="sp", causal=True)
    proj = jnp.einsum("bthx,hxd->btd", attn, layer_params["wo"])
    x = x + lax.psum(proj, "tp")
    h = _layer_norm(x, layer_params["ln2_scale"], layer_params["ln2_bias"])
    h = column_parallel_dense(h, layer_params["w1"], layer_params["b1"])
    h = jax.nn.gelu(h)
    h = row_parallel_dense(h, layer_params["w2"], axis_name="tp")
    return x + h + layer_params["b2"]


def _resolve_flash_ring(cfg: "ParallelTransformerConfig", t_local: int):
    """Trace-time engine choice (backend + tileability are static).
    The auto gate also checks the per-hop backward VMEM budget — each
    ring hop runs the dK/dV kernel at the local length (ADVICE r4)."""
    import numpy as np

    from ..ops.flash_attention import fits_vmem, supports_seq

    if cfg.flash_ring == "auto":
        return (
            jax.default_backend() == "tpu"
            and supports_seq(t_local)
            and fits_vmem(
                t_local,
                cfg.d_model // cfg.num_heads,
                1,
                np.dtype(cfg.dtype).itemsize,
            )
        )
    return bool(cfg.flash_ring)


def _stage_fn(stage_params, x, use_flash_ring=False, rope=False):
    """Apply this pp stage's layer stack (scan over its layers)."""

    def body(h, layer):
        return _block(layer, h, use_flash_ring, rope), None

    out, _ = lax.scan(body, x, stage_params)
    return out


DATA_AXES = ("dp", "ep", "sp")  # batch over dp+ep, sequence over sp


def _embed(embed_params, tokens, cfg: ParallelTransformerConfig):
    """Token (+ learned position, unless RoPE) embedding. tokens:
    [B_local, T_local] -> [B_local, T_local, d]."""
    sp_idx = lax.axis_index("sp")
    t_local = tokens.shape[1]
    x = embed_params["tok"][tokens]
    if not cfg.rope:
        pos = embed_params["pos"][sp_idx * t_local + jnp.arange(t_local)]
        x = x + pos[None]
    return x


def _tail_loss(tail_params, x, labels, cfg: ParallelTransformerConfig):
    """MoE block + final norm + vocab-parallel cross-entropy over the
    stack's output. x: [B, T_local, d], labels: [B, T_local] -> scalar
    (LOCAL mean; data-axis reduction is the caller's)."""
    b, t_local = labels.shape
    # Expert-parallel MoE block (switch-style) + residual.
    flat = x.reshape(b * t_local, -1)
    x = x + moe_ffn(
        tail_params["moe"],
        flat,
        axis_name="ep",
        capacity_factor=cfg.moe_capacity_factor,
        wire=cfg.moe_wire,
        intra_wire=cfg.moe_intra_wire,
        hier=cfg.moe_hier,
    ).reshape(x.shape)

    x = _layer_norm(x, tail_params["lnf_scale"], tail_params["lnf_bias"])
    # Vocab-parallel cross-entropy (the Megatron-style tail; single-chip
    # analog: ops/fused_xent.py). The head is sharded over "tp" on its
    # vocabulary axis — each member computes only its (bt, V/tp) logit
    # shard and the softmax statistics cross the axis as two scalars
    # per token (pmax of the shard max, psum of the scaled expsum, psum
    # of the masked target logit). Full-vocab logits never exist on any
    # device, so head memory AND logit traffic scale down with tp.
    tp_idx = lax.axis_index("tp")
    head = tail_params["lm_head"]  # local shard: [d, V/tp]
    v_local = head.shape[1]
    logits = jnp.einsum(
        "btd,dv->btv", x.astype(jnp.float32), head.astype(jnp.float32)
    )
    # stop_gradient BEFORE pmax: the stability shift carries no
    # gradient, and pmax has no differentiation rule — a symbolically
    # zero tangent keeps autodiff from ever asking for one
    m = lax.pmax(jnp.max(lax.stop_gradient(logits), axis=-1), "tp")
    s = lax.psum(
        jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), "tp"
    )
    lse = m + jnp.log(s)
    local = labels - tp_idx * v_local
    hit = (local >= 0) & (local < v_local)
    idx = jnp.clip(local, 0, v_local - 1)
    target = lax.psum(
        jnp.where(
            hit,
            jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0],
            0.0,
        ),
        "tp",
    )
    return (lse - target).mean()


def _pick_n_micro(b_local: int, want: int) -> int:
    """Largest microbatch count <= want that divides the local batch
    (min(want, b_local) alone crashes the reshape when it doesn't
    divide, e.g. b_local=6, want=4)."""
    n = min(want, b_local)
    while b_local % n:
        n -= 1
    return n


def _forward_loss(params, tokens, labels, cfg: ParallelTransformerConfig):
    """Per-device forward + loss, GPipe schedule (differentiate-through;
    the 1F1B path in make_train_step never calls this). tokens/labels:
    [B_local, T_local]."""
    t_local = tokens.shape[1]
    x = _embed(params["embed"], tokens, cfg)

    # Pipeline over microbatches (batch split).
    b_local = x.shape[0]
    n_micro = _pick_n_micro(b_local, cfg.n_microbatches)
    xm = x.reshape(n_micro, b_local // n_micro, t_local, -1)
    use_flash_ring = _resolve_flash_ring(cfg, t_local)
    out = gpipe(
        functools.partial(
            _stage_fn, use_flash_ring=use_flash_ring, rope=cfg.rope
        ),
        params["stages"],
        xm,
        axis_name="pp",
    )
    # Output lives on the last pp stage; broadcast to all stages so the
    # tail (loss) is computed everywhere (keeps the program SPMD-uniform).
    pp = lax.axis_size("pp")
    stage = lax.axis_index("pp")
    out = lax.psum(jnp.where(stage == pp - 1, out, jnp.zeros_like(out)), "pp")
    x = out.reshape(b_local, t_local, -1)
    loss = _tail_loss(params["tail"], x, labels, cfg)
    return lax.pmean(loss, DATA_AXES)


def _spec_axes(spec) -> set:
    """Mesh axes a PartitionSpec shards over."""
    axes = set()
    for part in spec:
        if part is None:
            continue
        for a in part if isinstance(part, tuple) else (part,):
            axes.add(a)
    return axes


def _sync_grads(grads, specs, axis_sizes):
    """Per-leaf gradient synchronization (rule in module docstring)."""
    all_axes = tuple(axis_sizes)

    def one(g, spec):
        sharded = _spec_axes(spec)
        reduce_axes = tuple(a for a in all_axes if a not in sharded)
        if reduce_axes:
            g = lax.pmean(g, reduce_axes)
        div = 1
        for a in sharded & {"pp", "ep", "tp"}:
            div *= axis_sizes[a]
        if div != 1:
            g = g / div
        return g

    return jax.tree_util.tree_map(one, grads, specs)


def make_train_step(cfg: ParallelTransformerConfig, mesh: Mesh):
    """Build the jitted full train step over the mesh: forward, backward,
    gradient sync on every axis, SGD update. Returns step(params, tokens,
    labels) -> (params, loss)."""
    specs = param_specs(cfg)
    data_spec = P(("dp", "ep"), "sp")
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = axis_sizes.get("tp", 1)
    if cfg.vocab_size % tp:
        raise ValueError(
            f"vocab_size={cfg.vocab_size} must divide evenly over the "
            f"tp axis ({tp}) for the vocab-parallel head"
        )

    def _grads_gpipe(params, tokens, labels):
        return jax.value_and_grad(_forward_loss)(
            params, tokens, labels, cfg
        )

    def _grads_1f1b(params, tokens, labels):
        """Training grads via the bounded-memory 1F1B schedule: embed
        under jax.vjp in front, the stage stack inside pipeline_1f1b,
        the MoE+head tail as its parameterized loss (per-microbatch
        expert capacity). Local grads carry NO data-axis scaling —
        matching the gpipe path, where the trailing pmean contributes
        none either (JAX transposes psum to psum: the 1/n and the
        backward psum cancel, so the cotangent reaching the local loss
        is 1). _sync_grads then treats both paths identically."""
        t_local = tokens.shape[1]
        x, embed_vjp = jax.vjp(
            lambda ep: _embed(ep, tokens, cfg), params["embed"]
        )
        b_local = x.shape[0]
        n_micro = _pick_n_micro(b_local, cfg.n_microbatches)
        xm = x.reshape(n_micro, b_local // n_micro, t_local, -1)
        lm = labels.reshape(n_micro, b_local // n_micro, t_local)
        use_flash_ring = _resolve_flash_ring(cfg, t_local)
        loss, stage_grads, tail_grads, dxm = pipeline_1f1b(
            functools.partial(
                _stage_fn, use_flash_ring=use_flash_ring, rope=cfg.rope
            ),
            lambda tp_, y, tgt: _tail_loss(tp_, y, tgt, cfg),
            params["stages"],
            xm,
            lm,
            axis_name="pp",
            loss_params=params["tail"],
            return_dx=True,
        )
        # input cotangents live on stage 0; broadcast over pp so every
        # stage computes identical (replicated) embed grads
        stage = lax.axis_index("pp")
        dx = lax.psum(
            jnp.where(stage == 0, dxm, jnp.zeros_like(dxm)), "pp"
        ).reshape(b_local, t_local, -1)
        (embed_grads,) = embed_vjp(dx.astype(x.dtype))
        # pipeline_1f1b returns EXACT per-stage grads; _sync_grads
        # expects the gpipe-autodiff convention, where pp-sharded stage
        # grads arrive pp-inflated (the transpose of the output
        # broadcast psum sums identical cotangents from all pp members)
        # and are divided back. Convert so one sync rule serves both.
        pp = lax.axis_size("pp")
        stage_grads = jax.tree_util.tree_map(
            lambda g: g * pp, stage_grads
        )
        grads = {
            "embed": embed_grads,
            "stages": stage_grads,
            "tail": tail_grads,
        }
        return lax.pmean(loss, DATA_AXES), grads

    if cfg.pipeline_schedule not in ("1f1b", "gpipe"):
        raise ValueError(
            f"unknown pipeline_schedule {cfg.pipeline_schedule!r}"
        )
    # pp=1 has nothing to schedule: the gpipe path is then plain
    # differentiate-through with full-batch MoE capacity and no
    # per-stage recompute — keep that cost/numerics for non-pipelined
    # meshes (ADVICE: 1f1b at pp=1 would only add ~2x stage FLOPs and
    # per-microbatch expert capacity).
    grads_fn = (
        _grads_1f1b
        if cfg.pipeline_schedule == "1f1b" and axis_sizes.get("pp", 1) > 1
        else _grads_gpipe
    )

    def per_device_step(params, tokens, labels):
        loss, grads = grads_fn(params, tokens, labels)
        grads = _sync_grads(grads, specs, axis_sizes)
        params = jax.tree_util.tree_map(
            lambda p, g: p - cfg.learning_rate * g.astype(p.dtype),
            params,
            grads,
        )
        return params, loss

    mapped = shard_map(
        per_device_step,
        mesh=mesh,
        in_specs=(specs, data_spec, data_spec),
        out_specs=(specs, P()),
        check_vma=False,
    )
    return jax.jit(mapped)
