"""Plain reference of the ``deepseek_v3`` family (Kanana-2-30B-A3B):
``jax.numpy`` in float32 at ``highest`` matmul precision, multi-head latent
attention written out (no absorbed form, no kernel, no cache), dense masked
attention computed in blocks of query rows, every held expert applied to
every token, no sort. It imports nothing of the program and is handed
weights that the benchmark made (``lib/weights.py``), in this tree (H heads,
nope = ``qk_nope_head_dim``, rd = ``qk_rope_head_dim``, vd = ``v_head_dim``,
r = ``kv_lora_rank``, E = ``n_routed_experts_total``, held =
``n_routed_experts``, the experts ``experts_held[0] .. experts_held[1]-1``,
f = ``moe_intermediate_size``, fs = f x ``n_shared_experts``):

    params/Embed_0/embedding [vocab, d]
    params/block_<i>/RMSNorm_0, RMSNorm_1/scale [d]      before attention, before the feed-forward
    params/block_<i>/MultiHeadAttention_0/
        q/kernel [d, H, nope + rd]     kv_a/kernel [d, r + rd]     kv_norm/scale [r]
        kv_b/kernel [r, H, nope + vd]  out/kernel [H, vd, d]
    params/block_<i>/mlp/{gate,up}/kernel [d, intermediate], down/kernel [intermediate, d]
                                                 the first ``first_k_dense_replace`` layers
    params/block_<i>/moe/router/kernel [d, E]   select_bias [E]
        w_gate, w_up [held, d, f]   w_down [held, f, d]
        shared/{gate,up}/kernel [d, fs]   shared/down/kernel [fs, d]     the other layers
    params/RMSNorm_0/scale [d]                   final norm
    params/lm_head/kernel [d, vocab]

Equations (the catalog row's ``config`` and, for what it has no key for, the
family's published modelling code; the configuration's ``assumed`` lists the
latter). With h = rms(x) (``rms_norm_eps``):

    q = W_q h                       [.., H, nope + rd] = (q_nope, q_rope)
    c = W_kva h                     [.., r + rd]       = (c_kv, k_rope): k_rope is one head for all H
    kv = W_kvb rms(c_kv)            [.., H, nope + vd] = (k_nope, v)
    q_rope, k_rope rotated by position (``rope_theta``): with
      ``rope_interleave`` the i-th pair is (x[2i], x[2i+1]), taken as the
      complex number x[2i] + i x[2i+1] and multiplied by exp(i pos
      theta^(-2i/rd)); the real parts are written first, then the imaginary
      parts. q_nope, k_nope and v are not rotated
    q = (q_nope, q_rope);  k = (k_nope, k_rope for every head)
    o = softmax(q k^T (nope + rd)^-0.5 + causal) v     [.., H, vd]
    x = x + W_o o;   x = x + F(rms(x))
    F dense: W_d(silu(W_g h) * W_u h)
    F experts: s = sigmoid(W_r h) over all E; sel = top_k(s + select_bias)
      (``n_group`` 1: no group limit); w = s[sel] / (sum(s[sel]) + 1e-20) *
      ``routed_scaling_factor``; y = shared(h) + sum over sel held here of
      w_e expert_e(h); the ``n_shared_experts`` shared experts are one gated
      MLP of their summed width
    logits = rms(x) @ head;  loss = mean cross entropy over all positions

Departures: what experts held on other chips would add is left out (the
configuration's deployment: this is one expert-parallel rank's part), as in
the program; the vocabulary is the configuration's slice; ``select_bias`` is
a fixed leaf (``noaux_tc``'s balancing update is outside the gradient step
and not run).

``precision`` is ``"float32"``, or ``"bfloat16"`` / ``"fp8"`` for a control
(``references/transformer.py: _product``: every matrix product's operands,
and with ``fp8`` the cotangents, rounded to that type). ``cfg["fault"]``
plants one fault of this model's own, by its name in ``FAULTS`` or by its
index there as a traced number (``tools/limits_deepseek_v3.py`` compiles one
program for all of them; an index that names none is no fault):
``scale_nope`` (the scores scaled by nope^-0.5, 128 for 192),
``rope_half_split`` (the rotation pairs x[i] with x[i + rd/2] where the
pairs are interleaved), ``no_k_rope`` (the rotated part left out of the
key), ``no_latent_norm``, ``top_k_less_1`` (the last of the k choices gets
no weight: top-5 for top-6), ``no_route_scale``, ``no_shared``.
"""

import json

import jax
import jax.numpy as jnp

from benchmark.references.afmoe import _gated_mlp, _rms
from benchmark.references.transformer import (  # noqa: F401 - the interface
    _product,
    diff_norms,
    leaf_norms,
)

QUERY_BLOCK = 256
LOSS_BLOCK = 2048
FAULTS = ("scale_nope", "rope_half_split", "no_k_rope", "no_latent_norm",
          "top_k_less_1", "no_route_scale", "no_shared")
# the keys of a configuration that shape the reference's program
_KEYS = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
         "num_hidden_layers", "first_k_dense_replace", "rope_theta",
         "rope_interleave", "rms_norm_eps", "num_experts_per_tok",
         "n_routed_experts_total", "experts_held", "n_shared_experts",
         "norm_topk_prob", "routed_scaling_factor", "scoring_func", "fault")


def program_key(cfg) -> str:
    """The part of a configuration that shapes the reference's program, as
    a hashable key for a cache of jitted functions (the loop hands back
    ``json.loads`` of it as ``cfg``)."""
    return json.dumps({k: cfg.get(k) for k in _KEYS})


def _when(cfg, name, faulty, normal):
    """``normal``, or ``faulty`` where the planted fault is ``name``."""
    fault = cfg.get("fault")
    if fault is None:
        return normal
    if isinstance(fault, str):
        return faulty if fault == name else normal
    return jnp.where(fault == FAULTS.index(name), faulty, normal)


def _rope(x, theta, interleave):
    """Rotate ``x [batch, seq, heads, rd]`` by position. The i-th pair,
    (x[2i], x[2i+1]) where ``interleave`` and (x[i], x[i + rd/2]) where
    not, is a complex number that turns by pos x theta^(-2i/rd); real
    parts first, then imaginary parts."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    if interleave:
        re, im = x[..., 0::2], x[..., 1::2]
    else:
        re, im = x[..., :half], x[..., half:]
    return jnp.concatenate([re * cos - im * sin, re * sin + im * cos],
                           axis=-1)


def _attention(h, p, cfg, precision):
    b, t, _ = h.shape
    heads = cfg["num_attention_heads"]
    nope, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    width = nope + cfg["qk_rope_head_dim"]
    q = _product("btd,dhk->bthk", h, p["q"]["kernel"], precision)
    c = _product("btd,dk->btk", h, p["kv_a"]["kernel"], precision)
    c_kv, k_rope = c[..., :rank], c[..., None, rank:]
    normed = _rms(c_kv, p["kv_norm"]["scale"], cfg["rms_norm_eps"])
    if cfg.get("fault") is not None:
        normed = _when(cfg, "no_latent_norm", c_kv, normed)
    kv = _product("btr,rhk->bthk", normed, p["kv_b"]["kernel"], precision)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    def rotate(x):
        out = _rope(x, cfg["rope_theta"], cfg["rope_interleave"])
        if cfg.get("fault") is not None:
            out = _when(cfg, "rope_half_split", _rope(
                x, cfg["rope_theta"], not cfg["rope_interleave"]), out)
        return out

    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], axis=-1)
    k_rope = rotate(k_rope) * _when(cfg, "no_k_rope", 0.0, 1.0)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, t, heads, k_rope.shape[-1]))],
        axis=-1)
    scale = _when(cfg, "scale_nope", jnp.float32(nope) ** -0.5,
                  jnp.float32(width) ** -0.5)
    block = min(QUERY_BLOCK, t)
    cols = jnp.arange(t)

    @jax.checkpoint
    def rows_of(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = _product("bqhk,bshk->bhqs", qb, k, precision) * scale
        rows = start + jnp.arange(block)
        keep = cols[None] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        return _product("bhqs,bshk->bqhk", probs, v, precision)

    out = jax.lax.map(rows_of, jnp.arange(0, t, block))  # [blocks, b, q, ..]
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, heads, v.shape[-1])
    return _product("bthk,hkd->btd", out, p["out"]["kernel"], precision)


def _route(h, p, cfg, precision):
    """``(chosen [tokens, k], gates [tokens, k])`` over all experts."""
    logits = _product("td,de->te", h, p["router"]["kernel"], precision)
    if cfg["scoring_func"] != "sigmoid":
        raise ValueError(f"scoring_func {cfg['scoring_func']!r}")
    scores = jax.nn.sigmoid(logits)
    k = cfg["num_experts_per_tok"]
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["select_bias"]), k)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates * _when(cfg, "top_k_less_1", jnp.arange(k) < k - 1, 1.0)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return chosen, gates * _when(cfg, "no_route_scale", 1.0,
                                 cfg["routed_scaling_factor"])


def _experts(h, p, cfg, precision):
    """The expert layer on ``h [tokens, d]``: the shared experts and the
    held experts' part of the routed result, each held expert applied to
    every token and weighted by the token's gate for it (zero where it was
    not chosen)."""
    first, last = cfg["experts_held"]
    chosen, gates = _route(h, p, cfg, precision)
    # gate of every token for every expert of the deployment
    dense_gates = jnp.sum(
        jax.nn.one_hot(chosen, cfg["n_routed_experts_total"],
                       dtype=jnp.float32) * gates[..., None], axis=1)

    @jax.checkpoint
    def add_expert(y, expert):
        w_gate, w_up, w_down, gate = expert
        out = _gated_mlp(h, {"gate": {"kernel": w_gate},
                             "up": {"kernel": w_up},
                             "down": {"kernel": w_down}}, precision)
        return y + gate[:, None] * out, None

    y = jnp.zeros_like(h)
    if cfg["n_shared_experts"]:
        y = _gated_mlp(h, p["shared"], precision) * _when(
            cfg, "no_shared", 0.0, 1.0)
    y, _ = jax.lax.scan(add_expert, y, (
        p["w_gate"], p["w_up"], p["w_down"], dense_gates[:, first:last].T))
    return y, chosen


def _layer(x, p, cfg, layer, precision):
    eps = cfg["rms_norm_eps"]
    h = _rms(x, p["RMSNorm_0"]["scale"], eps)
    x = x + _attention(h, p["MultiHeadAttention_0"], cfg, precision)
    h = _rms(x, p["RMSNorm_1"]["scale"], eps)
    chosen = None
    if layer < cfg["first_k_dense_replace"]:
        h = _gated_mlp(h, p["mlp"], precision)
    else:
        b, t, d = h.shape
        h, chosen = _experts(h.reshape(b * t, d), p["moe"], cfg, precision)
        h = h.reshape(b, t, d)
    return x + h, chosen


def _hidden(params, tokens, cfg, precision):
    """The final norm's output and every expert layer's choices."""
    p = params["params"]
    x = p["Embed_0"]["embedding"][tokens]
    routes = []
    for i in range(cfg["num_hidden_layers"]):
        x, chosen = jax.checkpoint(
            lambda x, bp, i=i: _layer(x, bp, cfg, i, precision))(
                x, p[f"block_{i}"])
        if chosen is not None:
            routes.append(chosen)
    return _rms(x, p["RMSNorm_0"]["scale"], cfg["rms_norm_eps"]), routes


def forward(params, tokens, cfg, precision="float32"):
    """Float32 logits ``[batch, seq, vocab]`` for ``tokens [batch, seq]``."""
    x, _ = _hidden(params, tokens, cfg, precision)
    return _product("btd,dv->btv", x, params["params"]["lm_head"]["kernel"],
                    precision)


def routes(params, tokens, cfg, precision="float32"):
    """The experts each token chose, ``[tokens, k]`` of ids over all the
    deployment's experts, one array per expert layer."""
    return _hidden(params, tokens, cfg, precision)[1]


def loss_sum(params, tokens, labels, cfg, precision="float32"):
    """Sum over all positions of the cross entropy of ``labels``; the
    logits are made ``LOSS_BLOCK`` positions at a time."""
    x, _ = _hidden(params, tokens, cfg, precision)
    head = params["params"]["lm_head"]["kernel"]
    block = min(LOSS_BLOCK, x.shape[1])

    @jax.checkpoint
    def positions(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, block, axis=1)
        lb = jax.lax.dynamic_slice_in_dim(labels, start, block, axis=1)
        logits = _product("btd,dv->btv", xb, head, precision)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return jnp.sum(logz - picked)

    return jnp.sum(jax.lax.map(positions, jnp.arange(0, x.shape[1], block)))


def loss_and_grads(params, tokens, labels, cfg, precision="float32",
                   block_rows=1):
    """Mean loss over all rows of ``tokens [rows, seq]`` and its gradient,
    ``block_rows`` rows at a time: the blocks are a rematerialised scan
    inside one differentiated function (one block, as the loop asks for at
    two rows of 8192, is no scan at all: one gradient tree is held)."""
    rows, seq = tokens.shape
    if rows % block_rows:
        raise ValueError(f"{rows} rows are no whole blocks of {block_rows}")
    blocks = rows // block_rows

    def total(p):
        if blocks == 1:
            return loss_sum(p, tokens, labels, cfg, precision)
        block_loss = jax.checkpoint(
            lambda a, b: loss_sum(p, a, b, cfg, precision))
        return jnp.sum(jax.lax.map(lambda ab: block_loss(*ab), (
            tokens.reshape(blocks, block_rows, seq),
            labels.reshape(blocks, block_rows, seq))))

    loss, grads = jax.value_and_grad(total)(params)
    n = rows * seq
    return loss / n, jax.tree.map(lambda g: g / n, grads)


def sgd_momentum_step(params, trace, tokens, labels, cfg, lr, momentum,
                      precision="float32", block_rows=1):
    """One step of SGD with momentum as optax has it: trace = g + m*trace,
    p = p - lr*trace. Returns the new params and trace, the loss before
    the step and the gradient."""
    loss, grads = loss_and_grads(params, tokens, labels, cfg, precision,
                                 block_rows)
    trace = jax.tree.map(lambda g, t: g + momentum * t, grads, trace)
    params = jax.tree.map(lambda p, t: p - lr * t, params, trace)
    return params, trace, loss, grads
