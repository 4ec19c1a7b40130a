"""Plain reference of the ``afmoe`` family (Arcee Trinity): ``jax.numpy`` in
float32 at ``highest`` matmul precision, dense masked attention computed in
blocks of query rows, every held expert applied to every token, no kernel,
no sort, no cache. It imports nothing of the program and is handed weights
that the benchmark made (``lib/weights.py``), in this tree (H query heads,
KV key/value heads, hd = ``head_dim``, E = ``num_experts_total``, held =
``num_experts``, the experts ``experts_held[0] .. experts_held[1]-1``):

    params/Embed_0/embedding [vocab, d]
    params/block_<i>/RMSNorm_0..3/scale [d]     input, post-attention, pre-mlp, post-mlp
    params/block_<i>/MultiHeadAttention_0/
        q/kernel [d, H, hd]   kv/kernel [d, 2, KV, hd]   gate/kernel [d, H, hd]
        q_norm/scale [hd]     k_norm/scale [hd]          out/kernel [H, hd, d]
    params/block_<i>/mlp/{gate,up}/kernel [d, intermediate], down/kernel [intermediate, d]
                                                 the first ``num_dense_layers`` layers
    params/block_<i>/moe/router/kernel [d, E]   select_bias [E]
        w_gate, w_up [held, d, f]   w_down [held, f, d]
        shared/{gate,up}/kernel [d, f]   shared/down/kernel [f, d]     the other layers
    params/RMSNorm_0/scale [d]                   final norm
    params/lm_head/kernel [d, vocab]

Equations (the catalog row's ``config`` and, for what it has no key for, the
family's published modelling code; the configuration's ``assumed`` lists the
latter):

    x = embedding[tokens] * sqrt(d)                              (mup_enabled)
    x = x + rms(attn(rms(x)));  x = x + rms(ffn(rms(x)))         (sandwich; rms_norm_eps)
    attn: q, k = rms over hd of the projections; RoPE (rotate-half,
      rope_theta) on sliding layers only; softmax(q k^T / sqrt(hd)) v under
      the causal mask, on sliding layers also row - col < sliding_window;
      8 query heads share a key/value head; o = o * sigmoid(W_gate h); W_o o
    dense ffn: W_d(silu(W_g h) * W_u h)
    expert ffn: s = sigmoid(W_r h) over all E; sel = top_k(s + select_bias);
      w = s[sel] / (sum(s[sel]) + 1e-20) * route_scale;
      y = shared(h) + sum over sel held here of w_e expert_e(h)
    logits = rms(x) @ head;  loss = mean cross entropy over all positions

Departures: what experts held on other chips would add is left out (the
configuration's deployment: this is one expert-parallel rank's part), as in
the program; the vocabulary is the configuration's slice; ``select_bias`` is
a fixed leaf (its balancing update, ``load_balance_coeff``, is outside the
gradient step and not run).

``precision`` is ``"float32"``, or ``"bfloat16"`` / ``"fp8"`` for a control
(``references/transformer.py: _product``: every matrix product's operands,
and with ``fp8`` the cotangents, rounded to that type). ``cfg["fault"]``
plants one fault of this model's own, by its name in ``FAULTS`` or by its
index there as a traced number (``tools/limits_afmoe.py`` compiles one
program for all of them; an index that names none is no fault): ``top7``
(the last of the k choices gets no weight), ``no_route_scale``,
``no_shared``, ``no_window``, ``rope_on_full``, ``half_buffer`` (a dispatch
buffer of half the ``tokens x top_k`` rows: pairs sorted past it are
dropped), ``half_rows`` (each held expert keeps the first half of its rows).
"""

import json

import jax
import jax.numpy as jnp

from benchmark.references.transformer import (  # noqa: F401 - the interface
    _product,
    diff_norms,
    leaf_norms,
)

QUERY_BLOCK = 256
LOSS_BLOCK = 2048
FAULTS = ("top7", "no_route_scale", "no_shared", "no_window", "rope_on_full",
          "half_buffer", "half_rows")
# the keys of a configuration that shape the reference's program
_KEYS = ("hidden_size", "head_dim", "num_attention_heads",
         "num_key_value_heads", "num_hidden_layers", "num_dense_layers",
         "layer_types", "sliding_window", "rope_theta", "rms_norm_eps",
         "num_experts_per_tok", "num_experts_total", "experts_held",
         "num_shared_experts", "route_norm", "route_scale", "score_func",
         "mup_enabled", "fault")


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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate ``x [batch, seq, heads, hd]`` by position, rotate-half."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(h, p, cfg, sliding, precision):
    b, t, _ = h.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    q = _product("btd,dhk->bthk", h, p["q"]["kernel"], precision)
    kv = _product("btd,dchk->btchk", h, p["kv"]["kernel"], precision)
    k, v = kv[:, :, 0], kv[:, :, 1]
    q = _rms(q, p["q_norm"]["scale"], eps)
    k = _rms(k, p["k_norm"]["scale"], eps)
    if sliding:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    elif cfg.get("fault") is not None:
        q = _when(cfg, "rope_on_full", _rope(q, cfg["rope_theta"]), q)
        k = _when(cfg, "rope_on_full", _rope(k, cfg["rope_theta"]), k)
    # every query head beside the key/value head it shares
    q = q.reshape(b, t, kv_heads, heads // kv_heads, hd)
    window = t
    if sliding:
        window = _when(cfg, "no_window", t, cfg["sliding_window"])
    block = min(QUERY_BLOCK, t)
    cols = jnp.arange(t)

    @jax.checkpoint
    def rows_of(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = _product("bqcgk,bsck->bcgqs", qb, k, precision) / jnp.sqrt(
            jnp.float32(hd))
        rows = start + jnp.arange(block)
        keep = (cols[None] <= rows[:, None]) & (
            rows[:, None] - cols[None] < window)
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        return _product("bcgqs,bsck->bqcgk", probs, v, precision)

    out = jax.lax.map(rows_of, jnp.arange(0, t, block))  # [blocks, b, q, ..]
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, heads, hd)
    gate = _product("btd,dhk->bthk", h, p["gate"]["kernel"], precision)
    out = out * jax.nn.sigmoid(gate)
    return _product("bthk,hkd->btd", out, p["out"]["kernel"], precision)


def _gated_mlp(h, p, precision):
    g = _product("...d,df->...f", h, p["gate"]["kernel"], precision)
    u = _product("...d,df->...f", h, p["up"]["kernel"], precision)
    return _product("...f,fd->...d", jax.nn.silu(g) * u, p["down"]["kernel"],
                    precision)


def _route(h, p, cfg, precision):
    """``(chosen [tokens, k], gates [tokens, k])`` over all experts."""
    logits = _product("td,de->te", h, p["router"]["kernel"], precision)
    if cfg["score_func"] != "sigmoid":
        raise ValueError(f"score_func {cfg['score_func']!r}")
    scores = jax.nn.sigmoid(logits)
    k = cfg["num_experts_per_tok"]
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["select_bias"]), k)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates * _when(cfg, "top7", jnp.arange(k) < k - 1, 1.0)
    if cfg["route_norm"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return chosen, gates * _when(cfg, "no_route_scale", 1.0,
                                 cfg["route_scale"])


def _drop(chosen, gates, cfg, first, held):
    """The planted drops: zero the gate of a pair that a smaller dispatch
    buffer would not hold. Pairs are ranked as the program sorts them: by
    held expert, then by token and choice."""
    if cfg.get("fault") is None:
        return gates
    tokens, k = chosen.shape
    local = chosen - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    rank = jnp.argsort(order)  # a pair's row in the sorted buffer
    sizes = jnp.sum(key[:, None] == jnp.arange(held + 1), axis=0)
    starts = jnp.cumsum(sizes) - sizes
    kept = _when(cfg, "half_buffer", rank < tokens * k // 2, True) & _when(
        cfg, "half_rows", rank - starts[key] < (sizes[key] + 1) // 2, True)
    return jnp.where(kept.reshape(tokens, k), gates, 0.0)


def _experts(h, p, cfg, precision):
    """The expert layer on ``h [tokens, d]``: the shared expert and the
    held experts' part of the routed result, each held expert applied to
    every token and weighted by the token's gate for it (zero where it was
    not chosen)."""
    first, last = cfg["experts_held"]
    chosen, gates = _route(h, p, cfg, precision)
    gates = _drop(chosen, gates, cfg, first, last - first)
    # gate of every token for every expert of the deployment
    dense_gates = jnp.sum(
        jax.nn.one_hot(chosen, cfg["num_experts_total"], dtype=jnp.float32)
        * gates[..., None], axis=1)

    @jax.checkpoint
    def add_expert(y, expert):
        w_gate, w_up, w_down, gate = expert
        out = _gated_mlp(h, {"gate": {"kernel": w_gate},
                             "up": {"kernel": w_up},
                             "down": {"kernel": w_down}}, precision)
        return y + gate[:, None] * out, None

    y = jnp.zeros_like(h)
    if cfg["num_shared_experts"]:
        y = _gated_mlp(h, p["shared"], precision) * _when(
            cfg, "no_shared", 0.0, 1.0)
    y, _ = jax.lax.scan(add_expert, y, (
        p["w_gate"], p["w_up"], p["w_down"], dense_gates[:, first:last].T))
    return y, chosen


def _layer(x, p, cfg, layer, precision):
    eps = cfg["rms_norm_eps"]
    sliding = cfg["layer_types"][layer] == "sliding_attention"
    h = _rms(x, p["RMSNorm_0"]["scale"], eps)
    h = _attention(h, p["MultiHeadAttention_0"], cfg, sliding, precision)
    x = x + _rms(h, p["RMSNorm_1"]["scale"], eps)
    h = _rms(x, p["RMSNorm_2"]["scale"], eps)
    chosen = None
    if layer < cfg["num_dense_layers"]:
        h = _gated_mlp(h, p["mlp"], precision)
    else:
        b, t, d = h.shape
        h, chosen = _experts(h.reshape(b * t, d), p["moe"], cfg, precision)
        h = h.reshape(b, t, d)
    return x + _rms(h, p["RMSNorm_3"]["scale"], eps), chosen


def _hidden(params, tokens, cfg, precision):
    """The final norm's output and every expert layer's choices."""
    p = params["params"]
    x = p["Embed_0"]["embedding"][tokens]
    if cfg["mup_enabled"]:
        x = x * jnp.sqrt(jnp.float32(cfg["hidden_size"]))
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
