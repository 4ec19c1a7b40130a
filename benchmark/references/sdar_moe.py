"""Plain reference of the ``sdar_moe`` family (SDAR-30B-A3B-Chat) in
block-diffusion training: ``jax.numpy`` in float32 at ``highest`` matmul
precision, the ``2L x 2L`` mask built from its four rules by comparison of
indices, dense masked attention computed in blocks of query rows, every held
expert applied to every token, no kernel, no sort, no cache. It imports
nothing of the program and is handed weights that the benchmark made
(``lib/weights.py``), in this tree (H query heads, KV key/value heads, hd =
``head_dim``, E = ``num_experts_total``, held = ``num_experts``, the experts
``experts_held[0] .. experts_held[1]-1``, f = ``moe_intermediate_size``):

    params/Embed_0/embedding [vocab, d]
    params/block_<i>/RMSNorm_0, RMSNorm_1/scale [d]     before attention, before the expert layer
    params/block_<i>/MultiHeadAttention_0/
        q/kernel [d, H, hd]   kv/kernel [d, 2, KV, hd]
        q_norm/scale [hd]     k_norm/scale [hd]          out/kernel [H, hd, d]
    params/block_<i>/moe/router/kernel [d, E]           (no selection bias)
        w_gate, w_up [held, d, f]   w_down [held, f, d]
    params/RMSNorm_0/scale [d]                          final norm
    params/lm_head/kernel [d, vocab]

Equations (the catalog row's ``config`` and, for what it has no key for, the
published modelling code of Qwen3-MoE, from which the family is initialised,
and the block-diffusion objective of BD3-LM, arXiv 2503.09573, under which
SDAR, arXiv 2510.06303, continues its training; the configuration's
``assumed`` lists the latter two):

    block:  x = x + attn(rms(x));  x = x + moe(rms(x));  a final rms; no bias
    attn:   q = W_q h to H heads of hd, k, v to KV heads; rms over hd of
            every head of q and of k (own scales); rotate-half RoPE at
            ``rope_theta`` on q and k; softmax(q k^T / sqrt(hd) + mask) v;
            H / KV query heads share a key/value head; W_o
    moe:    s = softmax(W_r h) over all E (float32); sel = top_k(s); w =
            s[sel] / (sum(s[sel]) + 1e-20) (``norm_topk_prob``); no scale, no
            selection bias, no shared expert; e(h) = W_d(silu(W_g h) * W_u h);
            y = sum over sel held here of w_e e(h)
    step:   ``tokens [rows, 2L]`` = xt (+) x0: a noised copy of the clean row
            x0 of L tokens in blocks of B = ``block_length``, b(i) = i // B
            (every (row, block) drew t = eps + (1 - eps) u and every token of
            the block became ``mask_token_id`` with probability t; the batch
            carries the draw, ``models/sdar_moe.py: make_batch``). Position
            ids are 0..L-1 twice. With i = r mod L, j = c mod L query r
            keeps key c iff
              r <  L, c <  L:  b(i) == b(j)      noised sees noised
              r <  L, c >= L:  b(j) <  b(i)      noised sees clean
              r >= L, c >= L:  b(j) <= b(i)      clean sees clean
              r >= L, c <  L:  never
            logits = rms(x[:, :L]) @ head: the noised half alone
    loss:   sum over rows and i < L of w_i CE(logits_i, x0_i) / (rows L),
            ``weights [rows, L]`` = w: 1/t where xt_i is the mask id, else 0

Departures: what experts held on other chips would add is left out (the
configuration's deployment: this is one expert-parallel rank's part), as in
the program; the vocabulary is the configuration's slice, whose last row
stands for the mask token.

``precision`` is ``"float32"``, or ``"bfloat16"`` / ``"fp8"`` for a control
(``references/transformer.py: _product``). ``cfg["fault"]`` plants one fault
of this model's own, by its name in ``FAULTS`` or by its index there as a
traced number (``tools/limits_sdar_moe.py`` compiles one program for all of
them; an index that names none is no fault): ``leak_own_clean`` (a noised
query also sees the clean copy of its own block, the leak that makes the
loss trivial), ``causal_in_block`` (inside a block a query sees only what is
not after it), ``no_noised_part`` (noised sees no noised key),
``positions_2l`` (positions 0..2L-1 where they are 0..L-1 twice),
``no_weight`` (1 for 1/t), ``head_on_clean`` (the head over the clean half),
``no_gate_norm`` (the gates not renormalised over the chosen),
``top_k_less_1`` (the last of the k choices gets no weight), ``half_blocks``
(the second half of every row's blocks left out of the loss, the mean taken
over the rest).
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
FAULTS = ("leak_own_clean", "causal_in_block", "no_noised_part",
          "positions_2l", "no_weight", "head_on_clean", "no_gate_norm",
          "top_k_less_1", "half_blocks")
# the keys of a configuration that shape the reference's program
_KEYS = ("hidden_size", "head_dim", "num_attention_heads",
         "num_key_value_heads", "num_hidden_layers", "rope_theta",
         "rms_norm_eps", "num_experts_per_tok", "num_experts_total",
         "experts_held", "norm_topk_prob", "block_length", "fault")


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


def keep_mask(cfg, rows, length: int):
    """``[len(rows), 2L]`` bool: which keys the queries ``rows`` keep, from
    the four rules, by comparison of indices."""
    block = cfg["block_length"]
    cols = jnp.arange(2 * length)
    r, c = rows[:, None], cols[None, :]
    i, j = r % length, c % length
    bi, bj = i // block, j // block
    # inside a block both ways; the fault keeps what is not after the query
    within = _when(cfg, "causal_in_block", j <= i, True)
    noised_noised = (bi == bj) & within & _when(
        cfg, "no_noised_part", False, True)
    noised_clean = _when(cfg, "leak_own_clean", bj <= bi, bj < bi)
    clean_clean = (bj < bi) | ((bj == bi) & within)
    return jnp.where(
        r < length,
        jnp.where(c < length, noised_noised, noised_clean),
        (c >= length) & clean_clean)


def _rope(x, theta, positions):
    """Rotate ``x [batch, seq, heads, hd]`` by ``positions [seq]``,
    rotate-half."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(h, p, cfg, precision):
    b, t, _ = h.shape  # t = 2L
    length = t // 2
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    q = _product("btd,dhk->bthk", h, p["q"]["kernel"], precision)
    kv = _product("btd,dchk->btchk", h, p["kv"]["kernel"], precision)
    k, v = kv[:, :, 0], kv[:, :, 1]
    q = _rms(q, p["q_norm"]["scale"], eps)
    k = _rms(k, p["k_norm"]["scale"], eps)
    at = jnp.arange(t)
    positions = _when(cfg, "positions_2l", at, at % length)
    q = _rope(q, cfg["rope_theta"], positions)
    k = _rope(k, cfg["rope_theta"], positions)
    # every query head beside the key/value head it shares
    q = q.reshape(b, t, kv_heads, heads // kv_heads, hd)
    block = min(QUERY_BLOCK, t)

    @jax.checkpoint
    def rows_of(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = _product("bqcgk,bsck->bcgqs", qb, k, precision) / jnp.sqrt(
            jnp.float32(hd))
        keep = keep_mask(cfg, start + jnp.arange(block), length)
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        return _product("bcgqs,bsck->bqcgk", probs, v, precision)

    out = jax.lax.map(rows_of, jnp.arange(0, t, block))  # [blocks, b, q, ..]
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, heads, hd)
    return _product("bthk,hkd->btd", out, p["out"]["kernel"], precision)


def _route(h, p, cfg, precision):
    """``(chosen [tokens, k], gates [tokens, k])`` over all experts."""
    logits = _product("td,de->te", h, p["router"]["kernel"], precision)
    scores = jax.nn.softmax(logits, axis=-1)
    k = cfg["num_experts_per_tok"]
    gates, chosen = jax.lax.top_k(scores, k)
    gates = gates * _when(cfg, "top_k_less_1", jnp.arange(k) < k - 1, 1.0)
    if cfg["norm_topk_prob"]:
        gates = _when(cfg, "no_gate_norm", gates, gates / (
            jnp.sum(gates, axis=-1, keepdims=True) + 1e-20))
    return chosen, gates


def _experts(h, p, cfg, precision):
    """The expert layer on ``h [tokens, d]``: the held experts' part of the
    routed result, each held expert applied to every token and weighted by
    the token's gate for it (zero where it was not chosen)."""
    first, last = cfg["experts_held"]
    chosen, gates = _route(h, p, cfg, precision)
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

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        p["w_gate"], p["w_up"], p["w_down"], dense_gates[:, first:last].T))
    return y, chosen


def _layer(x, p, cfg, precision):
    eps = cfg["rms_norm_eps"]
    h = _rms(x, p["RMSNorm_0"]["scale"], eps)
    x = x + _attention(h, p["MultiHeadAttention_0"], cfg, precision)
    h = _rms(x, p["RMSNorm_1"]["scale"], eps)
    b, t, d = h.shape
    h, chosen = _experts(h.reshape(b * t, d), p["moe"], cfg, precision)
    return x + h.reshape(b, t, d), chosen


def _hidden(params, tokens, cfg, precision):
    """The final norm's output over the noised half ``[rows, L, d]`` and
    every expert layer's choices (of all 2L positions)."""
    p = params["params"]
    x = p["Embed_0"]["embedding"][tokens]
    length = tokens.shape[1] // 2
    routes = []
    for i in range(cfg["num_hidden_layers"]):
        x, chosen = jax.checkpoint(
            lambda x, bp: _layer(x, bp, cfg, precision))(x, p[f"block_{i}"])
        routes.append(chosen)
    x = _when(cfg, "head_on_clean", x[:, length:], x[:, :length])
    return _rms(x, p["RMSNorm_0"]["scale"], cfg["rms_norm_eps"]), routes


def forward(params, tokens, cfg, precision="float32"):
    """Float32 logits ``[rows, L, vocab]`` for ``tokens [rows, 2L]``."""
    x, _ = _hidden(params, tokens, cfg, precision)
    return _product("btd,dv->btv", x, params["params"]["lm_head"]["kernel"],
                    precision)


def routes(params, tokens, cfg, precision="float32"):
    """The experts each position chose, ``[positions, k]`` of ids over all
    the deployment's experts, one array per layer."""
    return _hidden(params, tokens, cfg, precision)[1]


def loss_sum(params, tokens, weights, cfg, precision="float32"):
    """``(sum over rows and i < L of w_i CE(logits_i, x0_i), positions
    counted)``; the logits are made ``LOSS_BLOCK`` positions at a time."""
    x, _ = _hidden(params, tokens, cfg, precision)
    head = params["params"]["lm_head"]["kernel"]
    rows, length = weights.shape
    clean = tokens[:, length:]
    weights = _when(cfg, "no_weight", (weights > 0).astype(jnp.float32),
                    weights)
    counted = _when(cfg, "half_blocks",
                    jnp.arange(length) < length // 2, True)
    weights = jnp.where(counted, weights, 0.0)
    block = min(LOSS_BLOCK, length)

    @jax.checkpoint
    def positions(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, block, axis=1)
        lb = jax.lax.dynamic_slice_in_dim(clean, start, block, axis=1)
        wb = jax.lax.dynamic_slice_in_dim(weights, start, block, axis=1)
        logits = _product("btd,dv->btv", xb, head, precision)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return jnp.sum(wb * (logz - picked))

    total = jnp.sum(jax.lax.map(positions, jnp.arange(0, length, block)))
    return total, rows * jnp.sum(jnp.broadcast_to(counted, (length,)))


def loss_and_grads(params, tokens, weights, cfg, precision="float32",
                   block_rows=1):
    """The loss over all rows of ``tokens [rows, 2L]`` and its gradient,
    ``block_rows`` rows at a time: the blocks are a rematerialised scan
    inside one differentiated function (one block is no scan at all: one
    gradient tree is held)."""
    rows, positions = tokens.shape
    if rows % block_rows:
        raise ValueError(f"{rows} rows are no whole blocks of {block_rows}")
    blocks = rows // block_rows

    def total(p):
        if blocks == 1:
            return loss_sum(p, tokens, weights, cfg, precision)
        block_loss = jax.checkpoint(
            lambda a, b: loss_sum(p, a, b, cfg, precision))
        sums, counts = jax.lax.map(lambda ab: block_loss(*ab), (
            tokens.reshape(blocks, block_rows, positions),
            weights.reshape(blocks, block_rows, positions // 2)))
        return jnp.sum(sums), jnp.sum(counts)

    (loss, n), grads = jax.value_and_grad(total, has_aux=True)(params)
    return loss / n, jax.tree.map(lambda g: g / n, grads)


def sgd_momentum_step(params, trace, tokens, weights, cfg, lr, momentum,
                      precision="float32", block_rows=1):
    """One step of SGD with momentum as optax has it: trace = g + m*trace,
    p = p - lr*trace. Returns the new params and trace, the loss before
    the step and the gradient."""
    loss, grads = loss_and_grads(params, tokens, weights, cfg, precision,
                                 block_rows)
    trace = jax.tree.map(lambda g, t: g + momentum * t, grads, trace)
    params = jax.tree.map(lambda p, t: p - lr * t, params, trace)
    return params, trace, loss, grads
