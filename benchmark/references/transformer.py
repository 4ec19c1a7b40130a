"""Plain reference of the transformer family (GPT-2 when ``causal``, BERT's
encoder stack otherwise): ``jax.numpy`` in float32 at ``highest`` matmul
precision, dense attention, no kernel, no cache, no batching tricks. It
imports nothing of the program and is handed weights that the benchmark made
(``lib/weights.py``), in this tree:

    params/Embed_0/embedding [vocab, d]      token table
    params/Embed_1/embedding [positions, d]  learned positions
    params/block_<i>/LayerNorm_0, LayerNorm_1   {scale, bias} [d]
    params/block_<i>/MultiHeadAttention_0/qkv   kernel [d, 3, heads, hd], bias [3, heads, hd]
    params/block_<i>/MultiHeadAttention_0/out   kernel [heads, hd, d], bias [d]
    params/block_<i>/Dense_0 [d, d_ff], Dense_1 [d_ff, d]   {kernel, bias}
    params/LayerNorm_0 {scale, bias}         final norm
    params/lm_head     kernel [d, vocab], bias [vocab]

Block: x + attn(ln(x)); x + W2 gelu_tanh(W1 ln(x)) (pre-LN, eps 1e-6);
logits = ln(x) @ head + bias. Loss: mean cross entropy over all positions.
Departures from the published models, the program's own: BERT's block is
post-LN with token-type embeddings and an NSP head, here it is this block
with a bidirectional mask; GPT-2 ties the head to the token table, here the
head is a matrix of its own.

``precision`` is ``"float32"`` for the reference, or ``"bfloat16"``,
``"fp8"``, ``"int8"`` for a control: every matrix product then takes both
operands (and with ``fp8`` the cotangents, in e5m2) rounded to that type,
with one scale per tensor, and accumulates in float32.
"""

import json
from functools import partial

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _round_to(x, precision):
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if precision == "int8":
        scale = amax / 127.0
        return jnp.round(x / scale) * scale
    dtype = {"fp8": jnp.float8_e4m3fn, "fp8_grad": jnp.float8_e5m2}[precision]
    scale = amax / float(jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_cotangent(x, precision):
    return x


def _rc_fwd(x, precision):
    return x, None


def _rc_bwd(precision, _, g):
    return (_round_to(g, "fp8_grad" if precision == "fp8" else precision),)


_round_cotangent.defvjp(_rc_fwd, _rc_bwd)


def _product(spec, a, b, precision):
    out = jnp.einsum(spec, _round_to(a, precision), _round_to(b, precision),
                     precision="highest",
                     preferred_element_type=jnp.float32)
    if precision in ("fp8", "int8"):
        out = _round_cotangent(out, precision)
    return out


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def _block(x, p, causal, precision):
    b, t, d = x.shape
    attn = p["MultiHeadAttention_0"]
    h = _layer_norm(x, p["LayerNorm_0"])
    qkv = _product("btd,dchk->btchk", h, attn["qkv"]["kernel"], precision)
    qkv = qkv + attn["qkv"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    hd = q.shape[-1]
    scores = _product("bqhk,bshk->bhqs", q, k, precision) / jnp.sqrt(
        jnp.float32(hd))
    if causal:
        keep = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(keep[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _product("bhqs,bshk->bqhk", probs, v, precision)
    h = _product("bqhk,hkd->bqd", ctx, attn["out"]["kernel"], precision)
    x = x + h + attn["out"]["bias"]
    h = _layer_norm(x, p["LayerNorm_1"])
    h = _product("btd,df->btf", h, p["Dense_0"]["kernel"], precision)
    h = _gelu_tanh(h + p["Dense_0"]["bias"])
    h = _product("btf,fd->btd", h, p["Dense_1"]["kernel"], precision)
    return x + h + p["Dense_1"]["bias"]


def _stack_blocks(p, num_layers):
    blocks = [p[f"block_{i}"] for i in range(num_layers)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def forward(params, tokens, cfg, precision="float32"):
    """Logits ``[batch, seq, vocab]`` in float32 for ``tokens [batch, seq]``.
    The layers run as one scanned, rematerialised block so that the program
    is small and its backward keeps one layer's activations at a time."""
    p = params["params"]
    t = tokens.shape[1]
    x = p["Embed_0"]["embedding"][tokens] + p["Embed_1"]["embedding"][:t][None]
    body = jax.checkpoint(
        lambda x, bp: (_block(x, bp, cfg["causal"], precision), None))
    x, _ = jax.lax.scan(body, x, _stack_blocks(p, cfg["num_layers"]))
    x = _layer_norm(x, p["LayerNorm_0"])
    logits = _product("btd,dv->btv", x, p["lm_head"]["kernel"], precision)
    return logits + p["lm_head"]["bias"]


def loss_sum(params, tokens, labels, cfg, precision="float32"):
    """Sum over all positions of the cross entropy of ``labels``."""
    logits = forward(params, tokens, cfg, precision)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - picked)


def loss_and_grads(params, tokens, labels, cfg, precision="float32",
                   block_rows=4):
    """Mean loss over all rows of ``tokens [rows, seq]`` and its gradient,
    accumulated over blocks of ``block_rows`` rows so that it fits."""
    rows, seq = tokens.shape
    if rows % block_rows:
        raise ValueError(f"{rows} rows are no whole blocks of {block_rows}")
    tok = tokens.reshape(rows // block_rows, block_rows, seq)
    lab = labels.reshape(rows // block_rows, block_rows, seq)
    grad_fn = jax.value_and_grad(
        lambda p, a, b: loss_sum(p, a, b, cfg, precision))

    def body(carry, ab):
        loss, grads = grad_fn(params, *ab)
        return (carry[0] + loss, jax.tree.map(jnp.add, carry[1], grads)), None

    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(body, zero, (tok, lab))
    n = rows * seq
    return loss / n, jax.tree.map(lambda g: g / n, grads)


def sgd_momentum_step(params, trace, tokens, labels, cfg, lr, momentum,
                      precision="float32", block_rows=4):
    """One step of SGD with momentum as optax has it: trace = g + m*trace,
    p = p - lr*trace. Returns the new params and trace, the loss before
    the step and the gradient."""
    loss, grads = loss_and_grads(params, tokens, labels, cfg, precision,
                                 block_rows)
    trace = jax.tree.map(lambda g, t: g + momentum * t, grads, trace)
    params = jax.tree.map(lambda p, t: p - lr * t, params, trace)
    return params, trace, loss, grads


def program_key(cfg) -> str:
    """The part of a configuration that shapes the reference's program (the
    rest it reads from the weights), as a hashable key for a cache of jitted
    functions."""
    return json.dumps({k: cfg[k] for k in ("num_layers", "causal")})


def leaf_norms(tree):
    """Euclidean norm of every leaf, as one vector in flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def diff_norms(a, b):
    """Norm of ``a - b`` leaf by leaf."""
    return leaf_norms(jax.tree.map(lambda x, y: x - y, a, b))
