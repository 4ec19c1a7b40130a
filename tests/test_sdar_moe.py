"""Block-diffusion training (``TransformerConfig.block_diffusion``: a noised
and a clean copy of every row under one three-part mask) and the expert
layer without a selection bias, against the plain reference
``benchmark/references/sdar_moe.py``, at a small size on the CPU in float32:
hidden 64, 4 query / 2 key-value heads of 16, 8 experts top-2, three expert
layers, rows of 32 tokens in blocks of 4; and the flash kernels under the
mask in interpret mode.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest, weights
from horovod_tpu.common import tracing
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import moe

HERE = os.path.dirname(os.path.abspath(__file__))
SEQ = 32  # data tokens a row: 64 positions
TRAFFIC = {"batch_per_chip": 2, "seq": SEQ, "noise": {
    "per": "block", "eps": 0.001}, "labels": "clean token at masked positions"}


@pytest.fixture(autouse=True)
def float32_products():
    # XLA's CPU dots are float32 already; say so for any backend
    with jax.default_matmul_precision("highest"):
        yield


def _config(held=(0, 4)):
    with open(os.path.join(HERE, "benchmark", "data",
                           "tiny-sdar-moe.json")) as f:
        cfg = json.load(f)
    cfg["experts_held"] = list(held)
    cfg["num_experts"] = held[1] - held[0]
    return cfg


def _published():
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


def _family():
    return (manifest.load_module("models", "sdar_moe"),
            manifest.load_module("references", "sdar_moe"))


def _built(cfg, seed=7, remat=True, **changed):
    family, _ = _family()
    model = family.build_model(cfg, remat=remat)
    if changed:
        model = T.Transformer(dataclasses.replace(model.cfg, **changed))
    params = jax.jit(family.make_params(
        family.param_shapes(model, SEQ), cfg))(weights.seed_key(seed))
    tokens, loss_weights = family.make_batch(cfg, TRAFFIC, 1, 3)
    return model, params, jnp.asarray(tokens[0]), jnp.asarray(loss_weights[0])


def _mask(length, block):
    """The four rules, by comparison of indices (numpy, on its own)."""
    r = np.arange(2 * length)[:, None]
    c = np.arange(2 * length)[None, :]
    bi, bj = r % length // block, c % length // block
    return np.where(r < length, np.where(c < length, bi == bj, bj < bi),
                    (c >= length) & (bj <= bi))


# ------------------------- (a) the kernels against dense masked attention

def _dense(q, k, v, keep):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(keep[None, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("staging", ["whole-sequence", "by-block"])
@pytest.mark.parametrize("length, block, block_q, block_k, heads, kv_heads", [
    (32, 4, 16, 16, 2, 2),    # one query head a key/value head
    (32, 4, 16, 16, 8, 1),    # group 8
    (64, 16, 16, 16, 8, 1),   # a block as long as the tile
    (64, 4, 32, 16, 4, 2),    # a Q tile of two K tiles
    (64, 8, 8, 32, 2, 1),     # a K tile of four Q tiles
    (16, 4, 16, 16, 2, 1),    # one tile a copy
], ids=["mha", "gqa8", "block-is-tile", "wide-q-tile", "wide-k-tile",
        "one-tile"])
def test_kernels_under_the_mask_match_dense_attention(
        length, block, block_q, block_k, heads, kv_heads, staging,
        monkeypatch):
    if staging == "by-block":
        monkeypatch.setenv("HOROVOD_FLASH_VMEM_BUDGET", "1")
    assert fa.fits_vmem(2 * length, 16, heads // kv_heads, 4, block_k) == (
        staging == "whole-sequence")
    keys = jax.random.split(jax.random.PRNGKey(length + block), 4)
    q = jax.random.normal(keys[0], (2, 2 * length, heads, 16))
    k = jax.random.normal(keys[1], (2, 2 * length, kv_heads, 16))
    v = jax.random.normal(keys[2], (2, 2 * length, kv_heads, 16))
    w = jax.random.normal(keys[3], q.shape)
    keep = _mask(length, block)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                                  block_diffusion=block)

    np.testing.assert_allclose(flash(q, k, v), _dense(q, k, v, keep),
                               atol=2e-6, rtol=0)
    mine = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    theirs = jax.grad(lambda *a: jnp.sum(_dense(*a, keep) * w), (0, 1, 2))(
        q, k, v)
    for name, g, r in zip("qkv", mine, theirs):
        np.testing.assert_allclose(g, r, atol=1e-5, rtol=0, err_msg=name)


@pytest.mark.parametrize("length, block, block_q, block_k", [
    (8192, 4, 512, 512), (64, 4, 16, 16), (64, 16, 16, 16), (64, 4, 32, 16),
    (64, 8, 8, 32), (48, 4, 16, 8),
])
def test_the_loops_visit_the_tiles_the_mask_leaves_anything_in(
        length, block, block_q, block_k):
    """Counted from the loop bounds, against the mask itself at the small
    sizes: a tile is visited iff it keeps a pair."""
    n_q, n_k = 2 * length // block_q, 2 * length // block_k
    fwd, dkv = fa.blockdiff_tiles(2 * length, block_q, block_k, block)
    if length == 8192:
        # 16 noised tiles on themselves, and twice the 136 of a
        # block-causal row of 16 tiles: not the 1,024 of the square
        assert fwd == dkv == 16 + 2 * 136 == 288
        # and the blocked dK/dV kernel's grid holds just those, each step
        # a Q tile of the group's 8 query heads: 288 steps where the widest
        # band's rectangle held 8,192 of one query head each
        steps, kept = fa._dkv_steps(2 * length, block_q, block_k, False,
                                    None, block)
        assert kept == len(steps) == 288
        return
    keep = _mask(length, block).reshape(n_q, block_q, n_k, block_k)
    kept = keep.any(axis=(1, 3))
    assert fwd == dkv == int(kept.sum())
    for qi in range(n_q):
        visited = {j for first, last in fa._blockdiff_k_ranges(
            qi, block_q, block_k, length, block) for j in range(first, last)}
        assert visited == set(np.flatnonzero(kept[qi])), qi
    for ki in range(n_k):
        visited = {i for first, last in fa._blockdiff_q_ranges(
            ki, block_q, block_k, length, block) for i in range(first, last)}
        assert visited == set(np.flatnonzero(kept[:, ki])), ki
    # the blocked dK/dV kernel's grid: a step for each of those tiles, a K
    # tile's together, and no step beside them
    steps, tiles = fa._dkv_steps(
        2 * length, block_q, block_k, False, None, block)
    assert tiles == len(steps) == int(kept.sum())
    k_tile, q_tile, _ = fa._step_fields(steps)
    for ki in range(n_k):
        band = list(np.flatnonzero(kept[:, ki]))
        (at,) = np.nonzero(k_tile == ki)
        assert list(at) == list(range(at[0], at[0] + len(band)))
        assert list(q_tile[at]) == band


def test_the_mask_is_refused_beside_another_and_off_its_tiles():
    q = jnp.zeros((1, 64, 2, 16))
    with pytest.raises(ValueError, match="mask of its own"):
        fa.flash_attention(q, q, q, causal=True, block_diffusion=4)
    with pytest.raises(ValueError, match="mask of its own"):
        fa.flash_attention(q, q, q, lengths=jnp.array([64]),
                           block_diffusion=4)
    with pytest.raises(ValueError, match="whole number of blocks"):
        fa.flash_attention(q[:, :63], q[:, :63], q[:, :63],
                           block_diffusion=4)
    with pytest.raises(ValueError, match="whole number of blocks"):
        fa.flash_attention(q, q, q, block_diffusion=5)
    with pytest.raises(ValueError, match="divide the kernels' tiles"):
        fa.flash_attention(q, q, q, block_q=8, block_k=8, block_diffusion=16)


# ------------------------ (b) the mask against its definition, not itself

def _block_causal_forward(params, row, cfg):
    """Logits of a plain forward of ``row [rows, L]`` in which a token sees
    the tokens of its own block and of the blocks before it, positions
    0..L-1: the one mask ``b(j) <= b(i)`` over L x L, and nothing of the
    two-copy layout. Norms, rotation and expert layer are the reference's;
    the attention is written out here."""
    _, ref = _family()
    p = params["params"]
    length = row.shape[1]
    blocks = jnp.arange(length) // cfg["block_length"]
    keep = blocks[None, :] <= blocks[:, None]
    eps, group = cfg["rms_norm_eps"], (
        cfg["num_attention_heads"] // cfg["num_key_value_heads"])
    x = p["Embed_0"]["embedding"][row]
    for layer in range(cfg["num_hidden_layers"]):
        bp = p[f"block_{layer}"]
        a = bp["MultiHeadAttention_0"]
        h = ref._rms(x, bp["RMSNorm_0"]["scale"], eps)
        q = jnp.einsum("btd,dhk->bthk", h, a["q"]["kernel"])
        kv = jnp.einsum("btd,dchk->btchk", h, a["kv"]["kernel"])
        q = ref._rms(q, a["q_norm"]["scale"], eps)
        k = ref._rms(kv[:, :, 0], a["k_norm"]["scale"], eps)
        q, k = (ref._rope(z, cfg["rope_theta"], jnp.arange(length))
                for z in (q, k))
        k, v = (jnp.repeat(z, group, axis=2) for z in (k, kv[:, :, 1]))
        scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / np.sqrt(
            cfg["head_dim"])
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
        x = x + jnp.einsum("bthk,hkd->btd", out, a["out"]["kernel"])
        h = ref._rms(x, bp["RMSNorm_1"]["scale"], eps)
        y, _ = ref._experts(h.reshape(-1, h.shape[-1]), bp["moe"], cfg,
                            "float32")
        x = x + y.reshape(x.shape)
    x = ref._rms(x, p["RMSNorm_0"]["scale"], eps)
    return x @ p["lm_head"]["kernel"]


@pytest.mark.parametrize("flash", [True, False], ids=["kernels", "dense"])
def test_a_noised_block_reads_as_the_row_with_clean_blocks_before_it(flash):
    """For every block b, the logits at block b of the noised half equal
    what a plain block-causal forward of the L-token row [x0 blocks < b ;
    xt block b ; anything] gives there."""
    cfg = _config()
    model, params, tokens, _ = _built(
        cfg, remat=False, flash_attention=flash, flash_block_q=16,
        flash_block_k=16)
    block = cfg["block_length"]
    logits = model.apply(params, tokens, train=True)
    assert logits.shape == (2, SEQ, cfg["vocab_size"])
    assert logits.dtype == jnp.float32
    blocks = np.arange(SEQ) // block
    noised, clean = tokens[:, :SEQ], tokens[:, SEQ:]
    assert float(jnp.mean(noised != clean)) > 0.3  # the copies do differ
    for b in range(SEQ // block):
        # what is after the block is noise here and zeros there: anything
        row = jnp.where(blocks[None] < b, clean,
                        jnp.where(blocks[None] == b, noised, b % 2 * noised))
        alone = _block_causal_forward(params, row, cfg)
        at = slice(b * block, (b + 1) * block)
        np.testing.assert_allclose(logits[:, at], alone[:, at], atol=5e-6,
                                   rtol=0, err_msg=f"block {b}")


def test_the_dense_mask_is_the_four_rules():
    for length, block in ((32, 4), (16, 16), (24, 8)):
        np.testing.assert_array_equal(
            T.block_diffusion_mask(length, block), _mask(length, block))
        assert _mask(length, block).sum() == length * length + length * block


# ------------------ (c) the whole model and its step against the reference

# Float32 on both sides; what differs is the order of sums (the program
# sorts rows by expert, the reference adds expert after expert; flax's norms
# and jnp's differ in association). Measured: logits 2.4e-7, gradients 1.3e-7
# of a leaf's largest entry.
LOGITS_ATOL = 5e-6
GRAD_RTOL = 3e-5


def _loss(model, family, tokens, loss_weights):
    return lambda p: family.per_chip_loss(
        model.apply(p, tokens, train=True), tokens, loss_weights)


@pytest.mark.parametrize("flash", [True, False], ids=["kernels", "dense"])
@pytest.mark.parametrize("held", [(0, 4), (0, 8)], ids=["4of8", "8of8"])
def test_program_agrees_with_the_reference(held, flash):
    cfg = _config(held)
    family, ref = _family()
    model, params, tokens, loss_weights = _built(
        cfg, flash_attention=flash, flash_block_q=16, flash_block_k=16)
    np.testing.assert_allclose(
        model.apply(params, tokens, train=True),
        ref.forward(params, tokens, cfg), atol=LOGITS_ATOL, rtol=0)
    mine, grads = jax.value_and_grad(
        _loss(model, family, tokens, loss_weights))(params)
    theirs, ref_grads = ref.loss_and_grads(params, tokens, loss_weights, cfg)
    assert abs(float(mine) - float(theirs)) < 1e-5 * float(theirs)
    names = weights.leaf_names(grads)
    assert not any(n.endswith("select_bias") for n in names)
    for name, g, r in zip(names, jax.tree.leaves(grads),
                          jax.tree.leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(r)))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(g - r))) <= GRAD_RTOL * scale, name


def test_three_sgd_steps_agree_with_the_reference():
    import optax

    cfg = _config()
    family, ref = _family()
    model, params, tokens, loss_weights = _built(cfg)
    opt = optax.sgd(0.01, momentum=0.9)
    state = opt.init(params)
    ref_params, trace = params, jax.tree.map(jnp.zeros_like, params)
    for _ in range(3):
        loss, grads = jax.value_and_grad(
            _loss(model, family, tokens, loss_weights))(params)
        updates, state = opt.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        ref_params, trace, ref_loss, _ = ref.sgd_momentum_step(
            ref_params, trace, tokens, loss_weights, cfg, 0.01, 0.9,
            block_rows=1)
        assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    gaps = ref.diff_norms(params, ref_params)
    moved = ref.diff_norms(params, jax.jit(family.make_params(
        family.param_shapes(model, SEQ), cfg))(weights.seed_key(7)))
    assert float(jnp.max(gaps / moved)) < 1e-4


@pytest.fixture(scope="module")
def faulty_grads():
    """``(the whole reference's gradient, fault index -> gradient)``: one
    compiled program is handed the fault, as ``tools/limits_sdar_moe.py``
    does."""
    cfg = _config()
    _, ref = _family()
    _, params, tokens, loss_weights = _built(cfg)
    with jax.default_matmul_precision("highest"):
        faulty = jax.jit(lambda fault: ref.loss_and_grads(
            params, tokens, loss_weights, dict(cfg, fault=fault))[1])
        return faulty(jnp.int32(-1)), faulty


@pytest.mark.parametrize("fault", [
    "leak_own_clean", "causal_in_block", "no_noised_part", "positions_2l",
    "no_weight", "head_on_clean", "no_gate_norm", "top_k_less_1",
    "half_blocks"])
def test_a_fault_in_the_reference_moves_its_gradient(fault, faulty_grads):
    _, ref = _family()
    assert fault in ref.FAULTS and len(ref.FAULTS) == 9
    whole, faulty = faulty_grads
    grads = faulty(jnp.int32(ref.FAULTS.index(fault)))
    gap = max(float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(b)))
              for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(whole)))
    assert gap > 1e-2, gap  # a thousand times the program's distance


def test_a_fault_is_planted_by_name_or_by_index_and_none_is_the_reference():
    cfg = _config()
    _, ref = _family()
    _, params, tokens, loss_weights = _built(cfg)
    whole = ref.loss_sum(params, tokens, loss_weights, cfg)[0]
    none = ref.loss_sum(params, tokens, loss_weights,
                        dict(cfg, fault=jnp.int32(-1)))[0]
    assert float(none) == float(whole)
    by_name, by_index = (
        ref.loss_sum(params, tokens, loss_weights, dict(cfg, fault=f))[0]
        for f in ("leak_own_clean", jnp.int32(0)))
    assert float(by_name) == float(by_index) != float(whole)


def test_the_batch_carries_the_noise_and_a_seed_repeats():
    family, _ = _family()
    cfg = _config()
    traffic = dict(TRAFFIC, batch_per_chip=3, seq=4096)
    tokens, w = family.make_batch(cfg, traffic, 2, 2**31 + 5)
    again, w2 = family.make_batch(cfg, traffic, 2, 2**31 + 5)
    np.testing.assert_array_equal(tokens, again)
    np.testing.assert_array_equal(w, w2)
    assert tokens.shape == (2, 3, 8192) and tokens.dtype == np.int32
    assert w.shape == (2, 3, 4096) and w.dtype == np.float32
    noised, clean = tokens[..., :4096], tokens[..., 4096:]
    mask_id = cfg["mask_token_id"]
    assert clean.max() < mask_id and clean.min() >= 0
    masked = noised == mask_id
    np.testing.assert_array_equal(noised[~masked], clean[~masked])
    assert np.all(w[~masked] == 0) and np.all(w[masked] >= 1)
    # one t a block: the masked positions of a block share their weight
    by_block = w.reshape(2, 3, -1, cfg["block_length"])
    top = by_block.max(axis=-1, keepdims=True)
    assert np.all((by_block == 0) | (by_block == top))
    # t is uniform: half of the tokens are masked, and E[1/t at a mask] = 1
    assert 0.48 < masked.mean() < 0.52
    assert 0.9 < w.mean() < 1.1
    other, _ = family.make_batch(cfg, traffic, 2, 2**31 + 6)
    assert (other != tokens).mean() > 0.4


def test_the_family_draws_the_embeddings_its_own_way():
    """Every leaf is ``lib/weights.py``'s normal(0, 0.02) but the token
    embeddings: the data tokens' rows at ``embedding_std``, the mask
    token's row their mean; program and reference are handed the one
    tree, and a seed repeats."""
    family, _ = _family()
    cfg = _config()
    model = family.build_model(cfg)
    shapes = family.param_shapes(model, SEQ)
    mine = jax.jit(family.make_params(shapes, cfg))(weights.seed_key(2**31 + 9))
    plain = jax.jit(weights.make_params(shapes))(weights.seed_key(2**31 + 9))
    again = jax.jit(family.make_params(shapes, cfg))(weights.seed_key(2**31 + 9))
    assert jax.tree.structure(mine) == jax.tree.structure(plain)
    for name, a, b, c in zip(weights.leaf_names(mine), jax.tree.leaves(mine),
                             jax.tree.leaves(plain), jax.tree.leaves(again)):
        np.testing.assert_array_equal(a, c, err_msg=name)
        if name != "params/Embed_0/embedding":
            np.testing.assert_array_equal(a, b, err_msg=name)
    table = mine["params"]["Embed_0"]["embedding"]
    mask_id = cfg["mask_token_id"]
    np.testing.assert_allclose(table[:mask_id], 50.0 * plain["params"][
        "Embed_0"]["embedding"][:mask_id], rtol=1e-6)
    assert 0.95 < float(jnp.std(table[:mask_id])) < 1.05
    np.testing.assert_allclose(table[mask_id], jnp.mean(table[:mask_id], 0),
                               rtol=1e-6)
    # a tenth of a data token's norm and less: what a masked position's
    # router reads is its context, not one row every masked position shares
    assert float(jnp.linalg.norm(table[mask_id])) < 0.15 * float(
        jnp.linalg.norm(table[0]))


# ---------------------------- (d) the shares add up to the uncut layer

def _expert_layer(cfg_json):
    family, _ = _family()
    return T.ExpertFFN(family.build_model(cfg_json).cfg)


def test_all_eight_shares_make_the_uncut_layer():
    """Softmax top-2 of 8 with renormalised gates and no shared expert, so
    nothing is counted once: eight shares of one expert each."""
    _, ref = _family()
    whole = _config((0, 8))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
    full = _expert_layer(whole).init(jax.random.PRNGKey(1), x)["params"]
    full = jax.tree.map(
        lambda p: p + 0.02 * jax.random.normal(jax.random.PRNGKey(5), p.shape),
        full)
    uncut, _ = ref._experts(x.reshape(-1, 64), full, whole, "float32")
    total = 0
    for first in range(8):
        held = (first, first + 1)
        share = dict(full, **{name: full[name][first:first + 1]
                              for name in ("w_gate", "w_up", "w_down")})
        part = _expert_layer(_config(held)).apply({"params": share}, x)
        assert float(jnp.max(jnp.abs(part))) > 0
        total = total + part.reshape(-1, 64)
    np.testing.assert_allclose(total, uncut, atol=2e-6, rtol=0)


# ------------------------------ (e) the router without a selection bias

def test_an_expert_layer_without_a_selection_bias_has_no_such_leaf():
    cfg_json = _config()
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
    layer = _expert_layer(cfg_json)
    assert layer.cfg.moe_select_bias is False
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert set(params) == {"router", "w_gate", "w_up", "w_down"}
    biased = T.ExpertFFN(dataclasses.replace(layer.cfg, moe_select_bias=True))
    with_bias = dict(params, select_bias=jnp.zeros((8,), jnp.float32))
    assert set(biased.init(jax.random.PRNGKey(1), x)["params"]) == set(
        with_bias)
    # it routes as route_top_k with a zero bias
    np.testing.assert_array_equal(
        layer.apply({"params": params}, x),
        biased.apply({"params": with_bias}, x))
    logits = jax.random.normal(jax.random.PRNGKey(2), (64, 8))
    for score in ("softmax", "sigmoid"):
        chosen, gates = moe.route_top_k(logits, None, 2, score=score)
        chosen0, gates0 = moe.route_top_k(logits, jnp.zeros((8,)), 2,
                                          score=score)
        np.testing.assert_array_equal(chosen, chosen0)
        np.testing.assert_array_equal(gates, gates0)
    # the reckoning of the state counts one leaf less a layer
    assert T._param_count(biased.cfg) - T._param_count(layer.cfg) == 3 * 8


def test_softmax_gates_are_renormalised_over_the_chosen():
    logits = jax.random.normal(jax.random.PRNGKey(4), (64, 8))
    chosen, gates = moe.route_top_k(logits, None, 2, score="softmax")
    scores = jax.nn.softmax(logits, axis=-1)
    top, at = jax.lax.top_k(scores, 2)
    np.testing.assert_array_equal(chosen, at)
    np.testing.assert_allclose(gates, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)


# ------------------------------------------- what the mode refuses, and says

def test_the_model_is_built_from_the_one_config():
    model, params, _, _ = _built(_config())
    cfg = model.cfg
    assert isinstance(model, T.Transformer)
    assert cfg.layer_kinds == ("full/experts",) * 3
    assert (cfg.block_diffusion, cfg.moe_score, cfg.moe_select_bias,
            cfg.qk_norm, cfg.num_kv_heads) == (4, "softmax", False, True, 2)
    block = params["params"]["block_1"]
    assert set(block) == {"RMSNorm_0", "RMSNorm_1", "MultiHeadAttention_0",
                          "moe"}
    assert set(block["moe"]) == {"router", "w_gate", "w_up", "w_down"}
    assert "bias" not in params["params"]["lm_head"]
    # every standing configuration leaves the mode off
    assert T.TransformerConfig().block_diffusion == 0
    assert T.TransformerConfig().moe_select_bias is True


def test_what_the_mode_cannot_do_is_refused_with_a_message():
    model, params, tokens, _ = _built(_config(), remat=False)
    with pytest.raises(ValueError, match="no cache="):
        model.apply(params, tokens, cache=T.init_cache(model.cfg, 2),
                    cache_index=jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="lengths="):
        model.apply(params, tokens, lengths=jnp.array([64, 64]))
    with pytest.raises(ValueError, match="mask="):
        model.apply(params, tokens, mask=jnp.ones(tokens.shape, bool))
    with pytest.raises(ValueError, match="whole blocks"):
        model.apply(params, tokens[:, :63])
    with pytest.raises(ValueError, match="whole blocks"):
        model.apply(params, tokens[:, :60])  # 30 tokens: no blocks of 4
    window = T.Transformer(dataclasses.replace(
        model.cfg, sliding_window=8, layer_kinds=("window/experts",) * 3))
    with pytest.raises(ValueError, match="no window or latent layer"):
        window.apply(params, tokens)


def test_the_kernels_are_ridden_or_the_call_is_refused(monkeypatch):
    """Never the dense path silently: a length the kernels cannot tile is
    an error where they were asked for, and the counter stays where it
    was."""
    from horovod_tpu.common.metrics import registry

    model, params, tokens, _ = _built(
        _config(), remat=False, flash_attention=True, flash_block_q=16,
        flash_block_k=16)
    before = registry.snapshot().get("flash.dense_fallbacks", 0)
    monkeypatch.setattr(
        T.TransformerConfig, "flash_decline_reason",
        lambda self, mask=None, seq=None: "seq tiles no 8-aligned block")
    with pytest.raises(ValueError, match="rides the flash kernels or is"):
        model.apply(params, tokens)
    assert registry.snapshot().get("flash.dense_fallbacks", 0) == before
    monkeypatch.undo()  # the real one names the masks the kernels take
    assert "block_diffusion" in model.cfg.flash_decline_reason(mask=object())


def test_the_span_says_what_the_mode_makes_of_the_row(monkeypatch):
    monkeypatch.setenv("HOROVOD_TRACE", "0")
    tracing._reset()
    model, params, tokens, _ = _built(_config())
    jax.make_jaxpr(lambda p, t: model.apply(p, t, train=True))(params, tokens)
    span = [r for r in tracing.recorder().spans()
            if r["name"] == "hvd.trainer.trace_model"][-1]
    tracing._reset()
    assert span["tags"] == {
        "layers": 3, "remat": "recompute_all", "remat_saved_bytes": 0,
        "layer_kinds": "full/experts,full/experts,full/experts",
        "experts_total": 8, "experts_held": 4, "top_k": 2,
        # both copies of both rows pass the expert layers
        "moe_rows_capacity": 2 * 2 * SEQ * 2,
        "moe_rows_chunk": 2 * 2 * SEQ * 2 // 16,
        "block_length": 4, "positions": 2 * SEQ, "head_positions": SEQ,
        "score_pairs_kept": SEQ * SEQ + SEQ * 4,
    }


def test_the_scope_names_the_attention_kind():
    model, params, tokens, _ = _built(_config(), remat=False)
    text = jax.jit(jax.grad(lambda p: model.apply(
        p, tokens, train=True).sum())).lower(params).compile().as_text()
    assert "/attn_blockdiff/" in text
    assert "attn_full" not in text and "attn_window" not in text
    assert "moe_route" in text and "moe_shared" not in text


def test_the_cell_of_the_benchmark_reckons_both_copies():
    """SDAR's share at 1 x 8192 data tokens beside a v5e's limit: remat_plan
    is handed the 16,384 positions of the row's two copies and the state of
    the layers as cut."""
    family, _ = _family()
    published = _published()
    cfg = family.build_model(published, remat=True).cfg
    cfg = dataclasses.replace(cfg, flash_attention=True)  # as on the chip
    layers = published["num_hidden_layers"]
    layer = 18_874_368 + 4_352 + 262_144 + 16 * 4_718_592
    assert layer == 94_638_336
    assert T._param_count(cfg) == layers * layer + 2 * 38_895_616 + 2048
    # bfloat16 q and o at 32 heads of 128, k and v at 4, an lse a head;
    # top-8's chosen experts and sorted order
    outputs = 32 * 128 * 2 + 4 * 32
    named = outputs + (32 + 2 * 4) * 128 * 2
    routing = 2 * 8 * 4
    limit = int(15.75 * 2**30)
    mode, saved = T.remat_plan(cfg, 16384, limit)
    room = limit - 12 * T._param_count(cfg)
    assert mode in ("save_attention", "save_attention_out")
    per_token = (named if mode == "save_attention" else outputs) + routing
    assert saved == 16384 * layers * per_token
    assert saved <= T.REMAT_SAVE_SHARE[mode] * room
