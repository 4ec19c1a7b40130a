"""The sparse long-context block of ``TransformerConfig.layer_kinds`` (window
and full GQA layers with QK-norm and a gated output, RMSNorm in a sandwich,
a dense gated layer and expert layers that hold a share of the deployment's
experts) against the plain reference ``benchmark/references/afmoe.py``, at a
small size on the CPU in float32: hidden 64, heads 4/2 of 16, 8 experts
top-2, window 8 at seq 32, one dense and four expert layers [s, s, s, f, s].
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.lib import manifest, weights
from horovod_tpu.common import tracing
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import moe

HERE = os.path.dirname(os.path.abspath(__file__))
SEQ = 32


@pytest.fixture(autouse=True)
def float32_products():
    # XLA's CPU dots are float32 already; say so for any backend
    with jax.default_matmul_precision("highest"):
        yield


# the key that counts the experts held here, by model family
_HELD_KEY = {"afmoe": "num_experts", "deepseek_v3": "n_routed_experts"}


def _config(held=(0, 4), family="afmoe"):
    name = "tiny-" + family.replace("_", "-") + ".json"
    with open(os.path.join(HERE, "benchmark", "data", name)) as f:
        cfg = json.load(f)
    cfg["experts_held"] = list(held)
    cfg[_HELD_KEY[family]] = held[1] - held[0]
    return cfg


def _family(family="afmoe"):
    return (manifest.load_module("models", family),
            manifest.load_module("references", family))


def _built(cfg, seed=7, remat=True):
    family, _ = _family()
    model = family.build_model(cfg, remat=remat)
    params = jax.jit(weights.make_params(family.param_shapes(model, SEQ)))(
        weights.seed_key(seed))
    tokens, labels = family.make_batch(
        cfg, {"labels": "next-token", "batch_per_chip": 2, "seq": SEQ}, 1, 3)
    return model, params, jnp.asarray(tokens[0]), jnp.asarray(labels[0])


# ------------------------------- (a) the whole model against the reference

# Float32 on both sides; what differs is the order of sums (the program
# sorts rows by expert and sums a token's experts last, the reference adds
# expert after expert; flax's norms and jnp's differ in association).
# Measured: logits 4e-7 on values of 0.6, gradients 2.4e-6 of a leaf's
# largest entry. Ten times that is far under any fault (a routing fault
# reads 0.27, tests/benchmark/test_benchmark_afmoe.py).
LOGITS_ATOL = 5e-6
GRAD_RTOL = 3e-5


@pytest.mark.parametrize("held", [(0, 8), (0, 4), (2, 4), (4, 8)],
                         ids=["8of8", "4of8", "2of8", "last4of8"])
def test_program_agrees_with_the_reference(held):
    cfg = _config(held)
    _, ref = _family()
    model, params, tokens, labels = _built(cfg)
    np.testing.assert_allclose(
        model.apply(params, tokens, train=True),
        ref.forward(params, tokens, cfg), atol=LOGITS_ATOL, rtol=0)

    def loss(p):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, tokens, train=True).astype(jnp.float32),
            labels).mean()

    mine, grads = jax.value_and_grad(loss)(params)
    theirs, ref_grads = ref.loss_and_grads(params, tokens, labels, cfg)
    assert abs(float(mine) - float(theirs)) < 1e-5 * float(theirs)
    names = weights.leaf_names(grads)
    for name, g, r in zip(names, jax.tree.leaves(grads),
                          jax.tree.leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(r)))
        if name.endswith("select_bias"):
            # selection is not differentiated: the leaf is there, unmoved
            assert scale == 0 and float(jnp.max(jnp.abs(g))) == 0
            continue
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(g - r))) <= GRAD_RTOL * scale, name


def test_the_model_is_built_from_the_one_config():
    model, params, _, _ = _built(_config())
    cfg = model.cfg
    assert isinstance(model, T.Transformer)
    assert cfg.layer_kinds == (
        "window/dense", "window/experts", "window/experts",
        "full-nope/experts", "window/experts")
    block = params["params"]["block_3"]
    assert set(block["moe"]) == {"router", "select_bias", "shared", "w_gate",
                                 "w_up", "w_down"}
    assert block["moe"]["router"]["kernel"].shape == (64, 8)  # all experts
    assert block["moe"]["w_gate"].shape == (4, 64, 32)  # the held ones
    assert "mlp" in params["params"]["block_0"]
    assert "bias" not in params["params"]["lm_head"]


# ---------------------------- (b) the shares add up to the uncut layer

def _layer_input(seed=3):
    return 0.5 * jax.random.normal(jax.random.PRNGKey(seed), (2, SEQ, 64))


def _expert_layer(cfg_json, shared=True):
    family, _ = _family(cfg_json["model"])
    cfg = family.build_model(cfg_json).cfg
    if not shared:
        cfg = dataclasses.replace(cfg, moe_shared_d_ff=0)
    return T.ExpertFFN(cfg)


def _share_of(params, held):
    sliced = {k: v for k, v in params.items() if k != "shared"}
    for name in ("w_gate", "w_up", "w_down"):
        sliced[name] = params[name][held[0]:held[1]]
    return sliced


@pytest.mark.parametrize("family", ["afmoe", "deepseek_v3"])
def test_all_shares_and_the_shared_expert_once_make_the_uncut_layer(family):
    # top-2 of 8 with one shared expert 32 wide, or two that are one MLP
    # of 48: the same ExpertFFN under either family's keys
    _, ref = _family(family)
    whole = _config((0, 8), family)
    x = _layer_input()
    full = _expert_layer(whole).init(jax.random.PRNGKey(1), x)["params"]
    full = jax.tree.map(  # not the zero bias of init: let it choose
        lambda p: p + 0.02 * jax.random.normal(jax.random.PRNGKey(5), p.shape),
        full)
    uncut, _ = ref._experts(x.reshape(-1, 64), full, whole, "float32")
    total = ref._gated_mlp(x.reshape(-1, 64), full["shared"], "float32")
    for held in ((0, 2), (2, 4), (4, 6), (6, 8)):
        part = _expert_layer(_config(held, family), shared=False).apply(
            {"params": _share_of(full, held)}, x)
        assert float(jnp.max(jnp.abs(part))) > 0
        total = total + part.reshape(-1, 64)
    np.testing.assert_allclose(total, uncut, atol=2e-6, rtol=0)


# ------------------------- (c) dropless: every token on one held expert

@pytest.mark.parametrize("favoured, rows_here", [
    ((1, 5), 1), ((1, 2), 2),
], ids=["one-held-expert", "every-choice-lands-here"])
def test_collapsed_routing_drops_no_row(favoured, rows_here):
    _, ref = _family()
    cfg_json = _config((0, 4))
    layer = _expert_layer(cfg_json)
    x = _layer_input()
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    params = dict(params, select_bias=jnp.zeros(8).at[jnp.array(favoured)]
                  .set(10.0))
    out, state = layer.apply({"params": params}, x, mutable=["intermediates"])
    chosen = state["intermediates"]["chosen"][0]
    tokens = 2 * SEQ
    assert chosen.shape == (tokens, 2)
    assert set(np.unique(chosen)) == set(favoured)  # every token alike
    here = (chosen >= 0) & (chosen < 4)
    assert int(here.sum()) == rows_here * tokens  # the worst case at 2
    want, _ = ref._experts(x.reshape(-1, 64), params, cfg_json, "float32")
    np.testing.assert_allclose(out.reshape(-1, 64), want, atol=2e-6, rtol=0)
    # a token's row is its own: no other token's input reaches it
    bumped, _ = layer.apply(
        {"params": params}, x.at[0, 0].add(1.0), mutable=["intermediates"])
    assert float(jnp.max(jnp.abs((bumped - out)[0, 1:]))) == 0
    assert float(jnp.max(jnp.abs((bumped - out)[0, 0]))) > 0


# ------------- (c') the dispatch's window follows the rows routed here

_HELD = (2, 6)
_ROWS = 2 * SEQ * 2  # tokens x top-2
_CHUNK = _ROWS // 16


def _routing_with(rows_here):
    """``chosen``, ``gates [tokens, 2]`` with exactly ``rows_here`` (token,
    choice) pairs on the experts 2..5, spread over them and over the
    tokens; every token's two experts differ."""
    tokens = 2 * SEQ
    held = np.arange(*_HELD)
    elsewhere = np.array([0, 1, 6, 7])
    here = rows_here // tokens + (np.arange(tokens) < rows_here % tokens)
    rng = np.random.default_rng(rows_here)
    chosen = np.stack([
        rng.permutation(np.concatenate([
            np.roll(held, t)[:n], np.roll(elsewhere, t)[:2 - n]]))
        for t, n in enumerate(here)])
    gates = rng.uniform(0.2, 1.0, (tokens, 2))
    return (jnp.asarray(chosen, jnp.int32),
            jnp.asarray(gates / gates.sum(-1, keepdims=True), jnp.float32))


@pytest.mark.parametrize("rows_here", [
    0, 1, _CHUNK - 1, _CHUNK, _CHUNK + 1, 5 * _CHUNK + 3, _ROWS,
], ids=["none", "one", "chunk-less-1", "chunk", "chunk-and-1", "ragged",
        "every-pair"])
def test_the_window_follows_the_routed_rows_and_drops_none(
        rows_here, monkeypatch):
    _, ref = _family()
    cfg_json = dict(_config(_HELD), num_shared_experts=0)
    layer = _expert_layer(cfg_json, shared=False)
    x = _layer_input()
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    chosen, gates = _routing_with(rows_here)
    assert moe.window_chunk(_ROWS) == _CHUNK

    # the layer, told its routing: what it sows is what the loop took
    monkeypatch.setattr(moe, "route_top_k", lambda *a, **k: (chosen, gates))
    out, state = layer.apply({"params": params}, x, mutable=["intermediates"])
    sown = {k: int(v[0]) for k, v in state["intermediates"].items()
            if k.startswith("rows_")}
    here = (chosen >= _HELD[0]) & (chosen < _HELD[1])
    assert sown["rows_routed"] == int(here.sum()) == rows_here
    assert sown["rows_window"] == -(-rows_here // _CHUNK) * _CHUNK

    # output and every gradient against the float32 reference
    weigh = jax.random.normal(jax.random.PRNGKey(2), (2 * SEQ, 64))

    def mine(x, gates, w_gate, w_up, w_down):
        y, _, _ = moe.held_experts_ffn(
            x, chosen, gates, w_gate, w_up, w_down, _HELD[0])
        return jnp.sum(y * weigh), y

    def theirs(x, gates, w_gate, w_up, w_down):
        monkeypatch.setattr(ref, "_route", lambda *a: (chosen, gates))
        y, _ = ref._experts(x, dict(w_gate=w_gate, w_up=w_up, w_down=w_down),
                            cfg_json, "float32")
        return jnp.sum(y * weigh), y

    args = (x.reshape(-1, 64), gates,
            params["w_gate"], params["w_up"], params["w_down"])
    (_, y), grads = jax.value_and_grad(mine, range(5), has_aux=True)(*args)
    (_, want), want_grads = jax.value_and_grad(
        theirs, range(5), has_aux=True)(*args)
    np.testing.assert_allclose(y, want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(out.reshape(-1, 64), want, atol=2e-6, rtol=0)
    for name, g, r in zip(("x", "gates", "w_gate", "w_up", "w_down"),
                          grads, want_grads):
        scale = float(jnp.max(jnp.abs(r)))
        assert (scale > 0) == (rows_here > 0), name
        assert float(jnp.max(jnp.abs(g - r))) <= GRAD_RTOL * scale, name


def test_routing_weights_are_normalised_over_all_k_and_scaled():
    logits = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    bias = jnp.zeros(8).at[3].set(5.0)
    chosen, gates = moe.route_top_k(logits, bias, 2, scale=2.826)
    assert bool(jnp.all(jnp.any(chosen == 3, axis=1)))  # the bias selects
    np.testing.assert_allclose(gates.sum(-1), 2.826, rtol=1e-6)
    scores = jax.nn.sigmoid(logits)  # and does not weigh
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        gates, 2.826 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    grad = jax.grad(lambda b: moe.route_top_k(logits, b, 2)[1].sum())(bias)
    assert float(jnp.max(jnp.abs(grad))) == 0


# ------------------------------ (d) the kernels past the window, GQA 8 x 128

def _dense_band(q, k, v, window):
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(q.shape[-1])
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None]
    keep = cols <= rows
    if window:
        keep = keep & (rows - cols < window)
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.mark.parametrize("window", [192, None], ids=["window", "full"])
@pytest.mark.parametrize("staging", ["whole-sequence", "by-block"])
def test_gqa8_head128_kernels_match_the_dense_band(window, staging,
                                                   monkeypatch):
    # seq 512 is past the window of 192; 8 query heads share one kv head
    if staging == "by-block":
        monkeypatch.setenv("HOROVOD_FLASH_VMEM_BUDGET", "1")
    t, heads, d = 512, 8, 128
    assert fa.fits_vmem(t, d, heads, 4, 128) == (staging == "whole-sequence")
    key = jax.random.PRNGKey(0)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), shape)
                  for i, shape in enumerate([(1, t, heads, d), (1, t, 1, d),
                                             (1, t, 1, d), (1, t, heads, d)]))

    def through(attend):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attend(q, k, v) * w), (0, 1, 2))(q, k, v)

    mine, grads = through(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, window=window))
    want, want_grads = through(lambda q, k, v: _dense_band(q, k, v, window))
    # float32 online softmax against a dense one: rounding of sums of 512
    assert abs(float(mine) - float(want)) < 1e-3
    for g, r in zip(grads, want_grads):
        np.testing.assert_allclose(g, r, atol=2e-5, rtol=0)


def test_the_8k_shape_rides_the_kernels_with_its_q_group_staged_by_block():
    family, _ = _family()
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "trinity-mini.json")) as f:
        cfg = family.build_model(json.load(f), remat=True).cfg
    assert cfg.flash_decline_reason(seq=8192) is None
    group = cfg.num_heads // cfg.num_kv_heads
    assert (group, cfg.dim_per_head()) == (8, 128)
    # whole-sequence staging would be 48.8 MiB: the backward stages by block
    assert fa.bwd_vmem_bytes(8192, 128, group, 2, 512) > 48 * 2**20
    assert not fa.fits_vmem(8192, 128, group, 2, 512)
    # a grid step for each tile the mask keeps, taking the group's eight
    # query heads at once: bands of 5 Q tiles and 4, 3, 2, 1 at the end;
    # 16, 15, ..., 1
    assert fa._members_per_step(
        group, 512, (128, 2), (128, 2), (128, 2), (1, 4)) == group
    for window, tiles in ((2048, 12 * 5 + 10), (None, 136)):
        steps, kept = fa._dkv_steps(8192, 512, 512, True, window)
        assert kept == len(steps) == tiles
    # GPT-2's and BERT's shapes keep the kernel they had
    assert fa.fits_vmem(512, 64, 1, 2, 512)


# ----------------------------------------- (e) serving kwargs on the new kinds

def test_prefill_then_decode_equals_the_full_forward():
    model, params, tokens, _ = _built(_config(), remat=False)
    full = model.apply(params, tokens, train=False)
    cache = T.init_cache(model.cfg, batch=2, max_len=SEQ)
    zero = jnp.zeros((2,), jnp.int32)
    prefill, cache = model.apply(params, tokens[:, :20], train=False,
                                 cache=cache, cache_index=zero)
    steps = [prefill]
    for i in range(20, SEQ):
        logits, cache = model.apply(params, tokens[:, i:i + 1], train=False,
                                    cache=cache, cache_index=zero + i)
        steps.append(logits)
    # the same float32 sums in another order (a row at a time)
    np.testing.assert_allclose(jnp.concatenate(steps, axis=1), full,
                               atol=5e-6, rtol=0)


def test_a_page_table_on_the_new_kinds_says_what_is_missing():
    model, params, tokens, _ = _built(_config(), remat=False)
    cache = T.init_cache(model.cfg, batch=2, max_len=SEQ)
    with pytest.raises(NotImplementedError, match="paged kernel has no band"):
        model.apply(params, tokens[:, :8], train=False, cache=cache,
                    cache_index=jnp.zeros((2,), jnp.int32),
                    pages=jnp.zeros((2, 4), jnp.int32))


# ----------------------------------------------- (f) what the program says

# over the five layers at 2 x SEQ float32 tokens: the flash kernels' q, o
# (64 wide), k, v (32) and one lse a head, and in the four expert layers
# top-2's chosen experts and sorted order; save_matmuls' further outputs of
# the gate (64), W_o (64), the q and k/v projections that QK-norm reads
# (64 + 64), the last feed-forward matmul that the sandwich norm reads (64),
# and gate and up (2 x 96) in the dense layer, the router's logits (8, in
# float32 whatever the model's dtype) and the shared expert's gate and up
# (2 x 32) in the four expert layers
_ATTENTION_KEPT = 2 * SEQ * (
    5 * (4 * (64 + 64 + 32 + 32) + 4 * 4) + 4 * 2 * 2 * 4)
_MATMULS_KEPT = _ATTENTION_KEPT + 2 * SEQ * 4 * (
    5 * (64 + 64 + 64 + 64 + 64) + 2 * 96 + 4 * (8 + 2 * 32))
# the forward kernel's outputs alone: o (64 wide) and one lse a head, and
# the routing's integers
_OUTPUTS_KEPT = 2 * SEQ * (5 * (4 * 64 + 4 * 4) + 4 * 2 * 2 * 4)


def _state_bytes(cfg):
    return T.REMAT_STATE_BYTES_PER_PARAM * T._param_count(cfg)


@pytest.mark.parametrize("flash,room,want", [
    (True, 1 << 40, ("save_matmuls", _MATMULS_KEPT)),
    (True, 5 * _ATTENTION_KEPT, ("save_attention", _ATTENTION_KEPT)),
    # eight bytes under the kernels' residuals' share: their outputs alone,
    # down to a tenth of the room
    (True, 5 * _ATTENTION_KEPT - 8, ("save_attention_out", _OUTPUTS_KEPT)),
    (True, 10 * _OUTPUTS_KEPT, ("save_attention_out", _OUTPUTS_KEPT)),
    (True, 10 * _OUTPUTS_KEPT - 8, ("recompute_all", 0)),
    # off the kernels nothing goes by name
    (False, 5 * _ATTENTION_KEPT, ("recompute_all", 0)),
    (None, None, ("recompute_all", 0)),
], ids=["save_matmuls", "save_attention", "under-save_attention",
        "save_attention_out", "too-little-room", "dense-path",
        "limit-unknown"])
def test_remat_climbs_the_ladder_where_experts_are_held_and_says_so(
        flash, room, want, monkeypatch):
    monkeypatch.setenv("HOROVOD_TRACE", "0")
    tracing._reset()
    model, params, tokens, _ = _built(_config())
    cfg = dataclasses.replace(model.cfg, flash_attention=bool(flash))
    limit = None if room is None else _state_bytes(cfg) + room
    assert T._param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    assert T.remat_plan(cfg, 2 * SEQ, limit) == want
    monkeypatch.setattr(T, "_device_bytes_limit", lambda: limit)
    model = T.Transformer(cfg)
    jax.make_jaxpr(lambda p, t: model.apply(p, t, train=True))(params, tokens)
    span = [r for r in tracing.recorder().spans()
            if r["name"] == "hvd.trainer.trace_model"][-1]
    tracing._reset()
    assert span["tags"] == {
        "layers": 5, "remat": want[0], "remat_saved_bytes": want[1],
        "layer_kinds": "window/dense,window/experts,window/experts,"
                       "full-nope/experts,window/experts",
        "experts_total": 8, "experts_held": 4, "top_k": 2,
        "moe_rows_capacity": 2 * SEQ * 2, "moe_rows_chunk": 2 * SEQ * 2 // 16,
    }


def _cell_cfg():
    """Trinity-Mini's share as the benchmark's cell builds it on the chip."""
    family, _ = _family()
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "trinity-mini.json")) as f:
        cfg = family.build_model(json.load(f), remat=True).cfg
    return dataclasses.replace(cfg, flash_attention=True)


def test_the_cell_of_the_benchmark_keeps_the_kernels_residuals():
    """Trinity-Mini's share at 2 x 8192 tokens beside a v5e's limit: the
    matmuls' outputs are over their share of what 8.47 GB of state leave,
    the kernels' residuals are not (PERF.md section 6, PR 28)."""
    cfg = _cell_cfg()
    limit = int(15.74 * 2**30)
    assert T._param_count(cfg) == 705_474_304
    # bfloat16 q and o at 32 heads of 128, k and v at 4, an lse a head;
    # top-8's chosen experts and sorted order in the four expert layers
    per_token = 5 * ((2 * 4096 + 2 * 512) * 2 + 4 * 32) + 4 * 2 * 8 * 4
    assert T.remat_plan(cfg, 2 * 8192, limit) == (
        "save_attention", 2 * 8192 * per_token)
    assert T.remat_plan(cfg, 2 * 8192, 1 << 40)[0] == "save_matmuls"


def test_the_cell_of_the_benchmark_stays_above_the_kernels_outputs_rung():
    """The twin of Kanana's test (tests/test_deepseek_v3.py): Trinity-Mini's
    share is decided above ``save_attention_out`` on the ladder, so the rung
    of PR 32 leaves its step as it was: 1,524,629,504 bytes kept, 18.1% of
    the 8,435,004,661 that the state leaves, under the 0.2 of its rung."""
    cfg = _cell_cfg()
    limit = int(15.74 * 2**30)
    room = limit - _state_bytes(cfg)
    assert room == 8_435_004_661
    assert T.remat_plan(cfg, 2 * 8192, limit) == (
        "save_attention", 1_524_629_504)
    assert 1_524_629_504 <= T.REMAT_SAVE_SHARE["save_attention"] * room
    # what the poorer rung would keep of it: o at 32 heads of 128 and an lse
    # a head in five layers, the routing's integers in four
    outputs = 2 * 8192 * (5 * (4096 * 2 + 4 * 32) + 4 * 2 * 8 * 4)
    assert outputs == 685_768_704
    # it is taken only where the richer one no longer fits: a device with
    # eight bytes less than save_attention's share of room
    under = _state_bytes(cfg) + 5 * 1_524_629_504 - 8
    assert T.remat_plan(cfg, 2 * 8192, under) == (
        "save_attention_out", outputs)


def test_remat_plan_reckons_the_new_widths_of_a_dense_model():
    cfg = T.TransformerConfig(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=32,
        d_ff=96, remat=True, ffn_gated=True, attn_output_gate=True)
    # q, attention output and gate at 4 x 32; k and v at 2 x 32; W_o's
    # output at 64; gate and up at 96; one lse lane a head in float32
    per_token = 2 * (3 * 128 + 2 * 64 + 64 + 2 * 96) + 4 * 4
    assert T.remat_plan(cfg, 100, 1 << 40) == (
        "save_matmuls", 2 * 100 * per_token)


def test_the_scopes_name_the_expert_layer_and_the_attention_kind():
    model, params, tokens, _ = _built(_config(), remat=False)
    # the compiled step's op_names, which the benchmark reads: the
    # dispatch's rules are jitted, and a jitted function's own text names
    # its operations from its own top
    text = jax.jit(jax.grad(lambda p: model.apply(
        p, tokens, train=True).sum())).lower(params).compile().as_text()
    for scope in ("moe_route", "moe_dispatch", "moe_experts", "moe_shared",
                  "moe_combine"):
        assert re.search(rf"/moe/(jit\(\w+\)/)?{scope}/", text), scope
    for scope in ("attn_window", "attn_full"):
        assert f"/{scope}" in text, scope


def test_layer_kinds_are_checked():
    with pytest.raises(ValueError, match="names 1 layers"):
        T.TransformerConfig(num_layers=2, layer_kinds=("full/dense",)
                            ).layer_kind(0)
    with pytest.raises(ValueError, match="is not"):
        T.TransformerConfig(num_layers=1, layer_kinds=("local/dense",)
                            ).layer_kind(0)
    with pytest.raises(ValueError, match="needs sliding_window"):
        T.TransformerConfig().attention_kind("window")
