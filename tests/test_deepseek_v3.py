"""The ``latent`` attention kind of ``TransformerConfig.layer_kinds``
(multi-head latent attention: a low-rank K/V path, a rotation on part of a
head, a key wider than its value) over the dense and expert feed-forwards,
against the plain reference ``benchmark/references/deepseek_v3.py``, at a
small size on the CPU in float32 with widths in the published ratios: hidden
64, 4 heads of 12 + 6 / 12, latent 32, 8 experts top-2, one dense and three
expert layers; and the flash kernels with a value narrower than the key.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.lib import manifest, weights
from horovod_tpu.common import tracing
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import flash_attention as fa

HERE = os.path.dirname(os.path.abspath(__file__))
SEQ = 32


@pytest.fixture(autouse=True)
def float32_products():
    # XLA's CPU dots are float32 already; say so for any backend
    with jax.default_matmul_precision("highest"):
        yield


def _config(held=(0, 4)):
    with open(os.path.join(HERE, "benchmark", "data",
                           "tiny-deepseek-v3.json")) as f:
        cfg = json.load(f)
    cfg["experts_held"] = list(held)
    cfg["n_routed_experts"] = held[1] - held[0]
    return cfg


def _published():
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "kanana-2-30b-a3b.json")) as f:
        return json.load(f)


def _family():
    return (manifest.load_module("models", "deepseek_v3"),
            manifest.load_module("references", "deepseek_v3"))


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
# expert after expert; flax's norms and jnp's differ in association; the
# program's dense softmax is over whole rows, the reference's in blocks).
# Measured: logits 2.7e-7 on values of 0.67, gradients 1.6e-6 of a leaf's
# largest entry. Ten times that is far under the least planted fault (the
# scale 12^-0.5 for 18^-0.5 moves a logit by 1.5e-3, the half-split
# rotation by 4e-2).
LOGITS_ATOL = 5e-6
GRAD_RTOL = 3e-5


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no-remat"])
@pytest.mark.parametrize("held", [(0, 4), (0, 8)], ids=["4of8", "8of8"])
def test_program_agrees_with_the_reference(held, remat):
    cfg = _config(held)
    _, ref = _family()
    model, params, tokens, labels = _built(cfg, remat=remat)
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
    for name, g, r in zip(weights.leaf_names(grads), jax.tree.leaves(grads),
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
    assert cfg.layer_kinds == ("latent/dense",) + ("latent/experts",) * 3
    attention = params["params"]["block_2"]["MultiHeadAttention_0"]
    assert jax.tree.map(lambda x: x.shape, attention) == {
        "q": {"kernel": (64, 4, 18)},        # heads x (nope + rope)
        "kv_a": {"kernel": (64, 32 + 6)},    # the latent and one rope head
        "kv_norm": {"scale": (32,)},         # over the latent alone
        "kv_b": {"kernel": (32, 4, 12 + 12)},  # heads x (nope + value)
        "out": {"kernel": (4, 12, 64)},      # from the value's width
    }
    assert "mlp" in params["params"]["block_0"]
    # the two shared experts are one gated MLP of their summed width
    assert params["params"]["block_1"]["moe"]["shared"]["up"][
        "kernel"].shape == (64, 48)
    assert T._param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))


def test_a_fault_in_the_reference_moves_its_logits():
    """Each planted fault is a different function, by name and by its
    traced index alike (one compiled program serves all of them)."""
    _, ref = _family()
    cfg = _config()
    _, params, tokens, _ = _built(cfg)
    right = ref.forward(params, tokens, cfg)
    faulty = jax.jit(lambda fault: ref.forward(
        params, tokens, dict(cfg, fault=fault)))
    for i, name in enumerate(ref.FAULTS):
        by_name = ref.forward(params, tokens, dict(cfg, fault=name))
        assert float(jnp.max(jnp.abs(by_name - right))) > 1e-3, name
        np.testing.assert_allclose(faulty(jnp.int32(i)), by_name, atol=1e-6)
    np.testing.assert_allclose(faulty(jnp.int32(-1)), right, atol=1e-6)


# --------------------------- (b) the rotation on interleaved pairs

@pytest.mark.parametrize("offset", [0, 5], ids=["from-0", "offset-5"])
def test_interleaved_rope_is_a_complex_multiplication(offset):
    d, theta = 6, 1e6
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 3, d))
    out = T.apply_rope(x, theta, offset=offset, interleave=True)
    # pair i is the complex number x[2i] + i x[2i+1]; it turns by
    # pos * theta^(-2i/d); real parts are written first, then imaginary
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    pos = offset + np.arange(SEQ)
    turn = np.exp(1j * pos[:, None] * theta ** (-np.arange(d // 2) / (d // 2)))
    want = z * turn[None, :, None, :]
    np.testing.assert_allclose(out[..., :d // 2], want.real, atol=1e-5)
    np.testing.assert_allclose(out[..., d // 2:], want.imag, atol=1e-5)
    # and it is not the half-split rotation of the same numbers
    assert float(jnp.max(jnp.abs(out - T.apply_rope(x, theta, offset)))) > 0.1


# ------------- (c) the kernels with a value narrower than the key

def _dense_attention(q, k, v):
    group = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(q.shape[-1])
    keep = jnp.arange(t)[None] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.mark.parametrize("kv_heads", [2, 1], ids=["mha", "gqa2"])
@pytest.mark.parametrize("staging", ["whole-sequence", "by-block"])
def test_kernels_at_key_192_value_128_match_dense_attention(
        staging, kv_heads, monkeypatch):
    # forward, dQ and dK/dV (both stagings of its q group) in interpret mode
    if staging == "by-block":
        monkeypatch.setenv("HOROVOD_FLASH_VMEM_BUDGET", "1")
    t, heads, d, d_v = 256, 2, 192, 128
    assert fa.fits_vmem(t, d, heads // kv_heads, 4, 128, d_v) == (
        staging == "whole-sequence")
    key = jax.random.PRNGKey(0)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), shape)
                  for i, shape in enumerate([
                      (2, t, heads, d), (2, t, kv_heads, d),
                      (2, t, kv_heads, d_v), (2, t, heads, d_v)]))

    def through(attend):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attend(q, k, v) * w), (0, 1, 2))(q, k, v)

    out = fa.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    assert out.shape == (2, t, heads, d_v)  # as wide as the value
    mine, grads = through(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128))
    want, want_grads = through(_dense_attention)
    # float32 online softmax against a dense one: rounding of sums of 256
    assert abs(float(mine) - float(want)) < 1e-3
    for g, r in zip(grads, want_grads):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, atol=2e-5, rtol=0)


def test_kernels_with_lengths_at_unequal_widths():
    t, d, d_v = 128, 48, 32
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, t, 2, w))
               for i, w in enumerate((d, d, d_v)))
    lengths = jnp.array([t, 80])
    out = fa.flash_attention(q, k, v, causal=True, lengths=lengths,
                             block_q=64, block_k=64)
    want = _dense_attention(q, k, v)  # causal: a row sees no later pad
    np.testing.assert_allclose(out[0], want[0], atol=2e-5)
    np.testing.assert_allclose(out[1, :80], want[1, :80], atol=2e-5)
    assert float(jnp.max(jnp.abs(out[1, 80:]))) == 0


def test_q_and_k_must_share_their_width():
    x = jnp.zeros((1, 64, 2, 32))
    with pytest.raises(ValueError, match="share their head width"):
        fa.flash_attention(x, jnp.zeros((1, 64, 2, 16)), x)


def test_staging_estimate_and_limit_at_unequal_widths():
    # q (192) and do, o (128) of one head whole-sequence, one lse lane,
    # and the K, V, dK, dV blocks
    assert fa.bwd_vmem_bytes(8192, 192, 1, 2, 512, 128) == (
        8192 * ((192 + 2 * 128) * 2 + 4) + 2 * 512 * (192 + 128) * 2)
    # the value's width defaults to the key's: what it always returned
    assert fa.bwd_vmem_bytes(512, 64, 1, 2, 512) == (
        512 * (3 * 64 * 2 + 4) + 4 * 512 * 64 * 2)
    assert fa.fits_vmem(8192, 192, 1, 2, 512, 128)
    # K and V whole-sequence, twice, a 192-wide key at 256 lanes: 12 MiB
    # and what a kernel holds beside them pass Mosaic's default 16 MiB
    params = fa._staging_params(8192, (192, 2), (128, 2))
    assert params.vmem_limit_bytes == 2 * 12 * 2**20 + 6 * 2**20
    # the standing cells' shapes keep the kernels they had
    assert fa._staging_params(8192, (128, 2), (128, 2)) is None  # Trinity
    assert fa._staging_params(512, (64, 2), (64, 2)) is None  # GPT-2, BERT
    assert fa._staging_params(512, (64, 2), (64, 2), (64, 2), (1, 4)) is None


# ----------------------------- (d) serving kwargs on the latent kind

def test_prefill_then_decode_equals_the_full_forward():
    model, params, tokens, _ = _built(_config(), remat=False)
    full = model.apply(params, tokens, train=False)
    cache = T.init_cache(model.cfg, batch=2, max_len=SEQ)
    # the dense cache holds the expanded heads: a wider key than value
    assert cache[0]["k"].shape == (2, SEQ, 4, 18)
    assert cache[0]["v"].shape == (2, SEQ, 4, 12)
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


def test_a_page_table_on_a_latent_layer_says_what_is_missing():
    model, params, tokens, _ = _built(_config(), remat=False)
    cache = T.init_cache(model.cfg, batch=2, max_len=SEQ)
    with pytest.raises(NotImplementedError,
                       match="no compressed latent row.*B-M4"):
        model.apply(params, tokens[:, :8], train=False, cache=cache,
                    cache_index=jnp.zeros((2,), jnp.int32),
                    pages=jnp.zeros((2, 4), jnp.int32))


# ----------------------------------------------- (e) what the program says

# over the four layers at 2 x SEQ float32 tokens: the flash kernels' q and k
# (4 heads of 18), v and o (4 heads of 12) and one lse a head, and in the
# three expert layers top-2's chosen experts and sorted order;
# save_matmuls' further outputs of W_o (64) and of the joint down-projection
# that the latent's norm reads (32 + 6), gate and up (2 x 192) in the dense
# layer, the router's logits (8, in float32) and the shared experts' gate
# and up (2 x 48) in the three expert layers
_ATTENTION_KEPT = 2 * SEQ * (
    4 * (4 * 2 * 4 * (18 + 12) + 4 * 4) + 3 * 2 * 2 * 4)
_MATMULS_KEPT = _ATTENTION_KEPT + 2 * SEQ * 4 * (
    4 * (64 + 38) + 2 * 192 + 3 * (8 + 2 * 48))
# the forward kernel's outputs alone: o (4 heads of 12) and one lse a head,
# and the routing's integers
_OUTPUTS_KEPT = 2 * SEQ * (4 * (4 * 4 * 12 + 4 * 4) + 3 * 2 * 2 * 4)


@pytest.mark.parametrize("room,want", [
    (1 << 40, ("save_matmuls", _MATMULS_KEPT)),
    (5 * _ATTENTION_KEPT, ("save_attention", _ATTENTION_KEPT)),
    # eight bytes under the five residuals' share: the kernel's outputs
    # alone, down to a tenth of the room
    (5 * _ATTENTION_KEPT - 8, ("save_attention_out", _OUTPUTS_KEPT)),
    (10 * _OUTPUTS_KEPT, ("save_attention_out", _OUTPUTS_KEPT)),
    (10 * _OUTPUTS_KEPT - 8, ("recompute_all", 0)),
], ids=["save_matmuls", "save_attention", "under-save_attention",
        "save_attention_out", "too-little-room"])
def test_remat_reckons_the_unequal_residuals_and_the_span_says_so(
        room, want, monkeypatch):
    monkeypatch.setenv("HOROVOD_TRACE", "0")
    tracing._reset()
    model, params, tokens, _ = _built(_config())
    cfg = dataclasses.replace(model.cfg, flash_attention=True)
    limit = T.REMAT_STATE_BYTES_PER_PARAM * T._param_count(cfg) + room
    assert T.remat_plan(cfg, 2 * SEQ, limit) == want
    monkeypatch.setattr(T, "_device_bytes_limit", lambda: limit)
    model = T.Transformer(cfg)
    jax.make_jaxpr(lambda p, t: model.apply(p, t, train=True))(params, tokens)
    span = [r for r in tracing.recorder().spans()
            if r["name"] == "hvd.trainer.trace_model"][-1]
    tracing._reset()
    assert span["tags"] == {
        "layers": 4, "remat": want[0], "remat_saved_bytes": want[1],
        "layer_kinds": "latent/dense,latent/experts,latent/experts,"
                       "latent/experts",
        "experts_total": 8, "experts_held": 4, "top_k": 2,
        "moe_rows_capacity": 2 * SEQ * 2, "moe_rows_chunk": 2 * SEQ * 2 // 16,
        "qk_head_dim": 18, "v_head_dim": 12, "kv_lora_rank": 32,
    }


def test_the_cell_of_the_benchmark_keeps_the_kernels_outputs():
    """Kanana's share at 2 x 8192 tokens beside a v5e's limit: the kernels'
    residuals, 672 MB a layer, are over their share of what 8.25 GB of
    state leave (ISSUE 31); the forward kernel's outputs alone, 136 MB a
    layer, are 9.5% of it and under theirs (ISSUE 32; ROADMAP B-M4's
    training rung)."""
    family, _ = _family()
    cfg = family.build_model(_published(), remat=True).cfg
    cfg = dataclasses.replace(cfg, flash_attention=True)  # as on the chip
    assert T._param_count(cfg) == 687_502_976
    # bfloat16 q and k at 32 heads of 192, v and o at 32 of 128, an lse a
    # head; top-6's chosen experts and sorted order in five expert layers
    per_token = 6 * (2 * 32 * (192 + 128) * 2 + 4 * 32) + 5 * 2 * 6 * 4
    assert 6 * 2 * 8192 * (2 * 32 * (192 + 128) * 2 + 4 * 32) == 4_039_114_752
    limit = int(15.74 * 2**30)
    assert 2 * 8192 * per_token == 4_043_046_912
    state = T.REMAT_STATE_BYTES_PER_PARAM * T._param_count(cfg)
    room = limit - state
    assert (state, room) == (8_250_035_712, 8_650_660_597)
    assert 4_043_046_912 > T.REMAT_SAVE_SHARE["save_attention"] * room
    # of them the forward kernel's outputs: bfloat16 o at 32 heads of 128
    # and an lse a head in six layers, 8,320 bytes a token and layer, and
    # the routing's integers, 48 a token, in five
    outputs = 6 * 2 * 8192 * (32 * 128 * 2 + 4 * 32) + 5 * 2 * 8192 * 2 * 6 * 4
    assert outputs == 817_889_280 + 3_932_160 == 821_821_440
    assert outputs <= T.REMAT_SAVE_SHARE["save_attention_out"] * room
    assert round(outputs / room, 3) == 0.095
    assert T.remat_plan(cfg, 2 * 8192, limit) == (
        "save_attention_out", 821_821_440)
    # eight bytes under a tenth of the room nothing is kept
    assert T.remat_plan(cfg, 2 * 8192, state + 10 * outputs) == (
        "save_attention_out", outputs)
    assert T.remat_plan(cfg, 2 * 8192, state + 10 * outputs - 8) == (
        "recompute_all", 0)
    # the matmuls' outputs beside them: W_o's and the joint
    # down-projection's in six layers, gate and up of the dense layer, the
    # router's float32 logits and the shared experts' gate and up in five
    matmuls = 6 * (2048 + 576) * 2 + 2 * 6144 * 2 + 5 * (4 * 128 + 2 * 1536 * 2)
    # the kernels' residuals are so much of what a block could keep that
    # the richer rung's larger share admits it first: a device on which
    # save_attention fits (20.2 GB beside the state) has room for
    # save_matmuls (15.7 GB), so this model never stops on the middle rung
    assert T.remat_plan(cfg, 2 * 8192, 1 << 36) == (
        "save_matmuls", 2 * 8192 * (per_token + matmuls))
    room = int(4_043_046_912 / T.REMAT_SAVE_SHARE["save_attention"])
    assert T.remat_plan(cfg, 2 * 8192, state + room)[0] == "save_matmuls"


def test_the_scopes_name_the_latent_layer_and_what_it_adds():
    model, params, tokens, _ = _built(_config(), remat=False)
    text = jax.jit(jax.grad(lambda p: model.apply(
        p, tokens, train=True).sum())).lower(params).compile().as_text()
    import re

    assert "/attn_latent/" in text
    for part in ("kv_a", "kv_norm", "kv_b"):
        # (flax puts the methods' names between the two scopes)
        assert re.search(rf"/attn_latent/[^\"]*/latent_proj/{part}/", text), part
    # q's and the output projection are a plain layer's: outside it
    for part in ("q", "out"):
        assert re.search(rf"/attn_latent/[^\"]*/{part}/dot_general", text)
        assert not re.search(rf"/latent_proj/{part}/", text), part
    assert "attn_full" not in text and "attn_window" not in text
    # the interleaved pairs are strided slices, not a gather
    assert not re.search(r"attn_latent[^\"]*/(gather|scatter)", text)


def test_layer_kinds_are_checked():
    with pytest.raises(ValueError, match="is not"):
        T.TransformerConfig(num_layers=1, layer_kinds=("latnet/dense",)
                            ).layer_kind(0)
    with pytest.raises(ValueError, match="is not"):
        T.TransformerConfig(num_layers=1, layer_kinds=("latent-nope/dense",)
                            ).layer_kind(0)
    with pytest.raises(ValueError, match="needs rope, kv_lora_rank"):
        T.TransformerConfig(rope=True, kv_lora_rank=32).attention_kind(
            "latent")
    assert T.TransformerConfig(
        rope=True, kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=6,
        v_head_dim=12).attention_kind("latent") == (None, True)
