"""Model zoo: shapes, dtypes, and trainability (one-step loss decrease),
mirroring the reference's example-model smoke coverage
(examples/pytorch/pytorch_mnist.py path [V])."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import (
    MNISTConvNet,
    ResNet50,
    Transformer,
    TransformerConfig,
    ViT,
    ViTConfig,
)


def test_mnist_convnet_forward_and_train():
    model = MNISTConvNet()
    x = jnp.zeros((8, 28, 28, 1))
    params = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(params, x, train=False)
    assert logits.shape == (8, 10)

    y = jnp.zeros((8,), jnp.int32)
    opt = optax.sgd(0.1)
    state = opt.init(params)

    def loss_fn(p):
        lg = model.apply(p, x, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(lg, y).mean()

    l0, g = jax.value_and_grad(loss_fn)(params)
    updates, state = opt.update(g, state, params)
    params2 = optax.apply_updates(params, updates)
    l1 = loss_fn(params2)
    assert float(l1) < float(l0)


def test_resnet50_forward_shapes():
    model = ResNet50(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32
    # batch_stats collection exists (SyncBatchNorm state)
    assert "batch_stats" in variables


def test_resnet_sync_batchnorm_updates_stats():
    model = ResNet50(num_classes=10, dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    _, mutated = model.apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(mutated["batch_stats"])
    assert any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(before, after)
    )


@pytest.mark.parametrize("causal", [True, False])
def test_transformer_forward(causal):
    cfg = TransformerConfig.tiny(causal=causal)
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens, train=False)
    logits = model.apply(params, tokens, train=False)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_transformer_causality():
    """Changing a future token must not affect earlier logits."""
    cfg = TransformerConfig.tiny(causal=True)
    model = Transformer(cfg)
    t1 = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    t2 = t1.at[0, -1].set(99)
    params = model.init(jax.random.PRNGKey(0), t1, train=False)
    l1 = model.apply(params, t1, train=False)
    l2 = model.apply(params, t2, train=False)
    np.testing.assert_allclose(
        np.asarray(l1[0, :-1]), np.asarray(l2[0, :-1]), rtol=1e-5
    )
    assert not np.allclose(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]))


@pytest.mark.parametrize("causal", [True, False])
def test_transformer_padded_lengths_flash_matches_dense(causal):
    """lengths= keeps the flash path (interpret kernels here) and must
    match the dense path's masked computation logit-for-logit; padded
    positions must not influence valid ones."""
    import dataclasses

    cfg = TransformerConfig.tiny(causal=causal)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)),
        jnp.int32,
    )
    lengths = jnp.asarray([16, 7], jnp.int32)
    flash_cfg = dataclasses.replace(cfg, flash_attention=True)
    dense_cfg = dataclasses.replace(cfg, flash_attention=False)
    params = Transformer(flash_cfg).init(
        jax.random.PRNGKey(0), tokens, train=False
    )
    lf = Transformer(flash_cfg).apply(
        params, tokens, train=False, lengths=lengths
    )
    ld = Transformer(dense_cfg).apply(
        params, tokens, train=False, lengths=lengths
    )
    np.testing.assert_allclose(
        np.asarray(lf), np.asarray(ld), rtol=5e-4, atol=5e-4
    )
    # a token edit INSIDE the padding must not change valid logits
    tokens2 = tokens.at[1, 12].set(3)
    lf2 = Transformer(flash_cfg).apply(
        params, tokens2, train=False, lengths=lengths
    )
    np.testing.assert_allclose(
        np.asarray(lf[1, :7]), np.asarray(lf2[1, :7]), rtol=1e-5
    )


def test_lm_head_mixed_matches_fp32_within_bf16_rounding():
    """The mixed-precision head (bf16 operands, fp32 accumulation) must
    agree with the all-fp32 head to bf16 input-rounding tolerance, on
    an IDENTICAL param tree (checkpoints are layout-compatible)."""
    import dataclasses

    # bf16 trunk for BOTH configs: identical activations reach the
    # head, so the only difference measured is the head matmul's
    # precision (tiny()'s fp32 dtype would make the comparison vacuous)
    cfg32 = dataclasses.replace(
        TransformerConfig.tiny(causal=True),
        dtype=jnp.bfloat16,
        head_mixed_precision=False,
    )
    cfgmx = dataclasses.replace(cfg32, head_mixed_precision=True)
    tokens = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    p32 = Transformer(cfg32).init(jax.random.PRNGKey(0), tokens,
                                  train=False)
    pmx = Transformer(cfgmx).init(jax.random.PRNGKey(0), tokens,
                                  train=False)
    s32 = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype.name), p32)
    smx = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype.name), pmx)
    assert s32 == smx
    l32 = Transformer(cfg32).apply(p32, tokens, train=False)
    lmx = Transformer(cfgmx).apply(p32, tokens, train=False)
    assert lmx.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(l32)))
    assert float(jnp.max(jnp.abs(lmx - l32))) <= 0.02 * max(scale, 1.0)


def test_transformer_named_configs():
    gpt2 = TransformerConfig.gpt2_medium()
    assert (gpt2.num_layers, gpt2.d_model) == (24, 1024) and gpt2.causal
    bert = TransformerConfig.bert_large()
    assert (bert.num_layers, bert.d_model) == (24, 1024) and not bert.causal


def test_vit_forward():
    cfg = ViTConfig.tiny()
    model = ViT(cfg)
    x = jnp.zeros((2, 32, 32, 3))
    params = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(params, x, train=False)
    assert out.shape == (2, 10)


def test_resnet_space_to_depth_stem_matches_grid():
    """The s2d stem (MLPerf TPU trick) must produce the exact conv7
    output grid and train end-to-end."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.resnet import ResNet

    x = jnp.asarray(
        np.random.default_rng(1).normal(size=(2, 32, 32, 3)), jnp.float32
    )
    shapes = {}
    for stem in ("conv7", "space_to_depth"):
        m = ResNet(
            stage_sizes=(1, 1), num_classes=7, width=8,
            dtype=jnp.float32, stem=stem,
        )
        v = m.init(jax.random.PRNGKey(0), x, train=False)
        y, _ = m.apply(v, x, train=True, mutable=["batch_stats"])
        shapes[stem] = y.shape
        assert bool(jnp.isfinite(y).all())
    assert shapes["conv7"] == shapes["space_to_depth"] == (2, 7)


def test_resnet_space_to_depth_equivalent_function_class():
    """A 7x7/s2 stem conv embeds exactly into the 4x4/s1 s2d conv: with
    the re-laid-out weights both compute the same function."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, 16, 16, 3)), jnp.float32)
    w7 = jnp.asarray(rng.normal(size=(7, 7, 3, 4)), jnp.float32)
    y_ref = jax.lax.conv_general_dilated(
        x, w7, window_strides=(2, 2), padding=[(3, 3), (3, 3)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    # space-to-depth input
    n, h, w, c = x.shape
    x2 = (
        x.reshape(n, h // 2, 2, w // 2, 2, c)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(n, h // 2, w // 2, 4 * c)
    )
    # embed w7 into the (4,4,12,4) kernel: tap (dy,dx) lands at
    # s2d position (ey+2, ex+2) channel (py*2+px)*c+cc with
    # dy-3 = 2*ey+py
    w4 = np.zeros((4, 4, 4 * c, 4), np.float32)
    for dy in range(7):
        for dx in range(7):
            ey, py = divmod(dy - 3, 2)
            ex, px = divmod(dx - 3, 2)
            w4[ey + 2, ex + 2, (py * 2 + px) * c : (py * 2 + px + 1) * c] = (
                np.asarray(w7[dy, dx])
            )
    y_s2d = jax.lax.conv_general_dilated(
        x2, jnp.asarray(w4), window_strides=(1, 1),
        padding=[(2, 1), (2, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    np.testing.assert_allclose(
        np.asarray(y_ref), np.asarray(y_s2d), rtol=1e-5, atol=1e-5
    )


def test_vgg16_forward_and_train_step():
    """VGG-16 — the reference's 68%-scaling benchmark model
    (docs/benchmarks.rst [V]): forward shape + one grad step."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import VGG16

    m = VGG16(num_classes=13, classifier_width=64, dtype=jnp.float32)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 32, 32, 3)), jnp.float32
    )
    v = m.init(jax.random.PRNGKey(0), x, train=False)
    y = m.apply(v, x, train=False)
    assert y.shape == (2, 13)
    # 16 weight layers: 13 convs + 3 dense
    n_layers = len(jax.tree_util.tree_leaves(v["params"])) // 2
    assert n_layers == 16

    def loss(p):
        out = m.apply(
            {"params": p}, x, train=True, rngs={"dropout": jax.random.PRNGKey(1)}
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            out, jnp.zeros(2, jnp.int32)
        ).mean()

    g = jax.grad(loss)(v["params"])
    assert all(
        bool(jnp.isfinite(leaf).all()) for leaf in jax.tree_util.tree_leaves(g)
    )


def test_inception_v3_forward_shapes():
    """Inception V3 — the reference's headline ~90%-scaling model
    (docs/benchmarks.rst [V]): 299x299 input → 1000 logits, batch-stats
    collection works, param count ≈ 23.8M (torchvision parity ±5%)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import InceptionV3

    m = InceptionV3(dtype=jnp.float32)
    x = jnp.zeros((1, 299, 299, 3), jnp.float32)
    v = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0), x, train=False))
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(v["params"])
    )
    assert 22.5e6 < n_params < 25.5e6, n_params
    logits_shape = jax.eval_shape(
        lambda vv: m.apply(vv, x, train=False), v
    )
    assert tuple(logits_shape.shape) == (1, 1000)


def test_transformer_flash_matches_dense_path():
    """flash_attention='auto' must be numerically consistent with the
    dense path (same params, same tokens)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import Transformer, TransformerConfig

    cfg_dense = dataclasses.replace(
        TransformerConfig.tiny(causal=True), flash_attention=False
    )
    cfg_flash = dataclasses.replace(cfg_dense, flash_attention=True)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, size=(2, 32)), jnp.int32
    )
    params = Transformer(cfg_dense).init(
        jax.random.PRNGKey(0), tokens, train=False
    )
    out_d = Transformer(cfg_dense).apply(params, tokens, train=False)
    out_f = Transformer(cfg_flash).apply(params, tokens, train=False)
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_d), rtol=2e-4, atol=2e-4
    )


def test_transformer_gqa_flash_matches_dense():
    """num_kv_heads < num_heads: split q/kv projections, flash path
    reads shared kv rows; must match the dense path's repeated-head
    computation logit-for-logit."""
    import dataclasses

    cfg = dataclasses.replace(
        TransformerConfig.tiny(causal=True), num_kv_heads=2
    )
    assert cfg.num_heads % 2 == 0 and cfg.num_heads != 2
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 16)),
        jnp.int32,
    )
    flash_cfg = dataclasses.replace(cfg, flash_attention=True)
    dense_cfg = dataclasses.replace(cfg, flash_attention=False)
    params = Transformer(flash_cfg).init(
        jax.random.PRNGKey(0), tokens, train=False
    )
    # the GQA param tree splits the projection
    blk = params["params"]["block_0"]["MultiHeadAttention_0"]
    assert "q" in blk and "kv" in blk and "qkv" not in blk
    lf = Transformer(flash_cfg).apply(params, tokens, train=False)
    ld = Transformer(dense_cfg).apply(params, tokens, train=False)
    np.testing.assert_allclose(
        np.asarray(lf), np.asarray(ld), rtol=5e-4, atol=5e-4
    )


def test_vit_flash_pad_matches_dense():
    """ViT's untileable token count (tiny: 16+1=17) padded to the next
    8-multiple with lengths= must reproduce the unpadded dense model's
    logits exactly — on both the dense-with-lengths path and the
    flash-forced path (interpret kernels)."""
    import dataclasses

    x = jnp.asarray(
        np.random.default_rng(2).normal(size=(2, 32, 32, 3)), jnp.float32
    )
    base = dataclasses.replace(ViTConfig.tiny(), flash_pad=False)
    params = ViT(base).init(jax.random.PRNGKey(0), x, train=False)
    want = ViT(base).apply(params, x, train=False)
    for cfg in (
        dataclasses.replace(ViTConfig.tiny(), flash_pad=True),
        dataclasses.replace(
            ViTConfig.tiny(), flash_pad=True, flash_attention=True
        ),
    ):
        got = ViT(cfg).apply(params, x, train=False)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-5, atol=5e-5
        )


def test_transformer_mistral_trifecta_flash_matches_dense():
    """sliding_window + num_kv_heads + lengths composed in the model:
    flash path vs dense path logit-for-logit."""
    import dataclasses

    cfg = dataclasses.replace(
        TransformerConfig.tiny(causal=True),
        num_kv_heads=2, sliding_window=6,
    )
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(0, cfg.vocab_size, (2, 16)),
        jnp.int32,
    )
    lengths = jnp.asarray([16, 9], jnp.int32)
    flash_cfg = dataclasses.replace(cfg, flash_attention=True)
    dense_cfg = dataclasses.replace(cfg, flash_attention=False)
    params = Transformer(flash_cfg).init(
        jax.random.PRNGKey(0), tokens, train=False
    )
    lf = Transformer(flash_cfg).apply(
        params, tokens, train=False, lengths=lengths
    )
    ld = Transformer(dense_cfg).apply(
        params, tokens, train=False, lengths=lengths
    )
    np.testing.assert_allclose(
        np.asarray(lf), np.asarray(ld), rtol=5e-4, atol=5e-4
    )
    # the window actually bites: full-causal config differs
    full = dataclasses.replace(flash_cfg, sliding_window=None)
    lfull = Transformer(full).apply(
        params, tokens, train=False, lengths=lengths
    )
    assert not np.allclose(np.asarray(lf), np.asarray(lfull), atol=1e-3)


def test_rope_properties_and_llama_shape_trains():
    """RoPE: relative-position property (scores depend only on row-col
    offset) and a full Llama/Mistral-shaped config (RoPE + GQA +
    sliding window, no learned pos table) trains through flash."""
    import dataclasses

    from horovod_tpu.models.transformer import apply_rope

    # property: <rope(q)_i, rope(k)_j> is a function of (i - j) only
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 8, 1, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 8, 1, 16)), jnp.float32)
    # same q/k content placed at positions (2, 5) vs (0, 3): equal dots
    qc = jnp.broadcast_to(q[:, :1], q.shape)  # constant content
    kc = jnp.broadcast_to(k[:, :1], k.shape)
    rq, rk = apply_rope(qc), apply_rope(kc)
    dots = jnp.einsum("bthd,bshd->bts", rq, rk)[0]
    np.testing.assert_allclose(
        np.asarray(dots[2, 5]), np.asarray(dots[0, 3]), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(jnp.diag(dots)),
        np.full(8, float(dots[0, 0])), rtol=1e-5,
    )
    # offset shifts positions: rope(x, offset=3)[:, 0] == rope(x)[:, 3]
    x = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(apply_rope(x, offset=3)[:, 0]),
        np.asarray(apply_rope(jnp.roll(x, 3, 1))[:, 3]),
        rtol=1e-5, atol=1e-6,
    )

    cfg = dataclasses.replace(
        TransformerConfig.tiny(causal=True),
        rope=True, num_kv_heads=2, sliding_window=6,
        flash_attention=True,
    )
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                         jnp.int32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens, train=False)
    # no learned position table in the tree
    assert not any("Embed_1" in k for k in params["params"])
    import optax

    def loss_fn(p):
        lg = model.apply(p, tokens, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            lg.astype(jnp.float32), jnp.roll(tokens, -1, 1)
        ).mean()

    l0, g = jax.value_and_grad(loss_fn)(params)
    p2 = optax.apply_updates(
        params, jax.tree_util.tree_map(lambda x: -0.05 * x, g)
    )
    assert float(loss_fn(p2)) < float(l0)


@pytest.mark.parametrize(
    "rope,num_kv_heads", [(False, None), (True, 2)],
    ids=["learned-pos-mha", "rope-gqa"],
)
def test_transformer_incremental_decode_matches_full(rope, num_kv_heads):
    """The serving engine's model contract (docs/serving.md): the
    cache-threaded forward must reproduce the full-sequence forward —
    prefill logits equal to float32 rounding, and token-by-token decode matching
    the full forward's greedy argmax at every position."""
    from horovod_tpu.models.transformer import init_cache

    cfg = TransformerConfig(
        vocab_size=97, num_layers=2, d_model=32, num_heads=4, d_ff=64,
        max_len=32, causal=True, dtype=jnp.float32, rope=rope,
        num_kv_heads=num_kv_heads,
    )
    model = Transformer(cfg)
    rng = np.random.default_rng(7)
    T = 9
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, T)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens, train=False)
    full = np.asarray(model.apply(params, tokens, train=False))

    # whole-prompt prefill through the cache path: every position's
    # logits equal the full forward to float32 rounding (extra cache
    # keys are masked to exact zeros, but the reductions run over 16
    # keys and not 9, and the compiler may order them otherwise)
    cache = init_cache(cfg, 2, 16)
    logits, cache = model.apply(
        params, tokens, train=False,
        cache=cache, cache_index=jnp.zeros((2,), jnp.int32),
    )
    np.testing.assert_allclose(
        full, np.asarray(logits), rtol=2e-5, atol=2e-5
    )

    # token-by-token decode: greedy argmax bit-identical per position
    cache = init_cache(cfg, 2, 16)
    step_logits = []
    for i in range(T):
        lg, cache = model.apply(
            params, tokens[:, i:i + 1], train=False,
            cache=cache, cache_index=jnp.full((2,), i, jnp.int32),
        )
        step_logits.append(np.asarray(lg)[:, 0])
    stepwise = np.stack(step_logits, axis=1)
    np.testing.assert_array_equal(
        full.argmax(-1), stepwise.argmax(-1)
    )
    np.testing.assert_allclose(full, stepwise, rtol=2e-5, atol=2e-5)
    # staggered slots: the two rows decode at DIFFERENT cache indices
    # (row 0 at position 3, row 1 at position 7) in one call
    idx = jnp.asarray([3, 7], jnp.int32)
    stag_tokens = jnp.stack([tokens[0, 3], tokens[1, 7]])[:, None]
    cache4 = init_cache(cfg, 2, 16)
    _, cache4 = model.apply(
        params, tokens, train=False,
        cache=cache4, cache_index=jnp.zeros((2,), jnp.int32),
    )
    lg, _ = model.apply(
        params, stag_tokens, train=False,
        cache=cache4, cache_index=idx,
    )
    lg = np.asarray(lg)[:, 0]
    np.testing.assert_array_equal(
        full[0, 3].argmax(-1), lg[0].argmax(-1)
    )
    np.testing.assert_array_equal(
        full[1, 7].argmax(-1), lg[1].argmax(-1)
    )


def test_transformer_cache_rejects_bad_compositions():
    from horovod_tpu.models.transformer import init_cache

    cfg = TransformerConfig.tiny()
    model = Transformer(cfg)
    tokens = jnp.ones((1, 4), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens, train=False)
    cache = init_cache(cfg, 1, 8)
    idx = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="mask"):
        model.apply(
            params, tokens, train=False, cache=cache, cache_index=idx,
            mask=jnp.ones((1, 4), bool),
        )
    import dataclasses

    enc = dataclasses.replace(cfg, causal=False)
    enc_model = Transformer(enc)
    enc_params = enc_model.init(
        jax.random.PRNGKey(0), tokens, train=False
    )
    with pytest.raises(ValueError, match="causal"):
        enc_model.apply(
            enc_params, tokens, train=False,
            cache=init_cache(enc, 1, 8), cache_index=idx,
        )
