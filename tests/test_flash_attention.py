"""Flash-attention kernels vs the dense oracle: forward values and all
three input gradients, causal and bidirectional, odd block splits.
(The reference has no analog — its attention lives in torch/cuDNN; this
is the TPU-native hot-op kernel, ops/flash_attention.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import flash_attention


from conftest import dense_attention_oracle as dense_attention


def _rand(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape), jnp.float32
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq,block", [(64, 16), (96, 32)])
def test_forward_matches_dense(causal, seq, block):
    b, h, d = 2, 3, 8
    q = _rand((b, seq, h, d), 0)
    k = _rand((b, seq, h, d), 1)
    v = _rand((b, seq, h, d), 2)
    out = flash_attention(q, k, v, causal=causal, block_q=block,
                          block_k=block)
    ref = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal):
    b, seq, h, d = 1, 32, 2, 8
    q = _rand((b, seq, h, d), 3)
    k = _rand((b, seq, h, d), 4)
    v = _rand((b, seq, h, d), 5)
    w = _rand((b, seq, h, d), 6)  # fixed cotangent-shaping weights

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        return jnp.sum(o * w)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal) * w)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-5
        )


def test_block_autoshrink_short_sequence():
    # seq smaller than the default block: blocks shrink, output exact
    b, seq, h, d = 1, 8, 1, 4
    q = _rand((b, seq, h, d), 7)
    k = _rand((b, seq, h, d), 8)
    v = _rand((b, seq, h, d), 9)
    out = flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_bf16_inputs():
    b, seq, h, d = 1, 32, 2, 8
    q = _rand((b, seq, h, d), 10).astype(jnp.bfloat16)
    k = _rand((b, seq, h, d), 11).astype(jnp.bfloat16)
    v = _rand((b, seq, h, d), 12).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, causal=False, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        False,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=2e-2, atol=2e-2
    )


def test_untileable_seq_falls_back_to_dense():
    """ViT's 197 tokens (prime-ish) can't tile: uses_flash must gate it
    off so models never hand Mosaic an impossible block shape."""
    from horovod_tpu.models.transformer import TransformerConfig
    from horovod_tpu.ops.flash_attention import supports_seq

    assert supports_seq(512) and supports_seq(128) and supports_seq(4)
    assert not supports_seq(197)
    cfg = TransformerConfig(flash_attention=True)
    assert cfg.uses_flash(seq=512)
    assert not cfg.uses_flash(seq=197)


def test_dense_fallback_is_loud_and_counted(caplog):
    """The shape dispatch stays, but a model that wants the kernels and
    runs dense attention says so and counts it (the serving engine's
    paged_attn_fallbacks discipline)."""
    import dataclasses
    import logging

    import jax
    import jax.numpy as jnp

    from horovod_tpu.common.metrics import registry
    from horovod_tpu.models.transformer import Transformer, TransformerConfig

    cfg = dataclasses.replace(
        TransformerConfig.tiny(), num_layers=1, flash_attention=True
    )
    assert cfg.wants_flash() and "13" in cfg.flash_decline_reason(seq=13)
    assert cfg.flash_decline_reason(seq=16) is None
    before = registry.snapshot().get("flash.dense_fallbacks", 0)
    logger = logging.getLogger("horovod_tpu.models.transformer")
    logger.addHandler(caplog.handler)
    try:
        jax.eval_shape(
            lambda: Transformer(cfg).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 13), jnp.int32),
                train=False,
            )
        )
    finally:
        logger.removeHandler(caplog.handler)
    assert registry.snapshot()["flash.dense_fallbacks"] == before + 1
    assert "runs dense attention" in caplog.text
    # "auto" off the TPU never wanted the kernels: quiet
    assert not TransformerConfig.tiny().wants_flash()


def test_vmem_footprint_gate():
    """The dK/dV backward kernel stages the whole q-head group
    whole-sequence only where that fits the budget (ADVICE r4); past it
    the kernel stages the group by block within the band, so big
    seq*(h/kv_h) products stay on the flash path."""
    from horovod_tpu.models.transformer import TransformerConfig
    from horovod_tpu.ops.flash_attention import bwd_vmem_bytes, fits_vmem

    # bench configs stay comfortably inside the budget
    assert fits_vmem(512, 64, 1, 2)  # gpt2-medium
    assert fits_vmem(512, 64, 16, 2)  # gpt2-medium @ 1 kv head
    assert fits_vmem(8192, 128, 1, 2)  # ulysses auto-gate cap, MHA
    # the advisor's example: r=8, seq 4k, d=128, bf16 — ~25 MiB
    assert bwd_vmem_bytes(4096, 128, 8, 2) > 16 * 2**20
    assert not fits_vmem(4096, 128, 8, 2)

    # the model does not leave the flash path for it
    big = TransformerConfig(
        num_layers=1, d_model=1024, num_heads=8, num_kv_heads=1,
        causal=True, flash_attention=True,
    )
    assert big.uses_flash(seq=512)
    assert big.uses_flash(seq=4096)
    assert big.flash_decline_reason(seq=4096) is None

    # and a direct kernel call has nothing to warn of
    import warnings

    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 4096, 8, 128)), jnp.bfloat16)
    kv = jnp.asarray(rng.normal(size=(1, 4096, 1, 128)), jnp.bfloat16)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        flash_attention(q, kv, kv, causal=True)
    assert not any("VMEM budget" in str(x.message) for x in w)


def test_vit_forward_with_flash_forced_on():
    """The full ViT (seq 197) must run even with flash_attention=True —
    the dense fallback, not a Mosaic compile error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models.vit import ViT, ViTConfig

    cfg = ViTConfig.tiny()  # seq = (32/8)^2 + 1 = 17 — also untileable
    model = ViT(cfg)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 32, 32, 3)), jnp.float32
    )
    params = model.init(jax.random.PRNGKey(0), x, train=False)
    out = jax.jit(lambda p, x: model.apply(p, x, train=False))(params, x)
    assert out.shape == (2, cfg.num_classes)


@pytest.mark.parametrize("layout", ["compact", "broadcast"])
def test_lse_interchange_layouts_agree(layout, monkeypatch):
    """The width-1 lse interchange (ADVICE r3: 128x less bwd HBM
    traffic) and the legacy broadcast escape hatch must produce
    identical gradients."""
    if layout == "broadcast":
        monkeypatch.setenv("HOROVOD_FLASH_LSE_BROADCAST", "1")
    else:
        monkeypatch.delenv("HOROVOD_FLASH_LSE_BROADCAST", raising=False)
    b, seq, h, d = 1, 64, 2, 8
    q, k, v = (_rand((b, seq, h, d), s) for s in (7, 8, 9))

    def loss(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16
        ).sum()

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    ref = dense_attention(q, k, v, True)
    gq_r, gk_r, gv_r = jax.grad(
        lambda q, k, v: dense_attention(q, k, v, True).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for got, want in ((gq, gq_r), (gk, gk_r), (gv, gv_r)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )


def _dense_padded(q, k, v, causal, lengths):
    """Dense oracle for right-padded batches: key-validity mask per
    sequence, zero outputs at padded query rows (the flash contract)."""
    b, t, h, d = q.shape
    valid = jnp.arange(t)[None, :] < lengths[:, None]  # [b, t]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / np.sqrt(d)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    if causal:
        tri = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(tri[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return jnp.where(valid[:, None, :, None].transpose(0, 2, 1, 3), o, 0.0)


@pytest.mark.parametrize("causal", [False, True])
def test_padded_forward_matches_dense(causal):
    """lengths= masks keys past each sequence's length and zeroes
    padded query rows — vs the masked dense oracle."""
    b, seq, h, d = 3, 64, 2, 8
    q, k, v = (_rand((b, seq, h, d), s) for s in (10, 11, 12))
    lengths = jnp.asarray([64, 37, 9], jnp.int32)  # full, odd, short
    out = flash_attention(
        q, k, v, causal=causal, block_q=16, block_k=16, lengths=lengths
    )
    ref = _dense_padded(q, k, v, causal, lengths)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    # padded rows are exactly zero, not just close
    assert float(np.abs(np.asarray(out)[1, 37:]).max()) == 0.0
    assert float(np.abs(np.asarray(out)[2, 9:]).max()) == 0.0


@pytest.mark.parametrize("causal", [False, True])
def test_padded_gradients_match_dense(causal):
    """All three gradients through the padded kernels vs the masked
    dense oracle; grads at padded positions must be exactly zero and
    everywhere finite (the degenerate-lse inf·0 hazard)."""
    b, seq, h, d = 2, 32, 2, 8
    q, k, v = (_rand((b, seq, h, d), s) for s in (13, 14, 15))
    w = _rand((b, seq, h, d), 16)
    lengths = jnp.asarray([32, 11], jnp.int32)

    def loss(q, k, v):
        return (
            flash_attention(
                q, k, v, causal=causal, block_q=8, block_k=8,
                lengths=lengths,
            ) * w
        ).sum()

    def ref_loss(q, k, v):
        return (_dense_padded(q, k, v, causal, lengths) * w).sum()

    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        g, r = np.asarray(g), np.asarray(r)
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-4)
        assert float(np.abs(g[1, 11:]).max()) == 0.0


def _dense_gqa(q, k, v, causal, lengths=None):
    """Dense oracle for grouped-query attention: repeat kv heads."""
    t = q.shape[1]
    r = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2)
    if lengths is None:
        return dense_attention(q, kk, vv, causal)
    return _dense_padded(q, kk, vv, causal, lengths)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_lengths", [False, True])
def test_gqa_matches_dense(causal, use_lengths):
    """Grouped-query attention (kv_heads < heads): the kernels read
    shared kv rows via the p//r index maps — fwd and all three grads
    vs the repeat-heads dense oracle, with and without padding."""
    b, t, h, g, d = 2, 64, 8, 2, 16
    q = _rand((b, t, h, d), 20)
    k = _rand((b, t, g, d), 21)
    v = _rand((b, t, g, d), 22)
    lengths = jnp.asarray([64, 23], jnp.int32) if use_lengths else None

    out = flash_attention(
        q, k, v, causal=causal, block_q=16, block_k=16, lengths=lengths
    )
    ref = _dense_gqa(q, k, v, causal, lengths)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )

    got = jax.grad(
        lambda q, k, v: (flash_attention(
            q, k, v, causal=causal, block_q=16, block_k=16,
            lengths=lengths) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    want = jax.grad(
        lambda q, k, v: (_dense_gqa(q, k, v, causal, lengths) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, bb in zip(got, want):
        assert a.shape == bb.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(bb), rtol=2e-4, atol=2e-4
        )


def test_gqa_rejects_bad_head_ratio():
    q = _rand((1, 16, 6, 8), 0)
    kv = _rand((1, 16, 4, 8), 1)  # 4 does not divide 6
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, kv, kv)


def _dense_window(q, k, v, window, lengths=None):
    """Dense oracle for the causal sliding window: mask row-col >= W
    on top of causal (and optional right-padding)."""
    t = q.shape[1]
    rows = jnp.arange(t)[:, None]
    cols = jnp.arange(t)[None, :]
    band = (rows >= cols) & (rows - cols < window)
    d = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / np.sqrt(d)
    if lengths is not None:
        valid = jnp.arange(t)[None, :] < lengths[:, None]
        s = jnp.where(valid[:, None, None, :], s, -1e30)
    s = jnp.where(band[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    if lengths is not None:
        valid = jnp.arange(t)[None, :] < lengths[:, None]
        o = jnp.where(valid[:, :, None, None], o, 0.0)
    return o


@pytest.mark.parametrize("window", [8, 24, 64])
def test_sliding_window_matches_dense(window):
    """Mistral-style causal sliding window, in-kernel band masking with
    clamped block loops — fwd + all grads vs the banded dense oracle."""
    b, t, h, d = 2, 64, 2, 8
    q, k, v = (_rand((b, t, h, d), s) for s in (30, 31, 32))
    out = flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16, window=window
    )
    ref = _dense_window(q, k, v, window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    got = jax.grad(
        lambda q, k, v: (flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16,
            window=window) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    want = jax.grad(
        lambda q, k, v: (_dense_window(q, k, v, window) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, bb in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(bb), rtol=2e-4, atol=2e-4
        )


def test_sliding_window_composes_with_gqa_and_lengths():
    """window + GQA + lengths all at once (the Mistral trifecta)."""
    b, t, h, g, d = 2, 64, 4, 2, 8
    q = _rand((b, t, h, d), 33)
    k = _rand((b, t, g, d), 34)
    v = _rand((b, t, g, d), 35)
    lengths = jnp.asarray([64, 29], jnp.int32)
    r = h // g
    out = flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16,
        lengths=lengths, window=16,
    )
    ref = _dense_window(
        q, jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2),
        16, lengths,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    g_ = jax.grad(lambda q: flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16,
        lengths=lengths, window=16).sum())(q)
    assert np.isfinite(np.asarray(g_)).all()
    assert float(np.abs(np.asarray(g_)[1, 29:]).max()) == 0.0


def test_sliding_window_requires_causal():
    q = _rand((1, 16, 2, 8), 0)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=8)
