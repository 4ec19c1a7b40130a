"""Flash-attention kernels vs the dense oracle: forward values and all
three input gradients, causal and bidirectional, odd block splits.
(The reference has no analog — its attention lives in torch/cuDNN; this
is the TPU-native hot-op kernel, ops/flash_attention.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa
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


# ------------- the blocked dK/dV kernel: its table of steps, and its values

def _keep(mask, seq, arg):
    """Which key (column) a query (row) keeps, by comparison of indices."""
    r, c = np.arange(seq)[:, None], np.arange(seq)[None, :]
    if mask == "block_diffusion":
        half = seq // 2
        bi, bj = r % half // arg, c % half // arg
        return np.where(r < half, np.where(c < half, bi == bj, bj < bi),
                        (c >= half) & (bj <= bi))
    if mask == "full":
        return np.ones((seq, seq), bool)
    return (c <= r) & ((r - c < arg) if arg else True)


def _steps(mask, seq, block_q, block_k, arg):
    return fa._dkv_steps(
        seq, block_q, block_k, mask in ("causal", "window"),
        arg if mask == "window" else None,
        arg if mask == "block_diffusion" else None)


@pytest.mark.parametrize("mask, seq, block_q, block_k, arg, tiles", [
    # block-diffusion training's shapes (SDAR), and Trinity's two layer
    # kinds: counted, not compared with a 16,384 x 16,384 mask
    ("block_diffusion", 16384, 512, 512, 4, 288),
    ("causal", 8192, 512, 512, None, 136),
    ("window", 8192, 512, 512, 2048, 70),
    ("block_diffusion", 128, 16, 16, 4, None),
    ("block_diffusion", 128, 16, 16, 16, None),
    ("block_diffusion", 128, 32, 16, 4, None),
    ("block_diffusion", 128, 8, 32, 8, None),
    ("block_diffusion", 96, 16, 8, 4, None),
    ("causal", 64, 16, 16, None, None),
    ("causal", 64, 8, 16, None, None),
    ("causal", 96, 32, 16, None, None),
    ("causal", 96, 16, 48, None, None),
    ("window", 64, 16, 16, 8, None),
    ("window", 64, 16, 16, 24, None),
    ("window", 128, 16, 32, 40, None),
    ("window", 128, 32, 16, 17, None),
    ("full", 64, 16, 32, None, None),
])
def test_the_dkv_step_table_lists_the_tiles_the_mask_keeps(
        mask, seq, block_q, block_k, arg, tiles):
    """Exactly the tiles that keep a pair, each once (a step takes a Q
    tile of as many query heads as fit, whatever the group); a K tile's
    steps contiguous, Q tiles ascending, the first and the last marked:
    nothing for the grid to skip."""
    steps, kept = _steps(mask, seq, block_q, block_k, arg)
    assert steps.dtype == np.int32 and steps.shape == (kept,)
    k_tile, q_tile, edge = fa._step_fields(steps)
    n_q, n_k = seq // block_q, seq // block_k
    if tiles is not None:
        assert kept == tiles
        bands = [int(np.sum(k_tile == ki)) for ki in range(n_k)]
        if mask == "block_diffusion":
            assert bands == [1] * 16 + list(range(32, 0, -2))
        elif mask == "causal":
            assert bands == list(range(16, 0, -1))
        else:
            assert bands == [5] * 12 + [4, 3, 2, 1]
    else:
        keeps = _keep(mask, seq, arg).reshape(
            n_q, block_q, n_k, block_k).any(axis=(1, 3))
        assert kept == int(keeps.sum())
    start = 0
    for ki in range(n_k):
        (at,) = np.nonzero(k_tile == ki)
        assert list(at) == list(range(start, start + len(at))), ki
        start += len(at)
        if tiles is None:
            assert list(q_tile[at]) == list(np.flatnonzero(keeps[:, ki])), ki
        assert list(q_tile[at]) == sorted(set(q_tile[at])), ki
        want = np.zeros(len(at), int)
        want[0] |= fa._FIRST
        want[-1] |= fa._LAST
        assert list(edge[at]) == list(want), ki
    assert start == kept


def test_a_k_tile_with_an_empty_band_still_gets_a_step_and_writes_zeros(
        monkeypatch):
    """No mask of today's empties a K tile's band; were one to, the table
    gives the tile one step on a tile that the mask empties, so the kernel
    zeroes and stores its accumulators as for any other."""
    real = fa._dkv_q_range

    def emptied(ki, *args):
        first, last = real(ki, *args)
        return (first, first) if ki == 2 else (first, last)

    t, heads, d = 64, 4, 8
    q = _rand((1, t, heads, d), 50)
    k, v = _rand((1, t, 2, d), 51), _rand((1, t, 2, d), 52)

    def grads():
        return jax.grad(lambda k, v: flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16).sum(), (0, 1))(k, v)

    monkeypatch.setenv("HOROVOD_FLASH_VMEM_BUDGET", "1")
    untouched = grads()
    monkeypatch.setattr(fa, "_dkv_q_range", emptied)
    steps, kept = fa._dkv_steps(t, 16, 16, True, None)
    k_tile, q_tile, edge = fa._step_fields(steps)
    # bands of 4, 3, (2 ->) 0, 1 Q tiles, and the step that stands in
    assert kept == 8 and len(steps) == kept + 1
    (at,) = np.nonzero(k_tile == 2)
    assert list(edge[at]) == [fa._FIRST | fa._LAST]
    assert (q_tile[at] < 2).all()  # above the diagonal: nothing is kept
    for mine, theirs in zip(grads(), untouched):
        assert float(jnp.abs(mine[:, 32:48]).max()) == 0.0
        np.testing.assert_array_equal(mine[:, :32], theirs[:, :32])
        np.testing.assert_array_equal(mine[:, 48:], theirs[:, 48:])


def test_the_step_table_refuses_what_its_words_cannot_hold():
    # the largest it takes: 16,384 tiles (bands of two under this window,
    # of one at the end)
    steps, kept = fa._dkv_steps(16384 * 8, 8, 8, True, 8)
    k_tile, q_tile, _ = fa._step_fields(steps)
    assert (steps >= 0).all() and kept == len(steps) == 2 * 16384 - 1
    assert (k_tile.max(), q_tile.max()) == (16383, 16383)
    with pytest.raises(ValueError, match="16385 tiles"):
        fa._dkv_steps(16385 * 8, 8, 8, True, 8)


# q, do, o and the width-1 float32 lse of a 128-wide bf16 head, and of a
# 192-wide key with a 128-wide value
_HEAD_128 = ((128, 2), (128, 2), (128, 2), (1, 4))
_KEY_192 = ((192, 2), (128, 2), (128, 2), (1, 4))


@pytest.mark.parametrize("group, block_q, operands, members, limit_mib", [
    # SDAR's and Trinity's calls: the whole group, 10 MiB of blocks, which
    # compile at Mosaic's default limit (tests/test_tpu_compile.py)
    (8, 512, _HEAD_128, 8, None),
    (1, 512, _HEAD_128, 1, None),
    (4, 512, _HEAD_128, 4, None),
    # 12 MiB of blocks: the limit is raised to twice that and the room
    (8, 512, _KEY_192, 8, 30),
    (16, 512, _HEAD_128, 16, 46),
    # 40 MiB of blocks would not fit: two steps of 16 a Q tile
    (32, 512, _HEAD_128, 16, 46),
    (64, 256, _HEAD_128, 32, 46),
    # no divisor of 12 but 1, 2, 3, 4 and 6 leaves room
    (12, 1024, _HEAD_128, 6, 36),
    (7, 512, _HEAD_128, 7, None),
])
def test_the_members_a_step_takes_follow_the_group_and_its_blocks(
        group, block_q, operands, members, limit_mib):
    """The largest divisor of the group whose blocks, twice buffered, and
    the room beside them fit a step's share of VMEM; the limit the call
    asks Mosaic for is :func:`_staging_params`' for those blocks. Shapes
    alone, nothing traced."""
    assert fa._members_per_step(group, block_q, *operands) == members
    held = fa.staged_vmem_bytes(members * block_q, *operands)
    assert held + fa._VMEM_BESIDE_STAGED <= fa._STEP_VMEM
    if members < group:
        more = min(m for m in range(members + 1, group + 1) if group % m == 0)
        assert (fa.staged_vmem_bytes(more * block_q, *operands)
                + fa._VMEM_BESIDE_STAGED > fa._STEP_VMEM)
    params = fa._staging_params(members * block_q, *operands)
    assert (None if params is None
            else params.vmem_limit_bytes / 2**20) == limit_mib


def _blocked_case(mask, arg, heads, kv_heads, lengths):
    return pytest.param(mask, arg, heads, kv_heads, lengths, id="-".join(
        [mask, f"{heads}on{kv_heads}"] + (["padded"] if lengths else [])))


@pytest.mark.parametrize("mask, arg, heads, kv_heads, lengths", [
    _blocked_case("causal", None, 2, 2, None),
    _blocked_case("causal", None, 8, 1, None),
    _blocked_case("window", 24, 2, 2, None),
    _blocked_case("window", 40, 4, 1, None),
    _blocked_case("block_diffusion", 4, 2, 2, None),
    _blocked_case("block_diffusion", 8, 8, 2, None),
    _blocked_case("full", None, 4, 2, None),
    _blocked_case("full", None, 2, 2, [96, 37]),
    _blocked_case("causal", None, 4, 1, [50, 96]),
    _blocked_case("window", 24, 4, 2, [96, 17]),
])
def test_blocked_dkv_equals_the_whole_sequence_kernel(
        mask, arg, heads, kv_heads, lengths, monkeypatch):
    """The two forms of the dK/dV kernel on the same inputs: the same
    products of the same tiles, summed in the same order (Q tile by Q
    tile, and within one query head by query head, :func:`_dkv_members`),
    so the same bits; and both against dense attention under the mask."""
    blocked, whole, want = _dkv_both_ways(
        mask, arg, heads, kv_heads, lengths, monkeypatch)
    np.testing.assert_array_equal(blocked[0], whole[0])  # dQ: one kernel
    for name, mine, theirs, ref in zip("kv", blocked[1:], whole[1:],
                                       want[1:]):
        np.testing.assert_array_equal(mine, theirs, err_msg=name)
        np.testing.assert_allclose(mine, ref, atol=2e-5, rtol=1e-5,
                                   err_msg=name)


def _dkv_both_ways(mask, arg, heads, kv_heads, lengths, monkeypatch):
    """``(blocked, whole, dense)``: the gradients of q, k and v with the
    dK/dV kernel staged block by block (``fits_vmem`` made to refuse),
    whole-sequence, and by dense attention under the mask."""
    t, d = 96, 16
    q = _rand((2, t, heads, d), 60)
    k, v = _rand((2, t, kv_heads, d), 61), _rand((2, t, kv_heads, d), 62)
    w = _rand((2, t, heads, d), 63)
    kwargs = dict(block_q=16, block_k=16)
    if mask == "block_diffusion":
        kwargs["block_diffusion"] = arg
    else:
        kwargs.update(causal=mask != "full",
                      window=arg if mask == "window" else None)
    lens = None if lengths is None else jnp.asarray(lengths, jnp.int32)

    def grads(fits):
        monkeypatch.setattr(fa, "fits_vmem", lambda *a, **k: fits)
        # jitted, so that the calls leave their spans
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, lengths=lens, **kwargs) * w), (0, 1, 2)))(q, k, v)

    def dense(q, k, v):
        group = heads // kv_heads
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, group, 2))
        keep = jnp.asarray(_keep(mask, t, arg))[None, None]
        if lens is not None:
            keep = keep & (jnp.arange(t) < lens[:, None])[:, None, None, :]
        p = jax.nn.softmax(jnp.where(keep, s / np.sqrt(d), -1e30), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, group, 2))
        if lens is not None:
            o = jnp.where((jnp.arange(t) < lens[:, None])[..., None, None],
                          o, 0.0)
        return jnp.sum(o * w)

    blocked, whole = grads(False), grads(True)
    return blocked, whole, jax.grad(dense, (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("mask, arg, lengths, members", [
    ("causal", None, None, 1),
    ("window", 24, [96, 17], 2),
    ("block_diffusion", 4, None, 1),
    ("full", None, [50, 96], 2),
])
def test_a_group_split_over_steps_sums_to_the_same_bits(
        mask, arg, lengths, members, monkeypatch):
    """Where a step's share of VMEM holds fewer than the group's four
    query heads, the group's parts are the grid's innermost dimension: the
    sums keep their order, and the gradients their bits."""
    # one member's blocks at these shapes: 16 rows of four 512-byte rows
    # of float32, twice
    monkeypatch.setattr(fa, "_STEP_VMEM", fa._VMEM_BESIDE_STAGED
                        + members * 2 * 16 * 2048)
    assert fa._members_per_step(4, 16, *[(16, 4)] * 3, (1, 4)) == members
    before = len(_flash_calls())
    blocked, whole, _ = _dkv_both_ways(
        mask, arg, 4, 1, lengths, monkeypatch)
    (span,) = [t for t in _flash_calls()[before:]
               if t["kernel"] == "flash_dkv" and t["staging"] == "blocked"]
    assert span["members_per_step"] == members
    assert span["grid_steps"] == span["kept_tiles"] == (
        2 * len(_steps(mask, 96, 16, 16, arg)[0]) * 4 // members)
    for name, mine, theirs in zip("qkv", blocked, whole):
        np.testing.assert_array_equal(mine, theirs, err_msg=name)


def _flash_calls():
    from horovod_tpu.common import tracing

    return [r["tags"] for r in tracing.recorder().spans()
            if r["name"] == "hvd.kernels.flash_call"]


def test_a_traced_blocked_call_records_what_its_grid_holds(monkeypatch):
    """``hvd.kernels.flash_call`` of a blocked dK/dV call: a span while
    JAX traces it, tagged with the grid's steps and the tiles the mask
    keeps."""
    monkeypatch.setenv("HOROVOD_FLASH_VMEM_BUDGET", "1")
    q = _rand((2, 128, 4, 8), 70)
    kv = _rand((2, 128, 2, 8), 71)

    def grad(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, block_q=16, block_k=16, block_diffusion=4).sum(),
            (1, 2))(q, k, v)

    before = len(_flash_calls())
    jax.block_until_ready(jax.jit(grad)(q, kv, kv))
    (span,) = [t for t in _flash_calls()[before:]
               if t["kernel"] == "flash_dkv"]
    # 4 noised K tiles of 1 Q tile, clean bands of 8, 6, 4, 2: 24 tiles
    # for each of 4 kv rows, a step taking both query heads of the group
    # (the rectangle held 8 steps a K tile and member: 4 x 8 x 2 x 8 = 512)
    assert span == {
        "kernel": "flash_dkv", "staging": "blocked",
        "mask": "block_diffusion", "block_q": 16, "block_k": 16,
        "seq": 128, "heads": 8, "group": 2, "d_qk": 8, "d_v": 8,
        "grid_steps": 96, "kept_tiles": 96, "kv_rows": 4,
        "members_per_step": 2, "staged_vmem_bytes": 0,
        "vmem_limit_bytes": 0}


# seq 128 in tiles of 16: 8 x 8 tiles. What the blocked dK/dV grid keeps of
# them a kv row (a step takes the group's two query heads): the lower
# triangle; the band of a
# 40-wide window (a K tile is seen by its own Q tile and the three after
# it: 5 x 4, then 3, 2, 1); all of them (a length bound is a ``pl.when``
# inside a kept step); the block-diffusion mask's 24 (the test above).
_KEPT = {"causal": 36, "window": 26, "none": 64, "lengths": 64,
         "block_diffusion": 24}
_MASKS = {
    "causal": dict(causal=True),
    "window": dict(causal=True, window=40),
    "none": dict(),
    "lengths": dict(lengths=True),
    "block_diffusion": dict(block_diffusion=4),
}


@pytest.mark.parametrize("staging", ["whole", "blocked"])
@pytest.mark.parametrize("mask", sorted(_MASKS))
def test_every_kernel_call_leaves_its_plan(mask, staging, monkeypatch):
    """``hvd.kernels.flash_call``: one span a ``pallas_call`` of the
    gradient (forward, dQ, dK/dV), in either staging of dK/dV, under each
    mask, with the plan's numbers as reckoned by hand: 2 rows x 4 query
    heads on 2 kv heads, seq 128, blocks of 16, a 16-wide float32 key and
    an 8-wide value."""
    monkeypatch.setenv("HOROVOD_FLASH_VMEM_BUDGET",
                       "1" if staging == "blocked" else str(2**30))
    q = _rand((2, 128, 4, 16), 80)
    k = _rand((2, 128, 2, 16), 81)
    v = _rand((2, 128, 2, 8), 82)
    kwargs = dict(_MASKS[mask], block_q=16, block_k=16)
    if kwargs.pop("lengths", False):
        kwargs["lengths"] = jnp.asarray([128, 100], jnp.int32)

    def grad(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, **kwargs).sum(), (0, 1, 2))(q, k, v)

    before = len(_flash_calls())
    jax.block_until_ready(jax.jit(grad)(q, k, v))
    fwd, dq, dkv = _flash_calls()[before:]
    shared = {"mask": mask, "block_q": 16, "block_k": 16, "seq": 128,
              "heads": 8, "group": 2, "d_qk": 16, "d_v": 8,
              "vmem_limit_bytes": 0}
    # K and V whole-sequence, twice buffered, each row rounded up to 128
    # float32 lanes: 2 x 128 x (512 + 512) bytes
    kv_staged = 2 * 128 * 1024
    for span, kernel in ((fwd, "flash_fwd"), (dq, "flash_dq")):
        assert span == dict(
            shared, kernel=kernel, staging="whole", grid_steps=8 * 8,
            staged_vmem_bytes=kv_staged)
    if staging == "whole":
        # the group's q, do, o and lse: 2 x 128 rows of four 512-byte
        # rows, twice; a K tile a step of each of 4 kv rows
        assert dkv == dict(
            shared, kernel="flash_dkv", staging="whole", grid_steps=4 * 8,
            staged_vmem_bytes=2 * 256 * 2048)
    else:
        steps = 4 * _KEPT[mask]
        assert dkv == dict(
            shared, kernel="flash_dkv", staging="blocked",
            grid_steps=steps, kept_tiles=steps, kv_rows=4,
            members_per_step=2, staged_vmem_bytes=0)


def test_a_call_past_mosaics_default_limit_says_what_it_asks_for():
    """Past the 16 MiB a kernel may hold by default the span carries the
    limit the call asks for (``_staging_params``); traced only, nothing
    runs: 8192 positions of a 192-wide bf16 key and a 128-wide value."""
    q = jax.ShapeDtypeStruct((1, 8192, 2, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16)
    before = len(_flash_calls())
    jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal=True),
                   q, q, v)
    (fwd,) = _flash_calls()[before:]
    staged = 2 * 8192 * (512 + 256)  # 12 MiB
    assert (fwd["kernel"], fwd["staged_vmem_bytes"]) == ("flash_fwd", staged)
    assert fwd["vmem_limit_bytes"] == 2 * staged + 6 * 2**20
    assert (fwd["d_qk"], fwd["d_v"], fwd["group"]) == (192, 128, 1)
