"""What ``Transformer(remat=True)`` keeps between forward and backward
(``models/transformer.py: remat_plan``): the decision from shapes and the
device's memory limit, that saving changes no value, what the rematted
backward still runs, that the flash residuals' names cost a step without
remat nothing, and the tags that say which way the decision went."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import stripped_hlo

from horovod_tpu.common import tracing
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import flash_attention as fa

GIB = 1 << 30
V5E_LIMIT = int(15.75 * GIB)


def _gpt2m(**kw):
    return dataclasses.replace(
        T.TransformerConfig.gpt2_medium(), remat=True, **kw)


def _cell_saved_bytes(tokens):
    # ISSUE 26: layers x tokens x (5 d_model + d_ff) x 2 bytes, and one
    # fp32 lse per head and token
    return 24 * tokens * ((5 * 1024 + 4096) * 2 + 16 * 4)


# ------------------------------------------------ (a) the decision function

@pytest.mark.parametrize("cfg,tokens,limit,want", [
    # the benchmark's GPT-2 cells: 8 x 512 a chip, 11% of the device
    (_gpt2m(), 8 * 512, V5E_LIMIT, ("save_matmuls", _cell_saved_bytes(4096))),
    # 24 x 512 would keep a third of the device: the parent's behaviour
    (_gpt2m(), 24 * 512, V5E_LIMIT, ("recompute_all", 0)),
    # the limit cannot be read (CPU): the parent's behaviour
    (_gpt2m(), 8 * 512, None, ("recompute_all", 0)),
    (_gpt2m(), 8 * 512, 0, ("recompute_all", 0)),
    # block kinds the reckoning does not cover
    (_gpt2m(moe_experts=4), 8 * 512, V5E_LIMIT, ("recompute_all", 0)),
    # not asked for
    (T.TransformerConfig.gpt2_medium(), 8 * 512, V5E_LIMIT, ("off", 0)),
], ids=["cell-fits", "b24-too-large", "limit-unknown", "limit-zero", "moe",
        "remat-off"])
def test_remat_plan_decides_from_shapes_and_the_memory_limit(
        cfg, tokens, limit, want):
    assert T.remat_plan(cfg, tokens, limit) == want


def test_remat_plan_turns_exactly_at_the_share_of_the_limit():
    cfg = _gpt2m()
    saved = _cell_saved_bytes(4096)
    at = int(saved / T.REMAT_SAVE_SHARE)
    assert T.remat_plan(cfg, 4096, at) == ("save_matmuls", saved)
    assert T.remat_plan(cfg, 4096, at - 4) == ("recompute_all", 0)


def test_remat_plan_counts_shared_kv_heads_once():
    mha = T.remat_plan(_gpt2m(), 4096, V5E_LIMIT)[1]
    gqa = T.remat_plan(_gpt2m(num_kv_heads=4), 4096, V5E_LIMIT)[1]
    # k and v shrink from 16 heads to 4: 2 x 12 x 64 columns fewer
    assert mha - gqa == 24 * 4096 * 2 * 12 * 64 * 2


def test_the_device_limit_is_unknown_on_cpu():
    assert T._device_bytes_limit() is None


# ------------------------------------- a small model, the limit injected

SEQ = 32  # a size no dimension of a parameter has

_KINDS = {
    "causal-mha": dict(causal=True),
    "noncausal": dict(causal=False),
    "gqa": dict(causal=True, num_kv_heads=2),
}


def _tiny(kind, remat, flash=True):
    return dataclasses.replace(
        T.TransformerConfig.tiny(), max_len=64, remat=remat,
        flash_attention=flash, **_KINDS[kind])


def _loss_fn(cfg, tokens, labels):
    model = T.Transformer(cfg)

    def loss(params):
        logits = model.apply(params, tokens, train=True)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.take_along_axis(logp, labels[..., None], -1).mean()

    return model, loss


@pytest.fixture
def batch():
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, 256, (2, SEQ)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 256, (2, SEQ)), jnp.int32)
    return tokens, labels


@pytest.fixture
def limit(monkeypatch):
    """Sets what the model reads as the device's memory limit."""
    def set_limit(value):
        monkeypatch.setattr(T, "_device_bytes_limit", lambda: value)
    return set_limit


# ------------------------------------------------- (b) the same values

@pytest.mark.parametrize("flash", [True, False], ids=["flash", "dense"])
@pytest.mark.parametrize("kind", list(_KINDS))
def test_saving_matmuls_changes_no_loss_and_no_gradient(
        kind, flash, batch, limit):
    limit(1 << 40)
    model, plain = _loss_fn(_tiny(kind, False, flash), *batch)
    _, saving = _loss_fn(_tiny(kind, True, flash), *batch)
    params = model.init(jax.random.PRNGKey(0), batch[0], train=False)
    assert T.remat_plan(_tiny(kind, True, flash), 2 * SEQ, 1 << 40)[0] == (
        "save_matmuls")
    want_loss, want = jax.jit(jax.value_and_grad(plain))(params)
    got_loss, got = jax.jit(jax.value_and_grad(saving))(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-5, atol=2e-5)
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


# -------------------------------- (c) what the rematted backward still runs

def _backward_blocks(loss, params):
    """The ``remat2`` equations of the gradient's jaxpr (one per block: the
    backward with what it recomputes), each as ``(counts, input_bytes)``:
    how many flash kernels and weight matmuls (``dot_general`` without
    batch dimensions) it runs, and the bytes of activations it is handed
    (arrays with a dimension of ``SEQ``, which no parameter has)."""
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    blocks = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "remat2":
            continue
        counts = {"pallas_call": 0, "weight_matmul": 0}
        _count(eqn.params["jaxpr"], counts)
        # activations alone: the saving backward no longer reads the qkv
        # bias, which the recomputing one does
        handed = sum(
            v.aval.size * v.aval.dtype.itemsize for v in eqn.invars
            if SEQ in v.aval.shape)
        blocks.append((counts, handed))
    return blocks


def _count(jaxpr, counts):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            counts[name] += 1
            continue  # the kernel's own body is no part of the count
        if name == "dot_general":
            (_, batch_dims) = eqn.params["dimension_numbers"]
            if not batch_dims[0]:
                counts["weight_matmul"] += 1
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                _count(inner, counts)


@pytest.mark.parametrize("kind", list(_KINDS))
@pytest.mark.parametrize("engages", [True, False], ids=["saves", "declines"])
def test_the_rematted_backward_repeats_only_what_the_plan_says(
        kind, engages, batch, limit):
    limit(1 << 40 if engages else None)
    cfg = _tiny(kind, True)
    model, loss = _loss_fn(cfg, *batch)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch[0], train=False))
    blocks = _backward_blocks(loss, params)
    assert len(blocks) == cfg.num_layers
    # the backward of a block's weight matmuls: an input and a weight
    # gradient each (MHA has four of them, GQA splits qkv into q and kv)
    matmuls = 5 if cfg.num_kv_heads else 4
    for counts, _ in blocks:
        if engages:
            # flash_dq and flash_dkv, and no forward kernel; no forward
            # matmul beside the backward's own
            assert counts == {
                "pallas_call": 2, "weight_matmul": 2 * matmuls}
        else:
            # the parent's: the forward kernel again, and every forward
            # matmul whose output something reads (the last one's feeds
            # only the residual sum)
            assert counts == {
                "pallas_call": 3, "weight_matmul": 3 * matmuls - 1}


@pytest.mark.parametrize("kind", list(_KINDS))
def test_the_plan_reckons_the_bytes_the_backward_is_handed(
        kind, batch, limit):
    cfg = _tiny(kind, True)
    model, loss = _loss_fn(cfg, *batch)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch[0], train=False))
    limit(None)
    recomputing = sum(handed for _, handed in _backward_blocks(loss, params))
    limit(1 << 40)
    saving = sum(handed for _, handed in _backward_blocks(loss, params))
    mode, saved_bytes = T.remat_plan(cfg, batch[0].size, 1 << 40)
    assert mode == "save_matmuls"
    assert saving - recomputing == saved_bytes


# ------------------------------- (d) without remat the names cost nothing

def _optimised_hlo(loss, params):
    return stripped_hlo(
        jax.jit(jax.value_and_grad(loss)).lower(params).compile().as_text())


@pytest.mark.parametrize("kind", list(_KINDS))
def test_a_step_without_remat_compiles_to_the_untagged_program(
        kind, batch, monkeypatch):
    model, loss = _loss_fn(_tiny(kind, False), *batch)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch[0], train=False))
    tagged = _optimised_hlo(loss, params)
    assert "flash_fwd" in tagged or "while" in tagged  # the kernels are in it
    # the parent's forward rules: the residuals as they come
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    _, untagged_loss = _loss_fn(_tiny(kind, False), *batch)
    assert _optimised_hlo(untagged_loss, params) == tagged


# ------------------------------------------------ (e) the span's tags

@pytest.fixture
def ring(monkeypatch):
    monkeypatch.setenv("HOROVOD_TRACE", "0")  # process spans need no switch
    tracing._reset()
    yield tracing.recorder()
    tracing._reset()


@pytest.mark.parametrize("remat,value,want", [
    (False, 1 << 40, {"remat": "off", "remat_saved_bytes": 0}),
    (True, None, {"remat": "recompute_all", "remat_saved_bytes": 0}),
    (True, 1 << 40, {"remat": "save_matmuls"}),
], ids=["off", "recompute_all", "save_matmuls"])
def test_the_trace_model_span_says_what_remat_does(
        remat, value, want, batch, limit, ring):
    limit(value)
    cfg = _tiny("causal-mha", remat, flash=False)
    model = T.Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), batch[0], train=False)
    assert not ring.spans()  # an eager call opens no span
    jax.make_jaxpr(lambda p, t: model.apply(p, t, train=True))(
        params, batch[0])
    (span,) = [r for r in ring.spans()
               if r["name"] == "hvd.trainer.trace_model"]
    if remat and value:
        want["remat_saved_bytes"] = T.remat_plan(cfg, 2 * SEQ, value)[1]
        assert want["remat_saved_bytes"] > 0
    assert span["tags"] == {"layers": cfg.num_layers, **want}
