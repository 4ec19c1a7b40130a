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

def _attention_bytes(tokens):
    # q, k, v and the attention output at d_model each, one fp32 lse a head
    return 24 * tokens * (4 * 1024 * 2 + 16 * 4)


def _output_bytes(tokens):
    # the attention output at d_model and one fp32 lse a head: what the
    # forward kernel writes
    return 24 * tokens * (1024 * 2 + 16 * 4)


@pytest.mark.parametrize("cfg,tokens,limit,want", [
    # the benchmark's GPT-2 cells: 8 x 512 a chip
    (_gpt2m(), 8 * 512, V5E_LIMIT, ("save_matmuls", _cell_saved_bytes(4096))),
    # the largest saving measured to run (PERF.md section 6, PR 26)
    (_gpt2m(), 18 * 512, V5E_LIMIT,
     ("save_matmuls", _cell_saved_bytes(18 * 512))),
    # past it the next rung down, where the model rides the kernels
    (_gpt2m(flash_attention=True), 19 * 512, V5E_LIMIT,
     ("save_attention", _attention_bytes(19 * 512))),
    # the dense path has no names to keep by
    (_gpt2m(flash_attention=False), 19 * 512, V5E_LIMIT,
     ("recompute_all", 0)),
    # 24 x 512: the kernels' residuals are past their share too, the
    # forward kernel's outputs alone (a quarter of them) are not
    (_gpt2m(flash_attention=True), 24 * 512, V5E_LIMIT,
     ("save_attention_out", _output_bytes(24 * 512))),
    (_gpt2m(flash_attention=False), 24 * 512, V5E_LIMIT,
     ("recompute_all", 0)),
    # 46 x 512 is the largest step whose outputs fit a tenth of the room
    (_gpt2m(flash_attention=True), 46 * 512, V5E_LIMIT,
     ("save_attention_out", _output_bytes(46 * 512))),
    (_gpt2m(flash_attention=True), 47 * 512, V5E_LIMIT, ("recompute_all", 0)),
    # the limit cannot be read (CPU): the parent's behaviour
    (_gpt2m(), 8 * 512, None, ("recompute_all", 0)),
    (_gpt2m(), 8 * 512, 0, ("recompute_all", 0)),
    # the serving bank is not offered save_matmuls: its one-hot einsums'
    # outputs are tokens x experts x d_ff
    (_gpt2m(moe_experts=4, flash_attention=False), 8 * 512, V5E_LIMIT,
     ("recompute_all", 0)),
    (_gpt2m(moe_experts=4, flash_attention=True), 8 * 512, V5E_LIMIT,
     ("save_attention", _attention_bytes(4096))),
    (_gpt2m(moe_experts=4, flash_attention=True), 16 * 512, V5E_LIMIT,
     ("save_attention_out", _output_bytes(16 * 512))),
    # not asked for
    (T.TransformerConfig.gpt2_medium(), 8 * 512, V5E_LIMIT, ("off", 0)),
], ids=["cell-fits", "b18-largest-measured", "b19-keeps-attention",
        "b19-dense-path", "b24-keeps-the-outputs", "b24-dense-path",
        "b46-largest-outputs", "b47-too-large", "limit-unknown", "limit-zero",
        "moe-dense-path", "moe-keeps-attention", "moe-keeps-the-outputs",
        "remat-off"])
def test_remat_plan_decides_from_shapes_and_the_memory_limit(
        cfg, tokens, limit, want):
    assert T.remat_plan(cfg, tokens, limit) == want


@pytest.mark.parametrize("mode,saved,below", [
    ("save_matmuls", _cell_saved_bytes(4096),
     ("save_attention", _attention_bytes(4096))),
    ("save_attention", _attention_bytes(4096),
     ("save_attention_out", _output_bytes(4096))),
    ("save_attention_out", _output_bytes(4096), ("recompute_all", 0)),
])
def test_each_rung_turns_exactly_at_its_share_of_what_the_state_leaves(
        mode, saved, below):
    cfg = _gpt2m(flash_attention=True)
    state = _state_bytes(cfg)
    at = state + int(np.ceil(saved / T.REMAT_SAVE_SHARE[mode]))
    assert T.remat_plan(cfg, 4096, at) == (mode, saved)
    assert T.remat_plan(cfg, 4096, at - 8) == below
    # a device the state alone fills keeps nothing
    assert T.remat_plan(cfg, 4096, state) == ("recompute_all", 0)


def test_the_rungs_are_richest_first():
    assert list(T.REMAT_SAVE_SHARE) == [
        "save_matmuls", "save_attention", "save_attention_out"]
    # a poorer rung keeps fewer bytes a token, so its share is no larger
    shares = list(T.REMAT_SAVE_SHARE.values())
    assert shares == sorted(shares, reverse=True)


def test_remat_plan_counts_shared_kv_heads_once():
    mha = T.remat_plan(_gpt2m(), 4096, V5E_LIMIT)[1]
    gqa = T.remat_plan(_gpt2m(num_kv_heads=4), 4096, V5E_LIMIT)[1]
    # k and v shrink from 16 heads to 4: 2 x 12 x 64 columns fewer
    assert mha - gqa == 24 * 4096 * 2 * 12 * 64 * 2


@pytest.mark.parametrize("kw", [
    dict(),
    dict(causal=False),
    dict(num_kv_heads=2, rope=True),
    dict(num_kv_heads=2, head_dim=32, norm="rmsnorm", sandwich_norm=True,
         use_bias=False, ffn_gated=True, qk_norm=True, attn_output_gate=True),
    dict(qk_norm=True, ffn_gated=True),
    dict(moe_experts=4),
], ids=["gpt2", "bert", "gqa-rope", "sandwich-gated-nobias", "qknorm-gated",
        "serving-bank"])
def test_the_state_is_reckoned_from_the_parameters_the_model_creates(kw):
    cfg = dataclasses.replace(T.TransformerConfig.tiny(), **kw)
    shapes = jax.eval_shape(
        lambda: T.Transformer(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False))
    assert T._param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(shapes))


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
    # d_ff at 4 x d_model, as the real models have it: there the kernels'
    # residuals are under half of what save_matmuls keeps, and a range of
    # limits gives the middle rung
    return dataclasses.replace(
        T.TransformerConfig.tiny(), max_len=64, d_ff=256, remat=remat,
        flash_attention=flash, **_KINDS[kind])


def _state_bytes(cfg):
    return T.REMAT_STATE_BYTES_PER_PARAM * T._param_count(cfg)


def _limit_giving(mode, cfg, tokens):
    """A device memory limit at which ``remat_plan`` answers ``mode``."""
    if mode == "recompute_all":
        return None
    if mode == "save_matmuls":
        return 1 << 40
    kept = {"save_attention": _kernel_residual_bytes,
            "save_attention_out": _kernel_output_bytes}[mode](cfg, tokens)
    return _state_bytes(cfg) + int(np.ceil(kept / T.REMAT_SAVE_SHARE[mode]))


def _kernel_residual_bytes(cfg, tokens):
    """What goes by name: q and the attention output at every head, k and
    v at the K/V heads, one float32 lse a head, over all layers; of an
    expert layer the chosen experts and the dispatch's sorted order,
    int32."""
    heads, kv_heads = cfg.num_heads, cfg.num_kv_heads or cfg.num_heads
    row = (2 * heads + 2 * kv_heads) * cfg.dim_per_head()
    return tokens * (
        cfg.num_layers * (row * jnp.dtype(cfg.dtype).itemsize + 4 * heads)
        + cfg.expert_layers() * 2 * 4 * cfg.moe_top_k)


def _kernel_output_bytes(cfg, tokens):
    """What goes by name on the poorest saving rung: the attention output
    and one float32 lse at every head, over all layers; of an expert layer
    the routing's integers as above."""
    row = cfg.num_heads * cfg.dim_per_head()
    return tokens * (
        cfg.num_layers * (
            row * jnp.dtype(cfg.dtype).itemsize + 4 * cfg.num_heads)
        + cfg.expert_layers() * 2 * 4 * cfg.moe_top_k)


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

@pytest.mark.parametrize("flash,mode", [
    (True, "save_matmuls"), (False, "save_matmuls"), (True, "save_attention"),
    (True, "save_attention_out"),
], ids=["flash", "dense", "flash-save_attention", "flash-save_attention_out"])
@pytest.mark.parametrize("kind", list(_KINDS))
def test_saving_changes_no_loss_and_no_gradient(
        kind, flash, mode, batch, limit):
    value = _limit_giving(mode, _tiny(kind, True, flash), 2 * SEQ)
    limit(value)
    model, plain = _loss_fn(_tiny(kind, False, flash), *batch)
    _, saving = _loss_fn(_tiny(kind, True, flash), *batch)
    params = model.init(jax.random.PRNGKey(0), batch[0], train=False)
    assert T.remat_plan(_tiny(kind, True, flash), 2 * SEQ, value)[0] == mode
    want_loss, want = jax.jit(jax.value_and_grad(plain))(params)
    got_loss, got = jax.jit(jax.value_and_grad(saving))(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-5, atol=2e-5)
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


# -------------------------------- (c) what the rematted backward still runs

def _backward_blocks(loss, params, activations_only=True, also=()):
    """The ``remat2`` equations of the gradient's jaxpr (one per block: the
    backward with what it recomputes), each as ``(counts, input_bytes)``:
    how many Pallas kernels and weight matmuls (``dot_general`` without
    batch dimensions) it runs, and the bytes of activations it is handed
    (arrays with a dimension of ``SEQ``, which no parameter has; every
    array where not ``activations_only``). ``also`` names further
    primitives to count."""
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    blocks = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "remat2":
            continue
        counts = dict.fromkeys(("pallas_call", "weight_matmul", *also), 0)
        _count(eqn.params["jaxpr"], counts)
        # activations alone: the saving backward no longer reads the qkv
        # bias, which the recomputing one does
        handed = sum(
            v.aval.size * v.aval.dtype.itemsize for v in eqn.invars
            if SEQ in v.aval.shape or not activations_only)
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
        elif name in counts:
            counts[name] += 1
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                _count(inner, counts)


@pytest.mark.parametrize("kind", list(_KINDS))
@pytest.mark.parametrize(
    "mode", ["save_matmuls", "save_attention", "save_attention_out",
             "recompute_all"])
def test_the_rematted_backward_repeats_only_what_the_plan_says(
        kind, mode, batch, limit):
    cfg = _tiny(kind, True)
    value = _limit_giving(mode, cfg, batch[0].size)
    assert T.remat_plan(cfg, batch[0].size, value)[0] == mode
    limit(value)
    model, loss = _loss_fn(cfg, *batch)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch[0], train=False))
    blocks = _backward_blocks(loss, params)
    assert len(blocks) == cfg.num_layers
    # the backward of a block's weight matmuls: an input and a weight
    # gradient each (MHA has four of them, GQA splits qkv into q and kv)
    matmuls = 5 if cfg.num_kv_heads else 4
    projections = 2 if cfg.num_kv_heads else 1
    want = {
        # flash_dq and flash_dkv, and no forward kernel; no forward
        # matmul beside the backward's own
        "save_matmuls": {"pallas_call": 2, "weight_matmul": 2 * matmuls},
        # no forward kernel and no q/k/v projection; the output
        # projection and the first feed-forward matmul again
        "save_attention": {
            "pallas_call": 2,
            "weight_matmul": 3 * matmuls - 1 - projections},
        # no forward kernel, whose outputs are kept; every projection
        # again on the way to q, k and v, as under recompute_all
        "save_attention_out": {
            "pallas_call": 2, "weight_matmul": 3 * matmuls - 1},
        # the parent's: the forward kernel again, and every forward
        # matmul whose output something reads (the last one's feeds
        # only the residual sum)
        "recompute_all": {"pallas_call": 3, "weight_matmul": 3 * matmuls - 1},
    }[mode]
    for counts, _ in blocks:
        assert counts == want


@pytest.mark.parametrize(
    "mode", ["save_matmuls", "save_attention", "save_attention_out"])
@pytest.mark.parametrize("kind", list(_KINDS))
def test_the_plan_reckons_the_bytes_the_backward_is_handed(
        kind, mode, batch, limit):
    cfg = _tiny(kind, True)
    model, loss = _loss_fn(cfg, *batch)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch[0], train=False))
    limit(None)
    recomputing = sum(handed for _, handed in _backward_blocks(loss, params))
    value = _limit_giving(mode, cfg, batch[0].size)
    limit(value)
    saving = sum(handed for _, handed in _backward_blocks(loss, params))
    got, saved_bytes = T.remat_plan(cfg, batch[0].size, value)
    assert got == mode
    assert saving - recomputing == saved_bytes
    if mode == "save_attention":
        assert saved_bytes == _kernel_residual_bytes(cfg, batch[0].size)
    if mode == "save_attention_out":
        assert saved_bytes == _kernel_output_bytes(cfg, batch[0].size)


# ----------------------------------- (c') a small model that holds experts

def _tiny_experts(held=(0, 4)):
    """A window layer with a dense feed-forward and a full layer whose
    expert layer holds ``held`` of 8 experts, top-2, with a shared expert;
    QK-norm, a gated output, RMSNorm in a sandwich, no bias: the block of
    tests/test_afmoe.py at two layers."""
    return T.TransformerConfig(
        vocab_size=256, num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=96, max_len=64, dtype=jnp.float32,
        remat=True, flash_attention=True, rope=True, sliding_window=8,
        layer_kinds=("window/dense", "full-nope/experts"), norm="rmsnorm",
        sandwich_norm=True, use_bias=False, ffn_gated=True, qk_norm=True,
        attn_output_gate=True, moe_experts_total=8, moe_experts_held=held,
        moe_top_k=2, moe_d_ff=48, moe_shared_d_ff=48)


@pytest.mark.parametrize("held", [(0, 8), (0, 4), (2, 4)],
                         ids=["8of8", "4of8", "2of8"])
def test_an_expert_models_state_counts_the_experts_it_holds(held, batch):
    cfg = _tiny_experts(held)
    shapes = jax.eval_shape(lambda: T.Transformer(cfg).init(
        jax.random.PRNGKey(0), batch[0], train=False))
    count = T._param_count(cfg)
    assert count == sum(x.size for x in jax.tree.leaves(shapes))
    # gate, up and down of each expert held elsewhere are not here
    elsewhere = 8 - (held[1] - held[0])
    assert T._param_count(_tiny_experts((0, 8))) - count == (
        elsewhere * 3 * 64 * 48)
    # and the rung turns where this chip's state says, not the deployment's
    at = _limit_giving("save_attention", cfg, 2 * SEQ)
    kept = _kernel_residual_bytes(cfg, 2 * SEQ)
    assert at == T.REMAT_STATE_BYTES_PER_PARAM * count + 5 * kept  # 1 / 0.2
    assert T.remat_plan(cfg, 2 * SEQ, at) == ("save_attention", kept)
    # under it the forward kernel's outputs, down to a tenth of the room
    outputs = _kernel_output_bytes(cfg, 2 * SEQ)
    assert T.remat_plan(cfg, 2 * SEQ, at - 8) == (
        "save_attention_out", outputs)
    at = _limit_giving("save_attention_out", cfg, 2 * SEQ)
    assert at == T.REMAT_STATE_BYTES_PER_PARAM * count + 10 * outputs
    assert T.remat_plan(cfg, 2 * SEQ, at) == ("save_attention_out", outputs)
    assert T.remat_plan(cfg, 2 * SEQ, at - 8) == ("recompute_all", 0)


@pytest.mark.parametrize(
    "mode", ["save_matmuls", "save_attention", "save_attention_out"])
def test_an_expert_models_backward_is_handed_what_the_plan_reckons(
        mode, batch, limit):
    cfg = _tiny_experts()
    model, loss = _loss_fn(cfg, *batch)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch[0], train=False))
    # no bias in this model: both backwards read the same parameters, so
    # every handed array counts (an expert layer's rows are flattened to
    # tokens, which is d_model here)
    limit(None)
    recomputing = _backward_blocks(
        loss, params, activations_only=False, also=("top_k", "sort"))
    value = _limit_giving(mode, cfg, batch[0].size)
    limit(value)
    saving = _backward_blocks(
        loss, params, activations_only=False, also=("top_k", "sort"))
    # the gradient's jaxpr has the last block's backward first: in the
    # expert block three flash kernels, the second forward's three grouped
    # matmuls, the backward's six, and the five kernels without a body
    # that hand the dispatch's passes a buffer nobody has written (two in
    # the second forward, three in the backward); in the dense block the
    # three flash kernels
    assert [c["pallas_call"] for c, _ in recomputing] == [17, 3]
    # no flash forward a second time; the grouped matmuls run again
    assert [c["pallas_call"] for c, _ in saving] == [16, 2]
    # the routing's integer results go by name: no choice and no sort again
    assert [c["top_k"] + c["sort"] for c, _ in recomputing] == [2, 0]
    assert [c["top_k"] + c["sort"] for c, _ in saving] == [0, 0]
    got, saved_bytes = T.remat_plan(cfg, batch[0].size, value)
    assert got == mode
    # to half a percent: the gather of the gates also hands over its index
    # as jnp normalises it (tokens x top_k int32 beside the chosen experts),
    # and the selection bias is no longer read
    handed = sum(h for _, h in saving) - sum(h for _, h in recomputing)
    assert abs(handed - saved_bytes) <= 0.005 * saved_bytes


@pytest.mark.parametrize("mode", ["save_attention", "save_attention_out"])
def test_keeping_the_kernels_residuals_where_experts_are_held_changes_no_bit(
        mode, batch, limit):
    cfg = _tiny_experts()
    model, loss = _loss_fn(cfg, *batch)
    params = model.init(jax.random.PRNGKey(0), batch[0], train=False)
    limit(None)
    want_loss, want = jax.jit(jax.value_and_grad(loss))(params)
    value = _limit_giving(mode, cfg, batch[0].size)
    assert T.remat_plan(cfg, batch[0].size, value)[0] == mode
    limit(value)
    got_loss, got = jax.jit(jax.value_and_grad(loss))(params)
    assert float(got_loss) == float(want_loss)
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path))
    assert float(jnp.abs(got["params"]["block_1"]["moe"]["w_gate"]).max()) > 0


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


@pytest.mark.parametrize("remat,flash,value,want", [
    (False, False, 1 << 40, {"remat": "off", "remat_saved_bytes": 0}),
    (True, False, None, {"remat": "recompute_all", "remat_saved_bytes": 0}),
    (True, False, 1 << 40, {"remat": "save_matmuls"}),
    (True, True, _limit_giving(
        "save_attention", _tiny("causal-mha", True), 2 * SEQ),
     {"remat": "save_attention"}),
    (True, True, _limit_giving(
        "save_attention_out", _tiny("causal-mha", True), 2 * SEQ),
     {"remat": "save_attention_out"}),
], ids=["off", "recompute_all", "save_matmuls", "save_attention",
        "save_attention_out"])
def test_the_trace_model_span_says_what_remat_does(
        remat, flash, value, want, batch, limit, ring):
    limit(value)
    cfg = _tiny("causal-mha", remat, flash=flash)
    model = T.Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), batch[0], train=False)
    # an eager call opens no such span (its kernels, which JAX traces
    # even then, leave theirs: hvd.kernels.flash_call)
    assert "hvd.trainer.trace_model" not in {r["name"] for r in ring.spans()}
    jax.make_jaxpr(lambda p, t: model.apply(p, t, train=True))(
        params, batch[0])
    (span,) = [r for r in ring.spans()
               if r["name"] == "hvd.trainer.trace_model"]
    if remat and value:
        want["remat_saved_bytes"] = T.remat_plan(cfg, 2 * SEQ, value)[1]
        assert want["remat_saved_bytes"] > 0
    assert span["tags"] == {"layers": cfg.num_layers, **want}
