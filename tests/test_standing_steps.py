"""The new fields of ``TransformerConfig`` (per-layer kinds, norm, bias,
gates, experts) default to the block that the benchmark's standing cells
build: a GPT-2-shaped and a BERT-shaped model at test size compile, forward
and backward, to the optimised HLO they compiled to at the commit before
the fields came (PR 26, 2b6f930), with ``metadata={...}`` stripped. The
digests were taken there with this file's own function; a PR that means to
change what these models compile to takes them again and says so."""

import hashlib

import jax
import jax.numpy as jnp
import optax
import pytest
from conftest import stripped_hlo

from horovod_tpu.models import Transformer, TransformerConfig

AT_THE_PARENT = {
    "causal-remat-flash": "5e4e0e61352d7059",
    "bidirectional-flash": "894956b4f1f9665b",
    "causal-gqa-rope-window-dense": "4396aa3e3c64eb98",
}


def _config(kind):
    base = dict(vocab_size=256, num_layers=2, d_model=64, num_heads=4,
                d_ff=128, max_len=64, dtype=jnp.float32)
    return {
        "causal-remat-flash": TransformerConfig(
            **base, causal=True, remat=True, flash_attention=True),
        "bidirectional-flash": TransformerConfig(
            **base, causal=False, flash_attention=True),
        "causal-gqa-rope-window-dense": TransformerConfig(
            **base, causal=True, num_kv_heads=2, rope=True,
            sliding_window=8, flash_attention=False),
    }[kind]


def step_digest(kind) -> str:
    model = Transformer(_config(kind))
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, train=False))

    def loss(p, tokens):
        logits = model.apply(p, tokens, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), tokens).mean()

    text = jax.jit(jax.value_and_grad(loss)).lower(
        params, tokens).compile().as_text()
    return hashlib.sha256(stripped_hlo(text).encode()).hexdigest()[:16]


@pytest.mark.parametrize("kind", list(AT_THE_PARENT))
def test_the_standing_blocks_compile_to_what_they_did(kind):
    assert step_digest(kind) == AT_THE_PARENT[kind]
