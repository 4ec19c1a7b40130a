"""Model family ``sdar_moe`` (SDAR-30B-A3B-Chat) as the program builds it:
the program's one ``Transformer`` at a configuration file's sizes, every
layer a full GQA attention with QK-norm over an expert layer with no shared
expert and no selection bias, in block-diffusion training
(``TransformerConfig.block_diffusion``). The file's keys are the published
``config.json``'s; ``num_experts`` counts the experts this chip holds,
``experts_held`` names them, ``num_experts_total`` is the router's width;
``block_length``, ``mask_token_id`` and ``embedding_std`` are the
configuration's ``assumed``.

The step of this family takes its loss from here (``per_chip_loss``; loop
kind ``train-block-diffusion``), and its batch carries the noise: program
and reference see the same draw."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights
from benchmark.models.transformer import _DTYPES


def build_model(config: dict, remat: bool = False):
    """The program's model at the sizes of a configuration file."""
    from horovod_tpu.models import Transformer, TransformerConfig

    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise ValueError("every layer an expert layer only")
    if config["use_sliding_window"] or config["rope_scaling"] is not None:
        raise ValueError("no sliding window and no rope_scaling here")
    if config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("no attention bias and an untied head here")
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"],
        causal=True,
        dtype=_DTYPES[config["dtype"]],
        flash_block_q=config["flash_block"],
        flash_block_k=config["flash_block"],
        remat=remat,
        rope=True,
        rope_base=float(config["rope_theta"]),
        layer_kinds=("full/experts",) * config["num_hidden_layers"],
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        use_bias=False,
        ffn_gated=True,
        qk_norm=True,
        moe_experts_total=config["num_experts_total"],
        moe_experts_held=tuple(config["experts_held"]),
        moe_top_k=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        moe_score="softmax",
        moe_route_norm=config["norm_topk_prob"],
        moe_select_bias=False,
        block_diffusion=config["block_length"],
    )
    return Transformer(cfg)


def param_shapes(model, seq: int):
    """Shapes of the model's parameter tree (no value is taken from it):
    a row of ``seq`` data tokens is ``2 x seq`` positions."""
    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 2 * seq), jnp.int32),
        train=False))


def make_params(shapes, cfg: dict):
    """``build(key)``, to be jitted: ``lib/weights.py``'s fill of the tree
    (normal(0, 0.02) from the key's folds) with the token embeddings as this
    family draws them. The data tokens' rows are the same draw at
    ``embedding_std``: at 0.02 a row (norm 0.9) is swamped by the mean value
    vector that every attention layer passes on at a gain over 1, from the
    second layer on every position of a layer chooses the same eight
    experts, and this rank's load is 0 to 3 x 16,384 rows a layer by the
    draw (``PERF.md`` section 6, PR 33). The mask token's row is the mean of
    the data tokens' rows, as a token new to a trained vocabulary starts:
    half of the noised copy is that one token, and a row of its own draw
    would send a quarter of all positions to one set of eight experts."""
    fill = weights.make_params(shapes)
    mask_id = cfg["mask_token_id"]

    def build(key):
        params = fill(key)
        table = params["params"]["Embed_0"]["embedding"] * (
            cfg["embedding_std"] / weights.STD)
        table = table.at[mask_id].set(jnp.mean(table[:mask_id], axis=0))
        return {"params": {**params["params"],
                           "Embed_0": {"embedding": table}}}

    return build


def make_batch(cfg: dict, traffic: dict, world: int, seed: int):
    """``(int32 [world, rows, 2L], float32 [world, rows, L])`` from the
    seed: the noised copy of every row and then the clean row, and the
    loss's weights. The clean ids lie below the mask id; every (row, block)
    draws ``t = eps + (1 - eps) u``, ``u`` uniform on [0, 1); a token of the
    block becomes the mask id with probability ``t``, each on its own; the
    weight is ``1 / t`` where it did and 0 elsewhere."""
    noise = traffic["noise"]
    if (noise["per"], traffic["labels"]) != (
            "block", "clean token at masked positions"):
        raise ValueError("noise per block, labels the clean token, only")
    rows, seq = traffic["batch_per_chip"], traffic["seq"]
    block, mask_id = cfg["block_length"], cfg["mask_token_id"]
    rng = np.random.default_rng([int(seed), 0x73646172])
    clean = rng.integers(0, mask_id, (world, rows, seq), dtype=np.int32)
    eps = noise["eps"]
    t = eps + (1.0 - eps) * rng.random((world, rows, seq // block))
    t = np.repeat(t, block, axis=-1)
    masked = rng.random((world, rows, seq)) < t
    noised = np.where(masked, np.int32(mask_id), clean)
    weights = np.where(masked, 1.0 / t, 0.0).astype(np.float32)
    return np.concatenate([noised, clean], axis=-1), weights


def per_chip_loss(logits, tokens, weights):
    """The block-diffusion objective over this chip's rows: the weighted
    cross entropy of the clean tokens (the second half of ``tokens``) under
    the noised half's ``logits [rows, L, vocab]``, summed, over ``rows x
    L``."""
    import optax

    rows, length = weights.shape
    xent = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), tokens[:, length:])
    return jnp.sum(xent * weights) / (rows * length)
