"""Model family ``afmoe`` (Arcee Trinity) as the program builds it: the
program's one ``Transformer`` at a configuration file's sizes, through the
per-layer kinds of ``TransformerConfig``. The file's keys are the published
``config.json``'s; ``num_experts`` counts the experts this chip holds,
``experts_held`` names them, ``num_experts_total`` is the router's width."""

import numpy as np

from benchmark.models.transformer import _DTYPES, param_shapes  # noqa: F401


def layer_kinds(config: dict):
    """``"<attention>/<feed-forward>"`` per layer: sliding layers rotate
    positions and keep the window, full layers carry no rotation; the
    first ``num_dense_layers`` have a dense feed-forward."""
    attention = {"sliding_attention": "window", "full_attention": "full-nope"}
    return tuple(
        attention[kind] + (
            "/dense" if i < config["num_dense_layers"] else "/experts")
        for i, kind in enumerate(config["layer_types"]))


def build_model(config: dict, remat: bool = False):
    """The program's model at the sizes of a configuration file."""
    from horovod_tpu.models import Transformer, TransformerConfig

    if config["num_shared_experts"] not in (0, 1):
        raise ValueError("one shared expert at most")
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
        sliding_window=config["sliding_window"],
        layer_kinds=layer_kinds(config),
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        sandwich_norm=True,
        use_bias=False,
        ffn_gated=True,
        qk_norm=True,
        attn_output_gate=True,
        embed_scale=(float(config["hidden_size"]) ** 0.5
                     if config["mup_enabled"] else 1.0),
        moe_experts_total=config["num_experts_total"],
        moe_experts_held=tuple(config["experts_held"]),
        moe_top_k=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        moe_shared_d_ff=(config["moe_intermediate_size"]
                         * config["num_shared_experts"]),
        moe_score=config["score_func"],
        moe_route_norm=config["route_norm"],
        moe_route_scale=config["route_scale"],
    )
    return Transformer(cfg)


def make_batch(cfg: dict, traffic: dict, world: int, seed: int):
    """Token and label rows ``[world, batch, seq]`` from the seed, ids drawn
    from the configuration's slice of the vocabulary; ``next-token`` labels
    are the tokens shifted left (the last position's label is drawn)."""
    if traffic["labels"] != "next-token":
        raise ValueError(f"labels {traffic['labels']!r} not known")
    rng = np.random.default_rng([int(seed), 0x61666D6F])
    shape = (world, traffic["batch_per_chip"], traffic["seq"])
    tokens = rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
    labels = rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
    labels[..., :-1] = tokens[..., 1:]
    return tokens, labels
