"""Model family ``deepseek_v3`` (Kanana-2-30B-A3B) as the program builds it:
the program's one ``Transformer`` at a configuration file's sizes, every
layer's attention of the kind ``latent``. The file's keys are the published
``config.json``'s; ``n_routed_experts`` counts the experts this chip holds,
``experts_held`` names them, ``n_routed_experts_total`` is the router's
width. The published ``head_dim`` (64, the rotated part's width again) is
kept in the file and read by nothing: the widths are ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim`` and ``kv_lora_rank``."""

from benchmark.models.afmoe import make_batch  # noqa: F401 - the interface
from benchmark.models.transformer import _DTYPES, param_shapes  # noqa: F401


def layer_kinds(config: dict):
    """``"latent/<feed-forward>"`` per layer: the first
    ``first_k_dense_replace`` layers have a dense feed-forward, every
    other an expert layer (``moe_layer_freq`` 1)."""
    if config["moe_layer_freq"] != 1:
        raise ValueError("an expert layer every moe_layer_freq=1 layers only")
    return tuple(
        "latent/dense" if i < config["first_k_dense_replace"]
        else "latent/experts" for i in range(config["num_hidden_layers"]))


def build_model(config: dict, remat: bool = False):
    """The program's model at the sizes of a configuration file."""
    from horovod_tpu.models import Transformer, TransformerConfig

    if config["q_lora_rank"] is not None:
        raise ValueError("the program's latent layer has no low-rank q")
    if (config["n_group"], config["topk_group"]) != (1, 1):
        raise ValueError("the program's router has no group limit")
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is not qk_nope + qk_rope")
    if config["rope_scaling"] is not None or config["attention_bias"]:
        raise ValueError("no rope_scaling and no attention bias here")
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"],
        causal=True,
        dtype=_DTYPES[config["dtype"]],
        flash_block_q=config["flash_block"],
        flash_block_k=config["flash_block"],
        remat=remat,
        rope=True,
        rope_base=float(config["rope_theta"]),
        rope_interleave=config["rope_interleave"],
        layer_kinds=layer_kinds(config),
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        norm="rmsnorm",
        norm_eps=config["rms_norm_eps"],
        use_bias=False,
        ffn_gated=True,
        moe_experts_total=config["n_routed_experts_total"],
        moe_experts_held=tuple(config["experts_held"]),
        moe_top_k=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        # the shared experts are one gated MLP of their summed width
        moe_shared_d_ff=(config["moe_intermediate_size"]
                         * config["n_shared_experts"]),
        moe_score=config["scoring_func"],
        moe_route_norm=config["norm_topk_prob"],
        moe_route_scale=config["routed_scaling_factor"],
    )
    return Transformer(cfg)

