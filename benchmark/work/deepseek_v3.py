"""Operations and bytes that the ``deepseek_v3`` family's algorithm needs,
from a configuration file's shapes alone (its keys are the published
``config.json``'s). They count the mathematics, not an implementation: no
recomputation under remat, no padding of a 192-wide key to 256 lanes or of
the value to the key's width, no absorbed form; the causal mask counts the
pairs it keeps; the routed experts count the evaluations expected on this
chip, ``top_k x held / total`` a token, and the head its slice of the
vocabulary."""

from benchmark.lib import chip, work
from benchmark.work import afmoe

FLASH_KERNELS = work.FLASH_KERNELS


def score_pairs(seq: int) -> float:
    """(query, key) pairs one head keeps in a causal sequence of ``seq``."""
    return seq * (seq + 1) / 2.0


def head_widths(cfg: dict):
    """``(key width, value width)`` of a head: q and k are as wide as the
    key, v, the output and ``dO`` as the value."""
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def _expert_keys(cfg: dict) -> dict:
    """The configuration with its expert layers' sizes under the ``afmoe``
    family's keys too: the same ``ExpertFFN`` does the work, so that
    family's counts are these (``benchmark/work/afmoe.py``)."""
    return dict(
        cfg, num_experts=cfg["n_routed_experts"],
        num_experts_total=cfg["n_routed_experts_total"],
        layer_types=[None] * cfg["num_hidden_layers"],
        num_dense_layers=cfg["first_k_dense_replace"])


def expert_evaluations_per_token(cfg: dict) -> float:
    """Routed experts a token is expected to pass on this chip."""
    return afmoe.expert_evaluations_per_token(_expert_keys(cfg))


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """Forward FLOPs a token needs, by part."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    k_width, v_width = head_widths(cfg)
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    expert = 3 * 2.0 * d * cfg["moe_intermediate_size"]
    return {
        # q and the output projection
        "projections": layers * 2.0 * d * heads * (k_width + v_width),
        # what latent attention adds: the joint down-projection and the
        # up-projection to every head's key and value
        "latent_projections": layers * 2.0 * (
            d * (rank + rope)
            + rank * heads * (cfg["qk_nope_head_dim"] + v_width)),
        # QK^T over the key's width and PV over the value's
        "scores": layers * 2.0 * heads * (k_width + v_width)
        * score_pairs(seq) / seq,
        "dense_mlp": dense * 3 * 2.0 * d * cfg["intermediate_size"],
        "experts": (layers - dense) * (
            2.0 * d * cfg["n_routed_experts_total"]  # the router
            + expert * (cfg["n_shared_experts"]
                        + expert_evaluations_per_token(cfg))),
        "head": 2.0 * d * cfg["vocab_size"],
    }


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward (twice the forward) per trained token."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


# Per kernel: the S x S products it needs at the key's width (the scores,
# dQ = dS K, dK = dS^T Q) and at the value's (PV, dP = dO V^T, dV = P^T dO),
# and the tensors it reads or writes once at either width (q, k, dq, dk;
# v, o, do, dv).
_PRODUCTS = {"flash_fwd": (1, 1), "flash_dq": (2, 1), "flash_dkv": (2, 2)}
_TENSORS = {"flash_fwd": (2, 2), "flash_dq": (3, 3), "flash_dkv": (3, 4)}


def flash_call_work(cfg: dict, kernel: str, batch: int, seq: int,
                    itemsize: int = 2):
    """``(flops, bytes)`` of one call of a flash kernel on ``batch``
    causal sequences: every head has its own key and value."""
    k_width, v_width = head_widths(cfg)
    rows = batch * cfg["num_attention_heads"]
    at_k, at_v = _PRODUCTS[kernel]
    flops = 2.0 * rows * score_pairs(seq) * (at_k * k_width + at_v * v_width)
    at_k, at_v = _TENSORS[kernel]
    return flops, float(
        rows * seq * (at_k * k_width + at_v * v_width) * itemsize)


def flash_share(r, kernel: str):
    """Share of its roofline that a flash kernel reaches over a step's
    calls (every layer alike; remat's second forward calls ``flash_fwd``
    again, and both calls count on both sides): the least time of the
    calls over their device time. None where the trace has no such call."""
    trace = r.get("trace")
    if trace is None or not trace.devices:
        return None
    calls = trace.op_calls(kernel)
    if not calls:
        return None
    t = r["traffic"]
    least = work.roofline_seconds(*flash_call_work(
        r["cfg"], kernel, t["batch_per_chip"], t["seq"]),
        *chip.peaks(r["device_kind"]))
    return 100.0 * least * len(calls) / sum(calls)


def expert_matmul_work(cfg: dict, tokens: int, itemsize: int = 2):
    """``(flops, bytes)`` of one of an expert layer's three grouped
    matmuls on the rows expected here."""
    return afmoe.expert_matmul_work(_expert_keys(cfg), tokens, itemsize)


def experts_least_seconds_per_step(cfg: dict, tokens: int, peak: float,
                                   bw: float) -> float:
    """The least time the chip could take for a step's routed-expert
    matmuls: three forward and six backward in each expert layer."""
    return afmoe.experts_least_seconds_per_step(
        _expert_keys(cfg), tokens, peak, bw)


def experts_share(r, ms_per_step):
    """``moe.experts_roofline`` from the device time a step spends under
    the scope ``moe_experts`` (None: not read)."""
    return afmoe.experts_share(dict(r, cfg=_expert_keys(r["cfg"])),
                               ms_per_step)
