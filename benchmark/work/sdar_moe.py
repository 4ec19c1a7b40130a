"""Operations and bytes that the ``sdar_moe`` family's algorithm needs in
block-diffusion training, from a configuration file's shapes alone (its keys
are the published ``config.json``'s). They count the mathematics, not an
implementation: no recomputation under remat, no tile of the mask counted
whole where it is mostly empty. A row of ``seq`` data tokens is ``2 x seq``
positions (the noised copy and the clean row): both pass the projections,
the router and the experts; the three-part mask keeps ``seq^2 + seq x
block_length`` (query, key) pairs a head; the head reads the noised half.
The routed experts count the evaluations expected on this chip, ``top_k x
held / total`` a position. Per token means per data token, which is what
``train_tokens_per_s`` counts."""

from benchmark.lib import chip, work
from benchmark.work import afmoe

FLASH_KERNELS = work.FLASH_KERNELS


def score_pairs(seq: int, block: int) -> int:
    """(query, key) pairs one head keeps of a row of ``seq`` data tokens in
    blocks of ``block``: noised on noised ``seq x block``, noised on clean
    ``seq (seq - block) / 2``, clean on clean ``seq (seq + block) / 2``."""
    return seq * seq + seq * block


def expert_evaluations_per_position(cfg: dict) -> float:
    """Routed experts a position is expected to pass on this chip."""
    return afmoe.expert_evaluations_per_token(cfg)


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """Forward FLOPs a data token needs, by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q_width = cfg["num_attention_heads"] * hd
    kv_width = 2 * cfg["num_key_value_heads"] * hd
    layers = cfg["num_hidden_layers"]
    expert = 3 * 2.0 * d * cfg["moe_intermediate_size"]
    return {
        # q, k and v and the output projection, at both copies
        "projections": layers * 2 * 2.0 * d * (2 * q_width + kv_width),
        # QK^T and PV over the pairs the mask keeps
        "scores": layers * 4.0 * q_width
        * score_pairs(seq, cfg["block_length"]) / seq,
        # the router and the evaluations expected here, at both copies
        "experts": layers * 2 * (
            2.0 * d * cfg["num_experts_total"]
            + expert * expert_evaluations_per_position(cfg)),
        # over the noised half alone
        "head": 2.0 * d * cfg["vocab_size"],
    }


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward (twice the forward) per trained data token."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


def flash_call_work(cfg: dict, kernel: str, batch: int, seq: int,
                    itemsize: int = 2):
    """``(flops, bytes)`` of one call of a flash kernel on ``batch`` rows
    of ``seq`` data tokens: products over the kept pairs at every query
    head; q, o, dO and dQ at the query heads, K, V, dK and dV at the
    key/value heads, each read or written once over the ``2 x seq``
    positions."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    flops = (FLASH_KERNELS[kernel]["products"] * 2.0 * batch * heads
             * score_pairs(seq, cfg["block_length"]) * cfg["head_dim"])
    tensors = {"flash_fwd": 2 * heads + 2 * kv,
               "flash_dq": 4 * heads + 2 * kv,
               "flash_dkv": 3 * heads + 4 * kv}[kernel]
    return flops, float(
        tensors * batch * 2 * seq * cfg["head_dim"] * itemsize)


def flash_share(r, kernel: str):
    """Share of its roofline that a flash kernel reaches over a step's
    calls (every layer alike; a second forward under remat calls
    ``flash_fwd`` again, and both calls count on both sides): the least
    time of the calls over their device time. None where the trace has no
    such call."""
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
