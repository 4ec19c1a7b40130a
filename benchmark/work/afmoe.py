"""Operations and bytes that the ``afmoe`` family's algorithm needs, from a
configuration file's shapes alone (its keys are the published
``config.json``'s). They count the mathematics, not an implementation: no
recomputation under remat, no padding of the dispatch buffer, a causal mask
counts the pairs it keeps and a window the pairs of its band; the routed
experts count the evaluations expected on this chip, ``top_k x held /
total`` a token, and the head its slice of the vocabulary."""

from benchmark.lib import chip, work

FLASH_KERNELS = work.FLASH_KERNELS


def score_pairs(cfg: dict, seq: int, layer_type: str) -> float:
    """(query, key) pairs one head keeps in a sequence of ``seq``: a row
    sees itself and what is before it, on a sliding layer the last
    ``sliding_window`` of those."""
    w = cfg["sliding_window"]
    if layer_type == "full_attention" or w >= seq:
        return seq * (seq + 1) / 2.0
    return seq * w - w * (w - 1) / 2.0


def expert_evaluations_per_token(cfg: dict) -> float:
    """Routed experts a token is expected to pass on this chip."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / float(
        cfg["num_experts_total"])


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """Forward FLOPs a token needs, by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q_width = cfg["num_attention_heads"] * hd
    kv_width = 2 * cfg["num_key_value_heads"] * hd
    layers = cfg["layer_types"]
    dense = cfg["num_dense_layers"]
    expert = 3 * 2.0 * d * cfg["moe_intermediate_size"]
    return {
        # q, k and v, the output gate, the output projection
        "projections": len(layers) * 2.0 * d * (3 * q_width + kv_width),
        # QK^T and PV over the pairs each layer's mask keeps
        "scores": sum(4.0 * q_width * score_pairs(cfg, seq, kind) / seq
                      for kind in layers),
        "dense_mlp": dense * 3 * 2.0 * d * cfg["intermediate_size"],
        "experts": (len(layers) - dense) * (
            2.0 * d * cfg["num_experts_total"]  # the router
            + expert * (cfg["num_shared_experts"]
                        + expert_evaluations_per_token(cfg))),
        "head": 2.0 * d * cfg["vocab_size"],
    }


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward (twice the forward) per trained token."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


def flash_call_work(cfg: dict, kernel: str, batch: int, seq: int,
                    layer_type: str, itemsize: int = 2):
    """``(flops, bytes)`` of one call of a flash kernel on ``batch``
    sequences of a layer of ``layer_type``: products over the pairs its
    mask keeps at every query head; q, o, dO and dQ at the query heads, K,
    V, dK and dV at the key/value heads, each read or written once."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    flops = (FLASH_KERNELS[kernel]["products"] * 2.0 * batch * heads
             * score_pairs(cfg, seq, layer_type) * cfg["head_dim"])
    tensors = {"flash_fwd": 2 * heads + 2 * kv,
               "flash_dq": 4 * heads + 2 * kv,
               "flash_dkv": 3 * heads + 4 * kv}[kernel]
    return flops, float(tensors * batch * seq * cfg["head_dim"] * itemsize)


def flash_share(r, kernel: str):
    """Share of its roofline that a flash kernel reaches over a step's
    calls: the least time summed over the layers, each with its own mask,
    times the calls a layer makes, over the calls' device time. None where
    the trace has no such call."""
    trace = r.get("trace")
    if trace is None or not trace.devices:
        return None
    calls = trace.op_calls(kernel)
    cfg, t = r["cfg"], r["traffic"]
    if not calls or len(calls) % len(cfg["layer_types"]):
        return None
    peak, bw = chip.peaks(r["device_kind"])
    least = sum(
        work.roofline_seconds(*flash_call_work(
            cfg, kernel, t["batch_per_chip"], t["seq"], kind), peak, bw)
        for kind in cfg["layer_types"])
    return 100.0 * least * (len(calls) // len(cfg["layer_types"])) / sum(calls)


def expert_matmul_work(cfg: dict, tokens: int, itemsize: int = 2):
    """``(flops, bytes)`` of one of an expert layer's three grouped
    matmuls (``rows x hidden x moe_intermediate``, either way round) on the
    rows expected here: both matrices of activations once, the held
    experts' weights once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = tokens * expert_evaluations_per_token(cfg)
    return (2.0 * rows * d * f,
            float((rows * (d + f) + cfg["num_experts"] * d * f) * itemsize))


def experts_least_seconds_per_step(cfg: dict, tokens: int, peak: float,
                                   bw: float) -> float:
    """The least time the chip could take for a step's routed-expert
    matmuls: three forward and six backward in each expert layer (every
    one of them is ``rows x hidden x moe_intermediate``)."""
    layers = len(cfg["layer_types"]) - cfg["num_dense_layers"]
    return 9 * layers * work.roofline_seconds(
        *expert_matmul_work(cfg, tokens), peak, bw)


def experts_share(r, ms_per_step):
    """``moe.experts_roofline`` from the device time a step spends under
    the scope ``moe_experts`` (None: not read)."""
    if not ms_per_step or r["device_kind"] not in chip.CHIP_PEAKS:
        return None
    t = r["traffic"]
    least = experts_least_seconds_per_step(
        r["cfg"], t["batch_per_chip"] * t["seq"], *chip.peaks(r["device_kind"]))
    return 100.0 * least * 1e3 / ms_per_step


