"""Operations and bytes that the algorithm needs, from shapes alone. They
count the mathematics, not an implementation: no recomputation under remat,
no padding, a causal mask counts half of the score matrix.

``cfg`` is a configuration file's dict (``num_layers``, ``d_model``,
``num_heads``, ``d_ff``, ``vocab_size``, ``causal``)."""


def matmul_params(cfg: dict) -> int:
    """Parameters that a token is multiplied with in a forward pass: the
    qkv, output and feed-forward matrices of every layer and the output head.
    Embedding tables are looked up, not multiplied."""
    d, f = cfg["d_model"], cfg["d_ff"]
    return cfg["num_layers"] * (4 * d * d + 2 * d * f) + d * cfg["vocab_size"]


def attention_flops(cfg: dict, q_tokens: float, kv_tokens: float,
                    share: float = 1.0) -> float:
    """Forward FLOPs of one layer's score and value products for
    ``q_tokens`` queries against ``kv_tokens`` keys (``share`` is the part
    of the score matrix that the mask keeps)."""
    return 4.0 * q_tokens * kv_tokens * cfg["d_model"] * share


def causal_share(cfg: dict, seq: int) -> float:
    """Kept part of a square ``seq`` x ``seq`` score matrix."""
    return (seq + 1) / (2.0 * seq) if cfg["causal"] else 1.0


def forward_flops_per_seq(cfg: dict, seq: int) -> float:
    """One full sequence of ``seq`` tokens through the model."""
    return (2.0 * matmul_params(cfg) * seq + cfg["num_layers"]
            * attention_flops(cfg, seq, seq, causal_share(cfg, seq)))


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward (twice the forward) per trained token."""
    return 3.0 * forward_flops_per_seq(cfg, seq) / seq


def serve_flops(cfg: dict, prompt_len: int, out_len: int) -> float:
    """Forward FLOPs to prefill ``prompt_len`` tokens and decode
    ``out_len`` (the first of them comes from the prefill): every token
    through the matrices once and against the context before it."""
    total = prompt_len + out_len - 1
    return (2.0 * matmul_params(cfg) * total + cfg["num_layers"]
            * attention_flops(cfg, total, total, (total + 1) / (2.0 * total)))


# Matrix products of S x S x head_dim that each flash kernel's own outputs
# need from its own inputs: forward QK^T and PV; dQ needs the scores again,
# dP = dO V^T and dQ = dS K; dK/dV needs the scores, dV = P^T dO, dP and
# dK = dS^T Q. Tensors (of batch*heads*seq*head_dim elements) read or
# written once: forward q,k,v -> o; dQ q,k,v,o,do -> dq; dK/dV q,k,v,o,do
# -> dk,dv.
FLASH_KERNELS = {
    "flash_fwd": {"products": 2, "tensors": 4},
    "flash_dq": {"products": 3, "tensors": 6},
    "flash_dkv": {"products": 4, "tensors": 7},
}


def flash_call_work(cfg: dict, kernel: str, batch: int, seq: int,
                    itemsize: int = 2):
    """``(flops, bytes)`` of one call of a flash kernel on ``batch``
    sequences of ``seq`` tokens."""
    k = FLASH_KERNELS[kernel]
    elements = batch * seq * cfg["d_model"]
    flops = (k["products"] * 2.0 * batch * seq * seq * cfg["d_model"]
             * causal_share(cfg, seq))
    return flops, float(k["tensors"] * elements * itemsize)


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bw: float) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peak_flops, nbytes / peak_bw)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of keys and values that one cached token holds in all layers."""
    return 2 * cfg["num_layers"] * cfg["d_model"] * itemsize


def decode_step_bytes(cfg: dict, live_tokens: int, itemsize: int = 2) -> float:
    """Bytes one decode step has to read: every multiplied weight once, in
    the compute type, and the live keys and values once."""
    return float(matmul_params(cfg) * itemsize
                 + live_tokens * kv_bytes_per_token(cfg, itemsize))
