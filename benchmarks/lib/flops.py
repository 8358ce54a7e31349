"""Operations and bytes an algorithm needs, from shapes alone. ``m`` is a
configuration file's published keys (``hidden_size`` ...)."""


def _head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def matmul_params(m: dict) -> int:
    """Weights that a token is multiplied with: every projection of every
    layer and the output head; not the embedding gather, not the norms."""
    d, ff, hd = m["hidden_size"], m["intermediate_size"], _head_dim(m)
    h, kvh = m["num_attention_heads"], m.get("num_key_value_heads") or m["num_attention_heads"]
    layer = d * hd * (h + 2 * kvh) + h * hd * d + 3 * d * ff  # q,k,v,o + gate,up,down (SwiGLU)
    return m["num_hidden_layers"] * layer + d * m["vocab_size"]


def total_params(m: dict) -> int:
    """Matmul weights, plus the embedding table where it is not the head's own."""
    return matmul_params(m) + (0 if m.get("tie_word_embeddings") else m["hidden_size"] * m["vocab_size"])


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward+backward FLOPs per trained token: 6 per matmul weight,
    plus causal attention (QK^T and PV over the half of the square that the
    mask keeps: 2*S*H*D forward, three times that with the backward pass).
    Recomputation is not counted."""
    attn = 3 * 2 * seq_len * m["num_attention_heads"] * _head_dim(m) * m["num_hidden_layers"]
    return 6.0 * matmul_params(m) + attn


def flash_attention_cost(batch: int, seq_len: int, heads: int, kv_heads: int, head_dim: int, backward: bool) -> dict:
    """Least work of one causal attention call. Forward: QK^T and PV, half the
    square. Backward: dV, dP, dQ, dK (four matmuls; the recomputed QK^T is not
    required work). Bytes: every operand read once, every result written once, bf16."""
    half_square = batch * heads * seq_len * seq_len * head_dim  # 2*B*H*S*S*D / 2
    flops = (4 if backward else 2) * half_square
    q = batch * seq_len * heads * head_dim
    kv = batch * seq_len * kv_heads * head_dim
    tensors = (2 * q + 2 * kv) + ((2 * q + 2 * kv) if backward else 0) + (q if backward else 0)
    return {"flops": float(flops), "bytes": 2.0 * tensors}


def roofline_seconds(cost: dict, peaks: dict) -> dict:
    t_flops, t_bytes = cost["flops"] / peaks["bf16_flops"], cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "bound": "compute" if t_flops >= t_bytes else "memory"}
