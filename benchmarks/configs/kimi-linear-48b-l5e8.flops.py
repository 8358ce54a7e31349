"""Operations and bytes of ``kimi-linear-48b-l5e8``, from its published keys
(``m``): a chip's share of the experts and of the vocabulary, as the file
states them. Required work only: nothing recomputed, the KDA scan at its
chunk-free mathematical cost, the routed experts at the rows a uniform router
sends to the experts held here.
"""

GATE_RANK = 128  # the low-rank gates' rank: the configuration's `assumed`


def _kinds(m):
    lin = m["linear_attn_config"]
    return [("kda" if i + 1 in lin["kda_layers"] else "mla", "dense" if i < m["first_k_dense_replace"] else "routed")
            for i in range(m["num_hidden_layers"])]


def kda_scan_flops_per_token(m) -> float:
    """One KDA layer's recurrence, forward, a token: per head the decay of the
    state (d_k d_v), k^T S, the rank-one update and the read-out (2 d_k d_v each)."""
    lin = m["linear_attn_config"]
    return 7.0 * lin["num_heads"] * lin["head_dim"] ** 2


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d, lin = m["hidden_size"], m["linear_attn_config"]
    hd = lin["num_heads"] * lin["head_dim"]
    heads, qk, v = m["num_attention_heads"], m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    kda = 2.0 * (4 * d * hd + 2 * (d * GATE_RANK + GATE_RANK * hd) + d * lin["num_heads"]) \
        + 2.0 * 3 * lin["short_conv_kernel_size"] * hd + kda_scan_flops_per_token(m)
    mla = 2.0 * (d * heads * qk + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
                 + m["kv_lora_rank"] * heads * (m["qk_nope_head_dim"] + v) + heads * v * d) \
        + seq_len * heads * (qk + v)  # QK^T and PV over the half of the square the mask keeps
    expert = 3 * d * m["moe_intermediate_size"]
    rows_here = m["num_experts_per_token"] * m["num_experts"] / m["routed_over"]  # expert evaluations a token, here
    routed = 2.0 * (d * m["routed_over"] + m["num_shared_experts"] * expert + rows_here * expert)
    dense = 2.0 * 3 * d * m["intermediate_size"]
    total = 2.0 * d * m["vocab_size"]  # the head over the rows held; the embedding is a gather
    for mixer, ffn in _kinds(m):
        total += (kda if mixer == "kda" else mla) + (dense if ffn == "dense" else routed)
    return total


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward + backward FLOPs a trained token: three times the forward."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def kda_cost(m: dict, tokens: int, backward: bool) -> dict:
    """Least work of one KDA layer's scan over ``tokens``: the recurrence's
    FLOPs (twice over in the backward: two products for each of the
    forward's); q, k, v, the decay and the output read or written once
    (bf16, the decay float32), and their gradients in the backward."""
    lin = m["linear_attn_config"]
    hd = lin["num_heads"] * lin["head_dim"]
    flops = kda_scan_flops_per_token(m) * tokens * (2 if backward else 1)
    elems = tokens * hd
    bytes_ = elems * (4 * 2 + 4) + tokens * lin["num_heads"] * 4  # q, k, v, o; g; beta
    return {"flops": float(flops), "bytes": float(bytes_ * (2 if backward else 1) + (elems * 2 if backward else 0))}


def mla_attention_cost(m: dict, batch: int, seq_len: int, backward: bool) -> dict:
    """Least work of the MLA layer's causal attention call: QK^T over 192 and
    PV over 128, half the square; the backward's dV, dP, dQ, dK."""
    heads, qk, v = m["num_attention_heads"], m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    half = batch * heads * seq_len * seq_len  # 2 S^2 / 2
    flops = half * (qk + v) * (2 if backward else 1)
    rows = batch * seq_len * heads
    tensors = rows * (2 * qk + 2 * v)  # q, k, v, o
    return {"flops": float(flops), "bytes": 2.0 * (tensors * (2 if backward else 1) + (rows * v if backward else 0))}


def expert_matmul_cost(m: dict, rows: float, backward: bool) -> dict:
    """Least work of one routed layer's three grouped products over ``rows``
    (token, expert) pairs routed to the experts held here: the held experts'
    weights read once (written once more as gradients in the backward), the
    rows in and out."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    flops = 2.0 * 3 * d * f * rows * (2 if backward else 1)
    weights = m["num_experts"] * 3 * d * f
    acts = rows * (2 * d + 3 * f)
    return {"flops": flops, "bytes": 2.0 * (weights + acts) * (2 if backward else 1)}
