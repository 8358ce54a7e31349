"""Operations and bytes of ``qwen3-next-80b-l4e32``, from its published keys
(``m``): a chip's share of the experts and of the vocabulary, as the file
states them. Required work only: nothing recomputed, the DeltaNet scan at its
chunk-free mathematical cost, causal attention over the half of the square the
mask keeps, the routed experts at the rows a uniform router sends to the
experts held here.
"""


def full_layers(m: dict) -> int:
    """Layers whose mixer is softmax attention: every ``full_attention_interval``-th."""
    return int(m["num_hidden_layers"]) // int(m["full_attention_interval"])


def gdn_layers(m: dict) -> int:
    return int(m["num_hidden_layers"]) - full_layers(m)


def gdn_scan_flops_per_token(m: dict) -> float:
    """One DeltaNet layer's recurrence, forward, a token: per value head the
    decay of the state (d_k d_v), k^T S, the rank-one update and the read-out
    (2 d_k d_v each)."""
    return 7.0 * m["linear_num_value_heads"] * m["linear_key_head_dim"] * m["linear_value_head_dim"]


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d = m["hidden_size"]
    qk, v = m["linear_num_key_heads"] * m["linear_key_head_dim"], m["linear_num_value_heads"] * m["linear_value_head_dim"]
    gdn = 2.0 * (d * (2 * qk + 2 * v) + d * 2 * m["linear_num_value_heads"] + v * d) \
        + 2.0 * m["linear_conv_kernel_dim"] * (2 * qk + v) + gdn_scan_flops_per_token(m)
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    attn = 2.0 * (d * 2 * heads * hd + 2 * d * kv * hd + heads * hd * d) \
        + 3.0 * (heads + kv) * hd * m["partial_rotary_factor"] \
        + seq_len * heads * 2 * hd  # q with its gate, k, v, o; the rotated quarter; QK^T and PV over half the square
    expert = 3 * d * m["moe_intermediate_size"]
    rows_here = m["num_experts_per_tok"] * m["num_experts"] / m["routed_over"]  # expert evaluations a token, here
    routed = 2.0 * (d * m["routed_over"] + 3 * d * m["shared_expert_intermediate_size"] + d + rows_here * expert)
    return (gdn_layers(m) * gdn + full_layers(m) * attn + m["num_hidden_layers"] * routed
            + 2.0 * d * m["vocab_size"])  # the head over the rows held; the embedding is a gather


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward + backward FLOPs a trained token: three times the forward."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def gdn_cost(m: dict, tokens: int, backward: bool) -> dict:
    """Least work of one DeltaNet layer's scan over ``tokens``: the
    recurrence's FLOPs (twice over in the backward: two products for each of
    the forward's); q and k of the key heads, v and the output of the value
    heads read or written once in bf16, the decay and beta once in float32,
    and in the backward their gradients and the output's cotangent."""
    flops = gdn_scan_flops_per_token(m) * tokens * (2 if backward else 1)
    qk, v = m["linear_num_key_heads"] * m["linear_key_head_dim"], m["linear_num_value_heads"] * m["linear_value_head_dim"]
    bytes_ = tokens * ((2 * qk + 2 * v) * 2 + 2 * m["linear_num_value_heads"] * 4)  # q, k, v, o; g, beta
    return {"flops": float(flops), "bytes": float(bytes_ * (2 if backward else 1) + (tokens * v * 2 if backward else 0))}


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
