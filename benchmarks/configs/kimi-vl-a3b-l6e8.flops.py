"""Operations and bytes of ``kimi-vl-a3b-l6e8``, from its published keys
(``m``): a chip's share of the experts and of the vocabulary, as the file
states them. Required work only: nothing recomputed, causal attention over the
half of the square the mask keeps, the routed experts at the rows a uniform
router sends to the experts held here.
"""


def mla_layers(m: dict) -> int:
    """Every layer's mixer is latent attention."""
    return int(m["num_hidden_layers"])


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d, heads = m["hidden_size"], m["num_attention_heads"]
    rope, qk, v = m["qk_rope_head_dim"], m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    mla = 2.0 * (d * heads * qk + d * (m["kv_lora_rank"] + rope)
                 + m["kv_lora_rank"] * heads * (m["qk_nope_head_dim"] + v) + heads * v * d) \
        + 3.0 * (heads + 1) * rope \
        + seq_len * heads * (qk + v)  # every head's q_r and the one k_r turned; QK^T and PV over half the square
    expert = 3 * d * m["moe_intermediate_size"]
    rows_here = m["num_experts_per_tok"] * m["n_routed_experts"] / m["routed_over"]  # expert evaluations a token, here
    routed = 2.0 * (d * m["routed_over"] + m["n_shared_experts"] * expert + rows_here * expert)
    dense = 2.0 * 3 * d * m["intermediate_size"]
    n_dense = m["first_k_dense_replace"]
    return (mla_layers(m) * mla + n_dense * dense + (m["num_hidden_layers"] - n_dense) * routed
            + 2.0 * d * m["vocab_size"])  # the head over the rows held; the embedding is a gather


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward + backward FLOPs a trained token: three times the forward."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def mla_attention_cost(m: dict, batch: int, seq_len: int, backward: bool) -> dict:
    """Least work of one layer's causal attention call: QK^T over 192 and PV
    over 128, half the square; the backward's dV, dP, dQ, dK. q, k, v, o read
    or written once in bf16, and in the backward their gradients and do."""
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
    weights = m["n_routed_experts"] * 3 * d * f
    acts = rows * (2 * d + 3 * f)
    return {"flops": flops, "bytes": 2.0 * (weights + acts) * (2 if backward else 1)}
