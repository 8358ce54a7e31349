"""Operations and bytes of ``keye-vl2-30b-l4e16``, from its published keys
(``m``): a chip's share of the experts and of the vocabulary, as the file
states them. Required work only: nothing recomputed, the indexer's scores over
every visible key of a query, attention over the CHOSEN pairs alone (a program
that multiplies every causal pair and masks is not credited for the pairs it
throws away), the routed experts at the rows a uniform router sends to the
experts held here. The indexer's own loss (the head-summed probabilities, a
second pass over the chosen pairs) is not counted.
"""


def visible_pairs(seq_len: int) -> int:
    """(query, key) pairs with the key at or before the query."""
    return seq_len * (seq_len + 1) // 2


def chosen_pairs(m: dict, seq_len: int) -> int:
    """sum_t min(t + 1, topk): the pairs attention runs over, a sequence and layer."""
    k = min(int(m["sa_config"]["topk"]), seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k


def sparse_layers(m: dict) -> int:
    return int(m["num_hidden_layers"])


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d, sa = m["hidden_size"], m["sa_config"]
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    proj = 2.0 * (d * heads * hd + 2 * d * kv * hd + heads * hd * d)  # q, k, v, o
    index_proj = 2.0 * (d * j * di + d * sa["indexer_num_kv_heads"] * di + d * j)  # qI, kI, w
    index_scores = 2.0 * j * di * visible_pairs(seq_len) / seq_len
    attention = 4.0 * heads * hd * chosen_pairs(m, seq_len) / seq_len  # QK^T and PV over the chosen pairs
    rows_here = m["num_experts_per_tok"] * m["num_experts"] / m["routed_over"]  # expert evaluations a token, here
    routed = 2.0 * (d * m["routed_over"] + rows_here * 3 * d * m["moe_intermediate_size"])
    return m["num_hidden_layers"] * (proj + index_proj + index_scores + attention + routed) + 2.0 * d * m["vocab_size"]


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward + backward FLOPs a trained token: three times the forward."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def sparse_attention_cost(m: dict, batch: int, seq_len: int, backward: bool) -> dict:
    """Least work of one layer's attention over the chosen pairs: QK^T and PV forward, dV, dP, dQ and dK backward (the
    recomputed QK^T is not required work); q, k, v and o read or written once in bf16, and in the backward their
    gradients and the output's cotangent."""
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    flops = (8.0 if backward else 4.0) * batch * heads * hd * chosen_pairs(m, seq_len)
    q, kvs = batch * seq_len * heads * hd, batch * seq_len * kv * hd
    tensors = (2 * q + 2 * kvs) + ((2 * q + 2 * kvs + q) if backward else 0)
    return {"flops": flops, "bytes": 2.0 * tensors}


def index_cost(m: dict, batch: int, seq_len: int, backward: bool) -> dict:
    """Least work of one layer's indexer scores and choice: a product of 64 a head and visible pair (two in the
    backward, for dqI and dkI; the recomputed scores are not required work); qI and kI in bf16 and w in float32 read
    once, the choice written as one bit a visible pair; in the backward their gradients written and the scores'
    cotangent read as one bf16 value a chosen pair."""
    sa = m["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    flops = (4.0 if backward else 2.0) * batch * j * di * visible_pairs(seq_len)
    operands = batch * seq_len * (2 * j * di + 2 * di + 4 * j)
    if backward:
        return {"flops": flops, "bytes": 2.0 * operands + 2.0 * batch * chosen_pairs(m, seq_len)}
    return {"flops": flops, "bytes": operands + batch * visible_pairs(seq_len) / 8.0}


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
