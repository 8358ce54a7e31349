"""Operations and bytes of ``sdar-30b-a3b-l4e16``, from its published keys (``m``): a chip's share of the experts and
of the vocabulary, as the file states them. Required work only: nothing recomputed, attention over the pairs the
block-diffusion mask KEEPS alone (a program that multiplies more of the square and masks is not credited for what it
throws away), the routed experts at the rows a uniform router sends to the experts held here, the head over the noised
half of the positions.

The readers hand ``seq_len`` = the width of ``input_ids``, 2 L: the noised copy of a row and the clean one. Everything
here is counted a POSITION of those 2 L, which is what ``mfu.train`` multiplies by the rate the driver reports (two
positions a trained token).
"""


def kept_pairs(m: dict, seq_len: int) -> int:
    """(query, key) pairs the mask keeps of a row of ``seq_len`` = 2 L ids: noised -> own noised block L B, noised ->
    earlier clean blocks L (L - B) / 2, clean -> clean L (L + B) / 2: L^2 + L B."""
    L, B = seq_len // 2, int(m["block_length"])
    return L * L + L * B


def blockdiff_layers(m: dict) -> int:
    return int(m["num_hidden_layers"])


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d = m["hidden_size"]
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    proj = 2.0 * (d * heads * hd + 2 * d * kv * hd + heads * hd * d)  # q, k, v, o
    attention = 4.0 * heads * hd * kept_pairs(m, seq_len) / seq_len  # QK^T and PV over the kept pairs
    rows_here = m["num_experts_per_tok"] * m["num_experts"] / m["routed_over"]  # expert evaluations a position, here
    routed = 2.0 * (d * m["routed_over"] + rows_here * 3 * d * m["moe_intermediate_size"])
    head = 2.0 * d * m["vocab_size"] / 2  # over the noised half of the positions
    return m["num_hidden_layers"] * (proj + attention + routed) + head


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward + backward FLOPs a position: three times the forward."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def blockdiff_attention_cost(m: dict, batch: int, seq_len: int, backward: bool) -> dict:
    """Least work of one layer's attention over the KEPT pairs: QK^T and PV forward, dV, dP, dQ and dK backward (the
    recomputed QK^T is not required work); q, k, v and o read or written once in bf16, and in the backward their
    gradients and the output's cotangent. Whatever implements it (one call over the 2 L rows, two calls, the own-block
    term outside) is read by this one count."""
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    flops = (8.0 if backward else 4.0) * batch * heads * hd * kept_pairs(m, seq_len)
    q, kvs = batch * seq_len * heads * hd, batch * seq_len * kv * hd
    tensors = (2 * q + 2 * kvs) + ((2 * q + 2 * kvs + q) if backward else 0)
    return {"flops": flops, "bytes": 2.0 * tensors}


def expert_matmul_cost(m: dict, rows: float, backward: bool) -> dict:
    """Least work of one routed layer's three grouped products over ``rows`` (position, expert) pairs routed to the
    experts held here: the held experts' weights read once (written once more as gradients in the backward), the rows
    in and out."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    flops = 2.0 * 3 * d * f * rows * (2 if backward else 1)
    weights = m["num_experts"] * 3 * d * f
    acts = rows * (2 * d + 3 * f)
    return {"flops": flops, "bytes": 2.0 * (weights + acts) * (2 if backward else 1)}
