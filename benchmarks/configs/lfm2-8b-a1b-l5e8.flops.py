"""Operations and bytes of ``lfm2-8b-a1b-l5e8``, from its published keys (``m``): the layers the file holds
(``layers_here``, published indices into ``layer_types``, ``published_layers`` deep; a layer below ``num_dense_layers``
has the dense FFN), a chip's share of the experts and of the vocabulary. Required work only: nothing recomputed, a
convolution layer's two products (its gates and its filter are a few operations a channel and are counted by their
bytes, ``short_conv_cost``), attention over the half of the square the causal mask keeps, the routed experts at the rows
a uniform router sends to the experts held here, the tied head over the rows of the vocabulary held."""


def kind_of(m: dict, number: int) -> str:
    """The mixer of published layer ``number``: ``conv`` or ``full``, by ``layer_types``."""
    return {"conv": "conv", "full_attention": "full"}[m["layer_types"][number]]


def kinds(m: dict) -> list:
    """(mixer, ffn) of each layer held: the program's ``layer_kinds``."""
    return [(kind_of(m, int(n)), "dense" if int(n) < m["num_dense_layers"] else "routed") for n in m["layers_here"]]


def head_dim(m: dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d, hd = m["hidden_size"], head_dim(m)
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    conv = 2.0 * (d * 3 * d + d * d)  # W_in and W_out
    attention = 2.0 * (d * heads * hd + 2 * d * kv * hd + heads * hd * d) + 4.0 * heads * hd * (seq_len + 1) / 2.0  # q, k, v, o; QK^T and PV
    rows_here = m["num_experts_per_tok"] * m["num_experts"] / m["routed_over"]  # expert evaluations a token, here
    ffn = {"dense": 2.0 * 3 * d * m["intermediate_size"], "routed": 2.0 * (d * m["routed_over"] + rows_here * 3 * d * m["moe_intermediate_size"])}
    return sum({"conv": conv, "full": attention}[mixer] + ffn[kind] for mixer, kind in kinds(m)) + 2.0 * d * m["vocab_size"]


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward + backward FLOPs a trained token: three times the forward."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def short_conv_cost(m: dict, batch: int, seq_len: int, backward: bool) -> dict:
    """Least work of one convolution layer BETWEEN its two products, whatever implements it: forward, ``[B, C, u]`` read
    and ``C * c`` written once (8 bytes a channel and token in bf16) for ``B * u``, ``conv_L_cache`` taps and the second
    gate; backward, ``[B, C, u]`` and the output's cotangent read and ``[B, C, u]``'s written once (14), twice the
    operations. The filter and its gradient are ``conv_L_cache`` float32 a channel."""
    cells = float(batch * seq_len * m["hidden_size"])
    taps = m["conv_L_cache"]
    ops = 2.0 * taps + 1.0  # B * u, taps products and taps - 1 sums, C * c
    return {"flops": cells * ops * (2 if backward else 1),
            "bytes": cells * 2.0 * (7 if backward else 4) + 4.0 * taps * m["hidden_size"]}


def expert_matmul_cost(m: dict, rows: float, backward: bool) -> dict:
    """Least work of one routed layer's three grouped products over ``rows`` (token, expert) pairs routed to the
    experts held here: the held experts' weights read once (written once more as gradients in the backward), the rows
    in and out."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    flops = 2.0 * 3 * d * f * rows * (2 if backward else 1)
    weights = m["num_experts"] * 3 * d * f
    acts = rows * (2 * d + 3 * f)
    return {"flops": flops, "bytes": 2.0 * (weights + acts) * (2 if backward else 1)}


def mixed_attention_cost(m: dict, batch: int, seq_len: int, kind, backward: bool) -> dict:
    """Least work of one layer's attention call, for ``mixed_attention_roofline``, which sums it over ``kinds(m)``: nothing
    for a ``conv`` layer; for the ``full`` one, as every flash reader counts it, forward QK^T and PV over the half of the
    square the causal mask keeps, every query head over ``head_dim`` (32 heads of 64 on 8 key heads); backward dV, dP, dQ,
    dK (the recomputed QK^T is not required work). Bytes: q, k, v and o in bf16 and the row statistics (a float32 a head and
    query) moved once; in the backward those again with the output's cotangent, and dq, dk, dv written once."""
    if kind[0] != "full":
        return {"flops": 0.0, "bytes": 0.0}
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    flops = 4.0 * heads * hd * batch * seq_len * (seq_len + 1) / 2.0 * (2 if backward else 1)
    q, kvs, stats = batch * seq_len * heads * hd, batch * seq_len * kv * hd, batch * seq_len * heads
    moved = 2.0 * (2 * q + 2 * kvs) + 4.0 * stats
    return {"flops": float(flops), "bytes": moved + (moved + 2.0 * q if backward else 0.0)}
