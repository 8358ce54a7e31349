"""Operations and bytes of ``k-exaone-236b-l5e8``, from its published keys (``m``): the layers the file holds
(``layers_here``, published indices into ``layer_types``, ``sliding_windows`` and ``mlp_layer_types``), a host's share of
the experts and of the vocabulary. Required work only: nothing recomputed, a window layer's attention over the band of
``sliding_windows[i]`` keys a query keeps, the full layer's over the half of the square the causal mask keeps, the dense
FFN of layer 0, the routed experts (three products each) at the rows a uniform router sends to the experts held here,
the shared expert on every token, the router over all ``routed_over`` columns, the untied head over the rows held."""


def kinds(m: dict) -> list:
    """(mixer, ffn) of each layer held: the program's ``layer_kinds``."""
    return [("window" if m["layer_types"][int(n)] == "sliding_attention" else "nope", "routed" if m["mlp_layer_types"][int(n)] == "sparse" else "dense")
            for n in m["layers_here"]]


def windows(m: dict) -> list:
    """The keys a query of each held layer keeps at most: ``sliding_windows`` at the layers' indices, 0 for a full layer."""
    return [int(m["sliding_windows"][int(n)]) for n in m["layers_here"]]


def kept_pairs(seq_len: int, window: int) -> float:
    """(query, key) pairs a causal mask keeps of one row: every earlier key, or the last ``window`` of them."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) / 2.0
    return window * seq_len - window * (window - 1) / 2.0


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d, hd = m["hidden_size"], m["head_dim"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    projections = 2.0 * (d * heads * hd + 2 * d * kv * hd + heads * hd * d)
    rows_here = m["num_experts_per_tok"] * m["num_experts"] / m["routed_over"]  # expert evaluations a token, on this host
    ffn = {"dense": 2.0 * 3 * d * m["intermediate_size"],
           "routed": 2.0 * (d * m["routed_over"] + (rows_here + m["num_shared_experts"]) * 3 * d * m["moe_intermediate_size"])}
    total = 0.0
    for (mixer, kind), window in zip(kinds(m), windows(m)):
        total += projections + 4.0 * heads * hd * kept_pairs(seq_len, window) / seq_len + ffn[kind]  # QK^T and PV over the kept pairs
    return total + 2.0 * d * m["vocab_size"]


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward + backward FLOPs a trained token: three times the forward."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def exchange_cost(m: dict, rows_sent: float) -> dict:
    """Least traffic of the routed layers' exchanges for ``rows_sent`` rows that LEAVE a chip in a step's forward, all
    routed layers together: each row of ``hidden_size`` in bf16 goes to the chip that holds its expert and its result
    comes back, and in the backward the result's cotangent goes and the row's comes back: four crossings a row, each of
    which some chip has to send. A row that stays on its chip is no traffic, and an exchange made a second time under
    ``remat`` is recomputation, which no reader here counts as required work."""
    return {"bytes_sent": 4.0 * rows_sent * m["hidden_size"] * 2}


def mixed_attention_cost(m: dict, batch: int, seq_len: int, kind, backward: bool) -> dict:
    """Least work of one layer's attention call, for ``mixed_attention_roofline``, which sums it over ``kinds(m)``: forward
    QK^T and PV over the pairs the layer's mask keeps (``window``: the band of ``sliding_window`` keys; ``nope``: half the
    square), every query head over ``head_dim`` (64 heads of 128 on 8 key heads); backward dV, dP, dQ, dK (the recomputed
    QK^T is not required work). Bytes: q, k, v and o in bf16 and the row statistics (a float32 a head and query) moved
    once; in the backward those again with the output's cotangent, and dq, dk, dv written once."""
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    pairs = kept_pairs(seq_len, int(m["sliding_window"]) if kind[0] == "window" else 0)
    flops = 4.0 * heads * hd * batch * pairs * (2 if backward else 1)
    q, kvs, stats = batch * seq_len * heads * hd, batch * seq_len * kv * hd, batch * seq_len * heads
    moved = 2.0 * (2 * q + 2 * kvs) + 4.0 * stats
    return {"flops": float(flops), "bytes": moved + (moved + 2.0 * q if backward else 0.0)}
