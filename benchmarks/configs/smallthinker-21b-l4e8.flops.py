"""Operations and bytes of ``smallthinker-21b-l4e8``, from its published keys (``m``): the layers the file holds
(``layers_here``, published indices into the two layouts, ``published_layers`` deep), a chip's share of the experts and
of the vocabulary. Required work only: nothing recomputed, attention over the pairs each layer's mask KEEPS (half the
square under the full layer's causal mask, a band of ``sliding_window_size`` keys under a window layer's: a walk that
visits tiles outside the band is not credited for them), the routed experts at the rows a uniform router sends to the
experts held here, the head over the rows of the vocabulary held."""


def kind_of(m: dict, number: int) -> str:
    """The mixer of published layer ``number``: ``window`` where ``sliding_window_layout`` says so, else ``nope`` (every
    earlier key; the published ``rope_layout`` rotates exactly the window layers, which moves no FLOP counted here)."""
    return "window" if m["sliding_window_layout"][number] else "nope"


def kinds(m: dict) -> list:
    return [kind_of(m, int(n)) for n in m["layers_here"]]


def visible_pairs(seq_len: int, window=None) -> float:
    """(query, key) pairs a causal mask keeps, a sequence: half the square, or a band of ``window`` keys."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq_len - window) * float(window)


def _pairs(m: dict, seq_len: int, kind: str) -> float:
    return visible_pairs(seq_len, m["sliding_window_size"] if kind == "window" else None)


def _pair_flops(m: dict) -> float:
    """One layer's attention, forward, a (query, key) pair: every query head's q.k and p v over ``head_dim``."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"]


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d = m["hidden_size"]
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    proj = 2.0 * (d * heads * hd + 2 * d * kv * hd + heads * hd * d)  # q, k, v, o: the heads' width is not the model's
    rows_here = m["moe_num_active_primary_experts"] * m["moe_num_primary_experts"] / m["routed_over"]  # expert evaluations a token, here
    routed = 2.0 * (d * m["routed_over"] + rows_here * 3 * d * m["moe_ffn_hidden_size"])
    attention = sum(_pair_flops(m) * _pairs(m, seq_len, kind) / seq_len for kind in kinds(m))
    return len(kinds(m)) * (proj + routed) + attention + 2.0 * d * m["vocab_size"]  # the untied head over the rows held


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward + backward FLOPs a trained token: three times the forward."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def mixed_attention_cost(m: dict, batch: int, seq_len: int, kind: str, backward: bool) -> dict:
    """Least work of one layer's attention call (``kind``: ``window`` under the band, else half the square). Forward:
    QK^T and PV over the pairs the mask keeps. Backward: dV, dP, dQ, dK (the recomputed QK^T is not required work).
    Bytes: q, k, v and o in bf16 and the row statistics (a float32 a head and query) moved once; in the backward those
    again with the output's cotangent, and dq, dk, dv written once. Whatever implements the call (one fused backward,
    two kernels, a head at a time) is read by this one count."""
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    flops = _pair_flops(m) * batch * _pairs(m, seq_len, kind) * (2 if backward else 1)
    q, kvs, stats = batch * seq_len * heads * hd, batch * seq_len * kv * hd, batch * seq_len * heads
    moved = 2.0 * (2 * q + 2 * kvs) + 4.0 * stats
    return {"flops": float(flops), "bytes": moved + (moved + 2.0 * q if backward else 0.0)}


def expert_matmul_cost(m: dict, rows: float, backward: bool) -> dict:
    """Least work of one routed layer's three grouped products over ``rows`` (token, expert) pairs routed to the
    experts held here: the held experts' weights read once (written once more as gradients in the backward), the rows
    in and out."""
    d, f = m["hidden_size"], m["moe_ffn_hidden_size"]
    flops = 2.0 * 3 * d * f * rows * (2 if backward else 1)
    weights = m["moe_num_primary_experts"] * 3 * d * f
    acts = rows * (2 * d + 3 * f)
    return {"flops": flops, "bytes": 2.0 * (weights + acts) * (2 if backward else 1)}
