"""Operations and bytes of ``nemotron3-nano-30b-l9e8``, from its published keys (``m``): the layers the file holds
(``layers_here``, published indices into ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``E`` a routed FFN, ``*``
attention, each ALONE in its layer), a chip's share of the experts and of the vocabulary. Required work only: nothing
recomputed, a Mamba-2 layer's two products, its convolution and its scan at the chunked form's products over the causal
half of a chunk (``ssd_scan_flops_per_token``), attention over the half of the square the causal mask keeps, the routed
experts (TWO products each: no gate) at the rows a uniform router sends to the experts held here, the shared expert on
every token, the untied head over the rows of the vocabulary held."""

MIXERS = {"M": "ssd", "E": "none", "*": "nope"}


def kinds(m: dict) -> list:
    """(mixer, ffn) of each layer held: the program's ``layer_kinds``. A layer is one part: the other is ``none``."""
    return [(MIXERS[m["hybrid_override_pattern"][int(n)]], "routed" if m["hybrid_override_pattern"][int(n)] == "E" else "none")
            for n in m["layers_here"]]


def ssd_layers(m: dict) -> int:
    return sum(mixer == "ssd" for mixer, _ in kinds(m))


def ssd_sizes(m: dict):
    """(heads, a head's channels, groups, a state's columns, the convolution's channels)."""
    H, P, G, N = m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"], m["ssm_state_size"]
    return H, P, G, N, H * P + 2 * G * N


def ssd_scan_flops_per_token(m: dict) -> float:
    """One Mamba-2 layer's scan, forward, a token, as products of chunks of ``chunk_size`` tokens: ``C B^T`` a group and its
    masked product with X a head over the causal half of the chunk's square, a head's state read out (``C S^T``) and
    written (``X^T B``). The decays' ``exp`` and the elementwise masks are not counted."""
    H, P, G, N, _ = ssd_sizes(m)
    half = (m["chunk_size"] + 1) / 2.0  # keys a query of a chunk sees, on average
    return 2.0 * (G * half * N + H * half * P + 2 * H * P * N)


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d, hd = m["hidden_size"], m["head_dim"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    H, P, G, N, channels = ssd_sizes(m)
    mamba = 2.0 * (d * (H * P + channels + H) + H * P * d) + 2.0 * m["conv_kernel"] * channels + ssd_scan_flops_per_token(m)
    attention = 2.0 * (d * heads * hd + 2 * d * kv * hd + heads * hd * d) + 4.0 * heads * hd * (seq_len + 1) / 2.0  # q, k, v, o; QK^T and PV
    rows_here = m["num_experts_per_tok"] * m["n_routed_experts"] / m["routed_over"]  # expert evaluations a token, here
    routed = 2.0 * (d * m["routed_over"] + rows_here * 2 * d * m["moe_intermediate_size"]
                    + m["n_shared_experts"] * 2 * d * m["moe_shared_expert_intermediate_size"])
    part = {"ssd": mamba, "nope": attention, "none": 0.0, "routed": routed}
    return sum(part[mixer] + part[ffn] for mixer, ffn in kinds(m)) + 2.0 * d * m["vocab_size"]


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward + backward FLOPs a trained token: three times the forward."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def expert_matmul_cost(m: dict, rows: float, backward: bool) -> dict:
    """Least work of one routed layer's TWO grouped products (an expert is ``W2 relu(x W1)^2``: no gate) over ``rows``
    (token, expert) pairs routed to the experts held here: the held experts' weights read once (written once more as
    gradients in the backward), the rows in, the hidden rows written and read, the rows out."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    flops = 2.0 * 2 * d * f * rows * (2 if backward else 1)
    weights = m["n_routed_experts"] * 2 * d * f
    acts = rows * (2 * d + 2 * f)
    return {"flops": flops, "bytes": 2.0 * (weights + acts) * (2 if backward else 1)}


def ssd_cost(m: dict, batch: int, seq_len: int, backward: bool) -> dict:
    """Least work of ONE Mamba-2 layer's scan whatever implements it: the chunked products' FLOPs (twice over in the
    backward); bytes, forward: x, B and C read and y written once in bf16, ``delta`` in float32, and every chunk's state
    once (float32: what the backward starts a chunk from); backward: x, B, C, ``delta``, y's cotangent and the states read,
    the cotangents of x, B, C and ``delta`` written."""
    H, P, G, N, _ = ssd_sizes(m)
    tokens = float(batch * seq_len)
    operands = tokens * (2 * H * P + 2 * 2 * G * N + 4 * H)  # x, B, C in bf16, delta in float32
    states = tokens / m["chunk_size"] * H * P * N * 4
    flops = ssd_scan_flops_per_token(m) * tokens * (2 if backward else 1)
    moved = 2 * operands + tokens * 2 * H * P + states if backward else operands + tokens * 2 * H * P + states
    return {"flops": float(flops), "bytes": float(moved)}


def mixed_attention_cost(m: dict, batch: int, seq_len: int, kind, backward: bool) -> dict:
    """Least work of one layer's attention call, for ``mixed_attention_roofline``, which sums it over ``kinds(m)``: nothing
    for a Mamba-2 layer or a routed FFN's; for the ``nope`` one, as every flash reader counts it, forward QK^T and PV over
    the half of the square the causal mask keeps, every query head over ``head_dim`` (32 heads of 128 on 2 key heads);
    backward dV, dP, dQ, dK (the recomputed QK^T is not required work). Bytes: q, k, v and o in bf16 and the row statistics
    (a float32 a head and query) moved once; in the backward those again with the output's cotangent, and dq, dk, dv
    written once."""
    if kind[0] != "nope":
        return {"flops": 0.0, "bytes": 0.0}
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    flops = 4.0 * heads * hd * batch * seq_len * (seq_len + 1) / 2.0 * (2 if backward else 1)
    q, kvs, stats = batch * seq_len * heads * hd, batch * seq_len * kv * hd, batch * seq_len * heads
    moved = 2.0 * (2 * q + 2 * kvs) + 4.0 * stats
    return {"flops": float(flops), "bytes": moved + (moved + 2.0 * q if backward else 0.0)}
