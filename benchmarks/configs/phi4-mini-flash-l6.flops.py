"""Operations and bytes of ``phi4-mini-flash-l6``, from its published keys (``m``): the layers the file holds
(``layers_here``, published indices of a stack ``published_layers`` deep), a slice of the vocabulary, the scan's sizes as
the file's ``mamba`` group states them. Required work only: nothing recomputed, the scan at its mathematical cost,
attention over the pairs its mask keeps (a window's band, or half the square)."""


def kind_of(number: int, depth: int) -> str:
    """The mixer of published layer ``number`` of a stack ``depth`` deep (the reference's rule)."""
    half = depth // 2
    if number % 2 == 0:
        return "ssm" if number <= half else "gmu"
    return "diff_window" if number < half else "diff" if number == half + 1 else "diff_cross"


def kinds(m: dict) -> list:
    return [kind_of(int(n), int(m["published_layers"])) for n in m["layers_here"]]


def ssm_layers(m: dict) -> int:
    return kinds(m).count("ssm")


def diff_layers(m: dict) -> int:
    """Layers whose mixer is differential attention: window, full and cross."""
    return sum(kind.startswith("diff") for kind in kinds(m))


def head_dim(m: dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def visible_pairs(seq_len: int, window=None) -> float:
    """(query, key) pairs a causal mask keeps, a sequence: half the square, or a band of ``window`` keys."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq_len - window) * float(window)


def ssm_scan_flops_per_token(m: dict) -> float:
    """One scan layer's recurrence, forward, a token: per channel and state column the step times ``A``, the state times
    its decay, the input's outer product and its add, the read-out's product and its add (``exp`` not counted)."""
    return 6.0 * m["mamba"]["d_inner"] * m["mamba"]["d_state"]


def _pair_flops(m: dict) -> float:
    """Both maps of one differential-attention layer, forward, a (query, key) pair: every query head's q.k over
    ``head_dim`` and p v over twice that."""
    return 2.0 * m["num_attention_heads"] * 3 * head_dim(m)


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d, inner, n, rank = m["hidden_size"], m["mamba"]["d_inner"], m["mamba"]["d_state"], m["mamba"]["dt_rank"]
    q, kv = m["num_attention_heads"] * head_dim(m), m["num_key_value_heads"] * head_dim(m)
    pairs = lambda window: _pair_flops(m) * visible_pairs(seq_len, window) / seq_len
    mixer = {
        "ssm": 2.0 * (d * 2 * inner + inner * (rank + 2 * n) + rank * inner + inner * d) + 2.0 * m["mamba"]["d_conv"] * inner
        + ssm_scan_flops_per_token(m),
        "diff_window": 2.0 * (d * (q + 2 * kv) + q * d) + pairs(m["sliding_window"]),
        "diff": 2.0 * (d * (q + 2 * kv) + q * d) + pairs(None),
        "gmu": 2.0 * (d * inner + inner * d),
        "diff_cross": 2.0 * (d * q + q * d) + pairs(None),
    }
    ffn = 2.0 * 3 * d * m["intermediate_size"]
    return sum(mixer[kind] + ffn for kind in kinds(m)) + 2.0 * d * m["vocab_size"]  # the tied head over the rows held


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward + backward FLOPs a trained token: three times the forward."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def ssm_cost(m: dict, tokens: int, backward: bool) -> dict:
    """Least work of one scan layer over ``tokens``: the recurrence's FLOPs (twice over in the backward) and its operands
    once each: u and y in bf16, the step ``delta`` in float32, B and C; in the backward those again, y's cotangent, and the
    gradients of u (bf16), delta (float32), B and C. The chip's peak for these FLOPs is the vector unit's, which
    ``lib/peaks.py`` does not list: ``lib/flops.py::roofline_seconds`` then bounds the scan by its bytes."""
    inner, n = m["mamba"]["d_inner"], m["mamba"]["d_state"]
    flops = ssm_scan_flops_per_token(m) * tokens * (2 if backward else 1)
    operands = tokens * (inner * (2 + 4) + 2 * n * 2)  # u, delta, B, C
    moved = operands + tokens * inner * 2  # and y, or its cotangent
    return {"flops": float(flops), "bytes": float(moved + operands) if backward else float(moved)}


def diff_attention_cost(m: dict, batch: int, seq_len: int, kind: str, backward: bool) -> dict:
    """Least work of one differential-attention layer's two calls (``kind``: ``diff_window`` under the window, else
    full causal). Forward: QK^T and PV over the visible pairs. Backward: dV, dP, dQ, dK (the recomputed QK^T is not
    required work). Bytes: q, k, both maps' outputs, and v once a call, in bf16; in the backward those again, the
    outputs' cotangents, and dq, dk, dv (a call)."""
    pairs = batch * visible_pairs(seq_len, m["sliding_window"] if kind == "diff_window" else None)
    flops = _pair_flops(m) * pairs * (2 if backward else 1)
    hd = head_dim(m)
    q, k = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    tensors = batch * seq_len * (q + k + 2 * k + 2 * q)  # q, k, v twice (2 hd wide, half the heads), o (2 hd wide)
    return {"flops": float(flops), "bytes": 2.0 * tensors * (2 if backward else 1) + (2.0 * batch * seq_len * 2 * q if backward else 0.0)}
