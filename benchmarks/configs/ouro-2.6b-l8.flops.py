"""Operations and bytes of ``ouro-2.6b-l8``, from its published keys (``m``): the ``num_hidden_layers`` layers the file holds,
each applied ``total_ut_steps`` times a step on the same weights, and after EVERY pass the untied head over the whole
vocabulary and the exit gate. Required work only: nothing recomputed (a checkpointed block's second forward is the
program's choice, not the model's), a layer's seven products, attention over the half of the square the causal mask keeps.
A weight that is used four times costs four times: the count is of applications, not of parameters."""


def applications(m: dict) -> int:
    """Block applications a step: every layer held, once a pass."""
    return int(m["num_hidden_layers"]) * int(m["total_ut_steps"])


def block_flops_per_token(m: dict, seq_len: int) -> float:
    """One application of one layer, forward, a token: q, k, v, o and the SwiGLU's three products; QK^T and PV over the
    keys a query of the row sees on average."""
    d, hd, ff = m["hidden_size"], m["head_dim"], m["intermediate_size"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    products = 2.0 * (d * hd * (heads + 2 * kv) + heads * hd * d + 3 * d * ff)
    return products + 4.0 * heads * hd * (seq_len + 1) / 2.0


def head_flops_per_token(m: dict) -> float:
    """One pass's head and gate, forward, a token."""
    return 2.0 * m["hidden_size"] * (m["vocab_size"] + 1)


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    return m["total_ut_steps"] * (m["num_hidden_layers"] * block_flops_per_token(m, seq_len) + head_flops_per_token(m))


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Required forward + backward FLOPs a trained token: three times the forward. A token is counted ONCE, whatever the
    number of passes it goes through."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def head_share(m: dict, seq_len: int, layers: int = None) -> float:
    """The heads' and gates' part of a step's FLOPs at ``layers`` layers (None: those held here)."""
    n = m["num_hidden_layers"] if layers is None else layers
    return head_flops_per_token(m) / (n * block_flops_per_token(m, seq_len) + head_flops_per_token(m))


def attention_cost(m: dict, batch: int, seq_len: int, backward: bool) -> dict:
    """Least work of ONE causal attention call (one application of one layer). Forward: QK^T and PV, half the square.
    Backward: dV, dP, dQ, dK (four products; the QK^T made again is not required work). Bytes: every operand read once,
    every result written once, bf16."""
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    half_square = batch * heads * seq_len * seq_len * hd  # 2 B H S S D / 2
    q, k = batch * seq_len * heads * hd, batch * seq_len * kv * hd
    tensors = (2 * q + 2 * k) + ((3 * q + 2 * k) if backward else 0)
    return {"flops": float((4 if backward else 2) * half_square), "bytes": 2.0 * tensors}
