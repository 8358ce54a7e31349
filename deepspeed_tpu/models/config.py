"""Every field of a transformer's configuration and what follows from the fields alone. This module imports nothing of
the package: the layers that read a configuration sit above it, and what it says of its layers' KINDS needs the table
above them (``transformer.py``, whose ``TransformerConfig`` is this class and those readings)."""

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax.numpy as jnp


GATED = {"swiglu": "silu", "geglu": "gelu", "reglu": "relu"}  # ``activation``: a gated FFN's, and its gate's name in ``jax.nn``


@dataclass(frozen=True)
class TransformerFields:
    vocab_size: int = 32000
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: Optional[int] = None  # < n_heads => GQA (llama-70b style)
    head_dims: Optional[int] = None  # explicit head dim (gemma: != d_model/n_heads)
    d_model: int = 128
    d_ff: Optional[int] = None  # default: 4*d_model (gelu) or 8/3*d_model (swiglu)
    max_seq_len: int = 2048
    norm: str = "layernorm"  # layernorm | rmsnorm | layernorm_np (olmo: no affine params)
    # gelu (tanh approx) | gelu_exact (erf) | swiglu | geglu | reglu (relu(gate) * up: the gated FFNs', dense and routed) | relu |
    # relu2 (relu(up)^2: an FFN of TWO matrices, dense, routed and shared alike: the routed layer then has no ``experts_wg``)
    activation: str = "gelu"
    pos_emb: str = "learned"  # learned | rope | alibi | none
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0  # fraction of head_dim rotated (gpt-neox/phi partial rotary)
    rotary_dims: Optional[int] = None  # exact rotated dim count (gpt-j rotary_dim); overrides rotary_pct
    rope_style: str = "neox"  # neox (rotate-half) | gptj (interleaved pairs)
    # HF rope_scaling variants (transformers modeling_rope_utils.py):
    # linear (position interpolation), dynamic (NTK-by-parts at max_seq_len),
    # llama3 (frequency-banded interpolation — llama-3.1+), yarn
    rope_scaling: Optional[str] = None  # linear | dynamic | llama3 | yarn
    rope_factor: float = 1.0
    rope_orig_max_seq: Optional[int] = None  # original_max_position_embeddings
    rope_low_freq_factor: float = 1.0   # llama3
    rope_high_freq_factor: float = 4.0  # llama3
    rope_beta_fast: float = 32.0        # yarn extrapolation boundary
    rope_beta_slow: float = 1.0         # yarn interpolation boundary
    rope_attn_factor: Optional[float] = None  # yarn cos/sin scale; None = 0.1*ln(factor)+1
    clip_qkv: Optional[float] = None  # olmo: clamp q/k/v activations to [-c, c]
    # block wiring: sequential (gpt2/llama), parallel (gpt-neox: two norms,
    # x + attn(ln1 x) + mlp(ln2 x)), parallel_shared (falcon-7b/phi/gpt-j:
    # one norm feeds both attn and mlp)
    block_type: str = "sequential"
    dense_bias: Optional[bool] = None  # default: norm == "layernorm" (falcon: LN but bias-free)
    qkv_bias: Optional[bool] = None  # override for q/k/v projections only (qwen2)
    qk_norm: bool = False  # qwen3: per-head RMSNorm on q/k before rope (zero-centered weights under ``rms_offset``)
    # qwen3-next: q_proj is twice as wide, a head's columns its query and then a gate, and the attention's output is
    # multiplied by sigmoid(gate) ahead of o_proj: out = (softmax(q k^T / sqrt(D)) v * sigmoid(gate)) W_o
    attn_output_gate: bool = False
    attn_out_bias: Optional[bool] = None  # override for o_proj only (gpt-j: biased MLP, bias-free attn)
    lm_head_bias: bool = False  # phi / gpt-j carry a bias on the untied head
    embedding_norm: bool = False  # bloom: layernorm directly after the token embedding
    embed_scale: bool = False  # gemma: scale embeddings by sqrt(d_model)
    rms_offset: bool = False  # gemma: rmsnorm weights stored zero-centered, applied as (1 + w)
    sliding_window: Optional[int] = None  # mistral: query i attends keys in (i - w, i]
    # per-layer window selection: tuple of layer indices that apply
    # ``sliding_window``; None = every layer (gpt-neo alternating
    # global/local layers, qwen2 ``max_window_layers`` suffix windows)
    window_layers: Optional[Tuple[int, ...]] = None
    attn_scale: Optional[float] = None  # softmax scale override; None = 1/sqrt(head_dim) (gpt-neo: 1.0)
    # encoder family (BERT): bidirectional attention, post-LN blocks,
    # token-type embeddings, MLM transform head (ref module_inject/containers/bert.py)
    causal: bool = True  # False: bidirectional encoder
    # pre (gpt/llama) | post (BERT: norm after residual add) | sandwich (a norm before AND after each sublayer, the second
    # on the sublayer's output inside the residual branch: h + N2(Attn(N1(h))), then h + N4(FFN(N3(h))); four weights a layer)
    # | output (a norm on each sublayer's OUTPUT alone, inside the residual branch, and none on its input: h + N1(Attn(h)),
    # then h + N2(FFN(h)); two weights a layer, a last norm before the head as under ``pre``)
    norm_scheme: str = "pre"
    type_vocab_size: int = 0  # >0: token_type embeddings added to the input
    mlm_head: bool = False  # BERT cls.predictions transform (dense+act+LN) before the tied decoder
    tie_embeddings: bool = True
    dtype: Any = jnp.float32  # activation/compute dtype
    norm_eps: float = 1e-5
    dropout: float = 0.0
    remat: bool = False  # jax.checkpoint each block (activation checkpointing)
    scan_layers: bool = False  # lax.scan over layers (fast compile, pipeline-friendly)
    # MoE (reference deepspeed/moe): >0 experts turns MLP slots into MoE layers
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_layer_freq: int = 2  # every Nth block is MoE
    moe_aux_loss_coef: float = 0.01
    moe_min_capacity: int = 4
    # THE per-layer specification: one (mixer, ffn) pair a layer, each a name of the table of kinds (``transformer.py::
    # MIXERS``, ``FFNS``: a kind's module says what it computes). None: the pairs that ``window_layers`` and
    # ``moe_layer_freq`` describe (``kinds``)
    layer_kinds: Optional[Tuple[Tuple[str, str], ...]] = None
    kda_heads: int = 0  # kda: heads of ``kda_head_dim`` keys and values, a depthwise causal convolution of
    kda_head_dim: int = 128  # ``kda_conv_size`` on q, k and v, gates through ``kda_gate_rank``
    kda_conv_size: int = 4
    kda_gate_rank: int = 128
    # gdn: ``gdn_key_heads`` heads of q and k, each serving ``gdn_value_heads / gdn_key_heads`` value heads, all of
    # ``gdn_head_dim``; a depthwise causal convolution of ``gdn_conv_size`` on q, k and v; per value head and token
    # beta = sigmoid(x w_b), g = -exp(A_log) softplus(x w_a + dt_bias), S_t = (I - beta k k^T) exp(g) S_{t-1} + beta k v^T
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_head_dim: int = 128
    gdn_conv_size: int = 4
    # sparse: an indexer of ``index_heads`` heads of ``index_head_dim`` on one key head scores every visible key; a
    # query attends the ``index_topk`` best (every key, through the dense program, where the sequence is no longer)
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    # sparse, and softmax attention over one head size (full, window, nope): the attention's output projection starts at
    # this times its usual standard deviation. At a random start attention averages its keys, so every position gets
    # nearly the same vector and the stream collapses onto it layer by layer; a random router turns that into a load a
    # seed decides. A small start leaves the stream the tokens' own
    sparse_out_init_scale: float = 1.0
    mla_kv_rank: int = 512  # mla: ``n_heads`` heads; q and k of nope + rope dims (the rope dims rotated under
    # ``pos_emb="rope"`` by ``rope_theta`` / ``rope_style``, else nothing is), v of its own
    mla_qk_nope_dim: int = 128
    mla_qk_rope_dim: int = 64
    mla_v_dim: int = 128
    # routed: ``moe_num_experts`` router outputs, ``moe_top_k`` a token, experts ``moe_d_ff`` wide
    moe_d_ff: Optional[int] = None  # None: ``ffn_dim``
    moe_shared_d_ff: int = 0  # width of the shared expert every token also takes (n shared SwiGLUs of f added are one
    # of n * f, their columns side by side: give the sum); 0: none
    moe_route_scale: float = 1.0  # the renormalised weights of a token's experts are multiplied by this
    moe_held: Optional[Tuple[int, int]] = None  # (first, count): the experts THIS program holds; None: all
    # routed: a token's scores over all experts. "sigmoid": s = sigmoid(x W_r), the top k of s + selection bias;
    # "softmax": p = softmax(x W_r), the top k of p; either way the chosen ones rescaled to sum to one, times
    # ``moe_route_scale``
    moe_scoring: str = "sigmoid"
    moe_shared_gate: bool = False  # routed: the shared expert's output is multiplied by sigmoid(x w_s), w_s (d_model, 1)
    # ssm (a Mamba-1 layer) and gmu (a gate on a scan's output): ``ssm_inner`` channels, each with a state of ``ssm_state``
    # columns; a depthwise causal convolution of ``ssm_conv`` with a bias; the step through a product of ``ssm_dt_rank``
    ssm_inner: int = 0
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    # where the stack is a cut of a deeper model: the published index of each layer, which a kind that takes ``layer`` reads
    # (differential attention's lambda starts from it); None: 0 .. n_layers - 1
    layer_numbers: Optional[Tuple[int, ...]] = None
    # blockdiff (block-diffusion training): a row is ``[noised ; clean]``, each half a whole number of blocks of
    # ``block_length`` positions; ``mask_token_id`` is the id a noised position carries (an ordinary row of the embedding).
    # The mixer's positions and mask, and the loss's targets and weights, follow from these and the row's ids alone
    block_length: int = 0
    mask_token_id: int = 0
    # blockdiff: the per-head q/k norms' weights start at this (1: flax's ones). At one, attention over thousands of keys
    # is an average and a third of a row's positions carry the SAME mask token, so they reach a random router as one
    # vector and its load is a seed's draw; at 3 the scores' deviation is 9, a query picks a few keys as a trained model's
    # does, every position receives a vector of its own and the router's load is even
    blockdiff_qk_init_scale: float = 1.0
    # conv (a gated short convolution): [B, C, u] = x W_in, three chunks of ``d_model``; out = (C * conv(B * u)) W_out, the
    # convolution depthwise and causal over ``conv_kernel`` tokens, one filter a channel, no bias and no activation
    conv_kernel: int = 3
    # routed, sigmoid scoring: what is added to the sum a token's chosen scores are divided by (the families differ: 1e-20, 1e-6)
    moe_renorm_eps: float = 1e-20
    # ssd (a Mamba-2 layer): ``ssd_heads`` heads of ``ssd_head_dim`` channels, each with a state of ``ssd_state`` columns and ONE
    # decay a head and token; B and C are shared by the ``ssd_heads / ssd_groups`` heads of a group, and the gated norm ahead of
    # the output product runs over a group's channels; a depthwise causal convolution of ``ssd_conv`` with a bias on x, B and C
    ssd_heads: int = 0
    ssd_head_dim: int = 64
    ssd_state: int = 128
    ssd_groups: int = 1
    ssd_conv: int = 4
    # a looped (weight-shared) stack: the ``n_layers`` blocks are run ``loop_steps`` times over the SAME parameters, the final
    # norm at the end of every pass (pass t + 1 starts from the normed state), and ``return_hidden`` gives the ``loop_steps``
    # normed states stacked. The passes are a ``lax.scan`` whose body is the stack: its equations are in the program once
    loop_steps: int = 1
    # with a loop: after every pass the one head gives that pass's logits and a gate lambda_t = sigmoid(x(t) . w_g + b_g) says
    # how much of what is left exits there (``exit_gate/{kernel,bias}``, one for all passes; the last pass takes what is left).
    # The loss is the mean over targets of sum_t p_t nll_t - ``exit_entropy_coef`` H(p): the weights p_t carry a gradient
    exit_gate: bool = False
    exit_entropy_coef: float = 0.0

    def __post_init__(self):
        """What the fields alone rule out (what they say of the layers' KINDS is ``transformer.py::_kinds_of``'s)."""
        if self.norm_scheme not in ("pre", "post", "sandwich", "output"):
            raise ValueError(f"norm_scheme={self.norm_scheme!r}: pre, post, sandwich or output")
        if self.norm_scheme in ("sandwich", "output") and self.block_type != "sequential":
            raise ValueError(f"norm_scheme={self.norm_scheme!r} norms each sublayer's output inside its own residual branch: it needs "
                             f"block_type='sequential', not {self.block_type!r}")
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps={self.loop_steps}: the stack is run at least once")
        if self.exit_gate and self.loop_steps == 1:
            raise ValueError("exit_gate=True with loop_steps=1: a gate chooses among the passes of a loop, and one pass leaves no choice")
        if self.exit_entropy_coef and not self.exit_gate:
            raise ValueError(f"exit_entropy_coef={self.exit_entropy_coef} without exit_gate: the entropy is the exit distribution's")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def ffn_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.activation in GATED:  # gated MLPs get the 8/3 sizing
            return int(8 * self.d_model / 3 + 127) // 128 * 128 if self.d_model >= 128 else 2 * self.d_model
        return 4 * self.d_model

    @property
    def head_dim(self) -> int:
        if self.head_dims is not None:
            return self.head_dims
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def use_dense_bias(self) -> bool:
        return self.norm == "layernorm" if self.dense_bias is None else self.dense_bias

    @property
    def use_qkv_bias(self) -> bool:
        return self.use_dense_bias if self.qkv_bias is None else self.qkv_bias

    @property
    def use_attn_out_bias(self) -> bool:
        return self.use_dense_bias if self.attn_out_bias is None else self.attn_out_bias

    @property
    def rotary_dim(self) -> int:
        # even; partial rotary rotates the leading dims
        if self.rotary_dims is not None:
            return self.rotary_dims
        return max(2, int(self.head_dim * self.rotary_pct) // 2 * 2)
