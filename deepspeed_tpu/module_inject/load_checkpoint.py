"""HF-checkpoint interop: materialize HuggingFace GPT-2 / Llama / Mistral
checkpoints into this framework's :class:`~deepspeed_tpu.models.CausalLM`.

Parity: the reference's TP story is applying itself to *someone else's
model* — per-arch policies (``/root/reference/deepspeed/module_inject/
replace_module.py:182``), TP-aware checkpoint loading (``module_inject/
load_checkpoint.py``, ``inference/engine.py:331,441``). The TPU-native
equivalent is a weight-mapping loader: read the HF safetensors/torch
state dict on host, remap names + layouts into the CausalLM param pytree,
and ``jax.device_put`` with TP/ZeRO shardings so params are born sharded
(the ``zero.Init.materialize`` path) — no module surgery needed because
sharding is declarative here.

Supported architectures (the reference's policy-container breadth,
``module_inject/containers/`` + ``inference/v2/model_implementations/``):
``gpt2``, the llama family (``llama``, ``mistral``/``mixtral`` incl.
sliding-window attention, ``qwen2``), ``opt``, ``gpt_neox`` (pythia),
``gptj``, ``falcon`` (7b and 40b styles), ``phi``, ``bloom``,
``gpt_bigcode`` (starcoder), ``gemma``, ``stablelm``, ``phi3``, ``olmo``, and ``qwen3``.
"""

import json
import os
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..models.transformer import CausalLM, TransformerConfig
from ..utils.logging import logger

SAFETENSORS_NAME = "model.safetensors"
SAFETENSORS_INDEX = "model.safetensors.index.json"
TORCH_NAME = "pytorch_model.bin"
TORCH_INDEX = "pytorch_model.bin.index.json"


# ----------------------------------------------------------------------
# state-dict reading (host side, framework-agnostic numpy fp32)
# ----------------------------------------------------------------------
def _torch_to_numpy(t) -> np.ndarray:
    import torch

    if t.dtype in (torch.bfloat16, torch.float16):
        t = t.float()
    return t.detach().cpu().numpy()


def _read_safetensors(path: str) -> Dict[str, np.ndarray]:
    from safetensors import safe_open

    out = {}
    with safe_open(path, framework="pt") as f:  # pt framework: handles bf16
        for k in f.keys():
            out[k] = _torch_to_numpy(f.get_tensor(k))
    return out


def _read_torch_bin(path: str) -> Dict[str, np.ndarray]:
    import torch

    sd = torch.load(path, map_location="cpu", weights_only=True)
    return {k: _torch_to_numpy(v) for k, v in sd.items()}


def load_hf_state_dict(model_dir: str) -> Dict[str, np.ndarray]:
    """Read an HF checkpoint directory (single-file or sharded-index,
    safetensors or torch .bin) into a flat numpy state dict.

    Reference: sharded/meta checkpoint loading in ``inference/engine.py:
    331,441`` + ``module_inject/load_checkpoint.py``.
    """
    st = os.path.join(model_dir, SAFETENSORS_NAME)
    if os.path.exists(st):
        return _read_safetensors(st)
    for index_name, reader in ((SAFETENSORS_INDEX, _read_safetensors), (TORCH_INDEX, _read_torch_bin)):
        idx = os.path.join(model_dir, index_name)
        if os.path.exists(idx):
            with open(idx) as f:
                weight_map = json.load(f)["weight_map"]
            out = {}
            for shard in sorted(set(weight_map.values())):
                out.update(reader(os.path.join(model_dir, shard)))
            return out
    tb = os.path.join(model_dir, TORCH_NAME)
    if os.path.exists(tb):
        return _read_torch_bin(tb)
    raise FileNotFoundError(f"no {SAFETENSORS_NAME}/{TORCH_NAME} (or sharded index) under {model_dir}")


# ----------------------------------------------------------------------
# config mapping
# ----------------------------------------------------------------------
def _map_gelu(hf_act: str) -> str:
    """HF activation-name -> ours. HF 'gelu' is the exact erf GELU
    (``transformers.activations.GELUActivation``); 'gelu_new'/'gelu_fast'/
    'gelu_pytorch_tanh' are the tanh approximation our 'gelu' uses."""
    if hf_act == "relu":
        return "relu"
    if hf_act == "gelu":
        return "gelu_exact"
    return "gelu"


def _rope_scaling_kwargs(hf: Dict[str, Any]) -> Dict[str, Any]:
    """HF ``rope_scaling`` → TransformerConfig rope_* kwargs.

    Supported variants (``scaled_rope_frequencies`` implements the HF
    semantics, oracle-tested): linear, dynamic NTK, llama3 (llama-3.1+
    frequency-banded interpolation), yarn. ``longrope`` (phi-3 long
    contexts, per-dim factor tables) is still refused — loading it with
    base rope would silently diverge past the base window.
    """
    rs = hf.get("rope_scaling") or hf.get("rope_parameters")
    if not isinstance(rs, dict):
        return {}
    kind = rs.get("rope_type", rs.get("type", "default"))
    factor = rs.get("factor", 1.0)
    if kind in (None, "default"):
        return {}
    if factor is None or (float(factor) == 1.0 and kind in ("linear", "dynamic")):
        return {}  # identity interpolation
    if kind not in ("linear", "dynamic", "llama3", "yarn"):
        raise NotImplementedError(
            f"HF config requests rope_scaling={rs!r} ({hf.get('model_type', '?')}); supported "
            "variants: linear/dynamic/llama3/yarn — longrope-class per-dim tables are not, and "
            "loading with base rope would silently diverge past the base context")
    kw: Dict[str, Any] = {"rope_scaling": kind, "rope_factor": float(factor)}
    if kind == "dynamic":
        # HF _compute_dynamic_ntk_parameters rescales against
        # max_position_embeddings (its original_max_position_embeddings is
        # unused for dynamic), so at the checkpoint's own context the table
        # is the base rope; scaling kicks in only when max_seq_len is
        # overridden past it
        orig = hf.get("max_position_embeddings")
    else:
        orig = rs.get("original_max_position_embeddings")
    if orig:
        kw["rope_orig_max_seq"] = int(orig)
    if kind == "llama3":
        kw["rope_low_freq_factor"] = float(rs.get("low_freq_factor", 1.0))
        kw["rope_high_freq_factor"] = float(rs.get("high_freq_factor", 4.0))
    if kind == "yarn":
        kw["rope_beta_fast"] = float(rs.get("beta_fast") or 32.0)
        kw["rope_beta_slow"] = float(rs.get("beta_slow") or 1.0)
        if rs.get("attention_factor") is not None:
            kw["rope_attn_factor"] = float(rs["attention_factor"])
    return kw


def config_from_hf(hf: Dict[str, Any], dtype=None, **overrides) -> TransformerConfig:
    """Map an HF ``config.json`` dict to :class:`TransformerConfig`."""
    import jax.numpy as jnp

    model_type = hf.get("model_type", "")
    dtype = dtype if dtype is not None else jnp.float32
    rope_kw = _rope_scaling_kwargs(hf)
    if model_type == "gpt2":
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("n_layer", 12),
            n_heads=hf.get("n_head", 12),
            d_model=hf.get("n_embd", 768),
            max_seq_len=hf.get("n_positions", 1024),
            norm="layernorm",
            activation=_map_gelu(hf.get("activation_function", "gelu_new")),
            pos_emb="learned",
            tie_embeddings=True,
            norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            dtype=dtype,
        )
    elif model_type in ("llama", "mistral", "qwen2", "qwen3", "mixtral", "internlm", ""):
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("num_hidden_layers", 2),
            n_heads=hf.get("num_attention_heads", 4),
            n_kv_heads=hf.get("num_key_value_heads", hf.get("num_attention_heads", 4)),
            d_model=hf.get("hidden_size", 128),
            d_ff=hf.get("intermediate_size"),
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="rmsnorm",
            activation="swiglu",
            pos_emb="rope",
            rope_theta=hf.get("rope_theta", 10000.0),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            norm_eps=hf.get("rms_norm_eps", 1e-6),
            dtype=dtype,
        )
        if model_type == "qwen2":
            kw["qkv_bias"] = True
        if model_type == "llama" and hf.get("attention_bias"):
            kw["qkv_bias"] = True
            kw["attn_out_bias"] = True
        if model_type == "internlm":
            # ref module_inject/containers/internlm.py: llama layout with
            # config.bias toggling biases on q/k/v AND o (no HF-native class;
            # converter exercised via the shared llama machinery)
            kw["qkv_bias"] = bool(hf.get("bias", False))
            kw["attn_out_bias"] = bool(hf.get("bias", False))
        if model_type == "qwen3":
            kw["qk_norm"] = True
            if hf.get("head_dim"):
                kw["head_dims"] = int(hf["head_dim"])
        if model_type in ("mistral", "mixtral") and hf.get("sliding_window"):
            kw["sliding_window"] = int(hf["sliding_window"])
        # qwen2 gates its window behind use_sliding_window; HF windows only
        # layers with idx >= max_window_layers (the first mwl layers attend
        # fully) — expressed with per-layer window_layers
        if model_type in ("qwen2", "qwen3") and hf.get("use_sliding_window") and hf.get("sliding_window"):
            mwl = int(hf.get("max_window_layers", 28))  # HF Qwen2Config default
            n_layers = kw["n_layers"]
            if mwl <= 0:
                kw["sliding_window"] = int(hf["sliding_window"])
            elif mwl < n_layers:
                kw["sliding_window"] = int(hf["sliding_window"])
                kw["window_layers"] = tuple(range(mwl, n_layers))
            # mwl >= n_layers: HF uses full attention everywhere — no window
        if model_type == "mixtral":
            kw.update(
                moe_num_experts=hf.get("num_local_experts", 8),
                moe_top_k=hf.get("num_experts_per_tok", 2),
                moe_layer_freq=1,  # every mixtral block is MoE
                moe_aux_loss_coef=hf.get("router_aux_loss_coef", 0.02),
            )
    elif model_type == "olmo":
        kw = dict(
            clip_qkv=float(hf["clip_qkv"]) if hf.get("clip_qkv") else None,
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("num_hidden_layers", 2),
            n_heads=hf.get("num_attention_heads", 4),
            n_kv_heads=hf.get("num_key_value_heads", hf.get("num_attention_heads", 4)),
            d_model=hf["hidden_size"],
            d_ff=hf.get("intermediate_size"),
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm_np",
            activation="swiglu",
            pos_emb="rope",
            rope_theta=hf.get("rope_theta", 10000.0),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            dtype=dtype,
        )
    elif model_type == "phi3":
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("num_hidden_layers", 2),
            n_heads=hf.get("num_attention_heads", 4),
            n_kv_heads=hf.get("num_key_value_heads", hf.get("num_attention_heads", 4)),
            d_model=hf["hidden_size"],
            d_ff=hf.get("intermediate_size"),
            max_seq_len=hf.get("max_position_embeddings", 4096),
            norm="rmsnorm",
            activation="swiglu",
            pos_emb="rope",
            rope_theta=hf.get("rope_theta", 10000.0),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            norm_eps=hf.get("rms_norm_eps", 1e-5),
            dtype=dtype,
        )
        if hf.get("sliding_window"):
            kw["sliding_window"] = int(hf["sliding_window"])
    elif model_type == "stablelm":
        if hf.get("qk_layernorm", False):
            raise NotImplementedError("stablelm qk_layernorm (per-head q/k norms, stablelm-2-12b) unsupported")
        if hf.get("use_parallel_residual", False):
            raise NotImplementedError("stablelm use_parallel_residual variants are unsupported")
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("num_hidden_layers", 2),
            n_heads=hf.get("num_attention_heads", 4),
            n_kv_heads=hf.get("num_key_value_heads", hf.get("num_attention_heads", 4)),
            d_model=hf["hidden_size"],
            d_ff=hf.get("intermediate_size"),
            max_seq_len=hf.get("max_position_embeddings", 4096),
            norm="layernorm",
            activation="swiglu",
            pos_emb="rope",
            rotary_pct=hf.get("partial_rotary_factor", 0.25),
            rope_theta=hf.get("rope_theta", 10000.0),
            qkv_bias=hf.get("use_qkv_bias", False),
            dense_bias=False,  # layernorm carries biases but the linears do not
            tie_embeddings=hf.get("tie_word_embeddings", False),
            norm_eps=hf.get("layer_norm_eps", 1e-5),
            dtype=dtype,
        )
    elif model_type == "gemma":
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("num_hidden_layers", 2),
            n_heads=hf.get("num_attention_heads", 8),
            n_kv_heads=hf.get("num_key_value_heads", hf.get("num_attention_heads", 8)),
            head_dims=hf.get("head_dim", 256),
            d_model=hf["hidden_size"],
            d_ff=hf.get("intermediate_size"),
            max_seq_len=hf.get("max_position_embeddings", 8192),
            norm="rmsnorm",
            rms_offset=True,  # gemma stores zero-centered norm weights: (1 + w)
            embed_scale=True,  # embeddings scaled by sqrt(d_model)
            # HF keys both "gelu" (legacy checkpoints, which gemma actually
            # trained as tanh-approx) and "gelu_pytorch_tanh" to the tanh gate
            activation="geglu",
            pos_emb="rope",
            rope_theta=hf.get("rope_theta", 10000.0),
            tie_embeddings=hf.get("tie_word_embeddings", True),
            norm_eps=hf.get("rms_norm_eps", 1e-6),
            dtype=dtype,
        )
    elif model_type == "opt":
        if hf.get("word_embed_proj_dim", hf["hidden_size"]) != hf["hidden_size"]:
            raise NotImplementedError("OPT variants with word_embed_proj_dim != hidden_size (350m) "
                                      "need the embed in/out projections")
        if not hf.get("do_layer_norm_before", True):
            raise NotImplementedError("OPT with do_layer_norm_before=False (125m-era post-LN) unsupported")
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("num_hidden_layers", 12),
            n_heads=hf.get("num_attention_heads", 12),
            d_model=hf["hidden_size"],
            d_ff=hf.get("ffn_dim", 4 * hf["hidden_size"]),
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation=_map_gelu(hf.get("activation_function", "relu")),
            pos_emb="learned",
            tie_embeddings=hf.get("tie_word_embeddings", True),
            dtype=dtype,
        )
    elif model_type == "gpt_neox":
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("num_hidden_layers", 12),
            n_heads=hf.get("num_attention_heads", 12),
            d_model=hf["hidden_size"],
            d_ff=hf.get("intermediate_size", 4 * hf["hidden_size"]),
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation=_map_gelu(hf.get("hidden_act", "gelu")),
            pos_emb="rope",
            rotary_pct=hf.get("rotary_pct", 1.0),
            # modern transformers serializes rope_theta as authoritative,
            # alongside a possibly-stale legacy rotary_emb_base
            rope_theta=hf.get("rope_theta", hf.get("rotary_emb_base", 10000.0)),
            block_type="parallel" if hf.get("use_parallel_residual", True) else "sequential",
            tie_embeddings=hf.get("tie_word_embeddings", False),
            norm_eps=hf.get("layer_norm_eps", 1e-5),
            dtype=dtype,
        )
    elif model_type == "gptj":
        head_dim = hf["n_embd"] // hf["n_head"]
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("n_layer", 12),
            n_heads=hf.get("n_head", 12),
            d_model=hf["n_embd"],
            d_ff=hf.get("n_inner") or 4 * hf["n_embd"],
            max_seq_len=hf.get("n_positions", 2048),
            norm="layernorm",
            activation=_map_gelu(hf.get("activation_function", "gelu_new")),
            pos_emb="rope",
            rotary_dims=hf.get("rotary_dim") or head_dim,
            rope_style="gptj",
            block_type="parallel_shared",
            qkv_bias=False,
            attn_out_bias=False,
            dense_bias=True,
            lm_head_bias=True,
            tie_embeddings=False,
            norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            dtype=dtype,
        )
    elif model_type == "falcon":
        new_arch = hf.get("new_decoder_architecture", False)
        if not hf.get("parallel_attn", True):
            raise NotImplementedError("falcon with parallel_attn=False unsupported")
        if not new_arch and not hf.get("multi_query", True):
            raise NotImplementedError("falcon multi_query=False uses an interleaved qkv layout (rw-style); "
                                      "unsupported")
        if new_arch:  # 40b/180b: GQA + separate ln_attn/ln_mlp in parallel
            n_kv = hf.get("num_kv_heads") or hf.get("num_attention_heads", 8)
        else:  # 7b: MQA + one shared input layernorm
            n_kv = 1 if hf.get("multi_query", True) else hf.get("num_attention_heads", 8)
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("num_hidden_layers", 2),
            n_heads=hf.get("num_attention_heads", 8),
            n_kv_heads=n_kv,
            d_model=hf["hidden_size"],
            d_ff=hf.get("ffn_hidden_size") or 4 * hf["hidden_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation=_map_gelu(hf.get("activation", "gelu")),
            pos_emb="alibi" if hf.get("alibi", False) else "rope",
            rope_theta=hf.get("rope_theta", 10000.0),
            block_type="parallel" if new_arch else "parallel_shared",
            dense_bias=hf.get("bias", False),
            tie_embeddings=hf.get("tie_word_embeddings", True),
            norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            dtype=dtype,
        )
    elif model_type == "phi":
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("num_hidden_layers", 2),
            n_heads=hf.get("num_attention_heads", 4),
            n_kv_heads=hf.get("num_key_value_heads") or hf.get("num_attention_heads", 4),
            d_model=hf["hidden_size"],
            d_ff=hf.get("intermediate_size", 4 * hf["hidden_size"]),
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation=_map_gelu(hf.get("hidden_act", "gelu_new")),
            pos_emb="rope",
            rotary_pct=hf.get("partial_rotary_factor", 0.5),
            rope_theta=hf.get("rope_theta", 10000.0),
            block_type="parallel_shared",
            dense_bias=True,
            qkv_bias=True,
            lm_head_bias=True,
            tie_embeddings=hf.get("tie_word_embeddings", False),
            norm_eps=hf.get("layer_norm_eps", 1e-5),
            dtype=dtype,
        )
    elif model_type == "gpt_bigcode":
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("n_layer", 12),
            n_heads=hf.get("n_head", 12),
            n_kv_heads=1 if hf.get("multi_query", True) else hf.get("n_head", 12),
            d_model=hf["n_embd"],
            d_ff=hf.get("n_inner") or 4 * hf["n_embd"],
            max_seq_len=hf.get("n_positions", 2048),
            norm="layernorm",
            activation=_map_gelu(hf.get("activation_function", "gelu_pytorch_tanh")),
            pos_emb="learned",
            tie_embeddings=hf.get("tie_word_embeddings", True),
            norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            dtype=dtype,
        )
    elif model_type == "bert":
        # encoder family: bidirectional post-LN blocks, segment embeddings,
        # MLM transform head (ref module_inject/containers/bert.py,
        # replace_policy.py HFBertLayerPolicy)
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("num_hidden_layers", 12),
            n_heads=hf.get("num_attention_heads", 12),
            d_model=hf.get("hidden_size", 768),
            d_ff=hf.get("intermediate_size", 3072),
            max_seq_len=hf.get("max_position_embeddings", 512),
            norm="layernorm",
            activation=_map_gelu(hf.get("hidden_act", "gelu")),
            pos_emb="learned",
            causal=False,
            norm_scheme="post",
            embedding_norm=True,
            type_vocab_size=hf.get("type_vocab_size", 2),
            mlm_head=True,
            tie_embeddings=True,
            norm_eps=hf.get("layer_norm_eps", 1e-12),
            dtype=dtype,
        )
    elif model_type == "gpt_neo":
        # ref module_inject/containers/gptneo.py (HFGPTNEOLayerPolicy):
        # gpt2-style learned positions but torch-Linear projections, bias-free
        # q/k/v, UNSCALED attention logits, and alternating global/local
        # (window 256) layers via attention_layers
        d_model = hf.get("hidden_size", 2048)
        n_layers = hf.get("num_layers", 24)
        att_layers = hf.get("attention_layers")
        if not att_layers:  # expand [["global","local"], 12]-style attention_types
            att_layers = []
            for kinds, n in hf.get("attention_types") or [[["global"], n_layers]]:
                att_layers += list(kinds) * n
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=n_layers,
            n_heads=hf.get("num_heads", 16),
            d_model=d_model,
            d_ff=hf.get("intermediate_size") or 4 * d_model,
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation=_map_gelu(hf.get("activation_function", "gelu_new")),
            pos_emb="learned",
            qkv_bias=False,
            attn_scale=1.0,
            tie_embeddings=hf.get("tie_word_embeddings", True),
            norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            dtype=dtype,
        )
        local = tuple(i for i, kind in enumerate(att_layers[:n_layers]) if kind == "local")
        if local:
            kw["sliding_window"] = int(hf.get("window_size", 256))
            if len(local) < n_layers:
                kw["window_layers"] = local
    elif model_type == "distilbert":
        # ref module_inject/containers/distil_bert.py (HFDistilBertLayerPolicy):
        # BERT post-LN encoder minus token-type embeddings; MLM head =
        # vocab_transform -> gelu -> vocab_layer_norm -> tied projector
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("n_layers", 6),
            n_heads=hf.get("n_heads", 12),
            d_model=hf.get("dim", 768),
            d_ff=hf.get("hidden_dim", 3072),
            max_seq_len=hf.get("max_position_embeddings", 512),
            norm="layernorm",
            activation=_map_gelu(hf.get("activation", "gelu")),
            pos_emb="learned",
            causal=False,
            norm_scheme="post",
            embedding_norm=True,
            type_vocab_size=0,
            mlm_head=True,
            tie_embeddings=True,
            norm_eps=1e-12,  # hardcoded in HF DistilBert LayerNorms
            dtype=dtype,
        )
        if hf.get("sinusoidal_pos_embds"):
            raise NotImplementedError("distilbert sinusoidal_pos_embds unsupported (learned positions only)")
    elif model_type == "bloom":
        kw = dict(
            vocab_size=hf["vocab_size"],
            n_layers=hf.get("n_layer", 2),
            n_heads=hf.get("n_head", 8),
            d_model=hf["hidden_size"],
            d_ff=4 * hf["hidden_size"],
            max_seq_len=hf.get("seq_length", 2048),
            norm="layernorm",
            activation="gelu",
            pos_emb="alibi",
            embedding_norm=True,
            tie_embeddings=True,
            norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            dtype=dtype,
        )
    else:
        raise NotImplementedError(f"HF model_type '{model_type}' not supported (supported: gpt2, llama, "
                                  "mistral, qwen2, qwen3, mixtral, internlm, opt, gpt_neox, gptj, gpt_neo, "
                                  "falcon, phi, phi3, bloom, gpt_bigcode, gemma, stablelm, olmo, bert, "
                                  "distilbert)")
    if kw.get("pos_emb") == "rope":
        kw.update(rope_kw)
    elif rope_kw:
        raise NotImplementedError(f"rope_scaling on a non-rope architecture {model_type!r}")
    kw.update(overrides)
    return TransformerConfig(**kw)


# ----------------------------------------------------------------------
# weight remapping
# ----------------------------------------------------------------------
def _strip_prefix(sd: Dict[str, np.ndarray], prefixes=("transformer.", "model.")) -> Dict[str, np.ndarray]:
    out = {}
    for k, v in sd.items():
        for p in prefixes:
            if k.startswith(p):
                k = k[len(p):]
                break
        out[k] = v
    return out


def _norm_name(cfg: TransformerConfig, idx: int) -> str:
    base = "RMSNorm" if cfg.norm == "rmsnorm" else "LayerNorm"
    return f"{base}_{idx}"


def convert_gpt2(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``GPT2LMHeadModel`` state dict -> CausalLM param pytree.

    HF Conv1D stores weights as (in, out) — the flax kernel layout — so no
    transposes; the fused ``c_attn`` (in, 3*d) splits into q/k/v.
    """
    sd = _strip_prefix(sd)
    H, D = cfg.n_heads, cfg.head_dim
    dm = cfg.d_model
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {
        "wte": sd["wte.weight"],
        "wpe": sd["wpe.weight"][:cfg.max_seq_len],
        ln(0): {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
    }
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        ca_w, ca_b = sd[p + "attn.c_attn.weight"], sd[p + "attn.c_attn.bias"]
        qw, kw, vw = np.split(ca_w, 3, axis=1)
        qb, kb, vb = np.split(ca_b, 3)
        params[f"layer_{i}"] = {
            ln(0): {"scale": sd[p + "ln_1.weight"], "bias": sd[p + "ln_1.bias"]},
            ln(1): {"scale": sd[p + "ln_2.weight"], "bias": sd[p + "ln_2.bias"]},
            "attn": {
                "q_proj": {"kernel": qw.reshape(dm, H, D), "bias": qb.reshape(H, D)},
                "k_proj": {"kernel": kw.reshape(dm, H, D), "bias": kb.reshape(H, D)},
                "v_proj": {"kernel": vw.reshape(dm, H, D), "bias": vb.reshape(H, D)},
                "o_proj": {"kernel": sd[p + "attn.c_proj.weight"].reshape(H, D, dm),
                           "bias": sd[p + "attn.c_proj.bias"]},
            },
            "mlp": {
                "up_proj": {"kernel": sd[p + "mlp.c_fc.weight"], "bias": sd[p + "mlp.c_fc.bias"]},
                "down_proj": {"kernel": sd[p + "mlp.c_proj.weight"], "bias": sd[p + "mlp.c_proj.bias"]},
            },
        }
    return params


def convert_llama(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``LlamaForCausalLM`` (or mistral/qwen2/mixtral) state dict ->
    CausalLM pytree.

    torch ``nn.Linear`` stores (out, in) — transposed into flax (in, out);
    attention projections reshape the fused head dim into (H, head_dim).
    Mixtral MoE blocks map ``block_sparse_moe.gate`` -> gate kernel and
    per-expert w1/w3/w2 -> stacked wg/wi/wo expert tensors.
    """
    has_lm_head = "lm_head.weight" in sd
    sd = _strip_prefix(sd)
    H, KVH, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    dm = cfg.d_model

    def norm_params(prefix: str) -> Dict[str, np.ndarray]:
        # stablelm uses biased layernorms in the otherwise llama-shaped layout
        out = {"scale": sd[prefix + ".weight"]}
        if prefix + ".bias" in sd:
            out["bias"] = sd[prefix + ".bias"]
        return out

    np_norm = cfg.norm == "layernorm_np"  # olmo: no affine norm params
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {"wte": sd["embed_tokens.weight"]}
    if not np_norm:
        params[ln(0)] = norm_params("norm" if "norm.weight" in sd else "final_layernorm")
    if not cfg.tie_embeddings:
        lm_w = sd["lm_head.weight"] if has_lm_head else sd["embed_tokens.weight"]
        params["lm_head"] = {"kernel": lm_w.T}
    for i in range(cfg.n_layers):
        p = f"layers.{i}."
        layer = {
            **({} if np_norm else {ln(0): norm_params(p + "input_layernorm"),
                                   ln(1): norm_params(p + "post_attention_layernorm")}),
            "attn": {
                "q_proj": {"kernel": sd[p + "self_attn.q_proj.weight"].T.reshape(dm, H, D)},
                "k_proj": {"kernel": sd[p + "self_attn.k_proj.weight"].T.reshape(dm, KVH, D)},
                "v_proj": {"kernel": sd[p + "self_attn.v_proj.weight"].T.reshape(dm, KVH, D)},
                "o_proj": {"kernel": sd[p + "self_attn.o_proj.weight"].T.reshape(H, D, dm)},
            },
        }
        if p + "block_sparse_moe.gate.weight" in sd:  # mixtral MoE block
            E = cfg.moe_num_experts
            layer["moe"] = {
                "gate": {"kernel": sd[p + "block_sparse_moe.gate.weight"].T},
                "experts": {
                    # our Experts: h = silu(x@wg) * (x@wi); out = h@wo
                    "wg": np.stack([sd[p + f"block_sparse_moe.experts.{j}.w1.weight"].T for j in range(E)]),
                    "wi": np.stack([sd[p + f"block_sparse_moe.experts.{j}.w3.weight"].T for j in range(E)]),
                    "wo": np.stack([sd[p + f"block_sparse_moe.experts.{j}.w2.weight"].T for j in range(E)]),
                },
            }
        else:
            layer["mlp"] = {
                "gate_proj": {"kernel": sd[p + "mlp.gate_proj.weight"].T},
                "up_proj": {"kernel": sd[p + "mlp.up_proj.weight"].T},
                "down_proj": {"kernel": sd[p + "mlp.down_proj.weight"].T},
            }
        if cfg.qk_norm:  # qwen3 per-head q/k norms
            layer["attn"]["q_norm"] = {"scale": sd[p + "self_attn.q_norm.weight"]}
            layer["attn"]["k_norm"] = {"scale": sd[p + "self_attn.k_norm.weight"]}
        # qwen2 carries q/k/v biases; internlm (config.bias) also biases o
        for proj, heads in (("q_proj", H), ("k_proj", KVH), ("v_proj", KVH)):
            bkey = p + f"self_attn.{proj}.bias"
            if bkey in sd:
                layer["attn"][proj]["bias"] = sd[bkey].reshape(heads, D)
        if p + "self_attn.o_proj.bias" in sd:
            layer["attn"]["o_proj"]["bias"] = sd[p + "self_attn.o_proj.bias"]
        params[f"layer_{i}"] = layer
    return params


def convert_opt(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``OPTForCausalLM`` -> CausalLM pytree. torch Linear (out,in) is
    transposed; learned positions drop OPT's 2-slot offset (HF computes
    positions as mask-cumsum + 2, which for dense masks is arange + 2)."""
    sd = _strip_prefix(sd, ("model.decoder.", "decoder.", "model."))
    H, D, dm = cfg.n_heads, cfg.head_dim, cfg.d_model
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {
        "wte": sd["embed_tokens.weight"],
        "wpe": sd["embed_positions.weight"][2:2 + cfg.max_seq_len],
        ln(0): {"scale": sd["final_layer_norm.weight"], "bias": sd["final_layer_norm.bias"]},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": sd.get("lm_head.weight", sd["embed_tokens.weight"]).T}
    for i in range(cfg.n_layers):
        p = f"layers.{i}."
        attn = {}
        for name, hf_name in (("q_proj", "q_proj"), ("k_proj", "k_proj"), ("v_proj", "v_proj")):
            attn[name] = {"kernel": sd[p + f"self_attn.{hf_name}.weight"].T.reshape(dm, H, D),
                          "bias": sd[p + f"self_attn.{hf_name}.bias"].reshape(H, D)}
        attn["o_proj"] = {"kernel": sd[p + "self_attn.out_proj.weight"].T.reshape(H, D, dm),
                          "bias": sd[p + "self_attn.out_proj.bias"]}
        params[f"layer_{i}"] = {
            ln(0): {"scale": sd[p + "self_attn_layer_norm.weight"], "bias": sd[p + "self_attn_layer_norm.bias"]},
            ln(1): {"scale": sd[p + "final_layer_norm.weight"], "bias": sd[p + "final_layer_norm.bias"]},
            "attn": attn,
            "mlp": {
                "up_proj": {"kernel": sd[p + "fc1.weight"].T, "bias": sd[p + "fc1.bias"]},
                "down_proj": {"kernel": sd[p + "fc2.weight"].T, "bias": sd[p + "fc2.bias"]},
            },
        }
    return params


def convert_gpt_neox(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``GPTNeoXForCausalLM`` (pythia) -> pytree. The fused
    ``query_key_value`` is interleaved per head as (H, 3, D, dm)."""
    sd = _strip_prefix(sd, ("gpt_neox.",))
    H, D, dm = cfg.n_heads, cfg.head_dim, cfg.d_model
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {
        "wte": sd["embed_in.weight"],
        ln(0): {"scale": sd["final_layer_norm.weight"], "bias": sd["final_layer_norm.bias"]},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": sd["embed_out.weight"].T}
    for i in range(cfg.n_layers):
        p = f"layers.{i}."
        qkv_w = sd[p + "attention.query_key_value.weight"].reshape(H, 3, D, dm)
        qkv_b = sd[p + "attention.query_key_value.bias"].reshape(H, 3, D)
        attn = {}
        for j, name in enumerate(("q_proj", "k_proj", "v_proj")):
            attn[name] = {"kernel": np.transpose(qkv_w[:, j], (2, 0, 1)), "bias": qkv_b[:, j]}
        attn["o_proj"] = {"kernel": sd[p + "attention.dense.weight"].T.reshape(H, D, dm),
                          "bias": sd[p + "attention.dense.bias"]}
        params[f"layer_{i}"] = {
            ln(0): {"scale": sd[p + "input_layernorm.weight"], "bias": sd[p + "input_layernorm.bias"]},
            ln(1): {"scale": sd[p + "post_attention_layernorm.weight"],
                    "bias": sd[p + "post_attention_layernorm.bias"]},
            "attn": attn,
            "mlp": {
                "up_proj": {"kernel": sd[p + "mlp.dense_h_to_4h.weight"].T,
                            "bias": sd[p + "mlp.dense_h_to_4h.bias"]},
                "down_proj": {"kernel": sd[p + "mlp.dense_4h_to_h.weight"].T,
                              "bias": sd[p + "mlp.dense_4h_to_h.bias"]},
            },
        }
    return params


def convert_gptj(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``GPTJForCausalLM`` -> pytree: parallel-shared block, interleaved
    (gptj-style) rotary, biased MLP + biased untied head, bias-free attn."""
    sd = _strip_prefix(sd, ("transformer.",))
    H, D, dm = cfg.n_heads, cfg.head_dim, cfg.d_model
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {
        "wte": sd["wte.weight"],
        ln(0): {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
        "lm_head": {"kernel": sd["lm_head.weight"].T, "bias": sd["lm_head.bias"]},
    }
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        params[f"layer_{i}"] = {
            ln(0): {"scale": sd[p + "ln_1.weight"], "bias": sd[p + "ln_1.bias"]},
            "attn": {
                "q_proj": {"kernel": sd[p + "attn.q_proj.weight"].T.reshape(dm, H, D)},
                "k_proj": {"kernel": sd[p + "attn.k_proj.weight"].T.reshape(dm, H, D)},
                "v_proj": {"kernel": sd[p + "attn.v_proj.weight"].T.reshape(dm, H, D)},
                "o_proj": {"kernel": sd[p + "attn.out_proj.weight"].T.reshape(H, D, dm)},
            },
            "mlp": {
                "up_proj": {"kernel": sd[p + "mlp.fc_in.weight"].T, "bias": sd[p + "mlp.fc_in.bias"]},
                "down_proj": {"kernel": sd[p + "mlp.fc_out.weight"].T, "bias": sd[p + "mlp.fc_out.bias"]},
            },
        }
    return params


def convert_falcon(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``FalconForCausalLM`` -> pytree.

    7b-style (parallel_shared): fused qkv rows are [q (H*D), k (D), v (D)]
    with one shared input_layernorm. 40b-style (new_decoder_architecture,
    block_type "parallel"): GQA with per-kv-head grouped qkv rows
    [(G q) k v] x KVH and separate ln_attn / ln_mlp."""
    new_arch = cfg.block_type == "parallel"
    sd = _strip_prefix(sd, ("transformer.",))
    H, KVH, D, dm = cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_model
    G = H // KVH
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {
        "wte": sd["word_embeddings.weight"],
        ln(0): {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": sd["lm_head.weight"].T}
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        qkv = sd[p + "self_attention.query_key_value.weight"]
        if new_arch:
            w = qkv.reshape(KVH, G + 2, D, dm)
            qw = np.transpose(w[:, :G], (3, 0, 1, 2)).reshape(dm, H, D)
            kw = np.transpose(w[:, G], (2, 0, 1))  # (dm, KVH, D)
            vw = np.transpose(w[:, G + 1], (2, 0, 1))
            attn = {
                "q_proj": {"kernel": qw},
                "k_proj": {"kernel": kw},
                "v_proj": {"kernel": vw},
                "o_proj": {"kernel": sd[p + "self_attention.dense.weight"].T.reshape(H, D, dm)},
            }
            norms = {
                ln(0): {"scale": sd[p + "ln_attn.weight"], "bias": sd[p + "ln_attn.bias"]},
                ln(1): {"scale": sd[p + "ln_mlp.weight"], "bias": sd[p + "ln_mlp.bias"]},
            }
        else:
            qw, kw, vw = np.split(qkv, [H * D, (H + KVH) * D], axis=0)
            attn = {
                "q_proj": {"kernel": qw.T.reshape(dm, H, D)},
                "k_proj": {"kernel": kw.T.reshape(dm, KVH, D)},
                "v_proj": {"kernel": vw.T.reshape(dm, KVH, D)},
                "o_proj": {"kernel": sd[p + "self_attention.dense.weight"].T.reshape(H, D, dm)},
            }
            norms = {
                ln(0): {"scale": sd[p + "input_layernorm.weight"], "bias": sd[p + "input_layernorm.bias"]},
            }
        layer = {
            **norms,
            "attn": attn,
            "mlp": {
                "up_proj": {"kernel": sd[p + "mlp.dense_h_to_4h.weight"].T},
                "down_proj": {"kernel": sd[p + "mlp.dense_4h_to_h.weight"].T},
            },
        }
        if cfg.use_dense_bias:
            qkv_b = sd[p + "self_attention.query_key_value.bias"]
            if new_arch:
                b = qkv_b.reshape(KVH, G + 2, D)
                qb, kb, vb = b[:, :G].reshape(H, D), b[:, G], b[:, G + 1]
            else:
                qb, kb, vb = np.split(qkv_b, [H * D, (H + KVH) * D])
                qb, kb, vb = qb.reshape(H, D), kb.reshape(KVH, D), vb.reshape(KVH, D)
            layer["attn"]["q_proj"]["bias"] = qb
            layer["attn"]["k_proj"]["bias"] = kb
            layer["attn"]["v_proj"]["bias"] = vb
            layer["attn"]["o_proj"]["bias"] = sd[p + "self_attention.dense.bias"]
            layer["mlp"]["up_proj"]["bias"] = sd[p + "mlp.dense_h_to_4h.bias"]
            layer["mlp"]["down_proj"]["bias"] = sd[p + "mlp.dense_4h_to_h.bias"]
        params[f"layer_{i}"] = layer
    return params


def convert_phi(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``PhiForCausalLM`` (phi-1/phi-2) -> pytree: parallel-shared block
    with one layernorm, partial rotary, biases everywhere incl. lm_head."""
    has_lm_head = "lm_head.weight" in sd
    sd = _strip_prefix(sd, ("model.",))
    H, KVH, D, dm = cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_model
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {
        "wte": sd["embed_tokens.weight"],
        ln(0): {"scale": sd["final_layernorm.weight"], "bias": sd["final_layernorm.bias"]},
    }
    if not cfg.tie_embeddings:
        lm_w = sd["lm_head.weight"] if has_lm_head else sd["embed_tokens.weight"]
        params["lm_head"] = {"kernel": lm_w.T}
        if cfg.lm_head_bias:
            params["lm_head"]["bias"] = sd["lm_head.bias"]
    for i in range(cfg.n_layers):
        p = f"layers.{i}."
        params[f"layer_{i}"] = {
            ln(0): {"scale": sd[p + "input_layernorm.weight"], "bias": sd[p + "input_layernorm.bias"]},
            "attn": {
                "q_proj": {"kernel": sd[p + "self_attn.q_proj.weight"].T.reshape(dm, H, D),
                           "bias": sd[p + "self_attn.q_proj.bias"].reshape(H, D)},
                "k_proj": {"kernel": sd[p + "self_attn.k_proj.weight"].T.reshape(dm, KVH, D),
                           "bias": sd[p + "self_attn.k_proj.bias"].reshape(KVH, D)},
                "v_proj": {"kernel": sd[p + "self_attn.v_proj.weight"].T.reshape(dm, KVH, D),
                           "bias": sd[p + "self_attn.v_proj.bias"].reshape(KVH, D)},
                "o_proj": {"kernel": sd[p + "self_attn.dense.weight"].T.reshape(H, D, dm),
                           "bias": sd[p + "self_attn.dense.bias"]},
            },
            "mlp": {
                "up_proj": {"kernel": sd[p + "mlp.fc1.weight"].T, "bias": sd[p + "mlp.fc1.bias"]},
                "down_proj": {"kernel": sd[p + "mlp.fc2.weight"].T, "bias": sd[p + "mlp.fc2.bias"]},
            },
        }
    return params


def convert_phi3(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``Phi3ForCausalLM`` -> pytree: llama-shaped except the per-layer
    fused ``qkv_proj`` ([q (H*D), k, v] rows) and ``gate_up_proj``
    ([gate, up] rows), which are de-fused here and delegated."""
    H, KVH, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    out: Dict[str, np.ndarray] = {}
    for k, v in sd.items():
        if k.endswith("self_attn.qkv_proj.weight"):
            base = k[:-len("qkv_proj.weight")]
            qw, kw_, vw = np.split(v, [H * D, (H + KVH) * D], axis=0)
            out[base + "q_proj.weight"], out[base + "k_proj.weight"], out[base + "v_proj.weight"] = qw, kw_, vw
        elif k.endswith("mlp.gate_up_proj.weight"):
            base = k[:-len("gate_up_proj.weight")]
            gw, uw = np.split(v, 2, axis=0)
            out[base + "gate_proj.weight"], out[base + "up_proj.weight"] = gw, uw
        else:
            out[k] = v
    return convert_llama(out, cfg)


def convert_gpt_bigcode(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``GPTBigCodeForCausalLM`` (StarCoder) -> pytree: learned positions,
    MQA with contiguous [q (H*D), k (KVH*D), v (KVH*D)] fused rows stored in
    torch Linear (out, in) layout."""
    sd = _strip_prefix(sd, ("transformer.",))
    H, KVH, D, dm = cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_model
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {
        "wte": sd["wte.weight"],
        "wpe": sd["wpe.weight"][:cfg.max_seq_len],
        ln(0): {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": sd["lm_head.weight"].T}
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        qkv_w = sd[p + "attn.c_attn.weight"]
        qkv_b = sd[p + "attn.c_attn.bias"]
        if KVH == H:  # MHA variant: per-head interleaved [q_h k_h v_h] rows
            w3 = qkv_w.reshape(H, 3, D, dm)
            b3 = qkv_b.reshape(H, 3, D)
            qw, kw_, vw = (w3[:, j].reshape(H * D, dm) for j in range(3))
            qb, kb, vb = (b3[:, j].reshape(H * D) for j in range(3))
        else:  # MQA: contiguous [q (H*D), k (KVH*D), v (KVH*D)]
            qw, kw_, vw = np.split(qkv_w, [H * D, (H + KVH) * D], axis=0)
            qb, kb, vb = np.split(qkv_b, [H * D, (H + KVH) * D])
        params[f"layer_{i}"] = {
            ln(0): {"scale": sd[p + "ln_1.weight"], "bias": sd[p + "ln_1.bias"]},
            ln(1): {"scale": sd[p + "ln_2.weight"], "bias": sd[p + "ln_2.bias"]},
            "attn": {
                "q_proj": {"kernel": qw.T.reshape(dm, H, D), "bias": qb.reshape(H, D)},
                "k_proj": {"kernel": kw_.T.reshape(dm, KVH, D), "bias": kb.reshape(KVH, D)},
                "v_proj": {"kernel": vw.T.reshape(dm, KVH, D), "bias": vb.reshape(KVH, D)},
                "o_proj": {"kernel": sd[p + "attn.c_proj.weight"].T.reshape(H, D, dm),
                           "bias": sd[p + "attn.c_proj.bias"]},
            },
            "mlp": {
                "up_proj": {"kernel": sd[p + "mlp.c_fc.weight"].T, "bias": sd[p + "mlp.c_fc.bias"]},
                "down_proj": {"kernel": sd[p + "mlp.c_proj.weight"].T, "bias": sd[p + "mlp.c_proj.bias"]},
            },
        }
    return params


def convert_bert(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``BertForMaskedLM`` -> encoder param pytree.

    Post-LN block: ``attention.output.LayerNorm`` / ``output.LayerNorm``
    are the two in-block norms; ``cls.predictions.transform`` is the MLM
    head whose decoder ties to the word embeddings
    (ref ``module_inject/containers/bert.py``, ``HFBertLayerPolicy``).
    """
    sd = _strip_prefix(sd, prefixes=("bert.",))
    H, D = cfg.n_heads, cfg.head_dim
    dm = cfg.d_model
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {
        "wte": sd["embeddings.word_embeddings.weight"],
        "wpe": sd["embeddings.position_embeddings.weight"][:cfg.max_seq_len],
        "type_emb": sd["embeddings.token_type_embeddings.weight"],
        ln(0): {"scale": sd["embeddings.LayerNorm.weight"], "bias": sd["embeddings.LayerNorm.bias"]},
        "mlm_dense": {"kernel": sd["cls.predictions.transform.dense.weight"].T,
                      "bias": sd["cls.predictions.transform.dense.bias"]},
        ln(1): {"scale": sd["cls.predictions.transform.LayerNorm.weight"],
                "bias": sd["cls.predictions.transform.LayerNorm.bias"]},
        "mlm_bias": sd["cls.predictions.bias"],
    }
    for i in range(cfg.n_layers):
        p = f"encoder.layer.{i}."
        params[f"layer_{i}"] = {
            ln(0): {"scale": sd[p + "attention.output.LayerNorm.weight"],
                    "bias": sd[p + "attention.output.LayerNorm.bias"]},
            ln(1): {"scale": sd[p + "output.LayerNorm.weight"],
                    "bias": sd[p + "output.LayerNorm.bias"]},
            "attn": {
                "q_proj": {"kernel": sd[p + "attention.self.query.weight"].T.reshape(dm, H, D),
                           "bias": sd[p + "attention.self.query.bias"].reshape(H, D)},
                "k_proj": {"kernel": sd[p + "attention.self.key.weight"].T.reshape(dm, H, D),
                           "bias": sd[p + "attention.self.key.bias"].reshape(H, D)},
                "v_proj": {"kernel": sd[p + "attention.self.value.weight"].T.reshape(dm, H, D),
                           "bias": sd[p + "attention.self.value.bias"].reshape(H, D)},
                "o_proj": {"kernel": sd[p + "attention.output.dense.weight"].T.reshape(H, D, dm),
                           "bias": sd[p + "attention.output.dense.bias"]},
            },
            "mlp": {
                "up_proj": {"kernel": sd[p + "intermediate.dense.weight"].T,
                            "bias": sd[p + "intermediate.dense.bias"]},
                "down_proj": {"kernel": sd[p + "output.dense.weight"].T,
                              "bias": sd[p + "output.dense.bias"]},
            },
        }
    return params


def convert_gpt_neo(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``GPTNeoForCausalLM`` -> pytree. gpt2 layout but torch Linear
    (out, in) projections (transposed) with bias-free q/k/v."""
    sd = _strip_prefix(sd)
    H, D, dm = cfg.n_heads, cfg.head_dim, cfg.d_model
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {
        "wte": sd["wte.weight"],
        "wpe": sd["wpe.weight"][:cfg.max_seq_len],
        ln(0): {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": sd["lm_head.weight"].T}
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        a = p + "attn.attention."
        params[f"layer_{i}"] = {
            ln(0): {"scale": sd[p + "ln_1.weight"], "bias": sd[p + "ln_1.bias"]},
            ln(1): {"scale": sd[p + "ln_2.weight"], "bias": sd[p + "ln_2.bias"]},
            "attn": {
                "q_proj": {"kernel": sd[a + "q_proj.weight"].T.reshape(dm, H, D)},
                "k_proj": {"kernel": sd[a + "k_proj.weight"].T.reshape(dm, H, D)},
                "v_proj": {"kernel": sd[a + "v_proj.weight"].T.reshape(dm, H, D)},
                "o_proj": {"kernel": sd[a + "out_proj.weight"].T.reshape(H, D, dm),
                           "bias": sd[a + "out_proj.bias"]},
            },
            "mlp": {
                "up_proj": {"kernel": sd[p + "mlp.c_fc.weight"].T, "bias": sd[p + "mlp.c_fc.bias"]},
                "down_proj": {"kernel": sd[p + "mlp.c_proj.weight"].T, "bias": sd[p + "mlp.c_proj.bias"]},
            },
        }
    return params


def convert_distilbert(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``DistilBertForMaskedLM`` -> encoder pytree (BERT minus token-type
    embeddings; ``vocab_transform``/``vocab_layer_norm`` MLM head with the
    projector tied to the word embeddings)."""
    sd = _strip_prefix(sd, prefixes=("distilbert.",))
    H, D, dm = cfg.n_heads, cfg.head_dim, cfg.d_model
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {
        "wte": sd["embeddings.word_embeddings.weight"],
        "wpe": sd["embeddings.position_embeddings.weight"][:cfg.max_seq_len],
        ln(0): {"scale": sd["embeddings.LayerNorm.weight"], "bias": sd["embeddings.LayerNorm.bias"]},
        "mlm_dense": {"kernel": sd["vocab_transform.weight"].T, "bias": sd["vocab_transform.bias"]},
        ln(1): {"scale": sd["vocab_layer_norm.weight"], "bias": sd["vocab_layer_norm.bias"]},
        "mlm_bias": sd["vocab_projector.bias"],
    }
    for i in range(cfg.n_layers):
        p = f"transformer.layer.{i}."
        params[f"layer_{i}"] = {
            ln(0): {"scale": sd[p + "sa_layer_norm.weight"], "bias": sd[p + "sa_layer_norm.bias"]},
            ln(1): {"scale": sd[p + "output_layer_norm.weight"], "bias": sd[p + "output_layer_norm.bias"]},
            "attn": {
                "q_proj": {"kernel": sd[p + "attention.q_lin.weight"].T.reshape(dm, H, D),
                           "bias": sd[p + "attention.q_lin.bias"].reshape(H, D)},
                "k_proj": {"kernel": sd[p + "attention.k_lin.weight"].T.reshape(dm, H, D),
                           "bias": sd[p + "attention.k_lin.bias"].reshape(H, D)},
                "v_proj": {"kernel": sd[p + "attention.v_lin.weight"].T.reshape(dm, H, D),
                           "bias": sd[p + "attention.v_lin.bias"].reshape(H, D)},
                "o_proj": {"kernel": sd[p + "attention.out_lin.weight"].T.reshape(H, D, dm),
                           "bias": sd[p + "attention.out_lin.bias"]},
            },
            "mlp": {
                "up_proj": {"kernel": sd[p + "ffn.lin1.weight"].T, "bias": sd[p + "ffn.lin1.bias"]},
                "down_proj": {"kernel": sd[p + "ffn.lin2.weight"].T, "bias": sd[p + "ffn.lin2.bias"]},
            },
        }
    return params


def convert_bloom(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict:
    """HF ``BloomForCausalLM`` -> pytree: ALiBi attention, embedding
    layernorm, per-head-interleaved fused qkv (H, 3, D)."""
    sd = _strip_prefix(sd, ("transformer.",))
    H, D, dm = cfg.n_heads, cfg.head_dim, cfg.d_model
    ln = lambda i: _norm_name(cfg, i)
    params: Dict[str, Any] = {
        "wte": sd["word_embeddings.weight"],
        ln(0): {"scale": sd["word_embeddings_layernorm.weight"], "bias": sd["word_embeddings_layernorm.bias"]},
        ln(1): {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
    }
    for i in range(cfg.n_layers):
        p = f"h.{i}."
        qkv_w = sd[p + "self_attention.query_key_value.weight"].reshape(H, 3, D, dm)
        qkv_b = sd[p + "self_attention.query_key_value.bias"].reshape(H, 3, D)
        attn = {}
        for j, name in enumerate(("q_proj", "k_proj", "v_proj")):
            attn[name] = {"kernel": np.transpose(qkv_w[:, j], (2, 0, 1)), "bias": qkv_b[:, j]}
        attn["o_proj"] = {"kernel": sd[p + "self_attention.dense.weight"].T.reshape(H, D, dm),
                          "bias": sd[p + "self_attention.dense.bias"]}
        params[f"layer_{i}"] = {
            ln(0): {"scale": sd[p + "input_layernorm.weight"], "bias": sd[p + "input_layernorm.bias"]},
            ln(1): {"scale": sd[p + "post_attention_layernorm.weight"],
                    "bias": sd[p + "post_attention_layernorm.bias"]},
            "attn": attn,
            "mlp": {
                "up_proj": {"kernel": sd[p + "mlp.dense_h_to_4h.weight"].T,
                            "bias": sd[p + "mlp.dense_h_to_4h.bias"]},
                "down_proj": {"kernel": sd[p + "mlp.dense_4h_to_h.weight"].T,
                              "bias": sd[p + "mlp.dense_4h_to_h.bias"]},
            },
        }
    return params


_CONVERTERS = {
    "gpt2": convert_gpt2,
    "opt": convert_opt,
    "gpt_neox": convert_gpt_neox,
    "gptj": convert_gptj,
    "falcon": convert_falcon,
    "phi": convert_phi,
    "bloom": convert_bloom,
    "gpt_bigcode": convert_gpt_bigcode,
    "phi3": convert_phi3,
    "bert": convert_bert,
    "gpt_neo": convert_gpt_neo,
    "distilbert": convert_distilbert,
}


def convert_hf_state_dict(sd: Dict[str, np.ndarray], cfg: TransformerConfig, model_type: str) -> Dict:
    # llama/mistral/qwen2/mixtral/gemma share one key layout
    conv = _CONVERTERS.get(model_type, convert_llama)
    return conv(sd, cfg)


# ----------------------------------------------------------------------
# top-level loaders
# ----------------------------------------------------------------------
def load_hf_checkpoint(model_dir: str, dtype=None, mesh=None, shard: bool = False,
                       **config_overrides) -> Tuple[CausalLM, Dict]:
    """Load an HF checkpoint directory into ``(CausalLM, params)``.

    ``shard=True`` device-puts the params with the model's TP/replication
    rules over ``mesh`` (or the active mesh) so large checkpoints are
    born sharded — the ``zero.Init``-at-load path the reference gets via
    meta tensors + ``load_checkpoint.py``.
    """
    with open(os.path.join(model_dir, "config.json")) as f:
        hf_cfg = json.load(f)
    # validate the architecture BEFORE the (potentially multi-GB) weight read
    cfg = config_from_hf(hf_cfg, dtype=dtype, **config_overrides)
    sd = load_hf_state_dict(model_dir)
    return _materialize_hf(hf_cfg, sd, cfg=cfg, dtype=dtype, mesh=mesh, shard=shard, origin=model_dir,
                           **config_overrides)


def load_hf_model(hf_model, dtype=None, mesh=None, shard: bool = False,
                  **config_overrides) -> Tuple[CausalLM, Dict]:
    """Convert a LIVE HF torch model object into ``(CausalLM, params)`` —
    the reference's primary ``deepspeed.init_inference(model=hf_model)``
    usage (``inference/engine.py:39``), without a save/load round-trip."""
    hf_cfg = hf_model.config.to_dict()
    sd = {k: _torch_to_numpy(v) for k, v in hf_model.state_dict().items()}
    return _materialize_hf(hf_cfg, sd, dtype=dtype, mesh=mesh, shard=shard,
                           origin=type(hf_model).__name__, **config_overrides)


def _materialize_hf(hf_cfg: Dict, sd: Dict[str, np.ndarray], cfg=None, dtype=None, mesh=None,
                    shard: bool = False, origin: str = "?", **config_overrides) -> Tuple[CausalLM, Dict]:
    if cfg is None:
        cfg = config_from_hf(hf_cfg, dtype=dtype, **config_overrides)
    params = convert_hf_state_dict(sd, cfg, hf_cfg.get("model_type", ""))
    model = CausalLM(cfg)
    n_params = sum(int(np.prod(v.shape)) for v in _flat_leaves(params))
    logger.info(f"load_hf_checkpoint: {hf_cfg.get('model_type')} {n_params / 1e6:.1f}M params from {origin}")
    if shard:
        params = shard_params(params, model, mesh=mesh)
    return model, params


def tp_shardings(params: Dict, model=None, mesh=None, tp_size: Optional[int] = None):
    """NamedShardings for a serving layout: TP rules over the ``tensor``
    axis when ``tp > 1``, fully replicated otherwise. The ONE mapping from
    TP rules to shardings — used by the v1 engine, v2 engine, hybrid
    engine, and :func:`shard_params` so layouts cannot drift."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import get_mesh_topology
    from ..runtime.zero.partition import fit_spec, match_partition_rule, specs_to_shardings
    from .auto_tp import get_tp_rules

    topo = mesh if mesh is not None else get_mesh_topology()
    tp = tp_size or topo.model_parallel_size
    if tp <= 1:
        specs = jax.tree_util.tree_map(lambda _: P(), params)
    else:
        rules = get_tp_rules(params, tp, model)

        def leaf_spec(path, leaf):
            names = tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            # a dim the tensor axis does not divide (vocab 50257) stays whole
            s = fit_spec(match_partition_rule(names, rules), tuple(getattr(leaf, "shape", ())), topo)
            return s if s is not None else P()

        specs = jax.tree_util.tree_map_with_path(leaf_spec, params)
    return specs_to_shardings(specs, topo)


def shard_params(params: Dict, model=None, mesh=None, tp_size: Optional[int] = None):
    """Device-put a host param tree with TP rules applied (born sharded)."""
    import jax

    return jax.device_put(params, tp_shardings(params, model, mesh=mesh, tp_size=tp_size))


def _flat_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)
