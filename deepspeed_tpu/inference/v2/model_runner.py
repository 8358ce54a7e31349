"""Functional ragged forward over a ``CausalLM`` parameter tree.

Parity: reference ``inference/v2/model_implementations/`` builds its own
inference-only model graph (LayerContainer + policy) instead of running
the training module — same stance here: the runner consumes the flax
param pytree directly (``models/transformer.py`` layout) and executes a
paged-KV forward built from jnp ops + the Pallas paged-attention kernel.
Two jitted programs per model:

- ``prefill``: (1, S) tokens of one sequence chunk; standard causal
  attention against the gathered paged context (supports chunked prefill
  with history), KV written to pages via slot mapping.
- ``decode``: (B, 1) tokens, one per sequence; Pallas paged decode.

MoE blocks route through the same top-k gate + dispatch/combine einsums
as training, but with ``drop_tokens=False`` — serving must never drop a
token (reference ragged MoE kernels,
``inference/v2/kernels/ragged_ops/{moe_scatter,moe_gather,top_k_gating}``).

Tensor parallelism (reference ``v2/model_implementations/sharding/``):
with ``mesh``/``tp`` set, the projections/MLP/MoE partition under GSPMD
from the params' shardings, and the Pallas decode kernel runs under
``shard_map`` with heads split over the ``tensor`` axis (paged attention
is embarrassingly parallel over heads).
"""

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from ...comm.collectives import tp_all_reduce
from ...models.transformer import (TransformerConfig, alibi_slopes, apply_rope, scaled_rope_frequencies)
from ...ops.pallas.paged_attention import (kv_layer, kv_set_layer, paged_attention_decode,
                                           paged_attention_mixed, paged_attention_prefill,
                                           update_kv_pages)
from ...ops.registry import REGISTRY
from ...telemetry.tracing import region
from .modules import _norm_p, _proj, build_modules

_SHARD_MAP_KW = {"check_vma": False}


@dataclasses.dataclass(frozen=True)
class TPContext:
    """Explicit-collective tensor-parallel execution context.

    When set, the per-layer stack of every serving forward runs inside one
    ``shard_map`` region over ``axis``: attention heads / MLP hidden dims
    arrive pre-sharded (the params' GSPMD shardings, mirrored in
    ``param_specs``), the paged KV pool is sharded over its KV-head dim,
    block tables / token operands are replicated, and the two row-parallel
    partial sums per layer go through ``comm.collectives.tp_all_reduce``
    (optionally quantized / chunk-interleaved). Embedding and unembed stay
    outside the region under plain GSPMD — the vocab-sharded gather and
    head projection are exactly what XLA already handles well.
    """

    mesh: Any                 # jax.sharding.Mesh
    tp: int
    axis: str = "tensor"
    bits: int = 0             # DS_TPU_TP_ALLREDUCE_BITS (0 = full precision)
    interleave: int = 1       # chunks per activation allreduce (T3 seam)
    param_specs: Any = None   # PartitionSpec pytree over the layer_* subtree

    def signature(self) -> str:
        """Cache-key / fingerprint identity of this sharded program class."""
        axes = ",".join(f"{a}{s}" for a, s in
                        zip(self.mesh.axis_names, self.mesh.devices.shape) if s > 1)
        return f"tp{self.tp}:{self.axis}:b{self.bits}:il{self.interleave}:mesh[{axes}]"


def _attn_fns(cfg: TransformerConfig, interpret: bool, mesh, tp: int, window, slopes=None):
    """(decode_attn, prefill_attn, native) for one window value — shared by
    the ragged and fused forwards so both hot paths bake identical kernel
    variants (gpt-neo alternates global/local; qwen2 windows a layer
    suffix: each value is its own kind of layer and bakes its own variant).
    ``slopes`` overrides the baked ALiBi slopes (the manual-TP stack bakes
    each shard's dynamic slice; tracer-valued slopes are legal in the
    kernels)."""
    if mesh is not None and tp > 1:
        # heads split over `tensor`: each shard decodes its own heads
        # against its KV-page shard (ref v2 sharding helpers). Per-shard
        # slope slices aren't expressible as a baked constant, so ALiBi/
        # window models route through the gather path under TP.
        tp_decode_attn = shard_map(
            functools.partial(paged_attention_decode, interpret=interpret, scale=cfg.attn_scale),
            mesh=mesh, in_specs=(P(None, "tensor", None), P(None, None, "tensor", None),
                                 P(None, None, "tensor", None), P(None, None), P(None)),
            out_specs=P(None, "tensor", None), **_SHARD_MAP_KW)
        return tp_decode_attn, None, False
    if slopes is None and cfg.pos_emb == "alibi":
        slopes = alibi_slopes(cfg.n_heads)
    decode = functools.partial(paged_attention_decode, interpret=interpret, scale=cfg.attn_scale,
                               alibi_slopes=slopes, window=window)
    # interpret mode (CPU dev serving) keeps the compute-bound prefill on
    # the fused XLA gather path — emulating the page-walk kernel there is
    # strictly slower; on real TPU the kernel avoids the context gather
    prefill = None if interpret else functools.partial(
        paged_attention_prefill, scale=cfg.attn_scale, alibi_slopes=slopes, window=window)
    return decode, prefill, True


def _row_parallel(p: Dict, tp_reduce):
    """(projection params, deferred bias) of a row-parallel projection.
    Under manual TP every shard holds the whole bias but only a partial
    product: the bias must add ONCE, after ``tp_reduce`` sums the partials
    (inside the projection it would count ``tp`` times)."""
    if tp_reduce is None or "bias" not in p:
        return p, None
    return {k: v for k, v in p.items() if k != "bias"}, p["bias"]


def _transformer_layer(cfg: TransformerConfig, lp: Dict, x: jnp.ndarray, k_pages_i: jnp.ndarray,
                       v_pages_i: jnp.ndarray, slot_mapping: jnp.ndarray, cos, sin, positions: jnp.ndarray,
                       attn_apply, mods, moe: bool, tp_reduce=None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One transformer block over (B, S) tokens against this layer's page
    pool: qkv + rope + KV page write + ``attn_apply(q, kp, vp)`` + FFN.
    The attention itself is a caller closure so the ragged (single-mode)
    and fused (mixed decode+prefill) forwards share everything else —
    one weight read per layer regardless of how rows are batched.
    ``tp_reduce`` (the manual-TP stack) sums the two row-parallel partials
    — attention output after o_proj, FFN/MoE output after down_proj —
    across the tensor axis; head/hidden geometry is read off the arrays,
    so the same code runs full-size or shard-local."""
    B, S = x.shape[:2]
    dtype = cfg.dtype
    h = mods.norm(cfg, _norm_p(cfg, lp, 0), x)
    q = _proj(h, lp["attn"]["q_proj"], "bsd,dhk->bshk", dtype)
    k = _proj(h, lp["attn"]["k_proj"], "bsd,dhk->bshk", dtype)
    v = _proj(h, lp["attn"]["v_proj"], "bsd,dhk->bshk", dtype)
    if cfg.clip_qkv is not None:  # olmo: clamp projections before rope
        q, k, v = (jnp.clip(t, -cfg.clip_qkv, cfg.clip_qkv) for t in (q, k, v))
    if cfg.qk_norm:  # qwen3: per-head rms before rope
        rms = REGISTRY.get("rms_norm")
        scale = lambda p: 1.0 + p["scale"].astype(jnp.float32) if cfg.rms_offset else p["scale"]  # as ``Attention`` has them
        q = rms(q, scale(lp["attn"]["q_norm"]), cfg.norm_eps).astype(dtype)
        k = rms(k, scale(lp["attn"]["k_norm"]), cfg.norm_eps).astype(dtype)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, cos, sin, positions, rotary_dim=cfg.rotary_dim, style=cfg.rope_style)
        k = apply_rope(k, cos, sin, positions, rotary_dim=cfg.rotary_dim, style=cfg.rope_style)

    KVH, D = k.shape[-2], k.shape[-1]  # shard-local under manual TP
    kp, vp = update_kv_pages(k_pages_i, v_pages_i, k.reshape(B * S, KVH, D), v.reshape(B * S, KVH, D),
                             slot_mapping)

    attn = attn_apply(q, kp, vp)
    o_proj, o_bias = _row_parallel(lp["attn"]["o_proj"], tp_reduce)
    attn_out = _proj(attn, o_proj, "bshk,hkd->bsd", dtype)
    if tp_reduce is not None:
        attn_out = tp_reduce(attn_out)
        if o_bias is not None:
            attn_out = attn_out + o_bias.astype(dtype)

    if cfg.block_type == "parallel_shared":  # falcon-7b / phi / gpt-j
        ffn_in = h
    elif cfg.block_type == "parallel":  # gpt-neox parallel residual
        ffn_in = mods.norm(cfg, _norm_p(cfg, lp, 1), x)
    else:
        x = x + attn_out
        ffn_in = mods.norm(cfg, _norm_p(cfg, lp, 1), x)
    down_bias = None
    if moe:
        ffn_out = mods.moe(cfg, lp["moe"], ffn_in)
    else:
        down_proj, down_bias = _row_parallel(lp["mlp"]["down_proj"], tp_reduce)
        ffn_out = mods.mlp(cfg, {**lp["mlp"], "down_proj": down_proj}, ffn_in)
    if tp_reduce is not None:
        ffn_out = tp_reduce(ffn_out)
        if down_bias is not None:
            ffn_out = ffn_out + down_bias.astype(dtype)
    if cfg.block_type in ("parallel", "parallel_shared"):
        x = x + attn_out + ffn_out
    else:
        x = x + ffn_out
    return x, kp, vp


def _stack_body(cfg: TransformerConfig, interpret: bool, *, mixed: bool, decode: bool = False,
                n_dec: int = 0, chunk: int = 0, mesh=None, tp: int = 1, tp_local=None):
    """The per-layer transformer stack shared by all three serving forwards.

    Returns ``body(layer_params, x, k_pages, v_pages, block_tables,
    ctx_lens, slot_mapping, positions) -> (x, k_pages, v_pages)``.
    ``mixed`` selects the fused decode+prefill attention
    (``paged_attention_mixed``); otherwise the ragged single-mode module
    routing runs with the ``decode`` flag. ``tp_local = (axis, tp, bits,
    interleave)`` makes the body shard-local: it is then the region of a
    ``shard_map`` over ``axis`` — per-shard ALiBi slopes are sliced by
    ``axis_index``, head/hidden geometry is read off the (local) arrays,
    and the two per-layer partial sums reduce through ``tp_all_reduce``.
    ``mesh``/``tp`` are the legacy GSPMD arguments (weight-quantized TP
    keeps that path: ``custom_partitioning`` matmuls cannot run inside a
    manual shard_map region)."""
    mods = build_modules()
    tp_reduce = None
    if tp_local is not None:
        axis, tp_n, bits, interleave = tp_local
        tp_reduce = functools.partial(tp_all_reduce, group=axis, bits=bits, interleave=interleave)

    @functools.cache
    def layer_fn(window, moe: bool):
        """One KIND of layer (its window, dense or MoE) as one traced function,
        called once a layer with that layer's parameters and pages: ``jax.jit``
        keys its trace on the abstract arguments, so the Python below runs once
        a kind and program, the program holds one ``jit`` equation a layer on
        one shared jaxpr, lowering emits one function, and XLA inlines the
        calls. Everything a layer reads of the step comes in as an argument;
        only statics are closed over. Under the legacy GSPMD arguments XLA
        would partition a computation with several call sites once, as a
        function, without its callers in view (``models/transformer.py``
        ``block_fn``): there the cached equations are replayed into the
        program instead (``inline``), which is then the unrolled one."""

        def layer(lp, x, k_pages_i, v_pages_i, block_tables, ctx_lens, slot_mapping, positions, cos, sin, slopes):
            with region("block", site="serve"):  # the Python body: once a trace, not once a call
                return layer_body(lp, x, k_pages_i, v_pages_i, block_tables, ctx_lens, slot_mapping, positions, cos, sin, slopes)

        def layer_body(lp, x, k_pages_i, v_pages_i, block_tables, ctx_lens, slot_mapping, positions, cos, sin, slopes):
            # shard-local kernels bake the shard's slice of the slopes, a traced value; the others the whole table
            decode_attn, prefill_attn, decode_native = _attn_fns(
                cfg, interpret, mesh, tp, window, slopes if tp_local is not None else None)

            if mixed:
                def attn_apply(q, kp, vp):
                    out = paged_attention_mixed(q[0], kp, vp, block_tables, ctx_lens, positions[0],
                                                n_dec=n_dec, chunk=chunk, scale=cfg.attn_scale,
                                                alibi_slopes=slopes, window=window, decode_fn=decode_attn,
                                                prefill_fn=prefill_attn, native=decode_native)
                    return out[None]  # (1, T, H, D)
            else:
                def attn_apply(q, kp, vp):
                    return mods.attention(cfg, q, kp, vp, block_tables, ctx_lens, positions,
                                          decode=decode, slopes=slopes, decode_attn=decode_attn,
                                          decode_native=decode_native, prefill_attn=prefill_attn,
                                          window=window)

            return _transformer_layer(cfg, lp, x, k_pages_i, v_pages_i, slot_mapping, cos, sin, positions,
                                      attn_apply, mods, moe, tp_reduce=tp_reduce)

        return jax.jit(layer, inline=mesh is not None and tp > 1)

    def body(layer_params, x, k_pages, v_pages, block_tables, ctx_lens, slot_mapping, positions):
        cos = sin = None
        if cfg.pos_emb == "rope":
            cos, sin = scaled_rope_frequencies(cfg, cfg.rotary_dim)
        # slopes feed the gather-based attention used for prefill and for
        # the GSPMD-sharded decode; the native decode kernels bake them
        slopes = jnp.asarray(alibi_slopes(cfg.n_heads)) if cfg.pos_emb == "alibi" else None
        if tp_local is not None and slopes is not None:
            hs = cfg.n_heads // tp_n
            slopes = jax.lax.dynamic_slice(slopes.astype(jnp.float32),
                                           (jax.lax.axis_index(axis) * hs,), (hs,))
        for i in range(cfg.n_layers):
            x, kp, vp = layer_fn(cfg.window_for(i), cfg.moe_for(i))(
                layer_params[f"layer_{i}"], x, kv_layer(k_pages, i), kv_layer(v_pages, i), block_tables,
                ctx_lens, slot_mapping, positions, cos, sin, slopes)
            k_pages = kv_set_layer(k_pages, i, kp)
            v_pages = kv_set_layer(v_pages, i, vp)
        return x, k_pages, v_pages

    return body


def _run_stack(cfg: TransformerConfig, params: Dict, x, k_pages, v_pages, block_tables,
               ctx_lens, slot_mapping, positions, *, mixed: bool, decode: bool = False,
               n_dec: int = 0, chunk: int = 0, interpret: bool = False, mesh=None,
               tp: int = 1, tp_ctx: Optional[TPContext] = None):
    """Run the layer stack, under ``shard_map`` when a TPContext is set.

    The region covers exactly the per-layer loop: params arrive sharded
    per their GSPMD specs, the KV pools split over their KV-head dim, and
    every host-shaped operand (tokens already embedded into ``x``, block
    tables, context lengths, slots, positions) is replicated. ``x`` comes
    back replicated — the final layer's psum already made it so."""
    layer_params = {k: v for k, v in params.items() if k.startswith("layer_")}
    if tp_ctx is not None and tp_ctx.tp > 1:
        body = _stack_body(cfg, interpret, mixed=mixed, decode=decode, n_dec=n_dec, chunk=chunk,
                           tp_local=(tp_ctx.axis, tp_ctx.tp, tp_ctx.bits, tp_ctx.interleave))
        kv_spec = P(None, None, None, tp_ctx.axis, None)
        specs = tp_ctx.param_specs if tp_ctx.param_specs is not None else \
            jax.tree.map(lambda _: P(), layer_params)
        run = shard_map(body, mesh=tp_ctx.mesh,
                        in_specs=(specs, P(), kv_spec, kv_spec, P(), P(), P(), P()),
                        out_specs=(P(), kv_spec, kv_spec), **_SHARD_MAP_KW)
        return run(layer_params, x, k_pages, v_pages, block_tables, ctx_lens,
                   slot_mapping, positions)
    body = _stack_body(cfg, interpret, mixed=mixed, decode=decode, n_dec=n_dec, chunk=chunk,
                       mesh=mesh, tp=tp)
    return body(layer_params, x, k_pages, v_pages, block_tables, ctx_lens,
                slot_mapping, positions)


def ragged_forward(cfg: TransformerConfig, params: Dict, input_ids: jnp.ndarray, positions: jnp.ndarray,
                   k_pages: jnp.ndarray, v_pages: jnp.ndarray, block_tables: jnp.ndarray, ctx_lens: jnp.ndarray,
                   slot_mapping: jnp.ndarray, last_token_idx: jnp.ndarray, *, decode: bool,
                   interpret: bool = False, mesh=None, tp: int = 1,
                   tp_ctx: Optional[TPContext] = None) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One engine step over the paged cache.

    input_ids/positions: (B, S); k_pages/v_pages: (L, N, bs, KVH, D) — or
    the int8 ``(codes, scales)`` pools (``kv_quant_bits=8``), which thread
    through every program here as a pytree with unchanged signatures;
    block_tables: (B, P); ctx_lens: (B,) context length *including* the
    current tokens; slot_mapping: (B*S,) flat KV slots for the new tokens;
    last_token_idx: (B,) index of the last real (non-pad) token per row.
    Returns (last-real-token logits (B, V), k_pages, v_pages).
    """
    mods = build_modules()
    x = mods.embedding(cfg, params, input_ids, positions)
    x, k_pages, v_pages = _run_stack(cfg, params, x, k_pages, v_pages, block_tables, ctx_lens,
                                     slot_mapping, positions, mixed=False, decode=decode,
                                     interpret=interpret, mesh=mesh, tp=tp, tp_ctx=tp_ctx)
    return mods.unembed(cfg, params, x, last_token_idx), k_pages, v_pages


def fused_forward(cfg: TransformerConfig, params: Dict, input_ids: jnp.ndarray, positions: jnp.ndarray,
                  k_pages: jnp.ndarray, v_pages: jnp.ndarray, block_tables: jnp.ndarray, ctx_lens: jnp.ndarray,
                  slot_mapping: jnp.ndarray, last_flat: jnp.ndarray, *, n_dec: int, chunk: int,
                  interpret: bool = False, mesh=None, tp: int = 1,
                  tp_ctx: Optional[TPContext] = None) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """SplitFuse mixed step: decode rows AND chunked-prefill rows in ONE
    forward over the paged pool — every layer reads its weights once for
    the whole ragged token batch (the Dynamic SplitFuse point: prefill
    FLOPs keep decode's weight reads fed, and the host dispatches a
    single program per scheduler quantum).

    input_ids/positions/slot_mapping: (T,) flat token batch — flat slots
    [0, n_dec) are single-token decode rows; the remainder is the prefill
    segment, (n_pre, chunk) row-major. block_tables: (N, P) and
    ctx_lens/last_flat: (N,) are per-ROW (N = n_dec + n_pre, decode rows
    first); ``last_flat`` holds the flat index of each row's last real
    token. Returns ((N, V) fp32 next-token logits, k_pages, v_pages).
    """
    mods = build_modules()
    x = mods.embedding(cfg, params, input_ids[None], positions[None])  # (1, T, d)
    x, k_pages, v_pages = _run_stack(cfg, params, x, k_pages, v_pages, block_tables, ctx_lens,
                                     slot_mapping, positions[None], mixed=True, n_dec=n_dec,
                                     chunk=chunk, interpret=interpret, mesh=mesh, tp=tp,
                                     tp_ctx=tp_ctx)
    # per-row last-token hidden states -> (N, 1, d) so the unembed module's
    # (batch, seq) contract holds for the ragged flat batch
    x_last = x[0, last_flat][:, None, :]
    zeros = jnp.zeros((last_flat.shape[0],), jnp.int32)
    return mods.unembed(cfg, params, x_last, zeros), k_pages, v_pages


def spec_verify_forward(cfg: TransformerConfig, params: Dict, input_ids: jnp.ndarray, positions: jnp.ndarray,
                        k_pages: jnp.ndarray, v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                        ctx_lens: jnp.ndarray, slot_mapping: jnp.ndarray, *, chunk: int,
                        interpret: bool = False, mesh=None, tp: int = 1,
                        tp_ctx: Optional[TPContext] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Speculative-decode verify pass: every row is a ``chunk = K+1``-token
    tail (carry token + K drafts) of a live decoded sequence, run as a
    chunked-prefill-with-history segment through the same
    ``paged_attention_mixed`` machinery as the fused step — chunked
    prefill against existing context IS verification. Unlike
    ``fused_forward`` (which unembeds one position per row), acceptance
    needs logits at EVERY position, so the whole flat batch unembeds:
    returns ((T, V) fp32 logits, k_pages, v_pages) with T = B * chunk.
    """
    mods = build_modules()
    x = mods.embedding(cfg, params, input_ids[None], positions[None])  # (1, T, d)
    x, k_pages, v_pages = _run_stack(cfg, params, x, k_pages, v_pages, block_tables, ctx_lens,
                                     slot_mapping, positions[None], mixed=True, n_dec=0,
                                     chunk=chunk, interpret=interpret, mesh=mesh, tp=tp,
                                     tp_ctx=tp_ctx)
    # unembed every flat position: (T, 1, d) rows through the module's
    # (batch, seq) contract — T is small (rows x (K+1)), so the full
    # (T, V) logit block stays cheap and the acceptance math runs in-graph
    x_all = x[0][:, None, :]
    zeros = jnp.zeros((x_all.shape[0],), jnp.int32)
    return mods.unembed(cfg, params, x_all, zeros), k_pages, v_pages


def _stamp_cost_meta(fn, **meta):
    """Attach program-class metadata for the performance accountant's
    cost cards (telemetry/costs.py): the roofline report labels each
    bucket with its kind + static shape instead of a bare signature."""
    try:
        fn._cost_meta = meta
    except Exception:
        pass  # a backend whose jit wrapper rejects attributes loses labels only
    return fn


def make_spec_verify_fn(cfg: TransformerConfig, interpret: bool = False, mesh=None, tp: int = 1, *,
                        chunk: int, do_sample: bool = False, temperature: float = 1.0,
                        top_k: int = 0, top_p: float = 1.0, tp_ctx: Optional[TPContext] = None):
    """Jitted single-dispatch K-token verify (speculative decoding).

    One program per (chunk, sampling) signature: the verify forward
    scores all ``chunk = K+1`` positions per row, then device-side
    acceptance (``spec.select_committed``) picks each row's accepted
    draft count and its bonus/correction token in-graph — the host reads
    back one (B, chunk) int32 token block plus a (B,) int32 count, the
    same small-readback discipline as the fused burst. ``n_draft`` caps
    acceptance per row so short/padded draft windows never commit pad
    positions; rejected tail positions are rolled back by the state
    manager after the dispatch.
    """
    from .spec import select_committed

    fwd = functools.partial(spec_verify_forward, cfg, chunk=chunk, interpret=interpret, mesh=mesh,
                            tp=tp, tp_ctx=tp_ctx)

    def verify(params, ids, positions, k_pages, v_pages, block_tables, ctx, slots, n_draft, rng):
        # ids/positions/slots: (T,) flat, T = B * chunk; block_tables (B, P);
        # ctx/n_draft: (B,)
        logits, k_pages, v_pages = fwd(params, ids, positions, k_pages, v_pages,
                                       block_tables, ctx, slots)
        B = ctx.shape[0]
        lg = logits.reshape(B, chunk, -1)
        drafts = ids.reshape(B, chunk)[:, 1:]
        committed, accepted = select_committed(lg, drafts, n_draft, rng, do_sample=do_sample,
                                               temperature=temperature, top_k=top_k, top_p=top_p)
        return committed, accepted.astype(jnp.int32), k_pages, v_pages

    return _stamp_cost_meta(jax.jit(verify, donate_argnums=(3, 4)),
                            kind="spec_verify", chunk=chunk, sampled=do_sample)


def make_step_fns(cfg: TransformerConfig, interpret: bool = False, mesh=None, tp: int = 1,
                  tp_ctx: Optional[TPContext] = None):
    """Jitted (prefill_fn, decode_fn) with donated page buffers."""
    prefill = jax.jit(functools.partial(ragged_forward, cfg, decode=False, interpret=interpret,
                                        mesh=mesh, tp=tp, tp_ctx=tp_ctx),
                      donate_argnums=(3, 4), static_argnames=())
    decode = jax.jit(functools.partial(ragged_forward, cfg, decode=True, interpret=interpret,
                                       mesh=mesh, tp=tp, tp_ctx=tp_ctx),
                     donate_argnums=(3, 4), static_argnames=())
    return (_stamp_cost_meta(prefill, kind="prefill"),
            _stamp_cost_meta(decode, kind="decode"))


def make_burst_fn(cfg: TransformerConfig, interpret: bool = False, mesh=None, tp: int = 1,
                  do_sample: bool = False, temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                  tp_ctx: Optional[TPContext] = None):
    """Jitted multi-step fused decode (greedy or sampled).

    Runs ``steps`` paged-decode steps entirely on device under one
    dispatch: each step's device-side token choice (argmax, or the shared
    ``sample_logits`` when sampling) feeds the next step's input ids,
    positions/context lengths advance in-graph, and the per-step KV slots
    arrive precomputed because the host allocates blocks for the whole
    burst up front. Returns the (B, steps) tokens plus the updated page
    pool.

    The reference hides per-step launch latency with CUDA-graph replay
    (``inference/engine.py:524``) and an async scheduler in front of
    ``engine_v2.py:107``; the TPU-native form is one compiled
    ``lax.scan`` program, which also amortizes the host<->device readback
    to ``1/steps`` of a token per step.
    """
    from ..generation import sample_logits

    fwd = functools.partial(ragged_forward, cfg, decode=True, interpret=interpret, mesh=mesh,
                            tp=tp, tp_ctx=tp_ctx)

    def burst(params, ids0, positions0, k_pages, v_pages, block_tables, ctx0, slots, last, rng):
        # ids0/positions0 (B, 1); ctx0/last (B,); slots (steps, B)
        def step(carry, slots_t):
            ids, kp, vp, off, rng = carry
            logits, kp, vp = fwd(params, ids, positions0 + off, kp, vp, block_tables,
                                 ctx0 + off, slots_t, last)
            rng, step_rng = jax.random.split(rng)
            nxt = sample_logits(logits, step_rng, do_sample, temperature, top_k, top_p).astype(jnp.int32)
            return (nxt[:, None], kp, vp, off + 1, rng), nxt

        carry0 = (ids0, k_pages, v_pages, jnp.int32(0), rng)
        (_, k_pages, v_pages, _, _), toks = jax.lax.scan(step, carry0, slots)
        return toks.T, k_pages, v_pages

    return _stamp_cost_meta(jax.jit(burst, donate_argnums=(3, 4)),
                            kind="decode_burst", sampled=do_sample)


def make_fused_step_fn(cfg: TransformerConfig, interpret: bool = False, mesh=None, tp: int = 1, *,
                       n_dec: int, n_pre: int, chunk: int, do_sample: bool = False,
                       temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                       tp_ctx: Optional[TPContext] = None):
    """ONE dispatched program per scheduler quantum (Dynamic SplitFuse).

    The program runs the mixed prefill+decode pass (``fused_forward``),
    samples every row's next token on device, then advances the batch
    ``steps - 1`` further paged-decode steps under ``lax.scan`` — the
    step count is carried by the (steps-1, N) follow-on slot table's
    shape, so one jit wrapper serves the whole power-of-two burst ladder.
    Finished rows (== ``eos_id``; pass -1 to disable) are masked with
    ``lax.cond``-gated compute (whole-batch early-out) plus garbage-slot
    KV writes and a frozen token carry, and the only host readback is the
    final (N, steps) int32 token block — one int per sequence per step.

    ``n_dec``/``n_pre``/``chunk`` are the PADDED bucket shapes (static:
    they fix the decode/prefill split inside the traced program); the
    engine LRU-caches one wrapper per (bucket, sampling) signature like
    the burst programs.
    """
    from ..generation import sample_logits

    fwd = functools.partial(fused_forward, cfg, n_dec=n_dec, chunk=chunk,
                            interpret=interpret, mesh=mesh, tp=tp, tp_ctx=tp_ctx)
    dec_fwd = functools.partial(ragged_forward, cfg, decode=True, interpret=interpret, mesh=mesh,
                                tp=tp, tp_ctx=tp_ctx)
    n_rows = n_dec + n_pre

    def fused(params, ids, positions, k_pages, v_pages, block_tables, ctx, slots0, last_flat,
              adv_slots, garbage_slots, eos_id, rng):
        # ids/positions/slots0: (T,) flat; block_tables (N, P); ctx/last_flat/
        # garbage_slots (N,); adv_slots (steps-1, N); eos_id () int32 (-1 = off)
        logits, k_pages, v_pages = fwd(params, ids, positions, k_pages, v_pages,
                                       block_tables, ctx, slots0, last_flat)
        rng, r0 = jax.random.split(rng)
        tok0 = sample_logits(logits, r0, do_sample, temperature, top_k, top_p).astype(jnp.int32)
        done0 = tok0 == eos_id
        zeros_last = jnp.zeros((n_rows,), jnp.int32)

        def step(carry, slots_t):
            toks, done, kp, vp, off, rng = carry
            slots_w = jnp.where(done, garbage_slots, slots_t)

            def run(kp, vp):
                return dec_fwd(params, toks[:, None], (ctx + off)[:, None], kp, vp,
                               block_tables, ctx + off + 1, slots_w, zeros_last)

            def skip(kp, vp):
                return jnp.zeros_like(logits), kp, vp

            lg, kp, vp = jax.lax.cond(jnp.all(done), skip, run, kp, vp)
            rng, r = jax.random.split(rng)
            nxt = sample_logits(lg, r, do_sample, temperature, top_k, top_p).astype(jnp.int32)
            nxt = jnp.where(done, toks, nxt)  # finished rows repeat their eos
            done = done | (nxt == eos_id)
            return (nxt, done, kp, vp, off + 1, rng), nxt

        carry0 = (tok0, done0, k_pages, v_pages, jnp.int32(0), rng)
        (_, _, k_pages, v_pages, _, _), rest = jax.lax.scan(step, carry0, adv_slots)
        toks = jnp.concatenate([tok0[:, None], rest.T], axis=1)  # (N, steps)
        return toks, k_pages, v_pages

    return _stamp_cost_meta(jax.jit(fused, donate_argnums=(3, 4)),
                            kind="fused_step", n_dec=n_dec, n_pre=n_pre,
                            chunk=chunk, sampled=do_sample)
