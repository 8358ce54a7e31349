#!/usr/bin/env python3
"""Chip smoke: the trainer and the v2 ragged server on a real TPU, end to end.

    python chip_smoke.py              # one chip: kernels, trainer, server
    python chip_smoke.py --chips 4    # four chips: ZeRO-3 fsdp=4 and tensor_parallel=4,
                                      # each against its one-device run, and nothing else
    python chip_smoke.py --rehearse   # CPU, tiny sizes, interpreted kernels: checks this
                                      # script's control flow, can never print the ok line
    python chip_smoke.py --only hybrid   # one phase by name: the hybrid KDA / MLA / routed-FFN model of the
                                         # benchmark's second configuration against its plain reference
    python chip_smoke.py --only latent   # ... and its third's: rotated latent attention in every layer, 6 of 64 experts
    python chip_smoke.py --only deltanet  # ... and its fourth's: Gated DeltaNet 3:1 with gated GQA, softmax top-10 of 512
    python chip_smoke.py --only sparse   # ... and its fifth's: GQA over the keys a learned indexer chooses, 8 of 128 experts
    python chip_smoke.py --only sambay   # ... and its sixth's: Mamba-1 scans, differential attention, a second half that reads the first's
    python chip_smoke.py --only blockdiff  # ... and its seventh's: block-diffusion training, GQA under the block mask over a doubled row, 8 of 128 experts
    python chip_smoke.py --only mixed    # ... and its eighth's: one full layer without positions to three rotated window layers at 16,384 rows, ReGLU experts behind an early router
    python chip_smoke.py --only shortconv  # ... and its ninth's: gated short convolutions three layers in four beside GQA 32/8 of 64 at 16,384 rows, a biased sigmoid router of 4 in 32, a tied head
    python chip_smoke.py --only mamba2   # ... and its tenth's: Mamba-2 (SSD) scans, blocks of ONE part, ungated relu^2 experts 6 of 128 behind a biased sigmoid router, GQA 32/2 without positions

Everything runs in this one process (a chip belongs to one process), at the
full width and depth of GPT-2-124M, on weights and data made from ``--seed``.
Each phase prints one JSON line (name, ok, seconds, compile seconds, persistent
compile-cache hits/misses, what was compared and the error found). A failed
phase is reported and the remaining phases still run, but the exit code is then
non-zero and the last line is never the success line. On success the last line
of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "<device_kind>", "count": N}}

Without a TPU the script exits non-zero before any phase. The persistent
compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache_tpu`` (``deepspeed_tpu/utils/compile_cache.py``).
"""

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time
import traceback

TOL_PAGED = 0.03       # |kernel - gather reference| on O(1) bf16 attention outputs (1 bf16 ulp at 4.0)
TOL_NORM = 0.02        # |a - b| / (1 + |b|) on bf16 layer-norm outputs and input gradients
TOL_ADAM = 1e-3        # max|a - b| / max|b| of update/lr and of each moment, fp32 fused Adam vs the plain update
TOL_LOSS = 0.02        # |loss_pallas - loss_xla| on a bf16 GPT-2 loss of ~10.9
TOL_LOSS_SHARDED = 0.05  # per-step |loss_4dev - loss_1dev| over 3 bf16 Adam steps
F32_HEADROOM = 2.5     # float32-referenced rule: err(ours vs f32) <= 2.5 x err(plain bf16 vs f32)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Shapes of one run. ``FULL`` is GPT-2-124M and the llama-7B attention
    geometry; ``TINY`` exists only for ``--rehearse``."""
    vocab: int = 50257
    layers: int = 12
    heads: int = 12
    d_model: int = 768
    seq: int = 1024
    micro_bs: int = 8
    train_steps: int = 6
    mha: tuple = (8, 1024, 12, 12, 64)     # B, S, H, KVH, D
    gqa: tuple = (2, 4096, 32, 4, 128)
    kv_block: int = 128
    pool_blocks: int = 64
    prefill_chunk: int = 128
    adam_leaves: tuple = ((50257, 768), (1024, 768), (768, 3072), (3072,), (768,))
    n_requests: int = 8
    prompt_lens: tuple = (64, 512)
    new_tokens: int = 32
    sharded_steps: int = 3
    tp_requests: int = 4


FULL = Sizes()
TINY = Sizes(vocab=509, layers=2, heads=4, d_model=64, seq=128, micro_bs=4, train_steps=3,
             mha=(2, 128, 4, 4, 16), gqa=(1, 256, 4, 2, 16), kv_block=16, pool_blocks=24,
             prefill_chunk=16, adam_leaves=((509, 64), (64,)), n_requests=3, prompt_lens=(8, 40),
             new_tokens=6, sharded_steps=2, tp_requests=2)


def _parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes with interpreted kernels; never prints the ok line")
    ap.add_argument("--only", default=None, help="run the phases whose name contains this, and no other")
    return ap.parse_args()


ARGS = _parse_args()
REHEARSE = ARGS.rehearse
SZ = TINY if REHEARSE else FULL
if REHEARSE:
    # a rehearsal never takes a chip; four host devices stand in for --chips 4
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count={ARGS.chips}").strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from deepspeed_tpu.utils.compile_cache import enable_compilation_cache  # noqa: E402

CACHE_DIR = None if REHEARSE else enable_compilation_cache(jax)  # before the first compile
_COMPILE_SECS = [0.0]
monitoring.register_event_duration_secs_listener(
    lambda event, secs, **_: _COMPILE_SECS.__setitem__(0, _COMPILE_SECS[0] + secs)
    if event == "/jax/core/compile/backend_compile_duration" else None)


# ----------------------------------------------------------------------
# phase runner
# ----------------------------------------------------------------------
def _cache_counts():
    from deepspeed_tpu.telemetry import get_registry

    reg = get_registry()
    return (int(reg.peek("compile_cache_hits_total") or 0), int(reg.peek("compile_cache_misses_total") or 0))


FAILED = []


def run_phase(name, fn):
    """Run one phase and print its JSON line. A failure is recorded (the run
    then exits non-zero) and later phases still run: one call of the chip
    shows every fault, not the first."""
    from deepspeed_tpu.parallel.mesh import reset_mesh

    reset_mesh()  # every phase builds its own mesh; kernels consult the global one (ops/placement.py)
    hits0, miss0 = _cache_counts()
    c0, t0 = _COMPILE_SECS[0], time.perf_counter()
    line = {"phase": name, "ok": True}
    try:
        line.update(fn() or {})
    except Exception as e:  # noqa: BLE001 - reported, and the exit code carries it
        FAILED.append(name)
        line.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
        traceback.print_exc(file=sys.stderr)
    hits1, miss1 = _cache_counts()
    stats = jax.devices()[0].memory_stats() or {}
    line.update(seconds=round(time.perf_counter() - t0, 2), compile_seconds=round(_COMPILE_SECS[0] - c0, 2),
                cache_hits=hits1 - hits0, cache_misses=miss1 - miss0,
                hbm_gb={"in_use": round(stats.get("bytes_in_use", 0) / 2**30, 2),
                        "peak_so_far": round(stats.get("peak_bytes_in_use", 0) / 2**30, 2)})
    print(json.dumps(line), flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def max_abs(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


def scaled_err(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b) / (1.0 + jnp.abs(b))))


def rel_err(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))


def pad_to_block(n):
    return -(-n // SZ.kv_block) * SZ.kv_block


def padded_ids(rows, width):
    return jnp.asarray(np.array([r + [0] * (width - len(r)) for r in rows], np.int32))


def f32_rule(ours, plain, truth, floor=1e-6):
    """The float32-referenced rule: both contestants are bf16, so each is judged
    against a float32 computation of the same math, and ours fails only if its
    error clearly exceeds the plain bf16 path's own. A structural kernel bug is
    orders of magnitude off; a fixed absolute gate flags bf16 rounding."""
    err_ours, err_plain = max_abs(ours, truth), max_abs(plain, truth)
    return err_ours, err_plain, err_ours <= F32_HEADROOM * max(err_plain, floor)


def count_custom_calls(jitted, *args):
    """tpu_custom_call sites in the program ``jitted`` compiles for ``args``
    (abstract arguments are enough: the module is lowered, not compiled)."""
    return jitted.lower(*args).as_text().count("tpu_custom_call")


def abstract(tree):
    def one(x):
        if not hasattr(x, "shape"):
            return x
        sharding = x.sharding if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return jax.tree_util.tree_map(one, tree)


def gpt2_config(dtype=jnp.bfloat16):
    from deepspeed_tpu.models import TransformerConfig

    return TransformerConfig(vocab_size=SZ.vocab, n_layers=SZ.layers, n_heads=SZ.heads, d_model=SZ.d_model,
                             max_seq_len=SZ.seq, dtype=dtype)


def init_params(model):
    return model.init(jax.random.PRNGKey(ARGS.seed), {"input_ids": np.zeros((1, SZ.seq), np.int32)})


class plain_ops:
    """Force every registry op that has one onto its plain ``xla``
    implementation for the duration — the reference side of a comparison."""

    def __enter__(self):
        from deepspeed_tpu.ops.registry import REGISTRY

        self._names = [n for n, impls in REGISTRY._ops.items() if any(i.name == "xla" for i in impls)]
        self._prev = {n: REGISTRY.set_impl(n, "xla") for n in self._names}

    def __exit__(self, *exc):
        from deepspeed_tpu.ops.registry import REGISTRY

        for n in self._names:
            REGISTRY.set_impl(n, self._prev[n])


# ----------------------------------------------------------------------
# step 2: kernels of the main path against the plain references
# ----------------------------------------------------------------------
def _flash_check(shape):
    """flash_attention forward + gradients against ``attention_xla``, judged by
    the float32-referenced rule. The references run one KV-head group at a
    time (groups are independent), so the (B, H, S, S) float32 logits of the
    GQA shape never exist at once."""
    from deepspeed_tpu.ops.attention import attention_xla
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, KVH, D = shape
    G = H // KVH
    ks = jax.random.split(jax.random.PRNGKey(ARGS.seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KVH, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KVH, D), jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, S, H, D), jnp.bfloat16)

    def fwd_bwd(attn):
        def run(q, k, v, do):
            o, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, causal=True), q, k, v)
            return (o,) + vjp(do.astype(o.dtype))
        return jax.jit(run)

    ours = fwd_bwd(lambda q, k, v, **kw: flash_attention(q, k, v, interpret=REHEARSE, **kw))
    n_calls = count_custom_calls(ours, q, k, v, do)
    got = ours(q, k, v, do)
    plain = fwd_bwd(attention_xla)

    def by_group(dtype):
        parts = []
        for j in range(KVH):
            sl = slice(j * G, (j + 1) * G)
            parts.append(plain(q[:, :, sl].astype(dtype), k[:, :, j:j + 1].astype(dtype),
                               v[:, :, j:j + 1].astype(dtype), do[:, :, sl].astype(dtype)))
        return [jnp.concatenate([p[i] for p in parts], axis=2) for i in range(4)]

    ref_bf16 = by_group(jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        ref_f32 = by_group(jnp.float32)
    out, bad = {}, []
    for name, a, b, t in zip(("o", "dq", "dk", "dv"), got, ref_bf16, ref_f32):
        err_ours, err_plain, ok = f32_rule(a, b, t)
        out[name] = {"ours_vs_f32": err_ours, "xla_bf16_vs_f32": err_plain}
        if not ok or not bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))):
            bad.append(name)
    check(not bad, f"flash {shape}: {bad} exceed {F32_HEADROOM}x the plain bf16 error: {out}")
    check(REHEARSE or n_calls >= 2, f"flash {shape}: expected fwd+bwd kernels, found {n_calls} custom calls")
    return {"shape_BSHKD": list(shape), "compared": "flash_attention fwd+bwd vs attention_xla, f32-referenced",
            "errors": out, "custom_calls": n_calls}


def _paged_inputs(n_rows, kvq=0):
    """A pool of random pages at GPT-2 widths and ragged per-row contexts."""
    from deepspeed_tpu.ops.pallas.paged_attention import quantize_kv

    H, D, bs, N = SZ.heads, SZ.d_model // SZ.heads, SZ.kv_block, SZ.pool_blocks
    pages_per_seq = SZ.seq // bs
    rng = np.random.RandomState(ARGS.seed)
    kp = jax.random.normal(jax.random.PRNGKey(1), (N, bs, H, D), jnp.bfloat16)
    vp = jax.random.normal(jax.random.PRNGKey(2), (N, bs, H, D), jnp.bfloat16)
    if kvq:
        kp, vp = quantize_kv(kp), quantize_kv(vp)
    tables = jnp.asarray(rng.randint(1, N, size=(n_rows, pages_per_seq)).astype(np.int32))
    return H, D, bs, kp, vp, tables, rng


def _paged_decode_check(kvq):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention_decode, paged_attention_ref

    B = 8
    H, D, bs, kp, vp, tables, rng = _paged_inputs(B, kvq)
    ctx = jnp.asarray(rng.randint(1, SZ.seq + 1, size=(B,)).astype(np.int32))
    q = jax.random.normal(jax.random.PRNGKey(3), (B, H, D), jnp.bfloat16)
    fn = jax.jit(lambda q, kp, vp: paged_attention_decode(q, kp, vp, tables, ctx, interpret=REHEARSE))
    n_calls = count_custom_calls(fn, q, kp, vp)
    got = fn(q, kp, vp)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_ref(q[:, None], kp, vp, tables, ctx, (ctx - 1)[:, None])[:, 0]
    err = max_abs(got, ref)
    check(err <= TOL_PAGED, f"paged decode (kv_quant={kvq}) vs gather reference: {err} > {TOL_PAGED}")
    check(REHEARSE or n_calls >= 1, "paged decode lowered without a kernel")
    return {"compared": "paged_attention_decode vs paged_attention_ref", "kv_quant_bits": kvq,
            "max_abs_err": err, "tol": TOL_PAGED, "custom_calls": n_calls}


def _paged_chunk_inputs(n_pre):
    """Chunked-prefill rows that continue a partially written context."""
    S = SZ.prefill_chunk
    H, D, bs, kp, vp, tables, rng = _paged_inputs(n_pre)
    q0 = rng.randint(0, SZ.seq - S + 1, size=(n_pre,)).astype(np.int32)
    qpos = jnp.asarray(q0[:, None] + np.arange(S, dtype=np.int32)[None])
    ctx = jnp.asarray(q0 + S)
    q = jax.random.normal(jax.random.PRNGKey(4), (n_pre, S, H, D), jnp.bfloat16)
    return q, kp, vp, tables, ctx, qpos


def _paged_prefill_check():
    from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention_prefill, paged_attention_ref,
                                                          prefill_path)

    q, kp, vp, tables, ctx, qpos = _paged_chunk_inputs(4)
    path = prefill_path(q.shape[1], q.shape[2], q.shape[3])
    check(path == "kernel", f"a {q.shape[1]}-token chunk must take the kernel, took {path}")
    fn = jax.jit(lambda q, kp, vp: paged_attention_prefill(q, kp, vp, tables, ctx, qpos, interpret=REHEARSE))
    n_calls = count_custom_calls(fn, q, kp, vp)
    got = fn(q, kp, vp)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_ref(q, kp, vp, tables, ctx, qpos)
    err = max_abs(got, ref)
    check(err <= TOL_PAGED, f"paged prefill vs gather reference: {err} > {TOL_PAGED}")
    check(REHEARSE or n_calls >= 1, "paged prefill lowered without a kernel")
    return {"compared": "paged_attention_prefill vs paged_attention_ref", "chunk": int(q.shape[1]),
            "max_abs_err": err, "tol": TOL_PAGED, "custom_calls": n_calls}


def _paged_mixed_check():
    """The fused step's attention: decode rows and chunked-prefill rows of one
    flat token batch, as ``model_runner._stack_body`` calls it."""
    import functools

    from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention_decode, paged_attention_mixed,
                                                          paged_attention_prefill, paged_attention_ref)

    n_dec, n_pre, S = 8, 2, SZ.prefill_chunk
    qp, kp, vp, tables_p, ctx_p, qpos_p = _paged_chunk_inputs(n_pre)
    H, D, _, _, _, tables_d, rng = _paged_inputs(n_dec)
    ctx_d = jnp.asarray(rng.randint(1, SZ.seq + 1, size=(n_dec,)).astype(np.int32))
    qd = jax.random.normal(jax.random.PRNGKey(5), (n_dec, H, D), jnp.bfloat16)
    q = jnp.concatenate([qd, qp.reshape(n_pre * S, H, D)], axis=0)
    tables = jnp.concatenate([tables_d, tables_p], axis=0)
    ctx = jnp.concatenate([ctx_d, ctx_p], axis=0)
    pos = jnp.concatenate([ctx_d - 1, qpos_p.reshape(-1)], axis=0)
    fn = jax.jit(lambda q, kp, vp: paged_attention_mixed(
        q, kp, vp, tables, ctx, pos, n_dec=n_dec, chunk=S,
        decode_fn=functools.partial(paged_attention_decode, interpret=REHEARSE),
        prefill_fn=functools.partial(paged_attention_prefill, interpret=REHEARSE)))
    n_calls = count_custom_calls(fn, q, kp, vp)
    got = fn(q, kp, vp)
    with jax.default_matmul_precision("highest"):
        ref_d = paged_attention_ref(qd[:, None], kp, vp, tables_d, ctx_d, (ctx_d - 1)[:, None])[:, 0]
        ref_p = paged_attention_ref(qp, kp, vp, tables_p, ctx_p, qpos_p).reshape(n_pre * S, H, D)
    err = max_abs(got, jnp.concatenate([ref_d, ref_p], axis=0))
    check(err <= TOL_PAGED, f"paged mixed vs gather reference: {err} > {TOL_PAGED}")
    check(REHEARSE or n_calls >= 2, f"paged mixed: expected a decode and a prefill kernel, found {n_calls}")
    return {"compared": "paged_attention_mixed vs paged_attention_ref", "n_dec": n_dec, "n_pre": n_pre, "chunk": S,
            "max_abs_err": err, "tol": TOL_PAGED, "custom_calls": n_calls}


def _layer_norm_check():
    """The kernel the v2 server's ``norm_tpu`` dispatches for GPT-2, at the
    fused step's (1, T, d_model) bf16 shape with bf16 scale and bias."""
    from deepspeed_tpu.ops.pallas.norms import layer_norm, layer_norm_xla

    T, d = 8 + 2 * SZ.prefill_chunk, SZ.d_model
    ks = jax.random.split(jax.random.PRNGKey(ARGS.seed + 6), 3)
    x = (2.0 * jax.random.normal(ks[0], (1, T, d), jnp.float32) + 0.5).astype(jnp.bfloat16)
    w = (1.0 + 0.1 * jax.random.normal(ks[1], (d,), jnp.float32)).astype(jnp.bfloat16)
    b = (0.1 * jax.random.normal(ks[2], (d,), jnp.float32)).astype(jnp.bfloat16)
    ours = lambda x: layer_norm(x, w, b, 1e-5, interpret=REHEARSE)  # noqa: E731
    plain = lambda x: layer_norm_xla(x, w, b)  # noqa: E731
    dx = lambda ln: jax.jit(jax.grad(lambda x: (ln(x).astype(jnp.float32) ** 2).sum() * 1e-3))  # noqa: E731
    n_calls = count_custom_calls(jax.jit(ours), x)
    x32 = x.astype(jnp.float32)
    err_fwd = scaled_err(jax.jit(ours)(x), plain(x32))
    err_bwd = scaled_err(dx(ours)(x), dx(plain)(x32))
    check(err_fwd <= TOL_NORM and err_bwd <= TOL_NORM,
          f"layer_norm vs plain float32: fwd {err_fwd}, dx {err_bwd} > {TOL_NORM}")
    check(REHEARSE or n_calls >= 1, "layer_norm lowered without a kernel")
    return {"compared": "layer_norm fwd + dx vs layer_norm_xla in float32", "shape": [1, T, d],
            "scaled_err_fwd": err_fwd, "scaled_err_dx": err_bwd, "tol": TOL_NORM, "custom_calls": n_calls}


def _fused_adam_check():
    """fused_adam_flat at GPT-2's leaf shapes, two steps (the step is traced:
    one program serves every step), against the plain update."""
    from deepspeed_tpu.ops.pallas.fused_adam import adam_xla, fused_adam_flat

    worst, n_calls = 0.0, 0
    for i, shape in enumerate(SZ.adam_leaves):
        ks = jax.random.split(jax.random.PRNGKey(ARGS.seed + 10 + i), 2)
        p = 0.02 * jax.random.normal(ks[0], shape, jnp.float32)
        g = 1e-3 * jax.random.normal(ks[1], shape, jnp.float32)
        m = v = jnp.zeros(shape, jnp.float32)
        ours = jax.jit(lambda p, g, m, v, step: fused_adam_flat(p, g, m, v, 1e-4, step, weight_decay=0.01,
                                                                interpret=REHEARSE))
        plain = jax.jit(lambda p, g, m, v, step: adam_xla(p, g, m, v, 1e-4, step, weight_decay=0.01))
        n_calls += count_custom_calls(ours, p, g, m, v, jnp.int32(1))
        a = b = (p, m, v)
        for step in (1, 2):
            a = ours(a[0], g, a[1], a[2], jnp.int32(step))
            b = plain(b[0], g, b[1], b[2], jnp.int32(step))
        # the update is ~lr against parameters of ~0.02: compare the DELTA, not the parameter
        worst = max([worst, rel_err(a[0] - p, b[0] - p)] + [rel_err(x, y) for x, y in zip(a[1:], b[1:])])
    check(worst <= TOL_ADAM, f"fused_adam_flat vs plain Adam: relative error {worst} > {TOL_ADAM}")
    check(REHEARSE or n_calls >= len(SZ.adam_leaves), "fused_adam_flat lowered without a kernel")
    return {"compared": "fused_adam_flat vs adam_xla, 2 steps, update/lr and moments", "leaves": list(SZ.adam_leaves),
            "rel_err": worst, "tol": TOL_ADAM, "custom_calls": n_calls}


KERNEL_PHASES = (
    ("kernel/flash_mha", lambda: _flash_check(SZ.mha)),
    ("kernel/flash_gqa", lambda: _flash_check(SZ.gqa)),
    ("kernel/fused_adam", _fused_adam_check),
    ("kernel/layer_norm", _layer_norm_check),
    ("kernel/paged_decode", lambda: _paged_decode_check(0)),
    ("kernel/paged_decode_int8", lambda: _paged_decode_check(8)),
    ("kernel/paged_prefill", _paged_prefill_check),
    ("kernel/paged_mixed", _paged_mixed_check),
)


# ----------------------------------------------------------------------
# step 3: the trainer takes steps
# ----------------------------------------------------------------------
def _train_config(stage, micro_bs, optimizer="adam", mesh=None):
    cfg = {"train_micro_batch_size_per_gpu": micro_bs, "gradient_accumulation_steps": 1,
           "bf16": {"enabled": True}, "optimizer": {"type": optimizer, "params": {"lr": 1e-4}},
           "zero_optimization": {"stage": stage}, "steps_per_print": 10**9}
    if mesh:
        cfg["mesh"] = mesh
    return cfg


def _train_batch(global_bs):
    rng = np.random.RandomState(ARGS.seed)
    return {"input_ids": rng.randint(0, SZ.vocab, size=(global_bs, SZ.seq)).astype(np.int32)}


def _take_steps(engine, batch, n):
    losses = []
    for _ in range(n):
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        losses.append(loss)
    jax.block_until_ready((losses, engine.params))
    return [float(x) for x in losses]


def trainer_phase():
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops.registry import REGISTRY

    model = CausalLM(gpt2_config())
    params = init_params(model)
    batch = _train_batch(SZ.micro_bs)
    # the same batch through the model on the plain XLA implementations, BEFORE
    # the engine takes (and donates) the parameters
    params_c = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    with plain_ops():
        loss_plain = float(jax.jit(model.loss_fn)(params_c, batch))
    del params_c

    # ZeRO-2, bf16, no gradient accumulation, with the optimizer the Pallas kernel serves
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                               config=_train_config(2, SZ.micro_bs, optimizer="fusedadam"))
    del params
    attention = REGISTRY.selected("attention")
    check(REHEARSE or attention == "pallas", f"attention resolved to {attention!r}, not the Pallas kernel")
    check(REHEARSE or REGISTRY.selected("fused_adam") == "pallas", "fused_adam did not resolve to the Pallas kernel")
    check(engine._fused_step is not None, "the one-dispatch fused step was not built")
    dev_batch = engine._put_batch(batch)
    scale = engine.loss_scaler.loss_scale / engine.gradient_accumulation_steps
    n_calls = count_custom_calls(engine._fused_step, abstract(engine.params), abstract(engine._compute_params()),
                                 abstract(engine.opt_state), abstract(dev_batch), 0, scale,
                                 1.0 / engine.loss_scaler.loss_scale, 1e-4)
    check(REHEARSE or n_calls > 0, "the compiled train step holds no tpu_custom_call")

    losses = _take_steps(engine, dev_batch, SZ.train_steps)
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall on a repeated batch: {losses}")
    diff = abs(losses[0] - loss_plain)
    check(diff <= TOL_LOSS, f"first-step loss {losses[0]} vs plain XLA {loss_plain}: {diff} > {TOL_LOSS}")
    check(int(engine.skipped_steps) == 0, "a step overflowed and was skipped")
    return {"model": f"gpt2 L{SZ.layers} d{SZ.d_model} H{SZ.heads} V{SZ.vocab} S{SZ.seq} bf16",
            "config": "zero2 bf16 fusedadam", "micro_bs": SZ.micro_bs, "steps": SZ.train_steps, "losses": losses,
            "compared": "first-step loss vs the same batch on the registry's xla implementations",
            "loss_plain_xla": loss_plain, "abs_diff": diff, "tol": TOL_LOSS, "attention": attention,
            "fused_adam": REGISTRY.selected("fused_adam"), "step_custom_calls": n_calls}


# ----------------------------------------------------------------------
# step 4: the server answers requests
# ----------------------------------------------------------------------
def _prompts(n):
    rng = np.random.RandomState(ARGS.seed + 1)
    lo, hi = SZ.prompt_lens
    lens = [lo, hi] + rng.randint(lo, hi + 1, size=max(0, n - 2)).tolist()  # both ends of the range, then ragged
    return [rng.randint(0, SZ.vocab, size=(m,)).tolist() for m in lens[:n]]


class _RecordedPrograms:
    """Record the abstract signature of every fused program the engine
    dispatches, so each can be lowered again afterwards and searched for its
    kernels (the engine keeps the jitted wrappers, not their arguments)."""

    def __init__(self, eng):
        self.seen = {}
        fused_for = eng._fused_for

        def recording(D, P, S, sampling):
            fn = fused_for(D, P, S, sampling)

            def dispatch(*args):
                steps = int(args[9].shape[0]) + 1  # adv_slots: (steps - 1, rows)
                self.seen.setdefault((D, P, S, steps), (fn, abstract(args)))
                return fn(*args)

            return dispatch

        eng._fused_for = recording

    def report(self):
        out = []
        for (D, P, S, steps), (fn, args) in sorted(self.seen.items()):
            raw = fn
            while not hasattr(raw, "lower"):
                raw = raw.__wrapped__
            out.append({"decode_rows": D, "prefill_rows": P, "chunk": S, "steps": steps,
                        "tpu_custom_calls": count_custom_calls(raw, *args)})
        return out


def _reference_logits(cfg_dtype, params, ids, last):
    """Last-prompt-position logits of ``model.apply`` for right-padded
    prompts (a causal model: padding after a position cannot reach it)."""
    from deepspeed_tpu.models import CausalLM

    model = CausalLM(gpt2_config(cfg_dtype))
    cast = jax.tree_util.tree_map(lambda x: x.astype(cfg_dtype), params)
    fn = jax.jit(lambda p, ids: model.apply(p, ids)[jnp.arange(ids.shape[0]), last].astype(jnp.float32))
    return fn(cast, ids)


def _check_engine(eng, tp=1):
    check(REHEARSE or eng._interpret is False, "the engine chose interpret mode: its kernels are not compiled")
    check(eng._fused_enabled, "the fused step is off")
    check(eng._tp == tp, f"tensor_parallel is {eng._tp}, wanted {tp}")


def _put_logits(eng, prompts, uid0=1000):
    """Whole-prompt prefill through ``put``: (n, V) next-token logits."""
    uids = list(range(uid0, uid0 + len(prompts)))
    logits = eng.put(uids, prompts)
    eng.flush(uids)
    return jnp.asarray(logits)


def server_phase():
    from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import _next_pow2
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops.pallas.paged_attention import prefill_path

    model = CausalLM(gpt2_config())
    params = init_params(model)
    hbm0 = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
    eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig())  # the defaults a user gets
    _check_engine(eng)
    jax.block_until_ready((eng.k_pages, eng.v_pages))
    pool = {"kv_blocks": eng._n_kv_blocks, "budget_gb": eng._config.state_manager.memory_gb,
            "logical_gb": round((eng.k_pages.nbytes + eng.v_pages.nbytes) / 2**30, 2),
            "engine_hbm_gb": round(((jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0) - hbm0) / 2**30, 2)}
    programs = _RecordedPrograms(eng)
    prompts = _prompts(SZ.n_requests)
    outs = eng.generate(prompts, max_new_tokens=SZ.new_tokens)
    check([len(o) for o in outs] == [SZ.new_tokens] * len(prompts),
          f"requests returned {[len(o) for o in outs]} tokens, wanted {SZ.new_tokens} each")
    fused = programs.report()
    check(fused, "generate dispatched no fused program")
    check(REHEARSE or all(p["tpu_custom_calls"] > 0 for p in fused), f"a fused program holds no kernel: {fused}")

    # logits of the last prompt position: engine.put against model.apply,
    # both judged against the float32 model on the plain implementations
    H, D = SZ.heads, SZ.d_model // SZ.heads
    paths = {len(p): prefill_path(max(16, _next_pow2(len(p))), H, D) for p in prompts}
    got = _put_logits(eng, prompts)
    ids = padded_ids(prompts, pad_to_block(max(len(p) for p in prompts)))
    last = jnp.asarray([len(p) - 1 for p in prompts])
    plain_bf16 = _reference_logits(jnp.bfloat16, params, ids, last)
    with plain_ops(), jax.default_matmul_precision("highest"):
        truth = _reference_logits(jnp.float32, params, ids, last)
    err_eng, err_model, ok = f32_rule(got, plain_bf16, truth, floor=1e-3)
    check(bool(jnp.all(jnp.isfinite(got))), "engine logits are not finite")
    check(ok, f"put() logits vs float32 model: {err_eng} > {F32_HEADROOM} x model.apply's own bf16 error {err_model}")

    # teacher-forced greedy: ONE dense forward over prompt + answer gives the
    # plain greedy choice at every generated position
    seqs = padded_ids([p + o for p, o in zip(prompts, outs)], pad_to_block(max(len(p) for p in prompts) + SZ.new_tokens))
    params_c = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    greedy = np.asarray(jax.jit(lambda p, s: jnp.argmax(model.apply(p, s), axis=-1))(params_c, seqs))
    match = [int(greedy[i, len(p) - 1 + t] == o[t]) for i, (p, o) in enumerate(zip(prompts, outs))
             for t in range(SZ.new_tokens)]
    first = [int(jnp.argmax(got[i])) == outs[i][0] for i in range(len(prompts))]
    return {"model": f"gpt2 L{SZ.layers} d{SZ.d_model} H{SZ.heads} V{SZ.vocab} bf16", "requests": len(prompts),
            "prompt_lens": [len(p) for p in prompts], "new_tokens": SZ.new_tokens,
            "interpret": eng._interpret, "fused_enabled": eng._fused_enabled, "kv_pool": pool, "fused_programs": fused,
            "put_prefill_path_by_prompt_len": paths,
            "compared": "put() last-position logits vs model.apply, f32-referenced",
            "engine_vs_f32": err_eng, "model_bf16_vs_f32": err_model, "engine_vs_model_bf16": max_abs(got, plain_bf16),
            "rule": f"engine_vs_f32 <= {F32_HEADROOM} x max(model_bf16_vs_f32, 1e-3)",
            "greedy_match_share": round(sum(match) / len(match), 4),
            "first_token_matches_put_argmax": f"{sum(first)}/{len(first)}"}


# ----------------------------------------------------------------------
# --chips 4: sharded training and sharded serving against one device
# ----------------------------------------------------------------------
def _device_shares(tree):
    """{device id: share of the tree's bytes that device holds}."""
    per_dev, total = {}, 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, "addressable_shards") or leaf.ndim == 0:
            continue
        total += leaf.nbytes
        for s in leaf.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    return {d: round(b / total, 4) for d, b in sorted(per_dev.items())}


def _check_quartered(shares, what, n):
    check(len(shares) == n, f"{what} lies on {len(shares)} devices, not {n}: {shares}")
    check(all(0.8 / n <= s <= 1.25 / n for s in shares.values()),
          f"{what}: a device holds far from 1/{n} of the bytes: {shares}")


def zero3_phase():
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.parallel.mesh import MeshTopology
    from deepspeed_tpu.runtime.config import MeshConfig

    n = ARGS.chips
    model = CausalLM(gpt2_config())
    batch = _train_batch(SZ.micro_bs)  # ONE global batch for both engines
    check(SZ.micro_bs % n == 0, "global batch must split over the chips")

    # the one-device run first, on an explicit mesh over jax.devices()[:1]
    one = MeshTopology(MeshConfig.from_dict({"data": 1}), devices=jax.devices()[:1])
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=init_params(model), mesh=one,
                                               config=_train_config(3, SZ.micro_bs))
    losses_one = _take_steps(engine, batch, SZ.sharded_steps)
    del engine
    gc.collect()

    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=init_params(model),
                                               config=_train_config(3, SZ.micro_bs // n, mesh={"fsdp": n}))
    check(engine.topology.axis_size("fsdp") == n and engine.topology.n_devices == n, "mesh is not fsdp=4")
    losses = _take_steps(engine, batch, SZ.sharded_steps)
    check(all(np.isfinite(losses + losses_one)), f"non-finite loss: {losses} / {losses_one}")
    diffs = [abs(a - b) for a, b in zip(losses, losses_one)]
    check(max(diffs) <= TOL_LOSS_SHARDED, f"fsdp={n} losses {losses} vs one device {losses_one}: {diffs}")
    p_shares, o_shares = _device_shares(engine.params), _device_shares(engine.opt_state)
    if not REHEARSE:  # every leaf of the tiny model is under stage 3's persistence threshold and stays whole
        _check_quartered(p_shares, "parameters", n)
    _check_quartered(o_shares, "optimizer state", n)
    return {"config": f"zero3 bf16 adam mesh fsdp={n}", "steps": SZ.sharded_steps, "losses": losses,
            "losses_one_device": losses_one, "compared": "per-step loss, same global batch", "abs_diffs": diffs,
            "tol": TOL_LOSS_SHARDED, "param_byte_share_by_device": p_shares,
            "opt_state_byte_share_by_device": o_shares}


def tp_phase():
    from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
    from deepspeed_tpu.models import CausalLM

    n = ARGS.chips
    model = CausalLM(gpt2_config())
    params = init_params(model)
    prompts = _prompts(SZ.tp_requests)
    res = {}
    for tp in (1, n):
        eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(tensor_parallel=tp))
        _check_engine(eng, tp)
        programs = _RecordedPrograms(eng)
        outs = eng.generate(prompts, max_new_tokens=SZ.new_tokens)
        check([len(o) for o in outs] == [SZ.new_tokens] * len(prompts), f"tp={tp}: short answers")
        res[tp] = {"outs": outs, "logits": _put_logits(eng, prompts), "fused": programs.report()}
        if tp > 1:
            check(eng._tp_ctx is not None, "tensor_parallel engine did not take the shard_map stack")
            shares = {"k": _device_shares(eng.k_pages), "v": _device_shares(eng.v_pages)}
            for name, s in shares.items():
                _check_quartered(s, f"{name} pool", n)
            layer_shares = _device_shares({k: v for k, v in eng.params.items() if k.startswith("layer_")})
            check(len(layer_shares) == n, f"layer weights lie on {len(layer_shares)} devices")
            check(REHEARSE or all(p["tpu_custom_calls"] > 0 for p in res[tp]["fused"]),
                  f"a tp={tp} fused program holds no kernel")
        del eng
        gc.collect()
    # two bf16 runs that differ only in the order of the row-parallel sums:
    # judge the sharded one against float32 like every other comparison here
    ids = padded_ids(prompts, pad_to_block(max(len(p) for p in prompts)))
    last = jnp.asarray([len(p) - 1 for p in prompts])
    with plain_ops(), jax.default_matmul_precision("highest"):
        truth = _reference_logits(jnp.float32, params, ids, last)
    err_tp, err_one, ok = f32_rule(res[n]["logits"], res[1]["logits"], truth, floor=1e-3)
    check(ok, f"tp={n} logits vs float32: {err_tp} > {F32_HEADROOM} x tp=1's own error {err_one}")
    same = [int(a == b) for x, y in zip(res[n]["outs"], res[1]["outs"]) for a, b in zip(x, y)]
    return {"config": f"tensor_parallel={n} vs 1", "requests": len(prompts), "new_tokens": SZ.new_tokens,
            "compared": "put() last-position logits, f32-referenced", "tp_vs_f32": err_tp, "tp1_vs_f32": err_one,
            "tp_vs_tp1": max_abs(res[n]["logits"], res[1]["logits"]),
            "kv_pool_byte_share_by_device": shares, "layer_weight_byte_share_by_device": layer_shares,
            "token_match_share_vs_tp1": round(sum(same) / len(same), 4), "fused_programs": res[n]["fused"]}


# ----------------------------------------------------------------------
# the benchmark's models of several layer kinds, each against its configuration's plain float32 reference
# ----------------------------------------------------------------------
# Limits from five seeds (0, 11, 101, 2024, 31337; my chip run, PR 32: published widths, 5 layers, 1 x 8192), each
# between the LARGEST reading of the program and the SMALLEST of the control: the plain reference with bf16 weights
# AND bf16 state, gates and router scores (``low_state``), the precision below the one the description states. Every
# measure is a norm over a whole tensor, |x - x_f32| / |x_f32|. The maximum of the logits' scaled error over 168 M
# values, the first measure, is a few tokens whose expert choice flipped: 0.54-0.68 for the program against
# 0.62-0.70 for the control, so it is reported and not judged. The router's own gradient is left out for the same
# reason: flips of the top 8 rule it in both (0.43 / 0.38 on seed 11).
HYBRID_LIMITS = {        # the program's readings | the control's
    "logits": 0.042,     # 0.0338-0.0371 | 0.0479-0.0527
    "A_log": 0.075,      # the decay's own parameter (layer 2, KDA): 0.0494-0.0598 | 0.0646-0.0936. The two nearly meet,
                         # so this limit has room above the program only; the control fails it on 2 seeds of 5
    "k_conv": 0.066,     # the short convolution (layer 2, KDA): 0.0522-0.0566 | 0.0779-0.0854
    "experts_wg": 0.217,  # a held expert (layer 2, routed): 0.176-0.200 | 0.236-0.267
    "kv_b_proj": 0.046,  # the latent's expansion (layer 4, MLA): 0.0355-0.0415 | 0.0513-0.0574
}


HYBRID_OWNERS = {"A_log": ("layer_1", "kda"), "k_conv": ("layer_1", "kda"), "experts_wg": ("layer_1", "routed"),
                 "kv_b_proj": ("layer_3", "mla")}  # a judged leaf -> (its layer, the kind of layer part that owns it)


def _hybrid_leaf(tree, name):
    layer, kind = HYBRID_OWNERS[name]
    leaf = tree[layer][kind][name]
    return (leaf["kernel"] if isinstance(leaf, dict) else leaf).astype(jnp.float32)


# Kimi-VL's language model (``--only latent``): rotated latent attention in all six layers, 6 of 64 experts with two
# shared. Every leaf is layer 2's, the first routed layer. Two controls, each the plain bf16 reference with one thing
# wrong, and each has to break a limit on every seed: the rotation left out (``no_rope``), and the norms' statistics,
# the router's scores and the gates in bf16 (``low_state``), the precision below the one the description states.
# Limits from five seeds (0, 11, 101, 2024, 31337; my chip runs, PR 34: published widths, 6 layers, 1 x 8192). Without
# the rotation every measure reads 0.64 to 1.32: nothing is near. The lower precision lies 4% to 15% above the program
# in every measure but a held expert's, where it is a fifth to a third worse, ON THE SAME SEED; but a seed moves all three
# contestants together by up to 10% (31337 reads highest everywhere), so an absolute limit separates the program from
# the lower precision only there: ``experts_wg`` sits between the two with room on both sides and is the limit the
# ``low_state`` control breaks on every seed. The others hold the program's largest reading with 5% of room and catch
# what is grossly wrong; limits set from the first three seeds alone (6% of room) failed the program on 31337.
LATENT_LIMITS = {            # the program's readings | ``low_state``'s | ``no_rope``'s
    "logits": 0.0504,        # 0.0422-0.0480 | 0.0447-0.0511 | 0.64-0.69
    "q_proj": 0.0717,        # every head's query, its rotated 64-part included: 0.0610-0.0683 | 0.0682-0.0764 | 1.04-1.06
    "kv_a_rotated": 0.0700,  # the 64 columns of ``kv_a_proj`` that make the ONE rotated key part: 0.0602-0.0667 | 0.0682-0.0752 | 1.28-1.32
    "kv_b_proj": 0.0608,     # the latent's expansion: 0.0486-0.0579 | 0.0541-0.0661 | 0.64-0.69
    "experts_wg": 0.158,     # a held expert's gate matrix: 0.136-0.146 | 0.172-0.184 | 1.12-1.14
    "shared_gate": 0.0678,   # the two shared experts' gate matrix: 0.0566-0.0646 | 0.0640-0.0715 | 0.92-0.95
}


def _latent_leaf(tree, name):
    mla, routed = tree["layer_1"]["mla"], tree["layer_1"]["routed"]
    if name == "kv_a_rotated":
        return mla["kv_a_proj"]["kernel"][:, mla["kv_a_norm"]["scale"].shape[0]:].astype(jnp.float32)
    leaf = routed["shared_gate_proj"] if name == "shared_gate" else routed[name] if name == "experts_wg" else mla[name]
    return (leaf["kernel"] if isinstance(leaf, dict) else leaf).astype(jnp.float32)


# Qwen3-Next's language model (``--only deltanet``): three Gated DeltaNet layers and one output-gated GQA layer, every
# FFN softmax-routed (10 of 512, 32 held) with a gated shared expert. The DeltaNet and routed leaves are layer 1's
# (0-indexed: the second DeltaNet layer), the attention's layer 3's. Three controls, each the plain bf16 reference
# with one thing wrong, and each has to break a limit on every seed: layer 2's decay left out (``no_decay``: every
# measure reads 0.88 to 1.80, nothing is near), the attention's output gate left out (``no_gate``: its own layer's
# ``attn_q_proj`` reads 0.99 and the logits 0.075-0.080, where the cell's mean first loss hardly sees it: PERF.md
# section 2), and the state, gates and router scores in bf16 (``low_state``), the precision below the one the
# description states: 10% to 45% above the program in the large leaves and the logits ON THE SAME SEED. Limits from five
# seeds (0, 11, 101, 2024, 31337; my chip runs, PR 39: published widths, 4 layers, 1 x 8192; set after the first two,
# in place for the last three), each between the program's largest reading and ``low_state``'s smallest but for the
# two small leaves, ``A_log`` (32 numbers) and ``shared_expert_gate`` (2,048), which a seed moves by 20% to 50%: theirs
# have room above the program only, and ``low_state`` breaks them on four and three seeds of five.
DELTANET_LIMITS = {              # the program's readings | ``low_state``'s | ``no_gate``'s | ``no_decay``'s
    "logits": 0.032,             # 0.0276-0.0281 | 0.0331-0.0401 | 0.075-0.080 | 0.88-0.93
    "A_log": 0.060,              # the decay's own parameter, one a value head: 0.0361-0.0561 | 0.0451-0.0747 | 0.043-0.093 | 1.0
    "k_conv": 0.055,             # the key's short convolution: 0.0469-0.0484 | 0.0547-0.0674 | 0.073-0.080 | 1.72-1.80
    "attn_q_proj": 0.0535,       # every head's query AND its gate (layer 3): 0.0451-0.0483 | 0.0551-0.0614 | 0.99 | 1.01-1.06
    "experts_wg": 0.152,         # the held experts' gate matrices: 0.1340-0.1437 | 0.1543-0.1804 | 0.139-0.165 | 1.28-1.30
    "shared_expert_gate": 0.060,  # the (2048, 1) gate of the shared expert: 0.0438-0.0525 | 0.0549-0.0756 | 0.069-0.099 | 1.17-1.24
}


DELTANET_OWNERS = {"A_log": "gdn", "k_conv": "gdn", "experts_wg": "routed", "shared_expert_gate": "routed"}  # of layer 1


def _deltanet_leaf(tree, name):
    if name == "attn_q_proj":
        return tree["layer_3"]["attn"]["q_proj"]["kernel"].astype(jnp.float32)  # every head's query and its gate
    leaf = tree["layer_1"][DELTANET_OWNERS[name]][name]
    return (leaf["kernel"] if isinstance(leaf, dict) else leaf).astype(jnp.float32)


# Keye-VL-2.0's language model (``--only sparse``): four layers of grouped-query attention over the 2,048 keys a learned
# indexer chooses for each query, every FFN softmax-routed (8 of 128, 16 held). The program's logits, its indexer's loss
# a layer and its first gradient in five leaves of layer 1, each against the float32 reference GIVEN THE PROGRAM'S CHOICE
# (``choice=``: so a key that bf16 scores moved across a query's threshold is not read as an error of the arithmetic; how
# often that happens is reported beside, ``choice_agreement``: the share of the program's chosen pairs that the float32
# reference, choosing for itself, chose too: 0.998, 0.943-0.948, 0.964-0.982, 0.985-0.988 by layer). Controls, each the
# plain bf16 reference with one thing wrong, and each has to break a limit on every seed: the indexer left out
# (``no_indexer``: dense causal attention), the indexer's loss left out (``no_index_loss``: the indexer's leaves take a
# zero gradient and read 1.0), half the keys a query (``half_keys``: its own choice of 1,024) and, given the program's
# choice like ours, the softmaxes' statistics, the indexer's scores and the router's in bf16 (``low_state``: the precision
# below the one the description states). Limits from five seeds (0, 11, 101, 2024, 31337; my chip runs, PR 42: published
# widths, 4 layers, 1 x 8192; provisional after the first two, these after all five).
# ``low_state`` lies 6.6% to 12.4% above the program in the logits ON THE SAME SEED and a seed moves both by 20%, so no
# absolute limit parts them (seed 2024: the program 0.00711, ``low_state`` 0.00799; seed 31337: the program 0.00852): the
# measure that judges it is the program's distance over ``low_state``'s on the same seed, where ``low_state`` reads 1 by
# construction. The gradients' limits have room above the program only: under ``remat`` the blocks' kept values are
# rounded to bf16 where XLA otherwise carries float32 between fusions (``q_proj`` 0.039 with ``remat`` off, the plain bf16
# reference's 0.041, 0.129 with it on; every leaf of every layer moves 1.2 to 3.3 times: PERF.md section 6, PR 42), so the
# plain references, ``low_state`` among them, read BELOW the program there. ``experts_wg`` is read and not judged: a seed
# on which the router's top 8 flip between bf16 and float32 reads 0.42 (the plain reference 0.22) where the others read 0.05.
SPARSE_LIMITS = {                # the program's readings | ``low_state``'s | the smallest of ``no_indexer``'s and ``half_keys``'s
    "logits": 0.012,             # 0.00711-0.00852 | 0.00799-0.00919 | 0.0946
    "logits_over_low_state": 0.97,  # the program's logits' distance over ``low_state``'s, same seed: 0.890-0.938 | 1 | 10.3
    "index_loss": 0.015,         # the largest over the layers of |L_I - L_I_f32| / L_I_f32: 0.0035-0.0050 | 0.0012-0.0056 | 0.249 (``no_indexer`` has none: 1.0)
    "q_proj": 0.20,              # 0.1125-0.1358 | 0.0432-0.0557 | 0.430
    "o_proj": 0.03,              # 0.0144-0.0186 | 0.0119-0.0127 | 0.102
    "index_q_proj": 0.08,        # the indexer's leaves, which ``no_index_loss`` leaves at zero (1.0): 0.0218-0.0390 | 0.0149-0.0270 | 0.421
    "index_k_proj": 0.06,        # 0.0153-0.0248 | 0.0109-0.0172 | 0.361
    "index_w_proj": 0.05,        # 0.0139-0.0180 | 0.0098-0.0128 | 0.362
}
SPARSE_REPORTED = ("experts_wg",)  # read and not judged
SPARSE_CONTROLS = {"no_indexer": ({"no_indexer": True}, False), "no_index_loss": ({"no_index_loss": True}, True),
                   "half_keys": ({"topk": None}, False), "low_state": ({"low_state": True}, True)}  # (what is wrong, given the choice?)
SPARSE_CONFIG = "benchmarks/configs/keye-vl2-30b-l4e16.json"


SPARSE_OWNERS = dict({name: "sparse" for name in ("q_proj", "o_proj", "index_q_proj", "index_k_proj", "index_w_proj")}, experts_wg="routed")  # of layer 1


def _sparse_leaf(tree, name):
    leaf = tree["layer_1"][SPARSE_OWNERS[name]][name]
    return (leaf["kernel"] if isinstance(leaf, dict) else leaf).astype(jnp.float32)


def sparse_readings(seed):
    """{measure: {"ours", <control>...}}, every one |x - x_f32| / |x_f32| (the indexer's loss: the largest over the
    layers), and the choice's agreement a layer."""
    from benchmarks.lib import manifest as mf, weights

    here = os.path.dirname(os.path.abspath(__file__))
    cfg = mf.load_json(os.path.join(here, SPARSE_CONFIG))
    if REHEARSE:
        r = cfg["rehearse"]
        over = dict(r["published"], sa_config=dict(cfg["sa_config"], **r["published"]["sa_config"]))
        cfg = dict(cfg, **over, program=dict(cfg["program"], **r["program"]), reference=r["reference"])
    ref = mf.load_module(os.path.join(here, cfg["reference"]["module"]))
    # the usual start, not the cell's small ``o_proj`` (which keeps the router's load even, PERF.md section 6): here the
    # attention has to weigh in the logits for a wrong choice to break a limit, and the limits were read at this start
    cfg = dict(cfg, program=dict(cfg["program"], sparse_out_init_scale=1.0))
    model = weights.build_model(cfg)
    seq, pub, rc = cfg["program"]["max_seq_len"], mf.published(cfg), cfg["reference"]
    ids = jnp.asarray(np.random.default_rng([seed, 5]).integers(0, cfg["program"]["vocab_size"], (1, seq), np.int32))
    params = jax.jit(lambda k: model.init(k, {"input_ids": np.zeros((1, seq), np.int32)}))(weights.seed_key(seed))
    judged = tuple(k for k in SPARSE_LIMITS if k not in ("logits", "logits_over_low_state", "index_loss")) + SPARSE_REPORTED
    rel = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32)) / jnp.maximum(jnp.linalg.norm(b.astype(jnp.float32)), 1e-30))
    leaves_of = lambda grads: {name: _sparse_leaf(grads, name) for name in judged}
    by_layer = lambda sown, name: [sown[f"layer_{i}"]["sparse"][name][0] for i in range(cfg["program"]["n_layers"])]

    def ours():
        logits, mods = jax.jit(lambda p: model.module.apply({"params": p}, ids, mutable=("intermediates", "losses")))(params)
        sown = mods["intermediates"]
        choice = [jnp.swapaxes(m, 1, 2) != 0 for m in by_layer(sown, "choice")]  # query-major, as the reference takes it
        grads = leaves_of(jax.jit(jax.grad(lambda p: model.loss_fn(p, {"input_ids": ids})))(params))
        return logits, [float(x) for x in by_layer(sown, "index_loss")], grads, choice

    def plain(dtype, choice, **over):
        (_, (_, losses, logits)), grads = ref.loss_and_grads(params, ids, pub, dict(rc, **over), dtype, choice)
        return logits, [float(x) for x in losses], leaves_of(grads)

    logits, losses, leaves, choice = ours()
    truth_logits, truth_losses, truth = plain(jnp.float32, choice)  # given the program's choice
    own = ref.forward(params, ids, pub, rc, jnp.float32)[2]           # the float32 reference choosing for itself
    agreement = [float(jnp.sum(a & b) / jnp.sum(a)) for a, b in zip(choice, own)]
    pairs = [int(jnp.sum(a)) for a in choice]
    del own
    readings = {name: {} for name in tuple(SPARSE_LIMITS) + SPARSE_REPORTED}

    def take(who, logits, losses, leaves):
        readings["logits"][who] = rel(logits, truth_logits)
        readings["index_loss"][who] = max(abs(a - b) / max(abs(b), 1e-30) for a, b in zip(losses, truth_losses))
        for name in judged:
            readings[name][who] = rel(leaves[name], truth[name])

    take("ours", logits, losses, leaves)
    del logits, leaves
    for who, (wrong, given) in dict(SPARSE_CONTROLS, plain=({}, True)).items():
        wrong = {k: (pub["sa_config"]["topk"] // 2 if v is None else v) for k, v in wrong.items()}
        take(who, *plain(jnp.bfloat16, choice if given else None, **wrong))
        gc.collect()
    readings["logits_over_low_state"] = {who: value / readings["logits"]["low_state"] for who, value in readings["logits"].items()}
    return readings, {"choice_agreement": agreement, "chosen_pairs": pairs, "index_loss_f32": truth_losses, "tokens": int(seq),
                      "topk": int(pub["sa_config"]["topk"])}


def sparse_phase():
    """``sparse_readings`` of ``--seed`` against ``SPARSE_LIMITS``: the program under every limit, every control over
    at least one; exactly ``sum_t min(t + 1, topk)`` pairs chosen a layer."""
    readings, facts = sparse_readings(ARGS.seed)
    report = {name: dict(readings[name], limit=limit) for name, limit in SPARSE_LIMITS.items()}
    over = lambda who: [k for k, v in report.items() if not v[who] <= v["limit"]]
    failed_ours, failed_controls = over("ours"), {name: over(name) for name in SPARSE_CONTROLS}
    facts["not_judged"] = {name: readings[name] for name in SPARSE_REPORTED}
    seq, topk = facts["tokens"], min(facts["tokens"], facts["topk"])
    check(all(n == topk * (topk + 1) // 2 + (seq - topk) * topk for n in facts["chosen_pairs"]),
          f"a query did not get exactly its keys: {facts['chosen_pairs']} pairs a layer at {seq} positions")
    if not REHEARSE:  # the limits are the published widths'
        check(not failed_ours, f"the sparse model lies further from its float32 reference than allowed in {failed_ours}: {report}")
        passed = [name for name, failed in failed_controls.items() if not failed]
        check(not passed, f"the controls {passed} (the plain reference with one thing wrong) passed every limit: they prove nothing: {report}")
    return dict(facts, compared=report, control_failed=failed_controls)


# Phi-4-mini-flash-reasoning's six layers (``--only sambay``): two Mamba-1 scan layers, differential attention under a
# window and full, a gated memory unit and differential cross-attention, which read the scan's output and the full layer's
# keys and values. EVERY leaf of the gradient is read (90: a leaf's name is its path) and 78 are judged, a limit a class of
# leaf (its path without the layer: ``SAMBAY_CLASS_LIMITS``, else ``SAMBAY_LEAF_LIMIT``). NOT judged, only reported (a limit
# of None): the twelve lambda vectors. A layer's four are ONE scalar, dL/dlambda, times fixed vectors; at a random start the
# two maps are nearly equal and that scalar is a sum over 8,192 tokens x 20 heads x 128 that cancels to near zero, so its
# relative error reads the cancellation and not the arithmetic: 0.002 to 0.61 for the program over five seeds, 0.0008 to
# 0.57 for the bf16-state control (the CPU tests hold these leaves in float32, where nothing cancels into rounding).
# Five controls, each the plain bf16 reference with one thing wrong, and each has to break a limit on every seed: the
# window layer attending every earlier key (``no_window``), lambda = 0 (``no_lambda``), the memory taken after the ``z``
# gate (``gated_memory``), a cross layer attending layer 17's projections of its OWN input (``own_keys``): each of these
# four reads 0.08 to 1.1 in the logits and in 77 or more of the 78 leaves; and the scan's state, step and decay in bf16
# (``low_state``), the precision below the one the configuration states, which moves the scan's own small leaves and
# nothing else: ``A_log`` 0.131 to 0.797 where the program reads 0.037 to 0.047, so its limit lies between the two with
# 1.6 times of room on either side and is the limit ``low_state`` breaks on every seed (it also breaks the general limit in
# ``x_proj``, ``dt_proj`` or ``dt_bias`` of one or both scan layers on every seed read). Limits from four seeds (0, 11,
# 101, 2024; my chip runs, PR 46: published widths, 6 layers, 1 x 8192), checked on a fifth: the program's 78 judged
# leaves read 0.0026 to 0.0519 (most 0.034 to 0.048: bf16's rounding through six layers; the final norm's bias 0.003)
# and the logits 0.0259 to 0.0270; the general limit holds the largest with a quarter of room.
SAMBAY_PARTS = {"ssm": ("in_proj", "conv_kernel", "conv_bias", "x_proj", "dt_proj", "dt_bias", "A_log", "D", "out_proj"),
                "diff": ("q_proj", "k_proj", "v_proj", "o_proj", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln"),
                "gmu": ("in_proj", "out_proj"), "diff_cross": ("q_proj", "o_proj", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln")}
SAMBAY_PARTS["diff_window"] = SAMBAY_PARTS["diff"]
SAMBAY_MIXERS = ("ssm", "diff_window", "ssm", "diff", "gmu", "diff_cross")
SAMBAY_LEAF_LIMIT = 0.065    # the program's readings | the four structural controls' smallest | ``low_state``'s
SAMBAY_CLASS_LIMITS = {
    "logits": 0.04,          # 0.0259-0.0270 | own_keys 0.081, gated_memory 0.152, no_lambda 0.385, no_window 0.862 | 0.0254-0.0265
    "ssm/A_log": 0.08,       # 0.0371-0.0467 | 0.124-0.99 | 0.1308-0.7969
}


def _sambay_names():
    names = ["wte", "LayerNorm_0/scale", "LayerNorm_0/bias"]
    for i, mixer in enumerate(SAMBAY_MIXERS):
        names += [f"layer_{i}/{norm}/{leaf}" for norm in ("LayerNorm_0", "LayerNorm_1") for leaf in ("scale", "bias")]
        names += [f"layer_{i}/{mixer}/{leaf}" for leaf in SAMBAY_PARTS[mixer]] + [f"layer_{i}/mlp/{leaf}" for leaf in ("gate_proj", "up_proj", "down_proj")]
    return names


def _sambay_class(name):
    return name.split("/", 1)[1] if name.startswith("layer_") else name


SAMBAY_LIMITS = {"logits": SAMBAY_CLASS_LIMITS["logits"],
                 **{name: None if "/lambda_" in name else SAMBAY_CLASS_LIMITS.get(_sambay_class(name), SAMBAY_LEAF_LIMIT) for name in _sambay_names()}}


def _sambay_leaf(tree, name):
    leaf = functools.reduce(lambda node, key: node[key], name.split("/"), tree)
    if isinstance(leaf, dict):  # a product's kernel, or the sub-norm's scale
        leaf = leaf["kernel"] if "kernel" in leaf else leaf["scale"]
    return leaf.astype(jnp.float32)


# SDAR-30B-A3B's four layers in block-diffusion TRAINING (``--only blockdiff``): grouped-query attention under the block
# mask over a doubled row ``[noised ; clean]`` of 2 x 8,192 ids (the traffic's generator makes the row and its noise), every
# FFN softmax-routed (8 of 128, 16 held), a masked-token loss weighted a block over the noised half. The logits compared
# are the noised half's; EVERY leaf of the gradient is read (a leaf's name is its path, as in the sambay phase). Four
# controls, each the plain bf16 reference with one thing wrong, and each has to break a limit on every seed: a causal mask
# over the 2 L row (``causal``), the noised half blind to the clean one (``blind``), every masked position weighing one in
# place of B / m (``uniform``: the logits are the reference's own, its gradient is another loss's) and a target shifted
# by one (``shift``: likewise). The limits and the readings they were set from: PERF.md section 6, PR 49.
BLOCKDIFF_PARTS = {"blockdiff": ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"),
                   "routed": ("gate", "experts_wg", "experts_wi", "experts_wo")}
# Limits from five seeds (0, 11, 101, set from these three; 2024, 31337, which then passed; my chip runs, PR 49: published
# widths, 4 layers, 2 x 8,192 positions, the usual start), each between the LARGEST reading of the program and the SMALLEST
# of a control, by class of leaf (its path without the layer). NOT judged, only reported (a limit of None): the routers' own
# gradients, which flips of the top 8 of 128 rule in the program and in the plain bf16 path alike (HYBRID_LIMITS says the
# same of its router): 0.015 to 0.53 for the program, 0.39 at most for the plain bf16 reference with nothing wrong
# (``plain_bf16``, reported beside the program, judged by nothing), where the ``uniform`` control reads 0.19 to 0.59. The
# routed layer's other leaves (a held expert's three matrices and the norm ahead of the router) read the same flips through
# the experts' rows, a seed at a time: the program 0.017 to 0.148, the plain bf16 reference up to 0.202, the smallest control
# 0.457.
BLOCKDIFF_LEAF_LIMIT = 0.1   # every other leaf: the program 0.0068-0.0509 (q/k projections and norms 0.016-0.051, the rest under 0.017) | plain bf16 0.054 at most | the smallest control 0.405
BLOCKDIFF_CLASS_LIMITS = {
    "logits": 0.05,          # the noised half's: 0.0075-0.0091 | plain bf16 0.0083 | causal 1.32, blind 1.37 (``uniform`` and ``shift`` have the reference's own logits)
    "routed/gate": None,
    **{f"routed/experts_{m}": 0.27 for m in ("wg", "wi", "wo")},  # 0.0173-0.1481 | plain bf16 0.200 | 0.504 (the limit: sqrt of the two ends' product)
    "RMSNorm_1/scale": 0.27,  # the norm ahead of the router: 0.0206-0.1217 | plain bf16 0.202 | 0.457
}


def _decoder_names(parts, layers=4, kinds=None, tied=False):
    """Every leaf of a routed decoder's tree, a leaf's name its path: the tables (one where the head is ``tied`` to the
    embedding) and the final norm, then a layer's two norms and the leaves of its ``parts`` (part -> its leaves; ``kinds``: the
    parts each layer has, where the layers differ)."""
    names = ["wte"] + ([] if tied else ["lm_head"]) + ["RMSNorm_0/scale"]
    for i, own in enumerate(kinds or [tuple(parts)] * layers):
        names += [f"layer_{i}/{norm}/scale" for norm in ("RMSNorm_0", "RMSNorm_1")]
        names += [f"layer_{i}/{part}/{leaf}" for part in own for leaf in parts[part]]
    return names


BLOCKDIFF_LIMITS = {"logits": BLOCKDIFF_CLASS_LIMITS["logits"],
                    **{name: BLOCKDIFF_CLASS_LIMITS.get(_sambay_class(name), BLOCKDIFF_LEAF_LIMIT) for name in _decoder_names(BLOCKDIFF_PARTS)}}


def _blockdiff_rows(cfg, seed):
    """One row ``[xt ; x0]`` of the cell's traffic (its generator's noise) at the program's length."""
    from benchmarks.lib import manifest as mf

    gen = mf.load_module(f"{mf.BENCH}/generators/block_diffusion_batches.py")
    params = {"seq_len": cfg["program"]["max_seq_len"], "block_len": cfg["program"]["block_length"], "n_batches": 1}
    return gen.generate(params, seed, 0.0, {"vocab_size": cfg["program"]["vocab_size"], "global_batch": 1})["batches"][0]["input_ids"]


# SmallThinker-21BA3B's layers 0-3 (``--only mixed``): one full layer without positions and three rotated window layers of
# 4,096 at 16,384 rows, every FFN routed (6 of 64, 8 held) by a router that reads the attention's input, the experts
# ReGLU. EVERY leaf of the gradient is read (43: a leaf's name is its path). Four controls, each the plain bf16 reference
# with one thing wrong, and each has to break a limit on every seed: window layers that attend every earlier key
# (``no_window``), the full layer rotated too (``rotated_full``), the router on the experts' own input (``late_router``)
# and ``silu`` for ``relu`` (``silu_gate``). At the usual start of ``o_proj`` (the cell starts it small, which keeps the
# router's load even and would hide the attention's part of the logits at 0.02 of its size).
# Limits from three seeds (0, 11, 101) and checked on two more (2024, 31337, from the committed files alone, which passed;
# my chip runs, PR 53: published widths, 4 layers, 1 x 16,384), by class of leaf (its path without the layer). The logits and
# the attention's, the norms' and the tables' leaves separate cleanly: each limit lies between the LARGEST reading of the
# program and the SMALLEST of the controls' largest. NOT judged, only reported (a limit of None): the routers' own
# gradients, which flips of the top 6 of 64 rule in the program and in the plain bf16 path alike (0.041 to 0.233 for the
# program, 0.038 to 0.322 for ``plain_bf16``, the reference with nothing wrong, reported beside the program and judged by
# nothing). The routed layer's other leaves (a held expert's three matrices and the norm ahead of the experts) read the
# same flips through the experts' rows, a seed and a layer at a time: the program 0.026 to 0.241, the plain bf16 reference
# 0.026 to 0.233; a control's reading there may lie under the program's on another seed (``no_window`` 0.174,
# ``rotated_full`` 0.217 at the least), so their limit, the blockdiff phase's 0.27, guards against a gross fault and
# separates nothing: the logits and the other leaves are where every control fails. NOT a control, read on the first three
# seeds and taken out: the softmaxes' and the router's statistics in bf16 (``low_state``, the precision below the stated
# one) reads what the program reads, logits 0.0099 to 0.0119 and no leaf apart from it by more than the seeds are: at a
# random start a softmax over thousands of keys is an average, and bf16 statistics move it by rounding (PERF.md section 6,
# PR 53).
MIXED_PARTS = {"attn": ("q_proj", "k_proj", "v_proj", "o_proj"), "routed": ("gate", "experts_wg", "experts_wi", "experts_wo")}
MIXED_LEAF_LIMIT = 0.1       # every other leaf: the program 0.0061-0.0677 (layer 0's q_proj the largest) | plain bf16 0.0662 at most | the controls over these leaves: silu_gate 0.063-0.320, rotated_full 0.057-1.096, late_router 0.120-1.195, no_window 0.456-1.690, each control's largest 0.31 and more on every seed
MIXED_CLASS_LIMITS = {
    "logits": 0.03,          # 0.0092-0.0108 | plain bf16 0.0094-0.0109 | silu_gate 0.067-0.081, rotated_full 0.098-0.111, late_router 0.149-0.301, no_window 0.516-0.546
    "routed/gate": None,
    **{f"routed/experts_{m}": 0.27 for m in ("wg", "wi", "wo")},  # 0.026-0.241 | plain bf16 0.233 | (see above)
    "RMSNorm_1/scale": 0.27,  # the norm ahead of the experts: the same flips
}


MIXED_LIMITS = {"logits": MIXED_CLASS_LIMITS["logits"],
                **{name: MIXED_CLASS_LIMITS.get(_sambay_class(name), MIXED_LEAF_LIMIT) for name in _decoder_names(MIXED_PARTS)}}


# LFM2-8B-A1B's published layers 1-5 (``--only shortconv``): a gated short convolution over the dense SwiGLU, then GQA 32/8
# of 64 with q/k norms and three more convolutions over a routed FFN (sigmoid scores, 4 of 32, 8 held), the head tied to
# the embedding, at 16,384 rows. EVERY leaf of the gradient is read but the selection bias, a buffer whose gradient is zero
# on both sides (49 of the 53: a leaf's name is its path). Four controls, each the plain bf16 reference with one thing wrong, and
# each has to break a limit on every seed: a SiLU after the filter (``silu_filter``: what the scan layers' convolutions
# have), W_in's first two chunks the other way round (``chunks_cbu``), no q/k norm (``no_qk_norm``) and the chosen scores
# not rescaled (``no_renorm``). At the usual start of ``o_proj`` (the cell starts it small, which would hide the
# attention's part).
# Limits from three seeds (0, 11, 101) and checked on two more (2024, 31337, which passed: the program under all 46 judged limits, `silu_filter`, `chunks_cbu` and `no_renorm` over all 46 and `no_qk_norm` over 7 on all five; my chip runs, PR 55: published
# widths, 5 layers, 1 x 16,384; 4.4 minutes a seed), by class of leaf (its path without the layer). The program reads what
# the plain bf16 reference reads, leaf by leaf (two gates around a filter multiply their operands' rounding, so both lie
# further from float32 than a stack of attention layers does: logits 0.047-0.048). NOT judged, only reported (a limit of
# None): the routers' own gradients, which flips of the top 4 of 32 rule in the program and in the plain bf16 path alike
# (0.194-0.340 | 0.194-0.331). The routed layer's other leaves read the same flips through the experts' rows. ``no_qk_norm``
# breaks the attention layer's leaves alone (its logits read 0.055: one layer in five, at a start where attention is an
# average), the three others every class.
SHORTCONV_KINDS = (("conv", "mlp"), ("attn", "routed"), ("conv", "routed"), ("conv", "routed"), ("conv", "routed"))
SHORTCONV_PARTS = {"conv": ("in_proj", "conv_kernel", "out_proj"), "attn": ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"),
                   "mlp": ("gate_proj", "up_proj", "down_proj"), "routed": ("gate", "experts_wg", "experts_wi", "experts_wo")}
SHORTCONV_LEAF_LIMIT = 0.15  # every other leaf: the program 0.018-0.085 (a q_norm the largest) | plain bf16 0.018-0.077 | no_qk_norm 0.199-0.376 over the attention's products and 1.0 over its norms, no_renorm 0.217-0.999, silu_filter 0.370-1.505, chunks_cbu 0.484-1.507
SHORTCONV_CLASS_LIMITS = {
    "logits": 0.1,           # 0.0470-0.0482 | plain bf16 0.0473-0.0481 | no_qk_norm 0.0548-0.0557 (under it: see above), no_renorm 0.601-0.606, silu_filter 1.012-1.014, chunks_cbu 1.349-1.352
    "routed/gate": None,
    **{f"routed/experts_{m}": 0.35 for m in ("wg", "wi", "wo")},  # 0.138-0.229 | plain bf16 0.132-0.227 | no_qk_norm 0.152-0.251, silu_filter 1.328-1.692, chunks_cbu 1.408-1.418, no_renorm 2.467-2.653
    "RMSNorm_1/scale": 0.35,  # the norm ahead of the experts: the same flips: 0.069-0.232 | 0.067-0.231 | no_qk_norm 0.080-0.260, no_renorm 0.851-2.687 (layer 0's is the dense FFN's and reads 0.07)
}


SHORTCONV_LIMITS = {"logits": SHORTCONV_CLASS_LIMITS["logits"],
                    **{name: SHORTCONV_CLASS_LIMITS.get(_sambay_class(name), SHORTCONV_LEAF_LIMIT) for name in _decoder_names(SHORTCONV_PARTS, kinds=SHORTCONV_KINDS, tied=True)}}


# Nemotron-3-Nano-30B-A3B's published layers 0-8 (``--only mamba2``), MEMEM*EME: four Mamba-2 mixers, four routed FFNs of
# ungated relu^2 experts (6 of 128, 8 held, one shared) and one GQA 32/2 layer without positions, every layer ONE part
# under one norm, an untied head, at 8,192 rows; the float32 reference runs the recurrence token by token. EVERY leaf of the
# gradient is read but the selection bias, a buffer whose gradient is zero on both sides (68 of the 72: a leaf's name is its
# path). Five controls, each the plain bf16 reference with one thing wrong, and each has to break a limit on every seed:
# experts that are a gated SiLU on the one product there is (``gated_silu``), the decay dropped, ``A = 0`` (``no_decay``),
# the group norm before the gate (``norm_first``), no ``D x`` (``no_skip``) and the recurrence's state, step and statistics
# in bf16 (``low_state``: the precision below the stated one). At the usual start of every product, ``o_proj`` too, which is the cell's own start (``assumed.start`` in its file).
# Limits from three seeds (0, 11, 101; my chip runs, PR 59: published widths, 9 layers, 1 x 8,192; 215-405 s a seed) and checked on two more (2024, 31337, which passed: the program under all 65 judged limits, the four structural controls over all 65 and `low_state` over the logits' on all five),
# by class of leaf (its path without the layer). The program reads what the plain bf16 reference reads, leaf by leaf (four
# layers of gate-norm-scan multiply their operands' rounding: logits 0.047-0.051 beside 0.045-0.050). The four structural
# controls read six times the program and more in EVERY class (the least, ``gated_silu``: logits 0.300-0.305, a leaf 0.15 at
# the least, the final norm's scale on one seed). ``low_state`` lies a quarter above the program and is held to the LOGITS
# alone, whose limit is the square root of the two ends' product (0.0514 | 0.0638). NOT judged, only reported (a limit of
# None): the routers' own gradients, which flips of the top 6 of 128 rule in the program and in the plain bf16 path alike
# (0.166-0.311 | 0.144-0.302); a held expert's two matrices read the same flips through the experts' rows.
MAMBA2_KINDS = tuple({"M": ("ssd",), "E": ("routed",), "*": ("attn",)}[c] for c in "MEMEM*EME")
MAMBA2_PARTS = {"ssd": ("in_proj", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D", "norm_scale", "out_proj"),
                "attn": ("q_proj", "k_proj", "v_proj", "o_proj"),
                "routed": ("gate", "experts_wi", "experts_wo", "shared_up_proj", "shared_down_proj")}
MAMBA2_LEAF_LIMIT = 0.15     # every other leaf: the program 0.023-0.102 (the attention's k_proj the largest) | plain bf16 0.022-0.095 | low_state 0.031-0.129, gated_silu 0.153-0.584, norm_first 0.269-0.949, no_decay 0.629-4.556, no_skip 0.596-16.98
MAMBA2_CLASS_LIMITS = {
    "logits": 0.057,         # 0.0472-0.0514 | plain bf16 0.0452-0.0504 | low_state 0.0638-0.0686, gated_silu 0.300-0.305, norm_first 0.516-0.522, no_skip 1.225-1.233, no_decay 1.239-1.243
    "ssd/dt_bias": 0.25,     # one number a head, 64 a layer: 0.060-0.160 | plain bf16 0.051-0.139 | low_state 0.077-0.175, gated_silu 0.388-0.759, norm_first 0.611-1.106, no_skip 4.90-11.9, no_decay 5.82-13.5
    "routed/gate": None,
    "routed/experts_wi": 0.35,  # 0.131-0.244 | plain bf16 0.112-0.231 | low_state 0.187-0.295, gated_silu 0.433-0.796, norm_first 0.787-1.139, no_decay 1.276-1.709, no_skip 1.349-1.828
    "routed/experts_wo": 0.35,  # 0.131-0.243 | plain bf16 0.112-0.230 | (as above)
}
MAMBA2_NAMES = ["wte", "lm_head", "RMSNorm_0/scale"] + [f"layer_{i}/{leaf}" for i, (part,) in enumerate(MAMBA2_KINDS)
                                                          for leaf in ("RMSNorm_0/scale",) + tuple(f"{part}/{l}" for l in MAMBA2_PARTS[part])]
MAMBA2_LIMITS = {"logits": MAMBA2_CLASS_LIMITS["logits"],
                 **{name: MAMBA2_CLASS_LIMITS.get(_sambay_class(name), MAMBA2_LEAF_LIMIT) for name in MAMBA2_NAMES}}


# Ouro-2.6B's eight layers run FOUR times on the same weights (``--only loop``): sandwich norms, the final norm inside the
# loop, one head and one exit gate after every pass, the expected-loss objective whose weights carry a gradient. What the
# reference returns is a pass at a time (``benchmarks/configs/ouro-2.6b-l8.reference.py``), so the phase has readings of
# its own (``_loop_readings``): every pass's logits at 64 sampled positions, lambda_t at every position, the loss, and EVERY
# leaf's gradient, summed up as the worst leaf of the SHARED ones (a layer's, and the final norm's: each the sum of four
# uses), the gate's and the tables'. The gate is moved off its zero start (normal 0.05, bias 0.3: at zero lambda is 1/2
# whatever the state and the state takes no gradient through the gate).
#
# Limits from seeds 0, 11 and 101 (and 2024 for ``lambda``) at the published widths and 1 x 8,192 ids (my chip runs, PR 63; 505-650 s a seed, 63-208
# of them compile, 14.9 GB at the float32 gradient's peak), each the program's largest reading with a quarter to twice of
# room, under every reading of the controls it is there to catch:
LOOP_LIMITS = {             # the program's three readings | the plain bf16 reference's | ``low_state`` | the nearest structural control
    "logits": 0.027,        # 0.0193, 0.0207, 0.0195 | 0.0198-0.0214 | 0.0210-0.0218 (the precision is NOT seen here) | a layer short 0.69
    "lambda": 0.050,        # 0.0141, 0.0129, 0.0149 and, on the first fresh seed (2024), 0.0244 | 0.0127-0.0175 | 0.0147-0.0219 | a layer short 0.39:
                            # a ratio of norms over a gate drawn a seed, so twice the largest reading; the first limit, 0.020, failed seed 2024
    "loss": 8e-4,           # |loss - float32's|: 2.4e-4, 3.3e-5, 2.7e-4 | 1.7e-4-3.3e-4 | 2.2e-3, 4.6e-3, 4.9e-3: THE limit the precision fails
                            # on every seed, 2.7 times over it at the least; no entropy 0.036, uniform exits 0.018; a pass short reads
                            # 5.6e-4 on one seed (a mean loss hardly sees the fourth pass at a random start) and fails the gradients
    "grad_shared": 0.060,   # the worst of a layer's 11 leaves and the final norm, each the SUM OF FOUR USES: 0.0441, 0.0338, 0.0447 |
                            # 0.0342-0.0477 | 0.0567-0.0939 | a pass short (three uses) 0.59, 0.76, 0.83
    "grad_exit_gate": 0.14,  # 0.0698, 0.0107, 0.0241 (a vector of 2,048 and a scalar: the reading is a seed's) | 0.010-0.043 |
                            # 0.028-0.39 | a pass short 0.175, uniform exits 1.0 (the gate gets no gradient at all)
    "grad_tables": 0.045,   # the embedding and the head: 0.0345, 0.0231, 0.0313 | 0.0228-0.0341 | 0.039-0.080 | a pass short 0.39
}


def _loop_readings(cfg, model, ids, params, controls, seed):
    from benchmarks.lib import manifest as mf

    ref = mf.load_module(os.path.join(mf.ROOT, cfg["reference"]["module"]))
    pub, seq = mf.published(cfg), ids.shape[1]
    params = dict(params, exit_gate={"kernel": 0.05 * jax.random.normal(jax.random.split(jax.random.PRNGKey(seed & 0x7FFFFFFF))[0], params["exit_gate"]["kernel"].shape, jnp.float32),
                                     "bias": jnp.full((1,), 0.3, jnp.float32)})
    at = np.sort(np.random.default_rng([seed, 6]).choice(seq, min(64, seq), replace=False))
    on_host = lambda grads: {jax.tree_util.keystr(path): np.asarray(leaf, np.float32) for path, leaf in jax.tree_util.tree_leaves_with_path(grads)}

    def plain(dtype, **over):
        rc = dict(cfg["reference"], **over)
        (loss, out), grads = ref.loss_and_grads(params, ids, pub, rc, dtype)
        return np.asarray(ref.pass_logits(params, ids, pub, rc, dtype, at)), np.asarray(out["lam"]), float(loss), on_host(grads)

    def ours():
        w, gate = params["lm_head"]["kernel"].astype(model.cfg.dtype), params["exit_gate"]

        def read(p):
            hidden = model.apply(p, ids, return_hidden=True)  # (T, 1, S, d)
            logits = jnp.einsum("tbsd,dv->tbsv", hidden[:, :, at], w, preferred_element_type=jnp.float32)
            return logits, jax.nn.sigmoid(jnp.einsum("tbsd,d->tbs", hidden.astype(jnp.float32), gate["kernel"][:, 0]) + gate["bias"])[..., :-1]

        logits, lam = jax.jit(read)(params)
        loss, grads = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids})))(params)
        return np.asarray(logits), np.asarray(lam), float(loss), on_host(grads)

    rel = lambda a, b: float(np.linalg.norm((a - b).astype(np.float64)) / max(np.linalg.norm(b.astype(np.float64)), 1e-30))
    both = lambda a, b: (a[:min(len(a), len(b))], b[:min(len(a), len(b))])  # a control a pass short is compared on the passes it has
    truth = plain(jnp.float32)
    readings, leaves = {name: {} for name in LOOP_LIMITS}, {}
    contestants = [("ours", ours)] + [(name, functools.partial(plain, jnp.bfloat16, **wrong)) for name, wrong in controls.items()]
    for who, run in contestants + [("plain_bf16", functools.partial(plain, jnp.bfloat16))]:
        logits, lam, loss, grads = run()
        by_leaf = {name: rel(grads[name], truth[3][name]) for name in truth[3]}
        shared = [name for name in by_leaf if "layer_" in name or "RMSNorm_0" in name.split("]")[0]]
        worst = max(shared, key=by_leaf.get)
        readings["logits"][who], readings["lambda"][who] = rel(*both(logits, truth[0])), rel(*both(lam, truth[1]))
        readings["loss"][who] = abs(loss - truth[2])
        readings["grad_shared"][who], readings["grad_shared"][who + "_leaf"] = by_leaf[worst], worst
        readings["grad_exit_gate"][who] = max(by_leaf[name] for name in by_leaf if "exit_gate" in name)
        readings["grad_tables"][who] = max(by_leaf[name] for name in by_leaf if "wte" in name or "lm_head" in name)
        leaves[who] = by_leaf
        del logits, lam, grads
        gc.collect()
    print(json.dumps({"phase": "loop_leaves", "seed": seed, "loss_f32": truth[2], "by_leaf": leaves}), flush=True)
    return readings


# a phase's model: its configuration, the limits, where a judged leaf lies in the gradient tree, its controls
# (a name and what is wrong with the plain bf16 reference under it) and, where the rows are not uniform ids of the
# program's length, what makes them
SMOKE_MODELS = {
    "hybrid": ("benchmarks/configs/kimi-linear-48b-l5e8.json", HYBRID_LIMITS, _hybrid_leaf, {"control": {"low_state": True}}),
    "latent": ("benchmarks/configs/kimi-vl-a3b-l6e8.json", LATENT_LIMITS, _latent_leaf,
               {"no_rope": {"no_rope": True}, "low_state": {"low_state": True}}),
    "deltanet": ("benchmarks/configs/qwen3-next-80b-l4e32.json", DELTANET_LIMITS, _deltanet_leaf,
                 {"no_decay": {"no_decay_layer": 2}, "no_gate": {"no_output_gate": True}, "low_state": {"low_state": True}}),
    "sambay": ("benchmarks/configs/phi4-mini-flash-l6.json", SAMBAY_LIMITS, _sambay_leaf,
               {"no_window": {"no_window": True}, "no_lambda": {"no_lambda": True}, "gated_memory": {"gated_memory": True},
                "own_keys": {"own_keys": True}, "low_state": {"low_state": True}}),
    "blockdiff": ("benchmarks/configs/sdar-30b-a3b-l4e16.json", BLOCKDIFF_LIMITS, _sambay_leaf,
                  {"causal": {"mask": "causal"}, "blind": {"mask": "blind"}, "uniform": {"uniform_weights": True}, "shift": {"shift": True}},
                  # the usual start (q/k norms at one), not the cell's (at 3, which keeps the router's load even): where every
                  # position's vector is its own, a position's output hangs on its own top 8 of 128 and on the one or two keys
                  # its query picks, which bf16 rounding flips for a few positions in a hundred, in the program and in the
                  # plain bf16 reference alike: a measure that reads the flips reads no arithmetic
                  {"rows": _blockdiff_rows, "program": {"blockdiff_qk_init_scale": 1.0}, "report_plain": True}),
    "mixed": ("benchmarks/configs/smallthinker-21b-l4e8.json", MIXED_LIMITS, _sambay_leaf,
              {"no_window": {"windows": "none"}, "rotated_full": {"rotation": "all"}, "late_router": {"router": "late"}, "silu_gate": {"gate": "silu"}},
              {"program": {"sparse_out_init_scale": 1.0}, "report_plain": True}),
    "shortconv": ("benchmarks/configs/lfm2-8b-a1b-l5e8.json", SHORTCONV_LIMITS, _sambay_leaf,
                  {"silu_filter": {"filter_act": "silu"}, "chunks_cbu": {"chunks": "cbu"}, "no_qk_norm": {"qk_norm": "none"}, "no_renorm": {"renorm": "none"}},
                  {"program": {"sparse_out_init_scale": 1.0}, "report_plain": True}),
    "mamba2": ("benchmarks/configs/nemotron3-nano-30b-l9e8.json", MAMBA2_LIMITS, _sambay_leaf,
               {"gated_silu": {"expert_act": "silu_gated"}, "no_decay": {"decay": "none"}, "norm_first": {"norm": "before_gate"},
                "no_skip": {"skip": "none"}, "low_state": {"low_state": True}},
               {"report_plain": True}),
    "loop": ("benchmarks/configs/ouro-2.6b-l8.json", LOOP_LIMITS, None,
             {"steps_short": {"steps_short": True}, "norm_outside_loop": {"norm_outside_loop": True}, "no_sandwich": {"no_sandwich": True},
              "uniform_exit": {"uniform_exit": True}, "no_entropy": {"no_entropy": True}, "layers_short": {"layers_short": True},
              "low_state": {"low_state": True}},
             {"readings": _loop_readings}),  # what is compared is a pass at a time: readings of the phase's own
}


def f32_readings(which, seed):
    """One of ``SMOKE_MODELS`` at its published widths and timed sizes (on a
    rehearsal: its ``rehearse`` block), through ``CausalLM`` as the trainer
    runs it, and its controls (the plain reference in bf16 with one thing
    wrong), each against the configuration's own plain reference in float32:
    {measure: {"ours", <control>...}} for the logits and the first gradient
    of the judged leaves, every one |x - x_f32| / |x_f32|."""
    from benchmarks.lib import manifest as mf, reference, weights

    path, limits, leaf_of, controls, *own = SMOKE_MODELS[which]
    own = own[0] if own else {}  # what is the model's own: its rows, fields of the program that differ from the cell's
    rows_of = own.get("rows")  # what makes the rows, where they are not uniform ids
    cfg = mf.load_json(os.path.join(os.path.dirname(os.path.abspath(__file__)), path))
    if REHEARSE:
        r = dict(cfg["rehearse"])
        cfg = dict(cfg, **{k: v for k, v in r.pop("published", {}).items()})
        cfg.update(program=dict(cfg["program"], **r["program"]), reference=r["reference"])
    cfg = dict(cfg, program=dict(cfg["program"], **own.get("program", {})))
    model = weights.build_model(cfg)
    seq = cfg["program"]["max_seq_len"]
    if rows_of:
        ids = jnp.asarray(rows_of(cfg, seed))
    else:
        ids = jnp.asarray(np.random.default_rng([seed, 5]).integers(0, cfg["program"]["vocab_size"], (1, seq), np.int32))
    params = jax.jit(lambda k: model.init(k, {"input_ids": np.zeros((1, seq), np.int32)}))(weights.seed_key(seed))
    if "readings" in own:
        return own["readings"](cfg, model, ids, params, controls, seed), int(seq)
    ref_logits, ref_loss = reference.for_config(cfg)
    pub = mf.published(cfg)
    judged = tuple(k for k in limits if k != "logits")

    rel = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32)) / jnp.maximum(jnp.linalg.norm(b.astype(jnp.float32)), 1e-30))

    def leaves_of(grads):  # the leaves compared, and nothing else of a 2.4 GB tree; on the host, where every leaf is judged
        return {name: np.asarray(leaf_of(grads, name)) for name in judged}

    ref_module = mf.load_module(os.path.join(mf.ROOT, cfg["reference"]["module"]))

    def plain(dtype, **over):
        rc = dict(cfg["reference"], **over)
        logits = ref_logits(params, ids, pub, rc, dtype)
        if rows_of:  # a model with rows of its own has a loss of its own, which reads its controls (weights, targets) from ``rc``
            return logits, leaves_of(ref_module.loss_and_grads(params, ids, pub, rc, dtype)[1])
        return logits, leaves_of(jax.grad(lambda p: ref_loss(ref_logits(p, ids, pub, rc, dtype), ids))(params))

    # the positions the model's objective runs its head over (``TransformerConfig.objective``): every one, or the noised half
    head = model.cfg.objective.targets(model.cfg, ids)[0] if model.cfg.objective is not None else slice(None)

    def ours():
        logits = jax.jit(lambda p: model.apply(p, ids)[:, head])(params)
        return logits, leaves_of(jax.jit(jax.grad(lambda p: model.loss_fn(p, {"input_ids": ids})))(params))

    # one contestant at a time: the float32 reference's gradient alone takes most of the chip at 8192 tokens
    truth_logits, truth = plain(jnp.float32)
    readings = {name: {} for name in limits}
    contestants = [("ours", ours)] + [(name, functools.partial(plain, jnp.bfloat16, **wrong)) for name, wrong in controls.items()]
    if own.get("report_plain"):  # the plain bf16 reference with nothing wrong: read beside the program, judged by nothing
        contestants.append(("plain_bf16", functools.partial(plain, jnp.bfloat16)))
    for who, run in contestants:
        logits, leaves = run()
        readings["logits"][who] = rel(logits, truth_logits)
        readings["logits"][who + "_max_scaled"] = scaled_err(logits, truth_logits)  # the old measure, reported, not judged
        for name in judged:
            readings[name][who] = rel(leaves[name], truth[name])
        del logits, leaves
        gc.collect()
    return readings, int(seq)


def f32_phase(which):
    """``f32_readings`` of ``--seed`` against the model's limits: the program
    under every limit, every control over at least one."""
    readings, seq = f32_readings(which, ARGS.seed)
    limits, controls = SMOKE_MODELS[which][1], SMOKE_MODELS[which][3]
    report = {name: dict(readings[name], limit=limit) for name, limit in limits.items()}
    over = lambda who: [k for k, v in report.items() if v["limit"] is not None and who in v and not v[who] <= v["limit"]]  # None: read, not judged
    failed_ours, failed_controls = over("ours"), {name: over(name) for name in controls}
    print(json.dumps({"phase": f"{which}_readings", "seed": ARGS.seed, "compared": report}), flush=True)  # whole, whatever the verdict
    if not REHEARSE:  # the limits are the published widths': at a tiny width bf16 flips routes and proves nothing
        check(not failed_ours, f"the {which} model lies further from its float32 reference than allowed in {failed_ours}: {report}")
        passed = [name for name, failed in failed_controls.items() if not failed]
        check(not passed, f"the controls {passed} (the plain reference with one thing wrong) passed every limit: they prove nothing: {report}")
    return {"compared": report, "control_failed": failed_controls, "tokens": seq}


def main():
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if not REHEARSE and device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {device}); nothing can be proved here", file=sys.stderr)
        return 2
    if device["count"] != ARGS.chips:
        print(f"chip_smoke: --chips {ARGS.chips} but JAX sees {device['count']} devices", file=sys.stderr)
        return 2
    print(json.dumps({"phase": "device", "ok": True, **device, "seed": ARGS.seed, "rehearsal": REHEARSE,
                      "compile_cache_dir": CACHE_DIR, "jax": jax.__version__}), flush=True)
    if ARGS.chips == 1:
        phases = KERNEL_PHASES + (("trainer", trainer_phase), ("server", server_phase)) + tuple(
            (which, functools.partial(f32_phase, which)) for which in SMOKE_MODELS) + (("sparse", sparse_phase),)
    else:
        phases = (("zero3_fsdp", zero3_phase), ("serve_tp", tp_phase))
    phases = tuple(p for p in phases if ARGS.only is None or ARGS.only in p[0])
    for name, fn in phases:
        run_phase(name, fn)
        gc.collect()
    if FAILED:
        print(json.dumps({"ok": False, "failed": FAILED, "device": device}), flush=True)
        return 1
    if REHEARSE:
        print(json.dumps({"ok": False, "rehearsal": "passed", "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
