"""On-chip triage for the GQA flash backward mismatch (August 2026 session).

That session's smoke compared the Pallas GQA backward against the bf16 XLA oracle
with an absolute max-diff threshold of 0.1 and saw 0.125 on the real
chip. Both sides are bf16, so the diff could be (a) a genuine
revisit-accumulation / index-map bug in ``_dkv_kernel_gqa`` that only
real Mosaic exposes, or (b) bf16 rounding noise in the *oracle*. This
script separates the two: it computes an fp32 reference (same math, all
inputs upcast), then reports per-tensor (dq/dk/dv) max-abs and relative
error of kernel-vs-fp32 and oracle-vs-fp32. Verdict rule: the kernel is
correct iff its error against fp32 is within ~2x of the oracle's own
bf16 error; a structural bug shows up orders of magnitude larger and
concentrated in dk/dv.

    python tools/debug_flash_gqa.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention import attention_xla
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    print(f"[debug_flash_gqa] platform={jax.default_backend()}")
    B, S, H, D, KVH = 2, 512, 8, 64, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    kg = jax.random.normal(ks[1], (B, S, KVH, D), jnp.bfloat16)
    vg = jax.random.normal(ks[2], (B, S, KVH, D), jnp.bfloat16)
    slopes = np.geomspace(0.25, 0.001, H).astype(np.float32)

    for kw in ({}, {"alibi_slopes": slopes}, {"window": 128}):
        def loss(fn, q, k, v):
            return fn(q, k, v, causal=True, **kw).astype(jnp.float32).sum()

        gf = jax.jit(jax.grad(lambda q, k, v: loss(flash_attention, q, k, v), argnums=(0, 1, 2)))(q, kg, vg)
        gx = jax.jit(jax.grad(lambda q, k, v: loss(attention_xla, q, k, v), argnums=(0, 1, 2)))(q, kg, vg)
        # fp32 reference: same algebra, inputs upcast so matmul rounding is the
        # only difference left between the two bf16 paths
        g32 = jax.jit(jax.grad(lambda q, k, v: loss(attention_xla, q, k, v), argnums=(0, 1, 2)))(
            q.astype(jnp.float32), kg.astype(jnp.float32), vg.astype(jnp.float32))
        print(f"--- kwargs={kw}")
        for name, a, b, r in zip(("dq", "dk", "dv"), gf, gx, g32):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            r = np.asarray(r, np.float32)
            scale = np.abs(r).max() or 1.0
            d_ab = np.abs(a - b).max()
            d_ar = np.abs(a - r).max()
            d_br = np.abs(b - r).max()
            print(f"  {name}: |ref|max={scale:.3f}  kernel-vs-oracle={d_ab:.4f}"
                  f"  kernel-vs-fp32={d_ar:.4f} (rel {d_ar / scale:.2e})"
                  f"  oracle-vs-fp32={d_br:.4f} (rel {d_br / scale:.2e})")
            if d_ar > 2.5 * max(d_br, 1e-6):
                print(f"  {name}: KERNEL ERROR DOMINATES — structural suspect")


if __name__ == "__main__":
    main()
