"""Attention kernel A/B on hardware: ours vs jax's reference TPU flash
kernel vs plain XLA, fwd+bwd TF/s at training shapes.

The jax pallas ops kernel is the oracle for "what can this chip do at
this shape" — if it beats ours materially, the gap is our kernel
structure, not the hardware.

    python tools/ab_attn.py [B S H D]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

import jax
import jax.numpy as jnp


def bench(name, step, args, iters=20, flops=0):
    try:
        g = step(*args)
        jax.block_until_ready(g)
        t0 = time.perf_counter()
        for _ in range(iters):
            g = step(*args)
        jax.block_until_ready(g)
        dt = time.perf_counter() - t0
        print(f"[ab_attn] {name}: {flops * iters / dt / 1e12:.2f} TF/s ({dt / iters * 1e3:.2f} ms)")
    except Exception as e:  # noqa: BLE001
        print(f"[ab_attn] {name}: FAIL {type(e).__name__}: {e}")


def main():
    B, S, H, D = (int(x) for x in sys.argv[1:5]) if len(sys.argv) > 4 else (8, 1024, 12, 64)
    print(f"[ab_attn] B={B} S={S} H={H} D={D} platform={jax.devices()[0].platform}")
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(k2, (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(k3, (B, S, H, D), jnp.bfloat16)
    flops = 4 * B * H * S * S * D * 2.5  # fwd matmul pair x ~2.5 for fwd+bwd

    from deepspeed_tpu.ops.attention import attention_xla
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    ours = jax.jit(jax.grad(lambda q, k, v: flash_attention(q, k, v, causal=True).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2)))
    xla = jax.jit(jax.grad(lambda q, k, v: attention_xla(q, k, v, causal=True).astype(jnp.float32).sum(),
                           argnums=(0, 1, 2)))
    bench("ours-flash", ours, (q, k, v), flops=flops)
    bench("xla", xla, (q, k, v), flops=flops)

    from jax.experimental.pallas.ops.tpu import flash_attention as jfa

    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))  # jax kernel wants (B, H, S, D)
    oracle = jax.jit(jax.grad(lambda q, k, v: jfa.flash_attention(q, k, v, causal=True)
                              .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    bench("jax-oracle", oracle, (qt, kt, vt), flops=flops)

    # fwd-only views (serving prefill shape sensitivity)
    flops_fwd = 4 * B * H * S * S * D
    bench("ours-flash-fwd", jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)), (q, k, v),
          flops=flops_fwd)
    bench("xla-fwd", jax.jit(lambda q, k, v: attention_xla(q, k, v, causal=True)), (q, k, v), flops=flops_fwd)


if __name__ == "__main__":
    main()
