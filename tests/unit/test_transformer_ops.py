"""ops.transformer surface: each op binding against a naive oracle, and the
fused training layer against a hand-composed reference (the reference's
test pattern for DeepSpeedTransformerLayer, ``tests/unit/ops/transformer``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import transformer as T

pytestmark = pytest.mark.fast

RNG = np.random.RandomState(0)


def r(*shape):
    return jnp.asarray(RNG.randn(*shape).astype(np.float32))


def test_layer_norm_residual_matches_composition():
    x, bias, res = r(2, 4, 8), r(8), r(2, 4, 8)
    g, b = r(8), r(8)
    out, pre = T.layer_norm_residual(x, bias, res, g, b, 1e-5, store_pre_ln_res=True)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(x + bias + res), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(T.layer_norm(x + bias + res, g, b, 1e-5)),
                               rtol=1e-5, atol=1e-6)


def test_pre_rms_norm():
    x, res, g = r(2, 3, 8), r(2, 3, 8), r(8)
    out, new_res = T.pre_rms_norm(x, res, g)
    np.testing.assert_allclose(np.asarray(new_res), np.asarray(x + res), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(T.rms_norm(x + res, g)), rtol=1e-6)


def test_qkv_gemm_fuses_norm_and_projection():
    x, w, b = r(2, 4, 8), r(8, 24), r(24)
    g, beta = r(8), r(8)
    qkv, h = T.qkv_gemm(x, w, b, g, beta)
    np.testing.assert_allclose(np.asarray(h), np.asarray(T.layer_norm(x, g, beta)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(qkv), np.asarray(h @ w + b), rtol=1e-5, atol=1e-5)
    # rmsnorm flavor (ref rms_qkv_gemm_)
    qkv2, h2 = T.qkv_gemm(x, w, None, g, None, eps=1e-6, norm_type="rmsnorm")
    np.testing.assert_allclose(np.asarray(h2), np.asarray(T.rms_norm(x, g)), rtol=1e-5, atol=1e-6)


def test_mlp_gemm_residual_and_activations():
    x, res, ib = r(2, 4, 8), r(2, 4, 8), r(8)
    w1, b1, w2 = r(8, 16), r(16), r(16, 8)
    g, beta = r(8), r(8)
    for act, f in (("gelu", jax.nn.gelu), ("relu", jax.nn.relu), ("silu", jax.nn.silu)):
        out, pre = T.mlp_gemm(x, res, ib, w1, b1, w2, g, beta, activation=act)
        expect_pre = x + res + ib
        np.testing.assert_allclose(np.asarray(pre), np.asarray(expect_pre), rtol=1e-6)
        h = T.layer_norm(expect_pre, g, beta)
        np.testing.assert_allclose(np.asarray(out), np.asarray(f(h @ w1 + b1) @ w2), rtol=1e-5, atol=1e-5)


def test_elementwise_bias_ops():
    x, b, res = r(2, 4, 8), r(8), r(2, 4, 8)
    np.testing.assert_allclose(np.asarray(T.bias_add(x, b)), np.asarray(x + b), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(T.bias_gelu(x, b)), np.asarray(jax.nn.gelu(x + b)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(T.bias_relu(x, b)), np.asarray(jax.nn.relu(x + b)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(T.bias_residual(x, res, b)), np.asarray(x + res + b), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(T.vector_add(x, res, 0.5)), np.asarray(x + 0.5 * res), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(T.fused_gemm_gelu(x, r(8, 16), r(16), r(16, 8))).shape, (2, 4, 8))


def test_residual_add_bias_modes():
    h, res, attn = r(2, 3, 8), r(2, 3, 8), r(2, 3, 8)
    ab, fb = r(8), r(8)
    # preln gpt2-style (ref residual_add.py fallback math)
    out = T.residual_add_bias(h, res, attn, ab, fb, mp_size=2, mlp_after_attn=True, pre_layer_norm=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray((res + attn + ab + fb) / 2 + h), rtol=1e-5)
    # post-ln
    out = T.residual_add_bias(h, res, attn, ab, fb, mp_size=2, mlp_after_attn=True, pre_layer_norm=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(res + h + fb), rtol=1e-5)
    # gptj parallel
    out = T.residual_add_bias(h, res, attn, ab, fb, mp_size=2, mlp_after_attn=False, add_bias=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(res + h + attn + fb / 2 + ab / 2), rtol=1e-5)


def test_gated_activation():
    x, b = r(2, 3, 16), r(16)
    out = T.gated_activation(x, b, mode="silu")
    a, g = np.split(np.asarray(x + b), 2, axis=-1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jax.nn.silu(a) * g), rtol=1e-6)


def test_softmax_matches_masked_softmax():
    s = r(2, 4, 5, 5)
    mask = jnp.asarray(RNG.rand(2, 1, 5, 5) > 0.3)
    out = T.softmax(s, mask=mask, scale=0.5, causal=True)
    ref = np.asarray(s, np.float32) * 0.5
    ref = np.where(np.asarray(mask), ref, np.finfo(np.float32).min)
    tri = np.tril(np.ones((5, 5), bool))
    ref = np.where(tri, ref, np.finfo(np.float32).min)
    ref = np.asarray(jax.nn.softmax(jnp.asarray(ref), axis=-1))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-6)


def test_softmax_context_matches_attention():
    from deepspeed_tpu.ops.attention import attention_xla

    q, k, v = r(2, 4, 2, 8), r(2, 6, 2, 8), r(2, 6, 2, 8)
    out = T.softmax_context(q, k, v, causal=True, kv_len=6)
    ref = attention_xla(q, k, v, causal=True, kv_len=6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_apply_rotary_pos_emb_partial():
    from deepspeed_tpu.models.transformer import apply_rope, rope_frequencies

    q, k = r(1, 5, 2, 8), r(1, 5, 2, 8)
    pos = jnp.arange(5, dtype=jnp.int32)[None]
    qr, kr = T.apply_rotary_pos_emb(q, k, pos, rotary_dim=4, max_len=16)
    cos, sin = rope_frequencies(4, 16, 10000.0)
    np.testing.assert_allclose(np.asarray(qr), np.asarray(apply_rope(q, cos, sin, pos, rotary_dim=4)), rtol=1e-6)
    # untouched tail
    np.testing.assert_allclose(np.asarray(qr[..., 4:]), np.asarray(q[..., 4:]), rtol=1e-7)


def _rope_by_halves(x, cos, sin, positions, rd, offset, style):
    """The rotation as it was written before the one-pass form, the plain reference: the rotated span split in halves
    (``neox``) or in even and odd lanes (``gptj``), ``x1 * c - x2 * s`` and ``x2 * c + x1 * s`` in float32, joined."""
    xr = x[..., offset:offset + rd].astype(jnp.float32)
    c, s = cos[positions][:, :, None, :], sin[positions][:, :, None, :]
    if style == "gptj":
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
        out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(xr.shape)
    else:
        x1, x2 = jnp.split(xr, 2, axis=-1)
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([x[..., :offset], out.astype(x.dtype), x[..., offset + rd:]], axis=-1)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("style", ["neox", "gptj"])
@pytest.mark.parametrize("D,rd,offset", [(128, 128, 0), (256, 64, 0), (192, 64, 128)], ids=["whole_head", "first_quarter_of_256", "last_64_of_192"])
def test_the_one_pass_rotation_is_the_split_and_joined_one(D, rd, offset, style, dtype):
    """``apply_rope`` (``x * C + (x @ R) * S``, one pass over x at its full width) against the halves written out, value
    and vjp: the whole head (SDAR, Keye, OLMo), Qwen3-Next's 64 of 256, latent attention's trailing 64 of 192 (Kimi-VL's,
    ``gptj``); positions out of order (SDAR's ``arange(S) % L`` and a shuffled row). Op by op, so that no compiler
    contracts one form's multiply-add and not the other's: bit for bit in bf16, float32 to 1e-6."""
    from deepspeed_tpu.models.transformer import apply_rope, rope_frequencies

    B, Sq, Hq, L = 2, 48, 3, 24
    rng = np.random.RandomState(D + rd + len(style))
    x, g = (jnp.asarray(rng.randn(B, Sq, Hq, D), dtype) for _ in range(2))
    positions = jnp.asarray(np.stack([np.arange(Sq) % L, rng.permutation(Sq) % L]), jnp.int32)
    cos, sin = rope_frequencies(rd, L, 10000.0)
    got, pull = jax.vjp(lambda x: apply_rope(x, cos, sin, positions, rotary_dim=rd, style=style, offset=offset), x)
    want, pull_halves = jax.vjp(lambda x: _rope_by_halves(x, cos, sin, positions, rd, offset, style), x)
    back, back_halves = pull(g)[0], pull_halves(g)[0]
    assert got.dtype == want.dtype == back.dtype == dtype
    tol = dict(rtol=0, atol=0) if dtype == jnp.bfloat16 else dict(rtol=1e-6, atol=1e-6)
    for ours, halves in ((got, want), (back, back_halves)):
        np.testing.assert_allclose(np.asarray(ours, np.float32), np.asarray(halves, np.float32), **tol)
    outside = np.r_[0:offset, offset + rd:D]
    np.testing.assert_array_equal(np.asarray(got, np.float32)[..., outside], np.asarray(x, np.float32)[..., outside])  # passes through


def test_moe_helpers():
    res, out = r(2, 3, 8), r(2, 3, 8)
    coef = r(2, 3, 16)
    mixed = T.moe_res_matmul(res, coef, out)
    c1, c2 = np.split(np.asarray(coef), 2, axis=-1)
    np.testing.assert_allclose(np.asarray(mixed), np.asarray(res) * c1 + np.asarray(out) * c2, rtol=1e-6)
    a, b = r(5, 4, 3), r(5, 7)
    np.testing.assert_allclose(np.asarray(T.einsum_sec_sm_ecm(a, b)),
                               np.einsum("sec,sm->ecm", np.asarray(a), np.asarray(b)), rtol=1e-5)


@pytest.mark.parametrize("pre_ln", [True, False])
def test_transformer_layer_trains(pre_ln):
    cfg = T.DeepSpeedTransformerConfig(hidden_size=32, intermediate_size=64, heads=4, pre_layer_norm=pre_ln)
    layer = T.DeepSpeedTransformerLayer(cfg)
    x = r(2, 6, 32)
    mask = jnp.asarray(np.array([[1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 1, 1]], bool))
    params = layer.init(jax.random.PRNGKey(0), x, mask)

    def loss(p):
        return jnp.sum(layer.apply(p, x, mask)**2)

    g = jax.grad(loss)(params)
    norms = [float(jnp.linalg.norm(le)) for le in jax.tree_util.tree_leaves(g)]
    assert all(np.isfinite(n) for n in norms) and any(n > 0 for n in norms)


def test_transformer_layer_mask_blocks_pads():
    """Valid-token outputs must be independent of pad-position content."""
    cfg = T.DeepSpeedTransformerConfig(hidden_size=16, intermediate_size=32, heads=2)
    layer = T.DeepSpeedTransformerLayer(cfg)
    x1 = r(1, 5, 16)
    mask = jnp.asarray(np.array([[1, 1, 1, 0, 0]], bool))
    params = layer.init(jax.random.PRNGKey(0), x1, mask)
    x2 = x1.at[:, 3:].set(r(1, 2, 16) * 50.0)
    o1 = layer.apply(params, x1, mask)
    o2 = layer.apply(params, x2, mask)
    np.testing.assert_allclose(np.asarray(o1[:, :3]), np.asarray(o2[:, :3]), rtol=1e-5, atol=1e-5)


def test_transformer_layer_remat_matches():
    cfg = T.DeepSpeedTransformerConfig(hidden_size=16, intermediate_size=32, heads=2)
    cfg_r = T.DeepSpeedTransformerConfig(hidden_size=16, intermediate_size=32, heads=2, remat=True)
    x = r(1, 4, 16)
    params = T.DeepSpeedTransformerLayer(cfg).init(jax.random.PRNGKey(0), x)
    o = T.DeepSpeedTransformerLayer(cfg).apply(params, x)
    o_r = T.DeepSpeedTransformerLayer(cfg_r).apply(params, x)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r), rtol=1e-6)


def test_head_padding_ops():
    q, k, v = r(2, 4, 3, 20), r(2, 4, 3, 20), r(2, 4, 3, 20)
    qp, kp, vp = T.add_padding(q, k, v)
    assert qp.shape[-1] == 32
    np.testing.assert_array_equal(np.asarray(qp[..., :20]), np.asarray(q))
    assert float(jnp.abs(qp[..., 20:]).sum()) == 0.0
    qkv = r(2, 4, 3 * 3 * 20)
    q2, k2, v2 = T.pad_transform(qkv, heads=3)
    assert q2.shape == (2, 4, 3, 32)
    ref = np.asarray(qkv).reshape(2, 4, 3, 3, 20)
    np.testing.assert_array_equal(np.asarray(k2[..., :20]), ref[:, :, 1])
    assert T.padded_head_size(64) == 64 and T.padded_head_size(80) == 128


def test_on_device_meta_init():
    from deepspeed_tpu.models import CausalLM, gpt2_tiny
    from deepspeed_tpu.utils.init_on_device import OnDevice

    model = CausalLM(gpt2_tiny())
    batch = {"input_ids": np.zeros((1, 16), np.int32)}
    with OnDevice(dtype=jnp.bfloat16, device="meta"):
        meta = model.init(jax.random.PRNGKey(0), batch)
    leaves = jax.tree_util.tree_leaves(meta)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    assert all(l.dtype == jnp.bfloat16 for l in leaves if jnp.issubdtype(l.dtype, jnp.floating))
    # materialize against the abstract tree
    real = OnDevice.materialize(meta, lambda: model.init(jax.random.PRNGKey(0), batch))
    rl = jax.tree_util.tree_leaves(real)
    assert rl and all(isinstance(l, jax.Array) for l in rl)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(leaves, rl))
    # outside the context: normal init
    normal = model.init(jax.random.PRNGKey(0), batch)
    assert not isinstance(jax.tree_util.tree_leaves(normal)[0], jax.ShapeDtypeStruct)


def test_on_device_dtype_cast_on_device():
    from deepspeed_tpu.models import CausalLM, gpt2_tiny
    from deepspeed_tpu.utils.init_on_device import OnDevice

    model = CausalLM(gpt2_tiny())
    batch = {"input_ids": np.zeros((1, 16), np.int32)}
    with OnDevice(dtype=jnp.bfloat16, device=jax.devices()[0]):
        params = model.init(jax.random.PRNGKey(0), batch)
    flt = [l for l in jax.tree_util.tree_leaves(params) if jnp.issubdtype(l.dtype, jnp.floating)]
    assert flt and all(l.dtype == jnp.bfloat16 for l in flt)
