"""``ops/placement.py``: ONE rule says whether a call takes its Pallas kernel (``kernel_path``) and how the kernel sits on
the mesh (``batch_spec``, ``on_mesh``), and the word that is counted is the path that was traced. Every site of the
rule, on no mesh, a mesh of one device and one of four, on a backend said to be a TPU (at the rule's ONE reading of
it, ``placement.pallas_available``) and on the CPU: the word, the ``shard_map`` around the kernel, the count. A call is
traced (``jax.make_jaxpr``) and never lowered, so a kernel that Mosaic would compile is only looked at."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import TransformerConfig
from deepspeed_tpu.models.layers import Attention
from deepspeed_tpu.models.mixers import ShortConvMixer
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.ops import indexed_attention as sparse, kda as delta, placement, ssm
from deepspeed_tpu.ops.attention import attention
from deepspeed_tpu.ops.pallas import norms, short_conv
from deepspeed_tpu.ops.registry import REGISTRY, get_op
from deepspeed_tpu.parallel.mesh import initialize_mesh
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.telemetry.tracing import regions_traced

MESHES = {"no_mesh": None, "one_device": {"data": 1}, "four_devices": {"data": 2, "tensor": 2}}
f32 = jnp.float32
rand = lambda *shape: jnp.asarray(np.random.default_rng(len(shape)).standard_normal(shape), f32)
total = lambda *xs: sum(jnp.sum(x.astype(f32)) for x in jax.tree_util.tree_leaves(xs))


@pytest.fixture
def backend(monkeypatch):
    """Says which backend the rule sees, and forces the registry's ops the way it would choose there (``ops/registry.py``
    asks the backend by a binding of its own: ROADMAP.md, D20)."""
    forced = {}

    def say(tpu: bool):
        monkeypatch.setattr(placement, "pallas_available", lambda: tpu)
        for op in ("attention", "rms_norm"):
            forced.setdefault(op, REGISTRY.set_impl(op, "pallas" if tpu else "xla"))
    yield say
    for op, was in forced.items():
        REGISTRY.set_impl(op, was)


def mesh(name):
    cfg = MESHES[name]
    if cfg is not None:
        initialize_mesh(MeshConfig.from_dict(cfg), devices=jax.devices()[:int(np.prod(list(cfg.values())))], force=True)
    return 1 if cfg is None else int(np.prod(list(cfg.values())))


# -- the sites: (has the site specs, the traced call, the counter's (region, labels) without ``path``) --------------------
def _kda():
    q, k, v, g = rand(2, 4, 128, 16), rand(2, 4, 128, 16), rand(2, 4, 128, 32), -jnp.abs(rand(2, 4, 128, 16))
    return jax.make_jaxpr(jax.grad(lambda *a: total(delta.kda(*a, jax.nn.sigmoid(rand(2, 4, 128))))))(q, k, v, g)


def _gdn():
    q, k, v, g = rand(2, 2, 128, 16), rand(2, 2, 128, 16), rand(2, 4, 128, 32), -jnp.abs(rand(2, 4, 128))
    return jax.make_jaxpr(jax.grad(lambda *a: total(delta.gdn(*a, jax.nn.sigmoid(rand(2, 4, 128))))))(q, k, v, g)


def _ssm():
    u, dt, A, B, C, D = rand(2, 128, 128), jnp.abs(rand(2, 128, 128)), -jnp.abs(rand(128, 16)), rand(2, 128, 16), rand(2, 128, 16), rand(128)
    return jax.make_jaxpr(jax.grad(lambda *a: total(ssm.selective_scan(*a))))(u, dt, A, B, C, D)


def _sparse():
    q, k, v = rand(2, 256, 4, 64), rand(2, 256, 2, 64), rand(2, 256, 2, 64)
    mask_t = jnp.tril(jnp.ones((2, 256, 256), jnp.int8)).swapaxes(1, 2)
    return jax.make_jaxpr(lambda *a: sparse.sparse_attention(*a, mask_t, scale=0.125, path=sparse.path_for(256, 48)))(q, k, v)


def _short_conv():
    cfg = TransformerConfig(vocab_size=64, n_layers=1, n_heads=2, d_model=128, d_ff=128, max_seq_len=32, conv_kernel=3)
    mod, x = ShortConvMixer(cfg), rand(2, 32, 128)
    params = jax.eval_shape(mod.init, jax.random.PRNGKey(0), x, None)["params"]  # (a trace too: it counts the same word)
    return jax.make_jaxpr(lambda p, x: mod.apply({"params": p}, x, None))(params, x)


def _flash():
    q, k, v = rand(2, 128, 4, 64), rand(2, 128, 2, 64), rand(2, 128, 2, 64)
    return jax.make_jaxpr(jax.grad(lambda *a: total(attention(*a, causal=True, count_as={"op": "full"}))))(q, k, v)


def _moe():
    N, E, d = 512, 4, 128
    tokens, wg, wi, wo = rand(N, d), rand(E, d, d), rand(E, d, d), rand(E, d, d)
    idx = jnp.stack([jnp.arange(N) % E, (jnp.arange(N) + 1) % E], axis=1).astype(jnp.int32)
    call = lambda t, g, i, o: moe_layer._over_expert_axis(t, idx, jnp.full((N, 2), 0.5, f32), g, i, o, 0, E, placement.kernel_path() == "kernel")
    return jax.make_jaxpr(lambda *a: call(*a)[0])(tokens, wg, wi, wo)


def _norm():
    return jax.make_jaxpr(lambda x, w: get_op("rms_norm")(x, w))(rand(4, 256), rand(256))


SITES = {  # name: (the site gives specs for a mesh, the trace, (region, labels) of its count: None where it counts nothing)
    "kda": (True, _kda, ("mixer/kernel", {"op": "kda", "pass": "fwd"})),
    "gdn": (True, _gdn, ("mixer/kernel", {"op": "gdn", "pass": "fwd"})),
    "ssm": (False, _ssm, ("mixer/kernel", {"op": "ssm", "pass": "fwd"})),
    "sparse": (False, _sparse, ("mixer/kernel", {"op": "sparse", "pass": "fwd"})),
    "short_conv": (False, _short_conv, ("mixer/conv", {"op": "short_conv", "pass": "fwd"})),
    "flash": (True, _flash, ("mixer/kernel", {"op": "full", "pass": "fwd"})),
    "moe": (True, _moe, ("ffn/experts", {})),
    "norms": (True, _norm, None),  # through the registry, which counts nothing: the wrapper alone is the module's
}


@pytest.mark.parametrize("tpu", [True, False], ids=["tpu", "cpu"])
@pytest.mark.parametrize("where", list(MESHES))
@pytest.mark.parametrize("site", list(SITES))
def test_a_site_takes_the_rules_word_places_its_kernel_and_counts_the_word(site, where, tpu, backend):
    has_specs, trace, counter = SITES[site]
    devices = mesh(where)
    backend(tpu)
    want = "kernel" if tpu and (has_specs or devices == 1) else "xla"
    assert placement.kernel_path(has_specs=has_specs) == want
    count = lambda path: regions_traced(counter[0], path=path, **counter[1]) if counter else 0
    before = {path: count(path) for path in ("kernel", "xla")}
    text = str(trace())
    assert ("pallas_call" in text) == (want == "kernel"), (site, where, tpu)
    # XLA's forms are GSPMD's to split; the routed FFN's ``local`` is a function of its own (it sums over ``expert``) and
    # sits in the module's wrapper whichever form its products take
    assert ("shard_map" in text) == ((want == "kernel" or site == "moe") and devices > 1), (site, where, tpu)
    if counter:
        rose = {path for path in before if count(path) > before[path]}
        assert rose == {want}, (site, where, tpu, rose)


@pytest.mark.parametrize("name,shape,rest,want", [
    ("no_mesh", (4, 8, 128, 16), ("tensor", None, None), P()),
    ("one_device", (4, 8, 128, 16), ("tensor", None, None), P()),  # an axis of one is no split
    ("four_devices", (4, 8, 128, 16), ("tensor", None, None), P("data", "tensor")),
    ("four_devices", (4, 3, 128, 16), ("tensor", None, None), P("data")),  # three heads: the tensor axis does not divide them
    ("four_devices", (3, 8, 128, 16), ("tensor", None, None), P(None, "tensor")),
    ("four_devices", (4, 128), (None,), P("data")),
])
def test_operands_split_over_the_batch_axes_where_the_axes_divide(name, shape, rest, want):
    mesh(name)
    assert placement.batch_spec(shape, *rest) == want
    assert placement.axis_size("tensor") == (2 if name == "four_devices" else 1) and placement.axis_size("expert") == 1


@pytest.mark.parametrize("case", ["segment_ids", "kv_cache"])
def test_an_attention_layer_that_falls_to_xla_on_a_tpu_is_counted_xla(case, backend):
    """The flash kernels take neither packed segments nor a padded cache (``flash_attention``'s ``falls``): on a TPU such a
    call runs XLA's form, and the layer's ``full_path`` word, which the trainer's first-call line is made of, says so.
    (Before ``ops/placement.py`` the layer counted what it guessed from the backend: ``kernel``.)"""
    backend(True)
    cfg = TransformerConfig(vocab_size=64, n_layers=1, n_heads=4, n_kv_heads=2, d_model=64, d_ff=64, max_seq_len=32, pos_emb="rope")
    mod, x, positions = Attention(cfg), rand(2, 16, 64), jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    more = {"segment_ids": jnp.zeros((2, 16), jnp.int32)} if case == "segment_ids" else \
        {"kv_cache": (jnp.zeros((2, 32, 2, 16), f32), jnp.zeros((2, 32, 2, 16), f32), jnp.zeros((), jnp.int32))}
    params = jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0), x, positions))["params"]
    count = lambda path: regions_traced("mixer/kernel", op="full", path=path, **{"pass": "fwd"})
    before = count("kernel"), count("xla")
    text = str(jax.make_jaxpr(lambda p: mod.apply({"params": p}, x, positions, **more))(params))
    assert "pallas_call" not in text
    assert (count("kernel") - before[0], count("xla") - before[1]) == (0, 1)
    # the same layer on plain rows takes the kernel and says so
    text = str(jax.make_jaxpr(lambda p: mod.apply({"params": p}, x, positions))(params))
    assert "pallas_call" in text and (count("kernel") - before[0], count("xla") - before[1]) == (1, 1)


def test_the_thin_names_are_the_rule():
    """What a model or a test still imports by a site's own name calls the one rule."""
    assert norms.replicated_on_mesh is placement.replicated_on_mesh
    assert short_conv.path_for(16384, 2048, 3) == sparse.path_for(256, 48) == "xla"  # no TPU here
