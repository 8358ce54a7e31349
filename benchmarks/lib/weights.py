"""Seeded weights made on the device, in the type they are used in.

``CausalLM.init`` makes float32 parameters (15 GB for sixteen Mistral layers,
more than the chip holds beside the bf16 copy the engine then makes), so the
benchmark takes only the tree's shapes from the program (``jax.eval_shape``) and
fills every leaf in one jitted call: normal(0, 0.02) for matrices and the
embedding, ones for a norm's scale, zeros for a bias. The generator is XLA's
``rbg`` (the chip's own bit generator): threefry, JAX's default, took most of a
minute for 3.75 B values on a v5e (my chip run, PR 24). Same seed, same chip
kind, same weights.
"""

import jax
import jax.numpy as jnp
import numpy as np


def build_model(config: dict):
    """The program's ``CausalLM`` from a configuration file's ``program`` block."""
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    dtype = jnp.bfloat16 if config["program"].get("dtype") == "bfloat16" else jnp.float32
    return CausalLM(TransformerConfig(**dict(config["program"], dtype=dtype)))


def seed_key(seed: int, impl=None):
    """A PRNG key from any whole number up to 2**62: the driver's seeds pass
    2**31, which a 32-bit key seed does not hold."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl=impl), (seed >> 31) & 0x7FFFFFFF)


def param_shapes(model, seq_len: int = 16):
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, seq_len), np.int32)}))


def make_params(model, seed: int, dtype, std: float = 0.02, sharding=None):
    shapes = param_shapes(model)
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(key):
        leaves = []
        for i, (path, leaf) in enumerate(paths_leaves):
            name = jax.tree_util.keystr(path)
            if "scale" in name:
                leaves.append(jnp.ones(leaf.shape, dtype))
            elif "bias" in name:
                leaves.append(jnp.zeros(leaf.shape, dtype))
            else:
                leaves.append((jax.random.normal(jax.random.fold_in(key, i), leaf.shape, jnp.float32) * std).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(fill, out_shardings=sharding)(seed_key(seed, impl="rbg"))
