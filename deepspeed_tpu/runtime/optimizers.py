"""Optimizer factories.

Parity with the reference's basic-optimizer zoo (``engine.py:1271``
``_configure_basic_optimizer``: FusedAdam/CPUAdam/FusedLamb/Lion/Adagrad/
1-bit variants). On TPU the "fused" property is XLA fusion over the whole
update (plus an explicit Pallas fused-Adam kernel in ``ops/pallas``); the
same optax transform serves both the replicated (stage 0) and partitioned
(ZeRO) paths, because partitioning is a sharding of the state pytree, not
a different algorithm.

All optimizers are wrapped in ``optax.inject_hyperparams`` so the LR
scheduler can write ``learning_rate`` each step without recompilation.
"""

from typing import Any, Callable, Dict, Optional, Tuple

import optax

from ..utils.logging import logger

ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
FUSED_ADAM = "fusedadam"
CPU_ADAM = "cpuadam"  # host-offloaded states; same math
LAMB_OPTIMIZER = "lamb"
LION_OPTIMIZER = "lion"
SGD_OPTIMIZER = "sgd"
ADAGRAD_OPTIMIZER = "adagrad"
ONEBIT_ADAM = "onebitadam"
ZERO_ONE_ADAM = "zerooneadam"
ONEBIT_LAMB = "onebitlamb"
MUON = "muon"


def _adam_args(params: Dict) -> Dict:
    return dict(
        learning_rate=params.get("lr", 1e-3),
        b1=params.get("betas", (0.9, 0.999))[0],
        b2=params.get("betas", (0.9, 0.999))[1],
        eps=params.get("eps", 1e-8),
        weight_decay=params.get("weight_decay", 0.01),
    )


def create_optimizer(name: Optional[str], params: Optional[Dict] = None) -> optax.GradientTransformation:
    """Build an optax optimizer from the config ``optimizer`` section."""
    params = dict(params or {})
    name = (name or ADAMW_OPTIMIZER).lower()

    if name in (ONEBIT_ADAM, ZERO_ONE_ADAM, ONEBIT_LAMB):
        from .fp16.onebit import onebit_adam, onebit_lamb, zero_one_adam

        a = _adam_args(params)
        common = dict(b1=a["b1"], b2=a["b2"], eps=a["eps"], weight_decay=params.get("weight_decay", 0.0))
        if name == ONEBIT_ADAM:
            factory = lambda learning_rate, **kw: onebit_adam(
                learning_rate, freeze_step=params.get("freeze_step", 100),
                bias_correction=params.get("bias_correction", False), **kw)
        elif name == ZERO_ONE_ADAM:
            factory = lambda learning_rate, **kw: zero_one_adam(
                learning_rate, var_freeze_step=params.get("var_freeze_step", 100),
                var_update_scaler=params.get("var_update_scaler", 16), **kw)
        else:
            factory = lambda learning_rate, **kw: onebit_lamb(
                learning_rate, freeze_step=params.get("freeze_step", 100),
                max_coeff=params.get("max_coeff", 10.0), min_coeff=params.get("min_coeff", 0.01),
                bias_correction=params.get("bias_correction", False), **kw)
        return optax.inject_hyperparams(lambda learning_rate: factory(learning_rate, **common))(
            learning_rate=a["learning_rate"])

    if name == MUON:
        from .muon import muon

        # only the lr is a (traced) hyperparam: the rest drive Python-level
        # branching inside the transform and must stay static
        static = dict(momentum=params.get("momentum", 0.95), nesterov=params.get("nesterov", True),
                      ns_steps=params.get("ns_steps", 5), adam_lr=params.get("adam_lr", 3e-4),
                      weight_decay=params.get("weight_decay", 0.0))
        return optax.inject_hyperparams(lambda learning_rate: muon(learning_rate, **static))(
            learning_rate=params.get("lr", 0.02))

    if name == FUSED_ADAM and params.get("adam_w_mode", True):
        # explicit Pallas fused kernel when a TPU backend is live; the
        # registry's XLA entry covers everything else (same math as the
        # plain adam path below — fusion is the only difference). The
        # kernel implements decoupled AdamW only: L2 mode falls through
        # to the optax path so adam_w_mode=false keeps reference math.
        from ..ops.registry import REGISTRY
        from ..parallel.mesh import get_mesh_topology

        if REGISTRY.selected("fused_adam") == "pallas":
            topo = get_mesh_topology(required=False)
            if topo is None or topo.n_devices == 1:
                a = _adam_args(params)
                return optax.inject_hyperparams(
                    lambda learning_rate: _pallas_fused_adamw(learning_rate, a["b1"], a["b2"], a["eps"],
                                                              a["weight_decay"]))(learning_rate=a["learning_rate"])
            # GSPMD cannot partition a Mosaic kernel, and the flat 1-D kernel
            # cannot follow each leaf's ZeRO sharding: say so and take the
            # XLA-fused update below (the same math)
            logger.info(f"fusedadam: {topo.n_devices}-device mesh — the Pallas kernel runs on one device only; "
                        "using the XLA-fused AdamW update")

    if name in (ADAM_OPTIMIZER, FUSED_ADAM, CPU_ADAM):
        a = _adam_args(params)
        adam_mode = params.get("adam_w_mode", True)
        if not adam_mode:
            # classic L2 (non-decoupled): decay folds into the gradient before
            # the moments — must match HostOffloadOptimizer's adamw_mode=False
            def adam_l2(learning_rate, b1, b2, eps, weight_decay):
                return optax.chain(optax.add_decayed_weights(weight_decay),
                                   optax.scale_by_adam(b1=b1, b2=b2, eps=eps),
                                   optax.scale(-1.0 * learning_rate))

            return optax.inject_hyperparams(adam_l2)(learning_rate=a["learning_rate"], b1=a["b1"], b2=a["b2"],
                                                     eps=a["eps"], weight_decay=a["weight_decay"])
        return optax.inject_hyperparams(optax.adamw)(**a)
    if name == ADAMW_OPTIMIZER:
        return optax.inject_hyperparams(optax.adamw)(**_adam_args(params))
    if name == LAMB_OPTIMIZER:
        a = _adam_args(params)
        return optax.inject_hyperparams(optax.lamb)(learning_rate=a["learning_rate"], b1=a["b1"], b2=a["b2"],
                                                    eps=a["eps"], weight_decay=a["weight_decay"])
    if name == LION_OPTIMIZER:
        return optax.inject_hyperparams(optax.lion)(
            learning_rate=params.get("lr", 1e-4),
            b1=params.get("betas", (0.9, 0.99))[0],
            b2=params.get("betas", (0.9, 0.99))[1],
            weight_decay=params.get("weight_decay", 0.0),
        )
    if name == SGD_OPTIMIZER:
        return optax.inject_hyperparams(optax.sgd)(learning_rate=params.get("lr", 1e-3),
                                                   momentum=params.get("momentum", 0.0),
                                                   nesterov=params.get("nesterov", False))
    if name == ADAGRAD_OPTIMIZER:
        return optax.inject_hyperparams(optax.adagrad)(learning_rate=params.get("lr", 1e-2),
                                                       eps=params.get("eps", 1e-10))
    raise ValueError(f"Unknown optimizer type: {name}")


def _pallas_fused_adamw(learning_rate, b1, b2, eps, weight_decay) -> optax.GradientTransformation:
    """AdamW over the Pallas fused kernel (reference FusedAdam,
    ``csrc/adam/multi_tensor_adam.cu``): one kernel pass per leaf updates
    param/exp_avg/exp_avg_sq together. Returns updates = new_p - p so it
    composes as a standard optax transform."""
    import jax
    import jax.numpy as jnp

    from ..ops.registry import get_op

    def init(params):
        zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
        return {"count": jnp.zeros((), jnp.int32),
                "m": jax.tree_util.tree_map(zeros, params),
                "v": jax.tree_util.tree_map(zeros, params)}

    def update(grads, state, params=None):
        assert params is not None, "fused adam needs params"
        count = state["count"] + 1
        kernel = get_op("fused_adam")

        def leaf(p, g, m, v):
            p32 = p.astype(jnp.float32)
            new_p, new_m, new_v = kernel(p32, g.astype(jnp.float32), m, v, learning_rate, count,
                                         b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)
            return (new_p - p32).astype(p.dtype), new_m, new_v

        out = jax.tree_util.tree_map(leaf, params, grads, state["m"], state["v"])
        is3 = lambda x: isinstance(x, tuple) and len(x) == 3
        treedef = jax.tree_util.tree_structure(grads)
        leaves = jax.tree_util.tree_leaves(out, is_leaf=is3)
        pick = lambda i: jax.tree_util.tree_unflatten(treedef, [t[i] for t in leaves])
        return pick(0), {"count": count, "m": pick(1), "v": pick(2)}

    return optax.GradientTransformation(init, update)
