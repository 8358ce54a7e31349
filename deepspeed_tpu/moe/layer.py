"""MoE layer.

Parity: reference ``deepspeed/moe/layer.py`` (``MoE`` wrapper :17,
Residual MoE :30) + ``moe/experts.py`` (``Experts`` :13). Flax modules:
``MoE`` drops into a transformer's MLP slot; expert weights carry a
leading expert dimension sharded over the ``expert`` mesh axis (see
``partition_rules`` in ``models/transformer.py`` and the generic rules
here), which is what turns the dispatch einsums into all-to-alls under
GSPMD. Aux loss is sown into the ``losses`` collection and collected by
``CausalLM.loss_fn``.
"""

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..layer_kind import LayerKind
from ..ops import placement
from ..telemetry import device_counts
from ..telemetry.registry import get_registry
from ..telemetry.tracing import region
from .sharded_moe import GATES, RUNGS, SAVED, UNGATED, combine_output, gate_and_dispatch, routed_part, sigmoid_topk, softmax_topk


class Experts(nn.Module):
    """E parallel FFN experts evaluated with batched einsums (MXU-friendly).

    Reference ``moe/experts.py:13`` holds a ModuleList; here one stacked
    param with a leading expert dim, sharded over ``expert``.
    """

    num_experts: int
    d_model: int
    d_ff: int
    activation: str = "gelu"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):  # x: (E, C, d)
        E, d, f = self.num_experts, self.d_model, self.d_ff
        init = nn.initializers.normal(0.02)
        wi = self.param("wi", init, (E, d, f), jnp.float32)
        wo = self.param("wo", init, (E, f, d), jnp.float32)
        h = jnp.einsum("ecd,edf->ecf", x, wi.astype(self.dtype))
        if self.activation == "swiglu":
            wg = self.param("wg", init, (E, d, f), jnp.float32)
            g = jnp.einsum("ecd,edf->ecf", x, wg.astype(self.dtype))
            h = nn.silu(g) * h
        elif self.activation == "relu":
            h = nn.relu(h)
        else:
            h = nn.gelu(h, approximate=self.activation != "gelu_exact")
        return jnp.einsum("ecf,efd->ecd", h, wo.astype(self.dtype))


class MoE(LayerKind, nn.Module):
    """Reference ``moe/layer.py:17``. Gated expert-parallel FFN layer.

    Input (B, S, d) or (N, d); output same shape. The auxiliary
    load-balancing loss is sown under ``('losses', 'moe_aux_loss')``.
    """

    hidden_size: int
    num_experts: int = 8
    ep_size: int = 1  # informational; actual EP degree = mesh 'expert' axis
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_residual: bool = False
    d_ff: Optional[int] = None
    activation: str = "gelu"
    dtype: Any = jnp.float32
    sows, stackable = ("losses", "intermediates"), True  # its record as the layer kind ``moe``

    @classmethod
    def from_config(cls, cfg, kind):
        return cls(hidden_size=cfg.d_model, num_experts=cfg.moe_num_experts, k=cfg.moe_top_k,
                   capacity_factor=cfg.moe_capacity_factor, min_capacity=cfg.moe_min_capacity, d_ff=cfg.ffn_dim,
                   activation=cfg.activation, dtype=cfg.dtype, name="moe")

    @nn.compact
    def __call__(self, x, train: bool = True, rng=None):
        orig_shape = x.shape
        d = orig_shape[-1]
        assert d == self.hidden_size
        tokens = x.reshape(-1, d)

        gate_logits = nn.Dense(self.num_experts, use_bias=False, name="gate", dtype=jnp.float32,
                               param_dtype=jnp.float32)(tokens.astype(jnp.float32))
        cf = self.capacity_factor if train else self.eval_capacity_factor
        # inference must never drop a token (capacity is a TRAINING
        # regularizer; dropped tokens at eval silently corrupt logits —
        # cf. the v2 ragged serving path and the HF-parity contract)
        drop = self.drop_tokens and train
        l_aux, dispatched, combine, exp_counts = gate_and_dispatch(
            tokens, gate_logits, self.k, cf, self.min_capacity, rng=rng,
            noisy_gate_policy=self.noisy_gate_policy if train else None, drop_tokens=drop)

        # shard the expert dim -> XLA all-to-all over the expert mesh axis
        dispatched = jax.lax.with_sharding_constraint(dispatched, P("expert", None, None)) \
            if _mesh_has_axis("expert") else dispatched
        expert_out = Experts(self.num_experts, d, self.d_ff or 4 * d, self.activation, self.dtype,
                             name="experts")(dispatched)
        expert_out = jax.lax.with_sharding_constraint(expert_out, P("expert", None, None)) \
            if _mesh_has_axis("expert") else expert_out

        out = combine_output(expert_out, combine).reshape(orig_shape).astype(x.dtype)

        if self.use_residual:
            # Residual MoE (reference layer.py:30): mix with a dense MLP branch
            mlp_out = nn.Dense(d, use_bias=False, name="residual_mlp", dtype=self.dtype, param_dtype=jnp.float32)(
                nn.gelu(nn.Dense(self.d_ff or 4 * d, use_bias=False, name="residual_mlp_in", dtype=self.dtype,
                                 param_dtype=jnp.float32)(x)))
            coef = nn.Dense(2, use_bias=False, name="coefficient", dtype=jnp.float32, param_dtype=jnp.float32)(
                x.astype(jnp.float32))
            coef = jax.nn.softmax(coef, axis=-1)
            out = out * coef[..., 0:1].astype(x.dtype) + mlp_out * coef[..., 1:2].astype(x.dtype)

        self.sow("losses", "moe_aux_loss", l_aux)
        self.sow("intermediates", "exp_counts", exp_counts)
        return out


def _count_rows(rows):
    reg = get_registry()
    reg.counter("moe_rows_routed_here_total").inc(float(rows[:, 0].sum()))
    reg.counter("moe_rows_dropped_total").inc(float(rows[:, 1].sum()))
    reg.gauge("moe_expert_rows_max").set(float(rows[:, 2].max()))
    reg.gauge("moe_expert_rows_min").set(float(rows[:, 3].min()))
    reg.counter("moe_fallback_layers_total").inc(float((rows[:, 4] > 0).sum()))  # the first rung did not hold
    for rung, name in enumerate(RUNGS):
        reg.counter("moe_buffer_rung_layers_total", rung=name).inc(float((rows[:, 4] == rung).sum()))
    reg.gauge("moe_rows_over_uniform_max").set(float(rows[:, 5].max()) / 1000)
    # the rows that crossed chips (``exchanged_experts``: as many arrive somewhere as leave; 0 where no row travels) and
    # what the fullest and the emptiest chip of the host computed in a layer: the straggler
    sent, most, least = (rows[:, 6], rows[:, 7], rows[:, 8]) if rows.shape[1] > 6 else (0 * rows[:, 0], rows[:, 0], rows[:, 0])  # one chip: its own
    reg.counter("moe_rows_sent_total").inc(float(sent.sum()))
    reg.gauge("moe_chip_rows_max").set(float(most.max()))
    reg.gauge("moe_chip_rows_min").set(float(least.min()))


def report_rows(intermediates):
    """What the routed layers of a model sowed as ``rows`` in a forward pass,
    stacked and handed out of the step program for the registry
    (``telemetry/device_counts.py``: an output of the step, no host callback)."""
    rows = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates)
            if any(getattr(k, "key", None) == "rows" for k in path)]
    if rows:
        device_counts.report("moe_rows", jnp.stack(rows), _count_rows)


class RoutedMoE(LayerKind, nn.Module):
    """A routed FFN as the share of it that is held here, plus a shared expert.

    ``scoring="sigmoid"``: scores are sigmoids over ALL ``num_experts``; a
    token takes the top ``k`` of score + selection bias (``select_bias``: a
    parameter that takes no gradient, zero at start), weighted by the chosen
    scores rescaled to sum to one times ``scale``. ``scoring="softmax"``: a
    softmax over all of them, its top ``k``, rescaled the same way; no bias.
    ``shared_gate``: the shared expert's output times ``sigmoid(x w_s)``. ``held = (first, count)`` says which experts this
    layer holds (None: all): it routes over all, computes its own experts'
    part, and adds the shared expert once; what absent experts would add is
    left out. ``shared_ff`` is the shared part's whole width: a model with n
    shared experts of f gives n * f, since n SwiGLUs added are one with their
    columns side by side. No capacity, no drops, no auxiliary loss: cost follows the rows
    routed here (``sharded_moe.routed_part``). An expert has one of two forms,
    by the configuration's ``activation``. Gated, three matrices: ``wo (act(x
    wg) * x wi)``, the gate ``act`` ``relu`` for ``"reglu"`` and ``silu`` for
    every other value but the next. Ungated, two matrices (``"relu2"``,
    ``sharded_moe.UNGATED``): ``wo relu(x wi)^2``, no ``experts_wg`` and no
    ``shared_gate_proj`` in the parameter tree, through the same sort, ladder,
    grouped products and rows' sum; the shared expert has its experts' form.

    The router scores ``x``, the FFN's own input, unless the call is handed
    ``mixer_input`` (the kind ``routed_early``, ``EarlyRoutedMoE``): a router
    placed ahead of the attention reads the block's FIRST norm's output for
    ``idx`` / ``weights``, and the experts (and a shared one) still read ``x``.

    On a mesh the held experts are split by their leading dimension over its
    ``expert`` and ``fsdp`` axes (``MOE_PARTITION_RULES``), by one rule
    (``_over_expert_axis``): over an axis the rows are split over too
    (``fsdp``) each row goes to the chip that holds its expert and its result
    comes back (``sharded_moe.exchanged_experts``); over one they are not
    (``expert``) every chip routes the same tokens, computes the part of its
    own experts, and the parts are summed.
    """

    hidden_size: int
    num_experts: int
    k: int
    d_ff: int
    held: Optional[tuple] = None
    shared_ff: int = 0
    scale: float = 1.0
    scoring: str = "sigmoid"
    shared_gate: bool = False
    dtype: Any = jnp.float32
    renorm_eps: float = 1e-20  # sigmoid scoring: what is added to the sum the chosen scores are divided by
    act: str = "silu"  # the experts' gate (``sharded_moe.GATES``), or their activation where they have no gate (``UNGATED``)
    # its record as the layer kind ``routed``. The line's keys: how the grouped products and the rows' sum were traced,
    # the conditional's form where the buffer's first rung is smaller than every pair (``routed_part``: the rungs above
    # it keep nothing), and how the router scores its tokens and indexes the expert axis (``compare_sum``: ``held_experts``)
    sows, keeps, hybrid = ("intermediates",), (SAVED,), True
    paths = {"moe_path": ("ffn/experts", {}), "moe_combine": ("ffn/rows", {}), "moe_cond": ("ffn/cond", {})}
    path_words = {"moe_cond": "fallback_keeps_nothing"}  # the one form the conditional has
    joined = {"moe_router": ("ffn/router", ("sigmoid", "softmax", "compare_sum")), "moe_activation": ("ffn/experts", ("relu", "relu2"), "act"),
              "moe_exchange": ("ffn/exchange", ("rows", "sum"), "form")}  # how held experts split over a mesh meet their rows (``_over_expert_axis``); no key: nothing crosses chips
    report = staticmethod(report_rows)

    @classmethod
    def from_config(cls, cfg, kind):
        return cls(hidden_size=cfg.d_model, num_experts=cfg.moe_num_experts, k=cfg.moe_top_k, d_ff=cfg.moe_d_ff or cfg.ffn_dim,
                   held=cfg.moe_held, shared_ff=cfg.moe_shared_d_ff, scale=cfg.moe_route_scale, scoring=cfg.moe_scoring,
                   shared_gate=cfg.moe_shared_gate, dtype=cfg.dtype, renorm_eps=cfg.moe_renorm_eps, act={"reglu": "relu", "relu2": "relu2"}.get(cfg.activation, "silu"),
                   name="routed")

    @nn.compact
    def __call__(self, x, train: bool = True, mixer_input=None):
        d, E = self.hidden_size, self.num_experts
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"a routed layer scores by sigmoid or softmax, got {self.scoring!r}")
        first, count = self.held if self.held is not None else (0, E)
        tokens = x.reshape(-1, d)
        scored = tokens if mixer_input is None else mixer_input.reshape(-1, d)
        init = nn.initializers.normal(0.02)
        # the scoring, counted where it is chosen, and the input it read where that is not the FFN's own
        with region("ffn/router", path=self.scoring, **({} if mixer_input is None else {"input": "mixer_input"})):
            # which experts: no bf16 pass. Named, as the shared expert's products are (``SAVED``: a checkpointed block keeps
            # them): the scores and the activation follow by elementwise work
            logits = checkpoint_name(nn.Dense(E, use_bias=False, name="gate", dtype=jnp.float32, param_dtype=jnp.float32,
                                              precision=jax.lax.Precision.HIGHEST)(scored.astype(jnp.float32)), SAVED)
            if self.scoring == "softmax":
                idx, weights = softmax_topk(logits, self.k, self.scale)
            else:
                select_bias = self.param("select_bias", nn.initializers.zeros, (E,), jnp.float32)
                idx, weights = sigmoid_topk(logits, select_bias, self.k, self.scale, self.renorm_eps)
        gated = self.act not in UNGATED
        held = lambda name, *shape: self.param(f"experts_{name}", init, (count, *shape), jnp.float32).astype(self.dtype)
        wg = held("wg", d, self.d_ff) if gated else None  # an expert of two matrices has no such leaf
        wi, wo = held("wi", d, self.d_ff), held("wo", self.d_ff, d)
        # the rule's word; the products' own ``fits`` are ``sharded_moe._grouped``'s tiles and ``moe_sum_rows.fits``
        kernel = placement.kernel_path() == "kernel"
        out, *counts = _over_expert_axis(tokens.astype(self.dtype), idx, weights, wg, wi, wo, first, E, kernel, self.act)
        # (routed here, of them not computed, largest group, smallest group, the buffer's rung, routed here over the
        # uniform load in thousandths; on several chips also the rows that crossed chips and the fullest and the
        # emptiest chip's rows), sown: ``report_rows`` hands them on
        self.sow("intermediates", "rows", jnp.stack(counts).astype(jnp.int32))
        if self.shared_ff:
            with region("ffn/shared", **({"path": "gated"} if self.shared_gate else {})):
                dense = lambda feats, name: nn.Dense(feats, use_bias=False, name=name, dtype=self.dtype,
                                                     param_dtype=jnp.float32)
                of_tokens = lambda feats, name: checkpoint_name(dense(feats, name)(tokens), SAVED)
                if gated:
                    h = GATES[self.act](of_tokens(self.shared_ff, "shared_gate_proj")) * of_tokens(self.shared_ff, "shared_up_proj")
                else:
                    h = UNGATED[self.act](of_tokens(self.shared_ff, "shared_up_proj"))
                shared = dense(d, "shared_down_proj")(h)
                if self.shared_gate:  # the gate's backward reads the shared expert's output: named with the rest
                    gate = jax.nn.sigmoid(of_tokens(1, "shared_expert_gate").astype(jnp.float32))
                    shared = checkpoint_name(shared, SAVED) * gate.astype(shared.dtype)
                out = out + shared
        return out.reshape(x.shape).astype(x.dtype)


class EarlyRoutedMoE(RoutedMoE):
    """The kind ``routed_early``: ``RoutedMoE`` whose router is placed ahead of the attention. Its record asks the block
    for the first norm's output (``LayerKind.takes``) and scores THAT; the parameter tree is ``routed``'s."""

    takes = ("mixer_input",)
    joined = {**RoutedMoE.joined, "moe_router_input": ("ffn/router", ("mixer_input",), "input")}


def _over_expert_axis(tokens, idx, weights, wg, wi, wo, first, num_experts, kernel, act="silu"):
    """``routed_part`` on one chip; on a mesh, inside a shard_map in which the tokens are split over the batch axes and
    the held experts over ``placement.held_axes`` (``expert`` and ``fsdp``), by ONE rule: over an axis of those that the
    ROWS are split over too (``fsdp``: every chip its own tokens, the expert-parallel group a slice of the data-parallel
    one) a row travels to the chip that holds its expert and its result travels back (``exchanged_experts``); over an
    axis the rows are NOT split over (``expert``: every chip of it routes the same tokens) each chip computes its own
    experts' part and the parts are summed (``placement.on_mesh``: on one chip ``local`` is ``part``)."""
    axes = placement.held_axes(wo.shape)
    rows = placement.batch_spec(tokens.shape, None)
    split = rows[0] if len(rows) and rows[0] is not None else ()
    split = split if isinstance(split, tuple) else (split,)
    exchanged = tuple(a for a in axes if a in split)  # the rows travel
    summed = tuple(a for a in axes if a not in split)  # the parts are summed
    held = P(axes if len(axes) > 1 else axes[0], None, None) if axes else P()
    over = tuple(dict.fromkeys(split + axes))  # axes the pairs are spread over
    placed = placement.placed()  # several chips: the sown counts carry three more (what crossed chips, the fullest and the emptiest chip)

    def local(tokens, idx, weights, wg, wi, wo):
        n, axis = wo.shape[0], (exchanged or (None,))[0]
        block = lambda names: sum(jax.lax.axis_index(a) * int(np.prod([jax.lax.axis_size(b) for b in axes[axes.index(a) + 1:]])) for a in names)
        mine = first + block(summed) * n  # the first expert of this chip's block, or of its ``exchanged`` axis' blocks
        out, routed, dropped, largest, smallest, *sent, rung = routed_part(tokens, idx, weights, wg, wi, wo, mine, num_experts, kernel, act, axis=axis)
        uniform = idx.size * n * (jax.lax.axis_size(axis) if axis else 1) / num_experts  # what a uniform router sends a chip's experts
        over_uniform = jnp.round(routed.astype(jnp.float32) * (1000 / uniform)).astype(jnp.int32)
        if summed:
            with region("ffn/exchange", path="sum", form="sum"):
                out = jax.lax.psum(out, summed)
        sums, most, least = [routed, dropped], [largest, rung, over_uniform], [smallest]
        if placed:  # ... and what this chip computed: the straggler is the fullest
            sums, most, least = sums + [sent[0] if sent else jnp.zeros((), jnp.int32)], most + [routed], least + [routed]
        if over:  # the rung and the load are the fullest shard's: a (layer, step) pair counts once
            # (gathered and reduced: a count that came through an exchange carries a tangent of no type, and ``pmax`` has no rule)
            sums = [jax.lax.psum(x, over) for x in sums]
            most = [jnp.max(jax.lax.all_gather(x, over)) for x in most]
            least = [jnp.min(jax.lax.all_gather(x, over)) for x in least]
        (routed, dropped, *sent), (largest, rung, over_uniform, *most), (smallest, *least) = sums, most, least
        return out, routed, dropped, largest, smallest, rung, over_uniform, *sent, *most, *least

    # (an ungated expert's ``wg`` is None: no operand, and no spec for it)
    return placement.on_mesh(local, (rows, rows, rows, None if wg is None else held, held, held), (rows,) + (P(),) * 9)(tokens, idx, weights, wg, wi, wo)


def _mesh_has_axis(axis: str) -> bool:
    try:
        mesh = jax.sharding.get_abstract_mesh()
        return mesh is not None and axis in (mesh.axis_names or ())
    except Exception:
        return False


# expert dim over `expert` (EP), FFN dim over `tensor` — megatron-style
# per-expert TP (reference expert-tensor-parallelism, moe/mappings.py +
# FastGen's TP-sharded experts). GSPMD partitions the training einsums AND
# the serving `lax.ragged_dot` grouped GEMMs this way with only the
# canonical row-parallel allreduce (verified: no weight gathers in HLO),
# so Mixtral-class expert memory scales with tp instead of replicating.
MOE_PARTITION_RULES = [
    (("experts_wi",), placement.HELD),  # RoutedMoE: the held experts, whole in their width, over ``expert`` AND ``fsdp``:
    (("experts_wo",), placement.HELD),  # ZeRO's axis holds them BY EXPERT and never gathers them (``_over_expert_axis``)
    (("experts_wg",), placement.HELD),
    (("experts", "wi"), P("expert", None, "tensor")),
    (("experts", "wo"), P("expert", "tensor", None)),
    (("experts", "wg"), P("expert", None, "tensor")),
    (("gate", "kernel"), P(None, None)),
]
