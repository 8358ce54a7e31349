"""Decoder-only transformer family (GPT-2 style and Llama style).

These play the role of the reference's test/bench models
(``tests/unit/simple_model.py``, Megatron/HF models in examples): the
framework is model-agnostic, but ships first-class implementations that
are TPU-shaped — einsum matmuls onto the MXU, bf16 activations, static
shapes, optional remat and scan-over-layers, attention dispatched through
the kernel registry (Pallas flash on TPU).

Tensor-parallel sharding is declared as partition rules (param-path ->
PartitionSpec) rather than module surgery: the AutoTP analogue
(reference ``module_inject/auto_tp.py``) consumes these rules.
"""

import contextlib
import contextvars
import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..layer_kind import LayerKind
from ..moe.layer import MOE_PARTITION_RULES, EarlyRoutedMoE, MoE, RoutedMoE
from ..ops.fused_ce import fused_cross_entropy, fused_cross_entropy_sums, fused_cross_entropy_tokens
from ..telemetry import device_counts
from ..telemetry.registry import get_registry
from ..telemetry.tracing import region
from ..utils.init_on_device import on_device_init
from .config import TransformerFields
# (much of what moved below this module is imported from here all the same)
from .layers import (MLP, SAVED, Attention, LayerNorm, LayerNormNP, RMSNorm, UnrotatedAttention, _norm, _rope_table, alibi_slopes,  # noqa: F401
                     apply_rope, make_norm, rope_frequencies, scaled_rope_frequencies)
from .mixers import (BlockDiffMixer, DiffAttention, DiffCrossAttention, GatedMemory, GDNMixer, KDAMixer, MLAMixer, ShortConvMixer,
                     SparseMixer, SSDMixer, SSMMixer)


class Absent(LayerKind):
    """The kind ``none``, in either table: the half of a block that is not there (a stack whose layers are a mixer OR an FFN
    alone, ``y = x + Part(norm(x))``). No module, no norm, no residual add, no parameters, nothing kept; the block's one
    part is built, normed and added as it is in a block of two. ``hybrid``: a block of one part keeps by name what that
    part's ``keeps`` say (its kernel's outputs and its projections), whatever the part: it has no other half whose
    products its inputs alone would have it make again. No stacked form runs it."""

    hybrid = True


# THE table of layer kinds. A kind is declared once: its flax module carries its record (``../layer_kind.py::LayerKind``) and has
# one line here; ``Block``, ``block_fn``, ``CausalLM.loss_fn``, ``runtime/engine.py`` and ``inference/v2/engine_v2.py`` read
# the record and name no kind. Adding a kind: its module, one line here, its files under ``benchmarks/configs/``. Imports
# point one way: ``config.py`` (nothing of the package) <- ``layers.py`` <- ``mixers.py``, ``moe/layer.py`` <- this module
MIXERS = {"full": Attention, "window": Attention, "kda": KDAMixer, "gdn": GDNMixer, "mla": MLAMixer, "sparse": SparseMixer}
MIXERS |= {"ssm": SSMMixer, "diff": DiffAttention, "diff_window": DiffAttention, "gmu": GatedMemory, "diff_cross": DiffCrossAttention}
MIXERS |= {"blockdiff": BlockDiffMixer, "nope": UnrotatedAttention, "conv": ShortConvMixer, "ssd": SSDMixer, "none": Absent}
FFNS = {"dense": MLP, "moe": MoE, "routed": RoutedMoE, "routed_early": EarlyRoutedMoE, "none": Absent}


def records(kinds=None) -> Tuple[type, ...]:
    """The distinct records of these (mixer, ffn) pairs, mixers' then FFNs', in the table's order; None: the table's."""
    named = lambda table, at: [cls for name, cls in table.items() if kinds is None or any(kind[at] == name for kind in kinds)]
    return tuple(dict.fromkeys(named(MIXERS, 0) + named(FFNS, 1)))


def kinds_sow(kinds) -> bool:  # whether a block of one of these (mixer, ffn) pairs may sow
    return any(record.sows for record in records(kinds))


def remat_keeps(kind: Tuple[str, str]) -> Tuple[str, ...]:
    """The names a checkpointed block of this kind keeps beside its inputs (``remat_policy``); none: plain
    ``jax.checkpoint``, which keeps the block's inputs alone.

    The rule, read off the records: EVERY checkpointed block keeps its kernels' outputs; a hybrid block keeps its
    projections' results too. A block is hybrid where either of its parts says so (``LayerKind.hybrid``: every mixer but
    softmax attention over one head size, and the routed FFN). It keeps what its two parts declare (``keeps``) and the one
    name the block itself gives (``SAVED``: the mixer's output added to its input); any other block keeps what its parts
    declare WITHOUT ``SAVED``, so a part whose ``keeps`` is ``SAVED`` alone adds nothing. A name that no value of a block
    carries does nothing in ``save_only_these_names``: where the flash kernel is not the path taken (the CPU,
    ``attention_xla``) a plain block keeps its inputs alone.

    A plain block (softmax attention over one head size beside a dense or capacity-gated FFN, the decoder most users of
    ``remat: true`` train) so keeps the flash call's ``o`` and ``lse``, and its backward runs no second forward kernel.
    What that costs a layer, held for every layer at once: ONE more value of the block's input size (``o``: tokens x
    heads x head size, in the model's dtype) and 4 bytes a head and row (``lse``, float32). Its six products over the
    model width are made again. Why they are not kept there (``PERF.md`` section 6, PR 64; 32 block applications of
    8,192 x 2,048 in bf16, ms of second forward saved a GB kept): ``o`` and ``lse`` 1.09 GB for 74 ms, 68 ms a GB;
    q, k, v 3.2 GB for about 40 ms, 12 ms a GB; the FFN's gate and up 5.9 GB for about 75 ms, 13 ms a GB. The kernel's
    outputs are five times cheaper a millisecond than any product's result; all the products' results together are
    11 GB, which a 16 GB chip that holds the state of such a model does not have.

    A hybrid block's backward runs no kernel, no product over or onto the model width, no top-k and no sort a second
    time: kept are the kernels' outputs (the scan's with its states and inverses, the flash call's with its row
    statistics), every projection's result and the routed layer's scores, choice, sorted rows and grouped products; what
    lies between them is elementwise (and the low-rank gates' second products, over 128) and is made again.

    What that costs (``PERF.md`` section 6, PR 40): the projections are kept for every layer at once, not inside one
    layer's peak, so they grow with depth and tokens: 0.16 (``gdn``), 0.29 (``mla``) and 0.30 GB (``kda``, routed FFNs
    with a shared expert) of the step's temporaries a layer at 8,192 tokens, for 7-10% more tokens a second at 4 to 6
    layers. Qwen3-Next's four layers at 16,384 tokens, which the kernels' outputs alone let compile for a 16 GB chip,
    are refused with them by 2.0 GB. Where a step is refused, the names to give up are the widest a millisecond saved,
    by the block's own shapes: latent attention's assembled k and v (``kv_b_proj`` contracts the latent's 512, a quarter
    of the width: keep the latent alone), then the shared and dense FFN's gate and up (two values of ``d_ff`` a token for
    two products), then the mixers' q/k/v; the gates', the router's and the latent's few MB stay to the last.

    A kept value is rounded to the dtype the model states: ``jax.checkpoint`` puts a ``reduce_precision`` on every
    residual's producer, so forward and backward read the same number, where XLA's excess precision may carry a value
    that is made again on in float32 (``xla_allow_excess_precision``); the gradients are those of the block without a
    checkpoint."""
    mixer, ffn = MIXERS[kind[0]], FFNS[kind[1]]
    names = tuple(dict.fromkeys(mixer.keeps + ffn.keeps + (SAVED,)))
    return names if mixer.hybrid or ffn.hybrid else tuple(name for name in names if name != SAVED)


def remat_policy(kind: Tuple[str, str]):
    """``jax.checkpoint``'s (and ``flax.linen.remat``'s) ``policy`` for a block of this kind: ``remat_keeps``'s names,
    or None (the block's inputs alone) where it has none."""
    keeps = remat_keeps(kind)
    return jax.checkpoint_policies.save_only_these_names(*keeps) if keeps else None


@dataclass(frozen=True)
class TransformerConfig(TransformerFields):
    """``TransformerFields`` (every field: ``config.py``) and what the table says of its layers' kinds."""

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, ffn) of every layer: ``layer_kinds``, or what the older
        fields say: a window on the layers ``window_layers`` lists (all of
        them where it is None), a MoE every ``moe_layer_freq``-th block."""
        return _kinds_of(self)

    def window_for(self, layer_idx: int) -> Optional[int]:
        """Sliding-window width for one layer (None = no window): a reading of ``kinds``."""
        return self.sliding_window if self.kinds[layer_idx][0] == "window" else None

    def moe_for(self, layer_idx: int) -> bool:
        """Whether one layer's FFN slot holds experts: a reading of ``kinds``."""
        return self.kinds[layer_idx][1] not in ("dense", "none")

    @property
    def uniform_window(self) -> bool:
        """True when every layer shares one window config (scan/v2-servable)."""
        return len({self.window_for(i) for i in range(self.n_layers)}) <= 1

    @property
    def unstackable(self) -> Tuple[str, ...]:
        """The names of this model's kinds that the stacked forms (the scan over layers, the pipeline's stacking,
        ``inference/v2``) cannot run: they take softmax attention over one head size and dense or capacity-gated FFNs."""
        return tuple(sorted({name for pair in self.kinds for name, table in zip(pair, (MIXERS, FFNS)) if not table[name].stackable}))

    @property
    def shares(self) -> Tuple[str, ...]:
        """The names of the values this model's layers give to or take from one another (``LayerKind.gives``, ``takes``):
        the unrolled loop over layers carries them beside the activations; the stacked forms carry activations alone."""
        return tuple(sorted({name for mixer, _ in self.kinds for name in MIXERS[mixer].gives + MIXERS[mixer].takes}))

    @property
    def objective(self):
        """The record of the one kind of this model's layers that states the objective (``LayerKind.targets``: which
        positions the loss head runs over, their targets and weights), or None: next-token prediction over the row."""
        stating = [record for record in records(self.kinds) if getattr(record, "targets", None) is not None]  # (a mixer's)
        if len(stating) > 1:
            raise ValueError(f"layers of {len(stating)} kinds each state an objective of their own: a model has one")
        return stating[0] if stating else None

    @property
    def sows(self) -> bool:
        """Whether a block of this model may sow (an expert layer's auxiliary loss and rows, a sparse mixer's index
        loss and key counts): its loss is then traced with those collections mutable."""
        return kinds_sow(self.kinds)


@functools.lru_cache(maxsize=256)
def _kinds_of(cfg: TransformerConfig) -> Tuple[Tuple[str, str], ...]:
    """``TransformerConfig.kinds``, worked out once a configuration (it is frozen and hashable; nothing is kept on
    the instance, whose ``__dict__`` callers copy into new configurations)."""
    if cfg.layer_kinds is not None:
        kinds = tuple((str(m), str(f)) for m, f in cfg.layer_kinds)
        bad = [k for k in kinds if k[0] not in MIXERS or k[1] not in FFNS]
        if len(kinds) != cfg.n_layers or bad:
            raise ValueError(f"layer_kinds must give n_layers={cfg.n_layers} pairs of {tuple(MIXERS)} x {tuple(FFNS)}, got "
                             f"{len(kinds)} with {bad}")
        if ("none", "none") in kinds:
            raise ValueError("a layer of kind ('none', 'none') has neither a mixer nor an FFN: a block is one part or two; leave the "
                             "layer out of layer_kinds and lower n_layers")
        return kinds
    freq = max(1, cfg.moe_layer_freq)
    windowed = lambda i: cfg.sliding_window is not None and (cfg.window_layers is None or i in cfg.window_layers)
    return tuple(("window" if windowed(i) else "full",
                  "moe" if cfg.moe_num_experts > 0 and i % freq == freq - 1 else "dense")
                 for i in range(cfg.n_layers))


class Block(nn.Module):
    """One transformer block. Its fields are all a trace of it can depend on:
    a layer's place in the stack enters only through ``kind``
    (``cfg.kinds[i]``: its mixer and its FFN), so layers of one kind share
    one traced function (``block_fn``)."""

    cfg: TransformerConfig
    kind: Tuple[str, str] = ("full", "dense")
    is_training: bool = True  # static: MoE capacity-drop is train-only

    def _mlp(self, cfg, h, made=None):
        """The FFN on ``h``, handed by name what its record says it ``takes`` of the values this block ``made``."""
        ffn = FFNS[self.kind[1]]
        if ffn.takes and made is None:
            raise NotImplementedError(f"a {self.kind[1]} FFN takes {', '.join(ffn.takes)} of a sequential pre-norm block; this "
                                      f"block is block_type={cfg.block_type!r}, norm_scheme={cfg.norm_scheme!r}")
        return ffn.from_config(cfg, self.kind[1])(h, self.is_training, **{name: made[name] for name in ffn.takes})

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, segment_ids=None, taken=None):
        """``taken``: the values the mixer's record says it ``takes``, by name; a mixer that ``gives`` makes the result
        ``(x, {name: value})``."""
        cfg = self.cfg
        # the layer's two parts are built by their kinds' records (``LayerKind.from_config``), under their names in the tree
        mixer = MIXERS[self.kind[0]]
        one_part = Absent in (mixer, FFNS[self.kind[1]])
        if one_part and (kv_cache is not None or cfg.block_type != "sequential" or cfg.norm_scheme != "pre"):
            raise NotImplementedError(f"a block of one part ({self.kind}) is a sequential pre-norm block in training: it takes no KV "
                                      f"cache, and block_type={cfg.block_type!r}, norm_scheme={cfg.norm_scheme!r} wire two parts")
        attn = None if mixer is Absent else mixer.from_config(cfg, self.kind[0])
        given = {}

        def run_attn(h):
            if kv_cache is not None:
                return attn(h, positions, kv_cache, segment_ids)
            out = attn(h, positions, None, segment_ids, **{name: (taken or {})[name] for name in mixer.takes})
            if mixer.gives:
                out, values = out
                given.update(values)
            return out, None

        if cfg.block_type == "parallel_shared":  # falcon-7b / phi / gpt-j
            h = _norm(cfg, x)
            a, new_cache = run_attn(h)
            x = x + a + self._mlp(cfg, h)
        elif cfg.block_type == "parallel":  # gpt-neox use_parallel_residual
            a, new_cache = run_attn(_norm(cfg, x))
            x = x + a + self._mlp(cfg, _norm(cfg, x))
        elif cfg.norm_scheme == "post":  # BERT: norm AFTER each residual add
            a, new_cache = run_attn(x)
            x = _norm(cfg, x + a)
            x = _norm(cfg, x + self._mlp(cfg, x))
        elif cfg.norm_scheme == "sandwich":  # a norm before AND after each sublayer, the second inside the residual branch
            h = _norm(cfg, x)
            a, new_cache = run_attn(h)
            x = checkpoint_name(x + _norm(cfg, a), SAVED)
            x = x + _norm(cfg, self._mlp(cfg, _norm(cfg, x), {"mixer_input": h}))
        elif cfg.norm_scheme == "output":  # a norm on each sublayer's OUTPUT alone, inside the residual branch: none on its input
            # named (``SAVED``): a norm's backward starts from its INPUT, so a checkpointed block that keeps the two sublayers'
            # outputs does not make the output projection, the routed rows' return and the shared expert again to get them back;
            # the sums are the block's input plus two norms, elementwise, and are made again
            a, new_cache = run_attn(x)
            x = x + _norm(cfg, checkpoint_name(a, SAVED))
            x = x + _norm(cfg, checkpoint_name(self._mlp(cfg, x, {"mixer_input": x}), SAVED))
        elif one_part:  # y = x + Part(norm(x)): the one part's norm and add, nothing for the half that is not there
            h = _norm(cfg, x)
            x = x + (self._mlp(cfg, h, {"mixer_input": h}) if attn is None else run_attn(h)[0])
        else:
            h = _norm(cfg, x)
            a, new_cache = run_attn(h)
            # named (``SAVED``): the FFN half's backward starts from this sum, so a checkpointed block that keeps it does not
            # make the mixer's output projection again to get it back
            x = checkpoint_name(x + a, SAVED)
            # what the block made ahead of its mixer, for an FFN whose record asks (``LayerKind.takes``)
            x = x + self._mlp(cfg, _norm(cfg, x), {"mixer_input": h})
        if kv_cache is not None:
            return x, new_cache
        return (x, given) if mixer.gives else x


def _parts(out, cached: bool, kind: Tuple[str, str]):
    """What a ``Block`` of ``kind`` returned, as ((activations, new cache or None), the values its mixer gave by name)."""
    if cached:
        return out, {}
    return ((out[0], None), out[1]) if MIXERS[kind[0]].gives else ((out, None), {})


def _taken(cfg: TransformerConfig, i: int, shared: Dict) -> Dict:
    """What layer ``i``'s mixer takes (``LayerKind.takes``), by name: the model's own ``layer`` (the layer's published
    index, an int32 scalar: a value, so that layers of one kind share one trace) and what earlier layers gave."""
    takes = MIXERS[cfg.kinds[i][0]].takes
    have = dict(shared)
    if "layer" in takes:
        have["layer"] = jnp.asarray(i if cfg.layer_numbers is None else cfg.layer_numbers[i], jnp.int32)
    missing = [name for name in takes if name not in have]
    if missing:
        raise ValueError(f"layer {i} ({cfg.kinds[i][0]}) takes {', '.join(missing)}, which no earlier layer gives")
    return {name: have[name] for name in takes}


class Transformer(nn.Module):
    """Causal LM. ``__call__`` returns logits; ``loss`` the mean token CE."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, kv_caches=None, segment_ids=None, return_hidden=False,
                 train=None, pld_theta=None, token_type_ids=None):
        cfg = self.cfg
        # decode (kv caches) implies inference; forward-only callers pass
        # train=False so eval/serving never drops MoE tokens
        train = (kv_caches is None) if train is None else bool(train)
        if pld_theta is not None and cfg.scan_layers:
            raise ValueError("progressive layer drop needs the unrolled layer loop: set scan_layers=False")
        if cfg.scan_layers and len({mixer for mixer, _ in cfg.kinds}) > 1:
            raise ValueError("per-layer window_layers (layers of several mixers) needs heterogeneous blocks: set scan_layers=False")
        if cfg.scan_layers and cfg.shares:
            raise ValueError(f"layers that give or take values between blocks ({', '.join(cfg.shares)}) need the unrolled loop over "
                             f"layers, which carries them: set scan_layers=False")
        if cfg.scan_layers and cfg.unstackable:  # by the kinds' records (``LayerKind.stackable``)
            raise NotImplementedError(f"the scan over layers stacks softmax attention over one head size with dense or "
                                      f"capacity-gated MoE blocks; layers of kind {', '.join(cfg.unstackable)} need the unrolled "
                                      f"loop: set scan_layers=False")
        T = cfg.loop_steps
        if T > 1:
            for what, asked in (("scan_layers", cfg.scan_layers), ("kv_caches", kv_caches is not None),
                                ("pld_theta", pld_theta is not None), ("mlm_head", cfg.mlm_head)):
                if asked:
                    _refuse_loop(cfg, what)
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        emb = self.param("wte", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.d_model), jnp.float32)
        hook = _BLOCK_HOOK.get() if kv_caches is None and not self.is_initializing() else None
        if T > 1 and hook is not None:
            _refuse_loop(cfg, "block_hook")
        with region("embed"):
            x = hook.look_up(self.path + ("wte",), emb, input_ids, cfg.sows) if hook is not None else None
            x = (emb[input_ids] if x is None else x).astype(cfg.dtype)
            if cfg.embed_scale:  # gemma normalizer
                x = x * jnp.asarray(cfg.d_model**0.5, cfg.dtype)
            if cfg.pos_emb == "learned":
                wpe = self.param("wpe", nn.initializers.normal(0.02), (cfg.max_seq_len, cfg.d_model), jnp.float32)
                x = x + wpe[positions].astype(cfg.dtype)
            if cfg.type_vocab_size > 0:  # BERT segment embeddings
                tte = self.param("type_emb", nn.initializers.normal(0.02),
                                 (cfg.type_vocab_size, cfg.d_model), jnp.float32)
                tti = token_type_ids if token_type_ids is not None else jnp.zeros_like(input_ids)
                x = x + tte[tti].astype(cfg.dtype)
        if cfg.embedding_norm:  # bloom word_embeddings_layernorm / BERT embeddings.LayerNorm
            x = _norm(cfg, x)

        new_caches = [] if kv_caches is not None else None
        remat = cfg.remat and kv_caches is None
        if T > 1:
            x = self._loop_blocks(x, positions, segment_ids, train, remat)  # the passes' normed states, (T, B, S, d)
        elif cfg.scan_layers and kv_caches is None:
            # the stacked layers are of one kind (``unstackable``): its policy, as ``block_fn`` gives the unrolled ones
            x = self._scan_blocks(nn.remat(Block, static_argnums=(), policy=remat_policy(cfg.kinds[0])) if remat else Block, x,
                                  positions, segment_ids, train)
        else:
            # one traced function a KIND of block and program, applied once a layer to that layer's
            # parameters: the block's Python body runs once, not n_layers times
            kinds = functools.cache(functools.partial(block_fn, cfg, train=train, remat=remat))
            layers = [] if self.is_initializing() else [self.get_variable("params", f"layer_{i}")
                                                        for i in range(cfg.n_layers)]
            paths = [self.path + (f"layer_{i}",) for i in range(cfg.n_layers)]
            may_sow = [kinds_sow((kind,)) for kind in cfg.kinds]
            shared = {}  # the values layers have given so far, by name: a later giver of a name replaces an earlier one's
            for i in range(cfg.n_layers):
                kind = cfg.kinds[i]
                kv_cache = kv_caches[i] if kv_caches is not None else None
                taken = {} if kv_caches is not None else _taken(cfg, i, shared)
                if self.is_initializing():  # makes the tree: flax has to see every layer as a submodule
                    out = Block(cfg, kind, is_training=train, name=f"layer_{i}")(x, positions, kv_cache, segment_ids, taken)
                    (y, cache), given = _parts(out, kv_cache is not None, kind)
                else:
                    wrap = None
                    if hook is not None:
                        wrap, x = hook(paths, layers, may_sow, i, x)
                    (y, cache), sown, given = kinds(kind, wrap=wrap)(layers[i], x, positions, kv_cache, segment_ids, taken)
                    for col, tree in sown.items():  # what the block sowed (MoE auxiliary loss), where it was
                        if self.is_mutable_collection(col):
                            self.put_variable(col, f"layer_{i}", tree)
                shared.update(given)
                if kv_caches is not None:
                    new_caches.append(cache)
                elif pld_theta is not None and train:
                    # progressive layer drop (arXiv:2010.13369): deeper
                    # layers drop more; keep prob 1-(1-theta)*l/L
                    pkeep = 1.0 - (1.0 - pld_theta) * (i + 1) / cfg.n_layers
                    keep = jax.random.bernoulli(self.make_rng("pld"), pkeep)
                    y = jnp.where(keep, y, x)
                x = y

        if cfg.exit_gate and self.is_initializing():  # ``exit_gate/{kernel, bias}``, at zero: the loss alone reads them (``_loop_loss``)
            nn.Dense(1, name="exit_gate", param_dtype=jnp.float32, kernel_init=nn.initializers.zeros)(x[0, :1, :1].astype(jnp.float32))
        if T > 1:
            if return_hidden:
                return x
            x = x[-1]  # a caller that asks for logits gets the last pass's
        elif cfg.norm_scheme != "post":  # post-LN blocks already end normalized
            x = _norm(cfg, x)
        if cfg.mlm_head:
            # BERT cls.predictions.transform: dense + act + LN before the
            # tied decoder — part of the hidden pipeline so the fused-CE
            # loss path projects the transformed hidden
            x = nn.Dense(cfg.d_model, name="mlm_dense", dtype=cfg.dtype, param_dtype=jnp.float32)(x)
            # HF BertPredictionHeadTransform applies config.hidden_act
            if cfg.activation == "relu":
                x = nn.relu(x)
            else:
                x = nn.gelu(x, approximate=cfg.activation != "gelu_exact")
            x = _norm(cfg, x)
            # created unconditionally (not only on the logits path) so the
            # param tree is identical between loss and logits calls
            mlm_bias = self.param("mlm_bias", nn.initializers.zeros, (cfg.vocab_size,), jnp.float32)
        if return_hidden:
            # loss path: the head projection happens inside the fused CE
            # (ops/fused_ce.py) so full (B,S,V) logits never hit HBM
            return (x, new_caches) if kv_caches is not None else x
        with region("head"):
            if cfg.tie_embeddings:
                logits = jnp.einsum("bsd,vd->bsv", x, emb.astype(cfg.dtype))
                if cfg.mlm_head:  # BERT cls.predictions.bias rides the tied decoder
                    logits = logits + mlm_bias.astype(cfg.dtype)
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=cfg.lm_head_bias, name="lm_head", dtype=cfg.dtype,
                                  param_dtype=jnp.float32)(x)
            logits = logits.astype(jnp.float32)
        return (logits, new_caches) if kv_caches is not None else logits

    def _loop_blocks(self, x, positions, segment_ids, train, remat):
        """``cfg.loop_steps`` passes of the ``n_layers`` blocks over the SAME parameter trees, the final norm at the end of each
        pass; the passes' normed states, stacked. ONE ``lax.scan`` over passes whose body, under ``region("loop_step")``, is
        the stack and the norm as functions of their parameters: nothing is kept a pass but what ``block_fn`` keeps a block
        (with ``remat`` its input and its kernel's outputs, ``remat_keeps``: the scan stacks them a pass). Unrolled, the same
        passes made a step 1.3% longer, its compile twice as long and its temporaries 3 GB larger (``PERF.md`` section 6, PR 63)."""
        cfg = self.cfg
        if cfg.shares or cfg.sows or cfg.norm_scheme == "post":
            _refuse_loop(cfg, "layers that give, take or sow" if cfg.shares or cfg.sows else "norm_scheme='post'")
        norm = make_norm(cfg)  # ONE final norm, applied at the end of every pass
        if self.is_initializing():  # makes the tree: every parameter is reached in one pass
            for i, kind in enumerate(cfg.kinds):
                x = Block(cfg, kind, is_training=train, name=f"layer_{i}")(x, positions, None, segment_ids, {})
            return norm(x)[None]
        kinds = functools.cache(functools.partial(block_fn, cfg, train=train, remat=remat))
        layers = [self.get_variable("params", f"layer_{i}") for i in range(cfg.n_layers)]

        # a flax module may not be called inside the scan: the norm there is a function of its parameters, found by its name
        scale = self.variables["params"].get(norm.name, {})

        def one_pass(x, nothing):
            with region("loop_step"):  # on every instruction of a pass: what the step spends in the loop, beside the head and the gate
                for i, kind in enumerate(cfg.kinds):
                    (x, _), _, _ = kinds(kind)(layers[i], x, positions, None, segment_ids, {})
                with region("norm"):
                    x = make_norm(cfg).apply({"params": scale}, x)
            return x, x

        return jax.lax.scan(one_pass, x, None, length=cfg.loop_steps)[1]

    def _scan_blocks(self, block_cls, x, positions, segment_ids, train=True):
        cfg = self.cfg

        class ScanBody(nn.Module):
            cfg: TransformerConfig

            @nn.compact
            def __call__(self, carry, _):
                y = block_cls(self.cfg, self.cfg.kinds[0], is_training=train,
                              name="block")(carry, positions, None, segment_ids)
                return y, None

        scanned = nn.scan(ScanBody, variable_axes={"params": 0}, split_rngs={"params": True}, length=cfg.n_layers,
                          metadata_params={nn.PARTITION_NAME: "layers"})
        x, _ = scanned(cfg, name="layers")(x, None)
        return x


def _refuse_loop(cfg, what):
    """A looped stack (``loop_steps > 1``) runs through the unrolled loop over layers in training alone; ``what`` cannot."""
    why = {"scan_layers": "the scan over layers stacks the parameters a layer, and a pass would scan the stack anew: set scan_layers=False "
                          "(the loop scans the PASSES)",
           "kv_caches": "a looped model needs loop_steps caches a layer (or the last pass's alone) and an exit decided a token at "
                        "decode: training-side only",
           "block_hook": "ZeRO-3's per-layer gather (zero/overlap.py) would gather a layer once a pass: run stage 0-2 or overlap_comm off",
           "to_pipeline": "a stage would be handed the activations loop_steps times round the pipe, which the schedule does not do"}
    raise NotImplementedError(f"loop_steps={cfg.loop_steps} does not run with {what}: "
                              f"{why.get(what, 'the loop over passes carries activations alone, to one head after every pass')}")


def exit_distribution(logits):
    """``(log p, p)``, each (T, ...): the exit distribution over T passes from the T - 1 gates' logits (T - 1, ...), float32.
    ``lambda_t = sigmoid(logits[t])``, ``p_t = lambda_t prod_{j<t}(1 - lambda_j)`` and the last pass takes what is left, so the
    ``p_t`` sum to one and the last pass has no gate."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-logits), axis=0)  # log prod_{j<=t}(1 - lambda_j)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], axis=0)
    log_p = jnp.concatenate([jax.nn.log_sigmoid(logits) + before, stay[-1:]], axis=0)
    return log_p, jnp.exp(log_p)


def _count_loop(values):
    """On the host, a step's ``[mean p_t] + [mean nll_t] + [H(p), sum_t t p_t, block applications]``."""
    reg, T = get_registry(), (len(values) - 3) // 2
    for t in range(T):
        reg.gauge("train_loop_exit_mass", step=str(t + 1)).set(float(values[t]))
        reg.gauge("train_loop_step_loss", step=str(t + 1)).set(float(values[T + t]))
    reg.gauge("train_loop_exit_entropy").set(float(values[-3]))
    reg.gauge("train_loop_expected_steps").set(float(values[-2]))
    reg.counter("train_loop_block_applications_total").inc(float(values[-1]))


_SOWN = ("losses", "intermediates")  # collections a block may write (``moe/layer.py``)

_BLOCK_HOOK: contextvars.ContextVar = contextvars.ContextVar("transformer_block_hook", default=None)


@contextlib.contextmanager
def block_hook(hook):
    """While a ``Transformer`` is traced inside, its loop over layers asks
    ``hook(paths, layers, sows, i, x)`` before each block it applies without
    a KV cache: every layer's path in the parameter tree, its parameters and
    whether it may sow (lists: the hook may replace the parameters of layers
    still to come), which layer this is, and the activations it is about to
    take. The answer is ``(wrap, x)``: the activations to feed it, and
    ``block_fn``'s ``wrap`` for it or None. Layers that are to share one
    trace get the same ``wrap`` object.

    The token embedding's look-up is offered as ``hook.look_up(path, table,
    ids, sows)``: the answer is ``table[ids]`` or None. The loss head is
    offered as ``hook.head(paths, leaves, fn, vocab_dim, sows)``: its
    parameters' paths in the tree, the parameters (the weight first, then a
    bias), ``fn(leaves, hidden, labels, vocab_axis=None)`` that gives the
    summed loss and the count of tokens, each of shape (1,), and which
    dimension of the weight is the vocabulary. The answer is None, or
    ``run(hidden, labels)`` whose results, summed, are ``fn``'s.

    This is how a trainer runs a region some other way than XLA's
    partitioner would without the model knowing how. The three offers (a
    block, the look-up, the head) are ZeRO-3's: ``runtime/zero/overlap.py``
    is their one client."""
    token = _BLOCK_HOOK.set(hook)
    try:
        yield
    finally:
        _BLOCK_HOOK.reset(token)


def block_fn(cfg: TransformerConfig, kind: Tuple[str, str], train: bool, remat: bool, wrap=None):
    """One kind of block as ONE traced function of (the layer's parameters,
    activations, positions, its KV cache, segment ids, the values its mixer
    takes by name) -> ((activations, new cache), what the block sowed, the
    values its mixer gives by name). The two dicts are empty for a kind whose
    record names none, and an empty dict is no argument and no result of the
    traced function: such a kind's equations are what they were without them.

    ``jax.jit`` keys its trace on the abstract arguments, so every layer of
    the kind after the first reuses the jaxpr: the block's Python body (flax
    and all) runs once a kind, and each further layer replays the cached
    equations into the program (``inline=True``) with its constants shared.
    Why replayed and not called: XLA's TPU pipeline inlines a computation
    that has ONE call site before it partitions and keeps one with several,
    then partitions that computation once, as a function, without its callers
    in view. On four chips with ZeRO-3 that chose other collectives for the
    sixteen-fold block and cost 9% of the step (``PERF.md``, PR 27). Inlined
    at trace time the program is, equation for equation, the unrolled one.
    The v2 runner, whose programs are not partitioned by XLA, calls its layer
    (``inference/v2/model_runner.py`` ``_stack_body``).

    Built once a program (a call of ``Transformer.__call__``), never kept:
    what a trace reads of the process (the op registry, the mesh topology) is
    read as often as before. A block draws nothing today (no dropout, no
    router jitter); what it may draw later comes in as a key argument, as
    what it sows goes out. ``wrap`` takes the function and returns one of
    the same signature that runs the block some other way (``block_hook``);
    it is traced once a kind all the same."""
    block = Block(cfg, kind, is_training=train)

    def apply(params, x, positions, kv_cache, segment_ids, taken):
        # the Python body runs once a trace, not once a call: its count is the kinds of block a program traced. What a
        # block does outside its parts (the residual adds) falls to this region
        with region("block", site="train"):
            out, sown = block.apply({"params": params}, x, positions, kv_cache, segment_ids, taken, mutable=_SOWN)
        out, given = _parts(out, kv_cache is not None, kind)
        return out, sown, given

    fn = wrap(apply) if wrap is not None else apply
    if remat:
        # every block keeps, by name, its kernels' outputs, so its backward runs no kernel's forward again; a hybrid block
        # every projection's result too, and makes again only the elementwise work between them (``remat_keeps``)
        fn = jax.checkpoint(fn, policy=remat_policy(kind))
    return jax.jit(fn, inline=True)



def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray, ignore_index: int = -100) -> jnp.ndarray:
    """Mean CE over non-ignored positions; logits fp32 (B,S,V), labels (B,S)."""
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)


def _head_sums(leaves, hidden, labels, dtype, vd_layout, vocab_axis=None):
    """The loss head: the summed token cross-entropy and the count of tokens
    that are not ignored, each of shape (1,). ``vocab_axis``: as
    ``fused_cross_entropy_sums`` has it, the weight being this device's
    slice of the vocabulary and a bias that slice's or whole."""
    w, bias = leaves[0].astype(dtype), leaves[1] if len(leaves) > 1 else None
    n_own = w.shape[0 if vd_layout else 1]
    if vocab_axis is not None and bias is not None and bias.shape[0] != n_own:
        bias = jax.lax.dynamic_slice_in_dim(bias, jax.lax.axis_index(vocab_axis) * n_own, n_own)
    total, count = fused_cross_entropy_sums(hidden, w, labels, vd_layout=vd_layout, bias=bias, vocab_axis=vocab_axis)
    return total[None], count[None]


class CausalLM:
    """Binds a Transformer to the engine's ``loss_fn(params, batch, rng)`` contract.

    Batch convention: dict with ``input_ids`` (B,S) int32 and optional
    ``labels`` (shifted internally if absent).
    """

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self.module = Transformer(cfg)

    def init(self, rng, example_batch) -> Dict:
        return on_device_init(lambda: self.module.init(rng, example_batch["input_ids"])["params"])()

    def apply(self, params, input_ids, **kwargs):
        return self.module.apply({"params": params}, input_ids, **kwargs)

    def loss_fn(self, params, batch, rng=None) -> jnp.ndarray:
        input_ids = batch["input_ids"]
        pld_theta = batch.get("pld_theta")  # injected by the engine when PLD is on
        extra = {}
        if self.cfg.type_vocab_size > 0 and "token_type_ids" in batch:
            extra["token_type_ids"] = batch["token_type_ids"]
        if pld_theta is not None:
            if rng is None:
                raise ValueError("progressive layer drop needs the engine's step rng")
            extra["pld_theta"] = pld_theta
            extra["rngs"] = {"pld": rng}
        cfg = self.cfg
        if cfg.tie_embeddings:
            head = (("wte",),) + ((("mlm_bias",),) if cfg.mlm_head else ())
        else:
            head = (("lm_head", "kernel"),) + ((("lm_head", "bias"),) if cfg.lm_head_bias else ())
        leaves = tuple(functools.reduce(lambda tree, name: tree[name], path, params) for path in head)
        reported = []
        if cfg.sows:
            hidden, mods = self.module.apply({"params": params}, input_ids, return_hidden=True,
                                             mutable=_SOWN, **extra)
            aux_leaves = jax.tree_util.tree_leaves(mods.get("losses", {}))
            aux = sum(jnp.sum(l) for l in aux_leaves) if aux_leaves else 0.0
            # every kind present is handed what was sown: its device counts leave the step as an output, and what it
            # returns is its own loss (the FFNs' first: the order the step's equations have)
            reported = [record.report(mods.get("intermediates", {})) for record in reversed(records(cfg.kinds)) if record.report]
        else:
            hidden = self.apply(params, input_ids, return_hidden=True, **extra)
            aux = 0.0
        own = [loss for loss in reported if loss is not None]
        own = sum(own[1:], own[0]) if own else 0.0  # no ``0 +`` ahead of the one there is today
        if cfg.loop_steps > 1:
            if cfg.objective is not None:
                _refuse_loop(cfg, "an objective of a kind's own")
            return self._loop_loss(params, hidden, leaves, batch)
        with region("head"):
            w = leaves[0].astype(cfg.dtype)
            weights = None
            if cfg.objective is not None:
                # a kind with an objective of its own (block diffusion's masked-token loss): the head runs over the
                # positions it names, against its targets, each weighed by a CONSTANT (``fused_cross_entropy_sums`` gives
                # a weight no gradient; weights that carry one are the exit distribution's, ``_loop_loss``); all made here
                # from the ids, elementwise
                if "labels" in batch:
                    raise ValueError(f"a {cfg.objective.__name__} model makes its targets from input_ids: give no labels")
                keep, labels, weights, divisor = cfg.objective.targets(cfg, input_ids)
                hidden = hidden[:, keep]
            elif "labels" in batch:
                labels = batch["labels"]
            else:
                # shift left; keep S intact (last position ignored) so the fused
                # CE's sequence chunking stays aligned
                labels = jnp.concatenate(
                    [input_ids[:, 1:], jnp.full((input_ids.shape[0], 1), -100, input_ids.dtype)], axis=1)
            hook = _BLOCK_HOOK.get()
            by_hook = None
            if hook is not None:
                by_hook = hook.head(head, leaves, functools.partial(_head_sums, dtype=cfg.dtype, vd_layout=cfg.tie_embeddings),
                                    0 if cfg.tie_embeddings else 1, cfg.sows)
            if weights is not None:
                if by_hook is not None:
                    raise NotImplementedError("ZeRO-3's sliced loss head (zero/overlap.py) sums unweighted targets: a model "
                                              "whose objective weighs them (block diffusion) runs stage 0-2 or overlap_comm off")
                total, _ = fused_cross_entropy_sums(hidden, w, labels, vd_layout=cfg.tie_embeddings,
                                                    bias=leaves[1] if len(leaves) > 1 else None, weights=weights)
                ce = total / divisor
            elif by_hook is None:
                ce = fused_cross_entropy(hidden, w, labels, vd_layout=cfg.tie_embeddings,
                                         bias=leaves[1] if len(leaves) > 1 else None)
            else:  # a share of the sum and of the count from each device
                total, count = by_hook(hidden, labels)
                ce = jnp.sum(total) / jnp.maximum(jnp.sum(count), 1)
            # a kind's own loss (a sparse mixer's indexer's) adds its gradient, which reaches that kind's leaves alone, and
            # not its value: the step's loss stays the language model's (the value leaves the step as a device count)
            return ce + self.cfg.moe_aux_loss_coef * aux + (own - jax.lax.stop_gradient(own))

    def _loop_loss(self, params, hidden, leaves, batch):
        """A looped stack's loss from its ``T`` passes' normed states (T, B, S, d): the ONE head on every pass's state, a
        token's cross-entropy a pass (``fused_cross_entropy_tokens``: the logits never reach HBM, and the cotangent it is
        handed is a weight a token). Without a gate the loss is the last pass's. With one, in float32: the gate's logit a pass and
        token, the exit distribution ``p`` (``exit_distribution``) and the mean over targets of ``sum_t p_t nll_t -
        exit_entropy_coef H(p)``: ``p`` is DIFFERENTIATED, into the gate and through the states into the stack, as ``nll``
        is. What the step counts of it leaves as a device count (``_count_loop``)."""
        cfg, input_ids = self.cfg, batch["input_ids"]
        T = cfg.loop_steps
        labels = batch["labels"] if "labels" in batch else jnp.concatenate(
            [input_ids[:, 1:], jnp.full((input_ids.shape[0], 1), -100, input_ids.dtype)], axis=1)
        counted = jnp.maximum(jnp.sum(labels != -100), 1)
        w, bias = leaves[0].astype(cfg.dtype), leaves[1] if len(leaves) > 1 else None
        # ONE call over the passes it reads, stacked along the batch: the head's weight gradient is accumulated once, in one
        # float32 buffer, where a call a pass kept four alive (1.6 GB at 2,048 x 49,152). Ignored targets read 0
        read = hidden if cfg.exit_gate else hidden[-1:]
        with region("head", passes=str(read.shape[0])):
            nll = fused_cross_entropy_tokens(read.reshape(-1, *read.shape[2:]), w, jnp.tile(labels, (read.shape[0], 1)),
                                             vd_layout=cfg.tie_embeddings, bias=bias).reshape(read.shape[:3])
        if not cfg.exit_gate:
            return jnp.sum(nll) / counted
        with region("exit_gate"):
            gate = params["exit_gate"]
            logits = jnp.einsum("tbsd,d->tbs", hidden[:-1].astype(jnp.float32), gate["kernel"][:, 0].astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST) + gate["bias"].astype(jnp.float32)
            log_p, p = exit_distribution(logits)
            entropy = -jnp.sum(p * log_p, axis=0)
            mean = lambda per_token: jnp.sum(jnp.where(labels != -100, per_token, 0.0), axis=(-2, -1)) / counted
            steps = jnp.arange(1, T + 1, dtype=jnp.float32)
            device_counts.report("train_loop", jnp.concatenate([
                mean(p), jnp.sum(nll, axis=(-2, -1)) / counted,
                jnp.stack([mean(entropy), mean(jnp.einsum("t,tbs->bs", steps, p)), jnp.float32(cfg.n_layers * T)])]), _count_loop)
            return mean(jnp.sum(p * nll, axis=0) - cfg.exit_entropy_coef * entropy)

    def to_pipeline(self, num_stages: int, params=None, rng=None, example_batch=None):
        """Split the model into (embed, S stacked stages, head) for the
        pipeline engine. Stage params get a leading stage dim sharded over
        the ``pipe`` mesh axis; each stage runs n_layers/num_stages blocks.

        ``params``: existing parameter pytree to restructure (preferred);
        otherwise freshly initialized from ``rng`` + ``example_batch``.
        Returns (pipe_params, embed_fn, stage_fn, head_loss_fn, rules);
        ``embed_fn``/``head_loss_fn`` receive the shared non-stage param
        groups ``{"embed", "head"}`` so tied embeddings (reference
        ``TiedLayerSpec``, ``pipe/module.py:77``) are ONE leaf used by
        both ends — the compiler sums its two grad contributions, which is
        the reference's tied-grad allreduce (``pipe/engine.py:264``).
        """
        cfg = self.cfg
        if cfg.loop_steps > 1:
            _refuse_loop(cfg, "to_pipeline")
        if cfg.n_layers % num_stages != 0:
            raise ValueError(f"n_layers={cfg.n_layers} must divide evenly into {num_stages} pipeline stages")
        if cfg.scan_layers:
            raise ValueError("disable scan_layers for pipeline (stages are stacked instead)")
        if cfg.unstackable:
            raise NotImplementedError(f"layers of kind {', '.join(cfg.unstackable)} are not pipeline-partitionable yet: the stages' "
                                      f"stacking takes softmax attention and dense or capacity-gated MoE blocks")
        if cfg.shares:
            raise NotImplementedError(f"layers give or take {', '.join(cfg.shares)} between blocks, which the stages' stacking does "
                                      f"not carry: a stage is handed its activations alone")
        if cfg.mlm_head or cfg.type_vocab_size > 0:
            raise NotImplementedError("BERT-style models (mlm_head / token-type embeddings) are not "
                                      "pipeline-partitionable (the MLM head and segment embeddings are "
                                      "not part of the pipelined embed/loss functions)")
        layers_per_stage = cfg.n_layers // num_stages

        # Per-layer heterogeneity (MoE slots, sliding windows) pipelines by
        # stacking: sub-layer j of every stage shares one block program, so
        # the kind at global index s*lps+j must agree across stages s. MoE
        # (every moe_layer_freq-th block, reference moe/layer.py:90 under
        # pipe/module.py:86) aligns iff layers_per_stage % moe_layer_freq == 0;
        # sliding windows iff each sub-layer's window is identical across
        # stages (gpt-neo's alternating global/local pattern aligns whenever
        # layers_per_stage is even; qwen2 suffix windows only when the suffix
        # starts on a stage boundary AND covers whole stages)
        kind_per_sub = []
        for j in range(layers_per_stage):
            ks = {cfg.kinds[s * layers_per_stage + j] for s in range(num_stages)}
            if len({k[0] for k in ks}) > 1:
                raise NotImplementedError(
                    f"per-layer window pattern is not stage-uniform (sub-layer {j} sees mixers {sorted(k[0] for k in ks)} "
                    f"across stages); choose num_stages so the window pattern repeats per stage")
            if len(ks) > 1:
                raise ValueError(f"MoE x pipeline needs a stage-uniform expert pattern: sub-layer {j} is {sorted(ks)} "
                                 f"across stages")
            kind_per_sub.append(ks.pop())

        if params is None:
            params = self.init(rng if rng is not None else jax.random.PRNGKey(0), example_batch)

        # flax auto-names the module-level norms in creation order: the
        # embedding norm (bloom) is created before the blocks, the final
        # norm after them; layernorm_np (olmo) creates no params at all
        auto_norm_keys = sorted((k for k in params if k.rsplit("_", 1)[0] in ("LayerNorm", "RMSNorm")),
                                key=lambda k: int(k.rsplit("_", 1)[1]))
        embed_norm_key = auto_norm_keys.pop(0) if (cfg.embedding_norm and auto_norm_keys) else None

        embed_params = {"wte": params["wte"]}
        if cfg.pos_emb == "learned":
            embed_params["wpe"] = params["wpe"]
        if embed_norm_key is not None:
            embed_params[embed_norm_key] = params[embed_norm_key]
        # stack block params: sub_j leaf -> (S, ...) over stages
        stages = {}
        for j in range(layers_per_stage):
            per_stage = [params[f"layer_{s * layers_per_stage + j}"] for s in range(num_stages)]
            structs = {jax.tree_util.tree_structure(p) for p in per_stage}
            if len(structs) > 1:
                raise ValueError(f"sub-layer {j} has mismatched param structure across stages "
                                 f"(per-layer heterogeneity must be stage-uniform): {structs}")
            stages[f"sub_{j}"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *per_stage)
        head_params = {k: v for k, v in params.items()
                       if not (k.startswith("layer_") or k in ("wte", "wpe") or k == embed_norm_key)}
        pipe_params = {"embed": embed_params, "stages": stages, "head": head_params}

        # one block program per sub-layer: sub-layer j reproduces the global
        # MoE slot pattern (given the divisibility check above) and carries
        # the stage-uniform window
        blocks = [Block(cfg, kind_per_sub[j]) for j in range(layers_per_stage)]
        has_moe = cfg.moe_num_experts > 0
        norm_key = [k for k in head_params if "Norm" in k]
        paramless_norm = cfg.norm == "layernorm_np"

        def embed_fn(ps, input_ids):
            ep = ps["embed"]
            B, S = input_ids.shape
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
            x = ep["wte"][input_ids].astype(cfg.dtype)
            if cfg.embed_scale:  # gemma normalizer
                x = x * jnp.asarray(cfg.d_model**0.5, cfg.dtype)
            if cfg.pos_emb == "learned":
                x = x + ep["wpe"][positions].astype(cfg.dtype)
            if cfg.embedding_norm:  # bloom word_embeddings_layernorm
                if embed_norm_key is not None:
                    x = make_norm(cfg).apply({"params": ep[embed_norm_key]}, x)
                else:
                    x = make_norm(cfg).apply({"params": {}}, x)
            return x

        def stage_fn(sp, x):
            B, S = x.shape[0], x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
            aux = jnp.zeros((), jnp.float32)
            for j in range(layers_per_stage):
                if kinds_sow((kind_per_sub[j],)):
                    x, mods = blocks[j].apply({"params": sp[f"sub_{j}"]}, x, positions, mutable=_SOWN)
                    leaves = jax.tree_util.tree_leaves(mods.get("losses", {}))
                    aux = aux + sum(jnp.sum(l).astype(jnp.float32) for l in leaves)
                else:
                    x = blocks[j].apply({"params": sp[f"sub_{j}"]}, x, positions)
            if has_moe:
                # pre-scaled: the pipeline engine adds this straight into the
                # loss (and seeds its cotangent with 1.0 on the bwd clock)
                return x, aux * cfg.moe_aux_loss_coef
            return x

        stage_fn.has_aux = has_moe

        def head_loss_fn(ps, x, labels_or_ids, labels_are_shifted: bool):
            hp = ps["head"]
            if cfg.norm_scheme != "post":  # post-LN blocks already end normalized
                if paramless_norm:  # olmo: final norm has no params
                    x = make_norm(cfg).apply({"params": {}}, x)
                elif norm_key:
                    x = make_norm(cfg).apply({"params": hp[norm_key[0]]}, x)
            if labels_are_shifted:
                labels = labels_or_ids
            else:
                ids = labels_or_ids
                labels = jnp.concatenate([ids[:, 1:], jnp.full((ids.shape[0], 1), -100, ids.dtype)], axis=1)
            if cfg.tie_embeddings:
                return fused_cross_entropy(x, ps["embed"]["wte"].astype(cfg.dtype), labels, vd_layout=True)
            return fused_cross_entropy(x, hp["lm_head"]["kernel"].astype(cfg.dtype), labels, vd_layout=False,
                                       bias=hp["lm_head"]["bias"] if cfg.lm_head_bias else None)

        base_rules = self.partition_rules()
        rules = [(("stages",) + key, P(*(("pipe",) + tuple(spec)))) for key, spec in base_rules]
        rules += [(("stages",), P("pipe"))]
        rules += base_rules
        return pipe_params, embed_fn, stage_fn, head_loss_fn, rules

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        """Preallocated per-layer KV caches for incremental decoding."""
        cfg = self.cfg
        if cfg.loop_steps > 1:
            _refuse_loop(cfg, "kv_caches")
        dtype = dtype or cfg.dtype
        zeros = lambda: jnp.zeros((batch_size, max_len, cfg.kv_heads, cfg.head_dim), dtype)
        return [(zeros(), zeros(), jnp.asarray(0, jnp.int32)) for _ in range(cfg.n_layers)]

    def partition_rules(self):
        """(path-substring tuple, PartitionSpec) TP sharding rules — the
        AutoTP-analogue metadata (column-parallel QKV/up, row-parallel o/down,
        vocab-sharded embeddings). Paths are flax param path tuples."""
        return list(MOE_PARTITION_RULES) + [
            (("wte",), P("tensor", None)),
            (("wpe",), P(None, None)),
            (("q_proj", "kernel"), P(None, "tensor", None)),
            (("k_proj", "kernel"), P(None, "tensor", None)),
            (("v_proj", "kernel"), P(None, "tensor", None)),
            (("o_proj", "kernel"), P("tensor", None, None)),
            (("kv_b_proj", "kernel"), P(None, "tensor", None)),  # mla: heads expanded from the latent
            (("kv_a_proj", "kernel"), P(None, None)),
            (("gate_proj", "kernel"), P(None, "tensor")),
            (("up_proj", "kernel"), P(None, "tensor")),
            (("down_proj", "kernel"), P("tensor", None)),
            # column-parallel biases split with their kernel's output dim
            # (GPT-2-class models; row-parallel o/down biases stay whole
            # and add once, after the partial sums reduce)
            (("q_proj", "bias"), P("tensor", None)),
            (("k_proj", "bias"), P("tensor", None)),
            (("v_proj", "bias"), P("tensor", None)),
            (("gate_proj", "bias"), P("tensor")),
            (("up_proj", "bias"), P("tensor")),
            (("lm_head", "kernel"), P(None, "tensor")),
        ]


# -------------------- presets --------------------
def gpt2_tiny(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=1024, n_layers=2, n_heads=4, d_model=64, max_seq_len=256, **kw)


def gpt2_125m(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=50257, n_layers=12, n_heads=12, d_model=768, max_seq_len=1024, **kw)


def gpt2_1_3b(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=50257, n_layers=24, n_heads=32, d_model=2048, max_seq_len=1024, **kw)


def llama_tiny(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=1024, n_layers=2, n_heads=4, n_kv_heads=2, d_model=64, max_seq_len=256,
                             norm="rmsnorm", activation="swiglu", pos_emb="rope", tie_embeddings=False, **kw)


def llama2_7b(**kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=32000, n_layers=32, n_heads=32, d_model=4096, d_ff=11008, max_seq_len=4096,
                             norm="rmsnorm", activation="swiglu", pos_emb="rope", tie_embeddings=False, **kw)


def llama3_8b(**kw) -> TransformerConfig:
    """Llama-3.1-8B geometry: GQA 4:1, theta 5e5, banded rope scaling."""
    return TransformerConfig(vocab_size=128256, n_layers=32, n_heads=32, n_kv_heads=8, d_model=4096, d_ff=14336,
                             max_seq_len=131072, norm="rmsnorm", activation="swiglu", pos_emb="rope",
                             rope_theta=500000.0, rope_scaling="llama3", rope_factor=8.0,
                             rope_orig_max_seq=8192, tie_embeddings=False, **kw)
