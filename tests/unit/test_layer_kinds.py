"""A layer kind is declared once: its flax module carries its record (``deepspeed_tpu/layer_kind.py::LayerKind``) under one name in
the table (``models/transformer.py``), and the model, the trainer and the server read the record and name no kind.

(a) what a new kind costs: a mixer defined HERE, one line of the table, and a model with it trains through
``deepspeed_tpu.initialize`` under ``remat`` with its own sown count reported and its own key on the first-call line;
(b) the fifteen kinds' records against what a traced block of each sows, names and counts, and against what the stacked
forms take; (c) the hosts' sources spell no kind; and what must not move: ``TransformerConfig``'s fields, the five
cells' parameter trees and their checkpointed blocks' programs."""

import dataclasses
import hashlib
import io
import json
import os
import re
import tokenize

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

import deepspeed_tpu
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import CausalLM, TransformerConfig, transformer as table
from deepspeed_tpu.models.config import TransformerFields
from deepspeed_tpu.models.layers import SAVED, LayerKind
from deepspeed_tpu.models.transformer import _SOWN, Block, block_fn
from deepspeed_tpu.parallel.mesh import initialize_mesh, reset_mesh
from deepspeed_tpu.runtime import engine as trainer
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.telemetry import device_counts, get_registry, get_tracer
from deepspeed_tpu.telemetry.tracing import region

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KINDS = ("kda", "gdn", "mla", "sparse", "routed", "ssm", "diff", "diff_window", "gmu", "diff_cross", "blockdiff")  # the names no host may spell
sha = lambda text: hashlib.sha256(text.encode()).hexdigest()[:16]


# ------------------------------------------------------------------ (a) a kind of the test's own
class RunningMean(LayerKind, nn.Module):
    """A causal running mean as a token mixer: y_t = (mean of x_s over s <= t) W."""

    cfg: TransformerFields
    sows, keeps, hybrid = ("intermediates",), ("running_mean",), True
    paths = {"mean_path": ("mixer/kernel", {"op": "mean", "pass": "fwd"})}

    @staticmethod
    def report(intermediates):
        sizes = jnp.stack(jax.tree_util.tree_leaves(intermediates))
        device_counts.report("mean_size", sizes, lambda sizes: get_registry().gauge("running_mean_size").set(float(sizes.mean())))

    @nn.compact
    def __call__(self, x, positions=None, kv_cache=None, segment_ids=None):
        self.no_cache(kv_cache, segment_ids)
        with region("mixer/kernel", op="mean", path="xla", **{"pass": "fwd"}):
            mean = checkpoint_name(jnp.cumsum(x, axis=1) / jnp.arange(1, x.shape[1] + 1, dtype=x.dtype)[None, :, None], "running_mean")
        self.sow("intermediates", "mean_size", jnp.mean(jnp.abs(mean)))
        return nn.Dense(x.shape[-1], use_bias=False, name="o_proj")(mean)


def test_a_new_kind_is_its_module_and_one_line_of_the_table(monkeypatch):
    monkeypatch.setitem(table.MIXERS, "mean", RunningMean)  # the one line
    cfg = TransformerConfig(vocab_size=97, n_layers=2, n_heads=2, d_model=32, d_ff=48, max_seq_len=32, norm="rmsnorm", pos_emb="rope",
                            remat=True, layer_kinds=(("mean", "dense"), ("full", "dense")))
    assert cfg.sows and cfg.unstackable == ("mean",) and table.remat_keeps(("mean", "dense")) == ("running_mean", SAVED)
    model = CausalLM(cfg)
    ids = np.random.default_rng(0).integers(0, 97, (2, 32)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    assert set(params["layer_0"]) >= {"mean", "mlp"} and set(params["layer_0"]["mean"]) == {"o_proj"}
    # the stacked forms refuse it by the record's word, and it refuses a cache by the shared helper's
    with pytest.raises(NotImplementedError, match="mean"):
        InferenceEngineV2(model, jax.eval_shape(lambda: params))
    with pytest.raises(NotImplementedError, match="mean"):
        model.to_pipeline(1, params=params)
    with pytest.raises(NotImplementedError, match="a mean layer takes no KV cache"):
        model.apply(params, ids[:, :4], kv_caches=model.init_kv_caches(2, 8))
    reset_mesh()
    topo = initialize_mesh(MeshConfig.from_dict({"data": 1}), devices=jax.devices()[:1], force=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config={
        "train_micro_batch_size_per_gpu": 2, "optimizer": {"type": "adam", "params": {"lr": 1e-2}}, "zero_optimization": {"stage": 0},
        "mesh": {"data": 1}, "steps_per_print": 10**9})
    get_registry().gauge("running_mean_size").set(0.0)
    losses = []
    for _ in range(3):  # the device counts reach the registry a step late
        loss = engine.forward({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[2] < losses[0]
    assert get_registry().peek("running_mean_size") > 0
    said = [s["attrs"] for s in get_tracer().spans() if s["name"] == "program/first_call" and s["attrs"].get("family") == "train"][-1]
    assert said["layer_kinds"] == "full+dense:1,mean+dense:1" and said["mean_path"] == "xla"
    assert said["remat_keeps"] == f"flash_attention+{SAVED}+running_mean"  # the hybrid block's two; the plain one's kernel's outputs


# ------------------------------------------------------------------ (b) a record cannot lie
def tiny(mixer, ffn, **over):
    base = dict(vocab_size=97, n_layers=1, n_heads=4, n_kv_heads=2, d_model=32, d_ff=48, max_seq_len=64, norm="rmsnorm", activation="swiglu",
                pos_emb="rope", tie_embeddings=False, layer_kinds=((mixer, ffn),), sliding_window=16, kda_heads=2, kda_head_dim=16,
                kda_gate_rank=8, gdn_key_heads=2, gdn_value_heads=4, gdn_head_dim=16, mla_kv_rank=24, mla_qk_nope_dim=24, mla_qk_rope_dim=8,
                mla_v_dim=16, index_heads=2, index_head_dim=8, index_topk=16, moe_num_experts=8, moe_top_k=2, moe_d_ff=16, moe_shared_d_ff=16,
                ssm_inner=128, ssm_dt_rank=4, block_length=4, mask_token_id=96, ssd_heads=4, ssd_head_dim=8, ssd_state=16, ssd_groups=2)
    return TransformerConfig(**dict(base, **over))


def _taken_by(record, cfg, x, positions):
    """What a mixer of this record takes, by name: ``layer`` as the model gives it, the rest as zeros of the shapes the
    table's giver of the name hands on."""
    taken = {"layer": jnp.asarray(3, jnp.int32)} if "layer" in record.takes else {}
    for name in set(record.takes) - {"layer"}:
        giver = next(kind for kind, cls in table.MIXERS.items() if name in cls.gives)
        block = Block(cfg, (giver, "dense"))
        args = (x, positions, None, None, _taken_by(table.MIXERS[giver], cfg, x, positions))
        shapes = jax.eval_shape(lambda: block.apply(block.init(jax.random.PRNGKey(0), *args), *args))[1]
        taken[name] = jnp.zeros(shapes[name].shape, shapes[name].dtype)
    return taken


def _names(jaxpr, found):
    """The names ``checkpoint_name`` gave in a jaxpr, sub-jaxprs too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            found.add(eqn.params["name"])
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list)) else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _names(sub, found)
    return found


# a kernel's custom_vjp gives these where the kernel runs: off the TPU no trace shows them (``tests/unit/test_chip_compile.py``)
KERNELS_ALONE = {"kda_scan", "flash_attention", "ssm_scan", "short_conv", "ssd_scan"}
# a key that rises only then (``tests/unit/test_moe_sum_rows.py``; ``test_hybrid_layers.py`` and ``test_deltanet_layers.py``)
WHEN = {"moe_cond": "the buffer is smaller than every pair", "kda_heads_a_step": "the scan is the kernel", "gdn_heads_a_step": "the scan is the kernel",
        "blockdiff_tiles": "the attention is the kernel, whose walk it counts (tests/unit/test_blockdiff.py)", "blockdiff_pairs": "the same",
        "tiles_a_trip_fwd": "the attention is the flash kernel (tests/unit/test_pallas_ops.py, test_regions.py)", "tiles_a_trip_bwd": "the same",
        # (PR 53) softmax attention's kinds share one record, each key its own kind's; the window's walk is the kernel's
        "full_path": "the layer is of the kind full", "window_path": "the layer is of the kind window", "window_keys": "the same",
        "window_tiles": "the attention is the flash kernel under a window (tests/unit/test_mixed_attention_layers.py)", "window_tile": "the same",
        "moe_activation": "the experts' gate is relu (activation reglu) or they have none (relu2: tests/unit/test_mamba2_layers.py)",
        # (PR 58) XLA differentiates the plain lines: the backward is a call site of its own on the kernels' path alone
        "scan_operands_bwd": "the scan's operands are made by the kernels (tests/unit/test_scan_operands.py)",
        # (PR 62) the strip the indexer's loops over heads walk a tile in: a label of the kernels' call sites alone
        "index_strip": "the indexer's calls are the kernels (tests/unit/test_indexed_attention.py)",
        # (PR 66) how held experts split over a mesh meet their rows: on one chip nothing crosses chips and the line has no such key
        "moe_exchange": "the held experts are split over a mesh axis (tests/unit/test_exchanged_experts.py, tests/benchmarks/test_benchmark_kexaone.py)"}


@pytest.mark.parametrize("part,name", [(0, name) for name in table.MIXERS] + [(1, name) for name in table.FFNS])
def test_a_kinds_record_is_what_a_traced_block_of_it_does(part, name):
    """Beside the plainest other part (``full`` or ``dense``, whose own records are cases here too): the collections the
    block wrote, the names its values carry, the line keys whose counters rose, and what the stacked forms say."""
    kind = (name, "dense") if part == 0 else ("full", name)
    record, other = (table.MIXERS, table.FFNS)[part][name], (table.FFNS["dense"], table.MIXERS["full"])[part]
    assert not other.sows and not other.hybrid and other.stackable  # (``full`` says how its own call was traced: ``full_path``)
    cfg = tiny(*kind)
    x, positions = jnp.zeros((2, 64, 32)), jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 64))
    block = Block(cfg, kind)
    mixer = table.MIXERS[kind[0]]  # the values between blocks are a mixer's
    taken = _taken_by(mixer, cfg, x, positions)
    params = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), x, positions, None, None, taken))["params"]
    in_tree = {"full": "attn", "window": "attn", "nope": "attn", "dense": "mlp", "routed_early": "routed"}
    assert {n for n in params if "Norm" not in n} == {in_tree.get(n, n) for n in kind if n != "none"}  # its name in the tree; ``none`` has none
    assert len([n for n in params if "Norm" in n]) == (1 if "none" in kind else 2)  # (PR 59) and no norm of its own: a block of one part has one
    run = lambda p, x: block.apply({"params": p}, x, positions, None, None, taken, mutable=_SOWN)
    if mixer.gives:  # the block's result is then (activations, the values by name): exactly the names the record gives
        given = jax.eval_shape(run, params, x)[0][1]
        assert set(given) == set(mixer.gives)
        run = lambda p, x, run=run: (lambda out, sown: (out[0], sown))(*run(p, x))
    before = trainer._paths_traced()
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(run(p, x)[0])))(params, x)
    rose = {key for key, now in trainer._paths_traced().items() if now != before[key]}
    sown = jax.eval_shape(run, params, x)[1]
    assert {col for col, tree in sown.items() if jax.tree_util.tree_leaves(tree)} == set(record.sows)
    assert table.kinds_sow((kind,)) == bool(record.sows)
    declared = set(record.keeps) | set(other.keeps) | {SAVED}
    assert _names(jaxpr.jaxpr, set()) <= declared and declared - _names(jaxpr.jaxpr, set()) <= KERNELS_ALONE
    parts = tuple(dict.fromkeys((record, other)[part].keeps + (other, record)[part].keeps))  # the mixer's, then the FFN's
    # every block keeps its kernels' outputs; a hybrid one its projections' results too, the block's own sum among them
    assert table.remat_keeps(kind) == (tuple(dict.fromkeys(parts + (SAVED,))) if record.hybrid else tuple(name for name in parts if name != SAVED))
    keys = set(record.paths) | set(record.joined)  # ``full`` beside an FFN rotates, and says so (``rope``)
    assert rose - set(other.joined) - set(other.paths) <= keys and keys - rose <= set(WHEN)
    assert set(record.path_words) <= set(record.paths)
    # the stacked forms: the configuration's word, the pipeline's and the server's refusals
    assert cfg.unstackable == (() if record.stackable else (name,))
    model = CausalLM(cfg)
    if set(mixer.takes) - {"layer"}:  # a model of such a layer alone has no giver: refused in words before anything is built
        with pytest.raises(ValueError, match=f"layer 0 .{name}. takes .*, which no earlier layer gives"):
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)}))
        return
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)}))
    if record.stackable:
        assert set(model.to_pipeline(1, params=model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)}))[0]["stages"]) == {"sub_0"}
    else:
        for refused in (lambda: model.to_pipeline(1, params=shapes), lambda: InferenceEngineV2(model, shapes)):
            with pytest.raises(NotImplementedError, match=name):
                refused()


@pytest.mark.parametrize("name", list(table.MIXERS) + list(table.FFNS))
def test_every_entry_of_the_table_mixes_the_record_in(name):
    """``moe/`` mixes the record in as ``models/`` does (it lives outside both: ``deepspeed_tpu/layer_kind.py``), so a field the
    record gains is every kind's at once: no entry spells a default by hand, and each has every name the hosts read."""
    from deepspeed_tpu import layer_kind

    cls = {**table.MIXERS, **table.FFNS}[name]
    assert LayerKind is layer_kind.LayerKind and issubclass(cls, LayerKind)
    assert issubclass(cls, nn.Module) != (name == "none")  # (PR 59) the half that is not there is a record and no module
    fields = [f for f in vars(LayerKind) if not f.startswith("_")]
    assert {"sows", "report", "keeps", "hybrid", "paths", "path_words", "joined", "alone", "stackable", "gives", "takes", "targets"} <= set(fields)
    assert all(hasattr(cls, f) for f in fields)
    if cls.__module__.startswith("deepspeed_tpu.moe"):  # said only where they differ: what the parent's hand-spelled lines said
        assert (cls.gives, cls.targets, cls.alone, cls.path_words) == ((), None, False, {"moe_cond": "fallback_keeps_nothing"} if "routed" in name else {})


def test_a_model_of_one_kind_says_its_keys_only_where_the_record_asks():
    """The trainer's first-call line by the records alone (no step is run: the counters are what the traces above, or
    none, left): ``alone`` is the sparse mixer's."""
    notes_since = lambda mixer, before: trainer.DeepSpeedEngine._layer_kind_notes(
        type("E", (), {"module": type("M", (), {"cfg": tiny(mixer, "dense", n_layers=2, layer_kinds=((mixer, "dense"),) * 2)})}), before)
    notes = lambda mixer: notes_since(mixer, trainer._paths_traced())
    assert [name for name, record in table.MIXERS.items() if record.alone] == ["sparse", "blockdiff"] and not any(r.alone for r in table.FFNS.values())
    assert notes("mla") == {} and notes("sparse") == {"layer_kinds": "sparse+dense:2"} and notes("blockdiff") == {"layer_kinds": "blockdiff+dense:2"}
    # a key whose words are whatever its sites gave (``joined`` with None): the values that rose since, sorted
    assert table.MIXERS["blockdiff"].joined["blockdiff_tiles"] == ("mixer/kernel", None, "tiles")
    before = trainer._paths_traced()
    with region("mixer/kernel", op="blockdiff", path="kernel", tiles="3/4", pairs="80", **{"pass": "fwd"}):
        pass
    assert notes_since("blockdiff", before) == {"layer_kinds": "blockdiff+dense:2", "blockdiff_path": "kernel", "blockdiff_tiles": "3/4", "blockdiff_pairs": "80"}
    assert set(trainer._ROUTER_WORDS) == {"sigmoid", "softmax", "compare_sum"} and trainer._PATH_WORDS == {"moe_cond": "fallback_keeps_nothing"}


# ------------------------------------------------------------------ (c) the hosts spell no kind
def _strings(path):
    """(line, the literal) of every string token of a source file that is not a docstring (a statement of its own)."""
    with open(os.path.join(ROOT, path)) as f:
        tokens = list(tokenize.generate_tokens(io.StringIO(f.read()).readline))
    alone = lambda i: tokens[i - 1].type in (tokenize.NEWLINE, tokenize.INDENT, tokenize.DEDENT, tokenize.NL, tokenize.ENCODING) and \
        tokens[i + 1].type == tokenize.NEWLINE
    return [(tok.start[0], tok.string) for i, tok in enumerate(tokens) if tok.type == tokenize.STRING and not alone(i)]


@pytest.mark.parametrize("path", ["deepspeed_tpu/runtime/engine.py", "deepspeed_tpu/models/transformer.py", "deepspeed_tpu/inference/v2/engine_v2.py"])
def test_no_host_names_a_kind(path):
    named = [(line, s) for line, s in _strings(path) if any(re.search(rf"\b{kind}\b", s) for kind in KINDS)]
    if path.endswith("transformer.py"):  # the table's own three lines
        with open(os.path.join(ROOT, path)) as f:
            lines = f.read().splitlines()
        named = [(line, s) for line, s in named if not lines[line - 1].startswith(("MIXERS = ", "MIXERS |= ", "FFNS = "))]
    assert not named
    if path.endswith("runtime/engine.py"):  # not in a comment or a docstring either
        with open(os.path.join(ROOT, path)) as f:
            assert not re.findall("|".join(f'"{kind}"' for kind in KINDS), f.read())


# ------------------------------------------------------------------ what must not move
def test_the_configurations_fields_are_the_parents():
    """Names, order and defaults (made from the parent commit by the same line), and the class a caller gets adds none."""
    fields = dataclasses.fields(TransformerConfig)
    assert sha(repr([(f.name, repr(f.default)) for f in fields[:76]])) == "02e66817e969c9f2"  # PR 45's 76, as they were
    assert [(f.name, f.default) for f in fields[76:]] == [("ssm_inner", 0), ("ssm_state", 16), ("ssm_conv", 4), ("ssm_dt_rank", 0),
                                                          ("layer_numbers", None),  # PR 46: appended, nothing moved
                                                          ("block_length", 0), ("mask_token_id", 0), ("blockdiff_qk_init_scale", 1.0),  # PR 49: likewise
                                                          ("conv_kernel", 3), ("moe_renorm_eps", 1e-20),  # PR 55: likewise
                                                          ("ssd_heads", 0), ("ssd_head_dim", 64), ("ssd_state", 128), ("ssd_groups", 1), ("ssd_conv", 4),  # PR 59: likewise
                                                          ("loop_steps", 1), ("exit_gate", False), ("exit_entropy_coef", 0.0)]  # PR 63: likewise
    assert [f.name for f in fields] == [f.name for f in dataclasses.fields(TransformerFields)]
    cfg = TransformerConfig(n_layers=3)
    assert TransformerConfig(**cfg.__dict__) == cfg == dataclasses.replace(cfg) and hash(cfg) == hash(dataclasses.replace(cfg))


def rehearsal(name):
    """A benchmark configuration's model at its rehearsal width (``benchmarks/run.py --rehearse`` builds the same)."""
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    program = dict(cfg["program"], **cfg["rehearse"].get("program", {}))
    dtype = jnp.bfloat16 if program.pop("dtype", None) == "bfloat16" else jnp.float32
    hashable = lambda v: tuple(hashable(x) for x in v) if isinstance(v, list) else v
    return CausalLM(TransformerConfig(**{k: hashable(v) for k, v in program.items()}, dtype=dtype))


def tree_of(model):
    """(leaves, sha256 of the sorted "path shape dtype" lines, the sum of every leaf in float64) of ``init`` at key 7."""
    params = model.init(jax.random.PRNGKey(7), {"input_ids": np.zeros((2, 16), np.int32)})
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    lines = sorted(f"{jax.tree_util.keystr(p)} {tuple(l.shape)} {l.dtype}" for p, l in leaves)
    return len(lines), sha("\n".join(lines)), float(sum(np.asarray(l, np.float64).sum() for _, l in leaves))


# made from the parent commit (PR 44) by ``tree_of(rehearsal(name))`` under this suite's ``conftest.py``
TREES = {
    "olmo-1b": (15, "11cd7dcefaa78889", 17.00020208947356),
    "kimi-linear-48b-l5e8": (113, "10e6bd75fa26d5f2", -351.28896082537267),
    "kimi-vl-a3b-l6e8": (88, "4e1e64c10f4388d0", 941.2353062580679),
    "qwen3-next-80b-l4e32": (85, "857d3f2122f93517", 63.770677355315684),
    "keye-vl2-30b-l4e16": (71, "c5ae764653b35fc6", 818.3769185115896),
}


@pytest.mark.parametrize("name", sorted(TREES))
def test_the_five_cells_parameter_trees_are_the_parents(name):
    leaves, paths, total = tree_of(rehearsal(name))
    assert (leaves, paths) == TREES[name][:2] and total == pytest.approx(TREES[name][2], rel=1e-6, abs=1e-6)


PARENTS_KEEPS = ("kda_scan", "routed_ffn", "flash_attention", "projection")  # PR 40-44: one list for every hybrid pair
PAIRS = sorted({(name, kind) for name in TREES for kind in rehearsal(name).cfg.kinds})


@pytest.mark.parametrize("name,kind", PAIRS, ids=[f"{name}:{'+'.join(kind)}" for name, kind in PAIRS])
def test_a_checkpointed_block_is_the_program_it_was_under_the_parents_list(name, kind, monkeypatch):
    """Every (mixer, ffn) pair of the five cells: the jaxpr of the checkpointed block's gradient under the names the
    records give equals the one under the parent's tuple (a name no value of the block carries keeps nothing). A plain
    block (OLMo's, were it checkpointed) had no policy and has the flash call's name (PR 64): off the chip no value carries
    it, and the gradient is the parent's equation for equation, the ``policy=`` word aside."""
    cfg = dataclasses.replace(rehearsal(name).cfg, remat=True)
    x = jnp.zeros((1, 64, cfg.d_model), cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (1, 64))
    params = jax.eval_shape(lambda: Block(cfg, kind).init(jax.random.PRNGKey(0), x, positions))["params"]

    def program():
        loss = lambda p, x: jnp.sum(block_fn(cfg, kind, True, True)(p, x, positions, None, None, {})[0][0].astype(jnp.float32))
        return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)))

    ours = program()
    hybrid = table.MIXERS[kind[0]].hybrid or table.FFNS[kind[1]].hybrid
    parents = (PARENTS_KEEPS + (("sparse_attention",) if kind[0] == "sparse" else ())) if hybrid else ()
    assert set(table.remat_keeps(kind)) <= (set(parents) if hybrid else {"flash_attention"})
    monkeypatch.setattr(table, "remat_keeps", lambda kind: parents)
    same = (lambda text: text) if hybrid else (lambda text: re.sub(r"policy=[^\n]*", "policy=", text))
    assert same(program()) == same(ours) and ("checkpoint" in ours or "remat" in ours)
