"""The configuration ``keye-vl2-30b-l4e16`` (grouped-query attention 32/4 over the 2,048 keys a learned indexer chooses
for each query, a softmax-routed FFN of 8 of 128, as one chip's share of an 8-chip group): its files pass the
manifest's checks and hold the catalog row's widths, the program agrees with its plain float32 reference in logits, in
the indexer's loss a layer and in the gradient of every leaf, the reference takes a given choice and its controls move
the result, its FLOP module's total is a sum a reader can check by hand, its two readers read a made-up trace's
kernels and nothing else, and its rehearsal says what was traced. Nothing here pins an entry's place in
``BENCHMARK.json``: a later cell is appended after this one."""

import json
import os

import pytest

from benchmarks.lib import flops, manifest as mf

MANIFEST = mf.load_manifest()
NAME, CELL = "keye-vl2-30b-l4e16", "keye-vl2-30b-l4e16.pretrain-8k"
CONFIG = mf.load_json(os.path.join(mf.BENCH, "configs", f"{NAME}.json"))
PUBLISHED = mf.published(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("sparse_attention_roofline", "index_select_roofline")


def test_the_configuration_and_its_cell_have_no_problems():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert mf.config_problems(CONFIG, entry) == []
    assert [p for p in mf.problems(MANIFEST) if NAME in p or "sparse_attention" in p or "index_select" in p] == []
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "pretrain-8k", NAME) and "1/8" in cell["why"]
    assert CONFIG["trainer"]["train_micro_batch_size_per_gpu"] == 1 and CONFIG["trainer"]["zero_optimization"]["stage"] == 0
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in mf.metrics_of(MANIFEST, CELL, g)}
    assert {"train_tokens_per_s", "setup_s", "mfu.train", "moe_expert_matmul_roofline", *READERS} <= reported


@pytest.mark.parametrize("name", READERS)
def test_a_new_metric_is_this_cells_alone(name):
    metric = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert CELL in metric["workloads"] and (metric["unit"], metric["better"], metric["source"], metric["moves"]) == \
        ("%", "higher", "device_trace", "train_tokens_per_s")
    mod = mf.metric_module(name)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == tuple(metric[k] for k in ("unit", "better", "source", "layer", "moves"))


def _one_cell_base():
    """The first cell alone, as ``test_benchmark_hybrid.py::_one_cell_base`` builds it: cases on it count no cells of
    ``BENCHMARK.json`` as it stands."""
    m = json.loads(json.dumps(MANIFEST))
    first = m["workloads"][0]["name"]
    m["workloads"], m["configs"] = m["workloads"][:1], m["configs"][:1]
    for group in ("end_to_end", "per_layer"):
        m[group] = [dict(x, workloads=[first]) if "workloads" in x else x for x in m[group] if x.get("workloads", [first])[0] == first]
    return m


@pytest.mark.parametrize("case,needle", [
    ("as_it_is", None),
    ("with_this_cell", None),
    ("a_width_reduced", "reduced names a width"),
    ("a_held_count_not_reduced", "which reduced does not list"),
    ("the_entry_disagrees", "reduced differs between BENCHMARK.json and its file"),
])
def test_the_manifests_checks_on_a_one_cell_base(case, needle, tmp_path):
    m = _one_cell_base()
    if case != "as_it_is":
        cfg = json.loads(json.dumps(CONFIG))
        entry = dict(next(c for c in MANIFEST["configs"] if c["name"] == NAME))
        if case == "a_width_reduced":
            cfg["reduced"] = entry["reduced"] = CONFIG["reduced"] + ["moe_intermediate_size"]
        elif case == "a_held_count_not_reduced":
            cfg["reduced"] = entry["reduced"] = [k for k in CONFIG["reduced"] if k != "num_local_experts"]
        elif case == "the_entry_disagrees":
            entry["reduced"] = CONFIG["reduced"][:-1]
        assert (mf.config_problems(cfg, entry) == []) == (needle is None)
        assert needle is None or any(needle in p for p in mf.config_problems(cfg, entry))
        m["configs"].append(entry)
        m["workloads"].append(next(w for w in MANIFEST["workloads"] if w["name"] == CELL))
        for group in ("end_to_end", "per_layer"):
            m[group] += [dict(x, workloads=[CELL]) for x in MANIFEST[group] if CELL in x.get("workloads", []) and x["name"] not in {y["name"] for y in m[group]}]
            for x in m[group]:
                if "workloads" in x and x["name"] in ("train_tokens_per_s", "mfu.train") and CELL not in x["workloads"]:
                    x["workloads"].append(CELL)
    found = mf.problems(m)  # against the files on disk, which are sound: an entry that was changed above differs from its file, no more
    assert [p for p in found if "reduced differs" not in p] == [] and bool(found) == (case in ("a_width_reduced", "a_held_count_not_reduced", "the_entry_disagrees"))


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog of published configurations is not on this machine")
def test_every_width_is_the_sources():
    source = next(json.loads(line) for line in open(CATALOG) if '"name": "Keye-VL-2.0-30B-A3B"' in line)
    assert CONFIG["source"] == source["source_url"]
    source = source["config"]
    differs = {k for k, v in source.items() if CONFIG.get(k, "missing") != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"}
    assert CONFIG["sa_config"] == source["sa_config"] and CONFIG["rope_scaling"] == source["rope_scaling"]  # nested groups whole
    held = CONFIG["share"]["held"]
    assert CONFIG["share"]["chips_per_layer"] == 8 and CONFIG["routed_over"] == source["num_experts"] == source["num_local_experts"] == 128
    assert held["num_experts"] == held["num_local_experts"] == {"published": 128, "here": 16} and held["vocab_size"] == {"published": 151936, "here": 18992}
    assert CONFIG["num_hidden_layers"] == 4 and 128 // 8 == 16 >= 8 and 151936 // 8 == 18992  # the floors
    p, sa = CONFIG["program"], source["sa_config"]
    assert p["layer_kinds"] == [["sparse", "routed"]] * 4 and p["n_layers"] == 4
    assert (p["d_model"], p["n_heads"], p["n_kv_heads"], p["head_dims"], p["rope_theta"], p["norm_eps"]) == \
        (source["hidden_size"], source["num_attention_heads"], source["num_key_value_heads"], source["head_dim"], source["rope_theta"],
         source["rms_norm_eps"]) == (2048, 32, 4, 128, 1e7, 1e-6)
    assert (p["index_heads"], p["index_head_dim"], p["index_topk"]) == (sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]) == (16, 64, 2048)
    assert sa["indexer_num_kv_heads"] == 1 and sa["q_chunk_size"] == sa["kv_chunk_size"] == 512
    assert (p["moe_num_experts"], p["moe_top_k"], p["moe_d_ff"], p["moe_shared_d_ff"], p["moe_scoring"], p["moe_route_scale"]) == \
        (source["num_experts"], source["num_experts_per_tok"], source["moe_intermediate_size"], 0, "softmax", 1.0) and source["norm_topk_prob"] is True
    assert p["moe_held"] == [0, CONFIG["num_experts"]] and p["vocab_size"] == CONFIG["vocab_size"] and not p["tie_embeddings"] and p["qk_norm"]
    assert "d_ff" not in p and source["mlp_only_layers"] == [] and source["decoder_sparse_step"] == 1 and not source["attention_bias"]
    assert CONFIG["first_k_dense_replace"] == 0 and "first_k_dense_replace" not in source
    for key in ("qk_norm", "rotation", "indexer", "choice", "index_loss", "chunks", "vision_tower", "optimizer", "held", "weights", "start"):
        assert key in CONFIG["assumed"]
    assert p["sparse_out_init_scale"] == 0.02 and "0.02" in CONFIG["assumed"]["weights"]  # the start that keeps the router's load a seed's no more
    for word in ("8 v5e chips", "expert parallel 8", "8 ways", "pipeline", "absent", "512 rows"):
        assert word in CONFIG["deployment"]


def test_the_parameter_count_is_the_issues_sum():
    """465.4 M parameters by the shapes of the program's own tree: 5.58 GB of float32 master and two moments."""
    import jax
    import numpy as np

    from benchmarks.lib import weights

    shapes = weights.param_shapes(weights.build_model(CONFIG))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    mixer = shapes["layer_0"]["sparse"]
    assert count({k: v for k, v in mixer.items() if not k.startswith("index_")}) == 18874368 + 2 * 128
    assert count({k: v for k, v in mixer.items() if k.startswith("index_")}) == 2261120 == 2048 * 1024 + 2048 * 64 + 2048 * 16 + 128
    routed = shapes["layer_0"]["routed"]
    assert count({k: v for k, v in routed.items() if k.startswith("experts_")}) == 75497472 and count(routed["gate"]) == 262144
    assert 96.89e6 < count(shapes["layer_0"]) < 96.91e6 and count(shapes["wte"]) == count(shapes["lm_head"]) == 18992 * 2048
    assert 465.3e6 < count(shapes) < 465.5e6 and 5.58e9 < 12 * count(shapes) < 5.59e9


def test_the_flop_count_is_the_hand_written_sum():
    mod = flops.for_config(CONFIG)
    assert mod.chosen_pairs(PUBLISHED, 8192) == 14681088 == sum(min(t + 1, 2048) for t in range(8192)) and mod.visible_pairs(8192) == 33558528
    assert mod.chosen_pairs(PUBLISHED, 2048) == mod.visible_pairs(2048)  # nothing to choose: plain causal attention
    assert mod.chosen_pairs(PUBLISHED, 8192) / mod.visible_pairs(8192) == pytest.approx(0.4375, abs=1e-4)
    layer = 2 * 18874368 + 2 * (2048 * 1024 + 2048 * 64 + 2048 * 16) + 2 * 16 * 64 * 4096.5 + 4 * 32 * 128 * 14681088 / 8192 \
        + 2 * 2048 * 128 + 2 * 3 * 2048 * 768 * (8 * 16 / 128)
    assert 89.9e6 < layer < 90.1e6
    assert mod.forward_flops_per_token(PUBLISHED, 8192) == pytest.approx(4 * layer + 2 * 2048 * 18992)
    total = mod.train_flops_per_token(PUBLISHED, 8192)
    assert 1.31e9 < total < 1.32e9 and total == pytest.approx(3 * mod.forward_flops_per_token(PUBLISHED, 8192))
    dense = dict(PUBLISHED, sa_config=dict(PUBLISHED["sa_config"], topk=8192))  # every causal pair: 67.1 MFLOP a token and layer where 29.4 are
    assert mod.forward_flops_per_token(dense, 8192) - mod.forward_flops_per_token(PUBLISHED, 8192) == pytest.approx(4 * (67.1e6 - 29.4e6), rel=0.01)
    attn = mod.sparse_attention_cost(PUBLISHED, 1, 8192, backward=False)
    assert attn["flops"] == 4.0 * 32 * 128 * 14681088 and attn["bytes"] == 2.0 * (2 * 8192 * 4096 + 2 * 8192 * 512)
    assert mod.sparse_attention_cost(PUBLISHED, 1, 8192, backward=True)["flops"] == 2 * attn["flops"]
    index = mod.index_cost(PUBLISHED, 1, 8192, backward=False)
    assert index["flops"] == 2.0 * 16 * 64 * 33558528 and mod.index_cost(PUBLISHED, 1, 8192, backward=True)["flops"] == 2 * index["flops"]
    rows = 8192 * 8 * 16 / 128  # what a uniform router sends here: 8,192 pairs, 512 an expert
    assert rows == 8192 and mod.expert_matmul_cost(PUBLISHED, rows, backward=False)["flops"] == 6.0 * 2048 * 768 * rows
    assert mod.sparse_layers(PUBLISHED) == 4


def _tiny():
    """The rehearsal's width, all four layers, float32."""
    from benchmarks.lib import weights

    r = CONFIG["rehearse"]
    cfg = dict(CONFIG, **dict(r["published"], sa_config=dict(CONFIG["sa_config"], **r["published"]["sa_config"])), reference=r["reference"])
    cfg["program"] = dict(CONFIG["program"], **r["program"], dtype="float32")
    return cfg, weights.build_model(cfg)


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import numpy as np

    cfg, model = _tiny()
    ids = np.random.default_rng(0).integers(0, 509, (2, 80)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    leaves, tree = jax.tree_util.tree_flatten(params)  # the norms' weights start at one and the biases at zero: moved
    stirred = [x + 0.05 * jax.random.normal(jax.random.PRNGKey(7 + i), x.shape) for i, x in enumerate(leaves)]
    ref = mf.load_module(os.path.join(mf.ROOT, cfg["reference"]["module"]))
    return cfg, model, jax.tree_util.tree_unflatten(tree, stirred), ids, ref


def _close(a, b, tol):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * (1.0 + np.max(np.abs(b))), (np.max(np.abs(a - b)), np.max(np.abs(b)))


def _sown(model, params, ids):
    logits, mods = model.module.apply({"params": params}, ids, mutable=("intermediates", "losses"))
    return logits, [mods["intermediates"][f"layer_{i}"]["sparse"] for i in range(model.cfg.n_layers)]


def test_the_program_agrees_with_the_plain_reference_in_logits_index_loss_and_every_gradient(tiny_model):
    """Four layers, seeded weights, float32, 24 keys a query of up to 80: the program (key-major scores, the choice as a
    scattered ``top_k``, masked attention with its row statistics, the loss from one array) against the reference
    (query-major, head by head). The step's loss is the cross-entropy; its gradient is that of ``CE + sum L_I``."""
    import jax
    import jax.numpy as jnp

    cfg, model, params, ids, ref = tiny_model
    pub = mf.published(cfg)
    with jax.default_matmul_precision("highest"):
        logits, sown = _sown(model, params, ids)
        want_logits, want_losses, want_choice = ref.forward(params, ids, pub, cfg["reference"], jnp.float32)
        _close(logits, want_logits, 2e-5)
        for layer, loss, choice in zip(sown, want_losses, want_choice):
            _close(layer["index_loss"][0], loss, 1e-5)
            assert bool(jnp.all((jnp.swapaxes(layer["choice"][0], 1, 2) != 0) == choice))  # the same keys, query by query
        ours, g_ours = jax.value_and_grad(lambda p: model.loss_fn(p, {"input_ids": ids}))(params)
        ((total, (ce, losses, _)), g_theirs) = ref.loss_and_grads(params, ids, pub, cfg["reference"], jnp.float32)
    _close(ours, ce, 1e-6)
    _close(total, ce + sum(losses), 1e-6)
    theirs_by_path = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    leaves = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(leaves) == len(theirs_by_path) == len(jax.tree_util.tree_leaves(params))
    for path, leaf in leaves:
        _close(leaf, theirs_by_path[path], 5e-5)
    assert all(float(jnp.max(jnp.abs(l))) > 0 for _, l in leaves)  # every leaf takes a gradient, the indexer's from L_I


def test_the_reference_takes_a_given_choice(tiny_model):
    """Its own choice handed back changes nothing; another choice (every visible key) is another function, equal to the
    ``no_indexer`` control in logits and unequal to it in the indexer's loss, which that control does not have."""
    import jax
    import jax.numpy as jnp

    cfg, _, params, ids, ref = tiny_model
    pub, rc = mf.published(cfg), cfg["reference"]
    with jax.default_matmul_precision("highest"):
        logits, losses, own = ref.forward(params, ids, pub, rc, jnp.float32)
        again, losses_again, _ = ref.forward(params, ids, pub, rc, jnp.float32, choice=own)
        S = ids.shape[1]
        visible = [jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool)), (2, S, S))] * 4
        dense, dense_losses, _ = ref.forward(params, ids, pub, rc, jnp.float32, choice=visible)
        control = ref.logits(params, ids, pub, dict(rc, no_indexer=True), jnp.float32)
    assert float(jnp.max(jnp.abs(again - logits))) == 0.0 and [float(x) for x in losses_again] == [float(x) for x in losses]
    _close(dense, control, 1e-6)
    assert float(jnp.linalg.norm(dense - logits) / jnp.linalg.norm(logits)) > 1e-2 and all(float(x) > 0 for x in dense_losses)
    assert [int(jnp.sum(m)) for m in own] == [2 * sum(min(24, t + 1) for t in range(S))] * 4


@pytest.mark.parametrize("control,least", [({"no_indexer": True}, 1e-2), ({"topk": 12}, 1e-2), ({"layers_short": 1}, 1e-2),
                                           ({"no_final_norm": True}, 0.2)])
def test_the_references_controls_move_the_logits(tiny_model, control, least):
    import jax
    import jax.numpy as jnp

    cfg, _, params, ids, ref = tiny_model
    pub = mf.published(cfg)
    with jax.default_matmul_precision("highest"):
        sound = ref.logits(params, ids, pub, cfg["reference"], jnp.float32)
        broken = ref.logits(params, ids, pub, dict(cfg["reference"], **control), jnp.float32)
    assert float(jnp.linalg.norm(broken - sound) / jnp.linalg.norm(sound)) > least


def test_without_the_index_loss_the_indexers_leaves_take_no_gradient(tiny_model):
    import jax
    import jax.numpy as jnp

    cfg, _, params, ids, ref = tiny_model
    _, grads = ref.loss_and_grads(params, ids, mf.published(cfg), dict(cfg["reference"], no_index_loss=True), jnp.float32)
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        indexer = any(str(getattr(k, "key", "")).startswith("index_") for k in path)
        assert (float(jnp.max(jnp.abs(leaf))) == 0.0) == indexer, path


def test_the_low_state_control_is_the_bf16_reference_with_lower_statistics(tiny_model):
    import jax.numpy as jnp

    cfg, _, params, ids, ref = tiny_model
    pub, rc = mf.published(cfg), cfg["reference"]
    choice = ref.forward(params, ids, pub, rc, jnp.float32)[2]
    plain = ref.forward(params, ids, pub, rc, jnp.bfloat16, choice)[0]
    low = ref.forward(params, ids, pub, dict(rc, low_state=True), jnp.bfloat16, choice)[0]
    same = ref.forward(params, ids, pub, dict(rc, low_state=True), jnp.float32, choice)[0]  # float32 has no lower state
    assert 0 < float(jnp.max(jnp.abs(low - plain))) < 1.0
    assert float(jnp.max(jnp.abs(same - ref.forward(params, ids, pub, rc, jnp.float32, choice)[0]))) == 0.0


def _record(ops, steps=4, config=CONFIG):
    """A traced training record with one device, ``steps`` executions of the step program and these operations."""
    dev = {"ops": ops, "op_counts": {k: 1 for k in ops}, "modules": [("jit_fused_step(123)", 0.1 * i, 0.1 * i + 0.09) for i in range(steps)]}
    return {"reduced": {"devices": {"0": dev}, "window_s": 1.0}, "published": mf.published(config), "config": config,
            "train": {"micro_batch": 1, "seq_len": 8192, "steps": 100}, "device": {"kind": "TPU v5 lite", "count": 1},
            "counters": {}, "end_to_end": {"train_tokens_per_s": 1.0}}


# labels as ``lib/trace.py::op_label`` makes them from a v5e trace of this cell's step (my chip run, PR 42)
ATTENTION_OPS = {'sparse_bwd custom-call (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[4,8192,128]{2,1,0:T(8,12 custom_call_target="tpu_custom_call"': 0.148,
                 'sparse_fwd custom-call (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, f32[32,16,1,512]{3,2,1,0:T(1, custom_call_target="tpu_custom_call"': 0.084}
INDEX_OPS = {'index_select custom-call s8[1,8192,8192]{2,1,0:T(8,128)(4,1)S(1)} custom_call_target="tpu_custom_call"': 0.0705,
             'index_scores_bwd custom-call (bf16[1,16,8192,64]{3,2,1,0:T(8,128)(2,1)S(1)}, f32[1,16,1,8192]{3,2,1 custom_call_target="tpu_custom_call"': 0.037,
             'index_scores custom-call f32[1,8192,8192]{2,1,0:T(8,128)} custom_call_target="tpu_custom_call"': 0.0293}
OTHER = {"fusion.1 fusion bf16[8192,2048]{1,0}": 0.5,
         'sparse_probs custom-call f32[1,8192,8192]{2,1,0:T(8,128)} custom_call_target="tpu_custom_call"': 0.058,  # the index loss's second pass: in neither
         'flash_fwd custom-call (bf16[32,8192,128]{2,1,0}, f32[32,16,1,512]{3,2,1,0}) custom_call_target="tpu_custom_call"': 0.3,  # dense attention: not this model's
         'gmm custom-call bf16[32768,768]{1,0} custom_call_target="tpu_custom_call"': 0.2,
         'moe_sum_rows custom-call bf16[8192,2048]{1,0} custom_call_target="tpu_custom_call"': 0.1}


@pytest.mark.parametrize("metric,ops", [("sparse_attention_roofline", ATTENTION_OPS), ("index_select_roofline", INDEX_OPS)])
def test_a_reader_reads_its_kernels_and_nothing_else(metric, ops):
    mod = mf.metric_module(metric)
    share = mod.read(_record(dict(ops, **OTHER)))
    assert 0 < share < 100
    assert mod.read(_record(dict({k: 2 * v for k, v in ops.items()}, **OTHER))) == pytest.approx(share / 2)
    assert mod.read(_record(OTHER)) is None                                  # a program without the kernels: the parent commit's
    assert mod.read(dict(_record(dict(ops, **OTHER)), reduced=None)) is None  # an untraced run
    assert mod.read(dict(_record(dict(ops, **OTHER)), config={})) is None      # a configuration with no such layers
    other = mf.load_json(os.path.join(mf.BENCH, "configs", "qwen3-next-80b-l4e32.json"))
    assert mod.read(_record(dict(ops, **OTHER), config=other)) is None         # another configuration's FLOP module: nothing, and no raise


def test_the_readers_count_four_layers_of_chosen_pairs():
    from benchmarks.lib.peaks import peaks_for

    mod, peaks = flops.for_config(CONFIG), peaks_for("TPU v5 lite")
    attn = sum(flops.roofline_seconds(mod.sparse_attention_cost(PUBLISHED, 1, 8192, backward=b), peaks)["seconds"] for b in (False, True))
    assert mf.metric_module("sparse_attention_roofline").read(_record(ATTENTION_OPS)) == pytest.approx(100 * 4 * 4 * attn / 0.232)
    index = sum(flops.roofline_seconds(mod.index_cost(PUBLISHED, 1, 8192, backward=b), peaks)["seconds"] for b in (False, True))
    assert mf.metric_module("index_select_roofline").read(_record(INDEX_OPS)) == pytest.approx(100 * 4 * 4 * index / 0.1368)
    assert flops.roofline_seconds(mod.sparse_attention_cost(PUBLISHED, 1, 8192, backward=True), peaks)["bound"] == "compute"
    # a masked dense call at half the MXU's peak reads 0.4375 / 2: under 105% by construction, whatever the kernels' form
    dense_at_half = 2 * 12.0 * 32 * 128 * 33558528 / peaks["bf16_flops"]
    assert 100 * attn / dense_at_half == pytest.approx(21.9, abs=0.2)


def test_the_rehearsal_says_what_was_traced_and_every_query_got_its_keys():
    """A process of its own, as the driver starts one: the package's log line goes to that process's stdout."""
    import subprocess
    import sys

    out = subprocess.run([sys.executable, os.path.join(mf.ROOT, "benchmarks", "run.py"), "--workload", CELL, "--rehearse",
                          "--seed", str(2**31 + 11), "--seconds", "1"], capture_output=True, text=True, timeout=900,
                         cwd=mf.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last, counters = json.loads(lines[-1]), json.loads(lines[-2])["extras"]["counters"]
    assert last["correct"] is True and "first_loss_vs_f32" in out.stderr  # the f32 rule is the rehearsal's
    assert counters["moe_rows_routed_here_total"] > 0 and counters["moe_rows_dropped_total"] == 0
    chosen, visible = counters["sparse_keys_chosen_total"], counters["sparse_keys_visible_total"]
    assert visible > 0 and chosen / visible == pytest.approx((24 * 25 / 2 + 72 * 24) / (96 * 97 / 2))  # 24 keys a query of up to 96
    line = next(l for l in lines if "program first call: family=train" in l)
    for word in ("block_traces=1", "layer_kinds=sparse+routed:4", "sparse_path=xla", "moe_path=xla", "moe_router=softmax", "sparse_attention"):
        assert word in line
