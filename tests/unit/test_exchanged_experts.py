"""A routed layer whose held experts are split over an axis the ROWS are split over too (``moe/layer.py::_over_expert_axis``,
``moe/sharded_moe.py::exchanged_experts``): each row travels to the chip that holds its expert and its result travels back.
The exchange against one chip and against the sum form (an ``expert`` axis: every chip routes the same tokens), value and
every gradient, under a uniform router and under routers skewed so that one chip receives most rows and one none and the
ladder's fallback is taken, both of its rungs; the partition rule that pins the experts' leaves to ``fsdp`` by their leading
dimension, so that ZeRO-3 never gathers them (the four-device step's HLO); and the tree handed to ``initialize`` at ZeRO
stage 3, which is the engine's from then on."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.layer import MOE_PARTITION_RULES, RoutedMoE
from deepspeed_tpu.ops import placement
from deepspeed_tpu.parallel.mesh import get_mesh_topology, initialize_mesh, reset_mesh
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.telemetry.registry import get_registry

f32 = jnp.float32
# (experts, held (first, count), the bias that skews the choice, the rung expected of the exchange on fsdp=4, a lean): 2,048
# tokens a chip, 2 a token. One expert a chip of 64: a slab of 512 slots a pair of chips and a buffer of 2,048 rows a chip.
# Two a chip of 16: a uniform router sends 512 rows a pair, the slab holds 2,048 (four times), the buffer 4,096 (twice what
# a chip receives). The rung above the first is the LAST: every pair, in token chunks. A lean
# (chip, expert, strength): that chip's tokens alone lean to the expert, so ONE pair of chips passes twice its uniform load
ROUTERS = {
    "uniform": (64, (8, 4), {}, 0, None),
    "one_chip_most_rows_one_none": (64, (8, 4), {8: 10.0}, 2, None),       # every token's first pick is expert 8: chip 0's, 2,048 a slab of 512
    "every_pair_to_one_chip": (16, (4, 8), {4: 10.0, 5: 9.0}, 2, None),    # both picks of every token are chip 0's two experts: 4,096 a slab of 2,048
    "a_chip_receives_over_its_buffer": (16, (4, 8), {4: 0.4}, 2, None),    # expert 4 leans ahead on every chip: each slab holds (over 1,024, under 2,048), the buffer of 4,096 does not
    "one_pair_past_twice_uniform": (16, (4, 8), {}, 0, (0, 6, 1.8)),         # most of chip 0's tokens pick expert 6, chip 1's: over 1,024 rows in that slab and under 2,048
}
MESHES = {"fsdp4": {"fsdp": 4}, "expert4": {"expert": 4}, "expert2_fsdp2": {"expert": 2, "fsdp": 2}}


@pytest.fixture(autouse=True)
def _no_mesh_left_behind():
    reset_mesh()
    yield
    reset_mesh()


def _layer(router, shared=16, hidden=32):
    E, held, bias, rung, lean = ROUTERS[router]
    layer = RoutedMoE(hidden_size=hidden, num_experts=E, k=2, d_ff=16, held=held, shared_ff=shared, scale=2.5, dtype=f32)
    h = jax.random.normal(jax.random.PRNGKey(6), (4, 2048, hidden))
    params = layer.init(jax.random.PRNGKey(7), h)["params"]
    if lean is not None:  # along the router's column of that expert: the chip's every token scores it highest
        chip, expert, strength = lean
        column = params["gate"]["kernel"][:, expert]
        h = h.at[chip].add(strength * column / jnp.sum(column * column))
    select = np.zeros(E, np.float32)
    for e, v in bias.items():
        select[e] = v
    return layer, dict(params, select_bias=jnp.asarray(select)), h, rung


def _value_grads_rows(layer, params, h, mesh=None):
    def value_and_rows(p, x):  # one program: the sown counts are the differentiated call's own
        out, sown = layer.apply({"params": p}, x, mutable=["intermediates"])
        return jnp.sum(out ** 2), sown["intermediates"]["rows"][0]

    both = jax.value_and_grad(value_and_rows, argnums=(0, 1), has_aux=True)
    with jax.default_matmul_precision("highest"):
        if mesh is None:
            (value, rows), grads = both(params, h)
            return value, grads, rows
        topo = initialize_mesh(MeshConfig.from_dict(mesh), devices=jax.devices()[:4], force=True)
        with topo.mesh:
            (value, rows), grads = jax.jit(both)(params, h)
        reset_mesh()
        return value, grads, rows


ROUTINGS = [("uniform", "fsdp4"), ("uniform", "expert2_fsdp2"), ("one_chip_most_rows_one_none", "fsdp4"), ("every_pair_to_one_chip", "fsdp4"),
            ("a_chip_receives_over_its_buffer", "fsdp4"), ("one_pair_past_twice_uniform", "fsdp4"), ("one_chip_most_rows_one_none", "expert2_fsdp2")]


@pytest.mark.parametrize("router,mesh", ROUTINGS)
def test_the_exchange_gives_one_chips_output_and_gradients_and_the_sum_forms(router, mesh):
    """The layer on four virtual devices, its rows exchanged over ``fsdp`` (and its parts summed over ``expert`` where the
    mesh has both), against the layer on one device and against the sum form on ``expert=4``: the value, every leaf's
    gradient and the input's within 2e-6 of the largest entry (float32 at the highest precision: the order of a token's
    sum over its rows differs, nothing else), bit for bit where it does not (the counts). No row is dropped at any skew;
    the first rung holds where the fullest PAIR of chips' rows fit a slab (four times its uniform load) and the
    fullest chip's its buffer (twice), else every pair goes in token chunks; what the fullest and the emptiest chip computed, and
    the rows that crossed chips, are the sown rows' last three."""
    layer, params, h, rung = _layer(router)
    want, (g_params, g_h), rows_one = _value_grads_rows(layer, params, h)
    got, (e_params, e_h), rows = _value_grads_rows(layer, params, h, MESHES[mesh])
    summed, (s_params, s_h), rows_sum = _value_grads_rows(layer, params, h, MESHES["expert4"])
    close = lambda a, b: float(jnp.max(jnp.abs(a - b))) <= 2e-6 * float(jnp.max(jnp.abs(b))) + 1e-12
    assert close(got, want) and close(summed, want) and close(e_h, g_h) and close(s_h, g_h)
    for name in g_params:
        for a, b, c in zip(*(jax.tree_util.tree_leaves(g[name]) for g in (e_params, s_params, g_params))):
            assert close(a, c) and close(b, c), name
    routed, dropped, *_, sent, most, least = (int(x) for x in rows)
    assert (routed, dropped) == (int(rows_one[0]), 0) == (int(rows_sum[0]), 0) and (mesh != "fsdp4" or int(rows[4]) == rung)
    assert len(rows_one) == 6 and int(rows_sum[6]) == 0 and 0 < sent < routed  # one chip sows the six older counts; the sum form sends nothing; rows that stay are no traffic
    assert most >= routed / 4 >= least >= 0
    if router == "one_pair_past_twice_uniform":  # chip 0 sent chip 1 some 1,700 rows, which the buffer there held beside the others' 500-600 each
        assert 1024 + 3 * 500 < most <= 4096
    elif router != "uniform" and mesh == "fsdp4":  # chip 0 computed most rows, and where every pair is its own some chip none
        assert most > 2 * routed / 4 and (least == 0) == (router == "every_pair_to_one_chip")


@pytest.mark.parametrize("router,mesh", ROUTINGS)
def test_the_senders_sums_off_the_slabs_are_the_gathered_ones(router, mesh, monkeypatch):
    """The same seven routings at a width of whole lanes (128), the rule's word steered to ``kernel`` (its kernels
    interpreted): a token's sum over its own rows of the slabs, the combine and the backward of the rows' gather, read a
    token tile at a time (``ops/pallas/moe_sum_rows.py``, a group every ``slab`` rows) against the (tokens, k, d) gathers,
    on the sender's side and on the receiver's: the value, every leaf's gradient and the input's as close as the exchange
    is to one chip, the counts bit for bit, and the rung each routing takes the one it takes by the gathers (the chunked
    last rung, 512 tokens a chunk through the first rung's slabs, runs the same function and so the same kernel). The
    series ``ffn/rows`` says which was traced: the sender's call and the receiver's ``held_experts`` both ``kernel``."""
    layer, params, h, rung = _layer(router, hidden=128)
    want, (g_params, g_h), rows_gathered = _value_grads_rows(layer, params, h, MESHES[mesh])
    traced = lambda: {p: get_registry().peek("program_regions_traced_total", region="ffn/rows", path=p) or 0 for p in ("kernel", "xla")}
    monkeypatch.setattr(placement, "kernel_path", lambda *a, **k: "kernel")
    before = traced()
    got, (k_params, k_h), rows = _value_grads_rows(layer, params, h, MESHES[mesh])
    assert traced()["kernel"] > before["kernel"] and traced()["xla"] == before["xla"]
    close = lambda a, b: float(jnp.max(jnp.abs(a - b))) <= 2e-6 * float(jnp.max(jnp.abs(b))) + 1e-12
    assert close(got, want) and close(k_h, g_h)
    for a, b in zip(jax.tree_util.tree_leaves(k_params), jax.tree_util.tree_leaves(g_params)):
        assert close(a, b)
    assert [int(x) for x in rows] == [int(x) for x in rows_gathered] and int(rows[1]) == 0 and (mesh != "fsdp4" or int(rows[4]) == rung)


def test_the_last_rung_walks_token_chunks_through_the_first_rungs_slab():
    """``exchange_rungs``: a slab of four times a pair of chips' uniform load, a buffer of twice what a chip receives.
    ``_in_chunks``: the fewest equal chunks of a chip's tokens whose every pair fits the buffer's share of one sender."""
    assert sharded_moe.exchange_rungs(8192, 8, 2, 128, 4) == (4096, 8192)  # the cell's: a uniform router sends 1,024 rows a pair of chips, 4,096 to a chip
    assert sharded_moe.exchange_rungs(2048, 2, 1, 64, 4) == (512, 2048) and sharded_moe.exchange_rungs(96, 4, 1, 16, 4) == (96, 384)  # (capped at every pair one way)
    assert sharded_moe._in_chunks(8192, 2, 8192, 4) == (8192, 8) and sharded_moe._in_chunks(2048, 1, 2048, 4) == (2048, 4)
    assert sharded_moe._in_chunks(96, 1, 384, 4) == (384, 1) and sharded_moe._in_chunks(7, 2, 16, 4) == (16, 7)  # a prime count of tokens: one a chunk


@pytest.mark.parametrize("mesh,want", [("fsdp4", ("fsdp",)), ("expert4", ("expert",)), ("expert2_fsdp2", ("expert", "fsdp")), (None, ()), ("data4", ())])
def test_the_held_experts_lie_over_expert_and_fsdp_by_one_rule(mesh, want):
    """``placement.held_axes`` and the leaves' partition rule say the same thing: the leading dimension over ``expert``
    and ``fsdp``, each where it is wider than one, all or none by whether they divide the experts; a ``data`` axis holds
    them whole; inside another's manual region nothing is the layer's to split."""
    from deepspeed_tpu.runtime.zero.partition import match_partition_rule
    from deepspeed_tpu.utils import groups

    if mesh is not None:
        initialize_mesh(MeshConfig.from_dict({"data": 4} if mesh == "data4" else MESHES[mesh]), devices=jax.devices()[:4], force=True)
    assert placement.held_axes((8, 32, 16)) == want
    assert placement.held_axes((6, 32, 16)) == ()  # four chips do not divide six experts: whole on every chip
    for leaf in ("experts_wg", "experts_wi", "experts_wo"):
        assert match_partition_rule(("layer_1", "routed", leaf), MOE_PARTITION_RULES) == placement.HELD == jax.sharding.PartitionSpec(("expert", "fsdp"), None, None)
    if mesh in ("fsdp4", "expert2_fsdp2"):  # the axis the rows are exchanged over is ZeRO's and the batch's: the reference's expert group inside its data group
        assert groups.get_fsdp_axis() in want and groups.get_fsdp_axis() in groups.get_data_parallel_axis()
    if mesh is not None:  # told how many experts are held, the getters count the chips they are spread over as the layer does; untold, the ``expert`` axis as ever
        assert groups.get_expert_parallel_world_size(held=8) == (1 if mesh == "data4" else 4) and groups.get_expert_data_parallel_world_size(held=8) == (4 if mesh == "data4" else 1)
        assert groups.get_expert_parallel_world_size(held=6) == 1 and groups.get_expert_parallel_world_size() == get_mesh_topology().axis_size("expert")
    if mesh == "fsdp4":
        seen, rows = [], jax.sharding.PartitionSpec("fsdp")
        jax.shard_map(lambda x: seen.append(placement.held_axes((8, 32, 16))) or x, mesh=get_mesh_topology().mesh, in_specs=rows, out_specs=rows)(jnp.zeros((4, 2)))
        assert seen == [()]


def _engine(stage, mesh):
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=512, n_layers=2, n_heads=4, n_kv_heads=2, head_dims=16, d_model=64, d_ff=96, max_seq_len=64, norm="rmsnorm",
                            activation="swiglu", pos_emb="rope", tie_embeddings=False, qk_norm=True, sliding_window=16, norm_scheme="output",
                            layer_kinds=(("window", "dense"), ("nope", "routed")), moe_num_experts=16, moe_top_k=4, moe_d_ff=32, moe_shared_d_ff=32,
                            moe_scoring="sigmoid", moe_route_scale=2.5, moe_held=(4, 4), moe_aux_loss_coef=0.0, dtype=jnp.bfloat16)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 64), np.int32)})
    devices = int(np.prod(list(mesh.values())))
    topo = initialize_mesh(MeshConfig.from_dict(mesh), devices=jax.devices()[:devices], force=True)
    engine = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config={
        "train_micro_batch_size_per_gpu": 1, "bf16": {"enabled": True}, "optimizer": {"type": "adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": stage, "stage3_param_persistence_threshold": 0}, "steps_per_print": 10**9})[0]
    return engine, params, devices


@pytest.mark.parametrize("stage,mesh,gone", [(3, {"fsdp": 4}, True), (0, {"fsdp": 4}, False), (3, {"data": 1}, False)])
def test_the_tree_handed_to_initialize_is_the_engines_at_stage_three(stage, mesh, gone):
    """ZeRO stage 3 partitions the tree it is handed in place, as the reference's does a module's parameters: every leaf
    that was DIVIDED (a whole array on one device, put over four) is deleted once its shards stand, so the first chip never
    holds the tree beside its share of it; a leaf that stays whole on every chip may be the array handed in and is kept. At
    the other stages, and on one chip (an alias), the tree is left alone; the engine trains on either."""
    engine, params, devices = _engine(stage, mesh)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    deleted = {jax.tree_util.keystr(path) for path, leaf in leaves if leaf.is_deleted()}
    if gone:
        divided = {jax.tree_util.keystr(path) for (path, _), now in zip(leaves, jax.tree_util.tree_leaves(engine.params)) if not now.sharding.is_fully_replicated}
        assert deleted == divided and len(divided) >= 12 and any("experts_wo" in name for name in deleted)
        assert {name for name in divided if "experts_" in name} == {f"['layer_1']['routed']['experts_w{m}']" for m in "gio"}
    else:
        assert not deleted
    batch = {"input_ids": np.random.default_rng(0).integers(0, 512, (devices, 64), dtype=np.int32)}
    first = float(engine.train_batch(iter([batch])))
    assert np.isfinite(first) and float(engine.train_batch(iter([batch]))) < first


def test_no_all_gather_carries_an_experts_leaf_in_the_four_device_step():
    """The engine's fused step at ZeRO stage 3 over ``fsdp=4``, compiled for four virtual devices: every ``experts_*`` leaf
    (master, moments, carried copy) lies split by expert, ONE a chip of the four held; the rows travel (all-to-all
    operations), and no all-gather makes an array of all four experts' shape."""
    engine, _, _ = _engine(3, {"fsdp": 4})
    for tree in (engine.params, engine._compute_params() or engine.params):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            if "experts_" in jax.tree_util.keystr(path):
                assert leaf.sharding.spec == jax.sharding.PartitionSpec("fsdp") and leaf.addressable_shards[0].data.shape[0] == 1
    fn = engine._fused_step
    while not hasattr(fn, "lower") and hasattr(fn, "__wrapped__"):
        fn = fn.__wrapped__
    batch = engine._put_batch({"input_ids": np.zeros((4, 64), np.int32)}) if hasattr(engine, "_put_batch") else {"input_ids": jnp.zeros((4, 64), jnp.int32)}
    text = fn.lower(engine.params, engine._params_c, engine.opt_state, batch, 0, 1.0, 1.0, 1e-4).compile().as_text()
    gathers = re.findall(r"= (\S+) all-gather(?:-start)?\(", text)
    assert gathers and "all-to-all" in text
    whole = re.compile(r"\[4,64,32\]|\[4,32,64\]")  # all four held experts of (64, 32) or (32, 64) in one array
    assert not [g for g in gathers if whole.search(g)], [g for g in gathers if whole.search(g)]


def test_the_new_counters_and_gauges_reach_the_registry():
    """``_count_rows``: the rows that crossed chips are counted once, where they left; the fullest and the emptiest chip's
    rows are gauges, beside the six older entries."""
    from deepspeed_tpu.moe.layer import _count_rows

    reg = get_registry()
    before = {name: reg.peek(name) or 0 for name in ("moe_rows_sent_total", "moe_rows_routed_here_total", "moe_rows_dropped_total")}
    _count_rows(np.array([[100, 0, 30, 20, 0, 1100, 70, 40, 10], [120, 0, 50, 10, 1, 1300, 90, 60, 5]]))
    rose = {name: (reg.peek(name) or 0) - was for name, was in before.items()}
    assert rose == {"moe_rows_sent_total": 160.0, "moe_rows_routed_here_total": 220.0, "moe_rows_dropped_total": 0.0} and reg.peek("moe_rows_received_total") is None
    assert reg.peek("moe_chip_rows_max") == 60.0 and reg.peek("moe_chip_rows_min") == 5.0
