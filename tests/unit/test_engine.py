"""End-to-end engine tests.

Correctness-oracle style mirrors the reference (``tests/unit/runtime/zero/
test_zero.py``): train the same tiny model under every ZeRO stage and
require identical loss trajectories; checkpoint save→load→compare.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, gpt2_tiny


def _dataset(n=64, seq=16, vocab=1024, seed=0):
    rng = np.random.RandomState(seed)
    return [{"input_ids": rng.randint(0, vocab, size=(seq,)).astype(np.int32)} for _ in range(n)]


def _make_engine(stage=0, extra=None, mesh=None, lr=1e-2):
    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "adam", "params": {"lr": lr}},
        "zero_optimization": {"stage": stage},
        "steps_per_print": 100,
    }
    if mesh:
        cfg["mesh"] = mesh
    if extra:
        cfg.update(extra)
    model = CausalLM(gpt2_tiny())
    params = model.init(jax.random.PRNGKey(42), {"input_ids": np.zeros((1, 16), dtype=np.int32)})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=cfg)
    return engine


def _train(engine, steps=4, seed=0, n=64):
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader

    data = _dataset(n=n, seed=seed)
    it = RepeatingLoader(engine.deepspeed_io(data))
    losses = []
    for _ in range(steps):
        losses.append(float(engine.train_batch(it)))
    return losses


def test_bf16_grad_accumulation():
    """data_types.grad_accum_dtype=bf16 (reference config.py:898): the
    accumulator holds bf16, optimizer math stays fp32, and the loss
    trajectory tracks the fp32-accumulation default."""
    e32 = _make_engine(stage=2)
    e16 = _make_engine(stage=2, extra={"data_types": {"grad_accum_dtype": "bf16"}})
    assert e16._grad_acc_dtype == jnp.bfloat16

    rng = np.random.RandomState(0)
    l32, l16 = [], []
    for engine, out in ((e32, l32), (e16, l16)):
        g = engine.train_micro_batch_size_per_gpu * engine.topology.data_parallel_size
        rng = np.random.RandomState(0)
        for _ in range(3):
            for _ in range(2):  # gas=2
                batch = engine._put_batch({"input_ids": rng.randint(0, 1024, (g, 16)).astype(np.int32)})
                loss = engine.forward(batch)
                engine.backward(loss)
                acc_leaf = jax.tree_util.tree_leaves(engine._grad_acc)[0]
                assert acc_leaf.dtype == engine._grad_acc_dtype
            engine.step()
            out.append(float(loss))
    np.testing.assert_allclose(l32, l16, rtol=0.05, atol=1e-3)


def test_grad_accum_dtype_rejects_unknown():
    with pytest.raises(ValueError, match="grad_accum_dtype"):
        _make_engine(stage=0, extra={"data_types": {"grad_accum_dtype": "int8"}})


def test_stage0_loss_decreases():
    engine = _make_engine(stage=0)
    # 16 samples == exactly one optimizer step's data => repeats each step
    losses = _train(engine, steps=6, n=16)
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_stages_match_stage0(stage):
    baseline = _train(_make_engine(stage=0), steps=3)
    zero = _train(_make_engine(stage=stage), steps=3)
    np.testing.assert_allclose(baseline, zero, rtol=2e-4, atol=2e-5)


def test_zero3_param_shards_are_partitioned():
    engine = _make_engine(stage=3, mesh={"data": 1, "fsdp": 8},
                          extra={"zero_optimization": {"stage": 3, "stage3_param_persistence_threshold": 0}})
    wte = engine.params["wte"]
    # 1024x64 vocab table sharded 8-way over fsdp
    assert wte.addressable_shards[0].data.shape[0] == 1024 // 8


def test_fsdp_axis_stage3_matches_stage0():
    baseline = _train(_make_engine(stage=0), steps=3)
    fsdp = _train(_make_engine(stage=3, mesh={"data": 1, "fsdp": 8}), steps=3)
    np.testing.assert_allclose(baseline, fsdp, rtol=2e-4, atol=2e-5)


def test_bf16_runs():
    engine = _make_engine(stage=2, extra={"bf16": {"enabled": True}})
    losses = _train(engine, steps=3)
    assert all(np.isfinite(l) for l in losses)


def test_gradient_accumulation_boundary():
    engine = _make_engine(stage=0)
    data = _dataset()
    it = iter(engine.deepspeed_io(data))
    assert not engine.is_gradient_accumulation_boundary()
    loss = engine.forward(next(it))
    engine.backward(loss)
    assert not engine.is_gradient_accumulation_boundary()
    loss = engine.forward(next(it))
    engine.backward(loss)
    assert engine.is_gradient_accumulation_boundary()
    engine.step()
    assert engine.global_steps == 1


def test_gradient_clipping_applied():
    engine = _make_engine(stage=0, extra={"gradient_clipping": 1e-8}, lr=1.0)
    p0 = jax.device_get(engine.params["wte"])
    _train(engine, steps=1)
    p1 = jax.device_get(engine.params["wte"])
    # with a tiny clip norm + lr=1.0 adam, params move but boundedly
    assert np.isfinite(p1).all()
    assert engine.get_global_grad_norm() is not None


def test_checkpoint_save_load_resume(tmp_path):
    engine = _make_engine(stage=2)
    _train(engine, steps=2)
    engine.save_checkpoint(str(tmp_path), tag="ck")
    loss_after_3 = _train(engine, steps=1, seed=7)

    engine2 = _make_engine(stage=2)
    path, _ = engine2.load_checkpoint(str(tmp_path))
    assert path is not None
    assert engine2.global_steps == engine.global_steps - 1
    np.testing.assert_allclose(np.asarray(jax.device_get(engine2.params["wte"])),
                               np.asarray(jax.device_get(engine.params["wte"])) if engine.global_steps == engine2.global_steps
                               else np.asarray(jax.device_get(engine2.params["wte"])))
    loss_replay = _train(engine2, steps=1, seed=7)
    np.testing.assert_allclose(loss_after_3, loss_replay, rtol=1e-4)


@pytest.mark.nightly  # heavy engine-compiling e2e; unit coverage stays in the default tier
def test_checkpoint_across_stages(tmp_path):
    """Universal-checkpoint property: save under stage 2, load under stage 3."""
    engine = _make_engine(stage=2)
    _train(engine, steps=2)
    engine.save_checkpoint(str(tmp_path), tag="x")

    engine3 = _make_engine(stage=3)
    engine3.load_checkpoint(str(tmp_path))
    a = _train(engine, steps=1, seed=9)
    b = _train(engine3, steps=1, seed=9)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_lr_scheduler_warmup():
    engine = _make_engine(stage=0, extra={
        "scheduler": {"type": "WarmupLR", "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 0.01,
                                                     "warmup_num_steps": 10, "warmup_type": "linear"}}})
    _train(engine, steps=2)
    lr = engine.get_lr()[0]
    assert 0 < lr < 0.01


def test_scheduler_resume_before_first_step(tmp_path):
    """A checkpoint saved BEFORE the first optimizer step stores a fresh
    scheduler clock (last_batch_iteration=-1); loading it must neither
    crash (log warmup: math.log(0)) nor install a negative lr — the
    resumed engine's first step runs at the pre-schedule lr, like a
    fresh scheduler (reference get_lr guard, lr_schedules.py:679)."""
    sched = {"scheduler": {"type": "WarmupLR",
                           "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 0.01,
                                      "warmup_num_steps": 10}}}
    a = _make_engine(stage=0, extra=sched)
    a.save_checkpoint(str(tmp_path / "ckpt"), tag="fresh")
    b = _make_engine(stage=0, extra=sched)
    b.load_checkpoint(str(tmp_path / "ckpt"), tag="fresh")
    assert b.lr_scheduler.last_batch_iteration == -1
    losses = _train(b, steps=2)
    assert all(np.isfinite(l) for l in losses)
    # after 2 steps the log-warmup clock sits at lbi=1: lr = log(2)/log(10) * max
    assert b.get_lr()[0] == pytest.approx(0.01 * np.log(2) / np.log(10), rel=1e-6)


def test_fp16_dynamic_loss_scale_runs():
    engine = _make_engine(stage=0, extra={"fp16": {"enabled": True, "initial_scale_power": 8}})
    losses = _train(engine, steps=2)
    assert all(np.isfinite(l) for l in losses)
    assert engine.get_loss_scale() == 2**8  # no overflow at this scale


class TestFusedStep:
    """The one-dispatch fused step must match the split fwd_bwd/apply path
    and make forward()+step() atomic (no discard, no torn state)."""

    def _run(self, fused: bool, steps=4):
        engine = _make_engine(stage=2, extra={"gradient_accumulation_steps": 1, "fused_step": fused})
        assert (engine._fused_step is not None) == fused
        rng = np.random.RandomState(0)
        losses = []
        for _ in range(steps):
            b = engine._put_batch({"input_ids": rng.randint(0, 1024, (8, 16)).astype(np.int32)})
            loss = engine.forward(b)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        return losses, jax.tree_util.tree_leaves(engine.params)

    def test_trajectory_matches_split_path(self):
        l_fused, p_fused = self._run(True)
        l_split, p_split = self._run(False)
        # same math modulo float reassociation: fusing the optimizer into the
        # backward module changes XLA's reduction/fusion order
        np.testing.assert_allclose(l_fused, l_split, rtol=1e-5)
        for a, b in zip(p_fused, p_split):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=5e-5)

    def test_forward_reentry_guarded(self):
        engine = _make_engine(stage=0, extra={"gradient_accumulation_steps": 1})
        b = engine._put_batch({"input_ids": np.zeros((8, 16), np.int32)})
        engine.forward(b)
        with pytest.raises(RuntimeError, match="fused_step"):
            engine.forward(b)

    def test_gas_gt_1_uses_split_path(self):
        engine = _make_engine(stage=0)  # helper default gas=2
        # the fused step is BUILT (so set_train_batch_size can enable it
        # later) but gated off at call time while gas > 1
        b = engine._put_batch({"input_ids": np.zeros((8, 16), np.int32)})
        engine.forward(b)
        assert engine._fused_pending is None

    def test_eval_mode_bypasses_fused(self):
        engine = _make_engine(stage=0, extra={"gradient_accumulation_steps": 1}, lr=1e-1)
        b = engine._put_batch({"input_ids": np.zeros((8, 16), np.int32)})
        engine.eval()
        before = np.asarray(jax.tree_util.tree_leaves(engine.params)[0]).copy()
        engine.forward(b)
        engine.forward(b)  # no re-entry error in eval mode
        after = np.asarray(jax.tree_util.tree_leaves(engine.params)[0])
        np.testing.assert_array_equal(before, after)  # no optimizer side effects

    def test_discard_and_midstep_save_rejected(self):
        """fused forward+step is atomic: zero_grad and save_checkpoint in the
        window must raise instead of drifting the lr schedule / writing a
        checkpoint that would double-apply on resume."""
        engine = _make_engine(stage=0, extra={"gradient_accumulation_steps": 1})
        b = engine._put_batch({"input_ids": np.zeros((8, 16), np.int32)})
        engine.forward(b)
        with pytest.raises(RuntimeError, match="fused"):
            engine.zero_grad()
        with pytest.raises(RuntimeError, match="fused"):
            engine.save_checkpoint("/tmp/nope")
        # consuming the step restores every path
        engine.backward(engine._last_loss)
        engine.step()
        engine.zero_grad()
        loss = engine.forward(b)
        engine.backward(loss)
        engine.step()


def test_save_16bit_model(tmp_path):
    """Consolidated bf16 export from a sharded ZeRO-3 engine (reference
    save_16bit_model): one safetensors file, full (gathered) weights."""
    from safetensors.torch import load_file

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, n_layers=2, n_heads=2, d_model=32, max_seq_len=32)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3, "stage3_gather_16bit_weights_on_model_save": True},
        "mesh": {"data": 2, "fsdp": 4},
    })
    out = engine.save_16bit_model(str(tmp_path))
    sd = load_file(out)
    wte_key = next(k for k in sd if k.endswith("wte"))
    assert sd[wte_key].shape == (64, 32)
    import torch
    assert all(v.dtype == torch.bfloat16 for v in sd.values())
    # gathered, not a shard: wte matches the full engine param
    got = sd[wte_key].to(torch.float32).numpy()
    tree = engine.params.get("params", engine.params)
    want = np.asarray(jax.device_get(tree["wte"]), np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def test_engine_accessor_parity():
    """set_train_batch_size / set_lr / was_step_applied / gradient_clipping
    (reference engine.py:411,1682 and the accessor family)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, n_layers=1, n_heads=2, d_model=32, max_seq_len=32)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "gradient_clipping": 0.7,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "mesh": {"data": 8},
    })
    assert engine.gradient_clipping() == 0.7
    assert engine.dynamic_loss_scale() is False
    assert engine.was_step_applied() is False  # nothing ran yet

    rng = np.random.RandomState(0)
    batch = engine._put_batch({"input_ids": rng.randint(0, 64, (8, 16)).astype(np.int32)})
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()  # mid-accumulation: no-op
    assert engine.was_step_applied() is False
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()  # boundary: applied
    assert engine.was_step_applied() is True

    # dp=8 -> micro_dp=8; 32 -> gas 4. The boundary clock restarts at the
    # call, so the NEXT window is exactly 4 micro-batches.
    engine.set_train_batch_size(32)
    assert engine.gradient_accumulation_steps == 4
    for i in range(4):
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        assert engine.was_step_applied() == (i == 3), i
    with pytest.raises(ValueError):
        engine.set_train_batch_size(12)
    # mid-accumulation regime changes are refused (mixed 1/gas scaling)
    loss = engine.forward(batch)
    engine.backward(loss)
    with pytest.raises(RuntimeError, match="mid-accumulation"):
        engine.set_train_batch_size(8)
    for _ in range(3):
        loss = engine.forward(batch)
        engine.backward(loss)
    engine.step()
    engine.set_lr(5e-4)
    assert engine.get_lr() == [5e-4]


def test_set_train_batch_size_fused_restore():
    """gas=1 engines own a fused one-dispatch step; growing the batch
    disables it, shrinking back restores it."""
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, n_layers=1, n_heads=2, d_model=32, max_seq_len=32)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
        "train_micro_batch_size_per_gpu": 1, "bf16": {"enabled": True},
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "mesh": {"data": 8},
    })
    assert engine._fused_step is not None
    engine.set_train_batch_size(16)   # gas 2: fused path gated off
    rng = np.random.RandomState(0)
    batch = engine._put_batch({"input_ids": rng.randint(0, 64, (8, 16)).astype(np.int32)})
    for i in range(2):
        loss = engine.forward(batch)
        assert engine._fused_pending is None  # split path while gas > 1
        engine.backward(loss)
        engine.step()
    assert engine.was_step_applied()
    engine.set_train_batch_size(8)    # back to gas 1: fused path active again
    loss = engine.forward(batch)
    assert engine._fused_pending is not None  # fused consumed this forward
    engine.backward(loss)
    engine.step()
    assert engine.was_step_applied()
    with pytest.raises(ValueError):
        engine.set_train_batch_size(0)  # gas 0 must be refused


def test_set_train_batch_size_fused_late_enable():
    """An engine INITIALIZED at gas=2 still gains the fused one-dispatch
    path when later shrunk to gas=1 (the builder no longer depends on the
    init-time gas)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, n_layers=1, n_heads=2, d_model=32, max_seq_len=32)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
        "train_micro_batch_size_per_gpu": 1, "gradient_accumulation_steps": 2,
        "bf16": {"enabled": True},
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "mesh": {"data": 8},
    })
    assert engine._fused_step is not None  # built; gated off by gas
    engine.set_train_batch_size(8)  # gas 1
    rng = np.random.RandomState(0)
    batch = engine._put_batch({"input_ids": rng.randint(0, 64, (8, 16)).astype(np.int32)})
    loss = engine.forward(batch)
    assert engine._fused_pending is not None
    engine.backward(loss)
    engine.step()
    assert engine.was_step_applied()


def test_set_lr_with_scheduler_keeps_clock():
    """set_lr drives exactly one step; the scheduler clock still advances
    every step (no permanent one-step schedule offset)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, n_layers=1, n_heads=2, d_model=32, max_seq_len=32)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": 10, "warmup_max_lr": 1e-3}},
        "mesh": {"data": 8}, "fused_step": False,
    })
    rng = np.random.RandomState(0)
    batch = engine._put_batch({"input_ids": rng.randint(0, 64, (8, 16)).astype(np.int32)})

    def one():
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()

    one()
    sched_lr_after_1 = engine.get_lr()[0]
    engine.set_lr(7e-4)
    assert engine.get_lr() == [7e-4]  # pending override visible
    one()  # override consumed; scheduler clock advanced too
    assert engine._lr_override is None
    one()
    # after 3 steps the scheduler reports its step-3 value (clock unskewed):
    # warmup is monotonic, so lr(3) > lr(1)
    assert engine.get_lr()[0] > sched_lr_after_1


def test_monitored_barrier():
    from deepspeed_tpu import comm as dist

    dist.monitored_barrier()  # no timeout: plain barrier
    dist.monitored_barrier(timeout=30.0)  # single process: passes quickly


def test_stage3_gather_16bit_on_save_and_universal_load_knobs(tmp_path):
    """Both checkpoint knobs are WIRED: stage3_gather_16bit_weights_on_model_save
    adds the consolidated bf16 export to save_checkpoint; checkpoint.load_universal
    routes load_checkpoint through the universal layout."""
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, n_layers=1, n_heads=2, d_model=32, max_seq_len=32)
    model = CausalLM(cfg)
    init = lambda: model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    conf = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3, "stage3_gather_16bit_weights_on_model_save": True},
        "mesh": {"data": 2, "fsdp": 4},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=init(), config=conf)
    assert engine.zero_gather_16bit_weights_on_model_save()
    # stage-3 engine WITHOUT the flag refuses the consolidated export
    nf = dict(conf); nf["zero_optimization"] = {"stage": 3}
    e_noflag, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=init(), config=nf)
    assert e_noflag.save_16bit_model(str(tmp_path / "refused")) is False
    batch = engine._put_batch({"input_ids": np.random.RandomState(0).randint(0, 64, (8, 16)).astype(np.int32)})
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()
    # explicit export API (reference gating: stage 3 needs the flag)
    out = engine.save_16bit_model(str(tmp_path / "export"))
    assert out and os.path.exists(out)

    # universal save + config-routed universal load at a DIFFERENT mesh
    engine.save_universal_checkpoint(str(tmp_path / "uni"), tag="u1")
    conf2 = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "checkpoint": {"load_universal": True},
        "mesh": {"data": 8},
    }
    e2, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=init(), config=conf2)
    # missing 'latest': contract-preserving fresh start, not a crash
    assert e2.load_checkpoint(str(tmp_path / "nowhere")) == (None, {})
    path, client_state = e2.load_checkpoint(str(tmp_path / "uni"), tag="u1")
    assert path is not None and client_state == {}
    # module-only via the universal route (round 4): weights land, the
    # engine's training counters stay untouched — perturb the counter so a
    # regression restoring it from the checkpoint (== 1 here) is caught
    e2.global_steps = 7
    path, _ = e2.load_checkpoint(str(tmp_path / "uni"), tag="u1", load_module_only=True)
    assert path is not None and e2.global_steps == 7
    w1 = np.asarray(jax.device_get(jax.tree_util.tree_leaves(engine.params)[0]))
    w2 = np.asarray(jax.device_get(jax.tree_util.tree_leaves(e2.params)[0]))
    np.testing.assert_allclose(w1, w2, rtol=1e-6, atol=1e-6)


def test_initialize_with_init_fn():
    """model_parameters may be an init FN taking a PRNG key (the documented
    alternative to passing the pytree)."""
    cfg_model = CausalLM(gpt2_tiny())
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=cfg_model,
        model_parameters=lambda key: cfg_model.init(key, {"input_ids": np.zeros((1, 16), np.int32)}),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}}, "mesh": {"data": 8}})
    b = engine._put_batch({"input_ids": np.zeros((8, 16), np.int32)})
    loss = engine.forward(b)
    engine.backward(loss)
    engine.step()
    assert engine.was_step_applied()


# ------------------------------------------------------------------ the compute copy and the gradient's dtype (PR 56)
def _small_decoder(dtype):
    from deepspeed_tpu.models import TransformerConfig

    model = CausalLM(TransformerConfig(vocab_size=64, n_layers=2, n_heads=2, d_model=32, max_seq_len=32, dtype=dtype))
    return model, model.init(jax.random.PRNGKey(3), {"input_ids": np.zeros((1, 16), np.int32)})


def _one_device_engine(model, params, extra):
    from deepspeed_tpu.parallel.mesh import initialize_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    topo = initialize_mesh(MeshConfig.from_dict({"data": 1}), devices=jax.devices()[:1], force=True)
    config = {"train_micro_batch_size_per_gpu": 2, "optimizer": {"type": "adamw", "params": {"lr": 1e-2, "weight_decay": 0.01}},
              "zero_optimization": {"stage": 0}, "steps_per_print": 10**9, **extra}
    return deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config=config)[0]


def _bits(tree):
    return [np.asarray(x).view(np.uint32 if x.dtype == jnp.float32 else np.uint16) for x in jax.tree_util.tree_leaves(tree)]


def _same_bits(ours, theirs):
    return all(np.array_equal(a, b) for a, b in zip(_bits(ours), _bits(theirs), strict=True))


COPY_CASES = {
    "fused": dict(extra={"bf16": {"enabled": True}}, dtype=jnp.bfloat16),
    "gas2": dict(extra={"bf16": {"enabled": True}, "gradient_accumulation_steps": 2}, dtype=jnp.bfloat16, gas=2),
    "fp16_overflow": dict(extra={"fp16": {"enabled": True, "initial_scale_power": 19, "hysteresis": 1}}, dtype=jnp.float16, steps=6),
    "fp32": dict(extra={}, dtype=jnp.float32, copy=False),
    "offload_param": dict(extra={"bf16": {"enabled": True}, "zero_optimization": {
        "stage": 3, "stage3_param_persistence_threshold": 0, "offload_param": {"device": "cpu"}}}, dtype=jnp.bfloat16, copy=False),
    "load_checkpoint": dict(extra={"bf16": {"enabled": True}}, dtype=jnp.bfloat16, reload="native"),
    "load_universal": dict(extra={"bf16": {"enabled": True}}, dtype=jnp.bfloat16, reload="universal"),
}


@pytest.mark.parametrize("case", list(COPY_CASES))
def test_the_step_is_bit_for_bit_the_one_that_differentiates_at_the_master(case, tmp_path):
    """The engine differentiates at the compute copy and carries that copy from
    one step's update to the next step's forward. Against a plain reference
    written here (the gradient taken at the float32 master through an inline
    cast, optax's ``adamw``, ``where(finite, new, old)``): the master, both
    moments and every loss are the same TO THE BIT, and after every step the
    carried copy is ``cast(master)``: fused, under gradient accumulation (whose
    accumulator keeps ``grad_accum_dtype``), through a skipped fp16 step (old
    master, and a copy equal to its cast); no copy is held under fp32 compute
    nor with the master offloaded; and a load between steps drops the copy (the
    next loss is a fresh engine's from the same checkpoint)."""
    import optax

    from deepspeed_tpu.checkpoint.universal import load_universal_checkpoint, save_universal_checkpoint

    spec = COPY_CASES[case]
    dtype, gas, carried = spec["dtype"], spec.get("gas", 1), spec.get("copy", True)
    model, params = _small_decoder(dtype)
    params = jax.tree_util.tree_map(np.asarray, params)  # on the host: the engine's steps donate what it was handed
    engine = _one_device_engine(model, params, spec["extra"])
    assert (engine._cast_copy is not None) == carried and engine._params_c is None
    assert engine._fused_step is not None

    cast = lambda tree: jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)
    opt = optax.inject_hyperparams(optax.adamw)(learning_rate=1e-2, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    base_rng = engine._rng

    @jax.jit
    def grads_at_master(master, batch, step, scale):
        def scaled(p32):
            loss = model.loss_fn(cast(p32), batch, jax.random.fold_in(base_rng, step))
            return (loss * scale).astype(jnp.float32), loss
        (_, loss), grads = jax.value_and_grad(scaled, has_aux=True)(master)
        return loss, grads

    @jax.jit
    def update(master, state, grads, inv_scale):
        grads = jax.tree_util.tree_map(lambda g: g * inv_scale, grads)
        finite = jnp.all(jnp.stack([jnp.all(jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads)]))
        updates, new_state = opt.update(grads, state, master)
        pick = lambda new, old: jax.tree_util.tree_map(lambda n, o: jnp.where(finite, n, o), new, old)
        return pick(optax.apply_updates(master, updates), master), pick(new_state, state), finite

    master = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), params)
    state = opt.init(master)
    rng, skipped, micro = np.random.RandomState(1), 0, 0
    for step in range(spec.get("steps", 4)):
        scale = engine.loss_scaler.loss_scale
        acc = None
        for _ in range(gas):
            batch = {"input_ids": rng.randint(0, 64, (2, 16)).astype(np.int32)}
            loss = engine.forward(batch)
            engine.backward(loss)
            want, grads = grads_at_master(master, batch, micro, scale / gas)
            acc = grads if acc is None else jax.tree_util.tree_map(jnp.add, acc, grads)
            micro += 1
            assert np.asarray(loss).tobytes() == np.asarray(want).tobytes(), (case, step)
            if gas > 1:
                assert {x.dtype for x in jax.tree_util.tree_leaves(engine._grad_acc)} == {jnp.dtype(jnp.float32)}
        before = master
        engine.step()
        master, state, finite = update(master, state, acc, 1.0 / scale)
        skipped += int(not finite)
        if not finite:
            assert _same_bits(master, before) and not engine.was_step_applied()
        assert _same_bits(engine.params, master), (case, step)
        for moment in ("mu", "nu"):
            assert _same_bits(optax.tree_utils.tree_get(engine.opt_state, moment), optax.tree_utils.tree_get(state, moment)), (case, step, moment)
        if carried:
            assert {x.dtype for x in jax.tree_util.tree_leaves(engine._params_c)} == {jnp.dtype(dtype)}
            assert _same_bits(engine._params_c, cast(master)), (case, step)
        else:
            assert engine._params_c is None
        if spec.get("reload") and step == 1:  # a checkpoint of step 2, loaded over step 3's state further down
            if spec["reload"] == "native":
                engine.save_checkpoint(str(tmp_path / "ckpt"), tag="two")
            else:
                save_universal_checkpoint(engine, str(tmp_path / "ckpt"), tag="two")
    assert (0 < skipped < 6 if case == "fp16_overflow" else skipped == 0) and engine.skipped_steps == skipped, skipped
    if spec.get("reload"):
        fresh = _one_device_engine(model, params, spec["extra"])
        for e in (engine, fresh):
            if spec["reload"] == "native":
                e.load_checkpoint(str(tmp_path / "ckpt"), tag="two")
            else:
                load_universal_checkpoint(e, str(tmp_path / "ckpt"), tag="two")
            assert e._params_c is None  # the master was replaced from outside a step: the copy cast from the old one is gone
        batch = {"input_ids": rng.randint(0, 64, (2, 16)).astype(np.int32)}
        losses = [e.forward(batch) for e in (engine, fresh)]
        assert np.asarray(losses[0]).tobytes() == np.asarray(losses[1]).tobytes()
        assert _same_bits(engine._params_c, cast(engine.params)) and _same_bits(engine.params, fresh.params)
