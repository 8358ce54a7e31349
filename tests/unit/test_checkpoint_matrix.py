"""Checkpoint feature matrix: save -> load -> the trajectory CONTINUES.

The reference dedicates ~20 files to this contract (``/root/reference/
tests/unit/checkpoint/``: save/load x zero-stage x tp x moe x
lr-scheduler x world-resize). The TPU-native matrix runs the same grid
on the 8-device virtual mesh with the strongest available oracle:

    uninterrupted run A (N steps)  ==  run B (k steps) -> save -> fresh
    engine C <- load -> (N-k steps), step for step.

Equality of C's post-resume losses with A's tail proves parameters,
optimizer moments, lr-scheduler clock, AND the data-order bookkeeping
all survived the round trip — a weaker "params match after load" check
would miss a reset Adam moment or scheduler step.

Tier: nightly (every case compiles 3 engines on the CPU mesh); the
default tier keeps the per-subsystem sentinels in test_engine.py /
test_universal_checkpoint.py.
"""

import dataclasses

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, TransformerConfig, gpt2_tiny
from deepspeed_tpu.runtime.dataloader import RepeatingLoader

pytestmark = [
    pytest.mark.nightly,
    # Every case here compiles three multi-device training engines. On
    # jax 0.4.37 that workload died inside native XLA on this container's
    # CPU backend (intermittent segfaults that took the whole pytest process
    # down). On the installed JAX 0.9.0 it no longer reproduces: three full
    # runs of the matrix on the CPU passed (21 cases, ~4.5 min each, PR 22).
    # The skip stays only because the file is 4.5 minutes of one tier-1
    # worker and a crash there would fail the tier; lift it with the tier's
    # time budget in hand.
    pytest.mark.skipif(jax.default_backend() == "cpu",
                       reason="4.5 min of CPU compiles; passed 3/3 on JAX 0.9.0, see the note above"),
]

SEQ = 16
VOCAB = 512
PRE_STEPS, POST_STEPS = 3, 2


def _model(moe: bool):
    if moe:
        cfg = TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=4, d_model=32,
                                max_seq_len=64, moe_num_experts=4, moe_top_k=1,
                                moe_layer_freq=2, moe_capacity_factor=4.0)
    else:
        cfg = dataclasses.replace(gpt2_tiny(), vocab_size=VOCAB)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(42), {"input_ids": np.zeros((1, SEQ), np.int32)})
    return model, params


def _engine(stage, tp=1, moe=False, expert=1, scheduler=None, micro_bs=1):
    config = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": stage, "stage3_param_persistence_threshold": 0},
        "steps_per_print": 1 << 30,
        "mesh": {"data": -1, **({"tensor": tp} if tp > 1 else {}),
                 **({"expert": expert} if expert > 1 else {})},
    }
    if scheduler:
        config["scheduler"] = scheduler
    model, params = _model(moe)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=config)
    return engine


def _loader(engine, seed=0, n=64):
    rng = np.random.RandomState(seed)
    data = [{"input_ids": rng.randint(0, VOCAB, size=(SEQ,)).astype(np.int32)} for _ in range(n)]
    return RepeatingLoader(engine.deepspeed_io(data))


def _steps(engine, it, n):
    return [float(engine.train_batch(it)) for _ in range(n)]


def _assert_resumes(make_engine, tmp_path, via_universal=False, dst_engine=None,
                    rtol=2e-4, atol=2e-5):
    """The continues-oracle described in the module docstring."""
    ckpt = str(tmp_path / "ckpt")

    a = make_engine()
    base = _steps(a, _loader(a), PRE_STEPS + POST_STEPS)

    b = make_engine()
    it_b = _loader(b)
    pre = _steps(b, it_b, PRE_STEPS)
    np.testing.assert_allclose(pre, base[:PRE_STEPS], rtol=1e-6, atol=1e-7)
    if via_universal:
        b.save_universal_checkpoint(ckpt, tag="t")
    else:
        b.save_checkpoint(ckpt, tag="t")

    c = dst_engine() if dst_engine else make_engine()
    if via_universal:
        c.load_universal_checkpoint(ckpt, tag="t")
    else:
        c.load_checkpoint(ckpt, tag="t")
    assert c.global_steps == PRE_STEPS
    # a resuming trainer fast-forwards its loader to the recorded position
    it_c = _loader(c)
    for _ in range(PRE_STEPS):
        next(it_c)
    post = _steps(c, it_c, POST_STEPS)
    np.testing.assert_allclose(post, base[PRE_STEPS:], rtol=rtol, atol=atol,
                               err_msg="post-resume trajectory diverged from uninterrupted run")


# ---------------------------------------------------------------- zero x tp
@pytest.mark.parametrize("stage", [0, 1, 2, 3])
@pytest.mark.parametrize("tp", [1, 2])
def test_zero_tp_matrix(stage, tp, tmp_path):
    _assert_resumes(lambda: _engine(stage=stage, tp=tp), tmp_path)


# ---------------------------------------------------------------- moe
@pytest.mark.parametrize("stage", [0, 1, 2])
def test_moe_matrix(stage, tmp_path):
    _assert_resumes(lambda: _engine(stage=stage, moe=True, expert=2), tmp_path)


def test_moe_tp(tmp_path):
    """Experts shard over expert x tensor (the round-4 expert-TP layout)."""
    _assert_resumes(lambda: _engine(stage=1, tp=2, moe=True, expert=2), tmp_path)


# ---------------------------------------------------------------- lr schedulers
@pytest.mark.parametrize("sched", [
    {"type": "WarmupLR", "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 1e-2,
                                    "warmup_num_steps": 4}},
    {"type": "WarmupDecayLR", "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 1e-2,
                                         "warmup_num_steps": 2, "total_num_steps": 10}},
], ids=["warmup", "warmup-decay"])
@pytest.mark.parametrize("stage", [0, 2])
def test_scheduler_clock_survives(sched, stage, tmp_path):
    """Resuming mid-warmup must continue the lr ramp, not restart it: the
    trajectory oracle fails if the scheduler clock resets (step 4's lr
    would repeat step 1's)."""
    _assert_resumes(lambda: _engine(stage=stage, scheduler=sched), tmp_path)


# ---------------------------------------------------------------- precision state
def test_bf16_resume(tmp_path):
    """bf16 compute + fp32 master params survive the round trip."""

    def mk_bf16():
        config = {
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 2},
            "bf16": {"enabled": True},
            "steps_per_print": 1 << 30,
            "mesh": {"data": -1},
        }
        model, params = _model(moe=False)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=config)
        return engine

    # bf16 steps quantize the loss readback; the oracle tolerance widens
    _assert_resumes(mk_bf16, tmp_path, rtol=2e-2, atol=2e-2)


def test_fp16_loss_scaler_state_survives(tmp_path):
    """The dynamic loss scaler's (scale, growth counter) must resume, not
    reset: a reset scale replays the warmup overflow-probing phase and the
    trajectory detaches from the uninterrupted run."""

    def mk():
        config = {
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 1},
            "fp16": {"enabled": True, "initial_scale_power": 8, "loss_scale_window": 2},
            "steps_per_print": 1 << 30,
            "mesh": {"data": -1},
        }
        model, params = _model(moe=False)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=config)
        return engine

    a = mk()
    _steps(a, _loader(a), PRE_STEPS)
    scale_a = float(a.loss_scaler.loss_scale)

    ckpt = str(tmp_path / "ckpt")
    a.save_checkpoint(ckpt, tag="t")
    b = mk()
    b.load_checkpoint(ckpt, tag="t")
    assert float(b.loss_scaler.loss_scale) == scale_a
    # with window=2 the scale must have moved off its initial value by now
    post = _steps(b, _loader(b), 1)
    assert np.isfinite(post).all()


# ---------------------------------------------------------------- resize via universal
@pytest.mark.parametrize("src,dst", [
    ({"stage": 1, "mesh": {"data": 2, "fsdp": 2, "tensor": 2}, "micro": 2},
     {"stage": 1, "mesh": {"data": 8}, "micro": 1}),
    ({"stage": 2, "mesh": {"data": 4, "fsdp": 2}, "micro": 1},
     {"stage": 3, "mesh": {"data": 2, "fsdp": 4}, "micro": 1}),
    ({"stage": 3, "mesh": {"data": 8}, "micro": 1},
     {"stage": 2, "mesh": {"data": 2, "fsdp": 2, "tensor": 2}, "micro": 2}),
], ids=["dp4->dp8", "z2->z3-refsdp", "z3-dp8->z2-3d"])
def test_universal_resize(src, dst, tmp_path):
    """dp/fsdp/tp resize + cross-stage resume through the universal format
    (reference: checkpoint/test_universal_checkpoint.py world resize).
    Global batch is held fixed (micro x dp = 8) so trajectories compare."""

    def _from(d):
        cfg = {
            "train_micro_batch_size_per_gpu": d["micro"],
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": d["stage"], "stage3_param_persistence_threshold": 0},
            "steps_per_print": 1 << 30,
            "mesh": d["mesh"],
        }
        model, params = _model(moe=False)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=cfg)
        return engine

    _assert_resumes(lambda: _from(src), tmp_path, via_universal=True,
                    dst_engine=lambda: _from(dst))
