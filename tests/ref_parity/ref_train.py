"""Train a tiny HF GPT-2 with the *installed reference DeepSpeed*
(read-only at /root/reference) on CPU/gloo and dump the per-step loss
trajectory as JSON.

This is the reference half of the loss-curve-parity oracle: the matching native
half trains the same checkpoint through ``deepspeed_tpu.initialize``
and asserts per-step deltas (tests/unit/test_reference_parity.py).

Run as a subprocess, one per rank:

    RANK=r WORLD_SIZE=w LOCAL_RANK=r MASTER_ADDR=127.0.0.1 MASTER_PORT=p \
      python ref_train.py <spec.json>

spec.json: {ckpt_dir, steps, dtype: fp32|bf16, zero_stage, lr,
            global_batch, seq_len, data_seed, out_path}
Writes ``{out_path}.rank{r}`` with {"losses": [...]} — the local
mean-CE per step; equal per-rank batch sizes make the average of rank
files the global mean loss.

Reference entry points exercised: ``deepspeed.initialize``
(/root/reference/deepspeed/__init__.py:70), engine forward/backward/step
(runtime/engine.py), gloo TorchBackend (comm/torch.py), and for bf16 the
BF16/ZeRO optimizer wrapping — i.e. the real reference training loop,
not a re-implementation.
"""
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, "shims"))
sys.path.insert(0, "/root/reference")

import _ref_compat  # noqa: E402  (torch/numpy compat, pre-import)
import numpy as np  # noqa: E402
import torch  # noqa: E402

import deepspeed  # noqa: E402

_ref_compat.patch_deepspeed()


def main(spec_path: str) -> None:
    with open(spec_path) as f:
        spec = json.load(f)
    rank = int(os.environ.get("RANK", "0"))
    world = int(os.environ.get("WORLD_SIZE", "1"))
    micro_bs = spec["global_batch"] // world
    assert micro_bs * world == spec["global_batch"]

    from transformers import GPT2LMHeadModel

    torch.manual_seed(0)  # moot: weights come from the checkpoint
    model = GPT2LMHeadModel.from_pretrained(spec["ckpt_dir"])
    model.train()

    bf16 = spec["dtype"] == "bf16"
    fp16_cfg = spec.get("fp16")  # dynamic-loss-scale schedule parity leg
    gas = int(spec.get("gas", 1))
    if fp16_cfg:
        _ref_compat.enable_cpu_fp16()
    ds_config = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": gas,
        "steps_per_print": 1 << 30,  # silence the reference's step log
        # plain (non-decoupled) Adam with zero decay: the exact update
        # deepspeed_tpu's "Adam"+adam_w_mode=False produces
        "optimizer": {"type": "Adam",
                      "params": {"lr": spec["lr"], "betas": [0.9, 0.999], "eps": 1e-8,
                                 "weight_decay": float(spec.get("weight_decay", 0.0)),
                                 "torch_adam": True,
                                 "adam_w_mode": bool(spec.get("adam_w_mode", False))}},
        "zero_optimization": {"stage": spec["zero_stage"]},
        "bf16": {"enabled": bf16},
    }
    if fp16_cfg:
        ds_config["fp16"] = dict(fp16_cfg, enabled=True)
    if spec.get("gradient_clipping"):
        ds_config["gradient_clipping"] = float(spec["gradient_clipping"])
    if spec.get("scheduler"):
        ds_config["scheduler"] = spec["scheduler"]
    engine, _, _, _ = deepspeed.initialize(model=model, model_parameters=model.parameters(),
                                           config=ds_config, dist_init_required=True)

    vocab = model.config.vocab_size
    # the SAME one-call draw as test_reference_parity.make_batches: a finite
    # (n_batches, global_batch, seq) stream cycled so the model memorizes
    rng = np.random.default_rng(spec["data_seed"])
    data = rng.integers(0, vocab, size=(spec["n_batches"], spec["global_batch"], spec["seq_len"]))
    losses, scales, overflows = [], [], []
    for step in range(spec["steps"]):
        micro_losses = []
        for m in range(gas):  # micro-batch stream index = step*gas + m
            batch = data[(step * gas + m) % spec["n_batches"]]
            ids = torch.from_numpy(batch[rank * micro_bs:(rank + 1) * micro_bs].astype(np.int64))
            logits = engine(input_ids=ids).logits
            # shifted mean CE in fp32 — mirror CausalLM.loss_fn
            loss = torch.nn.functional.cross_entropy(
                logits[:, :-1].reshape(-1, vocab).float(), ids[:, 1:].reshape(-1))
            engine.backward(loss)
            engine.step()  # applies only at the gas boundary (ref contract)
            micro_losses.append(float(loss))
        losses.append(sum(micro_losses) / gas)
        if fp16_cfg:
            # zero fp16 optimizers carry a DynamicLossScaler; the unfused
            # stage-0 wrapper inlines cur_scale directly
            opt = engine.optimizer
            scaler = getattr(opt, "loss_scaler", None)
            scales.append(float(scaler.cur_scale if scaler is not None else opt.cur_scale))
            overflows.append(bool(opt.overflow))

    out = {"losses": losses}
    if fp16_cfg:
        out.update(scales=scales, overflows=overflows)
    with open(f"{spec['out_path']}.rank{rank}", "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    main(sys.argv[1])
