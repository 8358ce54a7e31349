"""The way into the trainer: ``deepspeed_tpu.initialize`` with the
configuration's own dictionary, then ``forward`` / ``backward`` / ``step``, one
optimizer step a batch, as a user's loop would.

The window holds whole steps: a step is dispatched while the window is open,
one step stays in flight behind the one being waited for (as a loop that reads
its loss a step late), and the window closes when the last dispatched step has
ended. The rate is every token of those steps over that whole time.

A traced run profiles ``trace_steps`` steps of the same loop BEFORE the window,
as part of set-up: every step of a training run is the same program on the same
shapes, a traced second of four chips is 240,000 device events, and the whole
40 s window took 13 minutes to write and reduce (my chip run, PR 24).
"""

import time

import numpy as np

from benchmarks.lib import reference, weights
from benchmarks.lib.manifest import BENCH, load_module, published


def _step(engine, batch):
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()
    return loss


def _loop(engine, batches, losses, keep_going):
    """Dispatch steps while ``keep_going(steps so far)``, waiting each time for
    the step before the one just dispatched; returns when the last has ended.
    Appends (batch index, loss) to ``losses`` and returns the times at which the
    steps ended, in seconds from the call. ``keep_going(steps, seconds)``."""
    import jax

    origin, first, ends = time.perf_counter(), len(losses), []
    while keep_going(len(losses) - first, time.perf_counter() - origin):
        b = len(losses) % len(batches)
        with jax.profiler.TraceAnnotation("bench/train_step", what="dispatch"):
            losses.append((b, _step(engine, batches[b])))
        if len(losses) - first >= 2:  # wait for the step before the one just dispatched
            with jax.profiler.TraceAnnotation("bench/wait_loss", what="one step behind"):
                jax.block_until_ready(losses[first + len(ends)][1])
            ends.append(time.perf_counter() - origin)
    while len(ends) < len(losses) - first:
        jax.block_until_ready(losses[first + len(ends)][1])
        ends.append(time.perf_counter() - origin)
    return ends


def run(cell, opts):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import initialize_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    cfg, traffic = cell["config"], cell["traffic"]
    chips = cell["chips"]
    model = weights.build_model(cfg)
    trainer = dict(cfg["trainer"])
    mesh_cfg = trainer.pop("mesh")
    global_batch = trainer["train_micro_batch_size_per_gpu"] * chips
    gen = load_module(f"{BENCH}/generators/{traffic['generator']}.py")
    batches = gen.generate(traffic["params"], opts["seed"], opts["seconds"],
                           {"vocab_size": model.cfg.vocab_size, "global_batch": global_batch})["batches"]
    seq_len = int(batches[0]["input_ids"].shape[1])

    params = jax.jit(lambda k: model.init(k, {"input_ids": np.zeros((1, seq_len), np.int32)}))(weights.seed_key(opts["seed"]))
    # the plain side of `correct`, before the engine takes the parameters: the first
    # batch's loss by the benchmark's own plain forward in bf16, one row at a time
    # (a row's float32 logits are 0.4 GB at 2048 x 50304)
    rows = batches[0]["input_ids"]
    loss_plain = float(np.mean([float(reference.causal_lm_loss(
        reference.decoder_logits(params, rows[r:r + 1], published(cfg), cfg["reference"]["norm"], jnp.bfloat16),
        rows[r:r + 1])) for r in range(rows.shape[0])]))
    # the GLOBAL mesh, as a config's "mesh" key makes it: the flash kernel finds its
    # shard_map axes there, and without it the TPU compiler refuses the step ("Mosaic
    # kernels cannot be automatically partitioned", my chip run, PR 24)
    topo = initialize_mesh(MeshConfig.from_dict(mesh_cfg), devices=jax.devices()[:chips], force=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, mesh=topo, config=trainer)
    del params
    t_built = time.perf_counter()
    opts["say"](f"engine built; plain first loss {loss_plain:.4f}")

    losses = []  # (batch index, loss) in step order
    for i in range(int(cfg["warmup_steps"])):
        losses.append((len(losses) % len(batches), _step(engine, batches[len(losses) % len(batches)])))
    jax.block_until_ready([l for _, l in losses])
    n_warm = len(losses)
    t_warm = time.perf_counter()

    opts["say"](f"warmed up: {n_warm} steps, losses {[round(float(l), 3) for _, l in losses]}, cache {opts['cache_counts']()}")
    if opts["tracer"].enabled:
        n_traced = int(cfg.get("trace_steps", 8))
        opts["tracer"].start()
        with jax.profiler.TraceAnnotation("bench/window"):
            _loop(engine, batches, losses, lambda n, t: n < n_traced)
        opts["tracer"].stop()
        opts["say"](f"traced {n_traced} steps")
    opts["compiles"].take()
    origin = time.perf_counter()
    setup_s = origin - opts["t0"]
    step_ends = _loop(engine, batches, losses, lambda n, t: t < opts["seconds"])
    elapsed = step_ends[-1]
    compiles, compile_s = opts["compiles"].take()
    opts["say"](f"window over: {len(step_ends)} steps in {elapsed:.2f}s")

    values = [(b, float(l)) for b, l in losses]
    n_steps = len(step_ends)
    tokens = n_steps * global_batch * seq_len
    first_seen, fell = {}, None
    for b, v in values:
        if b in first_seen:
            fell = v < first_seen[b]  # the last repeat decides
        else:
            first_seen[b] = v
    # tolerance 0.05 on a loss near ln(V) ~ 10.8: both sides are bf16 and differ in the
    # order of every reduction (fused cross-entropy, flash attention); PR 22 saw 4e-5 to
    # 0.02 between the program's own two paths. A missing layer or a wrong mask is > 0.1 off
    diff = abs(values[0][1] - loss_plain)
    finite = all(np.isfinite(v) for _, v in values)
    correct = bool(finite and fell and diff <= cfg["correct"]["first_loss_tol"] and int(engine.skipped_steps) == 0)
    return {
        "kind": "train", "correct": correct, "attempted": n_steps, "failed": 0 if finite else n_steps,
        "end_to_end": {"train_tokens_per_s": tokens / elapsed, "setup_s": setup_s},
        "compiles_in_window": compiles, "compile_seconds_in_window": compile_s,
        "seconds": opts["seconds"], "elapsed_s": elapsed,
        "train": {"global_batch": global_batch, "seq_len": seq_len, "micro_batch": trainer["train_micro_batch_size_per_gpu"],
                  "steps": n_steps, "step_ends": step_ends},
        "extras": {"first_loss": values[0][1], "plain_first_loss": loss_plain, "first_loss_diff": diff,
                   "last_loss": values[-1][1], "loss_fell_on_a_repeat": fell, "steps": n_steps,
                   "step_ms_median": float(np.median(np.diff([0.0] + step_ends))) * 1e3 if n_steps else None,
                   "elapsed_s": elapsed, "setup_split_s": {"build": t_built - opts["t0"], "warmup": t_warm - t_built}},
    }
