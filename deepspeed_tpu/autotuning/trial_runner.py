"""Isolated autotuning trial: one experiment in its own process.

The reference runs every autotuning experiment as a separate launcher
job (``/root/reference/deepspeed/autotuning/scheduler.py`` invoked from
``launcher/runner.py:359``) precisely so a crashing config cannot kill
the search. The in-process TPU trial path is cheaper but shares fate
with the tuner: a hard XLA abort or an OOM-kill takes the whole search
down. This runner restores the reference's isolation contract:

    python -m deepspeed_tpu.autotuning.trial_runner spec.json out.json

``spec.json``::

    {"config": <full merged ds config>,          # experiment already applied
     "model": {<TransformerConfig kwargs>} | "pkg.module:factory",
     "batches_npz": "/path/batches.npz",         # arrays of (n, B, ...) stacks
     "steps_per_trial": 4, "warmup_steps": 1, "metric": "throughput"}

Writes ``out.json``: {"value": float, "memory_bytes": int|null}. Any
failure leaves out.json absent and exits nonzero — the scheduler scores
the trial None and the search continues.
"""

import importlib
import json
import os
import sys

import numpy as np


def build_model(model_ref):
    """A model from a JSON-able reference: TransformerConfig kwargs dict,
    or an import path ``"pkg.module:factory"`` resolved here (the
    subprocess cannot receive a live callable)."""
    if isinstance(model_ref, str):
        mod, _, attr = model_ref.partition(":")
        if not attr:
            raise ValueError(f"model import path needs 'module:factory', got {model_ref!r}")
        return getattr(importlib.import_module(mod), attr)()
    from ..models import CausalLM, TransformerConfig

    return CausalLM(TransformerConfig(**model_ref))


def load_batches(spec):
    """Batches from ``batches_npz`` (local path) or ``batches_b64``
    (npz bytes inline in the spec — the remote/ssh transport, where the
    scheduler's temp files do not exist on the executing host)."""
    if "batches_b64" in spec:
        import base64
        import io

        z = np.load(io.BytesIO(base64.b64decode(spec["batches_b64"])))
    else:
        z = np.load(spec["batches_npz"])
    with z:
        stacks = {k: z[k] for k in z.files}
    n = next(iter(stacks.values())).shape[0]
    return [{k: v[i] for k, v in stacks.items()} for i in range(n)]


def run_spec(spec: dict) -> dict:
    import jax

    cache_dir = os.environ.get("DS_AT_COMPILE_CACHE")
    if cache_dir:
        # fresh-process trials recompile identical toy HLO; a shared
        # persistent cache makes repeat searches (and CI) ~cold-start-free
        from ..utils.compile_cache import enable_compilation_cache

        enable_compilation_cache(jax, cache_dir, min_compile_secs=1.0)

    from .autotuner import run_trial

    model = build_model(spec["model"])
    batches = load_batches(spec)
    params = model.init(jax.random.PRNGKey(0), batches[0])
    val, mem = run_trial(model, params, spec["config"], batches,
                         int(spec.get("steps_per_trial", 4)), int(spec.get("warmup_steps", 1)),
                         spec.get("metric", "throughput"))
    return {"value": float(val), "memory_bytes": mem}


RESULT_SENTINEL = "DS_TRIAL_RESULT "


def main(argv=None) -> int:
    """File transport: ``trial_runner spec.json out.json``. Pipe transport
    (remote slots — no shared filesystem): ``trial_runner -`` reads the
    spec from stdin and prints ``DS_TRIAL_RESULT {json}`` on stdout."""
    argv = argv if argv is not None else sys.argv[1:]
    pipe = argv == ["-"]
    if not pipe and len(argv) != 2:
        print("usage: python -m deepspeed_tpu.autotuning.trial_runner <spec.json> <out.json> | -",
              file=sys.stderr)
        return 2
    if pipe:
        spec = json.load(sys.stdin)
    else:
        with open(argv[0]) as f:
            spec = json.load(f)
    crash_stage = os.environ.get("DS_AT_TEST_CRASH_STAGE")
    if crash_stage is not None and \
            spec["config"].get("zero_optimization", {}).get("stage") == int(crash_stage):
        # test hook: simulate the failure class isolation exists for — a
        # hard kill (OOM killer / XLA abort) that no try/except survives
        os.abort()
    out = run_spec(spec)
    if pipe:
        print(RESULT_SENTINEL + json.dumps(out), flush=True)
        return 0
    tmp = argv[1] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, argv[1])
    return 0


if __name__ == "__main__":
    sys.exit(main())
