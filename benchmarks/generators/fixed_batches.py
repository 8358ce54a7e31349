"""Pre-training batches: ``n_batches`` seeded batches of whole sequences of
``seq_len`` uniform random tokens, which the trainer cycles through, one
optimizer step a batch. Random tokens carry no signal but the batch itself, so
a batch seen again must score lower than the first time: the run's own check
that the optimizer stepped.

params: ``seq_len``, ``n_batches``. The global batch is the configuration's
(micro-batch per chip times chips), handed in through ``ctx``.
"""

import numpy as np


def generate(params: dict, seed: int, seconds: float, ctx: dict) -> dict:
    rng = np.random.default_rng([int(seed), 2])
    shape = (int(params["n_batches"]), int(ctx["global_batch"]), int(params["seq_len"]))
    ids = rng.integers(0, int(ctx["vocab_size"]), size=shape, dtype=np.int32)
    return {"batches": [{"input_ids": ids[i]} for i in range(shape[0])]}
