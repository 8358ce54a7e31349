"""Block-diffusion training batches: ``n_batches`` seeded batches of rows ``[xt ; x0]``, which the trainer cycles
through, one optimizer step a batch. ``x0`` is ``seq_len`` uniform random tokens over ``[0, vocab_size - 1)`` in blocks
of ``block_len``; ``xt`` is its noised copy: for each block ``m`` is drawn uniformly from {1 .. block_len} and a
uniformly chosen subset of ``m`` of its positions carries ``MASK = vocab_size - 1`` (the vocabulary's last row), the
rest ``x0``'s own token. The noise is the generator's, a collator's work: a batch seen again carries the same noise, so
it must score lower than the first time, the run's own check that the optimizer stepped. Every block masks at least
one position, so a row's weights ``block_len / m`` sum to ``seq_len`` exactly and (block_len + 1) / (2 block_len) of
the positions are masked in expectation.

params: ``seq_len`` (L: a row is 2 L ids), ``block_len``, ``n_batches``. The global batch is the configuration's
(micro-batch per chip times chips), handed in through ``ctx``.
"""

import numpy as np


def generate(params: dict, seed: int, seconds: float, ctx: dict) -> dict:
    rng = np.random.default_rng([int(seed), 3])
    n, rows, L, B = int(params["n_batches"]), int(ctx["global_batch"]), int(params["seq_len"]), int(params["block_len"])
    if L % B:
        raise ValueError(f"seq_len={L} is not a whole number of blocks of block_len={B}")
    mask_id = int(ctx["vocab_size"]) - 1
    x0 = rng.integers(0, mask_id, size=(n, rows, L), dtype=np.int32)
    m = rng.integers(1, B + 1, size=(n, rows, L // B, 1))
    # a uniformly chosen subset of m of a block's positions: those whose rank in a random order is below m
    rank = np.argsort(np.argsort(rng.random((n, rows, L // B, B)), axis=-1), axis=-1)
    masked = (rank < m).reshape(n, rows, L)
    xt = np.where(masked, np.int32(mask_id), x0)
    ids = np.concatenate([xt, x0], axis=-1).astype(np.int32)
    return {"batches": [{"input_ids": ids[i]} for i in range(n)]}
