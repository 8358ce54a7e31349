"""Open-loop chat traffic: Poisson-like arrivals at a fixed rate, log-normal
prompt and output lengths, no shared prefix, unique seeded tokens.

Every run offers the SAME work. The number of requests is ``round(rate_rps *
seconds)``; prompt lengths, output lengths and the gaps between arrivals are
the evenly spaced quantiles of their distributions (a stratified sample of
size n), each set permuted independently. With ``order_seed`` in the params
the permutation is the mix's own and ``--seed`` draws only the tokens (and the
weights): a window at this server's rate holds tens of requests, not hundreds,
and which long prompt meets which burst of arrivals would otherwise decide a
tail more than any change to the program could. Without ``order_seed`` the
order follows ``--seed`` (the same work in another order).

params: ``rate_rps``; ``prompt`` and ``output``, each ``{"median", "p95",
"min", "max"}`` (log-normal through the median and the 95th percentile,
clipped); optional ``front_load_s``: every arrival falls due inside that many
seconds (the capacity run: offered load far above what the chip takes).
``salt`` changes the tokens only: the warm-up replays the same lengths and
arrivals on other tokens, so the window finds no prefix of it in the cache.
"""

import math
from statistics import NormalDist

import numpy as np

Z95 = NormalDist().inv_cdf(0.95)


def lognormal_quantiles(n: int, median: float, p95: float, lo: int, hi: int) -> np.ndarray:
    sigma = math.log(p95 / median) / Z95
    nd = NormalDist(math.log(median), sigma)
    q = [(i + 0.5) / n for i in range(n)]
    return np.clip(np.rint(np.exp([nd.inv_cdf(x) for x in q])), lo, hi).astype(np.int64)


def exponential_gaps(n: int, span: float) -> np.ndarray:
    """n gaps, the quantiles of an exponential, scaled to sum to ``span * n / (n + 1)``
    so that the last arrival stays inside the span."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (span * n / (n + 1)) / gaps.sum()


def generate(params: dict, seed: int, seconds: float, ctx: dict) -> dict:
    n = max(1, int(round(params["rate_rps"] * seconds)))
    span = float(params.get("front_load_s") or seconds)
    rng = np.random.default_rng([int(params.get("order_seed", seed)), 0])
    p, o = params["prompt"], params["output"]
    prompts = rng.permutation(lognormal_quantiles(n, p["median"], p["p95"], p["min"], p["max"]))
    outputs = rng.permutation(lognormal_quantiles(n, o["median"], o["p95"], o["min"], o["max"]))
    arrivals = np.cumsum(rng.permutation(exponential_gaps(n, span)))
    tok = np.random.default_rng([int(seed), 1 + int(ctx.get("salt", 0))])
    vocab = int(ctx["vocab_size"])
    return {"requests": [{"arrival_s": float(arrivals[i]),
                          "prompt": tok.integers(0, vocab, size=int(prompts[i])).tolist(),
                          "max_new_tokens": int(outputs[i])} for i in range(n)]}
