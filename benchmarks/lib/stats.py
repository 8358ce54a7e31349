"""Percentile and window arithmetic of the benchmark (the yardstick's own copy;
``inference/v2/sla.summarize`` runs its rate over the drain, this does not)."""

import math
from typing import Dict, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    closest ranks, numpy's default. None for no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def serve_summary(requests: List[Dict], commits: List[Tuple[float, int]], seconds: float,
                  drain_s: float) -> Dict:
    """Reduce one open-loop serving run to the end-to-end numbers.

    ``requests``: one dict per request offered in the window, with ``due``
    (scheduled arrival, seconds from the window's start), ``admitted``,
    ``first_token``, ``done`` (same clock, None where it never happened),
    ``n_new`` (tokens returned) and ``want`` (tokens asked). ``commits``:
    (time, tokens) per commit of output tokens. A request that is not done
    ``drain_s`` after the window's end, or returned another number of tokens
    than asked, is failed: its TTFT and TPOT are censored at the drain limit,
    so it sits in the tail and never improves a percentile.
    """
    limit = seconds + drain_s
    ttft, tpot, late, failed = [], [], [], 0
    for r in requests:
        done = r.get("done")
        ok = done is not None and done <= limit and r["n_new"] == r["want"]
        failed += not ok
        first = r.get("first_token")
        if first is None or first > limit:
            first = limit
        ttft.append(first - r["due"])
        if r["want"] > 1:
            tpot.append((done - first) / (r["want"] - 1) if ok else limit - r["due"])
        if r.get("admitted") is not None:
            late.append(r["admitted"] - r["due"])
    in_window = sum(n for t, n in commits if t <= seconds)
    ms = lambda v: None if v is None else v * 1000.0
    return {
        "attempted": len(requests), "failed": failed,
        "ttft_p50_ms": ms(percentile(ttft, 50)), "ttft_p95_ms": ms(percentile(ttft, 95)),
        "tpot_p50_ms": ms(percentile(tpot, 50)), "tpot_p95_ms": ms(percentile(tpot, 95)),
        "gen_late_p95_ms": ms(percentile(late, 95)),
        "serve_tokens_per_s": in_window / seconds,
        "tokens_in_window": in_window, "tokens_total": sum(n for _, n in commits),
        "n_ttft": len(ttft), "n_tpot": len(tpot),
    }


def completion_rate(done_times: Sequence[float], lo_share: float = 0.25, hi_share: float = 0.75) -> Optional[float]:
    """Completed requests per second over the middle stretch of a run: between
    ``lo_share`` and ``hi_share`` of the time of the last completion. What the
    capacity run reads (no latency limit defines capacity)."""
    xs = sorted(done_times)
    if len(xs) < 8:
        return None
    t0, t1 = xs[-1] * lo_share, xs[-1] * hi_share
    return sum(1 for t in xs if t0 < t <= t1) / (t1 - t0)
