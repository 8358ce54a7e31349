"""The way into the v2 server: ``inference/v2/replay.py::_drive_sla`` with
``timing="recorded"``, the program's own arrival-driven loop, fed a session
built from the benchmark's generator. No step loop is written here.

The driver's only hook is a wrapper on the engine INSTANCE's ``_run_fused``
(the one call ``_drive_sla`` makes for each quantum when the fused step is on):
it stamps the quantum's start and end on the host clock, counts the tokens each
request was given, puts a ``bench/run_fused`` span into the profiler's trace,
and lets the tracer start and stop between quanta.
"""

import time

import numpy as np

from benchmarks.lib import reference, stats as stats_lib, weights
from benchmarks.lib.manifest import BENCH, load_module, published

COUNTERS = ("infer_dispatches_total", "sched_prefill_chunks_total", "sched_useful_tokens_total",
            "infer_decode_tokens_total", "infer_prefill_tokens_total", "infer_fused_quanta_total",
            "infer_decode_steps_total")


class QuantumLog:

    def __init__(self, eng):
        import jax

        self.quanta, self.want, self.got, self.active = [], {}, {}, set()
        self.origin = 0.0
        inner = eng._run_fused

        def run_fused(quantum, decode_carry, steps, defer, eos_token_id):
            t0 = time.perf_counter()
            n_dec, n_pre = len(quantum.decode_uids), len(quantum.prefills)
            pre_tokens = sum(len(p.tokens) for p in quantum.prefills)
            kind = "decode" if not n_pre else ("mixed" if n_dec else "prefill")
            live_before = len(self.active)
            for p in quantum.prefills:
                self.active.add(p.uid)
            with jax.profiler.TraceAnnotation("bench/run_fused", what=f"{kind} dec{n_dec} pre{n_pre}x{pre_tokens} steps{steps}",
                                              group=f"{kind} steps{steps}", live_before=live_before):
                rows = inner(quantum, decode_carry, steps, defer, eos_token_id)
            t1 = time.perf_counter()
            committed = 0
            for uid, row in rows.items():
                if row is None:
                    continue
                n = min(len(row), self.want.get(uid, 1 << 30) - self.got.get(uid, 0))
                self.got[uid] = self.got.get(uid, 0) + n
                committed += n
                if self.got[uid] >= self.want.get(uid, 1 << 30):
                    self.active.discard(uid)
            self.quanta.append({"t0": t0 - self.origin, "t1": t1 - self.origin, "kind": kind, "n_dec": n_dec,
                                "n_pre": n_pre, "prefill_tokens": pre_tokens, "steps": steps,
                                "committed": committed, "live_before": live_before})
            return rows

        eng._run_fused = run_fused

    def begin(self, requests):
        self.quanta, self.got, self.active = [], {}, set()
        self.want = {i: r["max_new_tokens"] for i, r in enumerate(requests)}
        self.origin = time.perf_counter()
        return self.origin

    def summary(self):
        """Where the host clock went: seconds inside ``_run_fused`` by kind of
        quantum, seconds between quanta with and without a request in flight."""
        out = {}
        for q in self.quanta:
            k = out.setdefault(q["kind"], {"n": 0, "in_call_s": 0.0, "steps": 0, "tokens": 0})
            k["n"] += 1
            k["in_call_s"] += q["t1"] - q["t0"]
            k["steps"] += q["steps"]
            k["tokens"] += q["committed"]
        gaps = [(b["t0"] - a["t1"], b["live_before"]) for a, b in zip(self.quanta, self.quanta[1:])]
        out["between_quanta_busy_s"] = sum(g for g, live in gaps if live)
        out["between_quanta_no_request_s"] = sum(g for g, live in gaps if not live)
        out["longest_gaps_s"] = sorted((round(g, 3) for g, _ in gaps), reverse=True)[:5]
        out["longest_calls"] = sorted(((round(q["t1"] - q["t0"], 3), q["kind"], q["n_dec"], q["n_pre"], q["prefill_tokens"],
                                        q["steps"]) for q in self.quanta), reverse=True)[:5]
        return out


def _session(requests):
    from deepspeed_tpu.telemetry.journal import Session

    s = Session({})
    for i, r in enumerate(requests):
        s.requests[i] = {"prompt": r["prompt"], "arrival_s": r["arrival_s"], "max_new_tokens": r["max_new_tokens"]}
    return s


def _counters():
    from deepspeed_tpu.telemetry import get_registry

    reg = get_registry()
    return {c: float(reg.peek(c) or 0.0) for c in COUNTERS}


def check_logits(eng, params, published, ref_cfg, spec, seed, vocab):
    """A seeded sample of prompts through ``put()`` and a few decode steps
    through the paged cache; every step's logits against the plain full
    forward over prompt + chosen tokens, by the float32-referenced rule: the
    truth is the plain reference in float32 (weights upcast layer by layer, so
    it fits beside the bf16 model), the yardstick the same reference in bf16."""
    import jax.numpy as jnp

    rng = np.random.default_rng([int(seed), 7])
    lo, hi = spec["prompt_len"]
    prompts = [rng.integers(0, vocab, size=int(n)).tolist() for n in rng.integers(lo, hi + 1, size=spec["n_prompts"])]
    uids = [900000 + i for i in range(len(prompts))]
    steps = int(spec["decode_steps"])
    t_start = time.perf_counter()
    ours = [np.asarray(eng.put(uids, prompts), np.float32)]
    chosen = [ours[0].argmax(-1)]
    for _ in range(steps):
        ours.append(np.asarray(eng.put(uids, [[int(t)] for t in chosen[-1]]), np.float32))
        chosen.append(ours[-1].argmax(-1))
    eng.flush(uids)
    t_engine = time.perf_counter()
    width = -(-(hi + steps) // 128) * 128
    ids = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        seq = p + [int(c[i]) for c in chosen[:steps]]
        ids[i, :len(seq)] = seq
    rows = np.arange(len(prompts))[:, None]
    cols = np.asarray([[len(p) - 1 + j for j in range(steps + 1)] for p in prompts])
    pick = lambda dtype: np.asarray(reference.decoder_logits(params, ids, published, ref_cfg["norm"], dtype)[rows, cols])
    truth, plain = pick(jnp.float32), pick(jnp.bfloat16)
    got = np.stack(ours, axis=1)  # (n, steps + 1, V)
    err_ours, err_plain, ok = reference.f32_rule(got, plain, truth)
    return {"ok": ok and bool(np.all(np.isfinite(got))), "engine_vs_f32": err_ours, "plain_bf16_vs_f32": err_plain,
            "rule": f"engine_vs_f32 <= {reference.F32_HEADROOM} x max(plain_bf16_vs_f32, 1e-3)",
            "positions": int(cols.size), "logit_scale": float(np.max(np.abs(truth))),
            "engine_s": t_engine - t_start, "reference_s": time.perf_counter() - t_engine}


def run(cell, opts):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.replay import _drive_sla

    cfg, traffic, say = cell["config"], cell["traffic"], opts["say"]
    model = weights.build_model(cfg)
    vocab = model.cfg.vocab_size
    say("program imported")
    params = weights.make_params(model, opts["seed"], jnp.bfloat16)
    jax.block_until_ready(params)
    say("weights made")
    eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig.from_dict(cfg["engine"]))
    if not opts["rehearse"] and eng._interpret is not False:
        raise RuntimeError("the engine chose interpret mode: its kernels are not compiled")
    if not eng._fused_enabled:
        raise RuntimeError("the fused step is off: _drive_sla would bypass the driver's hook")
    jax.block_until_ready((eng.params, eng.k_pages, eng.v_pages))
    t_built = time.perf_counter()
    say(f"engine built: {eng._n_kv_blocks} KV blocks")

    logits = check_logits(eng, params, published(cfg), cfg["reference"], cfg["correct"], opts["seed"], vocab)
    t_checked = time.perf_counter()
    say(f"logits checked: {logits}")

    tp = traffic["params"]
    gen = load_module(f"{BENCH}/generators/{traffic['generator']}.py")
    ctx = {"vocab_size": vocab}
    requests = gen.generate(tp, opts["seed"], opts["seconds"], ctx)["requests"]
    log = QuantumLog(eng)
    # warm-up by replay: the cell's own lengths and arrivals on other tokens, paced as
    # recorded, again until a pass compiles nothing (the first pass stalls on every new
    # program, so its backlog reaches other shapes than a run in step with its arrivals)
    warm_programs, warm_passes = None, []
    for n_pass in range(0 if tp.get("skip_warmup") else int(tp.get("warmup_passes_max", 3))):
        warm = gen.generate(tp, opts["seed"], opts["seconds"], dict(ctx, salt=1 + n_pass))["requests"]
        opts["compiles"].take()
        log.begin(warm)
        _drive_sla(eng, _session(warm), timing="recorded")
        warm_passes.append(opts["compiles"].take()[0])
        warm_programs = len(eng._fused_fns)
        say(f"warm-up pass {n_pass}: {warm_passes[-1]} compiles, {warm_programs} fused programs, "
            f"{len(log.quanta)} quanta in {log.quanta[-1]['t1']:.1f}s, cache {opts['cache_counts']()}")
        if warm_passes[-1] == 0:
            break
    t_warm = time.perf_counter()

    before = _counters()
    opts["tracer"].start()
    opts["compiles"].take()
    origin = log.begin(requests)
    setup_s = origin - opts["t0"]
    say(f"window starts: setup_s {setup_s:.1f}")
    with jax.profiler.TraceAnnotation("bench/window"):
        results, rstats = _drive_sla(eng, _session(requests), timing="recorded")
    window_end = time.perf_counter() - origin
    say(f"run ended {window_end:.1f}s after the window's start")
    compiles, compile_s = opts["compiles"].take()
    after = _counters()

    reqs = [{"due": s.arrival, "admitted": s.admitted, "first_token": s.first_token, "done": s.done,
             "n_new": len(results.get(s.uid, [])), "want": requests[s.uid]["max_new_tokens"],
             "prompt_len": s.prompt_len} for s in rstats]
    commits = [(q["t1"], q["committed"]) for q in log.quanta]
    summary = stats_lib.serve_summary(reqs, commits, opts["seconds"], tp["drain_s"])
    return {
        "kind": "serve", "correct": bool(logits["ok"] and summary["failed"] == 0),
        "attempted": summary["attempted"], "failed": summary["failed"],
        "end_to_end": {"ttft_p95_ms": summary["ttft_p95_ms"], "tpot_p95_ms": summary["tpot_p95_ms"],
                       "serve_tokens_per_s": summary["serve_tokens_per_s"], "setup_s": setup_s},
        "summary": summary, "requests": reqs, "quanta": log.quanta,
        "counters": {k: after[k] - before[k] for k in after}, "compiles_in_window": compiles,
        "compile_seconds_in_window": compile_s, "seconds": opts["seconds"],
        "extras": {
            "logits": logits, "kv_blocks": eng._n_kv_blocks, "fused_programs_after_warmup": warm_programs,
            "compiles_by_warmup_pass": warm_passes,
            "fused_programs_after_window": len(eng._fused_fns), "run_ended_s": window_end,
            "completed_rps_mid": stats_lib.completion_rate([r["done"] for r in reqs if r["done"] is not None]),
            "setup_split_s": {"build": t_built - opts["t0"], "check": t_checked - t_built, "warmup": t_warm - t_checked},
            "quanta": len(log.quanta), "host_clock": log.summary(), "ttft_p50_ms": summary["ttft_p50_ms"], "tpot_p50_ms": summary["tpot_p50_ms"],
            "n_ttft": summary["n_ttft"], "n_tpot": summary["n_tpot"], "tokens_total": summary["tokens_total"],
        },
    }
