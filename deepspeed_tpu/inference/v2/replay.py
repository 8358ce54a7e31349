"""Replay harness for recorded serving sessions (telemetry/journal.py).

Three consumers of one journal:

- :func:`replay_oracle` — re-drive a fresh engine from the recorded
  arrivals and assert token-for-token digest equality against the
  recorded commit stream; on divergence, report the first divergent
  request/quantum with its surrounding event-ring context. This is the
  parity oracle the async-EngineCore refactor (ROADMAP) will be held to.
- :func:`replay_whatif` — replay the same arrival trace under
  overridden knobs/config (spec K, KV quant bits, spill watermark,
  scheduler budgets) and emit a comparative TTFT/TPOT/goodput/dispatch
  report: every incident capture doubles as an offline tuning benchmark
  (the DeepSpeed autotuner's re-evaluate-on-real-workload trick).
- :func:`determinism_audit` — record the same workload twice and diff
  the digest streams, catching host-side nondeterminism regressions.

Why replay is exact: serving is greedy during SLA runs and the decode
math is per-row (paged attention reads only a row's own KV), so
committed tokens do not depend on batch composition or admission
timing; sampled ``generate`` runs re-derive the identical rng stream
from the recorded seed because the loops consume it in dispatch order.
The digest chain (journal.roll_digest) therefore re-converges token for
token — anything that breaks that is a real behavioral change, which is
exactly what the oracle exists to catch.
"""

import contextlib
import dataclasses
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from ...telemetry import get_registry as get_telemetry_registry
from ...telemetry import span as telemetry_span
from ...telemetry.events import get_event_log
from ...telemetry.journal import Session, journal_override
from .scheduler import RaggedRequest
from .sla import RequestStat, summarize

# journal-knob name -> engine config field, for what-if overrides given
# in env-knob spelling (the spelling an operator already knows)
_KNOB_TO_FIELD = {
    "DS_TPU_SPEC_K": "spec_k",
    "DS_TPU_SPEC_DECODE": "spec_decode",
    "DS_TPU_SERVE_FUSED": "fused_step",
    "DS_TPU_KV_QUANT": "kv_quant_bits",
    "DS_TPU_KV_SPILL": "kv_spill",
    "DS_TPU_PREFIX_CACHE": "enable_prefix_cache",
    "DS_TPU_DECODE_BURST": "decode_burst",
    "DS_TPU_MIN_DECODE_BUCKET": "min_decode_bucket",
    "DS_TPU_TP": "tensor_parallel",
}
# engine-dict keys that live on RaggedBatchConfig, not the engine config
_STATE_FIELDS = ("max_ragged_batch_size", "max_ragged_sequence_count",
                 "num_kv_blocks", "kv_block_size", "max_context")
_BOOL_FIELDS = ("spec_decode", "fused_step", "kv_spill", "enable_prefix_cache")


def _coerce(value):
    """Parse CLI-style string override values ("true", "2", "0.5") into
    the types the config dataclasses expect; non-strings pass through."""
    if not isinstance(value, str):
        return value
    low = value.strip().lower()
    if low in ("true", "false"):
        return low == "true"
    for cast in (int, float):
        try:
            return cast(value)
        except ValueError:
            pass
    return value


@dataclass
class Divergence:
    uid: int
    position: int          # first divergent token index within the request
    quantum: Optional[int]  # recorded quantum that committed that token
    recorded: List[int]
    replayed: List[int]
    events: List[Dict] = field(default_factory=list)  # replay-side event-ring context


@dataclass
class OracleReport:
    ok: bool
    n_requests: int
    n_tokens: int
    digests_match: bool
    divergences: List[Divergence] = field(default_factory=list)

    @property
    def first(self) -> Optional[Divergence]:
        return self.divergences[0] if self.divergences else None


@contextlib.contextmanager
def _env_overrides(env: Dict[str, str]):
    """Scoped os.environ writes for knob-spelled what-if overrides that
    have no engine-config field (spill watermark, host pool size, ...)."""
    saved = {}
    for name, value in env.items():
        saved[name] = os.environ.get(name)
        os.environ[name] = str(value)
    try:
        yield
    finally:
        for name, prev in saved.items():
            if prev is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = prev


def build_engine_from_session(session: Session, overrides: Optional[Dict] = None,
                              model=None, params=None):
    """Rebuild an engine from a session header's fingerprint.

    ``model``/``params`` short-circuit model construction (replaying a
    real checkpoint); otherwise the model is rebuilt from the recorded
    ``model_cfg`` and params are re-derived from ``meta.param_seed``
    (synthetic workloads — the replay smoke records that seed precisely
    so the journal alone reproduces the session).
    """
    import jax
    import numpy as np

    from ...models import CausalLM
    from ...models.transformer import TransformerConfig
    from .engine_v2 import InferenceEngineV2, RaggedInferenceEngineConfig
    from .ragged.manager import RaggedBatchConfig

    overrides = dict(overrides or {})
    header = session.header
    eng = dict(header.get("engine", {}))

    # split the overrides: engine-config fields (possibly knob-spelled),
    # state-manager fields, and residual DS_TPU_* env knobs
    env: Dict[str, str] = {}
    for key in list(overrides):
        name = _KNOB_TO_FIELD.get(key, key)
        if name in _STATE_FIELDS or name in {f.name for f in dataclasses.fields(RaggedInferenceEngineConfig)}:
            if name != key:
                overrides[name] = overrides.pop(key)
        elif key.startswith("DS_TPU_"):
            env[key] = str(overrides.pop(key))
    eng.update({k: _coerce(v) for k, v in overrides.items()})
    for name in _BOOL_FIELDS:
        if eng.get(name) is not None:
            eng[name] = bool(eng[name])

    if model is None:
        mc = dict(header.get("model_cfg", {}))
        mc.pop("dtype", None)  # run dtype is the engine's to choose
        names = {f.name for f in dataclasses.fields(TransformerConfig)}
        mc = {k: v for k, v in mc.items() if k in names}
        if mc.get("window_layers") is not None:
            mc["window_layers"] = tuple(mc["window_layers"])
        model = CausalLM(TransformerConfig(**mc))
    if params is None:
        seed = int((header.get("meta") or {}).get("param_seed", 0))
        params = model.init(jax.random.PRNGKey(seed),
                            {"input_ids": np.zeros((1, 8), np.int32)})

    smc = RaggedBatchConfig(
        max_ragged_batch_size=int(eng.get("max_ragged_batch_size", 768)),
        max_ragged_sequence_count=int(eng.get("max_ragged_sequence_count", 512)),
        max_context=int(eng.get("max_context", 8192)),
        kv_block_size=int(eng.get("kv_block_size", 128)),
        num_kv_blocks=eng.get("num_kv_blocks"))
    cfg = RaggedInferenceEngineConfig(
        state_manager=smc,
        dtype=str(eng.get("dtype", "bfloat16")),
        fused_step=eng.get("fused_step"),
        spec_decode=eng.get("spec_decode"),
        spec_k=eng.get("spec_k"),
        spec_drafter=str(eng.get("spec_drafter", "prompt_lookup")),
        decode_burst=(None if eng.get("decode_burst") is None
                      else int(eng["decode_burst"])),
        min_decode_bucket=(None if eng.get("min_decode_bucket") is None
                           else int(eng["min_decode_bucket"])),
        quant_bits=int(eng.get("quant_bits", 0)),
        kv_quant_bits=eng.get("kv_quant_bits"),
        kv_spill=eng.get("kv_spill"),
        enable_prefix_cache=eng.get("enable_prefix_cache"),
        tensor_parallel=int(eng.get("tensor_parallel", 1)))
    # topology gate: a journal recorded under TP must be replayed on a
    # topology that can realize the SAME sharding — a silently different
    # mesh would diverge token streams with no fingerprint to blame
    tp = int(cfg.tensor_parallel)
    n_dev = jax.device_count()
    if tp > 1 and (n_dev < tp or n_dev % tp):
        raise RuntimeError(
            f"journal recorded tensor_parallel={tp} (mesh {eng.get('mesh', '?')}) but "
            f"{n_dev} local device(s) are available — refusing to replay on a "
            f"mismatched topology. On CPU, force host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp}.")
    with _env_overrides(env):
        engine = InferenceEngineV2(model, params, cfg)
    want_sig = eng.get("shard_sig")
    topo_overridden = ("tensor_parallel" in overrides or "DS_TPU_TP" in env
                       or "DS_TPU_TP_ALLREDUCE_BITS" in env)
    if want_sig and not topo_overridden and engine._shard_sig != want_sig:
        raise RuntimeError(
            f"rebuilt engine sharding {engine._shard_sig!r} != recorded "
            f"{want_sig!r} — the replay topology does not reproduce the "
            f"recorded mesh/allreduce layout")
    return engine


def _drive_sla(engine, session: Session, timing: str = "logical",
               eos_token_id: Optional[int] = None
               ) -> Tuple[Dict[int, List[int]], List[RequestStat]]:
    """Re-drive an engine with a session's recorded arrival trace.

    Mirrors ``sla.run_load``'s loop (spec -> fused -> burst -> unfused
    step order) but admits the RECORDED requests instead of sampling a
    workload. ``timing="logical"`` re-admits each request once the
    scheduler's quantum clock passes its recorded admission quantum —
    deterministic, wall-clock-free, the oracle's mode. ``timing=
    "recorded"`` paces admissions by the recorded arrival seconds so
    latency percentiles are comparable — the what-if mode.

    Each turn of the loop is a ``serve/admit``, ``serve/schedule`` and
    ``serve/commit`` span around the engine's ``infer/fused_step``, or a
    ``serve/idle_wait``, all with the id ``q`` of the quantum the turn
    assembles; each request leaves ``enqueue`` (stamped with its recorded
    arrival when that paces the run), ``first_token`` and ``finish`` in
    the event log, as ``sla.run_load`` does.
    """
    if timing not in ("logical", "recorded"):
        raise ValueError(f"timing must be 'logical' or 'recorded', got {timing!r}")
    order = sorted(session.requests, key=lambda u: (
        float(session.requests[u].get("arrival_s", 0.0)), int(u)))
    recs = session.requests
    if eos_token_id is None:
        eos_token_id = (session.header.get("run") or {}).get("eos_token_id")

    stats = {u: RequestStat(uid=u, prompt_len=len(recs[u]["prompt"]),
                            arrival=float(recs[u].get("arrival_s", 0.0)))
             for u in order}
    reqs: Dict[int, RaggedRequest] = {}
    pending: List[RaggedRequest] = []
    decode_ready: Dict[int, int] = {}
    results: Dict[int, List[int]] = {}
    next_i = 0
    engine._sampling = None
    events = get_event_log()
    t0 = time.perf_counter()

    def now() -> float:
        return time.perf_counter() - t0

    def due(i: int) -> bool:
        if i >= len(order):
            return False
        if timing == "logical":
            return int(recs[order[i]].get("arrival_q", 0)) <= engine.scheduler.last_quantum_id
        return float(recs[order[i]].get("arrival_s", 0.0)) <= now()

    def admit(force: bool = False) -> None:
        nonlocal next_i
        while next_i < len(order) and (force or due(next_i)):
            uid = order[next_i]
            reqs[uid] = RaggedRequest(uid=uid, tokens=list(recs[uid]["prompt"]),
                                      max_new_tokens=int(recs[uid].get("max_new_tokens", 0)) or 1 << 30)
            stats[uid].admitted = now()
            results[uid] = []
            pending.append(reqs[uid])
            events.emit("enqueue", uid, prompt=len(recs[uid]["prompt"]),
                        ts=t0 + stats[uid].arrival if timing == "recorded" else None)
            next_i += 1
            force = False  # force admits exactly one (the idle un-sticker)

    def commit(uid: int, toks_out: List[int]) -> None:
        req = reqs[uid]
        toks_out = list(toks_out)[:req.max_new_tokens - len(results[uid])]
        if not toks_out:
            return
        if eos_token_id is not None and eos_token_id in toks_out:
            toks_out = toks_out[:toks_out.index(eos_token_id) + 1]
        t = now()
        if not results[uid]:
            stats[uid].first_token = t
            events.emit("first_token", uid, ts=t0 + t)
        results[uid].extend(toks_out)
        stats[uid].n_new = len(results[uid])
        finished = (len(results[uid]) >= req.max_new_tokens or
                    (eos_token_id is not None and toks_out[-1] == eos_token_id))
        if finished:
            req.done = True
            stats[uid].done = t
            events.emit("finish", uid, ts=t0 + t, n_new=stats[uid].n_new)
            engine.flush([uid])
        else:
            decode_ready[uid] = toks_out[-1]

    prompts = {u: list(recs[u]["prompt"]) for u in order}
    fused = bool(getattr(engine, "_fused_enabled", False))
    spec_on = bool(getattr(engine, "_spec_enabled", False))

    while next_i < len(order) or pending or decode_ready:
        q = engine.scheduler.last_quantum_id + 1  # the quantum this turn assembles
        with telemetry_span("serve/admit", q=q):
            admit()
        if not pending and not decode_ready:
            if timing == "recorded":
                with telemetry_span("serve/idle_wait", q=q):
                    time.sleep(max(0.0, float(recs[order[next_i]].get("arrival_s", 0.0)) - now()))
                continue
            admit(force=True)  # logical clock can't advance while idle
            continue
        arrivals_due = due(next_i)
        if spec_on and not pending and not arrivals_due and decode_ready:
            sp_uids = list(decode_ready)
            rows = engine._run_spec_step(
                sp_uids, [decode_ready[u] for u in sp_uids],
                [prompts[u] + results[u] for u in sp_uids],
                [reqs[u].max_new_tokens - len(results[u]) for u in sp_uids])
            if rows is not None:
                for uid, toks_row in rows.items():
                    decode_ready.pop(uid)
                    commit(uid, toks_row)
                continue
        if fused:
            with telemetry_span("serve/schedule", q=q, queue=len(pending), decodes=len(decode_ready)) as sp:
                quantum = engine.scheduler.schedule_fused([r for r in pending if r.remaining_prefill],
                                                          list(decode_ready))
                q = engine.scheduler.last_quantum_id  # the id it claimed (a spec step may have claimed one before)
                sp.set(q=q, rows=quantum.n_rows, tokens=quantum.total_tokens)
            if quantum.empty:
                raise RuntimeError("scheduler deadlock: no work schedulable (KV pool too small?)")
            for pf in quantum.prefills:
                reqs[pf.uid].tokens = reqs[pf.uid].tokens[len(pf.tokens):]
            steps = 1
            if quantum.decode_uids and not quantum.prefills and not pending and not arrivals_due:
                rem = min(reqs[u].max_new_tokens - len(results[u]) for u in quantum.decode_uids)
                steps = max(1, engine._burst_steps({u: True for u in quantum.decode_uids}, rem))
            carry = [decode_ready.pop(u) for u in quantum.decode_uids]
            rows = engine._run_fused(quantum, carry, steps, False, eos_token_id)
            with telemetry_span("serve/commit", q=q):
                for uid, row in rows.items():
                    if row is not None:
                        commit(uid, row.tolist())
                pending = [r for r in pending if not r.done and r.remaining_prefill]
            continue
        if not pending and not arrivals_due and decode_ready:
            cap = min(engine.scheduler.max_sequences, engine.scheduler.max_batch_tokens)
            burst_uids = list(decode_ready)[:cap]
            rem = min(reqs[u].max_new_tokens - len(results[u]) for u in burst_uids)
            k = engine._burst_steps({u: decode_ready[u] for u in burst_uids}, rem)
            if k >= 2:
                toks = [decode_ready.pop(u) for u in burst_uids]
                out = engine._run_decode_burst(burst_uids, toks, k)
                for uid, row in zip(burst_uids, out):
                    commit(uid, row.tolist())
                continue
        step = engine.scheduler.schedule([r for r in pending if r.remaining_prefill],
                                         list(decode_ready))
        if step.empty:
            raise RuntimeError("scheduler deadlock: no work schedulable (KV pool too small?)")
        uids, toks = [], []
        for uid in step.decode_uids:
            uids.append(uid)
            toks.append([decode_ready.pop(uid)])
        for pf in step.prefills:
            req = reqs[pf.uid]
            uids.append(pf.uid)
            toks.append(pf.tokens)
            req.tokens = req.tokens[len(pf.tokens):]
        nxt = engine.put(uids, toks, return_tokens=True)
        for uid, tok in zip(uids, nxt):
            if reqs[uid].remaining_prefill:
                continue
            commit(uid, [int(tok)])
        pending = [r for r in pending if not r.done and r.remaining_prefill]

    for uid, toks in results.items():
        stats[uid].tokens = toks
    return results, [stats[u] for u in order]


def replay_tokens(session: Session, engine) -> Dict[int, List[int]]:
    """Re-drive ``engine`` from ``session`` and return uid -> tokens.

    ``generate`` sessions re-run ``engine.generate`` with the recorded
    arguments (the recorded seed re-derives the identical rng stream, so
    even sampled runs replay exactly); ``sla`` sessions re-drive the
    recorded arrival trace on the logical quantum clock. Recording is
    muted for the duration — a replay must never journal over itself.
    """
    with journal_override(None):
        if session.kind == "generate":
            run = dict(session.header.get("run") or {})
            prompts = [session.requests[u]["prompt"] for u in sorted(session.requests)]
            out = engine.generate(
                prompts,
                max_new_tokens=int(run.get("max_new_tokens", 32)),
                eos_token_id=run.get("eos_token_id"),
                do_sample=bool(run.get("do_sample", False)),
                temperature=float(run.get("temperature", 1.0)),
                top_k=int(run.get("top_k", 0)),
                top_p=float(run.get("top_p", 1.0)),
                seed=int(run.get("seed", 0)))
            return {u: out[i] for i, u in enumerate(sorted(session.requests))}
        results, _ = _drive_sla(engine, session, timing="logical")
        return results


def replay_oracle(session: Session, engine=None,
                  engine_factory: Optional[Callable] = None,
                  context_events: int = 16) -> OracleReport:
    """Token-exact replay check: re-drive a fresh engine and compare the
    committed streams against the recorded ones, digest for digest."""
    if engine is None:
        engine = (engine_factory or (lambda: build_engine_from_session(session)))()
    recorded = session.tokens_by_uid()
    replayed = replay_tokens(session, engine)
    m_div = get_telemetry_registry().counter("replay_divergences_total")
    events = get_event_log()

    divergences: List[Divergence] = []
    for uid in sorted(recorded):
        rec, rep = recorded[uid], replayed.get(uid, [])
        if rec == rep:
            continue
        pos = next((i for i, (a, b) in enumerate(zip(rec, rep)) if a != b),
                   min(len(rec), len(rep)))
        ctx = [dict(e) for e in events.events(uid=uid)[-context_events:]]
        divergences.append(Divergence(
            uid=uid, position=pos, quantum=session.quantum_of_commit(uid, pos),
            recorded=rec[max(0, pos - 4):pos + 4], replayed=rep[max(0, pos - 4):pos + 4],
            events=ctx))
        m_div.inc()
    divergences.sort(key=lambda d: (d.quantum if d.quantum is not None else 1 << 30, d.uid))
    return OracleReport(ok=not divergences, n_requests=len(recorded),
                        n_tokens=sum(len(t) for t in recorded.values()),
                        digests_match=not divergences, divergences=divergences)


def replay_whatif(session: Session, overrides: Dict,
                  engine_factory: Optional[Callable] = None,
                  timing: str = "recorded") -> Dict:
    """Replay the recorded arrival trace under overridden knobs and emit
    a comparative report against the session's recorded baseline."""
    factory = engine_factory or (lambda ov: build_engine_from_session(session, overrides=ov))
    engine = factory(overrides)
    tele = get_telemetry_registry()
    d0 = tele.peek("infer_dispatches_total") or 0.0
    t0 = time.perf_counter()
    _, stats = _drive_sla(engine, session, timing=timing)
    wall = time.perf_counter() - t0
    d1 = tele.peek("infer_dispatches_total") or 0.0

    candidate = summarize(stats) if any(s.done is not None for s in stats) else {}
    candidate["dispatches"] = d1 - d0
    candidate["wall_s"] = round(wall, 4)
    acct = getattr(engine, "_acct", None)
    if acct is not None and acct.enabled:
        candidate["acct_totals"] = dict(acct.totals())
        candidate["hbm"] = dict(acct.hbm())

    end = session.end or {}
    baseline = dict((end.get("summary") or {}).get("sla") or {})
    baseline["dispatches"] = (end.get("summary") or {}).get("dispatches")
    baseline["wall_s"] = end.get("wall_s")

    keys = ("tokens_per_sec", "requests_per_sec", "ttft_p50_s", "ttft_p95_s",
            "ttft_p99_s", "tpot_p50_s", "tpot_p95_s", "sla_miss_frac",
            "dispatches", "wall_s")
    rows = []
    for key in keys:
        b, c = baseline.get(key), candidate.get(key)
        delta = round(c - b, 4) if isinstance(b, (int, float)) and isinstance(c, (int, float)) else None
        rows.append({"metric": key, "baseline": b, "candidate": c, "delta": delta})
    return {"overrides": dict(overrides), "timing": timing,
            "baseline": baseline, "candidate": candidate, "rows": rows}


def determinism_audit(engine_factory: Callable, drive: Optional[Callable] = None,
                      spec=None) -> Dict:
    """Record the same workload twice on fresh engines and diff the
    digest streams — the CI tripwire for host-side nondeterminism
    (unordered dict walks, stray wall-clock branches, rng misuse).

    ``drive(engine)`` runs the workload (defaults to ``sla.run_load``
    with ``spec``); each run records into its own in-memory journal.
    """
    from ...telemetry.journal import Journal, sessions_from_records
    from .sla import run_load

    if drive is None:
        if spec is None:
            raise ValueError("determinism_audit needs a drive callable or a LoadSpec")
        drive = lambda eng: run_load(eng, spec)

    runs = []
    for _ in range(2):
        j = Journal()  # memory mode
        with journal_override(j):
            drive(engine_factory())
        runs.append(sessions_from_records(j.records)[-1])

    a, b = runs
    da, db = a.digests(), b.digests()
    mismatches = sorted(u for u in set(da) | set(db) if da.get(u) != db.get(u))
    qa = [q.get("digest") for q in a.quanta]
    qb = [q.get("digest") for q in b.quanta]
    if mismatches:
        get_telemetry_registry().counter("replay_divergences_total").inc(len(mismatches))
    return {"deterministic": not mismatches and qa == qb,
            "n_requests": len(da),
            "request_mismatches": mismatches,
            "quanta_equal": qa == qb,
            "n_quanta": (len(qa), len(qb))}
