#!/usr/bin/env python
"""Serving record/replay CLI (docs/OBSERVABILITY.md "Record & replay").

    python tools/replay.py smoke                       # record 8 requests, oracle-replay them
    python tools/replay.py oracle  JOURNAL [--session N]
    python tools/replay.py whatif  JOURNAL --set DS_TPU_SPEC_K=8 --set kv_quant_bits=8
    python tools/replay.py audit                       # double-run determinism diff

``oracle`` re-drives a fresh engine from a recorded journal and asserts
token-for-token digest equality (exit 1 on divergence, with the first
divergent request/quantum and its event-ring context). ``whatif``
replays the recorded arrival trace under overridden knobs and prints a
comparative TTFT/TPOT/goodput/dispatch table. ``smoke`` and ``audit``
are self-contained (synthetic tiny model) — the CI entry points.
"""

import argparse
import importlib.util
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))


def _perf_report():
    spec = importlib.util.spec_from_file_location(
        "perf_report_cli", os.path.join(_TOOLS_DIR, "perf_report.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _tiny_setup():
    """A seeded synthetic model + fused engine for smoke/audit — params
    derive from meta.param_seed, so the journal alone reproduces it."""
    import jax
    import numpy as np

    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.models.transformer import TransformerConfig
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2, RaggedBatchConfig,
                                            RaggedInferenceEngineConfig)

    cfg = TransformerConfig(vocab_size=128, n_layers=2, n_heads=4, n_kv_heads=2,
                            d_model=32, max_seq_len=128, norm="rmsnorm",
                            activation="swiglu", pos_emb="rope", tie_embeddings=False)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    ecfg = RaggedInferenceEngineConfig(
        state_manager=RaggedBatchConfig(kv_block_size=8, max_context=128, num_kv_blocks=64),
        dtype="float32")
    return lambda: InferenceEngineV2(model, params, ecfg)


def _smoke_spec():
    from deepspeed_tpu.inference.v2.sla import LoadSpec
    return LoadSpec(n_requests=8, arrival_rate=1e9, prompt_len_range=(4, 8),
                    max_new_tokens=8, vocab_size=128, seed=7)


def _load_session(path, index):
    from deepspeed_tpu.telemetry.journal import read_journal
    sessions = read_journal(path)
    if not sessions:
        raise SystemExit(f"replay: no sessions in {path}")
    try:
        return sessions[index]
    except IndexError:
        raise SystemExit(f"replay: session {index} out of range "
                         f"({len(sessions)} in {path})")


def _print_oracle(report) -> int:
    print(f"oracle: {report.n_requests} requests, {report.n_tokens} recorded tokens")
    if report.ok:
        print("oracle: PASS (digest-exact replay)")
        return 0
    d = report.first
    print(f"oracle: FAIL — {len(report.divergences)} divergent request(s)")
    print(f"  first divergence: uid={d.uid} token_pos={d.position} "
          f"recorded_quantum={d.quantum}")
    print(f"  recorded window: {d.recorded}")
    print(f"  replayed window: {d.replayed}")
    if d.events:
        print("  replay event-ring context:")
        for e in d.events:
            print(f"    {json.dumps(e, sort_keys=True, default=str)}")
    return 1


def cmd_smoke(args) -> int:
    from deepspeed_tpu.inference.v2.replay import build_engine_from_session, replay_oracle
    from deepspeed_tpu.inference.v2.sla import run_load
    from deepspeed_tpu.telemetry.journal import Journal, journal_override, read_journal

    outdir = args.dir or tempfile.mkdtemp(prefix="replay-smoke-")
    path = os.path.join(outdir, "smoke.jsonl")
    journal = Journal(path)
    journal.meta["param_seed"] = 0
    with journal_override(journal):
        run_load(_tiny_setup()(), _smoke_spec())
    journal.close()
    session = read_journal(path)[-1]
    report = replay_oracle(session, engine=build_engine_from_session(session))
    print(f"smoke: journal {path}")
    return _print_oracle(report)


def cmd_oracle(args) -> int:
    from deepspeed_tpu.inference.v2.replay import replay_oracle
    return _print_oracle(replay_oracle(_load_session(args.journal, args.session)))


def _parse_overrides(pairs):
    out = {}
    for pair in pairs or []:
        if "=" not in pair:
            raise SystemExit(f"replay: --set expects KEY=VALUE, got {pair!r}")
        key, value = pair.split("=", 1)
        out[key.strip()] = value.strip()
    return out


def cmd_whatif(args) -> int:
    from deepspeed_tpu.inference.v2.replay import replay_whatif

    session = _load_session(args.journal, args.session)
    report = replay_whatif(session, _parse_overrides(args.set),
                           timing=args.timing)
    pr = _perf_report()
    rows = [{"metric": r["metric"], "a": r["baseline"], "b": r["candidate"],
             "delta": r["delta"]} for r in report["rows"]]
    print(f"what-if: overrides {report['overrides']} (timing={report['timing']})")
    print(pr.render_compare(rows, label_a="recorded", label_b="what-if"))
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True, default=str))
    return 0


def cmd_audit(args) -> int:
    from deepspeed_tpu.inference.v2.replay import determinism_audit

    result = determinism_audit(_tiny_setup(), spec=_smoke_spec())
    print(json.dumps(result, indent=2, sort_keys=True, default=str))
    if result["deterministic"]:
        print("audit: PASS (two recordings, identical digest streams)")
        return 0
    print("audit: FAIL (host-side nondeterminism)")
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("smoke", help="record an 8-request fused run, oracle-replay it")
    sp.add_argument("--dir", default=None, help="journal directory (default: tmpdir)")
    sp.set_defaults(fn=cmd_smoke)

    sp = sub.add_parser("oracle", help="token-exact replay of a recorded journal")
    sp.add_argument("journal")
    sp.add_argument("--session", type=int, default=-1, help="session index (default: last)")
    sp.set_defaults(fn=cmd_oracle)

    sp = sub.add_parser("whatif", help="replay the trace under overridden knobs")
    sp.add_argument("journal")
    sp.add_argument("--session", type=int, default=-1)
    sp.add_argument("--set", action="append", metavar="KEY=VALUE",
                    help="override (engine config field or DS_TPU_* knob), repeatable")
    sp.add_argument("--timing", choices=("recorded", "logical"), default="recorded")
    sp.add_argument("--json", action="store_true", help="also dump the full report JSON")
    sp.set_defaults(fn=cmd_whatif)

    sp = sub.add_parser("audit", help="double-run determinism audit (synthetic workload)")
    sp.set_defaults(fn=cmd_audit)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
