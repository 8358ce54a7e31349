#!/usr/bin/env python
"""Replay-driven serving knob autotuner (docs/OBSERVABILITY.md
"Closing the loop").

    python tools/autotune_serve.py smoke                 # record tiny trace, tune, round-trip the profile
    python tools/autotune_serve.py tune JOURNAL --ttft-p99 0.5 --out auto
    python tools/autotune_serve.py tune JOURNAL --dim DS_TPU_SPEC_K=2,4,8 --mode grid
    python tools/autotune_serve.py show PROFILE.json

``tune`` searches the serving knob space over one recorded journal
session with successive halving: analytic cost-card pruning drops
padding-dominated configs before any replay, then ascending-budget
rounds (budget = number of trace requests replayed, what-if style via
``inference/v2/replay.py``) keep the top ``1/eta`` constraint-passing
survivors. Objective is goodput (PerfAccountant useful/slot tokens)
subject to a p99-TTFT constraint; the winner is written as a tuned
profile (``profiles/<device_kind>.json``) that engines pick up through
``DS_TPU_TUNED_PROFILE`` — explicit env knobs always shadow it.

``smoke`` is the self-contained CI entry point: record a tiny synthetic
trace, search a small neighborhood under a TTFT constraint, emit the
profile, reload an engine under it, and assert the tuned goodput
strictly beats the default knob vector.
"""

import argparse
import contextlib
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))


@contextlib.contextmanager
def _no_tuned_profile():
    """Search must score candidates from clean defaults: an installed
    tuned profile (or a DS_TPU_TUNED_PROFILE in the env) would leak the
    previous winner into every baseline and candidate engine."""
    from deepspeed_tpu.autotune.profile import maybe_load_tuned_profile
    saved = os.environ.pop("DS_TPU_TUNED_PROFILE", None)
    maybe_load_tuned_profile()  # knob now unset -> clears any overlay
    try:
        yield
    finally:
        if saved is not None:
            os.environ["DS_TPU_TUNED_PROFILE"] = saved


def _load_session(path, index):
    from deepspeed_tpu.telemetry.journal import read_journal
    sessions = read_journal(path)
    if not sessions:
        raise SystemExit(f"autotune: no sessions in {path}")
    try:
        return sessions[index]
    except IndexError:
        raise SystemExit(f"autotune: session {index} out of range "
                         f"({len(sessions)} in {path})")


def _print_leaderboard(out, constraint) -> None:
    res = out["result"]
    base = out["baseline"]
    print(f"autotune: {len(res.trials)} trials, {len(res.rejected)} rejected, "
          f"{out['n_pruned']} pruned analytically, "
          f"budget spent {out['budget_spent']} replayed requests")
    for rnd in res.rounds:
        print(f"  round budget={rnd['budget']}: {rnd['n_in']} in -> "
              f"{rnd['n_out']} survivors ({rnd['n_rejected']} rejected)")
    if constraint:
        print(f"  constraint: {constraint}")
    print(f"  baseline (default knobs): objective={base['objective']:.4f} "
          f"goodput={base['goodput_fraction']:.4f}")
    print("  leaderboard (final round):")
    for t in res.leaderboard[:8]:
        mark = "ok " if t.constraint_ok else "REJ"
        obj = "-" if t.objective is None else f"{t.objective:.4f}"
        print(f"    [{mark}] obj={obj} budget={t.budget} {t.key or '<defaults>'}")
    if res.winner is None:
        print("  winner: NONE (every config violated the constraint)")
    else:
        wt = res.winner_trial
        print(f"  winner: {res.winner or '<defaults>'}")
        print(f"    objective={wt.objective:.4f} vs baseline "
              f"{base['objective']:.4f} "
              f"({'+' if wt.objective >= base['objective'] else ''}"
              f"{(wt.objective - base['objective']):.4f})")


def _save(profile, out_spec):
    from deepspeed_tpu.autotune.profile import profile_path_for, save_profile
    path = profile_path_for() if out_spec == "auto" else out_spec
    save_profile(profile, path)
    print(f"autotune: tuned profile -> {path} "
          f"(provenance {profile.provenance_hash()})")
    return path


def cmd_tune(args) -> int:
    from deepspeed_tpu.autotune import autotune_session
    from deepspeed_tpu.autotune.space import DEFAULT_SPACE, grid, neighborhood, parse_dim

    session = _load_session(args.journal, args.session)
    dims = tuple(parse_dim(s) for s in args.dim) if args.dim else DEFAULT_SPACE
    configs = grid(dims) if args.mode == "grid" else neighborhood(dims)
    budgets = [int(b) for b in args.budgets.split(",")] if args.budgets else None
    constraint = {"ttft_p99_s": args.ttft_p99} if args.ttft_p99 else None
    with _no_tuned_profile():
        out = autotune_session(session, dims=dims, configs=configs,
                               budgets=budgets, eta=args.eta,
                               objective=args.objective,
                               constraint=constraint, timing=args.timing,
                               prune=not args.no_prune)
    _print_leaderboard(out, constraint)
    if args.json:
        res = out["result"]
        print(json.dumps({
            "winner": res.winner, "budget_spent": out["budget_spent"],
            "rounds": res.rounds, "n_pruned": out["n_pruned"],
            "baseline_objective": out["baseline"]["objective"],
            "winner_objective": (res.winner_trial.objective
                                 if res.winner_trial else None),
        }, indent=2, sort_keys=True, default=str))
    if out["profile"] is None:
        return 1
    if args.out:
        _save(out["profile"], args.out)
    return 0


def cmd_show(args) -> int:
    from deepspeed_tpu.autotune.profile import load_profile
    profile = load_profile(args.profile)
    print(json.dumps(profile.to_dict(), indent=2, sort_keys=True))
    print(f"provenance: {profile.provenance_hash()}")
    return 0


def _smoke_record(outdir):
    """Tiny seeded trace whose decode batch (3 rows) leaves real padding
    headroom — the search has a deterministic knob worth finding."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "replay_cli", os.path.join(_TOOLS_DIR, "replay.py"))
    rmod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = rmod
    spec.loader.exec_module(rmod)

    from deepspeed_tpu.inference.v2.sla import LoadSpec, run_load
    from deepspeed_tpu.telemetry.journal import Journal, journal_override, read_journal

    path = os.path.join(outdir, "autotune-smoke.jsonl")
    journal = Journal(path)
    journal.meta["param_seed"] = 0
    load = LoadSpec(n_requests=3, arrival_rate=1e9, prompt_len_range=(4, 8),
                    max_new_tokens=8, vocab_size=128, seed=7)
    with journal_override(journal):
        run_load(rmod._tiny_setup()(), load)
    journal.close()
    return path, read_journal(path)[-1]


def cmd_smoke(args) -> int:
    from deepspeed_tpu.autotune import autotune_session
    from deepspeed_tpu.autotune.profile import load_profile, maybe_load_tuned_profile
    from deepspeed_tpu.analysis import knobs

    outdir = args.dir or tempfile.mkdtemp(prefix="autotune-smoke-")
    path, session = _smoke_record(outdir)
    print(f"smoke: journal {path} ({len(session.requests)} requests, "
          f"{len(session.quanta)} quanta)")

    configs = [{}, {"DS_TPU_MIN_DECODE_BUCKET": "1"},
               {"DS_TPU_MIN_DECODE_BUCKET": "4"},
               {"DS_TPU_SPEC_K": "4", "DS_TPU_MIN_DECODE_BUCKET": "1"}]
    constraint = {"ttft_p99_s": 60.0}  # generous: CPU wall time is noisy
    with _no_tuned_profile():
        out = autotune_session(session, configs=configs,
                               budgets=[2, len(session.requests)],
                               constraint=constraint)
    _print_leaderboard(out, constraint)
    profile = out["profile"]
    if profile is None:
        print("smoke: FAIL — no constraint-passing winner")
        return 1
    if profile.score <= profile.baseline_score:
        print("smoke: FAIL — tuned objective does not beat default knobs")
        return 1

    profile_path = _save(profile, os.path.join(outdir, "tuned-profile.json"))
    # round-trip: a fresh engine under DS_TPU_TUNED_PROFILE must resolve
    # the winner's knob vector (and /varz must attribute it to the profile)
    with _no_tuned_profile():
        pass  # drop any overlay before installing ours
    os.environ["DS_TPU_TUNED_PROFILE"] = profile_path
    try:
        loaded = maybe_load_tuned_profile(force=True)
        assert loaded is not None and loaded.knobs == profile.knobs
        for name in profile.knobs:
            got, prov = knobs.get_str(name), knobs.provenance(name)
            if got != profile.knobs[name] or prov != "profile":
                print(f"smoke: FAIL — {name}={got!r} provenance={prov!r}")
                return 1
        reread = load_profile(profile_path)
        if reread.provenance_hash() != profile.provenance_hash():
            print("smoke: FAIL — provenance hash did not round-trip")
            return 1
    finally:
        os.environ.pop("DS_TPU_TUNED_PROFILE", None)
        maybe_load_tuned_profile()
    print(f"smoke: PASS (tuned {profile.score:.4f} > default "
          f"{profile.baseline_score:.4f}; profile round-trips)")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="autotune_serve",
                                     description=__doc__.split("\n\n")[0])
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("smoke", help="self-contained record->tune->round-trip check")
    p.add_argument("--dir", help="work dir (default: fresh temp dir)")
    p.set_defaults(fn=cmd_smoke)

    p = sub.add_parser("tune", help="search the knob space on a recorded journal")
    p.add_argument("journal")
    p.add_argument("--session", type=int, default=-1)
    p.add_argument("--dim", action="append", metavar="KNOB=V1,V2",
                   help="override the search space (repeatable)")
    p.add_argument("--mode", choices=("neighborhood", "grid"),
                   default="neighborhood")
    p.add_argument("--budgets", metavar="N1,N2",
                   help="ascending per-round request budgets")
    p.add_argument("--eta", type=int, default=2)
    p.add_argument("--objective", choices=("goodput", "goodput_tps"),
                   default="goodput")
    p.add_argument("--ttft-p99", type=float, default=None,
                   help="reject configs whose replayed p99 TTFT exceeds this")
    p.add_argument("--timing", choices=("logical", "recorded"),
                   default="logical")
    p.add_argument("--no-prune", action="store_true",
                   help="skip analytic cost-card pruning")
    p.add_argument("--out", metavar="PATH|auto",
                   help="write the winner's tuned profile ('auto' -> "
                        "profiles/<device_kind>.json)")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_tune)

    p = sub.add_parser("show", help="print a tuned profile + provenance hash")
    p.add_argument("profile")
    p.set_defaults(fn=cmd_show)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
