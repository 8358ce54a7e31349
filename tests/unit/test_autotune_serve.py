"""Closed-loop autotune subsystem (deepspeed_tpu/autotune/ + tools/).

Covers the ISSUE-16 acceptance bars: knob-overlay precedence
(env > profile > default) with per-knob provenance, successive halving
against a fake deterministic evaluator (budget accounting, constraint
rejection, tie-breaking, survivor counts), analytic cost-card pruning
on a recorded trace, ``_drive_sla`` timing modes, tuned-profile
round-trip through the engine, and the end-to-end record->search->profile->
reload loop beating the default knob vector.
"""

import copy

import jax
import numpy as np
import pytest

from deepspeed_tpu.analysis import knobs
from deepspeed_tpu.autotune import (analytic_prune, autotune_session,
                                    config_key, evaluate_config,
                                    successive_halving, predict_padding)
from deepspeed_tpu.autotune.profile import (TunedProfile, load_profile,
                                            maybe_load_tuned_profile,
                                            profile_provenance, save_profile,
                                            session_fingerprint, trace_hash)
from deepspeed_tpu.autotune import profile as profile_mod
from deepspeed_tpu.autotune.space import DEFAULT_SPACE, Dim, grid, neighborhood, parse_dim
from deepspeed_tpu.inference.v2.replay import _drive_sla, build_engine_from_session
from deepspeed_tpu.inference.v2.sla import LoadSpec, run_load
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.telemetry.events import get_event_log
from deepspeed_tpu.telemetry.health import get_health_monitor
from deepspeed_tpu.telemetry.journal import (Journal, journal_override,
                                             sessions_from_records, set_journal)


@pytest.fixture(autouse=True)
def _autotune_hygiene(monkeypatch):
    monkeypatch.delenv("DS_TPU_TUNED_PROFILE", raising=False)
    knobs.clear_profile()
    profile_mod._LOADED_PATH = None
    yield
    set_journal(None)
    get_event_log().clear()
    get_health_monitor().reset()
    knobs.clear_profile()
    profile_mod._LOADED_PATH = None


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=128, n_layers=2, n_heads=4, n_kv_heads=2,
                            d_model=32, max_seq_len=128, norm="rmsnorm",
                            activation="swiglu", pos_emb="rope", tie_embeddings=False)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    return model, params


@pytest.fixture(scope="module")
def sla_session(tiny):
    """One recorded 3-request SLA trace: the 3-row decode batch leaves
    real padding headroom, so MIN_DECODE_BUCKET=1 is a deterministic win."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2, RaggedBatchConfig,
                                            RaggedInferenceEngineConfig)
    model, params = tiny
    journal = Journal()  # memory mode
    journal.meta["param_seed"] = 0
    ecfg = RaggedInferenceEngineConfig(
        state_manager=RaggedBatchConfig(kv_block_size=8, max_context=128,
                                        num_kv_blocks=64),
        dtype="float32")
    spec = LoadSpec(n_requests=3, arrival_rate=1e9, prompt_len_range=(4, 8),
                    max_new_tokens=8, vocab_size=128, seed=7)
    with journal_override(journal):
        run_load(InferenceEngineV2(model, params, ecfg), spec)
    session = sessions_from_records(journal.records)[-1]
    set_journal(None)
    return session


# --------------------------------------------------- knob overlay precedence

class TestKnobOverlay:

    def test_env_beats_profile_beats_default(self, monkeypatch):
        assert knobs.get_int("DS_TPU_MIN_DECODE_BUCKET") == 8
        assert knobs.provenance("DS_TPU_MIN_DECODE_BUCKET") == "default"
        knobs.set_profile({"DS_TPU_MIN_DECODE_BUCKET": "4"})
        assert knobs.get_int("DS_TPU_MIN_DECODE_BUCKET") == 4
        assert knobs.provenance("DS_TPU_MIN_DECODE_BUCKET") == "profile"
        assert knobs.is_set("DS_TPU_MIN_DECODE_BUCKET")
        monkeypatch.setenv("DS_TPU_MIN_DECODE_BUCKET", "2")
        assert knobs.get_int("DS_TPU_MIN_DECODE_BUCKET") == 2
        assert knobs.provenance("DS_TPU_MIN_DECODE_BUCKET") == "env"
        knobs.clear_profile()
        assert knobs.get_int("DS_TPU_MIN_DECODE_BUCKET") == 2

    def test_active_profile_reports_env_shadowing(self, monkeypatch):
        knobs.set_profile({"DS_TPU_SPEC_K": "8", "DS_TPU_PREFILL_CHUNK": "128"},
                          meta={"path": "/tmp/p.json"})
        monkeypatch.setenv("DS_TPU_SPEC_K", "2")
        meta = knobs.active_profile()
        assert meta["path"] == "/tmp/p.json"
        assert meta["knobs"] == {"DS_TPU_SPEC_K": "8", "DS_TPU_PREFILL_CHUNK": "128"}
        assert meta["env_overridden"] == ["DS_TPU_SPEC_K"]

    def test_overlay_rejects_undeclared_and_nonstring(self):
        with pytest.raises(KeyError):
            knobs.set_profile({"DS_TPU_NOT_A_KNOB": "1"})
        with pytest.raises(TypeError):
            knobs.set_profile({"DS_TPU_SPEC_K": 8})

    def test_varz_knob_provenance_section(self):
        from deepspeed_tpu.telemetry.flight import knob_provenance, tuned_profile_section
        assert tuned_profile_section() == {"active": False}
        knobs.set_profile({"DS_TPU_SPEC_K": "8"}, meta={"path": "p", "provenance_hash": "h"})
        prov = knob_provenance()
        assert prov["DS_TPU_SPEC_K"] == "profile"
        assert prov["DS_TPU_KV_QUANT"] == "default"
        section = tuned_profile_section()
        assert section["active"] and section["provenance_hash"] == "h"


# ------------------------------------------------------------- search space

class TestSpace:

    def test_dim_requires_declared_knob(self):
        with pytest.raises(KeyError):
            Dim("DS_TPU_NOT_A_KNOB", ("1",))
        with pytest.raises(ValueError):
            Dim("DS_TPU_SPEC_K", ())

    def test_grid_and_neighborhood(self):
        dims = (Dim("DS_TPU_SPEC_K", ("2", "4")),
                Dim("DS_TPU_KV_QUANT", ("0", "8")))
        g = grid(dims)
        assert len(g) == 4 and all(len(c) == 2 for c in g)
        nb = neighborhood(dims)
        # base vector + one single-knob deviation per non-base value
        assert len(nb) == 3
        base = nb[0]
        assert base["DS_TPU_KV_QUANT"] == "0"  # declared default
        deviations = [{k: v for k, v in c.items() if base[k] != v} for c in nb[1:]]
        assert all(len(d) == 1 for d in deviations)
        keys = [config_key(c) for c in nb]
        assert len(keys) == len(set(keys))

    def test_config_key_canonical(self):
        a = {"DS_TPU_SPEC_K": "4", "DS_TPU_KV_QUANT": "8"}
        b = {"DS_TPU_KV_QUANT": "8", "DS_TPU_SPEC_K": "4"}
        assert config_key(a) == config_key(b)

    def test_parse_dim(self):
        d = parse_dim("DS_TPU_SPEC_K=2,4,8")
        assert d.name == "DS_TPU_SPEC_K" and d.values == ("2", "4", "8")
        with pytest.raises(ValueError):
            parse_dim("DS_TPU_SPEC_K")


# ------------------------------------- successive halving (fake evaluator)

class TestSuccessiveHalving:

    def _fake(self, scores, violators=(), calls=None):
        def evaluate(config, budget):
            if calls is not None:
                calls.append((config_key(config), budget))
            key = config.get("DS_TPU_SPEC_K", "def")
            return {"objective": scores[key],
                    "constraint_ok": key not in violators}
        return evaluate

    def test_budget_accounting_and_survivor_counts(self):
        configs = [{"DS_TPU_SPEC_K": k} for k in ("2", "4", "8")] + [{}]
        calls = []
        scores = {"2": 0.1, "4": 0.4, "8": 0.3, "def": 0.2}
        res = successive_halving(configs, self._fake(scores, calls=calls),
                                 budgets=[2, 8], eta=2)
        # round 0: all 4 at budget 2; round 1: ceil(4/2)=2 survivors at 8
        assert res.budget_spent == 4 * 2 + 2 * 8
        assert sum(t.budget for t in res.trials) == res.budget_spent
        assert res.rounds == [{"budget": 2, "n_in": 4, "n_out": 2, "n_rejected": 0},
                              {"budget": 8, "n_in": 2, "n_out": 2, "n_rejected": 0}]
        assert res.winner == {"DS_TPU_SPEC_K": "4"}
        # the two best advance, evaluated in deterministic key order
        assert calls[4:] == [("DS_TPU_SPEC_K=4", 8), ("DS_TPU_SPEC_K=8", 8)]

    def test_constraint_violators_rejected_permanently(self):
        scores = {"2": 0.9, "4": 0.4, "def": 0.2}
        configs = [{"DS_TPU_SPEC_K": "2"}, {"DS_TPU_SPEC_K": "4"}, {}]
        res = successive_halving(configs, self._fake(scores, violators={"2"}),
                                 budgets=[1, 2, 3], eta=2)
        # best raw score violates -> never advances, never re-evaluated
        assert res.winner == {"DS_TPU_SPEC_K": "4"}
        assert [t.key for t in res.rejected] == ["DS_TPU_SPEC_K=2"]
        assert all(t.key != "DS_TPU_SPEC_K=2" for t in res.trials if t.rnd > 0)

    def test_tie_breaks_on_config_key(self):
        scores = {"2": 0.5, "4": 0.5, "def": 0.5}
        res = successive_halving([{"DS_TPU_SPEC_K": "4"}, {"DS_TPU_SPEC_K": "2"}, {}],
                                 self._fake(scores), budgets=[4], eta=2)
        # all tie: the empty config's key '' sorts first
        assert res.winner == {}
        board = res.leaderboard
        assert [t.key for t in board] == ["", "DS_TPU_SPEC_K=2", "DS_TPU_SPEC_K=4"]

    def test_evaluator_exception_is_rejection_not_crash(self):
        def boom(config, budget):
            if config:
                raise RuntimeError("bad config")
            return {"objective": 1.0, "constraint_ok": True}
        res = successive_halving([{}, {"DS_TPU_SPEC_K": "4"}], boom, budgets=[2])
        assert res.winner == {}
        assert len(res.rejected) == 1
        assert "bad config" in res.rejected[0].info["error"]

    def test_input_validation(self):
        ev = self._fake({"def": 1.0})
        with pytest.raises(ValueError):
            successive_halving([{}], ev, budgets=[])
        with pytest.raises(ValueError):
            successive_halving([{}], ev, budgets=[4, 2])
        with pytest.raises(ValueError):
            successive_halving([{}], ev, budgets=[2], eta=1)
        with pytest.raises(ValueError):
            successive_halving([], ev, budgets=[2])

    def test_all_rejected_returns_no_winner(self):
        res = successive_halving([{}, {"DS_TPU_SPEC_K": "4"}],
                                 self._fake({"def": 1.0, "4": 2.0},
                                            violators={"def", "4"}),
                                 budgets=[1])
        assert res.winner is None and res.winner_trial is None
        assert len(res.rejected) == 2


# ---------------------------------------- analytic pruning + padding model

class TestAnalyticPrune:

    def test_padding_prediction_orders_bucket_sizes(self, sla_session):
        p_def = predict_padding(sla_session, {})
        p_b1 = predict_padding(sla_session, {"DS_TPU_MIN_DECODE_BUCKET": "1"})
        # 3 decode rows: bucket floor 8 pads to 8, floor 1 pads to 4
        assert p_b1["pred_slot"] < p_def["pred_slot"]
        assert p_b1["pred_goodput"] > p_def["pred_goodput"]
        assert p_b1["pred_useful"] == p_def["pred_useful"]

    def test_prune_drops_dominated_keeps_best(self, sla_session):
        configs = [{}, {"DS_TPU_MIN_DECODE_BUCKET": "1"},
                   {"DS_TPU_MIN_DECODE_BUCKET": "8"}]
        kept, pruned = analytic_prune(sla_session, configs)
        assert kept == [{"DS_TPU_MIN_DECODE_BUCKET": "1"}]
        assert {config_key(c) for c in pruned} == {"", "DS_TPU_MIN_DECODE_BUCKET=8"}

    def test_prune_never_crosses_non_padding_groups(self, sla_session):
        # different SPEC_K: padding model can't compare them -> both kept
        configs = [{"DS_TPU_SPEC_K": "2"}, {"DS_TPU_SPEC_K": "4"}]
        kept, pruned = analytic_prune(sla_session, configs)
        assert len(kept) == 2 and not pruned


# ------------------------------------------------- _drive_sla timing modes

class TestDriveSlaTiming:

    def test_recorded_and_logical_timing_replay_identical_tokens(self, sla_session):
        recorded = sla_session.tokens_by_uid()
        produced = {}
        for timing in ("logical", "recorded"):
            results, stats = _drive_sla(build_engine_from_session(sla_session),
                                        sla_session, timing=timing)
            toks = {uid: list(t) for uid, t in results.items()}
            assert toks == recorded, f"timing={timing} diverged from recording"
            produced[timing] = toks
            assert stats and all(s.ttft >= 0 for s in stats)
        assert produced["logical"] == produced["recorded"]

    def test_invalid_timing_rejected(self, sla_session):
        with pytest.raises(ValueError):
            _drive_sla(None, sla_session, timing="wall")


# ------------------------------------------------------------ tuned profile

class TestTunedProfile:

    def _profile(self, **kw):
        base = dict(device_kind="cpu", knobs={"DS_TPU_MIN_DECODE_BUCKET": "1"},
                    engine_fingerprint="eng123", trace_provenance="trace456",
                    objective="goodput", score=0.5, baseline_score=0.4,
                    constraint={"ttft_p99_s": 1.0})
        base.update(kw)
        return TunedProfile(**base)

    def test_roundtrip_and_provenance_hash(self, tmp_path):
        prof = self._profile()
        path = str(tmp_path / "cpu.json")
        save_profile(prof, path)
        again = load_profile(path)
        assert again.to_dict() == prof.to_dict()
        assert again.provenance_hash() == prof.provenance_hash()
        # identity covers knobs + engine + trace; score does not change it
        assert self._profile(score=0.9).provenance_hash() == prof.provenance_hash()
        assert (self._profile(knobs={"DS_TPU_MIN_DECODE_BUCKET": "4"})
                .provenance_hash() != prof.provenance_hash())

    def test_from_dict_rejects_unknown_fields_and_knobs(self):
        d = self._profile().to_dict()
        bad = dict(d); bad["surprise"] = 1
        with pytest.raises((KeyError, TypeError, ValueError)):
            TunedProfile.from_dict(bad)
        bad = copy.deepcopy(d); bad["knobs"] = {"DS_TPU_NOT_A_KNOB": "1"}
        with pytest.raises(KeyError):
            TunedProfile.from_dict(bad)

    def test_maybe_load_installs_overlay_env_still_wins(self, tmp_path, monkeypatch):
        path = str(tmp_path / "cpu.json")
        save_profile(self._profile(), path)
        monkeypatch.setenv("DS_TPU_TUNED_PROFILE", path)
        loaded = maybe_load_tuned_profile()
        assert loaded is not None
        assert knobs.get_int("DS_TPU_MIN_DECODE_BUCKET") == 1
        assert knobs.provenance("DS_TPU_MIN_DECODE_BUCKET") == "profile"
        prov = profile_provenance()
        assert prov["path"] == path and prov["env_overridden"] == []
        # explicit env knob shadows the profile value
        monkeypatch.setenv("DS_TPU_MIN_DECODE_BUCKET", "2")
        assert knobs.get_int("DS_TPU_MIN_DECODE_BUCKET") == 2
        assert profile_provenance()["env_overridden"] == ["DS_TPU_MIN_DECODE_BUCKET"]
        # unsetting the knob clears the overlay on the next load attempt
        monkeypatch.delenv("DS_TPU_TUNED_PROFILE")
        assert maybe_load_tuned_profile() is None
        assert knobs.active_profile() is None

    def test_auto_spec_silently_absent(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DS_TPU_TUNED_PROFILE", "auto")
        monkeypatch.setattr(profile_mod, "profile_path_for",
                            lambda *a, **k: str(tmp_path / "absent.json"))
        assert maybe_load_tuned_profile() is None

    def test_session_hashes_are_stable(self, sla_session):
        assert session_fingerprint(sla_session) == session_fingerprint(sla_session)
        assert trace_hash(sla_session) == trace_hash(sla_session)
        assert len(trace_hash(sla_session)) == 16


# ------------------------------------------------- end to end (acceptance)

class TestEndToEnd:

    def test_autotune_beats_defaults_and_profile_reloads(self, sla_session, tiny,
                                                         tmp_path, monkeypatch):
        """Record tiny trace -> search a small grid under a p99-TTFT
        constraint -> emit profile -> reload engine -> strictly better
        goodput than the default knob vector, deterministically."""
        out = autotune_session(
            sla_session,
            configs=[{}, {"DS_TPU_MIN_DECODE_BUCKET": "1"},
                     {"DS_TPU_MIN_DECODE_BUCKET": "4"}],
            budgets=[len(sla_session.requests)],
            constraint={"ttft_p99_s": 120.0})
        res = out["result"]
        assert res.winner == {"DS_TPU_MIN_DECODE_BUCKET": "1"}
        assert res.winner_trial.objective > out["baseline"]["objective"]
        assert out["budget_spent"] == sum(t.budget for t in res.trials)

        prof = out["profile"]
        assert prof is not None
        assert prof.score == res.winner_trial.objective
        assert prof.baseline_score == out["baseline"]["objective"]
        assert prof.engine_fingerprint == session_fingerprint(sla_session)
        assert prof.trace_provenance == trace_hash(sla_session)

        # the committed-profile round trip: a FRESH engine under
        # DS_TPU_TUNED_PROFILE resolves the winner's knob vector (a
        # session-rebuilt engine would rightly pin the recorded config)
        from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                                RaggedBatchConfig,
                                                RaggedInferenceEngineConfig)
        path = str(tmp_path / "tuned.json")
        save_profile(prof, path)
        monkeypatch.setenv("DS_TPU_TUNED_PROFILE", path)
        model, params = tiny
        engine = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
            state_manager=RaggedBatchConfig(kv_block_size=8, max_context=128,
                                            num_kv_blocks=64),
            dtype="float32"))
        assert engine._config.min_decode_bucket == 1
        assert knobs.provenance("DS_TPU_MIN_DECODE_BUCKET") == "profile"
        # and the session-rebuilt engine DOES pin the recorded default
        assert build_engine_from_session(sla_session)._config.min_decode_bucket == 8

        # determinism: re-evaluating the winner reproduces its objective
        monkeypatch.delenv("DS_TPU_TUNED_PROFILE")
        maybe_load_tuned_profile()
        again = evaluate_config(sla_session, res.winner,
                                budget=len(sla_session.requests))
        assert again["objective"] == pytest.approx(res.winner_trial.objective)

    def test_autotune_metrics_flow(self):
        from deepspeed_tpu.telemetry import get_registry
        reg = get_registry()
        before = reg.peek("autotune_trials_total") or 0.0
        successive_halving([{}, {"DS_TPU_SPEC_K": "4"}],
                           lambda c, b: {"objective": 1.0, "constraint_ok": True},
                           budgets=[1])
        assert (reg.peek("autotune_trials_total") or 0.0) == before + 2
