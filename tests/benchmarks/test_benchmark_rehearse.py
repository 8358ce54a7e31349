"""Both drivers end to end on the CPU at the rehearsal width, and the
measuring path's refusal to run without a TPU."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import manifest as mf

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture
def clean_env():
    """run.py sets the configuration's knobs in the environment, as a process
    of its own would; the suite's other tests must not inherit them."""
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), json.loads(out[-2])["extras"]


@pytest.mark.parametrize("workload", [w["name"] for w in mf.load_manifest()["workloads"]])
def test_rehearsal_ends_in_one_line_with_the_contracts_keys(workload, clean_env, capsys):
    assert bench_run.main(["--workload", workload, "--rehearse", "--seed", str(2**31 + 11), "--seconds", "3"]) == 0
    last, extras = _last_line(capsys)
    assert set(last) == CONTRACT_KEYS
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"] == {}, "a rehearsal prints no metric: its numbers are the CPU's"
    assert last["device"]["platform"] == "cpu" and extras["rehearsal"] is True


@pytest.mark.parametrize("traffic,requests", [("chat-steady", 6), ("chat-overload", 16), ("chat-capacity", 8)])
def test_rehearsal_of_the_serving_cells_that_are_not_listed_yet(traffic, requests, clean_env, capsys):
    """The serving driver's cells were not proven on the chip in PR 24 (PERF.md,
    Open questions), so BENCHMARK.json does not list them; their pieces stay,
    and run composed."""
    assert bench_run.main(["--config", "mistral-7b-l16", "--traffic", traffic, "--chips", "1", "--rehearse",
                           "--seed", str(2**31 + 5), "--seconds", "2"]) == 0
    last, extras = _last_line(capsys)
    assert set(last) == CONTRACT_KEYS and last["metrics"] == {}
    assert last["attempted"] == requests and last["failed"] == 0 and last["correct"] is True
    assert extras["counters"]["infer_dispatches_total"] == extras["quanta"] > 0
    assert extras["logits"]["ok"] is True and extras["host_clock"]["between_quanta_busy_s"] >= 0


@pytest.mark.parametrize("workload", [w["name"] for w in mf.load_manifest()["workloads"]])
def test_the_measuring_path_has_no_cpu_branch(workload, clean_env, capsys):
    assert bench_run.main(["--workload", workload, "--seed", "1", "--seconds", "1", "--trace", "0"]) != 0
    captured = capsys.readouterr()
    assert captured.out.strip() == "" and "no CPU branch" in captured.err


def test_the_plain_reference_agrees_with_the_program_in_float32():
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import reference, weights
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    for norm, tie, kvh in (("rmsnorm", False, 2), ("layernorm_np", True, 4)):
        cfg = TransformerConfig(vocab_size=64, n_layers=2, n_heads=4, n_kv_heads=kvh, d_model=32, d_ff=48,
                                activation="swiglu", pos_emb="rope", norm=norm, tie_embeddings=tie, dtype=jnp.float32)
        model = CausalLM(cfg)
        params = weights.make_params(model, 2**31 + 5, jnp.float32, std=0.2)
        ids = np.random.RandomState(0).randint(0, 64, (2, 12))
        published = {"num_hidden_layers": 2, "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": tie}
        ours = reference.decoder_logits(params, ids, published, norm)
        theirs = model.apply(params, jnp.asarray(ids))
        assert float(jnp.max(jnp.abs(ours - theirs))) < 1e-4
        assert abs(float(reference.causal_lm_loss(ours, ids)) - float(model.loss_fn(params, {"input_ids": jnp.asarray(ids)}))) < 1e-4
