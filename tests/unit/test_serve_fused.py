"""Fused continuous-batching serving step (SplitFuse single-dispatch).

The contract under test: every scheduler quantum — mixed chunked-prefill
plus decode rows — is ONE dispatched program, pure-decode quanta extend
to multi-step in-graph bursts, and the fused path is token-for-token
identical to the unfused per-phase dispatch loop (`DS_TPU_SERVE_FUSED=0`
fallback) in every mode: greedy deferred, EOS-cut, sampled, streaming.
Dispatch counts are observable on CPU via the telemetry counters
(``infer_dispatches_total`` / ``infer_fused_quanta_total``).
"""

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2, RaggedBatchConfig,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.models import CausalLM, TransformerConfig
from deepspeed_tpu.telemetry import get_registry


def _tiny_model():
    cfg = TransformerConfig(vocab_size=128, n_layers=2, n_heads=4, n_kv_heads=2, d_model=32, max_seq_len=256,
                            norm="rmsnorm", activation="swiglu", pos_emb="rope", tie_embeddings=False)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    return model, params


@pytest.fixture(scope="module")
def fused_setup():
    model, params = _tiny_model()

    def engine(fused, burst=8, blocks=128):
        smc = RaggedBatchConfig(kv_block_size=8, max_context=256, num_kv_blocks=blocks)
        return InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
            state_manager=smc, dtype="float32", fused_step=fused, decode_burst=burst))

    return model, params, engine


PROMPTS = [[3, 17, 42], [7, 7, 7, 7, 7], [100, 2], [55, 44, 33, 22, 11, 1, 0], [9] * 11, [1, 2, 3, 4]]


class TestFusedParity:

    def test_greedy_deferred(self, fused_setup):
        _, _, engine = fused_setup
        out_f = engine(True).generate(PROMPTS, max_new_tokens=9)
        out_u = engine(False).generate(PROMPTS, max_new_tokens=9)
        assert out_f == out_u

    def test_eos_mid_burst(self, fused_setup):
        # EOS cuts a request mid-quantum: the fused scan freezes the
        # finished row in-graph; the host truncates at commit and frees
        # its KV blocks while the others keep decoding
        _, _, engine = fused_setup
        ef, eu = engine(True), engine(False)
        greedy = ef.generate(PROMPTS, max_new_tokens=9)
        eos = greedy[0][3]  # hits row 0 mid-stream, others later or never
        free0 = ef.state.free_blocks
        out_f = ef.generate(PROMPTS, max_new_tokens=9, eos_token_id=eos)
        assert ef.state.free_blocks == free0  # eviction mid-quantum returned every block
        out_u = eu.generate(PROMPTS, max_new_tokens=9, eos_token_id=eos)
        assert out_f == out_u
        assert any(eos in o and len(o) < 9 for o in out_f)  # someone actually cut early

    def test_sampled_topk1(self, fused_setup):
        # top_k=1 sampling is argmax whatever the rng draw: exercises the
        # device-side sampler in the fused program with a deterministic
        # oracle (exact rng-sequence parity is impossible across program
        # structures; greedy-equivalence is the invariant)
        _, _, engine = fused_setup
        sf = engine(True).generate(PROMPTS, max_new_tokens=6, do_sample=True, top_k=1, seed=3)
        su = engine(False).generate(PROMPTS, max_new_tokens=6, do_sample=True, top_k=1, seed=3)
        assert sf == su

    def test_streaming_callback(self, fused_setup):
        _, _, engine = fused_setup
        streams_f, streams_u = {}, {}
        out_f = engine(True).generate(PROMPTS[:3], max_new_tokens=7,
                                      on_token=lambda u, t: streams_f.setdefault(u, []).append(t))
        engine(False).generate(PROMPTS[:3], max_new_tokens=7,
                               on_token=lambda u, t: streams_u.setdefault(u, []).append(t))
        assert streams_f == streams_u
        assert [streams_f[i] for i in range(3)] == out_f

    def test_chunked_prefill_mixed_quanta(self, fused_setup):
        # chunking forces quanta that mix mid-prompt prefill rows with
        # live decode rows — the SplitFuse case proper
        _, _, engine = fused_setup
        ef, eu = engine(True), engine(False)
        ef.scheduler.prefill_chunk = 4
        eu.scheduler.prefill_chunk = 4
        out_f = ef.generate(PROMPTS, max_new_tokens=5)
        assert out_f == eu.generate(PROMPTS, max_new_tokens=5)

    def test_kv_blocks_freed(self, fused_setup):
        _, _, engine = fused_setup
        eng = engine(True)
        free0 = eng.state.free_blocks
        eng.generate(PROMPTS[:2], max_new_tokens=4)
        assert eng.state.free_blocks == free0


class TestDispatchInvariant:

    def test_one_dispatch_per_quantum_and_10x(self, fused_setup):
        """The tentpole's acceptance bar: dispatches == quanta on a mixed
        serve trace, and >= 10x fewer dispatches per served token than the
        unfused per-step loop."""
        _, _, engine = fused_setup
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128, size=int(n)).tolist() for n in rng.integers(8, 17, 12)]
        disp = get_registry().counter("infer_dispatches_total")
        quanta = get_registry().counter("infer_fused_quanta_total")

        ef = engine(True, burst=32, blocks=256)
        d0, q0 = disp.value, quanta.value
        out_f = ef.generate(prompts, max_new_tokens=33)
        df, qf = disp.value - d0, quanta.value - q0
        assert df == qf, "a fused quantum must be exactly one dispatched program"

        eu = engine(False, burst=0, blocks=256)
        d0 = disp.value
        out_u = eu.generate(prompts, max_new_tokens=33)
        du = disp.value - d0
        assert out_f == out_u
        assert du >= 10 * df, f"fused served tokens in {df} dispatches vs {du} unfused (< 10x)"

    def test_multi_step_burst_inside_quantum(self, fused_setup):
        # pure-decode quanta between admission waves advance K steps per
        # dispatch: far fewer quanta than generated tokens
        _, _, engine = fused_setup
        quanta = get_registry().counter("infer_fused_quanta_total")
        ef = engine(True, burst=16)
        q0 = quanta.value
        # 17 = 1 (prefill wave) + 16 (one pow2 burst): 2 quanta total
        ef.generate(PROMPTS[:3], max_new_tokens=17)
        n_quanta = quanta.value - q0
        assert n_quanta <= 3, f"expected ~2 quanta (prefill wave + fused burst), got {n_quanta}"


class TestFusedProgramCache:

    def test_lru_eviction(self, fused_setup):
        _, _, engine = fused_setup
        eng = engine(True)
        cap = eng._MAX_FUSED_VARIANTS
        hot = (8, 0, 0)
        eng._fused_for(*hot, None)
        for i in range(cap + 3):  # churn distinct prefill buckets past capacity
            eng._fused_for(*hot, None)  # LRU touch keeps the hot signature alive
            eng._fused_for(8, 2 ** (i % 6), 16 + 16 * (i // 6), None)
        assert len(eng._fused_fns) <= cap
        # cache keys end with the engine's shard signature (tp topology)
        assert hot + (False, 1.0, 0, 1.0) + (eng._shard_sig,) in eng._fused_fns

    def test_bucketing(self, fused_setup):
        _, _, engine = fused_setup
        eng = engine(True)
        assert eng._fused_bucket(3, 0, 0) == (8, 0, 0)      # decode floor
        assert eng._fused_bucket(9, 0, 0) == (16, 0, 0)     # pow2 above floor
        assert eng._fused_bucket(0, 3, 5) == (0, 4, 16)     # chunk floor 16
        assert eng._fused_bucket(2, 1, 1) == (8, 1, 1)      # 1-token tail stays decode-shaped
        assert eng._fused_bucket(2, 2, 40) == (8, 2, 64)


class TestFusedScheduler:

    def test_quantum_descriptor(self, fused_setup):
        from deepspeed_tpu.inference.v2.scheduler import RaggedRequest

        _, _, engine = fused_setup
        eng = engine(True)
        eng.scheduler.prefill_chunk = 4
        reqs = [RaggedRequest(uid=50, tokens=list(range(10)), max_new_tokens=4)]
        q = eng.scheduler.schedule_fused(reqs, [])
        assert q.n_rows == 1 and q.total_tokens == 4
        assert not q.prefills[0].final
        eng.state.flush_sequence(50)

    def test_block_table_row(self, fused_setup):
        _, _, engine = fused_setup
        eng = engine(True)
        seq = eng.state.get_or_create_sequence(77)
        eng.state.allocate_for(seq, 20)  # 3 blocks of 8
        row = eng.state.block_table_row(seq, 6, fill_block=0)
        assert row.shape == (6,) and row.dtype == np.int32
        assert list(row[:3]) == list(seq.blocks) and all(row[3:] == 0)
        assert all(eng.state.block_table_row(None, 4, fill_block=5) == 5)
        eng.state.flush_sequence(77)


def test_generate_is_compile_free_after_warmup(monkeypatch):
    """A second generate() over the same prompts meets only bucket and
    burst shapes the first one compiled, with and without speculative
    decoding: mark the JitAuditor steady after the first pass and the
    second must trigger zero recompiles."""
    monkeypatch.setenv("DS_TPU_JIT_AUDIT", "1")
    cfg_model = TransformerConfig(vocab_size=128, n_layers=2, n_heads=4, n_kv_heads=2,
                                  d_model=32, max_seq_len=128, norm="rmsnorm",
                                  activation="swiglu", pos_emb="rope", tie_embeddings=False)
    model = CausalLM(cfg_model)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    rng = np.random.RandomState(0)
    # varied prompt lengths: a ragged workload
    prompts = [rng.randint(0, cfg_model.vocab_size, size=(int(l),)).tolist()
               for l in rng.randint(4, 13, size=3)]

    for spec in ("0", "1"):
        monkeypatch.setenv("DS_TPU_SPEC_DECODE", spec)
        eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
            state_manager=RaggedBatchConfig(kv_block_size=8, max_context=128,
                                            num_kv_blocks=64),
            dtype="float32"))
        eng.generate(prompts, max_new_tokens=8)  # warmup
        assert eng.jit_auditor.compiles > 0
        eng.jit_auditor.mark_steady()
        eng.generate(prompts, max_new_tokens=8)  # the steady window
        assert eng.jit_auditor.steady_recompiles == 0, \
            f"DS_TPU_SPEC_DECODE={spec}: the second generate() recompiled after warmup"
