"""Tensor-parallel fused serving (ISSUE 17, docs/SERVING.md "Tensor-parallel
serving").

CPU CI shape: tests/conftest.py forces 8 host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, so one process can
drive a real ``tensor=2`` mesh. Covered here:

- tp=1 parity: the default engine is byte-for-byte the pre-TP engine
  (no mesh, no TPContext, ``tp1`` program signatures);
- tp=2 greedy token equality with tp=1 across the fused SplitFuse step,
  speculative decode, and the prefix-cache re-serve path (including an
  out-of-vocab prompt id — the vocab-sharded embedding clamp);
- sharded-pool geometry: KV heads split over the tensor axis, per-shard
  pool bytes = 1/tp, allocator/manager geometry helpers;
- program-cache keys carry the sharding signature (stale single-chip
  programs are unreachable when TP toggles);
- journal fingerprint topology + replay refusal on a mismatched mesh;
- the EQuARX-style quantized allreduce error bound and the T3-style
  interleaved reduce's exactness;
- graft-lint fixtures proving the new collective idiom passes the
  ``collective-axis`` / ``divergent-collective`` checks clean.
"""

import importlib.util
import pathlib
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm.collectives import tp_all_reduce
from deepspeed_tpu.inference.v2 import (InferenceEngineV2, RaggedBatchConfig,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_runner import (_SHARD_MAP_KW, TPContext,
                                                     shard_map)
from deepspeed_tpu.inference.v2.ragged.blocked_allocator import \
    shard_pool_geometry
from deepspeed_tpu.inference.v2.ragged.manager import DSStateManager
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.parallel.mesh import mesh_signature, reset_mesh, serving_mesh

# 999 is out of vocab (128): regression cover for the embedding clamp — a
# vocab-sharded wte masks out-of-range gathers to zero where a single
# device clamps, so the clamp must be explicit for tp parity
_PROMPTS = [[3, 17, 42, 9, 999], [5, 6, 7], [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]]
_NEW = 10


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=128, n_layers=2, n_heads=4, n_kv_heads=2,
                            d_model=32, max_seq_len=128, norm="rmsnorm",
                            activation="swiglu", pos_emb="rope", tie_embeddings=False)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    return model, params


def _engine(tiny, tp=1, **kw):
    model, params = tiny
    reset_mesh()
    cfg = RaggedInferenceEngineConfig(
        state_manager=RaggedBatchConfig(kv_block_size=8, max_context=128,
                                        num_kv_blocks=64),
        dtype="float32", tensor_parallel=tp, **kw)
    return InferenceEngineV2(model, params, cfg)


def _toks(rows):
    return [list(map(int, r)) for r in rows]


# ------------------------------------------------------------- parity
class TestTPParity:

    @pytest.fixture(scope="class")
    def engines(self, tiny):
        e1 = _engine(tiny)
        out1 = _toks(e1.generate(_PROMPTS, max_new_tokens=_NEW))
        e2 = _engine(tiny, tp=2)
        out2 = _toks(e2.generate(_PROMPTS, max_new_tokens=_NEW))
        return e1, out1, e2, out2

    def test_tp1_is_the_existing_engine(self, engines):
        e1, _, _, _ = engines
        assert e1._tp == 1 and e1._tp_ctx is None and e1._mesh_topo is None
        assert e1._shard_sig == "tp1"

    def test_tp2_greedy_equals_tp1_fused(self, engines):
        _, out1, e2, out2 = engines
        assert e2._tp_ctx is not None and e2._tp_ctx.tp == 2
        assert out2 == out1

    def test_tp2_serves_a_vocab_the_tensor_axis_does_not_divide(self):
        """GPT-2's 50257 has no even divisor: the embedding stays whole
        (GSPMD, outside the shard_map region) instead of failing placement."""
        cfg = TransformerConfig(vocab_size=131, n_layers=2, n_heads=4, d_model=32, max_seq_len=128)
        model = CausalLM(cfg)
        params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
        prompts = [[5, 130, 7, 9], [1, 2, 3, 4, 5, 6]]
        outs = {tp: _toks(_engine((model, params), tp=tp).generate(prompts, max_new_tokens=4)) for tp in (1, 2)}
        assert outs[2] == outs[1]

    def test_tp2_equals_tp1_on_prefix_cache_reserve(self, engines):
        e1, out1, e2, out2 = engines
        # both engines run with the radix prefix cache on; a second pass
        # over the same prompts re-serves cached prefixes
        assert e1.state.prefix_cache is not None and e2.state.prefix_cache is not None
        r1 = _toks(e1.generate(_PROMPTS, max_new_tokens=_NEW))
        r2 = _toks(e2.generate(_PROMPTS, max_new_tokens=_NEW))
        assert r1 == out1 and r2 == out2

    def test_tp2_equals_tp1_spec_decode(self, tiny):
        # repetitive motifs so prompt-lookup actually drafts; bursts off so
        # every quantum retries the draft→verify path (test_spec_decode.py's
        # engagement recipe)
        motifs = [[5, 9, 13] * 3, [7] * 6, [3, 17, 42, 3, 17, 42]]
        s1 = _engine(tiny, spec_decode=True, spec_k=4, decode_burst=0)
        o1 = _toks(s1.generate(motifs, max_new_tokens=32))
        s2 = _engine(tiny, tp=2, spec_decode=True, spec_k=4, decode_burst=0)
        o2 = _toks(s2.generate(motifs, max_new_tokens=32))
        assert o1 == o2
        assert s2._spec_fns, "spec path did not dispatch"
        assert all(k[-1] == s2._shard_sig for k in s2._spec_fns)

    def test_program_cache_keys_carry_shard_sig(self, engines):
        e1, _, e2, _ = engines
        assert e2._fused_fns and all(k[-1] == e2._shard_sig for k in e2._fused_fns)
        assert all(k[-1] == "tp1" for k in e1._fused_fns)
        assert all(k[-1] == e2._shard_sig for k in e2._bursts)
        assert e2._shard_sig != e1._shard_sig

    def test_journal_fingerprint_topology(self, engines):
        e1, _, e2, _ = engines
        f1 = e1._journal_fingerprint()["engine"]
        f2 = e2._journal_fingerprint()["engine"]
        assert f1["tensor_parallel"] == 1 and f1["mesh"] == "mesh[none]"
        # conftest forces 8 host devices, so the serving mesh may carry a
        # data axis beside tensor=2 — compute the expectation, don't pin it
        assert f2["tensor_parallel"] == 2
        assert f2["mesh"] == mesh_signature(e2._mesh_topo)
        assert "tensor2" in f2["mesh"]
        assert f2["shard_sig"] == e2._shard_sig and f2["tp_allreduce_bits"] == 0
        assert any(s.endswith(e2._shard_sig) for s in
                   e2._program_signatures() if s.startswith("prefill"))


# ------------------------------------------------- sharded pool geometry
class TestShardedPoolGeometry:

    def test_shard_pool_geometry_units(self):
        g = shard_pool_geometry(64, 4096, 2)
        assert g["block_bytes_per_shard"] == 2048
        assert g["pool_bytes_per_shard"] == 64 * 2048
        assert g["pool_bytes_global"] == 64 * 4096
        assert shard_pool_geometry(8, 128, 1)["block_bytes_per_shard"] == 128
        with pytest.raises(ValueError):
            shard_pool_geometry(8, 100, 3)  # non-divisible bytes
        with pytest.raises(ValueError):
            shard_pool_geometry(8, 128, 0)

    def test_manager_shard_geometry_delegates(self):
        sm = DSStateManager(RaggedBatchConfig(kv_block_size=4, max_context=64),
                            num_kv_blocks=16)
        g = sm.shard_geometry(block_bytes=512, shard_degree=4)
        assert g["num_blocks"] == 16 and g["block_bytes_per_shard"] == 128

    def test_engine_pool_is_head_sharded(self, tiny):
        e2 = _engine(tiny, tp=2)
        spec = e2.k_pages.sharding.spec
        assert tuple(spec) == (None, None, None, "tensor", None)
        shard = e2.k_pages.addressable_shards[0].data
        assert shard.nbytes * 2 == e2.k_pages.nbytes  # per-shard bytes = 1/tp
        res = e2._residency_summary()
        assert res["tp_degree"] == 2
        assert res["block_bytes_per_shard"] * 2 == res["block_bytes"]

    def test_tp_refuses_kv_quant_and_spill(self, tiny):
        with pytest.raises(ValueError):
            _engine(tiny, tp=2, kv_quant_bits=8)
        with pytest.raises(ValueError):
            _engine(tiny, tp=2, kv_spill=True)


# ------------------------------------------------------- replay topology
class TestReplayTopology:

    def test_refuses_mismatched_device_count(self, tiny):
        from deepspeed_tpu.inference.v2.replay import build_engine_from_session
        from deepspeed_tpu.telemetry.journal import (Journal,
                                                     sessions_from_records)
        model, _ = tiny
        journal = Journal()  # memory mode
        journal.begin_session(
            {"engine": {"dtype": "float32", "tensor_parallel": 3,
                        "num_kv_blocks": 16, "kv_block_size": 8,
                        "max_context": 128, "mesh": "mesh[tensor3]"},
             "model_cfg": {"vocab_size": 128, "n_layers": 1, "n_heads": 3,
                           "n_kv_heads": 3, "d_model": 24, "max_seq_len": 128}},
            kind="generate", run={"seed": 0})
        journal.record_request(0, [1, 2], arrival_s=0.0, arrival_q=0, max_new_tokens=2)
        journal.record_commit(0, 1, [5, 5])
        journal.end_session({})
        session = sessions_from_records(journal.records)[-1]
        # 8 forced host devices % tp=3 != 0 -> the topology cannot be realized
        with pytest.raises(RuntimeError, match="mismatched topology"):
            build_engine_from_session(session)

    def test_tp2_journal_replays_token_exact(self, tiny):
        # a session recorded under tp=2 replays token-for-token through a
        # fresh tp=2 engine rebuilt from the journal header alone — the
        # oracle is the cross-topology determinism contract
        from deepspeed_tpu.inference.v2.replay import (
            build_engine_from_session, replay_oracle)
        from deepspeed_tpu.telemetry.journal import (Journal, journal_override,
                                                     sessions_from_records)
        journal = Journal()  # memory mode
        with journal_override(journal):
            eng = _engine(tiny, tp=2)
            out = eng.generate(_PROMPTS, max_new_tokens=_NEW)
        session = sessions_from_records(journal.records)[-1]
        assert session.header["engine"]["tensor_parallel"] == 2
        assert "tensor2" in session.header["engine"]["mesh"]
        assert session.header["engine"]["shard_sig"] == eng._shard_sig
        # meta.param_seed defaults to 0 — the same PRNGKey(0) the fixture
        # initialized with, so the rebuilt engine reproduces the weights
        report = replay_oracle(session, engine=build_engine_from_session(session))
        assert report.ok, report.divergences
        assert report.n_tokens == sum(len(t) for t in out)


# --------------------------------------------------- collective numerics
def _mesh2():
    return serving_mesh(tp=2).mesh


def _reduce_on_mesh(x, **kw):
    from jax.sharding import PartitionSpec as P
    mesh = _mesh2()
    fn = shard_map(lambda s: tp_all_reduce(s, group="tensor", **kw),
                   mesh=mesh, in_specs=P("tensor"), out_specs=P("tensor"),
                   **_SHARD_MAP_KW)
    return fn(x)


class TestTPAllReduce:

    def test_exact_reduce_matches_psum_and_interleave_is_exact(self):
        reset_mesh()
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 64), jnp.float32)
        base = np.asarray(_reduce_on_mesh(x))
        want = np.asarray(x[0] + x[1])
        np.testing.assert_allclose(base[0], want, rtol=1e-6)
        np.testing.assert_array_equal(base[0], base[1])  # replicated result
        # T3-style chunked reduce: each element reduced exactly once
        il = np.asarray(_reduce_on_mesh(x, interleave=4))
        np.testing.assert_array_equal(il, base)
        # non-divisible interleave falls back to the single reduce
        odd = np.asarray(_reduce_on_mesh(x[:, :, :63], interleave=4))
        np.testing.assert_allclose(odd[0], want[:, :63], rtol=1e-6)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_quantized_reduce_error_bound(self, bits):
        reset_mesh()
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 128), jnp.float32)
        got = np.asarray(_reduce_on_mesh(x, bits=bits))[0]
        want = np.asarray(x[0] + x[1])
        # EQuARX bound: per-element error <= tp * scale / 2, scale = shared
        # row amax / qmax (each shard's rounding error is at most scale/2)
        qmax = (1 << (bits - 1)) - 1
        amax = np.max(np.abs(np.asarray(x)), axis=(0, -1), keepdims=True)[0]
        bound = 2 * (amax / qmax) / 2 + 1e-6
        assert np.all(np.abs(got - want) <= bound)
        assert np.max(np.abs(got - want)) > 0  # it really quantized

    def test_quantized_reduce_shard_agreement(self):
        # integer-code psum is order-independent: both shards decode the
        # bit-identical result (the cross-shard token-equality invariant)
        reset_mesh()
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 32), jnp.float32)
        out = np.asarray(_reduce_on_mesh(x, bits=8))
        np.testing.assert_array_equal(out[0], out[1])

    def test_tpcontext_signature(self):
        reset_mesh()
        topo = serving_mesh(tp=2)
        # the mesh may carry a data axis too (conftest forces 8 host
        # devices): build the expectation from the actual topology
        msig = mesh_signature(topo)
        sig = TPContext(mesh=topo.mesh, tp=2, bits=8, interleave=2).signature()
        assert sig == f"tp2:tensor:b8:il2:{msig}"
        assert "tensor2" in msig and msig.startswith("mesh[")


# ----------------------------------------------------- graft-lint fixture
ROOT = pathlib.Path(__file__).resolve().parents[2]


def _load_dist_checks():
    spec = importlib.util.spec_from_file_location(
        "serve_tp_dist_checks", str(ROOT / "deepspeed_tpu" / "analysis" / "dist_checks.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


class TestGraftLintClean:
    """The TP collective idiom passes graft-lint's dist checks clean —
    the same checks ``tools/lint_all.py`` runs over the real tree."""

    # the shape of the serving TP reduce: literal "tensor" axis, collectives
    # in straight-line dataflow (the per-shard slopes slice is dataflow on
    # axis_index, not control flow)
    _FIXTURE = """
        import jax
        import jax.numpy as jnp
        from jax import lax

        def tp_reduce(x, bits):
            if bits <= 0:
                return lax.psum(x, "tensor")
            qmax = (1 << (bits - 1)) - 1
            amax = lax.pmax(jnp.max(jnp.abs(x), axis=-1, keepdims=True), "tensor")
            scale = jnp.maximum(amax, 1e-30) / qmax
            codes = jnp.round(x / scale).astype(jnp.int32)
            return lax.psum(codes, "tensor").astype(jnp.float32) * scale

        def layer(x, slopes):
            hs = 2
            local = jax.lax.dynamic_slice(
                slopes, (jax.lax.axis_index("tensor") * hs,), (hs,))
            attn = x * local[0]
            x = x + tp_reduce(attn, 0)
            return x + tp_reduce(x * 2.0, 8)

        def run(x, slopes, mesh):
            # bind the collective-bearing body by NAME: the binder analysis
            # links psum/axis_index to their shard_map entry through it
            return jax.shard_map(layer, mesh=mesh)(x, slopes)
    """

    def test_collective_axis_and_divergence_clean(self):
        dist_checks = _load_dist_checks()
        findings = dist_checks.lint_source(textwrap.dedent(self._FIXTURE),
                                           mesh_axes=("data", "tensor"))
        bad = [f for f in findings
               if f.check in ("collective-axis", "divergent-collective")]
        assert not bad, [f.message for f in bad]

    def test_checks_are_live_on_a_broken_sibling(self):
        # same fixture with a typo'd axis + a rank-tainted branch around a
        # collective: both checks must fire (proves the clean pass means
        # something)
        dist_checks = _load_dist_checks()
        broken = """
            import jax
            from jax import lax

            def layer(x):
                if lax.axis_index("tensor") == 0:
                    x = lax.psum(x, "tnesor")
                return x

            def run(x, mesh):
                return jax.shard_map(layer, mesh=mesh)(x)
        """
        findings = dist_checks.lint_source(textwrap.dedent(broken),
                                           mesh_axes=("data", "tensor"))
        checks = {f.check for f in findings}
        assert "collective-axis" in checks
        assert "divergent-collective" in checks
