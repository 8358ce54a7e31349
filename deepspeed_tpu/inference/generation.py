"""Shared KV-cache generation machinery.

One implementation of the compiled prefill/decode pair + sampling +
greedy/sampled decode loop, used by the v1 :class:`InferenceEngine`
(reference ``inference/engine.py:613 _generate``) and the RLHF
:class:`~deepspeed_tpu.runtime.hybrid_engine.DeepSpeedHybridEngine`
(reference ``runtime/hybrid_engine.py:174 generate``) — the reference
duplicates this loop per engine; keeping it single-sourced here means a
sampling fix lands everywhere.
"""

import weakref
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# per-model cache of jitted fused decode loops, keyed by the static
# (length, sampling, eos) signature — rebuilding the jit per generate()
# call would recompile every time
_FUSED_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _decode_step(apply_fn, params, token, caches):
    """THE per-token step (shared by the jitted loop and the fused scan)."""
    B = token.shape[0]
    cache_len = caches[0][2]
    positions = jnp.full((B, 1), cache_len, jnp.int32)
    logits, caches = apply_fn(params, token, positions=positions, kv_caches=caches)
    return logits[:, -1, :], caches


def build_step_fns(model) -> Tuple:
    """Jitted (prefill, decode_step) over ``model.apply`` with donated caches."""

    def prefill(params, input_ids, caches):
        B, S = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        logits, caches = model.apply(params, input_ids, positions=positions, kv_caches=caches)
        return logits[:, -1, :], caches

    def decode_step(params, token, caches):
        return _decode_step(model.apply, params, token, caches)

    return jax.jit(prefill, donate_argnums=(2,)), jax.jit(decode_step, donate_argnums=(2,))


def filter_logits(logits, temperature: float, top_k: int, top_p: float = 1.0):
    """Temperature/top-k/nucleus masking over (B, V) logits — the exact
    distribution ``sample_logits`` draws from, exposed separately so the
    speculative-decode verifier (``inference/v2/spec.py``) can score
    drafts against the same filtered target distribution."""
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        vals, _ = jax.lax.top_k(logits, top_k)
        logits = jnp.where(logits < vals[:, -1][:, None], -jnp.inf, logits)
    if top_p < 1.0:
        # nucleus: keep the smallest prefix of descending-prob tokens whose
        # mass reaches top_p (the first token always survives)
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p  # token enters before the mass crossed p
        keep = keep.at[:, 0].set(True)  # top-1 always survives (top_p <= 0 == greedy)
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1)
        logits = jnp.where(logits < cutoff[:, None], -jnp.inf, logits)
    return logits


def sample_logits(logits, rng, do_sample: bool, temperature: float, top_k: int, top_p: float = 1.0):
    if not do_sample or temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(rng, filter_logits(logits, temperature, top_k, top_p), axis=-1)


def _build_fused_decode(model, max_new_tokens: int, do_sample: bool, temperature: float, top_k: int,
                        top_p: float, eos_token_id: Optional[int]):
    """ONE jitted dispatch for the whole decode loop (lax.scan).

    The python-loop path pays host->device dispatch per token AND per
    sampling op — 5+ dispatches per generated token, which caps decode
    at the host's dispatch rate regardless of the model. Scanning the step fuses prefill-to-final
    into two dispatches total. EOS sequences keep emitting ``eos`` (no
    host-side early exit — XLA control flow is length-static)."""

    # weak ref: the cached jit's closure must not strongly reference the
    # model, or the WeakKeyDictionary entry (key == model) never collects
    model_ref = weakref.proxy(model)

    def fused(params, logits, caches, rng):
        B = logits.shape[0]
        finished0 = jnp.zeros((B,), bool)

        def step(carry, _):
            logits, caches, rng, finished = carry
            rng, step_rng = jax.random.split(rng)
            token = sample_logits(logits, step_rng, do_sample, temperature, top_k, top_p)
            if eos_token_id is not None:
                token = jnp.where(finished, eos_token_id, token)
                finished = finished | (token == eos_token_id)
            logits, caches = _decode_step(model_ref.apply, params, token[:, None], caches)
            return (logits, caches, rng, finished), token

        (logits, caches, rng, finished), tokens = jax.lax.scan(
            step, (logits, caches, rng, finished0), None, length=max_new_tokens - 1)
        rng, last_rng = jax.random.split(rng)
        last = sample_logits(logits, last_rng, do_sample, temperature, top_k, top_p)
        if eos_token_id is not None:
            last = jnp.where(finished, eos_token_id, last)
        tokens = jnp.concatenate([tokens.T, last[:, None]], axis=1) if max_new_tokens > 1 else last[:, None]
        # caches are returned ONLY to give every donated input an alias
        # target (the caller drops them): without this XLA warns "Some
        # donated buffers were not usable" and the in-loop cache updates
        # cannot reuse the donated pages in place
        return tokens, caches

    return jax.jit(fused, donate_argnums=(2,))


def generate_tokens(model, params, prefill_fn, decode_fn, input_ids, *, max_new_tokens: int, cache_len: int,
                    cache_dtype, do_sample: bool = False, temperature: float = 1.0, top_k: int = 0,
                    top_p: float = 1.0, eos_token_id: Optional[int] = None, seed: int = 0,
                    fused: bool = True):
    """Prefill + decode; returns (B, S + new) token ids.

    ``fused=True`` (default) runs the whole decode loop as one compiled
    ``lax.scan`` dispatch; ``fused=False`` keeps the per-token python loop
    (supports host-side early exit when every sequence hit EOS)."""
    input_ids = jnp.asarray(input_ids, jnp.int32)
    if input_ids.ndim == 1:
        input_ids = input_ids[None]
    B = input_ids.shape[0]
    caches = model.init_kv_caches(B, cache_len, dtype=cache_dtype)
    rng = jax.random.PRNGKey(seed)
    logits, caches = prefill_fn(params, input_ids, caches)

    if fused and max_new_tokens > 0:
        key = (max_new_tokens, do_sample, float(temperature), int(top_k), float(top_p), eos_token_id)
        per_model = _FUSED_CACHE.setdefault(model, {})
        fn = per_model.get(key)
        if fn is None:
            fn = per_model[key] = _build_fused_decode(model, max_new_tokens, do_sample, temperature,
                                                      top_k, top_p, eos_token_id)
        tokens, _ = fn(params, logits, caches, rng)
        return jnp.concatenate([input_ids, tokens], axis=1)

    out = [input_ids]
    finished = jnp.zeros((B,), bool)
    for i in range(max_new_tokens):
        rng, step_rng = jax.random.split(rng)
        token = sample_logits(logits, step_rng, do_sample, temperature, top_k, top_p)[:, None]
        if eos_token_id is not None:
            token = jnp.where(finished[:, None], eos_token_id, token)
            finished = finished | (token[:, 0] == eos_token_id)
        out.append(token)
        if eos_token_id is not None and bool(jnp.all(finished)):
            break
        if i < max_new_tokens - 1:
            logits, caches = decode_fn(params, token, caches)
    return jnp.concatenate(out, axis=1)
