"""Prompt-prefix KV cache: exact hits, suffix-only continuation prefill, LRU
eviction — all bit-equal to the uncached engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k_llms_tpu.engine.engine import LocalEngine
from k_llms_tpu.models import get_config, init_params

SYSTEM = [int(x) for x in jax.random.randint(jax.random.key(0), (48,), 5, 200)]
DOC_A = [int(x) for x in jax.random.randint(jax.random.key(1), (20,), 5, 200)]
DOC_B = [int(x) for x in jax.random.randint(jax.random.key(2), (25,), 5, 200)]


def _engines(cfg_overrides=None, **engine_kwargs):
    cfg = get_config("tiny")
    if cfg_overrides:
        cfg = cfg.with_(**cfg_overrides)
    params = init_params(cfg, jax.random.key(3))
    plain = LocalEngine(cfg, params=params, use_mesh=False)
    cached = LocalEngine(
        cfg, params=params, use_mesh=False,
        prefix_cache_size=4, prefix_cache_min_reuse=16, **engine_kwargs,
    )
    return plain, cached


def test_exact_hit_skips_device_prefill():
    plain, cached = _engines()
    prompt = SYSTEM + DOC_A
    r1 = cached.generate(prompt, n=2, max_new_tokens=4, temperature=0.7, seed=5)
    assert cached.prefix_cache_stats == {"hits": 0, "partial_hits": 0, "misses": 1}
    r2 = cached.generate(prompt, n=2, max_new_tokens=4, temperature=0.7, seed=5)
    assert cached.prefix_cache_stats["hits"] == 1
    np.testing.assert_array_equal(r1.tokens, r2.tokens)
    # And identical to the uncached engine.
    ref = plain.generate(prompt, n=2, max_new_tokens=4, temperature=0.7, seed=5)
    np.testing.assert_array_equal(r1.tokens, ref.tokens)


def test_shared_system_prefix_continuation_matches_dense():
    """Second document reuses the first prompt's system-prefix KV; the
    generation must match the uncached engine exactly."""
    plain, cached = _engines()
    cached.generate(SYSTEM + DOC_A, n=2, max_new_tokens=4, temperature=0.7, seed=7)
    r_cached = cached.generate(SYSTEM + DOC_B, n=2, max_new_tokens=4, temperature=0.7, seed=8)
    assert cached.prefix_cache_stats["partial_hits"] == 1
    r_plain = plain.generate(SYSTEM + DOC_B, n=2, max_new_tokens=4, temperature=0.7, seed=8)
    np.testing.assert_array_equal(r_cached.tokens, r_plain.tokens)
    np.testing.assert_allclose(
        r_cached.logprobs, r_plain.logprobs, rtol=1e-4, atol=1e-4
    )


def test_below_reuse_threshold_takes_full_prefill():
    _, cached = _engines()
    cached.generate(SYSTEM + DOC_A, n=1, max_new_tokens=2, temperature=0.5, seed=1)
    # Only 8 common tokens (< min_reuse 16): full prefill, counted as a miss.
    cached.generate(SYSTEM[:8] + DOC_B, n=1, max_new_tokens=2, temperature=0.5, seed=1)
    assert cached.prefix_cache_stats["misses"] == 2
    assert cached.prefix_cache_stats["partial_hits"] == 0


def test_growing_chain_hit_accounting():
    """The bench protocol's accounting, pinned: a growing prompt chain costs
    one miss then partial hits only; exact repeats of the longest prompt are
    full hits (zero prefill device work)."""
    _, cached = _engines()
    base = SYSTEM + DOC_A
    chain = [base, base + DOC_B, base + DOC_B + DOC_A]
    for p in chain:
        cached.generate(p, n=1, max_new_tokens=2, temperature=0.0, seed=1)
    assert cached.prefix_cache_stats == {"hits": 0, "partial_hits": 2, "misses": 1}
    for _ in range(2):
        cached.generate(chain[-1], n=1, max_new_tokens=2, temperature=0.0, seed=1)
    assert cached.prefix_cache_stats == {"hits": 2, "partial_hits": 2, "misses": 1}


def test_lru_eviction_caps_entries():
    _, cached = _engines()
    cached.prefix_cache_size = 2
    for s in range(4):
        prompt = [100 + s] * 40  # four disjoint prompts
        cached.generate(prompt, n=1, max_new_tokens=2, temperature=0.5, seed=s)
    assert len(cached._prefix_entries) == 2


def test_prompt_that_is_prefix_of_cached_prompt():
    """A new prompt fully contained in a cached one still gets a correct
    continuation (common length is capped so >=1 suffix token remains)."""
    plain, cached = _engines()
    cached.generate(SYSTEM + DOC_A, n=1, max_new_tokens=3, temperature=0.6, seed=9)
    short = SYSTEM + DOC_A[:5]
    r_cached = cached.generate(short, n=1, max_new_tokens=3, temperature=0.6, seed=10)
    r_plain = plain.generate(short, n=1, max_new_tokens=3, temperature=0.6, seed=10)
    np.testing.assert_array_equal(r_cached.tokens, r_plain.tokens)


@pytest.mark.parametrize("overrides", [
    dict(sliding_window=16, sliding_window_layers="all"),
    dict(sliding_window=16, sliding_window_layers="alternating"),
    dict(attn_softcap=50.0, query_scale=0.125),
    dict(attention_impl="flash_interpret", sliding_window=16, sliding_window_layers="all"),
    dict(attention_impl="flash_interpret", sliding_window=16, sliding_window_layers="alternating"),
    dict(attention_impl="flash_interpret", attn_softcap=50.0, query_scale=0.125),
])
def test_continuation_matches_dense_on_windowed_and_softcap_configs(overrides):
    """The continuation path builds masks over absolute positions, so sliding
    windows and softcaps must agree with the dense prefill bit-for-bit."""
    plain, cached = _engines(cfg_overrides=overrides)
    cached.generate(SYSTEM + DOC_A, n=2, max_new_tokens=3, temperature=0.7, seed=21)
    r_c = cached.generate(SYSTEM + DOC_B, n=2, max_new_tokens=3, temperature=0.7, seed=22)
    assert cached.prefix_cache_stats["partial_hits"] == 1
    r_p = plain.generate(SYSTEM + DOC_B, n=2, max_new_tokens=3, temperature=0.7, seed=22)
    np.testing.assert_array_equal(r_c.tokens, r_p.tokens)


def test_prefix_cache_on_mesh():
    """Continuation prefill under a (4, 2) mesh matches the uncached result."""
    from k_llms_tpu.parallel.mesh import make_mesh

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(3))
    mesh = make_mesh(4, 2)
    plain = LocalEngine(cfg, params=params, mesh=mesh)
    cached = LocalEngine(
        cfg, params=params, mesh=mesh, prefix_cache_size=4, prefix_cache_min_reuse=16
    )
    cached.generate(SYSTEM + DOC_A, n=4, max_new_tokens=3, temperature=0.7, seed=31)
    r_c = cached.generate(SYSTEM + DOC_B, n=4, max_new_tokens=3, temperature=0.7, seed=32)
    assert cached.prefix_cache_stats["partial_hits"] == 1
    r_p = plain.generate(SYSTEM + DOC_B, n=4, max_new_tokens=3, temperature=0.7, seed=32)
    np.testing.assert_array_equal(r_c.tokens, r_p.tokens)


def test_backend_config_plumbs_prefix_cache():
    from k_llms_tpu.backends.tpu import TpuBackend

    backend = TpuBackend(model="tiny", prefix_cache_size=3, prefix_cache_min_reuse=8)
    assert backend.engine.prefix_cache_size == 3
    assert backend.engine.prefix_cache_min_reuse == 8


def test_generate_many_uses_prefix_cache():
    """Coalesced batches consult and populate the cache per request."""
    from k_llms_tpu.engine.engine import GenRequestSpec

    plain, cached = _engines()
    cached.generate(SYSTEM + DOC_A, n=2, max_new_tokens=3, temperature=0.6, seed=40)
    batched = cached.generate_many(
        [GenRequestSpec(SYSTEM + DOC_A, 2, 41), GenRequestSpec(SYSTEM + DOC_B, 2, 42)],
        max_new_tokens=3,
        temperature=0.6,
    )
    assert cached.prefix_cache_stats["hits"] == 1  # exact reuse of DOC_A KV
    assert cached.prefix_cache_stats["partial_hits"] == 1  # DOC_B continuation
    solo = [
        plain.generate(p, n=2, max_new_tokens=3, temperature=0.6, seed=s)
        for p, s in ((SYSTEM + DOC_A, 41), (SYSTEM + DOC_B, 42))
    ]
    for s, b in zip(solo, batched):
        np.testing.assert_array_equal(s.tokens, b.tokens)


def test_oversized_continuation_falls_back_to_full_prefill():
    """A partial hit whose score tensor would blow the cap must take the full
    prefill path (counted as a miss) instead of the quadratic continuation."""
    _, cached = _engines()
    cached.MAX_CONT_SCORE_BYTES = 1  # force every continuation over the cap
    cached.generate(SYSTEM + DOC_A, n=1, max_new_tokens=2, temperature=0.5, seed=50)
    cached.generate(SYSTEM + DOC_B, n=1, max_new_tokens=2, temperature=0.5, seed=51)
    assert cached.prefix_cache_stats == {"hits": 0, "partial_hits": 0, "misses": 2}


def test_flash_continuation_matches_dense():
    """attention_impl="flash": the continuation prefill runs the flash kernel
    in q_offset mode — output must still be bit-equal to the uncached dense
    engine (VERDICT r2 #5)."""
    plain, cached = _engines(cfg_overrides={"attention_impl": "flash_interpret"})
    cached.generate(SYSTEM + DOC_A, n=2, max_new_tokens=4, temperature=0.7, seed=7)
    r_cached = cached.generate(SYSTEM + DOC_B, n=2, max_new_tokens=4, temperature=0.7, seed=8)
    assert cached.prefix_cache_stats["partial_hits"] == 1
    r_plain = plain.generate(SYSTEM + DOC_B, n=2, max_new_tokens=4, temperature=0.7, seed=8)
    np.testing.assert_array_equal(r_cached.tokens, r_plain.tokens)
    np.testing.assert_allclose(r_cached.logprobs, r_plain.logprobs, rtol=1e-4, atol=1e-4)


def test_flash_continuation_ignores_score_cap():
    """The 1 GB masked-XLA score cap does not apply to the flash path: even
    with the cap forced to 1 byte, the partial hit still takes continuation
    instead of falling back to full prefill."""
    plain, cached = _engines(cfg_overrides={"attention_impl": "flash_interpret"})
    cached.MAX_CONT_SCORE_BYTES = 1
    cached.generate(SYSTEM + DOC_A, n=1, max_new_tokens=3, temperature=0.6, seed=50)
    r = cached.generate(SYSTEM + DOC_B, n=1, max_new_tokens=3, temperature=0.6, seed=51)
    assert cached.prefix_cache_stats["partial_hits"] == 1
    ref = plain.generate(SYSTEM + DOC_B, n=1, max_new_tokens=3, temperature=0.6, seed=51)
    np.testing.assert_array_equal(r.tokens, ref.tokens)
