"""Ring decode against the SEQUENCE-SHARDED prefix (VERDICT r2 #6): with
``sp_decode=True`` the SP prefill's KV never regathers to the replicated
layout — decode attends it in place via ring attention — and the outputs are
bit-equal to the dense single-engine path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k_llms_tpu.engine.engine import LocalEngine
from conftest import shared_engine, shared_params
from k_llms_tpu.models import get_config
from k_llms_tpu.ops.ring_attention import ring_decode_prefix
from k_llms_tpu.parallel.mesh import make_mesh

PROMPT = [int(x) for x in jax.random.randint(jax.random.key(40), (64,), 5, 200)]


def _mesh_ok():
    return len(jax.devices()) >= 8


pytestmark = pytest.mark.skipif(
    not _mesh_ok(), reason="needs the 8-device CPU mesh"
)


# -- op level ----------------------------------------------------------------

def test_ring_decode_prefix_matches_dense_attention():
    """(out, m, l) from the ring decode op must reproduce plain softmax
    attention over the valid prefix keys."""
    mesh = make_mesh(8, 1)
    B, QH, KVH, D, S = 8, 4, 2, 16, 64
    plen = 50
    q = jax.random.normal(jax.random.key(1), (B, QH, D), jnp.float32)
    pk = jax.random.normal(jax.random.key(2), (1, S, KVH, D), jnp.float32)
    pv = jax.random.normal(jax.random.key(3), (1, S, KVH, D), jnp.float32)

    out, m, l = jax.jit(
        lambda q, pk, pv: ring_decode_prefix(mesh, q, pk, pv, jnp.int32(plen))
    )(q, pk, pv)

    G = QH // KVH
    qg = np.asarray(q).reshape(B, KVH, G, D)
    k = np.asarray(pk)[0]  # [S, KVH, D]
    v = np.asarray(pv)[0]
    scale = 1.0 / np.sqrt(D)
    s = np.einsum("bhgd,shd->bhgs", qg, k) * scale
    s[..., plen:] = -np.inf
    w = np.exp(s - s.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    ref = np.einsum("bhgs,shd->bhgd", w, v).reshape(B, QH, D)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)
    # m/l form a valid logsumexp decomposition of the same softmax
    lse = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) + s.max(-1)
    np.testing.assert_allclose(
        (np.asarray(m) + np.log(np.asarray(l))).reshape(B, KVH, G), lse, rtol=1e-5, atol=1e-5
    )


# -- engine level ------------------------------------------------------------

@pytest.fixture(scope="module")
def engines():
    from conftest import shared_engine

    dense = shared_engine("tiny")
    ring = shared_engine(
        "tiny", mesh_shape=(4, 2), sp_prefill_min_tokens=48, sp_decode=True,
    )
    return dense, ring


def test_sp_decode_matches_dense(engines):
    dense, ring = engines
    kw = dict(n=4, max_new_tokens=6, temperature=0.0, seed=11)
    r_d = dense.generate(PROMPT, **kw)
    r_r = ring.generate(PROMPT, **kw)
    assert ring._sp_prefill_cache, "SP prefill route was not taken"
    np.testing.assert_array_equal(r_r.tokens, r_d.tokens)
    np.testing.assert_allclose(r_r.logprobs, r_d.logprobs, rtol=1e-4, atol=1e-4)
    assert r_r.finish_reasons == r_d.finish_reasons


def test_sp_decode_sampled_matches_dense(engines):
    """Sampling streams are seed-deterministic, so even at temperature>0 the
    ring-decode engine must reproduce the dense engine exactly."""
    dense, ring = engines
    kw = dict(n=4, max_new_tokens=5, temperature=0.9, seed=23)
    r_d = dense.generate(PROMPT, **kw)
    r_r = ring.generate(PROMPT, **kw)
    np.testing.assert_array_equal(r_r.tokens, r_d.tokens)


def test_sp_decode_prefix_stays_sequence_sharded(engines):
    """The decode path must consume the prefix WITHOUT regathering: the stored
    SP prefill output's sharding shards the sequence axis over 'data'."""
    _, ring = engines
    fl, prefix = ring._prefill_full(PROMPT, len(PROMPT), 64)
    spec = prefix.k.sharding.spec
    assert spec[2] == "data", spec  # [L, B, S, KVH, D] — S sharded over data


def test_short_prompts_keep_replicated_path(engines):
    """Below sp_prefill_min_tokens the normal dense prefill + replicated
    decode runs (no ring loop variant)."""
    dense, ring = engines
    short = PROMPT[:20]
    kw = dict(n=2, max_new_tokens=4, temperature=0.0, seed=5)
    np.testing.assert_array_equal(
        ring.generate(short, **kw).tokens, dense.generate(short, **kw).tokens
    )


def test_sp_decode_composes_with_prefix_cache_exact_hits():
    """Exact repeats of an SP-resident prompt reuse the cached seq-sharded KV
    (no re-prefill) and reproduce the same generation."""
    cfg = get_config("tiny")
    params = shared_params(cfg)
    mesh = make_mesh(4, 2)
    eng = LocalEngine(
        cfg, params=params, mesh=mesh,
        sp_prefill_min_tokens=48, sp_decode=True, prefix_cache_size=2,
    )
    kw = dict(n=4, max_new_tokens=4, temperature=0.7, seed=13)
    r1 = eng.generate(PROMPT, **kw)
    assert eng.prefix_cache_stats == {"hits": 0, "partial_hits": 0, "misses": 1}
    r2 = eng.generate(PROMPT, **kw)
    assert eng.prefix_cache_stats["hits"] == 1
    np.testing.assert_array_equal(r1.tokens, r2.tokens)


def test_sp_exact_hit_ignores_replicated_layout_entry():
    """Regression: _sp_prefill_routed's exact-hit path used to return ANY
    entry under the prompt key without checking its layout label — handing a
    REPLICATED prefix to ring decode, which gathers the whole O(S) prefix
    into every device's HBM (the exact spike sp_decode exists to avoid). A
    wrong-layout hit must be treated as a miss and overwritten with the
    sequence-sharded twin."""
    cfg = get_config("tiny")
    params = shared_params(cfg)
    dense = shared_engine("tiny")
    mesh = make_mesh(4, 2)
    eng = LocalEngine(
        cfg, params=params, mesh=mesh,
        sp_prefill_min_tokens=48, sp_decode=True, prefix_cache_size=2,
    )
    # Plant a replicated-layout entry under the exact prompt key (what a
    # replicated-path run sharing the cache would leave behind).
    bucket = 64
    tokens = jnp.array(
        [PROMPT + [cfg.pad_token_id] * (bucket - len(PROMPT))], jnp.int32
    )
    fl, pref = eng._get_prefill(bucket)(eng.params, tokens, jnp.int32(len(PROMPT)))[:2]
    assert not eng._kv_seq_sharded(pref)
    eng._prefix_store(PROMPT, fl, pref, seq_sharded=False)

    kw = dict(n=4, max_new_tokens=4, temperature=0.0, seed=3)
    r = eng.generate(PROMPT, **kw)
    assert eng.prefix_cache_stats["hits"] == 0  # wrong layout: NOT a hit
    assert eng.prefix_cache_stats["misses"] == 1
    entry = eng._prefix_entries[tuple(PROMPT)]
    assert entry[4] is True
    assert entry[1].k.sharding.spec[2] == "data"
    np.testing.assert_array_equal(r.tokens, dense.generate(PROMPT, **kw).tokens)
    # The overwritten (right-layout) entry now serves exact hits.
    eng.generate(PROMPT, **kw)
    assert eng.prefix_cache_stats["hits"] == 1


def test_seq_sharded_cache_entry_never_partial_matches():
    """A seq-sharded (sp_decode) cache entry must be exact-hit-only: a shorter
    prompt sharing its prefix takes a full prefill (miss), never the
    replicated continuation that would all-gather the O(S) prefix."""
    cfg = get_config("tiny")
    params = shared_params(cfg)
    mesh = make_mesh(4, 2)
    eng = LocalEngine(
        cfg, params=params, mesh=mesh,
        sp_prefill_min_tokens=48, sp_decode=True,
        prefix_cache_size=2, prefix_cache_min_reuse=16,
    )
    eng.generate(PROMPT, n=4, max_new_tokens=2, temperature=0.5, seed=1)
    assert eng.prefix_cache_stats["misses"] == 1
    # Shorter prompt sharing a >=16-token prefix: below the SP threshold, so
    # it routes through the replicated cache path — which must NOT partial-hit
    # the seq-sharded entry.
    short = PROMPT[:20]
    eng.generate(short, n=2, max_new_tokens=2, temperature=0.5, seed=2)
    assert eng.prefix_cache_stats["partial_hits"] == 0
    assert eng.prefix_cache_stats["misses"] == 2


def test_prefill_with_cache_labels_sp_entries_seq_sharded():
    """The prefix-cache MISS path (generate_many / _prefill_routed) must store
    SP-prefilled KV with the seq_sharded label (ADVICE r3): unlabeled, a later
    longer prompt would partial-hit it and the replicated continuation would
    all-gather the O(S) prefix."""
    cfg = get_config("tiny")
    params = shared_params(cfg)
    mesh = make_mesh(4, 2)
    eng = LocalEngine(
        cfg, params=params, mesh=mesh,
        sp_prefill_min_tokens=48, sp_decode=True,
        prefix_cache_size=2, prefix_cache_min_reuse=16,
    )
    eng._prefill_routed(PROMPT, len(PROMPT), 64)
    entry = eng._prefix_entries[tuple(PROMPT)]
    assert entry[4] is True, "SP-prefilled cache entry mislabeled as replicated"
    assert entry[1].k.sharding.spec[2] == "data"
    # A longer prompt sharing the whole prefix must NOT partial-hit it.
    longer = PROMPT + PROMPT[:32]
    eng._prefill_routed(longer, len(longer), 128)
    assert eng.prefix_cache_stats["partial_hits"] == 0
    assert eng.prefix_cache_stats["misses"] == 2


def test_generate_many_with_sp_decode_prefix_cache_bit_equal():
    """Coalesced requests through the sp_decode + prefix-cache engine must
    reproduce the dense engine exactly (the resharding of the seq-sharded
    entry to the replicated layout happens once, after _prefill_routed)."""
    from k_llms_tpu.engine.engine import GenRequestSpec

    cfg = get_config("tiny")
    params = shared_params(cfg)
    dense = shared_engine("tiny")
    mesh = make_mesh(4, 2)
    eng = LocalEngine(
        cfg, params=params, mesh=mesh,
        sp_prefill_min_tokens=48, sp_decode=True,
        prefix_cache_size=2, prefix_cache_min_reuse=16,
    )
    items = [
        GenRequestSpec(prompt_ids=PROMPT, n=2, seed=7),
        GenRequestSpec(prompt_ids=PROMPT[:20], n=2, seed=9),
    ]
    kw = dict(max_new_tokens=4, temperature=0.8)
    got = eng.generate_many(items, **kw)
    want = dense.generate_many(items, **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.tokens, w.tokens)


# -- ring-layout continuation prefill (VERDICT r3 #6) ------------------------

def test_sp_partial_hit_continues_in_ring_layout():
    """A growing prompt re-using a cached SP-resident prefix must take the
    ring-layout CONTINUATION (partial hit — no full re-prefill), produce a
    sequence-sharded entry, and generate tokens bit-equal to the dense
    engine's."""
    cfg = get_config("tiny")
    params = shared_params(cfg)
    dense = shared_engine("tiny")
    mesh = make_mesh(4, 2)
    eng = LocalEngine(
        cfg, params=params, mesh=mesh,
        sp_prefill_min_tokens=48, sp_decode=True,
        prefix_cache_size=4, prefix_cache_min_reuse=16,
    )
    kw = dict(n=4, max_new_tokens=4, temperature=0.7, seed=13)

    r1 = eng.generate(PROMPT, **kw)
    assert eng.prefix_cache_stats == {"hits": 0, "partial_hits": 0, "misses": 1}
    np.testing.assert_array_equal(r1.tokens, dense.generate(PROMPT, **kw).tokens)

    longer = PROMPT + [int(x) for x in jax.random.randint(jax.random.key(7), (30,), 5, 200)]
    r2 = eng.generate(longer, **kw)
    assert eng.prefix_cache_stats["partial_hits"] == 1
    assert eng.prefix_cache_stats["misses"] == 1  # no full re-prefill
    np.testing.assert_array_equal(r2.tokens, dense.generate(longer, **kw).tokens)

    # The continuation's entry is itself sequence-sharded and re-usable:
    # a third, even longer prompt continues from IT.
    entry = eng._prefix_entries[tuple(longer)]
    assert entry[4] is True
    assert entry[1].k.sharding.spec[2] == "data"
    longest = longer + [int(x) for x in jax.random.randint(jax.random.key(8), (20,), 5, 200)]
    r3 = eng.generate(longest, **kw)
    assert eng.prefix_cache_stats["partial_hits"] == 2
    assert eng.prefix_cache_stats["misses"] == 1
    np.testing.assert_array_equal(r3.tokens, dense.generate(longest, **kw).tokens)


def test_sp_continuation_crosses_bucket_boundary():
    """Continuation where the longer prompt lands in a BIGGER bucket: the
    stored prefix grows to the new bucket (sharded pad) and outputs stay
    bit-equal to dense."""
    cfg = get_config("tiny")
    params = shared_params(cfg)
    dense = shared_engine("tiny")
    mesh = make_mesh(4, 2)
    eng = LocalEngine(
        cfg, params=params, mesh=mesh,
        sp_prefill_min_tokens=48, sp_decode=True,
        prefix_cache_size=4, prefix_cache_min_reuse=16,
    )
    kw = dict(n=4, max_new_tokens=3, temperature=0.6, seed=29)
    eng.generate(PROMPT, **kw)  # bucket 64
    # 64 + 80 = 144 tokens -> bucket 256 > the entry's 64
    longer = PROMPT + [int(x) for x in jax.random.randint(jax.random.key(3), (80,), 5, 200)]
    r2 = eng.generate(longer, **kw)
    assert eng.prefix_cache_stats["partial_hits"] == 1
    np.testing.assert_array_equal(r2.tokens, dense.generate(longer, **kw).tokens)
    assert eng._prefix_entries[tuple(longer)][1].k.shape[2] == 256


def test_sp_continuation_logprobs_match_dense():
    """Float agreement, not just greedy tokens: continuation-path logprobs
    must match the dense engine's within tolerance."""
    cfg = get_config("tiny")
    params = shared_params(cfg)
    dense = shared_engine("tiny")
    mesh = make_mesh(4, 2)
    eng = LocalEngine(
        cfg, params=params, mesh=mesh,
        sp_prefill_min_tokens=48, sp_decode=True,
        prefix_cache_size=2, prefix_cache_min_reuse=16,
    )
    kw = dict(n=2, max_new_tokens=4, temperature=0.0, seed=5)
    eng.generate(PROMPT, **kw)
    longer = PROMPT + [int(x) for x in jax.random.randint(jax.random.key(11), (25,), 5, 200)]
    r = eng.generate(longer, **kw)
    assert eng.prefix_cache_stats["partial_hits"] == 1
    want = dense.generate(longer, **kw)
    np.testing.assert_array_equal(r.tokens, want.tokens)
    np.testing.assert_allclose(r.logprobs, want.logprobs, rtol=2e-4, atol=2e-4)


def test_suffix_prefix_attention_matches_dense():
    """(acc, m, l) from the one-psum suffix-vs-prefix op must reproduce plain
    softmax attention over the valid prefix keys, for every suffix query."""
    from k_llms_tpu.ops.ring_attention import suffix_prefix_attention

    mesh = make_mesh(8, 1)
    QH, KVH, D, S, Sq = 4, 2, 16, 64, 8
    plen = 41
    q = jax.random.normal(jax.random.key(1), (1, QH, Sq, D), jnp.float32)
    pk = jax.random.normal(jax.random.key(2), (1, S, KVH, D), jnp.float32)
    pv = jax.random.normal(jax.random.key(3), (1, S, KVH, D), jnp.float32)

    acc, m, l = jax.jit(
        lambda q, pk, pv: suffix_prefix_attention(mesh, q, pk, pv, jnp.int32(plen))
    )(q, pk, pv)

    G = QH // KVH
    qg = np.asarray(q).reshape(1, KVH, G, Sq, D)
    k = np.asarray(pk)[0]
    v = np.asarray(pv)[0]
    s = np.einsum("bhgqd,shd->bhgqs", qg, k) / np.sqrt(D)
    s[..., plen:] = -np.inf
    s = s.reshape(1, QH, Sq, S)
    w = np.exp(s - s.max(-1, keepdims=True))
    ref_out = np.einsum(
        "bhgqs,shd->bhgqd", (w / w.sum(-1, keepdims=True)).reshape(1, KVH, G, Sq, S), v
    ).reshape(1, QH, Sq, D)
    got = np.asarray(acc) / np.asarray(l)[..., None]
    np.testing.assert_allclose(got, ref_out, rtol=2e-5, atol=2e-5)
    # (m, l) is a valid logsumexp decomposition
    lse = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) + s.max(-1)
    np.testing.assert_allclose(np.asarray(m) + np.log(np.asarray(l)), lse, rtol=1e-5, atol=1e-5)


def test_scatter_into_ring_writes_only_suffix_rows():
    from k_llms_tpu.ops.ring_attention import scatter_into_ring

    mesh = make_mesh(8, 1)
    S, Ssuf, KVH, D = 64, 16, 2, 4
    base = jax.random.normal(jax.random.key(1), (1, S, KVH, D), jnp.float32)
    suf = jax.random.normal(jax.random.key(2), (1, Ssuf, KVH, D), jnp.float32)
    start, total = 37, 48  # 11 real suffix rows; rows 48.. stay untouched
    out = jax.jit(
        lambda b, s: scatter_into_ring(mesh, b, s, jnp.int32(start), jnp.int32(total))
    )(base, suf)
    out = np.asarray(out)
    want = np.asarray(base).copy()
    want[0, start:total] = np.asarray(suf)[0, : total - start]
    np.testing.assert_array_equal(out, want)
