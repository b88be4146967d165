"""The one nucleus (top-p) search of the tree, ``ops/sampling.py::nucleus_threshold``.

Both samplers call it: the loop's per-row ``_sample_rows`` and the coalesced
paths' ``sample_logits``. It finds a row's boundary logit by bisection over
the integer image of the floats, with no sort. The pins: its kept set IS the
descending sort's set (compared as sets, never by sampling), whatever the
row's ``top_p`` and however degenerate its logits; the loop's tokens are the
tokens of the sort-based sampler it replaced; no loop program holds a ``sort``
or a ``cumsum``; and the search takes ``NUCLEUS_SEARCH_TRIPS`` = 32 passes
over the vocabulary by construction, which is why it needs no counter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_paged_attention_kernel import _equations

from k_llms_tpu.ops.sampling import NUCLEUS_SEARCH_TRIPS, nucleus_threshold

TOP_PS = (0.0, 0.5, 0.9, 0.95, 1.0)
# Float32 sums over a row carry rounding of about 1e-7 (a tree) to 1e-6 (a
# running sum over 152k sorted terms): a tie group whose exclusive cumulative
# mass lies this close to top_p may fall on either side, in the sort too.
ROUNDING = 1e-5


def sort_reference_threshold(scaled, top_ps):
    """The loop's sampler up to PR 30, kept here as the reference: sort
    descending, softmax, running sum, the smallest kept value."""
    sort_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sort_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_ps[:, None]
    return jnp.min(jnp.where(keep, sort_desc, jnp.inf), axis=-1)


def sort_sample_rows(logits, keys, temps, top_ps):
    """The loop's ``_sample_rows`` as it stood up to PR 30: the sort in place
    of the shared search, everything else to the letter."""
    from k_llms_tpu.engine.continuous import _poisoned_logits

    bad = _poisoned_logits(logits)
    finite = jnp.isfinite(logits)
    row_ok = jnp.any(finite, axis=-1, keepdims=True)
    logits = jnp.where(finite, logits, -jnp.inf)
    logits = jnp.where(row_ok, logits, 0.0)
    model_lps = jax.nn.log_softmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    thresh = sort_reference_threshold(scaled, top_ps)
    masked = jnp.where(scaled >= thresh[:, None], scaled, -jnp.inf)
    sampled = jax.vmap(jax.random.categorical)(keys, masked)
    greedy = jnp.argmax(scaled, axis=-1)
    tok = jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)
    lp = jnp.take_along_axis(model_lps, tok[:, None], axis=-1)[:, 0]
    return tok, lp, bad


def make_logits(kind, rows, vocab, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, vocab)).astype(np.float32)
    if kind == "peaked":
        x[:, 3] += 20
    elif kind == "flat":
        x = x * np.float32(1e-3)
    elif kind == "tied":
        x = np.round(x * 2) / 2
    elif kind == "signed_zero_tied":
        x = np.round(x * 2) / 2
        x = np.where((x == 0) & (rng.random(x.shape) < 0.5), np.float32(-0.0), x)
        assert np.signbit(x[x == 0]).any() and not np.signbit(x[x == 0]).all()
    elif kind == "three_finite":  # a grammar or pad mask left three tokens
        keep = rng.choice(vocab, size=3, replace=False)
        masked = np.full_like(x, -np.inf)
        masked[:, keep] = x[:, keep]
        x = masked
    elif kind == "all_equal":  # the sanitised row of a poisoned slot
        x = np.zeros_like(x)
    elif kind == "greedy_scaled":  # a temperature-0 row's ``scaled``
        x = x / np.float32(1e-6)
    else:
        assert kind == "normal"
    return x.astype(np.float32)


KINDS = (
    "normal", "peaked", "flat", "tied", "signed_zero_tied",
    "three_finite", "all_equal", "greedy_scaled",
)
SHAPES = [
    pytest.param(512, TOP_PS, id="V512-all-top_p"),
    pytest.param(152064, (0.5, 0.95), id="V152064-0.5-0.95"),
    pytest.param(152064, (0.9, 1.0), id="V152064-0.9-1.0"),
    pytest.param(152064, (0.0, 0.95), id="V152064-0.0-0.95"),
]


def exclusive_mass(row):
    """Per token, in float64: the softmax mass of the logits strictly above
    its own (what a descending sort has summed before it reaches the token's
    tie group)."""
    row = row.astype(np.float64)
    values, inverse, counts = np.unique(row, return_inverse=True, return_counts=True)
    weights = np.exp(values - values.max()) * counts  # ascending values
    weights /= weights.sum()
    above = np.concatenate([np.cumsum(weights[::-1])[::-1][1:], [0.0]])
    return above[inverse]


@pytest.mark.parametrize("vocab,top_ps", SHAPES)
@pytest.mark.parametrize("kind", KINDS)
def test_kept_set_is_the_sorts(kind, vocab, top_ps):
    rows = len(top_ps)
    x = make_logits(kind, rows, vocab, seed=vocab + len(kind))
    tp = jnp.asarray(top_ps, jnp.float32)
    thresh = np.asarray(jax.jit(nucleus_threshold)(jnp.asarray(x), tp))
    ref = np.asarray(jax.jit(sort_reference_threshold)(jnp.asarray(x), tp))
    assert not np.isnan(thresh).any()
    decided = 0
    for r, p in enumerate(top_ps):
        kept = x[r] >= thresh[r]
        # A set cut at one logit: upward closed, whole tie groups.
        assert kept.any() and (x[r][kept].min() == thresh[r] or thresh[r] == -np.inf)
        if p == 0.0:  # top-1 (OpenAI's reading of top_p 0), and its ties
            np.testing.assert_array_equal(kept, x[r] == x[r].max())
            continue
        above = exclusive_mass(x[r])
        must_keep, must_drop = above < p - ROUNDING, above >= p + ROUNDING
        assert kept[must_keep].all() and not kept[must_drop].any()
        if (must_keep | must_drop).all():  # no group within rounding of top_p
            np.testing.assert_array_equal(kept, x[r] >= ref[r])
            decided += 1
    if vocab == 512 and kind in ("normal", "peaked", "tied", "three_finite"):
        # The comparison with the sort is exact set equality in these rows,
        # not only the float64 sandwich.
        assert decided >= 3


def test_trip_count_is_a_constant():
    """32 masked reductions, one per bit of a float32, whatever the rows hold
    (the float-midpoint bracket it replaced took ~126 on an all-equal row, and
    every row waited): one scan of that length, no while loop."""
    assert NUCLEUS_SEARCH_TRIPS == 32
    jaxpr = jax.make_jaxpr(nucleus_threshold)(
        jnp.zeros((4, 64), jnp.float32), jnp.ones((4,), jnp.float32)
    )
    loops = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in ("scan", "while")]
    assert [e.primitive.name for e in loops] == ["scan"]
    assert loops[0].params["length"] == NUCLEUS_SEARCH_TRIPS


def test_sample_logits_uses_the_shared_search(monkeypatch):
    from k_llms_tpu.ops import sampling

    calls = []

    def spy(scaled, top_p):
        calls.append((scaled.shape, top_p.shape))
        return nucleus_threshold(scaled, top_p)

    monkeypatch.setattr(sampling, "nucleus_threshold", spy)
    x = jnp.asarray(make_logits("normal", 3, 64, seed=1))
    tokens, _ = sampling.sample_logits(x, jax.random.key(0), temperature=0.7, top_p=0.5)
    assert calls == [((3, 64), (3,))]
    kept = np.asarray(x / 0.7) >= np.asarray(
        sort_reference_threshold(x / 0.7, jnp.full((3,), 0.5))
    )[:, None]
    assert kept[np.arange(3), np.asarray(tokens)].all()


# ---------------------------------------------------------------------------
# the loop's sampler and its six programs
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=["dense", "paged"])
def recorded_loop(request):
    """A tiny loop that has run a plain and a grammar request, with the
    argument shapes of every program it dispatched."""
    from conftest import shared_engine
    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
    from k_llms_tpu.engine.grammar import grammar_for_schema, grammar_vocab
    from k_llms_tpu.engine.tokenizer import ByteTokenizer

    knobs = {"kv_layout": "paged", "kv_page_size": 16} if request.param == "paged" else {}
    loop = ContinuousDecodeLoop(
        shared_engine(model="tiny", **knobs), width=4, max_prompt=64, max_new=32
    )
    seen = {}

    def recording(build):
        def wrapped(grammar):
            fn = build(grammar)

            def call(*args, **state):  # the step takes the rows' recurrent state by keyword
                seen[fn.__name__] = (fn, jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))
                return fn(*args, **state)

            return call
        return wrapped

    loop._build_step = recording(loop._build_step)
    loop._build_first_token = recording(loop._build_first_token)
    tok = ByteTokenizer()
    schema = {"type": "object", "properties": {"ok": {"type": "boolean"}},
              "required": ["ok"], "additionalProperties": False}
    grammar = grammar_for_schema(schema, grammar_vocab(tok), vocab_digest="bytetok-test")
    try:
        loop.submit([1, 2, 3, 4, 5], n=2, max_new=3, temperature=0.8, top_p=0.95,
                    seed=3).result(timeout=120)
        loop.submit(tok.apply_chat_template([{"role": "user", "content": "x"}]), n=2,
                    max_new=3, temperature=0.8, top_p=0.95, seed=4,
                    grammar=grammar).result(timeout=120)
        yield request.param, loop, seen
    finally:
        loop.stop()


def test_no_loop_program_sorts_the_vocabulary(recorded_loop):
    layout, loop, seen = recorded_loop
    paged = "_paged" if layout == "paged" else ""
    assert set(seen) == {
        "_step" + paged, "_step" + paged + "_g", "_admit_sample", "_admit_g"
    }
    vocab = loop.engine.config.vocab_size
    for name, (fn, shapes) in seen.items():
        eqns = list(_equations(jax.make_jaxpr(fn)(*shapes).jaxpr))
        names = {e.primitive.name for e in eqns}
        over_vocab = {
            e.primitive.name for e in eqns
            if any(vocab in getattr(v.aval, "shape", ()) for v in e.invars)
        }
        assert "sort" not in names, name
        assert not over_vocab & {"cumsum", "cumlogsumexp", "top_k"}, (name, over_vocab)
        assert "reduce_sum" in over_vocab  # the search's masked reductions are seen
        # The nucleus search: the one scan that carries the [W] uint32 image,
        # NUCLEUS_SEARCH_TRIPS long; nothing in the sampler is data-dependent.
        searches = [
            e for e in eqns if e.primitive.name == "scan" and any(
                v.aval.dtype == jnp.uint32 and v.aval.shape == (loop.width,)
                for v in e.outvars)
        ]
        assert [e.params["length"] for e in searches] == [NUCLEUS_SEARCH_TRIPS], name
        if name.startswith("_admit"):
            assert "while" not in names, name


def test_loop_tokens_are_the_sort_samplers(recorded_loop):
    """The loop's ``_sample_rows`` against the sampler it replaced (the sort
    in place of the shared search, everything else to the letter), same keys:
    equal tokens, logprobs and poison verdicts, over mixed per-row
    temperatures and top_p, a greedy row, a masked row and a poisoned one."""
    _, loop, _ = recorded_loop
    _, sample_rows, _ = loop._sampler()
    rows, vocab = 8, loop.engine.config.vocab_size
    x = make_logits("normal", rows, vocab, seed=5) * 3
    x[5, 10:] = -np.inf  # a grammar mask's row
    x[6] = np.nan  # a poisoned slot: sanitised to all-equal, flagged
    temps = jnp.asarray([0.8, 0.8, 1.0, 0.3, 0.0, 0.8, 0.8, 1.5], jnp.float32)
    top_ps = jnp.asarray([0.95, 0.5, 1.0, 0.9, 0.95, 0.95, 0.95, 0.1], jnp.float32)
    new, old = jax.jit(sample_rows), jax.jit(sort_sample_rows)
    for step in range(24):
        keys = jax.vmap(
            lambda i: jax.random.fold_in(jax.random.fold_in(jax.random.key(11), step), i)
        )(jnp.arange(rows))
        got, want = new(jnp.asarray(x), keys, temps, top_ps), old(jnp.asarray(x), keys, temps, top_ps)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    assert bool(want[2][6]) and not bool(want[2][:6].any())
