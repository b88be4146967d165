"""On-device token sampling with logprob capture.

The n consensus samples are one batched categorical draw: per-sample RNG keys
(folded from the request seed) make the samples diverse yet reproducible —
covering the reference's `seed` pass-through
(`/root/reference/k_llms/resources/completions/completions.py:57-58`) that the
OpenAI backend only best-effort honors. The logprob of every emitted token is
captured from the UNtempered distribution (that is what OpenAI's `logprobs`
reports) and feeds the likelihood-weighted consensus mode.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# One trip of the nucleus search per bit of a float32.
NUCLEUS_SEARCH_TRIPS = 32


def _ordered_bits(x: jax.Array) -> jax.Array:
    """The order-preserving uint32 image of float32: ``a < b`` iff
    ``image(a) < image(b)``, with -0.0 and +0.0 on one value (they compare
    equal as floats, so they must as integers)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))


def _from_ordered_bits(u: jax.Array) -> jax.Array:
    bits = jnp.where(u >> 31 == 1, u & jnp.uint32(0x7FFFFFFF), ~u)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def nucleus_threshold(scaled: jax.Array, top_p: jax.Array) -> jax.Array:
    """Each row's top-p boundary logit: ``{scaled >= result}`` is the smallest
    set, taken in descending logit order, whose softmax mass reaches the row's
    ``top_p`` (the boundary token and its equal-logit ties stay in; a row
    always keeps its largest logit, so ``top_p`` 0 is top-1 and 1.0 keeps
    everything of measurable mass). scaled: [B, V] f32 with no NaN (``-inf``
    is a masked token); top_p: [B] f32. Returns [B] f32.

    No sort: ``mass({scaled >= t})`` is monotone in ``t``, so the largest
    ``t`` whose set still reaches ``top_p`` is found by bisection, and that
    ``t`` is a logit of the row — the one a descending sort with a cumulative
    sum would stop at. The bisection runs over the integer image of the
    floats, one bit a trip from the top: ``NUCLEUS_SEARCH_TRIPS`` masked
    reductions over [B, V] whatever the data (a float-midpoint bracket needs
    25–35 and, where it closes on zero, ~126, with every row waiting for the
    slowest). Mass is summed in vocabulary order where a sort sums in sorted
    order, so a row whose cumulative mass lies within float32 rounding of
    ``top_p`` at the boundary may keep one tie group more or less: either set
    is a nucleus.
    """
    keys = _ordered_bits(scaled)
    row_max = jnp.max(scaled, axis=-1)
    weights = jnp.exp(scaled - row_max[:, None])  # unnormalised probabilities
    need = top_p * jnp.sum(weights, axis=-1)

    def _trip(i, t):
        cand = t | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        mass = jnp.sum(jnp.where(keys >= cand[:, None], weights, 0.0), axis=-1)
        return jnp.where(mass >= need, cand, t)

    t = jax.lax.fori_loop(
        0, NUCLEUS_SEARCH_TRIPS, _trip, jnp.zeros(scaled.shape[:1], jnp.uint32)
    )
    # A nucleus holds the row's top logit (top_p 0: nothing else), and a
    # search that never found its mass (top_p above the summed mass by
    # rounding) keeps the whole row: -inf, not the NaN that image 0 decodes to.
    t = jnp.clip(t, _ordered_bits(jnp.float32(-jnp.inf)), _ordered_bits(row_max))
    return _from_ordered_bits(t)


def sample_logits(
    logits: jax.Array,
    key: Optional[jax.Array],
    temperature: float = 1.0,
    top_p: Optional[float] = None,
    top_k: Optional[int] = None,
    row_keys: Optional[jax.Array] = None,
    penalty: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Sample next tokens. logits: [B, V] f32; key: one PRNG key, folded per row.
    ``row_keys`` ([B] typed keys) overrides the internal per-row fold — the
    coalesced multi-request decode path derives each row's key from its OWN
    request seed so per-request draws don't depend on batch composition.
    ``penalty`` ([B, V] f32) is subtracted from the logits BEFORE temperature
    (OpenAI's frequency/presence formula: mu[j] - c[j]*a_freq - 1{c}*a_pres);
    it shapes the sampling distribution only — reported logprobs stay the
    unpenalized model distribution's.

    Returns (tokens [B] int32, logprobs [B] f32 — log p(token) under the
    untempered model distribution).
    """
    B, V = logits.shape
    # Failure tolerance: a sample whose logits went non-finite (overflow in a
    # bad checkpoint, etc.) must not poison the batch — sanitize to a uniform
    # distribution for that row; the consensus layer then simply outvotes it.
    finite = jnp.isfinite(logits)
    row_ok = jnp.any(finite, axis=-1, keepdims=True)
    logits = jnp.where(finite, logits, -jnp.inf)
    logits = jnp.where(row_ok, logits, 0.0)
    model_logprobs = jax.nn.log_softmax(logits, axis=-1)
    if penalty is not None:
        logits = logits - penalty

    if temperature == 0.0:
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        sampling_logits = logits / temperature

        if top_k is not None and top_k < V:
            kth = jnp.sort(sampling_logits, axis=-1)[:, V - top_k][:, None]
            sampling_logits = jnp.where(sampling_logits < kth, -jnp.inf, sampling_logits)

        if top_p is not None and top_p < 1.0:
            # The smallest set with cumulative mass >= top_p, boundary token
            # and its equal-logit ties in: the loop's search, one top_p a row.
            threshold = nucleus_threshold(
                sampling_logits, jnp.full((B,), top_p, jnp.float32)
            )
            sampling_logits = jnp.where(
                sampling_logits >= threshold[:, None], sampling_logits, -jnp.inf
            )

        if row_keys is None:
            keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, jnp.arange(B))
        else:
            keys = row_keys
        tokens = jax.vmap(lambda k, l: jax.random.categorical(k, l))(keys, sampling_logits)
        tokens = tokens.astype(jnp.int32)

    logprobs = jnp.take_along_axis(model_logprobs, tokens[:, None], axis=-1)[:, 0]
    return tokens, logprobs


def model_top_logprobs(
    logits: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array]:
    """Top-k alternatives under the UNtempered model distribution (what
    OpenAI's ``top_logprobs`` reports), with the same non-finite-row
    sanitization as :func:`sample_logits`. logits: [B, V] f32.

    Returns (token ids [B, k] int32, logprobs [B, k] f32, sorted desc).
    """
    finite = jnp.isfinite(logits)
    row_ok = jnp.any(finite, axis=-1, keepdims=True)
    logits = jnp.where(finite, logits, -jnp.inf)
    logits = jnp.where(row_ok, logits, 0.0)
    lps = jax.nn.log_softmax(logits, axis=-1)
    top_lps, top_ids = jax.lax.top_k(lps, k)
    return top_ids.astype(jnp.int32), top_lps
