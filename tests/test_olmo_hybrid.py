"""Olmo-Hybrid (models/hybrid.py's post-norm block: gated delta-rule layers with
negative eigenvalues beside full attention with QK-norm, 3 : 1, a SwiGLU MLP in
every block): the checks no other model has, against the plain reference
(benchmark/olmo_hybrid_reference.py) at the ``olmo-hybrid-tiny`` size on the
CPU, in float32. What every served model owes is tests/test_served_models.py's.

Tolerances. Both sides compute in float32, so what separates them is the order
of summation (the chunked WY form and its triangular solve against the
token-by-token recurrence, XLA's fusions), and this stack amplifies it: every
sublayer's output is RMS-normalised after the fact, so a small output's
rounding is scaled up with it. Against the same reference run in float64 the
program reads up to 9.2e-4 and the float32 reference itself 7.5e-4 (row 0,
position 70; medians 2e-5), on logits of magnitude ~2. So 2e-3 absolute is
twice the worst reading of either float32 side, and still 25 to 1,000 times
under what a missing term moves them by (beta without its factor 2, a state
that sees the padding or forgets its carry, a norm doubled: 0.05 to 2). The
delta rule alone, chunked against the recurrence on random inputs, is held to
1e-4 of its scale against the float64 recurrence (it reads ~1e-6).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import shared_params
from k_llms_tpu.models import hybrid, llama
from served_models import ROWS

TOL = 2e-3
ROW = ROWS["olmo-hybrid-tiny"]
CFG = ROW.cfg
N_D = CFG.layer_pattern.count("D")


@pytest.fixture(scope="module")
def params():
    return shared_params(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, 500, (2, 72)).astype(np.int32)


def state_leaves(state):
    return [np.asarray(a) for a in jax.tree.leaves(state)]


def close(got, want):
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL, rtol=0)


# -- the delta rule on its own -----------------------------------------------------------------

def recurrence64(q, k, v, beta, g, S0):
    """The rule written out in float64: o [B, S, H, dv], S_last."""
    S, o = S0.astype(np.float64), np.zeros(v.shape)
    for t in range(q.shape[1]):
        a = np.exp(g[:, t])[..., None, None]
        kS = np.einsum("bhk,bhkv->bhv", k[:, t], a * S)
        S = a * S + beta[:, t, :, None, None] * k[:, t, ..., None] * (v[:, t] - kS)[:, :, None, :]
        o[:, t] = np.einsum("bhkv,bhk->bhv", S, q[:, t])
    return o, S


def random_rule(S, seed, beta_max):
    rs = np.random.RandomState(seed)
    B_, H, dk, dv = 2, 3, 8, 12

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rs.randn(B_, S, H, dk)).astype(np.float32) * dk ** -0.5
    k = unit(rs.randn(B_, S, H, dk)).astype(np.float32)
    v = rs.randn(B_, S, H, dv).astype(np.float32)
    beta = rs.uniform(0, beta_max, (B_, S, H)).astype(np.float32)
    g = -rs.uniform(0, 0.5, (B_, S, H)).astype(np.float32)
    beta[1, S - 3:] = g[1, S - 3:] = 0.0  # padded positions: no write, no decay
    S0 = rs.randn(B_, H, dk, dv).astype(np.float32)
    return q, k, v, beta, g, S0


@pytest.mark.parametrize("S,block,beta_max", [(16, 16, 2.0), (48, 16, 2.0), (37, 16, 2.0),
                                              (128, 64, 2.0), (5, 8, 2.0), (40, 16, 1.0)])
def test_chunked_delta_rule_equals_the_recurrence(S, block, beta_max):
    """``delta_rule_chunked`` (sub-chunks of ``block``, the last one padded where
    S is no multiple) against the rule written out in float64, from a non-zero
    state, with beta up to 2 (a transition that flips the sign along k) and
    padding after position ``S - 3`` that must change nothing."""
    q, k, v, beta, g, S0 = random_rule(S, S, beta_max)
    assert beta_max < 2 or (beta > 1).mean() > 0.3
    o, last = hybrid.delta_rule_chunked(*map(jnp.asarray, (q, k, v, beta, g, S0)), block)
    want_o, want_S = recurrence64(q, k, v, beta, g, S0)
    scale = np.abs(want_o).max()
    np.testing.assert_allclose(np.asarray(o), want_o, atol=1e-4 * scale, rtol=0)
    np.testing.assert_allclose(np.asarray(last), want_S, atol=1e-4 * np.abs(want_S).max(), rtol=0)
    # The last three positions of row 1 are padding: its state is the one after S - 3 tokens.
    _, S_valid = recurrence64(*(a[:, :S - 3] for a in (q, k, v, beta, g)), S0)
    np.testing.assert_allclose(np.asarray(last)[1], S_valid[1], atol=1e-4 * np.abs(S_valid).max())


def test_one_token_step_equals_the_recurrence():
    q, k, v, beta, g, S0 = random_rule(1, 7, 2.0)
    o, S1 = hybrid.delta_rule_step(*map(jnp.asarray, (q[:, 0], k[:, 0], v[:, 0], beta[:, 0],
                                                      g[:, 0], S0)))
    want_o, want_S = recurrence64(q, k, v, beta, g, S0)
    np.testing.assert_allclose(np.asarray(o), want_o[:, 0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(S1), want_S, atol=1e-5, rtol=1e-5)


def test_beta_reaches_past_one_and_padding_leaves_the_state_and_tail_as_they_were(params):
    """What a D layer's update consumed: beta = 2 sigmoid(x W_b), past 1 for
    part of the heads (the factor 2 is ``linear_allow_neg_eigval``; without it
    the forward leaves the reference by ~0.5); a row with no valid position
    keeps its state and its conv tail bit for bit; a row's padded positions
    leave the tail at its last three valid inputs."""
    layer = params["layers"][0]
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(3, 24, CFG.hidden_size), jnp.float32)
    S = jnp.asarray(rs.randn(3, 4, 32, 64), jnp.float32)
    tail = jnp.asarray(rs.randn(3, 3, CFG.gdn_conv_dim), jnp.float32)
    valid = jnp.asarray([24, 0, 10])
    _, S1, tail1, seen = hybrid.gdn_mixer(CFG, layer, x, S, tail, valid)
    b = np.asarray(jnp.dot(x, layer["ab_proj"])[..., 4:])
    np.testing.assert_allclose(np.asarray(seen["beta"])[0], 2 / (1 + np.exp(-b[0])), rtol=1e-5)
    assert (np.asarray(seen["beta"])[0] > 1).mean() > 0.3
    np.testing.assert_array_equal(np.asarray(S1)[1], np.asarray(S)[1])
    np.testing.assert_array_equal(np.asarray(tail1)[1], np.asarray(tail)[1])
    assert not np.array_equal(np.asarray(S1)[2], np.asarray(S)[2])
    qkv = np.asarray(jnp.dot(x, layer["in_proj"]))[2, 7:10, :CFG.gdn_conv_dim]
    np.testing.assert_allclose(np.asarray(tail1)[2], qkv, atol=1e-5, rtol=1e-5)  # inputs 7, 8, 9: the last valid
    # Padding past valid_len is neither written nor decayed: the state after 10
    # valid positions of 24 is the state after those 10 alone.
    _, S10, tail10, _ = hybrid.gdn_mixer(CFG, layer, x[:, :10], S, tail, jnp.asarray([10, 10, 10]))
    np.testing.assert_allclose(np.asarray(S1)[2], np.asarray(S10)[2], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(tail1)[2], np.asarray(tail10)[2], atol=1e-5, rtol=1e-5)
    # One token: the step's form, idle rows bit for bit.
    _, S2, tail2, _ = hybrid.gdn_mixer(CFG, layer, x[:, :1], S, tail, jnp.asarray([1, 0, 1]))
    np.testing.assert_array_equal(np.asarray(S2)[1], np.asarray(S)[1])
    np.testing.assert_array_equal(np.asarray(tail2)[1], np.asarray(tail)[1])


# -- the program against the reference ---------------------------------------------------------

@jax.jit
def whole_prefill(params, padded, plen):
    whole = {}
    return llama.prefill(CFG, params, padded, plen, state=whole)[0], whole


@jax.jit
def chunk_step(params, toks, cache, start, valid, state):
    state = dict(state)
    return llama.prefill_chunk_step(CFG, params, toks, cache, start, valid, state=state) + (state,)


@pytest.mark.parametrize("plen,chunk", [(50, 16), (33, 32), (64, 16), (100, 64)])
def test_chunked_prefill_equals_whole_prefill_and_the_reference_state(params, tokens, plen, chunk):
    """Same logits, same delta-rule state, same conv tail, whichever way the
    prompt came in: in chunks (the last one padded, or exactly full) or whole
    at its padded bucket; and the state is the reference recurrence's after
    the prompt. The tail is the last three *valid* conv inputs."""
    seq = np.concatenate([tokens[0], tokens[1]])
    bucket = 128
    padded = np.pad(seq[None, :plen], ((0, 0), (0, bucket - plen)))
    want, whole = whole_prefill(params, jnp.asarray(padded), jnp.int32(plen))
    cache, state = llama.init_cache(CFG, 1, bucket), llama.init_state(CFG, 1)
    for start in range(0, plen, chunk):
        valid = min(chunk, plen - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :valid] = seq[start:start + valid]
        got, cache, state = chunk_step(params, jnp.asarray(toks), cache, jnp.int32(start),
                                       jnp.int32(valid), state)
    close(got, np.asarray(want))
    assert set(state) == set(whole) == {"gdn", "gdn_conv"} and len(state["gdn"]) == N_D
    for a, b in zip(state_leaves(state), state_leaves(whole)):  # they read up to 1.4e-4
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=0)
    assert state["gdn"][0].dtype == jnp.float32 and state["gdn"][0].shape == (1, 4, 32, 64)
    assert state["gdn_conv"][0].shape == (1, CFG.linear_conv_kernel - 1, CFG.gdn_conv_dim)
    states = []  # the reference's one call that stays eager: it hands its states out by a list
    ROW.ref_call(params, seq[:plen], positions=[plen - 1], states=states)
    for mine, theirs in zip(state["gdn"], states):  # entries of magnitude ~1; they read 1.4e-4
        np.testing.assert_allclose(np.asarray(mine[0]), np.asarray(theirs), atol=5e-4, rtol=0)
