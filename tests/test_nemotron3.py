"""Nemotron-3 (models/hybrid.py): Mamba-2 layers with recurrent state beside
the pages, a per-layer pattern of mixers, non-gated relu^2 experts — the
program against the plain reference (tests/nemotron3_reference.py, the
benchmark's copy byte for byte) at the ``nemotron3-tiny`` size on the CPU, in
float32.

Tolerances. Both sides compute in float32 here, so what separates them is the
order of summation (the chunked scan against the token-by-token recurrence,
grouped products against a loop over experts, XLA's fusions): 1e-4 absolute on
logits of magnitude ~0.8 is twenty times what the comparisons read (2e-6 to
5e-6) and a thousand times under what a missing term gives (a dropped D x, a
state that forgets its carry or sees the padding moves logits by 0.1 to 4).
"""

import filecmp
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nemotron3_reference as ref
from conftest import shared_engine, shared_params
from k_llms_tpu.models import get_config, hybrid, latent, llama
from k_llms_tpu.models.llama import KVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4
CFG = get_config("nemotron3-tiny")
N_M = CFG.layer_pattern.count("M")


def hf_dict(c):
    """The preset in the published config.json's own key names."""
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_heads,
        num_key_value_heads=c.num_kv_heads, head_dim=c.head_dim,
        mamba_num_heads=c.mamba_num_heads, mamba_head_dim=c.mamba_head_dim,
        n_groups=c.mamba_n_groups, ssm_state_size=c.ssm_state_size,
        conv_kernel=c.mamba_conv_kernel, chunk_size=c.mamba_chunk, layer_norm_epsilon=c.rms_eps,
        n_routed_experts=c.num_experts, num_experts_per_tok=c.num_experts_per_tok,
        moe_intermediate_size=c.moe_intermediate_size,
        moe_shared_expert_intermediate_size=c.moe_shared_intermediate_size,
        routed_scaling_factor=c.routed_scaling_factor, norm_topk_prob=True,
        hybrid_override_pattern=c.layer_pattern, num_hidden_layers=c.num_layers,
        vocab_size=c.vocab_size, time_step_min=c.time_step_min, time_step_max=c.time_step_max,
        time_step_floor=c.time_step_floor,
    )


@pytest.fixture(scope="module")
def params():
    return shared_params(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, 500, (2, 72)).astype(np.int32)


@pytest.fixture(scope="module")
def reference_logits(params, tokens):
    return [np.asarray(ref.forward(hf_dict(CFG), params, tokens[b])) for b in range(2)]


def state_leaves(state):
    return [np.asarray(a) for a in jax.tree.leaves(state)]


# -- the program against the reference ---------------------------------------------

@pytest.mark.parametrize("row", [0, 1])
def test_forward_matches_reference(params, tokens, reference_logits, row):
    logits, _ = llama.forward(CFG, params, jnp.asarray(tokens), jnp.ones(tokens.shape, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[row]), reference_logits[row], atol=TOL, rtol=0)


@pytest.mark.parametrize("S,block", [(16, 16), (48, 16), (37, 16), (128, 128), (5, 8)])
def test_chunked_scan_equals_the_sequential_recurrence(S, block):
    """``ssd_scan`` (blocks of ``block``, the last one padded where S is no
    multiple) against the recurrence written out, from a non-zero state, with
    padding after position ``S - 3`` that neither may see."""
    rs = np.random.RandomState(S)
    B_, G, R, P, N = 2, 2, 3, 4, 8
    x = rs.randn(B_, S, G, R, P).astype(np.float32)
    dt = np.abs(rs.randn(B_, S, G, R)).astype(np.float32) * 0.3
    dt[1, S - 3:] = 0.0  # padded positions: no decay, no input
    A = -np.abs(rs.randn(G, R)).astype(np.float32) - 0.1
    Bm, Cm = rs.randn(B_, S, G, N).astype(np.float32), rs.randn(B_, S, G, N).astype(np.float32)
    S0 = rs.randn(B_, G, R, P, N).astype(np.float32)
    y, last = hybrid.ssd_scan(*map(jnp.asarray, (x, dt, A, Bm, Cm, S0)), block)
    state, want = S0.astype(np.float64), np.zeros((B_, S, G, R, P))
    for t in range(S):
        state = (np.exp(dt[:, t] * A)[..., None, None] * state
                 + (dt[:, t, ..., None] * x[:, t])[..., None] * Bm[:, t, :, None, None, :])
        want[:, t] = np.einsum("bgrpn,bgn->bgrp", state, Cm[:, t])
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(last), state, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_prefill_then_paged_decode_matches_reference(params, tokens, reference_logits, chunk):
    """The loop's two programs by hand: chunks into a staging cache, a page
    pool and the lane's state (every chunk's last-token logits compared; the
    last chunk is padded), then ``paged_verify_step`` with three rows reading
    the shared prompt pages, each from its own copy of the state; the third row
    is idle and must neither move nor be right."""
    want, seq, plen, ps, rows = reference_logits[1], tokens[1], 50, 8, 3
    bucket, flat = 64, 40 * 8
    cache, state = llama.init_cache(CFG, 1, bucket), llama.init_state(CFG, 1)
    assert cache.k.shape == (1, 1, bucket, CFG.num_kv_heads, CFG.head_dim)
    pool = KVCache(k=jnp.zeros((1, flat, 2, 16)), v=jnp.zeros((1, flat, 2, 16)))
    for start in range(0, plen, chunk):
        valid = min(chunk, plen - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :valid] = seq[start:start + valid]
        aux, state = {}, dict(state)
        logits, cache, k_cols, v_cols = llama.prefill_chunk_step_paged(
            CFG, params, jnp.asarray(toks), cache, jnp.int32(start), jnp.int32(valid),
            aux=aux, state=state)
        assert aux["moe_counts"].shape == (4, CFG.num_experts)
        assert int(aux["moe_counts"].sum()) == 4 * chunk * CFG.num_experts_per_tok
        assert int(aux["ssm_rows_updated"]) == N_M and int(aux["ssm_tokens_scanned"]) == N_M * valid
        np.testing.assert_allclose(
            np.asarray(logits[0]), want[start + valid - 1], atol=TOL, rtol=0)
        slots = ps + start + np.arange(valid)  # page 0 is the trash page
        pool = KVCache(k=pool.k.at[:, slots].set(k_cols[:, :valid]),
                       v=pool.v.at[:, slots].set(v_cols[:, :valid]))
    assert k_cols.shape == (1, chunk, 2, 16)  # one paging layer of nine
    P, G = 64, 8
    pidx = np.tile(np.arange(P) % ps, (rows, 1)).astype(np.int32)
    pidx[:, :plen] = ps + np.arange(plen)
    gidx = np.stack([100 + 10 * r + np.arange(G) for r in range(rows)]).astype(np.int32)
    state = jax.tree.map(lambda a: jnp.repeat(a, rows, axis=0), state)
    active = jnp.asarray([True, True, False])
    for step in range(5):
        aux, before = {}, state_leaves(state)
        state = dict(state)
        logits, k_cols, v_cols = llama.paged_verify_step(
            CFG, params, jnp.full((rows, 1), seq[plen + step]), jnp.full((rows,), step),
            jnp.full((rows,), plen), pool, jnp.asarray(pidx), jnp.asarray(gidx), aux=aux,
            state=state, active=active)
        assert int(aux["ssm_rows_updated"]) == 2 * N_M
        pool = KVCache(k=pool.k.at[:, gidx[:, step]].set(k_cols), v=pool.v.at[:, gidx[:, step]].set(v_cols))
        for row in range(2):
            np.testing.assert_allclose(
                np.asarray(logits[row, 0]), want[plen + step], atol=TOL, rtol=0)
        for old, new in zip(before, state_leaves(state)):
            np.testing.assert_array_equal(old[2], new[2])  # the idle row's state, bit for bit
            assert not np.array_equal(old[0], new[0])
    assert np.abs(np.asarray(logits[2, 0]) - want[plen + 4]).max() > 0.1  # it stopped at the prompt


@pytest.mark.parametrize("plen,chunk", [(50, 16), (33, 32), (64, 16)])
def test_chunked_prefill_with_a_padded_final_chunk_equals_whole_prefill(params, tokens, plen, chunk):
    """Same logits, same SSM state, same conv tail, whichever way the prompt
    came in: in chunks (the last one padded, or exactly full) or whole at its
    padded bucket. The tail is the last three *valid* conv inputs."""
    seq, bucket = tokens[0], 64
    padded = np.pad(seq[None, :plen], ((0, 0), (0, bucket - plen)))
    whole = {}
    want, _ = llama.prefill(CFG, params, jnp.asarray(padded), jnp.int32(plen), state=whole)
    cache, state = llama.init_cache(CFG, 1, bucket), llama.init_state(CFG, 1)
    for start in range(0, plen, chunk):
        valid = min(chunk, plen - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :valid] = seq[start:start + valid]
        state = dict(state)
        got, cache = llama.prefill_chunk_step(
            CFG, params, jnp.asarray(toks), cache, jnp.int32(start), jnp.int32(valid), state=state)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL, rtol=0)
    assert set(state) == set(whole) == {"ssm", "conv"} and len(state["ssm"]) == N_M
    for a, b in zip(state_leaves(state), state_leaves(whole)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=TOL, rtol=0)
    assert state["ssm"][0].dtype == jnp.float32 and state["conv"][0].shape == (
        1, CFG.mamba_conv_kernel - 1, hybrid.conv_dim(CFG))


def test_reference_given_the_programs_routing_agrees_and_measures_its_slack(params, tokens, reference_logits):
    seq = tokens[0]
    aux = {"moe_chosen": None}
    padded = np.pad(seq[None], ((0, 0), (0, 128 - len(seq))))
    llama.prefill(CFG, params, jnp.asarray(padded), jnp.int32(len(seq)), aux=aux)
    chosen = np.asarray(aux["moe_chosen"])[:, :len(seq)]
    assert chosen.shape == (4, len(seq), CFG.num_experts_per_tok)
    slacks = []
    got = ref.forward(hf_dict(CFG), params, seq, given=chosen, slacks=slacks, positions=[5, 40])
    np.testing.assert_allclose(np.asarray(got), reference_logits[0][[5, 40]], atol=TOL, rtol=0)
    assert len(slacks) == 4 and all(float(jnp.max(jnp.abs(s))) == 0.0 for s in slacks)
    off = chosen.copy()
    off[0, 7, 0] = next(e for e in range(CFG.num_experts) if e not in chosen[0, 7])
    slacks = []
    ref.forward(hf_dict(CFG), params, seq, given=off, slacks=slacks)
    assert float(slacks[0][7]) > 0 and float(jnp.max(jnp.abs(slacks[0][:7]))) == 0.0


# -- through the engine and the continuous loop ------------------------------------------

def make_loop(width=8, chunk=32, ladder=()):
    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop

    engine = shared_engine("nemotron3-tiny", kv_layout="paged", kv_page_size=16)
    return ContinuousDecodeLoop(engine, width=width, max_prompt=256, max_new=16, eos_ids=[257],
                                prefill_chunk_tokens=chunk, prefill_chunk_ladder=ladder)


@pytest.mark.parametrize("plen,ladder,prefill_calls", [
    (100, (), 4), (20, (), 1), (200, (32, 64, 128), 2), (161, (32, 64, 128), 2)])
def test_n8_forks_one_state_into_eight_rows_that_diverge(params, plen, ladder, prefill_calls):
    """n = 8 sampled rows of one prompt (100 tokens: four chunks, the last
    padded; 20: whole-prompt admission; 200 and 161 on the ladder: a turn of
    128, then the state carried into a padded turn of 128 or of 64): pages are
    shared, the state is forked, the rows take different tokens — and every
    row's log-probabilities are the reference's along that row's own tokens.
    The counters add up."""
    from k_llms_tpu.utils.observability import LATENCY, MODEL_COUNTERS

    prompt = [int(t) for t in np.random.RandomState(1).randint(0, 250, plen)]
    loop = make_loop(ladder=ladder)
    before, installs = MODEL_COUNTERS.snapshot(), LATENCY.snapshot()["continuous.state_install"]["count"]
    try:
        got = loop.submit(prompt, n=8, max_new=10, temperature=1.0, top_p=1.0,
                          seed=5).result(timeout=300)
        stats = loop.stats
    finally:
        loop.stop()
    toks, lps = np.asarray(got.tokens)[:, :10], np.asarray(got.logprobs)[:, :10]
    assert len({tuple(r) for r in toks.tolist()}) > 4  # they diverged
    for row in range(8):
        seq = np.asarray(prompt + toks[row].tolist(), np.int32)
        logits = ref.forward(hf_dict(CFG), params, seq, positions=plen - 1 + np.arange(10))
        logits = logits.at[:, CFG.pad_token_id].set(-jnp.inf)  # the loop never samples pad
        want = np.asarray(jax.nn.log_softmax(logits, axis=-1))[np.arange(10), toks[row]]
        np.testing.assert_allclose(lps[row], want, atol=5e-4, rtol=0)
    grew = {k: v - before.get(k, 0) for k, v in MODEL_COUNTERS.snapshot().items()}
    # kllms_ssm_state_updates: rows x 4 a step, one row x 4 a chunk or whole prompt.
    assert grew["ssm_state_updates"] == (prefill_calls + 9 * 8) * N_M
    assert grew["ssm_tokens_scanned"] == (plen + 9 * 8) * N_M
    assert grew["moe_layer_calls"] == (prefill_calls + 9) * 4
    assert grew.get("mla_latent_rows_read", 0) == 0
    assert LATENCY.snapshot()["continuous.state_install"]["count"] == installs + 1
    assert stats["state_bytes"] == 8 * CFG.state_bytes_per_row and stats["pages"]["in_use"] == 0


def test_an_idle_slots_state_is_unchanged_and_a_reused_slot_equals_a_fresh_one():
    """A release does no device work, so a slot keeps its last tenant's state
    until admission overwrites it: the steps of a later request move its own
    slot's state and no other's, and what it generates on a used loop is what
    it generates on a fresh one, bit for bit."""
    rs = np.random.RandomState(2)
    first = [int(t) for t in rs.randint(0, 250, 70)]
    second = [int(t) for t in rs.randint(0, 250, 45)]
    kw = dict(max_new=8, temperature=0.7, top_p=0.9)
    fresh = make_loop()
    try:
        want = fresh.submit(second, n=1, seed=11, **kw).result(timeout=300)
    finally:
        fresh.stop()
    loop = make_loop()
    try:
        loop.submit(first, n=8, seed=3, **kw).result(timeout=300)  # every slot has had a tenant
        used = state_leaves(loop._state)
        got = loop.submit(second, n=1, seed=11, **kw).result(timeout=300)
        after = state_leaves(loop._state)
    finally:
        loop.stop()
    np.testing.assert_array_equal(np.asarray(got.tokens), np.asarray(want.tokens))
    np.testing.assert_array_equal(np.asarray(got.logprobs), np.asarray(want.logprobs))
    for old, new in zip(used, after):
        moved = [slot for slot in range(8) if not np.array_equal(old[slot], new[slot])]
        assert len(moved) == 1  # the one slot the second request took; seven idle ones untouched
        assert np.abs(old).max() > 0


@pytest.mark.parametrize("model", ["tiny", "xing4-tiny", "nemotron3-tiny"])
def test_a_model_without_recurrent_state_adds_no_operand_to_the_step_program(model):
    """The loop passes its state pytree to every step and chunk program; for a
    model without state-space layers that pytree is empty, so the programs have
    the operands and results they had (a new operand would make every cell pay
    a compile): counted on the traced programs themselves."""
    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop

    engine = shared_engine(model, kv_layout="paged", kv_page_size=16)
    loop = ContinuousDecodeLoop(engine, width=4, max_prompt=64, max_new=16, eos_ids=[257],
                                prefill_chunk_tokens=32)
    try:
        loop._build_device_state()
        W = 4
        packed = jnp.asarray(loop._pack_step(np.zeros((W, 1), np.int32), loop._pages.tables))
        step = jax.make_jaxpr(loop._step_fn)(
            engine.params, loop._pool.kv.k, loop._pool.kv.v, packed,
            jnp.zeros((W,), bool), state=loop._state)
        chunk = jax.make_jaxpr(engine._get_prefill_chunk(32, 64, True))(
            engine.params, jnp.zeros((1, 32), jnp.int32), llama.init_cache(engine.config, 1, 64),
            jnp.int32(0), jnp.int32(3), state=llama.init_state(engine.config, 1))
        n_params = len(jax.tree.leaves(engine.params))
        n_state = 2 * engine.config.layer_pattern.count("M")
        # The pool's pair, the state, the step's one packed array, the poison mask.
        assert len(step.jaxpr.invars) == n_params + 2 + n_state + 1 + 1
        assert len(chunk.jaxpr.invars) == n_params + 1 + 2 + n_state + 2
        if model != "nemotron3-tiny":
            assert loop._state == {}
            assert loop.stats["state_bytes"] == 0 and n_state == 0
        else:
            assert n_state == 8 and len(jax.tree.leaves(loop._state)) == 8
    finally:
        loop.stop()


# -- the mechanisms one by one ----------------------------------------------------------------

def all_experts_oracle(cfg, layer, h, chosen, w):
    """Every token through every expert, combined by a [T, E] weight: gated
    silu(gate) * up where the layer has a gate, relu(up)^2 where it has not."""
    combine = np.zeros((h.shape[0], cfg.num_experts), np.float32)
    for t in range(h.shape[0]):
        for k in range(chosen.shape[1]):
            combine[t, chosen[t, k]] += w[t, k]
    up = jnp.einsum("th,ehi->tei", h, layer["w_up"])[..., :layer["w_down"].shape[-2]]
    if "w_gate" in layer:
        act = jax.nn.silu(jnp.einsum("th,ehi->tei", h, layer["w_gate"])) * up
    else:
        act = jnp.square(jax.nn.relu(up))
    out = jnp.einsum("tei,eih->teh", act, layer["w_down"])
    return np.einsum("teh,te->th", np.asarray(out), combine)


@pytest.mark.parametrize("model,case", [("nemotron3-tiny", "random"), ("nemotron3-tiny", "ties"),
                                        ("nemotron3-tiny", "one_token"), ("xing4-tiny", "random"),
                                        ("xing4-tiny", "ties")])
def test_one_grouped_product_function_serves_gated_and_non_gated_experts(model, case):
    """``latent.routed_experts`` is the package's one router + grouped products:
    the hybrid stack's layer has no ``w_gate`` and takes the relu^2 form, the
    latent block's has one and takes the gated form; both against the oracle."""
    cfg, rs = get_config(model), np.random.RandomState(3)
    p = shared_params(cfg)
    if model == "xing4-tiny":
        layer = {k: v[0] for k, v in p["layers"].items()}
    else:
        layer = dict(p["layers"][1])
        assert "w_gate" not in layer and layer["w_up"].shape[-1] == hybrid.expert_columns(cfg) == 128
        assert not np.asarray(layer["w_up"][..., cfg.moe_intermediate_size:]).any()
    T = 1 if case == "one_token" else 24
    h = jnp.asarray(rs.randn(T, cfg.hidden_size), jnp.float32)
    if case == "ties":  # equal scores everywhere: top-k must take the lowest ids
        layer["w_router"] = jnp.zeros_like(layer["w_router"])
    out, counts, chosen = latent.routed_experts(cfg, layer, h)
    # The other form of the same layer: every expert for every token, taken
    # whenever a token is routed at all (share 0), never (2), and by the count.
    for share in (0.0, 2.0, hybrid.DENSE_SHARE):
        whole, counts_again, _ = latent.routed_experts(cfg, layer, h, dense_share=share)
        np.testing.assert_allclose(np.asarray(whole), np.asarray(out), atol=2e-5, rtol=0)
        np.testing.assert_array_equal(np.asarray(counts_again), np.asarray(counts))
    chosen_again, w = latent.route(cfg, layer, h)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen_again))
    np.testing.assert_allclose(np.asarray(w).sum(-1), cfg.routed_scaling_factor, rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(np.asarray(chosen).reshape(-1), minlength=cfg.num_experts))
    if case == "ties":
        assert (np.sort(np.asarray(chosen)) == np.arange(cfg.num_experts_per_tok)).all()
    np.testing.assert_allclose(
        np.asarray(out), all_experts_oracle(cfg, layer, h, np.asarray(chosen), np.asarray(w)),
        atol=2e-5, rtol=0)
    if model == "nemotron3-tiny":
        ref_chosen, ref_w, slack = ref.route(hf_dict(cfg), layer, h)
        assert float(jnp.max(jnp.abs(slack))) == 0.0
        np.testing.assert_array_equal(np.sort(np.asarray(chosen)), np.sort(np.asarray(ref_chosen)))
        want = ref.experts(hf_dict(cfg), layer, h) - ref.relu2(h @ layer["ws_up"]) @ layer["ws_down"]
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=0)


def test_seeded_state_space_constants_follow_the_published_initialisation(params):
    layer = params["layers"][0]
    dt = np.asarray(jax.nn.softplus(layer["dt_bias"]))
    assert (dt >= CFG.time_step_floor).all() and (dt >= 0.000999).all() and (dt <= 0.1001).all()
    A = np.exp(np.asarray(layer["A_log"]))
    assert (A >= 1).all() and (A <= 16).all() and (np.asarray(layer["D"]) == 1).all()
    assert layer["in_proj"].shape == (CFG.hidden_size, 2 * 128 + 2 * 2 * 32 + 8)
    assert "mlp_norm" not in params["layers"][5] and "w_gate" not in params["layers"][1]


def test_attention_takes_no_positions(params):
    """No rotary embedding: the attention layer's q and k do not depend on
    where the tokens sit."""
    layer = params["layers"][5]
    x = jnp.asarray(np.random.RandomState(4).randn(1, 6, CFG.hidden_size), jnp.float32)
    a = llama._attn_qkv(CFG, layer, x, jnp.arange(6)[None])
    b = llama._attn_qkv(CFG, layer, x, 100 + jnp.arange(6)[None])
    for p, q in zip(a, b):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(q))
    assert not CFG.use_rope and get_config("tiny").use_rope


# -- shapes, bytes and refusals -----------------------------------------------------------------

@pytest.mark.parametrize("name,want", [("nemotron3-nano-30b-a3b", 31.58e9),
                                       ("nemotron3-nano-30b-a3b-cut9", 6.073e9)])
def test_parameter_count_from_shapes(name, want):
    assert abs(hybrid.param_count(get_config(name)) - want) / want < 0.001


@pytest.mark.parametrize("name,m,e,a", [("nemotron3-nano-30b-a3b", 23, 23, 6),
                                        ("nemotron3-nano-30b-a3b-cut9", 4, 4, 1),
                                        ("nemotron3-tiny", 4, 4, 1)])
def test_the_pattern_says_which_layers_page_and_which_hold_state(name, m, e, a):
    cfg = get_config(name)
    pattern = cfg.layer_pattern
    assert len(pattern) == cfg.num_layers and set(pattern) == set("ME*")
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (m, e, a)
    assert cfg.is_hybrid and cfg.paging_layers == a
    shapes = cfg.state_shapes(3)
    assert shapes["ssm"] == (m, (3, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size),
                             jnp.dtype("float32"))
    assert shapes["conv"][:2] == (m, (3, cfg.mamba_conv_kernel - 1, hybrid.conv_dim(cfg)))
    kinds = [sorted(layer) for layer in jax.eval_shape(
        lambda: hybrid.init_params(cfg.with_(vocab_size=512), jax.random.key(0)))["layers"]]
    assert ["in_proj" in k for k in kinds] == [c == "M" for c in pattern]
    assert ["w_router" in k for k in kinds] == [c == "E" for c in pattern]
    assert ["wq" in k for k in kinds] == [c == "*" for c in pattern]


def test_a_pattern_that_does_not_fit_its_depth_is_refused():
    with pytest.raises(ValueError, match="layer_pattern"):
        hybrid.init_params(CFG.with_(num_layers=8), jax.random.key(0))
    with pytest.raises(ValueError, match="layer kind"):
        hybrid.init_params(CFG.with_(layer_pattern="MEMEM-EME"), jax.random.key(0))


def test_published_preset_holds_the_published_sizes():
    with open(os.path.join(ROOT, "benchmark", "configs", "nemotron3-nano-30b-a3b.json")) as f:
        hf = json.load(f)
    full, cut = get_config("nemotron3-nano-30b-a3b"), get_config(hf["serve"]["model"])
    assert hf_dict(cut) == {k: hf[k] for k in hf_dict(cut)}
    assert (full.num_layers, full.layer_pattern) == (
        hf["published"]["num_hidden_layers"], hf["published"]["hybrid_override_pattern"])
    assert full.layer_pattern.startswith(cut.layer_pattern)
    assert full.with_(name=cut.name, num_layers=9, layer_pattern="MEMEM*EME") == cut
    assert hf["reduced"] == ["num_hidden_layers", "hybrid_override_pattern"]
    assert hf["expand"] * hf["hidden_size"] == 5376 != cut.mamba_num_heads * cut.mamba_head_dim == 4096
    assert cut.attn_scale == pytest.approx(128 ** -0.5) and cut.dtype == "bfloat16"
    catalog = os.path.join("/opt/skills/guides/model-configs/architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == hf["source"])
        changed = {k for k, v in row["config"].items() if hf.get(k) != v}
        assert changed == set(hf["reduced"])


@pytest.mark.parametrize("name,per_token,per_row", [
    ("nemotron3-nano-30b-a3b-cut9", 1 * 2 * 2 * 128 * 2, 4 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)),
    ("nemotron3-tiny", 1 * 2 * 2 * 16 * 4, 4 * (8 * 16 * 32 * 4 + 3 * 256 * 4)),
    ("qwen2-7b", 2 * 28 * 512 * 2, 0),
    ("xing4-tiny", 3 * 128 * 4, 0)])  # a latent row of 40 is stored in a whole tile of 128 lanes
def test_pool_and_memory_model_count_paging_layers_and_the_rows_state(name, per_token, per_row):
    from k_llms_tpu.backends.tpu import HbmMemoryModel
    from k_llms_tpu.engine.paging import PagedKVPool

    cfg = get_config(name)
    model = HbmMemoryModel(cfg, param_bytes=0, hbm_bytes=16 << 30)
    assert model.kv_bytes_per_token == cfg.kv_bytes_per_token == per_token
    assert cfg.state_bytes_per_row == per_row
    assert model.row_margin_bytes == 4 * cfg.vocab_size + (64 << 10) + per_row
    if cfg.num_layers <= 9:
        pool = PagedKVPool(cfg.with_(vocab_size=512), total_pages=3, page_size=16)
        assert pool.kv.k.shape[0] == cfg.paging_layers and pool.pool_bytes() == 3 * 16 * per_token
        assert llama.init_cache(cfg, 2, 8).k.shape[0] == cfg.paging_layers
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(jax.eval_shape(
        lambda: llama.init_state(cfg, 5)))) == 5 * per_row


@pytest.mark.parametrize("what", ["mesh", "int8", "int4", "speculative", "sp_prefill",
                                  "prefix_cache", "dense_layout", "param_specs",
                                  "quantize_params", "init_quantized", "load_checkpoint",
                                  "config_from_hf", "generate", "generate_many", "decode_step",
                                  "no_continuous_batching"])
def test_what_the_recurrent_state_has_no_answer_for_raises_by_name(what, tmp_path, params):
    from k_llms_tpu.backends.tpu import TpuBackend
    from k_llms_tpu.engine.engine import GenRequestSpec, LocalEngine
    from k_llms_tpu.models import loader, quant
    from k_llms_tpu.parallel.sharding import param_specs

    def engine(**kw):
        return LocalEngine(CFG, params=params, **{"use_mesh": False, "kv_layout": "paged", **kw})

    def dense_decode():
        prefix = llama.init_cache(CFG, 1, 8)
        return llama.decode_step(CFG, params, jnp.zeros((2,), jnp.int32), jnp.int32(0),
                                 jnp.int32(4), llama.init_cache(CFG, 2, 4), prefix)

    calls = {
        "mesh": lambda: engine(use_mesh=True),  # eight virtual devices: a mesh would be built
        "int8": lambda: engine(quantize="int8"),
        "int4": lambda: engine(quantize="int4"),
        "speculative": lambda: engine(speculative="prompt_lookup"),
        "sp_prefill": lambda: engine(sp_prefill_min_tokens=64),
        "prefix_cache": lambda: engine(prefix_cache_size=4),
        "dense_layout": lambda: engine(kv_layout="dense"),
        "param_specs": lambda: param_specs(CFG),
        "quantize_params": lambda: quant.quantize_params(params),
        "init_quantized": lambda: quant.init_params_quantized(CFG, jax.random.key(0)),
        "load_checkpoint": lambda: loader.load_checkpoint(str(tmp_path), CFG),
        "config_from_hf": lambda: loader.config_from_hf(str(tmp_path)),
        "generate": lambda: shared_engine("nemotron3-tiny", kv_layout="paged", kv_page_size=16)
        .generate([1, 2, 3], n=2, max_new_tokens=2),
        "generate_many": lambda: shared_engine("nemotron3-tiny", kv_layout="paged", kv_page_size=16)
        .generate_many([GenRequestSpec([1, 2, 3], 1, 0, None, None)], max_new_tokens=2),
        "decode_step": dense_decode,
        "no_continuous_batching": lambda: TpuBackend(model="nemotron3-tiny"),
    }
    (tmp_path / "config.json").write_text(json.dumps(
        {"model_type": "nemotron_h", "hybrid_override_pattern": "ME*", "hidden_size": 64,
         "num_attention_heads": 4}))
    with pytest.raises(NotImplementedError, match="hybrid|recurrent state|nemotron"):
        calls[what]()


def test_the_front_door_serves_it_and_health_and_metrics_carry_the_state():
    """``create_app`` -> the continuous loop, n = 1 and n = 3 under a schema;
    no fallback counter moves; ``/healthz``'s hbm block and ``/metrics`` carry
    the state's bytes, the new counters and the install span."""
    import asyncio

    from k_llms_tpu import KLLMs
    from k_llms_tpu.backends.tpu import TpuBackend
    from k_llms_tpu.serving.app import create_app
    from k_llms_tpu.utils.observability import FAILURE_EVENTS, GRAMMAR_EVENTS, KERNEL_EVENTS

    # The suite's eight virtual devices would make a mesh, which is refused: hand over an engine.
    backend = TpuBackend(model="nemotron3-tiny", continuous_batching=True, continuous_max_prompt=512,
                         engine=shared_engine("nemotron3-tiny", kv_layout="paged", kv_page_size=64))
    app = create_app(client=KLLMs(backend=backend, model="nemotron3-tiny"))
    client = app.client
    before = {**FAILURE_EVENTS.snapshot(), **GRAMMAR_EVENTS.snapshot(), **KERNEL_EVENTS.snapshot()}
    schema = {"type": "object", "properties": {"a": {"type": "string", "enum": ["x", "y"]}},
              "required": ["a"], "additionalProperties": False}
    try:
        kw = dict(messages=[{"role": "user", "content": "hello there " * 15}],
                  model="nemotron3-tiny", seed=7, max_tokens=12, temperature=0.8)
        one = client.chat.completions.create(n=1, **kw)
        three = client.chat.completions.create(n=3, response_format={
            "type": "json_schema", "json_schema": {"name": "d", "schema": schema}}, **kw)
        health = client.backend.health()
        sent = []

        async def send(message):
            sent.append(message)

        asyncio.run(app._metrics({}, None, send, {}))
    finally:
        client.backend.close()
    assert len(one.choices) == 1 and len(three.choices) == 4
    assert all(json.loads(c.message.content)["a"] in ("x", "y") for c in three.choices)
    after = {**FAILURE_EVENTS.snapshot(), **GRAMMAR_EVENTS.snapshot(), **KERNEL_EVENTS.snapshot()}
    moved = {k for k in after if "fallback" in k and after[k] != before.get(k, 0)}
    assert not moved
    width = health["continuous"]["width"]
    assert health["hbm"]["state_bytes"] == width * CFG.state_bytes_per_row
    assert health["hbm"]["kv_bytes_per_token"] == CFG.kv_bytes_per_token
    assert health["continuous"]["admitted"] == 2
    body = b"".join(m.get("body", b"") for m in sent).decode()
    lines = dict(l.rsplit(" ", 1) for l in body.splitlines() if l and not l.startswith("#")
                 and "{" not in l)
    assert float(lines["kllms_continuous_state_bytes"]) == health["hbm"]["state_bytes"]
    assert float(lines["kllms_hbm_state_bytes"]) == health["hbm"]["state_bytes"]
    assert float(lines["kllms_continuous_state_install_seconds_count"]) >= 2
    assert float(lines["kllms_ssm_state_updates"]) > 0 and float(lines["kllms_ssm_tokens_scanned"]) > 0


# -- the benchmark's side ---------------------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_identical():
    assert filecmp.cmp(os.path.join(ROOT, "tests", "nemotron3_reference.py"),
                       os.path.join(ROOT, "benchmark", "nemotron3_reference.py"), shallow=False)


def test_benchmark_manifest_has_no_fault_and_the_new_metrics_read_what_they_say():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        import run
    finally:
        sys.path.pop(0)
    assert run.check_manifest() == []
    cell = "nemotron3-nano-30b-a3b.chat"
    cells = {w["name"]: w for w in run.load_json(ROOT, "BENCHMARK.json")["workloads"]}
    assert cells[cell] == dict(cells[cell], config="nemotron3-nano-30b-a3b", traffic="chat", chips=1)
    entry, _, config, traffic, e2e, layer_specs = run.load_cell(cell)
    names = {m["name"] for m in layer_specs}
    new = {"ssm_state_stream_share", "moe_expert_stream_share.nongated", "state_install_ms"}
    assert new | {"weight_stream_share", "device_idle_share", "loop_step_ms"} <= names
    assert not {"moe_expert_stream_share", "mla_latent_stream_share"} & names
    assert set(e2e) == {"latency_p50_ms", "tokens_per_s", "setup_s"}
    for other in cells:
        if other != cell:
            assert not new & {m["name"] for m in run.load_cell(other)[5]}
    counters = {"kllms_ssm_state_updates": 9000.0, "kllms_moe_experts_touched": 28000.0}
    src = {"capture": {"start": {}, "end": counters, "seconds": 3.0},
           "metrics_start": {"kllms_continuous_state_install_seconds_sum": 1.0,
                             "kllms_continuous_admitted": 10.0},
           "metrics_end": {"kllms_continuous_state_install_seconds_sum": 1.5,
                           "kllms_continuous_admitted": 210.0},
           "trace": {"busy_s": 2.2, "window_s": 3.0}, "config": config,
           "peaks": {"hbm_GB_per_s": 819}}
    values = {m["name"]: run.evaluate(m["read"], src) for m in layer_specs if m["name"] in new}
    assert values["ssm_state_stream_share"] == pytest.approx(
        100 * 9000 * 64 * 64 * 128 * 8 / 2.2 / 819e9)
    assert values["moe_expert_stream_share.nongated"] == pytest.approx(
        100 * 28000 * 2 * 2688 * 1856 * 2 / 2.2 / 819e9)
    assert values["state_install_ms"] == pytest.approx(1000 * 0.5 / 200)
    assert all(0 < v < 100 for v in values.values())
    # On a program without the counters and the span (the parent) the readers find nothing.
    bare = dict(src, capture={"start": {}, "end": {}, "seconds": 3.0}, metrics_start={},
                metrics_end={"kllms_continuous_admitted": 210.0})
    assert all(run.evaluate(m["read"], bare) is None for m in layer_specs if m["name"] in new)
