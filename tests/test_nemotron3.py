"""Nemotron-3 (models/hybrid.py): Mamba-2 layers with recurrent state beside
the pages, a per-layer pattern of mixers, non-gated relu^2 experts — the
checks no other model has, against the plain reference
(benchmark/nemotron3_reference.py) at the ``nemotron3-tiny`` size on the CPU,
in float32. What every served model owes is tests/test_served_models.py's.

Tolerances. Both sides compute in float32 here, so what separates them is the
order of summation (the chunked scan against the token-by-token recurrence,
grouped products against a loop over experts, XLA's fusions): 1e-4 absolute on
logits of magnitude ~0.8 is twenty times what the comparisons read (2e-6 to
5e-6) and a thousand times under what a missing term gives (a dropped D x, a
state that forgets its carry or sees the padding moves logits by 0.1 to 4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import shared_engine, shared_params
from k_llms_tpu.models import get_config, hybrid, latent, llama
from served_models import ROWS, nemotron3_hf as hf_dict, reference

TOL = 1e-4
ROW = ROWS["nemotron3-tiny"]
CFG, ref = ROW.cfg, ROW.ref
N_M = CFG.layer_pattern.count("M")


@pytest.fixture(scope="module")
def params():
    return shared_params(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, 500, (2, 72)).astype(np.int32)


def state_leaves(state):
    return [np.asarray(a) for a in jax.tree.leaves(state)]


# -- the program against the reference ---------------------------------------------

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


@jax.jit
def whole_prefill(params, padded, plen):
    whole = {}
    return llama.prefill(CFG, params, padded, plen, state=whole)[0], whole


@jax.jit
def chunk_step(params, toks, cache, start, valid, state):
    state = dict(state)
    return llama.prefill_chunk_step(CFG, params, toks, cache, start, valid, state=state) + (state,)


@pytest.mark.parametrize("plen,chunk", [(50, 16), (33, 32), (64, 16)])
def test_chunked_prefill_with_a_padded_final_chunk_equals_whole_prefill(params, tokens, plen, chunk):
    """Same logits, same SSM state, same conv tail, whichever way the prompt
    came in: in chunks (the last one padded, or exactly full) or whole at its
    padded bucket. The tail is the last three *valid* conv inputs."""
    seq, bucket = tokens[0], 64
    padded = np.pad(seq[None, :plen], ((0, 0), (0, bucket - plen)))
    want, whole = whole_prefill(params, jnp.asarray(padded), jnp.int32(plen))
    cache, state = llama.init_cache(CFG, 1, bucket), llama.init_state(CFG, 1)
    for start in range(0, plen, chunk):
        valid = min(chunk, plen - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :valid] = seq[start:start + valid]
        got, cache, state = chunk_step(params, jnp.asarray(toks), cache, jnp.int32(start),
                                       jnp.int32(valid), state)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL, rtol=0)
    assert set(state) == set(whole) == {"ssm", "conv"} and len(state["ssm"]) == N_M
    for a, b in zip(state_leaves(state), state_leaves(whole)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=TOL, rtol=0)
    assert state["ssm"][0].dtype == jnp.float32 and state["conv"][0].shape == (
        1, CFG.mamba_conv_kernel - 1, hybrid.conv_dim(CFG))


def test_reference_given_the_programs_routing_agrees_and_measures_its_slack(params, tokens):
    seq = tokens[0]
    aux = {"moe_chosen": None}
    padded = np.pad(seq[None], ((0, 0), (0, 128 - len(seq))))
    llama.prefill(CFG, params, jnp.asarray(padded), jnp.int32(len(seq)), aux=aux)
    chosen = np.asarray(aux["moe_chosen"])[:, :len(seq)]
    assert chosen.shape == (4, len(seq), CFG.num_experts_per_tok)
    slacks = []
    got = ref.forward(hf_dict(CFG), params, seq, given=chosen, slacks=slacks, positions=[5, 40])
    np.testing.assert_allclose(np.asarray(got), np.asarray(reference(ROW, params, seq))[[5, 40]], atol=TOL, rtol=0)
    assert len(slacks) == 4 and all(float(jnp.max(jnp.abs(s))) == 0.0 for s in slacks)
    off = chosen.copy()
    off[0, 7, 0] = next(e for e in range(CFG.num_experts) if e not in chosen[0, 7])
    slacks = []
    ref.forward(hf_dict(CFG), params, seq, given=off, slacks=slacks)
    assert float(slacks[0][7]) > 0 and float(jnp.max(jnp.abs(slacks[0][:7]))) == 0.0


# -- through the engine and the continuous loop ------------------------------------------

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
