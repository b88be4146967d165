"""Xing4.0 (models/latent.py): MLA latent cache, routed experts with a shared
one, hyper-connected streams — the program against the plain reference
(tests/xing4_reference.py, the benchmark's copy byte for byte) at the
``xing4-tiny`` size on the CPU, in float32.

Tolerances. Both sides compute in float32 here, so what separates them is the
order of summation (grouped products against a loop over experts, absorbed
against materialised attention, XLA's fusions): 1e-4 absolute on logits of
magnitude ~4 is twenty times what the comparisons read (5e-6 to 1.2e-5) and a
thousand times under what a missing term gives (a dropped mscale^2, shared
expert or H_res moves logits by 0.1 to 1).
"""

import filecmp
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xing4_reference as ref
from conftest import shared_engine, shared_params
from k_llms_tpu.models import get_config, latent, llama
from k_llms_tpu.models.llama import KVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4
CFG = get_config("xing4-tiny")


def hf_dict(c):
    """The preset in the published config.json's own key names."""
    _, factor, orig, beta_fast, beta_slow, all_dim = c.rope_scaling
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_heads,
        q_lora_rank=c.q_lora_rank, kv_lora_rank=c.kv_lora_rank,
        qk_nope_head_dim=c.qk_nope_head_dim, qk_rope_head_dim=c.qk_rope_head_dim,
        v_head_dim=c.v_head_dim, rms_norm_eps=c.rms_eps, rope_theta=c.rope_theta,
        rope_scaling=dict(type="yarn", factor=factor, original_max_position_embeddings=orig,
                          beta_fast=beta_fast, beta_slow=beta_slow, mscale=1,
                          mscale_all_dim=all_dim),
        n_routed_experts=c.num_experts, num_experts_per_tok=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor, norm_topk_prob=True,
        first_k_dense_replace=c.first_k_dense, num_hidden_layers=c.num_layers,
        hc_mult=c.hc_mult, hc_sinkhorn_iters=c.hc_sinkhorn_iters, hc_eps=c.hc_eps,
        mhc_h_res_clamp_min=-c.hc_res_clamp, mhc_h_res_clamp_max=c.hc_res_clamp,
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


def one_layer(params, group="layers", i=0):
    return {k: v[i] for k, v in params[group].items()}


# -- the program against the reference ---------------------------------------------

@pytest.mark.parametrize("row", [0, 1])
def test_forward_matches_reference(params, tokens, reference_logits, row):
    logits, _ = llama.forward(CFG, params, jnp.asarray(tokens), jnp.ones(tokens.shape, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[row]), reference_logits[row], atol=TOL, rtol=0)


@pytest.mark.parametrize("prompt_len", [24, 41])
def test_prefill_then_dense_decode_matches_reference(params, tokens, reference_logits, prompt_len):
    """Whole-prompt prefill (materialised attention, right-padded bucket), then
    decode steps through the dense caches (absorbed attention over a shared
    prefix), two rows forced along the same tokens."""
    want, seq = reference_logits[0], tokens[0]
    padded = np.pad(seq[None, :prompt_len], ((0, 0), (0, 64 - prompt_len)))
    logits, prefix = llama.prefill(CFG, params, jnp.asarray(padded), jnp.int32(prompt_len))
    np.testing.assert_allclose(np.asarray(logits[0]), want[prompt_len - 1], atol=TOL, rtol=0)
    gen = llama.init_cache(CFG, 2, 16)
    for step in range(6):
        cur = jnp.asarray([seq[prompt_len + step]] * 2)
        logits, gen = llama.decode_step(
            CFG, params, cur, jnp.int32(step), jnp.int32(prompt_len), gen, prefix)
        for row in range(2):
            np.testing.assert_allclose(
                np.asarray(logits[row]), want[prompt_len + step], atol=TOL, rtol=0)


@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_prefill_then_paged_decode_matches_reference(params, tokens, reference_logits, chunk):
    """The loop's two programs by hand: chunks into a staging cache and a page
    pool (every chunk's last-token logits compared), then ``paged_verify_step``
    with rows reading the shared prompt pages and their own generated slots."""
    want, seq, plen, ps, rows = reference_logits[1], tokens[1], 50, 8, 3
    bucket, flat = 64, 40 * 8
    cache = llama.init_cache(CFG, 1, bucket)
    pool = KVCache(k=jnp.zeros((CFG.num_layers, flat, 1, 40)), v=jnp.zeros((CFG.num_layers, flat, 1, 0)))
    for start in range(0, plen, chunk):
        valid = min(chunk, plen - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :valid] = seq[start:start + valid]
        aux = {}
        logits, cache, k_cols, v_cols = llama.prefill_chunk_step_paged(
            CFG, params, jnp.asarray(toks), cache, jnp.int32(start), jnp.int32(valid), aux=aux)
        assert aux["moe_counts"].shape == (2, CFG.num_experts)
        assert int(aux["moe_counts"].sum()) == 2 * chunk * CFG.num_experts_per_tok
        np.testing.assert_allclose(
            np.asarray(logits[0]), want[start + valid - 1], atol=TOL, rtol=0)
        slots = ps + start + np.arange(valid)  # page 0 is the trash page
        pool = KVCache(k=pool.k.at[:, slots].set(k_cols[:, :valid]),
                       v=pool.v.at[:, slots].set(v_cols[:, :valid]))
    assert v_cols.shape[-1] == 0 and k_cols.shape[-2:] == (1, 40)
    P, G = 64, 8
    pidx = np.tile(np.arange(P) % ps, (rows, 1)).astype(np.int32)
    pidx[:, :plen] = ps + np.arange(plen)
    gidx = np.stack([100 + 10 * r + np.arange(G) for r in range(rows)]).astype(np.int32)
    for step in range(5):
        aux = {}
        logits, k_cols, v_cols = llama.paged_verify_step(
            CFG, params, jnp.full((rows, 1), seq[plen + step]), jnp.full((rows,), step),
            jnp.full((rows,), plen), pool, jnp.asarray(pidx), jnp.asarray(gidx), aux=aux)
        assert int(aux["mla_latent_rows_read"]) == rows * (plen + step + 1) * CFG.num_layers
        pool = KVCache(k=pool.k.at[:, gidx[:, step]].set(k_cols), v=pool.v.at[:, gidx[:, step]].set(v_cols))
        for row in range(rows):
            np.testing.assert_allclose(
                np.asarray(logits[row, 0]), want[plen + step], atol=TOL, rtol=0)


def test_reference_given_the_programs_routing_agrees_and_measures_its_slack(params, tokens, reference_logits):
    """On the chip the reference is given the program's expert choices (a
    top-k turns on the last bit of a score) and reports how far they lie under
    its own. Here, in float32, the choices are the reference's own: slack 0 and
    the same logits; a choice forced one expert off shows its slack."""
    seq = tokens[0]
    aux = {"moe_chosen": None}
    cache = llama.init_cache(CFG, 1, 128)
    padded = np.pad(seq[None], ((0, 0), (0, 128 - len(seq))))
    llama.prefill_continue(CFG, params, jnp.asarray(padded), cache, jnp.int32(0),
                           jnp.int32(len(seq)), aux=aux)
    chosen = np.asarray(aux["moe_chosen"])[:, :len(seq)]
    assert chosen.shape == (2, len(seq), CFG.num_experts_per_tok)
    slacks = []
    got = ref.forward(hf_dict(CFG), params, seq, given=chosen, slacks=slacks, positions=[5, 40])
    np.testing.assert_allclose(np.asarray(got), reference_logits[0][[5, 40]], atol=TOL, rtol=0)
    assert len(slacks) == 2 and all(float(jnp.max(jnp.abs(s))) == 0.0 for s in slacks)
    off = chosen.copy()
    off[0, 7, 0] = next(e for e in range(CFG.num_experts) if e not in chosen[0, 7])
    slacks = []
    ref.forward(hf_dict(CFG), params, seq, given=off, slacks=slacks)
    assert float(slacks[0][7]) > 0 and float(jnp.max(jnp.abs(slacks[0][:7]))) == 0.0
    plain = {}  # a caller that does not ask gets the counts alone
    llama.prefill_continue(CFG, params, jnp.asarray(padded), llama.init_cache(CFG, 1, 128),
                           jnp.int32(0), jnp.int32(len(seq)), aux=plain)
    assert set(plain) == {"moe_counts"}


# -- through the engine and the continuous loop ------------------------------------------

@pytest.mark.parametrize("ladder,chunks,chunk_tokens", [((), 4, 32), ((32, 64, 128), 1, 128)])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_greedy_tokens_equal_through_the_loop_and_the_dense_path(layout, ladder, chunks, chunk_tokens):
    """n = 8 greedy samples of one chunked prompt (four chunks of 32, or on
    the ladder one turn of 128) through the continuous loop (paged: on shared
    latent pages, copy-on-write for the partial last page) equal
    ``generate``'s dense decode; the loop's programs count the router's loads
    on the way."""
    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
    from k_llms_tpu.utils.observability import MODEL_COUNTERS

    prompt = [int(t) for t in np.random.RandomState(1).randint(0, 250, 100)]
    engine = shared_engine("xing4-tiny", kv_layout=layout, kv_page_size=16)
    loop = ContinuousDecodeLoop(engine, width=8, max_prompt=256, max_new=16, eos_ids=[257],
                                prefill_chunk_tokens=32, prefill_chunk_ladder=ladder)
    before = MODEL_COUNTERS.snapshot()
    pool = getattr(engine, "_kv_pool", None)  # the engine's, so another case's copies are in it
    copies = pool.allocator.snapshot()["cow_copies"] if pool is not None else 0
    try:
        got = loop.submit(prompt, n=8, max_new=12, temperature=0.0, top_p=1.0,
                          seed=3).result(timeout=300)
        pages = loop.stats.get("pages")
    finally:
        loop.stop()
    # Taken before ``generate``: its whole-prompt prefill program counts too.
    grew = {k: v - before.get(k, 0) for k, v in MODEL_COUNTERS.snapshot().items()}
    want = shared_engine("xing4-tiny").generate(prompt, n=8, max_new_tokens=12, temperature=0.0, seed=3)
    np.testing.assert_array_equal(np.asarray(got.tokens)[:, :12], np.asarray(want.tokens)[:, :12])
    # The chunks and 11 steps, 2 expert layers each; a chunk's tokens, 8 rows a step, top-2.
    assert grew["moe_layer_calls"] == (chunks + 11) * 2
    assert grew["moe_pairs"] == (chunks * chunk_tokens + 11 * 8) * 2 * 2
    assert 0 < grew["moe_experts_touched"] <= grew["moe_layer_calls"] * CFG.num_experts
    assert grew["moe_max_load"] * CFG.num_experts >= grew["moe_pairs"]
    if layout == "paged":
        assert pages["cow_copies"] - copies == 8 and pages["in_use"] == 0
        assert grew["mla_latent_rows_read"] == sum(8 * (100 + t + 1) for t in range(11)) * 3


def test_the_dense_block_counts_nothing_and_aux_adds_up_into_the_counters():
    aux = {}
    cfg = get_config("tiny")
    p = shared_params(cfg)
    llama.prefill_chunk_step(cfg, p, jnp.zeros((1, 8), jnp.int32), llama.init_cache(cfg, 1, 16),
                             jnp.int32(0), jnp.int32(8), aux=aux)
    assert aux == {}
    from k_llms_tpu.utils.observability import MODEL_COUNTERS, note_model_aux

    before = MODEL_COUNTERS.snapshot()
    note_model_aux({"moe_counts": np.asarray([[3, 0, 1], [0, 0, 4]]),
                    "mla_latent_rows_read": np.int32(7),
                    "ssm_rows_updated": np.int32(12), "ssm_tokens_scanned": np.int32(40)})
    grew = {k: v - before.get(k, 0) for k, v in MODEL_COUNTERS.snapshot().items()}
    assert grew == {"moe_layer_calls": 2, "moe_pairs": 8, "moe_experts_touched": 3,
                    "moe_max_load": 7, "mla_latent_rows_read": 7,
                    "ssm_state_updates": 12, "ssm_tokens_scanned": 40}
    assert set(MODEL_COUNTERS.declared) == set(grew)


def test_metrics_page_exports_model_counters():
    import asyncio

    from k_llms_tpu import KLLMs
    from k_llms_tpu.serving.app import create_app

    app = create_app(client=KLLMs(backend="fake"))
    sent = []

    async def send(message):
        sent.append(message)

    asyncio.run(app._metrics({}, None, send, {}))
    body = b"".join(m.get("body", b"") for m in sent).decode()
    for name in ("moe_layer_calls", "moe_pairs", "moe_experts_touched", "moe_max_load",
                 "mla_latent_rows_read", "ssm_state_updates", "ssm_tokens_scanned"):
        assert f"\nkllms_{name} " in body and f"# TYPE kllms_{name} gauge" in body


# -- the mechanisms one by one ----------------------------------------------------------------

@pytest.mark.parametrize("shared_rows", [1, 2])
def test_absorbed_decode_equals_materialised_attention(params, shared_rows):
    """One function, two forms: W_kvb folded into query and output (decode)
    against keys and values materialised per head (prefill), over a prefix
    shared request-major plus private rows, under the same masks."""
    layer, rs = one_layer(params), np.random.RandomState(2)
    B, Sq, NH, P, S = 4, 1, CFG.num_heads, 10, 6
    q_nope = jnp.asarray(rs.randn(B, Sq, NH, CFG.qk_nope_head_dim), jnp.float32)
    q_rope = jnp.asarray(rs.randn(B, Sq, NH, CFG.qk_rope_head_dim), jnp.float32)
    width = CFG.kv_lora_rank + CFG.qk_rope_head_dim
    segments = [
        (jnp.asarray(rs.randn(shared_rows, P, width), jnp.float32),
         jnp.asarray(np.arange(P)[None, None, :] < np.array([10, 7, 9, 3])[:, None, None])),
        (jnp.asarray(rs.randn(B, S, width), jnp.float32),
         jnp.asarray(np.arange(S)[None, None, :] <= np.array([0, 5, 2, 3])[:, None, None])),
    ]
    absorbed = latent.mla_attend(CFG, layer, q_nope, q_rope, segments, absorb=True)
    materialised = latent.mla_attend(CFG, layer, q_nope, q_rope, segments, absorb=False)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(materialised), atol=2e-5, rtol=0)
    assert absorbed.shape == (B, Sq, NH * CFG.v_head_dim)


def all_experts_oracle(layer, h, chosen, w):
    """Every token through every expert, combined by a [T, E] weight."""
    combine = np.zeros((h.shape[0], CFG.num_experts), np.float32)
    for t in range(h.shape[0]):
        for k in range(chosen.shape[1]):
            combine[t, chosen[t, k]] += w[t, k]
    gate = jax.nn.silu(jnp.einsum("th,ehi->tei", h, layer["w_gate"]))
    up = jnp.einsum("th,ehi->tei", h, layer["w_up"])
    out = jnp.einsum("tei,eih->teh", gate * up, layer["w_down"])
    return np.einsum("teh,te->th", np.asarray(out), combine)


@pytest.mark.parametrize("case", ["random", "ties", "idle_expert", "one_token"])
def test_routed_compute_equals_all_experts_oracle(params, case):
    layer, rs = dict(one_layer(params)), np.random.RandomState(3)
    T = 1 if case == "one_token" else 24
    h = jnp.asarray(rs.randn(T, CFG.hidden_size), jnp.float32)
    if case == "ties":  # equal scores everywhere: top-k must take the lowest ids
        layer["w_router"] = jnp.zeros_like(layer["w_router"])
    if case == "idle_expert":  # the bias keeps experts 5 and 6 out of every top-k
        layer["router_bias"] = layer["router_bias"].at[5:7].set(-10.0)
    chosen, w = latent.route(CFG, layer, h)
    out, counts, chosen_again = latent.routed_experts(CFG, layer, h)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen_again))
    ref_chosen, ref_w, slack = ref.route(hf_dict(CFG), layer, h)
    assert float(jnp.max(jnp.abs(slack))) == 0.0
    np.testing.assert_array_equal(np.sort(np.asarray(chosen)), np.sort(np.asarray(ref_chosen)))
    np.testing.assert_allclose(np.asarray(w).sum(-1), CFG.routed_scaling_factor, rtol=1e-5)
    np.testing.assert_allclose(np.sort(np.asarray(w)), np.sort(np.asarray(ref_w)), atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(np.asarray(chosen).reshape(-1), minlength=CFG.num_experts))
    if case == "ties":
        assert (np.sort(np.asarray(chosen)) == np.arange(CFG.num_experts_per_tok)).all()
    if case == "idle_expert":
        assert counts[5] == 0 and counts[6] == 0
    np.testing.assert_allclose(
        np.asarray(out), all_experts_oracle(layer, h, np.asarray(chosen), np.asarray(w)),
        atol=2e-5, rtol=0)


@pytest.mark.parametrize("program", ["chunk", "step"])
def test_layer_scan_never_slices_the_expert_stacks(params, program):
    """The grouped product is a kernel call on the chip: handed a scanned
    [E, H, I] slice of the [Le, E, H, I] stack, XLA copies the slice out first
    (1.4 GB a layer a step at full width; half the step's device time in PR
    28's first traced run). The stacks therefore reach the scan as constants
    and every layer's groups are addressed in place."""
    if program == "chunk":
        jaxpr = jax.make_jaxpr(lambda p, t, c: llama.prefill_chunk_step(
            CFG, p, t, c, jnp.int32(0), jnp.int32(8)))(
                params, jnp.zeros((1, 8), jnp.int32), llama.init_cache(CFG, 1, 16))
    else:
        pool = KVCache(k=jnp.zeros((3, 64, 1, 40)), v=jnp.zeros((3, 64, 1, 0)))
        jaxpr = jax.make_jaxpr(lambda p: llama.paged_verify_step(
            CFG, p, jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.full((2,), 4), pool, jnp.zeros((2, 8), jnp.int32), jnp.zeros((2, 4), jnp.int32)))(params)
    stack_shapes = {params["layers"][k].shape for k in ("w_gate", "w_up", "w_down")}
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 2  # the dense-leading layers, then the expert layers
    found = 0
    for eqn in scans:
        consts, carry = eqn.params["num_consts"], eqn.params["num_carry"]
        scanned = [v.aval.shape for v in eqn.invars[consts + carry:]]
        assert not stack_shapes & set(scanned), "an expert stack is scanned (sliced per layer)"
        found += len(stack_shapes & {v.aval.shape for v in eqn.invars[:consts]})
    assert found == len(stack_shapes)


@pytest.mark.parametrize("scale,row_bound", [(0.5, 1e-3), (4.0, 5e-2), (200.0, 0.3)])
def test_h_res_is_doubly_stochastic_and_the_clamp_holds(params, scale, row_bound):
    """After 20 Sinkhorn rounds on exp(clamp(.)): the last division is by the
    column sums, so columns sum to 1 within 1e-5 (hc_eps and rounding); rows
    converge geometrically, to 1e-3 at the initial alpha (0.5, entries spread
    over e^+-2; read 7e-4), 5e-2 at alpha 4 (e^+-16; read 2.8e-2). At ``scale``
    200 the raw entries pass +-30: only the clamp keeps exp finite, and 20
    rounds leave rows that far apart within 0.3 (read 0.13)."""
    layer = dict(one_layer(params))
    layer["hc_attn_alpha"] = jnp.asarray([0.5, 0.5, scale], jnp.float32)
    X = jnp.asarray(np.random.RandomState(4).randn(2, 9, CFG.hc_mult, CFG.hidden_size), jnp.float32)
    h_pre, h_post, h_res = latent.hc_coefficients(CFG, layer, "hc_attn", X)
    h_res = np.asarray(h_res)
    assert np.isfinite(h_res).all() and (h_res >= 0).all()
    np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=row_bound)
    assert ((0 < np.asarray(h_pre)) & (np.asarray(h_pre) < 1)).all()
    assert ((0 < np.asarray(h_post)) & (np.asarray(h_post) < 2)).all()
    clamped = np.asarray(latent.sinkhorn(jnp.exp(jnp.clip(jnp.asarray([[40.0, -40.0], [0.0, 0.0]]), -30, 30)), 20, 1e-6))
    same = np.asarray(latent.sinkhorn(jnp.exp(jnp.asarray([[30.0, -30.0], [0.0, 0.0]])), 20, 1e-6))
    np.testing.assert_array_equal(clamped, same)


# -- shapes, bytes and refusals -----------------------------------------------------------------

@pytest.mark.parametrize("name,want", [("xing4-29b-a4b", 29.5e9), ("xing4-29b-a4b-cut7", 5.54e9)])
def test_parameter_count_from_shapes(name, want):
    assert abs(latent.param_count(get_config(name)) - want) / want < 0.01


def test_published_preset_holds_the_published_sizes():
    with open(os.path.join(ROOT, "benchmark", "configs", "xing4-29b-a4b.json")) as f:
        hf = json.load(f)
    full, cut = get_config("xing4-29b-a4b"), get_config(hf["serve"]["model"])
    assert hf_dict(cut) | {"norm_topk_prob": True} == {k: hf[k] for k in hf_dict(cut)}
    assert (cut.vocab_size, cut.intermediate_size, cut.moe_intermediate_size) == (
        hf["vocab_size"], hf["intermediate_size"], hf["moe_intermediate_size"])
    assert (full.num_layers, full.first_k_dense) == (
        hf["published"]["num_hidden_layers"], hf["published"]["first_k_dense_replace"])
    assert full.with_(name=cut.name, num_layers=7, first_k_dense=1) == cut
    assert cut.attn_scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)


# A latent row is stored in whole tiles of 128 lanes (576 -> 640, 40 -> 128): PR 35.
@pytest.mark.parametrize("name,per_token", [("xing4-29b-a4b-cut7", 7 * 640 * 2),
                                            ("xing4-tiny", 3 * 128 * 4),
                                            ("qwen2-7b", 2 * 28 * 512 * 2)])
def test_pool_and_memory_model_count_the_models_own_cache_row(name, per_token):
    from k_llms_tpu.backends.tpu import HbmMemoryModel
    from k_llms_tpu.engine.paging import PagedKVPool

    cfg = get_config(name)
    model = HbmMemoryModel(cfg, param_bytes=0, hbm_bytes=16 << 30)
    assert model.kv_bytes_per_token == cfg.kv_bytes_per_token == per_token
    assert model.describe()["kv_bytes_per_token"] == per_token
    if cfg.num_layers <= 7:  # the 28-layer pool is not worth building here
        pool = PagedKVPool(cfg.with_(vocab_size=512), total_pages=3, page_size=16)
        assert pool.pool_bytes() == 3 * 16 * per_token
        heads, k_width, v_width = cfg.cache_widths
        assert pool.kv.k.shape[2:] == (heads, cfg.pool_row_width) and pool.kv.v.shape[2:] == (heads, v_width)
        assert cfg.pool_row_width >= k_width and cfg.pool_row_width % 128 == 0
    rows = model.paged_max_rows(2048, 256, 64, fanout=8)
    assert rows == model.budget_bytes() // (
        4 * 64 * per_token + -(-32 * 64 * per_token // 8) + model.row_margin_bytes)


@pytest.mark.parametrize("what", ["mesh", "int8", "int4", "speculative", "sp_prefill",
                                  "param_specs", "quantize_params", "init_quantized",
                                  "load_checkpoint", "config_from_hf"])
def test_what_the_latent_block_cannot_do_raises_by_name(what, tmp_path, params):
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.models import loader, quant
    from k_llms_tpu.parallel.sharding import param_specs

    calls = {
        "mesh": lambda: LocalEngine(CFG),  # eight virtual devices: a mesh would be built
        "int8": lambda: LocalEngine(CFG, use_mesh=False, quantize="int8"),
        "int4": lambda: LocalEngine(CFG, use_mesh=False, quantize="int4"),
        "speculative": lambda: LocalEngine(CFG, use_mesh=False, speculative="prompt_lookup"),
        "sp_prefill": lambda: LocalEngine(CFG, use_mesh=False, sp_prefill_min_tokens=64),
        "param_specs": lambda: param_specs(CFG),
        "quantize_params": lambda: quant.quantize_params(params),
        "init_quantized": lambda: quant.init_params_quantized(CFG, jax.random.key(0)),
        "load_checkpoint": lambda: loader.load_checkpoint(str(tmp_path), CFG),
        "config_from_hf": lambda: loader.config_from_hf(str(tmp_path)),
    }
    (tmp_path / "config.json").write_text(json.dumps(
        {"model_type": "xing4_0", "kv_lora_rank": 512, "hidden_size": 64, "num_attention_heads": 4}))
    with pytest.raises(NotImplementedError, match="latent|xing4"):
        calls[what]()


@pytest.mark.parametrize("requested", ["auto", "pallas"])
def test_paged_kernel_fallback_for_latent_pages_is_counted_on_a_tpu(monkeypatch, requested):
    from k_llms_tpu.ops import paged_attention
    from k_llms_tpu.utils.observability import KERNEL_EVENTS

    monkeypatch.setattr(paged_attention.jax, "default_backend", lambda: "tpu")
    before = KERNEL_EVENTS.get("kernel.paged_attn_fallback.mla")
    assert paged_attention.resolve_paged_attention_impl(requested, config=CFG) == "xla"
    assert paged_attention.resolve_paged_attention_impl(requested, config=CFG, record=False) == "xla"
    assert KERNEL_EVENTS.get("kernel.paged_attn_fallback.mla") == before + 1
    assert paged_attention.resolve_paged_attention_impl(requested, config=get_config("tiny")) == "pallas"


# -- the benchmark's side ---------------------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_identical():
    assert filecmp.cmp(os.path.join(ROOT, "tests", "xing4_reference.py"),
                       os.path.join(ROOT, "benchmark", "xing4_reference.py"), shallow=False)


def test_benchmark_manifest_has_no_fault():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        import run
    finally:
        sys.path.pop(0)
    assert run.check_manifest() == []
    cells = {w["name"]: w for w in run.load_json(ROOT, "BENCHMARK.json")["workloads"]}
    assert cells["xing4-29b-a4b.extract"]["traffic"] == "extract"
    assert cells["qwen2-7b.chat"] == dict(cells["qwen2-7b.chat"], config="qwen2-7b", traffic="chat", chips=1)
    entry, _, config, traffic, e2e, layer_specs = run.load_cell("xing4-29b-a4b.extract")
    names = {m["name"] for m in layer_specs}
    assert {"moe_expert_stream_share", "mla_latent_stream_share", "moe_experts_touched_share",
            "moe_load_max_over_mean", "device_idle_share", "weight_stream_share"} <= names
    src = {"capture": {"start": {}, "end": {"kllms_moe_experts_touched": 330.0,
                                            "kllms_moe_layer_calls": 6.0, "kllms_moe_pairs": 768.0,
                                            "kllms_moe_max_load": 24.0,
                                            "kllms_mla_latent_rows_read": 1000.0}, "seconds": 3.0},
           "trace": {"busy_s": 0.03, "window_s": 3.0}, "config": config,
           "peaks": {"hbm_GB_per_s": 819}}
    values = {m["name"]: run.evaluate(m["read"], src) for m in layer_specs if m["name"] in names
              and m["name"].startswith(("moe_", "mla_"))}
    assert values["moe_experts_touched_share"] == pytest.approx(100 * 330 / (6 * 64))
    assert values["moe_load_max_over_mean"] == pytest.approx(24 * 64 / 768)
    assert values["moe_expert_stream_share"] == pytest.approx(
        100 * 330 * 3 * 3584 * 1024 * 2 / 0.03 / 819e9)
    assert values["mla_latent_stream_share"] == pytest.approx(100 * 1000 * 576 * 2 / 0.03 / 819e9)
    # On a program without the counters (the parent) the readers find nothing and say so.
    assert all(run.evaluate(m["read"], dict(src, capture={"start": {}, "end": {}, "seconds": 3.0})) is None
               for m in layer_specs if m["name"].startswith(("moe_", "mla_")))
