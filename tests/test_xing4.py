"""Xing4.0 (models/latent.py): MLA latent cache, routed experts with a shared
one, hyper-connected streams — the checks no other model has, against the
plain reference (benchmark/xing4_reference.py) at the ``xing4-tiny`` size on
the CPU, in float32. What every served model owes is tests/test_served_models.py's.

Tolerances. Both sides compute in float32 here, so what separates them is the
order of summation (grouped products against a loop over experts, absorbed
against materialised attention, XLA's fusions): 1e-4 absolute on logits of
magnitude ~4 is twenty times what the comparisons read (5e-6 to 1.2e-5) and a
thousand times under what a missing term gives (a dropped mscale^2, shared
expert or H_res moves logits by 0.1 to 1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import shared_engine, shared_loop, shared_params
from k_llms_tpu.models import get_config, latent, llama
from k_llms_tpu.models.llama import KVCache
from served_models import ROWS, reference, xing4_hf as hf_dict

TOL = 1e-4
ROW = ROWS["xing4-tiny"]
CFG, ref = ROW.cfg, ROW.ref


@pytest.fixture(scope="module")
def params():
    return shared_params(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, 500, (2, 72)).astype(np.int32)


@pytest.fixture(scope="module")
def reference_logits(params, tokens):
    return np.asarray(reference(ROW, params, tokens[0]))


def one_layer(params, group="layers", i=0):
    return {k: v[i] for k, v in params[group].items()}


# -- the program against the reference ---------------------------------------------

@pytest.mark.parametrize("prompt_len", [24, 41])
def test_prefill_then_dense_decode_matches_reference(params, tokens, reference_logits, prompt_len):
    """Whole-prompt prefill (materialised attention, right-padded bucket), then
    decode steps through the dense caches (absorbed attention over a shared
    prefix), two rows forced along the same tokens."""
    want, seq = reference_logits, tokens[0]
    padded = np.pad(seq[None, :prompt_len], ((0, 0), (0, 64 - prompt_len)))
    logits, prefix = jax.jit(llama.prefill, static_argnums=0)(
        CFG, params, jnp.asarray(padded), jnp.int32(prompt_len))
    np.testing.assert_allclose(np.asarray(logits[0]), want[prompt_len - 1], atol=TOL, rtol=0)
    gen = llama.init_cache(CFG, 2, 16)
    decode_step = jax.jit(llama.decode_step, static_argnums=0)
    for step in range(6):
        cur = jnp.asarray([seq[prompt_len + step]] * 2)
        logits, gen = decode_step(CFG, params, cur, jnp.int32(step), jnp.int32(prompt_len), gen, prefix)
        for row in range(2):
            np.testing.assert_allclose(
                np.asarray(logits[row]), want[prompt_len + step], atol=TOL, rtol=0)


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
    np.testing.assert_allclose(np.asarray(got), reference_logits[[5, 40]], atol=TOL, rtol=0)
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
    from k_llms_tpu.utils.observability import MODEL_COUNTERS

    prompt = [int(t) for t in np.random.RandomState(1).randint(0, 250, 100)]
    loop = shared_loop("xing4-tiny", kv_layout=layout, kv_page_size=16, width=8, max_prompt=256,
                       max_new=16, eos_ids=[257], prefill_chunk_tokens=32, prefill_chunk_ladder=ladder)
    before = MODEL_COUNTERS.snapshot()
    pool = getattr(loop.engine, "_kv_pool", None)  # the engine's, so another case's copies are in it
    copies = pool.allocator.snapshot()["cow_copies"] if pool is not None else 0
    got = loop.submit(prompt, n=8, max_new=12, temperature=0.0, top_p=1.0, seed=3).result(timeout=300)
    pages = loop.stats.get("pages")
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
                    "ssm_rows_updated": np.int32(12), "ssm_tokens_scanned": np.int32(40),
                    "gdn_rows_updated": np.int32(15), "gdn_tokens_scanned": np.int32(75)})
    grew = {k: v - before.get(k, 0) for k, v in MODEL_COUNTERS.snapshot().items()}
    assert grew == {"moe_layer_calls": 2, "moe_pairs": 8, "moe_experts_touched": 3,
                    "moe_max_load": 7, "mla_latent_rows_read": 7,
                    "ssm_state_updates": 12, "ssm_tokens_scanned": 40,
                    "gdn_state_updates": 15, "gdn_tokens_scanned": 75}
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
                 "mla_latent_rows_read", "ssm_state_updates", "ssm_tokens_scanned",
                 "gdn_state_updates", "gdn_tokens_scanned"):
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


# -- the kernel on a TPU -------------------------------------------------------------------------

@pytest.mark.parametrize("requested", ["auto", "pallas"])
def test_paged_kernel_fallback_for_latent_pages_is_counted_on_a_tpu(monkeypatch, requested):
    """On a TPU a latent model takes the Pallas kernel for latent pages, as a
    GQA model takes its own: no fallback is counted, under any reason."""
    from k_llms_tpu.ops import paged_attention
    from k_llms_tpu.utils.observability import KERNEL_EVENTS

    monkeypatch.setattr(paged_attention.jax, "default_backend", lambda: "tpu")
    before = KERNEL_EVENTS.snapshot()
    for config in (CFG, get_config("joyai-tiny"), get_config("tiny")):
        assert paged_attention.resolve_paged_attention_impl(requested, config=config) == "pallas"
        assert paged_attention.resolve_paged_attention_impl(
            requested, config=config, record=False) == "pallas"
    grew = {k: v - before.get(k, 0) for k, v in KERNEL_EVENTS.snapshot().items()}
    assert not any(v for k, v in grew.items() if "fallback" in k)
    assert KERNEL_EVENTS.get("kernel.paged_attn_fallback.mla") == before.get(
        "kernel.paged_attn_fallback.mla", 0)
