"""JoyAI-LLM-Flash (models/latent.py without streams, a held share of the
experts, the next-token module; engine/continuous.py's drafted step) against
the plain reference (tests/joyai_reference.py, the benchmark's copy byte for
byte) at the ``joyai-tiny`` size on the CPU, in float32.

Tolerances as in tests/test_xing4.py: both sides compute in float32, so 1e-4
absolute on logits of magnitude ~4 is twenty times what the comparisons read
and a thousand times under what a missing term gives. Token streams are held
EQUAL between the drafted loop and the same preset with the module count 0:
every emitted token is the draw the one-token loop makes.
"""

import filecmp
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import joyai_reference as ref
from conftest import shared_engine, shared_params
from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
from k_llms_tpu.models import get_config, latent, llama
from k_llms_tpu.models.config import register_config
from k_llms_tpu.models.llama import KVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4
CFG = get_config("joyai-tiny")
#: The same preset with the module count 0: the undrafted loop the streams are held to.
PLAIN = register_config(CFG.with_(name="joyai-tiny-undrafted", num_nextn_predict_layers=0))
SCHEMA = json.load(open(os.path.join(ROOT, "benchmark", "workloads", "extract.json")))["response_format"]


def hf_dict(c):
    """The preset in the published config.json's own key names."""
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_heads,
        q_lora_rank=c.q_lora_rank, kv_lora_rank=c.kv_lora_rank,
        qk_nope_head_dim=c.qk_nope_head_dim, qk_rope_head_dim=c.qk_rope_head_dim,
        v_head_dim=c.v_head_dim, rms_norm_eps=c.rms_eps, rope_theta=c.rope_theta,
        rope_scaling=None, n_routed_experts=c.num_experts,
        num_experts_per_tok=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor, norm_topk_prob=True,
        first_k_dense_replace=c.first_k_dense, num_hidden_layers=c.num_layers,
        num_nextn_predict_layers=c.num_nextn_predict_layers,
    )


HELD = (CFG.expert_offset, CFG.held_experts)


@pytest.fixture(scope="module")
def params():
    return shared_params(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, 500, (2, 72)).astype(np.int32)


@pytest.fixture(scope="module")
def reference(params, tokens):
    """[(logits [S, V], module logits [S - 1, V])] of the two sequences."""
    return [tuple(np.asarray(a) for a in ref.forward(hf_dict(CFG), params, tokens[b], HELD))
            for b in range(2)]


@pytest.fixture(scope="module")
def grammar():
    from k_llms_tpu.engine.grammar import grammar_for_schema, grammar_vocab
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    return grammar_for_schema(SCHEMA, grammar_vocab(get_tokenizer(None)))


def close(got, want):
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL, rtol=0)


# -- the presets ---------------------------------------------------------------------

def test_reference_copies_are_equal():
    assert filecmp.cmp(os.path.join(ROOT, "tests", "joyai_reference.py"),
                       os.path.join(ROOT, "benchmark", "joyai_reference.py"), shallow=False)


def test_cut8_is_the_arithmetic_of_the_configuration_file():
    cut = get_config("joyai-llm-flash-cut8")
    assert (cut.paging_layers, cut.kv_bytes_per_token) == (9, 9 * 640 * 2)  # 576 stored 640 wide
    assert (cut.held_experts, cut.num_experts, cut.hc_mult) == (128, 256, 1)
    count = latent.param_count(cut)
    assert abs(count - 5.693e9) < 2e6  # ISSUE 34's 5,693 M
    with open(os.path.join(ROOT, "benchmark", "configs", "joyai-llm-flash.json")) as f:
        hf = json.load(f)
    assert hf["n_routed_experts"] == cut.held_experts and hf["published"]["n_routed_experts"] == 256
    assert hf["num_hidden_layers"] == cut.num_layers and hf["serve"]["model"] == cut.name


def test_without_streams_the_block_carries_no_mixer_parameters(params):
    names = set(params["layers"]) | set(params["dense_layers"]) | set(params["mtp"]["layers"])
    assert not [n for n in names if n.startswith("hc_")]
    assert params["layers"]["w_router"].shape == (2, CFG.hidden_size, CFG.num_experts)
    assert params["layers"]["w_up"].shape[:2] == (2, CFG.held_experts)
    # The stack's weights do not depend on the module: the undrafted preset has the same.
    plain = shared_params(PLAIN)
    assert "mtp" not in plain
    np.testing.assert_array_equal(np.asarray(plain["layers"]["w_up"]), np.asarray(params["layers"]["w_up"]))


# -- the program against the reference -------------------------------------------------

@pytest.mark.parametrize("row", [0, 1])
def test_forward_and_the_modules_logits_match_reference(params, tokens, reference, row):
    logits, _, module = llama.forward(
        CFG, params, jnp.asarray(tokens), jnp.ones(tokens.shape, jnp.int32), with_module=True)
    close(logits[row], reference[row][0])
    close(module[row], reference[row][1])


@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_prefill_then_drafted_steps_match_reference(params, tokens, reference, chunk):
    """The drafted loop's programs by hand: chunks into a staging cache and a
    page pool of 9 cache layers, admission's module step on (h_{L-1}, first
    token), then steps that verify two positions a row, teacher-forced; rows
    alternate between an accepted and a rejected draft, and a rejected draft's
    rows (the stack's at P+1, the module's at P+2) are overwritten with
    garbage after the step: the next step never reads them."""
    (want, want_mod), seq, plen, ps, rows = reference[1], tokens[1], 50, 8, 3
    bucket, flat, L = 64, 40 * 8, CFG.num_layers
    cache, state = llama.init_cache(CFG, 1, bucket), llama.init_state(CFG, 1)
    assert cache.k.shape[0] == L + 1
    pool = KVCache(k=jnp.zeros((L + 1, flat, 1, 40)), v=jnp.zeros((L + 1, flat, 1, 0)))
    for start in range(0, plen, chunk):
        valid = min(chunk, plen - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :valid] = seq[start:start + valid]
        aux = {}
        logits, cache, k_cols, v_cols = llama.prefill_chunk_step_paged(
            CFG, params, jnp.asarray(toks), cache, jnp.int32(start), jnp.int32(valid),
            aux=aux, state=state)
        assert aux["moe_counts"].shape == (2, CFG.held_experts)
        close(logits[0], want[start + valid - 1])
        slots = ps + start + np.arange(valid)  # page 0 is the trash page
        pool = KVCache(k=pool.k.at[:, slots].set(k_cols[:, :valid]), v=pool.v)
    P, G = 64, 12
    pidx = np.tile(np.arange(P) % ps, (rows, 1)).astype(np.int32)
    pidx[:, :plen] = ps + np.arange(plen)
    gidx = np.stack([100 + 16 * r + np.arange(G) for r in range(rows)]).astype(np.int32)
    plens = jnp.full((rows,), plen)
    # Admission: the module's row at position L, and the logits behind the first draft.
    h_last = jnp.tile(state["mtp_h"][0], (rows, 1))
    mlogits, m_cols = llama.paged_draft_step(
        CFG, params, h_last[:, None], jnp.full((rows, 1), seq[plen]), jnp.zeros((rows,), jnp.int32),
        plens, pool, jnp.asarray(pidx), jnp.asarray(gidx))
    pool = KVCache(k=pool.k.at[L, gidx[:, 0]].set(m_cols[:, 0]), v=pool.v)
    for row in range(rows):
        close(mlogits[row, 0], want_mod[plen - 1])
    g = np.zeros((rows,), np.int32)
    for step in range(4):
        accept = np.array([(r + step) % 2 == 0 for r in range(rows)])
        at = plen + g
        draft = np.where(accept, seq[at + 1], (seq[at + 1] + 7) % 500)
        aux = {}
        logits, k_cols, _, hidden = llama.paged_verify_step(
            CFG, params, jnp.stack([jnp.asarray(seq[at]), jnp.asarray(draft)], axis=1),
            jnp.asarray(g), plens, pool, jnp.asarray(pidx), jnp.asarray(gidx), aux=aux,
            return_hidden=True)
        assert k_cols.shape == (L, rows, 2, 1, 40)
        mlogits, m_cols = llama.paged_draft_step(
            CFG, params, hidden, jnp.asarray(np.stack([seq[at + 1], seq[at + 2]], axis=1)),
            jnp.asarray(g + 1), plens, pool, jnp.asarray(pidx), jnp.asarray(gidx), aux=aux)
        assert aux["moe_counts"].shape == (3, CFG.held_experts)  # the module's layer too
        k = pool.k
        for r in range(rows):
            close(logits[r, 0], want[at[r]])
            close(mlogits[r, 0], want_mod[at[r]])
            k = k.at[:L, gidx[r, g[r]:g[r] + 2]].set(k_cols[:, r])
            k = k.at[L, gidx[r, g[r] + 1:g[r] + 3]].set(m_cols[r])
            if accept[r]:
                close(logits[r, 1], want[at[r] + 1])
                close(mlogits[r, 1], want_mod[at[r] + 1])
            else:  # stale rows: the next step overwrites them before anything reads them
                k = k.at[:L, gidx[r, g[r] + 1]].set(1e3)
                k = k.at[L, gidx[r, g[r] + 2]].set(1e3)
        pool = KVCache(k=k, v=pool.v)
        g = g + np.where(accept, 2, 1).astype(np.int32)


def test_the_two_halves_of_the_experts_add_up_to_the_uncut_layer(tokens):
    """Each chip's routed sum over its 4 of 8 experts, added, plus the shared
    expert once, is the reference's layer with every expert."""
    whole = CFG.with_(name="joyai-tiny-whole", experts_held=0)
    layer = {k: v[0] for k, v in shared_params(whole)["layers"].items()}
    h = jnp.asarray(np.random.RandomState(3).randn(40, CFG.hidden_size), jnp.float32)
    total, seen = 0.0, 0
    for offset in (0, 4):
        half = CFG.with_(name=f"joyai-tiny-half{offset}", experts_held=4, expert_offset=offset)
        part = {k: (v[offset:offset + 4] if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in layer.items()}
        out, counts, chosen = latent.routed_experts(half, part, h)
        assert counts.shape == (4,)
        seen += int(counts.sum())
        total = total + out
    assert seen == 40 * CFG.num_experts_per_tok  # every pair on exactly one chip
    with jax.default_matmul_precision("highest"):
        want = ref.experts(hf_dict(whole), layer, h) - ref.swiglu(
            h, layer["ws_gate"], layer["ws_up"], layer["ws_down"])
    close(total, np.asarray(want))
    # The whole-expert form indexes every expert's stack: not over a share.
    with pytest.raises(NotImplementedError, match="dense_share"):
        latent.routed_experts(half, part, h, dense_share=0.125)


# -- the drafted loop against the undrafted one -------------------------------------------

def run_loop(config, *, grammar=None, n=4, max_new=64, temperature=0.8, seed=5, plen=37,
             chunk=0, eos_ids=None, sink=None, loop_max_new=64):
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    engine = shared_engine(config, kv_layout="paged", kv_page_size=8)
    loop = ContinuousDecodeLoop(
        engine, width=8, max_prompt=64, max_new=loop_max_new, prefill_chunk_tokens=chunk,
        eos_ids=eos_ids or get_tokenizer(None).stop_ids)
    prompt = [int(t) for t in np.random.RandomState(1).randint(32, 127, size=plen)]
    try:
        result = loop.submit(prompt, n=n, max_new=max_new, temperature=temperature, top_p=0.95,
                             seed=seed, grammar=grammar, token_sink=sink).result(timeout=300)
        return result, loop.stats
    finally:
        loop.stop()


def same_streams(drafted, plain):
    np.testing.assert_array_equal(drafted.tokens, plain.tokens)
    np.testing.assert_array_equal(drafted.lengths, plain.lengths)
    assert drafted.finish_reasons == plain.finish_reasons
    np.testing.assert_allclose(drafted.logprobs, plain.logprobs, atol=1e-4, rtol=0)


@pytest.mark.parametrize("n", [1, 8])
@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("constrained", [False, True])
def test_drafted_streams_equal_undrafted(grammar, constrained, temperature, n):
    kw = dict(grammar=grammar if constrained else None, n=n, temperature=temperature,
              max_new=64 if constrained else 12, chunk=32 if n == 8 else 0, plen=50 if n == 8 else 37)
    (drafted, stats), (plain, plain_stats) = run_loop(CFG, **kw), run_loop(PLAIN, **kw)
    same_streams(drafted, plain)
    if constrained:  # the grammar forces most tokens: most drafts are accepted
        assert stats["steps"] < 0.7 * plain_stats["steps"]
        text = bytes(int(t) for t in drafted.tokens[0][:drafted.lengths[0]] if t < 256)
        assert set(json.loads(text)) == {"kind", "paid", "currency"}
    if n == 8:  # the rows' first writes copied the shared prompt page
        assert stats["pages"]["cow_copies"] >= 8


@pytest.mark.parametrize("max_new", [1, 2, 7, 8])
def test_max_tokens_odd_and_even(grammar, max_new):
    drafted, _ = run_loop(CFG, grammar=grammar, max_new=max_new)
    plain, _ = run_loop(PLAIN, grammar=grammar, max_new=max_new)
    same_streams(drafted, plain)
    assert list(drafted.lengths) == [max_new] * 4 and set(drafted.finish_reasons) == {"length"}


@pytest.mark.parametrize("seed", [3, 11])
def test_an_end_token_at_either_position_ends_the_row(grammar, seed):
    """Under the grammar the end token is forced after the closing brace, so it
    comes as an accepted draft's second token or as the next step's first;
    with every third id an end token, unconstrained rows end at random places."""
    drafted, _ = run_loop(CFG, grammar=grammar, seed=seed, n=8)
    plain, _ = run_loop(PLAIN, grammar=grammar, seed=seed, n=8)
    same_streams(drafted, plain)
    assert set(drafted.finish_reasons) == {"stop"}
    ends = list(range(0, 512, 3))
    drafted, _ = run_loop(CFG, seed=seed, n=8, eos_ids=ends, max_new=24)
    plain, _ = run_loop(PLAIN, seed=seed, n=8, eos_ids=ends, max_new=24)
    same_streams(drafted, plain)
    for row, length in zip(drafted.tokens, drafted.lengths):
        assert not set(row[:length - 1].tolist()) & set(ends)  # nothing emitted past an end


def test_a_sink_gets_every_token_index_once_and_in_order(grammar):
    seen = []
    drafted, _ = run_loop(CFG, grammar=grammar, sink=lambda step, row: seen.append((step, row.copy())))
    assert [s for s, _ in seen] == list(range(int(drafted.lengths.max())))
    for step, row in seen:
        for j in range(4):
            if step < drafted.lengths[j]:
                assert row[j] == drafted.tokens[j][step]


def test_a_hung_drafted_step_replays_the_same_stream(grammar):
    """The journal takes one or two tokens a row: a drafted step that hangs is
    abandoned, the loop rebuilds, the request replays from its prompt, and the
    sink sees every token index once."""
    from k_llms_tpu.engine.tokenizer import get_tokenizer
    from k_llms_tpu.reliability import failpoints as fp
    from k_llms_tpu.reliability.failpoints import FailSpec
    from k_llms_tpu.reliability.supervisor import LaunchBudgetModel

    base, _ = run_loop(CFG, grammar=grammar, n=2, seed=23)
    engine = shared_engine(CFG, kv_layout="paged", kv_page_size=8)
    budget = LaunchBudgetModel(base_s=0.1, per_token_s=0.01, multiplier=1.0,
                               min_budget_s=4.0, max_budget_s=4.0)
    loop = ContinuousDecodeLoop(
        engine, width=8, max_prompt=64, max_new=64, eos_ids=get_tokenizer(None).stop_ids,
        budget_model=budget, rebuild_fn=lambda: engine, max_rebuilds=3)
    sunk = []
    prompt = [int(t) for t in np.random.RandomState(1).randint(32, 127, size=37)]
    try:
        with fp.failpoints({"continuous.step": FailSpec(action="hang", times=1, delay=12.0)}):
            got = loop.submit(prompt, n=2, max_new=64, temperature=0.8, top_p=0.95, seed=23,
                              grammar=grammar,
                              token_sink=lambda s, t: sunk.append(s)).result(timeout=180)
        stats = loop.stats
    finally:
        loop.stop()
    assert stats["restarts"] >= 1 and stats["replayed_rows"] >= 2
    same_streams(got, base)
    assert sunk == list(range(int(got.lengths.max())))


def test_counters_count_row_steps_drafts_and_tokens(grammar):
    from k_llms_tpu.utils.observability import GRAMMAR_EVENTS, MODEL_COUNTERS, SPEC_COUNTERS

    from k_llms_tpu.observability.trace import RequestTrace, use_trace

    before = {**SPEC_COUNTERS.snapshot(), **MODEL_COUNTERS.snapshot(), **GRAMMAR_EVENTS.snapshot()}
    with use_trace(RequestTrace()) as trace:
        drafted, stats = run_loop(CFG, grammar=grammar)
    after = {**SPEC_COUNTERS.snapshot(), **MODEL_COUNTERS.snapshot(), **GRAMMAR_EVENTS.snapshot()}
    grew = {k: v - before.get(k, 0) for k, v in after.items()}
    # A row step is a row in a step, whatever it emitted; tokens are counted apart.
    assert grew["spec_drafts_verified"] == stats["row_steps"]
    assert grew["spec_tokens_emitted"] == int(drafted.lengths.sum()) - 4  # the first tokens are admission's
    assert grew["spec_drafts_accepted"] == grew["spec_tokens_emitted"] - grew["spec_drafts_verified"]
    assert grew["spec_drafts_accepted"] > 0.8 * grew["spec_drafts_verified"]
    # The request's own record (/debug/requests) carries the same counts and their share.
    notes = trace.annotations_snapshot()
    assert (notes["drafts_verified"], notes["drafts_accepted"]) == (
        grew["spec_drafts_verified"], grew["spec_drafts_accepted"])
    assert notes["draft_accepted_share"] == notes["drafts_accepted"] / notes["drafts_verified"]
    assert grew["grammar.masked_steps"] == int(drafted.lengths.sum())  # one mask a token
    # The module's expert layer is one more layer call a step (a prompt runs the
    # stack's two: it writes the module's cache rows without its experts).
    assert grew["moe_layer_calls"] == 3 * stats["steps"] + 2 and grew["mla_latent_rows_read"] > 0


def test_stop_sequences_across_a_two_token_emission():
    """The stop scan is the host's, over the text: a stop string that ends
    inside a two-token emission cuts the same text, with the same usage."""
    from k_llms_tpu import KLLMs

    outs = []
    for config in (CFG, PLAIN):
        client = KLLMs(backend="tpu", model=config.name, continuous_batching=True,
                       engine=shared_engine(config, kv_layout="paged"))
        r = client.chat.completions.create(
            messages=[{"role": "user", "content": "an invoice"}], model=config.name, n=4, seed=9,
            max_tokens=64, temperature=0.8, stop=['"paid"', "curr"],
            response_format={"type": "json_object"})
        outs.append(([c.message.content for c in r.choices], [c.finish_reason for c in r.choices],
                     r.usage.completion_tokens))
    assert outs[0] == outs[1]
    assert all('"paid"' not in text and "curr" not in text for text in outs[0][0])


@pytest.mark.parametrize("kw, named", [
    (dict(kv_layout="paged", prefix_cache_size=4), "prefix_cache_size=4"),
    (dict(kv_layout="dense"), "kv_layout='dense'"),
    (dict(kv_layout="paged", quantize="int8"), "int8"),
    (dict(kv_layout="paged", speculative="prompt_lookup"), "speculative"),
])
def test_what_the_module_cannot_ride_is_refused_by_name(params, kw, named):
    from k_llms_tpu.engine.engine import LocalEngine

    with pytest.raises(NotImplementedError, match=named):
        LocalEngine(CFG, params=params, use_mesh=False, **kw)


# -- what other models' programs did not move -------------------------------------------------

#: sha256 of each program's StableHLO text on the tree named beside it, made by
#: this test's own ``program_texts`` there. ``qwen2-shaped``: the parent of PR 34
#: (53cb5d9); the drafted step, the held share and the plain residual path are
#: static branches it never takes. ``nemotron3-tiny``: the parent of PR 35
#: (7ba35d2); its pool ``[1, flat, 2, 128]`` keeps its rows and its layer-axis
#: movers. ``xing4-tiny``: ``step`` and ``step_g`` are PR 35's own tree. They
#: moved because the pool they take is stored 128 lanes wide (40 before) and
#: the step gathers and writes it by (layer, slot) in the flat view
#: (``paging.scatter_rows``), which is the change; ``chunk`` never touches the pool
#: and is still PR 34's parent's.
PARENT_PROGRAMS = {
    "qwen2-shaped": {
        "step": "3776df69d413990ace1e724834e2f4f9e8d7782290cc3c61bb612913e867647a",
        "step_g": "e1a6f10c778e0fc9d140081bb7bfe63f1633656083caa2ebb9abf564b8dd9d74",
        "chunk": "b1ca8438c73e7a21b7a5cfa2676bde8ec43eeb67660c81dae26499ce9a8f53e4",
    },
    "xing4-tiny": {
        "step": "af02ea5c567784b72cf3c9cbbcf0bfc525b2eb5724bb522b55ea7cabe94efb38",
        "step_g": "679ee68d518a1c94efadbba753988567cf749449d00f046b47dd78745336de3d",
        "chunk": "ac609ad56b00b2c614ea8a4fa6213d824257ad6a4729288f1f239a2cb5e8b999",
    },
    "nemotron3-tiny": {
        "step": "1575d0d0d6c4a7bb3d020e0a6aa372cb7c48af6425e159304c4ff6ba49517cbf",
        "step_g": "0063ce72f12593099783ca4c6861556922ee8004209fdd60e4465e06af75b8b4",
        "chunk": "c3775e68172d09a2449a21d312f484c80f4fec94927087cee1838303005af0d2",
    },
}


def program_texts(config):
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.engine.grammar import grammar_for_schema, grammar_vocab
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    engine = LocalEngine(config, use_mesh=False, kv_layout="paged", kv_page_size=8)
    loop = ContinuousDecodeLoop(engine, width=4, max_prompt=32, max_new=8)
    loop._build_device_state()
    z = lambda dtype: jnp.zeros((4,), dtype)  # noqa: E731
    rows = (z(jnp.int32), z(jnp.int32), z(jnp.int32), z(bool), z(jnp.uint32), z(jnp.int32),
            z(jnp.float32), z(jnp.float32))
    layout = (jnp.asarray(loop._pages.prefix_idx), jnp.asarray(loop._pages.gen_idx), z(jnp.int32))
    pool, out = loop._pool, {}
    out["step"] = loop._step_fn.lower(
        engine.params, pool.kv.k, pool.kv.v, *rows, *layout, z(bool), state=loop._state).as_text()
    tiny_schema = {"type": "object", "properties": {"a": {"type": "boolean"}}, "required": ["a"],
                   "additionalProperties": False}
    loop._install_grammar(grammar_for_schema(tiny_schema, grammar_vocab(get_tokenizer(None))))
    out["step_g"] = loop._grammar_programs()["step"].lower(
        engine.params, pool.kv.k, pool.kv.v, *rows, *layout, z(bool), z(jnp.int32), z(bool),
        *loop._g_tabs(), state=loop._state).as_text()
    out["chunk"] = engine._get_prefill_chunk(32, 64, True).lower(
        engine.params, jnp.zeros((1, 32), jnp.int32), llama.init_cache(config, 1, 64),
        jnp.int32(0), jnp.int32(32), state=llama.init_state(config, 1)).as_text()
    loop.stop()
    return out


@pytest.fixture(scope="module")
def lowered():
    shaped = get_config("tiny").with_(name="qwen2-shaped", qkv_bias=True, rope_theta=1e6, rms_eps=1e-6)
    return {"qwen2-shaped": program_texts(shaped),
            **{name: program_texts(get_config(name)) for name in ("xing4-tiny", "nemotron3-tiny")}}


@pytest.mark.parametrize("program", ["step", "step_g", "chunk"])
@pytest.mark.parametrize("model", sorted(PARENT_PROGRAMS))
def test_other_models_loop_programs_are_the_parents(lowered, model, program):
    text = lowered[model][program]
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_PROGRAMS[model][program]
