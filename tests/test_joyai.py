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
             chunk=0, ladder=(), max_prompt=64, eos_ids=None, sink=None, loop_max_new=64):
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    engine = shared_engine(config, kv_layout="paged", kv_page_size=8)
    loop = ContinuousDecodeLoop(
        engine, width=8, max_prompt=max_prompt, max_new=loop_max_new, prefill_chunk_tokens=chunk,
        prefill_chunk_ladder=ladder, eos_ids=eos_ids or get_tokenizer(None).stop_ids)
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


@pytest.mark.parametrize("plen", [161, 250])
def test_drafted_admission_after_a_ladder_turn_equals_whole_prompt_undrafted(grammar, plen):
    """The lane on the ladder (a turn of 128, then a padded one of 64 or of
    128): the module's cache rows beside the stack's from both turns, ``h`` at
    the last valid position into ``_admit_drafts``. The drafted streams are
    the undrafted loop's over whole-prompt admission."""
    kw = dict(grammar=grammar, n=8, max_new=24, plen=plen, max_prompt=256)
    drafted, stats = run_loop(CFG, chunk=32, ladder=(32, 64, 128), **kw)
    plain, _ = run_loop(PLAIN, chunk=0, **kw)
    same_streams(drafted, plain)
    assert (stats["prefill_chunks"], stats["prefill_tokens"]) == (2, plen)


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
#: this test's own ``program_texts`` there. The three ``chunk`` programs:
#: ``qwen2-shaped`` and ``xing4-tiny`` the parent of PR 34 (53cb5d9), where the
#: drafted step, the held share and the plain residual path are static branches
#: they never take; ``nemotron3-tiny`` the parent of PR 35 (7ba35d2). The six
#: ``step`` / ``step_g`` programs are PR 37's own tree (the child of a70e348):
#: their text changed by construction, since a step takes one packed host array
#: and block tables where it took 11 to 13 arrays and token-level slot indices
#: (``continuous._unpack_step``, ``paging.expand_tables``), which is that change.
#: They pin every later PR; that PR 37 itself moved no model's loop is held by
#: the tokens below (``PARENT_STREAMS``, recorded on a70e348). ``qwen2-shaped``'s
#: three were made again where its block's rotary embedding became a rotation of
#: the head's lanes (``llama.rope_lanes``): a new program text with the same
#: values, which the same ``PARENT_STREAMS`` hold.
PARENT_PROGRAMS = {
    "qwen2-shaped": {
        "step": "dfeb6fdf80d878b155804f352b1f22e31249e848ac67aa6270e81f7a6941dc96",
        "step_g": "7bd4532584220564d45b973c0d367c380dafc670a576368efde41d61c89151fc",
        "chunk": "b9910b6ab864d49f39454861eb2bd36ff1fb301ed724beccc7865393496c3525",
    },
    "xing4-tiny": {
        "step": "8a80ec3a8e8d1dd7353e495c2b0f8a72d71fb6cdef9965b620cb970a375cffdf",
        "step_g": "ff0ddadba9a602394d8c338999dae7af07e8c43bf99a08ba979fff3f301192ec",
        "chunk": "ac609ad56b00b2c614ea8a4fa6213d824257ad6a4729288f1f239a2cb5e8b999",
    },
    "nemotron3-tiny": {
        "step": "6d06aba1dbb8878811c2b36b9c41c9e27204bdec0a9cf8067cebfa503b7d7d57",
        "step_g": "c77c4f82b0e0f37f7a60e78ede5218f6489b31938051ae10a812644380ee88d4",
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
    # A step's one host array: the mirrors as a new loop holds them, every table empty.
    packed = jnp.asarray(loop._pack_step(np.zeros((4, 1), np.int32), loop._pages.tables))
    no_poison = jnp.zeros((4,), bool)
    pool, out = loop._pool, {}
    out["step"] = loop._step_fn.lower(
        engine.params, pool.kv.k, pool.kv.v, packed, no_poison, state=loop._state).as_text()
    tiny_schema = {"type": "object", "properties": {"a": {"type": "boolean"}}, "required": ["a"],
                   "additionalProperties": False}
    loop._install_grammar(grammar_for_schema(tiny_schema, grammar_vocab(get_tokenizer(None))))
    out["step_g"] = loop._grammar_programs()["step"].lower(
        engine.params, pool.kv.k, pool.kv.v, packed, no_poison, *loop._g_tabs(),
        state=loop._state).as_text()
    out["chunk"] = engine._get_prefill_chunk(32, 64, True).lower(
        engine.params, jnp.zeros((1, 32), jnp.int32), llama.init_cache(config, 1, 64),
        jnp.int32(0), jnp.int32(32), state=llama.init_state(config, 1)).as_text()
    loop.stop()
    return out


SHAPED = get_config("tiny").with_(name="qwen2-shaped", qkv_bias=True, rope_theta=1e6, rms_eps=1e-6)


@pytest.fixture(scope="module")
def lowered():
    return {"qwen2-shaped": program_texts(SHAPED),
            **{name: program_texts(get_config(name)) for name in ("xing4-tiny", "nemotron3-tiny")}}


@pytest.mark.parametrize("program", ["step", "step_g", "chunk"])
@pytest.mark.parametrize("model", sorted(PARENT_PROGRAMS))
def test_other_models_loop_programs_are_the_parents(lowered, model, program):
    text = lowered[model][program]
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_PROGRAMS[model][program]


# -- the loop's tokens are the parent's --------------------------------------------------------

#: sha256 over the tokens, lengths and finish reasons of ``loop_streams`` as the
#: parent of PR 37 (a70e348) emitted them on the CPU: that PR re-made the six
#: step hashes above (a step takes one packed array now), so the proof that no
#: model's loop moved is what comes out of it.
PARENT_STREAMS = {
    "joyai-tiny-drafted": "92a50f4de477d2b0cd43c2a4a145e7efcbe15dd81335ea2f0d3acd714305b2a6",
    "joyai-tiny-drafted-grammar": "9222b97ad9fe9b733b0e91d35b3a0da57780f56ade5bf432ba733eda68855867",
    "nemotron3-tiny-paged": "0db372a287dda3a282cdf517d83ca799b9f98827b0bc7d090773c4096851403f",
    "nemotron3-tiny-paged-grammar": "e512348d95da253a2777e3a010cb293efdf10fd075c2fae5352665dcb688e946",
    "qwen2-shaped-dense": "a423609e1c1bbb87e7e2ce148a099c30aa1fd55f1f7389c730d4f75247a8fa4a",
    "qwen2-shaped-dense-grammar": "782bf52ff12f601c6c6329ec1c7ffba791a6ce7426b56536234a74fd11f0518a",
    "qwen2-shaped-paged": "a423609e1c1bbb87e7e2ce148a099c30aa1fd55f1f7389c730d4f75247a8fa4a",
    "qwen2-shaped-paged-grammar": "782bf52ff12f601c6c6329ec1c7ffba791a6ce7426b56536234a74fd11f0518a",
    "xing4-tiny-paged": "916411c142e43e1b4844d6bb483fd774e4f7a13cc2da306e4db83896f4ad699e",
    "xing4-tiny-paged-grammar": "30857165657fedad2619e802a08c78ee0da8764c10117f6859d9f6bf7ea80b78",
}

STREAM_CASES = {
    "qwen2-shaped-dense": ("qwen2-shaped", "dense", False),
    "qwen2-shaped-dense-grammar": ("qwen2-shaped", "dense", True),
    "qwen2-shaped-paged": ("qwen2-shaped", "paged", False),
    "qwen2-shaped-paged-grammar": ("qwen2-shaped", "paged", True),
    "xing4-tiny-paged": ("xing4-tiny", "paged", False),
    "xing4-tiny-paged-grammar": ("xing4-tiny", "paged", True),
    "nemotron3-tiny-paged": ("nemotron3-tiny", "paged", False),
    "nemotron3-tiny-paged-grammar": ("nemotron3-tiny", "paged", True),
    "joyai-tiny-drafted": ("joyai-tiny", "paged", False),
    "joyai-tiny-drafted-grammar": ("joyai-tiny", "paged", True),
}


def loop_streams(model, layout, grammar):
    """Two seeded requests through one loop, the second into slots the first
    left (and one more): four rows over a prompt that ends mid-page, then
    three with a seed past 2**31, greedy beside sampled."""
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    config = SHAPED if model == "qwen2-shaped" else get_config(model)
    engine = shared_engine(config, kv_layout=layout, kv_page_size=8)
    loop = ContinuousDecodeLoop(engine, width=6, max_prompt=64, max_new=64,
                                eos_ids=get_tokenizer(None).stop_ids)
    rng = np.random.RandomState(2)
    digest = hashlib.sha256()
    try:
        for n, plen, temperature, seed in ((4, 37, 0.8, 5), (3, 48, 0.0, 2**31 + 11)):
            prompt = [int(t) for t in rng.randint(32, 127, size=plen)]
            got = loop.submit(prompt, n=n, max_new=64 if grammar else 20, temperature=temperature,
                              top_p=0.95, seed=seed, grammar=grammar).result(timeout=300)
            digest.update(np.asarray(got.tokens, np.int32).tobytes())
            digest.update(np.asarray(got.lengths, np.int32).tobytes())
            digest.update(",".join(got.finish_reasons).encode())
    finally:
        loop.stop()
    return digest.hexdigest()


@pytest.mark.parametrize("case", sorted(STREAM_CASES))
def test_seeded_loop_streams_are_the_parents(grammar, case):
    model, layout, constrained = STREAM_CASES[case]
    assert loop_streams(model, layout, grammar if constrained else None) == PARENT_STREAMS[case]
