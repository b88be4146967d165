"""JoyAI-LLM-Flash (models/latent.py without streams, a held share of the
experts, the next-token module; engine/continuous.py's drafted step): the
checks no other model has, against the plain reference
(benchmark/joyai_reference.py) at the ``joyai-tiny`` size on the CPU, in
float32. What every served model owes is tests/test_served_models.py's.

Tolerances as in tests/test_xing4.py: both sides compute in float32, so 1e-4
absolute on logits of magnitude ~4 is twenty times what the comparisons read
and a thousand times under what a missing term gives. Token streams are held
EQUAL between the drafted loop and the same preset with the module count 0:
every emitted token is the draw the one-token loop makes.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, shared_engine, shared_loop, shared_params, stats_grown
from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
from k_llms_tpu.models import get_config, latent
from k_llms_tpu.models.config import register_config
from served_models import ROWS, joyai_hf as hf_dict

TOL = 1e-4
ROW = ROWS["joyai-tiny"]
CFG, ref = ROW.cfg, ROW.ref
#: The same preset with the module count 0: the undrafted loop the streams are held to.
PLAIN = register_config(CFG.with_(name="joyai-tiny-undrafted", num_nextn_predict_layers=0))
SCHEMA = json.load(open(os.path.join(ROOT, "benchmark", "workloads", "extract.json")))["response_format"]


@pytest.fixture(scope="module")
def params():
    return shared_params(CFG)


@pytest.fixture(scope="module")
def grammar():
    from k_llms_tpu.engine.grammar import grammar_for_schema, grammar_vocab
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    return grammar_for_schema(SCHEMA, grammar_vocab(get_tokenizer(None)))


def close(got, want):
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL, rtol=0)


# -- the presets ---------------------------------------------------------------------

def test_cut8_is_the_arithmetic_of_the_configuration_file():
    cut = get_config("joyai-llm-flash-cut8")
    assert (cut.paging_layers, cut.kv_bytes_per_token) == (9, 9 * 640 * 2)  # 576 stored 640 wide
    assert (cut.held_experts, cut.num_experts, cut.hc_mult) == (128, 256, 1)
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


# -- the experts against the reference -------------------------------------------------

def test_the_two_halves_of_the_experts_add_up_to_the_uncut_layer():
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
             chunk=0, ladder=(), max_prompt=64, eos_ids=None, sink=None):
    """One request through the session's loop of that shape; the loop's
    counters as this request moved them."""
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    loop = shared_loop(config, kv_page_size=8, width=8, max_prompt=max_prompt, max_new=64,
                       prefill_chunk_tokens=chunk, prefill_chunk_ladder=ladder,
                       eos_ids=eos_ids or get_tokenizer(None).stop_ids)
    prompt = [int(t) for t in np.random.RandomState(1).randint(32, 127, size=plen)]
    before = loop.stats
    result = loop.submit(prompt, n=n, max_new=max_new, temperature=temperature, top_p=0.95,
                         seed=seed, grammar=grammar, token_sink=sink).result(timeout=300)
    return result, stats_grown(before, loop.stats)


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
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.engine.tokenizer import get_tokenizer
    from k_llms_tpu.reliability import failpoints as fp
    from k_llms_tpu.reliability.failpoints import FailSpec
    from k_llms_tpu.reliability.supervisor import LaunchBudgetModel

    base, _ = run_loop(CFG, grammar=grammar, n=2, seed=23)
    # An engine of its own: a step abandoned while it holds the donated pool
    # leaves that pool dead, and no other test may be handed it.
    engine = LocalEngine(CFG, params=shared_params(CFG), use_mesh=False, kv_layout="paged",
                         kv_page_size=8)
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
