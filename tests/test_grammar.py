"""Compiled grammar masks (PR 12): schema-constrained n-way decoding.

The load-bearing pins: the packed uint32 token masks agree with the byte-DFA
oracle bit for bit (host and device), the process-wide cache makes one compile
per (schema, vocab) fleet-wide, the ``engine.grammar`` failpoint and compile
errors degrade to unconstrained decode WITHOUT erroring the request, output is
byte-identical to the pre-grammar path whenever no constraint is attached, and
constrained greedy decode parses under the schema for every TRUTH_DOCS shape.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest
from pydantic import BaseModel

from k_llms_tpu.engine.grammar import (
    CompiledGrammar,
    clear_grammar_cache,
    device_grammar,
    grammar_advance,
    grammar_cache_stats,
    grammar_for_schema,
    grammar_initial_state,
    grammar_mask_logits,
    grammar_vocab,
    validate_grammar_tokens,
)
from k_llms_tpu.engine.schema_constraint import compile_schema, validate_bytes
from k_llms_tpu.engine.tokenizer import ByteTokenizer
from k_llms_tpu.reliability import failpoints as fp
from k_llms_tpu.reliability.failpoints import FailSpec
from k_llms_tpu.utils.observability import GRAMMAR_EVENTS

TOK = ByteTokenizer()
VOCAB = grammar_vocab(TOK)


class Record(BaseModel):
    name: str
    count: int


def _events():
    return dict(GRAMMAR_EVENTS.snapshot())


def _delta(before, after, key):
    return after.get(key, 0) - before.get(key, 0)


def _grammar(schema):
    return grammar_for_schema(schema, VOCAB, vocab_digest="bytetok-test")


# ---------------------------------------------------------------------------
# mask packing + host/device parity
# ---------------------------------------------------------------------------


def test_packed_masks_match_dfa_oracle_per_token():
    """Every bit of the uint32-packed mask equals "this token's bytes survive
    the byte DFA from this state" — checked exhaustively over the byte vocab
    for a sample of states."""
    clear_grammar_cache()
    dfa = compile_schema(Record.model_json_schema())
    g = _grammar(Record.model_json_schema())
    assert isinstance(g, CompiledGrammar)
    n_states = g.trans.shape[0]
    for state in range(0, n_states, max(1, n_states // 12)):
        for token in range(TOK.vocab_size):
            bit = bool((g.masks[state, token // 32] >> (token % 32)) & 1)
            bs = VOCAB[token]
            if bs is None:
                assert not bit  # specials/pad never mask-allowed
                continue
            st = state
            for b in bs:
                st = int(g.trans[st, b]) if st >= 0 else -1
            assert bit == (st >= 0), (state, token)


def test_device_mask_and_advance_match_host_oracle():
    g = _grammar(Record.model_json_schema())
    d = device_grammar(g)
    doc = b'{"name":"ok","count":3}'
    state = grammar_initial_state(d, 1)
    eos = jnp.asarray([TOK.eos_id], jnp.int32)
    for i, byte in enumerate(doc):
        masked = grammar_mask_logits(d, jnp.zeros((1, TOK.vocab_size)), state, eos)
        allowed = np.asarray(masked[0] > jnp.finfo(jnp.float32).min / 2)
        host_state = int(np.asarray(state)[0])
        for token in range(0, TOK.vocab_size, 7):
            host_bit = bool((g.masks[host_state, token // 32] >> (token % 32)) & 1)
            if token == TOK.eos_id:
                host_bit = host_bit or bool(g.terminal[host_state])
            assert bool(allowed[token]) == host_bit, (i, token)
        assert allowed[byte], (i, chr(byte))
        state = grammar_advance(d, jnp.asarray([byte], jnp.int32), state)
    # Complete document: terminal, so EOS opens.
    masked = grammar_mask_logits(d, jnp.zeros((1, TOK.vocab_size)), state, eos)
    assert bool(np.asarray(masked[0] > jnp.finfo(jnp.float32).min / 2)[TOK.eos_id])
    ok, terminal = validate_grammar_tokens(g, list(doc))
    assert ok and terminal


def test_state_padding_is_inert():
    """pad_states rounds the state axis to a power of two (shared XLA program
    across schemas) without changing any mask or transition."""
    g = _grammar(Record.model_json_schema())
    plain, padded = device_grammar(g), device_grammar(g, pad_states=64)
    assert padded.trans.shape[0] >= 64
    assert padded.trans.shape[0] & (padded.trans.shape[0] - 1) == 0
    doc = b'{"name":"a","count":1}'
    for d in (plain, padded):
        state = grammar_initial_state(d, 1)
        for byte in doc:
            state = grammar_advance(d, jnp.asarray([byte], jnp.int32), state)
        eos = jnp.asarray([TOK.eos_id], jnp.int32)
        masked = grammar_mask_logits(d, jnp.zeros((1, TOK.vocab_size)), state, eos)
        assert bool(np.asarray(masked[0])[TOK.eos_id] == 0.0)


def test_specials_freeze_and_padded_rows_are_dead():
    g = _grammar(Record.model_json_schema())
    d = device_grammar(g, pad_states=64)
    state = grammar_initial_state(d, 2)
    # EOS/pad have token_len 0: advancing on them must not move the state.
    frozen = grammar_advance(
        d, jnp.asarray([TOK.eos_id, TOK.pad_id], jnp.int32), state
    )
    assert np.array_equal(np.asarray(frozen), np.asarray(state))
    # A padded (dead) state row allows nothing and EOS stays shut.
    dead = jnp.asarray([d.trans.shape[0] - 1], jnp.int32)
    masked = grammar_mask_logits(d, jnp.zeros((1, TOK.vocab_size)), dead,
                                 jnp.asarray([TOK.eos_id], jnp.int32))
    assert not np.any(np.asarray(masked[0]) > jnp.finfo(jnp.float32).min / 2)


# ---------------------------------------------------------------------------
# cache: one compile per (schema, vocab) per process
# ---------------------------------------------------------------------------


def test_cache_hit_returns_same_object_and_counts():
    clear_grammar_cache()
    before = _events()
    a = _grammar(Record.model_json_schema())
    mid = _events()
    b = _grammar(Record.model_json_schema())
    after = _events()
    assert a is b  # fleet members share one compiled table set
    assert _delta(before, mid, "grammar.miss") == 1
    assert _delta(before, mid, "grammar.compile") == 1
    assert _delta(mid, after, "grammar.hit") == 1
    assert _delta(mid, after, "grammar.compile") == 0
    stats = grammar_cache_stats()
    assert stats["entries"] >= 1 and stats["maxsize"] == 64


def test_cache_keys_split_on_schema_and_vocab():
    clear_grammar_cache()
    a = _grammar(Record.model_json_schema())
    other = grammar_for_schema(
        Record.model_json_schema(), VOCAB, vocab_digest="other-vocab"
    )
    generic = _grammar(None)
    assert a is not other  # same schema, different tokenizer -> distinct
    assert generic.digest.startswith("grammar-json-")
    assert grammar_cache_stats()["entries"] == 3


def test_unsupported_schema_degrades_to_generic_json():
    clear_grammar_cache()
    before = _events()
    g = _grammar({"type": "object"})  # free-form: SchemaUnsupported
    after = _events()
    assert isinstance(g, CompiledGrammar)
    assert g.digest.startswith("grammar-json-")
    assert _delta(before, after, "grammar.fallback_unsupported") == 1
    # Cached under the schema's own key: the second call is a pure hit.
    assert _grammar({"type": "object"}) is g
    # The generic grammar still accepts any JSON document.
    ok, terminal = validate_grammar_tokens(g, list(b'[1,{"k":null}]'))
    assert ok and terminal


# ---------------------------------------------------------------------------
# engine.grammar failpoint: degrade, never error
# ---------------------------------------------------------------------------


def test_engine_grammar_failpoint_fallback_degrades_to_unconstrained():
    """engine.grammar=fallback:2 — the registry drill: the next two compiles
    return None (unconstrained decode + post-hoc validation), counted, then
    the spec exhausts and compilation resumes."""
    clear_grammar_cache()
    before = _events()
    with fp.failpoints({"engine.grammar": FailSpec(action="fallback", times=2)}):
        assert _grammar(Record.model_json_schema()) is None  # fired (1)
        assert _grammar(Record.model_json_schema()) is None  # fired (2)
        assert isinstance(_grammar(Record.model_json_schema()), CompiledGrammar)
    after = _events()
    assert _delta(before, after, "grammar.fallback_failpoint") == 2


def test_engine_grammar_failpoint_raise_is_swallowed_and_counted():
    """The raise variant simulates a compile crash: grammar_for_schema still
    returns None — a constrained request NEVER errors on grammar failure."""
    clear_grammar_cache()
    before = _events()
    with fp.failpoints({"engine.grammar": FailSpec(action="raise", times=1)}):
        assert _grammar(Record.model_json_schema()) is None
    assert _delta(before, _events(), "grammar.fallback_error") == 1


def test_engine_grammar_env_syntax_parses():
    fp.configure_from_env("engine.grammar=fallback:1")
    try:
        clear_grammar_cache()
        before = _events()
        assert _grammar(Record.model_json_schema()) is None
        assert _delta(before, _events(), "grammar.fallback_failpoint") == 1
    finally:
        fp.clear()


def test_failpoint_request_degrades_but_succeeds():
    """End to end: with the failpoint armed, parse() still serves — decode is
    unconstrained, post-hoc validation stays authoritative."""
    from k_llms_tpu import KLLMs

    clear_grammar_cache()
    client = KLLMs(backend="tpu", model="tiny", max_new_tokens=32)
    with fp.failpoints({"engine.grammar": FailSpec(action="fallback", times=1)}):
        r = client.chat.completions.parse(
            messages=[{"role": "user", "content": "extract"}],
            response_format=Record, model="tiny", n=2, seed=5,
        )
    assert len(r.choices) == 3  # consensus + 2 samples: request served
    client.close()


# ---------------------------------------------------------------------------
# byte-identity: no constraint attached == pre-grammar output
# ---------------------------------------------------------------------------


def test_constrained_decoding_off_is_byte_identical_to_no_response_format():
    """BackendConfig(constrained_decoding=False) + response_format produces
    EXACTLY the tokens of a plain request: the grammar path adds nothing when
    no mask is attached."""
    from k_llms_tpu.backends.base import ChatRequest
    from k_llms_tpu.backends.tpu import BackendConfig, TpuBackend

    msgs = [{"role": "user", "content": "say something"}]

    def run(config_kwargs, req_kwargs):
        backend = TpuBackend(
            model="tiny",
            config=BackendConfig(model="tiny", max_new_tokens=24, **config_kwargs),
        )
        req = ChatRequest(messages=msgs, model="tiny", n=3, seed=17,
                          temperature=0.9, **req_kwargs)
        r = backend.chat_completion(req)
        texts = [c.message.content for c in r.choices[1:]]
        backend.drain()
        return texts

    plain = run({}, {})
    off = run({"constrained_decoding": False},
              {"response_format": {"type": "json_object"}})
    assert off == plain


def test_engine_generate_without_constraint_unchanged_by_grammar_import():
    """Direct engine check: generate() with constraint=None is deterministic
    and unaffected by grammar compilation happening in the same process."""
    from conftest import shared_engine

    eng = shared_engine(model="tiny")
    a = eng.generate([1, 2, 3], n=2, max_new_tokens=8, temperature=0.8, seed=9)
    clear_grammar_cache()
    _grammar(Record.model_json_schema())  # compile something in between
    b = eng.generate([1, 2, 3], n=2, max_new_tokens=8, temperature=0.8, seed=9)
    assert np.array_equal(a.tokens, b.tokens)
    assert np.allclose(a.logprobs, b.logprobs)


# ---------------------------------------------------------------------------
# the continuous loop decodes under the mask
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def loop():
    from conftest import shared_engine

    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop

    eng = shared_engine(model="tiny")
    lp = ContinuousDecodeLoop(eng, width=4, max_prompt=64, max_new=96)
    yield lp
    lp.stop()


def _prompt():
    return TOK.apply_chat_template([{"role": "user", "content": "extract"}])


def test_continuous_loop_constrained_rows_obey_grammar(loop):
    clear_grammar_cache()
    g = _grammar(Record.model_json_schema())
    r = loop.submit(
        _prompt(), n=3, max_new=96, temperature=1.0, top_p=None, seed=23,
        grammar=g,
    ).result(timeout=120)
    for i in range(3):
        ids = [int(t) for t in r.tokens[i][: int(r.lengths[i])]]
        body = [t for t in ids if t < 256]
        ok, _ = validate_grammar_tokens(g, body)
        assert ok, bytes(body)
        if r.finish_reasons[i] == "stop":
            Record.model_validate(json.loads(bytes(body)))


def test_continuous_loop_mixed_batch_leaves_plain_rows_byte_identical(loop):
    """A grammar row decoding beside a plain row must not perturb the plain
    row's tokens: masking is jnp.where-gated per row, and row keys are
    position-independent."""
    alone = loop.submit(
        [1, 2, 3, 4, 5], n=2, max_new=8, temperature=0.7, top_p=0.9, seed=31
    ).result(timeout=120)
    g = _grammar(Record.model_json_schema())
    noisy = loop.submit(
        _prompt(), n=1, max_new=64, temperature=1.0, top_p=None, seed=1, grammar=g
    )
    beside = loop.submit(
        [1, 2, 3, 4, 5], n=2, max_new=8, temperature=0.7, top_p=0.9, seed=31
    ).result(timeout=120)
    noisy.result(timeout=120)
    assert np.array_equal(alone.tokens, beside.tokens)
    assert np.allclose(alone.logprobs, beside.logprobs, atol=1e-5)


def test_continuous_loop_rejects_second_grammar_while_busy(loop):
    """The loop holds ONE resident grammar; a different schema mid-flight is
    bounced to the coalescing path via ValueError (the backend catches it)."""
    g1 = _grammar(Record.model_json_schema())

    class Other(BaseModel):
        flag: bool

    g2 = _grammar(Other.model_json_schema())
    assert g1.digest != g2.digest
    holder = {}

    def sink(step, _toks):
        if step == 0 and "err" not in holder:
            try:
                loop.submit(_prompt(), n=1, max_new=8, temperature=0.0,
                            top_p=None, seed=2, grammar=g2)
                holder["err"] = None
            except ValueError as e:
                holder["err"] = e

    fut = loop.submit(
        _prompt(), n=1, max_new=48, temperature=1.0, top_p=None, seed=3,
        grammar=g1, token_sink=sink,
    )
    fut.result(timeout=120)
    assert isinstance(holder.get("err"), ValueError)
    # Once drained, the other grammar is admissible (resident swap).
    r = loop.submit(_prompt(), n=1, max_new=48, temperature=0.0, top_p=None,
                    seed=2, grammar=g2).result(timeout=120)
    body = [int(t) for t in r.tokens[0][: int(r.lengths[0])] if int(t) < 256]
    assert validate_grammar_tokens(g2, body)[0]


# ---------------------------------------------------------------------------
# TRUTH_DOCS differential: constrained greedy parses under every schema shape
# ---------------------------------------------------------------------------


def _schema_of(value):
    """Structural JSON schema of a truth document (objects closed, arrays
    typed from their first element)."""
    if isinstance(value, bool):
        return {"type": "boolean"}
    if isinstance(value, int):
        return {"type": "integer"}
    if isinstance(value, float):
        return {"type": "number"}
    if isinstance(value, str):
        return {"type": "string"}
    if isinstance(value, list):
        return {"type": "array", "items": _schema_of(value[0])}
    if isinstance(value, dict):
        return {
            "type": "object",
            "properties": {k: _schema_of(v) for k, v in value.items()},
            "required": list(value),
            "additionalProperties": False,
        }
    raise TypeError(type(value))


@pytest.mark.parametrize("doc", ["invoice", "purchase_order", "profile"])
def test_constrained_greedy_parses_under_every_truth_schema(doc):
    """For each TRUTH_DOCS shape: greedy decode under the compiled grammar
    yields a mask-legal token stream, and a completed stream is a full JSON
    document valid under the byte DFA (the differential the bench reports)."""
    from conftest import shared_engine

    from k_llms_tpu.utils.quality import TRUTH_DOCS

    schema = _schema_of(TRUTH_DOCS[doc])
    dfa = compile_schema(schema)
    g = _grammar(schema)
    assert isinstance(g, CompiledGrammar)
    eng = shared_engine(model="tiny")
    r = eng.generate(
        _prompt(), n=2, max_new_tokens=160, temperature=0.0, seed=1,
        eos_ids=TOK.stop_ids, constraint=g,
    )
    for i in range(2):
        ids = [int(t) for t in r.tokens[i][: int(r.lengths[i])]]
        body = [t for t in ids if t < 256]
        ok, _ = validate_grammar_tokens(g, body)
        assert ok, bytes(body)
        assert validate_bytes(dfa, bytes(body))[0]
        if r.finish_reasons[i] == "stop":
            assert validate_bytes(dfa, bytes(body))[1]
            json.loads(bytes(body))
