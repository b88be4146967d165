"""Chunked prefill (ISSUE 18): long prompt ingestion interleaved into the
continuous loop's decode steps instead of one monolithic prefill under the
loop lock.

The determinism contract pinned here:

- **Output tokens are byte-identical** between chunked-on and chunked-off
  loops — greedy, sampled, grammar-constrained, and streamed alike. The
  first token comes from the final chunk's logits with the submission-pinned
  seed, and decode proceeds over the chunk-written KV.
- **Logprobs are ULP-equivalent** (atol 1e-5) across on/off: a C-token chunk
  and a whole-bucket prefill compile to different XLA programs (query-axis
  shape), whose matmul reductions differ in the last float32 bits. Within
  the chunked path itself — replay after a mid-chunk watchdog rebuild, or a
  prefix-cache hit on a chunk-ingested prompt — results ARE bitwise
  identical, because the same compiled programs rerun on the same inputs.
- Fault domains carry over: a hung chunk epoch-fences + rebuilds + replays
  byte-identically from cursor 0; a budget abort retires the PREFILLING row
  through the decode-abort counters; paged page accounting stays balanced.
"""

import json
import time

import numpy as np
import pytest

from k_llms_tpu.engine.continuous import ContinuousDecodeLoop, bucket_rungs, pick_chunk
from k_llms_tpu.reliability import failpoints as fp
from k_llms_tpu.reliability.deadline import RequestBudget
from k_llms_tpu.reliability.failpoints import FailSpec
from k_llms_tpu.reliability.supervisor import LaunchBudgetModel
from k_llms_tpu.types.wire import RequestCancelledError
from k_llms_tpu.utils.observability import FAILURE_EVENTS, RECOVERY_EVENTS

LONG_PROMPT = list(range(2, 100))  # 98 tokens: 4 chunks at C=32
CHUNK = 32
#: The ladder at CHUNK (``HbmMemoryModel.prefill_chunk_ladder`` at a width of
#: 8): a turn is 128 tokens while the prompt has as many left, then the
#: shortest of the three that covers the rest.
LADDER = (32, 64, 128)
#: (ladder, prompt tokens, lane turns): ``()`` is the single length; 98 tokens
#: stage in a bucket of 128, one turn; the others in 256, where 128 + 2 ends
#: on the rung of 32, 128 + 33 on 64 and 128 + 122 on 128.
LADDER_CASES = [((), 98, 4), (LADDER, 98, 1), (LADDER, 130, 2), (LADDER, 161, 2), (LADDER, 250, 2)]


def _step_budget(seconds: float) -> LaunchBudgetModel:
    return LaunchBudgetModel(
        base_s=0.1, per_token_s=0.01, multiplier=1.0,
        min_budget_s=seconds, max_budget_s=seconds,
    )


@pytest.fixture(scope="module")
def eng():
    from conftest import shared_engine

    return shared_engine(model="tiny")


@pytest.fixture(scope="module")
def paged_eng():
    from conftest import shared_engine

    return shared_engine(model="tiny", kv_layout="paged", kv_page_size=16)


def _prompt(plen):
    return [2 + i % 250 for i in range(plen)]


def _run(loop, prompt=LONG_PROMPT, **kw):
    kw.setdefault("n", 2)
    kw.setdefault("max_new", 8)
    kw.setdefault("temperature", 0.7)
    kw.setdefault("top_p", 0.9)
    kw.setdefault("seed", 11)
    return loop.submit(list(prompt), **kw).result(timeout=120)


def _assert_same_output(on, off, label=""):
    assert np.array_equal(on.tokens, off.tokens), label
    assert list(on.lengths) == list(off.lengths), label
    assert list(on.finish_reasons) == list(off.finish_reasons), label
    # ULP contract: see module docstring — on/off logprobs come from
    # different-shaped XLA programs, equal to within f32 noise.
    assert np.allclose(on.logprobs, off.logprobs, atol=1e-5), label


# -- on/off differentials ----------------------------------------------------

@pytest.mark.parametrize(
    "label,kw",
    [
        ("greedy", dict(temperature=0.0, top_p=None)),
        ("sampled", dict(temperature=0.7, top_p=0.9)),
    ],
)
@pytest.mark.parametrize("ladder,plen,turns", LADDER_CASES)
def test_chunked_on_off_differential_dense(eng, label, kw, ladder, plen, turns):
    """The tentpole differential: a long admission ingested in chunks, of one
    length or of the ladder's, every rung as a prompt's last turn, produces
    byte-identical output tokens to whole-prompt prefill. ``prefill_chunks``
    counts the turns and ``prefill_tokens`` the prompt's tokens, no padding."""
    off = ContinuousDecodeLoop(eng, width=4, max_prompt=256, max_new=16)
    try:
        base = _run(off, _prompt(plen), **kw)
    finally:
        off.stop()
    on = ContinuousDecodeLoop(
        eng, width=4, max_prompt=256, max_new=16, prefill_chunk_tokens=CHUNK,
        prefill_chunk_ladder=ladder,
    )
    try:
        got = _run(on, _prompt(plen), **kw)
        st = dict(on.stats)
    finally:
        on.stop()
    assert st["prefill_chunks"] == turns
    assert st["prefill_tokens"] == plen
    assert st["prefill_chunk_tokens"] == CHUNK  # the threshold, whatever the ladder
    _assert_same_output(got, base, label)


@pytest.mark.parametrize("ladder,plen,turns", LADDER_CASES)
def test_chunked_on_off_differential_paged(paged_eng, ladder, plen, turns):
    """Same pin on the paged layout: chunk KV scattered into the row's page
    run at its current offset, and page accounting balanced after retire."""
    off = ContinuousDecodeLoop(paged_eng, width=4, max_prompt=256, max_new=16)
    try:
        base = _run(off, _prompt(plen))
        base_g = _run(off, _prompt(plen), temperature=0.0, top_p=None, seed=3)
    finally:
        off.stop()
    on = ContinuousDecodeLoop(
        paged_eng, width=4, max_prompt=256, max_new=16,
        prefill_chunk_tokens=CHUNK, prefill_chunk_ladder=ladder,
    )
    try:
        assert on.paged
        got = _run(on, _prompt(plen))
        got_g = _run(on, _prompt(plen), temperature=0.0, top_p=None, seed=3)
        alloc = on._pool.allocator
        alloc.verify()
        free_mid = alloc.free_pages
        _run(on, _prompt(plen), seed=29)
        assert alloc.free_pages == free_mid  # no leak per admission cycle
        st = dict(on.stats)
    finally:
        on.stop()
    alloc.verify()
    assert (st["prefill_chunks"], st["prefill_tokens"]) == (3 * turns, 3 * plen)
    _assert_same_output(got, base, "paged sampled")
    _assert_same_output(got_g, base_g, "paged greedy")


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_ladder_on_off_differential_under_a_sliding_window(layout):
    """mistral's shape: a window on every layer that binds many times inside
    one turn of 128 and across two. The ladder's output is whole-prompt
    admission's."""
    from conftest import shared_engine
    from k_llms_tpu.models import get_config

    config = get_config("tiny").with_(name="tiny-window24", sliding_window=24)
    kw = dict(kv_layout="paged", kv_page_size=16) if layout == "paged" else {}
    engine = shared_engine(config, **kw)
    off = ContinuousDecodeLoop(engine, width=4, max_prompt=256, max_new=16)
    try:
        base = [_run(off, _prompt(plen)) for plen in (161, 250)]
    finally:
        off.stop()
    on = ContinuousDecodeLoop(engine, width=4, max_prompt=256, max_new=16,
                              prefill_chunk_tokens=CHUNK, prefill_chunk_ladder=LADDER)
    try:
        got = [_run(on, _prompt(plen)) for plen in (161, 250)]
        st = dict(on.stats)
    finally:
        on.stop()
    assert (st["prefill_chunks"], st["prefill_tokens"]) == (4, 161 + 250)
    for a, b in zip(got, base):
        _assert_same_output(a, b, layout)


def test_chunked_stream_sink_is_contiguous_and_identical(eng):
    """A streaming consumer over a chunked admission sees each step exactly
    once, in order, with tokens matching the authoritative buffers — and the
    stream equals the chunked-off stream byte-for-byte."""
    def collect(loop):
        sunk = []
        got = loop.submit(
            list(LONG_PROMPT), n=2, max_new=8, temperature=0.8, top_p=0.9,
            seed=17, token_sink=lambda s, t: sunk.append((s, t.copy())),
        ).result(timeout=120)
        return got, sunk

    off = ContinuousDecodeLoop(eng, width=4, max_prompt=128, max_new=16)
    try:
        base, base_sunk = collect(off)
    finally:
        off.stop()
    on = ContinuousDecodeLoop(
        eng, width=4, max_prompt=128, max_new=16, prefill_chunk_tokens=CHUNK
    )
    try:
        got, sunk = collect(on)
    finally:
        on.stop()
    assert np.array_equal(got.tokens, base.tokens)
    steps = [s for s, _ in sunk]
    assert steps == sorted(set(steps))
    for step, row in sunk:
        for j in range(2):
            if step < got.lengths[j]:
                assert row[j] == got.tokens[j, step]
    assert [(s, r.tolist()) for s, r in sunk] == [
        (s, r.tolist()) for s, r in base_sunk
    ]


def test_chunked_grammar_row_matches_off(eng):
    """A grammar-constrained long admission chunks like any other and still
    emits the identical, schema-valid stream."""
    from pydantic import BaseModel

    from k_llms_tpu.engine.grammar import (
        grammar_for_schema,
        grammar_vocab,
        validate_grammar_tokens,
    )
    from k_llms_tpu.engine.tokenizer import ByteTokenizer

    class Rec(BaseModel):
        name: str
        count: int

    tok = ByteTokenizer()
    g = grammar_for_schema(
        Rec.model_json_schema(), grammar_vocab(tok), vocab_digest="bytetok-rec"
    )
    # Long enough to span several chunks (ByteTokenizer: 1 token per byte).
    prompt = tok.apply_chat_template(
        [{"role": "user", "content": "extract the record " * 4}]
    )
    assert len(prompt) > 2 * CHUNK
    kw = dict(n=1, max_new=96, temperature=1.0, top_p=None, seed=23, grammar=g)

    off = ContinuousDecodeLoop(eng, width=2, max_prompt=128, max_new=96)
    try:
        base = off.submit(list(prompt), **kw).result(timeout=120)
    finally:
        off.stop()
    on = ContinuousDecodeLoop(
        eng, width=2, max_prompt=128, max_new=96, prefill_chunk_tokens=CHUNK
    )
    try:
        got = on.submit(list(prompt), **kw).result(timeout=120)
        st = dict(on.stats)
    finally:
        on.stop()
    assert st["prefill_chunks"] >= 2
    assert np.array_equal(got.tokens, base.tokens)
    body = [int(t) for t in got.tokens[0][: int(got.lengths[0])] if t < 256]
    ok, _ = validate_grammar_tokens(g, body)
    assert ok, bytes(body)
    if got.finish_reasons[0] == "stop":
        Rec.model_validate(json.loads(bytes(body)))


# -- interleaving ------------------------------------------------------------

def test_chunks_interleave_with_inflight_decode(eng):
    """While a long admission is PREFILLING, the in-flight row keeps
    decoding (prefill_interleaved counts chunks run alongside decode), and
    its output is untouched by the interleave (row keys are
    self-deterministic)."""
    solo = ContinuousDecodeLoop(eng, width=4, max_prompt=128, max_new=64)
    try:
        base = solo.submit(
            [7, 8, 9], n=1, max_new=48, temperature=0.6, top_p=0.9, seed=5
        ).result(timeout=120)
    finally:
        solo.stop()

    on = ContinuousDecodeLoop(
        eng, width=4, max_prompt=128, max_new=64, prefill_chunk_tokens=CHUNK
    )
    try:
        inflight = on.submit(
            [7, 8, 9], n=1, max_new=48, temperature=0.6, top_p=0.9, seed=5
        )
        long_fut = on.submit(
            list(LONG_PROMPT), n=1, max_new=8, temperature=0.0, top_p=None,
            seed=2,
        )
        got = inflight.result(timeout=120)
        long_res = long_fut.result(timeout=120)
        st = dict(on.stats)
    finally:
        on.stop()
    assert st["prefill_chunks"] >= 1
    assert st["prefill_interleaved"] >= 1, (
        "chunks should have run alongside the in-flight decode"
    )
    assert int(long_res.lengths[0]) > 0
    assert np.array_equal(got.tokens, base.tokens)
    assert np.array_equal(got.logprobs, base.logprobs)  # same programs: bitwise


def test_short_prompt_skips_chunking(eng):
    """prompt_len <= C: whole-prompt admission, zero chunk dispatches."""
    on = ContinuousDecodeLoop(
        eng, width=2, max_prompt=64, max_new=8, prefill_chunk_tokens=CHUNK
    )
    try:
        got = _run(on, prompt=[1, 2, 3, 4], n=1)
        st = dict(on.stats)
    finally:
        on.stop()
    assert st["prefill_chunks"] == 0
    assert int(got.lengths[0]) > 0


def test_prefix_cache_hit_skips_chunking_bitwise(paged_eng):
    """A prompt ingested via chunks lands in the prefix cache like any other;
    an identical follow-up admission skips PREFILLING entirely and reuses the
    stored run + first logits — bitwise-identical output, zero new chunks."""
    from conftest import shared_engine

    cached_eng = shared_engine(
        model="tiny", kv_layout="paged", kv_page_size=16, prefix_cache_size=4
    )
    on = ContinuousDecodeLoop(
        cached_eng, width=4, max_prompt=128, max_new=16,
        prefill_chunk_tokens=CHUNK,
    )
    try:
        first = _run(on)
        chunks_after_first = dict(on.stats)["prefill_chunks"]
        again = _run(on)
        st = dict(on.stats)
    finally:
        on.stop()
    assert chunks_after_first == (len(LONG_PROMPT) + CHUNK - 1) // CHUNK
    assert st["prefill_chunks"] == chunks_after_first  # hit: no new chunks
    assert np.array_equal(first.tokens, again.tokens)
    assert np.array_equal(first.logprobs, again.logprobs)  # bitwise reuse


# -- the length of a lane turn -------------------------------------------------

def _turns(rungs, plen, bucket):
    """``[(cursor, rung, valid)]`` of one prompt, as ``_prefill_chunk_once``
    walks it."""
    out, cursor = [], 0
    while cursor < plen:
        rung = pick_chunk(rungs, plen - cursor, bucket - cursor)
        out.append((cursor, rung, min(rung, plen - cursor)))
        cursor += rung
    return out


@pytest.mark.parametrize("remainder,want", [
    (1, 128), (128, 128), (129, 256), (256, 256), (257, 512), (511, 512), (512, 512),
    (513, 512), (1400, 512), (6000, 512),
])
def test_pick_chunk_reads_the_remainder(remainder, want):
    """The longest rung while the remainder fills it, else the shortest that
    covers it; a single length is that length whatever is left."""
    assert pick_chunk((128, 256, 512), remainder, 8192) == want
    assert pick_chunk((128,), remainder, 8192) == 128


@pytest.mark.parametrize("rungs,bucket", [
    ((128, 256, 512), 256), ((128, 256, 512), 512), ((128, 256, 512), 1024),
    ((128, 256, 512), 2048), ((128, 256, 512), 8192), ((128, 256), 256), ((128,), 2048),
    (LADDER, 64), (LADDER, 128), (LADDER, 256), (LADDER, 512),
])
def test_every_prompt_of_a_bucket_walks_the_ladder_within_it(rungs, bucket):
    """Every prompt the bucket stages (longer than the bucket below and than
    the shortest rung, which is what takes the lane): ``ceil(plen / longest)``
    turns, padding in the last turn alone and under twice the shortest rung,
    every turn's columns inside the staging cache, every rung one that
    ``bucket_rungs`` said the bucket's first chunk has to build, and the page
    run written once at each prompt position and nowhere else."""
    from k_llms_tpu.engine.paging import TRASH_PAGE, SlotPages, pages_for

    ps = 16
    pages = SlotPages(ps, 4, bucket, 16, pool_pages=2 * bucket)
    longest = max(r for r in rungs if r <= bucket)
    built, seen = set(bucket_rungs(rungs, bucket)), set()
    for plen in range(max(rungs[0], bucket // 2) + 1, bucket + 1):
        turns = _turns(rungs, plen, bucket)
        assert len(turns) == -(-plen // longest)
        assert all(valid == rung for _, rung, valid in turns[:-1])
        cursor, rung, valid = turns[-1]
        assert 0 <= rung - valid < 2 * rungs[0] and cursor + valid == plen
        assert all(cursor + rung <= bucket for cursor, rung, _ in turns)
        seen |= {rung for _, rung, _ in turns}
        if plen % 37 == 0 or plen == bucket:  # the page run, for a sample of them
            run = list(range(7, 7 + pages_for(plen, ps)))
            written = np.concatenate([
                pages.chunk_slots(run, cursor, rung, valid) for cursor, rung, valid in turns
            ])
            real = written[written // ps != TRASH_PAGE]
            assert sorted(real.tolist()) == [7 * ps + i for i in range(plen)]
            assert len(written) - len(real) == rung - valid
    assert seen == built and built <= set(rungs)


def test_a_bucket_cut_short_of_the_longest_rung_takes_what_fits():
    """A staging cache with less room than the longest rung (a bucket cut to
    ``max_seq_len``) is walked in the rungs that fit; none fitting, the
    shortest runs as the single length always has."""
    assert pick_chunk((128, 256, 512), 400, 384) == 256
    assert pick_chunk((128, 256, 512), 100, 384) == 128
    assert pick_chunk((128, 256, 512), 90, 96) == 128


@pytest.mark.parametrize("layout", ["dense", "paged", "paged-mesh"])
def test_a_warmed_bucket_compiles_nothing_for_another_tail(eng, paged_eng, layout):
    """A bucket's first chunk builds every length its prompts can take, the
    pool's scatter of each included: later prompts of the bucket, ending on
    the other two rungs, grow ``device.compile.programs`` by nothing."""
    import jax

    from conftest import shared_engine
    from k_llms_tpu.utils.compile_cache import compile_stats, configure_compile_cache

    if layout == "paged-mesh":
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual mesh")
        engine = shared_engine(model="tiny", mesh_shape=(4, 2), kv_layout="paged", kv_page_size=16)
    else:
        engine = paged_eng if layout == "paged" else eng
    configure_compile_cache()
    loop = ContinuousDecodeLoop(
        engine, width=4, max_prompt=256, max_new=16, prefill_chunk_tokens=CHUNK,
        prefill_chunk_ladder=LADDER,
    )
    try:
        _run(loop, _prompt(250))  # 128 + 122: the bucket of 256, ending on the longest rung
        _run(loop, _prompt(250), seed=5)  # whatever a second admission still builds
        before = compile_stats()["programs"]
        _run(loop, _prompt(130))  # ... + 2: the rung of 32
        _run(loop, _prompt(161))  # ... + 33: the rung of 64
        assert compile_stats()["programs"] == before
        st = dict(loop.stats)
    finally:
        loop.stop()
    assert (st["prefill_chunks"], st["prefill_tokens"]) == (8, 250 + 250 + 130 + 161)


def test_prefill_tokens_per_chunk_reads_the_two_counters():
    """The per-layer metric through the benchmark's own evaluator: the
    window's prompt tokens over its lane turns; on a program without the
    counter (this PR's parent) nothing, and no error. It is reported in every
    cell and lies last in the manifest."""
    import importlib
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmark"))
    try:
        run = importlib.import_module("run")
    finally:
        sys.path.pop(0)
    read = run.load_json(run.HERE, "layer_metrics", "prefill_tokens_per_chunk.json")["read"]
    src = {"metrics_start": {"kllms_continuous_prefill_tokens": 5000.0,
                             "kllms_continuous_prefill_chunks": 40.0},
           "metrics_end": {"kllms_continuous_prefill_tokens": 5000.0 + 3 * 1400.0,
                           "kllms_continuous_prefill_chunks": 40.0 + 3 * 3}}
    assert run.evaluate(read, src) == pytest.approx(1400.0 / 3)
    parent = {"metrics_start": {"kllms_continuous_prefill_chunks": 40.0},
              "metrics_end": {"kllms_continuous_prefill_chunks": 73.0}}
    assert run.evaluate(read, parent) is None
    assert run.check_manifest() == []
    for w in run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]:
        assert "prefill_tokens_per_chunk" in {m["name"] for m in run.load_cell(w["name"])[5]}


# -- knob normalization ------------------------------------------------------

def test_chunk_tokens_normalization(eng):
    for given, want in ((0, 0), (-5, 0), (1, 32), (31, 32), (32, 32),
                        (48, 32), (64, 64), (100, 64)):
        loop = ContinuousDecodeLoop(
            eng, width=1, max_prompt=64, max_new=4, prefill_chunk_tokens=given
        )
        try:
            assert loop.prefill_chunk_tokens == want, (given, want)
        finally:
            loop.stop()


@pytest.mark.parametrize("ladder,ok", [
    ((), True), ((32,), True), ((64, 32, 128), True), ((32, 64), True),
    ((64, 256), False), ((16, 32), False), ((32, 96), False),
])
def test_chunk_ladder_doubles_up_from_the_chunk(eng, ladder, ok):
    """An explicit chunk size is one length; a ladder beside it is that
    length and its doubles, and anything else is refused."""
    if not ok:
        with pytest.raises(ValueError, match="double up"):
            ContinuousDecodeLoop(eng, width=1, max_prompt=256, max_new=4,
                                 prefill_chunk_tokens=CHUNK, prefill_chunk_ladder=ladder)
        return
    loop = ContinuousDecodeLoop(eng, width=1, max_prompt=256, max_new=4,
                                prefill_chunk_tokens=CHUNK, prefill_chunk_ladder=ladder)
    try:
        assert loop.prefill_chunk_tokens == CHUNK
        assert loop._chunk_rungs == tuple(sorted({CHUNK, *ladder}))
    finally:
        loop.stop()
    off = ContinuousDecodeLoop(eng, width=1, max_prompt=256, max_new=4, prefill_chunk_ladder=ladder)
    try:
        assert off._chunk_rungs == ()  # chunking off: nothing to take in turns
    finally:
        off.stop()


@pytest.mark.parametrize("given,chunk,rungs", [
    (None, 32, (32, 64, 128)), (64, 64, (64,)), (100, 64, (64,)), (0, 0, ())])
def test_backend_hands_the_loop_the_ladder_for_the_automatic_size_alone(eng, given, chunk, rungs):
    """``BackendConfig.prefill_chunk_tokens``: None is the automatic size and
    its ladder, a number that one length, 0 off; ``/healthz`` reports the
    threshold either way."""
    from k_llms_tpu.backends.tpu import TpuBackend

    backend = TpuBackend(
        model="tiny", max_new_tokens=8, engine=eng, continuous_batching=True,
        continuous_width=4, continuous_max_prompt=256, continuous_max_new=16,
        prefill_chunk_tokens=given,
    )
    try:
        loop = backend._continuous
        assert loop.prefill_chunk_tokens == chunk and loop._chunk_rungs == rungs
        assert backend.health()["continuous"]["prefill_chunk_tokens"] == chunk
    finally:
        backend.close()


def test_memory_model_auto_chunk():
    from k_llms_tpu.backends.tpu import HbmMemoryModel
    from k_llms_tpu.models import get_config

    mm = HbmMemoryModel(get_config("tiny"), param_bytes=1 << 20)
    assert mm.prefill_chunk_tokens(4, 32) == 0  # tiny max_prompt: off
    c = mm.prefill_chunk_tokens(4, 1024)
    assert c >= 32 and (c & (c - 1)) == 0 and c <= 512


@pytest.mark.parametrize("width,max_prompt,want", [
    (32, 2048, (128, 256, 512)),   # the extract cells
    (32, 7168, (128, 256, 512)),   # command-a-plus: buckets up to 8,192
    (32, 512, (128, 256, 512)),    # mistral-7b: the longest rung is its largest bucket whole
    (32, 256, (128, 256)),         # no rung past the largest bucket
    (32, 200, (64, 128, 256)),     # C capped at max_prompt // 2; the bucket of 200 is 256
    (8, 2048, (32, 64, 128)),
    (4, 32, ()),                   # chunking off
])
def test_memory_model_auto_ladder(width, max_prompt, want):
    """The automatic size's ladder: C, 2C, 4C with C the rule as it stood,
    none longer than the largest prompt bucket."""
    from k_llms_tpu.backends.tpu import HbmMemoryModel
    from k_llms_tpu.models import get_config

    mm = HbmMemoryModel(get_config("tiny"), param_bytes=1 << 20)
    assert mm.prefill_chunk_ladder(width, max_prompt) == want
    assert mm.prefill_chunk_tokens(width, max_prompt) == (want[0] if want else 0)


# -- fault domains -----------------------------------------------------------

def test_mid_chunk_hang_rebuilds_and_replays_bitwise(eng):
    """A chunk wedged past the watchdog budget (continuous.prefill=hang) is
    abandoned, the loop rebuilds, and the journaled admission replays from
    cursor 0 — the SAME chunk programs rerun on the same inputs, so the
    replayed output is bitwise-identical to an uninterrupted chunked run."""
    baseline = ContinuousDecodeLoop(
        eng, width=4, max_prompt=128, max_new=16, prefill_chunk_tokens=CHUNK
    )
    try:
        base = _run(baseline, seed=23)
    finally:
        baseline.stop()

    loop = ContinuousDecodeLoop(
        eng, width=4, max_prompt=128, max_new=16, prefill_chunk_tokens=CHUNK,
        budget_model=_step_budget(6.0), rebuild_fn=lambda: eng, max_rebuilds=3,
    )
    try:
        hangs = RECOVERY_EVENTS.snapshot().get("continuous.step_hangs", 0)
        with fp.failpoints(
            {"continuous.prefill": FailSpec(action="hang", times=1, delay=20.0)}
        ):
            got = _run(loop, seed=23)
        assert RECOVERY_EVENTS.snapshot()["continuous.step_hangs"] > hangs
        st = dict(loop.stats)
    finally:
        loop.stop()
    assert st["restarts"] >= 1
    assert st["last_recovery_reason"] == "hung_step"
    assert np.array_equal(got.tokens, base.tokens)
    assert np.array_equal(got.logprobs, base.logprobs)  # bitwise: same programs
    assert list(got.lengths) == list(base.lengths)


def test_prefilling_budget_abort_retires_row(eng):
    """A budget cancelled mid-PREFILLING retires the admission through the
    decode-abort fault domain (typed error, counter, slots freed) without
    wedging the loop."""
    budget = RequestBudget()
    before = FAILURE_EVENTS.snapshot().get("engine.decode_abort", 0)
    loop = ContinuousDecodeLoop(
        eng, width=4, max_prompt=128, max_new=16, prefill_chunk_tokens=CHUNK
    )
    try:
        # Stretch the first chunk so the cancel lands mid-prefill: the hang
        # spec sleeps inline in the chunk dispatch (no watchdog on a bare
        # loop), and the budget check runs at the next chunk boundary.
        with fp.failpoints(
            {"continuous.prefill": FailSpec(action="hang", times=1, delay=1.0)}
        ):
            fut = loop.submit(
                list(LONG_PROMPT), n=2, max_new=16, temperature=0.7,
                top_p=0.9, seed=11, budget=budget,
            )
            time.sleep(0.2)
            budget.cancel()
            with pytest.raises(RequestCancelledError):
                fut.result(timeout=60)
        assert FAILURE_EVENTS.snapshot().get("engine.decode_abort", 0) > before
        assert dict(loop.stats)["aborted"] >= 1
        # Slots and pages are free again: a follow-up request runs clean.
        ok = _run(loop, seed=31)
        assert int(ok.lengths[0]) > 0
    finally:
        loop.stop()
