"""A decode step's host inputs are one array (engine/continuous.py::_pack_step
on the host, ``_unpack_step`` in the step programs): every slot mirror comes
back bit for bit in every layout, and a step hands its program device arrays
only, one upload a step on the loop's own counter."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import shared_engine
from k_llms_tpu.engine.continuous import ContinuousDecodeLoop, _unpack_step
from k_llms_tpu.engine.paging import TRASH_PAGE, table_width
from k_llms_tpu.reliability import failpoints as fp
from k_llms_tpu.reliability.failpoints import FailSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W, P, G, PS = 6, 64, 32, 8
#: layout -> (preset, engine options): the dense loop only tests run, the paged
#: loop every cell runs, the drafted loop of a model with a next-token module.
LAYOUTS = {
    "dense": ("tiny", {}),
    "paged": ("tiny", {"kv_layout": "paged", "kv_page_size": PS}),
    "drafting": ("joyai-tiny", {"kv_layout": "paged", "kv_page_size": PS}),
}


def make_loop(layout):
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    model, options = LAYOUTS[layout]
    return ContinuousDecodeLoop(shared_engine(model, **options), width=W, max_prompt=P, max_new=G,
                                eos_ids=get_tokenizer(None).stop_ids)


@pytest.fixture(scope="module")
def grammar():
    from k_llms_tpu.engine.grammar import grammar_for_schema, grammar_vocab
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    schema = json.load(open(os.path.join(ROOT, "benchmark", "workloads", "extract.json")))
    return grammar_for_schema(schema["response_format"], grammar_vocab(get_tokenizer(None)))


def bits(a):
    """An array as the integers its bytes spell: equal bits, not equal values."""
    a = np.asarray(a)
    return a.view({1: np.uint8, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("constrained", [False, True], ids=["plain", "grammar"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_pack_then_unpack_gives_back_every_mirror_bit_for_bit(layout, constrained):
    loop = make_loop(layout)
    try:
        rng = np.random.RandomState(3)
        pad = loop.engine.config.pad_token_id
        loop._cur[:] = [5, pad, 499, 0, 17, pad]
        loop._gen_lens[:] = [0, 31, 7, 1, 29, 12]
        loop._prompt_lens[:] = [1, 64, 37, 8, 63, 21]  # idle rows keep a last tenant's
        loop._active_mask[:] = [True, False, True, True, True, False]
        loop._seeds[:] = [0, 2**31, 2**32 - 1, 2**31 + 11, 7, 2**31 - 1]
        loop._sample_idx[:] = [0, 1, 2, 3, 31, 0]
        loop._temps[:] = [0.0, 0.8, 1e-45, 1.0, 2.5, 0.0]  # greedy rows, a denormal
        loop._top_ps[:] = [1e-40, 0.95, 1.0, 0.0, 1e-45, 0.5]  # denormals, the two ends
        loop._draft[:] = [11, pad, 12, 13, 14, pad]
        loop._max_news[:] = [3, 34, 10, 32, 31, 15]  # room for two but in row 4 (29 + 3 > 31)
        if constrained:
            loop._g_states[:] = [3, 0, 63, 1, 0, 9]
            loop._g_flags[:] = [True, False, True, False, False, True]
        books = ()
        if loop._pages is not None:
            writes = 1 + loop._pages.lookahead
            T = table_width(P, G + loop._pages.lookahead, PS)
            tables = rng.randint(1, 500, size=(W, T)).astype(np.int32)
            tables[1] = TRASH_PAGE  # an idle row's table is empty
            tables[3, 2:] = TRASH_PAGE  # a table shorter than the row's width
            books = (rng.randint(0, 4000, size=(W, writes)).astype(np.int32), tables)
        else:
            writes = 0
        packed = loop._pack_step(*books)
        named = 12 if loop._drafting else 10
        assert packed.dtype == np.int32
        assert packed.shape == (W, named + sum(b.shape[1] for b in books))
        rows = jax.jit(lambda p: _unpack_step(p, loop._drafting, writes))(jnp.asarray(packed))
        mirrors = {
            "cur": loop._cur, "gen_lens": loop._gen_lens, "prompt_lens": loop._prompt_lens,
            "active": loop._active_mask, "seeds": loop._seeds, "sample_idx": loop._sample_idx,
            "temps": loop._temps, "top_ps": loop._top_ps, "g_states": loop._g_states,
            "g_flags": loop._g_flags,
        }
        if loop._drafting:
            mirrors["draft"] = loop._draft
            mirrors["room"] = np.array([True, True, True, True, False, True])
        else:
            assert rows.draft is None and rows.room is None
        for name, mirror in mirrors.items():
            got = np.asarray(getattr(rows, name))
            assert got.dtype == mirror.dtype, name
            np.testing.assert_array_equal(bits(got), bits(mirror), err_msg=name)
        assert (np.asarray(rows.temps) == 0.0).tolist() == [True, False, False, False, False, True]
        if books:
            np.testing.assert_array_equal(np.asarray(rows.write_idx), books[0])
            np.testing.assert_array_equal(np.asarray(rows.tables), books[1])
        else:
            assert rows.tables.shape == rows.write_idx.shape == (W, 0)
        # A snapshot: what the worker writes into the mirrors next never reaches it.
        before = packed.copy()
        loop._cur[:] = 1
        loop._seeds[:] = 9
        loop._temps[:] = 3.0
        if books:
            books[1][:] = 7
        np.testing.assert_array_equal(packed, before)
    finally:
        loop.stop()


@pytest.mark.parametrize("constrained", [False, True], ids=["plain", "grammar"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_step_is_handed_device_arrays_only_and_counts_one_upload(layout, constrained, grammar):
    """Every call of a step program runs under ``jax.transfer_guard("disallow")``:
    a numpy argument would be an implicit transfer and raise. The stage's one
    ``jnp.asarray`` is the step's one upload; the ``engine.logits`` failpoint's
    mask is a second, on the steps it fires in."""
    loop = make_loop(layout)
    calls = []
    build = loop._build_step

    def guarded(grammar):
        fn = build(grammar)

        def call(*args, **kwargs):
            calls.append(sum(isinstance(a, np.ndarray) for a in jax.tree.leaves((args, kwargs))))
            with jax.transfer_guard("disallow"):
                return fn(*args, **kwargs)

        return call

    loop._build_step = guarded
    prompt = [int(t) for t in np.random.RandomState(1).randint(32, 127, size=21)]
    kw = dict(max_new=24 if constrained else 6, temperature=0.8, top_p=0.95,
              grammar=grammar if constrained else None)
    try:
        got = loop.submit(prompt, n=3, seed=2**31 + 5, **kw).result(timeout=300)
        assert not any(got.sample_errors or [])
        stats = loop.stats
        assert stats["steps"] == len(calls) > 0 and set(calls) == {0}
        assert stats["stage_uploads"] == stats["steps"]
        columns = (12 if loop._drafting else 10) + (
            0 if loop._pages is None
            else 1 + loop._pages.lookahead + loop._pages.tables.shape[1])
        assert stats["stage_bytes"] == stats["steps"] * W * columns * 4
        # The failpoint's mask goes up beside the packed array, twice here.
        with fp.failpoints({"engine.logits": FailSpec(action="nan", kill=1, seed=5, times=2)}):
            loop.submit(prompt, n=3, seed=7, **kw).result(timeout=300)
        after = loop.stats
        steps = after["steps"] - stats["steps"]
        assert steps >= 2 and set(calls) == {0}
        assert after["stage_uploads"] - stats["stage_uploads"] == steps + 2
        assert after["stage_bytes"] - stats["stage_bytes"] == steps * W * columns * 4 + 2 * W
    finally:
        loop.stop()
