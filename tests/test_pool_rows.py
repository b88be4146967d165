"""What the page pool stores for a token and how its movers address it.

A latent row ([c_kv | k_rope], one head, no V) is stored in whole tiles of 128
lanes (``ModelConfig.pool_row_width``) and every program addresses it by
(layer, slot) in the pool's flat view (``ops/attention.py::pool_*``); rows of
several KV heads are stored as they are and moved along the layer axis, as
before. Every writer is one of the page manager's movers (``engine/paging.py``:
``scatter_rows``, ``copy_rows``, ``write_drafted_rows``), which pick the form
from the pool's own shape. At the pool's boundary (``scatter_tokens``, ``gather_tokens``,
``copy_pages``) a row has the cache's own width whatever is stored, and the
pad lanes hold zeros after anything the loop does. What the compiled movers
cost on the chip is tests/test_tpu_compile.py's.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import shared_engine
from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
from k_llms_tpu.engine.engine import GenRequestSpec
from k_llms_tpu.engine.paging import PagedKVPool, scatter_rows, write_drafted_rows
from k_llms_tpu.models import get_config
from k_llms_tpu.ops.attention import pool_gather, pool_index, pool_layers, pool_scatter

MODELS = ["xing4-tiny", "joyai-tiny", "nemotron3-tiny", "tiny"]
LATENT = ["xing4-tiny", "joyai-tiny"]
PS = 8


def rows_of(config, n, seed):
    """n tokens' cache rows over the paging layers, (k, v), at the cache's own widths."""
    heads, k_width, v_width = config.cache_widths
    rng = np.random.default_rng(seed)
    shape = (config.paging_layers, n, heads)
    return tuple(jnp.asarray(rng.standard_normal(shape + (w,)), config.jax_dtype)
                 for w in (k_width, v_width))


def equal(got, want):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("model", MODELS)
def test_pool_row_width_and_bytes_a_token_are_what_the_pool_stores(model):
    config = get_config(model)
    heads, k_width, v_width = config.cache_widths
    pool = PagedKVPool(config, total_pages=5, page_size=PS)
    assert pool.kv.k.shape == (config.paging_layers, 5 * PS, heads, config.pool_row_width)
    assert pool.kv.v.shape == (config.paging_layers, 5 * PS, heads, v_width)
    assert config.kv_bytes_per_token * 5 * PS == pool.pool_bytes()
    if config.is_latent:
        assert (heads, v_width) == (1, 0)
        assert config.pool_row_width == -(-k_width // 128) * 128 > k_width
    else:
        assert config.pool_row_width == k_width


@pytest.mark.parametrize("preset,layers,row,per_token", [
    ("xing4-29b-a4b-cut7", 7, 576, 8960), ("joyai-llm-flash-cut8", 9, 576, 11520),
    ("nemotron3-nano-30b-a3b-cut9", 1, 128, 1024), ("qwen2-7b", 28, 128, 57344),
    ("mistral-7b", 32, 128, 131072)])
def test_the_cells_bytes_a_token(preset, layers, row, per_token):
    config = get_config(preset)
    assert (config.paging_layers, config.cache_widths[1]) == (layers, row)
    assert config.kv_bytes_per_token == per_token
    assert config.pool_row_width == (640 if config.is_latent else 128)


@pytest.mark.parametrize("preset,dense", [("xing4-29b-a4b-cut7", 7 * 576 * 2), ("joyai-llm-flash-cut8", 9 * 576 * 2),
                                          ("qwen2-7b", 57344), ("nemotron3-nano-30b-a3b-cut9", 1024)])
def test_dense_rows_are_counted_at_the_dense_caches_own_width(preset, dense):
    """A dense cache row is not padded: the bound for dense rows (``max_rows``)
    counts 576 lanes where the paged one counts the pool's 640."""
    from k_llms_tpu.backends.tpu import HbmMemoryModel

    config = get_config(preset)
    model = HbmMemoryModel(config, param_bytes=0, hbm_bytes=16 << 30)
    assert config.dense_kv_bytes_per_token == model.dense_kv_bytes_per_token == dense
    assert (dense < config.kv_bytes_per_token) == config.is_latent
    assert model.max_rows(4096) == model.budget_bytes() // (4096 * dense + model.row_margin_bytes)


@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_healthz_reports_what_a_token_holds_where_the_engine_keeps_it(layout):
    from k_llms_tpu import KLLMs

    config = get_config("xing4-tiny")
    client = KLLMs(backend="tpu", model=config.name, engine=shared_engine(config.name, kv_layout=layout))
    try:
        hbm = client.backend.health()["hbm"]
    finally:
        client.backend.close()
    assert hbm["paged"] == (layout == "paged")
    assert hbm["kv_bytes_per_token"] == 3 * (128 if layout == "paged" else 40) * 4


@pytest.mark.parametrize("model", MODELS)
def test_scatter_gather_and_copy_on_write_return_the_rows_written(model):
    config = get_config(model)
    heads, k_width, v_width = config.cache_widths
    pool = PagedKVPool(config, total_pages=6, page_size=PS)
    k, v = rows_of(config, 2 * PS, seed=1)
    slots = np.concatenate([np.arange(3 * PS, 4 * PS), np.arange(PS, 2 * PS)]).astype(np.int32)
    pool.scatter_tokens(k, v, slots)
    got = pool.gather_tokens(slots)
    assert got.k.shape == (config.paging_layers, 1, 2 * PS, heads, k_width)
    assert got.v.shape == (config.paging_layers, 1, 2 * PS, heads, v_width)
    equal(got.k[:, 0], k)
    equal(got.v[:, 0], v)
    pool.copy_pages([3, 1], [5, 2])  # pages 3 and 1 hold the rows; 5 and 2 get the copies
    copied = pool.gather_tokens(np.concatenate([np.arange(5 * PS, 6 * PS), np.arange(2 * PS, 3 * PS)]))
    equal(copied.k[:, 0], k)
    equal(copied.v[:, 0], v)
    equal(pool.gather_tokens(slots).k[:, 0], k)  # the sources stand
    untouched = pool.gather_tokens(np.arange(0, PS)).k  # the trash page and page 4: never written
    assert not np.asarray(untouched).any() and not np.asarray(pool.kv.k[:, 4 * PS:5 * PS]).any()
    assert not np.asarray(pool.kv.k[..., k_width:]).any()  # the pad lanes, where there are any
    assert np.asarray(pool.kv.k[:, slots, :, :k_width] == k).all()  # a row lies at (layer, slot)


@pytest.mark.parametrize("model", MODELS)
def test_a_later_scatter_overwrites_and_a_repeated_slot_keeps_one_of_its_rows(model):
    """The trash page takes every idle row's write: repeated slots are legal
    and leave one of the rows written there, whole."""
    config = get_config(model)
    pool = PagedKVPool(config, total_pages=3, page_size=PS)
    k, v = rows_of(config, 4, seed=2)
    pool.scatter_tokens(k, v, np.asarray([9, 10, 9, 11], np.int32))
    got = np.asarray(pool.gather_tokens(np.asarray([9, 10, 11], np.int32)).k[:, 0])
    equal(got[:, 1:], np.asarray(k)[:, [1, 3]])
    assert any((got[:, 0] == np.asarray(k)[:, j]).all() for j in (0, 2))
    k2, v2 = rows_of(config, 4, seed=3)
    pool.scatter_tokens(k2, v2, np.asarray([9, 10, 11, 12], np.int32))
    equal(pool.gather_tokens(np.asarray([9, 10, 11, 12], np.int32)).k[:, 0], k2)


# -- the latent pool's one way of addressing -------------------------------------------

@pytest.mark.parametrize("layers,flat,stored,width", [(3, 40, 128, 40), (9, 64, 640, 576), (1, 16, 128, 128)])
def test_pool_index_gather_and_scatter_against_plain_indexing(layers, flat, stored, width):
    rng = np.random.default_rng(layers)
    full = rng.standard_normal((layers, flat, 1, stored)).astype(np.float32)
    pool = jnp.asarray(full)
    slots = jnp.asarray(rng.integers(0, flat, (4, 5)), jnp.int32)
    one = pool_index(pool, jnp.int32(layers - 1), slots)
    equal(one, (layers - 1) * flat + np.asarray(slots))
    every = pool_layers(pool, slots)
    assert every.shape == (layers, 4, 5)
    equal(every, np.arange(layers)[:, None, None] * flat + np.asarray(slots)[None])
    equal(pool_layers(pool, slots, max(1, layers - 1)), np.asarray(every)[:max(1, layers - 1)])
    equal(pool_gather(pool, every, width), full[:, np.asarray(slots), 0, :width])
    where = jnp.asarray(rng.permutation(flat)[:6], jnp.int32)  # distinct slots
    rows = rng.standard_normal((layers, 6, 1, width)).astype(np.float32)
    wrote = np.asarray(pool_scatter(pool, pool_layers(pool, where), jnp.asarray(rows)))
    want = full.copy()
    want[:, np.asarray(where), :, :width] = rows
    want[:, np.asarray(where), :, width:] = 0
    equal(wrote, want)


@pytest.mark.parametrize("model", MODELS)
def test_the_steps_write_equals_a_scatter_along_the_layer_axis(model):
    """``scatter_rows`` (the write of ``_build_step`` and of the engine's own
    paged decode loop) against the parent's form, ``pool.at[:, idx].set``, on
    rows padded by hand: one scatter by (layer, slot) for a one-row pool, the
    parent's own form for the others."""
    config = get_config(model)
    _, width, _ = config.cache_widths
    pool = PagedKVPool(config, total_pages=4, page_size=PS)
    k, v = rows_of(config, 4, seed=4)
    idx = jnp.asarray([8, 17, 30, 3], jnp.int32)
    got_k, got_v = scatter_rows(pool.kv.k, pool.kv.v, idx, k, v)
    padded = jnp.pad(k, ((0, 0),) * 3 + ((0, config.pool_row_width - width),))
    equal(got_k, pool.kv.k.at[:, idx].set(padded))
    equal(got_v, pool.kv.v.at[:, idx].set(v))


def test_a_step_without_the_module_writes_the_stacks_layers_alone():
    """The engine's own loops run a drafting model undrafted: their step hands
    ``scatter_rows`` the stack's L layers of the pool's L + 1."""
    config = get_config("joyai-tiny")
    pool = PagedKVPool(config, total_pages=4, page_size=PS)
    k, v = rows_of(config, 3, seed=6)
    idx = jnp.asarray([9, 2, 27], jnp.int32)
    got, _ = scatter_rows(pool.kv.k, pool.kv.v, idx, k[:-1], v[:-1])
    want = np.zeros(pool.kv.k.shape, np.float32)
    want[:-1, np.asarray(idx), :, :config.cache_widths[1]] = np.asarray(k[:-1])
    equal(got, want)


def test_the_drafted_steps_write_lands_each_layers_rows_at_its_positions():
    config = get_config("joyai-tiny")
    L, (_, width, _) = config.num_layers, config.cache_widths
    pool = PagedKVPool(config, total_pages=6, page_size=PS)
    rng = np.random.default_rng(5)
    W, S = 3, 2
    stack = jnp.asarray(rng.standard_normal((L, W, S, 1, width)), config.jax_dtype)
    module = jnp.asarray(rng.standard_normal((W, S, 1, width)), config.jax_dtype)
    write_idx = jnp.asarray([[8, 9, 10], [23, 24, 25], [40, 41, 42]], jnp.int32)  # P, P+1, P+2
    got = np.asarray(write_drafted_rows(pool.kv.k, stack, module, write_idx))
    want = np.zeros_like(got)
    want[:L, np.asarray(write_idx[:, :2]), :, :width] = np.asarray(stack)
    want[L, np.asarray(write_idx[:, 1:]), :, :width] = np.asarray(module)
    equal(got, want)
    admitted = np.asarray(write_drafted_rows(pool.kv.k, None, module[:, :1], write_idx[:, :1]))
    want = np.zeros_like(got)
    want[L, np.asarray(write_idx[:, 0]), :, :width] = np.asarray(module[:, 0])
    equal(admitted, want)


# -- through the loop: chunks, steps, copy-on-write ------------------------------------

@pytest.mark.parametrize("ladder,plen", [((), 75), ((32, 64, 128), 161)])
@pytest.mark.parametrize("model", LATENT)
def test_pad_lanes_are_zero_after_chunks_steps_and_copy_on_write(model, ladder, plen):
    """A chunked prompt for n = 4 rows (chunks of 32, or the ladder's turn of
    128 and a padded one of 64; its partial last page copied for each row),
    decoded a few steps: the rows written hold numbers in the cache's own
    lanes and zeros in the pad lanes, everywhere in the pool."""
    config = get_config(model)
    _, width, _ = config.cache_widths
    engine = shared_engine(model, kv_layout="paged", kv_page_size=16)
    loop = ContinuousDecodeLoop(engine, width=4, max_prompt=256, max_new=8, eos_ids=[257],
                                prefill_chunk_tokens=32, prefill_chunk_ladder=ladder)
    prompt = [int(t) for t in np.random.RandomState(2).randint(0, 250, plen)]
    try:
        out = loop.submit(prompt, n=4, max_new=6, temperature=0.8, top_p=0.95, seed=5).result(timeout=300)
        pages = loop.stats.get("pages")
        pool_k = np.asarray(loop._pool.kv.k)
    finally:
        loop.stop()
    assert np.asarray(out.tokens).shape[0] == 4 and pages["cow_copies"] >= 4
    assert pool_k.shape[-1] == config.pool_row_width > width
    assert not pool_k[..., width:].any()
    written = np.abs(pool_k[..., :width]).sum(axis=(2, 3)) > 0  # [L, flat]
    assert written.sum(axis=1).min() >= plen + 4 * 5  # every cache layer: the prompt, then each row's steps


@pytest.mark.parametrize("model", LATENT)
def test_the_engines_own_paged_decode_loop_serves_a_latent_model(model):
    """What a latent deployment sends past the continuous loop (top_logprobs,
    penalties, logit_bias, a prompt beyond its bounds) decodes in
    ``generate_many`` on pages: the coalesced loop writes each step's rows
    through ``scatter_rows``, greedy tokens equal ``generate``'s (dense where
    the model has a dense layout), and the pad lanes stay zero."""
    config = get_config(model)
    _, width, _ = config.cache_widths
    engine = shared_engine(model, kv_layout="paged", kv_page_size=8, kv_pool_pages=128)
    solo = engine if config.num_nextn_predict_layers else shared_engine(model, kv_layout="dense")
    items = [GenRequestSpec(prompt_ids=list(range(3, 20)), n=2, seed=7),
             GenRequestSpec(prompt_ids=list(range(5, 16)), n=3, seed=11)]
    got = engine.generate_many(items, max_new_tokens=8, temperature=0.0, top_p=None, top_logprobs=2)
    for item, result in zip(items, got):
        assert not isinstance(result, Exception), result
        want = solo.generate(item.prompt_ids, n=item.n, max_new_tokens=8, temperature=0.0, seed=item.seed)
        equal(result.tokens, want.tokens)
    pool_k = np.asarray(engine._kv_pool.kv.k)
    assert pool_k.shape[-1] == config.pool_row_width > width
    assert np.abs(pool_k[: config.num_layers, :, :, :width]).sum() > 0 and not pool_k[..., width:].any()


def test_no_module_but_the_page_manager_writes_a_pool():
    """A pool write outside ``engine/paging.py``'s movers (over the helpers of
    ``ops/attention.py``) is a writer the next change of the pool's layout
    would miss, as PR 35's first round missed the engine's paged decode loop."""
    import pathlib
    import re

    import k_llms_tpu

    root = pathlib.Path(k_llms_tpu.__file__).parent
    writes = re.compile(r"pool\w*(\.reshape\([^)]*\))?\.at\[|pool_scatter\(")
    found = {p.relative_to(root).as_posix() for p in root.rglob("*.py") if writes.search(p.read_text())}
    assert found == {"engine/paging.py", "ops/attention.py"}
