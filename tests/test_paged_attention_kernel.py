"""Fused paged-attention differentials: pallas-interpret == xla == dense.

The fused op (ops/paged_attention.py) has one contract and two
implementations. These tests pin the equivalence chain at both levels:

* op level — ``paged_decode_attention_pallas(interpret=True)`` against the
  XLA reference on synthetic pools with ragged lengths, phase-shifted
  (continuous-layout) gen tables, and trash-page garbage, across page sizes;
  the ragged walk (a row's own page count as the trip count, K pages a
  block) with every page no row attends to poisoned;
* step level — ``paged_verify_step`` against the dense ``verify_step`` on
  identical KV contents: BITWISE for the "xla" impl (the serving CPU path),
  allclose (and, inside that band in f32, greedy-token-equal) for
  "pallas_interpret" (online softmax reorders float accumulation by design);

plus the selection contract: ``resolve_paged_attention_impl``'s CPU posture
("auto" -> xla, uncounted), the COUNTED fallback for an unsatisfiable
explicit "pallas", and the ``ops.paged_attn`` failpoint forcing the counted
fallback — the observability drill the README registry documents.

Widest page-size grids carry the ``slow`` tag; one mid-size representative
per class stays in tier-1.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k_llms_tpu.models import get_config
from k_llms_tpu.models.llama import KVCache, paged_verify_step, verify_step
from k_llms_tpu.ops import paged_attention as paged_attention_ops
from k_llms_tpu.ops.paged_attention import (
    PAGED_ATTENTION_IMPLS,
    live_pages,
    note_paged_attn_dispatch,
    paged_attention_page_tables,
    paged_decode_attention_pallas,
    paged_decode_attention_xla,
    pages_per_block,
    resolve_paged_attention_impl,
    table_pages,
)
from k_llms_tpu.reliability import failpoints as fp
from k_llms_tpu.reliability.failpoints import FailSpec
from k_llms_tpu.utils.observability import KERNEL_EVENTS, PAGED_ATTN_PAGES

CONFIG = get_config("tiny")
TRASH_PAGE = 0

# One fast mid-size representative; the widest/narrowest grids are slow.
PAGE_SIZES = [
    pytest.param(4, marks=pytest.mark.slow),
    8,
    pytest.param(16, marks=pytest.mark.slow),
]


def _params():
    from conftest import shared_params

    return shared_params(CONFIG, param_key=0)


# ---------------------------------------------------------------------------
# op level: synthetic pools, ragged tables, both layouts
# ---------------------------------------------------------------------------


def _build_tables(plens, G, ps, *, continuous):
    """Per-row block tables the way the engine lays them out.

    ``continuous=False`` is the coalesced-batch layout (gen rows start on
    fresh pages, phase 0); ``continuous=True`` is the continuous-loop layout
    where generated tokens continue the prompt's last partial page (phase =
    plen % ps). Unmapped positions point into the trash page, exactly like
    ``flat_slots`` does. Returns (prefix_idx [B, P], gen_idx [B, G],
    total_pages)."""
    B = len(plens)
    P = (max(int(p) for p in plens) + ps - 1) // ps * ps  # bucket width
    next_page = TRASH_PAGE + 1
    prefix_idx = np.empty((B, P), np.int32)
    gen_idx = np.empty((B, G), np.int32)
    for b, plen in enumerate(int(p) for p in plens):
        n_pp = -(-plen // ps)
        ppages = list(range(next_page, next_page + n_pp))
        next_page += n_pp
        for p in range(P):
            if p < plen:
                prefix_idx[b, p] = ppages[p // ps] * ps + p % ps
            else:
                prefix_idx[b, p] = TRASH_PAGE * ps + p % ps
        phase = plen % ps if continuous else 0
        n_gp = -(-(phase + G) // ps)
        if continuous and phase:
            gpages = [ppages[-1]] + list(range(next_page, next_page + n_gp - 1))
            next_page += n_gp - 1
        else:
            gpages = list(range(next_page, next_page + n_gp))
            next_page += n_gp
        for g in range(G):
            pos = phase + g
            gen_idx[b, g] = gpages[pos // ps] * ps + pos % ps
    return prefix_idx, gen_idx, next_page


# Both ops take the whole [L, flat, KVH, D] pool and a layer number. The op
# tests build L = 3 layers of different contents and read the first and the
# last, against the XLA op on that layer's slice alone.
POOL_LAYERS = 3
LAYERS = [0, 2]

# Sliding windows by where they bind, as functions of the page size: inside
# the longer rows' prompts (the walk starts at a later prefix page), inside
# the generated run (the prompt is out of sight and the walk starts at a later
# gen page), and nowhere. ``None`` is a model without one.
WINDOWS = {
    "none": lambda ps: None,
    "in_prompt": lambda ps: 2 * ps + 1,
    "in_generated": lambda ps: ps // 2 + 1,
    "wide": lambda ps: 100 * ps,
}
# Each window on one layer of the pool, no window on both.
LAYER_WINDOWS = [
    (0, "none"), (2, "none"), (2, "in_prompt"), (2, "in_generated"), (0, "wide"),
]


def _reference_masks(plens, glens, P, G, window):
    """``(key_mask [B, 1, G], prefix_mask [B, 1, P])`` as ``paged_verify_step``
    builds them: the fresh column at ``glens`` included, and under a window
    only the keys at absolute positions above ``plens + glens - window``."""
    s = np.arange(G)[None, None, :]
    c = np.arange(P)[None, None, :]
    glens, plens = glens[:, None, None], plens[:, None, None]
    key_mask, prefix_mask = s <= glens, c < plens
    if window is not None:
        key_mask = key_mask & (s > glens - window)
        prefix_mask = prefix_mask & (c > plens + glens - window)
    return key_mask, prefix_mask


@pytest.mark.parametrize("page_size", PAGE_SIZES)
@pytest.mark.parametrize("continuous", [False, True])
@pytest.mark.parametrize("layer,window", LAYER_WINDOWS)
def test_op_pallas_interpret_matches_xla(page_size, continuous, layer, window):
    """Ragged prompt/gen lengths, every page-boundary alignment class
    (mid-page, exact multiple, single-slot), trash garbage in the pool:
    the fused kernel must agree with the reference on both the coalesced
    (phase 0) and continuous (phase-shifted) gen layouts — reading its layer
    out of the whole pool, as the reference does bit for bit — and under a
    sliding window, against the reference given the windowed masks."""
    ps = page_size
    window = WINDOWS[window](ps)
    B, G = 4, 12
    QH, KVH, D = 4, 2, 16
    plens = np.array([1, ps, 2 * ps + 3, 2 * ps - 1], np.int32)
    wis = np.array([0, 3, G - 1, 7], np.int32)  # per-row generated counts

    prefix_idx, gen_idx, npages = _build_tables(
        plens, G, ps, continuous=continuous
    )
    if continuous:
        expect_phase = plens % ps
        _, _, phase = paged_attention_page_tables(
            jnp.asarray(prefix_idx), jnp.asarray(gen_idx), ps
        )
        np.testing.assert_array_equal(np.asarray(phase), expect_phase)

    keys = jax.random.split(jax.random.key(ps + int(continuous)), 5)
    pool_shape = (POOL_LAYERS, npages * ps, KVH, D)
    pool_k = jax.random.normal(keys[0], pool_shape, jnp.float32)
    pool_v = jax.random.normal(keys[1], pool_shape, jnp.float32)
    q = jax.random.normal(keys[2], (B, 1, QH, D), jnp.float32)
    nk = jax.random.normal(keys[3], (B, 1, KVH, D), jnp.float32)
    nv = jax.random.normal(keys[4], (B, 1, KVH, D), jnp.float32)
    sm_scale = 1.0 / math.sqrt(D)

    key_mask, prefix_mask = map(
        jnp.asarray, _reference_masks(plens, wis, prefix_idx.shape[1], G, window)
    )

    def xla_op(pk, pv, l):
        return paged_decode_attention_xla(
            q, pk, pv, jnp.int32(l),
            jnp.asarray(prefix_idx), jnp.asarray(gen_idx),
            nk, nv, jnp.asarray(wis), key_mask, prefix_mask,
            sm_scale=sm_scale,
        )

    out_x = xla_op(pool_k[layer][None], pool_v[layer][None], 0)
    np.testing.assert_array_equal(
        np.asarray(xla_op(pool_k, pool_v, layer)), np.asarray(out_x)
    )
    tables = paged_attention_page_tables(
        jnp.asarray(prefix_idx), jnp.asarray(gen_idx), ps
    )
    out_p = paged_decode_attention_pallas(
        q[:, 0], pool_k, pool_v, jnp.int32(layer), *tables, nk[:, 0], nv[:, 0],
        jnp.asarray(plens), jnp.asarray(wis),
        page_size=ps, sm_scale=sm_scale, window=window, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_x[:, 0]), rtol=2e-5, atol=2e-6
    )


# The ragged walk: (prompt lengths, generated counts) per row, in units the
# case scales by the page size. K is forced to 3 pages a block, so "3 * ps"
# is one whole block. ``None`` stands for the table's full width.
RAGGED_CASES = {
    # a slot with nothing walks zero pages, beside rows that do
    "idle_row": (lambda ps: [0, 2 * ps + 1, 0, ps], lambda ps, G: [0, 5, 0, 2]),
    # one partial page and nothing generated
    "one_partial_page": (lambda ps: [3, 1, ps - 1, 2], lambda ps, G: [0, 0, 0, 0]),
    # neither length a multiple of K * ps: the last block's tail is not fetched
    "not_a_block_multiple": (
        lambda ps: [4 * ps + 1, 3 * ps + 2, 7 * ps - 1, 5 * ps],
        lambda ps, G: [ps + 1, 2, G - 2, ps],
    ),
    # plen = P and as many generated as the table takes
    "full_table": (lambda ps: [6 * ps] * 4, lambda ps, G: [G - 1] * 4),
    # the generated tokens cross a page boundary mid-page (phase > 0 when continuous)
    "phase_crossing": (
        lambda ps: [ps + ps // 2, ps - 1, 2 * ps + 1, ps // 2],
        lambda ps, G: [ps // 2, 1, ps, 2 * ps - 1],
    ),
    # short and long rows in one call: trip counts from 0 to the whole table
    "mixed_rows": (lambda ps: [1, 6 * ps, 0, 2 * ps + 3], lambda ps, G: [G - 1, 0, 0, 1]),
}


@pytest.mark.parametrize("page_size", PAGE_SIZES)
@pytest.mark.parametrize("continuous", [False, True])
@pytest.mark.parametrize("layer,window", LAYER_WINDOWS)
@pytest.mark.parametrize("case", sorted(RAGGED_CASES))
def test_op_ragged_walk_reads_live_pages_only(
    case, layer, window, continuous, page_size, monkeypatch
):
    """Each row walks its own pages, K = 3 a block: the kernel agrees with
    the reference, which sees a clean pool, while every page that holds no
    position some row attends to — the trash page, table tails, idle rows'
    pages, pages the sliding window has passed, other layers' copies of them
    — is NaN in the kernel's pool. A fetched page's values reach the
    accumulator even under a zero weight (0 * NaN), so a finite, equal result
    says the walk started at the window's first page and stopped where the
    row's pages stop, and equality that it skipped nothing it should see."""
    ps = page_size
    window = WINDOWS[window](ps)
    G = 3 * ps  # gen table: 3 pages and the phase shift's spare
    plens = np.array(RAGGED_CASES[case][0](ps), np.int32)
    wis = np.array(RAGGED_CASES[case][1](ps, G), np.int32)
    B, QH, KVH, D = len(plens), 4, 2, 16
    monkeypatch.setattr(
        paged_attention_ops, "PAGE_BUFFER_BYTES", 3 * 4 * ps * KVH * D * 4
    )

    prefix_idx, gen_idx, npages = _build_tables(plens, G, ps, continuous=continuous)
    NP, NG = table_pages(prefix_idx.shape[1], G, ps)
    assert pages_per_block(ps * KVH * D * 4, NP + NG) == 3

    keys = jax.random.split(jax.random.key(len(case) + ps), 5)
    pool_shape = (POOL_LAYERS, npages * ps, KVH, D)
    pool_k = jax.random.normal(keys[0], pool_shape, jnp.float32)
    pool_v = jax.random.normal(keys[1], pool_shape, jnp.float32)
    q = jax.random.normal(keys[2], (B, 1, QH, D), jnp.float32)
    nk = jax.random.normal(keys[3], (B, 1, KVH, D), jnp.float32)
    nv = jax.random.normal(keys[4], (B, 1, KVH, D), jnp.float32)
    sm_scale = 1.0 / math.sqrt(D)

    key_mask, prefix_mask = _reference_masks(plens, wis, prefix_idx.shape[1], G, window)
    out_x = paged_decode_attention_xla(
        q, pool_k, pool_v, jnp.int32(layer),
        jnp.asarray(prefix_idx), jnp.asarray(gen_idx),
        nk, nv, jnp.asarray(wis), jnp.asarray(key_mask), jnp.asarray(prefix_mask),
        sm_scale=sm_scale,
    )

    # Pages some row attends to, from the reference's own masks (the fresh
    # column at ``wis`` is not read from the pool).
    in_pool = key_mask[:, 0] & (np.arange(G)[None, :] < wis[:, None])
    attended = np.concatenate([prefix_idx[prefix_mask[:, 0]], gen_idx[in_pool]])
    dead = np.ones((POOL_LAYERS, npages), bool)
    dead[layer, np.unique(attended // ps)] = False
    dead_slots = jnp.asarray(np.repeat(dead, ps, axis=1))[:, :, None, None]
    tables = paged_attention_page_tables(
        jnp.asarray(prefix_idx), jnp.asarray(gen_idx), ps
    )
    out_p = paged_decode_attention_pallas(
        q[:, 0],
        jnp.where(dead_slots, jnp.nan, pool_k), jnp.where(dead_slots, jnp.nan, pool_v),
        jnp.int32(layer), *tables, nk[:, 0], nv[:, 0],
        jnp.asarray(plens), jnp.asarray(wis),
        page_size=ps, sm_scale=sm_scale, window=window, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_x[:, 0]), rtol=2e-5, atol=2e-6
    )


@pytest.mark.parametrize("window", sorted(WINDOWS))
@pytest.mark.parametrize("page_size", [4, 8, 64])
def test_live_pages_counts_the_pages_the_reference_masks_leave(page_size, window):
    """``live_pages`` against a brute-force count over the masks the XLA
    reference is given: a table page is live when some position of it is
    unmasked and in the pool — prefix position c < plen sits on prefix page
    c // ps, generated position g < glen on gen page (phase + g) // ps — and
    under a sliding window the masks keep only the last W keys."""
    ps = page_size
    window = WINDOWS[window](ps)
    rng = np.random.default_rng(ps)
    P, G = 9 * ps, 4 * ps
    plens = np.concatenate([[0, 1, ps - 1, ps, ps + 1, P], rng.integers(0, P + 1, 58)])
    glens = np.concatenate([[0, 0, 1, ps, G - 1, G - 1], rng.integers(0, G, 58)])
    key_mask, prefix_mask = _reference_masks(plens, glens, P, G, window)
    gen_mask = key_mask[:, 0] & (np.arange(G)[None, :] < glens[:, None])  # in the pool
    for phase in (np.zeros_like(plens), plens % ps):  # coalesced, continuous
        (p0, n_prefix), (g0, n_gen) = live_pages(plens, glens, phase, ps, window)
        p0, g0 = np.broadcast_to(p0, plens.shape), np.broadcast_to(g0, plens.shape)
        for b in range(len(plens)):
            prefix_pages = np.unique(np.flatnonzero(prefix_mask[b, 0]) // ps)
            gen_pages = np.unique((phase[b] + np.flatnonzero(gen_mask[b])) // ps)
            # the walk takes one run of each table: the live pages are those
            np.testing.assert_array_equal(prefix_pages, np.arange(p0[b], n_prefix[b]))
            np.testing.assert_array_equal(gen_pages, np.arange(g0[b], n_gen[b]))
        NP, NG = table_pages(P, G, ps)
        assert n_prefix.max() <= NP and n_gen.max() <= NG
        # both runs start past page 0 for some row exactly when the window binds
        assert (p0.any() and g0.any()) == (window is not None and window < P + G)


@pytest.mark.parametrize("window", [None, 3])
def test_loop_counts_the_pages_its_steps_walk(window, monkeypatch):
    """A small paged loop on the interpreted kernel: greedy tokens equal the
    XLA-paged loop's, and the ``/metrics`` gauges advance by the sums the
    rows' lengths give, in layer-pages (a page once for every paging layer) —
    idle slots walking nothing, and under a sliding window (3: it leaves the
    prompt's first page, then the prompt, then the first generated page) the
    pages before its first one counted apart (a stack that mixes windowed and
    global layers under the kernel: tests/test_command_a_plus.py)."""
    import asyncio

    from conftest import shared_engine

    from k_llms_tpu import KLLMs
    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
    from k_llms_tpu.serving.app import create_app

    ps, width, max_prompt, max_new, n, new = 8, 4, 64, 16, 2, 10
    prompt = [5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15]  # 11 tokens: phase 3
    config = CONFIG.with_(sliding_window=window)
    windows = config.layer_windows
    assert windows == (window, window)
    engine = shared_engine(model=config, kv_layout="paged", kv_page_size=ps)
    runs = {}
    for impl in ("xla", "pallas_interpret"):
        monkeypatch.setattr(
            paged_attention_ops, "resolve_paged_attention_impl",
            lambda requested, impl=impl, **kw: impl,
        )
        loop = ContinuousDecodeLoop(
            engine, width=width, max_prompt=max_prompt, max_new=max_new, eos_ids=[257]
        )
        before = PAGED_ATTN_PAGES.snapshot()
        try:
            result = loop.submit(
                prompt, n=n, max_new=new, temperature=0.0, top_p=None, seed=1
            ).result(timeout=300)
            steps = loop.stats["steps"]
        finally:
            loop.stop()
        after = PAGED_ATTN_PAGES.snapshot()
        runs[impl] = (
            np.asarray(result.tokens), steps,
            {k: after.get(k, 0) - before.get(k, 0) for k in PAGED_ATTN_PAGES.declared},
        )
    tokens, steps, grew = runs["pallas_interpret"]
    np.testing.assert_array_equal(tokens, runs["xla"][0])
    # The first token comes from the prefill; step t has t tokens in the pool.
    assert steps == new - 1
    plen, phase = len(prompt), len(prompt) % ps
    held = windowed_out = 0
    for t in range(steps):
        # Table pages with a position in the pool, and those of them with a
        # position the query at plen + t still sees.
        for layer_window in windows:
            prompt_pages = {c // ps for c in range(plen)}
            gen_pages = {(phase + g) // ps for g in range(t)}
            held += n * (len(prompt_pages) + len(gen_pages))
            if layer_window is not None:
                first = plen + t - layer_window + 1
                prompt_pages -= {c // ps for c in range(plen) if c >= first}
                gen_pages -= {(phase + g) // ps for g in range(t) if plen + g >= first}
                windowed_out += n * (len(prompt_pages) + len(gen_pages))
    walked = held - windowed_out
    assert (windowed_out > 0) == (window is not None)
    assert grew == {
        "paged_attn_pages_walked": walked,
        "paged_attn_pages_tabled": (
            len(windows) * steps * width * sum(table_pages(max_prompt, max_new, ps))),
        "paged_attn_pages_windowed_out": windowed_out,
    }
    assert set(runs["xla"][2].values()) == {0}  # the XLA path gathers whole tables

    sent = []

    async def send(message):
        sent.append(message)

    asyncio.run(create_app(client=KLLMs(backend="fake"))._metrics({}, None, send, {}))
    body = b"".join(m.get("body", b"") for m in sent).decode()
    for name in PAGED_ATTN_PAGES.declared:
        assert f"# TYPE kllms_{name} gauge" in body
    value = next(
        line for line in body.splitlines()
        if line.startswith("kllms_paged_attn_pages_walked ")
    )
    assert float(value.split()[1]) >= walked


def _shared_prefix_case():
    """B = 4 rows of R = 2 requests over a 3-layer pool: request-major
    ``[R, P]`` and repeated ``[B, P]`` prefix tables, fresh gen pages per row."""
    ps = 8
    B, R, G = 4, 2, 8
    QH, KVH, D = 4, 2, 16
    plens_req = np.array([ps + 3, 2 * ps], np.int32)
    wis = np.array([0, 2, 5, 7], np.int32)

    prefix_req, _, npages0 = _build_tables(plens_req, 1, ps, continuous=False)
    # Fresh gen pages per row, past the prompt pages.
    gen_idx = np.empty((B, G), np.int32)
    next_page = npages0
    for b in range(B):
        gpages = list(range(next_page, next_page + -(-G // ps)))
        next_page += len(gpages)
        for g in range(G):
            gen_idx[b, g] = gpages[g // ps] * ps + g % ps

    keys = jax.random.split(jax.random.key(42), 5)
    pool_shape = (POOL_LAYERS, next_page * ps, KVH, D)
    return dict(
        ps=ps,
        prefix_req=prefix_req,
        prefix_row=np.repeat(prefix_req, B // R, axis=0),
        gen_idx=gen_idx,
        plens_row=np.repeat(plens_req, B // R),
        wis=wis,
        pool_k=jax.random.normal(keys[0], pool_shape, jnp.float32),
        pool_v=jax.random.normal(keys[1], pool_shape, jnp.float32),
        q=jax.random.normal(keys[2], (B, QH, D), jnp.float32),
        nk=jax.random.normal(keys[3], (B, KVH, D), jnp.float32),
        nv=jax.random.normal(keys[4], (B, KVH, D), jnp.float32),
        sm_scale=1.0 / math.sqrt(D),
    )


def _kernel_on(case, table, layer, mesh=None):
    tables = paged_attention_page_tables(
        jnp.asarray(table), jnp.asarray(case["gen_idx"]), case["ps"]
    )
    return np.asarray(
        paged_decode_attention_pallas(
            case["q"], case["pool_k"], case["pool_v"], jnp.int32(layer), *tables,
            case["nk"], case["nv"],
            jnp.asarray(case["plens_row"]), jnp.asarray(case["wis"]),
            page_size=case["ps"], sm_scale=case["sm_scale"], interpret=True,
            mesh=mesh,
        )
    )


@pytest.mark.parametrize("layer", LAYERS)
def test_op_shared_prefix_table_broadcasts(layer):
    """An [R, P] request-major prefix table (the engine's shared-prefix
    layout) must produce the same kernel output as the explicitly repeated
    [B, P] per-row table — and that output is the XLA op's on the layer's
    slice alone."""
    case = _shared_prefix_case()
    out = _kernel_on(case, case["prefix_req"], layer)
    np.testing.assert_array_equal(
        out, _kernel_on(case, case["prefix_row"], layer)
    )

    wis, plens_row = case["wis"], case["plens_row"]
    s = np.arange(case["gen_idx"].shape[1])[None, None, :]
    c = np.arange(case["prefix_req"].shape[1])[None, None, :]
    out_x = paged_decode_attention_xla(
        case["q"][:, None],
        case["pool_k"][layer][None], case["pool_v"][layer][None], jnp.int32(0),
        jnp.asarray(case["prefix_req"]), jnp.asarray(case["gen_idx"]),
        case["nk"][:, None], case["nv"][:, None], jnp.asarray(wis),
        jnp.asarray(s <= wis[:, None, None]),
        jnp.asarray(c < plens_row[:, None, None]),
        sm_scale=case["sm_scale"],
    )
    np.testing.assert_allclose(out, np.asarray(out_x[:, 0]), rtol=2e-5, atol=2e-6)


def test_op_under_mesh_reads_its_layer_per_shard():
    """Under a data x model mesh the kernel runs per shard: rows over data,
    kv heads (of the pool too) over model, the pool's layer axis whole and the
    layer number replicated. A shard scores its own kv heads' rows in one
    product, so against the single device (all heads in one) the sums run in
    another order: the kernel's own tolerance, not bit equality."""
    from k_llms_tpu.parallel.mesh import make_mesh

    case = _shared_prefix_case()
    layer = LAYERS[-1]
    np.testing.assert_allclose(
        _kernel_on(case, case["prefix_req"], layer, mesh=make_mesh(2, 2)),
        _kernel_on(case, case["prefix_req"], layer),
        rtol=2e-5, atol=2e-6,
    )


# ---------------------------------------------------------------------------
# step level: paged_verify_step vs the dense verify_step oracle
# ---------------------------------------------------------------------------


def _step_case(ps, *, fork_gen_page=False, seed=0):
    """Build a dense world and a paged world holding IDENTICAL KV values.

    R=2 coalesced requests, 2 rows each, ragged prompt and generated
    lengths. Invalid dense slots and the paged trash/unused pages hold
    DIFFERENT garbage, so agreement proves the masking contract, not shared
    zeros. ``fork_gen_page``: duplicate one live row's gen page to a fresh
    physical page with identical contents and retarget the table — the CoW
    layout; physical placement must be invisible."""
    cfg = CONFIG
    L, KVH, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    R, per, G = 2, 2, 10
    B = R * per
    plens_req = np.array([2 * ps + 3, ps], np.int32)
    P = 3 * ps
    lengths = np.array([0, 3, 5, G - 1], np.int32)

    rng = np.random.default_rng(seed)

    def randn(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    # Dense caches: valid values shared with the pool, garbage elsewhere.
    pref_k, pref_v = randn(L, R, P, KVH, D), randn(L, R, P, KVH, D)
    gen_k, gen_v = randn(L, B, G, KVH, D), randn(L, B, G, KVH, D)

    # Paged pool: prompt pages per request, fresh gen pages per row.
    n_pp = [-(-int(p) // ps) for p in plens_req]
    gp = -(-G // ps)
    npages = 1 + sum(n_pp) + B * gp + 1  # trash + prompts + gens + fork spare
    flat = npages * ps
    pool_k, pool_v = randn(L, flat, KVH, D), randn(L, flat, KVH, D)

    next_page = TRASH_PAGE + 1
    prefix_idx = np.empty((R, P), np.int32)
    for r in range(R):
        ppages = list(range(next_page, next_page + n_pp[r]))
        next_page += n_pp[r]
        plen = int(plens_req[r])
        for p in range(P):
            if p < plen:
                slot = ppages[p // ps] * ps + p % ps
                pool_k[:, slot] = pref_k[:, r, p]
                pool_v[:, slot] = pref_v[:, r, p]
                prefix_idx[r, p] = slot
            else:
                prefix_idx[r, p] = TRASH_PAGE * ps + p % ps
    gen_idx = np.empty((B, G), np.int32)
    for b in range(B):
        gpages = list(range(next_page, next_page + gp))
        next_page += gp
        for g in range(G):
            slot = gpages[g // ps] * ps + g % ps
            gen_idx[b, g] = slot
            if g < lengths[b]:
                pool_k[:, slot] = gen_k[:, b, g]
                pool_v[:, slot] = gen_v[:, b, g]

    if fork_gen_page:
        # Copy row 3's first gen page to the spare physical page and retarget
        # its table — byte-for-byte the pool state after a CoW copy.
        src = int(gen_idx[3, 0]) // ps
        dst = next_page
        pool_k[:, dst * ps:(dst + 1) * ps] = pool_k[:, src * ps:(src + 1) * ps]
        pool_v[:, dst * ps:(dst + 1) * ps] = pool_v[:, src * ps:(src + 1) * ps]
        for g in range(min(ps, G)):
            gen_idx[3, g] = dst * ps + g

    tokens = rng.integers(0, cfg.vocab_size, (B, 1)).astype(np.int32)
    dense = dict(
        gen_cache=KVCache(k=jnp.asarray(gen_k), v=jnp.asarray(gen_v)),
        prefix=KVCache(k=jnp.asarray(pref_k), v=jnp.asarray(pref_v)),
    )
    paged = dict(
        pool_kv=KVCache(k=jnp.asarray(pool_k), v=jnp.asarray(pool_v)),
        prefix_idx=jnp.asarray(prefix_idx),
        gen_idx=jnp.asarray(gen_idx),
    )
    return (
        jnp.asarray(tokens),
        jnp.asarray(lengths),
        jnp.asarray(plens_req),
        dense,
        paged,
    )


@pytest.mark.parametrize(
    "window, which", [(None, "all"), (6, "all"), (6, "alternating")],
    ids=["None", "6", "6-alternating"])
@pytest.mark.parametrize("page_size", PAGE_SIZES)
def test_step_xla_bitwise_dense_pallas_greedy(page_size, window, which):
    """The whole step on the coalesced layout ([R, P] shared prefix tables).
    Under the window, 6: it starts inside one request's prompt and has left
    the other's, whose rows then see generated tokens alone. A scanned stack
    that mixes windowed and global layers ("alternating") has one kernel call
    and so one window for every layer: asked for the kernel, it stays on the
    XLA masks, bit for bit."""
    config = CONFIG.with_(sliding_window=window, sliding_window_layers=which)
    params = _params()
    tokens, lengths, plens, dense, paged = _step_case(page_size)

    logits_d, cache_d = verify_step(
        config, params, tokens, lengths, plens,
        dense["gen_cache"], dense["prefix"],
    )
    logits_x, k_cols, v_cols = paged_verify_step(
        config, params, tokens, lengths, plens,
        paged["pool_kv"], paged["prefix_idx"], paged["gen_idx"],
        attn_impl="xla", page_size=page_size,
    )
    # The XLA impl IS the dense math over gathered pages: bitwise.
    np.testing.assert_array_equal(np.asarray(logits_x), np.asarray(logits_d))
    # The returned fresh columns must equal what dense wrote into its cache.
    wi = np.asarray(lengths)
    for b in range(tokens.shape[0]):
        np.testing.assert_array_equal(
            np.asarray(k_cols[:, b]), np.asarray(cache_d.k[:, b, wi[b]])
        )
        np.testing.assert_array_equal(
            np.asarray(v_cols[:, b]), np.asarray(cache_d.v[:, b, wi[b]])
        )

    logits_p, _, _ = paged_verify_step(
        config, params, tokens, lengths, plens,
        paged["pool_kv"], paged["prefix_idx"], paged["gen_idx"],
        attn_impl="pallas_interpret", page_size=page_size,
    )
    if config.mixes_windowed_layers:
        np.testing.assert_array_equal(np.asarray(logits_p), np.asarray(logits_x))
    # Online softmax reorders float accumulation: a tight numeric band is the
    # kernel's bar. In f32 at this size it lies far inside every top-two gap,
    # so the greedy tokens are equal as well.
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(logits_p, -1)), np.asarray(jnp.argmax(logits_d, -1))
    )
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(logits_d), rtol=3e-5, atol=3e-5
    )


def test_step_cow_forked_table_is_invisible():
    """A gen page forked CoW-style (same bytes, different physical page) must
    leave both impls' outputs unchanged: bitwise for xla vs dense, bitwise
    for pallas forked-vs-shared (identical shapes and op order)."""
    ps = 8
    params = _params()
    tokens, lengths, plens, dense, shared = _step_case(ps)
    _, _, _, _, forked = _step_case(ps, fork_gen_page=True)

    logits_d, _ = verify_step(
        CONFIG, params, tokens, lengths, plens,
        dense["gen_cache"], dense["prefix"],
    )
    logits_f, _, _ = paged_verify_step(
        CONFIG, params, tokens, lengths, plens,
        forked["pool_kv"], forked["prefix_idx"], forked["gen_idx"],
        attn_impl="xla", page_size=ps,
    )
    np.testing.assert_array_equal(np.asarray(logits_f), np.asarray(logits_d))

    outs = []
    for world in (shared, forked):
        logits_p, _, _ = paged_verify_step(
            CONFIG, params, tokens, lengths, plens,
            world["pool_kv"], world["prefix_idx"], world["gen_idx"],
            attn_impl="pallas_interpret", page_size=ps,
        )
        outs.append(np.asarray(logits_p))
    np.testing.assert_array_equal(outs[0], outs[1])


def _equations(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (scan and kernel bodies) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_interpret"])
def test_step_never_slices_one_layers_pool(attn_impl):
    """The pool is addressed by (layer, slot) where it is read: no equation of
    the step, the layer scan's body included, may produce one layer's pool
    (``[flat, KVH, D]`` or ``[1, flat, KVH, D]``). On the chip such a slice
    ahead of the attention op is a copy of the layer's whole pool, every
    layer, every decode step."""
    ps = 8
    params = _params()
    tokens, lengths, plens, _, paged = _step_case(ps)
    layer_pool = paged["pool_kv"].k.shape[1:]

    def step(pool_kv):
        return paged_verify_step(
            CONFIG, params, tokens, lengths, plens,
            pool_kv, paged["prefix_idx"], paged["gen_idx"],
            attn_impl=attn_impl, page_size=ps,
        )

    closed = jax.make_jaxpr(step)(paged["pool_kv"])
    equations = list(_equations(closed.jaxpr))
    assert any(eqn.primitive.name == "scan" for eqn in equations)
    slices = [
        f"{eqn.primitive.name} -> {var.aval.str_short()}"
        for eqn in equations
        for var in eqn.outvars
        if getattr(var.aval, "shape", None) in (layer_pool, (1, *layer_pool))
    ]
    assert not slices, slices


# ---------------------------------------------------------------------------
# selection, counters, and the ops.paged_attn failpoint
# ---------------------------------------------------------------------------


def _snap():
    return dict(KERNEL_EVENTS.snapshot())


def _delta(before, after, key):
    return after.get(key, 0) - before.get(key, 0)


def test_resolve_cpu_posture_counts_only_unsatisfied_pallas():
    assert jax.default_backend() != "tpu"
    before = _snap()
    assert resolve_paged_attention_impl("auto") == "xla"
    assert resolve_paged_attention_impl("xla") == "xla"
    mid = _snap()
    # "auto" -> xla off-TPU is the documented CPU posture, NOT a fallback.
    assert all(_delta(before, mid, k) == 0 for k in mid if "fallback" in k)
    # An explicit "pallas" that cannot run is a COUNTED degradation, keyed
    # by reason: off-TPU with a supported config, the reason is the platform.
    assert resolve_paged_attention_impl("pallas") == "xla"
    assert _delta(mid, _snap(), "kernel.paged_attn_fallback.platform") == 1
    with pytest.raises(ValueError):
        resolve_paged_attention_impl("flash")
    assert set(PAGED_ATTENTION_IMPLS) == {"auto", "pallas", "xla"}


def test_resolve_names_the_unsupported_feature_in_the_fallback_key():
    """Config-driven fallbacks are distinguishable from platform ones on
    /metrics: softcap models and scanned stacks that window some layers and
    not others record their own reason suffix, and the config reason wins over
    the platform reason. A window on every layer is the kernel's to serve, and
    so is a mix of windowed and global layers in a stack whose layers are
    unrolled (``command-a-plus``: each layer's call takes its own window): on
    a CPU an explicit "pallas" for such a model lacks the platform alone."""
    import dataclasses

    before = _snap()
    softcap = dataclasses.replace(CONFIG, attn_softcap=30.0)
    assert resolve_paged_attention_impl("pallas", config=softcap) == "xla"
    mixed = dataclasses.replace(
        CONFIG, sliding_window=128, sliding_window_layers="alternating"
    )
    assert mixed.layer_windows == (128, None)
    assert resolve_paged_attention_impl("pallas", config=mixed) == "xla"
    after = _snap()
    assert _delta(before, after, "kernel.paged_attn_fallback.softcap") == 1
    assert _delta(before, after, "kernel.paged_attn_fallback.sliding_window") == 1
    assert _delta(before, after, "kernel.paged_attn_fallback.platform") == 0

    sliding = dataclasses.replace(CONFIG, sliding_window=128)
    assert sliding.sliding_window_layers == "all" and not sliding.mixes_windowed_layers
    unrolled = get_config("command-a-plus-tiny")
    assert unrolled.mixes_windowed_layers and unrolled.attn_softcap is None
    for config in (sliding, unrolled):
        assert resolve_paged_attention_impl("pallas", config=config) == "xla"
    windowed = _snap()
    assert _delta(after, windowed, "kernel.paged_attn_fallback.sliding_window") == 0
    assert _delta(after, windowed, "kernel.paged_attn_fallback.platform") == 2


@pytest.mark.parametrize(
    "overrides,impl",
    [
        (dict(), "pallas"),
        (dict(sliding_window=4096), "pallas"),
        (dict(sliding_window=4096, sliding_window_layers="alternating"), "xla"),
        (dict(attn_softcap=30.0), "xla"),
        (dict(attn_softcap=30.0, sliding_window=4096, sliding_window_layers="alternating"), "xla"),
        ("command-a-plus-tiny", "pallas"),
    ],
)
def test_resolve_on_a_tpu_takes_the_kernel_for_a_window_on_every_layer(
    overrides, impl, monkeypatch
):
    """What "auto" picks where the platform is a TPU: the kernel for a model
    without a window, for one whose every layer has it (``mistral-7b``) and
    for windowed and global layers mixed in an unrolled stack; the counted XLA
    fallback for the scanned stack's mix and for softcap, which blocks first."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = _snap()
    config = get_config(overrides) if isinstance(overrides, str) else CONFIG.with_(**overrides)
    assert resolve_paged_attention_impl("auto", config=config) == impl
    fallbacks = {k: v for k, v in _snap().items() if "fallback" in k and v != before.get(k, 0)}
    if impl == "pallas":
        assert not fallbacks
    else:
        reason = "softcap" if "attn_softcap" in overrides else "sliding_window"
        assert list(fallbacks) == [f"kernel.paged_attn_fallback.{reason}"]


def test_ops_paged_attn_failpoint_forces_counted_fallback():
    """ops.paged_attn=fallback:2 — the registry drill: the next two launch
    resolutions take the counted XLA fallback regardless of the request, then
    the spec exhausts and resolution reverts to the normal posture."""
    before = _snap()
    with fp.failpoints({"ops.paged_attn": FailSpec(action="fallback", times=2)}):
        assert resolve_paged_attention_impl("auto") == "xla"  # fired (1)
        assert resolve_paged_attention_impl("auto") == "xla"  # fired (2)
        assert resolve_paged_attention_impl("auto") == "xla"  # exhausted
    after = _snap()
    assert _delta(before, after, "kernel.paged_attn_fallback.failpoint") == 2


def test_ops_paged_attn_env_syntax_parses():
    fp.configure_from_env("ops.paged_attn=fallback:1")
    try:
        before = _snap()
        assert resolve_paged_attention_impl("auto") == "xla"
        assert _delta(before, _snap(), "kernel.paged_attn_fallback.failpoint") == 1
    finally:
        fp.clear()


def test_dispatch_counters_and_metrics_group():
    before = _snap()
    note_paged_attn_dispatch("pallas")
    note_paged_attn_dispatch("pallas_interpret")  # counts as the kernel path
    note_paged_attn_dispatch("xla", 3)
    after = _snap()
    assert _delta(before, after, "kernel.paged_attn_pallas_dispatch") == 2
    assert _delta(before, after, "kernel.paged_attn_xla_dispatch") == 3

    # The group is wired into /metrics exporting (kllms_kernel_events_total).
    from k_llms_tpu.serving.app import _COUNTER_GROUPS

    assert ("kernel", "KERNEL_EVENTS") in _COUNTER_GROUPS
