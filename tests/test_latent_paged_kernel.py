"""The Pallas paged kernel for latent pages (``ops/paged_attention.py::
paged_latent_decode_pallas``) against its XLA reference, the gather of whole
block tables and ``mla_attend(absorb=True)`` (``models/latent.py``), in the
Pallas interpreter on the CPU at the ``xing4-tiny`` and ``joyai-tiny`` sizes.

Both sides compute in float32 here, so what separates them is the order of
summation of an online softmax over blocks: 2e-5, the GQA kernel's stated
tolerance over an f32 pool. Pages are small (8 positions) and, where a case
says so, a block is 3 pages, so rows walk several blocks and a block's last
pages lie past a row's end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import shared_params
from k_llms_tpu.models import get_config, latent, llama
from k_llms_tpu.models.llama import KVCache
from k_llms_tpu.ops import paged_attention

TOL = 2e-5
PS, P, G = 8, 32, 12  # page size, prompt slots, generated slots a row
MODELS = ("xing4-tiny", "joyai-tiny")

# (prompt lengths, generated rows in the pool, positions a row, shared prefix
# rows R or None, first prompt position, pages a block or None for the default).
CASES = {
    # Prompts that end mid-page: the generated run starts at phase plen % ps.
    "mid_page": ([13, 24, 29, 7], [3, 0, 9, 1], 1, None, 0, 3),
    # Prompts of whole pages: the generated run starts a page of its own.
    "page_aligned": ([16, 24, 8, 32], [2, 5, 0, 10], 1, None, 0, 3),
    "two_positions": ([13, 24, 29, 7], [3, 0, 8, 1], 2, None, 0, 3),
    # The next-token module's layer: position 0 holds nothing and is masked.
    "module": ([13, 24, 29, 1], [4, 1, 8, 1], 2, None, 1, 3),
    # Retired slots: lengths zeroed, so they walk no page.
    "idle_rows": ([0, 21, 0, 9], [0, 4, 0, 2], 1, None, 0, 3),
    # One block the whole table: its tail past each row's end is not fetched.
    "one_block": ([13, 3, 29, 7], [3, 0, 9, 1], 2, None, 0, None),
    # The engine's own decode loop: a request-level [R, P] prefix table.
    "shared_prefix": ([19, 19, 27, 27], [5, 5, 5, 5], 1, 2, 0, 3),
}


def layout(plens, glens, shared):
    """Flat-slot maps as the engine lays them out, pages drawn at random from
    the pool (page 0 the trash page). Own tables: a row's generated run
    continues its prompt's last page (phase ``plen % PS``). A shared prefix:
    ``[R, P]`` request-level, each row's generated run on fresh pages (phase
    0). Positions past a row's length point into the trash page."""
    rng = np.random.RandomState(len(plens) + sum(plens))
    free = list(rng.permutation(np.arange(1, 48)))
    trash = np.arange(max(P, G)) % PS
    B = len(plens)
    R = shared or B

    def slots(pages, positions):
        return np.asarray([pages[p // PS] * PS + p % PS for p in positions], np.int32)

    prefix_idx = np.tile(trash[:P], (R, 1)).astype(np.int32)
    gen_idx = np.tile(trash[:G], (B, 1)).astype(np.int32)
    for r in range(R):
        b = r * (B // R)
        pages = [free.pop() for _ in range(-(-(plens[b] + (0 if shared else G)) // PS))]
        prefix_idx[r, :plens[b]] = slots(pages, range(plens[b]))
        if not shared:
            gen_idx[b] = slots(pages, range(plens[b], plens[b] + G))
    if shared:
        for b in range(B):
            gen_idx[b] = slots([free.pop() for _ in range(-(-G // PS))], range(G))
    return jnp.asarray(prefix_idx), jnp.asarray(gen_idx)


def latent_pool(config, pad=0.0):
    """A pool of random rows, ``[L, 48 pages, 1, stored width]``, its pad lanes ``pad``."""
    _, width, _ = config.cache_widths
    rows = np.random.RandomState(5).randn(config.paging_layers, 48 * PS, 1, config.pool_row_width)
    rows[..., width:] = pad
    return jnp.asarray(rows, jnp.float32)


def masks(plens, glens, Sq, first):
    """``paged_verify_step``'s (``first`` 0) or ``paged_draft_step``'s (1) masks."""
    plens, glens = jnp.asarray(plens), jnp.asarray(glens)
    j = jnp.arange(Sq)[None, :]
    key_mask = jnp.arange(G)[None, None, :] <= (glens[:, None] + j)[:, :, None]
    c = jnp.arange(P)[None, None, :]
    prefix_mask = (c >= first) & (c < plens[:, None, None]) & jnp.ones((len(plens), Sq, 1), bool)
    return key_mask, prefix_mask


def attend(config, case, pool, impl):
    """One layer's ``latent._attend_paged`` on ``impl``'s path -> (out, rows kept)."""
    plens, glens, Sq, shared, first, _ = CASES[case]
    params = shared_params(config)
    layer = {k: v[0] for k, v in params["layers"].items()}
    prefix_idx, gen_idx = layout(plens, glens, shared)
    key_mask, prefix_mask = masks(plens, glens, Sq, first)
    B = len(plens)
    h = jnp.asarray(np.random.RandomState(6).randn(B, Sq, config.hidden_size), jnp.float32)
    positions = jnp.asarray(plens)[:, None] + jnp.asarray(glens)[:, None] + jnp.arange(Sq)[None]
    plen = jnp.asarray(plens[:: B // (shared or B)])
    kernel = latent._paged_kernel(config, impl, PS, prefix_idx, gen_idx, plen,
                                  jnp.asarray(glens), first_position=first)
    assert (kernel is None) is (impl == "xla")
    layer_no = config.paging_layers - 1 if first else 1
    kept = []
    out = latent._attend_paged(config, layer, h, positions, pool, jnp.int32(layer_no), prefix_idx,
                               gen_idx, jnp.asarray(glens), key_mask, prefix_mask, kept.append,
                               kernel=kernel)
    return np.asarray(out), np.asarray(kept[0])


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("model", MODELS)
def test_kernel_matches_the_gather_and_absorbed_attention(model, case, monkeypatch):
    config = get_config(model)
    block = CASES[case][-1]
    if block is not None:  # K pages a block: the buffer two slots of them hold
        monkeypatch.setattr(paged_attention, "PAGE_BUFFER_BYTES",
                            2 * block * PS * config.pool_row_width * 4)
    pool = latent_pool(config)
    want, want_rows = attend(config, case, pool, "xla")
    got, got_rows = attend(config, case, pool, "pallas_interpret")
    np.testing.assert_array_equal(got_rows, want_rows)
    assert np.abs(want).max() > 0.1  # a live comparison, not of zeros
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("model", MODELS)
def test_the_pools_pad_lanes_change_no_score(model):
    """The kernel scores a page on all its stored lanes; the queries' pad
    lanes are zeros, so whatever the pool's pad lanes hold (zeros by the
    movers' contract) leaves the output bit for bit where it was."""
    config = get_config(model)
    zeros, _ = attend(config, "two_positions", latent_pool(config), "pallas_interpret")
    filled, _ = attend(config, "two_positions", latent_pool(config, pad=1e3), "pallas_interpret")
    np.testing.assert_array_equal(filled, zeros)


def step_inputs(config, R):
    """Four rows of one loop step over a pool whose pages hold random rows."""
    plens, glens = [13, 24, 29, 7], [3, 0, 9, 1]
    if R is not None:
        plens, glens = [19, 19, 27, 27], [5, 5, 5, 5]
    prefix_idx, gen_idx = layout(plens, glens, R)
    pool = KVCache(k=latent_pool(config), v=jnp.zeros((config.paging_layers, 48 * PS, 1, 0)))
    tokens = jnp.asarray(np.random.RandomState(7).randint(0, 250, (4, 2)), jnp.int32)
    plen = jnp.asarray(plens[:: 4 // (R or 4)])
    return tokens, jnp.asarray(glens), plen, pool, prefix_idx, gen_idx


@pytest.mark.parametrize("shared", [None, 2])
def test_xing4_step_takes_the_kernel_with_the_same_logits_and_counts(shared):
    """``paged_verify_step`` on the kernel and on the gather: logits within the
    tolerance, this step's rows and every ``aux`` count equal
    (``mla_latent_rows_read``: the positions attended, from the masks on both
    paths); with a shared ``[R, P]`` prefix table as the engine's decode loop
    hands it."""
    config = get_config("xing4-tiny")
    params = shared_params(config)
    tokens, glens, plen, pool, prefix_idx, gen_idx = step_inputs(config, shared)
    out = {}
    for impl in ("xla", "pallas_interpret"):
        aux = {}
        logits, k_cols, v_cols = llama.paged_verify_step(
            config, params, tokens[:, :1], glens, plen, pool, prefix_idx, gen_idx,
            attn_impl=impl, page_size=PS, aux=aux)
        out[impl] = (np.asarray(logits), np.asarray(k_cols), {k: np.asarray(v) for k, v in aux.items()})
    (want, want_cols, want_aux), (got, got_cols, got_aux) = out["xla"], out["pallas_interpret"]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    # The first layer's rows are this step's inputs alone; the others follow
    # the layers before them.
    np.testing.assert_array_equal(got_cols[0], want_cols[0])
    np.testing.assert_allclose(got_cols, want_cols, atol=TOL, rtol=0)
    assert set(got_aux) == set(want_aux) == {"moe_counts", "mla_latent_rows_read"}
    for key in want_aux:
        np.testing.assert_array_equal(got_aux[key], want_aux[key])
    plens = np.repeat(np.asarray(plen), 4 // len(plen))
    assert int(got_aux["mla_latent_rows_read"]) == int(np.sum(plens + np.asarray(glens) + 1)) * 3


def test_joyai_drafted_step_takes_the_kernel_in_the_stack_and_the_module():
    """The drafted step's two programs at ``Sq == 2``: the stack
    (``paged_verify_step`` with ``return_hidden``) and the module
    (``paged_draft_step``, position 0 of its layer masked) on the kernel and on
    the gather: logits within the tolerance, rows and counts equal."""
    config = get_config("joyai-tiny")
    params = shared_params(config)
    tokens, glens, plen, pool, prefix_idx, gen_idx = step_inputs(config, None)
    out = {}
    for impl in ("xla", "pallas_interpret"):
        aux = {}
        logits, k_cols, _, hidden = llama.paged_verify_step(
            config, params, tokens, glens, plen, pool, prefix_idx, gen_idx,
            attn_impl=impl, page_size=PS, aux=aux, return_hidden=True)
        mlogits, m_cols = llama.paged_draft_step(
            config, params, hidden, tokens, glens + 1, plen, pool, prefix_idx, gen_idx,
            aux=aux, attn_impl=impl, page_size=PS)
        out[impl] = [np.asarray(a) for a in (logits, mlogits, k_cols, m_cols,
                                             aux["mla_latent_rows_read"], aux["moe_counts"])]
    want, got = out["xla"], out["pallas_interpret"]
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=0)
    np.testing.assert_array_equal(got[2][0], want[2][0])
    for a, b in zip(got[2:4], want[2:4]):
        np.testing.assert_allclose(a, b, atol=TOL, rtol=0)
    for a, b in zip(got[4:], want[4:]):
        np.testing.assert_array_equal(a, b)
    # The stack: prompt + pool rows + 1 or 2 fresh ones, 3 layers; the module:
    # the prompt less position 0, its pool rows and fresh ones.
    plens, glens = np.asarray(plen), np.asarray(glens)
    stack = 3 * int(np.sum(2 * (plens + glens) + 3))
    module = int(np.sum(2 * (plens - 1 + glens + 1) + 3))
    assert int(got[4]) == stack + module


def test_drafted_loop_on_the_kernel_emits_the_gathers_tokens(monkeypatch):
    """A small drafting loop whose steps run the interpreted kernel in the
    stack and the module: greedy tokens equal the XLA loop's, and each step
    counts a dispatch of the kernel."""
    from conftest import shared_engine

    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
    from k_llms_tpu.utils.observability import KERNEL_EVENTS

    engine = shared_engine("joyai-tiny", kv_layout="paged", kv_page_size=PS)
    prompt = [int(t) for t in np.random.RandomState(8).randint(0, 250, 21)]  # phase 5
    runs = {}
    for impl in ("xla", "pallas_interpret"):
        monkeypatch.setattr(paged_attention, "resolve_paged_attention_impl",
                            lambda requested, impl=impl, **kw: impl)
        loop = ContinuousDecodeLoop(engine, width=4, max_prompt=64, max_new=16, eos_ids=[257])
        before = KERNEL_EVENTS.get("kernel.paged_attn_pallas_dispatch")
        try:
            result = loop.submit(prompt, n=2, max_new=10, temperature=0.0, top_p=None,
                                 seed=1).result(timeout=300)
            steps = loop.stats["steps"]
        finally:
            loop.stop()
        runs[impl] = (np.asarray(result.tokens),
                      KERNEL_EVENTS.get("kernel.paged_attn_pallas_dispatch") - before, steps)
    np.testing.assert_array_equal(runs["pallas_interpret"][0], runs["xla"][0])
    assert runs["xla"][1] == 0 and runs["pallas_interpret"][1] == runs["pallas_interpret"][2] > 0
