"""The continuous loop's page protocol (engine/paging.py::SlotPages) without a
model or a loop: admission fan-out and roll-back, copy-on-write, page
boundaries, the chunked route, and the one reserve formula, against a bare
``PageAllocator`` (and once against a real ``PagedKVPool`` for the copy)."""

import numpy as np
import pytest

from k_llms_tpu.engine.paging import (
    TRASH_PAGE,
    PageAccountingError,
    PageAllocator,
    PagePoolExhausted,
    SlotPages,
    expand_tables,
    flat_slots,
    pages_for,
    row_reserve_pages,
    table_width,
)
from k_llms_tpu.ops.paged_attention import (
    live_pages,
    paged_attention_page_tables,
    table_pages,
)

W, P, G = 4, 64, 32


class _Pool:
    """What SlotPages needs of a pool, with the device copy recorded."""

    def __init__(self, total_pages, page_size):
        self.page_size = page_size
        self.allocator = PageAllocator(total_pages, page_size)
        self.copies = []

    def copy_pages(self, src, dst):
        self.copies.append((list(src), list(dst)))


def _books(ps=8, total=64, width=W):
    pool = _Pool(total, ps)
    books = SlotPages(ps, width, P, G)
    books.attach(pool)
    return books, pool, pool.allocator


def _admit(books, alloc, rows, plen, max_new, keep_owner=False):
    """Whole-prompt admission as the loop does it: a prefill's run, a
    reference a row, then the scratch owner lets go (or a cache keeps it)."""
    run = alloc.alloc(pages_for(plen, books.page_size))
    try:
        books.admit(rows, run, plen, max_new, alloc.alloc)
    finally:
        if not keep_owner:
            alloc.decref(run)
    return run


def _lens(rows, plen, gen):
    active = np.zeros((W,), bool)
    active[list(rows)] = True
    return active, np.full((W,), plen, np.int32), np.full((W,), gen, np.int32)


@pytest.mark.parametrize("n", [1, 3])
def test_admit_then_release_returns_every_page(n):
    books, _, alloc = _books()
    free0 = alloc.free_pages
    rows = list(range(n))
    _admit(books, alloc, rows, plen=21, max_new=12)
    assert books.held() == n * (pages_for(21, 8) + row_reserve_pages(21, 12, 8))
    for step in range(12):
        books.prepare_step(*_lens(rows, 21, step))
    for slot in rows:
        books.release(slot)
    assert books.held() == 0
    assert alloc.free_pages == free0
    alloc.verify()
    assert (books.prefix_idx // 8 == TRASH_PAGE).all()
    assert (books.gen_idx // 8 == TRASH_PAGE).all()


@pytest.mark.parametrize("route", ["admit", "reserve_chunked"])
def test_exhaustion_part_way_rolls_back_every_reference(route):
    plen, max_new, n = 21, 12, 3
    # Enough for the run and two of the three reserves: the third one fails.
    total = SlotPages(8, W, P, G).need(plen, n, max_new) - 1 + 1  # + the trash page
    books, _, alloc = _books(total=total)
    if route == "admit":
        run = alloc.alloc(pages_for(plen, 8))
        free0, refs0 = alloc.free_pages, alloc._ref.copy()
        with pytest.raises(PagePoolExhausted):
            books.admit(list(range(n)), run, plen, max_new, alloc.alloc)
    else:
        free0, refs0 = alloc.free_pages, alloc._ref.copy()
        with pytest.raises(PagePoolExhausted):
            books.reserve_chunked(n, plen, max_new, alloc.alloc)
    assert alloc.free_pages == free0
    assert (alloc._ref == refs0).all()
    assert books.held() == 0
    alloc.verify()


@pytest.mark.parametrize("plen", [16, 21])
def test_cow_fires_at_the_first_divergent_write_iff_the_prompt_ends_mid_page(plen):
    ps, rows = 8, [0, 1]
    books, pool, alloc = _books(ps)
    run = _admit(books, alloc, rows, plen, max_new=12, keep_owner=True)
    reserve0 = [len(books._reserved[s]) for s in rows]
    write_idx, tables = books.prepare_step(*_lens(rows, plen, 0))
    write_idx = write_idx[:, 0]  # a one-token loop's rows write one slot each
    own = [books._tables[s][plen // ps] for s in rows]
    for slot in range(W):  # what the step is handed: every row's table, trash past its end
        held = books._tables[slot]
        assert tables[slot].tolist() == held + [TRASH_PAGE] * (tables.shape[1] - len(held))
    if plen % ps:
        # One padded copy program: each row's private page from the shared one.
        ((src, dst),) = pool.copies
        assert src == [run[-1]] * 2 + [TRASH_PAGE] * (W - 2)
        assert dst == own + [TRASH_PAGE] * (W - 2)
        assert alloc.snapshot()["cow_copies"] == 2
        # The source keeps its other reader (the cache entry), nothing else.
        assert alloc.refcount(run[-1]) == 1
    else:
        assert pool.copies == []
        assert alloc.snapshot()["cow_copies"] == 0
        assert [len(books._tables[s]) for s in rows] == [len(run) + 1] * 2
    assert len(set(own)) == 2 and not set(own) & set(run)
    assert all(alloc.refcount(p) == 1 for p in own)
    assert [len(books._reserved[s]) for s in rows] == [r - 1 for r in reserve0]
    assert write_idx[:2].tolist() == [p * ps + plen % ps for p in own]
    assert (write_idx[2:] // ps == TRASH_PAGE).all()
    # Full prompt pages stay shared for the rows' lifetime.
    shared = run[: plen // ps]
    assert all(alloc.refcount(p) == 3 for p in shared)
    # The second write lands in the page the row now owns: no more copies.
    books.prepare_step(*_lens(rows, plen, 1))
    assert len(pool.copies) == (1 if plen % ps else 0)
    alloc.verify()


def test_a_page_boundary_pops_exactly_one_reserved_page():
    ps, plen, max_new = 8, 16, 20
    books, pool, alloc = _books(ps)
    _admit(books, alloc, [2], plen, max_new)
    sizes = []
    for step in range(max_new):
        write_idx, _ = books.prepare_step(*_lens([2], plen, step))
        assert write_idx.shape == (W, 1)
        sizes.append((len(books._tables[2]), len(books._reserved[2])))
        # The step reads its own write back through the gen map next step.
        assert write_idx[2, 0] == books.gen_idx[2, step]
    grew = [i for i in range(1, max_new) if sizes[i] != sizes[i - 1]]
    assert grew == [8, 16]  # positions 24 and 32
    assert all(t + r == sizes[0][0] + sizes[0][1] for t, r in sizes)
    assert pool.copies == []


def test_the_chunked_route_leaves_the_tables_whole_prompt_admission_leaves():
    ps, plen, max_new, rows, C = 8, 45, 12, [1, 3], 32
    whole, _, alloc_w = _books(ps)
    _admit(whole, alloc_w, rows, plen, max_new)
    chunked, _, alloc_c = _books(ps)
    run, reserved = chunked.reserve_chunked(len(rows), plen, max_new, alloc_c.alloc)
    landed = []
    for start in range(0, plen, C):
        valid = min(C, plen - start)
        slots = chunked.chunk_slots(run, start, C, valid)
        assert (slots[valid:] // ps == TRASH_PAGE).all()
        landed += slots[:valid].tolist()
    assert landed == flat_slots(run, np.arange(plen), ps).tolist()
    chunked.install(rows, run, reserved, plen)
    assert chunked._tables == whole._tables
    assert chunked._reserved == whole._reserved
    assert (chunked.prefix_idx == whole.prefix_idx).all()
    assert (chunked.gen_idx == whole.gen_idx).all()
    assert (alloc_c._ref == alloc_w._ref).all()
    assert alloc_c.free_pages == alloc_w.free_pages


def test_drop_gives_back_a_chunked_reservation_and_contains_a_corrupt_allocator():
    books, _, alloc = _books()
    free0 = alloc.free_pages
    run, reserved = books.reserve_chunked(3, 21, 12, alloc.alloc)
    entry = books.prefix_run(run, 21, 32)  # a cache entry's own reference
    assert all(alloc.refcount(p) == 4 for p in run)
    books.drop(3, run, reserved)
    assert all(alloc.refcount(p) == 1 for p in run)
    assert entry.release() == len(run)
    assert alloc.free_pages == free0
    alloc.verify()
    # A double drop is a double free: contained, not raised.
    run, reserved = books.reserve_chunked(1, 5, 4, alloc.alloc)
    books.drop(1, run, reserved)
    books.drop(1, run, reserved)
    with pytest.raises(PageAccountingError):
        alloc.decref(run)


@pytest.mark.parametrize("ps", [4, 16])
@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("plen,max_new", [(1, 1), (16, 16), (17, 32), (63, 5), (64, 32)])
def test_need_is_what_admission_takes_and_what_decoding_uses(plen, max_new, n, ps):
    books, _, alloc = _books(ps, total=256)
    rows = list(range(n))
    free0 = alloc.free_pages
    _admit(books, alloc, rows, plen, max_new)
    assert free0 - alloc.free_pages == books.need(plen, n, max_new)
    assert books.fits(plen, n, max_new)
    # Every write a row can make finds its page in the reserve...
    for step in range(max_new):
        books.prepare_step(*_lens(rows, plen, step))
    # ...and a row that shared nothing to copy has at most the CoW page left.
    assert all(len(books._reserved[s]) <= 1 for s in rows)
    for slot in rows:
        books.release(slot)
    assert alloc.free_pages == free0
    alloc.verify()


def test_the_default_pool_fits_the_widest_request_and_nothing_wider():
    books = SlotPages(16, W, P, G)
    assert books.planned_pages == books.default_pool_pages()
    assert books.fits(P, W, G)
    assert not SlotPages(16, W, P, G, pool_pages=books.need(P, W, G)).fits(P, W, G)
    assert SlotPages(16, W, P, G, pool_pages=books.need(P, W, G) + 1).fits(P, W, G)


#: A stack's windows by paging layer: uniform ones of 1, 3 and 32 layers (one
#: layer's numbers times the depth), then mixes (each layer under its own).
STACKS = [(None,), (64,), (16,), (4,), (16,) * 3, (None,) * 32, (4,) * 32,
          (16, 16, 16, None), (4, None), (4, 16, None, 4, 16, None)]


@pytest.mark.parametrize("windows", STACKS, ids=lambda w: "-".join(map(str, w)) if len(w) < 8
                         else f"{len(w)}x{w[0]}")
def test_walk_counts_are_layer_pages_of_the_same_lengths(windows):
    ps = 8
    books, _, alloc = _books(ps)
    _admit(books, alloc, [0, 1], 21, 12)
    _admit(books, alloc, [3], 40, 12)
    active = np.array([True, True, False, True])
    plens = np.array([21, 21, 33, 40], np.int32)  # slot 2: a retired tenant's
    glens = np.array([5, 0, 7, 11], np.int32)
    for g in range(int(glens.max()) + 1):  # the rows' writes so far, in order
        books.prepare_step(active, plens, np.minimum(g, glens))
    walked, tabled, windowed_out = books.walk_counts(active, plens, glens, windows=windows)
    phase = np.array([21 % ps, 21 % ps, 0, 40 % ps])
    held = (3 + 2) + (3 + 0) + 0 + (5 + 2)  # pages with a position in the pool
    # Queries at 26, 21 and 51: W = 16 sees from 11, 6 and 36; W = 4 from 23
    # (past the prompt's 21: all 3 prompt pages out; gen 2 is on gen page 0),
    # 18 (prompt page 2) and 48 (past the prompt's 40: 5 pages; gen 8 with
    # 40 % 8 = 0 is on gen page 1).
    out_of = {None: 0, 64: 0, 16: 1 + 0 + 4, 4: 3 + 2 + (5 + 1)}
    want_out = 0
    for window in windows:  # a layer at a time, each the kernel's own arithmetic
        (p0, n_prefix), (g0, n_gen) = live_pages(
            np.where(active, plens, 0), np.where(active, glens, 0), phase, ps, window
        )
        assert int(n_prefix.sum() + n_gen.sum()) == held
        assert int(np.sum(p0) + np.sum(g0)) == out_of[window]
        want_out += out_of[window]
    assert windowed_out == want_out
    assert walked == len(windows) * held - windowed_out
    assert tabled == len(windows) * W * sum(table_pages(P, G, ps))
    # A uniform stack's three numbers are one layer's, L-fold: every share reads the same.
    if len(set(windows)) == 1:
        one = books.walk_counts(active, plens, glens, windows=windows[:1])
        assert (walked, tabled, windowed_out) == tuple(len(windows) * x for x in one)


def test_cow_copies_the_shared_page_on_a_real_pool():
    import jax.numpy as jnp

    from k_llms_tpu.engine.paging import PagedKVPool
    from k_llms_tpu.models import get_config

    ps, plen = 8, 13
    pool = PagedKVPool(get_config("tiny"), 16, ps)
    books = SlotPages(ps, W, P, G)
    books.attach(pool)
    alloc = pool.allocator
    run = alloc.alloc(pages_for(plen, ps))
    marks = jnp.arange(plen, dtype=pool.kv.k.dtype)
    shape = (pool.kv.k.shape[0], plen) + pool.kv.k.shape[2:]
    cols = jnp.broadcast_to(marks[None, :, None, None], shape)
    pool.scatter_tokens(cols, cols, flat_slots(run, np.arange(plen), ps))
    books.admit([0, 1], run, plen, 4, alloc.alloc)
    books.prepare_step(*_lens([0, 1], plen, 0))
    for slot in (0, 1):
        assert books._tables[slot][-1] != run[-1]
        got = np.asarray(pool.kv.k[0, books.prefix_idx[slot, :plen], 0, 0])
        assert got.tolist() == list(range(plen))


# -- a step that writes P .. P+2 (the drafted loop: lookahead 2) -------------------------

def _ahead_books(ps=8, total=64):
    pool = _Pool(total, ps)
    books = SlotPages(ps, W, P, G, lookahead=2)
    books.attach(pool)
    return books, pool, pool.allocator


def test_lookahead_widens_the_gen_map_and_the_reserve():
    books, _, alloc = _ahead_books()
    assert books.gen_idx.shape == (W, G + 2)
    # A prompt that ends two positions before a page edge: the last step's
    # look-ahead crosses into one more page than the one-token loop reserves.
    plen, max_new = 14, 10  # positions 14 .. 23 for tokens, 24 and 25 ahead
    assert books.need(plen, 2, max_new) == SlotPages(8, W, P, G).need(plen, 2, max_new) + 2
    _admit(books, alloc, [0, 1], plen, max_new)
    gen = 0
    while gen < max_new - 1:  # a row's steps to its end, two tokens at a time
        write_idx, _ = books.prepare_step(*_lens([0, 1], plen, gen))
        assert write_idx.shape == (W, 3)
        gen += 2
    for slot in (0, 1):
        books.release(slot)
    alloc.verify()


@pytest.mark.parametrize("plen", [22, 23, 24])
def test_a_step_writing_three_positions_grows_at_a_page_edge(plen):
    """P, P+1 and P+2 land on whichever pages they fall in: the table grows
    before the write that crosses the edge (page size 8: 22, 23 cross inside
    the step, 24 starts on a fresh page)."""
    books, pool, alloc = _ahead_books()
    _admit(books, alloc, [0], plen, 12)
    table0 = len(books._tables[0])
    write_idx, _ = books.prepare_step(*_lens([0], plen, 0))
    want = flat_slots(books._tables[0], plen + np.arange(3), 8)
    np.testing.assert_array_equal(write_idx[0], want)
    np.testing.assert_array_equal(books.gen_idx[0, :3], want)
    assert len(books._tables[0]) == pages_for(plen + 3, 8) >= table0
    assert len(set(write_idx[0] // 8)) == (2 if plen in (22, 23) else 1)
    assert (write_idx[1:] // 8 == TRASH_PAGE).all()  # idle rows write into the trash page
    # One row alone: its prompt pages are its own, so nothing is copied.
    assert pool.copies == []
    books.release(0)
    alloc.verify()


@pytest.mark.parametrize("plen", [17, 22, 23])
def test_a_shared_prompt_page_under_any_of_the_three_is_copied_once_a_row(plen):
    books, pool, alloc = _ahead_books()
    run = _admit(books, alloc, [0, 1, 2], plen, 12, keep_owner=True)
    shared = run[-1]
    assert alloc.refcount(shared) == 4
    write_idx, _ = books.prepare_step(*_lens([0, 1, 2], plen, 0))
    (src, dst), = pool.copies  # one padded batch
    real = [(s, d) for s, d in zip(src, dst) if s != TRASH_PAGE]
    assert [s for s, _ in real] == [shared] * 3 and len({d for _, d in real}) == 3
    assert alloc.refcount(shared) == 1  # the owner's: every row now writes its own page
    pages = {int(p) for p in (write_idx[:3] // 8).reshape(-1)}
    assert shared not in pages and TRASH_PAGE not in pages
    for slot in range(3):  # no slot of one row is another row's
        assert not set(write_idx[slot]) & {int(s) for r in range(3) if r != slot for s in write_idx[r]}
    books.prepare_step(*_lens([0, 1, 2], plen, 2))  # nothing shared is left to copy
    assert len(pool.copies) == 1
    for slot in range(3):
        books.release(slot)
    alloc.decref(run)
    alloc.verify()


def test_a_failed_reserve_with_lookahead_rolls_back():
    plen, max_new, n = 14, 10, 3
    total = SlotPages(8, W, P, G, lookahead=2).need(plen, n, max_new)  # one short, with the trash page
    books, _, alloc = _ahead_books(total=total)
    run = alloc.alloc(pages_for(plen, 8))
    free0, refs0 = alloc.free_pages, alloc._ref.copy()
    with pytest.raises(PagePoolExhausted):
        books.admit(list(range(n)), run, plen, max_new, alloc.alloc)
    assert alloc.free_pages == free0 and (alloc._ref == refs0).all() and books.held() == 0
    # The one-token loop's books would have fitted the same request in the same pool.
    plain = SlotPages(8, W, P, G)
    plain.attach(_Pool(total, 8))
    assert plain.need(plen, n, max_new) < total


# -- the tables a step is handed, spread on the device ------------------------------------

def _spread(books, plens):
    """``expand_tables`` of the books' own array, jitted as a step program holds it."""
    import jax

    G_ = books.gen_idx.shape[1]
    fn = jax.jit(lambda t, p: expand_tables(t, p, books.page_size, books.max_prompt, G_))
    return [np.asarray(a) for a in fn(books.tables, np.asarray(plens, np.int32))]


def _assert_spread_is_the_mirrors(books, plens):
    prefix_idx, gen_idx = _spread(books, plens)
    assert prefix_idx.dtype == gen_idx.dtype == np.int32
    np.testing.assert_array_equal(prefix_idx, books.prefix_idx)
    np.testing.assert_array_equal(gen_idx, books.gen_idx)


@pytest.mark.parametrize("ps", [4, 8, 16])
@pytest.mark.parametrize("lookahead", [0, 2])
def test_the_spread_of_random_tables_is_refresh_element_for_element(ps, lookahead):
    """Every row's ``(table, plen)`` through ``_refresh`` on the host and
    through ``expand_tables`` in a program: an empty table, ``plen`` 0 over a
    table, a table shorter than the row's width, a ``plen`` mid-page and on a
    page edge, a full prompt, a table as wide as a row can make it."""
    rng = np.random.RandomState(ps + lookahead)
    width = 8
    books = SlotPages(ps, width, P, G, lookahead=lookahead)
    T = table_width(P, G + lookahead, ps)
    assert books.tables.shape == (width, T)
    plens = [0, 0, 5, ps + 3, 2 * ps, P, P - 1, 17]
    held = [0, 3, 1, 4, 2, pages_for(P, ps), T, T]
    for slot in range(width):
        books._tables[slot] = [int(p) for p in rng.randint(1, 200, size=held[slot])]
        books._refresh(slot, plens[slot])
        # _refresh itself is flat_slots of the table, prompt and generated side.
        want = flat_slots(books._tables[slot], plens[slot] + np.arange(G + lookahead), ps)
        np.testing.assert_array_equal(books.gen_idx[slot], want)
        want = flat_slots(books._tables[slot], np.arange(plens[slot]), ps)
        np.testing.assert_array_equal(books.prefix_idx[slot, :plens[slot]], want)
        assert (books.prefix_idx[slot, plens[slot]:] // ps == TRASH_PAGE).all()
    _assert_spread_is_the_mirrors(books, plens)


@pytest.mark.parametrize("lookahead", [0, 2])
def test_the_spread_follows_growth_copy_on_write_and_release(lookahead):
    """The books a loop keeps, step by step: after admission, after each
    step's growth and copies, after a release (an idle row's lengths are a
    retired tenant's on the host: the program is handed 0 for it)."""
    ps = 8
    pool = _Pool(96, ps)
    books = SlotPages(ps, W, P, G, lookahead=lookahead)
    books.attach(pool)
    alloc = pool.allocator
    _admit(books, alloc, [0, 1], 21, 12, keep_owner=True)  # shared, ends mid-page
    _admit(books, alloc, [3], 40, 20)  # alone, ends on a page edge
    active = np.array([True, True, False, True])
    plens = np.array([21, 21, 33, 40], np.int32)  # slot 2: a retired tenant's
    for gen in range(0, 12, 1 + lookahead // 2):
        glens = np.minimum(gen, np.array([11, 11, 0, 19], np.int32))
        write_idx, tables = books.prepare_step(active, plens, glens)
        assert tables is books.tables
        _assert_spread_is_the_mirrors(books, np.where(active, plens, 0))
        _, gen_idx = _spread(books, np.where(active, plens, 0))
        for slot in np.flatnonzero(active):  # the write slots are the gen map's
            np.testing.assert_array_equal(
                write_idx[slot], gen_idx[slot, glens[slot]:glens[slot] + 1 + lookahead])
    assert len(pool.copies) == 1 and alloc.snapshot()["cow_copies"] == 2
    books.release(1)
    active[1] = False
    assert books.tables[1].tolist() == [TRASH_PAGE] * books.tables.shape[1]
    _assert_spread_is_the_mirrors(books, np.where(active, plens, 0))
    books.reset()
    _assert_spread_is_the_mirrors(books, np.zeros((W,), np.int32))


@pytest.mark.parametrize("ps", [8, 16])
def test_the_kernels_page_tables_of_the_spread_are_the_tables_pages(ps):
    """``paged_attention_page_tables`` turns the spread straight back: the
    prompt's pages, the generated side's pages from the page ``plen`` lies
    in, and the phase ``plen % page_size``."""
    import jax.numpy as jnp

    rng = np.random.RandomState(ps)
    books = SlotPages(ps, W, P, G)
    plens = [ps + 3, 2 * ps, 0, P - 1]
    for slot, plen in enumerate(plens):
        books._tables[slot] = [int(p) for p in rng.randint(1, 200, size=pages_for(plen + G, ps))]
        books._refresh(slot, plen)
    prefix_idx, gen_idx = _spread(books, plens)
    prefix_pages, gen_pages, phase = (np.asarray(a) for a in paged_attention_page_tables(
        jnp.asarray(prefix_idx), jnp.asarray(gen_idx), ps))
    NP, NG = table_pages(P, G, ps)
    assert prefix_pages.shape == (W, NP) and gen_pages.shape == (W, NG)
    for slot, plen in enumerate(plens):
        table = books._tables[slot]
        assert phase[slot] == plen % ps
        whole = pages_for(plen, ps)  # a page the prompt reaches into is the table's
        assert prefix_pages[slot, :whole].tolist() == table[:whole]
        assert (prefix_pages[slot, whole:] == TRASH_PAGE).all()
        first = plen // ps
        n_gen = pages_for(plen % ps + G, ps)  # pages the generated positions span
        assert gen_pages[slot, :n_gen].tolist() == table[first:first + n_gen]


def test_a_table_too_narrow_for_the_row_is_refused_at_trace_time():
    with pytest.raises(ValueError, match="span"):
        expand_tables(np.zeros((W, 2), np.int32), np.zeros((W,), np.int32), 8, P, G)
