"""Paged KV cache: a fixed pool of fixed-size KV pages with refcounted
sharing and copy-on-write (the vLLM PagedAttention memory model, Kwon et al.
2023, §4), grown onto this engine's shared-prefix serving stack.

Why pages. The consensus workload decodes ``n`` continuations of ONE prompt;
dense per-row KV charges every row the full ``seq_len * kv_bytes_per_token``,
so HBM caps the admitted width long before compute does (ROADMAP open item 2).
With pages, the n rows of a fan-out hold *references* to one physical copy of
the prompt's pages; only the generated tail — tens of tokens against hundreds
— is private per row. Admitted width then scales ~n× on the shared-prefix
portion of the sequence at the same HBM budget.

Layout. The device pool is one flat pair of arrays ``[L, pages * page_size,
kv_heads, head_dim]`` (kv-head axis sharded over the existing tp mesh axis,
like every other KV buffer here). What a page row holds is the model's
(``ModelConfig.cache_widths``): K and V per KV head for the GQA block; for a
latent (MLA) model one ``[c_kv | k_rope]`` row in ``k``
(``[L, flat, 1, kv_lora_rank + qk_rope_head_dim`` rounded up to whole tiles
of 128 lanes``]``) and a ``v`` of width 0, so no V bytes exist. A *block
table* is a host-side list of page
ids per logical row; attention consumes it as flat slot indices
``page_id * page_size + offset`` through a plain gather
(``ops/attention.gather_kv_pages``). Gathered garbage in masked slots is
provably inert: masked scores are set to ``finfo.min`` before the softmax max,
``exp(min - m)`` underflows to exactly 0.0, and ``0 * finite_v == 0`` in the
values einsum — which is what makes the paged path byte-identical to dense
(pinned by tests/test_paged_differential.py).

Sharing discipline. Pages are shared ONLY between rows whose values are
provably bit-identical: (a) the n-way fork of one prefill at admission, and
(b) a prefix-cache entry extending another entry — the continuation prefill
literally copies the matched prefix's values, so the store shares the matched
run's full pages instead of re-materializing them. There is deliberately no
content-addressed dedup across independent prefills: different bucket sizes
compile different XLA programs whose results can differ in the last ulp, and
sharing those would silently break the dense≡paged bit-equality contract.

Copy-on-write. A row that appends its first divergent token into a partially
filled shared page (``prompt_len % page_size != 0``) gets a fresh page with
the shared page's contents copied on device first; full prompt pages stay
shared for the row's whole lifetime. Writers therefore always own their page
exclusively (refcount 1), which is the invariant that keeps cache entries and
sibling rows immutable.

Known sharp edge: the trash page (page 0) absorbs writes from inactive loop
rows and reads from masked slots. Its contents are arbitrary but finite under
healthy operation; a NaN-poisoned launch could park NaNs there, but such a
launch is already a numeric-quarantine event on the dense path too.
"""

from __future__ import annotations

import logging
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.lockcheck import make_rlock, note_device_dispatch
from ..ops.attention import pool_gather, pool_index, pool_layers, pool_scatter
from ..ops.paged_attention import live_pages, table_pages
from ..reliability import failpoints as _failpoints

logger = logging.getLogger(__name__)

#: Page id 0 is the TRASH page: never allocated, never in a block table.
#: Masked gather slots and inactive-row writes point into it, so every flat
#: index the device ever sees is in-bounds without data-dependent control flow.
TRASH_PAGE = 0


class PageAccountingError(RuntimeError):
    """A page-pool invariant was violated (leak, double free, negative
    refcount). Raised by :meth:`PageAllocator.verify` — wired into
    ``ContinuousDecodeLoop.stats`` so serving health checks fail fast instead
    of decoding against a corrupted pool."""


class PagePoolExhausted(RuntimeError):
    """Allocation could not be satisfied even after eviction."""


class PageAllocator:
    """Host-side page accounting: free stack + per-page refcounts.

    Thread-safe (the continuous-loop worker, the scheduler's coalesced path,
    and test threads all touch one pool). All refcount state is host-only —
    the device pool itself carries no metadata.
    """

    def __init__(self, total_pages: int, page_size: int):
        if total_pages < 2:
            raise ValueError("page pool needs >= 2 pages (one is the trash page)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.total_pages = int(total_pages)
        self.page_size = int(page_size)
        self._lock = make_rlock("engine.page_allocator")
        # LIFO free stack: recently freed pages are re-used first (their HBM
        # is warm and their contents are already overwritten by the next
        # owner's scatter before any unmasked read).
        self._free: List[int] = list(range(self.total_pages - 1, 0, -1))
        self._ref = np.zeros(self.total_pages, np.int64)
        self._ref[TRASH_PAGE] = 1  # permanently owned by the pool itself
        self._leaked = 0  # failpoint-injected leaks (engine.pages=leak:N)
        self.stats: Dict[str, int] = {
            "allocs": 0,
            "frees": 0,
            "cow_copies": 0,
            "peak_in_use": 1,
        }

    # -- queries -----------------------------------------------------------

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def in_use_pages(self) -> int:
        """Pages with a live reference (trash page included)."""
        with self._lock:
            return int((self._ref > 0).sum())

    @property
    def shared_pages(self) -> int:
        """Pages referenced by more than one owner (the physical prefix
        sharing the bench reports; trash excluded)."""
        with self._lock:
            shared = int((self._ref > 1).sum())
            return shared - (1 if self._ref[TRASH_PAGE] > 1 else 0)

    def refcount(self, page: int) -> int:
        with self._lock:
            return int(self._ref[page])

    # -- mutation ----------------------------------------------------------

    def alloc(self, count: int) -> List[int]:
        """Allocate ``count`` pages with refcount 1 each. All-or-nothing:
        raises :class:`PagePoolExhausted` without side effects when the free
        stack is short."""
        if count <= 0:
            return []
        with self._lock:
            if len(self._free) < count:
                raise PagePoolExhausted(
                    f"need {count} pages, {len(self._free)} free "
                    f"(pool={self.total_pages}, page_size={self.page_size})"
                )
            pages = [self._free.pop() for _ in range(count)]
            for p in pages:
                self._ref[p] = 1
            self.stats["allocs"] += count
            self.stats["peak_in_use"] = max(
                self.stats["peak_in_use"], self.in_use_pages
            )
            return pages

    def incref(self, pages: Sequence[int]) -> None:
        with self._lock:
            for p in pages:
                if p == TRASH_PAGE or self._ref[p] <= 0:
                    raise PageAccountingError(
                        f"incref on unowned page {p} (ref={int(self._ref[p])})"
                    )
                self._ref[p] += 1

    def decref(self, pages: Sequence[int]) -> List[int]:
        """Drop one reference per page; returns the pages that reached
        refcount 0 and went back on the free stack."""
        freed: List[int] = []
        with self._lock:
            for p in pages:
                if p == TRASH_PAGE or self._ref[p] <= 0:
                    raise PageAccountingError(
                        f"decref on unowned page {p} (ref={int(self._ref[p])})"
                    )
                self._ref[p] -= 1
                if self._ref[p] == 0:
                    self._free.append(p)
                    freed.append(p)
            self.stats["frees"] += len(freed)
        return freed

    def note_cow(self, count: int = 1) -> None:
        with self._lock:
            self.stats["cow_copies"] += count

    def leak(self, count: int) -> None:
        """Failpoint hook (``engine.pages=leak:N``): drop N pages from the
        free stack without accounting for them anywhere, simulating a lost
        decref so :meth:`verify` must trip."""
        with self._lock:
            n = min(count, len(self._free))
            for _ in range(n):
                self._free.pop()
            self._leaked += n

    # -- invariants --------------------------------------------------------

    def verify(self) -> None:
        """Assert the pool's conservation laws; raises
        :class:`PageAccountingError` on any violation:

        - no negative refcounts,
        - free + referenced == total (no page both free and owned, none lost),
        - the trash page is never on the free stack and never table-owned.
        """
        with self._lock:
            if (self._ref < 0).any():
                bad = np.flatnonzero(self._ref < 0).tolist()
                raise PageAccountingError(f"negative refcount on pages {bad}")
            free_set = set(self._free)
            if len(free_set) != len(self._free):
                raise PageAccountingError("duplicate pages on the free stack")
            if TRASH_PAGE in free_set:
                raise PageAccountingError("trash page on the free stack")
            owned = int((self._ref > 0).sum())
            if owned + len(self._free) != self.total_pages:
                raise PageAccountingError(
                    f"page leak: {owned} referenced + {len(self._free)} free "
                    f"!= {self.total_pages} total"
                    + (f" ({self._leaked} failpoint-leaked)" if self._leaked else "")
                )
            for p in free_set:
                if self._ref[p] != 0:
                    raise PageAccountingError(
                        f"page {p} is free but has refcount {int(self._ref[p])}"
                    )

    def check(self) -> Optional[str]:
        """Non-raising :meth:`verify`: the violation message, or None when
        the conservation laws hold. For callers that treat a corrupt pool as
        DATA — the continuous loop's stats quarantine reports the fault and
        flags the worker for rebuild instead of letting an accounting raise
        poison every subsequent health poll."""
        try:
            self.verify()
        except PageAccountingError as e:
            return str(e)
        return None

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "total_pages": self.total_pages,
                "page_size": self.page_size,
                "free": len(self._free),
                "in_use": self.in_use_pages - 1,  # trash excluded
                "shared": self.shared_pages,
                "cow_copies": self.stats["cow_copies"],
                "peak_in_use": self.stats["peak_in_use"] - 1,
                "allocs": self.stats["allocs"],
                "frees": self.stats["frees"],
            }


def pages_for(tokens: int, page_size: int) -> int:
    return -(-int(tokens) // int(page_size)) if tokens > 0 else 0


def row_reserve_pages(prompt_len: int, max_new: int, page_size: int) -> int:
    """Pages one decoding row's writes can touch: gen positions occupy pages
    plen//ps .. (plen+max_new-1)//ps; the first of those is the prompt's
    partial page (CoW target) when plen % ps != 0, fresh otherwise — the +1
    covers both cases."""
    ps = page_size
    return (prompt_len + max_new - 1) // ps - prompt_len // ps + 1


def flat_slots(pages: Sequence[int], positions: np.ndarray, page_size: int) -> np.ndarray:
    """Map logical token positions to flat pool slot indices through a block
    table. Positions past the table map into the trash page (they are masked
    by the consumer; this keeps every index in-bounds)."""
    positions = np.asarray(positions, np.int64)
    offs = positions % page_size
    table = np.asarray(pages, np.int64)
    if len(table) == 0:
        return (np.full_like(positions, TRASH_PAGE) * page_size + offs).astype(np.int32)
    page_i = positions // page_size
    in_range = page_i < len(table)
    page_ids = np.where(in_range, table[np.minimum(page_i, len(table) - 1)], TRASH_PAGE)
    return (page_ids * page_size + offs).astype(np.int32)


def table_width(max_prompt: int, gen_slots: int, page_size: int) -> int:
    """Pages a row's block table can come to hold: its prompt and every
    position its steps write."""
    return pages_for(max_prompt + gen_slots, page_size)


def expand_tables(tables, prompt_lens, page_size: int, max_prompt: int, gen_slots: int):
    """:meth:`SlotPages._refresh` inside a step program: ``(prefix_idx [W,
    max_prompt], gen_idx [W, gen_slots])``, one flat pool slot a position,
    from the rows' block tables ``[W, T]`` (padded with ``TRASH_PAGE``) and
    prompt lengths ``[W]`` (0 for an idle row, whose table is empty), element
    for element what the host mirrors hold. Traceable, and written as
    broadcasts, compares and selects alone (a gather a position, or a window
    a row, is a loop on the chip): the compiler fuses it into the consumers."""
    ps = page_size
    W, T = tables.shape
    if T * ps < max_prompt + gen_slots:
        raise ValueError(f"tables of {T} pages span {T * ps} positions, under {max_prompt + gen_slots}")
    within = jnp.arange(ps, dtype=jnp.int32)
    at = jnp.arange(max_prompt, dtype=jnp.int32)[None, :]
    plen = prompt_lens[:, None]
    # The prompt side: each page id over its page. Positions at or past the
    # prompt's end read through gen_idx instead, and point into the trash page.
    n_prefix = pages_for(max_prompt, ps)
    spanned = (tables[:, :n_prefix, None] * ps + within).reshape(W, n_prefix * ps)[:, :max_prompt]
    prefix_idx = jnp.where(at < plen, spanned, TRASH_PAGE * ps + (at - plen) % ps)
    # The generated side starts inside the page the prompt ends in: the few
    # pages its positions can span, picked out of the table by comparison
    # (past the table's end nothing matches: the trash page) ...
    n_gen = pages_for(ps - 1 + gen_slots, ps)
    wanted = (plen // ps + jnp.arange(n_gen, dtype=jnp.int32)[None, :])[:, :, None]
    own = jnp.arange(T, dtype=jnp.int32)[None, None, :] == wanted  # [W, n_gen, T]
    gen_pages = TRASH_PAGE + jnp.sum(
        jnp.where(own, tables[:, None, :] - TRASH_PAGE, 0), axis=-1, dtype=jnp.int32)
    # ... then each position's page among them, the same way.
    offset = plen % ps + jnp.arange(gen_slots, dtype=jnp.int32)[None, :]  # [W, gen_slots]
    mine = (offset // ps)[:, :, None] == jnp.arange(n_gen, dtype=jnp.int32)[None, None, :]
    page = jnp.sum(jnp.where(mine, gen_pages[:, None, :], 0), axis=-1, dtype=jnp.int32)
    return prefix_idx, page * ps + offset % ps


# ---------------------------------------------------------------------------
# The pool's movers
# ---------------------------------------------------------------------------
# Every program that writes a pool or reads rows out of one for the engine
# does it through these four, so what a pool stores and how it is addressed is
# known here (and, for the model's own gather inside a step, in
# ops/attention.py) and nowhere else. Two forms, chosen from the arrays' own
# shapes at trace time: a pool of one row a token and no V (a latent model's)
# is addressed by (layer, slot) in its flat view, its rows padded to the
# stored width on the way in and cut back on the way out; a pool of K and V
# per KV head, whose rows are whole tiles already, is indexed along the layer
# axis. The first compiles with no copy of the pool for the one-row pool, where
# the second would lay the whole pool out again and back
# (tests/test_tpu_compile.py); the second is what the GQA models' programs
# have always held, and their traces show no pool copy.

def _one_row(pool_k, pool_v) -> bool:
    return pool_k.shape[2] == 1 and pool_v.shape[-1] == 0


def scatter_rows(pool_k, pool_v, slots, k_rows, v_rows):
    """Rows ``[L, n, heads, width]`` written at flat slots ``slots`` [n] of
    the pool's first L cache layers -> (pool_k, pool_v): all of them, but for
    a step that runs a model's stack without its next-token module, whose
    cache layer is the pool's last. Inside a jitted program."""
    if _one_row(pool_k, pool_v):
        return pool_scatter(pool_k, pool_layers(pool_k, slots, k_rows.shape[0]), k_rows), pool_v
    return (pool_k.at[:, slots].set(k_rows.astype(pool_k.dtype)),
            pool_v.at[:, slots].set(v_rows.astype(pool_v.dtype)))


def gather_rows(pool_k, pool_v, slots, k_width: int):
    """-> (k, v) ``[L, 1, n, heads, width]`` at the cache's own widths: the
    dense prefix layout every engine consumer (decode prefix, continuation
    seed) expects."""
    if _one_row(pool_k, pool_v):
        rows = pool_gather(pool_k, pool_layers(pool_k, slots), k_width)  # [L, n, W]
        return rows[:, None, :, None, :], pool_v[:, slots][:, None]
    return pool_k[:, slots][:, None], pool_v[:, slots][:, None]


def copy_rows(pool_k, pool_v, src_slots, dst_slots):
    """Slots ``src_slots`` copied onto ``dst_slots`` in every cache layer
    (copy-on-write pages) -> (pool_k, pool_v)."""
    if _one_row(pool_k, pool_v):
        rows = pool_gather(pool_k, pool_layers(pool_k, src_slots), pool_k.shape[-1])
        return pool_scatter(pool_k, pool_layers(pool_k, dst_slots), rows), pool_v
    return (pool_k.at[:, dst_slots].set(pool_k[:, src_slots]),
            pool_v.at[:, dst_slots].set(pool_v[:, src_slots]))


@jax.named_scope("kv_write")
def write_drafted_rows(pool_k, stack_cols, module_cols, write_idx):
    """A drafted step's (or, with one position, an admission draft's) cache
    rows into the latent pool ``[L + 1, flat, 1, stored width]``, in ONE
    scatter: the stack's L layers write the first ``S`` of ``write_idx``'s
    positions (``stack_cols`` [L, W, S, 1, width]; None: no position), the
    module's layer the last ``S`` (``module_cols`` [W, S, 1, width]).
    ``write_idx`` [W, n]: P, P+1, P+2 for a step (S = 2), L alone for an
    admission (S = 1)."""
    L, S = pool_k.shape[0] - 1, module_cols.shape[1]
    idx = [pool_index(pool_k, L, write_idx[:, write_idx.shape[1] - S:]).reshape(-1)]
    cols = [module_cols.reshape(-1, module_cols.shape[-1])]
    if stack_cols is not None:
        idx.insert(0, pool_layers(pool_k, write_idx[:, :S], L).reshape(-1))
        cols.insert(0, stack_cols.reshape(-1, stack_cols.shape[-1]))
    return pool_scatter(pool_k, jnp.concatenate(idx), jnp.concatenate(cols))


class PagedKVPool:
    """The device-side page pool plus its jitted data movers.

    ``kv.k`` / ``kv.v``: ``[L, total_pages * page_size, heads, width]`` with
    (heads, k width, v width) from ``config.cache_widths`` — K and V per KV
    head, or one latent row and an empty V. A latent row is stored
    ``config.pool_row_width`` lanes wide (576 -> 640, pad lanes zero); the
    public ops take and give rows of the cache's own width (the movers above).
    All device ops that consume-and-replace the pool buffers (scatter, copy)
    dispatch under ``self.lock`` and swap ``self.kv`` atomically, so the
    continuous-loop worker and the scheduler threads never race a donated
    buffer. Gathers return fresh arrays and are safe at any time once they
    hold the lock long enough to read ``self.kv``.
    """

    def __init__(self, config, total_pages: int, page_size: int, dtype=None):
        from ..models.llama import KVCache

        self.config = config
        self.page_size = int(page_size)
        self.allocator = PageAllocator(total_pages, page_size)
        # Held across the jitted scatter/gather/copy dispatch on purpose:
        # self.kv swaps atomically with the donated buffers it replaces.
        self.lock = make_rlock("engine.kv_pool", allow_dispatch=True)
        flat = int(total_pages) * int(page_size)
        heads, k_width, v_width = config.cache_widths
        shape = (config.paging_layers, flat, heads)
        dtype = dtype or config.jax_dtype
        self.kv = KVCache(
            k=jnp.zeros(shape + (config.pool_row_width,), dtype),
            v=jnp.zeros(shape + (v_width,), dtype),
        )

        # The jitted movers; jax.jit keeps a program per argument shape.
        def _scatter(pool_k, pool_v, k_src, v_src, idx):
            return KVCache(*scatter_rows(pool_k, pool_v, idx, k_src, v_src))

        def _gather(pool_k, pool_v, idx):
            return KVCache(*gather_rows(pool_k, pool_v, idx, k_width))

        def _copy(pool_k, pool_v, src_idx, dst_idx):
            return KVCache(*copy_rows(pool_k, pool_v, src_idx, dst_idx))

        self._scatter_fn = jax.jit(_scatter, donate_argnums=(0, 1))
        self._gather_fn = jax.jit(_gather)
        self._copy_fn = jax.jit(_copy, donate_argnums=(0, 1))

    def pool_bytes(self) -> int:
        with self.lock:
            return int(self.kv.k.nbytes) + int(self.kv.v.nbytes)

    # -- public ops --------------------------------------------------------

    def scatter_tokens(self, k_src, v_src, slot_idx: np.ndarray) -> None:
        """Write token KV rows into flat pool slots. k_src/v_src:
        [L, n, KVH, D] (device arrays); slot_idx: host int32 [n]."""
        import jax.numpy as jnp

        idx = jnp.asarray(np.asarray(slot_idx, np.int32))
        with self.lock:
            note_device_dispatch("paged kv scatter")
            self.kv = self._scatter_fn(self.kv.k, self.kv.v, k_src, v_src, idx)

    def compile_scatter(self, k_src, v_src) -> None:
        """Build :meth:`scatter_tokens`' program for sources of these shapes
        (``jax.ShapeDtypeStruct``) ahead of its first call with them."""
        import jax
        import jax.numpy as jnp

        idx = jax.ShapeDtypeStruct((k_src.shape[1],), jnp.int32)
        with self.lock:
            self._scatter_fn.lower(self.kv.k, self.kv.v, k_src, v_src, idx).compile()

    def gather_tokens(self, slot_idx: np.ndarray):
        """Dense [L, 1, n, KVH, D] view of the given flat slots."""
        import jax.numpy as jnp

        idx = jnp.asarray(np.asarray(slot_idx, np.int32))
        with self.lock:
            note_device_dispatch("paged kv gather")
            return self._gather_fn(self.kv.k, self.kv.v, idx)

    def copy_pages(self, src_pages: Sequence[int], dst_pages: Sequence[int]) -> None:
        """Device copy of whole pages (the CoW mover). Pads to a stable width
        with trash->trash no-ops so every step shares one compiled program."""
        import jax.numpy as jnp

        assert len(src_pages) == len(dst_pages)
        if not src_pages:
            return
        ps = self.page_size
        src = np.concatenate(
            [np.arange(p * ps, (p + 1) * ps, dtype=np.int32) for p in src_pages]
        )
        dst = np.concatenate(
            [np.arange(p * ps, (p + 1) * ps, dtype=np.int32) for p in dst_pages]
        )
        with self.lock:
            note_device_dispatch("paged kv page copy")
            self.kv = self._copy_fn(
                self.kv.k, self.kv.v, jnp.asarray(src), jnp.asarray(dst)
            )


class PagedPrefixRun:
    """A prompt prefix stored as a run of pool pages (the paged form of a
    prefix-cache entry's KV). Owns one reference per page; ``release()`` is
    idempotent. ``bucket`` records the dense bucket the prefill produced, so
    materialization reproduces the exact array shape the dense path stores."""

    __slots__ = ("pool", "pages", "plen", "bucket", "_released")

    def __init__(self, pool: PagedKVPool, pages: List[int], plen: int, bucket: int):
        self.pool = pool
        self.pages = list(pages)
        self.plen = int(plen)
        self.bucket = int(bucket)
        self._released = False

    def retain(self) -> None:
        self.pool.allocator.incref(self.pages)

    def release(self) -> int:
        """Drop the run's own reference (one-shot); returns how many pages
        actually hit the free stack — pages still pinned by rows or by a
        younger run sharing this prefix stay allocated."""
        if self._released:
            return 0
        self._released = True
        return len(self.pool.allocator.decref(self.pages))

    def _slots(self, length: int) -> np.ndarray:
        return flat_slots(self.pages, np.arange(length), self.pool.page_size)

    def materialize(self):
        """Dense [L, 1, bucket, KVH, D] KVCache, bit-identical to the dense
        entry at every unmasked position (masked slots gather trash, which the
        consumers' masking provably zeroes)."""
        return self.pool.gather_tokens(self._slots(self.bucket))

    def gather_prefix_padded(self, p: int, out_len: int):
        """Dense [L, 1, out_len] cache seeded with positions [0, p) — the
        paged twin of ``pad(matched_kv.k[:, :, :p])`` on the dense path.
        Positions >= p gather trash; the continuation prefill overwrites or
        masks all of them before any unmasked read."""
        idx = flat_slots(self.pages, np.arange(out_len), self.pool.page_size)
        idx[p:] = (np.arange(out_len - p) % self.pool.page_size).astype(np.int32)
        return self.pool.gather_tokens(idx)


class SlotPages:
    """The page books of one paged decode loop: per slot a block TABLE of pool
    pages and the RESERVE its decode steps draw from. A decode step takes the
    tables themselves (``tables``, a row each, padded with the trash page) and
    spreads them into one flat pool slot a position on the device
    (:func:`expand_tables`); the host keeps the same spread in ``prefix_idx``
    / ``gen_idx`` for admission's own programs and for the walk's phase. The
    protocol (sharing and copy-on-write as in the module docstring):

    - Admission shares ONE page run of the prompt between a request's n rows,
      a reference each, and reserves every row's private generation pages up
      front, so a step in flight can never fail on allocation. All of it is
      taken or none (:meth:`admit`, :meth:`reserve_chunked`).
    - :meth:`prepare_step` grows a table at a page boundary and copies a
      shared page before a row's first write into it, out of the reserve. A
      drafted step writes ``lookahead`` positions past the row's next one (its
      draft's row, and the next-token module's one further), so all of them
      are made private, and every reserve is reckoned with them.
    - :meth:`release` drops every reference a retired slot holds.

    Allocation goes through the caller's ``alloc`` (the engine's evicting
    allocator: eviction is the prefix cache's policy, not the pool's) under
    the engine's paged mutex, which the caller takes. The class has no lock
    of its own: every method runs under the owning loop's lock, so the order
    (loop, paged mutex, allocator) stays the caller's.
    """

    def __init__(self, page_size: int, width: int, max_prompt: int, max_new: int,
                 pool_pages: Optional[int] = None, lookahead: int = 0) -> None:
        self.page_size = int(page_size)
        self.width, self.max_prompt, self.max_new = int(width), int(max_prompt), int(max_new)
        self.lookahead = int(lookahead)
        #: Pages the pool has, or will be built with: what :meth:`fits` holds
        #: a request's peak demand against before a pool exists.
        self.planned_pages = int(pool_pages or self.default_pool_pages())
        # All that follows — kllms: guarded-by[engine.continuous]
        self.pool: Optional[PagedKVPool] = None
        self._tables: List[List[int]] = []
        self._reserved: List[List[int]] = []
        # What a decode step is handed: every slot's table as one array.
        gen_slots = self.max_new + self.lookahead
        self.tables = np.full(
            (self.width, table_width(self.max_prompt, gen_slots, self.page_size)),
            TRASH_PAGE, np.int32)
        # The tables spread into one flat pool slot a position: what the step
        # program rebuilds on the device, kept here for admission's programs.
        self.prefix_idx = np.zeros((self.width, self.max_prompt), np.int32)
        self.gen_idx = np.zeros((self.width, gen_slots), np.int32)
        self._clear()

    # -- sizing ------------------------------------------------------------

    def default_pool_pages(self) -> int:
        """Pool sizing when neither the engine nor the backend pinned one:
        every slot decoding a DISTINCT max-shape prompt (the no-sharing worst
        case), plus one reserve page per slot for CoW, a couple of prompt-size
        runs of prefix-cache slack, and the trash page."""
        ps = self.page_size
        per_slot = pages_for(self.max_prompt + self.max_new + self.lookahead, ps) + 1
        return self.width * per_slot + 2 * pages_for(self.max_prompt, ps) + 1

    def need(self, plen: int, n: int, max_new: int) -> int:
        """Peak page demand of one request alone, which is what admission
        takes: one shared prompt run plus n private generation reserves."""
        return pages_for(plen, self.page_size) + max(1, n) * self._row_reserve(plen, max_new)

    def _row_reserve(self, plen: int, max_new: int) -> int:
        """:func:`row_reserve_pages` with the positions a drafted step writes
        ahead: a row's last step still finds its pages reserved."""
        return row_reserve_pages(plen, max_new + self.lookahead, self.page_size)

    def fits(self, plen: int, n: int, max_new: int) -> bool:
        """Can the pool hold this request even with the prefix cache fully
        evicted (the trash page is no one's)? A hint where no lock is held:
        admission finds out for certain."""
        return self.need(plen, n, max_new) <= self.planned_pages - 1

    # -- life cycle --------------------------------------------------------

    def attach(self, pool: PagedKVPool) -> None:
        self.pool = pool
        self.planned_pages = pool.allocator.total_pages

    def reset(self) -> None:
        """Forget the pool and every table WITHOUT decref: the pool dies with
        its torn-down engine, and a decref against a replaced allocator would
        corrupt the new pool's accounting."""
        self.pool = None
        self._clear()

    def _clear(self) -> None:
        """Every slot as a release leaves it: no table, no reserve, and the
        spread of an empty table (which is what a step program makes of an
        idle row's) in the index mirrors."""
        self._tables = [[] for _ in range(self.width)]
        self._reserved = [[] for _ in range(self.width)]
        for slot in range(self.width):
            self._refresh(slot, 0)

    def held(self) -> int:
        """Page references the slots hold (tables and reserves)."""
        return sum(map(len, self._tables)) + sum(map(len, self._reserved))

    # -- admission ---------------------------------------------------------

    def _fan_out(self, run_pages: List[int], refs: int, n_rows: int, reserve: int,
                 alloc: Callable[[int], List[int]]) -> List[List[int]]:
        """``refs`` more references on the prompt run and a reserve of
        ``reserve`` pages for each of ``n_rows`` rows; whatever was taken is
        given back before a failure leaves here."""
        allocator = self.pool.allocator
        taken = 0
        reserved: List[List[int]] = []
        try:
            for _ in range(refs):
                allocator.incref(run_pages)
                taken += 1
            for _ in range(n_rows):
                reserved.append(alloc(reserve))
        except BaseException:
            for lst in reserved:
                allocator.decref(lst)
            for _ in range(taken):
                allocator.decref(run_pages)
            raise
        return reserved

    def admit(self, rows: Sequence[int], run_pages: List[int], plen: int, max_new: int,
              alloc: Callable[[int], List[int]]) -> None:
        """Whole-prompt admission: the rows share ``run_pages`` (a prefill's
        or a cache entry's run, whose owner keeps its own reference), one
        reference each, and each gets its reserve. Raises
        :class:`PagePoolExhausted` with everything rolled back if the
        reserves don't fit."""
        reserve = self._row_reserve(plen, max_new)
        reserved = self._fan_out(run_pages, len(rows), len(rows), reserve, alloc)
        self.install(rows, run_pages, reserved, plen)

    def reserve_chunked(self, n_rows: int, plen: int, max_new: int,
                        alloc: Callable[[int], List[int]]) -> Tuple[List[int], List[List[int]]]:
        """Chunked admission, before the first chunk: a fresh prompt run (its
        allocation is the first row's reference, the other rows' are added)
        and every row's reserve — :meth:`need` in full, so a half-prefilled
        admission can never strand on allocation. ``(run_pages, reserved)``
        are the caller's to :meth:`install` or :meth:`drop`."""
        run_pages = alloc(pages_for(plen, self.page_size))
        try:
            reserve = self._row_reserve(plen, max_new)
            return run_pages, self._fan_out(run_pages, n_rows - 1, n_rows, reserve, alloc)
        except BaseException:
            self.pool.allocator.decref(run_pages)
            raise

    def chunk_slots(self, run_pages: Sequence[int], start: int, width: int,
                    valid: int) -> np.ndarray:
        """Flat pool slots for a ``width``-token chunk's KV columns, landing
        in the run at offset ``start``; the pad positions past ``valid``
        retarget to trash."""
        ps = self.page_size
        slots = flat_slots(run_pages, start + np.arange(width), ps)
        trash = (np.arange(width) % ps + TRASH_PAGE * ps).astype(np.int32)
        slots[valid:] = trash[valid:]
        return slots

    def install(self, rows: Sequence[int], run_pages: Sequence[int],
                reserved: Sequence[List[int]], plen: int) -> None:
        """Make the run the rows' tables (the references were taken by
        :meth:`admit` or :meth:`reserve_chunked`)."""
        for j, slot in enumerate(rows):
            self._tables[slot] = list(run_pages)
            self._reserved[slot] = reserved[j]
            self._refresh(slot, plen)

    def prefix_run(self, run_pages: Sequence[int], plen: int, bucket: int) -> PagedPrefixRun:
        """The run as a prefix-cache entry, with a reference of its own (the
        run is already scattered, so storing it is pure accounting)."""
        run = PagedPrefixRun(self.pool, list(run_pages), plen, bucket)
        run.retain()
        return run

    def drop(self, n_rows: int, run_pages: List[int], reserved: Sequence[List[int]]) -> None:
        """Give back what :meth:`reserve_chunked` took, for an admission that
        never installed. A corrupt allocator is contained: the references are
        dropped (the pool audit quarantines it) so the caller can still fail
        the request typed instead of wedging retirement."""
        if self.pool is None:
            return
        try:
            for lst in [run_pages] * n_rows + list(reserved):
                self.pool.allocator.decref(lst)
        except PageAccountingError:
            logger.exception("page release failed retiring a PREFILLING admission")

    # -- the decode step ---------------------------------------------------

    def _refresh(self, slot: int, plen: int) -> None:
        """Rebuild one slot's row of ``tables`` and its flat gather indices
        from its block table. Must run after ANY table change (admit,
        extension, CoW, release): a stale index could keep gathering a page
        that was freed and reused."""
        ps = self.page_size
        table = self._tables[slot]
        self.tables[slot, :len(table)] = table
        self.tables[slot, len(table):] = TRASH_PAGE
        P, G = self.max_prompt, self.gen_idx.shape[1]
        pidx = flat_slots(table, np.arange(P), ps)
        # Positions at/after the prompt end read through gen_idx instead;
        # point them into the trash page (masked, but must stay in bounds).
        pidx[plen:] = (np.arange(P - plen) % ps).astype(np.int32)
        self.prefix_idx[slot] = pidx
        self.gen_idx[slot] = flat_slots(table, plen + np.arange(G), ps)

    def prepare_step(self, active: np.ndarray, prompt_lens: np.ndarray,
                     gen_lens: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Resolve each row's write slots for the upcoming step, performing
        page-table maintenance on the way: append a reserved page when a
        write crosses a page boundary, copy-on-write when a target page is
        still shared with other readers. Returns what the step takes of the
        books, ``(write_idx [W, 1 + lookahead], tables [W, T])``: the flat
        slots of the row's next position and the ``lookahead`` after it
        (inactive rows write into the trash page), and every row's table (the
        books' own array: copy it to keep it). Never allocates — admission
        reserved every page this can pop."""
        pool = self.pool
        ps = self.page_size
        allocator = pool.allocator
        W, ahead = self.width, self.lookahead
        write_idx = np.empty((W, 1 + ahead), np.int32)
        cow_src: List[int] = []
        cow_dst: List[int] = []
        for slot in range(W):
            if not active[slot]:
                write_idx[slot] = TRASH_PAGE * ps + slot % ps
                continue
            plen = int(prompt_lens[slot])
            pos = plen + int(gen_lens[slot])
            table = self._tables[slot]
            for page_i in range(pos // ps, (pos + ahead) // ps + 1):
                if page_i == len(table):
                    table.append(self._reserved[slot].pop())
                    self._refresh(slot, plen)
                elif allocator.refcount(table[page_i]) > 1:
                    # First divergent write into the shared partial prompt page:
                    # give this row a private copy, then retarget its table.
                    new_page = self._reserved[slot].pop()
                    cow_src.append(table[page_i])
                    cow_dst.append(new_page)
                    table[page_i] = new_page
                    allocator.note_cow()
                    self._refresh(slot, plen)
            write_idx[slot] = [table[at // ps] * ps + at % ps for at in range(pos, pos + ahead + 1)]
        if cow_src:
            # Pad with trash->trash no-ops so every CoW batch shares one
            # compiled copy program regardless of how many rows diverged.
            pad = [TRASH_PAGE] * (W - len(cow_src))
            pool.copy_pages(cow_src + pad, cow_dst + pad)
            # Our reference on each source page must outlive the device copy
            # that reads it — decref only after the copy is enqueued (the
            # pool swap orders it before the next step's gathers).
            allocator.decref(cow_src)
        return write_idx, self.tables

    def walk_counts(self, active: np.ndarray, prompt_lens: np.ndarray,
                    gen_lens: np.ndarray, windows: Sequence[Optional[int]] = (None,)
                    ) -> Tuple[int, int, int]:
        """(layer-pages the live rows' walks hold, layer-pages the step's
        tables hold, layer-pages holding a pool position that lie before a
        layer's window's first page and so are not walked) for the upcoming
        decode step, each summed over the paging layers, from what the step
        program hands the paged kernel: the lengths with idle slots zeroed, the
        phase out of the gen slot map, and ``windows``, each paging layer's
        sliding window (``ModelConfig.layer_windows``). Layers with one window
        walk alike, so a uniform stack's three numbers are one layer's times
        its depth."""
        ps = self.page_size
        lens = (np.where(active, prompt_lens, 0), np.where(active, gen_lens, 0),
                self.gen_idx[:, 0] % ps, ps)
        tabled = self.width * sum(table_pages(self.max_prompt, self.max_new, ps))
        walked = windowed_out = 0
        for window, layers in Counter(windows).items():
            (p0, n_prefix), (g0, n_gen) = live_pages(*lens, window)
            out = int(np.sum(p0) + np.sum(g0))
            walked += layers * (int(n_prefix.sum() + n_gen.sum()) - out)
            windowed_out += layers * out
        return walked, len(windows) * tabled, windowed_out

    # -- retirement --------------------------------------------------------

    def release(self, slot: int) -> None:
        """Drop a retired slot's page references (shared prompt pages survive
        while the prefix cache or sibling rows still hold them)."""
        if self.pool is None:
            return
        allocator = self.pool.allocator
        spec = _failpoints.fire("engine.pages")
        if spec is not None and spec.action == "leak":
            allocator.leak(max(1, int(spec.kill)))
        table, self._tables[slot] = self._tables[slot], []
        reserved, self._reserved[slot] = self._reserved[slot], []
        if table:
            allocator.decref(table)
        if reserved:
            allocator.decref(reserved)
        self._refresh(slot, 0)

    def forget(self, slot: int) -> None:
        """Drop a slot's references WITHOUT decref: containment over an
        allocator that is already corrupt (and quarantined)."""
        self._tables[slot], self._reserved[slot] = [], []
        self._refresh(slot, 0)
