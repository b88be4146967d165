"""Fused paged-decode attention: block-table gather inside the QK^T.V loop.

The paged KV pool (engine/paging.py) stores every row's keys and values as
pool pages addressed through per-row block tables. Before this op existed the
layer scan materialized the gathered K/V (`take_along_axis` twice per layer)
and then ran dense attention over the copy — the gather bandwidth alone is
2 * bytes(KV) per decode step per layer at 8B widths. The Pallas kernel here
does what vLLM's PagedAttention does on GPU: the grid walks rows, each row
walks the pages it has (:func:`live_pages` — a trip count from its own
lengths, zero for an idle slot), and each page is a DMA out of the pool in
HBM addressed *through the block table*, K pages a block into a double
buffer, so the gather IS the attention's K/V load — no materialized copy, one
online-softmax pass over blocks scored whole (all query heads against the
block's ``K * page_size * KVH`` pool rows), and the current step's fresh
column (not yet scattered into the pool) folded in at the end.

Two implementations, one contract:

- ``paged_decode_attention_pallas``: the fused kernel. Uses scalar prefetch
  (page tables, per-row lengths/phase and the layer number) to drive its own
  page copies out of the whole ``[L, ...]`` pool, read in place. TPU
  only in production; ``interpret=True`` exists for the differential tests.
- ``paged_decode_attention_xla``: jittable pure-XLA reference with identical
  semantics — and byte-identical to the dense `_block` decode math (same op
  order, same masks), which is what the serving path runs everywhere Pallas
  is unavailable (tier-1 CI is `JAX_PLATFORMS=cpu`; interpret mode is never
  used for serving).

Selection is ``resolve_paged_attention_impl`` (backed by
``BackendConfig.paged_attention_impl``): "xla" | "pallas" | "auto", with an
automatic COUNTED fallback (``kernel.paged_attn_fallback.<reason>``, where
the suffix names what blocked the kernel: failpoint / softcap /
sliding_window / mla / platform) when "pallas" is requested but can't run, or
when "auto" on a TPU meets a model outside the kernel's support; "auto"
choosing XLA off-TPU is the documented CPU posture, not a fallback, so it is
not counted. The ``ops.paged_attn`` failpoint forces the fallback branch for
drills. A sliding window is inside the kernel's support: it is part of the
walk and of the mask (:func:`live_pages`), static in each call. A stack whose
layers are unrolled (models/hybrid.py) hands each layer's call its own
(``ModelConfig.layer_windows``), so windowed and global layers mix under the
kernel there. The scanned GQA stack (models/llama.py) has one call for every
layer: a window that every layer has (Mistral) is served, a per-layer mix
(Gemma-2's "alternating", which its softcap blocks first) is ``sliding_window``.

Masking contract (shared with `gather_kv_pages`): out-of-table positions
point into the trash page; their values are arbitrary-but-finite and every
consumer forces their scores to ``NEG_INF`` before the softmax max, so they
contribute an exact 0.0 — the invariant behind paged == dense bit-equality.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from ..parallel.mesh import DATA_AXIS, MODEL_AXIS
from ..reliability import failpoints as _failpoints
from ..utils.observability import KERNEL_EVENTS
from .attention import (
    NEG_INF,
    decode_prefix_attention,
    gather_kv_pages,
    mesh_axis,
    multi_device,
    shard_kernel,
)

#: Values accepted by ``BackendConfig.paged_attention_impl`` /
#: ``LocalEngine(paged_attention_impl=...)``. "pallas_interpret" is a
#: tests-only extra understood by ``paged_verify_step`` — never returned by
#: :func:`resolve_paged_attention_impl`, never run in the serving path.
PAGED_ATTENTION_IMPLS = ("auto", "pallas", "xla")


def resolve_paged_attention_impl(
    requested: str, *, config=None, record: bool = True
) -> str:
    """Pick the paged-attention implementation for the current process.

    requested: "auto" | "pallas" | "xla"; config: optional ModelConfig — a
    model using attention softcap, a latent (MLA) cache, or a scanned GQA
    stack with a sliding window on some layers and not on others
    (``sliding_window_layers == "alternating"``: the scan has one kernel call
    and so one window for every layer) is outside the kernel's support and
    resolves to "xla". A window on every layer is served by the kernel, and so
    is a hybrid stack's mix of windowed and global layers (its layers are
    unrolled: each call takes its own window).
    Resolution is host-side and happens once
    per loop/launch build, not per step. An explicit "pallas" request that
    cannot be honored records ``kernel.paged_attn_fallback.<reason>``, where
    the reason distinguishes config-driven fallbacks (``softcap``,
    ``sliding_window``, ``mla`` — the model is outside the kernel's support;
    on a TPU these are counted under "auto" too) from
    environment-driven ones (``platform`` — no TPU) and drills
    (``failpoint``); "auto" picking XLA off-TPU is the expected CPU posture
    and is NOT counted. The ``ops.paged_attn`` failpoint (action
    ``fallback``) forces the counted fallback for observability drills.
    ``record=False`` asks what WOULD resolve without firing the failpoint or
    counting anything (``health()`` reports it).
    """
    if requested not in PAGED_ATTENTION_IMPLS:
        raise ValueError(
            f"paged_attention_impl must be one of {PAGED_ATTENTION_IMPLS}, "
            f"got {requested!r}"
        )
    spec = _failpoints.fire("ops.paged_attn") if record else None
    if spec is not None and spec.action == "fallback":
        KERNEL_EVENTS.record("kernel.paged_attn_fallback.failpoint")
        return "xla"
    if requested == "xla":
        return "xla"
    if config is not None and config.is_latent:
        blocked: Optional[str] = "mla"  # a latent page is no (KVH, D) tile
    elif config is not None and config.attn_softcap is not None:
        blocked = "softcap"
    elif config is not None and config.mixes_windowed_layers and not config.is_hybrid:
        blocked = "sliding_window"  # one scanned kernel call, one window
    else:
        blocked = None
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and blocked is None:
        return "pallas"
    # Counted when the kernel was asked for by name, or when "auto" would
    # have taken it here and the model is what stands in the way.
    if record and (requested == "pallas" or (on_tpu and blocked is not None)):
        KERNEL_EVENTS.record(f"kernel.paged_attn_fallback.{blocked or 'platform'}")
    return "xla"


def note_paged_attn_dispatch(impl: str, n: int = 1) -> None:
    """Count a paged-attention dispatch (one per decode launch / continuous
    paged step, host-side — never inside jit). Interpret-mode runs count as
    pallas: the kernel code path is what's being exercised."""
    if impl in ("pallas", "pallas_interpret"):
        KERNEL_EVENTS.record("kernel.paged_attn_pallas_dispatch", n)
    else:
        KERNEL_EVENTS.record("kernel.paged_attn_xla_dispatch", n)


# ---------------------------------------------------------------------------
# XLA reference (always available; the serving path off-TPU)
# ---------------------------------------------------------------------------


def paged_decode_attention_xla(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    layer: jax.Array,
    prefix_idx: jax.Array,
    gen_idx: jax.Array,
    new_k: jax.Array,
    new_v: jax.Array,
    write_index: jax.Array,
    key_mask: jax.Array,
    prefix_mask: jax.Array,
    *,
    sm_scale: float,
    softcap: Optional[float] = None,
    prefix_lengths: Optional[jax.Array] = None,
    flash_prefix: Optional[str] = None,
    mesh=None,
) -> jax.Array:
    """Reference paged decode attention, byte-identical to the dense path.

    q/new_k/new_v: this step's post-RoPE projections, ``[B, Sq, QH|KVH, D]``
    (``Sq == 1`` on the decode hot path); pool_k/pool_v: the whole flat
    page pool ``[L, total_pages * page_size, KVH, D]`` and ``layer``, the
    int32 scalar that says which layer's slots to read (the pool is never
    sliced: see :func:`gather_kv_pages`); prefix_idx
    ``[B|R, P]`` / gen_idx ``[B, G]``: flat pool slots per logical position
    (an ``[R, P]`` prefix is shared request-major, exactly like the dense
    shared-prefix cache); write_index ``[B]``: each row's write offset into
    its gen slots; key_mask ``[B, Sq, G]`` / prefix_mask ``[B, Sq, P]``:
    the same masks the dense `_block` receives.

    The op order — gather, per-row fresh-column insert, masked scores,
    concatenated softmax (or the flash-prefix logsumexp merge when
    ``flash_prefix`` names the resolved decode kernel, "flash" or the
    tests-only "flash_interpret") — replicates `models/llama.py::_block`'s decode branch
    operation for operation, so outputs are bit-identical to dense attention
    on equal inputs. Returns attn ``[B, Sq, QH, D]`` f32.
    """
    from ..models.llama import (
        _gqa_scores,
        _gqa_scores_shared,
        _gqa_values,
        _gqa_values_shared,
        _merge_prefix_tail,
        _softcap,
    )

    pk, pv = gather_kv_pages(pool_k, pool_v, prefix_idx, layer)  # [B|R, P, KVH, D]
    gk, gv = gather_kv_pages(pool_k, pool_v, gen_idx, layer)  # [B, G, KVH, D]
    # The dense path's per-row cache write: the freshly computed column lands
    # at each row's own offset before attention reads it.
    row_update = jax.vmap(
        lambda c, kk, off: lax.dynamic_update_slice_in_dim(c, kk, off, axis=0)
    )
    gk = row_update(gk, new_k.astype(gk.dtype), write_index)
    gv = row_update(gv, new_v.astype(gv.dtype), write_index)

    if flash_prefix:
        out_p, m_p, l_p = decode_prefix_attention(
            q[:, 0],
            pk,
            pv,
            prefix_lengths,
            sm_scale=sm_scale,
            interpret=flash_prefix == "flash_interpret",
            mesh=mesh,
        )
        return _merge_prefix_tail(
            q,
            gk,
            gv,
            key_mask,
            sm_scale,
            out_p[:, :, None],
            m_p[:, :, None],
            l_p[:, :, None],
        )

    scores = _gqa_scores(q, gk) * sm_scale  # [B, QH, Sq, G] f32
    if softcap is not None:
        scores = _softcap(scores, softcap)
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(key_mask[:, None, :, :], scores, neg)
    p_scores = _gqa_scores_shared(q, pk) * sm_scale  # [B, QH, Sq, P]
    if softcap is not None:
        p_scores = _softcap(p_scores, softcap)
    p_scores = jnp.where(prefix_mask[:, None, :, :], p_scores, neg)
    all_scores = jnp.concatenate([p_scores, scores], axis=-1)
    weights = jax.nn.softmax(all_scores, axis=-1)
    plen = pk.shape[1]
    return _gqa_values_shared(weights[..., :plen], pv) + _gqa_values(
        weights[..., plen:], gv
    )


# ---------------------------------------------------------------------------
# Page-table derivation (shared by the Pallas caller)
# ---------------------------------------------------------------------------


def table_pages(prefix_slots: int, gen_slots: int, page_size: int) -> Tuple[int, int]:
    """Widths of the page tables :func:`paged_attention_page_tables` derives
    from ``[.., P]`` and ``[.., G]`` slot maps: ``(NP, NG)``. The +1 gen page
    absorbs the phase shift's worst case."""
    return -(-prefix_slots // page_size), -(-gen_slots // page_size) + 1


def paged_attention_page_tables(
    prefix_idx: jax.Array, gen_idx: jax.Array, page_size: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Derive per-row PAGE tables from flat-SLOT index maps.

    The engine's index maps carry one flat slot per logical position
    (position p -> page * page_size + offset). The kernel wants the page
    granularity back: ``prefix_pages [B|R, ceil(P/ps)]``, ``gen_pages
    [B, ceil(G/ps) + 1]`` and ``gen_phase [B]`` — the in-page offset of gen
    position 0 (``plen % ps`` for the continuous layout where generated
    tokens continue the prompt's last partial page; 0 for the coalesced
    fresh-page layout). Pages for fully-masked table regions are whatever
    slot the map pointed at (typically trash) — the kernel's validity
    predicate masks every position they cover, so their contents are
    don't-care.

    Traceable (pure jnp); layer-invariant, so callers hoist it outside the
    layer scan.
    """
    ps = page_size
    prefix_pages = prefix_idx[..., ::ps] // ps  # [B|R, ceil(P/ps)]
    G = gen_idx.shape[-1]
    _, NG = table_pages(prefix_idx.shape[-1], G, ps)
    phase = gen_idx[:, :1] % ps  # [B, 1]
    starts = jnp.arange(NG, dtype=jnp.int32)[None, :] * ps - phase  # [B, NG]
    src = jnp.clip(starts, 0, G - 1)
    gen_pages = jnp.take_along_axis(gen_idx, src, axis=1) // ps  # [B, NG]
    return (
        prefix_pages.astype(jnp.int32),
        gen_pages.astype(jnp.int32),
        phase[:, 0].astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# Fused Pallas kernel
# ---------------------------------------------------------------------------

#: VMEM the kernel's page buffers may take: two slots (one scored while the
#: other fills) of K pages of keys and K of values. K follows from this and
#: the page's own bytes (:func:`pages_per_block`).
PAGE_BUFFER_BYTES = 2 * 1024 * 1024


def pages_per_block(page_bytes: int, table_pages: int) -> int:
    """K, the pages the kernel fetches and scores together: what fits the
    double-buffered K and V page buffers, at most the whole table."""
    return max(1, min(PAGE_BUFFER_BYTES // (4 * page_bytes), table_pages))


def live_pages(prompt_lens, gen_lens, gen_phase, page_size: int, window=None):
    """The pages of a row's table that hold a position it attends to, a
    contiguous run of each table: ``((first, end) of the prefix table's,
    (first, end) of the generated table's)``, ``end`` exclusive.

    prompt_lens / gen_lens: valid prompt positions and generated tokens in the
    pool (the current token's fresh column is not in it); gen_phase: the
    in-page offset of generated position 0. A row with no generated token has
    no generated page whatever its phase, so a row with nothing has none at
    all. window: the model's sliding window W, or None. The query at absolute
    position ``q = prompt_lens + gen_lens`` sees key ``a`` iff ``a > q - W``
    (itself and W - 1 before it), so the first prompt position it sees is
    ``max(0, q - W + 1)`` and the first generated one ``max(0, gen_lens - W +
    1)``; the run starts at the page that holds it, and a page before it holds
    only positions the reference's masks give weight 0. Without a window both
    runs start at the literal 0. Plain integer arithmetic: the kernel calls it
    on its SMEM scalars, the loop's page counter on numpy vectors.
    """
    ps = page_size
    n_prefix = (prompt_lens + (ps - 1)) // ps
    n_gen = (gen_phase + gen_lens + (ps - 1)) // ps * (gen_lens > 0)
    if window is None:
        return (0, n_prefix), (0, n_gen)

    def at_least_0(x):
        return x * (x > 0)

    first_prompt = at_least_0(prompt_lens + gen_lens - (window - 1))
    first_gen = at_least_0(gen_lens - (window - 1))
    # The run starts at the page of the first position seen, where the pool
    # holds one: a window that has left the prompt, or that holds the fresh
    # column alone, leaves an empty run (first == end).
    p0 = n_prefix - (n_prefix - first_prompt // ps) * (first_prompt < prompt_lens)
    g0 = n_gen - (n_gen - (gen_phase + first_gen) // ps) * (first_gen < gen_lens)
    return (p0, n_prefix), (g0, n_gen)


def _paged_decode_kernel(
    # scalar prefetch (SMEM) -------------------------------------------------
    tables_ref,  # [B, NP + NG] int32: pool page per (row, table page)
    plen_ref,  # [B] int32: valid prefix length per row
    glen_ref,  # [B] int32: generated count per row (current token excluded)
    phase_ref,  # [B] int32: in-page offset of gen position 0
    layer_ref,  # [1] int32: the layer whose pages are read
    # data -------------------------------------------------------------------
    q_ref,  # [1, QH, D] — one row's queries; query head h*G+g shares kv head h
    k_hbm,  # [L * npages, page_size * KVH, D] — the whole pool, left in HBM;
    v_hbm,  # a page's row r is (position r // KVH, kv head r % KVH)
    nk_ref,  # [1, QH, D] — this step's fresh key column (not yet in pool),
    nv_ref,  # each kv head's repeated for its G query heads
    o_ref,  # [1, QH, D] f32
    # scratch ----------------------------------------------------------------
    k_buf,  # VMEM [2, K, page_size * KVH, D]: two slots of K pages
    v_buf,  # VMEM [2, K, page_size * KVH, D]
    sems,  # DMA semaphores [2, 2]: (slot, keys | values)
    *,
    sm_scale: float,
    page_size: int,
    num_prefix_pages: int,
    num_gen_pages: int,
    pages_per_layer: int,
    kv_heads: int,
    block_pages: int,
    window: Optional[int],
):
    # Grid (row,). A row walks its own live pages and no others: the first
    # n_prefix columns of its prefix table, then the first n_gen of its gen
    # table (under a window each run starts at the window's first page: p0,
    # g0), K pages a block. Each page is one DMA out of the pool in HBM,
    # addressed through the block table and the layer number here in the
    # body. Two slots: while a block is scored out of one, the other fills
    # with the row's next. The trip count is read from SMEM, so a row with
    # nothing fetches nothing and runs the fresh-column fold alone.
    #
    # A block is scored whole, every kv head at once: its K * ps * KVH pool
    # rows against all QH queries in one product, a query head's weights
    # kept on its own kv head's rows (the others score NEG_INF like any
    # masked slot). That computes KVH times the scores it keeps, and takes
    # the pages exactly as the pool lays them out: cutting one head's rows
    # out of the (position, head) interleave cost more than the scores.
    #
    # ``window`` is static. Without one every windowed term below is a Python
    # branch not taken, and the kernel is the one a model without a window
    # always had, operation for operation.
    b = pl.program_id(0)
    ps, K, KVH = page_size, block_pages, kv_heads
    plen, glen, phase = plen_ref[b], glen_ref[b], phase_ref[b]
    (p0, n_prefix), (g0, n_gen) = live_pages(plen, glen, phase, ps, window)
    n_prefix = jnp.minimum(n_prefix, num_prefix_pages)
    n_gen = jnp.minimum(n_gen, num_gen_pages)
    if window is not None:  # the runs' lengths from here on, not their ends
        n_prefix = n_prefix - jnp.minimum(p0, n_prefix)
        n_gen = n_gen - jnp.minimum(g0, n_gen)
    live = n_prefix + n_gen
    n_blocks = (live + (K - 1)) // K
    base = layer_ref[0] * pages_per_layer

    def page_copies(page, slot, t):  # a page of keys and of values into a slot
        return [
            pltpu.make_async_copy(hbm.at[page], buf.at[slot, t], sems.at[slot, kv])
            for kv, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))
        ]

    def start_block(blk, slot):
        for t in range(K):  # static unroll
            i = blk * K + t

            @pl.when(i < live)
            def _start():
                col = jnp.where(i < n_prefix, i, num_prefix_pages + i - n_prefix)
                if window is not None:
                    col = col + jnp.where(i < n_prefix, p0, g0)
                for copy in page_copies(base + tables_ref[b, col], slot, t):
                    copy.start()

    def wait_block(blk, slot):
        for t in range(K):

            @pl.when(blk * K + t < live)
            def _wait():
                for copy in page_copies(0, slot, t):  # the shape is what is waited on
                    copy.wait()

    @pl.when(b == 0)
    def _first_row():
        # A block's tail past the row's last page is not fetched: what the
        # slot holds there is scored NEG_INF, and its weight, an exact 0,
        # multiplies whatever V the slot last held. Pool values are finite;
        # uninitialised VMEM need not be.
        v_buf[...] = jnp.zeros_like(v_buf)

    start_block(0, 0)

    QH, D = q_ref.shape[1], q_ref.shape[2]
    T = K * ps * KVH  # pool rows of a block, in (page, position, head) order
    row = lax.broadcasted_iota(jnp.int32, (QH, T), 1)
    tok = row // KVH  # which of the block's K * ps token slots
    own = row % KVH == lax.broadcasted_iota(jnp.int32, (QH, T), 0) // (QH // KVH)
    q = q_ref[0].astype(jnp.float32)  # [QH, D]

    def block(blk, carry):
        m, l, acc = carry
        slot = lax.rem(blk, 2)

        @pl.when(blk + 1 < n_blocks)
        def _next():
            start_block(blk + 1, 1 - slot)

        # Logical position of each of the block's token slots: prefix pages
        # count from 0; gen pages are phase-shifted (gen position g lives at
        # in-page offset (phase + g) % ps of gen page (phase + g) // ps).
        # Anything outside [0, limit) — padding, the phase shift's dead
        # lead-in, the block's unfetched tail — scores NEG_INF and
        # contributes an exact 0 (the TRASH_PAGE contract). So does a
        # position the window has passed: the query at plen + glen sees
        # absolute position a (a prompt position's own, plen + a generated
        # one's) iff a > plen + glen - window.
        prefix_toks = (n_prefix - blk * K) * ps
        is_prefix = tok < prefix_toks
        pos = jnp.where(is_prefix, tok + blk * (K * ps), tok - prefix_toks - phase)
        if window is not None:
            pos = pos + jnp.where(is_prefix, p0, g0) * ps
        limit = jnp.where(is_prefix, plen, glen)
        valid = own & (pos >= 0) & (pos < limit)
        if window is not None:
            valid = valid & (pos > jnp.where(is_prefix, plen, 0) + glen - window)

        wait_block(blk, slot)
        s = lax.dot_general(
            q, k_buf[slot].reshape(T, D).astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        s = jnp.where(valid, s * sm_scale, NEG_INF)  # [QH, T]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + lax.dot_general(
            p, v_buf[slot].reshape(T, D).astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m, l, acc = lax.fori_loop(
        0,
        n_blocks,
        block,
        (
            jnp.full((QH, 1), NEG_INF, jnp.float32),
            jnp.zeros((QH, 1), jnp.float32),
            jnp.zeros((QH, D), jnp.float32),
        ),
    )

    # Fold in the CURRENT token's fresh K/V column — the caller hasn't
    # scattered it into the pool yet (the dense twin writes it into the
    # cache before attending; same visibility, no pool round-trip).
    s = jnp.sum(q * nk_ref[0].astype(jnp.float32), axis=1, keepdims=True) * sm_scale
    m_new = jnp.maximum(m, s)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)  # [QH, 1]
    l = l * alpha + p
    acc = acc * alpha + p * nv_ref[0].astype(jnp.float32)
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)


def paged_decode_attention_pallas(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    layer: jax.Array,
    prefix_pages: jax.Array,
    gen_pages: jax.Array,
    gen_phase: jax.Array,
    new_k: jax.Array,
    new_v: jax.Array,
    prompt_lens: jax.Array,
    gen_lens: jax.Array,
    *,
    page_size: int,
    sm_scale: float,
    window: Optional[int] = None,
    interpret: bool = False,
    mesh=None,
) -> jax.Array:
    """Fused paged decode attention (``Sq == 1``).

    q: [B, QH, D]; pool_k/pool_v: the whole flat pool
    [L, total_pages * page_size, KVH, D], read in place; layer: int32 scalar,
    the layer whose pages the kernel's copies address (a fifth
    scalar-prefetch operand, so no layer's pool is sliced out for the custom
    call); prefix_pages [B|R, NP] / gen_pages [B, NG] / gen_phase [B]: from
    :func:`paged_attention_page_tables`;
    new_k/new_v [B, KVH, D]: this step's fresh column; prompt_lens /
    gen_lens [B]: per-row valid counts — they are the walk's trip count too
    (:func:`live_pages`), so a row whose lengths are zero reads no page;
    window: this layer's sliding window, or None — the walk starts at the
    window's first page and the mask ends at its edge.
    Returns [B, QH, D] f32 — the normalized output the XLA reference
    produces, up to the float ordering of an online softmax over blocks
    (f32 accumulation: 2e-5 beside the reference over an f32 pool, bf16's own
    resolution over a bf16 pool). Not bit-exact, so greedy tokens equal the
    reference's wherever its top two logits lie further apart than that
    noise, and may swap across a nearer tie; the differential tests pin
    both sides of that (tokens on decisive prompts, logits at every step
    teacher-forced: ``tests/chip_kernel_check.py``).

    Under a multi-device ``mesh`` the kernel runs per shard: kv heads (and
    the pool, which is sharded the same way) over the model axis, rows and
    their tables over the data axis when they divide; the pool itself is
    replicated over data, and so is the layer number. A shard scores a block
    against its own kv heads' rows alone, so its sums run in another order
    than one device's: sharded and unsharded agree to the same tolerance,
    not bit for bit.
    """
    B = q.shape[0]
    if prefix_pages.shape[0] != B:  # [R, NP] shared prefix -> per-row table
        prefix_pages = jnp.repeat(
            prefix_pages, B // prefix_pages.shape[0], axis=0,
            total_repeat_length=B,
        )
    local = functools.partial(
        _paged_decode_local, page_size=page_size, sm_scale=sm_scale,
        window=window, interpret=interpret,
    )
    if multi_device(mesh):
        b_ax = mesh_axis(mesh, DATA_AXIS, B)
        h_ax = mesh_axis(mesh, MODEL_AXIS, pool_k.shape[2])
        rows, pool = P(b_ax, h_ax, None), P(None, None, h_ax, None)
        local = shard_kernel(
            local, mesh,
            in_specs=(
                rows, pool, pool, P(), P(b_ax, None), P(b_ax, None), P(b_ax),
                rows, rows, P(b_ax), P(b_ax),
            ),
            out_specs=rows,
        )
    return local(
        q, pool_k, pool_v, jnp.asarray(layer, jnp.int32).reshape(1),
        prefix_pages, gen_pages, gen_phase, new_k, new_v, prompt_lens, gen_lens,
    )


def _paged_decode_local(
    q, pool_k, pool_v, layer, prefix_pages, gen_pages, gen_phase, new_k, new_v,
    prompt_lens, gen_lens, *, page_size, sm_scale, window, interpret,
):
    """One shard's fused paged decode (the whole call on a single device)."""
    B, QH, D = q.shape
    L, flat, KVH = pool_k.shape[:3]
    G = QH // KVH  # query head h*G+g shares kv head h
    ps = page_size
    npages = flat // ps
    NP = prefix_pages.shape[1]
    NG = gen_pages.shape[1]
    tables = jnp.concatenate([prefix_pages, gen_pages], axis=1).astype(jnp.int32)
    K = pages_per_block(ps * KVH * D * pool_k.dtype.itemsize, NP + NG)

    # Every layer's pages in one page axis, a page's (position, head) rows in
    # one row axis: page p of layer l is page l * npages + p. Both are free
    # reshapes of the pool as the device lays it out (rows of D, position
    # major), so the custom call's operand is the pool itself.
    pk = pool_k.reshape(L * npages, ps * KVH, D)
    pv = pool_v.reshape(L * npages, ps * KVH, D)

    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=sm_scale,
        page_size=ps,
        num_prefix_pages=NP,
        num_gen_pages=NG,
        pages_per_layer=npages,
        kv_heads=KVH,
        block_pages=K,
        window=window,
    )
    row = pl.BlockSpec((1, QH, D), lambda b, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[
            row,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            row,
            row,
        ],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((2, K, ps * KVH, D), pool_k.dtype),
            pltpu.VMEM((2, K, ps * KVH, D), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, QH, D), jnp.float32),
        # Rows run in order on one core: the first clears the page buffers.
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention_decode",
    )(
        tables,
        prompt_lens.astype(jnp.int32),
        gen_lens.astype(jnp.int32),
        gen_phase.astype(jnp.int32),
        layer,
        q,
        pk,
        pv,
        jnp.repeat(new_k, G, axis=1),
        jnp.repeat(new_v, G, axis=1),
    )
