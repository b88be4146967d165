"""Fused paged-decode attention: block-table gather inside the QK^T.V loop.

The paged KV pool (engine/paging.py) stores every row's keys and values as
pool pages addressed through per-row block tables. Before this op existed the
layer scan materialized the gathered K/V (`take_along_axis` twice per layer)
and then ran dense attention over the copy — the gather bandwidth alone is
2 * bytes(KV) per decode step per layer at 8B widths. The Pallas kernel here
does what vLLM's PagedAttention does on GPU: the grid walks (row, page) and
each page block's HBM read is indexed *through the block table* by the
BlockSpec index_map, so the gather IS the attention's K/V load — no
materialized copy, one online-softmax pass, and the current step's fresh
column (not yet scattered into the pool) folded in at finalize.

Two implementations, one contract:

- ``paged_decode_attention_pallas``: the fused kernel. Uses scalar prefetch
  (page tables, per-row lengths/phase and the layer number) to drive the
  data BlockSpecs over the whole ``[L, ...]`` pool, read in place. TPU
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
drills.

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
    model using attention softcap, sliding windows or a latent (MLA) cache is
    outside the kernel's support and resolves to "xla". Resolution is host-side and happens once
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
    elif config is not None and config.sliding_window is not None:
        blocked = "sliding_window"
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
    fresh-page layout). The +1 gen page absorbs the phase shift's worst
    case. Pages for fully-masked table regions are whatever slot the map
    pointed at (typically trash) — the kernel's validity predicate masks
    every position they cover, so their contents are don't-care.

    Traceable (pure jnp); layer-invariant, so callers hoist it outside the
    layer scan.
    """
    ps = page_size
    prefix_pages = prefix_idx[..., ::ps] // ps  # [B|R, ceil(P/ps)]
    G = gen_idx.shape[-1]
    NG = -(-G // ps) + 1
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


def _paged_decode_kernel(
    # scalar prefetch (SMEM) -------------------------------------------------
    tables_ref,  # [B, NP + NG] int32: pool page per (row, page block)
    plen_ref,  # [B] int32: valid prefix length per row
    glen_ref,  # [B] int32: generated count per row (current token excluded)
    phase_ref,  # [B] int32: in-page offset of gen position 0
    layer_ref,  # [1] int32: the layer whose pages are read (index maps only)
    # data -------------------------------------------------------------------
    q_ref,  # [1, KVH, G, D] — one row's queries, grouped per kv head
    k_ref,  # [1, page_size, KVH, D] — page tables_ref[b, j] of that layer
    v_ref,  # [1, page_size, KVH, D]
    nk_ref,  # [1, KVH, D] — this step's fresh key column (not yet in pool)
    nv_ref,  # [1, KVH, D]
    o_ref,  # [1, KVH, G, D] f32
    # VMEM scratch -----------------------------------------------------------
    acc_ref,  # [KVH, G, D] f32
    m_ref,  # [KVH, G] f32 running max
    l_ref,  # [KVH, G] f32 running denominator
    *,
    sm_scale: float,
    page_size: int,
    num_prefix_pages: int,
    kv_heads: int,
):
    # Grid (row, page block): pages run prefix-first then gen; TPU grids
    # execute sequentially so the online-softmax scratch persists across the
    # page axis. The block-table indirection, layer number included, already
    # happened in the BlockSpec index_map — by the time this body runs,
    # k_ref/v_ref ARE the right page.
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    Gq = q_ref.shape[2]
    offs = lax.broadcasted_iota(jnp.int32, (Gq, page_size), 1)
    is_prefix = j < num_prefix_pages
    # Logical position of each in-page slot: prefix pages count from 0;
    # gen pages are phase-shifted (gen position g lives at in-page offset
    # (phase + g) % ps of gen page (phase + g) // ps).
    pos = jnp.where(
        is_prefix,
        j * page_size + offs,
        (j - num_prefix_pages) * page_size + offs - phase_ref[b],
    )
    limit = jnp.where(is_prefix, plen_ref[b], glen_ref[b])
    # TRASH_PAGE safety: any slot outside [0, limit) — padding, the phase
    # shift's dead lead-in, trash-retargeted table tails — scores NEG_INF
    # and contributes an exact 0.
    valid = (pos >= 0) & (pos < limit)

    for h in range(kv_heads):  # static unroll
        q = q_ref[0, h].astype(jnp.float32)  # [Gq, D]
        k = k_ref[0, :, h, :].astype(jnp.float32)  # [page_size, D]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = jnp.where(valid, s * sm_scale, NEG_INF)  # [Gq, page_size]

        m_prev = m_ref[h][:, None]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[h] = l_ref[h] * alpha[:, 0] + jnp.sum(p, axis=1)
        acc_ref[h] = acc_ref[h] * alpha + lax.dot_general(
            p,
            v_ref[0, :, h, :].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[h] = m_new[:, 0]

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # Fold in the CURRENT token's fresh K/V column — the caller hasn't
        # scattered it into the pool yet (the dense twin writes it into the
        # cache before attending; same visibility, no pool round-trip).
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32)  # [Gq, D]
            nk = nk_ref[0, h].astype(jnp.float32)  # [D]
            s = jnp.sum(q * nk[None, :], axis=1, keepdims=True) * sm_scale
            m_prev = m_ref[h][:, None]
            m_new = jnp.maximum(m_prev, s)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)  # [Gq, 1]
            l = l_ref[h] * alpha[:, 0] + p[:, 0]
            acc = acc_ref[h] * alpha + p * nv_ref[0, h].astype(jnp.float32)[None, :]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = acc / safe_l[:, None]


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
    interpret: bool = False,
    mesh=None,
) -> jax.Array:
    """Fused paged decode attention (``Sq == 1``).

    q: [B, QH, D]; pool_k/pool_v: the whole flat pool
    [L, total_pages * page_size, KVH, D], read in place; layer: int32 scalar,
    the layer whose pages the index maps address (a fifth scalar-prefetch
    operand, so no layer's pool is sliced out for the custom call);
    prefix_pages [B|R, NP] / gen_pages [B, NG] / gen_phase [B]: from
    :func:`paged_attention_page_tables`;
    new_k/new_v [B, KVH, D]: this step's fresh column; prompt_lens /
    gen_lens [B]: per-row valid counts. Returns [B, QH, D] f32 — the same
    normalized output the XLA reference produces (up to online-softmax
    float ordering; token-exact under greedy, pinned by the differential
    tests). Under a multi-device ``mesh`` the kernel runs per shard: kv heads
    (and the pool, which is sharded the same way) over the model axis, rows
    and their tables over the data axis when they divide; the pool itself is
    replicated over data, and so is the layer number.
    """
    B = q.shape[0]
    if prefix_pages.shape[0] != B:  # [R, NP] shared prefix -> per-row table
        prefix_pages = jnp.repeat(
            prefix_pages, B // prefix_pages.shape[0], axis=0,
            total_repeat_length=B,
        )
    local = functools.partial(
        _paged_decode_local, page_size=page_size, sm_scale=sm_scale,
        interpret=interpret,
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
    prompt_lens, gen_lens, *, page_size, sm_scale, interpret,
):
    """One shard's fused paged decode (the whole call on a single device)."""
    B, QH, D = q.shape
    L, flat, KVH = pool_k.shape[:3]
    G = QH // KVH
    ps = page_size
    npages = flat // ps
    NP = prefix_pages.shape[1]
    NG = gen_pages.shape[1]
    tables = jnp.concatenate([prefix_pages, gen_pages], axis=1).astype(jnp.int32)

    q4 = q.reshape(B, KVH, G, D)  # query head h*G+g shares kv head h
    # Every layer's pages in one page axis (a free reshape): page p of layer
    # l is block l * npages + p, so the custom call's operand is the pool.
    pk4 = pool_k.reshape(L * npages, ps, KVH, D)
    pv4 = pool_v.reshape(L * npages, ps, KVH, D)

    def page_map(b, j, tables, plen, glen, phase, layer):
        return (layer[0] * npages + tables[b, j], 0, 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=sm_scale,
        page_size=ps,
        num_prefix_pages=NP,
        kv_heads=KVH,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, NP + NG),
        in_specs=[
            pl.BlockSpec((1, KVH, G, D), lambda b, j, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, ps, KVH, D), page_map),
            pl.BlockSpec((1, ps, KVH, D), page_map),
            pl.BlockSpec((1, KVH, D), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, KVH, D), lambda b, j, *_: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KVH, G, D), lambda b, j, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KVH, G, D), jnp.float32),
            pltpu.VMEM((KVH, G), jnp.float32),
            pltpu.VMEM((KVH, G), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), jnp.float32),
        interpret=interpret,
        name="paged_attention_decode",
    )(
        tables,
        prompt_lens.astype(jnp.int32),
        gen_lens.astype(jnp.int32),
        gen_phase.astype(jnp.int32),
        layer,
        q4,
        pk4,
        pv4,
        new_k,
        new_v,
    )
    return out.reshape(B, QH, D)
