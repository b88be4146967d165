"""Flash attention for TPU (Pallas) with an XLA reference path.

The prefill hot loop is a classic flash-attention pattern: tile Q and K/V into
VMEM blocks, keep running max/sum/accumulator scratch across the K grid axis
(TPU grids execute sequentially, so scratch persists), and never materialize
the [Sq, Sk] score matrix in HBM. GQA is handled by mapping each query head's
K/V BlockSpec onto its shared kv head — no head replication in memory.

`attention_xla` is the always-available reference implementation (also the
numerical oracle in tests, where the kernel runs in interpret mode on CPU).

Two rules hold for every Pallas call in the package. (1) Mosaic kernels cannot
be partitioned by GSPMD, so under a multi-device mesh the call runs per shard
inside ``shard_map`` (:func:`shard_kernel`): heads over the model axis, batch
rows over the data axis when they divide. (2) Interpret mode is never chosen
from the platform: ``"flash"`` means the compiled kernel and resolves to the
XLA reference off-TPU (:func:`resolve_attention_impl`); only the explicit
``"flash_interpret"`` name — which tests use — runs the interpreter.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

NEG_INF = float(jnp.finfo(jnp.float32).min)

#: Values of ``ModelConfig.attention_impl`` / ``decode_attention_impl``.
ATTENTION_IMPLS = ("xla", "flash", "flash_interpret")


def resolve_attention_impl(requested: str) -> str:
    """The implementation a config's ``"xla" | "flash" | "flash_interpret"``
    runs in this process: ``"flash"`` is the Mosaic-compiled kernel, which
    exists on TPU only — anywhere else the served path takes the XLA
    reference (the same posture as paged ``"auto"``). ``"flash_interpret"``
    runs the kernel body in the Pallas interpreter on any backend; tests ask
    for it by name, nothing selects it from the platform."""
    if requested not in ATTENTION_IMPLS:
        raise ValueError(
            f"attention impl must be one of {ATTENTION_IMPLS}, got {requested!r}"
        )
    if requested == "flash" and jax.default_backend() != "tpu":
        return "xla"
    return requested


def mesh_axis(mesh, axis: str, size: int) -> Optional[str]:
    """``axis`` when ``size`` elements split evenly over it, else None (the
    dimension is then replicated over that axis inside the shard_map)."""
    return axis if size % mesh.shape[axis] == 0 else None


def multi_device(mesh) -> bool:
    """Does a Pallas call under ``mesh`` need :func:`shard_kernel`? (One
    device compiles the call directly.)"""
    return mesh is not None and mesh.size > 1


def shard_kernel(fn, mesh, in_specs, out_specs):
    """``fn`` run per shard of a multi-device ``mesh`` (Mosaic refuses
    automatic partitioning). ``check_vma`` is off because ``pallas_call``
    outputs carry no varying-axes annotation."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    key_mask: Optional[jax.Array] = None,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Reference attention. q: [B, QH, Sq, D]; k/v: [B, KVH, Sk, D];
    key_mask: [B, Sk] booleans. Returns [B, QH, Sq, D] (f32)."""
    B, QH, Sq, D = q.shape
    KVH = k.shape[1]
    G = QH // KVH
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    qg = q.reshape(B, KVH, G, Sq, D)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    Sk = k.shape[2]
    if causal:
        cmask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        scores = jnp.where(cmask[None, None, None], scores, NEG_INF)
    if key_mask is not None:
        scores = jnp.where(key_mask[:, None, None, None, :].astype(bool), scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", weights, v.astype(jnp.float32))
    return out.reshape(B, QH, Sq, D)


def _flash_kernel(
    keylen_ref,  # [B, 1] int32 in SMEM: valid (prefix) key count per batch row
    window_ref,  # [1, 1] int32 in SMEM: sliding window (2^30 = no window)
    qoff_ref,  # [1, 1] int32 in SMEM: absolute position of query row 0
    q_ref,  # [1, 1, block_q, D]
    k_ref,  # [1, 1, block_k, D]
    v_ref,  # [1, 1, block_k, D]
    o_ref,  # [1, 1, block_q, D]
    acc_ref,  # VMEM scratch [block_q, D] f32
    m_ref,  # VMEM scratch [block_q, 1] f32 running max
    l_ref,  # VMEM scratch [block_q, 1] f32 running sum
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    softcap: Optional[float],
):
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # q_offset shifts queries to ABSOLUTE positions (continuation prefill:
    # query row 0 sits at position prefix_len over a key space that starts at
    # the sequence's position 0). Zero for ordinary same-origin prefill.
    q_start = qi * block_q + qoff_ref[0, 0]
    k_start = ki * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale  # [block_q, block_k]
        if softcap is not None:  # Gemma-2 attention softcap
            s = softcap * jnp.tanh(s / softcap)

        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        valid = cols < keylen_ref[bi, 0]
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        if causal:
            valid = jnp.logical_and(valid, cols <= rows)
        # Sliding window (dynamic so alternating-layer configs can scan one
        # kernel): query at row sees keys in (row - W, row].
        valid = jnp.logical_and(valid, cols > rows - window_ref[0, 0])
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Renormalize the old accumulator, fold in the new block.
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [block_q, block_k]
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p,
            v_ref[0, 0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    if causal:
        # Skip K blocks entirely above the causal diagonal (q_start already
        # carries the traced absolute offset, so this stays exact under it).
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = acc_ref[:] / safe_l
        # A row with NO valid key anywhere (m never left the floor — e.g. a
        # padded query whose window misses the valid key range entirely)
        # accumulated exp(0)=1 garbage; emit zeros for it instead.
        out = jnp.where(m_ref[:] == NEG_INF, 0.0, out)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _decode_prefix_kernel(
    keylen_ref,  # [R, 1] int32 in SMEM: valid prefix length per request
    q_ref,  # [1, KVH, QR, D] — all of one request's query rows, per kv head
    k_ref,  # [1, block_k, KVH, D]
    v_ref,  # [1, block_k, KVH, D]
    o_ref,  # [1, KVH, QR, D] f32 (normalized within the prefix phase)
    m_o_ref,  # [1, KVH, QR] f32 running max (for the caller's logsumexp merge)
    l_o_ref,  # [1, KVH, QR] f32 softmax denominator at m
    acc_ref,  # VMEM scratch [KVH, QR, D] f32
    m_ref,  # VMEM scratch [KVH, QR] f32
    l_ref,  # VMEM scratch [KVH, QR] f32
    *,
    sm_scale: float,
    block_k: int,
    kv_heads: int,
):
    # Grid (R, key blocks): every block takes FULL (KVH, D) trailing axes, so
    # TPU tiling constraints are met for any head count / head dim, each KV
    # block streams from HBM exactly once, and the kv-head loop unrolls inside
    # the kernel over VMEM-resident data.
    r = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    QR = q_ref.shape[2]
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (QR, block_k), 1)
    valid = cols < keylen_ref[r, 0]

    for h in range(kv_heads):  # static unroll
        q = q_ref[0, h].astype(jnp.float32)  # [QR, D]
        k = k_ref[0, :, h, :].astype(jnp.float32)  # [block_k, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = jnp.where(valid, s * sm_scale, NEG_INF)  # [QR, block_k]

        m_prev = m_ref[h][:, None]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[h] = l_ref[h] * alpha[:, 0] + jnp.sum(p, axis=1)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p,
            v_ref[0, :, h, :].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[h] = m_new[:, 0]

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = acc_ref[:] / safe_l[:, :, None]
        m_o_ref[0] = m_ref[:]
        l_o_ref[0] = l_ref[:]


def decode_prefix_attention(
    q: jax.Array,
    prefix_k: jax.Array,
    prefix_v: jax.Array,
    prompt_lens: jax.Array,
    *,
    sm_scale: Optional[float] = None,
    block_k: int = 128,
    interpret: bool = False,
    mesh=None,
):
    """Decode-step attention over the SHARED-PREFIX KV, as a Pallas kernel.

    The decode hot loop splits attention into (a) the prompt prefix — hundreds
    of keys, stored once per request and shared by all its samples — and (b)
    the per-row generated tail (tens of keys). This kernel handles phase (a),
    where the HBM traffic is: the grid walks (request, kv head, key block) so
    each prefix block is streamed from HBM ONCE per (request, head) and hit by
    the request's whole [n_per*G, D] query tile on the MXU — versus one read
    per batch row in a naive layout. Phase (b) plus an exact logsumexp merge
    stay in XLA (`models/llama.py::_block`).

    q: [B, QH, D] (rows request-major, B % R == 0); prefix_k/v:
    [R, P, KVH, D]; prompt_lens: [R] valid key counts. Returns
    (out [B, QH, D] f32 — normalized within the prefix phase, m [B, QH],
    l [B, QH]) for the caller's merge. Under a multi-device ``mesh`` heads
    shard over the model axis and rows replicate over data (a request's rows
    are one query tile; splitting them would split the tile).
    """
    if multi_device(mesh):
        h_ax = mesh_axis(mesh, MODEL_AXIS, prefix_k.shape[2])
        local = functools.partial(
            decode_prefix_attention, sm_scale=sm_scale, block_k=block_k,
            interpret=interpret,
        )
        return shard_kernel(
            local, mesh,
            in_specs=(
                P(None, h_ax, None), P(None, None, h_ax, None),
                P(None, None, h_ax, None), P(),
            ),
            out_specs=(P(None, h_ax, None), P(None, h_ax), P(None, h_ax)),
        )(q, prefix_k, prefix_v, prompt_lens)
    B, QH, D = q.shape
    R, P_len, KVH, _ = prefix_k.shape
    G = QH // KVH
    n_per = B // R
    QR = n_per * G
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    block_k = min(block_k, P_len)

    # Request-major query tile per kv head: [R, KVH, n_per*G, D]. Row (r, h,
    # i*G + g) is batch row r*n_per + i, query head h*G + g.
    q4 = q.reshape(R, n_per, KVH, G, D).transpose(0, 2, 1, 3, 4).reshape(R, KVH, QR, D)

    grid = (R, pl.cdiv(P_len, block_k))
    kernel = functools.partial(
        _decode_prefix_kernel, sm_scale=scale, block_k=block_k, kv_heads=KVH
    )

    out, m, l = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((R, KVH, QR, D), jnp.float32),
            jax.ShapeDtypeStruct((R, KVH, QR), jnp.float32),
            jax.ShapeDtypeStruct((R, KVH, QR), jnp.float32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, 1), lambda r, ki: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, KVH, QR, D), lambda r, ki: (r, 0, 0, 0)),
            pl.BlockSpec((1, block_k, KVH, D), lambda r, ki: (r, ki, 0, 0)),
            pl.BlockSpec((1, block_k, KVH, D), lambda r, ki: (r, ki, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, KVH, QR, D), lambda r, ki: (r, 0, 0, 0)),
            pl.BlockSpec((1, KVH, QR), lambda r, ki: (r, 0, 0)),
            pl.BlockSpec((1, KVH, QR), lambda r, ki: (r, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((KVH, QR, D), jnp.float32),
            pltpu.VMEM((KVH, QR), jnp.float32),
            pltpu.VMEM((KVH, QR), jnp.float32),
        ],
        interpret=interpret,
        name="decode_prefix_attention",
    )(prompt_lens.astype(jnp.int32).reshape(R, 1), q4, prefix_k, prefix_v)

    def back(x):  # [R, KVH, QR, ...] -> [B, QH, ...]
        tail = x.shape[3:]
        x = x.reshape(R, KVH, n_per, G, *tail).swapaxes(1, 2)
        return x.reshape(B, QH, *tail)

    return back(out), back(m), back(l)


NO_WINDOW = 1 << 30


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    key_lengths: Optional[jax.Array] = None,
    sm_scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window=None,
    q_offset=None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    mesh=None,
    head_axis: str = MODEL_AXIS,
    batch_axis: Optional[str] = DATA_AXIS,
) -> jax.Array:
    """Pallas flash attention. q: [B, QH, Sq, D]; k/v: [B, KVH, Sk, D];
    key_lengths: [B] int32 — keys at positions >= length are masked (the
    padding pattern our engine produces; a prefix length rides SMEM where an
    arbitrary mask array would break TPU tiling). ``softcap`` applies Gemma-2's
    cap*tanh(s/cap) to the scaled scores. ``window`` limits each query to the
    last W keys — a static int or a TRACED scalar, so alternating-window
    configs (Gemma-2) can select W per scanned layer without recompiling.
    ``q_offset`` (static int or traced scalar) is the absolute position of
    query row 0 — the continuation-prefill mode, where a suffix of queries
    attends a key space rooted at position 0; causality and windows are
    evaluated at row + q_offset. Returns [B, QH, Sq, D].

    Sq/Sk pad to block multiples internally; GQA maps query head h onto kv head
    h // (QH // KVH) via the BlockSpec index maps. Under a multi-device
    ``mesh`` the kernel runs per shard: heads over ``head_axis`` (contiguous
    head blocks keep the q-head -> kv-head grouping), batch rows over
    ``batch_axis`` — model and data by default; Ulysses context parallelism
    puts the heads on its sequence axis instead.
    """
    B, QH, Sq, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    if key_lengths is None:
        key_lengths = jnp.full((B,), Sk, jnp.int32)
    key_lengths = key_lengths.astype(jnp.int32).reshape(B, 1)
    if window is None:
        window = NO_WINDOW
    window_arr = jnp.asarray(window, jnp.int32).reshape(1, 1)
    qoff_arr = jnp.asarray(0 if q_offset is None else q_offset, jnp.int32).reshape(1, 1)
    local = functools.partial(
        _flash_attention_local, causal=causal, sm_scale=sm_scale,
        softcap=softcap, block_q=block_q, block_k=block_k, interpret=interpret,
        # One kernel, two uses: a capture tells whole-prompt prefill from the
        # q_offset continuation by the kernel's name.
        name="flash_prefill" if q_offset is None else "flash_continue",
    )
    if multi_device(mesh):
        b_ax = mesh_axis(mesh, batch_axis, B) if batch_axis else None
        h_ax = mesh_axis(mesh, head_axis, KVH)
        heads = P(b_ax, h_ax, None, None)
        local = shard_kernel(
            local, mesh,
            in_specs=(heads, heads, heads, P(b_ax, None), P(), P()),
            out_specs=heads,
        )
    return local(q, k, v, key_lengths, window_arr, qoff_arr)


def _flash_attention_local(
    q, k, v, key_lengths, window_arr, qoff_arr, *,
    causal, sm_scale, softcap, block_q, block_k, interpret, name,
):
    """One shard's flash attention (the whole call on a single device)."""
    B, QH, Sq, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    G = QH // KVH
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    block_q = max(8, min(block_q, Sq))
    block_k = max(8, min(block_k, Sk))
    Sq_pad = pl.cdiv(Sq, block_q) * block_q
    Sk_pad = pl.cdiv(Sk, block_k) * block_k
    if Sk_pad != Sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sk_pad - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sk_pad - Sk), (0, 0)))
    if Sq_pad != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sq_pad - Sq), (0, 0)))

    grid = (B, QH, Sq_pad // block_q, Sk_pad // block_k)

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        softcap=softcap,
    )

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, QH, Sq_pad, D), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, 1), lambda b, h, qi, ki: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda b, h, qi, ki: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda b, h, qi, ki: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(key_lengths, window_arr, qoff_arr, q, k, v)

    return out[:, :, :Sq, :]


def gather_kv_pages(
    pool_k: jax.Array,
    pool_v: jax.Array,
    slot_idx: jax.Array,
    layer: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Block-table gather: materialize logical KV rows from a flat page pool.

    pool_k/pool_v: the whole pool, ``[L, total_pages * page_size, KVH, D]``;
    slot_idx: int32 flat slot indices of any shape (typically ``[B, S]`` —
    each row's block table expanded to per-position slots); layer: int32
    scalar, which layer's slots to read. Returns ``(k, v)`` shaped
    ``slot_idx.shape + (KVH, D)``.

    One gather from the pool seen as ``[L * flat, KVH, D]`` (a free reshape)
    at ``slot_idx + layer * flat``: the layer number is part of the address,
    so no layer's pool is ever sliced out ahead of the gather (``pool[layer]``
    first would copy ``flat * KVH * D`` elements per layer per step).

    Out-of-table positions point into the trash page (page 0) by convention;
    their values are arbitrary-but-finite and every consumer masks their
    scores to ``NEG_INF`` before the softmax max, so they contribute an exact
    0.0 to the output — which is what keeps the paged attention path
    byte-identical to the dense one.
    """
    idx = slot_idx + layer * pool_k.shape[1]
    return tuple(
        jnp.take(pool.reshape(-1, *pool.shape[2:]), idx, axis=0)
        for pool in (pool_k, pool_v)
    )


# ---------------------------------------------------------------------------
# A one-row page pool in its flat view
# ---------------------------------------------------------------------------
# A pool whose token holds one row and no V (a latent model's ``[L, flat, 1,
# W]``, W whole tiles of 128 lanes) is addressed one way: a cache row is row
# ``layer * flat + slot`` of the view ``[L * flat, W]`` (a reshape: the chip
# keeps such a row on the minor axis). A gather or a scatter there moves the
# rows it names. A scatter along the layer axis (``pool.at[:, slots]``) or rows
# that are no whole tiles make the compiler lay the whole pool out again around
# the op, and back (tests/test_tpu_compile.py). The model's step reads through
# :func:`pool_gather`; every writer is one of the page manager's movers
# (engine/paging.py), and no other module writes a pool.

def pool_index(pool_k: jax.Array, layers, slots) -> jax.Array:
    """Rows of the flat view for cache layers ``layers`` at flat slots
    ``slots`` (they broadcast)."""
    return layers * pool_k.shape[1] + slots


def pool_layers(pool_k: jax.Array, slots: jax.Array, count: Optional[int] = None) -> jax.Array:
    """:func:`pool_index` of ``slots`` [...] in each of the first ``count``
    cache layers (None: all of them) -> [count, ...]."""
    layers = jnp.arange(count or pool_k.shape[0], dtype=slots.dtype)
    return pool_index(pool_k, layers.reshape((-1,) + (1,) * slots.ndim), slots[None])


def pool_gather(pool_k: jax.Array, index: jax.Array, width: int) -> jax.Array:
    """The rows at ``index`` (:func:`pool_index`), cut back to the cache row's
    own ``width`` -> ``index.shape + (width,)``."""
    return jnp.take(pool_k.reshape(-1, pool_k.shape[-1]), index, axis=0)[..., :width]


def pool_scatter(pool_k: jax.Array, index: jax.Array, rows: jax.Array) -> jax.Array:
    """``rows`` ``[..., width]``, one a place of ``index``, written into the
    pool in one scatter, their pad lanes zeros."""
    stored = pool_k.shape[-1]
    rows = rows.reshape(-1, rows.shape[-1]).astype(pool_k.dtype)
    rows = jnp.pad(rows, ((0, 0), (0, stored - rows.shape[-1])))
    flat_view = pool_k.reshape(-1, stored).at[index.reshape(-1)].set(rows)
    return flat_view.reshape(pool_k.shape)
