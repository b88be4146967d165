"""Ring attention: exact sequence-parallel attention over a mesh axis.

Long-context support the reference cannot have (its sequence length is the
provider's problem, SURVEY.md §5): shard the sequence across devices, keep Q
local, and rotate K/V chunks around the ring with ``ppermute`` while
accumulating flash-style online softmax state. Every chunk transfer overlaps a
compute step and rides ICI; memory per device is O(S/P), so context scales
linearly with the ring size.

Causality is handled with global positions: device d owns query positions
[d*S_local, (d+1)*S_local); at ring step i it holds the K/V chunk of device
(d - i) mod P.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _pvary(x, axes):
    """Mark a replicated value as varying over ``axes`` (shard_map's
    varying-axes typing needs loop carries marked up front)."""
    return lax.pcast(x, axes, to="varying")


def _chunk_attention_update(q, k, v, q_pos, k_pos, causal, scale, acc, m, l):
    """One online-softmax accumulation step against a K/V chunk.

    q: [B, QH, Sq, D]; k/v: [B, KVH, Sk, D]; q_pos/k_pos: global positions.
    acc: [B, QH, Sq, D] f32; m/l: [B, QH, Sq, 1] f32.
    """
    B, QH, Sq, D = q.shape
    KVH = k.shape[1]
    G = QH // KVH

    qg = q.reshape(B, KVH, G, Sq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    s = (s * scale).reshape(B, QH, Sq, -1)
    if causal:
        mask = k_pos[None, :] <= q_pos[:, None]  # [Sq, Sk]
        s = jnp.where(mask[None, None], s, NEG_INF)

    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pg = p.reshape(B, KVH, G, Sq, -1)
    delta = jnp.einsum("bhgqk,bhkd->bhgqd", pg, v.astype(jnp.float32)).reshape(
        B, QH, Sq, D
    )
    acc_new = acc * alpha + delta
    return acc_new, m_new, l_new


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Per-shard body (call inside shard_map). q: [B, QH, S_local, D];
    k/v: [B, KVH, S_local, D] — all sharded on the sequence axis."""
    B, QH, S_local, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    p_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)

    q_pos = my_idx * S_local + jnp.arange(S_local)

    # pvary: the accumulators start identical on every device but become
    # device-varying inside the loop; shard_map's axis typing requires the
    # carry to be marked varying up front.
    acc0 = _pvary(jnp.zeros((B, QH, S_local, D), jnp.float32), (axis_name,))
    m0 = _pvary(jnp.full((B, QH, S_local, 1), NEG_INF, jnp.float32), (axis_name,))
    l0 = _pvary(jnp.zeros((B, QH, S_local, 1), jnp.float32), (axis_name,))

    perm = [(j, (j + 1) % p_size) for j in range(p_size)]

    def step(i, carry):
        acc, m, l, k_cur, v_cur = carry
        src = (my_idx - i) % p_size
        k_pos = src * S_local + jnp.arange(S_local)
        acc, m, l = _chunk_attention_update(
            q, k_cur, v_cur, q_pos, k_pos, causal, scale, acc, m, l
        )
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        return (acc, m, l, k_cur, v_cur)

    acc, m, l, _, _ = lax.fori_loop(0, p_size, step, (acc0, m0, l0, k, v))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l).astype(q.dtype)


def ring_decode_prefix(
    mesh: Mesh,
    q: jax.Array,
    prefix_k: jax.Array,
    prefix_v: jax.Array,
    prefix_len: jax.Array,
    *,
    seq_axis: str = "data",
    model_axis: str = "model",
    sm_scale: Optional[float] = None,
):
    """Decode-step attention over a SEQUENCE-SHARDED prefix: the ring decode
    half of O(S/P) long-context serving (the SP prefill already leaves its KV
    sharded over ``seq_axis``; this attends it in place instead of
    all-gathering a replicated copy).

    q: [B, QH, D] with B sharded over ``seq_axis`` (the decode batch layout)
    and QH over ``model_axis``; prefix_k/v: [1, S, KVH, D] with S over
    ``seq_axis`` and KVH over ``model_axis``; prefix_len: scalar valid key
    count. Queries stay put; K/V chunks rotate the ring (P-1 ppermute hops
    per decode step) with online-softmax accumulation. Returns
    (out [B, QH, D] f32 — normalized within the prefix phase, m [B, QH],
    l [B, QH]) — the same contract as ``decode_prefix_attention``, so the
    caller's exact logsumexp merge with the generated tail applies unchanged.
    """

    def local(q, pk, pv, plen):
        B_local, QH, D = q.shape
        S_local = pk.shape[1]
        KVH = pk.shape[2]
        G = QH // KVH
        scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
        p_size = lax.psum(1, seq_axis)
        my_idx = lax.axis_index(seq_axis)

        qg = q.astype(jnp.float32).reshape(B_local, KVH, G, D)
        # Accumulators become varying over every axis the inputs vary on
        # (sequence ring + model-sharded heads), so mark them up front.
        vary = tuple(a for a in (seq_axis, model_axis) if a in mesh.axis_names)
        acc0 = _pvary(jnp.zeros((B_local, QH, D), jnp.float32), vary)
        m0 = _pvary(jnp.full((B_local, QH), NEG_INF, jnp.float32), vary)
        l0 = _pvary(jnp.zeros((B_local, QH), jnp.float32), vary)

        perm = [(j, (j + 1) % p_size) for j in range(p_size)]

        def step(i, carry):
            acc, m, l, k_cur, v_cur = carry
            src = (my_idx - i) % p_size
            cols = src * S_local + jnp.arange(S_local)
            valid = cols < plen  # [S_local]
            # [B, KVH, G, D] x [S, KVH, D] -> [B, KVH, G, S]
            s = jnp.einsum(
                "bhgd,shd->bhgs", qg, k_cur[0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(valid[None, None, None, :], s, NEG_INF)
            s = s.reshape(B_local, QH, S_local)

            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m, m_cur)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[:, :, None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            delta = jnp.einsum(
                "bhgs,shd->bhgd",
                p.reshape(B_local, KVH, G, S_local),
                v_cur[0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ).reshape(B_local, QH, D)
            acc_new = acc * alpha[:, :, None] + delta
            k_nxt = lax.ppermute(k_cur, seq_axis, perm)
            v_nxt = lax.ppermute(v_cur, seq_axis, perm)
            return (acc_new, m_new, l_new, k_nxt, v_nxt)

        acc, m, l, _, _ = lax.fori_loop(0, p_size, step, (acc0, m0, l0, pk, pv))
        safe_l = jnp.where(l == 0.0, 1.0, l)
        return acc / safe_l[:, :, None], m, l

    q_spec = P(seq_axis, model_axis, None)
    kv_spec = P(None, seq_axis, model_axis, None)
    out_spec = (q_spec, P(seq_axis, model_axis), P(seq_axis, model_axis))
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P()),
        out_specs=out_spec,
    )(q, prefix_k, prefix_v, prefix_len)


def ring_verify_prefix(
    mesh: Mesh,
    q: jax.Array,
    prefix_k: jax.Array,
    prefix_v: jax.Array,
    prefix_len: jax.Array,
    *,
    seq_axis: str = "data",
    model_axis: str = "model",
    sm_scale: Optional[float] = None,
):
    """Multi-query sibling of :func:`ring_decode_prefix` for speculative
    VERIFY steps: score a whole draft block (Sq = lookahead + 1 queries per
    row) against the sequence-sharded prefix in one ring pass, so spec decode
    composes with sp_decode instead of falling back to the normal loop.

    Every verify query sits past the prompt, so the prefix phase is
    NON-CAUSAL — all Sq queries see exactly the ``prefix_len`` valid keys,
    which is the same per-chunk valid-column mask the decode op uses; the ring
    structure is otherwise identical (K/V chunks rotate, queries stay put,
    online-softmax accumulation, still P-1 hops per verify rather than per
    token — the whole point of verifying blocks).

    q: [B, QH, Sq, D] with B sharded over ``seq_axis`` and QH over
    ``model_axis``; prefix_k/v: [1, S, KVH, D] with S over ``seq_axis``;
    prefix_len: scalar valid key count. Returns (out [B, QH, Sq, D] f32 —
    normalized within the prefix phase, m [B, QH, Sq], l [B, QH, Sq]) for the
    caller's exact logsumexp merge with the generated-KV tail.
    """

    def local(q, pk, pv, plen):
        B_local, QH, Sq, D = q.shape
        S_local = pk.shape[1]
        KVH = pk.shape[2]
        G = QH // KVH
        scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
        p_size = lax.psum(1, seq_axis)
        my_idx = lax.axis_index(seq_axis)

        qg = q.astype(jnp.float32).reshape(B_local, KVH, G, Sq, D)
        vary = tuple(a for a in (seq_axis, model_axis) if a in mesh.axis_names)
        acc0 = _pvary(jnp.zeros((B_local, QH, Sq, D), jnp.float32), vary)
        m0 = _pvary(jnp.full((B_local, QH, Sq), NEG_INF, jnp.float32), vary)
        l0 = _pvary(jnp.zeros((B_local, QH, Sq), jnp.float32), vary)

        perm = [(j, (j + 1) % p_size) for j in range(p_size)]

        def step(i, carry):
            acc, m, l, k_cur, v_cur = carry
            src = (my_idx - i) % p_size
            cols = src * S_local + jnp.arange(S_local)
            valid = cols < plen  # [S_local]
            # [B, KVH, G, Sq, D] x [S, KVH, D] -> [B, KVH, G, Sq, S]
            s = jnp.einsum(
                "bhgqd,shd->bhgqs", qg, k_cur[0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
            s = s.reshape(B_local, QH, Sq, S_local)

            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m, m_cur)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            delta = jnp.einsum(
                "bhgqs,shd->bhgqd",
                p.reshape(B_local, KVH, G, Sq, S_local),
                v_cur[0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ).reshape(B_local, QH, Sq, D)
            acc_new = acc * alpha[..., None] + delta
            k_nxt = lax.ppermute(k_cur, seq_axis, perm)
            v_nxt = lax.ppermute(v_cur, seq_axis, perm)
            return (acc_new, m_new, l_new, k_nxt, v_nxt)

        acc, m, l, _, _ = lax.fori_loop(0, p_size, step, (acc0, m0, l0, pk, pv))
        safe_l = jnp.where(l == 0.0, 1.0, l)
        return acc / safe_l[..., None], m, l

    q_spec = P(seq_axis, model_axis, None, None)
    kv_spec = P(None, seq_axis, model_axis, None)
    out_spec = (q_spec, P(seq_axis, model_axis, None), P(seq_axis, model_axis, None))
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P()),
        out_specs=out_spec,
    )(q, prefix_k, prefix_v, prefix_len)


def ring_attention(
    mesh: Mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    seq_axis: str = "data",
    causal: bool = True,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """shard_map wrapper: q [B, QH, S, D], k/v [B, KVH, S, D] with S sharded
    over ``seq_axis``. Exact (same result as full attention), memory O(S/P)."""
    spec = P(None, None, seq_axis, None)

    fn = functools.partial(
        ring_attention_local, axis_name=seq_axis, causal=causal, sm_scale=sm_scale
    )
    sharded = jax.shard_map(
        lambda q, k, v: fn(q, k, v),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return sharded(q, k, v)


def suffix_prefix_attention(
    mesh: Mesh,
    q: jax.Array,
    prefix_k: jax.Array,
    prefix_v: jax.Array,
    prefix_len: jax.Array,
    *,
    seq_axis: str = "data",
    model_axis: str = "model",
    sm_scale: Optional[float] = None,
):
    """Partial-softmax attention of REPLICATED suffix queries over a
    SEQUENCE-SHARDED prefix — the attention half of continuation prefill on an
    SP-resident cache entry (VERDICT r3 #6).

    q: [1, QH, Sq, D] replicated over ``seq_axis`` (QH over ``model_axis``);
    prefix_k/v: [1, S, KVH, D] with S over ``seq_axis``; prefix_len: scalar
    valid key count (the REUSED prefix length — may be shorter than the
    entry's stored length). Each device scores its local chunk and the
    partials merge with ONE pmax+psum logsumexp reduction (a one-shot
    continuation has no pipeline to overlap, so the ring rotation's P-1 hops
    buy nothing here). Returns (acc [1, QH, Sq, D] f32 — UNNORMALIZED,
    m [1, QH, Sq], l [1, QH, Sq]) for the caller's exact logsumexp merge with
    the suffix's causal self-attention. Never materializes more than O(S/P)
    prefix per device.
    """

    def local(q, pk, pv, plen):
        B, QH, Sq, D = q.shape
        S_loc, KVH = pk.shape[1], pk.shape[2]
        G = QH // KVH
        scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
        my_idx = lax.axis_index(seq_axis)
        cols = my_idx * S_loc + jnp.arange(S_loc)
        valid = cols < plen

        qg = q.astype(jnp.float32).reshape(B, KVH, G, Sq, D)
        s = jnp.einsum(
            "bhgqd,shd->bhgqs", qg, pk[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        s = s.reshape(B, QH, Sq, S_loc)
        m_loc = jnp.max(s, axis=-1)
        p = jnp.exp(s - m_loc[..., None])
        # A device whose chunk has NO valid columns contributes l=0 (p rows
        # are exp(NEG_INF - NEG_INF) = 1 garbage otherwise).
        any_valid = jnp.any(valid)
        p = jnp.where(any_valid, p, 0.0)
        l_loc = jnp.sum(p, axis=-1)
        acc_loc = jnp.einsum(
            "bhgqs,shd->bhgqd",
            p.reshape(B, KVH, G, Sq, S_loc),
            pv[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ).reshape(B, QH, Sq, D)

        m_g = lax.pmax(m_loc, seq_axis)
        w = jnp.exp(m_loc - m_g)
        l_g = lax.psum(l_loc * w, seq_axis)
        acc_g = lax.psum(acc_loc * w[..., None], seq_axis)
        return acc_g, m_g, l_g

    q_spec = P(None, model_axis, None, None)
    kv_spec = P(None, seq_axis, model_axis, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P()),
        out_specs=(q_spec, P(None, model_axis, None), P(None, model_axis, None)),
    )(q, prefix_k, prefix_v, prefix_len)


def scatter_into_ring(
    mesh: Mesh,
    prefix: jax.Array,
    suffix: jax.Array,
    start: jax.Array,
    total_len: jax.Array,
    *,
    seq_axis: str = "data",
    model_axis: str = "model",
) -> jax.Array:
    """Write REPLICATED suffix rows into a SEQUENCE-SHARDED buffer in place:
    global row ``start + i`` takes ``suffix[:, i]`` for i < total_len - start;
    every other row keeps its value. prefix: [1, S, KVH, D] with S over
    ``seq_axis``; suffix: [1, Ssuf, KVH, D] replicated over ``seq_axis``.
    Each device updates only its own chunk — O(S/P), no gather."""

    def local(pk, sk, start, total):
        S_loc = pk.shape[1]
        my_idx = lax.axis_index(seq_axis)
        cols = my_idx * S_loc + jnp.arange(S_loc)
        idx = cols - start
        take = (idx >= 0) & (idx < sk.shape[1]) & (cols < total)
        vals = jnp.take(sk[0], jnp.clip(idx, 0, sk.shape[1] - 1), axis=0)
        return jnp.where(take[None, :, None, None], vals[None], pk)

    spec = P(None, seq_axis, model_axis, None)
    rep = P(None, None, model_axis, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, rep, P(), P()),
        out_specs=spec,
    )(prefix, suffix, start, total_len)
