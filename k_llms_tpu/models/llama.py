"""Pure-functional Llama-family transformer (GQA + RoPE + RMSNorm + SwiGLU).

TPU-first design notes:
- Parameters are a pytree with all layers STACKED on a leading axis and the
  layer stack applied with ``lax.scan`` — one traced block regardless of depth,
  so XLA compiles fast and fuses identically for 2 or 32 layers.
- All matmuls are laid out (tokens, features) x (features, features') so they
  tile straight onto the MXU; bf16 weights/activations, f32 norm/softmax
  accumulation.
- KV caches are preallocated [L, B, S, KVH, D] and updated with
  ``lax.dynamic_update_slice_in_dim`` — static shapes, no data-dependent
  control flow, jit-stable across decode steps.
- The decode path supports a SHARED-PREFIX cache: the prompt (identical across
  the n consensus samples) is prefilled once at batch=1 and every sample
  attends to it broadcast, so prompt KV is stored once instead of n times —
  the HBM win that lets n=32 consensus fit on one chip.

This file replaces the reference's model layer, which is the remote OpenAI API
(`/root/reference/k_llms/resources/completions/completions.py:73`).
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig
from .quant import qdot, qeinsum

Params = Dict[str, Any]


class KVCache(NamedTuple):
    """Stacked per-layer cache: k/v are [paging layers, batch, max_len, kv_heads,
    head_dim] (every layer, or a hybrid stack's attention layers)."""

    k: jax.Array
    v: jax.Array

    @property
    def max_len(self) -> int:
        return self.k.shape[2]


def init_cache(config: ModelConfig, batch: int, max_len: int, dtype=None) -> KVCache:
    dtype = dtype or config.jax_dtype
    heads, k_width, v_width = config.cache_widths
    shape = (config.paging_layers, batch, max_len, heads)
    return KVCache(k=jnp.zeros(shape + (k_width,), dtype), v=jnp.zeros(shape + (v_width,), dtype))


def init_state(config: ModelConfig, rows: int) -> Dict[str, jax.Array]:
    """The recurrent state ``rows`` rows hold beside their cache, zeroed:
    ``{}`` for a model without any (see ``ModelConfig.state_shapes``), so it
    adds no operand to a program it is passed to."""
    return {k: tuple(jnp.zeros(shape, dtype) for _ in range(m))
            for k, (m, shape, dtype) in config.state_shapes(rows).items()}


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_params(config: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random (scaled-normal) initialization; real checkpoints come from
    k_llms_tpu.models.loader."""
    if config.is_latent:
        from . import latent

        return latent.init_params(config, key, dtype)
    if config.is_hybrid:
        from . import hybrid

        return hybrid.init_params(config, key, dtype)
    dtype = dtype or config.jax_dtype
    H, I, V = config.hidden_size, config.intermediate_size, config.vocab_size
    L, Q, KV = config.num_layers, config.q_dim, config.kv_dim

    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    ks = jax.random.split(k_layers, 8)
    # Offset norms (Gemma) store w with effective scale (1 + w): identity is 0.
    norm_init = jnp.zeros if config.norm_offset else jnp.ones
    layers = {
        "attn_norm": norm_init((L, H), dtype),
        "wq": normal(ks[0], (L, H, Q), 1.0 / math.sqrt(H)),
        "wk": normal(ks[1], (L, H, KV), 1.0 / math.sqrt(H)),
        "wv": normal(ks[2], (L, H, KV), 1.0 / math.sqrt(H)),
        "wo": normal(ks[3], (L, Q, H), 1.0 / math.sqrt(Q)),
        "mlp_norm": norm_init((L, H), dtype),
    }
    if config.num_experts > 0:  # Mixtral family: per-expert MLP + router
        E = config.num_experts
        layers["w_router"] = normal(ks[7], (L, H, E), 1.0 / math.sqrt(H))
        layers["w_gate"] = normal(ks[4], (L, E, H, I), 1.0 / math.sqrt(H))
        layers["w_up"] = normal(ks[5], (L, E, H, I), 1.0 / math.sqrt(H))
        layers["w_down"] = normal(ks[6], (L, E, I, H), 1.0 / math.sqrt(I))
    else:
        layers["w_gate"] = normal(ks[4], (L, H, I), 1.0 / math.sqrt(H))
        layers["w_up"] = normal(ks[5], (L, H, I), 1.0 / math.sqrt(H))
        layers["w_down"] = normal(ks[6], (L, I, H), 1.0 / math.sqrt(I))
    if config.qkv_bias:  # Qwen2 family
        layers["bq"] = jnp.zeros((L, Q), dtype)
        layers["bk"] = jnp.zeros((L, KV), dtype)
        layers["bv"] = jnp.zeros((L, KV), dtype)
    if config.post_block_norms:  # Gemma-2: norms on attention/MLP outputs
        layers["post_attn_norm"] = norm_init((L, H), dtype)
        layers["post_mlp_norm"] = norm_init((L, H), dtype)
    params: Params = {
        "embed": normal(k_embed, (V, H), 1.0 / math.sqrt(H)),
        "layers": layers,
        "final_norm": norm_init((H,), dtype),
        "lm_head": normal(k_head, (H, V), 1.0 / math.sqrt(H)),
    }
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float, offset: bool = False) -> jax.Array:
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    w = (1.0 + weight.astype(jnp.float32)).astype(x.dtype) if offset else weight
    return (x32 * scale).astype(x.dtype) * w


def _softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 soft capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(x / cap)


def _activation(config: ModelConfig, x: jax.Array) -> jax.Array:
    if config.act == "gelu":  # GeGLU (Gemma): tanh-approximate gelu
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def _moe_mlp(config: ModelConfig, layer: Params, h: jax.Array) -> jax.Array:
    """Mixtral top-k token-choice MoE, computed densely over the stacked expert
    weights — one einsum per projection, no ragged gather/scatter, so XLA tiles
    it straight onto the MXU and GSPMD turns the expert axis sharding into
    expert parallelism. Router softmax is over the selected top-k only
    (Mixtral semantics), scattered back to a [B,S,E] combine weight."""
    E, K = config.num_experts, config.num_experts_per_tok
    router_logits = (h @ layer["w_router"]).astype(jnp.float32)  # [B,S,E]
    top_vals, top_idx = lax.top_k(router_logits, K)
    top_w = jax.nn.softmax(top_vals, axis=-1)  # [B,S,K]
    combine = (jax.nn.one_hot(top_idx, E, dtype=jnp.float32) * top_w[..., None]).sum(
        axis=-2
    )  # [B,S,E]

    gate = _activation(config, qeinsum("bsh,ehi->bsei", h, layer["w_gate"]))
    up = qeinsum("bsh,ehi->bsei", h, layer["w_up"])
    expert_out = qeinsum("bsei,eih->bseh", gate * up, layer["w_down"])
    return jnp.einsum("bseh,bse->bsh", expert_out, combine.astype(expert_out.dtype))


def _rope_inv_freq(d: int, theta: float, scaling) -> jax.Array:
    """Per-pair inverse frequencies, with optional llama3-style scaling
    (HF rope_type="llama3"; Llama-3.1/3.2 checkpoints): wavelengths past
    original_ctx/low_freq divide by ``factor``, short ones stay, the band
    between interpolates smoothly."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if scaling is None:
        return inv_freq
    if scaling[0] == "yarn":
        # YaRN (DeepSeek-V3 form): pairs that turn more than beta_fast times
        # within the original context keep their frequency, those that turn
        # fewer than beta_slow times divide it by ``factor``, a linear ramp
        # over the pair index between. cos/sin carry mscale/mscale_all_dim,
        # which is 1 for every registered model; the score scale carries
        # mscale^2 (ModelConfig.attn_scale).
        _, factor, orig_ctx, beta_fast, beta_slow, _ = scaling

        def correction_dim(rotations):
            return d * math.log(orig_ctx / (rotations * 2.0 * math.pi)) / (2.0 * math.log(theta))

        low = max(math.floor(correction_dim(beta_fast)), 0)
        high = min(math.ceil(correction_dim(beta_slow)), d - 1)
        ramp = jnp.clip(
            (jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0
        )
        return inv_freq * (1.0 - ramp) + (inv_freq / factor) * ramp
    factor, low_freq_factor, high_freq_factor, orig_ctx = scaling
    wavelen = 2.0 * math.pi / inv_freq
    low_wavelen = orig_ctx / low_freq_factor
    high_wavelen = orig_ctx / high_freq_factor
    smooth = (orig_ctx / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor
    )
    interpolated = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    scaled = jnp.where(wavelen > low_wavelen, inv_freq / factor, interpolated)
    return jnp.where(wavelen < high_wavelen, inv_freq, scaled)


def rope_embed(
    x: jax.Array, positions: jax.Array, theta: float, scaling=None
) -> jax.Array:
    """Rotary embedding. x: [B, S, heads, D], positions: [B, S]."""
    d = x.shape[-1]
    inv_freq = _rope_inv_freq(d, theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def rope_lanes(
    x: jax.Array, positions: jax.Array, theta: float, scaling=None
) -> jax.Array:
    """:func:`rope_embed` without splitting the head: one expression over all
    D lanes, ``a·c + b·s`` with ``a = (x1, x1)``, ``b = (-x2, x2)`` (both from
    a roll of ``x`` by D/2), ``c = (cos, sin)`` and ``s = (sin, cos)``. Lane
    by lane that is the split form's own expression in its own operand order
    (which matters where the compiler fuses the first product into a
    multiply-add), so the value is the same bit for bit: ``(-x2)·sin`` is
    ``-(x2·sin)`` exactly.

    The GQA block's form. Under the split and the concatenate after it,
    layout assignment wants the q and k projections heads-major, and copies
    each int8 weight out of the scanned stack every layer to get that; here
    the products read the weights where they lie."""
    d = x.shape[-1]
    inv_freq = _rope_inv_freq(d, theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, D/2]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    c = jnp.concatenate([cos, sin], axis=-1)
    s = jnp.concatenate([sin, cos], axis=-1)
    first = jnp.arange(d) < d // 2
    xf = x.astype(jnp.float32)
    rolled = jnp.roll(xf, d // 2, axis=-1)  # (x2, x1)
    a = jnp.where(first, xf, rolled)
    b = jnp.where(first, -rolled, xf)
    return (a * c + b * s).astype(x.dtype)


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: [B, Sq, QH, D], k: [B, Sk, KVH, D] -> scores [B, QH, Sq, Sk]."""
    B, Sq, QH, D = q.shape
    KVH = k.shape[2]
    G = QH // KVH
    qg = q.reshape(B, Sq, KVH, G, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    return scores.reshape(B, QH, Sq, k.shape[1])


def _gqa_scores_shared(q: jax.Array, k: jax.Array) -> jax.Array:
    """Shared-prefix scores: q [B, Sq, QH, D] vs R shared key sets
    k [R, Sk, KVH, D], batch rows grouped request-major (row b belongs to
    request b // (B//R)). Each prefix is stored ONCE and shared by its
    request's samples via a reshaped einsum — no materialized per-sample
    copies (the HBM saving behind n=32 on one chip), and no gather when
    several requests decode coalesced in one batch. R=1 is the single-request
    case (one prompt broadcast over all n samples)."""
    B, Sq, QH, D = q.shape
    R, Sk, KVH, _ = k.shape
    G = QH // KVH
    qg = q.reshape(R, B // R, Sq, KVH, G, D)
    scores = jnp.einsum("rnqhgd,rkhd->rnhgqk", qg, k, preferred_element_type=jnp.float32)
    return scores.reshape(B, QH, Sq, Sk)


def _gqa_values(weights: jax.Array, v: jax.Array) -> jax.Array:
    """weights: [B, QH, Sq, Sk], v: [B, Sk, KVH, D] -> [B, Sq, QH, D] f32.

    V stays in its cache dtype (bf16) with f32 MXU accumulation — an explicit
    astype(f32) here would materialize a double-width copy of the whole cache
    every decode step (HBM traffic is the decode bottleneck)."""
    B, QH, Sq, Sk = weights.shape
    KVH = v.shape[2]
    G = QH // KVH
    wg = weights.astype(v.dtype).reshape(B, KVH, G, Sq, Sk)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", wg, v, preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, QH, v.shape[3])


def _gqa_values_shared(weights: jax.Array, v: jax.Array) -> jax.Array:
    """weights: [B, QH, Sq, Sk], R shared value sets v: [R, Sk, KVH, D] ->
    [B, Sq, QH, D] f32. Row grouping mirrors :func:`_gqa_scores_shared`."""
    B, QH, Sq, Sk = weights.shape
    R, _, KVH, _ = v.shape
    G = QH // KVH
    wg = weights.astype(v.dtype).reshape(R, B // R, KVH, G, Sq, Sk)
    out = jnp.einsum("rnhgqk,rkhd->rnqhgd", wg, v, preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, QH, v.shape[3])


@jax.named_scope("attn_qkv")
def _attn_qkv(
    config: ModelConfig, layer: Params, x: jax.Array, positions: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared attention head: pre-norm -> QKV projection (+ optional biases)
    -> head split -> RoPE. Factored out of :func:`_block` so the paged twin
    (:func:`_block_paged`) runs the exact same ops — bit-identity between the
    dense and paged decode paths holds by construction, not by replication."""
    B, Sq, _ = x.shape
    h = rms_norm(x, layer["attn_norm"], config.rms_eps, config.norm_offset)
    q, k, v = qdot(h, layer["wq"]), qdot(h, layer["wk"]), qdot(h, layer["wv"])
    if "bq" in layer:  # Qwen2-family QKV biases (static per-config structure)
        q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
    q = q.reshape(B, Sq, config.num_heads, config.head_dim)
    k = k.reshape(B, Sq, config.num_kv_heads, config.head_dim)
    v = v.reshape(B, Sq, config.num_kv_heads, config.head_dim)

    if config.use_rope:
        q = rope_lanes(q, positions, config.rope_theta, config.rope_scaling)
        k = rope_lanes(k, positions, config.rope_theta, config.rope_scaling)
    return q, k, v


@jax.named_scope("mlp")
def _mlp_sublayer(config: ModelConfig, layer: Params, x: jax.Array) -> jax.Array:
    """Post-attention MLP sublayer with its residual (dense MLP or MoE; none
    in a hybrid stack's attention block, whose layer is the mixer alone)."""
    if "mlp_norm" not in layer:
        return x
    offset = config.norm_offset
    h = rms_norm(x, layer["mlp_norm"], config.rms_eps, offset)
    if "w_router" in layer:  # MoE (Mixtral)
        out = _moe_mlp(config, layer, h)
    else:
        gate = _activation(config, qdot(h, layer["w_gate"]))
        up = qdot(h, layer["w_up"])
        out = qdot(gate * up, layer["w_down"])
    if "post_mlp_norm" in layer:
        out = rms_norm(out, layer["post_mlp_norm"], config.rms_eps, offset)
    return x + out


@jax.named_scope("attn_out")
def _attn_residual(
    config: ModelConfig, layer: Params, x: jax.Array, attn: jax.Array
) -> jax.Array:
    """Attention output projection plus the block's first residual."""
    out = qdot(attn, layer["wo"])
    if "post_attn_norm" in layer:
        out = rms_norm(out, layer["post_attn_norm"], config.rms_eps, config.norm_offset)
    return x + out


def _merge_prefix_tail(q, cache_k, cache_v, key_mask, scale, out_p, m_p, l_p):
    """Exact logsumexp merge of a prefix-phase partial (normalized out,
    running max m, denominator l — each [B, QH, Sq]-leading; single-query
    callers pass Sq=1) with the per-row generated-KV tail computed in XLA.
    Returns the merged attention [B, Sq, QH, D] f32 (caller casts/reshapes)."""
    s_g = _gqa_scores(q, cache_k) * scale  # [B, QH, Sq, G]
    s_g = jnp.where(key_mask[:, None, :, :], s_g, jnp.finfo(jnp.float32).min)
    m_g = jnp.max(s_g, axis=-1)  # [B, QH, Sq]
    p_g = jnp.exp(s_g - m_g[..., None])
    l_g = jnp.sum(p_g, axis=-1)  # [B, QH, Sq]
    out_g = _gqa_values(p_g, cache_v).transpose(0, 2, 1, 3)  # [B, QH, Sq, D]

    m = jnp.maximum(m_p, m_g)
    a_p = jnp.exp(m_p - m)
    a_g = jnp.exp(m_g - m)
    denom = l_p * a_p + l_g * a_g
    merged = (
        out_p * (l_p * a_p)[..., None] + out_g * a_g[..., None]
    ) / jnp.where(denom == 0.0, 1.0, denom)[..., None]
    return merged.transpose(0, 2, 1, 3)  # [B, Sq, QH, D]


def _block(
    config: ModelConfig,
    layer: Params,
    x: jax.Array,
    positions: jax.Array,
    kv: Tuple[jax.Array, jax.Array],
    write_index: Optional[jax.Array],
    key_mask: jax.Array,
    prefix_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    prefix_mask: Optional[jax.Array] = None,
    key_lengths: Optional[jax.Array] = None,
    prefix_lengths: Optional[jax.Array] = None,
    window_value=None,
    sp_ring_mesh=None,
    mesh=None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One transformer block over (possibly cached) keys.

    x: [B, Sq, H]; kv: layer cache (k, v) each [B, Smax, KVH, D];
    write_index: scalar slot where this call's k/v are written (None = positions
    0..Sq, i.e. prefill); key_mask: [B|1, Sq, Smax] additive-mask booleans for the
    self cache; prefix_kv/prefix_mask: optional shared-prompt cache [R, P, KVH, D]
    and [1|B, Sq, P]; prefix_lengths: [R] valid prefix key counts (decode only —
    enables the Pallas shared-prefix decode kernel). ``sp_ring_mesh``: a Mesh
    marking the prefix KV as SEQUENCE-SHARDED over the mesh's data axis —
    decode attends it in place via ring attention (O(S/P) per device) instead
    of the replicated-prefix paths. ``mesh``: the engine's device mesh, which
    the Pallas kernels need to run per shard (None on one device).
    """
    from ..ops.attention import resolve_attention_impl

    B, Sq, H = x.shape
    scale = config.attn_scale
    prefill_impl = resolve_attention_impl(config.attention_impl)
    decode_impl = resolve_attention_impl(config.decode_attention_impl)

    q, k, v = _attn_qkv(config, layer, x, positions)

    cache_k, cache_v = kv
    with jax.named_scope("kv_write"):
        if write_index is None:
            cache_k = lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), 0, axis=1)
            cache_v = lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), 0, axis=1)
        elif getattr(write_index, "ndim", 0) == 1:
            # Per-ROW write offsets (speculative verify: rows have different
            # generated lengths) — a vmapped dynamic_update_slice per batch row.
            row_update = jax.vmap(
                lambda c, kk, off: lax.dynamic_update_slice_in_dim(c, kk, off, axis=0)
            )
            cache_k = row_update(cache_k, k.astype(cache_k.dtype), write_index)
            cache_v = row_update(cache_v, v.astype(cache_v.dtype), write_index)
        else:
            cache_k = lax.dynamic_update_slice_in_dim(
                cache_k, k.astype(cache_k.dtype), write_index, axis=1
            )
            cache_v = lax.dynamic_update_slice_in_dim(
                cache_v, v.astype(cache_v.dtype), write_index, axis=1
            )

    def mlp(y: jax.Array) -> jax.Array:
        return _mlp_sublayer(config, layer, y)

    def attn_out(attn: jax.Array) -> jax.Array:
        return _attn_residual(config, layer, x, attn)

    # Full-sequence prefill takes the Pallas flash path: prefix-length masking,
    # causal structure, attention softcap (Gemma-2) and sliding windows
    # (Mistral "all", Gemma-2 "alternating" via a dynamic per-layer window
    # scalar) are all kernel-supported.
    if (
        prefill_impl != "xla"
        and write_index is None
        and prefix_kv is None
        and key_lengths is not None
    ):
        from ..ops.attention import flash_attention

        attn = flash_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=True,
            key_lengths=key_lengths,
            sm_scale=scale,
            softcap=config.attn_softcap,
            window=window_value,
            interpret=prefill_impl == "flash_interpret",
            mesh=mesh,
        ).transpose(0, 2, 1, 3)
        attn = attn.astype(x.dtype).reshape(B, Sq, config.q_dim)
        return mlp(attn_out(attn)), (cache_k, cache_v)

    # Continuation prefill (prefix-cache partial hit): suffix queries at
    # absolute positions write_index.. attend the full cache through the same
    # flash kernel in q_offset mode — no [Sq, Smax] score tensor in HBM, so
    # no 1 GB masked-XLA cap and no full-prefill fallback at long suffixes.
    # Keys beyond the written range are zeros from the padded cache seed and
    # sit above every valid query's causal horizon.
    if (
        prefill_impl != "xla"
        and write_index is not None
        and getattr(write_index, "ndim", 0) == 0
        and Sq > 1
        and prefix_kv is None
    ):
        from ..ops.attention import flash_attention

        attn = flash_attention(
            q.transpose(0, 2, 1, 3),
            cache_k.transpose(0, 2, 1, 3),
            cache_v.transpose(0, 2, 1, 3),
            causal=True,
            sm_scale=scale,
            softcap=config.attn_softcap,
            window=window_value,
            q_offset=write_index,
            interpret=prefill_impl == "flash_interpret",
            mesh=mesh,
        ).transpose(0, 2, 1, 3)
        attn = attn.astype(x.dtype).reshape(B, Sq, config.q_dim)
        return mlp(attn_out(attn)), (cache_k, cache_v)

    def _merge_tail(out_p, m_p, l_p):
        attn = _merge_prefix_tail(
            q, cache_k, cache_v, key_mask, scale, out_p, m_p, l_p
        )
        return attn.astype(x.dtype).reshape(B, Sq, config.q_dim)

    # Decode/verify step against a SEQUENCE-SHARDED prefix (ring attention):
    # the SP prefill left its KV sharded over the mesh's data axis; chunks
    # rotate the ring with online-softmax accumulation, so the prefix is never
    # gathered and long-context serving stays O(S/P) end-to-end. Sq == 1 is
    # the plain decode step; Sq > 1 is a speculative VERIFY block scoring the
    # whole draft window in one ring pass (all verify queries sit past the
    # prompt, so prefix visibility is non-causal and the same valid-column
    # masking applies).
    if (
        sp_ring_mesh is not None
        and write_index is not None
        and prefix_kv is not None
        and prefix_lengths is not None
        and config.attn_softcap is None
        and config.sliding_window is None
    ):
        from ..ops.ring_attention import ring_decode_prefix, ring_verify_prefix

        plen = prefix_lengths.reshape(-1)[0]  # ring path is single-request (R=1)
        if Sq == 1:
            out_p, m_p, l_p = ring_decode_prefix(
                sp_ring_mesh, q[:, 0], prefix_kv[0], prefix_kv[1], plen,
                sm_scale=scale,
            )
            out_p = out_p[:, :, None]  # [B, QH, 1, D]
            m_p = m_p[:, :, None]
            l_p = l_p[:, :, None]
        else:
            out_p, m_p, l_p = ring_verify_prefix(
                sp_ring_mesh,
                q.transpose(0, 2, 1, 3),  # [B, QH, Sq, D]
                prefix_kv[0],
                prefix_kv[1],
                plen,
                sm_scale=scale,
            )
        return mlp(attn_out(_merge_tail(out_p, m_p, l_p))), (cache_k, cache_v)

    # Decode step against a shared prefix: the Pallas decode kernel streams
    # each prefix KV block from HBM once per (request, kv head) and hits it
    # with the request's whole query tile; the short generated tail plus an
    # exact logsumexp merge stay in XLA. Gated to tile-friendly shapes
    # (query rows per request >= one sublane tile).
    if (
        decode_impl != "xla"
        and config.sliding_window is None
        and config.attn_softcap is None
        and write_index is not None
        and Sq == 1
        and prefix_kv is not None
        and prefix_lengths is not None
        and (B // prefix_kv[0].shape[0]) * (config.num_heads // config.num_kv_heads) >= 8
    ):
        from ..ops.attention import decode_prefix_attention

        pk, pv = prefix_kv
        out_p, m_p, l_p = decode_prefix_attention(
            q[:, 0],
            pk,
            pv,
            prefix_lengths,
            sm_scale=scale,
            interpret=decode_impl == "flash_interpret",
            mesh=mesh,
        )
        return (
            mlp(attn_out(_merge_tail(out_p[:, :, None], m_p[:, :, None], l_p[:, :, None]))),
            (cache_k, cache_v),
        )

    scores = _gqa_scores(q, cache_k) * scale  # [B, QH, Sq, Smax] f32
    if config.attn_softcap is not None:
        scores = _softcap(scores, config.attn_softcap)
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(key_mask[:, None, :, :], scores, neg)

    if prefix_kv is not None:
        pk, pv = prefix_kv
        p_scores = _gqa_scores_shared(q, pk) * scale  # [B, QH, Sq, P]
        if config.attn_softcap is not None:
            p_scores = _softcap(p_scores, config.attn_softcap)
        p_scores = jnp.where(prefix_mask[:, None, :, :], p_scores, neg)
        all_scores = jnp.concatenate([p_scores, scores], axis=-1)
        weights = jax.nn.softmax(all_scores, axis=-1)
        P = pk.shape[1]
        attn = _gqa_values_shared(weights[..., :P], pv) + _gqa_values(weights[..., P:], cache_v)
    else:
        weights = jax.nn.softmax(scores, axis=-1)
        attn = _gqa_values(weights, cache_v)

    attn = attn.astype(x.dtype).reshape(B, Sq, config.q_dim)
    return mlp(attn_out(attn)), (cache_k, cache_v)


def _local_layer_flags(config: ModelConfig) -> Optional[jax.Array]:
    """[L] bool: layer uses the windowed mask. None when no per-layer mixing
    (full causal everywhere, or every layer windowed)."""
    if not config.mixes_windowed_layers:
        return None
    return jnp.asarray([w is not None for w in config.layer_windows[: config.num_layers]])


def _apply_stack(
    config: ModelConfig,
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    cache: KVCache,
    write_index: Optional[jax.Array],
    key_mask: jax.Array,
    prefix: Optional[KVCache] = None,
    prefix_mask: Optional[jax.Array] = None,
    key_lengths: Optional[jax.Array] = None,
    key_mask_global: Optional[jax.Array] = None,
    prefix_mask_global: Optional[jax.Array] = None,
    prefix_lengths: Optional[jax.Array] = None,
    sp_ring_mesh=None,
    mesh=None,
    aux: Optional[dict] = None,
    valid_len: Optional[jax.Array] = None,
    state: Optional[dict] = None,
) -> Tuple[jax.Array, KVCache]:
    """Scan the layer stack. cache k/v: [L, B, Smax, KVH, D].

    When layers alternate local/global attention (Gemma-2), ``key_mask`` /
    ``prefix_mask`` hold the WINDOWED masks, the ``*_global`` twins hold the
    full-causal ones, and a scanned per-layer flag picks between them.

    ``aux``: a dict the caller wants the stack's own counts in (what a model
    counts is its own affair: see models/latent.py; the dense block counts
    nothing and leaves it empty). A latent model's stack is models/latent.py's.

    ``valid_len`` [B] and ``state`` are for a stack with recurrent state
    (models/hybrid.py): how many of each row's positions are tokens, and the
    rows' state going in and coming out (a dict like ``aux``). Every other
    stack ignores both.
    """
    if config.is_hybrid:
        from . import hybrid

        return hybrid.apply_stack(
            config, params, x, positions, cache, write_index, key_mask, valid_len,
            key_lengths=key_lengths, prefix=prefix, state=state, aux=aux,
            sp_ring_mesh=sp_ring_mesh, mesh=mesh, key_mask_global=key_mask_global,
        )
    if config.is_latent:
        from . import latent

        return latent.apply_stack(
            config, params, x, positions, cache, write_index, key_mask,
            prefix=prefix, prefix_mask=prefix_mask, aux=aux,
            sp_ring_mesh=sp_ring_mesh, mesh=mesh,
        )
    local_flags = _local_layer_flags(config) if key_mask_global is not None else None

    def body(carry, scanned):
        x = carry
        flag = scanned.get("flag")
        if flag is None:
            km, pm = key_mask, prefix_mask
            # Static per-model window ("all" layers or none).
            window_value = config.sliding_window
        else:
            km = jnp.where(flag, key_mask, key_mask_global)
            pm = (
                jnp.where(flag, prefix_mask, prefix_mask_global)
                if prefix_mask is not None
                else None
            )
            # Alternating layers: the scanned flag picks this layer's window
            # (a traced scalar — the flash kernel takes it dynamically).
            from ..ops.attention import NO_WINDOW

            window_value = jnp.where(
                flag, jnp.int32(config.sliding_window), jnp.int32(NO_WINDOW)
            )
        x, new_kv = _block(
            config,
            scanned["layers"],
            x,
            positions,
            scanned["kv"],
            write_index,
            km,
            prefix_kv=scanned.get("prefix"),
            prefix_mask=pm,
            key_lengths=key_lengths,
            prefix_lengths=prefix_lengths,
            window_value=window_value,
            sp_ring_mesh=sp_ring_mesh,
            mesh=mesh,
        )
        return x, new_kv

    # Optional scanned slots (shared prefix, per-layer window flags) are
    # present-or-absent dict keys — one scan covers every combination with a
    # statically known pytree structure.
    xs = {"layers": params["layers"], "kv": (cache.k, cache.v)}
    if prefix is not None:
        xs["prefix"] = (prefix.k, prefix.v)
    if local_flags is not None:
        xs["flag"] = local_flags
    x, new_kv = lax.scan(body, x, xs)

    return x, KVCache(k=new_kv[0], v=new_kv[1])


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def _embed(config: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    if config.embed_scale:  # Gemma: normalize embedding magnitude
        x = x * jnp.asarray(math.sqrt(config.hidden_size), x.dtype)
    return x


def _final_norm(config: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    if config.is_hybrid:
        from . import hybrid

        return hybrid.stack_norm(config, x, params["final_norm"])
    return rms_norm(x, params["final_norm"], config.rms_eps, config.norm_offset)


@jax.named_scope("lm_head")
def _logits(config: ModelConfig, params: Params, h: jax.Array) -> jax.Array:
    if config.tie_embeddings:  # the head is the embedding table, read in place
        logits = jnp.einsum("...h,vh->...v", h, params["embed"],
                            preferred_element_type=jnp.float32)
    else:
        logits = qdot(h, params["lm_head"]).astype(jnp.float32)
    if config.logit_softcap is not None:
        logits = _softcap(logits, config.logit_softcap)
    return logits


def encode(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,
    pad_mask: jax.Array,
    mesh=None,
) -> jax.Array:
    """Final hidden states [B,S,H] — the on-device embedding provider only
    mean-pools hidden states. Under ``jax.jit`` the unused logits output (the
    lm_head projection, the single largest matmul in the network) is pruned by
    XLA dead-code elimination, so this thin wrapper costs nothing."""
    return forward(config, params, tokens, pad_mask, mesh=mesh)[1]


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,
    pad_mask: jax.Array,
    mesh=None,
    with_module: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence causal forward (no cache). Returns (logits f32 [B,S,V],
    final hidden states [B,S,H]); ``with_module`` (a model with a next-token
    module, unpadded rows) adds the module's logits [B,S-1,V], row i for token
    i+2."""
    B, S = tokens.shape
    positions = jnp.cumsum(pad_mask.astype(jnp.int32), axis=1) - 1
    positions = jnp.maximum(positions, 0)
    x = _embed(config, params, tokens)

    causal = jnp.tril(jnp.ones((S, S), bool))
    key_mask_global = None
    if config.sliding_window is not None:  # query i sees keys (i-W, i]
        band = causal & jnp.triu(jnp.ones((S, S), bool), -(config.sliding_window - 1))
        if config.mixes_windowed_layers:
            key_mask_global = causal[None, :, :] & pad_mask[:, None, :].astype(bool)
        causal = band
    key_mask = causal[None, :, :] & pad_mask[:, None, :].astype(bool)

    cache = init_cache(config, B, S)
    key_lengths = pad_mask.astype(jnp.int32).sum(axis=1)
    x, _ = _apply_stack(
        config,
        params,
        x,
        positions,
        cache,
        None,
        key_mask,
        key_lengths=key_lengths,
        key_mask_global=key_mask_global,
        mesh=mesh,
        valid_len=key_lengths,  # a recurrent state takes the padding to be on the right
    )
    h = _final_norm(config, params, x)
    logits = _logits(config, params, h)
    if with_module:
        from . import latent

        return logits, h, _logits(config, params, latent.mtp_forward(config, params, tokens, x))
    return logits, h


def prefill(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,
    prompt_len: jax.Array,
    mesh=None,
    aux: Optional[dict] = None,
    state: Optional[dict] = None,
) -> Tuple[jax.Array, KVCache]:
    """Prefill the shared prompt at batch=1. tokens: [1, S] (bucket-padded on the
    right), prompt_len: scalar valid length. Returns (last-token logits [1, V],
    prefix KVCache [L, 1, S, KVH, D]). ``aux`` and ``state`` as in
    :func:`_apply_stack`: a model with recurrent state leaves the state after
    the prompt's last token in ``state``."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = emb = _embed(config, params, tokens)

    causal = jnp.tril(jnp.ones((S, S), bool))
    valid = jnp.arange(S)[None, :] < prompt_len  # [1, S]
    key_mask_global = None
    if config.sliding_window is not None:
        band = causal & jnp.triu(jnp.ones((S, S), bool), -(config.sliding_window - 1))
        if config.mixes_windowed_layers:
            key_mask_global = causal[None, :, :] & valid[:, None, :]
        causal = band
    key_mask = causal[None, :, :] & valid[:, None, :]

    cache = init_cache(config, B, S)
    key_lengths = jnp.broadcast_to(prompt_len, (B,)).astype(jnp.int32)
    x, cache = _apply_stack(
        config,
        params,
        x,
        positions,
        cache,
        None,
        key_mask,
        key_lengths=key_lengths,
        key_mask_global=key_mask_global,
        mesh=mesh,
        aux=aux,
        valid_len=key_lengths,
        state=state,
    )
    if config.num_nextn_predict_layers:
        from . import latent

        cache = latent.mtp_ingest(
            config, params, emb, x, positions, cache, None, key_lengths - 1, state)
    h = _final_norm(config, params, x)
    last = jnp.take_along_axis(h, (prompt_len - 1).reshape(B, 1, 1).astype(jnp.int32), axis=1)
    logits = _logits(config, params, last[:, 0, :])
    return logits, cache


def prefill_continue(
    config: ModelConfig,
    params: Params,
    suffix_tokens: jax.Array,
    cache: KVCache,
    prefix_len: jax.Array,
    total_len: jax.Array,
    mesh=None,
    aux: Optional[dict] = None,
    state: Optional[dict] = None,
) -> Tuple[jax.Array, KVCache]:
    """Prefill a prompt SUFFIX against an already-computed prompt-prefix KV —
    the prefix-caching path (the reference has no model layer; its provider
    re-reads the full prompt every request).

    ``cache`` [L, 1, Btot, KVH, D] holds the reused prefix KV at positions
    0..prefix_len (rest arbitrary); suffix_tokens: [1, Sq] right-padded; the
    suffix KV is written in place at positions prefix_len.. and the UPDATED
    cache is returned — directly the decode loop's shared-prefix cache and
    the next cache entry. Attention masks are built over absolute positions,
    so sliding windows (static or alternating) and softcaps work unchanged.
    Returns (last-valid-token logits [1, V], updated KVCache). ``state``: the
    recurrent state after the prefix going in, after the suffix's last valid
    token coming out (a model without any ignores it).
    """
    B, Sq = suffix_tokens.shape
    Btot = cache.k.shape[2]
    positions = prefix_len + jnp.broadcast_to(jnp.arange(Sq)[None, :], (B, Sq))
    x = emb = _embed(config, params, suffix_tokens)

    rows = prefix_len + jnp.arange(Sq)[None, :, None]  # absolute query positions
    cols = jnp.arange(Btot)[None, None, :]
    causal_abs = cols <= rows  # [1, Sq, Btot]
    key_mask_global = None
    if config.sliding_window is not None:
        band = causal_abs & (cols > rows - config.sliding_window)
        if config.mixes_windowed_layers:
            key_mask_global = causal_abs
        causal_abs = band
    x, cache = _apply_stack(
        config,
        params,
        x,
        positions,
        cache,
        prefix_len,
        causal_abs,
        key_mask_global=key_mask_global,
        mesh=mesh,
        aux=aux,
        valid_len=jnp.broadcast_to(total_len - prefix_len, (B,)).astype(jnp.int32),
        state=state,
    )
    if config.num_nextn_predict_layers:
        from . import latent

        cache = latent.mtp_ingest(
            config, params, emb, x, positions, cache, prefix_len,
            jnp.broadcast_to(total_len - prefix_len - 1, (B,)), state)
    h = _final_norm(config, params, x)
    last_row = (total_len - prefix_len - 1).reshape(B, 1, 1).astype(jnp.int32)
    last = jnp.take_along_axis(h, last_row, axis=1)
    logits = _logits(config, params, last[:, 0, :])
    return logits, cache


def prefill_chunk_step(
    config: ModelConfig,
    params: Params,
    chunk_tokens: jax.Array,
    cache: KVCache,
    cursor: jax.Array,
    valid_len: jax.Array,
    mesh=None,
    aux: Optional[dict] = None,
    state: Optional[dict] = None,
) -> Tuple[jax.Array, KVCache]:
    """Extend a partially-filled prompt prefix by one chunk — the unit of
    chunked prefill (Sarathi-style: prompt ingestion interleaved with decode
    steps instead of one monolithic prefill).

    ``chunk_tokens``: [1, C] the next C prompt tokens, right-padded;
    ``cache``: [L, 1, B, KVH, D] staging cache holding positions 0..cursor;
    ``cursor``: scalar absolute offset of this chunk's first token;
    ``valid_len``: scalar count of non-pad tokens in the chunk.

    Semantically a chunk IS a prompt-suffix continuation, so this delegates to
    :func:`prefill_continue` — same ``_apply_stack``/``_block`` branches, same
    absolute-position masks — which is what makes the final chunk's logits
    byte-identical to whole-prompt prefill (pinned by the chunked-on/off
    differential in tests/test_chunked_prefill.py). Returns (last-valid-token
    logits [1, V] — meaningful only on the final chunk — and the updated
    cache).
    """
    return prefill_continue(
        config, params, chunk_tokens, cache, cursor, cursor + valid_len,
        mesh=mesh, aux=aux, state=state,
    )


def prefill_chunk_step_paged(
    config: ModelConfig,
    params: Params,
    chunk_tokens: jax.Array,
    cache: KVCache,
    cursor: jax.Array,
    valid_len: jax.Array,
    mesh=None,
    aux: Optional[dict] = None,
    state: Optional[dict] = None,
) -> Tuple[jax.Array, KVCache, jax.Array, jax.Array]:
    """Paged twin of :func:`prefill_chunk_step`: identical compute against the
    dense staging cache (byte-identity comes for free from the shared path),
    plus the chunk's freshly written KV columns sliced out so the caller can
    ``scatter_tokens`` them into the row's reserved page run at its current
    offset. Returns (logits [1, V], updated cache, k_cols [L, C, KVH, D],
    v_cols [L, C, KVH, D])."""
    C = chunk_tokens.shape[1]
    logits, cache = prefill_chunk_step(
        config, params, chunk_tokens, cache, cursor, valid_len, mesh=mesh, aux=aux,
        state=state,
    )
    k_cols = jax.lax.dynamic_slice_in_dim(cache.k[:, 0], cursor, C, axis=1)
    v_cols = jax.lax.dynamic_slice_in_dim(cache.v[:, 0], cursor, C, axis=1)
    return logits, cache, k_cols, v_cols


def decode_step(
    config: ModelConfig,
    params: Params,
    token: jax.Array,
    step: jax.Array,
    prompt_len: jax.Array,
    gen_cache: KVCache,
    prefix: KVCache,
    sp_ring_mesh=None,
    mesh=None,
) -> Tuple[jax.Array, KVCache]:
    """One decode step for all samples against their shared prefix(es).

    token: [B] current tokens; step: scalar decode index (0-based); prompt_len:
    scalar, or [R] vector of per-request prompt lengths when R coalesced
    requests decode together (rows grouped request-major, B % R == 0);
    gen_cache: [L, B, G, KVH, D]; prefix: [L, R, P, KVH, D].
    ``sp_ring_mesh``: prefix is sequence-sharded over the mesh's data axis;
    attend it via ring decode (see ``_block``). Returns (logits f32 [B, V],
    updated gen_cache).
    """
    B = token.shape[0]
    G = gen_cache.max_len
    P = prefix.max_len

    # Per-ROW prompt length: scalar (legacy single-request) broadcasts to all
    # rows; an [R] vector repeats over each request's contiguous row group.
    pl = jnp.asarray(prompt_len, jnp.int32).reshape(-1)
    pl_row = jnp.repeat(pl, B // pl.shape[0], total_repeat_length=B)  # [B]

    positions = (pl_row + step)[:, None]
    x = _embed(config, params, token[:, None])

    # Self (generated) keys: slots 0..step inclusive are valid after the write.
    self_mask = (jnp.arange(G)[None, None, :] <= step) & jnp.ones((B, 1, 1), bool)
    # Prefix keys: positions < the row's prompt_len are valid.
    prefix_mask = jnp.arange(P)[None, None, :] < pl_row[:, None, None]
    self_mask_global = prefix_mask_global = None
    if config.sliding_window is not None:
        # Query position is prompt_len + step; key position k is visible iff
        # q_pos - k_pos < W. Gen slot s sits at position prompt_len + s.
        W = config.sliding_window
        if config.mixes_windowed_layers:
            self_mask_global, prefix_mask_global = self_mask, prefix_mask
        self_mask = self_mask & (jnp.arange(G)[None, None, :] > step - W)
        prefix_mask = prefix_mask & (
            jnp.arange(P)[None, None, :] > pl_row[:, None, None] + step - W
        )

    x, gen_cache = _apply_stack(
        config,
        params,
        x,
        positions,
        gen_cache,
        step,
        self_mask,
        prefix=prefix,
        prefix_mask=prefix_mask,
        key_mask_global=self_mask_global,
        prefix_mask_global=prefix_mask_global,
        prefix_lengths=pl,
        sp_ring_mesh=sp_ring_mesh,
        mesh=mesh,
    )
    h = _final_norm(config, params, x)
    logits = _logits(config, params, h[:, 0, :])
    return logits, gen_cache


def verify_step(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,
    lengths: jax.Array,
    prompt_len: jax.Array,
    gen_cache: KVCache,
    prefix: KVCache,
    sp_ring_mesh=None,
    mesh=None,
    aux: Optional[dict] = None,
) -> Tuple[jax.Array, KVCache]:
    """Speculative-decoding verification: score k+1 tokens per row in ONE
    forward (the draft-tree trunk of prompt-lookup decoding).

    tokens: [B, Sq] — row b's last accepted token followed by its drafts;
    lengths: [B] per-row generated-token counts (the write offset into the
    row's gen cache slots); prompt_len: scalar or [R] as in decode_step.
    KVs for all Sq positions are written at per-row offsets; acceptance-
    rejected slots simply get overwritten by a later verify.
    ``sp_ring_mesh``: as in :func:`decode_step` — the prefix KV is
    sequence-sharded over the mesh's data axis and each block verifies the
    draft window against it via ring attention. Returns
    (logits f32 [B, Sq, V] — logits[b, j] conditions on tokens[b, :j+1] —
    and the updated gen_cache).
    """
    B, Sq = tokens.shape
    G = gen_cache.max_len
    P = prefix.max_len

    pl = jnp.asarray(prompt_len, jnp.int32).reshape(-1)
    pl_row = jnp.repeat(pl, B // pl.shape[0], total_repeat_length=B)  # [B]
    lengths = lengths.astype(jnp.int32)

    j = jnp.arange(Sq)[None, :]  # query index within the verify block
    positions = pl_row[:, None] + lengths[:, None] + j  # [B, Sq]
    x = _embed(config, params, tokens)

    # Gen slot s holds the row's s-th generated token: query j sees slots
    # <= lengths + j (its own freshly written slot included, like decode).
    s = jnp.arange(G)[None, None, :]
    self_mask = s <= (lengths[:, None] + j)[:, :, None]  # [B, Sq, G]
    c = jnp.arange(P)[None, None, :]
    prefix_mask = (c < pl_row[:, None, None]) & jnp.ones((B, Sq, 1), bool)
    self_mask_global = prefix_mask_global = None
    if config.sliding_window is not None:
        W = config.sliding_window
        if config.mixes_windowed_layers:
            self_mask_global, prefix_mask_global = self_mask, prefix_mask
        qpos_gen = (lengths[:, None] + j)[:, :, None]  # query's gen position
        self_mask = self_mask & (s > qpos_gen - W)
        prefix_mask = prefix_mask & (c > positions[:, :, None] - W)

    x, gen_cache = _apply_stack(
        config,
        params,
        x,
        positions,
        gen_cache,
        lengths,
        self_mask,
        prefix=prefix,
        prefix_mask=prefix_mask,
        key_mask_global=self_mask_global,
        prefix_mask_global=prefix_mask_global,
        prefix_lengths=pl,
        sp_ring_mesh=sp_ring_mesh,
        mesh=mesh,
        aux=aux,
    )
    h = _final_norm(config, params, x)
    logits = _logits(config, params, h)
    return logits, gen_cache


# ---------------------------------------------------------------------------
# Paged KV path (block-table gather over a flat page pool)
# ---------------------------------------------------------------------------

def _paged_attend(
    config: ModelConfig,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pool_kv: KVCache,
    layer_idx: jax.Array,
    prefix_idx: jax.Array,
    gen_idx: jax.Array,
    write_index: jax.Array,
    key_mask: jax.Array,
    prefix_mask: jax.Array,
    prefix_lengths: Optional[jax.Array] = None,
    page_tables=None,
    page_size: Optional[int] = None,
    attn_impl: str = "xla",
    mesh=None,
    window: Optional[int] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """The attention of :func:`_block_paged`, for any block that has its
    queries, keys and values ``[B, Sq, heads, D]`` (the parallel block's too,
    models/hybrid.py): the fused kernel or the XLA reference over the pool ->
    (attn ``[B, Sq, heads * D]``, this step's (k_col, v_col) in pool dtype)."""
    from ..ops.attention import resolve_attention_impl
    from ..ops.paged_attention import (
        paged_decode_attention_pallas,
        paged_decode_attention_xla,
    )

    B, Sq = q.shape[:2]
    scale = config.attn_scale
    k_col = k[:, 0].astype(pool_kv.k.dtype)
    v_col = v[:, 0].astype(pool_kv.v.dtype)

    with jax.named_scope("paged_attn"):
        if (
            attn_impl in ("pallas", "pallas_interpret")
            and Sq == 1
            and page_tables is not None
            and prefix_lengths is not None
            and config.attn_softcap is None
        ):
            prefix_pages, gen_pages, gen_phase = page_tables
            plen = jnp.asarray(prefix_lengths, jnp.int32).reshape(-1)
            pl_row = jnp.repeat(plen, B // plen.shape[0], total_repeat_length=B)
            attn = paged_decode_attention_pallas(
                q[:, 0],
                pool_kv.k,
                pool_kv.v,
                layer_idx,
                prefix_pages,
                gen_pages,
                gen_phase,
                k_col,
                v_col,
                pl_row,
                write_index.astype(jnp.int32),
                page_size=page_size,
                sm_scale=scale,
                window=window,
                interpret=attn_impl == "pallas_interpret",
                mesh=mesh,
            )[:, None]  # [B, 1, QH, D]
        else:
            # Same gate as _block's decode_prefix_attention branch, so a config
            # running flash decode on dense caches keeps it on paged ones.
            decode_impl = resolve_attention_impl(config.decode_attention_impl)
            flash_prefix = (
                decode_impl != "xla"
                and config.sliding_window is None
                and config.attn_softcap is None
                and Sq == 1
                and prefix_lengths is not None
                and (B // prefix_idx.shape[0]) * (config.num_heads // config.num_kv_heads) >= 8
            )
            attn = paged_decode_attention_xla(
                q,
                pool_kv.k,
                pool_kv.v,
                layer_idx,
                prefix_idx,
                gen_idx,
                k,
                v,
                write_index,
                key_mask,
                prefix_mask,
                sm_scale=scale,
                softcap=config.attn_softcap,
                prefix_lengths=prefix_lengths,
                flash_prefix=decode_impl if flash_prefix else None,
                mesh=mesh,
            )
    return attn.astype(q.dtype).reshape(B, Sq, -1), (k_col, v_col)


def _block_paged(
    config: ModelConfig,
    layer: Params,
    x: jax.Array,
    positions: jax.Array,
    pool_kv: KVCache,
    layer_idx: jax.Array,
    prefix_idx: jax.Array,
    gen_idx: jax.Array,
    write_index: jax.Array,
    key_mask: jax.Array,
    prefix_mask: jax.Array,
    prefix_lengths: Optional[jax.Array] = None,
    page_tables=None,
    page_size: Optional[int] = None,
    attn_impl: str = "xla",
    mesh=None,
    window: Optional[int] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Paged twin of :func:`_block` for the ``Sq == 1`` decode/verify step.

    KV comes from the whole flat page pool (``pool_kv``,
    ``[L, flat, KVH, D]``) through block tables and this layer's number
    (``layer_idx``, int32 scalar): the pool is never sliced, the attention op
    addresses (layer, slot) itself. Attention runs in
    ``ops/paged_attention.py`` — the fused Pallas kernel when ``attn_impl``
    selects it (block-table gather folded into the K/V load, no materialized
    copy) or the byte-identical XLA reference otherwise. Returns
    ``(x, (k_col, v_col))`` where the cols ``[B, KVH, D]``
    are this step's freshly computed column in pool dtype — the caller
    scatters them into the pool (the old path extracted the same column from
    the written gather transient via ``take_along_axis``; taking it straight
    from the projection is bit-identical and skips the round-trip).
    ``window``: this layer's sliding window (``config.layer_windows``), static:
    the kernel's walk and mask take it; the XLA path has it in its masks.
    """
    q, k, v = _attn_qkv(config, layer, x, positions)
    attn, cols = _paged_attend(
        config, q, k, v, pool_kv, layer_idx, prefix_idx, gen_idx, write_index, key_mask,
        prefix_mask, prefix_lengths=prefix_lengths, page_tables=page_tables, page_size=page_size,
        attn_impl=attn_impl, mesh=mesh, window=window)
    x = _attn_residual(config, layer, x, attn)
    return _mlp_sublayer(config, layer, x), cols


def _apply_stack_paged(
    config: ModelConfig,
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    pool_kv: KVCache,
    prefix_idx: jax.Array,
    gen_idx: jax.Array,
    write_index: jax.Array,
    key_mask: jax.Array,
    prefix_mask: jax.Array,
    key_mask_global: Optional[jax.Array] = None,
    prefix_mask_global: Optional[jax.Array] = None,
    prefix_lengths: Optional[jax.Array] = None,
    attn_impl: str = "xla",
    page_size: Optional[int] = None,
    mesh=None,
    aux: Optional[dict] = None,
    valid_len: Optional[jax.Array] = None,
    state: Optional[dict] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Paged twin of :func:`_apply_stack`: per-layer KV lives in a flat page
    pool addressed through block tables instead of dense caches.

    pool_kv k/v: ``[L, total_pages * page_size, KVH, D]``; prefix_idx /
    gen_idx: int32 ``[B|R, P]`` / ``[B, G]`` flat pool slots for each row's
    prompt and generated positions (an ``[R, P]`` prefix table is shared
    request-major like the dense shared-prefix cache; out-of-table positions
    map into the trash page and are masked). Each layer runs
    :func:`_block_paged` on the WHOLE pool plus its own layer number (the scan
    body closes over the pool; only the number is scanned), which fuses the
    block-table gather into attention — on the Pallas path nothing dense is
    ever materialized; on the XLA reference the gather happens INSIDE the
    layer scan so the transient is the gathered rows of one layer, 1/L of a
    dense cache. No layer's pool is sliced out on either path: a slice ahead
    of the attention op is a copy of ``flat * KVH * D`` elements per layer
    per step, whatever the pages hold.

    Unmasked pool values are bit-identical to dense cache contents and masked
    slots contribute an exact 0.0 through the softmax (scores forced to
    ``finfo.min`` before the max; ``exp`` underflows to 0; ``0 * finite ==
    0``), so the whole stack is byte-identical to :func:`_apply_stack` on
    equal inputs. Returns ``(x, k_cols, v_cols)`` with the cols
    ``[L, B, KVH, D]`` — each row's freshly written KV column for the
    caller's pool scatter. ``valid_len`` and ``state`` as in
    :func:`_apply_stack`.
    """
    if config.is_hybrid:
        from . import hybrid

        return hybrid.apply_stack_paged(
            config, params, x, positions, pool_kv, prefix_idx, gen_idx, write_index,
            key_mask, prefix_mask, valid_len, prefix_lengths=prefix_lengths,
            attn_impl=attn_impl, page_size=page_size, state=state, aux=aux, mesh=mesh,
            key_mask_global=key_mask_global, prefix_mask_global=prefix_mask_global,
        )
    if config.is_latent:
        from . import latent

        return latent.apply_stack_paged(
            config, params, x, positions, pool_kv, prefix_idx, gen_idx,
            write_index, key_mask, prefix_mask, aux=aux, mesh=mesh,
            prefix_lengths=prefix_lengths, attn_impl=attn_impl, page_size=page_size,
        )
    local_flags = _local_layer_flags(config) if key_mask_global is not None else None

    page_tables = None
    # The kernel's window is static in its call and this scan has one body for
    # every layer: a stack that mixes windowed and global layers stays on the
    # XLA masks here (resolve_paged_attention_impl says so by name); one whose
    # layers are unrolled (models/hybrid.py) hands each call its own window.
    if attn_impl in ("pallas", "pallas_interpret") and not config.mixes_windowed_layers:
        from ..ops.paged_attention import paged_attention_page_tables

        # Layer-invariant: hoisted out of the scan so the slot->page
        # arithmetic runs once per step, not once per layer.
        page_tables = paged_attention_page_tables(prefix_idx, gen_idx, page_size)

    def body(carry, scanned):
        x = carry
        flag = scanned.get("flag")
        if flag is None:
            km, pm = key_mask, prefix_mask
        else:
            km = jnp.where(flag, key_mask, key_mask_global)
            pm = jnp.where(flag, prefix_mask, prefix_mask_global)
        x, cols = _block_paged(
            config,
            scanned["layers"],
            x,
            positions,
            pool_kv,
            scanned["layer"],
            prefix_idx,
            gen_idx,
            write_index,
            km,
            pm,
            prefix_lengths=prefix_lengths,
            page_tables=page_tables,
            page_size=page_size,
            attn_impl=attn_impl,
            mesh=mesh,
            window=config.sliding_window,
        )
        return x, cols

    xs = {
        "layers": params["layers"],
        "layer": jnp.arange(pool_kv.k.shape[0], dtype=jnp.int32),
    }
    if local_flags is not None:
        xs["flag"] = local_flags
    x, cols = lax.scan(body, x, xs)
    return x, cols[0], cols[1]


def paged_verify_step(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,
    lengths: jax.Array,
    prompt_len: jax.Array,
    pool_kv: KVCache,
    prefix_idx: jax.Array,
    gen_idx: jax.Array,
    attn_impl: str = "xla",
    page_size: Optional[int] = None,
    mesh=None,
    aux: Optional[dict] = None,
    state: Optional[dict] = None,
    active: Optional[jax.Array] = None,
    return_hidden: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Paged twin of :func:`verify_step` — the continuous decode loop's step
    when its slots hold block tables into a shared page pool instead of dense
    per-row caches. ``Sq == 1`` in every loop but the drafted one, whose step
    verifies ``Sq == 2`` positions a row (the latent block only; k_cols then
    carry an ``Sq`` axis behind the rows') and asks, by ``return_hidden``,
    for the stack's output before the final norm as a fourth result.

    tokens: [B, Sq] current tokens; lengths: [B] generated counts (also each
    row's write offset into its gen slots); prompt_len: scalar or [R];
    pool_kv: the flat page pool ``[L, flat, KVH, D]``; prefix_idx [B|R, P] /
    gen_idx [B, G]: flat pool slots per logical position. Masks are built
    EXACTLY as in :func:`verify_step` (same shapes, same predicates), so the
    two paths select identical attention branches and produce bit-identical
    logits — pinned by tests/test_paged_differential.py. ``attn_impl``
    selects the fused attention ("xla" reference, "pallas" kernel, or the
    tests-only "pallas_interpret"); ``page_size`` is required for the Pallas
    paths (slot->page table derivation). ``state``: the rows' recurrent
    state, advanced by this token for the rows that are ``active`` ([B] bool;
    None: all) and left as it is for the others. Returns (logits f32
    [B, 1, V], k_cols, v_cols [L, B, KVH, D]).
    """
    B, Sq = tokens.shape
    G = gen_idx.shape[1]
    P = prefix_idx.shape[1]

    pl = jnp.asarray(prompt_len, jnp.int32).reshape(-1)
    pl_row = jnp.repeat(pl, B // pl.shape[0], total_repeat_length=B)  # [B]
    lengths = lengths.astype(jnp.int32)

    j = jnp.arange(Sq)[None, :]
    positions = pl_row[:, None] + lengths[:, None] + j  # [B, Sq]
    x = _embed(config, params, tokens)

    s = jnp.arange(G)[None, None, :]
    self_mask = s <= (lengths[:, None] + j)[:, :, None]  # [B, Sq, G]
    c = jnp.arange(P)[None, None, :]
    prefix_mask = (c < pl_row[:, None, None]) & jnp.ones((B, Sq, 1), bool)
    self_mask_global = prefix_mask_global = None
    if config.sliding_window is not None:
        W = config.sliding_window
        if config.mixes_windowed_layers:
            self_mask_global, prefix_mask_global = self_mask, prefix_mask
        qpos_gen = (lengths[:, None] + j)[:, :, None]
        self_mask = self_mask & (s > qpos_gen - W)
        prefix_mask = prefix_mask & (c > positions[:, :, None] - W)

    x, k_cols, v_cols = _apply_stack_paged(
        config,
        params,
        x,
        positions,
        pool_kv,
        prefix_idx,
        gen_idx,
        lengths,
        self_mask,
        prefix_mask,
        key_mask_global=self_mask_global,
        prefix_mask_global=prefix_mask_global,
        prefix_lengths=pl,
        attn_impl=attn_impl,
        page_size=page_size,
        mesh=mesh,
        aux=aux,
        valid_len=active,
        state=state,
    )
    h = _final_norm(config, params, x)
    logits = _logits(config, params, h)
    if return_hidden:
        return logits, k_cols, v_cols, x
    return logits, k_cols, v_cols


def paged_draft_step(
    config: ModelConfig,
    params: Params,
    hidden: jax.Array,
    tokens: jax.Array,
    lengths: jax.Array,
    prompt_len: jax.Array,
    pool_kv: KVCache,
    prefix_idx: jax.Array,
    gen_idx: jax.Array,
    aux: Optional[dict] = None,
    attn_impl: str = "xla",
    page_size: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The next-token module's step through the page pool (models/latent.py),
    beside :func:`paged_verify_step` in the drafted loop: ``tokens`` [B, Sq]
    at generated offsets ``lengths + j`` (the slots the module's cache rows
    land in), ``hidden`` [B, Sq, H] the stack's output before its final norm
    one position earlier. Positions, masks and ``attn_impl`` as there, with
    position 0, which holds nothing in the module's layer, masked out of the
    prompt's rows. Returns (logits f32 [B, Sq, V] for the token after each,
    the module's cache rows [B, Sq, 1, width])."""
    from . import latent

    B, Sq = tokens.shape
    pl = jnp.asarray(prompt_len, jnp.int32).reshape(-1)
    pl_row = jnp.repeat(pl, B // pl.shape[0], total_repeat_length=B)  # [B]
    lengths = lengths.astype(jnp.int32)
    offsets = lengths[:, None] + jnp.arange(Sq)[None, :]  # [B, Sq]
    self_mask = jnp.arange(gen_idx.shape[1])[None, None, :] <= offsets[:, :, None]
    c = jnp.arange(prefix_idx.shape[1])[None, None, :]
    prefix_mask = (c >= 1) & (c < pl_row[:, None, None]) & jnp.ones((B, Sq, 1), bool)
    h, cols = latent.mtp_paged(
        config, params, hidden, tokens, pl_row[:, None] + offsets, pool_kv,
        prefix_idx, gen_idx, lengths, self_mask, prefix_mask, aux=aux,
        prompt_lens=pl_row, attn_impl=attn_impl, page_size=page_size,
    )
    with jax.named_scope("mtp_head"):
        return _logits(config, params, h), cols
