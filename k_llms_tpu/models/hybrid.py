"""The hybrid stack: Mamba-2 mixers, routed experts and GQA attention by pattern.

The Nemotron-H family's stack (``model_type`` ``nemotron_h``), behind the same
entry points as every other model: ``models/llama.py``'s ``forward``,
``prefill``, ``prefill_continue``, the chunk steps and ``paged_verify_step``
hand the stack to :func:`apply_stack` / :func:`apply_stack_paged` here when
``config.is_hybrid``. Every block is ``x <- x + mixer(RMSNorm(x))`` with ONE
mixer, chosen by the layer's character in ``config.layer_pattern``:

- ``M`` **Mamba-2** (:func:`mamba_mixer`). ``[z | xBC | dt] = W_in h``; a
  causal depthwise conv over ``xBC`` then silu; ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; a gated group RMSNorm
  (``y * silu(z)`` first); ``W_out``. A call over several tokens runs the
  chunked (SSD) form in blocks of ``config.mamba_chunk``, a call over one the
  recurrence itself; both start from the row's state and stop at its
  ``valid_len`` (padded positions get ``dt = 0``: no decay, no input).
- ``E`` **routed experts**: ``models/latent.py``'s router and grouped
  products in their non-gated form (``W_down relu(W_up h)^2``; the layer has
  no ``w_gate``) plus one shared expert of the same form. A call that touches
  more than ``DENSE_SHARE`` of the experts computes them all instead (decided
  on the device): the grouped product's kernel runs at an eighth of the HBM
  rate at one or two rows an expert.
- ``*`` **attention**: ``llama._block`` / ``_block_paged`` as they are (the
  layer has no MLP and ``config.use_rope`` is False), so the flash, XLA and
  paged kernels are the GQA models' own.
- ``L`` / ``G`` **the parallel block** (Cohere's ``use_parallel_block``;
  :func:`parallel_block`): ``n = LN(x)``; ``x <- x + Attn(n) + MoE(n)``, one
  norm, two branches, one residual add. ``LN`` is the mean-centred LayerNorm
  without bias (:func:`layer_norm`; the final norm too). ``Attn`` is GQA
  without bias: ``L`` inside ``config.sliding_window`` under RoPE, ``G`` over
  everything with no positional embedding. ``MoE`` is the latent block's
  router and grouped products over gated experts (a held share of them:
  ``config.experts_held``) plus the shared experts as one fused SwiGLU, times
  ``1 / n_shared_experts`` where the model averages them. A step reads the
  pool through the paged kernel with the layer's own window, static in its
  call; prompts take the XLA masks over the dense staging cache, scored one kv
  head's group of queries at a time (128 heads x a 8,192-key bucket in one
  float32 array is 0.5 GB). No recurrent state: ``state`` stays empty.

**Two kinds of per-row state.** Only ``*`` layers have keys and values: cache
and pool have ``config.paging_layers`` leading entries, cache layer ``a`` is
the ``a``-th ``*``. The ``m``-th ``M`` layer keeps ``state["ssm"][m]`` ``[B, heads,
head_dim, N]`` (float32 by default: it is stored in the dtype it comes in) and
``state["conv"][m]`` ``[B, taps - 1, conv channels]``, the conv's last inputs:
one array a layer, so that a donated state is updated in place. ``state`` is
a dict the caller passes and returns from its program, like ``aux``: entries
it holds are the rows' state going in (absent: zeros), and the stack replaces
them with the state after each row's valid tokens.

Layers are not stacked: ``params["layers"]`` is a list of per-layer dicts (three
kinds of layer cannot share a scan), so every expert stack is its own
``[E, K, N]`` parameter and the grouped products read it in place. ``w_up``
is stored with its output columns padded with zeros to a multiple of 128
(:func:`expert_columns`): the chip keeps an array whose minor dimension is
not a multiple of its 128 lanes with that dimension second, and the grouped
product's kernel then copies the whole stack (1.3 GB) on every call (the
program compiled for a described v5e; PERF.md §6).

``aux`` gains ``moe_counts`` ``[E layers, experts]`` (and ``moe_chosen`` when
asked, as in models/latent.py), ``ssm_rows_updated`` (rows with a valid token
x M layers: the states this call advanced) and ``ssm_tokens_scanned`` (valid
tokens x M layers). A caller that puts the key ``ssm_inputs`` into ``aux``
gets back under it what each M layer's state update consumed, a list of
``{dt [B, S, G, R], x [B, S, G, R, P], B [B, S, G, N]}`` in float32
(benchmark/check_nemotron3.py replays the recurrence from them; the loop does
not ask).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import llama
from .config import PAGING_KINDS, ModelConfig
from .latent import _dot, _moe_mlp, _refuse, _write_cache, routed_experts
from .llama import KVCache, Params, rms_norm, rope_embed

_F32 = jnp.float32


def conv_dim(config: ModelConfig) -> int:
    return config.mamba_num_heads * config.mamba_head_dim + 2 * config.mamba_n_groups * config.ssm_state_size


def expert_columns(config: ModelConfig) -> int:
    """Columns ``w_up`` is stored with: moe_intermediate_size rounded up to the
    128 lanes (1,856 -> 1,920); the columns past the width are zero."""
    return -(-config.moe_intermediate_size // 128) * 128


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(config: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random init made directly in the model dtype, an expert (or an eighth
    of a table) at a time: a float32 copy of one full-width expert stack would
    be 2.6 GB. The state-space constants follow the published initialisation,
    so that a state neither dies in ten tokens nor grows: ``A_log = log
    U[1, 16]``, ``dt_bias`` the inverse softplus of a log-uniform step in
    [time_step_min, time_step_max] floored at time_step_floor, ``D = 1``; the
    router bias is zero."""
    dtype = dtype or config.jax_dtype
    H, V, E = config.hidden_size, config.vocab_size, config.num_experts
    Hm, P = config.mamba_num_heads, config.mamba_head_dim
    d_inner, Cd, K = Hm * P, conv_dim(config), config.mamba_conv_kernel
    Q, KV = config.q_dim, config.kv_dim
    Im = config.moe_intermediate_size
    Is = config.moe_shared_intermediate_size or Im * config.n_shared_experts

    def stack(k, count, shape, scale, columns=None):
        def one(kk):
            w = (jax.random.normal(kk, shape, _F32) * scale).astype(dtype)
            return w if columns is None else jnp.pad(w, ((0, 0), (0, columns - shape[-1])))

        return lax.map(one, jax.random.split(k, count))

    def normal(k, shape, scale):
        parts = math.gcd(8, shape[0])
        return stack(k, parts, (shape[0] // parts,) + shape[1:], scale).reshape(shape)

    def layer(kind: str, k) -> Dict[str, Any]:
        ks = jax.random.split(k, 6)
        if kind == "M":
            dt = jnp.exp(jax.random.uniform(ks[2], (Hm,), _F32) * (
                math.log(config.time_step_max) - math.log(config.time_step_min)
            ) + math.log(config.time_step_min))
            dt = jnp.maximum(dt, config.time_step_floor)
            return {
                "norm": jnp.ones((H,), dtype),
                "in_proj": normal(ks[0], (H, 2 * d_inner + 2 * config.mamba_n_groups
                                          * config.ssm_state_size + Hm), H ** -0.5),
                "conv_w": normal(ks[1], (K, Cd), K ** -0.5),
                "conv_b": jnp.zeros((Cd,), dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                "A_log": jnp.log(jax.random.uniform(ks[3], (Hm,), _F32, 1.0, 16.0)),
                "D": jnp.ones((Hm,), _F32),
                "gate_norm": jnp.ones((d_inner,), dtype),
                "out_proj": normal(ks[4], (d_inner, H), d_inner ** -0.5),
            }
        if kind == "E":
            return {
                "norm": jnp.ones((H,), dtype),
                "w_router": normal(ks[0], (H, E), H ** -0.5),
                "router_bias": jnp.zeros((E,), _F32),
                "w_up": stack(ks[1], E, (H, Im), H ** -0.5, columns=expert_columns(config)),
                "w_down": stack(ks[2], E, (Im, H), Im ** -0.5),
                "ws_up": normal(ks[3], (H, Is), H ** -0.5),
                "ws_down": normal(ks[4], (Is, H), Is ** -0.5),
            }
        if kind == "*":
            return {
                "attn_norm": jnp.ones((H,), dtype),
                "wq": normal(ks[0], (H, Q), H ** -0.5),
                "wk": normal(ks[1], (H, KV), H ** -0.5),
                "wv": normal(ks[2], (H, KV), H ** -0.5),
                "wo": normal(ks[3], (Q, H), Q ** -0.5),
            }
        if kind in PARALLEL_KINDS:
            ke = jax.random.split(ks[5], 6)
            Eh = config.held_experts
            return {
                "norm": jnp.ones((H,), dtype),
                "wq": normal(ks[0], (H, Q), H ** -0.5),
                "wk": normal(ks[1], (H, KV), H ** -0.5),
                "wv": normal(ks[2], (H, KV), H ** -0.5),
                "wo": normal(ks[3], (Q, H), Q ** -0.5),
                "w_router": normal(ks[4], (H, E), H ** -0.5),
                "router_bias": jnp.zeros((E,), _F32),
                "w_gate": stack(ke[0], Eh, (H, Im), H ** -0.5),
                "w_up": stack(ke[1], Eh, (H, Im), H ** -0.5),
                "w_down": stack(ke[2], Eh, (Im, H), Im ** -0.5),
                "ws_gate": normal(ke[3], (H, Is), H ** -0.5),
                "ws_up": normal(ke[4], (H, Is), H ** -0.5),
                "ws_down": normal(ke[5], (Is, H), Is ** -0.5),
            }
        raise ValueError(f"{config.name}: layer kind {kind!r} in {config.layer_pattern!r}")

    if len(config.layer_pattern) != config.num_layers:
        raise ValueError(
            f"{config.name}: layer_pattern has {len(config.layer_pattern)} characters "
            f"for {config.num_layers} layers"
        )
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, config.num_layers)
    params = {
        "embed": normal(k_embed, (V, H), H ** -0.5),
        "layers": [layer(kind, k) for kind, k in zip(config.layer_pattern, layer_keys)],
        "final_norm": jnp.ones((H,), dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = normal(k_head, (H, V), H ** -0.5)
    return params


def param_count(config: ModelConfig) -> int:
    """Parameters of the configuration, from the shapes ``init_params`` builds
    less the zero columns ``w_up`` is stored with."""
    shapes = jax.eval_shape(lambda: init_params(config, jax.random.key(0)))
    padding = (config.layer_pattern.count("E") * config.num_experts * config.hidden_size
               * (expert_columns(config) - config.moe_intermediate_size))
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(shapes)) - padding


# ---------------------------------------------------------------------------
# The Mamba-2 mixer
# ---------------------------------------------------------------------------

def _conv(config: ModelConfig, layer: Params, xBC: jax.Array, tail: jax.Array,
          valid_len: jax.Array):
    """Causal depthwise conv over [the row's last inputs | this call's] ->
    (silu(conv + bias) [B, S, Cd], the new last inputs [B, K-1, Cd]: the K-1
    columns that end at each row's last valid one)."""
    K, S = config.mamba_conv_kernel, xBC.shape[1]
    seq = jnp.concatenate([tail.astype(xBC.dtype), xBC], axis=1)  # [B, K-1+S, Cd]
    w = layer["conv_w"].astype(_F32)
    out = sum(seq[:, k:k + S].astype(_F32) * w[k] for k in range(K))
    out = jax.nn.silu(out + layer["conv_b"].astype(_F32))
    new_tail = jax.vmap(lambda s, n: lax.dynamic_slice_in_dim(s, n, K - 1, axis=0))(seq, valid_len)
    return out, new_tail.astype(tail.dtype)


def ssd_scan(x, dt, A, Bm, Cm, S0, block: int):
    """The chunked (SSD) form of ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
    B_t; y_t = S_t C_t``. x [B, S, G, R, P] (heads as groups x heads a
    group), dt [B, S, G, R] (0 where a position is padding), A [G, R], Bm and
    Cm [B, S, G, N], S0 [B, G, R, P, N]; all float32. Blocks of ``block``
    positions: inside a block the quadratic form, between blocks the state.
    Returns (y [B, S, G, R, P], the state after the last position)."""
    B_, S = x.shape[:2]
    pad = (-S) % block
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
    nb = (S + pad) // block
    blocks = tuple(jnp.moveaxis(a.reshape(B_, nb, block, *a.shape[2:]), 1, 0)
                   for a in (x, dt, Bm, Cm))
    causal = jnp.tril(jnp.ones((block, block), bool))

    def one(S_prev, blk):
        xb, dtb, Bb, Cb = blk
        cs = jnp.cumsum(dtb * A, axis=1)  # [B, Q, G, R]: log decay from the block's start, inclusive
        dx = dtb[..., None] * xb
        # Inside the block: y_t += sum_{s<=t} (C_t . B_s) exp(cs_t - cs_s) dt_s x_s.
        seg = cs[:, :, None] - cs[:, None, :]  # [B, t, s, G, R]
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None], seg, -jnp.inf))
        scores = jnp.einsum("btgn,bsgn->btsg", Cb, Bb)
        y = jnp.einsum("btsgr,bsgrp->btgrp", scores[..., None] * decay, dx)
        # From the state the block started with.
        y = y + jnp.einsum("bgrpn,btgn->btgrp", S_prev, Cb) * jnp.exp(cs)[..., None]
        to_end = jnp.exp(cs[:, -1:] - cs)  # [B, Q, G, R]
        S_new = jnp.exp(cs[:, -1])[..., None, None] * S_prev + jnp.einsum(
            "bsgrp,bsgn->bgrpn", dx * to_end[..., None], Bb)
        return S_new, y

    S_last, ys = lax.scan(one, S0, blocks)
    y = jnp.moveaxis(ys, 0, 1).reshape(B_, S + pad, *x.shape[2:])
    return y[:, :S], S_last


def mamba_mixer(config: ModelConfig, layer: Params, h: jax.Array, ssm: jax.Array,
                tail: jax.Array, valid_len: jax.Array):
    """h [B, S, H] -> (out [B, S, H], ssm', tail', the update's inputs).
    ``ssm`` [B, heads, P, N] and ``tail`` [B, K-1, Cd] are the rows' state
    going in; what comes back is the state after each row's first
    ``valid_len`` positions (0: unchanged)."""
    B_, S, _ = h.shape
    Hm, P, G, N = (config.mamba_num_heads, config.mamba_head_dim,
                   config.mamba_n_groups, config.ssm_state_size)
    d_inner, R = Hm * P, Hm // G
    with jax.named_scope("ssm_in_proj"):
        zxbcdt = _dot(h, layer["in_proj"])
        z, xBC, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim(config)], axis=-1)
    with jax.named_scope("ssm_conv"):
        xBC, new_tail = _conv(config, layer, xBC, tail, valid_len)
    with jax.named_scope("ssm_scan"):
        valid = jnp.arange(S)[None, :] < valid_len[:, None]  # [B, S]
        x = xBC[..., :d_inner].reshape(B_, S, G, R, P)
        Bm = xBC[..., d_inner:d_inner + G * N].reshape(B_, S, G, N)
        Cm = xBC[..., d_inner + G * N:].reshape(B_, S, G, N)
        dt = jax.nn.softplus(dt.astype(_F32) + layer["dt_bias"])
        dt = jnp.where(valid[..., None], dt, 0.0).reshape(B_, S, G, R)
        x = jnp.where(valid[..., None, None, None], x, 0.0)
        A = -jnp.exp(layer["A_log"]).reshape(G, R)
        S0 = ssm.astype(_F32).reshape(B_, G, R, P, N)
        if S == 1:  # one update a row
            dA = jnp.exp(dt[:, 0] * A)  # [B, G, R]
            S1 = dA[..., None, None] * S0 + (dt[:, 0, ..., None] * x[:, 0])[..., None] * Bm[
                :, 0, :, None, None, :]
            S1 = jnp.where(valid[:, 0, None, None, None, None], S1, S0)
            y = jnp.sum(S1 * Cm[:, 0, :, None, None, :], axis=-1)[:, None]
        else:
            y, S1 = ssd_scan(x, dt, A, Bm, Cm, S0, min(config.mamba_chunk, S))
        y = y + layer["D"].reshape(G, R)[..., None] * x
        new_ssm = S1.reshape(ssm.shape).astype(ssm.dtype)
        seen = {"dt": dt, "x": x, "B": Bm}
    with jax.named_scope("ssm_gate_norm"):
        y = y.reshape(B_, S, G, d_inner // G) * jax.nn.silu(z.astype(_F32)).reshape(
            B_, S, G, d_inner // G)
        y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + config.rms_eps)
        y = y.reshape(B_, S, d_inner).astype(h.dtype) * layer["gate_norm"]
    with jax.named_scope("ssm_out_proj"):
        return _dot(y, layer["out_proj"]), new_ssm, new_tail, seen


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------

def _relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


#: Share of the experts with a token above which an expert layer computes
#: them all (``latent.routed_experts``): the grouped and the whole form cost
#: the same at 19 of 128 touched (my chip run, PR 32). Distinct chat rows touch
#: ~75 %, a chunk all; a request's eight samples under a grammar ~5 %.
DENSE_SHARE = 1 / 8


def moe_mixer(config: ModelConfig, layer: Params, h: jax.Array):
    """h [B, S, H] -> (routed + shared [B, S, H], {counts, chosen})."""
    B_, S, H = h.shape
    out, counts, chosen = routed_experts(config, layer, h.reshape(B_ * S, H), DENSE_SHARE)
    with jax.named_scope("moe_shared"):
        shared = _dot(_relu2(_dot(h, layer["ws_up"])), layer["ws_down"])
    return out.reshape(B_, S, H) + shared, {"counts": counts, "chosen": chosen}


# ---------------------------------------------------------------------------
# The parallel block
# ---------------------------------------------------------------------------

#: The pattern's characters whose layer is the parallel block: "L" windowed
#: under RoPE, "G" global without a positional embedding.
PARALLEL_KINDS = "LG"


@jax.named_scope("layer_norm")
def layer_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """Cohere's LayerNorm: subtract the mean, divide by sqrt(var + eps), times
    a weight; no bias."""
    x32 = x.astype(_F32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * weight


def stack_norm(config: ModelConfig, x: jax.Array, weight: jax.Array) -> jax.Array:
    """The stack's norm: the parallel block's LayerNorm where the pattern has
    one, else RMSNorm."""
    if any(kind in PARALLEL_KINDS for kind in config.layer_pattern):
        return layer_norm(x, weight, config.rms_eps)
    return rms_norm(x, weight, config.rms_eps)


@jax.named_scope("attn_qkv")
def _parallel_qkv(config: ModelConfig, layer: Params, n: jax.Array, positions: jax.Array,
                  rope: bool):
    B_, S, _ = n.shape
    q = _dot(n, layer["wq"]).reshape(B_, S, config.num_heads, config.head_dim)
    k = _dot(n, layer["wk"]).reshape(B_, S, config.num_kv_heads, config.head_dim)
    v = _dot(n, layer["wv"]).reshape(B_, S, config.num_kv_heads, config.head_dim)
    if rope:
        q = rope_embed(q, positions, config.rope_theta, config.rope_scaling)
        k = rope_embed(k, positions, config.rope_theta, config.rope_scaling)
    return q, k, v


@jax.named_scope("attn")
def _attend_masked(config: ModelConfig, q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                   key_mask: jax.Array) -> jax.Array:
    """q [B, Sq, QH, D] against a dense cache [B, Smax, KVH, D] under
    ``key_mask`` [B|1, Sq, Smax], one kv head's group of queries at a time
    -> [B, Sq, QH * D]."""
    B_, Sq, QH, D = q.shape
    KVH = cache_k.shape[2]
    qg = q.reshape(B_, Sq, KVH, QH // KVH, D)

    def one(h):
        k, v = cache_k[:, :, h], cache_v[:, :, h]  # [B, Smax, D]
        s = jnp.einsum("bqgd,bkd->bgqk", qg[:, :, h], k,
                       preferred_element_type=_F32) * config.attn_scale
        s = jnp.where(key_mask[:, None], s, jnp.finfo(_F32).min)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bgqk,bkd->bqgd", w, v, preferred_element_type=_F32)

    out = lax.map(one, jnp.arange(KVH))  # [KVH, B, Sq, G, D]
    return jnp.moveaxis(out, 0, 2).astype(q.dtype).reshape(B_, Sq, QH * D)


def parallel_block(config: ModelConfig, layer: Params, x: jax.Array, attention):
    """``x + Attn(n) + MoE(n)`` with ``n = LN(x)``. ``attention(n) -> (attn
    [B, S, QH * D], kv)`` is the caller's (dense cache or page pool)."""
    n = layer_norm(x, layer["norm"], config.rms_eps)
    attn, kv = attention(n)
    with jax.named_scope("attn_out"):
        attn = _dot(attn, layer["wo"])
    moe, routed = _moe_mlp(config, layer, n)
    with jax.named_scope("parallel_add"):
        return x + attn + moe, kv, routed


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

_BLOCK = "the hybrid stack (recurrent state beside the cache)"


def _run(config: ModelConfig, params: Params, x: jax.Array, valid_len: jax.Array,
         state: Optional[dict], aux: Optional[dict], attend):
    """Every layer in the pattern's order. ``attend(kind, layer, x, a) -> (x,
    kv, routed)`` runs paging layer number ``a`` (block and residual; ``routed``
    the parallel block's {counts, chosen}, None for "*"); returns (x, the list
    of the ``kv`` it gave back) and fills ``state`` and ``aux``."""
    recurrent = "M" in config.layer_pattern
    state_in = state if state else llama.init_state(config, x.shape[0])
    ssm_in, conv_in = state_in.get("ssm"), state_in.get("conv")
    ssm_out, conv_out, seen, routed, kvs = [], [], [], [], []
    for kind, layer in zip(config.layer_pattern, params["layers"]):
        if kind in PAGING_KINDS:
            x, kv, r = attend(kind, layer, x, len(kvs))
            kvs.append(kv)
            if r is not None:
                routed.append(r)
            continue
        h = rms_norm(x, layer["norm"], config.rms_eps)
        if kind == "M":
            m = len(ssm_out)
            out, ssm, tail, inputs = mamba_mixer(
                config, layer, h, ssm_in[m], conv_in[m], valid_len)
            ssm_out.append(ssm)
            conv_out.append(tail)
            seen.append(inputs)
        else:
            out, r = moe_mixer(config, layer, h)
            routed.append(r)
        x = x + out
    if state is not None and recurrent:
        state["ssm"], state["conv"] = tuple(ssm_out), tuple(conv_out)
    if aux is not None:
        for key in ("counts", "chosen") if "moe_chosen" in aux else ("counts",):
            aux["moe_" + key] = jnp.stack([r[key] for r in routed])
        if "ssm_inputs" in aux:
            aux["ssm_inputs"] = seen
        if recurrent:
            aux["ssm_rows_updated"] = jnp.sum(valid_len > 0, dtype=jnp.int32) * len(ssm_out)
            aux["ssm_tokens_scanned"] = jnp.sum(valid_len, dtype=jnp.int32) * len(ssm_out)
    return x, kvs


def apply_stack(
    config: ModelConfig,
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    cache: KVCache,
    write_index,
    key_mask: jax.Array,
    valid_len: jax.Array,
    key_lengths: Optional[jax.Array] = None,
    prefix: Optional[KVCache] = None,
    state: Optional[dict] = None,
    aux: Optional[dict] = None,
    sp_ring_mesh=None,
    mesh=None,
    key_mask_global: Optional[jax.Array] = None,
) -> Tuple[jax.Array, KVCache]:
    """``llama._apply_stack`` for the hybrid stack, over dense caches
    ``[paging layers, B, Smax, KVH, D]``: the full forward, whole-prompt
    prefill and the chunk steps. ``valid_len`` [B]: how many of each row's
    positions are tokens (the rest is right padding the state must not see).
    Where windowed and global layers mix, ``key_mask`` is the windowed mask
    and ``key_mask_global`` the full-causal one, as in ``llama._apply_stack``;
    each layer takes its own by its kind."""
    _refuse(config, _BLOCK, mesh=mesh, sp_ring_mesh=sp_ring_mesh,
            **{"a shared-prefix decode or verify step (the dense decode path)": prefix})

    def attend(kind, layer, x, a):
        if kind == "*":
            return llama._block(config, layer, x, positions, (cache.k[a], cache.v[a]),
                                write_index, key_mask, key_lengths=key_lengths) + (None,)
        mask = key_mask if kind == "L" or key_mask_global is None else key_mask_global

        def attention(n):
            q, k, v = _parallel_qkv(config, layer, n, positions, rope=kind == "L")
            with jax.named_scope("kv_write"):
                ck = _write_cache(cache.k[a], k, write_index)
                cv = _write_cache(cache.v[a], v, write_index)
            return _attend_masked(config, q, ck, cv, mask), (ck, cv)

        return parallel_block(config, layer, x, attention)

    x, kvs = _run(config, params, x, valid_len, state, aux, attend)
    return x, KVCache(k=jnp.stack([k for k, _ in kvs]), v=jnp.stack([v for _, v in kvs]))


def apply_stack_paged(
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
    valid_len: Optional[jax.Array],
    prefix_lengths: Optional[jax.Array] = None,
    attn_impl: str = "xla",
    page_size: Optional[int] = None,
    state: Optional[dict] = None,
    aux: Optional[dict] = None,
    mesh=None,
    key_mask_global: Optional[jax.Array] = None,
    prefix_mask_global: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``llama._apply_stack_paged`` for the hybrid stack: the attention layers
    read the pool ``[paging layers, flat, KVH, D]`` through ``_block_paged``
    (the parallel block: through its attention, ``_paged_attend``), each with its
    own window (``config.layer_windows``) static in the kernel's call and, on
    the XLA path, its own masks (``*_global`` for a layer without a window, as
    in ``llama._apply_stack_paged``); the Mamba-2 layers advance the rows
    whose ``valid_len`` ([B] bool or 0/1; None: all) is set. Returns (x,
    k_cols, v_cols ``[paging layers, B, KVH, D]``)."""
    _refuse(config, _BLOCK, mesh=mesh)
    B_ = x.shape[0]
    valid_len = jnp.ones((B_,), jnp.int32) if valid_len is None else valid_len.astype(jnp.int32)
    page_tables = None
    if attn_impl in ("pallas", "pallas_interpret"):
        from ..ops.paged_attention import paged_attention_page_tables

        page_tables = paged_attention_page_tables(prefix_idx, gen_idx, page_size)
    windows = config.layer_windows

    def attend(kind, layer, x, a):
        glob = windows[a] is None and key_mask_global is not None
        paged = dict(
            pool_kv=pool_kv, layer_idx=jnp.int32(a), prefix_idx=prefix_idx, gen_idx=gen_idx,
            write_index=write_index, key_mask=key_mask_global if glob else key_mask,
            prefix_mask=prefix_mask_global if glob else prefix_mask,
            prefix_lengths=prefix_lengths, page_tables=page_tables, page_size=page_size,
            attn_impl=attn_impl, window=windows[a])
        if kind == "*":
            return llama._block_paged(config, layer, x, positions, **paged) + (None,)

        def attention(n):
            q, k, v = _parallel_qkv(config, layer, n, positions, rope=kind == "L")
            return llama._paged_attend(config, q, k, v, **paged)

        return parallel_block(config, layer, x, attention)

    x, cols = _run(config, params, x, valid_len, state, aux, attend)
    return x, jnp.stack([k for k, _ in cols]), jnp.stack([v for _, v in cols])
