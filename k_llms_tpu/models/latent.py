"""The latent block: MLA + routed experts with a shared one + hyper-connections.

The Xing4.0 family's layer (DeepSeek-V3's attention and router under
manifold-constrained hyper-connections, arXiv:2512.24880), behind the same
entry points as every other model: ``models/llama.py``'s ``forward``,
``prefill``, ``prefill_continue``, the chunk steps, ``decode_step``,
``verify_step`` and ``paged_verify_step`` build positions and masks as always
and hand the stack to :func:`apply_stack` / :func:`apply_stack_paged` here
when ``config.is_latent``.

- **Cache.** One row a token a layer: ``[c_kv | k_rope]`` after the latent's
  norm and after RoPE, ``kv_lora_rank + qk_rope_head_dim`` wide, in the ``k``
  array of the usual (k, v) pair as ``[..., 1, width]``; ``v`` has width 0.
  The page pool stores the row ``config.pool_row_width`` lanes wide (whole
  tiles of 128: 576 -> 640, the pad lanes zeros nobody reads), and every
  program addresses it by (layer, slot) in the pool's flat view: the gathers
  here through ``ops/attention.py::pool_gather``, every writer through the
  page manager's movers (``engine/paging.py``).
- **Two attention forms, one function** (:func:`mla_attend`): prefill and
  chunks materialise per-head keys and values from the latent; a decode or
  verify step absorbs the up-projections into the query and the output, so it
  reads only the latent rows. Both give the same numbers up to rounding.
- **Two kinds of layer.** ``params["dense_layers"]`` (the leading
  ``first_k_dense`` layers, dense SwiGLU) and ``params["layers"]`` (routed
  experts) are each one ``lax.scan``; cache layer ``i`` is stack layer ``i``.
- **Routed experts.** The step's token-expert pairs are sorted by expert and
  run as grouped products over the stacked expert weights
  (``jax.lax.ragged_dot``): an expert no token chose is never read, and the
  stacks of all layers are read in place (no layer's slice is copied out).
- **Streams.** The scan carries ``[B, S, hc_mult, H]``; the mixers run in
  float32 with no MXU pass in the stream arithmetic. ``hc_mult == 1`` is the
  block without streams (JoyAI-LLM-Flash): no mixer parameters, and a
  sublayer is the plain pre-norm residual ``X + f(RMSNorm(X))``.
- **A share of the experts.** ``config.experts_held`` of the router's
  ``num_experts`` have their stacks here (another chip holds the rest): the
  router still chooses among all of them and normalises over all it chose, and
  a pair whose expert lies elsewhere contributes nothing to this chip's sum.
- **The next-token module** (``params["mtp"]``, ``num_nextn_predict_layers``
  1): one more expert layer whose input at slot ``p`` is ``W_eh [RMSNorm_h(h_{p-1});
  RMSNorm_e(Emb(t_p))]``, with ``h`` the main stack's output before
  ``final_norm``; its own final norm, the shared head, logits for ``t_{p+1}``.
  Its cache layer is the last paging layer and its row for the pair
  ``(h_{p-1}, t_p)`` lies at position ``p``, the token it embeds (position 0
  holds nothing and is masked), so rows that differ by sample lie on a
  request row's private pages. Prompts write the module's cache rows beside the main
  stack's (:func:`mtp_ingest`); the loop's drafted step runs the whole block
  (:func:`mtp_paged`).

What the stack counts for the loop goes into ``aux`` (a dict the caller
passes and returns from its program): ``moe_counts`` ``[expert layers, E]``,
tokens per expert over the rows computed, and ``mla_latent_rows_read``, the
cache rows a paged step attended over rows and layers. A caller that puts the
key ``moe_chosen`` into ``aux`` beforehand gets the router's choices back
under it, ``[expert layers, tokens, K]`` (benchmark/check_xing4.py hands them
to the reference; the loop does not ask).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import pool_gather, pool_index
from .config import ModelConfig
from .llama import KVCache, Params, rms_norm, rope_embed

_F32 = jnp.float32
_NEG = jnp.finfo(jnp.float32).min


def mixer_width(n: int) -> int:
    """Outputs of one hyper-connection mixer: n (pre) + n (post) + n*n (res)."""
    return n * (n + 2)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(config: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random init made directly in the model dtype, one layer at a time
    (``lax.map`` over per-layer keys): a float32 copy of one full-width expert
    stack would be 5.6 GB. The mixers start with ``H_res`` near the identity
    (bias 3 on its diagonal) but token-dependent (alpha 0.5 on a unit-variance
    projection), ``H_pre`` 0.5 and ``H_post`` 1 on average; the router bias is
    zero."""
    dtype = dtype or config.jax_dtype
    H, V, n = config.hidden_size, config.vocab_size, config.hc_mult
    NH, E, Eh = config.num_heads, config.num_experts, config.held_experts
    dn, dr, dv = config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim
    rq, rkv = config.q_lora_rank, config.kv_lora_rank
    I, Im = config.intermediate_size, config.moe_intermediate_size
    Is = Im * config.n_shared_experts
    Ld, Le = config.first_k_dense, config.num_layers - config.first_k_dense

    def stack(k, count, shape, scale, out_dtype=dtype):
        def one(kk):
            return (jax.random.normal(kk, shape, _F32) * scale).astype(out_dtype)

        return lax.map(one, jax.random.split(k, count))

    def group(k, count: int, moe: bool) -> Dict[str, Any]:
        ks = jax.random.split(k, 16)
        bias = jnp.concatenate([jnp.zeros(2 * n), 3.0 * jnp.eye(n).reshape(-1)]).astype(_F32)
        g = {
            "attn_norm": jnp.ones((count, H), dtype),
            "wq_a": stack(ks[0], count, (H, rq), H ** -0.5),
            "q_norm": jnp.ones((count, rq), dtype),
            "wq_b": stack(ks[1], count, (rq, NH * (dn + dr)), rq ** -0.5),
            "wkv_a": stack(ks[2], count, (H, rkv + dr), H ** -0.5),
            "kv_norm": jnp.ones((count, rkv), dtype),
            "wkv_b": stack(ks[3], count, (rkv, NH * (dn + dv)), rkv ** -0.5),
            "wo": stack(ks[4], count, (NH * dv, H), (NH * dv) ** -0.5),
            "mlp_norm": jnp.ones((count, H), dtype),
        }
        for i, name in enumerate(("hc_attn", "hc_mlp") if n > 1 else ()):
            g[name + "_phi"] = stack(ks[5 + i], count, (n * H, mixer_width(n)),
                                     (n * H) ** -0.5, _F32)
            g[name + "_alpha"] = jnp.full((count, 3), 0.5, _F32)
            g[name + "_bias"] = jnp.broadcast_to(bias, (count, mixer_width(n)))
        if not moe:
            g["w_gate"] = stack(ks[7], count, (H, I), H ** -0.5)
            g["w_up"] = stack(ks[8], count, (H, I), H ** -0.5)
            g["w_down"] = stack(ks[9], count, (I, H), I ** -0.5)
            return g
        g["w_router"] = stack(ks[10], count, (H, E), H ** -0.5)
        g["router_bias"] = jnp.zeros((count, E), _F32)
        g["w_gate"] = stack(ks[7], count, (Eh, H, Im), H ** -0.5)
        g["w_up"] = stack(ks[8], count, (Eh, H, Im), H ** -0.5)
        g["w_down"] = stack(ks[9], count, (Eh, Im, H), Im ** -0.5)
        g["ws_gate"] = stack(ks[11], count, (H, Is), H ** -0.5)
        g["ws_up"] = stack(ks[12], count, (H, Is), H ** -0.5)
        g["ws_down"] = stack(ks[13], count, (Is, H), Is ** -0.5)
        return g

    k_embed, k_dense, k_moe, k_head = jax.random.split(key, 4)
    # The two vocabulary tables in eight slices, for the same reason.
    vr, hr = math.gcd(8, V), math.gcd(8, H)
    params = {
        "embed": stack(k_embed, vr, (V // vr, H), H ** -0.5).reshape(V, H),
        "dense_layers": group(k_dense, Ld, moe=False),
        "layers": group(k_moe, Le, moe=True),
        "final_norm": jnp.ones((H,), dtype),
        "lm_head": stack(k_head, hr, (H // hr, V), H ** -0.5).reshape(H, V),
    }
    if config.num_nextn_predict_layers:
        # Its own key, so the main stack's weights are the same preset's with
        # the module count 0.
        k_block, k_proj = jax.random.split(jax.random.fold_in(key, 1))
        params["mtp"] = {
            "hnorm": jnp.ones((H,), dtype),
            "enorm": jnp.ones((H,), dtype),
            "eh_proj": stack(k_proj, 1, (2 * H, H), (2 * H) ** -0.5)[0],
            "layers": group(k_block, config.num_nextn_predict_layers, moe=True),
            "final_norm": jnp.ones((H,), dtype),
        }
    return params


def param_count(config: ModelConfig) -> int:
    """Parameters of the configuration, from the shapes ``init_params`` builds."""
    shapes = jax.eval_shape(lambda: init_params(config, jax.random.key(0)))
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(shapes))


# ---------------------------------------------------------------------------
# Hyper-connections
# ---------------------------------------------------------------------------

def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``iters`` rounds of dividing rows, then columns, by their sums + eps
    (last two axes). Unrolled: 20 rounds on a 4x4 is a few hundred flops."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hc_coefficients(config: ModelConfig, layer: Params, name: str, X: jax.Array):
    """One mixer on the streams X [B, S, n, H] -> (H_pre [B,S,n], H_post
    [B,S,n], H_res [B,S,n,n]) in float32."""
    B, S, n, H = X.shape
    x = X.astype(_F32).reshape(B, S, n * H)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + config.rms_eps)
    proj = jnp.einsum("bsk,km->bsm", x, layer[name + "_phi"],
                      precision=lax.Precision.HIGHEST)
    alpha, bias = layer[name + "_alpha"], layer[name + "_bias"]
    h_pre = jax.nn.sigmoid(alpha[0] * proj[..., :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[..., n: 2 * n] + bias[n: 2 * n])
    res = (alpha[2] * proj[..., 2 * n:] + bias[2 * n:]).reshape(B, S, n, n)
    res = jnp.clip(res, -config.hc_res_clamp, config.hc_res_clamp)
    return h_pre, h_post, sinkhorn(jnp.exp(res), config.hc_sinkhorn_iters, config.hc_eps)


def _hc_sublayer(config: ModelConfig, layer: Params, name: str, norm: str, X, fn):
    """``X <- H_res X + H_post^T fn(RMSNorm(H_pre X))``. The stream arithmetic
    is multiply-and-sum in float32 (n is 4: no matmul unit, no bf16 pass).
    Without streams (``hc_mult == 1``) there is no mixer: ``X + fn(RMSNorm(X))``."""
    if config.hc_mult == 1:
        return X + fn(rms_norm(X[:, :, 0], layer[norm], config.rms_eps))[:, :, None]
    with jax.named_scope("hc_mix"):
        h_pre, h_post, h_res = hc_coefficients(config, layer, name, X)
        X32 = X.astype(_F32)
        h = jnp.sum(h_pre[..., None] * X32, axis=2).astype(X.dtype)
    y = fn(rms_norm(h, layer[norm], config.rms_eps))
    with jax.named_scope("hc_mix"):
        mixed = jnp.sum(h_res[..., None] * X32[:, :, None, :, :], axis=3)
        return (mixed + h_post[..., None] * y.astype(_F32)[:, :, None, :]).astype(X.dtype)


# ---------------------------------------------------------------------------
# Latent attention
# ---------------------------------------------------------------------------

def _dot(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x, w, preferred_element_type=_F32).astype(x.dtype)


@jax.named_scope("mla_q")
def _mla_q(config: ModelConfig, layer: Params, h: jax.Array, positions: jax.Array):
    """h [B, S, H] -> (q_nope [B,S,NH,dn], q_rope [B,S,NH,dr], roped)."""
    B, S, _ = h.shape
    dn = config.qk_nope_head_dim
    c_q = rms_norm(_dot(h, layer["wq_a"]), layer["q_norm"], config.rms_eps)
    q = _dot(c_q, layer["wq_b"]).reshape(B, S, config.num_heads, -1)
    return q[..., :dn], rope_embed(q[..., dn:], positions, config.rope_theta, config.rope_scaling)


@jax.named_scope("mla_kv_latent")
def _mla_kv_latent(config: ModelConfig, layer: Params, h: jax.Array, positions: jax.Array):
    """h [B, S, H] -> the cache row [B, S, rkv + dr]: normed latent | roped key."""
    rkv = config.kv_lora_rank
    kva = _dot(h, layer["wkv_a"])
    c_kv = rms_norm(kva[..., :rkv], layer["kv_norm"], config.rms_eps)
    k_r = rope_embed(kva[..., None, rkv:], positions, config.rope_theta, config.rope_scaling)
    return jnp.concatenate([c_kv, k_r[..., 0, :]], axis=-1)


def mla_attend(
    config: ModelConfig,
    layer: Params,
    q_nope: jax.Array,
    q_rope: jax.Array,
    segments: List[Tuple[jax.Array, jax.Array]],
    absorb: bool,
) -> jax.Array:
    """Attention of q over cached latent rows -> [B, Sq, NH * dv].

    ``segments``: (rows [R, S, width], mask [B|1, Sq, S]) pairs whose keys are
    concatenated under one softmax; R divides B and row b reads set
    ``b // (B // R)`` (a shared prefix is stored once, as in
    ``_gqa_scores_shared``). ``absorb`` picks the form: False materialises
    ``k_nope`` and ``v`` per head from each latent (prefill, chunks); True
    folds ``W_kvb``'s key half into the query and its value half into the
    output, so the rows are read as they lie in the cache (decode)."""
    B, Sq, NH, dn = q_nope.shape
    rkv, dv = config.kv_lora_rank, config.v_head_dim
    dtype = q_nope.dtype
    wkv_b = layer["wkv_b"].reshape(rkv, NH, dn + dv)

    def grouped(x, R):  # [B, ...] -> [R, B // R, ...]
        return x.reshape(R, B // R, *x.shape[1:])

    if absorb:
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.einsum("bqhd,chd->bqhc", q_nope, wkv_b[..., :dn],
                               preferred_element_type=_F32).astype(dtype)
        q_full = jnp.concatenate([q_lat, q_rope], axis=-1)  # [B, Sq, NH, rkv + dr]
    scores, values = [], []
    for rows, mask in segments:
        R = rows.shape[0]
        if absorb:
            s = jnp.einsum("rnqhw,rkw->rnhqk", grouped(q_full, R), rows,
                           preferred_element_type=_F32)
            values.append(rows[..., :rkv])
        else:
            kv = jnp.einsum("rkc,chd->rkhd", rows[..., :rkv], wkv_b,
                            preferred_element_type=_F32).astype(dtype)
            s = jnp.einsum("rnqhd,rkhd->rnhqk", grouped(q_nope, R), kv[..., :dn],
                           preferred_element_type=_F32)
            s = s + jnp.einsum("rnqhd,rkd->rnhqk", grouped(q_rope, R), rows[..., rkv:],
                               preferred_element_type=_F32)
            values.append(kv[..., dn:])
        s = s.reshape(B, NH, Sq, rows.shape[1]) * config.attn_scale
        scores.append(jnp.where(mask[:, None, :, :], s, _NEG))
    probs = jax.nn.softmax(jnp.concatenate(scores, axis=-1), axis=-1).astype(dtype)
    out, start = 0.0, 0
    for v in values:
        R, S = v.shape[:2]
        p = grouped(probs[..., start: start + S], R)
        start += S
        eq = "rnhqk,rkc->rnqhc" if absorb else "rnhqk,rkhd->rnqhd"
        out = out + jnp.einsum(eq, p, v, preferred_element_type=_F32).reshape(B, Sq, NH, -1)
    if absorb:
        with jax.named_scope("mla_absorb"):
            out = jnp.einsum("bqhc,chd->bqhd", out.astype(dtype), wkv_b[..., dn:],
                             preferred_element_type=_F32)
    return out.astype(dtype).reshape(B, Sq, NH * dv)


@jax.named_scope("attn_out")
def _attn_out(layer: Params, attn: jax.Array) -> jax.Array:
    return _dot(attn, layer["wo"])


def _write_cache(cache: jax.Array, new: jax.Array, write_index) -> jax.Array:
    """The three cache writes of ``llama._block``: positions 0.. (prefill), a
    scalar offset (continuation, decode), per-row offsets (verify)."""
    new = new.astype(cache.dtype)
    if write_index is None:
        return lax.dynamic_update_slice_in_dim(cache, new, 0, axis=1)
    if getattr(write_index, "ndim", 0) == 1:
        return jax.vmap(
            lambda c, x, off: lax.dynamic_update_slice_in_dim(c, x, off, axis=0)
        )(cache, new, write_index)
    return lax.dynamic_update_slice_in_dim(cache, new, write_index, axis=1)


# ---------------------------------------------------------------------------
# MLP sublayers
# ---------------------------------------------------------------------------

def _swiglu(h, gate, up, down):
    return _dot(jax.nn.silu(_dot(h, gate)) * _dot(h, up), down)


@jax.named_scope("moe_router")
def route(config: ModelConfig, layer: Params, h: jax.Array):
    """h [T, H] -> (chosen [T, K] expert ids, weights [T, K] f32): sigmoid
    scores, top-k of score + bias (``lax.top_k``: ties to the lower id),
    weights from the scores alone, normalised, times the scaling factor."""
    g = jax.nn.sigmoid(jnp.dot(h, layer["w_router"], preferred_element_type=_F32))
    _, chosen = lax.top_k(g + layer["router_bias"], config.num_experts_per_tok)
    w = jnp.take_along_axis(g, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * config.routed_scaling_factor
    return chosen, w


#: The expert stacks. The layer scan does not slice them: the grouped product
#: is a kernel call and wants its operand whole, so a scanned [E, ...] slice
#: of the [Le, E, ...] stack is copied out first — 1.4 GB a layer a step at
#: full width, half of the step's device time (my chip run, PR 28).
_EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def _grouped_dot(x: jax.Array, w: jax.Array, counts: jax.Array, index) -> jax.Array:
    """x [M, K] (rows grouped by expert) times each group's own matrix. ``w``
    is one layer's [E, K, N], or every expert layer's [Le, E, K, N] read in
    place: all Le * E matrices are groups and only layer ``index``'s are given
    rows (an empty group is never read)."""
    if w.ndim == 4:
        counts = jnp.zeros(w.shape[:2], counts.dtype).at[index].set(counts).reshape(-1)
        w = w.reshape(-1, *w.shape[2:])
    return lax.ragged_dot(x, w, counts)


def _expert_act(layer: Params, project) -> jax.Array:
    """What goes into the down projection, from ``project(name)``, the
    tokens' product with the stack ``layer[name]``: gated ``silu(gate) * up``
    where the layer has a ``w_gate``, else non-gated ``relu(up)^2``
    (models/hybrid.py) with the zero columns ``w_up`` is stored with left
    behind."""
    if "w_gate" in layer:
        return jax.nn.silu(project("w_gate")) * project("w_up")
    return jnp.square(jax.nn.relu(project("w_up")))[..., : layer["w_down"].shape[-2]]


def _every_expert(layer: Params, h: jax.Array, chosen: jax.Array, w: jax.Array) -> jax.Array:
    """Every token through every expert of one layer's [E, K, N] stacks,
    combined by the router's weights (0 for an expert a token did not choose):
    two batched products that stream all E experts once at the HBM rate,
    whatever the routing."""
    T, E = h.shape[0], layer["w_up"].shape[0]
    combine = jnp.zeros((T, E), _F32).at[jnp.arange(T)[:, None], chosen].add(w)

    def project(name):
        return jnp.einsum("th,ehi->eti", h, layer[name], preferred_element_type=_F32).astype(h.dtype)

    act = _expert_act(layer, project)
    act = (act.astype(_F32) * combine.T[..., None]).astype(h.dtype)
    return jnp.einsum("eti,eih->th", act, layer["w_down"], preferred_element_type=_F32).astype(h.dtype)


def routed_experts(config: ModelConfig, layer: Params, h: jax.Array,
                   dense_share: Optional[float] = None):
    """h [T, H] -> (sum of the chosen experts' weighted outputs [T, H], tokens
    per held expert [E held] int32, chosen [T, K]). The T*K token-expert pairs
    are sorted by expert and each projection is one grouped product over the
    stacked expert weights (``layer`` holds one layer's, or all layers' and
    ``expert_layer``: see :func:`_grouped_dot`). Gated experts (``w_gate`` in
    the layer: three stacks) or non-gated ``W_down relu(W_up h)^2`` (two).

    Where the chip holds a share of the experts (``config.experts_held``), the
    router's choice and weights are over all ``num_experts``; the pairs of
    experts held elsewhere sort behind the last held group, where the grouped
    product gives them no matrix, and add nothing to the sum.

    ``dense_share``: the call decides on the device, from the counts it has
    anyway, and computes every expert for every token (:func:`_every_expert`)
    when more than this share of the experts has a token. The grouped
    product's kernel costs by the expert it touches (0.19 ms a touched expert
    for an up and a down product at 2688 x 1856, against 3.7 ms for all 128
    streamed whole; my chip run, PR 32), so few touched experts are cheaper
    grouped and many cheaper whole. None (the latent block, whose programs
    this leaves as they were): always grouped."""
    T, K = h.shape[0], config.num_experts_per_tok
    chosen, w = route(config, layer, h)
    index = layer.get("expert_layer")
    held = config.held_experts
    if dense_share is not None and held != config.num_experts:
        raise NotImplementedError(
            f"{config.name}: the whole-expert form (dense_share) over a held share of the experts")
    with jax.named_scope("moe_experts"):
        flat = chosen.reshape(-1)
        if held != config.num_experts:
            local = flat - config.expert_offset
            here = (local >= 0) & (local < held)
            flat = jnp.where(here, local, held)  # elsewhere: behind the last held group
        order = jnp.argsort(flat, stable=True)
        counts = jnp.bincount(flat, length=held).astype(jnp.int32)

        def grouped():
            x = jnp.take(h, order // K, axis=0)  # [T*K, H], grouped by expert
            act = _expert_act(layer, lambda name: _grouped_dot(x, layer[name], counts, index))
            y = _grouped_dot(act, layer["w_down"], counts, index)  # [T*K, H]
            y = jnp.take(y, jnp.argsort(order), axis=0)  # back to token order
            if held != config.num_experts:
                y = jnp.where(here[:, None], y, 0)
            return jnp.sum(y.reshape(T, K, -1).astype(_F32) * w[..., None], axis=1).astype(h.dtype)

        if dense_share is None:
            out = grouped()
        else:
            many = jnp.sum(counts > 0) > dense_share * config.num_experts
            out = lax.cond(many, lambda: _every_expert(layer, h, chosen, w), grouped)
    return out, counts, chosen


def _moe_mlp(config: ModelConfig, layer: Params, h: jax.Array):
    """h [B, S, H] -> (routed + shared [B, S, H], {counts, chosen}): the held
    experts' share of the routed sum, and the shared experts as one fused
    SwiGLU (their sum), times 1 / n_shared_experts where the model averages
    them (``config.shared_experts_averaged``: the parallel block's,
    models/hybrid.py)."""
    B, S, H = h.shape
    out, counts, chosen = routed_experts(config, layer, h.reshape(B * S, H))
    with jax.named_scope("moe_shared"):
        shared = _swiglu(h, layer["ws_gate"], layer["ws_up"], layer["ws_down"])
        if config.shared_experts_averaged:
            shared = shared * jnp.asarray(1.0 / config.n_shared_experts, shared.dtype)
    return out.reshape(B, S, H) + shared, {"counts": counts, "chosen": chosen}


def _mlp_sublayer(config: ModelConfig, layer: Params, X: jax.Array):
    """The MLP sublayer under its mixer -> (X, the router's {counts, chosen}
    for an expert layer, {} for a dense one)."""
    routed = {}

    def fn(h):
        if "w_router" in layer:
            out, r = _moe_mlp(config, layer, h)
            routed.update(r)
            return out
        with jax.named_scope("mlp"):
            return _swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])

    X = _hc_sublayer(config, layer, "hc_mlp", "mlp_norm", X, fn)
    return X, routed


# ---------------------------------------------------------------------------
# The two stacks
# ---------------------------------------------------------------------------

def _refuse(config: ModelConfig, block: str = "the latent block", **unsupported) -> None:
    on = [name for name, value in unsupported.items() if value is not None]
    if on:
        raise NotImplementedError(
            f"{config.name}: {block} does not run with {', '.join(on)} yet"
        )


def _scan_groups(config: ModelConfig, params: Params, X, body, per_layer):
    """Run ``body(X, layer, scanned) -> (X, ys)`` over the dense-leading
    layers, then the expert layers; ``per_layer`` holds arrays with a leading
    [L] axis that are split between the two scans. The expert stacks are not
    scanned: ``layer`` carries them whole beside ``expert_layer``, this layer's
    place in them. Returns (X, [ys of each scan])."""
    Ld = config.first_k_dense
    outs = []
    for group, lo, hi in (("dense_layers", 0, Ld), ("layers", Ld, config.num_layers)):
        if hi == lo:
            continue
        routed = "w_router" in params[group]
        whole = {k: params[group][k] for k in _EXPERT_STACKS} if routed else {}
        xs = {"layers": {k: v for k, v in params[group].items() if k not in whole},
              **{k: v[lo:hi] for k, v in per_layer.items()}}
        if whole:
            xs["layers"]["expert_layer"] = jnp.arange(hi - lo, dtype=jnp.int32)

        def step(X, scanned, whole=whole):
            return body(X, {**scanned["layers"], **whole}, scanned)

        X, ys = lax.scan(step, X, xs)
        outs.append(ys)
    return X, outs


def _streams_in(config: ModelConfig, x: jax.Array) -> jax.Array:
    return jnp.repeat(x[:, :, None, :], config.hc_mult, axis=2)


def _streams_out(X: jax.Array) -> jax.Array:
    return jnp.sum(X.astype(_F32), axis=2).astype(X.dtype)


def _collect(aux: Optional[dict], outs) -> None:
    """The expert layers' router outputs into ``aux`` (see the module docstring)."""
    if aux is None:
        return
    for key in ("counts", "chosen") if "moe_chosen" in aux else ("counts",):
        for ys in outs:  # only the expert layers' scan carries them
            if key in ys:
                aux["moe_" + key] = ys[key]


def _attend_cache(config, layer, h, positions, cached, write_index, key_mask,
                  prefix=None, prefix_mask=None):
    """One layer's attention over its dense cache ``cached`` [B, Smax, 1,
    width] with this call's rows written in -> (out, the rows [B, Smax,
    width]). With ``prefix`` ([R, P, 1, width]: a decode or verify step) the
    absorbed form, without the materialised one."""
    q_nope, q_rope = _mla_q(config, layer, h, positions)
    with jax.named_scope("kv_write"):
        rows = _write_cache(
            cached[:, :, 0], _mla_kv_latent(config, layer, h, positions), write_index
        )
    segments = [(rows, key_mask)]
    if prefix is not None:
        segments.insert(0, (prefix[:, :, 0], prefix_mask))
    out = mla_attend(config, layer, q_nope, q_rope, segments, absorb=prefix is not None)
    return _attn_out(layer, out), rows


def _attend_paged(config, layer, h, positions, pool_k, layer_no, prefix_idx, gen_idx,
                  write_index, key_mask, prefix_mask, keep):
    """One layer's attention over cache layer ``layer_no`` of the page pool:
    the rows' pages gathered by block table, this step's rows ([B, Sq, width],
    handed to ``keep``) inserted, the absorbed form -> out."""
    q_nope, q_rope = _mla_q(config, layer, h, positions)
    col = _mla_kv_latent(config, layer, h, positions).astype(pool_k.dtype)  # [B, Sq, W]
    keep(col)
    with jax.named_scope("paged_attn"):
        def gather(slots):  # [B|R, P] -> [B|R, P, W]
            return pool_gather(pool_k, pool_index(pool_k, layer_no, slots), col.shape[-1])

        prefix_rows = gather(prefix_idx)
        gen_rows = _write_cache(gather(gen_idx), col, write_index)
        out = mla_attend(
            config, layer, q_nope, q_rope,
            [(prefix_rows, prefix_mask), (gen_rows, key_mask)], absorb=True,
        )
    return _attn_out(layer, out)


def apply_stack(
    config: ModelConfig,
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    cache: KVCache,
    write_index,
    key_mask: jax.Array,
    prefix: Optional[KVCache] = None,
    prefix_mask: Optional[jax.Array] = None,
    aux: Optional[dict] = None,
    sp_ring_mesh=None,
    mesh=None,
) -> Tuple[jax.Array, KVCache]:
    """``llama._apply_stack`` for the latent block. cache.k [L, B, Smax, 1,
    width]; with a ``prefix`` ([L, R, P, 1, width]: a decode or verify step)
    attention takes the absorbed form, without one the materialised form. A
    next-token module's cache layer (the last) goes through as it came."""
    _refuse(config, mesh=mesh, sp_ring_mesh=sp_ring_mesh)

    def body(X, layer, scanned):
        new_rows = []

        def attn(h):
            out, rows = _attend_cache(
                config, layer, h, positions, scanned["kv"], write_index, key_mask,
                scanned.get("prefix"), prefix_mask,
            )
            new_rows.append(rows)
            return out

        X = _hc_sublayer(config, layer, "hc_attn", "attn_norm", X, attn)
        X, routed = _mlp_sublayer(config, layer, X)
        return X, {"kv": new_rows[0][:, :, None, :], **routed}

    per_layer = {"kv": cache.k}
    if prefix is not None:
        per_layer["prefix"] = prefix.k
    X, outs = _scan_groups(config, params, _streams_in(config, x), body, per_layer)
    _collect(aux, outs)
    new_k = [ys["kv"] for ys in outs]
    if config.num_nextn_predict_layers:
        new_k.append(cache.k[config.num_layers:])
    new_k = jnp.concatenate(new_k, axis=0)
    return _streams_out(X), KVCache(k=new_k, v=cache.v)


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
    aux: Optional[dict] = None,
    mesh=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``llama._apply_stack_paged`` for the latent block: each layer gathers
    its rows' latent pages from the whole pool ([L, flat, 1, stored width]) by
    block table and layer number (:func:`pool_gather`), inserts this step's
    rows, and attends in the absorbed form (XLA; the Pallas paged kernel's
    (KVH, D) shapes do not fit a latent page). Returns (x, k_cols [L, B, 1,
    width], v_cols [L, B, 1, 0]) at ``Sq == 1``; a step of more positions a
    row (the drafted step) gets k_cols [L, B, Sq, 1, width]. L counts the
    stack's layers, not a next-token module's."""
    _refuse(config, mesh=mesh)
    one = x.shape[1] == 1

    def body(X, layer, scanned):
        cols = []

        def attn(h):
            return _attend_paged(
                config, layer, h, positions, pool_kv.k, scanned["layer"],
                prefix_idx, gen_idx, write_index, key_mask, prefix_mask,
                keep=lambda col: cols.append(col[:, 0] if one else col),
            )

        X = _hc_sublayer(config, layer, "hc_attn", "attn_norm", X, attn)
        X, routed = _mlp_sublayer(config, layer, X)
        return X, {"col": cols[0][..., None, :], **routed}

    layers = jnp.arange(config.num_layers, dtype=jnp.int32)
    X, outs = _scan_groups(config, params, _streams_in(config, x), body, {"layer": layers})
    _collect(aux, outs)
    if aux is not None:
        attended = jnp.sum(prefix_mask, dtype=jnp.int32) + jnp.sum(key_mask, dtype=jnp.int32)
        aux["mla_latent_rows_read"] = attended * config.num_layers
    k_cols = jnp.concatenate([ys["col"] for ys in outs], axis=0)  # [L, B, 1, W]
    return _streams_out(X), k_cols, jnp.zeros(k_cols.shape[:-1] + (0,), pool_kv.v.dtype)


# ---------------------------------------------------------------------------
# The next-token module
# ---------------------------------------------------------------------------

def _mtp_layer(params: Params) -> Params:
    """The module's one block, off its stack of one (a reshape: nothing is copied)."""
    return {k: v[0] for k, v in params["mtp"]["layers"].items()}


@jax.named_scope("mtp_embed")
def mtp_input(config: ModelConfig, params: Params, h: jax.Array, emb: jax.Array) -> jax.Array:
    """``W_eh [RMSNorm_h(h) ; RMSNorm_e(emb)]``: the main stack's output before
    its final norm, and the embedding of the token one position on."""
    mtp = params["mtp"]
    pair = jnp.concatenate([rms_norm(h.astype(emb.dtype), mtp["hnorm"], config.rms_eps),
                            rms_norm(emb, mtp["enorm"], config.rms_eps)], axis=-1)
    return _dot(pair, mtp["eh_proj"])


def _mtp_block(config: ModelConfig, params: Params, h: jax.Array, emb: jax.Array,
               attend, aux: Optional[dict]) -> jax.Array:
    """The module's block on the pairs (``h``, ``emb``) -> its output after the
    module's own norm. ``attend(layer, x)`` is the caller's attention over the
    module's cache layer; the router's counts (and choices, where the caller
    asked for them) join the stack's in ``aux``: one more expert layer."""
    layer = _mtp_layer(params)
    with jax.named_scope("mtp_block"):
        X = _streams_in(config, mtp_input(config, params, h, emb))
        X = _hc_sublayer(config, layer, "hc_attn", "attn_norm", X, lambda x: attend(layer, x))
        X, routed = _mlp_sublayer(config, layer, X)
    for key in ("counts", "chosen") if aux is not None else ():
        if aux.get("moe_" + key) is not None:
            aux["moe_" + key] = jnp.concatenate([aux["moe_" + key], routed[key][None]], axis=0)
    return rms_norm(_streams_out(X), params["mtp"]["final_norm"], config.rms_eps)


def mtp_ingest(config: ModelConfig, params: Params, emb: jax.Array, x: jax.Array,
               positions: jax.Array, cache: KVCache, write_index, last: jax.Array,
               state: Optional[dict]) -> KVCache:
    """A prompt's (or a chunk's) rows of the module's cache layer, written
    beside the main stack's: slot ``p`` holds the latent row of the pair
    ``(h_{p-1}, t_p)``, which depends on the block's input alone, so no
    attention and no expert runs here. ``emb`` [B, S, H] the tokens'
    embeddings, ``x`` the main stack's output at the same positions, ``last``
    [B] the last valid row. ``state["mtp_h"]``: ``h`` just before these
    positions going in (a chunk's predecessor; zeros at a prompt's start, whose
    slot 0 is masked wherever the layer is read), at ``last`` coming out."""
    B = x.shape[0]
    before = state.get("mtp_h") if state is not None else None
    before = jnp.zeros((B, 1, x.shape[-1]), x.dtype) if before is None else before[0][:, None].astype(x.dtype)
    layer = _mtp_layer(params)
    with jax.named_scope("mtp_block"):
        inp = mtp_input(config, params, jnp.concatenate([before, x[:, :-1]], axis=1), emb)
        h = rms_norm(inp, layer["attn_norm"], config.rms_eps)
        rows = _write_cache(cache.k[-1, :, :, 0], _mla_kv_latent(config, layer, h, positions),
                            write_index)
    if state is not None:
        state["mtp_h"] = (jnp.take_along_axis(x, last.reshape(B, 1, 1).astype(jnp.int32), axis=1)[:, 0],)
    return KVCache(k=cache.k.at[-1, :, :, 0].set(rows), v=cache.v)


def mtp_forward(config: ModelConfig, params: Params, tokens: jax.Array, x: jax.Array) -> jax.Array:
    """The module over whole sequences with no cache (tests): tokens [B, S],
    ``x`` the main stack's output before its final norm -> the module's output
    after its own norm [B, S - 1, H]; row ``i`` (the pair ``(h_i, t_{i+1})``)
    predicts ``t_{i+2}``."""
    B, S = tokens.shape
    emb = jnp.take(params["embed"], tokens[:, 1:], axis=0)
    positions = jnp.broadcast_to(jnp.arange(1, S)[None], (B, S - 1))
    causal = jnp.tril(jnp.ones((S - 1, S - 1), bool))[None]
    empty = jnp.zeros((B, S - 1, 1, config.cache_widths[1]), x.dtype)

    def attend(layer, h):
        return _attend_cache(config, layer, h, positions, empty, None, causal)[0]

    return _mtp_block(config, params, x[:, :-1], emb, attend, None)


def mtp_paged(
    config: ModelConfig,
    params: Params,
    h: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    pool_kv: KVCache,
    prefix_idx: jax.Array,
    gen_idx: jax.Array,
    write_index: jax.Array,
    key_mask: jax.Array,
    prefix_mask: jax.Array,
    aux: Optional[dict] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The module through the page pool, for the loop: ``h`` [B, Sq, H] the
    main stack's output at the positions before ``positions``, ``tokens`` [B,
    Sq] the tokens AT ``positions``. Masks and indices as
    :func:`apply_stack_paged` takes them (the caller masks position 0, which
    holds nothing in this layer). Returns (the module's output after its norm
    [B, Sq, H], its cache rows [B, Sq, 1, width])."""
    cols = []

    def attend(layer, x):
        return _attend_paged(
            config, layer, x, positions, pool_kv.k, config.num_layers,
            prefix_idx, gen_idx, write_index, key_mask, prefix_mask, keep=cols.append,
        )

    out = _mtp_block(config, params, h, jnp.take(params["embed"], tokens, axis=0), attend, aux)
    if aux is not None and "mla_latent_rows_read" in aux:
        aux["mla_latent_rows_read"] += (
            jnp.sum(prefix_mask, dtype=jnp.int32) + jnp.sum(key_mask, dtype=jnp.int32))
    return out, cols[0][:, :, None, :]
