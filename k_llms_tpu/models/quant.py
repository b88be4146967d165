"""Int8 weight-only quantization for the matmul weights.

The reference has no model layer at all (its "engine" is the OpenAI HTTP API,
`/root/reference/k_llms/resources/completions/completions.py:73`); this is a
capability of the local TPU engine. Autoregressive decode is HBM-bandwidth
bound: every step streams the full weight set from HBM. Storing matmul weights
as int8 (symmetric, per-output-channel scales) halves that traffic, and lets
8B-class weights fit a single v5e chip (16 GB HBM) with room for KV caches.

Design: a :class:`QTensor` pytree (int8 payload + f32 scale) flows through the
same params tree, ``lax.scan``, and ``pjit`` shardings as the bf16 weights.
``qdot(x, w)`` dispatches on the weight type, so the model code in
``models/llama.py`` is quantization-agnostic: the int8→bf16 cast happens inside
the fused matmul (weights are read from HBM as int8; the per-channel scale is
applied to the matmul output, so no dequantized copy is ever materialized).
Embeddings and norms stay bf16 — lookups only stream the rows they touch.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.w4matmul import Q4Tensor, pack_int4, supports_int4, unpack_int4, w4_matmul


class QTensor(NamedTuple):
    """Symmetric per-output-channel int8 weight: ``q`` has the weight's shape
    [..., in, out]; ``scale`` is f32 [..., 1, out]."""

    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype


WeightLike = Union[jax.Array, QTensor, Q4Tensor]

# Matmul weights to quantize (all contract over axis -2). Embeddings and norms
# stay in the model dtype.
_QUANT_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# Megatron-style tensor-parallel layout of the quantized matmuls: column-
# parallel weights shard output columns over the model axis; row-parallel
# weights shard the contraction axis (their matmul psums partials).
_COL_PARALLEL_KEYS = frozenset({"wq", "wk", "wv", "w_gate", "w_up"})
_ROW_PARALLEL_KEYS = frozenset({"wo", "w_down"})


def _dense_quant_shapes(config) -> "Dict[str, tuple]":
    """(K, N) of each dense quantized matmul (MoE expert stacks are 4D with
    the layer axis and int4-ineligible, so they are not listed)."""
    H, I = config.hidden_size, config.intermediate_size
    Q, KV = config.q_dim, config.kv_dim
    return {
        "wq": (H, Q),
        "wk": (H, KV),
        "wv": (H, KV),
        "wo": (Q, H),
        "w_gate": (H, I),
        "w_up": (H, I),
        "w_down": (I, H),
    }


def int4_mesh_compatible(config, tp: int) -> bool:
    """True when every int4-eligible weight can shard over ``tp`` model-axis
    devices without splitting a quantization group (row-parallel needs
    K % (GROUP*tp) == 0) or fracturing columns (col-parallel needs
    N % tp == 0). MoE configs keep int4 off the experts already."""
    from ..ops.w4matmul import GROUP

    if tp <= 1:
        return True
    if config.num_experts > 0:
        return False  # expert einsums have no sharded-int4 path
    shapes = dict(_dense_quant_shapes(config))
    shapes["lm_head"] = (config.hidden_size, config.vocab_size)
    slow = []
    for key, (k, n) in shapes.items():
        ndim = 2 if key == "lm_head" else 3
        if not _int4_eligible_shape(ndim, k, n):
            continue  # stays int8, XLA partitions it natively
        if key in _ROW_PARALLEL_KEYS:
            if k % (GROUP * tp):
                return False
            local_k, local_n = k // tp, n
        else:
            if n % tp:
                return False
            local_k, local_n = k, n // tp
        # Correct but slow: a local shard whose blocking misses the Pallas
        # kernel's grid takes the XLA dequant fallback — int4's HBM-traffic
        # win evaporates for that weight. Surface it. (Divisibility by ANY
        # block choice == divisibility by the smallest, since the choices are
        # multiples of it — single source of truth in ops/w4matmul.py.)
        from ..ops.w4matmul import KERNEL_K_BLOCKS, KERNEL_N_BLOCKS

        if local_k % min(KERNEL_K_BLOCKS) or local_n % min(KERNEL_N_BLOCKS):
            slow.append((key, (local_k, local_n)))
    if slow:
        import logging

        logging.getLogger(__name__).warning(
            "int4 on model parallel=%d for %s: local shards %s miss the w4a16 "
            "kernel blocking and will use the XLA dequant fallback (correct, "
            "but without the 4-bit HBM-traffic win)",
            tp,
            config.name,
            slow,
        )
    return True


def _quant_leaf_nodes(params: "Dict[str, Any]"):
    """The quantizable matmul leaf-nodes of a params tree (single source for
    every stored-layout probe). The hybrid stack's per-layer list
    (models/hybrid.py) holds none: ``quantize_params`` refuses it."""
    if isinstance(params["layers"], dict):
        for key in _QUANT_LAYER_KEYS:
            yield params["layers"].get(key)
    yield params.get("lm_head")


def tree_has_q4(params: "Dict[str, Any]") -> bool:
    """True when any quantized matmul leaf is stored int4 (pre-quantized
    checkpoints keep their layout through quantize_weight_bits)."""
    return any(isinstance(w, Q4Tensor) for w in _quant_leaf_nodes(params))


def tree_fully_quantized(params: "Dict[str, Any]") -> bool:
    """True when every quantizable matmul already holds a QTensor/Q4Tensor —
    ``quantize_params`` would hand each one back unchanged."""
    return all(isinstance(w, (QTensor, Q4Tensor)) for w in _quant_leaf_nodes(params))


def stored_quant_layout(params: "Dict[str, Any]") -> "str | None":
    """The quantization a params tree actually stores — 'int4' if any leaf is
    Q4Tensor, 'int8' if any is QTensor, None for a plain bf16 tree. Lets a
    caller follow a pre-quantized checkpoint's layout whatever flag was
    passed."""
    nodes = list(_quant_leaf_nodes(params))
    if any(isinstance(w, Q4Tensor) for w in nodes):
        return "int4"
    if any(isinstance(w, QTensor) for w in nodes):
        return "int8"
    return None


def align_quantized_specs(
    params: "Dict[str, Any]", qspecs: "Dict[str, Any]", pspecs: "Dict[str, Any]"
) -> "Dict[str, Any]":
    """Reconcile a spec tree with the ACTUAL layout of a pre-quantized params
    tree: quantize_weight_bits keeps a checkpoint's stored QTensor/Q4Tensor
    layout regardless of the requested bits, so out_shardings built from the
    request alone would diverge in pytree structure and crash pjit."""

    def reconcile(w, spec_node, weight_spec):
        if isinstance(w, Q4Tensor) and not isinstance(spec_node, Q4Tensor):
            return Q4Tensor(q=weight_spec, scale=weight_spec)
        if isinstance(w, QTensor) and not isinstance(spec_node, QTensor):
            parts = list(weight_spec)
            if len(parts) >= 2:
                parts[-2] = None
            return QTensor(q=weight_spec, scale=P(*parts))
        return spec_node

    layers = dict(qspecs["layers"])
    for key in _QUANT_LAYER_KEYS:
        layers[key] = reconcile(
            params["layers"].get(key), layers[key], pspecs["layers"][key]
        )
    out = dict(qspecs)
    out["layers"] = layers
    out["lm_head"] = reconcile(params.get("lm_head"), qspecs["lm_head"], pspecs["lm_head"])
    return out


def mark_int4_partitioning(params: "Dict[str, Any]", mesh) -> "Dict[str, Any]":
    """Stamp every Q4Tensor leaf-node with its tensor-parallel layout + mesh so
    ``qdot`` routes through the shard_mapped kernel. Idempotent; trees without
    Q4 nodes pass through unchanged (checkpoint loads arrive unmarked)."""
    layers = dict(params["layers"])
    for key in _QUANT_LAYER_KEYS:
        w = layers.get(key)
        if isinstance(w, Q4Tensor):
            part = "col" if key in _COL_PARALLEL_KEYS else "row"
            layers[key] = Q4Tensor(w.q, w.scale, part=part, mesh=mesh)
    out = dict(params)
    out["layers"] = layers
    head = out.get("lm_head")
    if isinstance(head, Q4Tensor):
        out["lm_head"] = Q4Tensor(head.q, head.scale, part="col", mesh=mesh)
    return out


def quantize_weight(w: jax.Array) -> QTensor:
    """Symmetric int8 per-output-channel: scale over the contraction axis (-2)."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QTensor(q=q, scale=scale)


def qdot(x: jax.Array, w: WeightLike) -> jax.Array:
    """``x @ w`` for a plain array, a QTensor, or a Q4Tensor. For QTensor the
    int8 payload is cast inside the matmul (HBM reads stay int8) and the
    per-channel scale is applied to the output. For Q4Tensor the Pallas w4a16
    kernel unpacks nibbles in VMEM (HBM reads stay int4); the kernel is
    Mosaic-only, so off-TPU the product is the XLA dequant reference (the
    interpreter is something only a test asks :func:`w4_matmul` for)."""
    if isinstance(w, Q4Tensor):
        x2 = x.reshape(-1, x.shape[-1])
        if jax.default_backend() != "tpu":
            out = (x2.astype(jnp.float32) @ unpack_int4(w)).astype(x.dtype)
        elif w.part is not None and w.mesh is not None:
            from ..ops.w4matmul import w4_matmul_tp

            out = w4_matmul_tp(x2, w)
        else:
            out = w4_matmul(x2, w)
        return out.reshape(*x.shape[:-1], w.q.shape[-1])
    if isinstance(w, QTensor):
        out = x @ w.q.astype(x.dtype)
        return out * w.scale[..., 0, :].astype(out.dtype)
    return x @ w


def qeinsum(spec: str, x: jax.Array, w: WeightLike) -> jax.Array:
    """``einsum(spec, x, w)`` for a plain array or QTensor weight. Requires the
    output's trailing axes to line up with the weight's non-contracted axes
    (true for the MoE expert einsums: "bsh,ehi->bsei", "bsei,eih->bseh"), so
    the squeezed per-channel scale broadcasts onto the output."""
    if isinstance(w, QTensor):
        out = jnp.einsum(spec, x, w.q.astype(x.dtype))
        return out * w.scale[..., 0, :].astype(out.dtype)
    return jnp.einsum(spec, x, w)


def _int4_eligible_shape(ndim: int, k: int, n: int) -> bool:
    """Q4 needs whole 256-row K blocks and 128-col N blocks; MoE expert stacks
    ([L, E, K, N], ndim 4) stay int8 — their einsum contraction has no w4
    kernel. Tiny test models fail the divisibility and stay int8 too. Single
    predicate for BOTH the quantize path and the random-init path, so the two
    always build the same QTensor/Q4Tensor tree layout for a given config."""
    return ndim <= 3 and supports_int4(k) and n % 128 == 0


def _int4_eligible(w: jax.Array) -> bool:
    return _int4_eligible_shape(w.ndim, w.shape[-2], w.shape[-1])


def quantize_weight_bits(w: WeightLike, bits: int) -> WeightLike:
    if isinstance(w, (QTensor, Q4Tensor)):
        # Already quantized — e.g. an orbax checkpoint of a quantized tree
        # loaded with the quantization flag still set. Keep the stored layout
        # (re-quantizing int8<->int4 from the lossy payload would only lose
        # more precision).
        return w
    if bits == 4 and _int4_eligible(w):
        return pack_int4(w)
    return quantize_weight(w)


def quantize_params(params: Dict[str, Any], bits: int = 8) -> Dict[str, Any]:
    """Quantize the seven block matmuls and lm_head; leave embed/norms as-is.

    ``bits=4`` packs eligible weights group-wise int4 (:mod:`ops.w4matmul`);
    ineligible ones (MoE expert stacks, non-divisible shapes) fall back int8.
    """
    if "dense_layers" in params:
        raise NotImplementedError(
            "quantize_params: the latent block's tree (models/latent.py) is served in "
            "its own dtype; int8/int4 expert stacks under the grouped products are not written"
        )
    if isinstance(params["layers"], list):
        raise NotImplementedError(
            "quantize_params: the hybrid stack's tree (models/hybrid.py) is served in its "
            "own dtype; int8/int4 for the non-gated expert stacks and the Mamba-2 "
            "projections are not written"
        )
    layers = dict(params["layers"])
    for key in _QUANT_LAYER_KEYS:
        layers[key] = quantize_weight_bits(layers[key], bits)
    out = dict(params)
    out["layers"] = layers
    out["lm_head"] = quantize_weight_bits(params["lm_head"], bits)
    return out


def init_params_quantized(
    config, key: jax.Array, dtype=None, bits: int = 8, dist: str = "random"
) -> Dict[str, Any]:
    """Random int8-quantized init, building the QTensor tree DIRECTLY.

    For synthetic flagship benches: an 8B bf16 tree (~16 GB) cannot sit in one
    v5e chip's HBM next to its int8 copy during quantization, so the usual
    init-then-quantize path is unusable at that scale. Here the int8 payloads
    are drawn uniformly and scales are constants chosen so effective weights
    have ~N(0, 1/fan_in) magnitude (finite logits; a random model is all a
    synthetic bench needs). Mirrors the tree structure of
    ``llama.init_params`` + ``quantize_params``.

    ``dist="cheap"`` replaces every PRNG draw with a broadcast deterministic
    pattern (same shapes/scales, zero threefry work). For sharding dry runs on
    virtual CPU meshes: non-partitionable threefry gets REPLICATED under
    GSPMD — every virtual device computes the full billion-element draw — so
    a random 8B-width init costs minutes of host time that validates nothing
    the pattern init doesn't (the dry run checks layouts and compiled
    programs, not weight statistics).
    """
    import math

    if dist not in ("random", "cheap"):
        raise ValueError(f"Unknown dist {dist!r}; use 'random' or 'cheap'")
    if config.is_latent:
        raise NotImplementedError(
            f"{config.name}: no quantized init for the latent block; it is served in "
            f"{config.dtype} (int8/int4 expert stacks are not written)"
        )
    if config.is_hybrid:
        raise NotImplementedError(
            f"{config.name}: no quantized init for the hybrid stack; it is served in "
            f"{config.dtype} (int8/int4 for its per-layer expert stacks are not written)"
        )
    cheap = dist == "cheap"
    dtype = dtype or config.jax_dtype
    H, I, V = config.hidden_size, config.intermediate_size, config.vocab_size
    L, Q, KV = config.num_layers, config.q_dim, config.kv_dim

    def _pattern_i8(shape) -> jax.Array:
        # Varies along the output-channel axis only: broadcast is trivially
        # partitionable, and matmul outputs stay non-degenerate.
        row = ((jnp.arange(shape[-1]) * 37) % 251 - 125).astype(jnp.int8)
        return jnp.broadcast_to(row, shape)

    def qinit(k, shape) -> WeightLike:
        K, N = shape[-2], shape[-1]
        if bits == 4 and _int4_eligible_shape(len(shape), K, N):
            from ..ops.w4matmul import GROUP

            # Random packed bytes = two uniform nibbles in [-8, 7] apiece
            # (std = sqrt(E[k^2]-mu^2) over -8..7 ~= 4.61); scale so effective
            # weights are ~N(0, 1/fan_in).
            nibble_std = math.sqrt(sum(v * v for v in range(-8, 8)) / 16 - 0.25)
            pshape = shape[:-2] + (K // 2, N)
            q = (
                _pattern_i8(pshape)
                if cheap
                else jax.random.randint(k, pshape, -128, 128, jnp.int8)
            )
            scale_val = 1.0 / (nibble_std * math.sqrt(K))
            scale = jnp.full(shape[:-2] + (K // GROUP, N), scale_val, jnp.float32)
            return Q4Tensor(q=q, scale=scale)
        q = (
            _pattern_i8(shape)
            if cheap
            else jax.random.randint(k, shape, -127, 128, jnp.int8)
        )
        # std(uniform int8) = 127/sqrt(3); scale it to 1/sqrt(fan_in).
        scale_val = math.sqrt(3.0) / (127.0 * math.sqrt(shape[-2]))
        scale = jnp.full(shape[:-2] + (1, shape[-1]), scale_val, jnp.float32)
        return QTensor(q=q, scale=scale)

    def normal(k, shape, scale):
        if cheap:
            row = ((jnp.arange(shape[-1]) * 53) % 17 - 8).astype(jnp.float32) / 8.0
            return jnp.broadcast_to(row * scale, shape).astype(dtype)
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    k_embed, k_layers, k_head = jax.random.split(key, 3)
    ks = jax.random.split(k_layers, 8)
    norm_init = jnp.zeros if config.norm_offset else jnp.ones
    layers: Dict[str, Any] = {
        "attn_norm": norm_init((L, H), dtype),
        "wq": qinit(ks[0], (L, H, Q)),
        "wk": qinit(ks[1], (L, H, KV)),
        "wv": qinit(ks[2], (L, H, KV)),
        "wo": qinit(ks[3], (L, Q, H)),
        "mlp_norm": norm_init((L, H), dtype),
    }
    if config.num_experts > 0:
        E = config.num_experts
        layers["w_router"] = normal(ks[7], (L, H, E), 1.0 / math.sqrt(H))
        layers["w_gate"] = qinit(ks[4], (L, E, H, I))
        layers["w_up"] = qinit(ks[5], (L, E, H, I))
        layers["w_down"] = qinit(ks[6], (L, E, I, H))
    else:
        layers["w_gate"] = qinit(ks[4], (L, H, I))
        layers["w_up"] = qinit(ks[5], (L, H, I))
        layers["w_down"] = qinit(ks[6], (L, I, H))
    if config.qkv_bias:
        layers["bq"] = jnp.zeros((L, Q), dtype)
        layers["bk"] = jnp.zeros((L, KV), dtype)
        layers["bv"] = jnp.zeros((L, KV), dtype)
    if config.post_block_norms:
        layers["post_attn_norm"] = norm_init((L, H), dtype)
        layers["post_mlp_norm"] = norm_init((L, H), dtype)
    return {
        "embed": normal(k_embed, (V, H), 1.0 / math.sqrt(H)),
        "layers": layers,
        "final_norm": norm_init((H,), dtype),
        "lm_head": qinit(k_head, (H, V)),
    }


def quantized_param_specs(
    specs: Dict[str, Any], bits: int = 8, config=None
) -> Dict[str, Any]:
    """Map a bf16 param-spec tree to the quantized tree: the int8 payload keeps
    the weight's spec; the scale keeps it too except on the contraction axis
    (size 1 after the keepdims reduce — an axis of size 1 can't shard).

    With ``bits=4`` (requires ``config`` for the shapes), int4-eligible keys
    get Q4Tensor spec nodes instead — both the packed payload ([.., K/2, N])
    and the per-group scale ([.., K/GROUP, N]) keep the weight's spec, since
    group packing is blocked along the contraction axis."""

    def scale_spec(spec: P) -> P:
        parts = list(spec)
        if len(parts) >= 2:
            parts[-2] = None
        return P(*parts)

    q4_keys = set()
    if bits == 4 and config is not None:
        for key, (k, n) in _dense_quant_shapes(config).items():
            if config.num_experts > 0 and key in ("w_gate", "w_up", "w_down"):
                continue  # 4D expert stacks stay int8
            if _int4_eligible_shape(3, k, n):
                q4_keys.add(key)
        if _int4_eligible_shape(2, config.hidden_size, config.vocab_size):
            q4_keys.add("lm_head")

    def qspec(key: str, spec: P):
        if key in q4_keys:
            return Q4Tensor(q=spec, scale=spec)
        return QTensor(q=spec, scale=scale_spec(spec))

    layers = dict(specs["layers"])
    for key in _QUANT_LAYER_KEYS:
        layers[key] = qspec(key, layers[key])
    out = dict(specs)
    out["layers"] = layers
    out["lm_head"] = qspec("lm_head", specs["lm_head"])
    return out
