"""Checkpoint I/O: orbax-native save/load + HF safetensors import.

The reference is a stateless SDK with no checkpointing (SURVEY.md §5); the local
backend needs weight loading only. Two formats:

- **orbax**: our native format — the params pytree as-is, restorable directly
  onto a sharded mesh.
- **safetensors**: import path for Hugging Face Llama checkpoints
  (model*.safetensors + config.json), remapped into our stacked-layer layout.
"""

from __future__ import annotations

import json
import logging
import os
import zlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from ..reliability import failpoints as _failpoints
from ..types.wire import CheckpointCorruptError
from ..utils.observability import QUARANTINE_EVENTS

logger = logging.getLogger(__name__)


def _to_checkpoint_tree(tree: Any) -> Any:
    """Serialize quantized weight nodes as plain dicts with an EXPLICIT "fmt"
    leaf (4 = group-wise int4, 8 = per-channel int8) so restore dispatches on
    the recorded layout instead of inferring it from scale shapes (ADVICE r2).
    Static partition metadata (Q4Tensor.part/mesh) is process-local and not
    serialized — the engine re-marks after load."""
    from .quant import Q4Tensor, QTensor

    # 0-d ndarray, not np.int32 scalar: StandardCheckpointer's type check
    # accepts arrays only (numpy scalars fail save on current orbax).
    if isinstance(tree, Q4Tensor):
        return {"q": tree.q, "scale": tree.scale, "fmt": np.array(4, np.int32)}
    if isinstance(tree, QTensor):
        return {"q": tree.q, "scale": tree.scale, "fmt": np.array(8, np.int32)}
    if isinstance(tree, dict):
        return {k: _to_checkpoint_tree(v) for k, v in tree.items()}
    return tree


def param_summary(params: Any) -> Dict[str, Any]:
    """Operator-facing weight identity: total bytes, dtype histogram (leaf
    counts), and a content checksum (crc32 over path + bytes of every leaf,
    in deterministic pytree order). Computed once at load time on the host
    copies and surfaced through ``health()`` so operators can verify WHICH
    weights are actually serving — and the supervisor can prove a rebuilt
    engine reloaded identical ones."""
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    total = 0
    hist: Dict[str, int] = {}
    crc = 0
    for path, leaf in leaves:
        arr = np.asarray(leaf)
        total += arr.nbytes
        key = str(arr.dtype)
        hist[key] = hist.get(key, 0) + 1
        crc = zlib.crc32(jax.tree_util.keystr(path).encode(), crc)
        crc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), crc)
    return {
        "total_bytes": total,
        "num_leaves": len(leaves),
        "dtype_histogram": hist,
        "checksum": f"{crc & 0xFFFFFFFF:08x}",
    }


def _manifest_path(path: str) -> str:
    # SIBLING of the checkpoint dir, not inside it: orbax owns the dir's
    # layout and an extra file would trip its structure validation.
    return os.path.abspath(path).rstrip("/") + ".params.json"


def verify_param_integrity(
    params: Any, manifest: Optional[Dict[str, Any]] = None
) -> Dict[str, Any]:
    """Fail-fast weight verification at load time. Two layers:

    1. Every float leaf must be fully finite — a bit-flipped or truncated
       checkpoint shows up as NaN/Inf and would otherwise poison every decode.
    2. When a save-time manifest exists, the recomputed summary's checksum
       must match the recorded one (bytes-exact identity).

    Raises the typed :class:`CheckpointCorruptError` (HTTP 500, code
    ``checkpoint_corrupt``) on either failure; serving garbage weights is
    strictly worse than refusing to start. Returns the computed summary so
    callers don't pay a second full pass."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        arr = np.asarray(leaf)
        if arr.dtype.kind != "f" or arr.size == 0:
            continue
        try:
            finite = bool(np.isfinite(arr).all())
        except TypeError:  # numpy without direct ufunc support for the dtype
            finite = bool(np.isfinite(arr.astype(np.float32)).all())
        if not finite:
            QUARANTINE_EVENTS.record("quarantine.checksum_failures")
            raise CheckpointCorruptError(
                f"checkpoint leaf {jax.tree_util.keystr(path)} contains "
                "non-finite values; refusing to serve corrupted weights"
            )
    summary = param_summary(params)
    if manifest is not None and manifest.get("checksum") not in (
        None,
        summary["checksum"],
    ):
        QUARANTINE_EVENTS.record("quarantine.checksum_failures")
        raise CheckpointCorruptError(
            f"checkpoint checksum mismatch: loaded {summary['checksum']}, "
            f"manifest records {manifest['checksum']}"
        )
    return summary


def _corrupt_params(params: Any) -> Any:
    """``loader.params=corrupt`` failpoint: overwrite the leading values of
    the first float leaf with NaN, simulating the bit-rot a real corrupted
    checkpoint exhibits, so ``verify_param_integrity`` must trip."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    for i, leaf in enumerate(leaves):
        arr = np.asarray(leaf)
        if arr.dtype.kind == "f" and arr.size:
            bad = np.array(arr)
            bad.reshape(-1)[: min(16, bad.size)] = np.nan
            leaves[i] = bad
            break
    return jax.tree_util.tree_unflatten(treedef, leaves)


def save_checkpoint(path: str, params: Dict[str, Any]) -> None:
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    checkpointer = ocp.StandardCheckpointer()
    checkpointer.save(path, _to_checkpoint_tree(params))
    checkpointer.wait_until_finished()
    # Integrity manifest (best-effort: a read-only destination must not fail
    # the save): load_checkpoint verifies its checksum on restore.
    try:
        with open(_manifest_path(path), "w") as f:
            json.dump(param_summary(params), f)
    except OSError:
        logger.warning("could not write param manifest next to %s", path, exc_info=True)


def load_orbax(path: str) -> Dict[str, Any]:
    import orbax.checkpoint as ocp

    checkpointer = ocp.StandardCheckpointer()
    restored = checkpointer.restore(os.path.abspath(path))
    return _rebuild_qtensors(restored)


def _rebuild_qtensors(tree: Any) -> Any:
    """Rebuild QTensor/Q4Tensor nodes from restored dicts.

    Checkpoints written by this version carry an explicit "fmt" leaf
    (4 = group-wise int4, 8 = per-channel int8) and dispatch on it. Legacy
    checkpoints (pre-fmt NamedTuple saves, restored by orbax as bare
    {"q", "scale"} dicts) fall back to the scale-shape heuristic: int8 keeps a
    keepdims per-channel scale ([..., 1, N]); int4 carries one scale per
    128-row group ([..., K/128, N], K >= 256 so never 1)."""
    from .quant import Q4Tensor, QTensor

    if isinstance(tree, dict):
        keys = set(tree.keys())
        if keys == {"q", "scale", "fmt"}:
            fmt = int(np.asarray(tree["fmt"]))
            if fmt == 4:
                return Q4Tensor(q=tree["q"], scale=tree["scale"])
            if fmt == 8:
                return QTensor(q=tree["q"], scale=tree["scale"])
            raise ValueError(f"unknown quantized-weight fmt {fmt} in checkpoint")
        if keys == {"q", "scale"} and getattr(tree["q"], "dtype", None) == jnp.int8:
            if tree["scale"].shape[-2] > 1:
                return Q4Tensor(q=tree["q"], scale=tree["scale"])
            return QTensor(q=tree["q"], scale=tree["scale"])
        return {k: _rebuild_qtensors(v) for k, v in tree.items()}
    return tree


def _hf_key(layer: int, name: str) -> str:
    return f"model.layers.{layer}.{name}.weight"


def load_safetensors(path: str, config: ModelConfig, dtype=None) -> Dict[str, Any]:
    """Import an HF Llama checkpoint directory into the stacked-params layout.

    HF stores per-layer [out, in] matrices; our layout is [in, out] stacked on a
    leading layer axis. HF's q/k weights are in interleaved-rotary order which
    matches the half-split RoPE used here after the standard permutation.
    """
    from safetensors import safe_open

    dtype = dtype or config.jax_dtype
    files = sorted(
        os.path.join(path, f) for f in os.listdir(path) if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {path!r}")

    tensors: Dict[str, np.ndarray] = {}
    for file in files:
        with safe_open(file, framework="numpy") as f:
            for key in f.keys():
                tensors[key] = f.get_tensor(key)

    def t(key: str) -> np.ndarray:  # HF [out, in] -> ours [in, out]
        return np.asarray(tensors[key]).T

    # NB on RoPE layout: HF Llama applies rotary with the same split-half
    # (rotate_half) convention our rope_embed uses, so q/k weights import
    # without re-permutation.
    L = config.num_layers
    # Gemma-2 checkpoints name the PRE-MLP norm "pre_feedforward_layernorm" and
    # reuse "post_attention_layernorm" for the post-norm on attention output;
    # Llama-family checkpoints use "post_attention_layernorm" as the pre-MLP norm.
    mlp_norm_key = (
        "pre_feedforward_layernorm" if config.post_block_norms else "post_attention_layernorm"
    )
    layers = {
        "attn_norm": np.stack([np.asarray(tensors[_hf_key(i, "input_layernorm")]) for i in range(L)]),
        "wq": np.stack([t(_hf_key(i, "self_attn.q_proj")) for i in range(L)]),
        "wk": np.stack([t(_hf_key(i, "self_attn.k_proj")) for i in range(L)]),
        "wv": np.stack([t(_hf_key(i, "self_attn.v_proj")) for i in range(L)]),
        "wo": np.stack([t(_hf_key(i, "self_attn.o_proj")) for i in range(L)]),
        "mlp_norm": np.stack([np.asarray(tensors[_hf_key(i, mlp_norm_key)]) for i in range(L)]),
    }
    if config.num_experts > 0:
        # Mixtral: block_sparse_moe.gate = router [E, H]; experts.{e}.w1/w3/w2
        # are gate/up/down. Stack experts then layers: [L, E, in, out].
        E = config.num_experts
        layers["w_router"] = np.stack(
            [t(f"model.layers.{i}.block_sparse_moe.gate.weight") for i in range(L)]
        )
        for ours, hf in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
            layers[ours] = np.stack(
                [
                    np.stack(
                        [
                            t(f"model.layers.{i}.block_sparse_moe.experts.{e}.{hf}.weight")
                            for e in range(E)
                        ]
                    )
                    for i in range(L)
                ]
            )
    else:
        layers["w_gate"] = np.stack([t(_hf_key(i, "mlp.gate_proj")) for i in range(L)])
        layers["w_up"] = np.stack([t(_hf_key(i, "mlp.up_proj")) for i in range(L)])
        layers["w_down"] = np.stack([t(_hf_key(i, "mlp.down_proj")) for i in range(L)])
    if config.post_block_norms:  # Gemma-2
        layers["post_attn_norm"] = np.stack(
            [np.asarray(tensors[_hf_key(i, "post_attention_layernorm")]) for i in range(L)]
        )
        layers["post_mlp_norm"] = np.stack(
            [np.asarray(tensors[_hf_key(i, "post_feedforward_layernorm")]) for i in range(L)]
        )

    if config.qkv_bias:  # Qwen2 family
        for ours, hf_name in (("bq", "q_proj"), ("bk", "k_proj"), ("bv", "v_proj")):
            layers[ours] = np.stack(
                [
                    np.asarray(tensors[f"model.layers.{i}.self_attn.{hf_name}.bias"])
                    for i in range(L)
                ]
            )

    embed = np.asarray(tensors["model.embed_tokens.weight"])
    if "lm_head.weight" in tensors:
        lm_head = np.asarray(tensors["lm_head.weight"]).T
    else:  # tied embeddings (llama-3.2-1b)
        lm_head = embed.T

    params = {
        "embed": jnp.asarray(embed, dtype),
        "layers": {k: jnp.asarray(v, dtype) for k, v in layers.items()},
        "final_norm": jnp.asarray(np.asarray(tensors["model.norm.weight"]), dtype),
        "lm_head": jnp.asarray(lm_head, dtype),
    }
    return params


def load_checkpoint(path: str, config: ModelConfig, dtype=None) -> Dict[str, Any]:
    """Dispatch on content: safetensors dir vs orbax dir. Every load runs
    integrity verification (finite floats + manifest checksum when one was
    written at save time) and fails fast with a typed
    :class:`CheckpointCorruptError` rather than serving garbage weights."""
    if config.is_latent:
        raise NotImplementedError(
            f"{config.name}: no checkpoint mapping for the latent block (MLA projections, "
            "expert stacks, hyper-connection mixers); it runs on seeded weights"
        )
    if config.is_hybrid:
        raise NotImplementedError(
            f"{config.name}: no checkpoint mapping for the hybrid stack (Mamba-2 mixers, "
            "expert stacks, parallel blocks, a per-layer pattern); it runs on seeded weights"
        )
    if os.path.isdir(path) and any(f.endswith(".safetensors") for f in os.listdir(path)):
        params = load_safetensors(path, config, dtype)
    else:
        params = load_orbax(path)
    fp = _failpoints.fire("loader.params")
    if fp is not None and fp.action == "corrupt":
        params = _corrupt_params(params)
    manifest = None
    if os.path.exists(_manifest_path(path)):
        with open(_manifest_path(path)) as f:
            manifest = json.load(f)
    global last_load_summary
    last_load_summary = verify_param_integrity(params, manifest)
    return params


#: Summary of the most recent successful load_checkpoint, for backends to
#: surface through ``health()`` without re-hashing the whole tree.
last_load_summary: Optional[Dict[str, Any]] = None


def _rope_scaling_from_hf(rs: Optional[dict]):
    """HF rope_scaling dict -> our (factor, low, high, original_ctx) tuple.
    Only rope_type="llama3" (Llama-3.1/3.2) is modeled; other types raise so a
    checkpoint never silently runs with wrong frequencies."""
    if not rs:
        return None
    kind = rs.get("rope_type") or rs.get("type")
    if kind == "llama3":
        return (
            float(rs["factor"]),
            float(rs.get("low_freq_factor", 1.0)),
            float(rs.get("high_freq_factor", 4.0)),
            int(rs.get("original_max_position_embeddings", 8192)),
        )
    if kind in ("default", None):
        return None
    raise ValueError(f"unsupported rope_scaling type {kind!r}")


def config_from_hf(path: str) -> Optional[ModelConfig]:
    """Build a ModelConfig from an HF config.json, if present."""
    cfg_path = os.path.join(path, "config.json")
    if not os.path.exists(cfg_path):
        return None
    with open(cfg_path) as f:
        hf = json.load(f)
    if "kv_lora_rank" in hf:
        raise NotImplementedError(
            f"config.json of model_type {hf.get('model_type')!r} describes latent attention; "
            "use the registered preset (models/config.py), no checkpoint mapping exists"
        )
    if "hybrid_override_pattern" in hf:
        raise NotImplementedError(
            f"config.json of model_type {hf.get('model_type')!r} describes a hybrid stack "
            "(a per-layer pattern of mixers); use the registered preset (models/config.py), "
            "no checkpoint mapping exists"
        )
    hidden = hf["hidden_size"]
    heads = hf["num_attention_heads"]
    model_type = hf.get("model_type", "llama")
    # Qwen2 ships a huge nominal sliding_window with use_sliding_window=false;
    # Mistral configs carry the real window (or null for v0.3+).
    sliding_window = hf.get("sliding_window")
    if model_type == "qwen2" and not hf.get("use_sliding_window", False):
        sliding_window = None
    gemma2 = model_type == "gemma2"
    query_scale = None
    if hf.get("query_pre_attn_scalar"):
        query_scale = float(hf["query_pre_attn_scalar"]) ** -0.5
    return ModelConfig(
        qkv_bias=model_type == "qwen2" or hf.get("attention_bias", False),
        sliding_window=sliding_window,
        num_experts=hf.get("num_local_experts", 0),
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        sliding_window_layers="alternating" if gemma2 else "all",
        act="gelu" if gemma2 else "silu",
        norm_offset=gemma2,
        embed_scale=gemma2,
        post_block_norms=gemma2,
        attn_softcap=hf.get("attn_logit_softcapping"),
        logit_softcap=hf.get("final_logit_softcapping"),
        query_scale=query_scale,
        name=os.path.basename(os.path.normpath(path)),
        vocab_size=hf["vocab_size"],
        hidden_size=hidden,
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim", hidden // heads),
        rope_theta=hf.get("rope_theta", 500000.0),
        rope_scaling=_rope_scaling_from_hf(hf.get("rope_scaling")),
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        max_seq_len=min(hf.get("max_position_embeddings", 8192), 8192),
        bos_token_id=hf.get("bos_token_id", 128000),
        eos_token_id=hf.get("eos_token_id", 128001),
        pad_token_id=hf.get("pad_token_id") or hf.get("eos_token_id", 128001),
    )
