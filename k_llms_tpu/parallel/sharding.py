"""Partition specs for the Llama parameter/cache pytrees.

Megatron-style tensor parallelism laid out so every collective rides ICI:
column-parallel in-projections (wq/wk/wv/w_gate/w_up sharded on the output
feature axis), row-parallel out-projections (wo/w_down sharded on the input
feature axis) — GSPMD then inserts exactly one reduce per block. Embedding and
lm_head shard the vocab axis. Norms replicate. KV caches shard batch over
``data`` and kv-heads over ``model``.
"""

from __future__ import annotations

from typing import Any, Dict

from jax.sharding import PartitionSpec as P

from ..models.config import ModelConfig
from .mesh import DATA_AXIS, MODEL_AXIS


def param_specs(config: ModelConfig) -> Dict[str, Any]:
    """Pytree of PartitionSpec matching models.llama.init_params."""
    if config.is_latent:
        raise NotImplementedError(
            f"{config.name}: no partition specs for the latent block's tree yet "
            "(dense_layers/layers groups, latent projections, expert stacks, mixers): "
            "it runs on one device"
        )
    if config.is_hybrid:
        raise NotImplementedError(
            f"{config.name}: no partition specs for the hybrid stack's tree yet (per-layer "
            "Mamba-2, expert and attention dicts) nor for its recurrent state: it runs on "
            "one device"
        )
    layers = {
        "attn_norm": P(None, None),
        "wq": P(None, None, MODEL_AXIS),
        "wk": P(None, None, MODEL_AXIS),
        "wv": P(None, None, MODEL_AXIS),
        "wo": P(None, MODEL_AXIS, None),
        "mlp_norm": P(None, None),
        "w_gate": P(None, None, MODEL_AXIS),
        "w_up": P(None, None, MODEL_AXIS),
        "w_down": P(None, MODEL_AXIS, None),
    }
    if config.num_experts > 0:
        # Expert parallelism: the expert axis of [L, E, H, I] weights shards
        # over "model"; each device computes its experts, GSPMD reduces the
        # combine. The router replicates.
        layers["w_router"] = P(None, None, None)
        layers["w_gate"] = P(None, MODEL_AXIS, None, None)
        layers["w_up"] = P(None, MODEL_AXIS, None, None)
        layers["w_down"] = P(None, MODEL_AXIS, None, None)
    if config.qkv_bias:
        # Biases follow their projection's output-feature sharding.
        layers["bq"] = P(None, MODEL_AXIS)
        layers["bk"] = P(None, MODEL_AXIS)
        layers["bv"] = P(None, MODEL_AXIS)
    if config.post_block_norms:
        layers["post_attn_norm"] = P(None, None)
        layers["post_mlp_norm"] = P(None, None)
    return {
        "embed": P(MODEL_AXIS, None),  # vocab-sharded
        "layers": layers,
        "final_norm": P(None),
        "lm_head": P(None, MODEL_AXIS),
    }


def cache_specs(shared_prefix: bool = False):
    """KV cache [L, B, S, KVH, D]: samples over data, kv heads over model.
    The shared prefix has batch 1, so only heads shard."""
    if shared_prefix:
        return P(None, None, None, MODEL_AXIS, None)
    return P(None, DATA_AXIS, None, MODEL_AXIS, None)


def batch_spec():
    """Per-sample vectors (tokens, logprobs, done flags): sharded over data."""
    return P(DATA_AXIS)
