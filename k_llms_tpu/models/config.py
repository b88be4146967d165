"""Model architecture configs.

Flagship target is Llama-3-8B (BASELINE.md north star); the 1B config is the
single-v5e-chip bench model (8B bf16 weights alone exceed one chip's 16 GB HBM —
8B runs tensor-parallel over the mesh), and ``tiny`` keeps CI compiles fast.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Dict

import jax.numpy as jnp


#: The characters of ``layer_pattern`` whose layer holds keys and values.
PAGING_KINDS = "*LG"


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 500000.0
    # Llama-3.1/3.2-style frequency-dependent RoPE scaling:
    # (factor, low_freq_factor, high_freq_factor, original_max_position).
    # None = vanilla RoPE. Long wavelengths (past original_max/low_freq)
    # divide by factor, short ones keep, the band between interpolates —
    # matching HF's rope_type="llama3".
    # A tuple that starts with "yarn" is YaRN instead (DeepSeek-V3 form):
    # ("yarn", factor, original_max_position, beta_fast, beta_slow,
    # mscale_all_dim) — per-pair frequencies blend plain and factor-divided
    # ones over the betas' correction range; the attention scale gains
    # mscale(factor, mscale_all_dim)^2 (see :attr:`attn_scale`).
    rope_scaling: "tuple | None" = None
    rms_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: str = "bfloat16"
    # Prefill attention implementation: "xla" (einsum, runs anywhere) or
    # "flash" (Pallas TPU kernel, ops/attention.py). "flash" means the
    # Mosaic-compiled kernel: off-TPU it resolves to "xla"
    # (ops/attention.py::resolve_attention_impl). "flash_interpret" runs the
    # kernel in the Pallas interpreter and is for tests only. Speed against
    # XLA on the chip: not measured in this round.
    attention_impl: str = "xla"
    # Decode-step attention: "xla" (default) or "flash" (Pallas shared-prefix
    # kernel, ops/attention.py::decode_prefix_attention — streams each prefix
    # KV block once per request with the whole query tile on the MXU); same
    # resolution rule and tests-only "flash_interpret" as above. An earlier
    # builder's note put the kernel at 0.94x of XLA at the 8B/int8/n=32/
    # 256-token-prefix shape (decode there is weight-streaming-bound); no
    # driver record holds that figure, so treat it as not measured.
    decode_attention_impl: str = "xla"
    # Architecture variants beyond Llama:
    # - qkv_bias: additive bias on q/k/v projections (Qwen2 family).
    # - sliding_window: each query attends only to the last W keys
    #   (Mistral family); None = full causal. Forces the XLA path of the
    #   dense flash kernels; the paged decode kernel takes each layer's own
    #   window, static in its call (ops/paged_attention.py).
    # - sliding_window_layers: which layers the window binds: "all" (every
    #   layer — Mistral) or "alternating" (even layers windowed, odd layers
    #   global — Gemma-2). A hybrid stack's pattern says it layer by layer
    #   ("L" windowed, "G" global). Either way the per-layer list every reader
    #   takes is :attr:`layer_windows`, the window of each paging layer.
    qkv_bias: bool = False
    sliding_window: "int | None" = None
    sliding_window_layers: str = "all"
    # Gemma-family variants:
    # - act: MLP gate activation, "silu" (Llama) or "gelu" (GeGLU); "relu2"
    #   (non-gated relu(x)^2 experts: hybrid stacks only).
    # - norm_offset: RMSNorm scales by (1 + w) instead of w.
    # - embed_scale: multiply token embeddings by sqrt(hidden_size).
    # - post_block_norms: Gemma-2 extra norms on the attention and MLP outputs
    #   (before each residual add).
    # - attn_softcap / logit_softcap: cap*tanh(x/cap) on attention scores /
    #   final logits. Softcaps force the XLA attention path.
    # - query_scale: attention score scale; None = 1/sqrt(head_dim).
    act: str = "silu"
    norm_offset: bool = False
    embed_scale: bool = False
    post_block_norms: bool = False
    attn_softcap: "float | None" = None
    logit_softcap: "float | None" = None
    query_scale: "float | None" = None
    # Mixture-of-experts (Mixtral family): every MLP becomes num_experts
    # experts with top-k token-choice routing. 0 = dense MLP.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Latent attention (MLA; models/latent.py): kv_lora_rank > 0 switches the
    # whole block. Queries go through a q_lora_rank bottleneck to num_heads
    # heads of [qk_nope_head_dim | qk_rope_head_dim]; keys and values are one
    # kv_lora_rank latent plus one shared rope key a token — the only thing
    # the cache holds (num_kv_heads and head_dim are unused). The same block
    # carries the routed-expert layer (sigmoid "noaux_tc" router: top-k of
    # score + bias, weights from the scores, times routed_scaling_factor; the
    # first first_k_dense layers keep a dense MLP of intermediate_size, the
    # rest run num_experts experts of moe_intermediate_size plus
    # n_shared_experts always-on ones) and hyper-connections (hc_mult residual
    # streams mixed per sublayer by a Sinkhorn-normalised matrix).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    first_k_dense: int = 0
    routed_scaling_factor: float = 1.0
    hc_mult: int = 1  # 1: no streams, no mixer parameters; X <- X + f(RMSNorm(X))
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # The expert layer's share of a layer's experts (latent block): the router
    # is ``num_experts`` wide and chooses among all of them; this chip holds
    # the stacks of experts [expert_offset, expert_offset + experts_held) and
    # computes those pairs alone (another chip holds the rest; its sum is
    # added elsewhere). 0 held: all of them.
    experts_held: int = 0
    expert_offset: int = 0
    # Next-token modules (DeepSeek-V3 section 2.2, depth 1; models/latent.py):
    # one more expert layer with its own cache layer that reads the main
    # stack's output at position i beside the embedding of token i+1 and
    # predicts token i+2 through the shared head. The paged continuous loop
    # drafts with it (engine/continuous.py); nothing else runs it.
    num_nextn_predict_layers: int = 0
    # Hybrid stacks (Nemotron-H; models/hybrid.py): a non-empty layer_pattern
    # switches the whole stack. One character a layer, each layer ONE pre-norm
    # mixer and no MLP of its own: "M" a Mamba-2 mixer (mamba_num_heads heads
    # of mamba_head_dim, mamba_n_groups B/C groups of ssm_state_size, a causal
    # depthwise conv of mamba_conv_kernel taps over x|B|C), "E" routed experts
    # (the latent block's router; non-gated relu^2 experts of
    # moe_intermediate_size plus one shared expert of
    # moe_shared_intermediate_size), "*" GQA attention (use_rope False: no
    # rotary embedding). Only "*" layers page; an "M" layer keeps a
    # fixed-size recurrent state a row (:attr:`state_shapes`).
    # "L" and "G" are the parallel block (Cohere's ``use_parallel_block``): one
    # mean-centred LayerNorm, then GQA attention and the expert layer (the
    # latent block's router over gated experts of moe_intermediate_size, plus
    # n_shared_experts always-on ones fused into one of
    # moe_shared_intermediate_size, averaged when shared_experts_averaged) side
    # by side on the normed input, one residual add. "L" attends inside
    # sliding_window under RoPE, "G" over everything with no positional
    # embedding; both page. tie_embeddings: the head is the embedding table.
    layer_pattern: str = ""
    use_rope: bool = True
    shared_experts_averaged: bool = False
    tie_embeddings: bool = False
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 1
    ssm_state_size: int = 0
    mamba_conv_kernel: int = 4
    mamba_chunk: int = 128  # block length of the chunked (SSD) scan
    # Seeded initialisation of the step dt (log-uniform, as published).
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    moe_shared_intermediate_size: int = 0  # 0: moe_intermediate_size * n_shared_experts
    # byte tokenizer vocab fits any vocab_size >= 260; HF tokenizers use the full space
    bos_token_id: int = 256
    eos_token_id: int = 257
    pad_token_id: int = 258

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def is_hybrid(self) -> bool:
        return bool(self.layer_pattern)

    @property
    def layer_windows(self) -> "tuple":
        """The sliding window of each paging layer, in cache-layer order: W
        where the layer attends inside ``sliding_window``, None where it
        attends over everything."""
        W = self.sliding_window
        if self.is_hybrid:
            return tuple(None if kind == "G" else W
                         for kind in self.layer_pattern if kind in PAGING_KINDS)
        if self.sliding_window_layers == "alternating":
            return tuple(W if i % 2 == 0 else None for i in range(self.paging_layers))
        return (W,) * self.paging_layers

    @property
    def mixes_windowed_layers(self) -> bool:
        """Windowed and global layers in one stack (Gemma-2's "alternating",
        a "LLLG" pattern), against no window or one that every layer has."""
        return len(set(self.layer_windows)) > 1

    @property
    def paging_layers(self) -> int:
        """Layers that hold keys and values, so the leading axis of every
        cache and of the page pool: all of them (a next-token module's layer
        behind the stack's), or a hybrid stack's attention layers."""
        if self.is_hybrid:
            return sum(kind in PAGING_KINDS for kind in self.layer_pattern)
        return self.num_layers + self.num_nextn_predict_layers

    @property
    def held_experts(self) -> int:
        """Experts whose stacks this chip holds (see ``experts_held``)."""
        return self.experts_held or self.num_experts

    def state_shapes(self, rows: int) -> "Dict[str, tuple]":
        """The recurrent state ``rows`` rows hold beside their pages, as
        ``{name: (state layers, shape, dtype)}``: the float32 SSM state
        ``[rows, heads, head_dim, N]`` and the conv's last inputs ``[rows,
        taps - 1, channels]`` of each "M" layer (one array a layer: a layer's
        update then happens in place); for a model with a next-token module,
        ``mtp_h`` ``[rows, hidden]``: the main stack's output at a prompt's
        last position, which the module pairs with the first sampled token.
        Empty for a model with neither, so a pytree built from it adds no
        operand to any program."""
        if self.num_nextn_predict_layers:
            return {"mtp_h": (1, (rows, self.hidden_size), self.jax_dtype)}
        m = self.layer_pattern.count("M")
        if not m:
            return {}
        conv_dim = self.mamba_num_heads * self.mamba_head_dim + 2 * self.mamba_n_groups * self.ssm_state_size
        return {
            "ssm": (m, (rows, self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size),
                    jnp.dtype("float32")),
            "conv": (m, (rows, self.mamba_conv_kernel - 1, conv_dim), self.jax_dtype),
        }

    @property
    def state_bytes_per_row(self) -> int:
        return sum(m * math.prod(shape) * dtype.itemsize
                   for m, shape, dtype in self.state_shapes(1).values())

    @property
    def cache_widths(self) -> "tuple[int, int, int]":
        """(heads, k width, v width) of one token's cache row in one layer.
        A latent model caches one [c_kv | k_rope] row and no V: its ``v``
        arrays exist with width 0, so every mover of a (k, v) pair still
        works and moves no bytes for it."""
        if self.is_latent:
            return 1, self.kv_lora_rank + self.qk_rope_head_dim, 0
        return self.num_kv_heads, self.head_dim, self.head_dim

    @property
    def pool_row_width(self) -> int:
        """Lanes the page pool stores for ``cache_widths``' k width. A latent
        row (one head, no V) is rounded up to whole tiles of 128
        lanes (576 -> 640: ``[c_kv | k_rope | zeros]``): the chip then keeps a
        token's row on the pool's minor axis and a mover addresses it in
        place, where a row of 4.5 tiles lies strided and every program that
        touches one lays the whole pool out again (engine/paging.py). The pad
        lanes are written as zeros and never read. Rows of several heads are
        whole tiles already and stored as they are."""
        _, k_width, _ = self.cache_widths
        return -(-k_width // 128) * 128 if self.is_latent else k_width

    @property
    def kv_bytes_per_token(self) -> int:
        """Bytes one token holds in the page pool over the paging layers, in
        the model dtype (a latent row counts its pad lanes: they are stored)."""
        heads, _, v_width = self.cache_widths
        return self.paging_layers * heads * (self.pool_row_width + v_width) * self.jax_dtype.itemsize

    @property
    def dense_kv_bytes_per_token(self) -> int:
        """Bytes one token holds in a dense cache (``init_cache``), whose rows
        are the cache's own width: what :attr:`kv_bytes_per_token` is for every
        model whose pool rows are not padded."""
        heads, k_width, v_width = self.cache_widths
        return self.paging_layers * heads * (k_width + v_width) * self.jax_dtype.itemsize

    @property
    def attn_scale(self) -> float:
        """Attention score scale: ``query_scale`` if set, else 1/sqrt(qk
        width), times YaRN's mscale(factor, mscale_all_dim)^2."""
        if self.query_scale is not None:
            return self.query_scale
        if not self.is_latent:
            return 1.0 / math.sqrt(self.head_dim)
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_scaling is not None and self.rope_scaling[0] == "yarn":
            _, factor, _, _, _, all_dim = self.rope_scaling
            if factor > 1 and all_dim:
                scale *= (0.1 * all_dim * math.log(factor) + 1.0) ** 2
        return scale

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)


_REGISTRY: Dict[str, ModelConfig] = {}


def register_config(config: ModelConfig) -> ModelConfig:
    _REGISTRY[config.name] = config
    return config


def get_config(name: str) -> ModelConfig:
    key = name.lower()
    if key not in _REGISTRY:
        raise KeyError(f"Unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[key]


register_config(
    ModelConfig(
        name="llama-3-8b",
        attention_impl="flash",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        max_seq_len=8192,
    )
)

register_config(
    ModelConfig(
        name="llama-3.2-1b",
        attention_impl="flash",
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        rope_theta=500000.0,
        # Llama-3.2 checkpoints ship rope_type="llama3" with factor 32.
        rope_scaling=(32.0, 1.0, 4.0, 8192),
        max_seq_len=8192,
    )
)

# Bench-scale model with a byte-level vocab: all FLOPs in the transformer stack,
# negligible embedding table, fits one v5e chip with room for n=32 KV caches.
register_config(
    ModelConfig(
        name="llama-1b-byte",
        attention_impl="flash",
        vocab_size=512,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=16,
        num_kv_heads=8,
        head_dim=128,
        max_seq_len=4096,
    )
)

# Gemma-2 family: GeGLU, (1+w) RMSNorm, post-block norms, sqrt(H) embedding
# scale, attention + final-logit softcaps, alternating local/global attention,
# tied embeddings, big head_dim with a fixed query scale.
register_config(
    ModelConfig(
        name="gemma-2-2b",
        vocab_size=256000,  # HF gemma-2 safetensors layout (not the 256128 padded Flax release)
        hidden_size=2304,
        intermediate_size=9216,
        num_layers=26,
        num_heads=8,
        num_kv_heads=4,
        head_dim=256,
        rope_theta=10000.0,
        rms_eps=1e-6,
        max_seq_len=8192,
        sliding_window=4096,
        sliding_window_layers="alternating",
        act="gelu",
        norm_offset=True,
        embed_scale=True,
        post_block_norms=True,
        attn_softcap=50.0,
        logit_softcap=30.0,
        query_scale=256.0**-0.5,  # query_pre_attn_scalar=256
        bos_token_id=2,
        eos_token_id=1,
        pad_token_id=0,
    )
)

register_config(
    ModelConfig(
        name="gemma-2-9b",
        vocab_size=256000,  # HF gemma-2 safetensors layout (not the 256128 padded Flax release)
        hidden_size=3584,
        intermediate_size=14336,
        num_layers=42,
        num_heads=16,
        num_kv_heads=8,
        head_dim=256,
        rope_theta=10000.0,
        rms_eps=1e-6,
        max_seq_len=8192,
        sliding_window=4096,
        sliding_window_layers="alternating",
        act="gelu",
        norm_offset=True,
        embed_scale=True,
        post_block_norms=True,
        attn_softcap=50.0,
        logit_softcap=30.0,
        query_scale=256.0**-0.5,
        bos_token_id=2,
        eos_token_id=1,
        pad_token_id=0,
    )
)

# Qwen2 family: Llama architecture + QKV biases, 1e6 rope theta.
register_config(
    ModelConfig(
        name="qwen2-7b",
        attention_impl="flash",
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=1000000.0,
        rms_eps=1e-6,
        max_seq_len=8192,
        qkv_bias=True,
        bos_token_id=151643,
        eos_token_id=151645,
        pad_token_id=151643,
    )
)

register_config(
    ModelConfig(
        name="qwen2.5-0.5b",
        attention_impl="flash",
        vocab_size=151936,
        hidden_size=896,
        intermediate_size=4864,
        num_layers=24,
        num_heads=14,
        num_kv_heads=2,
        head_dim=64,
        rope_theta=1000000.0,
        rms_eps=1e-6,
        max_seq_len=8192,
        qkv_bias=True,
        bos_token_id=151643,
        eos_token_id=151645,
        pad_token_id=151643,
    )
)

# Mixtral family: Mistral attention + 8-expert top-2 MoE MLPs. Experts shard
# over the "model" mesh axis (expert parallelism).
register_config(
    ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        rms_eps=1e-5,
        max_seq_len=8192,
        num_experts=8,
        num_experts_per_tok=2,
        bos_token_id=1,
        eos_token_id=2,
        pad_token_id=2,
    )
)

# Mistral family: Llama architecture + sliding-window attention.
register_config(
    ModelConfig(
        name="mistral-7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=10000.0,
        rms_eps=1e-5,
        max_seq_len=8192,
        sliding_window=4096,
        bos_token_id=1,
        eos_token_id=2,
        pad_token_id=2,
    )
)

# Xing4.0-29B-A4B (https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B):
# MLA + 64 routed experts (top-4, sigmoid noaux_tc) with one shared expert +
# 4 hyper-connected residual streams + YaRN. Published in bfloat16; served so
# (int8/int4, a mesh, sequence-parallel prefill and speculation are refused at
# build time by name). The published preset is for shape arithmetic and the
# loader's refusal: 29.5 B parameters do not fit a chip. ``-cut7`` is the
# one-chip cut the benchmark serves: depth only — 1 leading dense layer and 6
# of the 38 expert layers, every width, all 64 experts and the whole
# vocabulary as published (benchmark/configs/xing4-29b-a4b.json has the
# arithmetic and what is assumed). The next-token module is not built.
_XING4 = ModelConfig(
    name="xing4-29b-a4b",
    vocab_size=131072,
    hidden_size=3584,
    intermediate_size=9216,
    num_layers=40,
    num_heads=32,
    num_kv_heads=32,
    head_dim=192,  # unused by the latent block; the qk width, for readers
    rope_theta=10000.0,
    rope_scaling=("yarn", 64.0, 4096, 32.0, 1.0, 1.0),
    rms_eps=1e-6,
    max_seq_len=8192,  # served context; the config declares 262,144
    num_experts=64,
    num_experts_per_tok=4,
    q_lora_rank=768,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    moe_intermediate_size=1024,
    n_shared_experts=1,
    first_k_dense=2,
    routed_scaling_factor=2.0,
    hc_mult=4,
    hc_sinkhorn_iters=20,
    hc_eps=1e-6,
    hc_res_clamp=30.0,
)
register_config(_XING4)
register_config(_XING4.with_(name="xing4-29b-a4b-cut7", num_layers=7, first_k_dense=1))
# CPU test size of the same block: 1 dense + 2 expert layers, 8 experts top-2,
# the published ratios of the head widths (nope : rope : v = 2 : 1 : 2).
register_config(
    _XING4.with_(
        name="xing4-tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=160,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=24,
        rope_scaling=("yarn", 64.0, 64, 32.0, 1.0, 1.0),
        max_seq_len=4096,
        dtype="float32",
        num_experts=8,
        num_experts_per_tok=2,
        q_lora_rank=24,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        moe_intermediate_size=32,
        first_k_dense=1,
    )
)

# JoyAI-LLM-Flash (https://huggingface.co/jdopensource/JoyAI-LLM-Flash, model_type
# joyai_llm_flash, "48B-A2.7B"): the latent block without streams (plain
# pre-norm residuals, plain RoPE at theta 3.2e7): MLA, 1 dense layer, then 256
# routed SwiGLU experts top-8 (sigmoid noaux_tc, scaling 2.5) + 1 shared, and
# one next-token module. The published preset is for shape arithmetic.
# ``-cut8`` is what the benchmark serves on one chip: 1 dense + 7 of the 39
# expert layers + the module, every width and the whole vocabulary as
# published, and of each layer's 256 experts the 128 that one of two chips
# holds (benchmark/configs/joyai-llm-flash.json has the arithmetic, the
# deployment and what is assumed). bfloat16, the paged continuous loop, which
# drafts with the module by the preset alone.
_JOYAI = ModelConfig(
    name="joyai-llm-flash",
    vocab_size=129280,
    hidden_size=2048,
    intermediate_size=7168,
    num_layers=40,
    num_heads=32,
    num_kv_heads=32,
    head_dim=192,  # unused by the latent block; the qk width, for readers
    rope_theta=32000000.0,
    rope_scaling=None,
    rms_eps=1e-6,
    max_seq_len=8192,  # served context; the config declares 131,072
    num_experts=256,
    num_experts_per_tok=8,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    moe_intermediate_size=768,
    n_shared_experts=1,
    first_k_dense=1,
    routed_scaling_factor=2.5,
    hc_mult=1,
    num_nextn_predict_layers=1,
)
register_config(_JOYAI)
register_config(_JOYAI.with_(name="joyai-llm-flash-cut8", num_layers=8, experts_held=128))
# CPU test size of the same block: 1 dense + 2 expert layers + the module, 8
# experts top-2 of which 4 are held, the published ratios of the head widths.
register_config(
    _JOYAI.with_(
        name="joyai-tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=160,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=24,
        max_seq_len=4096,
        dtype="float32",
        num_experts=8,
        num_experts_per_tok=2,
        experts_held=4,
        q_lora_rank=48,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        moe_intermediate_size=24,
    )
)

# NVIDIA-Nemotron-3-Nano-30B-A3B (model_type nemotron_h,
# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): 52
# single-mixer blocks, 23 Mamba-2 / 23 routed-expert (128 non-gated relu^2
# experts top-6 + one shared) / 6 GQA attention without rotary embedding.
# The published preset is for shape arithmetic (31.58 B parameters do not fit
# a chip). ``-cut9`` is the one-chip cut the benchmark serves: depth only, the
# first nine layers of the pattern (4 M : 4 E : 1 *), every width, all 128
# experts and the whole vocabulary (benchmark/configs/nemotron3-nano-30b-a3b.json
# has the arithmetic and what is assumed). Served in bfloat16 through the
# paged continuous loop only; what the recurrent state has no answer for is
# refused by name (engine/engine.py).
_NEMOTRON3 = ModelConfig(
    name="nemotron3-nano-30b-a3b",
    vocab_size=131072,
    hidden_size=2688,
    intermediate_size=1856,  # unused: the pattern has no dense-MLP layer
    num_layers=52,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    rope_theta=10000.0,  # unused: use_rope is False
    use_rope=False,
    rms_eps=1e-5,
    max_seq_len=8192,  # served context; the config declares 262,144
    act="relu2",
    num_experts=128,
    num_experts_per_tok=6,
    moe_intermediate_size=1856,
    moe_shared_intermediate_size=3712,
    n_shared_experts=1,
    routed_scaling_factor=2.5,
    layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    mamba_num_heads=64,
    mamba_head_dim=64,
    mamba_n_groups=8,
    ssm_state_size=128,
    mamba_conv_kernel=4,
    mamba_chunk=128,
)
register_config(_NEMOTRON3)
register_config(
    _NEMOTRON3.with_(name="nemotron3-nano-30b-a3b-cut9", num_layers=9, layer_pattern="MEMEM*EME")
)
# CPU test size of the same stack: the cut's pattern, 8 experts top-2, the
# published ratios (state 2 x head_dim, the shared expert twice an expert).
register_config(
    _NEMOTRON3.with_(
        name="nemotron3-tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=48,
        num_layers=9,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=4096,
        dtype="float32",
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=48,
        moe_shared_intermediate_size=96,
        layer_pattern="MEMEM*EME",
        mamba_num_heads=8,
        mamba_head_dim=16,
        mamba_n_groups=2,
        ssm_state_size=32,
        mamba_chunk=16,
    )
)

# command-a-plus-05-2026 (CohereLabs, model_type cohere2_moe, "Command A+
# 218B-A25B"; https://huggingface.co/CohereLabs/command-a-plus-05-2026): 32
# parallel blocks (one mean-centred LayerNorm, GQA attention 128q/8kv of 128
# and the expert layer side by side, one residual add), three windowed layers
# (window 4,096, RoPE at theta 5e4) to one global layer without a positional
# embedding; 128 SwiGLU experts of 4,096 top-8 by sigmoid scores, normalised,
# plus 4 shared experts averaged; tied embeddings. The published preset is for
# shape arithmetic (218 B parameters). ``-cut4`` is what the benchmark serves
# on one chip: one period of the pattern (4 of 32 layers), every width as
# published, and one chip's share of a layer that eight chips hold: 16 of the
# 128 experts and 32,768 of the 262,144 rows of the vocabulary
# (benchmark/configs/command-a-plus.json has the arithmetic, the deployment
# and what is assumed). bfloat16, the paged continuous loop only; the text
# model alone (the vision tower is not built).
_COMMAND_A_PLUS = ModelConfig(
    name="command-a-plus",
    vocab_size=262144,
    hidden_size=4096,
    intermediate_size=4096,  # read as one expert's width: moe_intermediate_size
    num_layers=32,
    num_heads=128,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=50000.0,
    rms_eps=1e-5,  # layer_norm_eps
    max_seq_len=8192,  # served context; the config declares 200,000
    sliding_window=4096,
    num_experts=128,
    num_experts_per_tok=8,
    moe_intermediate_size=4096,
    n_shared_experts=4,
    moe_shared_intermediate_size=16384,
    shared_experts_averaged=True,
    routed_scaling_factor=1.0,
    tie_embeddings=True,
    layer_pattern="LLLG" * 8,
)
register_config(_COMMAND_A_PLUS)
register_config(
    _COMMAND_A_PLUS.with_(name="command-a-plus-cut4", num_layers=4, layer_pattern="LLLG",
                          experts_held=16, vocab_size=32768)
)
# CPU test size of the same block: one period, a window of 12 (it binds within
# two pages of 8), 16 experts top-2 of which 2 are held, 2 shared.
register_config(
    _COMMAND_A_PLUS.with_(
        name="command-a-plus-tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=32,
        num_layers=4,
        num_heads=8,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=4096,
        dtype="float32",
        sliding_window=12,
        num_experts=16,
        num_experts_per_tok=2,
        experts_held=2,
        moe_intermediate_size=32,
        n_shared_experts=2,
        moe_shared_intermediate_size=64,
        layer_pattern="LLLG",
    )
)

register_config(
    ModelConfig(
        name="tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=160,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=4096,
        dtype="float32",
    )
)
