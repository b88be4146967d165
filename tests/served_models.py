"""The served model families as rows: each tiny preset, its plain reference in
``benchmark/`` and what the conformance tests (tests/test_served_models.py)
hold it to. A model's own file (tests/test_<family>.py) imports its row from
here for the checks no other model has.

The reference is the benchmark's own file (``benchmark/<name>_reference.py``,
the one its chip check runs), imported from there. :func:`reference` runs it
compiled: one program per (row, positions kept, sequence length), so eight
rows of one n-way case cost one compile.
"""

import functools
import json
import os
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, benchmark_module
from k_llms_tpu.models import get_config, hybrid, latent


# -- the published configuration files' key names -------------------------------------

def xing4_hf(c):
    _, factor, orig, beta_fast, beta_slow, all_dim = c.rope_scaling
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_heads,
        q_lora_rank=c.q_lora_rank, kv_lora_rank=c.kv_lora_rank,
        qk_nope_head_dim=c.qk_nope_head_dim, qk_rope_head_dim=c.qk_rope_head_dim,
        v_head_dim=c.v_head_dim, rms_norm_eps=c.rms_eps, rope_theta=c.rope_theta,
        rope_scaling=dict(type="yarn", factor=factor, original_max_position_embeddings=orig,
                          beta_fast=beta_fast, beta_slow=beta_slow, mscale=1,
                          mscale_all_dim=all_dim),
        n_routed_experts=c.num_experts, num_experts_per_tok=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor, norm_topk_prob=True,
        first_k_dense_replace=c.first_k_dense, num_hidden_layers=c.num_layers,
        hc_mult=c.hc_mult, hc_sinkhorn_iters=c.hc_sinkhorn_iters, hc_eps=c.hc_eps,
        mhc_h_res_clamp_min=-c.hc_res_clamp, mhc_h_res_clamp_max=c.hc_res_clamp,
    )


def nemotron3_hf(c):
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_heads,
        num_key_value_heads=c.num_kv_heads, head_dim=c.head_dim,
        mamba_num_heads=c.mamba_num_heads, mamba_head_dim=c.mamba_head_dim,
        n_groups=c.mamba_n_groups, ssm_state_size=c.ssm_state_size,
        conv_kernel=c.mamba_conv_kernel, chunk_size=c.mamba_chunk, layer_norm_epsilon=c.rms_eps,
        n_routed_experts=c.num_experts, num_experts_per_tok=c.num_experts_per_tok,
        moe_intermediate_size=c.moe_intermediate_size,
        moe_shared_expert_intermediate_size=c.moe_shared_intermediate_size,
        routed_scaling_factor=c.routed_scaling_factor, norm_topk_prob=True,
        hybrid_override_pattern=c.layer_pattern, num_hidden_layers=c.num_layers,
        vocab_size=c.vocab_size, time_step_min=c.time_step_min, time_step_max=c.time_step_max,
        time_step_floor=c.time_step_floor,
    )


def joyai_hf(c):
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_heads,
        q_lora_rank=c.q_lora_rank, kv_lora_rank=c.kv_lora_rank,
        qk_nope_head_dim=c.qk_nope_head_dim, qk_rope_head_dim=c.qk_rope_head_dim,
        v_head_dim=c.v_head_dim, rms_norm_eps=c.rms_eps, rope_theta=c.rope_theta,
        rope_scaling=None, n_routed_experts=c.num_experts,
        num_experts_per_tok=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor, norm_topk_prob=True,
        first_k_dense_replace=c.first_k_dense, num_hidden_layers=c.num_layers,
        num_nextn_predict_layers=c.num_nextn_predict_layers,
    )


COMMAND_KINDS = {"L": "sliding_attention", "G": "full_attention"}
OLMO_KINDS = {"D": "linear_attention", "F": "full_attention"}


def command_a_plus_hf(c):
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_heads,
        num_key_value_heads=c.num_kv_heads, head_dim=c.head_dim, layer_norm_eps=c.rms_eps,
        layer_types=[COMMAND_KINDS[k] for k in c.layer_pattern], sliding_window=c.sliding_window,
        rope_theta=c.rope_theta, num_experts_per_tok=c.num_experts_per_tok, norm_topk_prob=True,
        num_shared_experts=c.n_shared_experts, logit_scale=1, num_hidden_layers=c.num_layers,
    )


def olmo_hybrid_hf(c):
    """(``head_dim`` is the configuration file's assumption.)"""
    return dict(
        hidden_size=c.hidden_size, intermediate_size=c.intermediate_size,
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads, head_dim=c.head_dim,
        rms_norm_eps=c.rms_eps, num_hidden_layers=c.num_layers, vocab_size=c.vocab_size,
        layer_types=[OLMO_KINDS[k] for k in c.layer_pattern],
        linear_num_key_heads=c.linear_num_key_heads, linear_num_value_heads=c.linear_num_value_heads,
        linear_key_head_dim=c.linear_key_head_dim, linear_value_head_dim=c.linear_value_head_dim,
        linear_conv_kernel_dim=c.linear_conv_kernel, linear_allow_neg_eigval=c.linear_allow_neg_eigval,
    )


def interleaved(c, params):
    """command-a-plus's tree as its reference takes it: the windowed layers'
    ``W_q`` and ``W_k`` with each head's columns in interleaved rotary order
    (the program rotates half-split pairs)."""
    ref = benchmark_module("command_a_plus_reference")
    layers = [dict(layer, **{name: ref.interleaved_columns(layer[name], c.head_dim)
                             for name in (("wq", "wk") if kind == "L" else ())})
              for kind, layer in zip(c.layer_pattern, params["layers"])]
    return dict(params, layers=layers)


# The config.json each served model's source publishes, keyed by its URL (the
# configuration file's ``source``), copied as published.
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "published_configs.jsonl")) as f:
    PUBLISHED = {row["source_url"]: row["config"] for row in map(json.loads, f)}


# -- the rows ---------------------------------------------------------------------------

SMALL_SCHEMA = {"type": "object", "properties": {"a": {"type": "string", "enum": ["x", "y"]}},
                "required": ["a"], "additionalProperties": False}


def workload_schema(traffic):
    with open(os.path.join(ROOT, "benchmark", "workloads", f"{traffic}.json")) as f:
        return json.load(f)["response_format"]


@dataclass(frozen=True)
class Served:
    preset: str
    reference: str  # the module's name in benchmark/
    hf: Callable  # ModelConfig -> the published config.json's own key names
    tol: float = 1e-4  # on logits; each model file's docstring says why
    held: bool = False  # the reference takes ``experts_held=(offset, count)``
    ref_tree: Optional[Callable] = None  # (config, params) -> the tree the reference takes
    moe_layers: int = 0  # routed layers a token goes through in the stack
    state: tuple = ()  # a recurrent model's (counter prefix, pattern letter of its layers)
    page_size: int = 16  # the page size of the row's loops and refusals
    steps: str = "one"  # the chunked-prefill test's steps: "one", "drafted" or "windowed"
    chunk_cases: tuple = ((16, "xla"), (32, "xla"))
    forward_moves: tuple = ()  # (config changes, params changes): each leaves the reference
    forward_holds: tuple = ()  # ... and each keeps to it
    moved_by: float = 0.01
    n8: tuple = ()  # (prompt length, ladder, lane turns) of the n-way fork
    n8_whole: bool = False  # every row of the fork runs its 10 tokens (none samples the stop token)
    lp_tol: float = 5e-4  # on the fork's log-probabilities
    front_door: dict = field(default_factory=dict)
    counters: tuple = ()  # the /metrics families the served requests move
    param_counts: tuple = ()  # (preset, want)
    pools: tuple = ()  # (preset, cache bytes a token, state bytes a row)
    refuses: dict = field(default_factory=dict)  # what -> the message it is refused by
    hf_file: Optional[dict] = None  # a checkpoint's config.json the loader refuses
    published: Optional[dict] = None  # the served preset against its configuration file
    manifest: dict = field(default_factory=dict)

    @property
    def cfg(self):
        return get_config(self.preset)

    @property
    def ref(self):
        return benchmark_module(self.reference)

    def ref_call(self, params, tokens, **kw):
        """The reference, eager, as the chip check calls it."""
        cfg = self.cfg
        params = self.ref_tree(cfg, params) if self.ref_tree else params
        held = ((cfg.expert_offset, cfg.held_experts),) if self.held else ()
        return self.ref.forward(self.hf(cfg), params, tokens, *held, **kw)


def reference(row, params, tokens, positions=None):
    """The row's reference on one sequence, compiled: logits [S, V] (or, for
    a model with a next-token module, the pair with its logits [S - 1, V])."""
    keep = None if positions is None else tuple(int(p) for p in positions)
    return _compiled(row.preset, keep)(params, jnp.asarray(tokens))


@functools.lru_cache(maxsize=None)
def _compiled(preset, positions):
    return jax.jit(lambda params, tokens: ROWS[preset].ref_call(params, tokens, positions=positions))


def param_count(cfg):
    return (latent if cfg.is_latent else hybrid).param_count(cfg)


def _without_shared_expert(params):
    return dict(params, layers=dict(params["layers"], ws_down=0 * params["layers"]["ws_down"]))


def _nemotron3_without_d(params):
    return dict(params, layers=[dict(p, D=0 * p["D"]) if "D" in p else p for p in params["layers"]])


def _olmo_norm_doubled(params):
    return dict(params, layers=[dict(p, post_mlp_norm=p["post_mlp_norm"] * 2) for p in params["layers"]])


def _nemotron3_published(hf, full, cut):
    assert full.layer_pattern.startswith(cut.layer_pattern)
    assert hf["expand"] * hf["hidden_size"] == 5376 != cut.mamba_num_heads * cut.mamba_head_dim == 4096


def _olmo_published(hf, full, cut):
    assert not cut.use_rope


ROWS = {row.preset: row for row in (
    Served(
        "xing4-tiny", "xing4_reference", xing4_hf, moe_layers=2,
        forward_moves=(({"rope_scaling": None}, None), ({}, _without_shared_expert),
                       ({"hc_sinkhorn_iters": 1}, None)),
        front_door=dict(prompt="an invoice " * 12, n=8, max_tokens=64, max_prompt=512,
                        schema=workload_schema("extract")),
        counters=("moe_layer_calls", "mla_latent_rows_read"),
        param_counts=(("xing4-29b-a4b", pytest.approx(29.5e9, rel=0.01)),
                      ("xing4-29b-a4b-cut7", pytest.approx(5.54e9, rel=0.01))),
        # A latent row is stored in whole tiles of 128 lanes (576 -> 640, 40 -> 128).
        pools=(("xing4-29b-a4b-cut7", 7 * 640 * 2, 0), ("xing4-tiny", 3 * 128 * 4, 0),
               ("qwen2-7b", 2 * 28 * 512 * 2, 0)),
        refuses=dict.fromkeys(("default_engine", "mesh", "int8", "int4", "speculative", "sp_prefill",
                               "param_specs", "quantize_params", "init_quantized", "load_checkpoint",
                               "config_from_hf"), "latent|xing4"),
        hf_file={"model_type": "xing4_0", "kv_lora_rank": 512, "hidden_size": 64,
                 "num_attention_heads": 4},
        published=dict(config="xing4-29b-a4b", cut=dict(num_layers=7, first_k_dense=1),
                       sizes={k: k for k in ("vocab_size", "intermediate_size", "moe_intermediate_size")},
                       reduced=("num_hidden_layers", "first_k_dense_replace", "num_nextn_predict_layers"),
                       attn_scale=192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2),
        manifest=dict(
            cell="xing4-29b-a4b.extract",
            cells={"qwen2-7b.chat": dict(config="qwen2-7b", traffic="chat", chips=1)},
            has={"moe_expert_stream_share", "mla_latent_stream_share", "moe_experts_touched_share",
                 "moe_load_max_over_mean", "device_idle_share", "weight_stream_share"},
            counters={"kllms_moe_experts_touched": 330.0, "kllms_moe_layer_calls": 6.0,
                      "kllms_moe_pairs": 768.0, "kllms_moe_max_load": 24.0,
                      "kllms_mla_latent_rows_read": 1000.0},
            busy_s=0.03,
            reads={"moe_experts_touched_share": 100 * 330 / (6 * 64),
                   "moe_load_max_over_mean": 24 * 64 / 768,
                   "moe_expert_stream_share": 100 * 330 * 3 * 3584 * 1024 * 2 / 0.03 / 819e9,
                   "mla_latent_stream_share": 100 * 1000 * 576 * 2 / 0.03 / 819e9},
            bare=("moe_", "mla_")),
    ),
    Served(
        "nemotron3-tiny", "nemotron3_reference", nemotron3_hf, moe_layers=4, state=("ssm", "M"),
        forward_moves=(({}, _nemotron3_without_d), ({"routed_scaling_factor": 1.0}, None)),
        forward_holds=(({"mamba_chunk": 8}, None),),
        moved_by=0.1,
        n8=((100, (), 4), (20, (), 1), (200, (32, 64, 128), 2), (161, (32, 64, 128), 2)),
        n8_whole=True,
        # Its expert columns are stored padded to 128: the tree outweighs the count.
        front_door=dict(prompt="hello there " * 15, n=3, max_tokens=12, max_prompt=512,
                        counted_params=False),
        counters=("ssm_state_updates", "ssm_tokens_scanned"),
        param_counts=(("nemotron3-nano-30b-a3b", pytest.approx(31.58e9, rel=0.001)),
                      ("nemotron3-nano-30b-a3b-cut9", pytest.approx(6.073e9, rel=0.001))),
        pools=(("nemotron3-nano-30b-a3b-cut9", 1 * 2 * 2 * 128 * 2,
                4 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)),
               ("nemotron3-tiny", 1 * 2 * 2 * 16 * 4, 4 * (8 * 16 * 32 * 4 + 3 * 256 * 4))),
        refuses=dict.fromkeys(("default_engine", "mesh", "int8", "int4", "speculative", "sp_prefill",
                               "prefix_cache", "dense_layout", "param_specs", "quantize_params", "init_quantized",
                               "load_checkpoint", "config_from_hf", "generate", "generate_many",
                               "decode_step", "no_continuous_batching"),
                              "hybrid|recurrent state|nemotron"),
        hf_file={"model_type": "nemotron_h", "hybrid_override_pattern": "ME*", "hidden_size": 64,
                 "num_attention_heads": 4},
        published=dict(config="nemotron3-nano-30b-a3b",
                       cut=dict(num_layers=9, layer_pattern="MEMEM*EME"),
                       reduced=("num_hidden_layers", "hybrid_override_pattern"),
                       attn_scale=128 ** -0.5, also=_nemotron3_published),
        manifest=dict(
            cell="nemotron3-nano-30b-a3b.chat",
            own={"ssm_state_stream_share", "moe_expert_stream_share.nongated", "state_install_ms"},
            has={"weight_stream_share", "device_idle_share", "loop_step_ms"},
            lacks={"moe_expert_stream_share", "mla_latent_stream_share"},
            shared={"olmo-hybrid-7b.chat": {"state_install_ms"}},  # its state's fork too
            counters={"kllms_ssm_state_updates": 9000.0, "kllms_moe_experts_touched": 28000.0},
            scrapes=({"kllms_continuous_state_install_seconds_sum": 1.0,
                      "kllms_continuous_admitted": 10.0},
                     {"kllms_continuous_state_install_seconds_sum": 1.5,
                      "kllms_continuous_admitted": 210.0}),
            busy_s=2.2,
            reads={"ssm_state_stream_share": 100 * 9000 * 64 * 64 * 128 * 8 / 2.2 / 819e9,
                   "moe_expert_stream_share.nongated": 100 * 28000 * 2 * 2688 * 1856 * 2 / 2.2 / 819e9,
                   "state_install_ms": 1000 * 0.5 / 200},
            bare=tuple(sorted({"ssm_state_stream_share", "moe_expert_stream_share.nongated",
                               "state_install_ms"}))),
    ),
    Served(
        "joyai-tiny", "joyai_reference", joyai_hf, held=True, moe_layers=2, page_size=8,
        steps="drafted",
        forward_moves=(({}, _without_shared_expert), ({"routed_scaling_factor": 1.0}, None)),
        front_door=dict(prompt="an invoice " * 12, n=8, max_tokens=64, max_prompt=512,
                        schema=workload_schema("extract")),
        counters=("moe_layer_calls", "mla_latent_rows_read"),
        param_counts=(("joyai-llm-flash-cut8", pytest.approx(5.693e9, abs=2e6)),),  # the file's 5,693 M
        pools=(("joyai-llm-flash-cut8", 9 * 640 * 2, 2048 * 2), ("joyai-tiny", 4 * 128 * 4, 64 * 4)),
        refuses={"default_engine": "latent block.*device mesh", "prefix_cache": "prefix_cache_size=4",
                 "dense_layout": "kv_layout='dense'", "int8": "int8", "speculative": "speculative"},
        published=dict(config="joyai-llm-flash", cut=dict(num_layers=8, experts_held=128),
                       sizes={"n_routed_experts": "held_experts"},
                       reduced=("num_hidden_layers", "n_routed_experts"), attn_scale=192 ** -0.5),
        manifest=dict(cell="joyai-llm-flash.extract",
                      own={"draft_accepted_share", "moe_expert_stream_share.held",
                           "moe_experts_touched_share.held"},
                      has={"paged_attn_walked_share.latent", "device_idle_share",
                           "weight_stream_share", "loop_step_ms"},
                      lacks={"moe_expert_stream_share", "mla_latent_stream_share",
                             "paged_attn_walked_share"}),
    ),
    Served(
        "command-a-plus-tiny", "command_a_plus_reference", command_a_plus_hf, held=True,
        ref_tree=interleaved, moe_layers=4, page_size=8, steps="windowed",
        chunk_cases=((8, "xla"), (8, "pallas_interpret"), (16, "xla"), (16, "pallas_interpret")),
        forward_moves=(({"sliding_window": 13}, None), ({"layer_pattern": "LLLL"}, None),
                       ({"shared_experts_averaged": False}, None)),
        front_door=dict(prompt="a contract of many pages " * 12, n=8, max_tokens=64, max_prompt=448,
                        schema=workload_schema("extract-long")),
        counters=("moe_layer_calls", "moe_experts_touched"),
        param_counts=(("command-a-plus-cut4", pytest.approx(4.733e9, abs=1e6)),  # the file's 4,733 M
                      ("command-a-plus", pytest.approx(218e9, abs=1e9))),  # "218B"
        pools=(("command-a-plus-cut4", 4 * 8 * 256 * 2, 0), ("command-a-plus-tiny", 4 * 2 * 32 * 4, 0)),
        refuses=dict.fromkeys(("default_engine", "mesh", "int8", "speculative", "sp_prefill",
                               "prefix_cache", "dense_layout", "param_specs", "quantize_params", "init_quantized",
                               "load_checkpoint", "generate", "decode_step",
                               "no_continuous_batching"),
                              "hybrid|parallel block|command-a-plus"),
        published=dict(config="command-a-plus",
                       cut=dict(num_layers=4, layer_pattern="LLLG", experts_held=16, vocab_size=32768),
                       sizes={"num_experts": "held_experts", "vocab_size": "vocab_size"},
                       reduced=("num_hidden_layers", "layer_types", "num_experts", "vocab_size"),
                       described=("layer_types",),  # the block says the pattern in words
                       attn_scale=128 ** -0.5),
        manifest=dict(
            cell="command-a-plus.extract-long",
            own={"paged_attn_walked_share.mixed", "paged_attn_windowed_out_share",
                 "paged_attn_kv_stream_share", "moe_expert_stream_share.held16",
                 "moe_experts_touched_share.held16", "prefill_chunk_ms.extract-long",
                 "moe_load_max_over_mean.held16"},
            # ... and the accepted reads of what the cell's traffic drives: the grammar
            # mask under the schema and the consolidation of n = 8, which list it too.
            has={"weight_stream_share", "device_idle_share", "loop_step_ms",
                 "grammar_masked_share", "consolidate_ms"},
            lacks={"paged_attn_walked_share", "moe_expert_stream_share.held",
                   "moe_load_max_over_mean", "mla_latent_stream_share", "prefill_chunk_ms.extract"},
            shared={"olmo-hybrid-7b.chat": {"paged_attn_kv_stream_share"}},  # an MHA cache stream
            like="extract",
            traffic={"doc_tokens": {"dist": "lognormal", "median": 2400, "sigma": 0.8,
                                    "min": 200, "max": 6000, "pool": 8}},
            config={"num_experts": 16, "intermediate_size": 4096, "head_dim": 128}),
    ),
    Served(
        "olmo-hybrid-tiny", "olmo_hybrid_reference", olmo_hybrid_hf, tol=2e-3, state=("gdn", "D"),
        chunk_cases=((16, "xla"), (32, "pallas_interpret")),
        forward_moves=(({"linear_allow_neg_eigval": False}, None), ({}, _olmo_norm_doubled)),
        # The F layer takes no positions, whatever the flag.
        forward_holds=(({"use_rope": True}, None),),
        moved_by=0.05,
        lp_tol=2e-3,
        n8=((100, (), 4), (20, (), 1), (200, (32, 64, 128), 2), (161, (32, 64, 128), 2)),
        front_door=dict(prompt="hello there " * 15, n=3, max_tokens=12, max_prompt=512),
        counters=("gdn_state_updates", "gdn_tokens_scanned"),
        # A delta-rule layer 215,570,172, an attention layer 185,809,920, the
        # embedding and the head 385,351,680 each, the final norm 3,840.
        param_counts=(("olmo-hybrid-7b", 8 * (3 * 215_570_172 + 185_809_920) + 2 * 385_351_680 + 3840),
                      ("olmo-hybrid-7b-cut20",
                       5 * (3 * 215_570_172 + 185_809_920) + 2 * 385_351_680 + 3840)),
        # 81.9 KB of cache a token (30 KV heads stored at 32: ``cache_heads``) and
        # 34.2 MB of state a row at the cut.
        pools=(("olmo-hybrid-7b-cut20", 5 * 32 * 128 * 2 * 2, 15 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)),
               ("olmo-hybrid-tiny", 2 * 4 * 64 * 2 * 4, 6 * (4 * 32 * 64 * 4 + 3 * 512 * 4))),
        refuses=dict.fromkeys(("default_engine", "mesh", "int8", "speculative", "prefix_cache",
                               "dense_layout", "param_specs", "quantize_params", "load_checkpoint",
                               "config_from_hf", "generate", "no_continuous_batching"),
                              "hybrid|recurrent state"),
        published=dict(config="olmo-hybrid-7b", cut=dict(num_layers=20, layer_pattern="DDDF" * 5),
                       reduced=("num_hidden_layers", "layer_types"), attn_scale=128 ** -0.5,
                       file={"max_position_embeddings": 65536, "rope_parameters": {"rope_theta": None}},
                       also=_olmo_published),
        hf_file={"model_type": "olmo_hybrid", "layer_types": ["linear_attention", "full_attention"],
                 "hidden_size": 64, "num_attention_heads": 4},
        manifest=dict(
            cell="olmo-hybrid-7b.chat",
            own={"gdn_state_stream_share"},
            has={"state_install_ms", "paged_attn_walked_share", "paged_attn_kv_stream_share",
                 "weight_stream_share", "loop_step_ms", "prefill_chunk_ms.chat", "loop_emit_ms"},
            lacks={"ssm_state_stream_share", "moe_expert_stream_share"},
            counters={"kllms_gdn_state_updates": 9000.0, "kllms_paged_attn_pages_walked": 4000.0},
            busy_s=2.2,
            reads={"gdn_state_stream_share": 100 * 9000 * 30 * 96 * 192 * 8 / 2.2 / 819e9,
                   "paged_attn_kv_stream_share": 100 * 4000 * 64 * 30 * 128 * 2 * 2 / 2.2 / 819e9},
            bare=("gdn_state_stream_share",)),
    ),
)}
