"""Llama-3.1/3.2-style RoPE scaling (HF rope_type="llama3"): frequency
adjustment differentially pinned against transformers' implementation, and
end-to-end logits parity on a checkpoint that ships rope_scaling."""

import json

import jax
import numpy as np
import pytest

from k_llms_tpu.models import get_config
from k_llms_tpu.models.llama import _rope_inv_freq
from k_llms_tpu.models.loader import _rope_scaling_from_hf

SCALING = {
    "rope_type": "llama3",
    "factor": 8.0,
    "low_freq_factor": 1.0,
    "high_freq_factor": 4.0,
    "original_max_position_embeddings": 64,
}


def test_inv_freq_matches_transformers():
    from transformers import LlamaConfig
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    hf_cfg = LlamaConfig(
        hidden_size=64,
        num_attention_heads=4,
        head_dim=16,
        rope_theta=10000.0,
        rope_scaling=dict(SCALING),
        max_position_embeddings=512,
    )
    ref_inv_freq, _ = ROPE_INIT_FUNCTIONS["llama3"](hf_cfg, device="cpu")
    ours = _rope_inv_freq(16, 10000.0, _rope_scaling_from_hf(SCALING))
    np.testing.assert_allclose(
        np.asarray(ours), ref_inv_freq.numpy(), rtol=1e-6, atol=1e-8
    )


def test_hf_rope_scaling_parsing():
    assert _rope_scaling_from_hf(None) is None
    assert _rope_scaling_from_hf({"rope_type": "default"}) is None
    assert _rope_scaling_from_hf(SCALING) == (8.0, 1.0, 4.0, 64)
    with pytest.raises(ValueError):
        _rope_scaling_from_hf({"rope_type": "yarn", "factor": 4.0})


def test_registered_llama32_config_carries_scaling():
    cfg = get_config("llama-3.2-1b")
    assert cfg.rope_scaling == (32.0, 1.0, 4.0, 8192)
    assert get_config("llama-3-8b").rope_scaling is None


def test_logits_match_transformers_with_scaling(tmp_path):
    """Full parity: a checkpoint whose config.json ships llama3 rope_scaling
    must reproduce transformers' logits at positions PAST the original
    context window (where the scaling actually changes the frequencies)."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    from k_llms_tpu.models.llama import forward
    from k_llms_tpu.models.loader import config_from_hf, load_checkpoint

    d = tmp_path / "scaled"
    hf_config = LlamaConfig(
        vocab_size=320,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        rope_scaling=dict(SCALING),
        rms_norm_eps=1e-5,
        max_position_embeddings=512,
        bos_token_id=0,
        eos_token_id=1,
        tie_word_embeddings=False,
        attention_bias=False,
    )
    torch.manual_seed(1)
    model = LlamaForCausalLM(hf_config).eval()
    model.save_pretrained(str(d), safe_serialization=True)
    assert json.load(open(d / "config.json"))["rope_scaling"]["rope_type"] == "llama3"

    cfg = config_from_hf(str(d)).with_(dtype="float32")
    assert cfg.rope_scaling == (8.0, 1.0, 4.0, 64)
    params = load_checkpoint(str(d), cfg)

    rng = np.random.default_rng(7)
    ids = rng.integers(2, 320, size=(1, 100), dtype=np.int64)  # past orig ctx 64
    with torch.no_grad():
        ref = model(torch.tensor(ids)).logits.numpy()

    import jax.numpy as jnp

    ours, _ = forward(cfg, params, jnp.asarray(ids), jnp.ones((1, 100), jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-3, atol=2e-3)

    # Sanity: scaling OFF must NOT match at long positions — the parity above
    # is really exercising the scaled frequencies.
    cfg_off = cfg.with_(rope_scaling=None)
    off, _ = forward(cfg_off, params, jnp.asarray(ids), jnp.ones((1, 100), jnp.int32))
    assert not np.allclose(np.asarray(off), ref, rtol=2e-3, atol=2e-3)


# -- YaRN (DeepSeek-V3 form; models/latent.py's rope dims) ----------------------------

XING_YARN = ("yarn", 64.0, 4096, 32.0, 1.0, 1.0)


def hand_yarn(d, theta, factor, orig, beta_fast, beta_slow):
    """The frequencies by hand: pair i keeps 1/theta^(2i/d) below the fast
    correction dim, takes a 1/factor of it above the slow one, a linear blend
    between."""
    import math

    def dim(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(dim(beta_fast)), 0), min(math.ceil(dim(beta_slow)), d - 1)
    out = []
    for i in range(d // 2):
        keep = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        extra = theta ** (-2 * i / d)
        out.append(extra * keep + extra / factor * (1.0 - keep))
    return low, high, np.asarray(out)


@pytest.mark.parametrize("d,theta,scaling", [
    (64, 10000.0, XING_YARN),
    (8, 10000.0, ("yarn", 64.0, 64, 32.0, 1.0, 1.0)),
    (64, 10000.0, ("yarn", 40.0, 4096, 32.0, 1.0, 0.707)),
])
def test_yarn_inv_freq_matches_hand_computed(d, theta, scaling):
    _, factor, orig, beta_fast, beta_slow, _ = scaling
    low, high, want = hand_yarn(d, theta, factor, orig, beta_fast, beta_slow)
    ours = np.asarray(_rope_inv_freq(d, theta, scaling))
    np.testing.assert_allclose(ours, want, rtol=1e-6)
    # Fast pairs are untouched, slow pairs divided by the factor, exactly.
    np.testing.assert_allclose(ours[: low + 1], theta ** (-2 * np.arange(low + 1) / d), rtol=1e-6)
    np.testing.assert_allclose(
        ours[high:], theta ** (-2 * np.arange(high, d // 2) / d) / factor, rtol=1e-6)
    if scaling == XING_YARN:
        assert (low, high) == (10, 23)  # 64 ln(4096 / 64 pi) / (2 ln 1e4) = 10.47; ... / 2 pi = 22.51


def test_yarn_inv_freq_matches_transformers():
    from transformers import PretrainedConfig
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    hf_cfg = PretrainedConfig(
        hidden_size=64 * 32, num_attention_heads=32, head_dim=64, rope_theta=10000.0,
        max_position_embeddings=262144, qk_rope_head_dim=64,
        rope_scaling={"rope_type": "yarn", "factor": 64.0, "beta_fast": 32, "beta_slow": 1,
                      "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 4096},
    )
    ref_inv_freq, attention_factor = ROPE_INIT_FUNCTIONS["yarn"](hf_cfg, device="cpu")
    np.testing.assert_allclose(
        np.asarray(_rope_inv_freq(64, 10000.0, XING_YARN)), ref_inv_freq.numpy(), rtol=1e-5)
    assert attention_factor == pytest.approx(1.0)  # mscale / mscale_all_dim on cos and sin


def test_registered_xing4_config_carries_yarn_and_its_score_scale():
    cfg = get_config("xing4-29b-a4b")
    assert cfg.rope_scaling == XING_YARN
    mscale = 0.1 * np.log(64.0) + 1.0
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert get_config("qwen2-7b").attn_scale == 1.0 / np.sqrt(128)
    assert get_config("gemma-2-2b").attn_scale == 256.0 ** -0.5


# -- the GQA block's lane rotation (models/llama.py::rope_lanes) ------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("scaling", [None, (8.0, 1.0, 4.0, 8192)], ids=["plain", "llama3"])
@pytest.mark.parametrize("d", [64, 128])
def test_the_lane_rotation_is_rope_embed_bit_for_bit(d, scaling, dtype):
    """Random heads at positions 0..8,191, each form compiled on its own: the
    same bits, in float32 and in bfloat16."""
    import jax.numpy as jnp

    from k_llms_tpu.models.llama import rope_embed, rope_lanes

    x = (jax.random.normal(jax.random.key(d), (2, 4096, 4, d), jnp.float32) * 3).astype(dtype)
    positions = jnp.arange(8192, dtype=jnp.int32).reshape(2, 4096)
    split, lanes = (np.asarray(jax.jit(f, static_argnums=(2, 3))(x, positions, 1e6, scaling))
                    for f in (rope_embed, rope_lanes))
    bits = np.uint32 if dtype == "float32" else np.uint16
    assert np.array_equal(split.view(bits), lanes.view(bits))
