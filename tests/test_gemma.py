"""Gemma-2 family: GeGLU, offset RMSNorm, post-block norms, embed scaling,
softcaps, and alternating local/global attention — the most divergent
architecture the one-program transformer covers."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k_llms_tpu.engine.engine import LocalEngine
from k_llms_tpu.engine.tokenizer import ByteTokenizer
from k_llms_tpu.models import get_config, init_params
from k_llms_tpu.models.llama import decode_step, forward, init_cache, prefill, rms_norm

TINY_GEMMA = get_config("tiny").with_(
    name="tiny-gemma",
    sliding_window=5,
    sliding_window_layers="alternating",
    act="gelu",
    norm_offset=True,
    embed_scale=True,
    post_block_norms=True,
    attn_softcap=50.0,
    logit_softcap=30.0,
    query_scale=16.0**-0.5,
    num_layers=4,  # even count: two local, two global layers
)


def test_registry_gemma_configs():
    for name in ("gemma-2-2b", "gemma-2-9b"):
        cfg = get_config(name)
        assert cfg.post_block_norms and cfg.attn_softcap == 50.0
        assert cfg.sliding_window_layers == "alternating"


def test_offset_rms_norm():
    x = jnp.ones((1, 4), jnp.float32) * 2.0
    w = jnp.zeros((4,), jnp.float32)
    # offset: weight 0 means identity scale (1 + 0).
    np.testing.assert_allclose(
        np.asarray(rms_norm(x, w, 1e-6, offset=True)),
        np.asarray(rms_norm(x, jnp.ones((4,)), 1e-6, offset=False)),
        rtol=1e-6,
    )


def test_gemma_param_tree():
    params = init_params(TINY_GEMMA, jax.random.key(0))
    layers = params["layers"]
    assert "post_attn_norm" in layers and "post_mlp_norm" in layers
    # Offset norms initialize at 0 (effective scale 1).
    assert float(jnp.abs(layers["attn_norm"]).max()) == 0.0
    assert float(jnp.abs(params["final_norm"]).max()) == 0.0


def test_gemma_forward_shapes_and_softcap():
    params = init_params(TINY_GEMMA, jax.random.key(1))
    tokens = jax.random.randint(jax.random.key(2), (2, 12), 0, TINY_GEMMA.vocab_size)
    mask = jnp.ones_like(tokens)
    logits, hidden = forward(TINY_GEMMA, params, tokens, mask)
    assert logits.shape == (2, 12, TINY_GEMMA.vocab_size)
    # Final softcap bounds every logit strictly below the cap.
    assert float(jnp.abs(logits).max()) < 30.0


def test_gemma_decode_matches_forward():
    """Alternating local/global masks + shared-prefix decode must reproduce the
    full forward — this pins the per-layer jnp.where mask selection in the scan
    AND the windowed decode arithmetic simultaneously."""
    cfg = TINY_GEMMA
    params = init_params(cfg, jax.random.key(3))
    S = 16
    tokens = jax.random.randint(jax.random.key(4), (1, S), 0, cfg.vocab_size)
    prompt_len = jnp.int32(9)  # window 5 < prompt: both mask regimes exercised

    pl_logits, prefix = prefill(cfg, params, tokens, prompt_len)
    full, _ = forward(
        cfg, params, tokens, (jnp.arange(S)[None, :] < prompt_len).astype(jnp.int32)
    )
    np.testing.assert_allclose(pl_logits[0], full[0, 8], rtol=1e-4, atol=1e-4)

    n = 2
    gen_cache = init_cache(cfg, n, 5)
    for step in range(4):
        tk = jnp.broadcast_to(tokens[0, 9 + step], (n,))
        logits, gen_cache = decode_step(
            cfg, params, tk, jnp.int32(step), prompt_len, gen_cache, prefix
        )
        full_s, _ = forward(
            cfg, params, tokens, (jnp.arange(S)[None, :] < 10 + step).astype(jnp.int32)
        )
        np.testing.assert_allclose(logits[0], full_s[0, 9 + step], rtol=1e-4, atol=1e-4)


def test_alternating_differs_from_all_windowed():
    params = init_params(TINY_GEMMA, jax.random.key(5))
    all_local = TINY_GEMMA.with_(sliding_window_layers="all")
    S = 14
    tokens = jax.random.randint(jax.random.key(6), (1, S), 0, TINY_GEMMA.vocab_size)
    mask = jnp.ones_like(tokens)
    a, _ = forward(TINY_GEMMA, params, tokens, mask)
    b, _ = forward(all_local, params, tokens, mask)
    # Global layers see past the window; all-windowed layers cannot.
    assert not np.allclose(np.asarray(a[0, -1]), np.asarray(b[0, -1]))


def test_gemma_engine_generate():
    engine = LocalEngine(TINY_GEMMA, use_mesh=False)
    tok = ByteTokenizer()
    ids = tok.apply_chat_template([{"role": "user", "content": "gemma check"}])
    r = engine.generate(ids, n=3, max_new_tokens=6, temperature=1.0, seed=0)
    assert r.tokens.shape == (3, 6)
    again = engine.generate(ids, n=3, max_new_tokens=6, temperature=1.0, seed=0)
    np.testing.assert_array_equal(r.tokens, again.tokens)


def test_gemma_engine_sharded():
    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-device CPU mesh")
    from k_llms_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(2, 2, jax.devices()[:4])
    engine = LocalEngine(TINY_GEMMA, mesh=mesh)
    tok = ByteTokenizer()
    ids = tok.apply_chat_template([{"role": "user", "content": "sharded gemma"}])
    r = engine.generate(ids, n=4, max_new_tokens=6, seed=1)
    assert r.tokens.shape == (4, 6)


def test_config_from_hf_gemma2(tmp_path):
    from k_llms_tpu.models.loader import config_from_hf

    hf = {
        "model_type": "gemma2",
        "vocab_size": 256128,
        "hidden_size": 2304,
        "intermediate_size": 9216,
        "num_hidden_layers": 26,
        "num_attention_heads": 8,
        "num_key_value_heads": 4,
        "head_dim": 256,
        "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6,
        "max_position_embeddings": 8192,
        "sliding_window": 4096,
        "query_pre_attn_scalar": 256,
        "attn_logit_softcapping": 50.0,
        "final_logit_softcapping": 30.0,
        "bos_token_id": 2,
        "eos_token_id": 1,
        "pad_token_id": 0,
    }
    d = tmp_path / "gemma2"
    d.mkdir()
    (d / "config.json").write_text(json.dumps(hf))
    cfg = config_from_hf(str(d))
    assert cfg.act == "gelu" and cfg.norm_offset and cfg.embed_scale
    assert cfg.post_block_norms and cfg.sliding_window_layers == "alternating"
    assert cfg.attn_softcap == 50.0 and cfg.logit_softcap == 30.0
    assert cfg.query_scale == pytest.approx(256.0**-0.5)
    assert cfg.head_dim == 256  # from hf, NOT hidden/heads (2304/8=288)


def test_safetensors_import_gemma_norms(tmp_path):
    from safetensors.numpy import save_file

    from k_llms_tpu.models.loader import load_safetensors

    cfg = TINY_GEMMA.with_(dtype="float32")
    params = init_params(cfg, jax.random.key(7))
    rng = np.random.default_rng(0)
    for key in ("attn_norm", "mlp_norm", "post_attn_norm", "post_mlp_norm"):
        params["layers"][key] = jnp.asarray(
            rng.standard_normal(params["layers"][key].shape), jnp.float32
        )

    tensors = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["final_norm"]),
        # Tied embeddings: no lm_head.weight in the file (Gemma).
    }
    hf_weights = {
        "wq": "self_attn.q_proj",
        "wk": "self_attn.k_proj",
        "wv": "self_attn.v_proj",
        "wo": "self_attn.o_proj",
        "w_gate": "mlp.gate_proj",
        "w_up": "mlp.up_proj",
        "w_down": "mlp.down_proj",
    }
    hf_norms = {
        "attn_norm": "input_layernorm",
        "post_attn_norm": "post_attention_layernorm",
        "mlp_norm": "pre_feedforward_layernorm",
        "post_mlp_norm": "post_feedforward_layernorm",
    }
    for i in range(cfg.num_layers):
        for ours, hf in hf_weights.items():
            tensors[f"model.layers.{i}.{hf}.weight"] = np.ascontiguousarray(
                np.asarray(params["layers"][ours][i]).T
            )
        for ours, hf in hf_norms.items():
            tensors[f"model.layers.{i}.{hf}.weight"] = np.asarray(params["layers"][ours][i])
    ckpt = tmp_path / "hf-gemma"
    ckpt.mkdir()
    save_file(tensors, str(ckpt / "model.safetensors"))

    loaded = load_safetensors(str(ckpt), cfg, dtype=jnp.float32)
    # Norms land in the right slots (the post_attention_layernorm name trap).
    np.testing.assert_allclose(
        np.asarray(loaded["layers"]["post_attn_norm"]),
        np.asarray(params["layers"]["post_attn_norm"]),
    )
    np.testing.assert_allclose(
        np.asarray(loaded["layers"]["mlp_norm"]), np.asarray(params["layers"]["mlp_norm"])
    )
    # Tied embeddings: lm_head = embed.T
    np.testing.assert_allclose(
        np.asarray(loaded["lm_head"]), np.asarray(params["embed"]).T
    )


def test_gemma_flash_forward_matches_xla():
    """Flash prefill now covers softcap + alternating windows: the full Gemma-2
    style forward must match the XLA path."""
    import numpy as np

    cfg_xla = TINY_GEMMA.with_(attention_impl="xla")
    cfg_flash = TINY_GEMMA.with_(attention_impl="flash_interpret")
    params = init_params(cfg_xla, jax.random.key(2))
    S = 24
    tokens = jax.random.randint(jax.random.key(3), (2, S), 0, cfg_xla.vocab_size)
    mask = (jnp.arange(S)[None, :] < jnp.array([[S], [17]])).astype(jnp.int32)
    a, _ = forward(cfg_xla, params, tokens, mask)
    b, _ = forward(cfg_flash, params, tokens, mask)
    # Padded query rows whose sliding window misses the valid range entirely
    # have no defined output (kernel zeroes them, XLA spreads uniform) — only
    # the valid rows carry semantics.
    np.testing.assert_allclose(
        np.asarray(a)[0], np.asarray(b)[0], rtol=2e-3, atol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(a)[1, :17], np.asarray(b)[1, :17], rtol=2e-3, atol=2e-3
    )
