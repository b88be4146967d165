"""int4 (w4a16) quantization: packing, the Pallas kernel (interpret mode on
CPU), and the engine/backend plumbing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k_llms_tpu.ops.w4matmul import (
    GROUP,
    Q4Tensor,
    pack_int4,
    supports_int4,
    unpack_int4,
    w4_matmul,
)


def test_pack_unpack_roundtrip_exact():
    # Values already on the int4 grid round-trip exactly through pack/unpack.
    key = jax.random.key(0)
    ints = jax.random.randint(key, (256, 128), -7, 8).astype(jnp.float32)
    w = ints * 0.01  # uniform scale per group -> amax/7 recovers the grid
    q4 = pack_int4(w)
    assert q4.q.shape == (128, 128)
    assert q4.scale.shape == (2, 128)
    deq = unpack_int4(q4)
    np.testing.assert_allclose(np.asarray(deq), np.asarray(w), rtol=1e-5, atol=1e-7)


def test_pack_quantization_error_bounded():
    w = jax.random.normal(jax.random.key(1), (512, 256), jnp.float32)
    q4 = pack_int4(w)
    deq = np.asarray(unpack_int4(q4))
    w_np = np.asarray(w)
    # Max error within a group is scale/2; scale = amax/7.
    scales = np.abs(w_np.reshape(-1, GROUP, 256)).max(axis=1) / 7.0
    err = np.abs(deq - w_np).reshape(-1, GROUP, 256).max(axis=1)
    assert (err <= scales / 2 + 1e-7).all()


def test_kernel_matches_xla_reference():
    # Real kernel blocking (K=512 -> one 512 block; N=512) in interpret mode.
    key = jax.random.key(2)
    w = jax.random.normal(key, (512, 512), jnp.float32)
    q4 = pack_int4(w)
    x = jax.random.normal(jax.random.key(3), (48, 512), jnp.float32)
    ref = x @ unpack_int4(q4).astype(x.dtype)
    out = w4_matmul(x, q4, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_kernel_multiblock_grid():
    # Multiple row/N/K blocks: K=1024 (one block of 8 groups), N=768 (128-col
    # blocks x6), rows spanning two row blocks when block_rows is small.
    w = jax.random.normal(jax.random.key(4), (1024, 768), jnp.float32)
    q4 = pack_int4(w)
    x = jax.random.normal(jax.random.key(5), (40, 1024), jnp.float32)
    ref = x @ unpack_int4(q4).astype(x.dtype)
    out = w4_matmul(x, q4, block_rows=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_kernel_bf16_activations():
    w = jax.random.normal(jax.random.key(6), (512, 512), jnp.float32)
    q4 = pack_int4(w)
    x = jax.random.normal(jax.random.key(7), (16, 512), jnp.bfloat16)
    ref = (x.astype(jnp.float32) @ unpack_int4(q4)).astype(jnp.bfloat16)
    out = w4_matmul(x, q4, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=5e-2, atol=5e-2
    )


def test_qdot_dispatches_q4():
    from k_llms_tpu.models.quant import qdot

    w = jax.random.normal(jax.random.key(8), (256, 256), jnp.float32)
    q4 = pack_int4(w)
    x = jax.random.normal(jax.random.key(9), (2, 3, 256), jnp.float32)
    out = qdot(x, q4)
    assert out.shape == (2, 3, 256)
    ref = x @ unpack_int4(q4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_supports_int4_gate():
    assert supports_int4(256) and supports_int4(4096)
    assert not supports_int4(128) and not supports_int4(320)


def test_quantize_params_bits4_mixed_tree():
    """bits=4 packs eligible weights Q4 and falls back int8 for the rest."""
    from k_llms_tpu.models import get_config, init_params
    from k_llms_tpu.models.quant import QTensor, quantize_params

    cfg = get_config("tiny").with_(
        hidden_size=256, intermediate_size=512, num_layers=2, vocab_size=384
    )
    params = init_params(cfg, jax.random.key(0))
    qp = quantize_params(params, bits=4)
    assert isinstance(qp["layers"]["w_gate"], Q4Tensor)  # 256 -> 512
    assert isinstance(qp["layers"]["w_down"], Q4Tensor)  # 512 -> 256
    assert isinstance(qp["lm_head"], Q4Tensor)  # 256 -> 384
    # wk: K=256 eligible, N=kv_dim may not be 128-divisible on tiny; just check
    # the tree is fully quantized one way or the other.
    for name in ("wq", "wk", "wv", "wo"):
        assert isinstance(qp["layers"][name], (Q4Tensor, QTensor))


def test_int4_generate_end_to_end():
    """A small-but-eligible model generates through the full engine with
    quantize="int4" (CPU: XLA fallback inside w4_matmul for tiny shapes,
    interpret-mode kernel for eligible ones)."""
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.models import get_config

    cfg = get_config("tiny").with_(
        hidden_size=256,
        intermediate_size=512,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=64,
        vocab_size=384,
        max_seq_len=128,
    )
    from conftest import shared_engine

    eng = shared_engine(cfg, quantize="int4")
    assert eng.quantized == "int4"
    res = eng.generate([5, 6, 7], n=2, max_new_tokens=4, temperature=0.7, seed=11)
    assert res.tokens.shape == (2, 4)
    assert (res.tokens < 384).all()


def test_int4_orbax_roundtrip(tmp_path):
    """Q4Tensor leaves survive an orbax save/restore (rebuilt by scale shape)."""
    from k_llms_tpu.models.loader import load_orbax, save_checkpoint

    w = jax.random.normal(jax.random.key(10), (256, 128), jnp.float32)
    q4 = pack_int4(w)
    tree = {"layers": {"w_up": q4}, "note": jnp.ones((2,))}
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, tree)
    restored = load_orbax(path)
    assert isinstance(restored["layers"]["w_up"], Q4Tensor)
    np.testing.assert_array_equal(
        np.asarray(restored["layers"]["w_up"].q), np.asarray(q4.q)
    )


def test_quantize_params_passes_through_quantized_leaves():
    """Serving a quantized checkpoint with the quantization flag still set
    must keep the stored leaves, not crash or re-quantize the lossy payload."""
    from k_llms_tpu.models import get_config, init_params
    from k_llms_tpu.models.quant import QTensor, quantize_params

    cfg = get_config("tiny").with_(
        hidden_size=256, intermediate_size=512, num_layers=2, vocab_size=384
    )
    q4_tree = quantize_params(init_params(cfg, jax.random.key(0)), bits=4)
    for bits in (4, 8):
        again = quantize_params(q4_tree, bits=bits)
        assert again["layers"]["w_gate"] is q4_tree["layers"]["w_gate"]
        assert isinstance(again["lm_head"], Q4Tensor)
    q8_tree = quantize_params(init_params(cfg, jax.random.key(0)), bits=8)
    again8 = quantize_params(q8_tree, bits=4)
    assert isinstance(again8["layers"]["w_gate"], QTensor)


def test_init_params_quantized_bits4_shapes():
    from k_llms_tpu.models import get_config
    from k_llms_tpu.models.quant import init_params_quantized

    cfg = get_config("tiny").with_(
        hidden_size=256, intermediate_size=512, num_layers=2, vocab_size=384
    )
    params = init_params_quantized(cfg, jax.random.key(0), bits=4)
    gate = params["layers"]["w_gate"]
    assert isinstance(gate, Q4Tensor)
    assert gate.q.shape == (2, 128, 512)
    assert gate.scale.shape == (2, 2, 512)


# -- int4 under tensor parallelism (VERDICT r2 #7) ---------------------------

def _int4_cfg():
    from k_llms_tpu.models import get_config

    return get_config("tiny").with_(
        hidden_size=256,
        intermediate_size=512,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=64,
        vocab_size=384,
        max_seq_len=128,
    )


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")
def test_int4_on_mesh_bitcompares_single_chip():
    """quantization="int4" survives a data=4 x model=2 mesh (shard_mapped
    w4a16) and produces the single-chip engine's exact tokens/logprobs."""
    from conftest import shared_engine

    cfg = _int4_cfg()
    solo = shared_engine(cfg, param_key=4, quantize="int4")
    tp = shared_engine(cfg, param_key=4, mesh_shape=(4, 2), quantize="int4")
    assert tp.quantized == "int4"  # no silent int8 downgrade any more
    assert tp.params["layers"]["wo"].part == "row"
    assert tp.params["layers"]["wq"].part == "col"

    prompt = [5, 6, 7, 8, 9]
    kw = dict(n=4, max_new_tokens=6, temperature=0.0, seed=3)
    r_solo = solo.generate(prompt, **kw)
    r_tp = tp.generate(prompt, **kw)
    np.testing.assert_array_equal(r_tp.tokens, r_solo.tokens)
    np.testing.assert_allclose(r_tp.logprobs, r_solo.logprobs, rtol=1e-4, atol=1e-4)

    # sampled path too (same seed stream on both engines)
    kw = dict(n=4, max_new_tokens=4, temperature=0.9, seed=17)
    np.testing.assert_array_equal(
        tp.generate(prompt, **kw).tokens, solo.generate(prompt, **kw).tokens
    )


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")
@pytest.mark.parametrize("part", ["col", "row"])
def test_w4_matmul_tp_interpret_matches_dequant_reference(part):
    """The shard_mapped kernel itself (off-TPU the engine takes the XLA
    dequant reference, so the per-shard wiring is pinned here, with the
    interpreter asked for by name): column- and row-parallel layouts on a
    data=4 x model=2 mesh against the dense dequantized product."""
    from k_llms_tpu.ops.w4matmul import unpack_int4, w4_matmul_tp
    from k_llms_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(4, 2)
    w = pack_int4(jax.random.normal(jax.random.key(3), (512, 256), jnp.float32))
    x = jax.random.normal(jax.random.key(4), (8, 512), jnp.float32)
    out = w4_matmul_tp(x, Q4Tensor(w.q, w.scale, part=part, mesh=mesh), interpret=True)
    ref = x @ unpack_int4(w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")
def test_int4_downgrades_when_groups_would_split():
    """tp=4 over a K=256 row-parallel weight would split a quantization group
    (needs K % (128*4) == 0) — the engine must fall back to int8, loudly."""
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.models.quant import int4_mesh_compatible
    from k_llms_tpu.parallel.mesh import make_mesh

    cfg = _int4_cfg()
    assert int4_mesh_compatible(cfg, 2)
    assert not int4_mesh_compatible(cfg, 4)
    eng = LocalEngine(cfg, mesh=make_mesh(2, 4), quantize="int4")
    assert eng.quantized == "int8"


def test_int4_fmt_marker_roundtrip(tmp_path):
    """Checkpoints record the quantized layout explicitly (fmt leaf) instead
    of relying on the scale-shape heuristic (ADVICE r2)."""
    from k_llms_tpu.models import init_params
    from k_llms_tpu.models.loader import load_orbax, save_checkpoint
    from k_llms_tpu.models.quant import QTensor, quantize_params

    # intermediate_size=384: w_down has K=384 (not a 256 multiple), so the
    # tree is a GENUINE int4/int8 mix — both fmt branches get exercised.
    cfg = _int4_cfg().with_(intermediate_size=384)
    qp = quantize_params(init_params(cfg, jax.random.key(1)), bits=4)
    assert isinstance(qp["layers"]["w_down"], QTensor)
    path = str(tmp_path / "ckpt4")
    save_checkpoint(path, qp)
    restored = load_orbax(path)
    assert isinstance(restored["layers"]["w_gate"], Q4Tensor)
    assert isinstance(restored["lm_head"], Q4Tensor)
    assert isinstance(restored["layers"]["w_down"], QTensor)  # fmt=8 branch
    np.testing.assert_array_equal(
        np.asarray(restored["layers"]["w_gate"].q),
        np.asarray(qp["layers"]["w_gate"].q),
    )
    np.testing.assert_array_equal(
        np.asarray(restored["layers"]["w_down"].q),
        np.asarray(qp["layers"]["w_down"].q),
    )


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")
def test_prequantized_checkpoint_layout_survives_mesh_init():
    """A pre-quantized tree keeps its STORED layout through engine init on a
    mesh even when the requested bits differ (quantize_weight_bits documents
    layout preservation): int8 tree + quantize="int4" must not crash on a
    spec-structure mismatch, and generation still works."""
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.models import init_params
    from k_llms_tpu.models.quant import QTensor, quantize_params
    from k_llms_tpu.parallel.mesh import make_mesh

    cfg = _int4_cfg()
    int8_tree = quantize_params(init_params(cfg, jax.random.key(6)), bits=8)
    mesh = make_mesh(4, 2)
    eng = LocalEngine(cfg, params=int8_tree, mesh=mesh, quantize="int4")
    assert isinstance(eng.params["layers"]["w_gate"], QTensor)  # stored layout kept
    r = eng.generate([5, 6, 7], n=4, max_new_tokens=3, temperature=0.5, seed=2)
    assert r.tokens.shape == (4, 3)

    # And the inverse: stored int4 + requested int8 on a COMPATIBLE mesh keeps
    # int4 leaves and marks them for the sharded kernel.
    int4_tree = quantize_params(init_params(cfg, jax.random.key(7)), bits=4)
    eng2 = LocalEngine(cfg, params=int4_tree, mesh=mesh, quantize="int8")
    assert eng2.params["layers"]["w_gate"].part == "col"
    r2 = eng2.generate([5, 6, 7], n=4, max_new_tokens=3, temperature=0.5, seed=2)
    assert r2.tokens.shape == (4, 3)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")
def test_stored_int4_incompatible_mesh_raises_before_pjit():
    """A pre-quantized int4 tree whose groups cannot shard over the model axis
    must fail with the clear ValueError BEFORE the sharded quantize/put (which
    would otherwise die inside pjit with an opaque sharding error)."""
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.models import init_params
    from k_llms_tpu.models.quant import quantize_params
    from k_llms_tpu.parallel.mesh import make_mesh

    cfg = _int4_cfg()  # K=256 row weights: groups split at tp=4
    int4_tree = quantize_params(init_params(cfg, jax.random.key(9)), bits=4)
    with pytest.raises(ValueError, match="re-quantize to int8 or change the mesh"):
        LocalEngine(cfg, params=int4_tree, mesh=make_mesh(2, 4), quantize="int4")
    with pytest.raises(ValueError, match="re-quantize to int8 or change the mesh"):
        LocalEngine(cfg, params=int4_tree, mesh=make_mesh(2, 4), quantize="int8")
