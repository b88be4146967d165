"""Mosaic-compiled kernels against their XLA references, ON THE CHIP.

Not collected by pytest (the suite is pinned to the CPU, where the kernels can
only run in the interpreter): run ``python tests/chip_kernel_check.py`` through
the chip tool. It refuses a CPU. Geometry is qwen2-7b's (28 query heads over 4
kv heads — a 7-row query tile, under one f32 sublane tile — head_dim 128,
64-token pages), the shapes ``chip_smoke.py`` serves.

Each kernel is compared twice: in f32 under ``highest`` matmul precision,
where the interpret-mode CPU differential's tolerance applies unchanged
(tests/test_ops.py, tests/test_paged_attention_kernel.py), and in bf16 — the
served dtype — where kernel and reference round differently (the kernel
accumulates f32 over f32-cast K/V, XLA feeds bf16 to the MXU) and the bound is
bf16's own resolution. On several chips every comparison is repeated under the
data x model meshes the engine builds, the kernel running per shard.

The last stage is the model-level differential at full width (qwen2-7b, all 28
layers, int8 from the seed): a greedy n=8 request through the continuous loop
with the paged Pallas kernel, again with the paged XLA reference, and through
the dense ``generate`` — the three must emit the same tokens in every row
(``--skip-model`` leaves it out; it builds two 8 GB engines one after the
other). Prints one line per comparison and exits non-zero if any failed.

The latent model (``xing4-29b-a4b``: MLA pages, routed experts,
hyper-connections) runs no Pallas kernel of this repo and is not checked here:
its on-chip comparison, the loop's programs at full width against the plain
float32 reference, is ``python benchmark/check_xing4.py``.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from test_paged_attention_kernel import _build_tables  # noqa: E402

from k_llms_tpu.ops.attention import attention_xla, flash_attention  # noqa: E402
from k_llms_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention_page_tables,
    paged_decode_attention_pallas,
    paged_decode_attention_xla,
)
from k_llms_tpu.parallel.mesh import make_mesh  # noqa: E402

QH, KVH, D, PS = 28, 4, 128, 64
SCALE = 1.0 / math.sqrt(D)
# (rtol, atol): f32 from the CPU differentials; bf16 = a few ulps of an O(1) value.
TOL = {jnp.float32: (2e-5, 2e-5), jnp.bfloat16: (2e-2, 2e-2)}
failures = []


def report(name, got, ref, dtype):
    rtol, atol = TOL[dtype]
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    err = float(np.max(np.abs(got - ref)))
    ok = bool(np.isfinite(got).all() and np.allclose(got, ref, rtol=rtol, atol=atol))
    print(f"{'ok  ' if ok else 'FAIL'} {name:<58} max|diff|={err:.2e} "
          f"(rtol={rtol:g}, atol={atol:g})", flush=True)
    if not ok:
        failures.append(name)


def paged_case(dtype, continuous, mesh, layers=1, layer=0):
    """The kernel reading ``layer`` out of a pool of ``layers`` against the XLA
    op on that layer's slice alone."""
    plens = np.array([1, PS, 400, 1408, 131, 511, 512, 77], np.int32)
    B, G = len(plens), 256
    wis = np.array([0, 3, G - 1, 63, 64, 65, 200, 7], np.int32)
    prefix_idx, gen_idx, npages = _build_tables(plens, G, PS, continuous=continuous)
    keys = jax.random.split(jax.random.key(int(continuous)), 5)
    pool_shape = (layers, npages * PS, KVH, D)
    pool_k = jax.random.normal(keys[0], pool_shape, jnp.float32).astype(dtype)
    pool_v = jax.random.normal(keys[1], pool_shape, jnp.float32).astype(dtype)
    q = jax.random.normal(keys[2], (B, 1, QH, D), jnp.float32).astype(dtype)
    nk = jax.random.normal(keys[3], (B, 1, KVH, D), jnp.float32).astype(dtype)
    nv = jax.random.normal(keys[4], (B, 1, KVH, D), jnp.float32).astype(dtype)
    key_mask = jnp.asarray(np.arange(G)[None, None, :] <= wis[:, None, None])
    prefix_mask = jnp.asarray(
        np.arange(prefix_idx.shape[1])[None, None, :] < plens[:, None, None]
    )
    pidx, gidx = jnp.asarray(prefix_idx), jnp.asarray(gen_idx)
    ref = jax.jit(
        lambda *a: paged_decode_attention_xla(*a, sm_scale=SCALE)
    )(q, pool_k[layer][None], pool_v[layer][None], jnp.int32(0), pidx, gidx, nk, nv,
      jnp.asarray(wis), key_mask, prefix_mask)

    def kernel(q, pool_k, pool_v, layer, pidx, gidx, nk, nv, plens, wis):
        tables = paged_attention_page_tables(pidx, gidx, PS)
        return paged_decode_attention_pallas(
            q[:, 0], pool_k, pool_v, layer, *tables, nk[:, 0], nv[:, 0], plens, wis,
            page_size=PS, sm_scale=SCALE, mesh=mesh,
        )

    got = jax.jit(kernel)(
        q, pool_k, pool_v, jnp.int32(layer), pidx, gidx, nk, nv,
        jnp.asarray(plens), jnp.asarray(wis),
    )
    layout = "continuous" if continuous else "coalesced"
    report(f"paged decode {layout} layer {layer} of {layers} "
           f"{jnp.dtype(dtype).name} {mesh_name(mesh)}", got, ref[:, 0], dtype)


def flash_case(dtype, mesh):
    keys = jax.random.split(jax.random.key(7), 3)
    S = 2048
    q = jax.random.normal(keys[0], (1, QH, S, D), jnp.float32).astype(dtype)
    k = jax.random.normal(keys[1], (1, KVH, S, D), jnp.float32).astype(dtype)
    v = jax.random.normal(keys[2], (1, KVH, S, D), jnp.float32).astype(dtype)
    lens = jnp.array([1408], jnp.int32)
    mask = (jnp.arange(S)[None, :] < lens[:, None]).astype(jnp.int32)
    ref = jax.jit(lambda q, k, v: attention_xla(
        q, k, v, causal=True, key_mask=mask, sm_scale=SCALE))(q, k, v)
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, key_lengths=lens, sm_scale=SCALE, mesh=mesh))(q, k, v)
    # Rows past the prompt have no defined output (the engine never reads them).
    report(f"flash prefill S={S} len=1408 {jnp.dtype(dtype).name} {mesh_name(mesh)}",
           got[:, :, :1408], ref[:, :, :1408], dtype)

    # Continuation (chunked prefill): 128 queries at offset 256 over 512 keys.
    Sq, Sk, off = 128, 512, 256
    qc, kc, vc = q[:, :, :Sq], k[:, :, :Sk], v[:, :, :Sk]
    rows = off + jnp.arange(Sq)[:, None]
    causal_abs = jnp.arange(Sk)[None, :] <= rows  # [Sq, Sk]
    scores = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qc.reshape(1, KVH, QH // KVH, Sq, D), kc,
        preferred_element_type=jnp.float32,
    ) * SCALE
    scores = jnp.where(causal_abs[None, None, None], scores, jnp.finfo(jnp.float32).min)
    ref = jnp.einsum(
        "bhgqk,bhkd->bhgqd", jax.nn.softmax(scores, axis=-1), vc.astype(jnp.float32)
    ).reshape(1, QH, Sq, D)
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, sm_scale=SCALE, q_offset=jnp.int32(off), mesh=mesh))(qc, kc, vc)
    report(f"flash continuation Sq={Sq} Sk={Sk} {jnp.dtype(dtype).name} {mesh_name(mesh)}",
           got, ref, dtype)


def greedy_model_case():
    import gc

    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    ids = tok.apply_chat_template(
        [{"role": "user", "content": "[greedy] You are an extraction engine. Read the doc"}]
    )
    runs = {}
    for impl in ("pallas", "xla"):
        eng = LocalEngine(
            "qwen2-7b", quantize="int8", kv_layout="paged",
            paged_attention_impl=impl, use_mesh=False,
        )
        if impl == "pallas":
            dense = eng.generate(
                ids, n=8, max_new_tokens=12, temperature=0.0, seed=5, eos_ids=tok.stop_ids
            )
            runs["dense generate"] = (dense.tokens, dense.logprobs)
        loop = ContinuousDecodeLoop(
            eng, width=32, max_prompt=512, max_new=256, eos_ids=tok.stop_ids
        )
        try:
            r = loop.submit(
                ids, n=8, max_new=12, temperature=0.0, top_p=None, seed=5
            ).result(timeout=900)
        finally:
            loop.stop()
        runs[f"loop paged {impl}"] = (r.tokens, r.logprobs)
        # The worker's frame holds the engine until the thread has exited;
        # the next 8 GB engine only fits once this one is really gone.
        if loop._thread is not None:
            loop._thread.join(timeout=30)
        del loop, eng, r
        gc.collect()
    ref_tokens, ref_lps = runs["dense generate"]
    for name, (tokens, lps) in runs.items():
        same = bool((tokens == ref_tokens[0]).all())
        err = float(np.max(np.abs(lps - ref_lps)))
        ok = same and err < 0.05
        print(f"{'ok  ' if ok else 'FAIL'} greedy n=8 x12 tokens, {name:<18} rows and paths "
              f"identical={same} max|dlogprob|={err:.4f} tokens={tokens[0].tolist()}", flush=True)
        if not ok:
            failures.append(f"greedy {name}")


def mesh_name(mesh):
    return "1 device" if mesh is None else f"mesh {dict(mesh.shape)}"


def main():
    device = jax.devices()[0]
    if device.platform == "cpu":
        sys.exit("chip_kernel_check: needs the accelerator (the CPU only has the interpreter)")
    n = len(jax.devices())
    print(f"chip_kernel_check: {n} x {device.device_kind} ({device.platform})", flush=True)
    meshes = [None]
    if n >= 4:
        meshes += [make_mesh(4, 1), make_mesh(2, 2)]
    elif n >= 2:
        meshes += [make_mesh(1, 2)]
    for mesh in meshes:
        for dtype in (jnp.float32, jnp.bfloat16):
            # "highest" makes XLA's f32 reference a true f32 matmul on the MXU.
            with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
                for continuous in (False, True):
                    paged_case(dtype, continuous, mesh)
                paged_case(dtype, True, mesh, layers=2, layer=1)
                flash_case(dtype, mesh)
    if "--skip-model" not in sys.argv[1:]:
        greedy_model_case()
    if failures:
        sys.exit(f"chip_kernel_check FAILED: {failures}")
    print("chip_kernel_check ok", flush=True)


if __name__ == "__main__":
    main()
