"""Mosaic-compiled kernels against their XLA references, ON THE CHIP.

Not collected by pytest (the suite is pinned to the CPU, where the kernels can
only run in the interpreter): run ``python tests/chip_kernel_check.py`` through
the chip tool. It refuses a CPU. Geometry is qwen2-7b's (28 query heads over 4
kv heads — a 7-row query tile, under one f32 sublane tile — head_dim 128,
64-token pages), the shapes ``chip_smoke.py`` serves; the sliding-window cases
(``--window`` runs them alone) take mistral-7b's (32 over 8).

Each kernel is compared twice: in f32 under ``highest`` matmul precision,
where the interpret-mode CPU differential's tolerance applies unchanged
(tests/test_ops.py, tests/test_paged_attention_kernel.py), and in bf16 — the
served dtype — where kernel and reference round differently (the kernel
accumulates f32 over f32-cast K/V, XLA feeds bf16 to the MXU) and the bound is
bf16's own resolution. On several chips every comparison is repeated under the
data x model meshes the engine builds, the kernel running per shard. The paged
kernel is also given the loop's own shapes: 32 ragged rows (nothing to a full
2,048-token table) over qwen2-7b's whole ``[28, 79936, 4, 128]`` pool.

The last stage is the model-level differential at full width (qwen2-7b, all 28
layers, int8 from the seed; ``--skip-model`` leaves it out, it builds two 8 GB
engines one after the other): four greedy n=8 requests through the continuous
loop with the paged Pallas kernel, again with the paged XLA reference, and
through the dense ``generate``. Then (``TeacherForced``) ``paged_verify_step``
with the kernel and with the XLA reference over one and the same pool, fed each
path's tokens: the kernel's whole-vocabulary logits against the reference's at
every step, and every token a served path emitted, with the logprob it
reported, against the reference's logits at that step. A path's token must be
the reference's wherever the reference's top two logits lie twice the model's
path tolerance (``PATH_TOL``, ``PATH_TOLS``) apart or more, at all 12 steps, whether or not a nearer tie came before.
Prints one line per comparison and exits non-zero if any failed.
``--rehearse`` runs that stage alone at toy size with the interpreted kernel,
on any platform, to debug this script before a chip call.

The sliding window (PR 33): the paged kernel at mistral-7b's heads under a
window of 96 that binds in every row but the shortest (rows of 1 to 1,663
positions, both layouts, against the XLA op given the windowed masks), and the
model stage again on ``mistral-7b`` (32 layers, int8, window 4,096: wider than
the loop's rows, as in the ``mistral-7b.chat`` cell), at the limits qwen2-7b's
stage is held to.

``--sampler`` runs the sampler stage alone (it is also the default run's last
stage): the loop's nucleus search (``ops/sampling.py::nucleus_threshold``)
against the descending sort it replaced in PR 31, at the two published
vocabularies the cells serve — qwen2-7b's ``[32, 152064]`` and
xing4-29b-a4b's ``[32, 131072]`` — on the model's own last-position logits
for 32 distinct contexts, at the cells' temperature 0.8 and top_p 0.95 and
over mixed per-row values. Kept sets are compared as sets; where the two
differ, every token between the two cuts must lie within float32 rounding of
``top_p`` in float64 cumulative mass (the sort sums in sorted order, the
search in vocabulary order). Then the loop's whole ``_sample_rows`` is timed,
new and sort-based, ``SAMPLER_CALLS`` calls chained inside one program so the
host's dispatch is not in the number.

The latent model (``xing4-29b-a4b``: MLA pages, routed experts,
hyper-connections) runs no Pallas kernel of this repo and is not checked here:
its on-chip comparison, the loop's programs at full width against the plain
float32 reference, is ``python benchmark/check_xing4.py``.
"""

import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from test_nucleus import (  # noqa: E402
    ROUNDING, exclusive_mass, sort_reference_threshold, sort_sample_rows,
)
from test_paged_attention_kernel import _build_tables, _reference_masks  # noqa: E402

from k_llms_tpu.ops.attention import attention_xla, flash_attention  # noqa: E402
from k_llms_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention_page_tables,
    paged_decode_attention_pallas,
    paged_decode_attention_xla,
)
from k_llms_tpu.parallel.mesh import make_mesh  # noqa: E402

QH, KVH, D, PS = 28, 4, 128, 64
MISTRAL_HEADS = (32, 8)
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


# The loop's own shapes (benchmark cells: width 32, 2,048-token prompt table,
# 256 generated): 32 rows from nothing to the whole table — idle slots, one
# partial page, chat-short and extract-long rows, lengths on and off page and
# block boundaries — in the cells' pool of 1,249 pages.
RAGGED_PLENS = np.array(
    [0, 2048, 1, 63, 64, 65, 0, 120, 32, 480, 257, 1152, 1924, 1424, 1424, 1424,
     511, 512, 513, 0, 2047, 1025, 96, 200, 300, 0, 1600, 1601, 77, 1999, 640, 8],
    np.int32,
)
RAGGED_WIS = np.array(
    [0, 255, 0, 1, 63, 64, 0, 95, 3, 17, 255, 63, 0, 20, 41, 62,
     1, 0, 2, 0, 130, 64, 96, 7, 128, 0, 191, 192, 5, 254, 33, 250],
    np.int32,
)
RAGGED_POOL_PAGES = 1249


def paged_case(dtype, continuous, mesh, layers=1, layer=0, ragged=False,
               window=None, heads=(QH, KVH)):
    """The kernel reading ``layer`` out of a pool of ``layers`` against the XLA
    op on that layer's slice alone. ``ragged``: the 32-row case above.
    ``window``: a sliding window, in the kernel's walk and in the masks the
    reference is given; ``heads``: (query, kv) heads."""
    QH, KVH = heads
    if ragged:
        plens, wis = RAGGED_PLENS, RAGGED_WIS
    else:
        plens = np.array([1, PS, 400, 1408, 131, 511, 512, 77], np.int32)
        wis = np.array([0, 3, 255, 63, 64, 65, 200, 7], np.int32)
    B, G = len(plens), 256
    prefix_idx, gen_idx, npages = _build_tables(plens, G, PS, continuous=continuous)
    if ragged:
        assert npages <= RAGGED_POOL_PAGES, npages
        npages = RAGGED_POOL_PAGES
    keys = jax.random.split(jax.random.key(int(continuous)), 5)
    pool_shape = (layers, npages * PS, KVH, D)
    pool_k = jax.random.normal(keys[0], pool_shape, jnp.float32).astype(dtype)
    pool_v = jax.random.normal(keys[1], pool_shape, jnp.float32).astype(dtype)
    q = jax.random.normal(keys[2], (B, 1, QH, D), jnp.float32).astype(dtype)
    nk = jax.random.normal(keys[3], (B, 1, KVH, D), jnp.float32).astype(dtype)
    nv = jax.random.normal(keys[4], (B, 1, KVH, D), jnp.float32).astype(dtype)
    key_mask, prefix_mask = map(
        jnp.asarray, _reference_masks(plens, wis, prefix_idx.shape[1], G, window)
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
            page_size=PS, sm_scale=SCALE, window=window, mesh=mesh,
        )

    got = jax.jit(kernel)(
        q, pool_k, pool_v, jnp.int32(layer), pidx, gidx, nk, nv,
        jnp.asarray(plens), jnp.asarray(wis),
    )
    layout = "continuous" if continuous else "coalesced"
    rows = f"ragged {B} rows " if ragged else ""
    if window is not None:
        rows += f"window {window} {QH}q/{KVH}kv "
    report(f"paged decode {rows}{layout} layer {layer} of {layers} "
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


# The model stage's limits. Random weights put the whole 152k vocabulary within
# a few nats (a greedy token's logprob reads about -8, the reference's top two
# logits lie under 0.1 apart at 4 to 10 of a prompt's 12 steps), so two correct
# paths, whose logits differ by rounding alone, need not pick the same token
# across a near tie. Every comparison below is therefore made against the
# reference's own logits, at every step, and a token may differ from the
# reference's only where those logits do not decide.
#
# Kernel against XLA on one pool, |dlogit| over the whole vocabulary and all 12
# steps of a prompt, as read on the v5e (PR 29; bf16 pages, int8 weights, 28
# layers — the kernel-level TOL above does not survive 28 layers, for either
# kernel): this kernel rms 0.0140-0.0144, max 0.069-0.076; the parent's (row,
# page) grid kernel 0.0136-0.0142, 0.072-0.076; with the last prompt page not
# walked rms >= 0.033, max >= 0.20; with the newest pooled token masked — one
# token of 330 on the long prompts, the smallest fault there is — rms >= 0.0175,
# max >= 0.094. The limits lie between the two readings. mistral-7b (PR 33; 32
# layers, 32k vocabulary, window 4,096) is held to the same limits and read
# rms 0.0138-0.0147, max 0.068-0.076.
FORCED_RMS = 0.016
FORCED_MAX = 0.10
# |dlogprob| on one token between two correct paths (the parent's bound; read up
# to 0.045 between a served path and the forced reference, 0.034 between the
# kernel and XLA on one pool). mistral-7b's served paths sit further from the
# forced reference, the kernel or not (PR 33: loop + XLA-paged 0.0511, loop +
# kernel 0.0507, dense generate 0.0437; kernel against XLA on one pool 0.033),
# so its served paths are held to ``PATH_TOLS``' entry.
PATH_TOL = 0.05
PATH_TOLS = {"mistral-7b": 0.07}
GREEDY_NEW = 12
GREEDY_PROMPTS = (
    "[greedy] You are an extraction engine. Read the doc",
    "[greedy] Summarise the following invoice and return the total amount due, "
    "the vendor and the date. " * 3,
    "hello",
    "[greedy] " + "The quick brown fox jumps over the lazy dog. " * 8,
)


class TeacherForced:
    """Both paged implementations of ``paged_verify_step`` over ONE pool, fed a
    given token sequence: the prompts are prefilled once into their own pages
    (continuous layout, the generated tokens continuing the last prompt page),
    one row a prompt in a batch of 8 whose other rows are idle slots, and each
    step's XLA columns are what the pool receives, so at every step the kernel
    and the reference read the same bytes. ``run(tokens)`` returns both paths'
    logits ``[rows, new, V]``: index t is the distribution token t is drawn
    from (t = 0 comes from the prefill and is the same array in both)."""

    def __init__(self, eng, prompts, kernel="pallas", page_size=PS):
        from k_llms_tpu.models.llama import KVCache, paged_verify_step, prefill

        config, self.params = eng.config, eng.params
        self.rows, self.pad, B, G = len(prompts), config.pad_token_id, 8, 256
        plens = np.array([len(ids) for ids in prompts] + [0] * (B - self.rows), np.int32)
        prefix_idx, self.gen_idx, npages = _build_tables(plens, G, page_size, continuous=True)
        S = max(32, 1 << (int(plens.max()) - 1).bit_length())
        prefill_fn = jax.jit(lambda p, t, n: prefill(config, p, t, n))
        pool, first = None, []
        for r, ids in enumerate(prompts):
            tokens = np.full((1, S), self.pad, np.int32)
            tokens[0, :len(ids)] = ids
            logits, cache = prefill_fn(self.params, jnp.asarray(tokens), jnp.int32(len(ids)))
            if pool is None:  # garbage everywhere a prompt does not write
                shape = (cache.k.shape[0], npages * page_size) + cache.k.shape[3:]
                pool = [
                    jax.random.normal(key, shape, jnp.float32).astype(cache.k.dtype)
                    for key in jax.random.split(jax.random.key(7), 2)
                ]
            slots = jnp.asarray(prefix_idx[r, :len(ids)])
            pool = [p.at[:, slots].set(c[:, 0, :len(ids)]) for p, c in zip(pool, cache)]
            first.append(np.asarray(logits[0], np.float32))
        self.pool0, self.first = pool, np.stack(first)
        self.live = np.arange(B) < self.rows
        self.idle = np.arange(B) % page_size  # idle rows write into the trash page

        def step(impl):
            return jax.jit(lambda p, pk, pv, cur, glen: paged_verify_step(
                config, p, cur[:, None], glen, jnp.asarray(plens), KVCache(k=pk, v=pv),
                jnp.asarray(prefix_idx), jnp.asarray(self.gen_idx),
                attn_impl=impl, page_size=page_size,
            ))

        self.steps = {"kernel": step(kernel), "xla": step("xla")}

    def run(self, tokens):
        rows, new = tokens.shape
        logits = {name: [self.first] for name in self.steps}
        pool = self.pool0
        for t in range(new - 1):
            cur = np.full(self.live.shape, self.pad, np.int32)
            cur[:rows] = tokens[:, t]
            glen = np.where(self.live, t, 0).astype(np.int32)
            out = {
                name: fn(self.params, *pool, jnp.asarray(cur), jnp.asarray(glen))
                for name, fn in self.steps.items()
            }
            for name, (step_logits, _, _) in out.items():
                logits[name].append(np.asarray(step_logits[:rows, 0], np.float32))
            write = jnp.asarray(np.where(self.live, self.gen_idx[:, t], self.idle))
            pool = [
                p.at[:, write].set(c.astype(p.dtype)) for p, c in zip(pool, out["xla"][1:])
            ]
        return {name: np.stack(steps, axis=1) for name, steps in logits.items()}


def check(ok, line, failure):
    print(f"{'ok  ' if ok else 'FAIL'} {line}", flush=True)
    if not ok:
        failures.append(failure)


def log_softmax(x):
    x = x - x.max(axis=-1, keepdims=True)
    return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))


def greedy_model_case(model="qwen2-7b", quantize="int8", kernel="pallas", page_size=PS):
    import gc

    served_tol = PATH_TOLS.get(model, PATH_TOL)
    margin = 2 * served_tol  # a greedy token lies less than this under the reference's best

    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    prompts = [
        tok.apply_chat_template([{"role": "user", "content": text}])
        for text in GREEDY_PROMPTS
    ]
    new = GREEDY_NEW
    runs = {}
    for impl in ("xla", "pallas"):  # the kernel's engine last: it stays for the forced passes
        eng = LocalEngine(
            model, quantize=quantize, kv_layout="paged", kv_page_size=page_size,
            paged_attention_impl=impl, use_mesh=False,
        )
        if impl == "pallas":
            runs["dense generate"] = [
                eng.generate(
                    ids, n=8, max_new_tokens=new, temperature=0.0, seed=5,
                    eos_ids=tok.stop_ids,
                )
                for ids in prompts
            ]
        loop = ContinuousDecodeLoop(
            eng, width=32, max_prompt=512, max_new=256, eos_ids=tok.stop_ids
        )
        try:
            runs[f"loop paged {impl}"] = [
                loop.submit(
                    ids, n=8, max_new=new, temperature=0.0, top_p=None, seed=5
                ).result(timeout=900)
                for ids in prompts
            ]
        finally:
            loop.stop()
        # The worker's frame holds the engine until the thread has exited;
        # the next 8 GB engine only fits once this one is really gone.
        if loop._thread is not None:
            loop._thread.join(timeout=30)
        del loop
        if impl == "pallas":
            # Every path's own tokens through both implementations on one
            # pool; paths that emitted the same tokens share a pass.
            forced, eng_pad = TeacherForced(eng, prompts, kernel, page_size), eng.config.pad_token_id
            passes, by_tokens = {}, {}
            for name, results in runs.items():
                tokens = np.stack([np.asarray(r.tokens)[0, :new] for r in results])
                if tokens.tobytes() not in by_tokens:
                    passes[name] = (tokens, forced.run(tokens))
                    by_tokens[tokens.tobytes()] = passes[name][1]["xla"]
            del forced
        del eng
        gc.collect()
    reference = np.stack([np.asarray(r.tokens)[0, :new] for r in runs["dense generate"]])

    # How decisive the reference is: its top two (sampleable) logits, a step.
    for i, ref in enumerate(by_tokens[reference.tobytes()]):
        top2 = np.sort(np.delete(ref, eng_pad, axis=-1), axis=-1)[:, -2:]
        gaps = top2[:, 1] - top2[:, 0]
        print(f"note prompt {i} ({len(prompts[i])} tokens): the reference's top two logits lie "
              f"{gaps.min():.4f} apart at step {gaps.argmin()}, under {margin} at "
              f"{int((gaps < margin).sum())} of {new} steps", flush=True)

    # 1. The kernel against the XLA reference on the same pool, every step of
    # every distinct token sequence: whole-vocabulary logits, and the logprob
    # of the token the path emitted.
    for name, (tokens, logits) in passes.items():
        for i in range(len(prompts)):
            got, ref = logits["kernel"][i], logits["xla"][i]
            diff, steps = np.abs(got - ref), np.arange(new)
            rms = float(np.sqrt((diff ** 2).mean()))
            dlp = float(np.abs(
                log_softmax(got)[steps, tokens[i]] - log_softmax(ref)[steps, tokens[i]]).max())
            swaps = int((got.argmax(-1) != ref.argmax(-1)).sum())
            ok = bool(np.isfinite(got).all() and rms < FORCED_RMS and diff.max() < FORCED_MAX
                      and dlp < PATH_TOL)
            check(ok, f"teacher-forced x{new} along {name}, prompt {i} ({len(prompts[i])} tokens), "
                  f"kernel vs XLA-paged on one pool: |dlogit| rms={rms:.4f} (<{FORCED_RMS}) "
                  f"max={diff.max():.4f} (<{FORCED_MAX}), emitted token's max|dlogprob|={dlp:.4f} "
                  f"(<{PATH_TOL}), argmax differs at {swaps} steps",
                  f"teacher-forced {name} prompt {i}")

    # 2. Each served path against the reference's logits along its OWN tokens,
    # at every step: its rows are identical, each token it emitted lies less
    # than ``margin`` under the reference's best there (so it IS the
    # reference's token wherever the reference decides, before and after any
    # tie), and the logprob it reported is the reference's up to ``served_tol``.
    for name, results in runs.items():
        own = np.stack([np.asarray(r.tokens)[0, :new] for r in results])
        for i, r in enumerate(results):
            tokens, lps = np.asarray(r.tokens)[:, :new], np.asarray(r.logprobs)[:, :new]
            ref = by_tokens[own.tobytes()][i].copy()
            if eng_pad not in tok.stop_ids:
                ref[:, eng_pad] = -np.inf  # as every served path masks it before sampling
            steps = np.arange(new)
            behind = float((ref.max(-1) - ref[steps, tokens[0]]).max())
            err = float(np.abs(lps - log_softmax(ref)[steps, tokens[0]]).max())
            rows_same = bool((tokens == tokens[0]).all())
            left = np.flatnonzero(tokens[0] != reference[i])
            top2 = np.sort(ref, axis=-1)[:, -2:]
            note = "the reference's tokens" if not left.size else (
                f"leaves the reference's tokens at step {left[0]}, where its top two "
                f"logits lie {top2[left[0], 1] - top2[left[0], 0]:.4f} apart")
            ok = rows_same and behind < margin and err < served_tol
            check(ok, f"greedy n=8 x{new} tokens, prompt {i}, {name:<18} rows identical={rows_same}, "
                  f"{note}; furthest under the reference's best={behind:.4f} (<{margin}) "
                  f"max|dlogprob|={err:.4f} (<{served_tol}) tokens={tokens[0].tolist()}",
                  f"greedy {name} prompt {i}")


SAMPLER_CALLS = 20
SAMPLER_CASES = (  # (model, quantize): the cells' two large vocabularies
    ("qwen2-7b", "int8"),
    ("xing4-29b-a4b-cut7", False),
)


def sampler_case(model, quantize, rows=32, context=16):
    """The shared nucleus search against the sort, and the loop's sampler
    timed with each, on ``model``'s own logits (see the module docstring)."""
    import gc

    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.models.llama import forward
    from k_llms_tpu.ops.sampling import nucleus_threshold

    eng = LocalEngine(model, quantize=quantize, use_mesh=False)
    config = eng.config
    V = config.vocab_size
    tokens = jnp.asarray(
        np.random.default_rng(31).integers(0, 256, (rows, context)), jnp.int32)
    logits = jax.jit(lambda p, t: forward(config, p, t, jnp.ones_like(t))[0][:, -1])(
        eng.params, tokens)
    _, sample_rows, mask_pad = ContinuousDecodeLoop(
        eng, width=rows, max_prompt=64, max_new=32)._sampler()
    logits = jax.block_until_ready(mask_pad(logits))
    del eng
    gc.collect()
    host = np.asarray(logits)
    print(f"note {model}: logits [{rows}, {V}] mean {host[np.isfinite(host)].mean():.3f} "
          f"sd {host[np.isfinite(host)].std():.3f} max {host.max():.3f}", flush=True)

    mixes = {
        "T 0.8, top_p 0.95 (the cells')": (np.full(rows, 0.8), np.full(rows, 0.95)),
        "mixed rows": (np.resize([0.8, 1.0, 0.3, 0.0, 1.5], rows),
                       np.resize([0.0, 0.5, 0.9, 0.95, 1.0, 0.1, 0.99], rows)),
    }
    for label, (temps, top_ps) in mixes.items():
        temps, top_ps = np.float32(temps), np.float32(top_ps)
        scaled = jnp.asarray(host) / jnp.maximum(jnp.asarray(temps), 1e-6)[:, None]
        new = np.asarray(jax.jit(nucleus_threshold)(scaled, jnp.asarray(top_ps)))
        old = np.asarray(jax.jit(sort_reference_threshold)(scaled, jnp.asarray(top_ps)))
        scaled = np.asarray(scaled)
        equal = within = 0
        worst, sizes = 0.0, []
        for r in range(rows):
            kept_new, kept_old = scaled[r] >= new[r], scaled[r] >= old[r]
            sizes.append(int(kept_new.sum()))
            if top_ps[r] == 0.0:  # top-1 by the search; the sort keeps nothing at all
                equal += bool((kept_new == (scaled[r] == scaled[r].max())).all())
                continue
            if (kept_new == kept_old).all():
                equal += 1
                continue
            between = kept_new != kept_old
            off = np.abs(exclusive_mass(scaled[r])[between] - top_ps[r]).max()
            worst = max(worst, float(off))
            within += bool(off < ROUNDING)
        check(equal + within == rows,
              f"nucleus search vs sort, {model} [{rows}, {V}], {label}: kept sets equal in "
              f"{equal} rows, {within} more differ only by tokens within {ROUNDING} of top_p in "
              f"float64 mass (furthest {worst:.2e}); kept {min(sizes)}..{max(sizes)} of {V}",
              f"nucleus {model} {label}")

    def chained(sample):
        def run(logits, keys, temps, top_ps):
            # Each call hangs on the one before (through ``c``) and every
            # result is used, so nothing is hoisted out of the loop or dropped.
            def body(_, c):
                tok, lp, _ = sample(logits + c * 1e-30, keys, temps + c * 1e-9, top_ps)
                return (tok.sum() % 2).astype(jnp.float32) + 0.0 * lp.sum()
            return jax.lax.fori_loop(0, SAMPLER_CALLS, body, jnp.float32(0))
        return jax.jit(run)

    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(7), i))(jnp.arange(rows))
    temps, top_ps = (jnp.asarray(np.float32(a)) for a in mixes["T 0.8, top_p 0.95 (the cells')"])
    tok_new = sample_rows(logits, keys, temps, top_ps)[0]
    tok_old = jax.jit(sort_sample_rows)(logits, keys, temps, top_ps)[0]
    same = int((np.asarray(tok_new) == np.asarray(tok_old)).sum())
    times = {}
    for name, fn in (("sort", chained(sort_sample_rows)), ("search", chained(sample_rows))):
        jax.block_until_ready(fn(logits, keys, temps, top_ps))
        reads = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(logits, keys, temps, top_ps))
            reads.append((time.perf_counter() - t0) / SAMPLER_CALLS * 1e3)
        times[name] = sorted(reads)[len(reads) // 2]
    check(same == rows and times["search"] < 2.5,
          f"loop sampler, {model} [{rows}, {V}], T 0.8 top_p 0.95: tokens equal in {same} of "
          f"{rows} rows; ms a call on {jax.devices()[0].device_kind}, {SAMPLER_CALLS} chained in "
          f"one program, median of 5: "
          f"sort {times['sort']:.3f}, search {times['search']:.3f} (budget < 2.5)",
          f"sampler time {model}")


def window_cases(skip_model):
    """The sliding window on one device: the op where the window binds, then
    the model whose window the served rows never reach."""
    for dtype in (jnp.float32, jnp.bfloat16):
        with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
            for continuous in (False, True):
                paged_case(dtype, continuous, None, layers=2, layer=1, window=96,
                           heads=MISTRAL_HEADS)
    if not skip_model:
        greedy_model_case("mistral-7b")


def mesh_name(mesh):
    return "1 device" if mesh is None else f"mesh {dict(mesh.shape)}"


def main():
    device = jax.devices()[0]
    if "--rehearse" in sys.argv[1:]:
        # The model stage's own mechanics at toy size, anywhere: the tiny model
        # in f32, the kernel in the interpreter (the loops run what the
        # platform resolves). Debugs this script without a chip; proves no kernel.
        from k_llms_tpu.models import get_config

        greedy_model_case("tiny", False, "pallas_interpret", 8)
        # A window that binds inside every prompt but "hello"'s.
        greedy_model_case(
            get_config("tiny").with_(sliding_window=24), False, "pallas_interpret", 8)
        sampler_case("tiny", False, rows=4)
        sys.exit(f"rehearsal FAILED: {failures}" if failures else 0)
    if device.platform == "cpu":
        sys.exit("chip_kernel_check: needs the accelerator (the CPU only has the interpreter)")
    n = len(jax.devices())
    print(f"chip_kernel_check: {n} x {device.device_kind} ({device.platform})", flush=True)
    if "--sampler" in sys.argv[1:]:
        for case in SAMPLER_CASES:
            sampler_case(*case)
        sys.exit(f"chip_kernel_check FAILED: {failures}" if failures else 0)
    if "--window" in sys.argv[1:]:
        window_cases(skip_model="--skip-model" in sys.argv[1:])
        sys.exit(f"chip_kernel_check FAILED: {failures}" if failures else 0)
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
                # qwen2-7b's whole pool in bf16 ([28, 79936, 4, 128], 2.3 GB
                # each of K and V); four of its layers in f32 at the same size.
                deep = 28 if dtype == jnp.bfloat16 else 4
                paged_case(dtype, True, mesh, layers=deep, layer=deep - 1, ragged=True)
                flash_case(dtype, mesh)
    window_cases(skip_model=True)
    if "--skip-model" not in sys.argv[1:]:
        greedy_model_case()
        greedy_model_case("mistral-7b")
        for case in SAMPLER_CASES:
            sampler_case(*case)
    if failures:
        sys.exit(f"chip_kernel_check FAILED: {failures}")
    print("chip_kernel_check ok", flush=True)


if __name__ == "__main__":
    main()
