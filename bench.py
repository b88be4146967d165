"""Headline benchmark (driver-run, real TPU).

Measures the BASELINE.md target on the FLAGSHIP configuration: Llama-3-8B
shape (synthetic int8 weights — no 8B checkpoint asset ships with this repo),
n=32 consensus p50 latency vs single-sample p50, end-to-end through the public
``KLLMs(backend="tpu")`` client (batched decode + on-device embeddings +
host-side consensus). Also reported, so the numbers are auditable rather than
self-referential:

- decode tokens/sec/chip plus the HBM bytes streamed per decode step and the
  implied bandwidth utilization (decode is HBM-bound; v5e peak is 819 GB/s);
- consensus QUALITY on the scripted noise model (field accuracy of consensus
  vs single sample, the reference's ~0.85 quality bar, README_TESTS.md:212);
- concurrent-request throughput: 5 concurrent clients vs serial (the
  reference's 5-worker baseline, README_TESTS.md:214) via the coalescing
  scheduler.

Prints ONE JSON line:
  metric = n32_consensus_p50_over_single_p50 (lower is better, target < 2.0)
  vs_baseline = 2.0 / value  (>1.0 means the BASELINE.md <2x target is beaten)

One process does everything: a chip belongs to the process that first touches
JAX, so nothing here starts a child that needs a device. The hermetic sections
run on whatever backend the process has; the chip section (flagship,
concurrency, speculative, prefix cache) refuses a CPU before it builds a
model. Any section that raises is reported in the JSON and makes the exit
code non-zero.
"""

import json
import statistics
import sys
import threading
import time

import jax

RUNS = 3
MAX_NEW = 64
N_CONSENSUS = 32
FLAGSHIP = "llama-3-8b"
# Peak HBM bandwidth per chip in GB/s, keyed by jax's device_kind. Source:
# Google Cloud documentation, "TPU v5e" (819 GB/s). A device that is not in
# the table is an error, not a default.
PEAK_HBM_GBS = {"TPU v5 lite": 819.0}

MESSAGES = [
    {
        "role": "user",
        "content": (
            "Extract the invoice fields from this document: ACME Corp, "
            "invoice number INV-2024-00417, issued March 3rd, total due "
            "$4,310.55, payment terms net 30, contact billing@acme.example."
        ),
    }
]


def _tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def _decode_hbm_bytes_per_step(engine, n: int, prompt_len: int, max_new: int) -> int:
    """Bytes a decode step streams from HBM: every non-embedding weight once
    (the embedding table is only gathered for n rows), plus the FULL padded KV
    buckets — the XLA attention reads the whole prefix bucket and the whole
    generated-cache buffer every step, masked positions included."""
    from k_llms_tpu.engine.engine import _bucket

    params = engine.params
    weight_bytes = _tree_bytes(params) - params["embed"].nbytes
    cfg = engine.config
    kv_elem = 2 * 2  # k and v, bf16
    prefix_bucket = min(_bucket(prompt_len, minimum=32), cfg.max_seq_len)
    prefix_bytes = (
        cfg.num_layers * prefix_bucket * cfg.num_kv_heads * cfg.head_dim * kv_elem
    )
    gen_bytes = cfg.num_layers * n * max_new * cfg.num_kv_heads * cfg.head_dim * kv_elem
    return int(weight_bytes + prefix_bytes + gen_bytes)


def require_chip():
    """The first device, which must be an accelerator: a chip-section number
    from a CPU would be written under a device metric's name."""
    device = jax.devices()[0]
    if device.platform == "cpu":
        raise RuntimeError(
            "bench.py's chip section needs an accelerator; JAX found only "
            f"{device.platform}:{device.device_kind}"
        )
    return device


def peak_hbm_gbs(device) -> float:
    try:
        return PEAK_HBM_GBS[device.device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published HBM peak for device_kind {device.device_kind!r}; "
            f"add it to PEAK_HBM_GBS with its source (known: {sorted(PEAK_HBM_GBS)})"
        ) from None


def bench_flagship() -> "tuple[dict, object, object]":
    """Returns (metrics dict, backend, client) — the backend/client are reused
    by the concurrency section so the 8B engine initializes once."""
    from k_llms_tpu import KLLMs
    from k_llms_tpu.backends.tpu import TpuBackend

    device = require_chip()
    peak_gbs = peak_hbm_gbs(device)  # before the model: an unknown chip fails fast

    # int8 weight-only quantization is the flagship serving config: decode is
    # HBM-bandwidth bound, int8 halves the streamed bytes, and 8B-class
    # weights (~8.6 GB with bf16 embeddings) fit one 16 GB v5e chip beside
    # the n=32 KV cache.
    backend = TpuBackend(model=FLAGSHIP, max_new_tokens=MAX_NEW, quantization="int8")
    client = KLLMs(backend=backend, model=FLAGSHIP)

    def run(n: int) -> float:
        t0 = time.perf_counter()
        client.chat.completions.create(
            messages=MESSAGES, model=FLAGSHIP, n=n, temperature=0.8, top_p=0.95, seed=1234
        )
        return time.perf_counter() - t0

    # Warmup / compile both programs.
    run(1)
    run(N_CONSENSUS)

    single = [run(1) for _ in range(RUNS)]
    consensus = [run(N_CONSENSUS) for _ in range(RUNS)]
    p50_single = statistics.median(single)
    p50_consensus = statistics.median(consensus)
    ratio = p50_consensus / p50_single

    # Engine-level decode throughput and HBM accounting. Prefill and fixed
    # dispatch overhead are removed by differencing two decode lengths.
    tok = backend.tokenizer
    ids = tok.apply_chat_template(MESSAGES, add_generation_prompt=True)

    def engine_time(max_new: int, seed: int) -> float:
        t0 = time.perf_counter()
        backend.engine.generate(
            ids, n=N_CONSENSUS, max_new_tokens=max_new, temperature=0.8, seed=seed
        )
        return time.perf_counter() - t0

    engine_time(8, seed=0)  # warm both decode-loop compiles
    engine_time(MAX_NEW, seed=0)
    # Median of several differenced pairs: a single host hiccup in one run
    # must not leak an absurd step time into the headline numbers.
    diffs = [
        engine_time(MAX_NEW, seed=7 + i) - engine_time(8, seed=7 + i)
        for i in range(3)
    ]
    step_s = statistics.median(diffs) / (MAX_NEW - 8)
    if step_s <= 0:
        raise RuntimeError(f"non-positive decode step time from diffs {diffs}")
    tokens_per_sec_chip = N_CONSENSUS / step_s / max(1, len(jax.devices()))

    prompt_len = len(ids)
    bytes_per_step = _decode_hbm_bytes_per_step(
        backend.engine, N_CONSENSUS, prompt_len, MAX_NEW
    )
    bandwidth_util = bytes_per_step / step_s / (peak_gbs * 1e9)

    return {
        "model": FLAGSHIP,
        "quantization": "int8",
        "device": str(device),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "peak_hbm_gbs": peak_gbs,
        "params_bytes": int(_tree_bytes(backend.engine.params)),
        "p50_single_s": round(p50_single, 4),
        "p50_n32_consensus_s": round(p50_consensus, 4),
        "ratio": round(ratio, 4),
        "decode_step_ms": round(step_s * 1000, 3),
        "decode_tokens_per_sec_chip": round(tokens_per_sec_chip, 1),
        "hbm_bytes_per_step": bytes_per_step,
        "hbm_bandwidth_util": round(bandwidth_util, 4),
        "prompt_tokens": prompt_len,
        "max_new_tokens": MAX_NEW,
        "runs": RUNS,
    }, backend, client


def bench_concurrency(backend, client) -> dict:
    """5 concurrent clients vs the same 5 requests serial, n=4 each — the
    coalescing scheduler should fuse the concurrent decodes."""
    N_REQ, N_PER = 5, 4
    prompts = [f"Summarize item {i}: " + MESSAGES[0]["content"] for i in range(N_REQ)]

    def one(i: int):
        return client.chat.completions.create(
            messages=[{"role": "user", "content": prompts[i]}],
            model=FLAGSHIP,
            n=N_PER,
            temperature=0.8,
            seed=500 + i,
        )

    # Warm every program shape a 5-request race can hit: the solo decode and
    # each power-of-two coalesced group size (opportunistic coalescing makes
    # the group composition timing-dependent; generate_many buckets R to
    # powers of two precisely so this warm set is exhaustive).
    from k_llms_tpu.engine.engine import GenRequestSpec

    one(0)
    tok = backend.tokenizer
    warm_ids = tok.apply_chat_template(
        [{"role": "user", "content": prompts[0]}], add_generation_prompt=True
    )
    for r in (2, 4, 8):
        backend.engine.generate_many(
            [GenRequestSpec(warm_ids, N_PER, i) for i in range(r)],
            max_new_tokens=backend.default_max_new_tokens,
            temperature=0.8,
            eos_ids=tok.stop_ids,
        )

    def timed_serial() -> float:
        t0 = time.perf_counter()
        for i in range(N_REQ):
            one(i)
        return time.perf_counter() - t0

    def timed_concurrent() -> float:
        threads = [threading.Thread(target=one, args=(i,)) for i in range(N_REQ)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    # Two rounds each, best-of (first concurrent round can still catch a
    # straggler composition).
    serial_s = min(timed_serial() for _ in range(2))
    concurrent_s = min(timed_concurrent() for _ in range(2))

    return {
        "requests": N_REQ,
        "n_per_request": N_PER,
        "serial_s": round(serial_s, 4),
        "concurrent_s": round(concurrent_s, 4),
        "speedup": round(serial_s / concurrent_s, 3),
        "scheduler": {
            k: v for k, v in backend.scheduler.stats.items() if k in ("batches", "coalesced")
        },
    }


def bench_speculative(backend) -> dict:
    """Prompt-copying extraction workload under prompt-lookup speculation —
    the canonical spec-decode win (the continuation copies spans of the
    prompt, so trailing-bigram drafts verify). The spec engine SHARES the
    flagship's initialized int8 params (no second 8B init); spec-off runs the
    plain flagship engine on the identical request."""
    from k_llms_tpu.engine.engine import LocalEngine

    eng_off = backend.engine
    eng_on = LocalEngine(
        eng_off.config, params=eng_off.params, mesh=eng_off.mesh,
        quantize="int8", speculative="prompt_lookup", spec_lookahead=4,
    )
    # Extraction shape: instruction head + a long literal field run the
    # answer must copy. Greedy + logit_bias pins the continuation to the run
    # so the measured acceptance is the workload's, not sampling noise.
    prompt = list(b"Copy the serial field exactly: serial=") + [120] * 96
    kw = dict(
        n=1, max_new_tokens=MAX_NEW, temperature=0.0, seed=3,
        logit_bias={120: 100.0},
    )

    def timed(eng, seed: int) -> float:
        t0 = time.perf_counter()
        eng.generate(prompt, **{**kw, "seed": seed})
        return time.perf_counter() - t0

    timed(eng_on, 0)  # compile
    timed(eng_off, 0)
    p50_on = statistics.median(timed(eng_on, 7 + i) for i in range(RUNS))
    p50_off = statistics.median(timed(eng_off, 7 + i) for i in range(RUNS))
    stats = dict(eng_on.spec_stats)
    return {
        "workload": "prompt-copy extraction (96-token literal run)",
        "prompt_tokens": len(prompt),
        "max_new_tokens": MAX_NEW,
        "spec_lookahead": 4,
        "tokens_per_iteration": stats.get("tokens_per_iteration"),
        "verify_iterations": stats.get("verify_iterations"),
        "drafted": stats.get("drafted"),
        "accepted": stats.get("accepted"),
        "p50_spec_on_s": round(p50_on, 4),
        "p50_spec_off_s": round(p50_off, 4),
        "speedup": round(p50_off / p50_on, 3),
        "runs": RUNS,
    }


def bench_prefix_cache(backend) -> dict:
    """Repeated growing-prompt requests through the prefix cache (the
    multi-turn / shared-system-prompt serving pattern): one miss, then
    suffix-only continuations, then exact-hit repeats. Decode work is
    identical on both engines, so the latency delta IS the prefill time
    saved. An sp_decode long-prompt variant runs when the mesh has a data
    axis to shard over."""
    from k_llms_tpu.engine.engine import LocalEngine

    eng_plain = backend.engine
    cfg = eng_plain.config
    eng_cache = LocalEngine(
        cfg, params=eng_plain.params, mesh=eng_plain.mesh, quantize="int8",
        prefix_cache_size=8, prefix_cache_min_reuse=16,
    )
    base = list(b"System: extract fields faithfully. Document: ")
    grow = [list(b" invoice total $4,310.55 net 30 terms, item %d." % i) for i in range(5)]
    chain = [base]
    for g in grow:
        chain.append(chain[-1] + g)
    requests = chain + [chain[-1]] * 2  # growing chain, then exact repeats
    kw = dict(n=1, max_new_tokens=8, temperature=0.0, seed=5)

    def run_all(eng) -> float:
        t0 = time.perf_counter()
        for p in requests:
            eng.generate(p, **kw)
        return time.perf_counter() - t0

    run_all(eng_cache)  # compile every shape (miss + continuation + hit)
    run_all(eng_plain)
    eng_cache._prefix_entries.clear()
    eng_cache.prefix_cache_stats = {"hits": 0, "partial_hits": 0, "misses": 0}
    p50_cached = statistics.median(
        # Cold cache each round so every pass pays exactly one miss.
        (eng_cache._prefix_entries.clear() or run_all(eng_cache))
        for _ in range(RUNS)
    )
    p50_plain = statistics.median(run_all(eng_plain) for _ in range(RUNS))
    stats = dict(eng_cache.prefix_cache_stats)

    result = {
        "workload": f"growing chain x{len(chain)} + 2 exact repeats",
        "prompt_tokens_final": len(chain[-1]),
        "p50_cached_s": round(p50_cached, 4),
        "p50_plain_s": round(p50_plain, 4),
        "prefill_saved_s": round(p50_plain - p50_cached, 4),
        "speedup": round(p50_plain / p50_cached, 3),
        "cache_stats_total": stats,
        "runs": RUNS,
    }

    mesh = eng_plain.mesh
    if mesh is not None and mesh.shape.get("data", 1) > 1:
        eng_sp = LocalEngine(
            cfg, params=eng_plain.params, mesh=mesh, quantize="int8",
            sp_prefill_min_tokens=256, sp_decode=True,
            prefix_cache_size=4, prefix_cache_min_reuse=64,
        )
        ring = mesh.shape["data"]
        long_prompt = (list(b"Summarize: ") + list(range(32, 96)) * 8)[: 512 // ring * ring]
        sp_kw = dict(n=1, max_new_tokens=8, temperature=0.0, seed=9)
        eng_sp.generate(long_prompt, **sp_kw)  # compile + miss
        t0 = time.perf_counter()
        for _ in range(RUNS):
            eng_sp.generate(long_prompt, **sp_kw)  # exact hits, ring decode
        result["sp_decode_long"] = {
            "prompt_tokens": len(long_prompt),
            "p50_exact_hit_s": round((time.perf_counter() - t0) / RUNS, 4),
            "cache_stats": dict(eng_sp.prefix_cache_stats),
        }
    else:
        result["sp_decode_long"] = {
            "skipped": "mesh data axis <= 1: no sequence axis to shard over"
        }
    return result


def bench_quality() -> dict:
    """Host-side consensus quality on the scripted noise model (hermetic —
    needs no device).

    ``default`` is the DEFAULT settings path (VERDICT r3 #3: alignment
    refinement + canonical spelling resolve ON by default — monotone in n and
    above the 0.85 bar at the headline n=32); ``reference_exact`` runs the
    bit-identical-to-reference escape hatch for contrast — it shows the high-n
    row-drop the default posture fixes. Both run n in {8,16,32} over 3
    distinct truth documents (VERDICT r2 #3)."""
    from k_llms_tpu.consensus.settings import ConsensusSettings
    from k_llms_tpu.utils.quality import consensus_quality_eval

    return {
        "default": consensus_quality_eval(n_values=(8, 16, 32), trials=12),
        "reference_exact": consensus_quality_eval(
            n_values=(8, 16, 32), trials=12,
            consensus_settings=ConsensusSettings(reference_exact=True),
        ),
    }


def bench_paged_kv() -> dict:
    """Paged-vs-dense KV capacity at equal HBM budget, plus a live page-pool
    run (hermetic — static accounting needs no device at all, the pool run
    uses the tiny model).

    Headline: how many decode rows fit in the flagship chip's post-params HBM
    under each layout for the n=32 shared-prompt extraction workload. The
    dense layout charges every row the full prompt+max_new KV; the paged
    layout charges each row its private generation reserve plus 1/n of the
    shared prompt pages (``HbmMemoryModel.paged_max_rows``), so width scales
    ~n x on the prompt-dominated shapes. Uses the real 8B int8 param
    footprint via ``jax.eval_shape`` (no weights materialize). The pool run
    decodes an actual n=32 fan-out through the paged continuous loop and
    reports the allocator's own stats — pages in use vs the dense-equivalent
    page count, shared pages, copy-on-write copies — with conservation
    checked by the loop's stats property."""
    import numpy as np

    from k_llms_tpu.backends.tpu import BackendConfig, HbmMemoryModel
    from k_llms_tpu.engine.paging import pages_for
    from k_llms_tpu.models import get_config
    from k_llms_tpu.models.quant import init_params_quantized

    cfg = get_config(FLAGSHIP)
    shapes = jax.eval_shape(
        lambda key: init_params_quantized(cfg, key, bits=8),
        jax.ShapeDtypeStruct((2,), np.uint32),
    )
    param_bytes = sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(shapes)
    )
    ps = BackendConfig.model_fields["kv_page_size"].default
    mm = HbmMemoryModel(cfg, param_bytes=param_bytes, hbm_bytes=16 << 30)

    def shape_row(prompt_len: int) -> dict:
        dense = mm.max_rows(prompt_len + MAX_NEW)
        paged = mm.paged_max_rows(prompt_len, MAX_NEW, ps, fanout=N_CONSENSUS)
        return {
            "prompt_len": prompt_len,
            "max_new": MAX_NEW,
            "dense_max_rows": dense,
            "paged_max_rows": paged,
            "width_ratio_x": round(paged / max(1, dense), 2),
        }

    accounting = {
        "model": FLAGSHIP,
        "quantization": "int8",
        "param_bytes": param_bytes,
        "kv_bytes_per_token": mm.kv_bytes_per_token,
        "budget_bytes": mm.budget_bytes(),
        "page_size": ps,
        "fanout": N_CONSENSUS,
        # The repeated-extraction workload (one ~1.4k-token instruction
        # prompt, many documents) is the headline shared-prompt shape; the
        # 200-token flagship prompt is reported for contrast — short prompts
        # are reserve-dominated and amortize less.
        "extraction_1408": shape_row(1408),
        "flagship_200": shape_row(200),
    }

    # Live pool: n=32 greedy fan-out through the paged continuous loop.
    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.models import get_config as _gc
    from k_llms_tpu.models.llama import init_params

    tiny = _gc("tiny")
    engine = LocalEngine(
        tiny,
        params=init_params(tiny, jax.random.PRNGKey(0)),
        use_mesh=False,
        kv_layout="paged",
        kv_page_size=8,
    )
    # 37 tokens = 4 full pages + a partial one, so every row's first
    # generated token lands in the shared partial page and the n-1 losers
    # copy-on-write — the bench exercises (and reports) the CoW path.
    prompt = [(i * 31) % 150 + 3 for i in range(37)]
    max_new = 8
    loop = ContinuousDecodeLoop(engine, width=32, max_prompt=64, max_new=max_new)
    try:
        t0 = time.perf_counter()
        loop.submit(
            prompt, n=32, max_new=max_new, temperature=0.0, top_p=None, seed=11
        ).result(timeout=600)
        elapsed = time.perf_counter() - t0
        snap = dict(loop.stats["pages"])  # runs PageAllocator.verify()
    finally:
        loop.stop()
    dense_equiv = 32 * pages_for(len(prompt) + max_new, 8)
    snap["dense_equivalent_pages"] = dense_equiv
    snap["peak_page_savings_x"] = round(dense_equiv / max(1, snap["peak_in_use"]), 2)
    return {
        "accounting": accounting,
        "pool_run": {
            "n": 32, "prompt_len": len(prompt), "max_new": max_new,
            "page_size": 8, "elapsed_s": round(elapsed, 2), **snap,
        },
    }


def bench_paged_attention() -> dict:
    """Fused paged decode attention vs the materializing step it replaced
    (hermetic, CPU-safe).

    Timed: one paged decode-attention step at n in {8, 32} on the tiny head
    geometry — ``paged_decode_attention_xla`` (the fused op: gather feeds the
    scores directly, this step's fresh column folded in without a pool
    round-trip) vs the PR 7 movement (gather to a dense copy, dense attention
    over the copy, then ``take_along_axis`` to re-extract the written column
    for the pool scatter). p50 over repeated jitted calls.

    Static: the per-step gather traffic BOTH XLA paths materialize — and the
    Pallas kernel's BlockSpec indirection reads in place instead — at the
    real int8 8B footprint, via ``jax.eval_shape`` only (no weights, no
    device): the repeated-extraction prompt shape (1408 tokens, shared by
    each request's fan-out) plus per-row gen slots, every layer, per decode
    step."""
    import functools

    import jax.numpy as jnp
    import numpy as np

    from k_llms_tpu.models import get_config
    from k_llms_tpu.models.llama import (
        _gqa_scores,
        _gqa_scores_shared,
        _gqa_values,
        _gqa_values_shared,
    )
    from k_llms_tpu.ops.attention import gather_kv_pages
    from k_llms_tpu.ops.paged_attention import paged_decode_attention_xla

    cfg = get_config("tiny")
    D, QH, KVH = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    sm_scale = 1.0 / float(np.sqrt(D))
    ps, P, G = 8, 64, 32
    rng = np.random.default_rng(17)

    def materializing(
        q, pool_k, pool_v, layer, prefix_idx, gen_idx, new_k, new_v, write_index,
        key_mask, prefix_mask,
    ):
        # The PR 7 step, operation for operation: gather both regions to a
        # dense copy, run the dense attention over the copy, re-extract the
        # written column from the copy for the pool scatter.
        pk, pv = gather_kv_pages(pool_k, pool_v, prefix_idx, layer)
        gk, gv = gather_kv_pages(pool_k, pool_v, gen_idx, layer)
        row_update = jax.vmap(
            lambda c, kk, off: jax.lax.dynamic_update_slice_in_dim(
                c, kk, off, axis=0
            )
        )
        gk = row_update(gk, new_k, write_index)
        gv = row_update(gv, new_v, write_index)
        neg = jnp.finfo(jnp.float32).min
        scores = jnp.where(
            key_mask[:, None, :, :], _gqa_scores(q, gk) * sm_scale, neg
        )
        p_scores = jnp.where(
            prefix_mask[:, None, :, :], _gqa_scores_shared(q, pk) * sm_scale, neg
        )
        w = jax.nn.softmax(jnp.concatenate([p_scores, scores], axis=-1), axis=-1)
        out = _gqa_values_shared(w[..., :P], pv) + _gqa_values(w[..., P:], gv)
        idx = write_index[:, None, None, None]
        return (
            out,
            jnp.take_along_axis(gk, idx, axis=1)[:, 0],
            jnp.take_along_axis(gv, idx, axis=1)[:, 0],
        )

    def timed_row(n: int) -> dict:
        B = n
        npages = P // ps + B * (G // ps) + 1
        flat = npages * ps
        pool_k = jnp.asarray(rng.standard_normal((1, flat, KVH, D)), jnp.float32)
        pool_v = jnp.asarray(rng.standard_normal((1, flat, KVH, D)), jnp.float32)
        # One request, n rows sharing its prefix (the consensus fan-out
        # shape): request-level [1, P] prefix table, per-row gen slots.
        prefix_idx = jnp.asarray(
            (np.arange(P) + ps)[None, :], jnp.int32
        )
        gen_pages = (P // ps + 1) + np.arange(B * (G // ps)).reshape(B, G // ps)
        gen_idx = jnp.asarray(
            (gen_pages[:, np.repeat(np.arange(G // ps), ps)] * ps
             + np.tile(np.arange(ps), G // ps)[None, :]),
            jnp.int32,
        )
        q = jnp.asarray(rng.standard_normal((B, 1, QH, D)), jnp.float32)
        new_k = jnp.asarray(rng.standard_normal((B, 1, KVH, D)), jnp.float32)
        new_v = jnp.asarray(rng.standard_normal((B, 1, KVH, D)), jnp.float32)
        glen, plen = G // 2, P - 3
        write_index = jnp.full((B,), glen, jnp.int32)
        key_mask = jnp.broadcast_to(jnp.arange(G) <= glen, (B, 1, G))
        prefix_mask = jnp.broadcast_to(jnp.arange(P) < plen, (B, 1, P))
        args = (
            q, pool_k, pool_v, jnp.int32(0), prefix_idx, gen_idx, new_k, new_v,
            write_index, key_mask, prefix_mask,
        )
        fused = jax.jit(
            functools.partial(paged_decode_attention_xla, sm_scale=sm_scale)
        )
        mat = jax.jit(materializing)

        def p50(fn) -> float:
            jax.block_until_ready(fn(*args))  # compile
            samples = []
            for _ in range(30):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                samples.append(time.perf_counter() - t0)
            return statistics.median(samples)

        f, m = p50(fused), p50(mat)
        return {
            "n": n,
            "fused_xla_p50_us": round(f * 1e6, 1),
            "materializing_p50_us": round(m * 1e6, 1),
            "speedup_x": round(m / max(f, 1e-12), 2),
        }

    # Static gather accounting at the 8B int8 deployment shape: what the
    # take_along_axis gather materializes per decode step across all layers.
    from k_llms_tpu.backends.tpu import BackendConfig
    from k_llms_tpu.models.quant import init_params_quantized

    cfg8 = get_config(FLAGSHIP)
    shapes = jax.eval_shape(
        lambda key: init_params_quantized(cfg8, key, bits=8),
        jax.ShapeDtypeStruct((2,), np.uint32),
    )
    param_bytes = sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(shapes)
    )
    ps8 = BackendConfig.model_fields["kv_page_size"].default
    prompt_len, gen_bucket = 1408, MAX_NEW
    pool_shape = jax.ShapeDtypeStruct(
        (1, 64 * ps8, cfg8.num_kv_heads, cfg8.head_dim), cfg8.jax_dtype
    )
    layer0 = jax.ShapeDtypeStruct((), np.int32)

    def gather_bytes(n: int) -> int:
        outs = jax.eval_shape(
            gather_kv_pages, pool_shape, pool_shape,
            jax.ShapeDtypeStruct((1, prompt_len), np.int32), layer0,
        ) + jax.eval_shape(
            gather_kv_pages, pool_shape, pool_shape,
            jax.ShapeDtypeStruct((n, gen_bucket), np.int32), layer0,
        )
        per_layer = sum(
            int(np.prod(o.shape)) * np.dtype(o.dtype).itemsize for o in outs
        )
        return per_layer * cfg8.num_layers

    # Coalesced admitted-width accounting: the per-launch row cap
    # generate_many's scheduler hint derives from paged_max_rows (each row
    # charged its gen reserve plus a 1/n share of the shared prompt) vs the
    # dense-layout cap the coalesced path used before it went paged.
    from k_llms_tpu.backends.tpu import HbmMemoryModel

    mm = HbmMemoryModel(cfg8, param_bytes=param_bytes, hbm_bytes=16 << 30)
    dense_rows = mm.max_rows(prompt_len + gen_bucket)

    def width_row(n: int) -> dict:
        paged_rows = mm.paged_max_rows(prompt_len, gen_bucket, ps8, fanout=n)
        return {
            "fanout": n,
            "dense_max_rows": dense_rows,
            "paged_max_rows": paged_rows,
            "width_ratio_x": round(paged_rows / max(1, dense_rows), 2),
        }

    return {
        "timed_tiny": [timed_row(8), timed_row(32)],
        "accounting_8b": {
            "model": FLAGSHIP,
            "quantization": "int8",
            "param_bytes": param_bytes,
            "prompt_len": prompt_len,
            "gen_bucket": gen_bucket,
            "page_size": ps8,
            "gather_bytes_per_step_n8": gather_bytes(8),
            "gather_bytes_per_step_n32": gather_bytes(32),
            "coalesced_width_n8": width_row(8),
            "coalesced_width_n32": width_row(32),
            "note": (
                "bytes the XLA paths materialize per decode step (all "
                "layers, shared [1, P] prefix + per-row gen slots); the "
                "Pallas kernel reads pages in place through its BlockSpec "
                "index_map instead. coalesced_width_*: the paged-vs-dense "
                "per-launch row caps generate_many admits against"
            ),
        },
    }


def bench_host_consensus() -> dict:
    """Host-side consolidation latency at the headline n=32 (hermetic, no
    device): the consensus stage every request pays after decode. Runs cold
    (fresh similarity caches per request — the worst case) and warm (shared
    per-backend scorer, the production configuration)."""
    from k_llms_tpu.consensus.consolidation import consolidate_chat_completions
    from k_llms_tpu.consensus.similarity import SimilarityScorer
    from k_llms_tpu.types import ChatCompletion
    from k_llms_tpu.utils.quality import DEFAULT_TRUTH, make_noisy_samples

    samples = make_noisy_samples(DEFAULT_TRUTH, N_CONSENSUS, 0.15, 7)
    comp = ChatCompletion.model_validate(
        {
            "id": "c", "created": 0, "model": "m", "object": "chat.completion",
            "choices": [
                {
                    "finish_reason": "stop",
                    "index": i,
                    "message": {"role": "assistant", "content": s},
                }
                for i, s in enumerate(samples)
            ],
        }
    )
    shared = SimilarityScorer.levenshtein()
    consolidate_chat_completions(comp, shared)  # warm the shared scorer

    def timed(fresh: bool, reps: int = 15) -> float:
        t0 = time.perf_counter()
        for _ in range(reps):
            scorer = SimilarityScorer.levenshtein() if fresh else shared
            consolidate_chat_completions(comp, scorer)
        return (time.perf_counter() - t0) / reps * 1000.0

    return {
        "n": N_CONSENSUS,
        "cold_ms": round(timed(True), 2),
        "warm_ms": round(timed(False), 2),
    }


def bench_constrained() -> dict:
    """Grammar-constrained vs unconstrained n-way structured extraction
    (hermetic — tiny model on CPU-JAX, the same fused mask ops as chip).

    Headline: at n in {8, 32} every completed constrained sample parses and
    validates into the schema (parse-valid rate 1.0), so the
    retry-on-parse-failure loop an unconstrained deployment needs
    (``would_retry`` failed samples per request) disappears. Also reports the
    compile-cache amortization (one compile across every run), the per-step
    p50 cost of the fused mask+advance against the unmasked step, and the
    off-switch differential: ``constrained_decoding=False`` plus a
    ``response_format`` is byte-identical to no response_format at all."""
    import numpy as np
    from pydantic import BaseModel, Field

    from k_llms_tpu import KLLMs
    from k_llms_tpu.backends.base import ChatRequest
    from k_llms_tpu.backends.tpu import BackendConfig, TpuBackend
    from k_llms_tpu.engine.grammar import (
        clear_grammar_cache,
        grammar_cache_stats,
        grammar_for_schema,
    )
    from k_llms_tpu.utils.observability import GRAMMAR_EVENTS

    class Record(BaseModel):
        name: str = Field(max_length=12)
        count: int

    msgs = [{"role": "user", "content": "extract the record"}]
    clear_grammar_cache()
    out: dict = {"runs": []}
    for constrained in (True, False):
        backend = TpuBackend(
            model="tiny",
            config=BackendConfig(
                model="tiny", max_new_tokens=96,
                constrained_decoding=constrained,
            ),
        )
        client = KLLMs(backend=backend, model="tiny")
        for n in (8, 32):
            before = dict(GRAMMAR_EVENTS.snapshot())
            t0 = time.perf_counter()
            r = client.chat.completions.parse(
                messages=msgs, response_format=Record, model="tiny",
                n=n, seed=100 + n,
            )
            wall = time.perf_counter() - t0
            after = dict(GRAMMAR_EVENTS.snapshot())
            samples = r.choices[1:]
            completed = [c for c in samples if c.finish_reason == "stop"]
            valid = [c for c in completed if c.message.parsed is not None]
            out["runs"].append({
                "constrained": constrained,
                "n": n,
                "completed": len(completed),
                "parse_valid": len(valid),
                "parse_valid_rate": round(len(valid) / max(1, len(completed)), 4),
                # Each completed-but-unparseable sample is a retry an
                # unconstrained deployment would pay; the mask makes it 0.
                "would_retry": len(completed) - len(valid),
                "consensus_parsed": r.choices[0].message.parsed is not None,
                "masked_steps": after.get("grammar.masked_steps", 0)
                - before.get("grammar.masked_steps", 0),
                "wall_s": round(wall, 3),
            })
        client.close()
    out["grammar_cache"] = grammar_cache_stats()

    # Per-step overhead of the fused mask+advance, engine-level (n=8 rows,
    # per executed decode step — constrained rows finish early, so normalize
    # by steps actually run, not tokens emitted).
    backend = TpuBackend(model="tiny", config=BackendConfig(model="tiny"))
    eng, tok = backend.engine, backend.tokenizer
    vocab, vd = backend._grammar_vocab()
    g = grammar_for_schema(Record.model_json_schema(), vocab, vocab_digest=vd)
    ids = tok.apply_chat_template(msgs)

    def step_p50_us(constraint) -> float:
        eng.generate(ids, n=8, max_new_tokens=16, temperature=1.0, seed=0,
                     eos_ids=tok.stop_ids, constraint=constraint)  # compile
        per_step = []
        for rep in range(5):
            t0 = time.perf_counter()
            r = eng.generate(ids, n=8, max_new_tokens=64, temperature=1.0,
                             seed=1 + rep, eos_ids=tok.stop_ids,
                             constraint=constraint)
            steps = max(1, int(np.max(r.lengths)))
            per_step.append((time.perf_counter() - t0) / steps * 1e6)
        return round(statistics.median(per_step), 1)

    unmasked = step_p50_us(None)
    masked = step_p50_us(g)
    out["step_p50_us"] = {
        "unconstrained": unmasked,
        "constrained": masked,
        "overhead_x": round(masked / unmasked, 3) if unmasked else None,
    }

    # Off-switch differential: no mask attached => byte-identical output.
    def texts(cfg_kwargs, req_kwargs):
        b = TpuBackend(
            model="tiny",
            config=BackendConfig(model="tiny", max_new_tokens=24, **cfg_kwargs),
        )
        req = ChatRequest(messages=msgs, model="tiny", n=4, seed=41,
                          temperature=0.9, **req_kwargs)
        r = b.chat_completion(req)
        got = [c.message.content for c in r.choices[1:]]
        b.drain()
        return got

    out["off_switch_byte_identical"] = texts(
        {"constrained_decoding": False},
        {"response_format": {"type": "json_object"}},
    ) == texts({}, {})
    return out


def bench_consensus() -> dict:
    """Host vs device consolidation across n ∈ {8, 32, 128} (hermetic; on CI
    the "device" is CPU-JAX, same kernels as chip). Axes per n: cold (fresh
    scorer per request, empty caches) vs warm (shared scorer, production
    config), and device with the bucket/memo caches disabled — the cache's
    own contribution. Headline: warm device n=32 vs the r05 host baseline
    (15.74 ms), the ISSUE r08 3x target."""
    from k_llms_tpu.consensus.consolidation import consolidate_chat_completions
    from k_llms_tpu.consensus.device import DeviceSimilarityScorer, device_available
    from k_llms_tpu.consensus.similarity import SimilarityScorer
    from k_llms_tpu.types import ChatCompletion
    from k_llms_tpu.utils.quality import DEFAULT_TRUTH, make_noisy_samples

    def make_comp(n: int) -> ChatCompletion:
        samples = make_noisy_samples(DEFAULT_TRUTH, n, 0.15, 7)
        return ChatCompletion.model_validate(
            {
                "id": "c", "created": 0, "model": "m", "object": "chat.completion",
                "choices": [
                    {
                        "finish_reason": "stop",
                        "index": i,
                        "message": {"role": "assistant", "content": s},
                    }
                    for i, s in enumerate(samples)
                ],
            }
        )

    def timed(comp, factory, reps: int) -> float:
        t0 = time.perf_counter()
        for _ in range(reps):
            consolidate_chat_completions(comp, factory())
        return round((time.perf_counter() - t0) / reps * 1000.0, 2)

    def fresh_device(cache: bool):
        s = DeviceSimilarityScorer(method="levenshtein")
        s.cache_enabled = cache
        return s

    out: dict = {"device_available": device_available(), "grid": []}
    for n in (8, 32, 128):
        comp = make_comp(n)
        reps = 15 if n <= 32 else 5
        host_shared = SimilarityScorer.levenshtein()
        consolidate_chat_completions(comp, host_shared)  # warm the shared scorer
        row: dict = {
            "n": n,
            "host_cold_ms": timed(comp, SimilarityScorer.levenshtein, reps),
            "host_warm_ms": timed(comp, lambda: host_shared, reps),
        }
        if out["device_available"]:
            dev_shared = DeviceSimilarityScorer(method="levenshtein")
            consolidate_chat_completions(comp, dev_shared)  # jit + cache warm
            row["device_cold_ms"] = timed(comp, lambda: fresh_device(True), reps)
            row["device_nocache_ms"] = timed(comp, lambda: fresh_device(False), reps)
            row["device_warm_ms"] = timed(comp, lambda: dev_shared, reps)
            row["speedup_warm_x"] = round(row["host_warm_ms"] / row["device_warm_ms"], 2)
        out["grid"].append(row)
    # Host warm n=32 from a chip capture that left the repo in PR 21 (it
    # predates the host path's memos); kept only so the section's output keeps
    # its shape until the benchmark is rebuilt (ROADMAP S1/S9).
    r05_host_warm_n32 = 15.74
    for row in out["grid"]:
        if row["n"] == 32 and "device_warm_ms" in row:
            out["speedup_vs_r05_host_x"] = round(r05_host_warm_n32 / row["device_warm_ms"], 2)
    return out


def bench_serving() -> dict:
    """Hermetic serving workload (PR 6): a loopback HTTP server (stdlib
    runner, ServerThread) over the tiny CPU backend, driven with httpx —
    the serving stack end to end, no device required.

    Two headline numbers:

    - TTFT: p50 time-to-first-SSE-delta for stream=true vs the p50 full
      response latency of the same request non-streamed. Streaming's reason
      to exist is that the first token arrives a decode-step in, not a full
      consensus later.
    - Occupancy under staggered load: the same 6-request trickle (arrivals
      mid-decode of earlier requests) through (a) the continuous in-flight
      slot loop and (b) the coalescing scheduler. Occupancy = useful row-
      steps / (serving width W * sequential device steps): late arrivals can
      JOIN the continuous batch, so its device steps carry more live rows;
      the coalesced path decodes each straggler as its own launch.
    """
    import httpx

    from k_llms_tpu import KLLMs
    from k_llms_tpu.backends.tpu import TpuBackend
    from k_llms_tpu.serving import ServerThread, ServingApp

    # Stagger chosen well inside one request's decode time (tiny model on
    # CPU decodes ~0.1s at 48 tokens), so later arrivals genuinely land
    # mid-decode of earlier ones — the case the slot loop exists for.
    W, N_PER, MAX_TOK = 4, 2, 48
    N_REQ, STAGGER_S = 6, 0.01
    msgs = [{"role": "user", "content": "Stream me a short answer."}]

    def make_client(continuous: bool) -> KLLMs:
        backend = TpuBackend(
            model="tiny", max_new_tokens=MAX_TOK, batch_window=0.0,
            continuous_batching=continuous, continuous_width=W,
            continuous_max_prompt=128, continuous_max_new=64,
        )
        return KLLMs(backend=backend, model="tiny")

    out: dict = {
        "width": W, "requests": N_REQ, "n_per_request": N_PER,
        "max_tokens": MAX_TOK, "stagger_s": STAGGER_S,
    }

    # -- TTFT vs non-stream p50 (continuous backend, loopback socket) ------
    client = make_client(continuous=True)
    with ServerThread(ServingApp(client)) as srv:
        url = srv.base_url + "/v1/chat/completions"

        def body(seed: int, stream: bool) -> dict:
            return {
                "messages": msgs, "model": "tiny", "n": N_PER,
                "max_tokens": MAX_TOK, "temperature": 0.8, "seed": seed,
                "stream": stream,
            }

        httpx.post(url, json=body(0, False), timeout=600)  # warm compiles
        ttfts, fulls = [], []
        for i in range(5):
            t0 = time.perf_counter()
            with httpx.stream("POST", url, json=body(10 + i, True), timeout=600) as r:
                frames = r.iter_raw()
                next(frames, None)
                ttfts.append(time.perf_counter() - t0)
                for _ in frames:
                    pass
            t0 = time.perf_counter()
            httpx.post(url, json=body(10 + i, False), timeout=600)
            fulls.append(time.perf_counter() - t0)
        ttft_p50 = statistics.median(ttfts)
        full_p50 = statistics.median(fulls)
        out["ttft_stream_p50_s"] = round(ttft_p50, 4)
        out["nonstream_p50_s"] = round(full_p50, 4)
        out["ttft_speedup"] = round(full_p50 / ttft_p50, 2)

        # -- staggered occupancy: continuous --------------------------------
        loop = client.backend._continuous
        steps0, rows0 = loop.stats["steps"], loop.stats["row_steps"]

        def fire(seed: int) -> None:
            httpx.post(url, json=body(seed, False), timeout=600)

        threads = [
            threading.Thread(target=fire, args=(100 + i,)) for i in range(N_REQ)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
            time.sleep(STAGGER_S)
        for t in threads:
            t.join()
        cont_makespan = time.perf_counter() - t0
        steps = loop.stats["steps"] - steps0
        row_steps = loop.stats["row_steps"] - rows0
        out["continuous"] = {
            "occupancy": round(row_steps / max(1, steps * W), 4),
            "device_steps": steps,
            "row_steps": row_steps,
            "joined_in_flight": loop.stats["joined_in_flight"],
            "makespan_s": round(cont_makespan, 4),
        }
    client.backend.close()

    # -- staggered occupancy: coalesced baseline ---------------------------
    client2 = make_client(continuous=False)
    engine2 = client2.backend.engine
    launches: list = []
    orig_many = engine2.generate_many

    def counted_many(specs, **kw):
        # One entry per LAUNCH (a coalesced group decodes together, so its
        # device steps are the longest member's, not the sum).
        results = orig_many(specs, **kw)
        lens = [
            int(x)
            for res in results
            if res is not None and getattr(res, "lengths", None) is not None
            for x in res.lengths
        ]
        if lens:
            launches.append((sum(lens), max(lens)))
        return results

    engine2.generate_many = counted_many
    with ServerThread(ServingApp(client2)) as srv2:
        url2 = srv2.base_url + "/v1/chat/completions"
        httpx.post(
            url2,
            json={"messages": msgs, "model": "tiny", "n": N_PER,
                  "max_tokens": MAX_TOK, "temperature": 0.8, "seed": 0},
            timeout=600,
        )  # warm
        launches.clear()

        def fire2(seed: int) -> None:
            httpx.post(
                url2,
                json={"messages": msgs, "model": "tiny", "n": N_PER,
                      "max_tokens": MAX_TOK, "temperature": 0.8, "seed": seed},
                timeout=600,
            )

        threads = [
            threading.Thread(target=fire2, args=(100 + i,)) for i in range(N_REQ)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
            time.sleep(STAGGER_S)
        for t in threads:
            t.join()
        coal_makespan = time.perf_counter() - t0
    client2.backend.close()
    # Sequential device steps at serving width W: each launch runs
    # max(lengths) steps with its own (small) row count; useful row-steps are
    # the tokens actually produced.
    useful = sum(tokens for tokens, _ in launches)
    total_steps = sum(steps for _, steps in launches)
    out["coalesced"] = {
        "occupancy": round(useful / max(1, total_steps * W), 4),
        "launches": len(launches),
        "device_steps": total_steps,
        "makespan_s": round(coal_makespan, 4),
    }
    out["occupancy_gain"] = round(
        out["continuous"]["occupancy"] / max(1e-9, out["coalesced"]["occupancy"]), 3
    )
    return out


def bench_hedging() -> dict:
    """Tail-latency rescue via replica hedging (hermetic — FakeBackend
    members, no device): a 2-member replica set where one member is made slow
    through the keyed ``replica.dispatch`` sleep failpoint. Round-robin
    routing pins half the primaries onto the slow member (health routing
    would learn to avoid it and hide the effect), so with hedging OFF the
    p99 — and here even the p50 — carries the injected stall, while with
    hedging ON the duplicate dispatch on the healthy member rescues the tail
    at roughly the hedge delay."""
    from k_llms_tpu.backends.base import ChatRequest
    from k_llms_tpu.backends.fake import FakeBackend
    from k_llms_tpu.reliability import failpoints as fp
    from k_llms_tpu.reliability.failpoints import FailSpec
    from k_llms_tpu.reliability.replicas import ReplicaSet

    slow_s, hedge_delay_s, requests = 0.060, 0.015, 40

    def quantile(xs: list, q: float) -> float:
        ordered = sorted(xs)
        return ordered[min(len(ordered) - 1, int(q * (len(ordered) - 1)))]

    def run(hedge: bool) -> dict:
        rs = ReplicaSet(
            members=[FakeBackend(["hedged"]), FakeBackend(["hedged"])],
            model="fake",
            hedge=hedge,
            hedge_delay_s=hedge_delay_s,
            route_policy="round_robin",
        )
        request = ChatRequest(
            messages=[{"role": "user", "content": "bench"}], model="fake"
        )
        latencies = []
        with fp.failpoints(
            {"replica.dispatch": FailSpec(action="sleep", member="r1", delay=slow_s)}
        ):
            for _ in range(requests):
                t0 = time.perf_counter()
                rs.dispatch_chat_completion(request)
                latencies.append((time.perf_counter() - t0) * 1000.0)
        stats = rs.stats()
        rs._executor.shutdown(wait=False)
        return {
            "p50_ms": round(quantile(latencies, 0.50), 2),
            "p99_ms": round(quantile(latencies, 0.99), 2),
            "hedges_won": sum(s["hedges_won"] for s in stats.values()),
        }

    off, on = run(False), run(True)
    return {
        "requests": requests,
        "slow_member_stall_ms": slow_s * 1000.0,
        "hedge_delay_ms": hedge_delay_s * 1000.0,
        "hedging_off": off,
        "hedging_on": on,
        "p99_speedup_x": round(off["p99_ms"] / max(on["p99_ms"], 1e-6), 2),
    }


def bench_tenancy() -> dict:
    """Weighted-fair isolation under skewed offered load (ISSUE 16,
    hermetic — EngineScheduler directly, no device): two equal-weight
    tenants, one offering 10x the other's load, every item pre-queued
    behind a blocked worker so the dequeue ORDER is pure scheduler policy.
    The acceptance number: at the instant the light tenant's last item is
    served, the heavy tenant must have been served a near-equal share —
    equal weights mean equal goodput, regardless of the 10:1 backlog skew.
    A FIFO queue would score ~10:1 here (the light tenant starves behind
    the flood); WFQ alternates and scores ~1:1."""
    import threading

    from k_llms_tpu.engine.scheduler import EngineScheduler
    from k_llms_tpu.reliability.tenancy import TenancyConfig

    heavy_n, light_n = 1000, 100
    tenancy = TenancyConfig.from_options(
        tenants={"heavy": {"weight": 1.0}, "light": {"weight": 1.0}}
    )
    sched = EngineScheduler(
        name="bench-tenancy", batch_window=0.0, tenancy=tenancy
    )
    served = {"heavy": 0, "light": 0}
    heavy_at_light_done = [0]
    gate = threading.Event()
    blocker = sched.submit(gate.wait)
    while not (sched.stats["queued"] == 0 and blocker.running()):
        time.sleep(0.005)

    def make_fn(tenant: str, last_light: bool):
        def fn(payloads):
            served[tenant] += len(payloads)
            if last_light:
                heavy_at_light_done[0] = served["heavy"]
            return list(payloads)

        return fn

    futures = []
    # Heavy floods FIRST: with FIFO dequeue the light tenant would wait out
    # the full 10x backlog before its first item moves.
    for i in range(heavy_n):
        futures.append(sched.submit_batched(
            ("heavy", i), i, make_fn("heavy", False), weight=1, tenant="heavy"
        ))
    for i in range(light_n):
        futures.append(sched.submit_batched(
            ("light", i), i, make_fn("light", i == light_n - 1),
            weight=1, tenant="light",
        ))
    t0 = time.perf_counter()
    gate.set()
    for f in futures:
        f.result(timeout=120)
    drain_s = time.perf_counter() - t0
    blocker.result(timeout=10)
    health = sched.health()
    sched.shutdown()

    # Goodput split while BOTH tenants were backlogged: served counts at the
    # moment the light tenant finished. Equal weights -> ratio ~1.0.
    heavy_share = heavy_at_light_done[0]
    ratio = heavy_share / max(1, light_n)
    return {
        "offered": {"heavy": heavy_n, "light": light_n},
        "weights": {"heavy": 1.0, "light": 1.0},
        "heavy_served_at_light_done": heavy_share,
        "light_served": light_n,
        "goodput_ratio_heavy_over_light": round(ratio, 3),
        "within_10pct_of_weights": bool(abs(ratio - 1.0) <= 0.10),
        "drain_s": round(drain_s, 3),
        "served_per_tenant": {
            t: health["tenants"][t]["served"] for t in ("heavy", "light")
        },
    }


def bench_batch_lane() -> dict:
    """Durable offline batch lane (ISSUE 17, hermetic — FakeBackend with a
    fixed per-call service time, no device): (a) throughput — a 64-item
    durable batch job drained by the lane's bounded worker pool vs the same
    64 bodies executed foreground one at a time; the lane overlaps
    ``max_in_flight`` items so wall time divides by ~the pool width minus
    the per-item durable-commit fsyncs, while every output lands exactly
    once through the crash-safe store; (b)
    isolation — interactive p50/p99 client latency with the lane off vs
    grinding a second 64-item job; the pool is bounded, so foreground calls
    on the same client stay flat instead of queueing behind the backlog."""
    import shutil
    import tempfile

    from k_llms_tpu import KLLMs
    from k_llms_tpu.backends.fake import FakeBackend
    from k_llms_tpu.reliability.jobstore import JobStore
    from k_llms_tpu.serving.batch import BatchLane

    work_s, items, in_flight, interactive_n = 0.008, 64, 4, 40

    def quantile(xs: list, q: float) -> float:
        ordered = sorted(xs)
        return ordered[min(len(ordered) - 1, int(q * (len(ordered) - 1)))]

    client = KLLMs(backend=FakeBackend(), model="fake-model")
    real_create = client.chat.completions.create

    def timed_create(*args, **kwargs):
        time.sleep(work_s)  # fixed per-item service time: makes overlap visible
        return real_create(*args, **kwargs)

    client.chat.completions.create = timed_create

    def job_body(tag: str) -> bytes:
        return "\n".join(
            json.dumps({"custom_id": f"{tag}-{i}", "body": {
                "messages": [{"role": "user", "content": f"{tag} {i}"}],
                "n": 1, "seed": 1000 + i,
            }})
            for i in range(items)
        ).encode()

    def interactive() -> list:
        lats = []
        for i in range(interactive_n):
            t0 = time.perf_counter()
            client.chat.completions.create(
                messages=[{"role": "user", "content": f"interactive {i}"}],
                model="fake-model", n=1, seed=5000 + i,
            )
            lats.append((time.perf_counter() - t0) * 1000.0)
        return lats

    # (a) Foreground baseline: the same 64 bodies, strictly sequential.
    t0 = time.perf_counter()
    for i in range(items):
        client.chat.completions.create(
            messages=[{"role": "user", "content": f"foreground {i}"}],
            model="fake-model", n=1, seed=1000 + i,
        )
    foreground_s = time.perf_counter() - t0

    root = tempfile.mkdtemp(prefix="kllms-bench-batch-")
    lane = BatchLane(client, JobStore(root), max_in_flight=in_flight)
    try:
        t0 = time.perf_counter()
        wire = lane.submit(job_body("lane"), tenant="bench")
        assert lane.wait_idle(120.0), lane.health()
        lane_s = time.perf_counter() - t0
        final = lane.job_wire(wire["id"])
        records = [
            json.loads(l)
            for l in lane.output_bytes(wire["id"]).decode().splitlines()
        ]
        assert final["status"] == "completed", final
        assert len({r["id"] for r in records}) == items, "duplicate outputs"

        # (b) Interactive latency with the lane quiet, then grinding.
        lat_off = interactive()
        lane.submit(job_body("grind"), tenant="bench")
        lat_on = interactive()
        assert lane.wait_idle(120.0), lane.health()
        lane.drain(timeout=10.0)
    finally:
        lane.close()
        shutil.rmtree(root, ignore_errors=True)

    p99_off = quantile(lat_off, 0.99)
    p99_on = quantile(lat_on, 0.99)
    return {
        "items": items,
        "max_in_flight": in_flight,
        "service_time_ms": work_s * 1000.0,
        "foreground_s": round(foreground_s, 3),
        "lane_s": round(lane_s, 3),
        "lane_speedup_x": round(foreground_s / max(lane_s, 1e-6), 2),
        "outputs_exactly_once": len({r["id"] for r in records}) == items,
        "interactive": {
            "requests": interactive_n,
            "lane_off": {
                "p50_ms": round(quantile(lat_off, 0.50), 2),
                "p99_ms": round(p99_off, 2),
            },
            "lane_on": {
                "p50_ms": round(quantile(lat_on, 0.50), 2),
                "p99_ms": round(p99_on, 2),
            },
            "p99_ratio_on_over_off": round(p99_on / max(p99_off, 1e-6), 2),
        },
    }


def bench_chunked_prefill() -> dict:
    """Chunked prefill (ISSUE 18, hermetic — tiny model, dense continuous
    loop): the ISSUE's trickle-plus-whale workload. An in-flight row streams
    tokens continuously while one 1408-token admission lands; with chunking
    OFF the whole-prompt prefill runs between two decode steps, so the row's
    inter-token gap spikes by the full prefill and a short request submitted
    behind the whale waits just as long for its first token. With chunking ON
    (the HbmMemoryModel auto size for this shape) one chunk rides between
    decode steps: the max gap stays within a small multiple of the steady
    p50, the short request admits after at most one chunk, and the whale's
    own output tokens are byte-identical to the monolithic path (the
    differential tests/test_chunked_prefill.py pins). The whale's TTFT is
    the price paid, reported honestly."""
    import numpy as np

    from k_llms_tpu.backends.tpu import HbmMemoryModel
    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.models import get_config
    from k_llms_tpu.models.llama import init_params
    from k_llms_tpu.utils.observability import LATENCY

    tiny = get_config("tiny")
    engine = LocalEngine(
        tiny, params=init_params(tiny, jax.random.PRNGKey(0)), use_mesh=False
    )
    width, max_prompt, max_new = 4, 2048, 256
    long_prompt = [(i * 17) % 150 + 3 for i in range(1408)]
    short_prompt = [(i * 13) % 150 + 3 for i in range(12)]
    auto_chunk = HbmMemoryModel(tiny, param_bytes=1 << 20).prefill_chunk_tokens(
        width, max_prompt
    )

    def quantile(xs: list, q: float) -> float:
        ordered = sorted(xs)
        return ordered[min(len(ordered) - 1, int(q * (len(ordered) - 1)))]

    def step_hist() -> "list[tuple[float, int]]":
        return list(
            LATENCY.snapshot().get("continuous.step", {}).get("buckets", [])
        )

    def hist_bound(before, after, q: float) -> "float | None":
        """Smallest bucket bound covering quantile q of the continuous.step
        observations made between the two snapshots (cumulative counts)."""
        delta = [
            (le, b - a)
            for (le, b), (_, a) in zip(after, before or [(0.0, 0)] * len(after))
        ]
        total = delta[-1][1] if delta else 0
        if total <= 0:
            return None
        need = max(1, int(q * total))
        for le, cum in delta:
            if cum >= need:
                return le
        return None

    def run(chunk_tokens: int) -> "tuple[dict, object]":
        loop = ContinuousDecodeLoop(
            engine, width=width, max_prompt=max_prompt, max_new=max_new,
            prefill_chunk_tokens=chunk_tokens,
        )
        try:
            # Warm every program (decode at the 2048 bucket, whole prefill,
            # chunk step): compile time must not masquerade as stall.
            loop.submit(
                list(long_prompt), n=1, max_new=4, temperature=0.0,
                top_p=None, seed=1,
            ).result(timeout=900)
            stamps: list = []
            h_start = step_hist()
            inflight = loop.submit(
                [5, 9, 23], n=1, max_new=max_new - 8, temperature=0.6,
                top_p=0.9, seed=7,
                token_sink=lambda s, t: stamps.append(time.perf_counter()),
            )
            while len(stamps) < 48:  # establish a steady decode cadence
                time.sleep(0.002)
            long_first: list = []
            h_mid = step_hist()
            t_long = time.perf_counter()
            long_fut = loop.submit(
                list(long_prompt), n=1, max_new=8, temperature=0.0,
                top_p=None, seed=3,
                token_sink=lambda s, t: (
                    long_first.append(time.perf_counter())
                    if not long_first else None
                ),
            )
            # The trickle request stuck behind the whale: its TTFT is the
            # headline admission-latency number.
            short_first: list = []
            t_short = time.perf_counter()
            short_fut = loop.submit(
                list(short_prompt), n=1, max_new=4, temperature=0.0,
                top_p=None, seed=5,
                token_sink=lambda s, t: (
                    short_first.append(time.perf_counter())
                    if not short_first else None
                ),
            )
            long_res = long_fut.result(timeout=900)
            h_end = step_hist()
            short_fut.result(timeout=900)
            inflight.result(timeout=900)
            chunks = dict(loop.stats)["prefill_chunks"]
        finally:
            loop.stop()
        # Skip the first few post-admission gaps: the row's own warm-in
        # (sink registration, first-step bookkeeping) is not steady cadence.
        gaps = list(zip(stamps[8:], stamps[9:]))
        steady = [b - a for a, b in gaps if b <= t_long]
        stall = [
            b - a for a, b in gaps if b > t_long and a < long_first[0]
        ]
        steady_p50 = quantile(steady, 0.5)
        max_stall = max(stall) if stall else None
        # The acceptance metric verbatim: the ``continuous.step`` histogram
        # (decode dispatch only — the interleaved chunk times into its own
        # ``continuous.prefill_chunk`` family), steady p50 bucket vs the max
        # bucket observed while the whale ingests.
        step_p50_le = hist_bound(h_start, h_mid, 0.5)
        step_max_le = hist_bound(h_mid, h_end, 1.0)
        return {
            "prefill_chunk_tokens": chunk_tokens,
            "prefill_chunks": chunks,
            "steady_step_p50_ms": round(steady_p50 * 1000.0, 3),
            "max_gap_during_admission_ms": (
                round(max_stall * 1000.0, 3) if max_stall is not None else None
            ),
            "stall_over_steady_p50_x": (
                round(max_stall / max(steady_p50, 1e-9), 2)
                if max_stall is not None else None
            ),
            "short_ttft_ms": round((short_first[0] - t_short) * 1000.0, 3),
            "long_ttft_ms": round((long_first[0] - t_long) * 1000.0, 3),
            "step_hist_steady_p50_le_ms": (
                round(step_p50_le * 1000.0, 1) if step_p50_le else None
            ),
            "step_hist_admission_max_le_ms": (
                round(step_max_le * 1000.0, 1) if step_max_le else None
            ),
            "step_max_within_3x_p50": (
                step_max_le <= 3.0 * step_p50_le
                if step_p50_le and step_max_le else None
            ),
        }, long_res.tokens

    off, off_tokens = run(0)
    on, on_tokens = run(auto_chunk)
    return {
        "model": "tiny",
        "layout": "dense",
        "width": width,
        "max_prompt": max_prompt,
        "long_prompt_tokens": len(long_prompt),
        "auto_chunk_tokens": auto_chunk,
        "off": off,
        "on": on,
        "long_output_identical": bool(np.array_equal(off_tokens, on_tokens)),
        "short_ttft_speedup_x": round(
            off["short_ttft_ms"] / max(on["short_ttft_ms"], 1e-6), 2
        ),
    }


def _emit(value, vs_baseline, detail: dict, error: "str | None" = None) -> None:
    line = {
        "metric": "n32_consensus_p50_over_single_p50",
        "value": value,
        "unit": "x",
        "vs_baseline": vs_baseline,
        "detail": detail,
    }
    if error is not None:
        line["error"] = error
    print(json.dumps(line))


def _error(exc: BaseException, limit: int = 300) -> dict:
    return {"error": f"{type(exc).__name__}: {exc}"[:limit]}


def main() -> None:
    from k_llms_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    detail: dict = {}
    failed = []
    # Hermetic sections: a failure in one is a bug, is reported under the
    # section's own key, and fails the run — the others still run.
    for name, section in (
        ("quality", bench_quality),
        ("host_consensus", bench_host_consensus),
        ("consensus", bench_consensus),
        ("constrained", bench_constrained),
        ("paged_kv", bench_paged_kv),
        ("paged_attention", bench_paged_attention),
        ("hedging", bench_hedging),
        ("tenancy", bench_tenancy),
        ("batch_lane", bench_batch_lane),
        ("chunked_prefill", bench_chunked_prefill),
        ("serving", bench_serving),
    ):
        try:
            detail[name] = section()
        except Exception as exc:
            detail[name] = _error(exc)
            failed.append(name)

    # Chip section. The flagship is the headline: without it there is no value.
    try:
        flagship, backend, client = bench_flagship()
        detail["flagship"] = flagship
        detail["concurrency"] = bench_concurrency(backend, client)
    except Exception as exc:
        message = _error(exc, 500)["error"]
        print(f"# chip section failed: {message}", file=sys.stderr)
        _emit(None, None, detail, error=message)
        sys.exit(1)
    for name, section in (
        ("speculative", bench_speculative),
        ("prefix_cache", bench_prefix_cache),
    ):
        try:
            detail[name] = section(backend)
        except Exception as exc:
            detail[name] = _error(exc)
            failed.append(name)
    ratio = flagship["ratio"]
    error = f"sections failed: {', '.join(failed)}" if failed else None
    _emit(ratio, round(2.0 / ratio, 4), detail, error=error)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
