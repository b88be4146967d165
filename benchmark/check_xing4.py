#!/usr/bin/env python3
"""benchmark/check_xing4.py — the loop's own programs against the plain reference, on the chip.

    python3 benchmark/check_xing4.py [--seed N] [--doc-tokens N] [--steps N] [--rows N]

Builds ``xing4-29b-a4b`` exactly as ``serve.py`` does
(``create_app(**config["serve"])``), then drives the programs the continuous
loop runs, at the cell's sizes and with the loop's own pool, width and paged
attention choice:

1. the loop's chunk step (``prefill_chunk_step_paged``, jitted as
   ``engine._get_prefill_chunk`` jits it, with the router's choices as one more
   output): one extract-shaped prompt (1,024-token prefix + a document) in
   128-token chunks into latent pages; every chunk's last-token logits are kept;
2. decode steps through ``paged_verify_step`` at the loop's width: ``--rows``
   rows fan out on the prompt's shared pages (private copy of the last partial
   page, as the loop's copy-on-write leaves it), each forced along its own
   tokens for ``--steps`` steps; every step's logits are kept.

Then the engine is dropped (its 11 GB and a float32 expert do not sit together
with room to spare; the parameters stay) and ``xing4_reference.forward`` runs
each row's whole sequence in float32, layer by layer, op by op. One line a
comparison; exit code 1 if any limit fails.

**What is compared, and the limits.** For each kept position, ``err`` =
||program logits - reference logits|| / ||reference logits|| over the whole
vocabulary (never sampled tokens: with random weights the largest logit turns
on rounding).

The router takes the top 4 of 64 sigmoid scores, and the 4th and 5th lie
closer than bfloat16 resolves in about one token-layer in ten: left alone,
program and reference then run different experts, that position's ``err``
jumps to 0.2-0.8, and through attention every later position inherits some of
it (first chip run: median 0.25 with a floor of 0.07, which says nothing about
the arithmetic). So the comparison is made in two parts, each tight:

- ``SLACK_MEAN_LIMIT`` and ``SLACK_MAX_LIMIT``: the program's choice of
  experts, at every token and layer, against the reference's own top-k
  boundary: ``slack`` = the k-th best score + bias of the reference minus the
  worst among the program's chosen, 0 where the sets agree. Its mean over all
  token-layers says how noisy the router's input is (precision); its largest
  value holds every single choice to the neighbourhood of a tie (a router
  that ranks by anything else is off by 0.1 and more).
- ``MEDIAN_LIMIT`` and ``MAX_LIMIT``: ``err`` with the reference given the
  program's choices (``forward(given=...)``: the weights are still the
  reference's own scores). The median over a comparison's positions (all chunk
  ends; all decode steps of all rows) and the largest.

PERF.md has the two readings each limit lies between: what bfloat16 against
float32 gives, and what the reference itself gives in the nearest precisions
below the configuration's (``--lower 1``: every weight rounded to
float8_e4m3's three mantissa bits, and through per-output-channel int8),
compared with the float32 reference under the same routing; both must come out
over a limit.

``--free 1`` adds the unconditioned comparison (the reference routing for
itself) as a reading, with no limit.

``--platform cpu`` is a rehearsal at ``xing4-tiny``: it proves the script, not
the model, and its line says so.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

# Each between its two readings on the chip (PERF.md §6 has them, per seed):
# bfloat16 against float32 read err median 0.034-0.036 / max 0.040-0.044 and
# slack mean 0.00037 / max 0.019; int8 weights read 0.138 / 0.155 (min 0.122)
# and 0.0056 / 0.098; float8 mantissas read higher still.
SLACK_MEAN_LIMIT = 0.0015
SLACK_MAX_LIMIT = 0.045
MEDIAN_LIMIT = 0.07
MAX_LIMIT = 0.085


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--doc-tokens", type=int, default=500)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--lower", type=int, choices=(0, 1), default=0)
    ap.add_argument("--free", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    import xing4_reference as ref
    from k_llms_tpu.engine.paging import flat_slots, pages_for
    from k_llms_tpu.models.llama import (
        KVCache, init_cache, paged_verify_step, prefill_chunk_step_paged)
    from k_llms_tpu.serving.app import create_app
    from k_llms_tpu.utils.observability import KERNEL_EVENTS

    with open(os.path.join(HERE, "configs", "xing4-29b-a4b.json")) as f:
        hf = json.load(f)
    serve = dict(hf["serve"])
    if args.platform == "cpu":
        serve["model"] = "xing4-tiny"
    platform = jax.devices()[0].platform
    if platform != args.platform:
        sys.exit(f"check_xing4.py: running on {platform!r}, asked for {args.platform!r}")

    t0 = time.monotonic()
    app = create_app(**serve)
    backend = app.client.backend
    engine, loop = backend.engine, backend._continuous
    config = engine.config
    if args.platform == "cpu":  # the tiny preset's own sizes, in the published key names
        hf.update(hidden_size=config.hidden_size, num_attention_heads=config.num_heads,
                  q_lora_rank=config.q_lora_rank, kv_lora_rank=config.kv_lora_rank,
                  qk_nope_head_dim=config.qk_nope_head_dim,
                  qk_rope_head_dim=config.qk_rope_head_dim, v_head_dim=config.v_head_dim,
                  n_routed_experts=config.num_experts,
                  num_experts_per_tok=config.num_experts_per_tok,
                  num_hidden_layers=config.num_layers,
                  rope_scaling=dict(hf["rope_scaling"],
                                    original_max_position_embeddings=config.rope_scaling[2]))
    if not loop._built:
        loop._build_device_state()
    pool, W, P, G = loop._pool, loop.width, loop.max_prompt, loop.max_new
    ps, C = pool.page_size, loop.prefill_chunk_tokens  # the loop's own: 128 at width 32
    print(f"built {config.name} on {platform} in {time.monotonic() - t0:.1f}s: "
          f"param_bytes {engine.param_footprint_bytes()}, width {W}, max_prompt {P}, "
          f"max_new {G}, page {ps}, chunk {C}, pool pages {pool.allocator.total_pages} "
          f"({pool.pool_bytes()} B), paged attention {loop._paged_attn_impl!r}, kernel events "
          f"{KERNEL_EVENTS.snapshot()}", flush=True)

    rng = np.random.default_rng(args.seed)
    prefix_tokens = 1024 if args.platform != "cpu" else 96
    plen = prefix_tokens + args.doc_tokens
    prompt = rng.integers(32, 127, size=plen).astype(np.int32)  # printable bytes, as the cell's text
    forced = rng.integers(32, 127, size=(args.rows, args.steps)).astype(np.int32)
    _ids, _plen, bucket = engine._prep_prompt([int(t) for t in prompt])

    # 1. chunked prefill through the loop's chunk step, into the prompt's page run.
    def chunk_step(params, chunk_tokens, cache, cursor, valid_len):
        aux = {"moe_chosen": None}
        return prefill_chunk_step_paged(
            config, params, chunk_tokens, cache, cursor, valid_len, aux=aux) + (aux,)

    run_pages = engine._alloc_pages_with_evict(pages_for(plen, ps))
    chunk_fn = jax.jit(chunk_step, donate_argnums=(2,))
    cache = init_cache(config, 1, bucket)
    chunk_logits, chunk_pos, moe_counts, chunk_chosen = [], [], [], []
    for start in range(0, plen, C):
        valid = min(C, plen - start)
        chunk = np.full((1, C), config.pad_token_id, np.int32)
        chunk[0, :valid] = prompt[start:start + valid]
        slots = flat_slots(run_pages, start + np.arange(C), ps)
        slots[valid:] = (np.arange(C) % ps)[valid:]  # pad positions go to the trash page
        logits, cache, k_cols, v_cols, aux = chunk_fn(
            engine.params, jnp.asarray(chunk), cache, jnp.int32(start), jnp.int32(valid))
        pool.scatter_tokens(k_cols, v_cols, slots)
        chunk_logits.append(np.asarray(logits[0], np.float32))
        chunk_pos.append(start + valid - 1)
        moe_counts.append(np.asarray(aux["moe_counts"]))
        chunk_chosen.append(np.asarray(aux["moe_chosen"])[:, :valid])
    del cache
    prompt_chosen = np.concatenate(chunk_chosen, axis=1)  # [expert layers, plen, K]
    touched = np.mean([(c > 0).mean() for c in moe_counts])
    print(f"prefill: {len(chunk_pos)} chunks of {C} into {len(run_pages)} pages; experts touched "
          f"a chunk-layer {100 * touched:.1f}%", flush=True)

    # 2. decode steps at the loop's width: rows fan out on the shared prompt pages.
    tables = []
    for _ in range(args.rows):
        table = list(run_pages)
        if plen % ps:  # the loop's copy-on-write: a private copy of the partial last page
            own = engine._alloc_pages_with_evict(1)
            pool.copy_pages([table[-1]], own)
            table[-1] = own[0]
        table += engine._alloc_pages_with_evict(pages_for(plen + args.steps, ps) - len(table))
        tables.append(table)
    pidx = np.tile((np.arange(P) % ps).astype(np.int32), (W, 1))
    gidx = np.tile((np.arange(G) % ps).astype(np.int32), (W, 1))
    prompt_lens = np.zeros((W,), np.int32)
    for r, table in enumerate(tables):
        pidx[r] = flat_slots(table, np.arange(P), ps)
        pidx[r, plen:] = (np.arange(P - plen) % ps).astype(np.int32)
        gidx[r] = flat_slots(table, plen + np.arange(G), ps)
        prompt_lens[r] = plen

    def step(params, pool_k, pool_v, cur, gen_lens, prompt_lens, pidx, gidx):
        aux = {"moe_chosen": None}
        logits, k_cols, v_cols = paged_verify_step(
            config, params, cur[:, None], gen_lens, prompt_lens, KVCache(k=pool_k, v=pool_v),
            pidx, gidx, attn_impl=loop._paged_attn_impl, page_size=ps, aux=aux)
        return logits[:, 0, :], k_cols, v_cols, aux

    step_fn = jax.jit(step)
    step_logits = np.zeros((args.rows, args.steps, config.vocab_size), np.float32)
    step_chosen = []  # a step: [expert layers, W, K]
    for t in range(args.steps):
        cur = np.full((W,), config.pad_token_id, np.int32)
        cur[:args.rows] = forced[:, t]
        gen_lens = np.zeros((W,), np.int32)
        gen_lens[:args.rows] = t
        write = (np.arange(W) % ps).astype(np.int32)  # idle rows write into the trash page
        write[:args.rows] = gidx[:args.rows, t]
        logits, k_cols, v_cols, aux = step_fn(
            engine.params, pool.kv.k, pool.kv.v, jnp.asarray(cur), jnp.asarray(gen_lens),
            jnp.asarray(prompt_lens), jnp.asarray(pidx), jnp.asarray(gidx))
        pool.scatter_tokens(k_cols, v_cols, write)
        step_logits[:, t] = np.asarray(logits[:args.rows], np.float32)
        step_chosen.append(np.asarray(aux["moe_chosen"]))
    print(f"decode: {args.steps} steps at width {W}, {args.rows} rows on shared pages; last step "
          f"experts touched {100 * (np.asarray(aux['moe_counts']) > 0).mean():.1f}%, latent rows "
          f"read {int(aux['mla_latent_rows_read'])}", flush=True)
    if not (np.isfinite(step_logits).all() and np.isfinite(np.stack(chunk_logits)).all()):
        sys.exit("check_xing4.py: the program's logits are not finite")

    # 3. drop the engine, keep the parameters, run the reference.
    params = engine.params
    stats = jax.devices()[0].memory_stats() or {}
    print(f"allocator peak with the engine up: {stats.get('peak_bytes_in_use')}", flush=True)
    backend.close()
    del app, backend, engine, loop, pool, chunk_fn, step_fn, logits, k_cols, v_cols, aux
    gc.collect()
    jax.clear_caches()

    def err(program, reference):
        reference = np.asarray(reference, np.float32)
        return float(np.linalg.norm(program - reference) / np.linalg.norm(reference))

    ok = True

    def verdict(name, errs, limits=True):
        nonlocal ok
        med, top = float(np.median(errs)), float(np.max(errs))
        if not limits:
            print(f"{name}: {len(errs)} positions, err median {med:.4f} min {min(errs):.4f} "
                  f"max {top:.4f} (a reading, no limit)", flush=True)
            return
        passed = med <= MEDIAN_LIMIT and top <= MAX_LIMIT
        ok = ok and passed
        print(f"{name}: {len(errs)} positions, err median {med:.4f} (limit {MEDIAN_LIMIT}) max "
              f"{top:.4f} (limit {MAX_LIMIT}) -> {'ok' if passed else 'FAIL'}", flush=True)

    def given_for(r):  # row r's whole sequence: the prompt's choices, then its steps'
        own = np.stack([c[:, r] for c in step_chosen], axis=1)  # [expert layers, steps, K]
        return np.concatenate([prompt_chosen, own], axis=1)

    step_errs, free_errs, all_slack, first = [], [], [], None
    both = np.concatenate([chunk_pos, plen + np.arange(args.steps)])
    for r in range(args.rows):
        t1 = time.monotonic()
        tokens = np.concatenate([prompt, forced[r]])
        want, slacks = (both if r == 0 else plen + np.arange(args.steps)), []
        out = np.asarray(ref.forward(hf, params, tokens, positions=want, given=given_for(r),
                                     slacks=slacks), np.float32)
        # The prompt's slack is the same for every row: count it once.
        slack = np.stack([np.asarray(s) for s in slacks])[:, 0 if r == 0 else plen:]
        all_slack.append(slack.reshape(-1))
        disagree = float((slack > 0).mean())
        if r == 0:
            first = out
            verdict("chunk ends vs reference (given the program's routing)",
                    [err(p, q) for p, q in zip(chunk_logits, out[:len(chunk_pos)])])
            out = out[len(chunk_pos):]
        errs = [err(step_logits[r, t], out[t]) for t in range(args.steps)]
        step_errs += errs
        print(f"  row {r}: decode err {' '.join(f'{e:.4f}' for e in errs)}; routing slack max "
              f"{float(slack.max()):.5f}, sets differ at {100 * disagree:.2f}% of token-layers "
              f"({time.monotonic() - t1:.1f}s of reference)", flush=True)
        if args.free and r < 2:
            out = np.asarray(ref.forward(hf, params, tokens, positions=want), np.float32)
            if r == 0:
                verdict("chunk ends vs reference (routing for itself)",
                        [err(p, q) for p, q in zip(chunk_logits, out[:len(chunk_pos)])], False)
                out = out[len(chunk_pos):]
            free_errs += [err(step_logits[r, t], out[t]) for t in range(args.steps)]
    verdict("paged decode steps vs reference (given the program's routing)", step_errs)
    if free_errs:
        verdict("paged decode steps vs reference (routing for itself)", free_errs, False)
    all_slack = np.concatenate(all_slack)
    passed = all_slack.mean() <= SLACK_MEAN_LIMIT and all_slack.max() <= SLACK_MAX_LIMIT
    ok = ok and passed
    print(f"the program's expert choices vs the reference's own top-k: {all_slack.size} "
          f"token-layers, sets differ at {100 * (all_slack > 0).mean():.2f}%, slack mean "
          f"{all_slack.mean():.6f} (limit {SLACK_MEAN_LIMIT}) max {all_slack.max():.5f} "
          f"(limit {SLACK_MAX_LIMIT}) -> {'ok' if passed else 'FAIL'}", flush=True)

    if args.lower:
        @jax.jit  # fused: the eager chain would hold three float32 copies of a table
        def float8(a):
            # A scaled float8_e4m3: its 3 mantissa bits, the exponent left wide
            # (an unscaled cast would push weights of ~1/sqrt(H) into e4m3's
            # subnormals; and under jit XLA drops a cast there and back).
            return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)

        @jax.jit
        def int8(a):  # symmetric, one scale per output channel (the last axis)
            scale = jnp.max(jnp.abs(a.astype(jnp.float32)), axis=-2, keepdims=True) / 127.0
            return (jnp.round(a / scale).clip(-127, 127) * scale).astype(a.dtype)

        tokens = np.concatenate([prompt, forced[0]])
        for name, rounding in (("float8 (3 mantissa bits)", float8), ("int8 per channel", int8)):
            def lowered(a, rounding=rounding):
                floating = jnp.issubdtype(a.dtype, jnp.floating)
                return rounding(a) if floating and a.ndim >= 2 else a

            def lower_layer(cfg, p, X, **routed):
                # One array at a time, dropping the original slice: a layer's
                # expert stacks are 1.4 GB, and two copies beside the
                # parameters do not fit.
                return ref.layer(cfg, {k: lowered(p.pop(k)) for k in list(p)}, X, **routed)

            low_params = dict(params, embed=None, lm_head=None)
            for table in ("embed", "lm_head"):
                low_params[table] = lowered(params[table])
            slacks = []
            low = np.asarray(ref.forward(hf, low_params, tokens, layer_fn=lower_layer,
                                         positions=both, given=given_for(0), slacks=slacks),
                             np.float32)
            del low_params
            errs = [err(p, q) for p, q in zip(low, first)]
            slack = np.stack([np.asarray(x) for x in slacks])
            med, top = float(np.median(errs)), float(np.max(errs))
            caught = [what for what, over in (
                ("err median", med > MEDIAN_LIMIT), ("err max", top > MAX_LIMIT),
                ("slack mean", slack.mean() > SLACK_MEAN_LIMIT),
                ("slack max", slack.max() > SLACK_MAX_LIMIT)) if over]
            ok = ok and bool(caught)
            print(f"{name} weights vs the float32 reference (same routing): {len(errs)} positions, "
                  f"err median {med:.4f} min {min(errs):.4f} max {top:.4f}; slack of the program's "
                  f"choices under this router mean {slack.mean():.6f} max {slack.max():.5f} -> "
                  + (f"not correct by {', '.join(caught)}, as it must be" if caught
                     else "PASSES EVERY LIMIT: too loose"), flush=True)

    print(json.dumps({"check": "xing4-29b-a4b", "platform": platform, "model": config.name,
                      "seed": args.seed, "prompt_tokens": plen, "ok": bool(ok)}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
