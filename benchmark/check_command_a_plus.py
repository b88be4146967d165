#!/usr/bin/env python3
"""benchmark/check_command_a_plus.py — the loop's programs against the plain reference, on the chip.

    python3 benchmark/check_command_a_plus.py [--seed N] [--docs N,N] [--steps N] [--rows N] [--ref-rows N] [--lower 1]

After ``check_joyai.py``. Builds ``command-a-plus`` exactly as ``serve.py`` does
(``create_app(**config["serve"])``), then drives the model programs the
continuous loop's chunk lane and decode step are made of, at the cell's sizes
and with the loop's own pool, width, page size and chunk:

1. the loop's chunk step (``prefill_chunk_step_paged``, jitted as
   ``engine._get_prefill_chunk`` jits it, with the router's choices as one more
   output): two extract-long-shaped prompts (the 1,024-token prefix + a
   document), one under the 4,096 window (``--docs`` 700: 1.7k tokens, a
   staging cache of 2,048) and one past it (4,880: 5.9k tokens, a staging cache
   of 8,192, so chunks cross the window's edge and the global layer reads what
   the three windowed ones no longer see), in the loop's own chunks (128 tokens
   at width 32) into the pool's pages; every chunk's last-token logits
   are kept;
2. ``--rows`` rows a prompt on the prompt's shared pages (a private copy of the
   last partial page, as the loop's copy-on-write leaves it), each forced along
   its own tokens for ``--steps`` decode steps at the loop's width:
   ``paged_verify_step`` with the loop's resolved attention (the Pallas paged
   kernel on a TPU, each layer's call with its own window) and the step's own
   scatter. The grammar-free and the grammar step programs run this same model
   program (the grammar masks its logits afterwards), so one comparison holds
   both.

Then the engine is dropped (the parameters stay) and
``command_a_plus_reference.forward`` runs ``--ref-rows`` rows a prompt, whole
sequences in float32 with ``experts_held=(0, 16)``, the windowed layers' ``W_q``
and ``W_k`` permuted to the published interleaved rotary pairs. One line a
comparison; exit code 1 if any limit fails.

**What is compared, and the limits** (the form of ``check_joyai.py``): for each
kept position ``err`` = ||program logits - reference logits|| / ||reference
logits|| over the (sliced) vocabulary, at chunk ends and after steps; and
``prob slack``, the largest probability either side gives a token over what the
other gives it (what a sampler could see). The router takes the top 8 of 128
sigmoid scores, and neighbours lie closer than bfloat16 resolves, so the
reference is given the program's expert choices (``forward(given=...)``) and
the choices themselves are held to the reference's own top-k boundary by
``slack`` (``command_a_plus_reference.route``). PERF.md section 6 has the two
readings each limit lies between: bfloat16 against float32 on three seeds, and
the reference with every weight through float8's three mantissa bits and
through per-channel int8 (``--lower 1``), which must fail at least one.

``--platform cpu`` is a rehearsal at ``command-a-plus-tiny``: it proves the
script, not the model, and its line says so.
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

# Each between its two readings on the chip (PERF.md section 6 has them, per
# seed): bfloat16 against float32, and int8 / float8 weights against float32.
# Probabilities over 32,768 seeded logits are small, so the probability slack
# is too: the program's lies under int8's.
SLACK_MEAN_LIMIT = 0.0001
SLACK_MAX_LIMIT = 0.008
MEDIAN_LIMIT = 0.012
MAX_LIMIT = 0.015
PROB_SLACK_LIMIT = 3e-5
KINDS = {"L": "sliding_attention", "G": "full_attention"}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=12)
    ap.add_argument("--docs", default="700,4880", help="document tokens of the two prompts")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--ref-rows", type=int, default=2)
    ap.add_argument("--lower", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    import command_a_plus_reference as ref
    from k_llms_tpu.engine.paging import flat_slots, pages_for, scatter_rows
    from k_llms_tpu.models.llama import (
        KVCache, init_cache, init_state, paged_verify_step, prefill_chunk_step_paged)
    from k_llms_tpu.serving.app import create_app

    with open(os.path.join(HERE, "configs", "command-a-plus.json")) as f:
        hf = json.load(f)
    serve = dict(hf["serve"])
    docs = [int(d) for d in args.docs.split(",")]
    prefix_tokens = 1024
    if args.platform == "cpu":
        serve.update(model="command-a-plus-tiny", continuous_max_prompt=960)
        docs, prefix_tokens = [d // 8 for d in docs], 96
    platform = jax.devices()[0].platform
    if platform != args.platform:
        sys.exit(f"check_command_a_plus.py: running on {platform!r}, asked for {args.platform!r}")

    t0 = time.monotonic()
    app = create_app(**serve)
    backend = app.client.backend
    engine, loop = backend.engine, backend._continuous
    config = engine.config
    held = (config.expert_offset, config.held_experts)
    if args.platform == "cpu":  # the tiny preset's own sizes, in the published key names
        hf.update(hidden_size=config.hidden_size, num_attention_heads=config.num_heads,
                  num_key_value_heads=config.num_kv_heads, head_dim=config.head_dim,
                  sliding_window=config.sliding_window,
                  num_experts_per_tok=config.num_experts_per_tok,
                  num_shared_experts=config.n_shared_experts)
    assert [KINDS[k] for k in config.layer_pattern] == hf["layer_types"]
    if not loop._built:
        loop._build_device_state()
    pool, W, P = loop._pool, loop.width, loop.max_prompt
    G = loop._pages.gen_idx.shape[1]
    ps, C = pool.page_size, loop.prefill_chunk_tokens
    print(f"built {config.name} on {platform} in {time.monotonic() - t0:.1f}s: "
          f"param_bytes {engine.param_footprint_bytes()}, width {W}, max_prompt {P}, gen slots "
          f"{G}, page {ps}, chunk {C}, cache layers {config.paging_layers} (windows "
          f"{config.layer_windows}), pool pages {pool.allocator.total_pages} "
          f"({pool.pool_bytes()} B), experts held {held}, paged attention "
          f"{loop._paged_attn_impl}", flush=True)
    assert 2 * args.rows <= W

    rng = np.random.default_rng(args.seed)
    span = args.steps + 1
    K = config.num_experts_per_tok

    # 1. chunked prefill through the loop's chunk step, into each prompt's page run.
    def chunk_step(params, chunk_tokens, cache, cursor, valid_len, state):
        aux, state = {"moe_chosen": None}, dict(state)
        return prefill_chunk_step_paged(
            config, params, chunk_tokens, cache, cursor, valid_len, aux=aux, state=state
        ) + (aux, state)

    chunk_fn = jax.jit(chunk_step, donate_argnums=(2,))
    prompts = []  # (tokens, page run, chunk ends, their logits, the router's choices)
    for doc in docs:
        plen = prefix_tokens + doc
        prompt = rng.integers(32, 127, size=plen).astype(np.int32)  # printable bytes, as the cell's text
        _ids, _plen, bucket = engine._prep_prompt([int(t) for t in prompt])
        run_pages = engine._alloc_pages_with_evict(pages_for(plen, ps))
        cache, lane = init_cache(config, 1, bucket), init_state(config, 1)
        ends, logits_at, chosen = [], [], []
        for start in range(0, plen, C):
            valid = min(C, plen - start)
            chunk = np.full((1, C), config.pad_token_id, np.int32)
            chunk[0, :valid] = prompt[start:start + valid]
            slots = flat_slots(run_pages, start + np.arange(C), ps)
            slots[valid:] = (np.arange(C) % ps)[valid:]  # pad positions go to the trash page
            logits, cache, k_cols, v_cols, aux, lane = chunk_fn(
                engine.params, jnp.asarray(chunk), cache, jnp.int32(start), jnp.int32(valid), lane)
            pool.scatter_tokens(k_cols, v_cols, slots)
            logits_at.append(np.asarray(logits[0], np.float32))
            ends.append(start + valid - 1)
            chosen.append(np.asarray(aux["moe_chosen"])[:, :valid])
        del cache
        prompts.append((prompt, run_pages, ends, logits_at, np.concatenate(chosen, axis=1)))
        print(f"prefill: {plen} tokens in {len(ends)} chunks of {C} over a staging cache of "
              f"{bucket} into {len(run_pages)} pages, {k_cols.shape[0]} cache layers a chunk",
              flush=True)
    Le = prompts[0][4].shape[0]

    # 2. rows fan out on the shared prompt pages; teacher-forced steps at the loop's width.
    pidx = np.tile((np.arange(P) % ps).astype(np.int32), (W, 1))
    gidx = np.tile((np.arange(G) % ps).astype(np.int32), (W, 1))
    prompt_lens = np.zeros((W,), np.int32)
    forced = rng.integers(32, 127, size=(2 * args.rows, span)).astype(np.int32)
    owner = []
    for which, (prompt, run_pages, *_rest) in enumerate(prompts):
        plen = len(prompt)
        for _ in range(args.rows):
            r, table = len(owner), list(run_pages)
            if plen % ps:  # the loop's copy-on-write: a private copy of the partial last page
                own = engine._alloc_pages_with_evict(1)
                pool.copy_pages([table[-1]], own)
                table[-1] = own[0]
            table += engine._alloc_pages_with_evict(pages_for(plen + span + 1, ps) - len(table))
            pidx[r] = flat_slots(table, np.arange(P), ps)
            pidx[r, plen:] = (np.arange(P - plen) % ps).astype(np.int32)
            gidx[r] = flat_slots(table, plen + np.arange(G), ps)
            prompt_lens[r] = plen
            owner.append(which)
    rows = np.arange(len(owner))

    def step(params, pool_k, pool_v, cur, gen_lens, prompt_lens, pidx, gidx, write):
        aux = {"moe_chosen": None}
        logits, k_cols, v_cols = paged_verify_step(
            config, params, cur[:, None], gen_lens, prompt_lens, KVCache(k=pool_k, v=pool_v),
            pidx, gidx, attn_impl=loop._paged_attn_impl, page_size=ps, aux=aux)
        return (logits[:, 0],) + scatter_rows(pool_k, pool_v, write, k_cols, v_cols) + (aux,)

    step_fn = jax.jit(step, donate_argnums=(1, 2))
    trash = (np.arange(W) % ps).astype(np.int32)
    step_logits, step_chosen = [], []
    for g in range(args.steps):
        cur = np.full((W,), config.pad_token_id, np.int32)
        cur[rows] = forced[:, g]
        write = trash.copy()
        write[rows] = gidx[rows, g]
        gen_lens = np.zeros((W,), np.int32)
        gen_lens[rows] = g
        with pool.lock:
            logits, new_k, new_v, aux = step_fn(
                engine.params, pool.kv.k, pool.kv.v, jnp.asarray(cur), jnp.asarray(gen_lens),
                jnp.asarray(prompt_lens), jnp.asarray(pidx), jnp.asarray(gidx), jnp.asarray(write))
            pool.kv = KVCache(k=new_k, v=new_v)
        step_logits.append(np.asarray(logits, np.float32)[rows])
        step_chosen.append(np.asarray(aux["moe_chosen"]).reshape(Le, W, K)[:, rows])
    if not all(np.isfinite(a).all() for a in step_logits + [l for p in prompts for l in p[3]]):
        sys.exit("check_command_a_plus.py: the program's logits are not finite")
    print(f"decode: {args.steps} steps at width {W}, {args.rows} rows a prompt on shared pages; "
          f"last step held experts touched "
          f"{100 * (np.asarray(aux['moe_counts']) > 0).mean():.1f}%", flush=True)

    # 3. drop the engine, keep the parameters, run the reference.
    params = engine.params
    stats = jax.devices()[0].memory_stats() or {}
    print(f"allocator peak with the engine up: {stats.get('peak_bytes_in_use')}", flush=True)
    backend.close()
    del app, backend, engine, loop, pool, chunk_fn, step_fn, new_k, new_v, aux, lane
    gc.collect()
    jax.clear_caches()
    # The reference rotates the published interleaved pairs: the windowed
    # layers' W_q and W_k go over with each head's columns in that order.
    permute = jax.jit(ref.interleaved_columns, static_argnums=1)
    params = dict(params, layers=[
        dict(layer, **{name: permute(layer[name], config.head_dim)
                       for name in (("wq", "wk") if kind == "L" else ())})
        for kind, layer in zip(config.layer_pattern, params["layers"])])

    def err(program, reference):
        return float(np.linalg.norm(program - reference) / np.linalg.norm(reference))

    def prob_slack(program, reference):
        p, q = (np.exp(a - a.max()) / np.exp(a - a.max()).sum() for a in (program, reference))
        return float(np.abs(p - q).max())

    ok = True

    def verdict(name, pairs):
        nonlocal ok
        errs = [err(p, q) for p, q in pairs]
        slack = max(prob_slack(p, q) for p, q in pairs)
        med, top = float(np.median(errs)), float(np.max(errs))
        passed = med <= MEDIAN_LIMIT and top <= MAX_LIMIT and slack <= PROB_SLACK_LIMIT
        ok = ok and passed
        print(f"{name}: {len(errs)} positions, err median {med:.4f} (limit {MEDIAN_LIMIT}) max "
              f"{top:.4f} (limit {MAX_LIMIT}), prob slack {slack:.2e} (limit {PROB_SLACK_LIMIT}) "
              f"-> {'ok' if passed else 'FAIL'}", flush=True)

    def sequence(r):
        return np.concatenate([prompts[owner[r]][0], forced[r, :args.steps]])

    def given_for(r):
        """The program's choices along row r's own sequence: the prompt's from
        its chunks, then each step's."""
        plen = len(prompts[owner[r]][0])
        stack = np.concatenate(
            [prompts[owner[r]][4]] + [c[:, r:r + 1] for c in step_chosen], axis=1)
        assert stack.shape == (Le, plen + args.steps, K)
        return [stack[i] for i in range(Le)]

    all_slack, kept = [], {}
    for which, (prompt, _run, ends, chunk_logits, _chosen) in enumerate(prompts):
        plen, step_pairs = len(prompt), []
        for r in [r for r in rows if owner[r] == which][:args.ref_rows]:
            t1 = time.monotonic()
            first = r == which * args.rows  # the prompt's own positions once a prompt
            want = (ends if first else []) + [plen + g for g in range(args.steps)]
            slacks = []
            out = np.asarray(ref.forward(hf, params, sequence(r), experts_held=held,
                                         positions=want, given=given_for(r), slacks=slacks),
                             np.float32)
            at = dict(zip(want, out))
            lo = 0 if first else plen  # the prompt's slack is the same for every row
            all_slack.append(np.concatenate([np.asarray(s)[lo:] for s in slacks]))
            if first:
                kept[which] = (r, want, out)
                verdict(f"prompt of {plen}: chunk ends vs reference (given the program's routing)",
                        [(p, at[q]) for p, q in zip(chunk_logits, ends)])
            pairs = [(step_logits[g][r], at[plen + g]) for g in range(args.steps)]
            step_pairs += pairs
            print(f"  row {r}: {plen + args.steps} tokens, {len(pairs)} step positions err max "
                  f"{max(err(p, q) for p, q in pairs):.4f} ({time.monotonic() - t1:.1f}s of "
                  f"reference)", flush=True)
        verdict(f"prompt of {plen}: paged steps vs reference (given the program's routing)",
                step_pairs)
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
            # A scaled float8_e4m3: its 3 mantissa bits, the exponent left wide.
            return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)

        @jax.jit
        def int8(a):  # symmetric, one scale per output channel (the last axis)
            scale = jnp.max(jnp.abs(a.astype(jnp.float32)), axis=-2, keepdims=True) / 127.0
            return (jnp.round(a / scale).clip(-127, 127) * scale).astype(a.dtype)

        r, want, out0 = kept[0]  # the shorter prompt's first row
        for name, rounding in (("float8 (3 mantissa bits)", float8), ("int8 per channel", int8)):
            def lowered(a, rounding=rounding):
                floating = jnp.issubdtype(a.dtype, jnp.floating)
                return rounding(a) if floating and a.ndim >= 2 else a

            def lower_layer(cfg, p, x, pos, kind, **routed):
                return ref.layer(cfg, {k: lowered(p.pop(k)) for k in list(p)}, x, pos, kind,
                                 **routed)

            low_params = dict(params, embed=lowered(params["embed"]))
            slacks = []
            low = np.asarray(ref.forward(hf, low_params, sequence(r), experts_held=held,
                                         layer_fn=lower_layer, positions=want,
                                         given=given_for(r), slacks=slacks), np.float32)
            del low_params
            both = list(zip(low, out0))
            errs = [err(p, q) for p, q in both]
            slack = np.concatenate([np.asarray(x) for x in slacks])
            med, top = float(np.median(errs)), float(np.max(errs))
            probs = max(prob_slack(p, q) for p, q in both)
            caught = [what for what, over in (
                ("err median", med > MEDIAN_LIMIT), ("err max", top > MAX_LIMIT),
                ("prob slack", probs > PROB_SLACK_LIMIT),
                ("slack mean", slack.mean() > SLACK_MEAN_LIMIT),
                ("slack max", slack.max() > SLACK_MAX_LIMIT)) if over]
            ok = ok and bool(caught)
            print(f"{name} weights vs the float32 reference (same routing): {len(errs)} positions, "
                  f"err median {med:.4f} min {min(errs):.4f} max {top:.4f}, prob slack {probs:.2e}; "
                  f"slack of the program's choices under this router mean {slack.mean():.6f} max "
                  f"{slack.max():.5f} -> "
                  + (f"not correct by {', '.join(caught)}, as it must be" if caught
                     else "PASSES EVERY LIMIT: too loose"), flush=True)

    print(json.dumps({"check": "command-a-plus", "platform": platform, "model": config.name,
                      "seed": args.seed, "prompt_tokens": [len(p[0]) for p in prompts],
                      "steps": args.steps, "ok": bool(ok)}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
