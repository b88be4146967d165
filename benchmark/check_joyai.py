#!/usr/bin/env python3
"""benchmark/check_joyai.py — the drafted loop's programs against the plain reference, on the chip.

    python3 benchmark/check_joyai.py [--seed N] [--doc-tokens N] [--steps N] [--rows N] [--ref-rows N]

After ``check_xing4.py``. Builds ``joyai-llm-flash`` exactly as ``serve.py``
does (``create_app(**config["serve"])``), then drives the model programs the
continuous loop's drafted step is made of, at the cell's sizes and with the
loop's own pool, width, page size and chunk:

1. the loop's chunk step (``prefill_chunk_step_paged`` with the lane's state,
   jitted as ``engine._get_prefill_chunk`` jits it, with the router's choices
   as one more output): one extract-shaped prompt (1,024-token prefix + a
   document) in chunks into latent pages, 9 cache layers (the next-token
   module's rows beside the stack's); every chunk's last-token logits are kept;
2. admission's module step (``paged_draft_step`` at ``Sq == 1``) on
   ``(h_{L-1}, first token)`` for ``--rows`` rows that share the prompt's pages
   (private copy of the last partial page, as the loop's copy-on-write leaves
   it), each with its own first token;
3. ``--steps`` drafted decode steps at the loop's width: ``paged_verify_step``
   at ``Sq == 2`` over ``[cur, draft]`` and ``paged_draft_step`` at ``Sq == 2``,
   with the step's own scatter (``write_drafted_rows``), each row forced
   along its own tokens. Rows alternate, step by step, between a draft that
   is the row's next token
   (accepted: the row moves two positions, and the second position's logits
   and the module's second pair are compared too) and one that is not
   (rejected: the row moves one, and what the step wrote at P+1 and, for the
   module, P+2 is stale). Every later step's logits are compared, so a stale
   row that was read would show.

Then the engine is dropped (the parameters stay) and
``joyai_reference.forward`` runs ``--ref-rows`` of the rows' whole sequences in
float32 with ``experts_held=(0, 128)``. One line a comparison; exit code 1 if
any limit fails.

**What is compared, and the limits** (the form of ``check_xing4.py``): for each
kept position ``err`` = ||program logits - reference logits|| / ||reference
logits|| over the whole vocabulary, for the logits after ``cur``, the logits
after an accepted draft, and the module's logits; and ``prob slack``, the
largest probability either side gives a token over what the other gives it
(what a sampler could see). The router takes the top 8 of 256 sigmoid scores,
and neighbours lie closer than bfloat16 resolves, so the reference is given
the program's expert choices (``forward(given=...)``) and the choices
themselves are held to the reference's own top-k boundary by ``slack``
(``joyai_reference.route``). PERF.md section 6 has the two readings each limit
lies between: bfloat16 against float32 on seeds 12-14, and the reference with
every weight through float8's three mantissa bits and through per-channel int8
(``--lower 1``), which must fail at least one.

``--platform cpu`` is a rehearsal at ``joyai-tiny``: it proves the script, not
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

# Each between its two readings on the chip (PERF.md section 6 has them, per
# seed): bfloat16 against float32, and int8 / float8 weights against float32.
# Probabilities over 129,280 seeded logits are small (the largest is ~4e-4),
# so the probability slack is too: the program's lies under int8's ~1e-4.
SLACK_MEAN_LIMIT = 0.0005
SLACK_MAX_LIMIT = 0.015
MEDIAN_LIMIT = 0.03
MAX_LIMIT = 0.04
PROB_SLACK_LIMIT = 5e-5


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=12)
    ap.add_argument("--doc-tokens", type=int, default=500)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--ref-rows", type=int, default=4)
    ap.add_argument("--lower", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    import joyai_reference as ref
    from k_llms_tpu.engine.continuous import write_drafted_rows
    from k_llms_tpu.engine.paging import flat_slots, pages_for
    from k_llms_tpu.models.llama import (
        KVCache, init_cache, init_state, paged_draft_step, paged_verify_step,
        prefill_chunk_step_paged)
    from k_llms_tpu.serving.app import create_app

    with open(os.path.join(HERE, "configs", "joyai-llm-flash.json")) as f:
        hf = json.load(f)
    serve = dict(hf["serve"])
    if args.platform == "cpu":
        serve["model"] = "joyai-tiny"
    platform = jax.devices()[0].platform
    if platform != args.platform:
        sys.exit(f"check_joyai.py: running on {platform!r}, asked for {args.platform!r}")

    t0 = time.monotonic()
    app = create_app(**serve)
    backend = app.client.backend
    engine, loop = backend.engine, backend._continuous
    config = engine.config
    hf["n_routed_experts"] = config.num_experts  # the router's width: the file's is the held 128
    held = (config.expert_offset, config.held_experts)
    if args.platform == "cpu":  # the tiny preset's own sizes, in the published key names
        hf.update(hidden_size=config.hidden_size, num_attention_heads=config.num_heads,
                  q_lora_rank=config.q_lora_rank, kv_lora_rank=config.kv_lora_rank,
                  qk_nope_head_dim=config.qk_nope_head_dim,
                  qk_rope_head_dim=config.qk_rope_head_dim, v_head_dim=config.v_head_dim,
                  num_experts_per_tok=config.num_experts_per_tok,
                  num_hidden_layers=config.num_layers)
    if not loop._built:
        loop._build_device_state()
    pool, W, P = loop._pool, loop.width, loop.max_prompt
    G = loop._pages.gen_idx.shape[1]
    ps, C = pool.page_size, loop.prefill_chunk_tokens
    print(f"built {config.name} on {platform} in {time.monotonic() - t0:.1f}s: "
          f"param_bytes {engine.param_footprint_bytes()}, width {W}, max_prompt {P}, gen slots "
          f"{G}, page {ps}, chunk {C}, cache layers {config.paging_layers}, pool pages "
          f"{pool.allocator.total_pages} ({pool.pool_bytes()} B), experts held {held}", flush=True)

    rng = np.random.default_rng(args.seed)
    prefix_tokens = 1024 if args.platform != "cpu" else 96
    plen = prefix_tokens + args.doc_tokens
    span = 2 * args.steps + 3
    prompt = rng.integers(32, 127, size=plen).astype(np.int32)  # printable bytes, as the cell's text
    forced = rng.integers(32, 127, size=(args.rows, span)).astype(np.int32)
    _ids, _plen, bucket = engine._prep_prompt([int(t) for t in prompt])

    # 1. chunked prefill through the loop's chunk step, into the prompt's page run.
    def chunk_step(params, chunk_tokens, cache, cursor, valid_len, state):
        aux, state = {"moe_chosen": None}, dict(state)
        return prefill_chunk_step_paged(
            config, params, chunk_tokens, cache, cursor, valid_len, aux=aux, state=state
        ) + (aux, state)

    run_pages = engine._alloc_pages_with_evict(pages_for(plen, ps))
    chunk_fn = jax.jit(chunk_step, donate_argnums=(2,))
    cache, lane = init_cache(config, 1, bucket), init_state(config, 1)
    chunk_logits, chunk_pos, chunk_chosen = [], [], []
    for start in range(0, plen, C):
        valid = min(C, plen - start)
        chunk = np.full((1, C), config.pad_token_id, np.int32)
        chunk[0, :valid] = prompt[start:start + valid]
        slots = flat_slots(run_pages, start + np.arange(C), ps)
        slots[valid:] = (np.arange(C) % ps)[valid:]  # pad positions go to the trash page
        logits, cache, k_cols, v_cols, aux, lane = chunk_fn(
            engine.params, jnp.asarray(chunk), cache, jnp.int32(start), jnp.int32(valid), lane)
        pool.scatter_tokens(k_cols, v_cols, slots)
        chunk_logits.append(np.asarray(logits[0], np.float32))
        chunk_pos.append(start + valid - 1)
        chunk_chosen.append(np.asarray(aux["moe_chosen"])[:, :valid])
    del cache
    prompt_chosen = np.concatenate(chunk_chosen, axis=1)  # [expert layers of the stack, plen, K]
    Le, K = prompt_chosen.shape[0], prompt_chosen.shape[2]
    print(f"prefill: {len(chunk_pos)} chunks of {C} into {len(run_pages)} pages, "
          f"{k_cols.shape[0]} cache layers a chunk", flush=True)

    # 2. rows fan out on the shared prompt pages; admission's module step.
    tables = []
    for _ in range(args.rows):
        table = list(run_pages)
        if plen % ps:  # the loop's copy-on-write: a private copy of the partial last page
            own = engine._alloc_pages_with_evict(1)
            pool.copy_pages([table[-1]], own)
            table[-1] = own[0]
        table += engine._alloc_pages_with_evict(pages_for(plen + span + 2, ps) - len(table))
        tables.append(table)
    pidx = np.tile((np.arange(P) % ps).astype(np.int32), (W, 1))
    gidx = np.tile((np.arange(G) % ps).astype(np.int32), (W, 1))
    prompt_lens = np.zeros((W,), np.int32)
    for r, table in enumerate(tables):
        pidx[r] = flat_slots(table, np.arange(P), ps)
        pidx[r, plen:] = (np.arange(P - plen) % ps).astype(np.int32)
        gidx[r] = flat_slots(table, plen + np.arange(G), ps)
        prompt_lens[r] = plen
    rows = np.arange(args.rows)

    def admit(params, pool_k, pool_v, h_last, tok0, prompt_lens, pidx, gidx, write):
        aux = {"moe_counts": jnp.zeros((0, held[1]), jnp.int32),
               "moe_chosen": jnp.zeros((0, W, K), jnp.int32)}
        mlogits, m_cols = paged_draft_step(
            config, params, h_last[:, None], tok0[:, None], jnp.zeros_like(prompt_lens),
            prompt_lens, KVCache(k=pool_k, v=pool_v), pidx, gidx, aux=aux)
        return (mlogits[:, 0], write_drafted_rows(pool_k, None, m_cols, write[:, None]),
                aux["moe_chosen"])

    def step(params, pool_k, pool_v, cur, draft, nxt, gen_lens, prompt_lens, pidx, gidx, write):
        aux = {"moe_chosen": None}
        pool = KVCache(k=pool_k, v=pool_v)
        logits, k_cols, _, hidden = paged_verify_step(
            config, params, jnp.stack([cur, draft], axis=1), gen_lens, prompt_lens, pool, pidx,
            gidx, attn_impl=loop._paged_attn_impl, page_size=ps, aux=aux, return_hidden=True)
        mlogits, m_cols = paged_draft_step(
            config, params, hidden, nxt, gen_lens + 1, prompt_lens, pool, pidx, gidx, aux=aux)
        return logits, mlogits, write_drafted_rows(pool_k, k_cols, m_cols, write), aux

    trash = (np.arange(W) % ps).astype(np.int32)
    first = np.full((W,), config.pad_token_id, np.int32)
    first[rows] = forced[:, 0]
    write = trash.copy()
    write[rows] = gidx[rows, 0]
    h_last = jnp.broadcast_to(lane["mtp_h"][0], (W, config.hidden_size))
    admit_fn, step_fn = jax.jit(admit, donate_argnums=(1,)), jax.jit(step, donate_argnums=(1,))
    with pool.lock:
        mlogits, new_k, chosen = admit_fn(
            engine.params, pool.kv.k, pool.kv.v, h_last, jnp.asarray(first),
            jnp.asarray(prompt_lens), jnp.asarray(pidx), jnp.asarray(gidx), jnp.asarray(write))
        pool.kv = KVCache(k=new_k, v=pool.kv.v)
    # Per row and sequence position p: what the program computed there.
    main_at = [dict() for _ in rows]   # p -> logits after the token at p
    mod_at = [dict() for _ in rows]    # i -> module logits of the pair (h_i, t_{i+1})
    main_chosen = [dict() for _ in rows]  # p -> [Le, K]
    mod_chosen = [dict() for _ in rows]   # i -> [K]
    mlogits, chosen = np.asarray(mlogits, np.float32), np.asarray(chosen)
    for r in rows:
        mod_at[r][plen - 1] = mlogits[r]
        mod_chosen[r][plen - 1] = chosen[-1, r]

    # 3. drafted steps, rows alternating between an accepted and a rejected draft.
    g = np.zeros((W,), np.int32)
    for t in range(args.steps):
        accept = np.array([(r + t) % 2 == 0 for r in rows])
        cur, draft = first.copy(), first.copy()
        nxt = np.full((W, 2), config.pad_token_id, np.int32)
        write = np.tile(trash[:, None], (1, 3))
        for r in rows:
            cur[r] = forced[r, g[r]]
            draft[r] = forced[r, g[r] + 1] if accept[r] else (forced[r, g[r] + 1] - 32 + 7) % 95 + 32
            nxt[r] = forced[r, g[r] + 1:g[r] + 3]
            write[r] = gidx[r, g[r]:g[r] + 3]
        with pool.lock:
            logits, mlogits, new_k, aux = step_fn(
                engine.params, pool.kv.k, pool.kv.v, jnp.asarray(cur), jnp.asarray(draft),
                jnp.asarray(nxt), jnp.asarray(g), jnp.asarray(prompt_lens), jnp.asarray(pidx),
                jnp.asarray(gidx), jnp.asarray(write))
            pool.kv = KVCache(k=new_k, v=pool.kv.v)
        logits, mlogits = np.asarray(logits, np.float32), np.asarray(mlogits, np.float32)
        chosen = np.asarray(aux["moe_chosen"]).reshape(Le + 1, W, 2, K)
        for r in rows:
            for j in range(2 if accept[r] else 1):
                p = plen + g[r] + j
                main_at[r][p], mod_at[r][p] = logits[r, j], mlogits[r, j]
                main_chosen[r][p], mod_chosen[r][p] = chosen[:Le, r, j], chosen[Le, r, j]
            g[r] += 2 if accept[r] else 1
    if not all(np.isfinite(v).all() for d in main_at + mod_at for v in d.values()):
        sys.exit("check_joyai.py: the program's logits are not finite")
    print(f"decode: {args.steps} drafted steps at width {W}, {args.rows} rows on shared pages, "
          f"rows moved {[int(x) for x in g[rows]]} positions; last step latent rows read "
          f"{int(aux['mla_latent_rows_read'])}, held experts touched "
          f"{100 * (np.asarray(aux['moe_counts']) > 0).mean():.1f}%", flush=True)

    # 4. drop the engine, keep the parameters, run the reference.
    params = engine.params
    stats = jax.devices()[0].memory_stats() or {}
    print(f"allocator peak with the engine up: {stats.get('peak_bytes_in_use')}", flush=True)
    backend.close()
    del app, backend, engine, loop, pool, chunk_fn, step_fn, admit_fn, new_k, aux, lane, h_last
    gc.collect()
    jax.clear_caches()

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
        return errs

    def sequence(r):
        return np.concatenate([prompt, forced[r, :g[r] + 1]])

    def given_for(r, S):
        """The program's choices along row r's own sequence: the prompt's, then
        each position's from the step that computed it; the module's pairs
        inside the prompt never ran an expert in the program (-1: the
        reference's own)."""
        stack = np.full((Le, S, K), -1, np.int32)
        stack[:, :plen] = prompt_chosen
        module = np.full((S - 1, K), -1, np.int32)
        for p, c in main_chosen[r].items():
            stack[:, p] = c
        for i, c in mod_chosen[r].items():
            if i < S - 1:
                module[i] = c
        return [stack[i] for i in range(Le)] + [module]

    all_slack, first_ref = [], None
    pairs = {"after cur or an accepted draft": [], "the module's": []}
    for r in rows[:args.ref_rows]:
        t1 = time.monotonic()
        tokens = sequence(r)
        S = len(tokens)
        mains = sorted(p for p in main_at[r] if p < S)
        mods = sorted(i for i in mod_at[r] if i < S - 1)
        want = sorted(set((chunk_pos if r == 0 else []) + mains + [i + 1 for i in mods]))
        slacks = []
        out, mout = ref.forward(hf, params, tokens, experts_held=held, positions=want,
                                given=given_for(r, S), slacks=slacks)
        out, mout = np.asarray(out, np.float32), np.asarray(mout, np.float32)
        at = {p: out[k] for k, p in enumerate(want)}
        mat = {p - 1: mout[k] for k, p in enumerate([p for p in want if p >= 1])}
        # The prompt's slack is the same for every row: count it once.
        lo = 0 if r == 0 else plen - 1
        all_slack.append(np.concatenate([np.asarray(s)[lo:] for s in slacks]))
        if r == 0:
            first_ref = (want, out, mout)
            verdict("chunk ends vs reference (given the program's routing)",
                    [(p, at[q]) for p, q in zip(chunk_logits, chunk_pos)])
        row_main = [(main_at[r][p], at[p]) for p in mains]
        row_mod = [(mod_at[r][i], mat[i]) for i in mods]
        pairs["after cur or an accepted draft"] += row_main
        pairs["the module's"] += row_mod
        print(f"  row {r}: {len(tokens)} tokens, {len(row_main)} verified positions err max "
              f"{max(err(p, q) for p, q in row_main):.4f}, {len(row_mod)} module positions err max "
              f"{max(err(p, q) for p, q in row_mod):.4f} ({time.monotonic() - t1:.1f}s of reference)",
              flush=True)
    for name, got in pairs.items():
        verdict(f"drafted steps, logits {name} vs reference (given the program's routing)", got)
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

        tokens = sequence(0)
        want, out0, mout0 = first_ref
        for name, rounding in (("float8 (3 mantissa bits)", float8), ("int8 per channel", int8)):
            def lowered(a, rounding=rounding):
                floating = jnp.issubdtype(a.dtype, jnp.floating)
                return rounding(a) if floating and a.ndim >= 2 else a

            def lower_layer(cfg, p, x, pos, **routed):
                return ref.layer(cfg, {k: lowered(p.pop(k)) for k in list(p)}, x, pos, **routed)

            low_params = dict(params, embed=lowered(params["embed"]),
                              lm_head=lowered(params["lm_head"]),
                              mtp=dict(params["mtp"], eh_proj=lowered(params["mtp"]["eh_proj"])))
            slacks = []
            low, mlow = ref.forward(hf, low_params, tokens, experts_held=held, layer_fn=lower_layer,
                                    positions=want, given=given_for(0, len(tokens)), slacks=slacks)
            del low_params
            both = list(zip(np.asarray(low, np.float32), out0)) + list(
                zip(np.asarray(mlow, np.float32), mout0))
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

    print(json.dumps({"check": "joyai-llm-flash", "platform": platform, "model": config.name,
                      "seed": args.seed, "prompt_tokens": plen, "steps": args.steps,
                      "ok": bool(ok)}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
