#!/usr/bin/env python3
"""benchmark/check_nemotron3.py — the loop's own programs against the plain reference, on the chip.

    python3 benchmark/check_nemotron3.py [--seed N] [--steps N] [--ref-rows N] [--lower 1]

(``--ref-rows``: how many of the 32 rows the reference runs, 8 unless said; all
32 rows go through the programs either way.)

Builds ``nemotron3-nano-30b-a3b`` exactly as ``serve.py`` does
(``create_app(**config["serve"])``), then drives the programs the continuous
loop runs, at the cell's sizes and with the loop's own pool, width, chunk,
state arrays and paged attention choice:

1. **prefill, both ways**: 32 distinct chat-shaped prompts (32-480 tokens; one
   of 480 and one of 100 always among them). A prompt over one chunk goes
   through the chunk step (``prefill_chunk_step_paged``, jitted as
   ``engine._get_prefill_chunk`` jits it, with the router's choices as one more
   output): 128-token chunks, the last one padded, the lane's recurrent state
   carried from chunk to chunk. A prompt of at most one chunk goes through
   whole-prompt admission's program (``prefill`` at its padded bucket). Either
   way the keys and values land in the attention layer's pages, the final
   state is installed into the row by the loop's own ``_install_state``, and
   the last token's logits are kept;
2. **decode at width 32**: ``--steps`` steps through ``paged_verify_step`` with
   the state donated through the step, every row forced along its own tokens;
   every step's logits are kept;
3. **the fork**: one 300-token prompt's state installed into 8 rows that
   share its pages (a private copy of the partial last page, as the loop's
   copy-on-write leaves it) and then take different tokens for 24 steps, the
   other 24 slots idle: an idle slot's state must come back bit for bit.

Then the engine is dropped (the parameters stay) and
``nemotron3_reference.forward`` runs each row's whole sequence in float32,
layer by layer, op by op, the state-space layers as the sequential recurrence.
One line a comparison; exit code 1 if any limit fails.

**What is compared, and the limits** (PERF.md §6 has the readings each lies
between). ``err`` = ||program logits - reference logits|| / ||reference
logits|| over the whole vocabulary, never sampled tokens. As for xing4, the
router's 6th and 7th scores lie closer than bfloat16 resolves at some
token-layers, so the reference is given the program's expert choices
(``forward(given=...)``; its weights are still its own scores) and the choices
are held to the reference's own top-k boundary by their ``slack``:

- ``MEDIAN_LIMIT``, ``MAX_LIMIT``: ``err`` over a comparison's positions (all
  prompt ends; all decode steps of all rows; the fork's steps);
- ``SLACK_MEAN_LIMIT``, ``SLACK_MAX_LIMIT``: the program's choices against the
  reference's own, over all token-layers;
- ``STATE_REPLAY_LIMIT``: the logits cannot tell a float32 state from a
  bfloat16 one (the state's rounding adds under a percent to an ``err`` of
  several, in quadrature). So the state update is held on its own: the step
  hands out what each Mamba-2 layer's update consumed (``dt``, ``x``, ``B`` as
  the program computed them), the recurrence is replayed from the installed
  state in float64 on the host for the first rows, and the program's final
  state after ``--steps`` steps must agree with the replay to this relative
  error. It reads ~3e-5 for the float32 state and ~7e-3 for a bfloat16 one.

``--lower 1`` adds the readings that must fail: the reference with every
weight rounded to float8_e4m3's three mantissa bits and through
per-output-channel int8, against itself in float32 under the same routing, and
the decode run again with the state kept in bfloat16.

``--platform cpu`` is a rehearsal at ``nemotron3-tiny``: it proves the script,
not the model, and its line says so.
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
# bfloat16 against float32 read err median 0.0100-0.0113 / max 0.0107-0.0129
# and slack mean 0.00005 / max 0.0049-0.0059; int8 weights read 0.043 / 0.047-0.048
# (min 0.037) and 0.00093-0.00098 / 0.021-0.023; float8 mantissas higher still.
# The state's replay reads 2.5e-5-3.0e-5 in float32 and 6.1e-3-7.1e-3 kept in bfloat16.
SLACK_MEAN_LIMIT = 0.00022
SLACK_MAX_LIMIT = 0.011
MEDIAN_LIMIT = 0.022
MAX_LIMIT = 0.025
STATE_REPLAY_LIMIT = 4e-4
TAP_ROWS = 4  # rows whose state updates are replayed on the host
FORK_ROWS, FORK_PROMPT, FORK_STEPS = 8, 300, 24


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=96)
    # A row of reference takes ~45 s on the chip (op by op, 128 experts a layer).
    ap.add_argument("--ref-rows", type=int, default=8)
    ap.add_argument("--lower", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    import nemotron3_reference as ref
    from k_llms_tpu.engine.paging import flat_slots, pages_for
    from k_llms_tpu.models.llama import (
        KVCache, init_cache, init_state, paged_verify_step, prefill,
        prefill_chunk_step_paged)
    from k_llms_tpu.serving.app import create_app
    from k_llms_tpu.utils.observability import KERNEL_EVENTS

    with open(os.path.join(HERE, "configs", "nemotron3-nano-30b-a3b.json")) as f:
        hf = json.load(f)
    serve = dict(hf["serve"])
    if args.platform == "cpu":
        serve["model"] = "nemotron3-tiny"
    platform = jax.devices()[0].platform
    if platform != args.platform:
        sys.exit(f"check_nemotron3.py: running on {platform!r}, asked for {args.platform!r}")

    t0 = time.monotonic()
    app = create_app(**serve)
    backend = app.client.backend
    engine, loop = backend.engine, backend._continuous
    config = engine.config
    if args.platform == "cpu":  # the tiny preset's own sizes, in the published key names
        hf.update(hidden_size=config.hidden_size, num_attention_heads=config.num_heads,
                  num_key_value_heads=config.num_kv_heads, head_dim=config.head_dim,
                  mamba_num_heads=config.mamba_num_heads, mamba_head_dim=config.mamba_head_dim,
                  n_groups=config.mamba_n_groups, ssm_state_size=config.ssm_state_size,
                  n_routed_experts=config.num_experts,
                  num_experts_per_tok=config.num_experts_per_tok,
                  moe_intermediate_size=config.moe_intermediate_size)
    if not loop._built:
        loop._build_device_state()
    pool, W, P, G = loop._pool, loop.width, loop.max_prompt, loop.max_new
    ps, C = pool.page_size, loop.prefill_chunk_tokens  # the loop's own: 128 at width 32
    n_m = config.layer_pattern.count("M")
    rows = W
    print(f"built {config.name} on {platform} in {time.monotonic() - t0:.1f}s: "
          f"param_bytes {engine.param_footprint_bytes()}, width {W}, max_prompt {P}, "
          f"max_new {G}, page {ps}, chunk {C}, pool pages {pool.allocator.total_pages} "
          f"({pool.pool_bytes()} B), state {loop.stats['state_bytes']} B, paged attention "
          f"{loop._paged_attn_impl!r}, kernel events {KERNEL_EVENTS.snapshot()}", flush=True)
    if args.steps > G:
        sys.exit(f"check_nemotron3.py: --steps {args.steps} over the loop's max_new {G}")

    rng = np.random.default_rng(args.seed)
    plens = [480, 100] + [int(n) for n in rng.integers(32, 481, size=rows - 2)]
    prompts = [rng.integers(32, 127, size=n).astype(np.int32) for n in plens]
    forced = rng.integers(32, 127, size=(rows, args.steps)).astype(np.int32)

    # -- the three programs, each with the router's choices as one more output ----------
    def chunk_step(params, chunk_tokens, cache, state, cursor, valid_len):
        aux = {"moe_chosen": None}
        state = dict(state)
        return prefill_chunk_step_paged(
            config, params, chunk_tokens, cache, cursor, valid_len, aux=aux, state=state
        ) + (aux, state)

    def whole_prompt(params, tokens, prompt_len):
        aux, state = {"moe_chosen": None}, {}
        return prefill(config, params, tokens, prompt_len, aux=aux, state=state) + (aux, state)

    def step(params, pool_k, pool_v, state, cur, gen_lens, prompt_lens, active, pidx, gidx):
        aux = {"moe_chosen": None, "ssm_inputs": None}
        state = dict(state)
        logits, k_cols, v_cols = paged_verify_step(
            config, params, cur[:, None], jnp.where(active, gen_lens, 0),
            jnp.where(active, prompt_lens, 0), KVCache(k=pool_k, v=pool_v), pidx, gidx,
            attn_impl=loop._paged_attn_impl, page_size=ps, aux=aux, state=state, active=active)
        aux["ssm_inputs"] = [{k: v[:TAP_ROWS, 0] for k, v in s.items()} for s in aux["ssm_inputs"]]
        return logits[:, 0, :], k_cols, v_cols, aux, state

    chunk_fn = jax.jit(chunk_step, donate_argnums=(2, 3))
    whole_fn = jax.jit(whole_prompt)
    step_fn = jax.jit(step, donate_argnums=(3,))

    def prefill_prompt(prompt):
        """One prompt through the program its length takes -> (its pages, the
        last token's logits, the router's choices [E layers, plen, K], the
        lane's final state)."""
        plen = len(prompt)
        _ids, _plen, bucket = engine._prep_prompt([int(t) for t in prompt])
        if plen <= C:
            tokens = np.full((1, bucket), config.pad_token_id, np.int32)
            tokens[0, :plen] = prompt
            logits, prefix, aux, state = whole_fn(engine.params, jnp.asarray(tokens), jnp.int32(plen))
            with engine._paged_mutex:
                run = engine._run_from_dense(prefix, plen, bucket)
            return (list(run.pages), np.asarray(logits[0], np.float32),
                    np.asarray(aux["moe_chosen"])[:, :plen], state)
        pages = engine._alloc_pages_with_evict(pages_for(plen, ps))
        cache, state, chosen = init_cache(config, 1, bucket), init_state(config, 1), []
        for start in range(0, plen, C):
            valid = min(C, plen - start)
            chunk = np.full((1, C), config.pad_token_id, np.int32)
            chunk[0, :valid] = prompt[start:start + valid]
            slots = flat_slots(pages, start + np.arange(C), ps)
            slots[valid:] = (np.arange(C) % ps)[valid:]  # pad positions go to the trash page
            logits, cache, k_cols, v_cols, aux, state = chunk_fn(
                engine.params, jnp.asarray(chunk), cache, state, jnp.int32(start), jnp.int32(valid))
            pool.scatter_tokens(k_cols, v_cols, slots)
            chosen.append(np.asarray(aux["moe_chosen"])[:, :valid])
        return pages, np.asarray(logits[0], np.float32), np.concatenate(chosen, axis=1), state

    def tables(row_pages, row_plens, steps):
        pidx = np.tile((np.arange(P) % ps).astype(np.int32), (W, 1))
        gidx = np.tile((np.arange(G) % ps).astype(np.int32), (W, 1))
        lens = np.zeros((W,), np.int32)
        for r, (table, plen) in enumerate(zip(row_pages, row_plens)):
            table += engine._alloc_pages_with_evict(pages_for(plen + steps, ps) - len(table))
            pidx[r] = flat_slots(table, np.arange(P), ps)
            pidx[r, plen:] = (np.arange(P - plen) % ps).astype(np.int32)
            gidx[r] = flat_slots(table, plen + np.arange(G), ps)
            lens[r] = plen
        return pidx, gidx, lens

    def decode(state, live, tokens, pidx, gidx, lens, steps):
        """``steps`` steps at width W with rows ``live`` active -> (logits
        [live, steps, V], choices a step [E layers, W, K], the update's inputs a
        step, the final state)."""
        out = np.zeros((live, steps, config.vocab_size), np.float32)
        active = np.arange(W) < live
        chosen, inputs = [], []
        for t in range(steps):
            cur = np.full((W,), config.pad_token_id, np.int32)
            cur[:live] = tokens[:, t]
            write = (np.arange(W) % ps).astype(np.int32)  # idle rows write into the trash page
            write[:live] = gidx[:live, t]
            logits, k_cols, v_cols, aux, state = step_fn(
                engine.params, pool.kv.k, pool.kv.v, state, jnp.asarray(cur),
                jnp.full((W,), t, jnp.int32), jnp.asarray(lens), jnp.asarray(active),
                jnp.asarray(pidx), jnp.asarray(gidx))
            pool.scatter_tokens(k_cols, v_cols, write)
            out[:, t] = np.asarray(logits[:live], np.float32)
            chosen.append(np.asarray(aux["moe_chosen"]))
            inputs.append(jax.device_get(aux["ssm_inputs"]))
        return out, chosen, inputs, state, aux

    # 1. prefill every row, install its state with the loop's own copy.
    t1 = time.monotonic()
    row_pages, first_logits, prompt_chosen = [], [], []
    for r, prompt in enumerate(prompts):
        pages, logits, chosen, lane = prefill_prompt(prompt)
        loop._install_state([r], lane)
        row_pages.append(pages)
        first_logits.append(logits)
        prompt_chosen.append(chosen)
    pidx, gidx, lens = tables(row_pages, plens, args.steps)
    chunked = sum(n > C for n in plens)
    print(f"prefill: {rows} prompts of {min(plens)}-{max(plens)} tokens, {chunked} through the "
          f"chunk step ({C}-token chunks, state carried), {rows - chunked} through whole-prompt "
          f"admission; {time.monotonic() - t1:.1f}s", flush=True)

    # 2. decode at the loop's width, the state donated through the step.
    state0 = jax.tree.map(jnp.copy, loop._state)
    t1 = time.monotonic()
    step_logits, step_chosen, inputs32, final32, aux = decode(
        loop._state, rows, forced, pidx, gidx, lens, args.steps)
    loop._state = None  # donated
    counts = np.asarray(aux["moe_counts"])
    print(f"decode: {args.steps} steps at width {W} in {time.monotonic() - t1:.1f}s (first call "
          f"compiles); last step experts touched {100 * (counts > 0).mean():.1f}% "
          f"({(counts > 0).sum(axis=1).tolist()} of {config.num_experts} a layer), states "
          f"advanced {int(aux['ssm_rows_updated'])}", flush=True)
    final32 = jax.device_get({k: [a[:TAP_ROWS] for a in v] for k, v in final32.items()})
    start32 = jax.device_get({k: [a[:TAP_ROWS] for a in v] for k, v in state0.items()})
    if args.lower:
        state16 = dict(state0, ssm=tuple(a.astype(jnp.bfloat16) for a in state0["ssm"]))
        start16 = jax.device_get([a[:TAP_ROWS].astype(jnp.float32) for a in state16["ssm"]])
        _, _, inputs16, final16, _ = decode(state16, rows, forced, pidx, gidx, lens, args.steps)
        final16 = jax.device_get([a[:TAP_ROWS].astype(jnp.float32) for a in final16["ssm"]])
    del state0

    # 3. the fork: one prompt's state into FORK_ROWS rows on shared pages, the rest idle.
    fork_prompt = rng.integers(32, 127, size=FORK_PROMPT).astype(np.int32)
    fork_forced = rng.integers(32, 127, size=(FORK_ROWS, FORK_STEPS)).astype(np.int32)
    pages, fork_first, fork_prompt_chosen, lane = prefill_prompt(fork_prompt)
    loop._state = init_state(config, W)
    idle = init_state(config, 1)
    idle = jax.tree.map(lambda a: a + 1, idle)  # a last tenant's leftovers in the idle slots
    loop._install_state(list(range(FORK_ROWS, W)), idle)
    loop._install_state(list(range(FORK_ROWS)), lane)
    fork_tables = []
    for _ in range(FORK_ROWS):
        table = list(pages)
        if FORK_PROMPT % ps:  # the loop's copy-on-write: a private copy of the partial last page
            own = engine._alloc_pages_with_evict(1)
            pool.copy_pages([table[-1]], own)
            table[-1] = own[0]
        fork_tables.append(table)
    f_pidx, f_gidx, f_lens = tables(fork_tables, [FORK_PROMPT] * FORK_ROWS, FORK_STEPS)
    fork_logits, fork_chosen, _, fork_final, aux = decode(
        loop._state, FORK_ROWS, fork_forced, f_pidx, f_gidx, f_lens, FORK_STEPS)
    loop._state = None
    idle_same = all(bool(jnp.all(a[FORK_ROWS:] == b)) for a, b in zip(
        jax.tree.leaves(fork_final), jax.tree.leaves(idle)))
    print(f"fork: {FORK_PROMPT}-token prompt into {FORK_ROWS} rows on shared pages, "
          f"{FORK_STEPS} steps; states advanced a step {int(aux['ssm_rows_updated'])} "
          f"(= {FORK_ROWS} x {n_m}); the {W - FORK_ROWS} idle slots' state unchanged bit for "
          f"bit: {idle_same}", flush=True)
    finite = all(np.isfinite(a).all() for a in (step_logits, fork_logits, np.stack(first_logits)))
    if not finite:
        sys.exit("check_nemotron3.py: the program's logits are not finite")
    ok = idle_same and int(aux["ssm_rows_updated"]) == FORK_ROWS * n_m

    # -- the state update replayed on the host --------------------------------------------
    def replay(start, inputs, final):
        """float64 recurrence from ``start`` over the program's own inputs ->
        the largest relative error of a layer's final state."""
        worst = 0.0
        m_layers = [p for kind, p in zip(config.layer_pattern, engine.params["layers"]) if kind == "M"]
        for m, layer in enumerate(m_layers):
            A = -np.exp(np.asarray(layer["A_log"], np.float64)).reshape(
                config.mamba_n_groups, -1)  # [G, R]
            S = np.asarray(start[m], np.float64).reshape(
                TAP_ROWS, *A.shape, config.mamba_head_dim, config.ssm_state_size)
            for step_inputs in inputs:
                dt, x, Bm = (np.asarray(step_inputs[m][k], np.float64) for k in ("dt", "x", "B"))
                S = (np.exp(dt * A)[..., None, None] * S
                     + (dt[..., None] * x)[..., None] * Bm[:, :, None, None, :])
            got = np.asarray(final[m], np.float64).reshape(S.shape)
            worst = max(worst, float(np.linalg.norm(got - S) / np.linalg.norm(S)))
        return worst

    r32 = replay(start32["ssm"], inputs32, final32["ssm"])
    passed = r32 <= STATE_REPLAY_LIMIT
    ok = ok and passed
    print(f"state after {args.steps} steps vs its float64 replay ({TAP_ROWS} rows, {n_m} "
          f"layers): relative error {r32:.3e} (limit {STATE_REPLAY_LIMIT}) -> "
          f"{'ok' if passed else 'FAIL'}", flush=True)
    if args.lower:
        r16 = replay(start16, inputs16, final16)
        caught = r16 > STATE_REPLAY_LIMIT
        ok = ok and caught
        print(f"the state kept in bfloat16, after {args.steps} steps vs its float64 replay: "
              f"relative error {r16:.3e} -> "
              + ("not correct by the state limit, as it must be" if caught
                 else "PASSES THE STATE LIMIT: too loose"), flush=True)

    # -- drop the engine, keep the parameters, run the reference ------------------------------
    params = engine.params
    stats = jax.devices()[0].memory_stats() or {}
    print(f"allocator peak with the engine up: {stats.get('peak_bytes_in_use')}", flush=True)
    backend.close()
    del app, backend, engine, loop, pool, chunk_fn, whole_fn, step_fn, aux, lane, idle, fork_final
    gc.collect()
    jax.clear_caches()

    def err(program, reference):
        reference = np.asarray(reference, np.float32)
        return float(np.linalg.norm(program - reference) / np.linalg.norm(reference))

    def verdict(name, errs):
        nonlocal ok
        med, top = float(np.median(errs)), float(np.max(errs))
        passed = med <= MEDIAN_LIMIT and top <= MAX_LIMIT
        ok = ok and passed
        print(f"{name}: {len(errs)} positions, err median {med:.4f} (limit {MEDIAN_LIMIT}) min "
              f"{min(errs):.4f} max {top:.4f} (limit {MAX_LIMIT}) -> {'ok' if passed else 'FAIL'}",
              flush=True)

    def given_for(prompt_part, steps_chosen, r):  # a row's whole sequence: prompt, then steps
        own = np.stack([c[:, r] for c in steps_chosen], axis=1)  # [E layers, steps, K]
        return np.concatenate([prompt_part, own], axis=1)

    def compare(name, rows_, prompts_, forced_, firsts, prompt_parts, steps_chosen, logits, steps):
        end_errs, step_errs, slack_all, thirds = [], [], [], [[], [], []]
        for r in range(rows_):
            t1 = time.monotonic()
            plen = len(prompts_[r])
            tokens = np.concatenate([prompts_[r], forced_[r]])
            want, slacks = plen - 1 + np.arange(steps + 1), []
            out = np.asarray(ref.forward(
                hf, params, tokens, positions=want,
                given=given_for(prompt_parts[r], steps_chosen, r), slacks=slacks), np.float32)
            slack = np.stack([np.asarray(s) for s in slacks])
            slack_all.append(slack.reshape(-1))
            end_errs.append(err(firsts[r], out[0]))
            errs = [err(logits[r, t], out[t + 1]) for t in range(steps)]
            step_errs += errs
            for i in range(3):
                thirds[i] += errs[i * steps // 3:(i + 1) * steps // 3]
            print(f"  {name} row {r} ({plen} tokens): prompt end err {end_errs[-1]:.4f}; decode err "
                  f"first {errs[0]:.4f} median {np.median(errs):.4f} last {errs[-1]:.4f} max "
                  f"{max(errs):.4f}; slack max {float(slack.max()):.5f}, sets differ at "
                  f"{100 * float((slack > 0).mean()):.2f}% ({time.monotonic() - t1:.1f}s)", flush=True)
        verdict(f"{name}: prompt ends vs reference (given the program's routing)", end_errs)
        verdict(f"{name}: decode steps vs reference (given the program's routing)", step_errs)
        print(f"  {name}: decode err median by third of the steps "
              f"{' '.join(f'{np.median(t):.4f}' for t in thirds)}", flush=True)
        return np.concatenate(slack_all)

    n_ref = min(args.ref_rows, rows)
    slack = [compare("chat rows", n_ref, prompts, forced, first_logits, prompt_chosen,
                     step_chosen, step_logits, args.steps)]
    slack.append(compare("fork rows", min(FORK_ROWS, max(2, n_ref // 4)),
                         [fork_prompt] * FORK_ROWS, fork_forced, [fork_first] * FORK_ROWS,
                         [fork_prompt_chosen] * FORK_ROWS, fork_chosen, fork_logits, FORK_STEPS))
    slack = np.concatenate(slack)
    passed = slack.mean() <= SLACK_MEAN_LIMIT and slack.max() <= SLACK_MAX_LIMIT
    ok = ok and passed
    print(f"the program's expert choices vs the reference's own top-k: {slack.size} token-layers, "
          f"sets differ at {100 * (slack > 0).mean():.2f}%, slack mean {slack.mean():.6f} (limit "
          f"{SLACK_MEAN_LIMIT}) max {slack.max():.5f} (limit {SLACK_MAX_LIMIT}) -> "
          f"{'ok' if passed else 'FAIL'}", flush=True)

    if args.lower:
        @jax.jit  # fused: the eager chain would hold three float32 copies of a table
        def float8(a):
            # A scaled float8_e4m3: its 3 mantissa bits, the exponent left wide.
            return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)

        @jax.jit
        def int8(a):  # symmetric, one scale per output channel (the last axis)
            scale = jnp.max(jnp.abs(a.astype(jnp.float32)), axis=-2, keepdims=True) / 127.0
            scale = jnp.where(scale == 0, 1.0, scale)  # w_up's zero columns
            return (jnp.round(a / scale).clip(-127, 127) * scale).astype(a.dtype)

        tokens = np.concatenate([prompts[0], forced[0]])
        want = len(prompts[0]) - 1 + np.arange(args.steps + 1)
        given = given_for(prompt_chosen[0], step_chosen, 0)
        first = np.asarray(ref.forward(hf, params, tokens, positions=want, given=given), np.float32)
        for name, rounding in (("float8 (3 mantissa bits)", float8), ("int8 per channel", int8)):
            def lowered(a, rounding=rounding):
                floating = jnp.issubdtype(a.dtype, jnp.floating)
                return rounding(a) if floating and a.ndim >= 2 else a

            def lower_layer(cfg, kind, p, x, **routed):
                # One array at a time, dropping the original slice.
                return ref.layer(cfg, kind, {k: lowered(p.pop(k)) for k in list(p)}, x, **routed)

            low_params = dict(params, embed=None, lm_head=None)
            for table in ("embed", "lm_head"):
                low_params[table] = lowered(params[table])
            slacks = []
            low = np.asarray(ref.forward(hf, low_params, tokens, layer_fn=lower_layer,
                                         positions=want, given=given, slacks=slacks), np.float32)
            del low_params
            errs = [err(p, q) for p, q in zip(low, first)]
            low_slack = np.stack([np.asarray(x) for x in slacks])
            med, top = float(np.median(errs)), float(np.max(errs))
            caught = [what for what, over in (
                ("err median", med > MEDIAN_LIMIT), ("err max", top > MAX_LIMIT),
                ("slack mean", low_slack.mean() > SLACK_MEAN_LIMIT),
                ("slack max", low_slack.max() > SLACK_MAX_LIMIT)) if over]
            ok = ok and bool(caught)
            print(f"{name} weights vs the float32 reference (same routing): {len(errs)} positions, "
                  f"err median {med:.4f} min {min(errs):.4f} max {top:.4f}; slack of the program's "
                  f"choices under this router mean {low_slack.mean():.6f} max {low_slack.max():.5f} -> "
                  + (f"not correct by {', '.join(caught)}, as it must be" if caught
                     else "PASSES EVERY LIMIT: too loose"), flush=True)

    print(json.dumps({"check": "nemotron3-nano-30b-a3b", "platform": platform, "model": config.name,
                      "seed": args.seed, "rows": rows, "steps": args.steps, "ok": bool(ok)}),
          flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
