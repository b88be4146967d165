"""One conformance suite for the served model families: every check that each
model owes the same way, over the rows of tests/served_models.py (one tiny
preset a family), on the CPU in float32.

Each row names its plain reference (``benchmark/<name>_reference.py``, the
file the benchmark's chip check runs), which runs compiled here
(``served_models.reference``); tolerances are the row's, and each model's own
file says why. A check that applies to some rows only runs for each of them:
the n-way fork and the idle slot for every model with recurrent state, the
routing counters for every model with routed experts.

To add a served model: write its plain reference in ``benchmark/``, add one
``Served`` row to tests/served_models.py (its reference, its published
configuration's key names, its counter arithmetic, sizes, refusals and cell)
and its source's config.json as one line of tests/published_configs.jsonl,
and give it a file of its own only for checks no other model has. Nothing
else in the tests changes.
"""

import asyncio
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, benchmark_module, shared_engine, shared_loop, shared_params
from k_llms_tpu.models import get_config, llama
from k_llms_tpu.models.llama import KVCache
from served_models import PUBLISHED, ROWS, SMALL_SCHEMA, param_count, reference

TOKENS = np.random.RandomState(0).randint(0, 500, (2, 72)).astype(np.int32)


def cases(attr):
    """pytest params of (preset, *case) over each row's ``attr`` cases."""
    return [pytest.param(p, *case, id="-".join(str(x) for x in (p, *case) if isinstance(x, (str, int))))
            for p, row in ROWS.items() for case in getattr(row, attr)]


def close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=0)


def leaves(state):
    return [np.asarray(a) for a in jax.tree.leaves(state)]


def grew_since(before, counters):
    return {k: v - before.get(k, 0) for k, v in counters.snapshot().items()}


forward = jax.jit(llama.forward, static_argnums=0, static_argnames="with_module")


@functools.lru_cache(maxsize=None)
def program_logits(preset, length):
    """``llama.forward`` over both rows of TOKENS[:, :length]: the logits, and
    a drafting model's module logits beside them."""
    cfg = ROWS[preset].cfg
    seq = jnp.asarray(TOKENS[:, :length])
    out = forward(cfg, shared_params(cfg), seq, jnp.ones(seq.shape, jnp.int32),
                  with_module=bool(cfg.num_nextn_predict_layers))
    return out[0], (out[2] if cfg.num_nextn_predict_layers else None)


# -- the program against the reference ---------------------------------------------------

@pytest.mark.parametrize("row", [0, 1])
@pytest.mark.parametrize("length", [7, 72])  # inside command-a-plus's window of 12, and six windows long
@pytest.mark.parametrize("preset", ROWS)
def test_forward_matches_reference(preset, length, row):
    served = ROWS[preset]
    logits, module = program_logits(preset, length)
    want = reference(served, shared_params(served.cfg), TOKENS[row, :length])
    if module is None:
        close(logits[row], want, served.tol)
    else:
        close(logits[row], want[0], served.tol)
        close(module[row], want[1], served.tol)


@pytest.mark.parametrize("preset", [p for p, row in ROWS.items() if row.forward_moves])
def test_forward_moves_with_what_the_block_is_made_of(preset):
    """The comparison sees each piece the row names: each change of
    ``forward_moves`` (to the configuration or to the tree) takes the logits
    past ``moved_by`` of the reference's, each of ``forward_holds`` leaves
    them within the tolerance."""
    served = ROWS[preset]
    cfg, params = served.cfg, shared_params(served.cfg)
    seq = jnp.asarray(TOKENS[:1, :40])
    want = reference(served, params, seq[0])
    want = np.asarray(want[0] if cfg.num_nextn_predict_layers else want)

    def moved(changes=None, tree=None):
        config = cfg.with_(**changes) if changes else cfg
        logits, _ = forward(config, tree(params) if tree else params, seq, jnp.ones(seq.shape, jnp.int32))
        return float(np.abs(np.asarray(logits[0]) - want).max())

    assert moved() < served.tol
    for change in served.forward_moves:
        assert moved(*change) > served.moved_by, change
    for change in served.forward_holds:
        assert moved(*change) < served.tol, change


# -- the loop's programs by hand -------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def chunk_program(preset):
    cfg = ROWS[preset].cfg

    @jax.jit
    def chunk(params, toks, cache, start, valid, state):
        aux, state = {}, dict(state)
        return llama.prefill_chunk_step_paged(cfg, params, toks, cache, start, valid, aux=aux,
                                              state=state) + (aux, state)
    return chunk


def check_aux(served, aux, routed, scanned, rows=1):
    """What a program's ``aux`` holds: expert loads over the held experts for
    every routed layer, and the state counters of the row's recurrent kind."""
    cfg = served.cfg
    if served.moe_layers:
        assert aux["moe_counts"].shape == (served.moe_layers, cfg.held_experts)
        if cfg.held_experts == cfg.num_experts:
            assert int(aux["moe_counts"].sum()) == served.moe_layers * routed * cfg.num_experts_per_tok
    else:
        assert "moe_counts" not in aux
    for kind in ("ssm", "gdn"):
        if served.state and served.state[0] == kind:
            n = cfg.layer_pattern.count(served.state[1])
            assert int(aux[f"{kind}_rows_updated"]) == rows * n
            if scanned is not None:
                assert int(aux[f"{kind}_tokens_scanned"]) == n * scanned
        else:
            assert f"{kind}_rows_updated" not in aux


def prefill_in_chunks(served, params, seq, plen, chunk, bucket, pool, slot_of, want):
    """The lane's turns over one prompt: chunks into a staging cache and the
    pool's slots (``slot_of`` maps positions to them), each chunk's last-token
    logits against ``want``, the last chunk padded where ``chunk`` does not
    divide ``plen``. Returns the pool and the state after the prompt."""
    cfg = served.cfg
    heads, kw, vw = cfg.cache_widths
    cache, state = llama.init_cache(cfg, 1, bucket), llama.init_state(cfg, 1)
    assert cache.k.shape == (cfg.paging_layers, 1, bucket, heads, kw)
    for start in range(0, plen, chunk):
        valid = min(chunk, plen - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :valid] = seq[start:start + valid]
        logits, cache, k_cols, v_cols, aux, state = chunk_program(served.preset)(
            params, jnp.asarray(toks), cache, jnp.int32(start), jnp.int32(valid), state)
        check_aux(served, aux, chunk, valid)
        assert set(state) == set(cfg.state_shapes(1))
        close(logits[0], want[start + valid - 1], served.tol)
        slots = slot_of(start + np.arange(valid))
        pool = KVCache(k=pool.k.at[:, slots].set(k_cols[:, :valid]),
                       v=pool.v.at[:, slots].set(v_cols[:, :valid]))
    assert k_cols.shape == (cfg.paging_layers, chunk, heads, kw) and v_cols.shape[-1] == vw
    return pool, state


def empty_pool(cfg, flat):
    heads, kw, vw = cfg.cache_widths
    return KVCache(k=jnp.zeros((cfg.paging_layers, flat, heads, kw)),
                   v=jnp.zeros((cfg.paging_layers, flat, heads, vw)))


def one_token_steps(served, params, chunk, attn_impl):
    """One prompt of 50 on its pages, then ``paged_verify_step`` for five
    steps, three rows reading the shared prompt pages and their own slots; a
    recurrent model's rows each from their own copy of the state, the third
    idle: it must neither move nor be right."""
    cfg = served.cfg
    want, seq, plen, ps, rows = reference(served, params, TOKENS[1]), TOKENS[1], 50, 8, 3
    pool, state = prefill_in_chunks(served, params, seq, plen, chunk, 64, empty_pool(cfg, 40 * 8),
                                    lambda at: ps + at, want)  # page 0 is the trash page
    P, G = 64, 8
    pidx = np.tile(np.arange(P) % ps, (rows, 1)).astype(np.int32)
    pidx[:, :plen] = ps + np.arange(plen)
    gidx = np.stack([100 + 10 * r + np.arange(G) for r in range(rows)]).astype(np.int32)
    state = jax.tree.map(lambda a: jnp.repeat(a, rows, axis=0), state)
    live = 2 if served.state else rows
    active = jnp.arange(rows) < live if served.state else None

    @jax.jit
    def step(params, cur, lengths, pool, state):
        aux, state = {}, dict(state)
        return llama.paged_verify_step(
            cfg, params, cur, lengths, jnp.full((rows,), plen), pool, jnp.asarray(pidx),
            jnp.asarray(gidx), aux=aux, state=state, active=active, attn_impl=attn_impl,
            page_size=ps) + (aux, state)

    for g in range(5):
        before = leaves(state)
        logits, k_cols, v_cols, aux, state = step(
            params, jnp.full((rows, 1), seq[plen + g]), jnp.full((rows,), g), pool, state)
        check_aux(served, aux, rows, None, rows=live)
        if cfg.is_latent:
            assert int(aux["mla_latent_rows_read"]) == rows * (plen + g + 1) * cfg.num_layers
        pool = KVCache(k=pool.k.at[:, gidx[:, g]].set(k_cols), v=pool.v.at[:, gidx[:, g]].set(v_cols))
        for row in range(live):
            close(logits[row, 0], want[plen + g], served.tol)
        for old, new in zip(before, leaves(state)):
            np.testing.assert_array_equal(old[2], new[2])  # the idle row's state, bit for bit
            assert not np.array_equal(old[0], new[0])
    if served.state:  # the idle row stopped at the prompt
        assert np.abs(np.asarray(logits[2, 0]) - want[plen + 4]).max() > served.moved_by


def drafted_steps(served, params, chunk, attn_impl):
    """The drafted loop's programs: a prompt of 50 into a pool of the stack's
    cache layers and the module's, admission's module step on (h_{L-1}, first
    token), then four steps that verify two positions a row, teacher-forced;
    rows alternate between an accepted and a rejected draft, and a rejected
    draft's rows (the stack's at P+1, the module's at P+2) are overwritten with
    garbage after the step: the next step never reads them."""
    cfg = served.cfg
    (want, want_mod), seq, plen, ps, rows = reference(served, params, TOKENS[1]), TOKENS[1], 50, 8, 3
    L = cfg.num_layers
    assert cfg.paging_layers == L + 1
    pool, state = prefill_in_chunks(served, params, seq, plen, chunk, 64, empty_pool(cfg, 40 * 8),
                                    lambda at: ps + at, want)
    P, G = 64, 12
    pidx = np.tile(np.arange(P) % ps, (rows, 1)).astype(np.int32)
    pidx[:, :plen] = ps + np.arange(plen)
    gidx = np.stack([100 + 16 * r + np.arange(G) for r in range(rows)]).astype(np.int32)
    plens = jnp.full((rows,), plen)

    @jax.jit
    def verify(params, toks, g, pool):
        aux = {}
        logits, k_cols, _, hidden = llama.paged_verify_step(
            cfg, params, toks, g, plens, pool, jnp.asarray(pidx), jnp.asarray(gidx), aux=aux,
            attn_impl=attn_impl, return_hidden=True)
        return logits, k_cols, hidden, aux

    @jax.jit
    def draft(params, hidden, toks, g, pool, aux):
        aux = dict(aux)
        return llama.paged_draft_step(cfg, params, hidden, toks, g, plens, pool, jnp.asarray(pidx),
                                      jnp.asarray(gidx), aux=aux) + (aux,)

    # Admission: the module's row at position L, and the logits behind the first draft.
    h_last = jnp.tile(state["mtp_h"][0], (rows, 1))
    mlogits, m_cols, _ = draft(params, h_last[:, None], jnp.full((rows, 1), seq[plen]),
                               jnp.zeros((rows,), jnp.int32), pool, {})
    pool = KVCache(k=pool.k.at[L, gidx[:, 0]].set(m_cols[:, 0]), v=pool.v)
    for row in range(rows):
        close(mlogits[row, 0], want_mod[plen - 1], served.tol)
    g = np.zeros((rows,), np.int32)
    for step in range(4):
        accept = np.array([(r + step) % 2 == 0 for r in range(rows)])
        at = plen + g
        drafts = np.where(accept, seq[at + 1], (seq[at + 1] + 7) % 500)
        logits, k_cols, hidden, aux = verify(
            params, jnp.stack([jnp.asarray(seq[at]), jnp.asarray(drafts)], axis=1), jnp.asarray(g), pool)
        assert k_cols.shape == (L, rows, 2, 1, 40)
        mlogits, m_cols, aux = draft(params, hidden, jnp.asarray(np.stack([seq[at + 1], seq[at + 2]], axis=1)),
                                     jnp.asarray(g + 1), pool, aux)
        assert aux["moe_counts"].shape == (served.moe_layers + 1, cfg.held_experts)  # the module's layer too
        k = pool.k
        for r in range(rows):
            close(logits[r, 0], want[at[r]], served.tol)
            close(mlogits[r, 0], want_mod[at[r]], served.tol)
            k = k.at[:L, gidx[r, g[r]:g[r] + 2]].set(k_cols[:, r])
            k = k.at[L, gidx[r, g[r] + 1:g[r] + 3]].set(m_cols[r])
            if accept[r]:
                close(logits[r, 1], want[at[r] + 1], served.tol)
                close(mlogits[r, 1], want_mod[at[r] + 1], served.tol)
            else:  # stale rows: the next step overwrites them before anything reads them
                k = k.at[:L, gidx[r, g[r] + 1]].set(1e3)
                k = k.at[L, gidx[r, g[r] + 2]].set(1e3)
        pool = KVCache(k=k, v=pool.v)
        g = g + np.where(accept, 2, 1).astype(np.int32)


def _pages(plen, span, ps, next_page):
    count = -(-(plen + span) // ps)
    return list(range(next_page, next_page + count)), next_page + count


def windowed_steps(served, params, chunk, attn_impl):
    """Two prompts, one inside the window of 12 (5 tokens) and one past it
    (21, so chunks end on both sides of the window's edge), each in chunks
    into its pages; then two rows a prompt on the prompt's shared pages, the
    last partial page copied for each (the loop's copy-on-write), continuing
    with different tokens; 14 teacher-forced steps through the pool, so the
    short prompt's rows cross the window's edge mid-way and every row crosses
    page boundaries, each layer under its own window."""
    cfg = served.cfg
    ps, L, steps, P, G = 8, cfg.paging_layers, 14, 32, 16
    flat = 40 * ps
    pool = empty_pool(cfg, flat)
    next_page, rows = 1, []  # page 0 is the trash page
    for prompt_no, plen in enumerate((5, 21)):
        base = TOKENS[prompt_no]
        seqs = [base[:plen + steps + 1].copy() for _ in range(2)]
        seqs[1][plen:] = (seqs[1][plen:] + 7) % 500  # the second row's own continuation
        want = [np.asarray(reference(served, params, s)) for s in seqs]
        run, next_page = _pages(plen, 0, ps, next_page)
        pool, state = prefill_in_chunks(served, params, base, plen, chunk, 32, pool,
                                        lambda at: np.asarray(run)[at // ps] * ps + at % ps, want[0])
        for seq, logits_want in zip(seqs, want):
            table = list(run)
            if plen % ps:  # a private copy of the shared partial page
                own, next_page = next_page, next_page + 1
                src = np.arange(ps) + table[-1] * ps
                pool = KVCache(k=pool.k.at[:, own * ps + np.arange(ps)].set(pool.k[:, src]),
                               v=pool.v.at[:, own * ps + np.arange(ps)].set(pool.v[:, src]))
                table[-1] = own
            more, next_page = _pages(0, plen + G - len(table) * ps, ps, next_page)
            rows.append((plen, seq, logits_want, table + more))
    assert next_page * ps <= flat
    B = len(rows)
    pidx = np.tile(np.arange(P) % ps, (B, 1)).astype(np.int32)
    gidx = np.zeros((B, G), np.int32)
    for b, (plen, _, _, table) in enumerate(rows):
        at = np.arange(plen)
        pidx[b, :plen] = np.asarray(table)[at // ps] * ps + at % ps
        at = plen + np.arange(G)
        gidx[b] = np.asarray(table)[at // ps] * ps + at % ps
    plens = jnp.asarray([r[0] for r in rows])

    @jax.jit
    def step(cur, lengths, pool):
        aux = {}
        return llama.paged_verify_step(
            cfg, params, cur[:, None], lengths, plens, pool, jnp.asarray(pidx),
            jnp.asarray(gidx), attn_impl=attn_impl, page_size=ps, aux=aux) + (aux,)

    for g in range(steps):
        cur = np.asarray([seq[plen + g] for plen, seq, _, _ in rows], np.int32)
        logits, k_cols, v_cols, aux = step(jnp.asarray(cur), jnp.full((B,), g), pool)
        assert k_cols.shape == (L, B, cfg.num_kv_heads, cfg.head_dim)
        check_aux(served, aux, B, None)
        for b, (plen, _, want, _) in enumerate(rows):
            close(logits[b, 0], want[plen + g], served.tol)
        pool = KVCache(k=pool.k.at[:, gidx[:, g]].set(k_cols),
                       v=pool.v.at[:, gidx[:, g]].set(v_cols))


STEPS = {"one": one_token_steps, "drafted": drafted_steps, "windowed": windowed_steps}


@pytest.mark.parametrize("preset,chunk,attn_impl", cases("chunk_cases"))
def test_chunked_prefill_then_paged_steps_match_reference(preset, chunk, attn_impl):
    """The loop's programs by hand, compiled: a prompt in the lane's chunks
    into a staging cache and the pool's pages (every chunk's last-token logits
    against the reference, the last chunk padded), then the row's own steps
    through the pool (``one_token_steps``, ``drafted_steps`` or
    ``windowed_steps``), on the XLA path or the interpreted paged kernel."""
    served = ROWS[preset]
    STEPS[served.steps](served, shared_params(served.cfg), chunk, attn_impl)


# -- through the continuous loop ------------------------------------------------------------

def loop_for(served, ladder=()):
    return shared_loop(served.preset, kv_page_size=served.page_size, width=8, max_prompt=256,
                       max_new=16, eos_ids=[257], prefill_chunk_tokens=32, prefill_chunk_ladder=ladder)


@pytest.mark.parametrize("preset,plen,ladder,turns", cases("n8"))
def test_n8_forks_one_state_into_eight_rows_that_diverge(preset, plen, ladder, turns):
    """n = 8 sampled rows of one prompt (100 tokens: four chunks of 32, the
    last padded; 20: whole-prompt admission; on the ladder 200 and 161: a turn
    of 128, then the state carried into a padded turn of 128 or of 64): pages
    are shared, the state is forked, the rows take different tokens, and every
    row's log-probabilities are the reference's along that row's own tokens.
    The counters add up: rows x state layers a step, one row a lane turn."""
    from k_llms_tpu.utils.observability import LATENCY, MODEL_COUNTERS

    served = ROWS[preset]
    cfg, params = served.cfg, shared_params(served.cfg)
    kind, letter = served.state
    prompt = [int(t) for t in np.random.RandomState(1).randint(0, 250, plen)]
    loop = loop_for(served, ladder)
    before, installs = MODEL_COUNTERS.snapshot(), LATENCY.snapshot()["continuous.state_install"]["count"]
    got = loop.submit(prompt, n=8, max_new=10, temperature=1.0, top_p=1.0, seed=5).result(timeout=300)
    stats = loop.stats
    grew = grew_since(before, MODEL_COUNTERS)
    toks, lps = np.asarray(got.tokens)[:, :10], np.asarray(got.logprobs)[:, :10]
    lengths = np.asarray(got.lengths)  # a row that samples the stop token ends early
    if served.n8_whole:
        assert (lengths == 10).all()
    assert len({tuple(r) for r in toks.tolist()}) > 4  # they diverged
    for row in range(8):
        n = int(lengths[row])
        seq = np.asarray(prompt + toks[row, :n].tolist(), np.int32)
        logits = reference(served, params, seq, positions=plen - 1 + np.arange(n))
        logits = logits.at[:, cfg.pad_token_id].set(-jnp.inf)  # the loop never samples pad
        want = np.asarray(jax.nn.log_softmax(logits, axis=-1))[np.arange(n), toks[row, :n]]
        np.testing.assert_allclose(lps[row, :n], want, atol=served.lp_tol, rtol=0)
    layers, steps = cfg.layer_pattern.count(letter), int((lengths - 1).sum())
    assert grew[f"{kind}_state_updates"] == (turns + steps) * layers
    assert grew[f"{kind}_tokens_scanned"] == (plen + steps) * layers
    other = {"ssm": "gdn", "gdn": "ssm"}[kind]
    assert grew.get(f"{other}_state_updates", 0) == grew.get("mla_latent_rows_read", 0) == 0
    assert grew.get("moe_layer_calls", 0) == (turns + int(lengths.max()) - 1) * served.moe_layers
    assert LATENCY.snapshot()["continuous.state_install"]["count"] == installs + 1
    assert stats["state_bytes"] == 8 * cfg.state_bytes_per_row and stats["pages"]["in_use"] == 0


@pytest.mark.parametrize("preset", [p for p, row in ROWS.items() if row.state])
def test_an_idle_slots_state_is_unchanged_and_a_reused_slot_equals_a_fresh_one(preset):
    """A release does no device work, so a slot keeps its last tenant's state
    until admission overwrites it: the steps of a later request move its own
    slot's state and no other's, and what it generates on a used loop is what
    it generates on a fresh one, bit for bit."""
    from k_llms_tpu.engine.continuous import ContinuousDecodeLoop

    served = ROWS[preset]
    rs = np.random.RandomState(2)
    first = [int(t) for t in rs.randint(0, 250, 70)]
    second = [int(t) for t in rs.randint(0, 250, 45)]
    kw = dict(max_new=8, temperature=0.7, top_p=0.9)
    loop = loop_for(served)
    fresh = ContinuousDecodeLoop(loop.engine, width=8, max_prompt=256, max_new=16, eos_ids=[257],
                                 prefill_chunk_tokens=32)
    try:
        want = fresh.submit(second, n=1, seed=11, **kw).result(timeout=300)
    finally:
        fresh.stop()
    loop.submit(first, n=8, seed=3, **kw).result(timeout=300)  # every slot has had a tenant
    used = leaves(loop._state)
    got = loop.submit(second, n=1, seed=11, **kw).result(timeout=300)
    after = leaves(loop._state)
    np.testing.assert_array_equal(np.asarray(got.tokens), np.asarray(want.tokens))
    np.testing.assert_array_equal(np.asarray(got.logprobs), np.asarray(want.logprobs))
    assert len(used) == 2 * served.cfg.layer_pattern.count(served.state[1])
    for old, new in zip(used, after):
        moved = [slot for slot in range(8) if not np.array_equal(old[slot], new[slot])]
        assert len(moved) == 1  # the one slot the second request took; seven idle ones untouched
        assert np.abs(old).max() > 0


@pytest.mark.parametrize("preset", ROWS)
def test_the_front_door_serves_it_and_health_and_metrics_carry_its_arithmetic(preset):
    """``create_app`` -> the continuous loop, n = 1 and n > 1 under a schema
    (the row's cell's, or a small one); no fallback counter moves;
    ``/healthz`` carries the parameters' bytes, the cache's and the state's
    arithmetic, and ``/metrics`` the state's bytes, the install span and the
    row's model counters."""
    from k_llms_tpu import KLLMs
    from k_llms_tpu.backends.tpu import TpuBackend
    from k_llms_tpu.serving.app import create_app
    from k_llms_tpu.utils.observability import (FAILURE_EVENTS, GRAMMAR_EVENTS, KERNEL_EVENTS,
                                                MODEL_COUNTERS)

    served = ROWS[preset]
    cfg, door = served.cfg, served.front_door
    schema = door.get("schema", SMALL_SCHEMA)
    # The suite's eight virtual devices would make a mesh, which is refused: hand over an engine.
    backend = TpuBackend(model=preset, continuous_batching=True, continuous_max_prompt=door["max_prompt"],
                         engine=shared_engine(cfg, kv_layout="paged", kv_page_size=64))
    app = create_app(client=KLLMs(backend=backend, model=preset))
    client = app.client
    before = {**FAILURE_EVENTS.snapshot(), **GRAMMAR_EVENTS.snapshot(), **KERNEL_EVENTS.snapshot()}
    counted = MODEL_COUNTERS.snapshot()
    try:
        kw = dict(messages=[{"role": "user", "content": door["prompt"]}], model=preset, seed=7,
                  max_tokens=door["max_tokens"], temperature=0.8)
        one = client.chat.completions.create(n=1, **kw)
        many = client.chat.completions.create(n=door["n"], response_format={
            "type": "json_schema", "json_schema": {"name": "d", "schema": schema}}, **kw)
        health = client.backend.health()
        sent = []

        async def send(message):
            sent.append(message)

        asyncio.run(app._metrics({}, None, send, {}))
    finally:
        client.backend.close()
    assert len(one.choices) == 1 and len(many.choices) == door["n"] + 1
    for choice in many.choices:
        answer = json.loads(choice.message.content)
        assert set(answer) == set(schema["required"])
        assert all(answer[k] in p["enum"] for k, p in schema["properties"].items() if "enum" in p)
    after = {**FAILURE_EVENTS.snapshot(), **GRAMMAR_EVENTS.snapshot(), **KERNEL_EVENTS.snapshot()}
    assert not {k for k in after if "fallback" in k and after[k] != before.get(k, 0)}
    width = health["continuous"]["width"]
    assert health["hbm"]["state_bytes"] == width * cfg.state_bytes_per_row
    assert health["hbm"]["kv_bytes_per_token"] == cfg.kv_bytes_per_token == dict(
        (p, t) for p, t, _ in served.pools)[preset]
    if door.get("counted_params", True):  # float32 here
        assert health["hbm"]["param_bytes"] == 4 * param_count(cfg)
    assert health["continuous"]["admitted"] == 2
    body = b"".join(m.get("body", b"") for m in sent).decode()
    lines = dict(line.rsplit(" ", 1) for line in body.splitlines()
                 if line and not line.startswith("#") and "{" not in line)
    assert float(lines["kllms_continuous_state_bytes"]) == health["hbm"]["state_bytes"]
    assert float(lines["kllms_hbm_state_bytes"]) == health["hbm"]["state_bytes"]
    if served.state:
        assert float(lines["kllms_continuous_state_install_seconds_count"]) >= 2
    grew = grew_since(counted, MODEL_COUNTERS)  # the counters are the process's
    assert all(float(lines[f"kllms_{name}"]) >= grew[name] > 0 for name in served.counters)
    if served.moe_layers:
        assert 0 < grew["moe_experts_touched"] <= grew["moe_layer_calls"] * cfg.held_experts


# -- sizes, bytes and refusals ---------------------------------------------------------------

@pytest.mark.parametrize("preset,name,want", cases("param_counts"))
def test_parameter_count_from_shapes(preset, name, want):
    assert param_count(get_config(name)) == want


@pytest.mark.parametrize("preset", [p for p, row in ROWS.items() if row.published])
def test_published_preset_holds_the_published_sizes(preset):
    """The served cut is the published preset with the depth (and what
    ``reduced`` names) cut, in the configuration file's own key names: the
    keys where the file differs from its source's config.json are exactly
    ``reduced``, and the whole preset is the source's config.json, and what
    the file lists as ``published``."""
    served = ROWS[preset]
    p = served.published
    with open(os.path.join(ROOT, "benchmark", "configs", f"{p['config']}.json")) as f:
        hf = json.load(f)
    full, cut = get_config(p["config"]), get_config(hf["serve"]["model"])
    sizes = p.get("sizes", {})  # file key -> the cut's attribute, where the names differ
    mine = {k: v for k, v in served.hf(cut).items() if k not in sizes}
    assert mine == {k: hf[k] for k in mine}
    assert {k: getattr(cut, name) for k, name in sizes.items()} == {k: hf[k] for k in sizes}
    assert full.with_(name=cut.name, **p["cut"]) == cut
    source = PUBLISHED[hf["source"]]
    assert {k for k, v in source.items() if hf.get(k) != v} == set(hf["reduced"])
    mine = served.hf(full)
    assert {k: mine[k] for k in mine if k in source} == {k: source[k] for k in mine if k in source}
    compared = set(hf["published"]) & set(mine) - set(p.get("described", ()))
    assert compared and {k: hf["published"][k] for k in compared} == {k: mine[k] for k in compared}
    assert hf["reduced"] == list(p["reduced"])
    assert cut.attn_scale == pytest.approx(p["attn_scale"]) and cut.dtype == "bfloat16"
    assert {k: hf[k] for k in p.get("file", {})} == p.get("file", {})
    if "also" in p:
        p["also"](hf, full, cut)


@pytest.mark.parametrize("preset,name,per_token,per_row", cases("pools"))
def test_pool_and_memory_model_count_paging_layers_and_the_rows_state(preset, name, per_token, per_row):
    from k_llms_tpu.backends.tpu import HbmMemoryModel
    from k_llms_tpu.engine.paging import PagedKVPool

    cfg = get_config(name)
    model = HbmMemoryModel(cfg, param_bytes=0, hbm_bytes=16 << 30)
    assert model.kv_bytes_per_token == cfg.kv_bytes_per_token == per_token
    assert model.describe()["kv_bytes_per_token"] == per_token
    assert cfg.state_bytes_per_row == per_row
    assert model.row_margin_bytes == 4 * cfg.vocab_size + (64 << 10) + per_row
    if cfg.num_layers <= 9:  # a 20- or 28-layer pool is not worth building here
        pool = PagedKVPool(cfg.with_(vocab_size=512), total_pages=3, page_size=16)
        assert pool.pool_bytes() == 3 * 16 * per_token
        heads, k_width, v_width = cfg.cache_widths
        assert pool.kv.k.shape[0] == cfg.paging_layers == llama.init_cache(cfg, 2, 8).k.shape[0]
        assert pool.kv.k.shape[2:] == (heads, cfg.pool_row_width) and pool.kv.v.shape[2:] == (heads, v_width)
        assert cfg.pool_row_width >= k_width and (cfg.pool_row_width % 128 == 0 or not cfg.is_latent)
    rows = model.paged_max_rows(2048, 256, 64, fanout=8)
    assert rows == model.budget_bytes() // (
        4 * 64 * per_token + -(-32 * 64 * per_token // 8) + model.row_margin_bytes)
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(jax.eval_shape(
        lambda: llama.init_state(cfg, 5)))) == 5 * per_row
    state = ROWS[preset].state
    if state and name in (preset, *(p for p, _ in ROWS[preset].param_counts)):
        shapes = cfg.state_shapes(3)
        assert len(shapes) == 2 and shapes[state[0]][0] == cfg.layer_pattern.count(state[1])


def refusal(what, cfg, params, tmp_path, page_size):
    """The call that names what ``cfg`` cannot ride."""
    from k_llms_tpu.backends.tpu import TpuBackend
    from k_llms_tpu.engine.engine import GenRequestSpec, LocalEngine
    from k_llms_tpu.models import loader, quant
    from k_llms_tpu.parallel.sharding import param_specs

    def engine(**kw):
        return LocalEngine(cfg, params=params, **{"use_mesh": False, "kv_layout": "paged", **kw})

    def served():
        return shared_engine(cfg, kv_layout="paged", kv_page_size=page_size)

    return {
        "default_engine": lambda: LocalEngine(cfg),  # what a user gets: a mesh of the eight devices
        "mesh": lambda: engine(use_mesh=True),  # eight virtual devices: a mesh would be built
        "int8": lambda: engine(quantize="int8"),
        "int4": lambda: engine(quantize="int4"),
        "speculative": lambda: engine(speculative="prompt_lookup"),
        "sp_prefill": lambda: engine(sp_prefill_min_tokens=64),
        "prefix_cache": lambda: engine(prefix_cache_size=4),
        "dense_layout": lambda: engine(kv_layout="dense"),
        "param_specs": lambda: param_specs(cfg),
        "quantize_params": lambda: quant.quantize_params(params),
        "init_quantized": lambda: quant.init_params_quantized(cfg, jax.random.key(0)),
        "load_checkpoint": lambda: loader.load_checkpoint(str(tmp_path), cfg),
        "config_from_hf": lambda: loader.config_from_hf(str(tmp_path)),
        "generate": lambda: served().generate([1, 2, 3], n=2, max_new_tokens=2),
        "generate_many": lambda: served().generate_many(
            [GenRequestSpec([1, 2, 3], 1, 0, None, None)], max_new_tokens=2),
        "decode_step": lambda: llama.decode_step(
            cfg, params, jnp.zeros((2,), jnp.int32), jnp.int32(0), jnp.int32(4),
            llama.init_cache(cfg, 2, 4), llama.init_cache(cfg, 1, 8)),
        "no_continuous_batching": lambda: TpuBackend(model=cfg.name),
    }[what]


@pytest.mark.parametrize("preset,what", [pytest.param(p, w, id=f"{p}-{w}")
                                         for p, row in ROWS.items() for w in row.refuses])
def test_what_the_model_cannot_ride_is_refused_by_name(preset, what, tmp_path):
    served = ROWS[preset]
    if served.hf_file:
        (tmp_path / "config.json").write_text(json.dumps(served.hf_file))
    call = refusal(what, served.cfg, shared_params(served.cfg), tmp_path, served.page_size)
    with pytest.raises(NotImplementedError, match=served.refuses[what]):
        call()


# -- the benchmark's side ------------------------------------------------------------------------

@pytest.mark.parametrize("preset", ROWS)
def test_benchmark_manifest_has_no_fault_and_the_cells_metrics_read_what_they_say(preset):
    """The row's cell in BENCHMARK.json: its per-layer metrics (those it
    alone lists, those it shares, those it must not list), and each read
    through the benchmark's own evaluator on a canned capture, which finds
    nothing on a program without the counters (the parent of the PR that
    added them)."""
    run = benchmark_module("run")
    m = ROWS[preset].manifest
    assert run.check_manifest() == []
    cells = {w["name"]: w for w in run.load_json(ROOT, "BENCHMARK.json")["workloads"]}
    assert all(name == f"{w['config']}.{w['traffic']}" for name, w in cells.items())
    assert cells[m["cell"]]["chips"] == 1 and len(cells[m["cell"]]["why"]) <= 200
    for name, want in m.get("cells", {}).items():
        assert cells[name] == dict(cells[name], **want)
    _, _, config, traffic, e2e, specs = run.load_cell(m["cell"])
    names = {spec["name"] for spec in specs}
    own = m.get("own", set())
    assert own | m["has"] <= names and not m.get("lacks", set()) & names
    assert set(e2e) == {"latency_p50_ms", "tokens_per_s", "setup_s"}
    for other in cells:
        if other != m["cell"]:
            assert own & {s["name"] for s in run.load_cell(other)[5]} == m.get("shared", {}).get(other, set())
    if "like" in m:
        with open(os.path.join(ROOT, "benchmark", "workloads", f"{m['like']}.json")) as f:
            like = json.load(f)
        assert {k: v for k, v in traffic.items() if k not in m["traffic"]} == {
            k: v for k, v in like.items() if k not in m["traffic"]}
    assert {k: traffic[k] for k in m.get("traffic", {})} == m.get("traffic", {})
    assert {k: config[k] for k in m.get("config", {})} == m.get("config", {})
    if "reads" not in m:
        return
    start, end = m.get("scrapes", ({}, {}))
    src = {"capture": {"start": {}, "end": m["counters"], "seconds": 3.0},
           "metrics_start": start, "metrics_end": end,
           "trace": {"busy_s": m["busy_s"], "window_s": 3.0}, "config": config,
           "peaks": {"hbm_GB_per_s": 819}}
    values = {s["name"]: run.evaluate(s["read"], src) for s in specs if s["name"] in m["reads"]}
    assert values == {k: pytest.approx(v) for k, v in m["reads"].items()}
    assert all(0 < v < 100 for v in values.values())
    # On a program without the counters and the span the readers find nothing.
    bare = dict(src, capture={"start": {}, "end": {}, "seconds": 3.0}, metrics_start={},
                metrics_end={k: v for k, v in end.items() if k == "kllms_continuous_admitted"})
    assert all(run.evaluate(s["read"], bare) is None for s in specs if s["name"].startswith(m["bare"]))
