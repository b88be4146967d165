"""command-a-plus-05-2026 (models/hybrid.py's parallel block: one mean-centred
LayerNorm, GQA attention and routed experts side by side, three windowed
layers to one global layer without a positional embedding, a held share of the
experts, tied embeddings) against the plain reference
(tests/command_a_plus_reference.py, the benchmark's copy byte for byte) at the
``command-a-plus-tiny`` size on the CPU, in float32.

Tolerances as in tests/test_joyai.py: both sides compute in float32, so 1e-4
absolute on logits of magnitude ~4 is twenty times what the comparisons read
and a thousand times under what a missing term gives (a key one position past
the window's edge, the 1/2 on the shared branch, rope on the global layer).
The reference rotates interleaved pairs, the program half-split ones: the
comparison permutes each head's columns of the windowed layers' ``W_q`` and
``W_k`` (``ref.interleaved_columns``) before the reference sees them.
"""

import filecmp
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import command_a_plus_reference as ref
from conftest import shared_engine, shared_params
from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
from k_llms_tpu.models import get_config, hybrid, latent, llama
from k_llms_tpu.models.llama import KVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4
CFG = get_config("command-a-plus-tiny")
HELD = (CFG.expert_offset, CFG.held_experts)
KINDS = {"L": "sliding_attention", "G": "full_attention"}


def hf_dict(c):
    """The preset in the published config.json's own key names."""
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_heads,
        num_key_value_heads=c.num_kv_heads, head_dim=c.head_dim, layer_norm_eps=c.rms_eps,
        layer_types=[KINDS[k] for k in c.layer_pattern], sliding_window=c.sliding_window,
        rope_theta=c.rope_theta, num_experts_per_tok=c.num_experts_per_tok, norm_topk_prob=True,
        num_shared_experts=c.n_shared_experts, logit_scale=1, num_hidden_layers=c.num_layers,
    )


def for_reference(c, params):
    """The program's tree as the reference takes it: the windowed layers'
    ``W_q`` and ``W_k`` with each head's columns in the interleaved order."""
    layers = [dict(layer, **{name: ref.interleaved_columns(layer[name], c.head_dim)
                             for name in (("wq", "wk") if kind == "L" else ())})
              for kind, layer in zip(c.layer_pattern, params["layers"])]
    return dict(params, layers=layers)


@pytest.fixture(scope="module")
def params():
    return shared_params(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, 500, (2, 72)).astype(np.int32)


def reference_logits(params, sequence):
    return np.asarray(ref.forward(hf_dict(CFG), for_reference(CFG, params), sequence, HELD))


def close(got, want):
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL, rtol=0)


# -- the presets ---------------------------------------------------------------------

def test_reference_copies_are_equal():
    assert filecmp.cmp(os.path.join(ROOT, "tests", "command_a_plus_reference.py"),
                       os.path.join(ROOT, "benchmark", "command_a_plus_reference.py"), shallow=False)


def test_cut4_is_the_arithmetic_of_the_configuration_file():
    cut, whole = get_config("command-a-plus-cut4"), get_config("command-a-plus")
    assert (cut.paging_layers, cut.kv_bytes_per_token) == (4, 4 * 8 * 256 * 2)
    assert (cut.held_experts, cut.num_experts, cut.vocab_size) == (16, 128, 32768)
    assert cut.layer_windows == (4096, 4096, 4096, None) and cut.mixes_windowed_layers
    assert whole.layer_windows == cut.layer_windows * 8 and whole.paging_layers == 32
    attention = 2 * 4096 * 16384 + 2 * 4096 * 1024
    # (+ the norm's weight and the router's bias, which is zero)
    layer = attention + 4 * 3 * 4096 * 4096 + 4096 * 128 + 16 * 3 * 4096 * 4096 + 4096 + 128
    assert hybrid.param_count(cut) == 4 * layer + 32768 * 4096 + 4096
    assert abs(hybrid.param_count(cut) - 4.733e9) < 1e6  # ISSUE 38's 4,733 M
    assert abs(hybrid.param_count(whole) - 218e9) < 1e9  # "218B"
    with open(os.path.join(ROOT, "benchmark", "configs", "command-a-plus.json")) as f:
        hf = json.load(f)
    assert hf["num_experts"] == cut.held_experts and hf["published"]["num_experts"] == 128
    assert hf["vocab_size"] == cut.vocab_size and hf["published"]["vocab_size"] == 262144
    assert hf["num_hidden_layers"] == cut.num_layers == len(hf["layer_types"])
    assert [KINDS[k] for k in cut.layer_pattern] == hf["layer_types"]
    assert hf["serve"]["model"] == cut.name and hf["serve"]["continuous_max_prompt"] == 7168
    assert (hf["sliding_window"], hf["rope_theta"], hf["num_experts_per_tok"]) == (
        cut.sliding_window, cut.rope_theta, cut.num_experts_per_tok)
    assert set(hf["reduced"]) == {"num_hidden_layers", "layer_types", "num_experts", "vocab_size"}


def test_the_tree_is_tied_and_holds_a_share_of_the_experts(params):
    assert "lm_head" not in params and not llama.init_state(CFG, 3)
    layer = params["layers"][0]
    assert layer["w_router"].shape == (CFG.hidden_size, CFG.num_experts)
    assert layer["w_up"].shape == (CFG.held_experts, CFG.hidden_size, CFG.moe_intermediate_size)
    assert layer["ws_up"].shape == (CFG.hidden_size, 2 * CFG.moe_intermediate_size)
    assert "mlp_norm" not in layer and "attn_norm" not in layer  # one norm a block


# -- the program against the reference -------------------------------------------------

@pytest.mark.parametrize("length", [7, 72])  # inside the window of 12, and six windows long
def test_forward_matches_reference(params, tokens, length):
    seq = tokens[:, :length]
    logits, _ = llama.forward(CFG, params, jnp.asarray(seq), jnp.ones(seq.shape, jnp.int32))
    for row in range(2):
        close(logits[row], reference_logits(params, seq[row]))


def test_forward_moves_with_what_the_block_is_made_of(params, tokens):
    """Each piece the tolerance has to see: the window's edge, no rope on the
    global layer, the mean in the norm, the 1/2 on the shared branch."""
    seq = jnp.asarray(tokens[:1, :40])
    ones = jnp.ones(seq.shape, jnp.int32)
    base, _ = llama.forward(CFG, params, seq, ones)
    for other in (CFG.with_(name="w13", sliding_window=13),
                  CFG.with_(name="all-windowed", layer_pattern="LLLL"),
                  CFG.with_(name="summed", shared_experts_averaged=False)):
        moved, _ = llama.forward(other, params, seq, ones)
        assert float(jnp.abs(moved - base).max()) > 100 * TOL
    x = jnp.asarray(np.random.RandomState(1).randn(3, CFG.hidden_size), jnp.float32) + 2.0
    w = jnp.ones((CFG.hidden_size,))
    normed = hybrid.layer_norm(x, w, CFG.rms_eps)
    assert float(jnp.abs(jnp.mean(normed, axis=-1)).max()) < 1e-5
    assert float(jnp.abs(normed - llama.rms_norm(x, w, CFG.rms_eps)).max()) > 0.5


def _pages(plen, span, ps, next_page):
    count = -(-(plen + span) // ps)
    return list(range(next_page, next_page + count)), next_page + count


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_then_paged_steps_match_reference(params, tokens, chunk, attn_impl):
    """The loop's programs by hand: two prompts, one inside the window of 12
    (5 tokens) and one past it (21, so chunks end on both sides of the window's
    edge), each in chunks into a staging cache and into the pool's pages; then
    two rows a prompt on the prompt's shared pages, the last partial page
    copied for each (the loop's copy-on-write), continuing with different
    tokens; 14 teacher-forced steps through the page pool, so the short
    prompt's rows cross the window's edge mid-way and every row crosses page
    boundaries. Logits at every chunk's end and every step against the
    reference's full forward, under the XLA masks and under the interpreted
    kernel with each layer's own window."""
    ps, L, steps, P, G = 8, CFG.paging_layers, 14, 32, 16
    flat = 40 * ps
    pool = KVCache(k=jnp.zeros((L, flat, CFG.num_kv_heads, CFG.head_dim)),
                   v=jnp.zeros((L, flat, CFG.num_kv_heads, CFG.head_dim)))
    next_page, rows = 1, []  # page 0 is the trash page
    for prompt_no, plen in enumerate((5, 21)):
        base = tokens[prompt_no]
        seqs = [base[:plen + steps + 1].copy() for _ in range(2)]
        seqs[1][plen:] = (seqs[1][plen:] + 7) % 500  # the second row's own continuation
        want = [reference_logits(params, s) for s in seqs]
        run, next_page = _pages(plen, 0, ps, next_page)
        cache, state = llama.init_cache(CFG, 1, 32), llama.init_state(CFG, 1)
        for start in range(0, plen, chunk):
            valid = min(chunk, plen - start)
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :valid] = base[start:start + valid]
            aux = {}
            logits, cache, k_cols, v_cols = llama.prefill_chunk_step_paged(
                CFG, params, jnp.asarray(toks), cache, jnp.int32(start), jnp.int32(valid),
                aux=aux, state=state)
            assert aux["moe_counts"].shape == (4, CFG.held_experts) and not state
            close(logits[0], want[0][start + valid - 1])
            at = np.arange(start, start + valid)
            slots = np.asarray(run)[at // ps] * ps + at % ps
            pool = KVCache(k=pool.k.at[:, slots].set(k_cols[:, :valid]),
                           v=pool.v.at[:, slots].set(v_cols[:, :valid]))
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
            CFG, params, cur[:, None], lengths, plens, pool, jnp.asarray(pidx),
            jnp.asarray(gidx), attn_impl=attn_impl, page_size=ps, aux=aux) + (aux,)

    for g in range(steps):
        cur = np.asarray([seq[plen + g] for plen, seq, _, _ in rows], np.int32)
        logits, k_cols, v_cols, aux = step(jnp.asarray(cur), jnp.full((B,), g), pool)
        assert k_cols.shape == (L, B, CFG.num_kv_heads, CFG.head_dim)
        assert aux["moe_counts"].shape == (4, CFG.held_experts)
        for b, (plen, _, want, _) in enumerate(rows):
            close(logits[b, 0], want[plen + g])
        pool = KVCache(k=pool.k.at[:, gidx[:, g]].set(k_cols),
                       v=pool.v.at[:, gidx[:, g]].set(v_cols))


@pytest.mark.parametrize("held", [2, 8])
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(held):
    """Each chip's routed sum over its ``held`` of 16 experts (eight chips of
    2, as the cut's eight of 16; two of 8), added, plus the shared experts
    once, is the reference's layer with every expert."""
    whole = CFG.with_(name="command-a-plus-tiny-whole", experts_held=0)
    layer = shared_params(whole)["layers"][0]
    assert layer["w_up"].shape[0] == 16
    h = jnp.asarray(np.random.RandomState(3).randn(40, CFG.hidden_size), jnp.float32)
    total, seen = 0.0, 0
    for offset in range(0, 16, held):
        share = CFG.with_(name=f"share{held}-{offset}", experts_held=held, expert_offset=offset)
        part = {k: (v[offset:offset + held] if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in layer.items()}
        out, counts, _ = latent.routed_experts(share, part, h)
        assert counts.shape == (held,)
        seen += int(counts.sum())
        total = total + out
    assert seen == 40 * CFG.num_experts_per_tok  # every pair on exactly one chip
    with jax.default_matmul_precision("highest"):
        want = ref.experts(hf_dict(whole), layer, h)
        shared = ref.swiglu(h, layer["ws_gate"], layer["ws_up"], layer["ws_down"]) / 2
    close(total + shared, np.asarray(want))
    # One share's block output is its routed part plus the shared experts, whole.
    part = {k: (v[:held] if k in ("w_gate", "w_up", "w_down") else v) for k, v in layer.items()}
    first = CFG.with_(name=f"share{held}-first", experts_held=held)
    out, routed = latent._moe_mlp(first, part, h[None])
    with jax.default_matmul_precision("highest"):
        close(out[0], np.asarray(ref.experts(hf_dict(first), part, h, (0, held))))
    assert routed["counts"].shape == (held,)


def test_the_shared_branch_is_the_average_of_the_shared_experts():
    """Four shared experts fused into one SwiGLU four times as wide, times 1/4:
    the mean of the four taken one at a time."""
    four = CFG.with_(name="command-a-plus-tiny-4shared", n_shared_experts=4,
                     moe_shared_intermediate_size=4 * CFG.moe_intermediate_size)
    layer = shared_params(four)["layers"][3]
    n = jnp.asarray(np.random.RandomState(5).randn(1, 9, CFG.hidden_size), jnp.float32)
    both, _ = latent._moe_mlp(four, layer, n)
    routed, _, _ = latent.routed_experts(four, layer, n[0])
    width = CFG.moe_intermediate_size
    with jax.default_matmul_precision("highest"):
        each = [ref.swiglu(n[0], layer["ws_gate"][:, j * width:(j + 1) * width],
                           layer["ws_up"][:, j * width:(j + 1) * width],
                           layer["ws_down"][j * width:(j + 1) * width]) for j in range(4)]
    close(both[0] - routed, np.asarray(sum(each) / 4))
    summed, _ = latent._moe_mlp(four.with_(shared_experts_averaged=False), layer, n)
    close(summed[0] - routed, np.asarray(sum(each)))


# -- the loop ----------------------------------------------------------------------------

def run_loop(config, *, layout="paged", chunk=32, ladder=(), max_prompt=88, plens=(70, 88, 10),
             impl=None, monkeypatch=None):
    from k_llms_tpu.engine.tokenizer import get_tokenizer
    from k_llms_tpu.ops import paged_attention as ops

    if impl is not None:
        monkeypatch.setattr(ops, "resolve_paged_attention_impl", lambda requested, **kw: impl)
    engine = shared_engine(config, kv_layout=layout, kv_page_size=8)
    loop = ContinuousDecodeLoop(engine, width=6, max_prompt=max_prompt, max_new=16,
                                eos_ids=get_tokenizer(None).stop_ids, prefill_chunk_tokens=chunk,
                                prefill_chunk_ladder=ladder)
    rng, out = np.random.RandomState(2), []
    try:
        for n, plen in zip((4, 2, 2), plens):
            prompt = [int(t) for t in rng.randint(32, 127, size=plen)]
            got = loop.submit(prompt, n=n, max_new=12, temperature=0.0, top_p=None,
                              seed=5).result(timeout=300)
            out.append(np.asarray(got.tokens))
        return out, loop.stats
    finally:
        loop.stop()


@pytest.mark.parametrize("model, layout", [("command-a-plus-tiny", "paged"), ("tiny", "paged"),
                                           ("tiny", "dense")])
def test_a_prompt_chunks_into_a_bucket_above_max_prompt(model, layout):
    """``max_prompt`` 88 is no power of two: prompts of 70 and 88 tokens chunk
    into a bucket of 128, larger than the loop's prompt slots. The chunk
    lane's staging cache, the tables and the dense install take it, and the
    tokens are whole-prompt admission's."""
    config = get_config(model)
    chunked, stats = run_loop(config, layout=layout, chunk=32)
    whole, _ = run_loop(config, layout=layout, chunk=0)
    assert stats["prefill_chunks"] == 3 + 3  # 70 and 88 tokens in chunks of 32; 10 in none
    for a, b in zip(chunked, whole):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("plens", [(161, 250, 10), (130, 256, 10)])
def test_the_ladders_turns_keep_each_layers_window(plens):
    """Two prompts on the ladder (a turn of 128, then one of 64, 128 or 32
    padded, or of 128 whole, in a bucket of 256): the three windowed layers'
    masks over the staging cache, many windows deep into a turn and across
    the two, give whole-prompt admission's tokens."""
    kw = dict(max_prompt=256, plens=plens)
    chunked, stats = run_loop(CFG, chunk=32, ladder=(32, 64, 128), **kw)
    whole, _ = run_loop(CFG, chunk=0, **kw)
    assert (stats["prefill_chunks"], stats["prefill_tokens"]) == (2 + 2, sum(plens[:2]))
    for a, b in zip(chunked, whole):
        np.testing.assert_array_equal(a, b)


def test_the_loop_on_the_kernel_counts_layer_pages_under_each_layers_window(monkeypatch):
    """The loop's greedy tokens on the interpreted kernel are the XLA path's,
    and its three gauges count layer-pages: three layers walk inside their
    window of 12, the fourth everything."""
    from k_llms_tpu.ops.paged_attention import live_pages, table_pages
    from k_llms_tpu.utils.observability import KERNEL_EVENTS, PAGED_ATTN_PAGES

    xla, _ = run_loop(CFG, plens=(29,), impl="xla", monkeypatch=monkeypatch)
    events = KERNEL_EVENTS.snapshot()
    before = PAGED_ATTN_PAGES.snapshot()
    kernel, stats = run_loop(CFG, plens=(29,), impl="pallas_interpret", monkeypatch=monkeypatch)
    grew = {k: v - before.get(k, 0) for k, v in PAGED_ATTN_PAGES.snapshot().items()}
    np.testing.assert_array_equal(kernel[0], xla[0])
    moved = {k: v - events.get(k, 0) for k, v in KERNEL_EVENTS.snapshot().items()}
    assert moved["kernel.paged_attn_pallas_dispatch"] == stats["steps"] == 11
    assert not [k for k, v in moved.items() if "fallback" in k and v]
    walked = out = 0
    for g in range(11):
        for window in CFG.layer_windows:
            (p0, n_prefix), (g0, n_gen) = live_pages(29, g, 29 % 8, 8, window)
            walked += 4 * (n_prefix - p0 + n_gen - g0)
            out += 4 * (p0 + g0)
    assert out > 0 and grew == {
        "paged_attn_pages_walked": walked, "paged_attn_pages_windowed_out": out,
        "paged_attn_pages_tabled": 4 * 11 * 6 * sum(table_pages(88, 16, 8))}


def test_the_front_door_serves_it_under_a_schema_and_health_carries_the_arithmetic():
    """``create_app`` -> the continuous loop, n = 1 and n = 8 under the
    cell's schema with a prompt past the window; no fallback counter moves;
    ``/healthz`` carries the parameters' bytes and the cache's arithmetic and
    ``/metrics`` the expert counters over the held experts."""
    import asyncio

    from k_llms_tpu import KLLMs
    from k_llms_tpu.backends.tpu import TpuBackend
    from k_llms_tpu.serving.app import create_app
    from k_llms_tpu.utils.observability import FAILURE_EVENTS, GRAMMAR_EVENTS, KERNEL_EVENTS

    with open(os.path.join(ROOT, "benchmark", "workloads", "extract-long.json")) as f:
        schema = json.load(f)["response_format"]
    # The suite's eight virtual devices would make a mesh, which is refused: hand over an engine.
    backend = TpuBackend(model=CFG.name, continuous_batching=True, continuous_max_prompt=448,
                         engine=shared_engine(CFG, kv_layout="paged", kv_page_size=64))
    app = create_app(client=KLLMs(backend=backend, model=CFG.name))
    client = app.client
    before = {**FAILURE_EVENTS.snapshot(), **GRAMMAR_EVENTS.snapshot(), **KERNEL_EVENTS.snapshot()}
    try:
        kw = dict(messages=[{"role": "user", "content": "a contract of many pages " * 12}],
                  model=CFG.name, seed=7, max_tokens=64, temperature=0.8)
        one = client.chat.completions.create(n=1, **kw)
        eight = client.chat.completions.create(n=8, response_format={
            "type": "json_schema", "json_schema": {"name": "d", "schema": schema}}, **kw)
        health = client.backend.health()
        sent = []

        async def send(message):
            sent.append(message)

        asyncio.run(app._metrics({}, None, send, {}))
    finally:
        client.backend.close()
    assert len(one.choices) == 1 and len(eight.choices) == 9
    assert all(set(json.loads(c.message.content)) == {"kind", "paid", "currency"}
               for c in eight.choices)
    after = {**FAILURE_EVENTS.snapshot(), **GRAMMAR_EVENTS.snapshot(), **KERNEL_EVENTS.snapshot()}
    assert not {k for k in after if "fallback" in k and after[k] != before.get(k, 0)}
    assert health["hbm"]["param_bytes"] == 4 * hybrid.param_count(CFG)  # float32 here
    assert health["hbm"]["kv_bytes_per_token"] == CFG.kv_bytes_per_token == 4 * 2 * 32 * 4
    assert health["hbm"]["state_bytes"] == 0 and health["continuous"]["admitted"] == 2
    body = b"".join(m.get("body", b"") for m in sent).decode()
    lines = dict(l.rsplit(" ", 1) for l in body.splitlines() if l and not l.startswith("#")
                 and "{" not in l)
    calls, touched = float(lines["kllms_moe_layer_calls"]), float(lines["kllms_moe_experts_touched"])
    assert calls > 0 and 0 < touched <= calls * CFG.held_experts


# -- what it cannot ride ---------------------------------------------------------------------

@pytest.mark.parametrize("what", [
    "mesh", "int8", "speculative", "sp_prefill", "prefix_cache", "dense_layout", "param_specs",
    "quantize_params", "init_quantized", "load_checkpoint", "generate", "decode_step",
    "no_continuous_batching"])
def test_what_the_block_cannot_ride_is_refused_by_name(params, tmp_path, what):
    from k_llms_tpu.backends.tpu import TpuBackend
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.models import loader, quant
    from k_llms_tpu.parallel.sharding import param_specs

    def engine(**kw):
        return LocalEngine(CFG, params=params, **{"use_mesh": False, "kv_layout": "paged", **kw})

    def dense_decode():
        return llama.decode_step(CFG, params, jnp.zeros((2,), jnp.int32), jnp.int32(0),
                                 jnp.int32(4), llama.init_cache(CFG, 2, 4),
                                 llama.init_cache(CFG, 1, 8))

    calls = {
        "mesh": lambda: engine(use_mesh=True),  # eight virtual devices: a mesh would be built
        "int8": lambda: engine(quantize="int8"),
        "speculative": lambda: engine(speculative="prompt_lookup"),
        "sp_prefill": lambda: engine(sp_prefill_min_tokens=64),
        "prefix_cache": lambda: engine(prefix_cache_size=4),
        "dense_layout": lambda: engine(kv_layout="dense"),
        "param_specs": lambda: param_specs(CFG),
        "quantize_params": lambda: quant.quantize_params(params),
        "init_quantized": lambda: quant.init_params_quantized(CFG, jax.random.key(0)),
        "load_checkpoint": lambda: loader.load_checkpoint(str(tmp_path), CFG),
        "generate": lambda: shared_engine(CFG, kv_layout="paged", kv_page_size=8)
        .generate([1, 2, 3], n=2, max_new_tokens=2),
        "decode_step": dense_decode,
        "no_continuous_batching": lambda: TpuBackend(model=CFG.name),
    }
    with pytest.raises(NotImplementedError, match="hybrid|parallel block|command-a-plus"):
        calls[what]()


# -- the benchmark's side ----------------------------------------------------------------------

def test_benchmark_manifest_has_no_fault_and_the_new_metrics_read_what_they_say():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        import run
    finally:
        sys.path.pop(0)
    assert run.check_manifest() == []
    cell = "command-a-plus.extract-long"
    cells = {w["name"]: w for w in run.load_json(ROOT, "BENCHMARK.json")["workloads"]}
    assert cells[cell] == dict(cells[cell], config="command-a-plus", traffic="extract-long", chips=1)
    assert len(cells[cell]["why"]) <= 200
    _, _, config, traffic, e2e, layer_specs = run.load_cell(cell)
    names = {m["name"] for m in layer_specs}
    new = {"paged_attn_walked_share.mixed", "paged_attn_windowed_out_share",
           "paged_attn_kv_stream_share", "moe_expert_stream_share.held16",
           "moe_experts_touched_share.held16", "prefill_chunk_ms.extract-long",
           "moe_load_max_over_mean.held16"}
    # ... and the accepted reads of what the cell's traffic drives: the grammar
    # mask under the schema and the consolidation of n = 8, which list it too.
    assert new | {"weight_stream_share", "device_idle_share", "loop_step_ms",
                  "grammar_masked_share", "consolidate_ms"} <= names
    assert not {"paged_attn_walked_share", "moe_expert_stream_share.held",
                "moe_load_max_over_mean", "mla_latent_stream_share",
                "prefill_chunk_ms.extract"} & names
    assert set(e2e) == {"latency_p50_ms", "tokens_per_s", "setup_s"}
    for other in cells:
        if other != cell:
            assert not new & {m["name"] for m in run.load_cell(other)[5]}
    with open(os.path.join(ROOT, "benchmark", "workloads", "extract.json")) as f:
        extract = json.load(f)
    assert {k: v for k, v in traffic.items() if k != "doc_tokens"} == {
        k: v for k, v in extract.items() if k != "doc_tokens"}
    assert traffic["doc_tokens"] == {"dist": "lognormal", "median": 2400, "sigma": 0.8,
                                     "min": 200, "max": 6000, "pool": 8}
    assert (config["num_experts"], config["intermediate_size"], config["head_dim"]) == (16, 4096, 128)
