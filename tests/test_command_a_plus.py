"""command-a-plus-05-2026 (models/hybrid.py's parallel block: one mean-centred
LayerNorm, GQA attention and routed experts side by side, three windowed
layers to one global layer without a positional embedding, a held share of the
experts, tied embeddings): the checks no other model has, against the plain
reference (benchmark/command_a_plus_reference.py) at the
``command-a-plus-tiny`` size on the CPU, in float32. What every served model
owes is tests/test_served_models.py's.

Tolerances as in tests/test_joyai.py: both sides compute in float32, so 1e-4
absolute on logits of magnitude ~4 is twenty times what the comparisons read
and a thousand times under what a missing term gives (a key one position past
the window's edge, the 1/2 on the shared branch, rope on the global layer).
The reference rotates interleaved pairs, the program half-split ones: the
comparison permutes each head's columns of the windowed layers' ``W_q`` and
``W_k`` (``ref.interleaved_columns``) before the reference sees them.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, shared_engine, shared_loop, shared_params, stats_grown
from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
from k_llms_tpu.models import get_config, hybrid, latent, llama
from served_models import COMMAND_KINDS as KINDS, ROWS, command_a_plus_hf as hf_dict

TOL = 1e-4
ROW = ROWS["command-a-plus-tiny"]
CFG, ref = ROW.cfg, ROW.ref


@pytest.fixture(scope="module")
def params():
    return shared_params(CFG)


def close(got, want):
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL, rtol=0)


# -- the presets ---------------------------------------------------------------------

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


def test_the_block_norm_is_mean_centred():
    """The block's one norm is a LayerNorm: its output has no mean, and it is
    not the RMS norm the other blocks take."""
    x = jnp.asarray(np.random.RandomState(1).randn(3, CFG.hidden_size), jnp.float32) + 2.0
    w = jnp.ones((CFG.hidden_size,))
    normed = hybrid.layer_norm(x, w, CFG.rms_eps)
    assert float(jnp.abs(jnp.mean(normed, axis=-1)).max()) < 1e-5
    assert float(jnp.abs(normed - llama.rms_norm(x, w, CFG.rms_eps)).max()) > 0.5


# -- the experts against the reference -------------------------------------------------

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

def loop_of(config, layout="paged", chunk=32, ladder=(), max_prompt=88, own=False):
    """The session's loop of this shape, or (``own``) a new one."""
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    kw = dict(width=6, max_prompt=max_prompt, max_new=16, eos_ids=get_tokenizer(None).stop_ids,
              prefill_chunk_tokens=chunk, prefill_chunk_ladder=ladder)
    if own:
        return ContinuousDecodeLoop(shared_engine(config, kv_layout=layout, kv_page_size=8), **kw)
    return shared_loop(config, kv_layout=layout, kv_page_size=8, **kw)


def run_loop(loop, plens=(70, 88, 10)):
    """Greedy requests of n = 4, 2, 2; their tokens, and the loop's counters
    as they moved them."""
    rng, out, before = np.random.RandomState(2), [], loop.stats
    for n, plen in zip((4, 2, 2), plens):
        prompt = [int(t) for t in rng.randint(32, 127, size=plen)]
        got = loop.submit(prompt, n=n, max_new=12, temperature=0.0, top_p=None,
                          seed=5).result(timeout=300)
        out.append(np.asarray(got.tokens))
    return out, stats_grown(before, loop.stats)


@pytest.mark.parametrize("model, layout", [("command-a-plus-tiny", "paged"), ("tiny", "paged"),
                                           ("tiny", "dense")])
def test_a_prompt_chunks_into_a_bucket_above_max_prompt(model, layout):
    """``max_prompt`` 88 is no power of two: prompts of 70 and 88 tokens chunk
    into a bucket of 128, larger than the loop's prompt slots. The chunk
    lane's staging cache, the tables and the dense install take it, and the
    tokens are whole-prompt admission's."""
    config = get_config(model)
    chunked, stats = run_loop(loop_of(config, layout, chunk=32))
    whole, _ = run_loop(loop_of(config, layout, chunk=0))
    assert stats["prefill_chunks"] == 3 + 3  # 70 and 88 tokens in chunks of 32; 10 in none
    for a, b in zip(chunked, whole):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("plens", [(161, 250, 10), (130, 256, 10)])
def test_the_ladders_turns_keep_each_layers_window(plens):
    """Two prompts on the ladder (a turn of 128, then one of 64, 128 or 32
    padded, or of 128 whole, in a bucket of 256): the three windowed layers'
    masks over the staging cache, many windows deep into a turn and across
    the two, give whole-prompt admission's tokens."""
    chunked, stats = run_loop(loop_of(CFG, chunk=32, ladder=(32, 64, 128), max_prompt=256), plens)
    whole, _ = run_loop(loop_of(CFG, chunk=0, max_prompt=256), plens)
    assert (stats["prefill_chunks"], stats["prefill_tokens"]) == (2 + 2, sum(plens[:2]))
    for a, b in zip(chunked, whole):
        np.testing.assert_array_equal(a, b)


def test_the_loop_on_the_kernel_counts_layer_pages_under_each_layers_window(monkeypatch):
    """The loop's greedy tokens on the interpreted kernel are the XLA path's
    (which the CPU takes by itself), and its three gauges count layer-pages:
    three layers walk inside their window of 12, the fourth everything."""
    from k_llms_tpu.ops import paged_attention as ops
    from k_llms_tpu.ops.paged_attention import live_pages, table_pages
    from k_llms_tpu.utils.observability import KERNEL_EVENTS, PAGED_ATTN_PAGES

    xla, _ = run_loop(loop_of(CFG), plens=(29,))
    monkeypatch.setattr(ops, "resolve_paged_attention_impl", lambda requested, **kw: "pallas_interpret")
    loop = loop_of(CFG, own=True)  # its kernel is resolved when it builds
    events = KERNEL_EVENTS.snapshot()
    before = PAGED_ATTN_PAGES.snapshot()
    try:
        kernel, stats = run_loop(loop, plens=(29,))
    finally:
        loop.stop()
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
