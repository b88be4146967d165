"""The paged decode kernel through the TPU's own compiler, without a chip.

``libtpu`` is installed, so a v5e can be described and compiled for though
none is attached: Mosaic refuses here what it would refuse on the chip (a
misaligned slice, too much VMEM), which the interpreter never does. Nothing
runs, so this says nothing about results or times. The topology is described
inside a fixture — never at import — and the compiles happen in the test's own
process; all of them live in this one file (one xdist worker loads the library).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from k_llms_tpu.ops.paged_attention import paged_decode_attention_pallas, table_pages

# (query heads, kv heads, window, prompt slots): qwen2-7b's and mistral-7b's
# attention at the benchmark loop's shapes, a window that binds inside those
# rows, and command-a-plus's two kinds of layer (16 queries a kv head, tables of
# 112 + 5 pages a row) with the window binding inside the longer prompts.
GEOMETRIES = {
    "qwen2-7b": (28, 4, None, 512),
    "mistral-7b": (32, 8, 4096, 512),
    "mistral-7b-window-binds": (32, 8, 96, 512),
    "command-a-plus-windowed": (128, 8, 4096, 7168),
    "command-a-plus-global": (128, 8, None, 7168),
    "olmo-hybrid-full": (32, 32, None, 1024),  # 30 heads each side, stored at 32
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_paged_decode_kernel_compiles_for_v5e(name, one_chip):
    QH, KVH, window, prompt_slots = GEOMETRIES[name]
    B, D, ps, L, pages = 32, 128, 64, 4, 500
    NP, NG = table_pages(prompt_slots, 256, ps)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = shape((L, pages * ps, KVH, D), jnp.bfloat16)
    rows = shape((B,), jnp.int32)
    column = shape((B, KVH, D), jnp.bfloat16)
    compiled = jax.jit(
        lambda *a: paged_decode_attention_pallas(
            *a, page_size=ps, sm_scale=D ** -0.5, window=window
        )
    ).lower(
        shape((B, QH, D), jnp.bfloat16), pool, pool, shape((), jnp.int32),
        shape((B, NP), jnp.int32), shape((B, NG), jnp.int32), rows,
        column, column, rows, rows,
    ).compile()
    assert "paged_attention_decode" in compiled.as_text()


def test_a_step_over_windowed_and_global_layers_is_the_kernel_in_every_layer(one_chip):
    """command-a-plus-cut4's decode step at the cell's sizes (width 32, 7,168
    prompt slots, pages of 64): one ``paged_attention_decode`` custom call a
    layer, each with its own static window, and no gather of a row's pages
    (the XLA path's ``[32, 7168, ...]`` and ``[32, 256, ...]``)."""
    import re

    from k_llms_tpu.models import llama
    from k_llms_tpu.models.config import get_config

    config = get_config("command-a-plus-cut4")
    W, P, G, ps, pages = 32, 7168, 256, 64, 3800

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: shape(a.shape, a.dtype),
                          jax.eval_shape(lambda: llama.init_params(config, jax.random.key(0))))
    pool = shape((config.paging_layers, pages * ps, config.num_kv_heads, config.head_dim),
                 jnp.bfloat16)

    def step(params, pool_k, pool_v, tokens, lengths, plens, prefix_idx, gen_idx):
        aux = {}
        return llama.paged_verify_step(
            config, params, tokens, lengths, plens, llama.KVCache(k=pool_k, v=pool_v),
            prefix_idx, gen_idx, attn_impl="pallas", page_size=ps, aux=aux) + (aux,)

    rows = shape((W,), jnp.int32)
    compiled = jax.jit(step).lower(
        params, pool, pool, shape((W, 1), jnp.int32), rows, rows, shape((W, P), jnp.int32),
        shape((W, G), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%paged_attention_decode\S* = \S+ custom-call\(", text)) == 4
    assert re.findall(r"= \w+\[32,(?:7168|7424|256),\S* gather\(", text) == []
    # The step's transients beside 9.5 GB of weights and a 3.9 GB pool.
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("heads", [32, 30])
def test_the_olmo_hybrid_step_updates_state_in_place_and_reads_the_pool_where_it_lies(
        heads, one_chip):
    """olmo-hybrid-7b-cut20's decode step at the cell's sizes (width 32, 1,024
    prompt slots, pages of 64, a pool of 737 pages): one
    ``paged_attention_decode`` a full-attention layer, the delta-rule layers'
    donated state written in place, and no copy of the
    whole pool, with cache rows stored at ``cache_heads`` (32). Stored at the
    model's 30 KV heads the rows lie padded on the chip's tiles, the kernel's
    view of the pool is no bitcast, and the step copies the pool: the form
    PR 40 left (``ModelConfig.cache_heads``)."""
    import re

    from k_llms_tpu.models import llama
    from k_llms_tpu.models.config import get_config

    config = get_config("olmo-hybrid-7b-cut20")
    assert config.cache_heads == 32 and config.num_kv_heads == 30
    W, P, G, ps, flat = 32, 1024, 256, 64, 737 * 64

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: shape(a.shape, a.dtype),
                          jax.eval_shape(lambda: llama.init_params(config, jax.random.key(0))))
    state = jax.tree.map(lambda a: shape(a.shape, a.dtype),
                         jax.eval_shape(lambda: llama.init_state(config, W)))
    pool = shape((config.paging_layers, flat, heads, config.head_dim), jnp.bfloat16)

    def step(params, pool_k, pool_v, tokens, lengths, plens, prefix_idx, gen_idx, state):
        aux, state = {}, dict(state)
        out = llama.paged_verify_step(
            config, params, tokens, lengths, plens, llama.KVCache(k=pool_k, v=pool_v),
            prefix_idx, gen_idx, attn_impl="pallas", page_size=ps, aux=aux, state=state)
        return out + (aux, state)

    rows = shape((W,), jnp.int32)
    lowered = jax.jit(step, donate_argnums=(8,)).lower(
        params, pool, pool, shape((W, 1), jnp.int32), rows, rows, shape((W, P), jnp.int32),
        shape((W, G), jnp.int32), state)
    if heads == 30:  # the model's own heads on a padded pool: the view copies it
        with pytest.raises(Exception, match="(?i)memory|hbm"):
            lowered.compile()  # 3.5 GB of pool copies beside 14.8 GB: over the chip
        return
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(re.findall(r"%paged_attention_decode\S* = \S+ custom-call\(", text)) == 5
    assert re.findall(r"= f32\[32,30,96,192\]\S* copy\(", text) == []  # the state is not copied
    assert whole_pool_copies(text, config.paging_layers, flat) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= W * config.state_bytes_per_row * 0.95  # state in place
    assert memory.temp_size_in_bytes < 0.3e9


# -- the latent page pool's movers: no program lays the whole pool out again ---------------
#
# (cache layers, flat slots) of each latent cell's pool: 1,249 / 1,281 pages of 64
# (benchmark/configs/*.json `serve`, PERF.md section 4); a row is 576 lanes, stored 640.
LATENT_POOLS = {
    "xing4-29b-a4b": ("xing4-29b-a4b-cut7", 7, 79936),
    "joyai-llm-flash": ("joyai-llm-flash-cut8", 9, 81984),
}
LOOP_WIDTH, LOOP_PROMPT, LOOP_NEW, CHUNK = 32, 2048, 64, 128


def whole_pool_copies(text, layers, flat):
    """`copy` / `transpose` results shaped like the whole pool, in either of its
    views (the latent pool's V, of width 0, holds no byte and does not count)."""
    import re

    shapes = r"(?:%d,%d|%d)(?:,\d+)*\]" % (layers, flat, layers * flat)
    found = re.findall(r"= \w+\[(" + shapes + r")\S* (?:copy|transpose)\(", text)
    return [dims for dims in found if not dims.endswith(",0]")]


def latent_mover(program, model, shape):
    """(the repo's own mover, its arguments at the cell's sizes); the pool is argument 0."""
    from k_llms_tpu.engine.paging import PagedKVPool, scatter_rows, write_drafted_rows
    from k_llms_tpu.models.config import get_config
    from k_llms_tpu.ops.attention import pool_gather, pool_index

    preset, layers, flat = LATENT_POOLS[model]
    config = get_config(preset)
    _, width, _ = config.cache_widths
    assert (config.paging_layers, width, config.pool_row_width) == (layers, 576, 640)
    movers = PagedKVPool(config.with_(vocab_size=512), total_pages=2, page_size=64)  # a toy pool: its movers
    pool_k = shape((layers, flat, 1, config.pool_row_width), jnp.bfloat16)
    pool_v = shape((layers, flat, 1, 0), jnp.bfloat16)
    slots = lambda *dims: shape(dims, jnp.int32)  # noqa: E731
    if program == "chunk_scatter":
        cols = shape((layers, CHUNK, 1, width), jnp.bfloat16)
        return movers._scatter_fn, (pool_k, pool_v, cols, shape((layers, CHUNK, 1, 0), jnp.bfloat16), slots(CHUNK))
    if program == "cow_copy":
        return movers._copy_fn, (pool_k, pool_v, slots(LOOP_WIDTH * 64), slots(LOOP_WIDTH * 64))
    drafting = config.num_nextn_predict_layers
    stack = layers - drafting

    def read_rows(pool_k, prefix_idx, gen_idx, count):
        def layer(acc, number):  # what latent._attend_paged reads, every cache layer in turn
            rows = [pool_gather(pool_k, pool_index(pool_k, number, idx), width) for idx in (prefix_idx, gen_idx)]
            return acc + sum(jnp.sum(r.astype(jnp.float32), axis=1) for r in rows), None

        return jax.lax.scan(layer, jnp.zeros((LOOP_WIDTH, width)), jnp.arange(count, dtype=jnp.int32))[0]

    if program == "engine_loop":
        # engine.py::_get_decode_loop on pages: the pool carried through a
        # while loop, each turn the stack's gathers and scatter_rows at one
        # generated position (a drafting model runs undrafted there, so its
        # module's cache layer, the pool's last, is left alone).
        def loop(pool_k, pool_v, prefix_idx, gen_idx, cols):
            def turn(step, carry):
                pool_k, pool_v, acc = carry
                read = read_rows(pool_k, prefix_idx, gen_idx, stack)
                slots_now = jax.lax.dynamic_index_in_dim(gen_idx, step, axis=1, keepdims=False)
                k_cols = (cols + read[None, :, None, :]).astype(cols.dtype)
                return scatter_rows(pool_k, pool_v, slots_now, k_cols, k_cols[..., :0]) + (acc + read,)

            return jax.lax.fori_loop(0, LOOP_NEW, turn, (pool_k, pool_v, jnp.zeros((LOOP_WIDTH, width))))

        return jax.jit(loop, donate_argnums=(0, 1)), (
            pool_k, pool_v, slots(LOOP_WIDTH, LOOP_PROMPT), slots(LOOP_WIDTH, LOOP_NEW),
            shape((stack, LOOP_WIDTH, 1, width), jnp.bfloat16))
    assert program == "step"

    def step(pool_k, pool_v, prefix_idx, gen_idx, cols, module_cols, write_idx):
        read = read_rows(pool_k, prefix_idx, gen_idx, layers)
        if drafting:
            return write_drafted_rows(pool_k, cols, module_cols, write_idx), read
        return scatter_rows(pool_k, pool_v, write_idx, cols, cols[..., :0])[0], read  # _build_step's write

    sq = 1 + drafting
    stack_cols = (stack, LOOP_WIDTH) + ((sq,) if drafting else ()) + (1, width)
    return jax.jit(step, donate_argnums=(0,)), (
        pool_k, pool_v, slots(LOOP_WIDTH, LOOP_PROMPT), slots(LOOP_WIDTH, LOOP_NEW + drafting * 2),
        shape(stack_cols, jnp.bfloat16), shape((LOOP_WIDTH, sq, 1, width), jnp.bfloat16),
        slots(LOOP_WIDTH, 3) if drafting else slots(LOOP_WIDTH))


@pytest.mark.parametrize("program", ["step", "chunk_scatter", "cow_copy", "engine_loop"])
@pytest.mark.parametrize("model", sorted(LATENT_POOLS))
def test_latent_pool_movers_leave_the_pool_where_it_lies(model, program, one_chip):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn, args = latent_mover(program, model, shape)
    compiled = fn.lower(*args).compile()
    _, layers, flat = LATENT_POOLS[model]
    assert whole_pool_copies(compiled.as_text(), layers, flat) == []
    pool_bytes = layers * flat * 640 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 10


@pytest.mark.parametrize("form", ["layer_axis_576", "layer_axis_640", "flat_576"])
def test_the_forms_this_pool_left_do_copy_the_whole_pool(form, one_chip):
    """The same assertion bites on what the pool was (a 576-wide row under a
    scatter along the layer axis) and on either half of the cure alone."""
    from k_llms_tpu.ops.attention import pool_layers, pool_scatter

    layers, flat = LATENT_POOLS["joyai-llm-flash"][1:]
    stored = 640 if form.endswith("640") else 576

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def scatter(pool_k, cols, idx):
        if form.startswith("layer_axis"):
            cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, stored - 576),))
            return pool_k.at[:, idx].set(cols)
        return pool_scatter(pool_k, pool_layers(pool_k, idx), cols)

    compiled = jax.jit(scatter, donate_argnums=(0,)).lower(
        shape((layers, flat, 1, stored), jnp.bfloat16), shape((layers, CHUNK, 1, 576), jnp.bfloat16),
        shape((CHUNK,), jnp.int32)).compile()
    assert whole_pool_copies(compiled.as_text(), layers, flat)
    assert compiled.memory_analysis().temp_size_in_bytes > layers * flat * stored * 2 / 2


# -- a step's block tables, spread inside the program ---------------------------------------
#
# (max_prompt, max_new, lookahead, drafting) of a cell's loop at width 32 and pages of 64: the
# packed array a step takes (engine/continuous.py::_pack_step) and paging.expand_tables.
STEP_LOOPS = {
    "qwen2-7b": (2048, 256, 0),
    "mistral-7b": (512, 256, 0),
    "joyai-llm-flash": (2048, 256, 2),
}


@pytest.mark.parametrize("form", ["compare_select", "window_a_row"])
@pytest.mark.parametrize("name", sorted(STEP_LOOPS))
def test_the_steps_table_spread_is_fusions_alone_on_v5e(name, form, one_chip):
    """``expand_tables`` compiles to elementwise fusions: no gather, no loop over
    the rows. The form it is not written in, one ``dynamic_slice`` a row of the
    spanned table (shorter to read), becomes a loop of 32 turns on the chip, and
    the assertion bites on it."""
    import re

    from k_llms_tpu.engine.continuous import _unpack_step
    from k_llms_tpu.engine.paging import expand_tables, table_width

    P, max_new, ahead = STEP_LOOPS[name]
    W, ps, G = 32, 64, max_new + ahead
    T = table_width(P, G, ps)
    named = 12 if ahead else 10

    def window_a_row(tables, plens):
        spanned = (tables[:, :, None] * ps + jnp.arange(ps, dtype=jnp.int32)).reshape(W, T * ps)
        return jax.vmap(lambda row, start: jax.lax.dynamic_slice_in_dim(row, start, G))(spanned, plens)

    def spread(packed):
        rows = _unpack_step(packed, bool(ahead), 1 + ahead)
        plens = jnp.where(rows.active, rows.prompt_lens, 0)
        if form == "window_a_row":
            return window_a_row(rows.tables, plens)
        return expand_tables(rows.tables, plens, ps, P, G)

    packed = jax.ShapeDtypeStruct((W, named + 1 + ahead + T), jnp.int32, sharding=one_chip)
    text = jax.jit(spread).lower(packed).compile().as_text()
    loops = re.findall(r" (while|gather)\(", text)
    assert bool(loops) is (form == "window_a_row"), loops


# -- the latent step on the Pallas kernel for latent pages -----------------------------------


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("model", sorted(LATENT_POOLS))
def test_the_latent_step_reads_live_pages_in_place_on_the_kernel(model, impl, one_chip):
    """A latent cell's decode step at the loop's sizes (width 32, 2,048
    prompt slots, pages of 64; joyai's drafted step: the stack at ``Sq == 2``
    and the module): on the kernel, one ``paged_latent_attention_decode`` a
    scan and one for the module, its pool operand a bitcast of the pool (no
    copy, no layout change), and no gather of every row's whole table
    (``[32 x 2048, 640]``, the line the XLA path's step leads with; the
    assertion bites there)."""
    import re

    from k_llms_tpu.models import llama
    from k_llms_tpu.models.config import get_config

    preset, layers, flat = LATENT_POOLS[model]
    config = get_config(preset)
    drafting = config.num_nextn_predict_layers
    sq, ps = 1 + drafting, 64

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: shape(a.shape, a.dtype),
                          jax.eval_shape(lambda: llama.init_params(config, jax.random.key(0))))

    def step(params, pool_k, pool_v, tokens, lengths, plens, prefix_idx, gen_idx):
        aux, pool = {}, llama.KVCache(k=pool_k, v=pool_v)
        logits, k_cols, _, hidden = llama.paged_verify_step(
            config, params, tokens, lengths, plens, pool, prefix_idx, gen_idx,
            attn_impl=impl, page_size=ps, aux=aux, return_hidden=True)
        if not drafting:
            return logits, k_cols, aux
        return llama.paged_draft_step(
            config, params, hidden, tokens, lengths + 1, plens, pool, prefix_idx, gen_idx,
            aux=aux, attn_impl=impl, page_size=ps) + (logits, k_cols, aux)

    rows = shape((LOOP_WIDTH,), jnp.int32)
    compiled = jax.jit(step).lower(
        params, shape((layers, flat, 1, config.pool_row_width), jnp.bfloat16),
        shape((layers, flat, 1, 0), jnp.bfloat16), shape((LOOP_WIDTH, sq), jnp.int32), rows,
        rows, shape((LOOP_WIDTH, LOOP_PROMPT), jnp.int32),
        shape((LOOP_WIDTH, LOOP_NEW + 2 * drafting), jnp.int32)).compile()
    text = compiled.as_text()
    whole_tables = re.findall(r"= bf16\[(?:65536,640|32,2048,640)\]\S* (?:fusion|gather)\(", text)
    calls = re.findall(r"%paged_latent_attention_decode\S* = \S+ custom-call\(([^)]*)\)", text)
    assert whole_pool_copies(text, layers, flat) == []
    if impl == "xla":
        assert whole_tables and not calls
        return
    assert whole_tables == []
    assert len(calls) == 2 + drafting  # the dense-leading scan, the expert scan, the module
    pool_view = "bf16[%d,%d,%d]" % (layers * flat // ps, ps, config.pool_row_width)
    for operands in calls:
        pool_operand = operands.split(", ")[6].split("*/")[-1]  # after five scalar operands and q
        defined = re.search(re.escape(pool_operand) + r" = (\S+) (\w+)\(", text)
        assert defined.group(1).startswith(pool_view) and defined.group(2) == "bitcast"
    assert compiled.memory_analysis().temp_size_in_bytes < 0.02e9


# -- the int8 GQA stack's projections, read where they lie ----------------------------------
#
# (preset, prompt slots) of the int8 GQA cells' loops at width 32 and pages of 64
# (benchmark/configs/*.json `serve`: int8, `continuous_max_prompt`).
INT8_GQA = {"qwen2-7b": 2048, "mistral-7b": 512}


def int8_weight_moves(text):
    """Copies of one layer's int8 weight (``s8[1, ...]``, taken out of the
    scanned stack), and the ``dynamic-slice`` fusions that materialise one."""
    import re

    found = re.findall(r"%(\S+) = (s8\[1,[\d,]+\])\S* (copy|fusion)\(", text)
    return [(name, dims) for name, dims, op in found if op == "copy" or "dynamic-slice" in name]


def int8_stack(preset, shape):
    from k_llms_tpu.models import quant
    from k_llms_tpu.models.config import get_config

    config = get_config(preset)
    params = jax.eval_shape(
        lambda: quant.init_params_quantized(config, jax.random.key(0), dist="cheap"))
    return config, jax.tree.map(lambda a: shape(a.shape, a.dtype), params)


@pytest.mark.parametrize("rope", ["lanes", "split"])
@pytest.mark.parametrize("model", sorted(INT8_GQA))
def test_the_int8_gqa_step_reads_wq_and_wk_where_they_lie(model, rope, one_chip, monkeypatch):
    """The loop's decode step of an int8 GQA cell (width 32, pages of 64, 256
    generated slots, the Pallas paged kernel): no layer's weight is copied or
    materialised out of the stack. Under rope's split and concatenate
    (``rope_embed``, the witness) layout assignment wants q's and k's
    projections heads-major and pays for it by copying wq and wk, transposed,
    every layer; the lane rotation (``rope_lanes``) leaves them where they lie."""
    from k_llms_tpu.models import llama

    if rope == "split":
        monkeypatch.setattr(llama, "rope_lanes", llama.rope_embed)
    W, G, ps, pages = 32, 256, 64, 500

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    config, params = int8_stack(model, shape)
    pool = shape((config.paging_layers, pages * ps, config.num_kv_heads, config.head_dim),
                 jnp.bfloat16)

    def step(params, pool_k, pool_v, tokens, lengths, plens, prefix_idx, gen_idx):
        aux = {}
        return llama.paged_verify_step(
            config, params, tokens, lengths, plens, llama.KVCache(k=pool_k, v=pool_v),
            prefix_idx, gen_idx, attn_impl="pallas", page_size=ps, aux=aux) + (aux,)

    rows = shape((W,), jnp.int32)
    compiled = jax.jit(step).lower(
        params, pool, pool, shape((W, 1), jnp.int32), rows, rows,
        shape((W, INT8_GQA[model]), jnp.int32), shape((W, G), jnp.int32)).compile()
    moves = int8_weight_moves(compiled.as_text())
    if rope == "split":
        square = "s8[1,%d,%d]" % (config.hidden_size, config.q_dim)
        assert square in {dims for _, dims in moves}  # wq's copy, and its slice
        return
    assert moves == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2e6


@pytest.mark.parametrize("rope", ["lanes", "split"])
@pytest.mark.parametrize("model", sorted(INT8_GQA))
def test_the_int8_gqa_chunk_on_xla_attention_reads_wq_where_it_lies(
        model, rope, one_chip, monkeypatch):
    """A 512-token prompt chunk (``prefill_chunk_step_paged`` into a staging
    cache) on XLA's attention, the path ``mistral-7b`` serves its chunks on:
    no copy of wq, the square weight. At these sizes wk and wv stay copied
    (the attention's operands set their layout), and ``qwen2-7b``'s served chunk
    runs the flash kernel, whose heads-major operands keep all three copies
    whatever rope's form."""
    from k_llms_tpu.models import llama

    if rope == "split":
        monkeypatch.setattr(llama, "rope_lanes", llama.rope_embed)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    config, params = int8_stack(model, shape)
    config = config.with_(attention_impl="xla")
    C, total = 512, INT8_GQA[model] + 512
    cache = shape((config.num_layers, 1, total, config.num_kv_heads, config.head_dim),
                  jnp.bfloat16)

    def chunk(params, tokens, cache_k, cache_v, cursor, valid):
        return llama.prefill_chunk_step_paged(
            config, params, tokens, llama.KVCache(k=cache_k, v=cache_v), cursor, valid)

    compiled = jax.jit(chunk, donate_argnums=(2, 3)).lower(
        params, shape((1, C), jnp.int32), cache, cache, shape((), jnp.int32),
        shape((), jnp.int32)).compile()
    copied = {dims for _, dims in int8_weight_moves(compiled.as_text())}
    square = "s8[1,%d,%d]" % (config.hidden_size, config.q_dim)
    assert (square in copied) is (rope == "split")
