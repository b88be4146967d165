"""What later changes must leave as it is: other models' programs, pinned by
the sha256 of their StableHLO text, and the continuous loop's seeded token
streams, pinned by the sha256 of what it emits, each as the tree named beside
its table produced them. A pin that no longer matches is a failing test; a
change that moves a program on purpose says so where it re-pins it.

Two tables of programs, each lowered its own way: ``PARENT_LOOP_PROGRAMS``
the loop's own step and chunk programs at the tiny presets on the CPU
(``loop_programs``), ``PARENT_CUT_PROGRAMS`` the model's step and chunk
programs at two served cuts' widths for the TPU (``cut_programs``).
"""

import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, shared_engine
from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
from k_llms_tpu.models import get_config, hybrid, llama
from k_llms_tpu.models.llama import KVCache


@pytest.fixture(scope="module")
def grammar():
    from k_llms_tpu.engine.grammar import grammar_for_schema, grammar_vocab
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    with open(os.path.join(ROOT, "benchmark", "workloads", "extract.json")) as f:
        schema = json.load(f)["response_format"]
    return grammar_for_schema(schema, grammar_vocab(get_tokenizer(None)))


# -- what other models' programs did not move -------------------------------------------------

#: sha256 of each program's StableHLO text on the commit named beside it, made
#: there by the lowering ``loop_programs`` does. The three ``chunk`` programs:
#: ``qwen2-shaped`` and ``xing4-tiny`` on 53cb5d9, where the drafted step, the
#: held share and the plain residual path are static branches they never take;
#: ``nemotron3-tiny`` on 7ba35d2. The six ``step`` / ``step_g`` programs on the
#: child of a70e348: their text changed by construction there, since a step
#: takes one packed host array and block tables where it took 11 to 13 arrays
#: and token-level slot indices (``continuous._unpack_step``,
#: ``paging.expand_tables``), which is that change. They pin every later change;
#: that the change itself moved no model's loop is held by the tokens below
#: (``PARENT_STREAMS``, recorded on a70e348). ``qwen2-shaped``'s three were made
#: again where its block's rotary embedding became a rotation of the head's
#: lanes (``llama.rope_lanes``): a new program text with the same values, which
#: the same ``PARENT_STREAMS`` hold.
PARENT_LOOP_PROGRAMS = {
    "qwen2-shaped": {
        "step": "dfeb6fdf80d878b155804f352b1f22e31249e848ac67aa6270e81f7a6941dc96",
        "step_g": "7bd4532584220564d45b973c0d367c380dafc670a576368efde41d61c89151fc",
        "chunk": "b9910b6ab864d49f39454861eb2bd36ff1fb301ed724beccc7865393496c3525",
    },
    "xing4-tiny": {
        "step": "8a80ec3a8e8d1dd7353e495c2b0f8a72d71fb6cdef9965b620cb970a375cffdf",
        "step_g": "ff0ddadba9a602394d8c338999dae7af07e8c43bf99a08ba979fff3f301192ec",
        "chunk": "ac609ad56b00b2c614ea8a4fa6213d824257ad6a4729288f1f239a2cb5e8b999",
    },
    "nemotron3-tiny": {
        "step": "6d06aba1dbb8878811c2b36b9c41c9e27204bdec0a9cf8067cebfa503b7d7d57",
        "step_g": "c77c4f82b0e0f37f7a60e78ede5218f6489b31938051ae10a812644380ee88d4",
        "chunk": "c3775e68172d09a2449a21d312f484c80f4fec94927087cee1838303005af0d2",
    },
}


def loop_programs(config):
    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.engine.grammar import grammar_for_schema, grammar_vocab
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    engine = LocalEngine(config, use_mesh=False, kv_layout="paged", kv_page_size=8)
    loop = ContinuousDecodeLoop(engine, width=4, max_prompt=32, max_new=8)
    loop._build_device_state()
    # A step's one host array: the mirrors as a new loop holds them, every table empty.
    packed = jnp.asarray(loop._pack_step(np.zeros((4, 1), np.int32), loop._pages.tables))
    no_poison = jnp.zeros((4,), bool)
    pool, out = loop._pool, {}
    out["step"] = loop._step_fn.lower(
        engine.params, pool.kv.k, pool.kv.v, packed, no_poison, state=loop._state).as_text()
    tiny_schema = {"type": "object", "properties": {"a": {"type": "boolean"}}, "required": ["a"],
                   "additionalProperties": False}
    loop._install_grammar(grammar_for_schema(tiny_schema, grammar_vocab(get_tokenizer(None))))
    out["step_g"] = loop._grammar_programs()["step"].lower(
        engine.params, pool.kv.k, pool.kv.v, packed, no_poison, *loop._g_tabs(),
        state=loop._state).as_text()
    out["chunk"] = engine._get_prefill_chunk(32, 64, True).lower(
        engine.params, jnp.zeros((1, 32), jnp.int32), llama.init_cache(config, 1, 64),
        jnp.int32(0), jnp.int32(32), state=llama.init_state(config, 1)).as_text()
    loop.stop()
    return out


SHAPED = get_config("tiny").with_(name="qwen2-shaped", qkv_bias=True, rope_theta=1e6, rms_eps=1e-6)


@pytest.fixture(scope="module")
def lowered():
    return {"qwen2-shaped": loop_programs(SHAPED),
            **{name: loop_programs(get_config(name)) for name in ("xing4-tiny", "nemotron3-tiny")}}


@pytest.mark.parametrize("program", ["step", "step_g", "chunk"])
@pytest.mark.parametrize("model", sorted(PARENT_LOOP_PROGRAMS))
def test_other_models_loop_programs_are_the_parents(lowered, model, program):
    text = lowered[model][program]
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_LOOP_PROGRAMS[model][program]


# -- the loop's tokens are the parent's --------------------------------------------------------

#: sha256 over the tokens, lengths and finish reasons of ``loop_streams`` as
#: a70e348 emitted them on the CPU: the change after it re-made the six step
#: hashes above (a step takes one packed array now), so the proof that no
#: model's loop moved is what comes out of it.
PARENT_STREAMS = {
    "joyai-tiny-drafted": "92a50f4de477d2b0cd43c2a4a145e7efcbe15dd81335ea2f0d3acd714305b2a6",
    "joyai-tiny-drafted-grammar": "9222b97ad9fe9b733b0e91d35b3a0da57780f56ade5bf432ba733eda68855867",
    "nemotron3-tiny-paged": "0db372a287dda3a282cdf517d83ca799b9f98827b0bc7d090773c4096851403f",
    "nemotron3-tiny-paged-grammar": "e512348d95da253a2777e3a010cb293efdf10fd075c2fae5352665dcb688e946",
    "qwen2-shaped-dense": "a423609e1c1bbb87e7e2ce148a099c30aa1fd55f1f7389c730d4f75247a8fa4a",
    "qwen2-shaped-dense-grammar": "782bf52ff12f601c6c6329ec1c7ffba791a6ce7426b56536234a74fd11f0518a",
    "qwen2-shaped-paged": "a423609e1c1bbb87e7e2ce148a099c30aa1fd55f1f7389c730d4f75247a8fa4a",
    "qwen2-shaped-paged-grammar": "782bf52ff12f601c6c6329ec1c7ffba791a6ce7426b56536234a74fd11f0518a",
    "xing4-tiny-paged": "916411c142e43e1b4844d6bb483fd774e4f7a13cc2da306e4db83896f4ad699e",
    "xing4-tiny-paged-grammar": "30857165657fedad2619e802a08c78ee0da8764c10117f6859d9f6bf7ea80b78",
}

STREAM_CASES = {
    "qwen2-shaped-dense": ("qwen2-shaped", "dense", False),
    "qwen2-shaped-dense-grammar": ("qwen2-shaped", "dense", True),
    "qwen2-shaped-paged": ("qwen2-shaped", "paged", False),
    "qwen2-shaped-paged-grammar": ("qwen2-shaped", "paged", True),
    "xing4-tiny-paged": ("xing4-tiny", "paged", False),
    "xing4-tiny-paged-grammar": ("xing4-tiny", "paged", True),
    "nemotron3-tiny-paged": ("nemotron3-tiny", "paged", False),
    "nemotron3-tiny-paged-grammar": ("nemotron3-tiny", "paged", True),
    "joyai-tiny-drafted": ("joyai-tiny", "paged", False),
    "joyai-tiny-drafted-grammar": ("joyai-tiny", "paged", True),
}


def loop_streams(model, layout, grammar):
    """Two seeded requests through one loop, the second into slots the first
    left (and one more): four rows over a prompt that ends mid-page, then
    three with a seed past 2**31, greedy beside sampled."""
    from k_llms_tpu.engine.tokenizer import get_tokenizer

    config = SHAPED if model == "qwen2-shaped" else get_config(model)
    engine = shared_engine(config, kv_layout=layout, kv_page_size=8)
    loop = ContinuousDecodeLoop(engine, width=6, max_prompt=64, max_new=64,
                                eos_ids=get_tokenizer(None).stop_ids)
    rng = np.random.RandomState(2)
    digest = hashlib.sha256()
    try:
        for n, plen, temperature, seed in ((4, 37, 0.8, 5), (3, 48, 0.0, 2**31 + 11)):
            prompt = [int(t) for t in rng.randint(32, 127, size=plen)]
            got = loop.submit(prompt, n=n, max_new=64 if grammar else 20, temperature=temperature,
                              top_p=0.95, seed=seed, grammar=grammar).result(timeout=300)
            digest.update(np.asarray(got.tokens, np.int32).tobytes())
            digest.update(np.asarray(got.lengths, np.int32).tobytes())
            digest.update(",".join(got.finish_reasons).encode())
    finally:
        loop.stop()
    return digest.hexdigest()


@pytest.mark.parametrize("case", sorted(STREAM_CASES))
def test_seeded_loop_streams_are_the_parents(grammar, case):
    model, layout, constrained = STREAM_CASES[case]
    assert loop_streams(model, layout, grammar if constrained else None) == PARENT_STREAMS[case]


# -- the served cuts' programs for the TPU ----------------------------------------------------

#: sha256 of the StableHLO of the cut presets' decode step (``paged_verify_step``
#: at the loop's width 32 on the Pallas paged kernel, lowered for the TPU, the
#: state donated) and chunk program (``prefill_chunk_step_paged``, 128 tokens
#: into a staging cache of 512) as c50bcd8 lowers them. The kernels'
#: serialized bodies are cut out before hashing: they carry the checkout's
#: absolute paths. The change after c50bcd8 touched the hybrid stack and the
#: state plumbing both models run; these say that neither program moved.
PARENT_CUT_PROGRAMS = {
    "nemotron3-nano-30b-a3b-cut9": {
        "step": "e48f35a7393e6803dd27782064578765018d38670be63be841da78b83b00346b",
        "chunk": "f82fd80f55663efb019c0fc9791ba9e55089cd20ec73a0892bcbc6dbb8e949a0",
    },
    "command-a-plus-cut4": {
        "step": "7c649870199036f5de0f211e6eff2456e0c28c54a55596129399ce4d3cbc38b6",
        "chunk": "94fb8e230939734618059824aee99254eb4c38496cbd2e9d591431d75f924f4c",
    },
}


def cut_programs(name, W=32, P=2048, G=128, ps=64, C=128, bucket=512):
    """The two programs' StableHLO at the cut's widths, from shapes alone."""
    cfg = get_config(name)
    params = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.key(0)))
    heads, kw, vw = cfg.cache_widths
    pool = [jax.ShapeDtypeStruct((cfg.paging_layers, 64 * ps, heads, w), cfg.jax_dtype)
            for w in (kw, vw)]

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def step(params, pool_k, pool_v, cur, lens, plens, active, pidx, gidx, state):
        aux, state = {}, dict(state)
        return llama.paged_verify_step(
            cfg, params, cur[:, None], lens, plens, KVCache(k=pool_k, v=pool_v), pidx, gidx,
            attn_impl="pallas", page_size=ps, aux=aux, state=state, active=active) + (aux, state)

    def chunk(params, toks, cache, cursor, valid, state):
        aux, state = {}, dict(state)
        return llama.prefill_chunk_step_paged(cfg, params, toks, cache, cursor, valid,
                                              aux=aux, state=state) + (aux, state)

    lowered = {
        "step": jax.jit(step, donate_argnums=(1, 2, 9)).trace(
            params, *pool, i32(W), i32(W), i32(W), jax.ShapeDtypeStruct((W,), bool),
            i32(W, P), i32(W, G), jax.eval_shape(lambda: llama.init_state(cfg, W))),
        "chunk": jax.jit(chunk, donate_argnums=(2, 5)).trace(
            params, i32(1, C), jax.eval_shape(lambda: llama.init_cache(cfg, 1, bucket)), i32(),
            i32(), jax.eval_shape(lambda: llama.init_state(cfg, 1))),
    }
    return {k: re.sub(r'\\22body\\22: \\22[A-Za-z0-9+/=]+\\22', "BODY",
                      v.lower(lowering_platforms=("tpu",)).as_text())
            for k, v in lowered.items()}


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("name", sorted(PARENT_CUT_PROGRAMS))
def test_the_other_hybrid_models_programs_are_the_parents(name):
    for program, text in cut_programs(name).items():
        assert hashlib.sha256(text.encode()).hexdigest() == PARENT_CUT_PROGRAMS[name][program], program
