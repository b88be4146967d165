"""Local inference engine: n-way consensus sampling as ONE batched decode.

This is the TPU-native replacement for the reference's HTTP boundary
(`/root/reference/k_llms/resources/completions/completions.py:73`): an n-sample
request becomes a single XLA program — prefill the shared prompt once at
batch=1, then autoregressively decode all n samples as the batch dimension,
each sample attending to the broadcast shared-prefix KV plus its own generated
KV. Per-token logprobs are captured on device for likelihood-weighted consensus.

Design points (SURVEY.md §7 stage 4, "hard parts" b/c):
- ragged stopping: mask-and-continue inside one ``lax.while_loop`` with an
  all-done early exit — one compiled program, no data-dependent shapes;
- sample diversity with reproducibility: per-sample/per-step PRNG keys folded
  from the request ``seed``;
- compile stability: prompt lengths bucket to powers of two; jitted callables
  cache per (bucket, n, max_new, sampling-config).
"""

from __future__ import annotations

import logging
import os
import random as _pyrandom
import threading
from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import io_callback
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..analysis.lockcheck import make_rlock, note_device_dispatch, race_exempt
from ..models.config import ModelConfig, get_config
from ..models.llama import (
    KVCache,
    decode_step,
    encode,
    init_cache,
    init_params,
    paged_verify_step,
    prefill,
    prefill_chunk_step,
    prefill_chunk_step_paged,
    prefill_continue,
    verify_step,
)
from ..ops.attention import resolve_attention_impl
from ..ops.sampling import model_top_logprobs, sample_logits
from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, auto_mesh
from ..parallel.sharding import batch_spec, cache_specs, param_specs
from ..reliability import failpoints as _failpoints
from ..reliability.deadline import RequestBudget
from ..types.wire import BackendUnavailableError, KLLMsError
from ..utils.compile_cache import configure_compile_cache
from ..utils.observability import FAILURE_EVENTS, QUARANTINE_EVENTS, note_model_aux

logger = logging.getLogger(__name__)

MAX_EOS_IDS = 4
# OpenAI allows up to 4 stop sequences; device halting matches token suffixes
# up to this many tokens (longer stops degrade to host-side text truncation).
MAX_STOP_SEQS = 4
MAX_STOP_LEN = 8

# A coalesced group is split at most this many times on device OOM before its
# members fail (2**5 = a 32-request group degrades all the way to solo).
MAX_OOM_SPLITS = 5


def is_resource_exhausted(e: BaseException) -> bool:
    """Is this the device's out-of-memory signal? jaxlib surfaces HBM
    exhaustion as XlaRuntimeError("RESOURCE_EXHAUSTED: ..."), and PJRT plugins
    vary the exception class but keep the gRPC status name in the message —
    so match on the marker, not the type. Typed lifecycle errors are never
    OOM even if a message embeds the marker."""
    if isinstance(e, KLLMsError):
        return False
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg


def stop_window_match(window: jax.Array, stops: jax.Array) -> jax.Array:
    """[B, L] rolling token window vs [S, L] right-aligned -1-padded stop
    sequences: -1 padding positions auto-match, and a stop only counts if it
    has at least one real token. Shared by the normal and speculative decode
    loops so halting semantics can never drift apart. Returns [B] bool."""
    pad_pos = stops < 0
    eq = window[:, None, :] == stops[None, :, :]
    row_hit = jnp.all(eq | pad_pos[None, :, :], axis=-1)  # [B, S]
    live = jnp.any(~pad_pos, axis=-1)  # [S]
    return jnp.any(row_hit & live[None, :], axis=-1)


def _constraint_ops(constraint):
    """Uniform grammar-automaton interface for a decode loop: returns
    ``(tables, initial_state, mask_logits, advance)`` where state is always a
    tuple (splat into mask/advance), or None when unconstrained. Shared by the
    normal and speculative loops so both mask logits and advance state with
    identical semantics."""
    if constraint is None:
        return None
    from .token_constraint import TokenConstraint

    if constraint == "json":
        from .json_constraint import advance, device_tables, initial_state, mask_logits

        return device_tables(), initial_state, mask_logits, advance
    if isinstance(constraint, TokenConstraint):
        from .token_constraint import (
            device_token_table,
            token_advance,
            token_initial_state,
            token_mask_logits,
        )

        jt = device_token_table(constraint)
        return (
            jt,
            lambda n: (token_initial_state(jt, n),),
            token_mask_logits,
            lambda t, tok, state: (token_advance(t, tok, state),),
        )
    from .grammar import CompiledGrammar

    if isinstance(constraint, CompiledGrammar):
        from .grammar import (
            device_grammar,
            grammar_advance,
            grammar_initial_state,
            grammar_mask_logits,
        )

        jt = device_grammar(constraint)
        return (
            jt,
            lambda n: (grammar_initial_state(jt, n),),
            grammar_mask_logits,
            lambda t, tok, state: (grammar_advance(t, tok, state),),
        )
    from .schema_constraint import (
        device_dfa,
        dfa_advance,
        dfa_initial_state,
        dfa_mask_logits,
    )

    jt = device_dfa(constraint)
    return (
        jt,
        lambda n: (dfa_initial_state(jt, n),),
        dfa_mask_logits,
        lambda t, tok, state: (dfa_advance(t, tok, state),),
    )


class GenerationResult(NamedTuple):
    tokens: np.ndarray  # [n, max_new] int32, pad_id after finish
    logprobs: np.ndarray  # [n, max_new] f32, 0.0 after finish
    lengths: np.ndarray  # [n] generated token counts (including the stop token)
    finish_reasons: List[str]  # "stop" | "length" per sample
    prompt_len: int
    # Only when requested via top_logprobs=k: per-step top-k alternatives
    # under the untempered model distribution (OpenAI `top_logprobs`).
    top_tokens: Optional[np.ndarray] = None  # [n, max_new, k] int32
    top_logprobs: Optional[np.ndarray] = None  # [n, max_new, k] f32
    # THIS request's speculative-decoding stats, captured at generation time
    # (engine.spec_stats mirrors the most recent request for convenience, but
    # is shared mutable state — concurrent tracing must read this field).
    spec_stats: Optional[Dict[str, Any]] = None
    # Per-sample failure records (index-aligned with tokens rows): None for a
    # healthy sample, an error dict for one lost mid-decode (injected fault or
    # per-sample abort). Consolidation drops failed samples from the vote and
    # surfaces them in the response's `degraded` marker.
    sample_errors: Optional[List[Optional[Dict[str, Any]]]] = None


class GenRequestSpec(NamedTuple):
    """One request's slice of a coalesced decode batch (see generate_many)."""

    prompt_ids: List[int]
    n: int = 1
    seed: Optional[int] = None
    # Lifecycle budget (deadline + cancel token). NOT part of the scheduler's
    # batch_key — requests with different deadlines still coalesce; each row
    # group aborts independently via the decode loop's cancellation poll.
    budget: Optional[RequestBudget] = None
    # Streaming tap: called from the host as ``sink(step, token_ids[n_per])``
    # for each decode step of THIS request's rows (best-effort — delivery is
    # via an unordered io_callback; the engine reorders and dedups, and the
    # caller must reconcile against the final GenerationResult). Like budget,
    # not part of the batch_key: streaming and non-streaming requests coalesce.
    token_sink: Optional[Callable[[int, np.ndarray], None]] = None


def _kill_sample_errors(n: int, fp: "_failpoints.FailSpec") -> List[Optional[Dict[str, Any]]]:
    """Seeded selection of which of a request's n samples an injected
    ``engine.decode`` kill_samples failpoint loses."""
    rng = _pyrandom.Random(fp.seed)
    idx = rng.sample(range(n), min(fp.kill, n))
    errs: List[Optional[Dict[str, Any]]] = [None] * n
    for i in idx:
        errs[i] = {
            "type": "server_error",
            "code": "decode_fault",
            "message": "sample lost mid-decode (injected failpoint engine.decode)",
        }
    return errs


def _quarantine_error() -> Dict[str, Any]:
    return {
        "type": "server_error",
        "code": "numeric_poison",
        "message": (
            "sample quarantined: non-finite or degenerate logits detected "
            "mid-decode"
        ),
    }


def _poisoned_logits(logits: jax.Array) -> jax.Array:
    """[B, V] -> [B] bool: rows whose logits are numerically poisoned — any
    NaN or +Inf anywhere, or EVERY column -Inf (a fully-degenerate
    distribution nothing can be sampled from). Partial -Inf is normal
    (constraint/pad masks), so only the all-masked case counts.

    Runs inside the jitted decode loops each step; it is a reduction over
    logits the step already materialized, so the cost is one fused elementwise
    pass — the price of never letting a poisoned row reach consensus."""
    bad_val = jnp.any(jnp.isnan(logits) | (logits == jnp.inf), axis=-1)
    degenerate = jnp.max(logits, axis=-1) == -jnp.inf
    return jnp.logical_or(bad_val, degenerate)


def _bucket(n: int, minimum: int = 32) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _spec_acceptance_stats(
    count_np: np.ndarray, iters_np: np.ndarray, lookahead: int = 0
) -> Dict[str, Any]:
    """Acceptance observability over a row slice: tokens each row emitted per
    verify it entered. 1.0 = no draft ever accepted; > 1 is the speculative
    win users tune spec_lookahead against. The FIRST token comes from prefill
    logits, not a verify (hence count - 1). Single source for the solo loop,
    the coalesced per-request slices, and the engine-level mirror — the
    convention must never drift between them.

    With ``lookahead`` (= K, drafts proposed per verify) the dict also carries
    raw draft accounting: ``drafted`` = K per verify entered; ``accepted`` =
    emitted tokens beyond the one each verify yields for free (every verify
    emits 1 + accepted_i tokens, and the first token is prefill's)."""
    rates = (count_np - 1.0) / np.maximum(iters_np, 1)
    ran = iters_np > 0
    emitted = np.maximum(count_np - 1, 0)
    stats: Dict[str, Any] = {
        "verify_iterations": int(iters_np.max(initial=0)),
        "tokens_per_iteration": (
            round(float(rates[ran].mean()), 3) if ran.any() else None
        ),
    }
    if lookahead:
        stats["drafted"] = int(iters_np.sum()) * int(lookahead)
        stats["accepted"] = int(np.maximum(emitted - iters_np, 0).sum())
    return stats


class LocalEngine:
    """Owns params on the mesh plus jit caches for prefill/decode/embedding."""

    def __init__(
        self,
        config: ModelConfig | str,
        params: Optional[Dict[str, Any]] = None,
        mesh: Optional[Mesh] = None,
        model_parallel: Optional[int] = None,
        param_seed: int = 0,
        use_mesh: bool = True,
        quantize: "bool | str" = False,
        sp_prefill_min_tokens: Optional[int] = None,
        sp_attention: str = "ring",
        sp_decode: bool = False,
        prefix_cache_size: int = 0,
        prefix_cache_min_reuse: int = 32,
        speculative: Optional[str] = None,
        spec_lookahead: int = 4,
        kv_layout: str = "dense",
        kv_page_size: int = 64,
        kv_pool_pages: Optional[int] = None,
        paged_attention_impl: str = "auto",
        paged_generate_many: bool = True,
    ):
        # First JAX touch of every process that serves: place the persistent
        # compile cache before anything is jitted.
        configure_compile_cache()
        self.config = get_config(config) if isinstance(config, str) else config
        if mesh is None and use_mesh and len(jax.devices()) > 1:
            mesh = auto_mesh(model_parallel=model_parallel)
        self.mesh = mesh
        if quantize is True:
            quantize = "int8"
        if self.config.is_latent or self.config.is_hybrid:
            # What the latent block (models/latent.py) and the hybrid stack
            # (models/hybrid.py) cannot do yet fails here, by name, before
            # anything is built; nothing falls back. The hybrid stack's last
            # two: each needs an answer for the rows' recurrent state that is
            # not written (a snapshot at page boundaries; a decode loop other
            # than the paged continuous one that carries it).
            hybrid = self.config.is_hybrid
            # A hybrid stack without a Mamba-2 layer (the parallel block's) has
            # no state; what it lacks is the dense decode path, unwritten.
            why = ("a recurrent state cannot be rolled back; a cached prefix needs the state "
                   "at its page boundary; only the paged continuous loop carries the state"
                   if "M" in self.config.layer_pattern else
                   "the stack's dense decode and verify steps are not written: the paged "
                   "continuous loop alone serves it")
            # A next-token module drafts in the paged continuous loop alone:
            # its cache layer is a paging layer and its first draft needs the
            # prompt's last hidden state, which a cached prefix does not hold.
            drafts = bool(self.config.num_nextn_predict_layers)
            refused = [
                what for what, asked in (
                    (f"a device mesh ({len(jax.devices())} devices; build the "
                     "engine with use_mesh=False or give the process one device)",
                     mesh is not None),
                    (f"quantize={quantize!r} (int8/int4 expert stacks)", bool(quantize)),
                    ("sp_prefill_min_tokens (sequence-parallel prefill)",
                     sp_prefill_min_tokens is not None),
                    (f"speculative={speculative!r} (the dense-cache speculation path)",
                     speculative is not None),
                    (f"prefix_cache_size={prefix_cache_size}", hybrid and prefix_cache_size > 0),
                    (f"kv_layout={kv_layout!r} (build with kv_layout='paged')",
                     hybrid and kv_layout != "paged"),
                    (f"prefix_cache_size={prefix_cache_size} (the next-token module's first "
                     "draft needs the prompt's last hidden state, which a cached prefix does "
                     "not hold)", drafts and prefix_cache_size > 0),
                    (f"kv_layout={kv_layout!r} (the next-token module drafts in the paged "
                     "continuous loop alone; build with kv_layout='paged')",
                     drafts and kv_layout != "paged"),
                ) if asked
            ]
            if refused:
                block = (f"the hybrid stack ({why})" if hybrid else "the latent block")
                raise NotImplementedError(
                    f"{self.config.name}: {block} is not implemented for " + "; ".join(refused)
                )
        if params is not None and not quantize:
            # A PRE-quantized checkpoint passed with quantize unset must still
            # route through the quantized spec/partitioning machinery: the
            # bf16 pspecs tree doesn't match QTensor/Q4Tensor leaves, so the
            # mesh device_put below would die in an opaque pytree/GSPMD error,
            # and an unmarked Q4Tensor would skip the int4 mesh-compat check
            # (ADVICE r3). Detect the stored layout and follow it.
            from ..models.quant import stored_quant_layout

            layout = stored_quant_layout(params)
            if layout is not None:
                quantize = layout
                logger.info(
                    "params tree is pre-quantized (%s); enabling quantize=%r "
                    "to match the stored layout",
                    self.config.name,
                    quantize,
                )
        int4_mesh_ok: Optional[bool] = None  # evaluated at most once per init
        if mesh is not None and quantize:
            from ..models.quant import int4_mesh_compatible, tree_has_q4

            # A supplied PRE-quantized int4 tree keeps its stored layout
            # through quantize_weight_bits, so mesh compatibility must be
            # checked BEFORE the sharded quantize/put — otherwise pjit fails
            # first with an opaque dimension-not-divisible error (and, with
            # quantize="int4", a misleading int8-downgrade warning).
            stored_q4 = params is not None and tree_has_q4(params)
            if quantize == "int4" or stored_q4:
                int4_mesh_ok = int4_mesh_compatible(
                    self.config, mesh.shape.get(MODEL_AXIS, 1)
                )
            if stored_q4 and not int4_mesh_ok:
                raise ValueError(
                    f"checkpoint stores int4 weights whose quantization groups "
                    f"cannot shard over model parallel="
                    f"{mesh.shape.get(MODEL_AXIS, 1)} for {self.config.name}; "
                    "re-quantize to int8 or change the mesh"
                )
            if quantize == "int4" and not stored_q4 and not int4_mesh_ok:
                # int4 on a mesh runs the w4a16 kernel shard_mapped over the
                # model axis (ops/w4matmul.py::w4_matmul_tp) — possible
                # whenever no quantization group would split across devices;
                # otherwise int8 (XLA-native, partitionable) is the fallback.
                logger.warning(
                    "int4 shards don't align with model parallel=%s for %s; using int8",
                    mesh.shape.get(MODEL_AXIS, 1),
                    self.config.name,
                )
                quantize = "int8"
        self.quantized = quantize
        bits = 4 if quantize == "int4" else 8

        # Specs are needed to place or quantize a tree; a latent model has none
        # yet (parallel/sharding.py refuses) and was held to neither above.
        pspecs = param_specs(self.config) if (mesh is not None or quantize) else None
        if quantize:
            from ..models.quant import quantize_params, quantized_param_specs

            qspecs = quantized_param_specs(pspecs, bits=bits, config=self.config)

        if params is None:
            if quantize:
                # Build the int8/int4 tree directly — an 8B bf16 tree (~16 GB)
                # cannot coexist with its quantized copy in one chip's HBM.
                from ..models.quant import init_params_quantized

                init = partial(init_params_quantized, self.config, bits=bits)
            else:
                init = partial(init_params, self.config)
            if self.mesh is not None:
                init = jax.jit(
                    init,
                    out_shardings=self._shard_tree(qspecs if quantize else pspecs),
                )
            else:
                init = jax.jit(init)
            params = init(jax.random.key(param_seed))
        else:
            if quantize:
                # Quantize on device (jitted) so the bf16 tree never has to fit
                # alongside a second full copy in HBM per-shard. A PRE-quantized
                # checkpoint keeps its stored layout (quantize_weight_bits), so
                # the spec tree must follow the actual leaves, not the request.
                from ..models.quant import align_quantized_specs, tree_fully_quantized

                put_specs = align_quantized_specs(params, qspecs, pspecs)
                if tree_fully_quantized(params):
                    # Nothing to quantize, and a jitted identity would COPY the
                    # tree: a second engine sharing an 8 GB int8 tree (a
                    # speculative or prefix-cache twin beside the serving
                    # engine) must alias it, or it does not fit a 16 GB chip.
                    if self.mesh is not None:
                        params = jax.device_put(params, self._shard_tree(put_specs))
                else:
                    qz = jax.jit(
                        partial(quantize_params, bits=bits),
                        out_shardings=self._shard_tree(put_specs) if self.mesh is not None else None,
                    )
                    params = qz(params)
            elif self.mesh is not None:
                params = jax.device_put(params, self._shard_tree(pspecs))
        if self.mesh is not None and quantize:
            # Mark every int4 leaf with its TP layout — whatever its origin
            # (fresh int4 init, or a pre-quantized checkpoint whose stored
            # int4 layout survives an int8 request). An unmarked Q4Tensor on a
            # mesh would hand GSPMD an unpartitionable pallas call. Mesh
            # compatibility was already enforced above, before any sharded put.
            from ..models.quant import mark_int4_partitioning, tree_has_q4

            if tree_has_q4(params):
                params = mark_int4_partitioning(params, self.mesh)
        self.params = params

        # Sequence-parallel prefill threshold: prompts at least this long
        # route through ring attention over the mesh's data axis (activations
        # and KV sharded O(S/P) per device during prefill) when a mesh exists
        # and the config's attention has no score-level features the ring
        # kernel can't express. None disables the route.
        self.sp_prefill_min_tokens = sp_prefill_min_tokens
        # Context-parallel attention strategy for the SP prefill: "ring"
        # (O(S/P) memory, P-1 hops) or "ulysses" (all-to-all head resharding).
        # Validated eagerly — a typo must fail at construction, not on the
        # first long prompt hours into serving.
        if sp_attention not in ("ring", "ulysses"):
            raise ValueError(
                f"Unknown sp_attention {sp_attention!r}; use 'ring' or 'ulysses'"
            )
        self.sp_attention = sp_attention
        # Ring DECODE against the SP-resident prefix (VERDICT r2 #6): the SP
        # prefill's KV stays sequence-sharded over the data axis and decode
        # attends it in place (K/V chunks rotate the ring each step), so long-
        # context serving is O(S/P) per device end-to-end instead of gathering
        # a replicated prefix for the decode loop. Single-request path only;
        # coalesced batches and the prefix cache keep the replicated layout.
        self.sp_decode = sp_decode

        # Prompt-prefix KV cache (LRU over full prompts, device-resident).
        # Repeated-extraction workloads share a long instruction/system
        # prefix; a new prompt reuses the longest common token prefix of any
        # cached prompt's KV and prefills only the suffix
        # (models/llama.py::prefill_continue). 0 disables.
        self.prefix_cache_size = prefix_cache_size
        self.prefix_cache_min_reuse = prefix_cache_min_reuse
        from collections import OrderedDict

        # value: (first_logits, prefix KVCache, prompt_len, np.int32 token ids,
        #         seq_sharded — each layout continues only in its own layout)
        self._prefix_entries: "OrderedDict[Tuple[int, ...], Tuple[Any, KVCache, int, Any]]" = (
            OrderedDict()
        )
        # Best-effort cache counters: a lost increment under concurrent
        # routes skews stats, never correctness; readers snapshot via dict().
        # kllms: unguarded — best-effort counters; losses skew stats only
        self.prefix_cache_stats = {"hits": 0, "partial_hits": 0, "misses": 0}
        # Speculative-decode counters, same contract as prefix_cache_stats:
        # published whole-object after each spec decode, snapshot via dict().
        # kllms: unguarded — best-effort counters; losses skew stats only
        self.spec_stats: Dict[str, Any] = {}
        # Abort-flag budgets and streaming token sinks for in-flight decodes:
        # published/retracted by the single generating thread; the jitted
        # io_callback reader tolerates a stale or missing snapshot.
        # kllms: unguarded — single-writer publish; io_callback reads tolerate staleness
        self._active_budgets: Dict[int, Any] = {}
        # kllms: unguarded — single-writer publish; io_callback reads tolerate staleness
        self._active_token_sinks: Dict[int, Any] = {}
        # Runtime twin of the annotations above: the lockset sanitizer
        # (KLLMS_RACECHECK=1) skips exactly the fields the static rule skips.
        race_exempt(
            self,
            "prefix_cache_stats",
            "spec_stats",
            "_active_budgets",
            "_active_token_sinks",
            "_tap_state",
            "_kv_pool",
        )

        # Paged KV layout (engine/paging.py): prefix-cache entries and the
        # continuous decode loop's slots hold refcounted PAGES of a fixed pool
        # instead of dense per-row caches, so an n-way fan-out's shared prompt
        # is stored once physically. "dense" keeps every path exactly as
        # before (the config-selected fallback the differential tests compare
        # against). The pool is built lazily on first paged use.
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"Unknown kv_layout {kv_layout!r}; use 'dense' or 'paged'")
        self.kv_layout = kv_layout
        self.kv_page_size = int(kv_page_size)
        self.kv_pool_pages = kv_pool_pages
        # Paged-attention kernel selection ("auto" picks Pallas on TPU, the
        # jittable XLA reference elsewhere; see ops/paged_attention.py). The
        # choice is resolved once per launch/loop build, never per step.
        from ..ops.paged_attention import PAGED_ATTENTION_IMPLS

        if paged_attention_impl not in PAGED_ATTENTION_IMPLS:
            raise ValueError(
                f"Unknown paged_attention_impl {paged_attention_impl!r}; "
                f"use one of {PAGED_ATTENTION_IMPLS}"
            )
        self.paged_attention_impl = paged_attention_impl
        # When the engine is paged, coalesced generate_many launches decode
        # against pool block tables too (dense stays the fallback on pool
        # exhaustion and the comparison baseline for differential tests).
        self.paged_generate_many = bool(paged_generate_many)
        # Published once under _paged_mutex by _ensure_kv_pool and never
        # replaced (a rebuild swaps the whole engine); unsynchronized readers
        # (health(), loop sizing) tolerate the pre-publish None via getattr.
        # kllms: unguarded — publish-once under _paged_mutex; readers tolerate None
        self._kv_pool: Optional[Any] = None
        # Serializes paged cache-entry/allocator mutation between the
        # continuous-loop worker and scheduler threads (dense entries are
        # immutable arrays and never needed this; page refcounts do).
        # allow_dispatch: paged admission prefills under this mutex so page
        # reservation and the KV writes they cover commit atomically.
        self._paged_mutex = make_rlock("engine.paged_mutex", allow_dispatch=True)

        # Speculative decoding: "prompt_lookup" drafts the next spec_lookahead
        # tokens from the prompt's own text and verifies them in one forward
        # (ops/speculative.py). Opt-in; sampling distribution is exact at any
        # temperature (sample-and-match acceptance).
        if speculative not in (None, "prompt_lookup"):
            raise ValueError(
                f"Unknown speculative mode {speculative!r}; use 'prompt_lookup'"
            )
        self.speculative = speculative
        self.spec_lookahead = max(1, int(spec_lookahead))
        # Last speculative request's acceptance stats (verify_iterations,
        # tokens_per_iteration) — the knob users tune spec_lookahead against.
        self.spec_stats: Dict[str, Any] = {}

        # Device-OOM recovery (PR 2): generate_many catches RESOURCE_EXHAUSTED
        # from a coalesced launch and recursively halves the group instead of
        # failing every member. The scheduler subscribes via these hooks to
        # back off / restore its coalescing width.
        self.oom_stats: Dict[str, int] = {"splits": 0, "unrecovered": 0}
        self.on_oom: Optional[Any] = None  # called once per caught device OOM
        self.on_launch_ok: Optional[Any] = None  # called after clean launches
        # Called with the spec_stats dict after every speculative launch, so
        # the scheduler/observability layer can aggregate drafted/accepted
        # without polling the engine.
        self.on_spec_stats: Optional[Any] = None
        # Numeric-integrity quarantine: cumulative counts plus a per-launch
        # hook (poisoned_rows, total_rows) the supervisor subscribes to for
        # poison-rate escalation. Clean launches report (0, total) so the
        # supervisor's rate window decays.
        self.quarantine_stats: Dict[str, int] = {"samples": 0, "launches": 0}
        self.on_quarantine: Optional[Any] = None

        self._prefill_cache: Dict[Any, Any] = {}
        self._sp_prefill_cache: Dict[Any, Any] = {}
        self._sp_continue_cache: Dict[Any, Any] = {}
        self._continue_cache: Dict[Any, Any] = {}
        self._chunk_cache: Dict[Any, Any] = {}
        self._decode_cache: Dict[Any, Any] = {}
        self._spec_decode_cache: Dict[Any, Any] = {}
        self._embed_cache: Dict[Any, Any] = {}

    # -- sharding helpers -------------------------------------------------
    def _shard_tree(self, spec_tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree)

    def _constraint(self, x, spec):
        if self.mesh is None:
            return x
        return lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    @property
    def data_parallel_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[DATA_AXIS]

    def param_footprint_bytes(self) -> int:
        """Total bytes of the resident parameter tree (sum over leaves; a
        quantized tree reports its quantized size). Feeds the backend's HBM
        memory model — measured from the actual leaves rather than re-derived
        from the config so quantization/layout choices are automatically
        reflected."""
        total = 0
        for leaf in jax.tree.leaves(self.params):
            nbytes = getattr(leaf, "nbytes", None)
            if nbytes is not None:
                total += int(nbytes)
        return total

    # -- prefill ----------------------------------------------------------
    def _get_prefill(self, bucket: int):
        fn = self._prefill_cache.get(bucket)
        if fn is None:
            def _prefill(params, tokens, prompt_len):
                # Beside the logits and the cache: what the stack counted
                # and the prompt's final recurrent state, as the chunk
                # program returns them (both empty for most models).
                state: Dict[str, Any] = {}
                aux: Dict[str, Any] = {}
                return prefill(
                    self.config, params, tokens, prompt_len, mesh=self.mesh,
                    aux=aux, state=state,
                ) + (aux, state)

            if self.mesh is not None:
                out_shardings = (
                    NamedSharding(self.mesh, P(None, None)),
                    KVCache(
                        k=NamedSharding(self.mesh, cache_specs(shared_prefix=True)),
                        v=NamedSharding(self.mesh, cache_specs(shared_prefix=True)),
                    ),
                    {},
                    {},
                )
                fn = jax.jit(_prefill, out_shardings=out_shardings)
            else:
                fn = jax.jit(_prefill)
            self._prefill_cache[bucket] = fn
        return fn

    def _use_sp_prefill(self, prompt_len: int, bucket: int) -> bool:
        config = self.config
        return (
            self.mesh is not None
            and self.sp_prefill_min_tokens is not None
            and prompt_len >= self.sp_prefill_min_tokens
            and self.mesh.shape[DATA_AXIS] > 1
            # forward_sequence_parallel hard-requires S % ring == 0.
            and bucket % self.mesh.shape[DATA_AXIS] == 0
            and config.attn_softcap is None
            and config.sliding_window is None
        )

    def _get_sp_prefill(self, bucket: int):
        """Jitted sequence-parallel prefill (ring attention over the data
        axis): same (first_logits, prefix KVCache) contract as the dense
        prefill, with the prefix resharded to the decode layout on the way
        out."""
        fn = self._sp_prefill_cache.get(bucket)
        if fn is None:
            from .long_context import forward_sequence_parallel

            config = self.config
            mesh = self.mesh

            from ..models.llama import _logits

            def _sp(params, tokens, prompt_len):
                # Ignore the full [B, S, V] logits (XLA dead-code-eliminates
                # the O(S*V) projection when unused) and project only the last
                # prompt position's hidden state — the logits matmul over the
                # whole sequence would dwarf the O(S/P) memory budget this
                # path exists for.
                _, h, kv = forward_sequence_parallel(
                    config, params, tokens, mesh,
                    seq_axis=DATA_AXIS, attention=self.sp_attention,
                )
                h_last = lax.dynamic_slice_in_dim(h, prompt_len - 1, 1, axis=1)
                return _logits(config, params, h_last)[:, 0, :], kv

            # sp_decode keeps the KV SEQUENCE-SHARDED for ring decode (the
            # whole point: never materialize a replicated O(S) prefix copy);
            # otherwise reshard to the replicated decode layout on the way out.
            kv_spec = (
                P(None, None, DATA_AXIS, MODEL_AXIS, None)
                if self.sp_decode
                else cache_specs(shared_prefix=True)
            )
            out_shardings = (
                NamedSharding(mesh, P(None, None)),
                KVCache(
                    k=NamedSharding(mesh, kv_spec),
                    v=NamedSharding(mesh, kv_spec),
                ),
            )
            fn = jax.jit(_sp, out_shardings=out_shardings)
            self._sp_prefill_cache[bucket] = fn
        return fn

    def _get_sp_continue(self, s_bucket: int, in_bucket: int, out_bucket: int):
        """Jitted ring-layout continuation prefill (VERDICT r3 #6): suffix
        tokens forward against an SP-resident prefix, suffix KV scattered into
        the sequence-sharded layout — same (first_logits, prefix) contract as
        the SP prefill, prefix at ``out_bucket``."""
        key = (s_bucket, in_bucket, out_bucket)
        fn = self._sp_continue_cache.get(key)
        if fn is None:
            from .long_context import forward_sp_continuation

            mesh = self.mesh

            def _cont(params, suffix_tokens, prefix, plen, total):
                return forward_sp_continuation(
                    self.config, params, suffix_tokens, prefix, mesh,
                    plen, total, out_bucket, seq_axis=DATA_AXIS,
                )

            kv_spec = P(None, None, DATA_AXIS, MODEL_AXIS, None)
            out_shardings = (
                NamedSharding(mesh, P(None, None)),
                KVCache(
                    k=NamedSharding(mesh, kv_spec),
                    v=NamedSharding(mesh, kv_spec),
                ),
            )
            fn = jax.jit(_cont, out_shardings=out_shardings)
            self._sp_continue_cache[key] = fn
        return fn

    def _sp_prefix_match(self, ids: List[int]) -> Tuple[Optional[KVCache], int]:
        """Longest common token prefix across SEQUENCE-SHARDED cache entries
        only (the ring-decode route's counterpart of _prefix_match — the two
        layouts never cross-match; each route continues in its own layout)."""
        return self._match_prefix_entries(ids, want_seq_sharded=True)

    def _sp_prefill_routed(self, prompt_ids: List[int], prompt_len: int, bucket: int):
        """SP-resident prefill through the prefix cache: exact hit -> zero
        device work; partial hit past the reuse threshold -> ring-layout
        continuation (suffix-only forward, O(S/P) per device throughout);
        miss -> full sequence-parallel prefill. Stores the resulting
        sequence-sharded entry either way."""
        config = self.config
        if not self.prefix_cache_size:
            return self._prefill_full(prompt_ids, prompt_len, bucket)
        key = tuple(prompt_ids)
        # Exact hits must honor the layout label (entry index 4): a REPLICATED
        # entry handed to ring decode gathers the whole prefix into every
        # device's HBM — the exact spike sp_decode exists to avoid. Treat a
        # wrong-layout hit as a miss; the full SP prefill below overwrites the
        # entry with its sequence-sharded twin.
        with self._paged_mutex:
            hit = self._prefix_entries.get(key)
            if hit is not None and hit[4]:
                self._prefix_entries.move_to_end(key)
                self.prefix_cache_stats["hits"] += 1
                return hit[0], hit[1]

        matched_kv, p = self._sp_prefix_match(prompt_ids)
        if matched_kv is not None and p >= self.prefix_cache_min_reuse:
            s_bucket = _bucket(max(1, prompt_len - p), minimum=32)
            in_bucket = int(matched_kv.k.shape[2])
            out_bucket = max(bucket, in_bucket)
            ring = self.mesh.shape[DATA_AXIS]
            # The suffix self-attention materializes a per-layer f32 score
            # tensor [QH, Ssuf, Ssuf]; past the cap the full SP prefill is the
            # better program (ring attention, O(S/P) scores).
            continuation_ok = (
                p + s_bucket <= config.max_seq_len
                and out_bucket % ring == 0
                and config.num_heads * s_bucket * s_bucket * 4
                <= self.MAX_CONT_SCORE_BYTES
            )
            if continuation_ok:
                self.prefix_cache_stats["partial_hits"] += 1
                suffix = prompt_ids[p:]
                suffix_tokens = jnp.array(
                    [suffix + [config.pad_token_id] * (s_bucket - len(suffix))],
                    jnp.int32,
                )
                first_logits, prefix = self._get_sp_continue(
                    s_bucket, in_bucket, out_bucket
                )(
                    self.params, suffix_tokens, matched_kv,
                    jnp.int32(p), jnp.int32(prompt_len),
                )
                self._prefix_store(
                    prompt_ids, first_logits, prefix,
                    seq_sharded=self._kv_seq_sharded(prefix),
                )
                return first_logits, prefix

        self.prefix_cache_stats["misses"] += 1
        first_logits, prefix = self._prefill_full(prompt_ids, prompt_len, bucket)
        self._prefix_store(
            prompt_ids, first_logits, prefix,
            seq_sharded=self._kv_seq_sharded(prefix),
        )
        return first_logits, prefix

    # -- prefix cache ------------------------------------------------------
    def _get_prefill_continue(self, s_bucket: int, total_bucket: int):
        """Jitted suffix prefill: writes suffix KV into the reused prefix
        cache at write_index=prefix_len; same output contract as prefill."""
        key = (s_bucket, total_bucket)
        fn = self._continue_cache.get(key)
        if fn is None:
            def _cont(params, suffix_tokens, cache, prefix_len, total_len):
                return prefill_continue(
                    self.config, params, suffix_tokens, cache, prefix_len,
                    total_len, mesh=self.mesh,
                )

            if self.mesh is not None:
                out_shardings = (
                    NamedSharding(self.mesh, P(None, None)),
                    KVCache(
                        k=NamedSharding(self.mesh, cache_specs(shared_prefix=True)),
                        v=NamedSharding(self.mesh, cache_specs(shared_prefix=True)),
                    ),
                )
                fn = jax.jit(_cont, out_shardings=out_shardings, donate_argnums=(2,))
            else:
                fn = jax.jit(_cont, donate_argnums=(2,))
            self._continue_cache[key] = fn
        return fn

    def _get_prefill_chunk(self, c_bucket: int, total_bucket: int, paged: bool):
        """Jitted chunked-prefill step (continuous loop): extend a staging
        prefix cache by one C-token chunk at a dynamic cursor. The paged
        variant additionally returns the chunk's KV columns for the caller to
        scatter into the row's page run. Same model path as
        :func:`_get_prefill_continue` — byte-identity with whole-prompt
        prefill is structural, not re-derived."""
        key = (c_bucket, total_bucket, paged)
        fn = self._chunk_cache.get(key)
        if fn is None:
            step = prefill_chunk_step_paged if paged else prefill_chunk_step

            def _chunk(params, chunk_tokens, cache, cursor, valid_len, state=None):
                # ``state``: the prompt's recurrent state so far, handed back
                # (last output) as it is after the chunk's valid tokens; an
                # empty dict for most models: no operand. Before it: what the
                # model's stack counted (see the loop's step programs);
                # likewise.
                aux: Dict[str, Any] = {}
                state = dict(state or {})
                return step(
                    self.config, params, chunk_tokens, cache, cursor, valid_len,
                    mesh=self.mesh, aux=aux, state=state,
                ) + (aux, state)

            if self.mesh is not None:
                kv_sh = KVCache(
                    k=NamedSharding(self.mesh, cache_specs(shared_prefix=True)),
                    v=NamedSharding(self.mesh, cache_specs(shared_prefix=True)),
                )
                logits_sh = NamedSharding(self.mesh, P(None, None))
                if paged:
                    # Chunk KV columns [L, C, KVH, D]: heads shard tp, like
                    # the pool they are scattered into.
                    cols_sh = NamedSharding(self.mesh, P(None, None, MODEL_AXIS, None))
                    out_shardings = (logits_sh, kv_sh, cols_sh, cols_sh, {}, {})
                else:
                    out_shardings = (logits_sh, kv_sh, {}, {})
                fn = jax.jit(_chunk, out_shardings=out_shardings, donate_argnums=(2,),
                             donate_argnames=("state",))
            else:
                fn = jax.jit(_chunk, donate_argnums=(2,), donate_argnames=("state",))
            self._chunk_cache[key] = fn
        return fn

    def prefix_cached_len(self, prompt_ids: List[int]) -> int:
        """How many leading tokens of ``prompt_ids`` the prefix cache can
        supply without device work: the full length on a usable exact hit, the
        common-prefix length on a partial hit past the reuse threshold, else
        0. A pure probe — no LRU bump, no stats, no device work — used by the
        continuous loop to decide whether a long admission should take the
        cache path (zero/short prefill) or chunked prefill."""
        if self.prefix_cache_size <= 0:
            return 0
        key = tuple(prompt_ids)
        with self._paged_mutex:
            hit = self._prefix_entries.get(key)
            if hit is not None and not hit[4]:
                return len(prompt_ids)
        _, p = self._prefix_match(list(prompt_ids))
        return p if p >= self.prefix_cache_min_reuse else 0

    def _prefix_store_paged_run(self, ids: List[int], first_logits, run) -> None:
        """Insert an ALREADY-SCATTERED page run as a prefix-cache entry (the
        chunked-prefill finish path: the prompt's KV is already resident in
        the pool, so re-deriving a run from dense would scatter it twice).
        The caller transfers one reference to the cache; with the cache
        disabled the reference is released immediately."""
        from .paging import PagedPrefixRun

        with self._paged_mutex:
            if self.prefix_cache_size <= 0:
                run.release()
                return
            key = tuple(ids)
            old = self._prefix_entries.get(key)
            if old is not None and isinstance(old[1], PagedPrefixRun):
                old[1].release()
            self._prefix_entries[key] = (
                first_logits, run, len(ids), np.asarray(ids, np.int32), False
            )
            self._prefix_entries.move_to_end(key)
            while len(self._prefix_entries) > self.prefix_cache_size:
                _, evicted = self._prefix_entries.popitem(last=False)
                if isinstance(evicted[1], PagedPrefixRun):
                    evicted[1].release()

    @staticmethod
    def _kv_seq_sharded(kv: KVCache) -> bool:
        """Whether a prefix KV is stored SEQUENCE-SHARDED (axis 2 of
        [L, B, S, KVH, D] partitioned over the data axis) — read from the
        array's actual sharding, not from re-deriving the routing predicate,
        so the label can never desync from the layout it describes."""
        spec = getattr(getattr(kv.k, "sharding", None), "spec", None)
        return bool(spec is not None and len(spec) > 2 and spec[2] == DATA_AXIS)

    # -- paged KV pool -----------------------------------------------------

    def _ensure_kv_pool(self, min_pages: int = 0):
        """Build (or return) the engine's page pool. Sizing: an explicit
        ``kv_pool_pages`` wins; otherwise the caller's ``min_pages`` (the
        continuous loop passes its worst-case working set). The pool is a
        fixed allocation for the engine's lifetime — a rebuild replaces the
        whole engine, pool included."""
        from .paging import PagedKVPool

        with self._paged_mutex:
            if self._kv_pool is None:
                from .paging import pages_for

                # Default sizing mirrors what the DENSE prefix cache would
                # hold: one mid-size run per entry plus one in flight. An
                # explicit kv_pool_pages or a larger caller min_pages wins.
                cache_pages = 0
                if self.prefix_cache_size:
                    cache_pages = (self.prefix_cache_size + 1) * pages_for(
                        min(self.config.max_seq_len, 2048), self.kv_page_size
                    )
                total = max(
                    int(self.kv_pool_pages or 0), int(min_pages),
                    cache_pages, 8,
                )
                self._kv_pool = PagedKVPool(self.config, total, self.kv_page_size)
                if self.mesh is not None:
                    # Pool layout [L, flat, KVH, D]: kv heads sharded on the
                    # tp axis, like every dense KV buffer here.
                    self._kv_pool.kv = jax.device_put(
                        self._kv_pool.kv,
                        KVCache(
                            k=NamedSharding(self.mesh, P(None, None, MODEL_AXIS, None)),
                            v=NamedSharding(self.mesh, P(None, None, MODEL_AXIS, None)),
                        ),
                    )
            return self._kv_pool

    def _alloc_pages_with_evict(self, count: int) -> List[int]:
        """Allocate pages, evicting LRU paged cache entries under pressure.
        Caller holds ``_paged_mutex``. Raises PagePoolExhausted only when the
        pool is short even with every evictable entry gone."""
        from .paging import PagePoolExhausted

        alloc = self._kv_pool.allocator
        try:
            return alloc.alloc(count)
        except PagePoolExhausted:
            self._evict_paged_entries(need_pages=count - alloc.free_pages)
            return alloc.alloc(count)

    def _evict_paged_entries(self, need_pages: int) -> int:
        """Evict paged prefix-cache entries LRU-first until ``need_pages``
        pages have actually returned to the free stack. Pages still referenced
        by in-flight rows (or by a younger entry extending this one) survive
        the eviction — only the entry's own reference drops, and the last
        reader's retirement frees them (pinned by
        test_paged_eviction.py)."""
        from .paging import PagedPrefixRun

        freed = 0
        for key in list(self._prefix_entries.keys()):
            if freed >= need_pages:
                break
            run = self._prefix_entries[key][1]
            if isinstance(run, PagedPrefixRun):
                del self._prefix_entries[key]
                freed += run.release()
        return freed

    def _run_from_dense(
        self,
        prefix: KVCache,
        plen: int,
        bucket: int,
        base_run=None,
        base_len: int = 0,
    ):
        """Convert a dense prefill result [L, 1, bucket, KVH, D] into a page
        run. When ``base_run`` is the cache entry this prefill CONTINUED from,
        its full pages below ``base_len`` are SHARED (incref, no copy, no
        rewrite) — the continuation seeded its cache from those exact bits, so
        sharing preserves the bit-equality contract; only the new tail is
        scattered. Caller holds ``_paged_mutex``."""
        from .paging import TRASH_PAGE, PagedPrefixRun, flat_slots, pages_for

        pool = self._ensure_kv_pool()
        ps = pool.page_size
        npages = pages_for(plen, ps)
        shared = 0
        if base_run is not None:
            shared = min(min(base_len, plen) // ps, npages)
            if shared:
                pool.allocator.incref(base_run.pages[:shared])
        try:
            fresh = self._alloc_pages_with_evict(npages - shared)
        except Exception:
            if shared:
                pool.allocator.decref(base_run.pages[:shared])
            raise
        pages = list(base_run.pages[:shared] if shared else []) + fresh
        # Fixed-length scatter (bucket positions → few jit variants): shared
        # pages and post-prompt positions retarget into the trash page, whose
        # contents are don't-care by contract.
        idx = flat_slots(pages, np.arange(bucket), ps)
        trash = (np.arange(bucket) % ps + TRASH_PAGE * ps).astype(np.int32)
        if shared:
            idx[: shared * ps] = trash[: shared * ps]
        idx[plen:] = trash[plen:]
        pool.scatter_tokens(prefix.k[:, 0], prefix.v[:, 0], idx)
        return PagedPrefixRun(pool, pages, plen, bucket)

    def _entry_prefix_kv(self, entry) -> KVCache:
        """Entry slot 1 as dense arrays (materializing a page run)."""
        from .paging import PagedPrefixRun

        kv = entry[1]
        if isinstance(kv, PagedPrefixRun):
            return kv.materialize()
        return kv

    def paged_admit_prefix(self, prompt_ids: List[int], prompt_len: int, bucket: int):
        """Admission-time prefix for the continuous decode loop's PAGED mode:
        returns ``(first_logits, run, transient, state)``; ``state`` is the
        prompt's final recurrent state (``{}`` for a model without any, and
        on a cache hit, which such a model never has: a prefix cache is
        refused for it at build time). A cached paged entry's run
        is returned directly (zero device work, pages shared); otherwise the
        routed prefill runs and its result becomes either the just-stored
        cache run or, with the cache disabled, a TRANSIENT run the caller
        releases after pinning pages per row. May raise
        :class:`~.paging.PagePoolExhausted` — the loop keeps the request
        queued and retries after retirements free pages."""
        from .paging import PagedPrefixRun

        key = tuple(prompt_ids)
        with self._paged_mutex:
            if self.prefix_cache_size > 0:
                hit = self._prefix_entries.get(key)
                if hit is not None and isinstance(hit[1], PagedPrefixRun):
                    self._prefix_entries.move_to_end(key)
                    self.prefix_cache_stats["hits"] += 1
                    return hit[0], hit[1], False, {}
        first_logits, prefix, state = self._prefill_routed(
            prompt_ids, prompt_len, bucket, with_state=True
        )
        with self._paged_mutex:
            if self.prefix_cache_size > 0:
                hit = self._prefix_entries.get(key)
                if hit is not None and isinstance(hit[1], PagedPrefixRun):
                    return first_logits, hit[1], False, state
            run = self._run_from_dense(prefix, prompt_len, bucket)
            return first_logits, run, True, state

    def _prefix_store(
        self,
        ids: List[int],
        first_logits,
        prefix: KVCache,
        seq_sharded: bool = False,
        base_run=None,
        base_len: int = 0,
    ) -> None:
        from .paging import PagedPrefixRun, PagePoolExhausted

        stored = prefix
        with self._paged_mutex:
            if self.kv_layout == "paged" and not seq_sharded:
                # Entries live as page runs; sibling entries extending a
                # common prefix SHARE its full pages instead of copying
                # (base_run). Pool pressure falls back to a dense entry —
                # correctness never depends on pages being available.
                try:
                    stored = self._run_from_dense(
                        prefix, len(ids), int(prefix.k.shape[2]),
                        base_run=base_run, base_len=base_len,
                    )
                except PagePoolExhausted:
                    stored = prefix
            key = tuple(ids)
            old = self._prefix_entries.get(key)
            if old is not None and isinstance(old[1], PagedPrefixRun):
                old[1].release()
            self._prefix_entries[key] = (
                first_logits, stored, len(ids), np.asarray(ids, np.int32), seq_sharded
            )
            self._prefix_entries.move_to_end(key)
            while len(self._prefix_entries) > self.prefix_cache_size:
                _, evicted = self._prefix_entries.popitem(last=False)
                if isinstance(evicted[1], PagedPrefixRun):
                    evicted[1].release()

    def _prefix_match(self, ids: List[int]) -> Tuple[Optional[KVCache], int]:
        """Longest common token prefix across cached prompts (vectorized —
        long prompts are exactly the cache's target workload). Returns the
        matched entry's KV and the usable common length (capped below the new
        prompt's length so there is always >=1 suffix token to prefill).

        Sequence-sharded entries (sp_decode) are skipped: the REPLICATED
        continuation prefill padding/slicing one would all-gather the full
        O(S) prefix onto every device. They have their own continuation in
        their own layout instead (_sp_prefix_match + _sp_prefill_routed)."""
        return self._match_prefix_entries(ids, want_seq_sharded=False)

    def _match_prefix_entries(
        self, ids: List[int], want_seq_sharded: bool
    ) -> Tuple[Optional[KVCache], int]:
        """The shared longest-common-prefix scan over cache entries of ONE
        layout (capping rules live here, once for both routes)."""
        ids_np = np.asarray(ids, np.int32)
        best_kv, best_p = None, 0
        with self._paged_mutex:
            for _, kv, plen, arr, seq_sharded in self._prefix_entries.values():
                if seq_sharded != want_seq_sharded:
                    continue
                limit = min(len(ids) - 1, plen)
                neq = np.flatnonzero(arr[:limit] != ids_np[:limit])
                p = int(neq[0]) if neq.size else limit
                if p > best_p:
                    best_p, best_kv = p, kv
        return best_kv, best_p

    # Where attention_impl resolves to "xla" (always off-TPU), continuation
    # prefill materializes a per-layer f32 score tensor [num_heads, s_bucket,
    # cont_bucket]; cap it at ~1 GB and fall back to FULL prefill beyond. The
    # flash kernel's q_offset mode runs the suffix with no score tensor in
    # HBM, so the cap — and the fallback — don't apply at any suffix length.
    MAX_CONT_SCORE_BYTES = 1 << 30

    def _prefill_with_cache(
        self,
        prompt_ids: List[int],
        prompt_len: int,
        bucket: int,
        allow_seq_sharded: bool = False,
    ):
        """Prefill through the prompt-prefix cache: exact hit -> zero device
        work; partial hit past the reuse threshold -> suffix-only prefill;
        miss -> full (dense or sequence-parallel) prefill. Always stores the
        resulting full-prompt KV back into the LRU.

        ``allow_seq_sharded``: exact hits on SEQUENCE-SHARDED entries are only
        returned when the caller declares it reshards them (generate_many's
        replicated coalesced path does); otherwise the wrong-layout hit is a
        miss — the mirror of _sp_prefill_routed's layout check."""
        from .paging import PagedPrefixRun

        key = tuple(prompt_ids)
        with self._paged_mutex:
            hit = self._prefix_entries.get(key)
            if hit is not None and (allow_seq_sharded or not hit[4]):
                self._prefix_entries.move_to_end(key)
                self.prefix_cache_stats["hits"] += 1
                return hit[0], self._entry_prefix_kv(hit)

            matched_kv, p = self._prefix_match(prompt_ids)
            matched_run = matched_kv if isinstance(matched_kv, PagedPrefixRun) else None
            if matched_run is not None:
                # Pin the matched run's pages for the duration of this call:
                # a concurrent store's eviction must not free them while the
                # continuation reads them (or before the new entry increfs
                # the shared prefix pages).
                matched_run.retain()
        try:
            return self._prefill_with_cache_matched(
                prompt_ids, prompt_len, bucket, matched_kv, matched_run, p
            )
        finally:
            if matched_run is not None:
                with self._paged_mutex:
                    self._kv_pool.allocator.decref(matched_run.pages)

    def _prefill_with_cache_matched(
        self, prompt_ids, prompt_len, bucket, matched_kv, matched_run, p
    ):
        config = self.config
        s_bucket = _bucket(max(1, prompt_len - p), minimum=32)
        # Power-of-two rounding capped at max_seq_len: no position past the
        # model's maximum is ever addressable, so rows beyond it would be
        # pure allocation waste (p + s_bucket <= max_seq_len is guarded
        # below, so the capped size always fits the write).
        cont_bucket = max(
            bucket, min(_bucket(p + s_bucket, minimum=32), config.max_seq_len)
        )
        continuation_ok = (
            matched_kv is not None
            and p >= self.prefix_cache_min_reuse
            and p + s_bucket <= config.max_seq_len
            and (
                resolve_attention_impl(config.attention_impl) != "xla"
                or config.num_heads * s_bucket * cont_bucket * 4
                <= self.MAX_CONT_SCORE_BYTES
            )
        )
        base_run, base_len = None, 0
        if continuation_ok:
            self.prefix_cache_stats["partial_hits"] += 1
            suffix = prompt_ids[p:]
            suffix_tokens = jnp.array(
                [suffix + [config.pad_token_id] * (s_bucket - len(suffix))], jnp.int32
            )
            # Seed the cache with the reused prefix rows; cont_bucket >= the
            # full bucketed write at position p because dynamic_update_slice
            # silently CLAMPS an out-of-bounds start index (which would land
            # the suffix KV at the wrong rows). The continuation jit donates
            # this buffer and writes the suffix KV in place.
            if matched_run is not None:
                # Paged entry: gather positions [0, p) out of the pool into
                # the dense seed (bit-identical to the pad-of-slice below at
                # every position the continuation reads).
                cache0 = matched_run.gather_prefix_padded(p, cont_bucket)
                base_run, base_len = matched_run, p
            else:
                pad = [(0, 0)] * 5
                pad[2] = (0, cont_bucket - p)
                cache0 = KVCache(
                    k=jnp.pad(matched_kv.k[:, :, :p], pad),
                    v=jnp.pad(matched_kv.v[:, :, :p], pad),
                )
            first_logits, prefix = self._get_prefill_continue(s_bucket, cont_bucket)(
                self.params, suffix_tokens, cache0,
                jnp.int32(p), jnp.int32(prompt_len),
            )
            if cont_bucket != bucket:
                prefix = KVCache(
                    k=prefix.k[:, :, :bucket], v=prefix.v[:, :, :bucket]
                )
        else:
            self.prefix_cache_stats["misses"] += 1
            first_logits, prefix = self._prefill_full(prompt_ids, prompt_len, bucket)
        # With sp_decode, an SP-routed prefill emits SEQUENCE-SHARDED KV;
        # storing it unlabeled would hand it to the partial-hit continuation
        # path later, whose eager slice/pad all-gathers the full O(S) prefix —
        # the exact HBM spike the seq-sharded label exists to prevent
        # (ADVICE r3). The label reads the array's actual layout.
        self._prefix_store(
            prompt_ids, first_logits, prefix,
            seq_sharded=self._kv_seq_sharded(prefix),
            base_run=base_run, base_len=base_len,
        )
        return first_logits, prefix

    def _prefill_full(
        self, prompt_ids: List[int], prompt_len: int, bucket: int, with_state: bool = False
    ):
        """One full-prompt prefill: dense, or sequence-parallel when the
        prompt qualifies (the single dispatch point for generate,
        generate_many, the prefix-cache miss path and the loop's whole-prompt
        admission, which asks ``with_state`` for the prompt's final recurrent
        state as a third result). What the model's stack counted goes to
        ``MODEL_COUNTERS`` here, as the loop's programs' does at readback."""
        tokens = jnp.array(
            [prompt_ids + [self.config.pad_token_id] * (bucket - prompt_len)],
            jnp.int32,
        )
        if self._use_sp_prefill(prompt_len, bucket):
            # Refused at build time for a model with recurrent state.
            return self._get_sp_prefill(bucket)(
                self.params, tokens, jnp.int32(prompt_len)
            ) + (({},) if with_state else ())
        first_logits, prefix, aux, state = self._get_prefill(bucket)(
            self.params, tokens, jnp.int32(prompt_len)
        )
        if aux:
            # kllms: ignore[host-sync-hot-path] — a few counters a prompt; admission reads the first logits next anyway
            note_model_aux(jax.device_get(aux))
        return (first_logits, prefix, state) if with_state else (first_logits, prefix)

    def _prefill_routed(
        self,
        prompt_ids: List[int],
        prompt_len: int,
        bucket: int,
        allow_seq_sharded: bool = False,
        with_state: bool = False,
    ):
        if self.prefix_cache_size > 0:
            # No model with recurrent state gets here: a prefix cache is
            # refused for it at build time.
            return self._prefill_with_cache(
                prompt_ids, prompt_len, bucket, allow_seq_sharded=allow_seq_sharded
            ) + (({},) if with_state else ())
        return self._prefill_full(prompt_ids, prompt_len, bucket, with_state=with_state)

    # -- decode loop ------------------------------------------------------
    # -- cancellation plumbing --------------------------------------------
    def _poll_abort_flags(self, num_requests: int) -> np.ndarray:
        """[R] bool: which active requests' budgets are spent. Reads
        ``_active_budgets`` (set around each decode by generate/generate_many;
        safe shared state — the scheduler serializes device work). Padding
        rows beyond the budget list never abort."""
        budgets = getattr(self, "_active_budgets", None) or []
        out = np.zeros((num_requests,), np.bool_)
        for i, b in enumerate(budgets[:num_requests]):
            if b is not None and b.should_abort():
                out[i] = True
        return out

    def _abort_poller(self, num_requests: int):
        """Host-side budget poll as a jit-safe callable for the decode loops.
        The callback closes over ``self`` (NOT a specific budget), so compiled
        loops cached across requests always read the current request's state.
        ``step`` is a data dependency only — it pins the callback inside the
        while_loop body so XLA cannot hoist or CSE it out."""

        def _host_poll(step):
            del step
            return self._poll_abort_flags(num_requests)

        def poll(step):
            return io_callback(
                _host_poll,
                jax.ShapeDtypeStruct((num_requests,), jnp.bool_),
                step,
                ordered=False,
            )

        return poll

    # -- streaming tap ----------------------------------------------------
    def _reset_tap_state(self) -> None:
        """Per-launch reorder state for the streaming token tap. The scheduler
        serializes device launches, so one tap stream is live at a time."""
        # kllms: unguarded — one launch in flight; serialized by the scheduler, not a lock
        self._tap_state = {"next": 0, "pending": {}, "seen": set()}

    def _deliver_tap_step(self, step: int, toks: np.ndarray) -> None:
        """Deliver one step's tokens to the active sinks IN ORDER. The tap's
        io_callback is unordered (XLA may run it out of step order, twice, or
        drop it if the result were unused — the marker data-dependency
        prevents the last), so arrivals go through a step-keyed reorder
        buffer with a seen-set: sinks observe step 0,1,2,... exactly once.
        Steps that never arrive stall the buffer harmlessly; the backend's
        final flush reconciles against the completed GenerationResult."""
        state = getattr(self, "_tap_state", None)
        sinks = getattr(self, "_active_token_sinks", None)
        if state is None or not sinks:
            return
        if step in state["seen"]:
            return
        state["seen"].add(step)
        state["pending"][step] = toks
        while state["next"] in state["pending"]:
            rows = state["pending"].pop(state["next"])  # [R_pad, n_per]
            for r, sink in enumerate(sinks):
                if sink is None:
                    continue
                try:
                    sink(state["next"], rows[r])
                except Exception:  # a broken sink must not poison decode
                    logger.exception("token sink failed; dropping stream tap")
                    sinks[r] = None
            state["next"] += 1

    def _token_tap(self, num_requests: int, n_per: int):
        """Host-side per-step token delivery as a jit-safe callable, mirroring
        ``_abort_poller``: the callback closes over ``self`` so compiled loops
        cached across requests always feed the CURRENT request's sinks; the
        (R, n_per) grouping is frozen into the closure alongside the compiled
        shape it describes. The returned marker is always False; callers must
        fold it into loop state (``done = done | marker``) so XLA cannot elide
        the unordered callback."""

        def _host_deliver(step, toks):
            try:
                rows = np.asarray(toks).reshape(num_requests, n_per)
                self._deliver_tap_step(int(step), rows)
            except Exception:  # never raise through the runtime
                logger.exception("token tap delivery failed")
            return np.bool_(False)

        def tap(step, toks):
            return io_callback(
                _host_deliver,
                jax.ShapeDtypeStruct((), jnp.bool_),
                step,
                toks,
                ordered=False,
            )

        return tap

    def _apply_decode_faults(
        self, result: GenerationResult, budget: Optional[RequestBudget]
    ) -> GenerationResult:
        """Post-decode fault surfacing for ONE request: a spent budget raises
        its typed lifecycle error (the decode loop already froze the rows);
        an active ``engine.decode`` kill_samples failpoint marks a seeded
        subset of samples lost (tokens cleared, ``sample_errors`` filled) so
        the partial-failure consensus path is exercisable without real
        device faults."""
        if budget is not None and budget.should_abort():
            FAILURE_EVENTS.record("engine.decode_abort")
            raise budget.error("engine decode")
        fp = _failpoints.fire("engine.decode")
        if fp is None or fp.action != "kill_samples" or fp.kill <= 0:
            return result
        n = result.tokens.shape[0]
        errs = _kill_sample_errors(n, fp)
        killed = [i for i, e in enumerate(errs) if e is not None]
        if not killed:
            return result
        FAILURE_EVENTS.record("engine.samples_killed", len(killed))
        if result.sample_errors:
            # Compose with earlier per-sample faults (e.g. quarantine): a kill
            # overwrites, everything else survives.
            errs = [
                e if e is not None else prev
                for e, prev in zip(errs, result.sample_errors)
            ]
        toks = result.tokens.copy()
        lps = result.logprobs.copy()
        lengths = result.lengths.copy()
        for i in killed:
            toks[i, :] = self.config.pad_token_id
            lps[i, :] = 0.0
            lengths[i] = 0
        return result._replace(
            tokens=toks, logprobs=lps, lengths=lengths, sample_errors=errs
        )

    # -- numeric-integrity quarantine --------------------------------------
    def _poison0_array(self, n_rows: int, live_rows: Optional[Sequence[int]] = None) -> jax.Array:
        """First-step poison-injection mask [n_rows] bool for the decode
        loops: all-False in production; with an active ``engine.logits`` nan
        failpoint, a seeded subset of the LIVE rows (padding rows excluded —
        their poison would be invisible) is poisoned. The zeros mask is cached
        per width so the hot path pays no per-launch transfer."""
        fp = _failpoints.fire("engine.logits")
        if fp is not None and fp.action == "nan" and fp.kill > 0:
            rows = list(live_rows) if live_rows is not None else list(range(n_rows))
            rng = _pyrandom.Random(fp.seed)
            chosen = rng.sample(rows, min(fp.kill, len(rows)))
            mask = np.zeros((n_rows,), np.bool_)
            mask[chosen] = True
            return jnp.asarray(mask)
        return self._no_poison(n_rows)

    def _no_poison(self, n_rows: int) -> jax.Array:
        """The all-False mask ``[n_rows]`` bool, one cached device array a
        width: a caller that counts its uploads knows it by identity."""
        cache = getattr(self, "_zero_poison", None)
        if cache is None:
            cache = {}
            self._zero_poison = cache
        cached = cache.get(n_rows)
        if cached is None:
            cached = jnp.zeros((n_rows,), jnp.bool_)
            cache[n_rows] = cached
        return cached

    def _note_quarantine(self, poisoned: int, total: int) -> None:
        """Per-launch quarantine accounting + supervisor hook. Called for
        EVERY launch (clean ones report poisoned=0) so a rate window decays."""
        if poisoned:
            self.quarantine_stats["samples"] += poisoned
            self.quarantine_stats["launches"] += 1
            QUARANTINE_EVENTS.record("quarantine.samples", poisoned)
            QUARANTINE_EVENTS.record("quarantine.launches")
            logger.warning(
                "numeric poison: %d/%d decode row(s) quarantined this launch",
                poisoned,
                total,
            )
        if self.on_quarantine is not None:
            self.on_quarantine(poisoned, total)

    def _quarantine_result(
        self, result: GenerationResult, pois_rows: np.ndarray
    ) -> GenerationResult:
        """Clear quarantined sample rows (tokens→pad, logprobs→0, length→0)
        and mark them as partial-failure members (``sample_errors`` code
        ``numeric_poison``) so PR-1 survivor consensus drops them from the
        vote and scales likelihoods — healthy samples in the same request are
        untouched."""
        killed = np.flatnonzero(pois_rows[: result.tokens.shape[0]])
        if killed.size == 0:
            return result
        toks = result.tokens.copy()
        lps = result.logprobs.copy()
        lengths = result.lengths.copy()
        errs = (
            list(result.sample_errors)
            if result.sample_errors
            else [None] * toks.shape[0]
        )
        for i in killed:
            toks[i, :] = self.config.pad_token_id
            lps[i, :] = 0.0
            lengths[i] = 0
            errs[i] = _quarantine_error()
        return result._replace(
            tokens=toks, logprobs=lps, lengths=lengths, sample_errors=errs
        )

    def _get_decode_loop(
        self,
        num_requests: int,
        n_per: int,
        max_new: int,
        temperature: float,
        top_p: Optional[float],
        top_k: Optional[int],
        constraint: Optional[str] = None,
        top_logprobs: Optional[int] = None,
        frequency_penalty: float = 0.0,
        presence_penalty: float = 0.0,
        use_logit_bias: bool = False,
        use_stops: bool = False,
        sp_prefix: bool = False,
        use_cancel: bool = False,
        use_stream: bool = False,
        paged_impl: Optional[str] = None,
    ):
        """Jitted decode loop for R requests × n_per samples each (R=1 is the
        single-request case; R>1 is the cross-request coalesced batch).
        ``sp_prefix``: the prefix KV arrives sequence-sharded from the SP
        prefill and is attended via ring decode without regathering.
        ``paged_impl``: None decodes against dense caches; a paged-attention
        impl name ("xla" | "pallas" | tests-only "pallas_interpret") decodes
        against the shared page pool through block tables instead — same
        sampler, same key schedule, byte-identical tokens on the "xla" impl.

        Rows are grouped request-major, so each request's shared-prefix KV is
        consumed by its own row group through the reshaped einsum in
        ``_gqa_scores_shared`` — no per-row gather, no prefix duplication.
        Per-row PRNG keys derive from (request key, step, row-within-request),
        so a request's samples are reproducible regardless of what it was
        batched with.
        """
        from .grammar import CompiledGrammar
        from .token_constraint import TokenConstraint

        constraint_key = constraint
        if isinstance(constraint, TokenConstraint):
            constraint_key = ("token", constraint.digest)
        elif isinstance(constraint, CompiledGrammar):
            constraint_key = ("grammar", constraint.digest)
        elif constraint is not None and constraint != "json":
            constraint_key = ("schema", constraint.digest)
        cache_key = (
            num_requests, n_per, max_new, temperature, top_p, top_k, constraint_key,
            top_logprobs, frequency_penalty, presence_penalty, use_logit_bias,
            use_stops, sp_prefix, use_cancel, use_stream, paged_impl,
        )
        fn = self._decode_cache.get(cache_key)
        if fn is not None:
            return fn

        config = self.config
        pad_id = config.pad_token_id
        R, B = num_requests, num_requests * n_per

        cops = _constraint_ops(constraint)
        if cops is not None:
            jt, initial_state, mask_logits, advance = cops

        abort_poll = self._abort_poller(R) if use_cancel else None
        token_tap = self._token_tap(R, n_per) if use_stream else None

        def _row_keys(req_keys, step):
            # fold_in(fold_in(req_key, step), row_within_request): with R=1
            # this is exactly sample_logits' internal per-row fold of a
            # step-folded key, so solo results are R-independent.
            step_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(req_keys, step)
            rk = jax.vmap(
                lambda k: jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(n_per))
            )(step_keys)
            return rk.reshape(B)

        def _run_loop(
            params, kv0, step_fn, prompt_lens, first_logits, req_keys, eos_ids,
            bias, stops, poison0,
        ):
            # Decode-loop core shared by the dense and paged KV layouts:
            # ``kv0`` is the opaque KV carry (a dense gen KVCache, or the
            # paged pool's (k, v) arrays) and ``step_fn(params, cur, step,
            # kv) -> (logits [B, V], kv)`` advances it one token. Everything
            # else — sampling, penalties, constraints, stops, quarantine,
            # streaming, cancellation — is layout-independent.
            # ``bias`` [V] f32 (zeros when use_logit_bias is False — a dead
            # arg then, kept so the signature is uniform): OpenAI logit_bias,
            # applied via the penalty mechanism so reported logprobs stay the
            # unbiased model distribution's.
            # ``poison0`` [B] bool: rows whose first-step logits are forced to
            # NaN (the ``engine.logits`` nan failpoint — all False in
            # production), exercising the same quarantine path a real
            # device-corruption would take.
            # ``stops`` [MAX_STOP_SEQS, MAX_STOP_LEN] int32: tokenized stop
            # sequences, right-aligned and -1-padded; all -1 when unused. A
            # row halts the step its recent-token window matches any stop
            # suffix, so no decode steps (or billing) run past the stop.
            sample = partial(
                sample_logits, temperature=temperature, top_p=top_p, top_k=top_k
            )

            jstate = initial_state(B) if constraint is not None else None

            # pad_id must never be SAMPLED on a live row (lengths count
            # non-pad tokens; an interior pad would punch a hole in the
            # sequence). Masked dynamically because HF tokenizers may map
            # pad onto eos — then it must stay sampleable as the stop token.
            pad_col = jnp.where(
                jnp.isin(jnp.int32(pad_id), eos_ids), 0.0, -jnp.inf
            )

            def _mask_pad(logits):
                return logits.at[:, pad_id].add(pad_col)

            # First token: each request's prefill logits, n_per draws apiece.
            V = first_logits.shape[-1]
            logits0 = jnp.broadcast_to(first_logits[:, None, :], (R, n_per, V)).reshape(B, V)
            if jstate is not None:
                logits0 = mask_logits(jt, logits0, *jstate, eos_ids)
            logits0 = _mask_pad(logits0)
            # Numeric-integrity quarantine, step 0: detect poisoned rows
            # (after injection), then sanitize them to a uniform distribution
            # so sampling's top-p bisection stays well-defined — the row's
            # output is discarded anyway (token forced to pad, row frozen).
            logits0 = jnp.where(poison0[:, None], jnp.nan, logits0)
            bad0 = _poisoned_logits(logits0)
            logits0 = jnp.where(bad0[:, None], 0.0, logits0)
            tok0, lp0 = sample(
                logits0,
                None,
                row_keys=_row_keys(req_keys, jnp.int32(0)),
                penalty=-bias[None, :] if use_logit_bias else None,
            )
            tok0 = jnp.where(bad0, pad_id, tok0).astype(jnp.int32)
            lp0 = jnp.where(bad0, 0.0, lp0)
            tok0 = self._constraint(tok0, batch_spec())
            if jstate is not None:
                jstate = advance(jt, tok0, *jstate)
            done0 = jnp.logical_or(jnp.isin(tok0, eos_ids), bad0)
            if use_stream:
                # Streaming tap, step 0: the marker is constant-False but MUST
                # be folded into loop state or XLA elides the unordered
                # callback (it has no other consumer).
                done0 = jnp.logical_or(done0, token_tap(jnp.int32(0), tok0))

            def _stop_match(recent):
                return stop_window_match(recent, stops)

            if use_stops:
                recent0 = (
                    jnp.full((B, MAX_STOP_LEN), -1, jnp.int32).at[:, -1].set(tok0)
                )
                done0 = jnp.logical_or(done0, _stop_match(recent0))
            else:
                recent0 = jnp.zeros((B, 0), jnp.int32)

            tokens_buf = jnp.full((B, max_new), pad_id, jnp.int32).at[:, 0].set(tok0)
            logprob_buf = jnp.zeros((B, max_new), jnp.float32).at[:, 0].set(lp0)

            # Optional top-k alternatives per step (OpenAI `top_logprobs`),
            # captured from the same post-constraint-mask logits that sampling
            # sees. Zero-size dummies thread through the loop when off.
            K = top_logprobs or 0
            if K:
                t_ids0, t_lps0 = model_top_logprobs(logits0, K)
                tt_buf = jnp.zeros((B, max_new, K), jnp.int32).at[:, 0].set(t_ids0)
                tl_buf = jnp.zeros((B, max_new, K), jnp.float32).at[:, 0].set(t_lps0)
            else:
                tt_buf = jnp.zeros((B, 0, 0), jnp.int32)
                tl_buf = jnp.zeros((B, 0, 0), jnp.float32)

            # Frequency/presence penalties over GENERATED tokens (vLLM
            # semantics): per-row counts live in the loop state; the penalty
            # array shapes the sampling distribution each step. Zero-size
            # dummy when both are off.
            penalized = frequency_penalty != 0.0 or presence_penalty != 0.0
            V_counts = config.vocab_size if penalized else 0
            counts0 = jnp.zeros((B, V_counts), jnp.float32)
            if penalized:
                counts0 = counts0.at[jnp.arange(B), tok0].add(1.0)

            def _penalty(counts):
                pen = None
                if penalized:
                    pen = frequency_penalty * counts + presence_penalty * (
                        counts > 0
                    ).astype(jnp.float32)
                if use_logit_bias:  # penalty is SUBTRACTED; bias adds
                    pen = -bias[None, :] if pen is None else pen - bias[None, :]
                return pen

            def cond(state):
                step, cur, done, *_ = state
                return jnp.logical_and(step < max_new - 1, jnp.logical_not(jnp.all(done)))

            def body(state):
                step, cur, done, kv, toks, lps, tt, tl, counts, jst, recent, pois = state
                logits, kv = step_fn(params, cur, step, kv)
                if jst is not None:
                    logits = mask_logits(jt, logits, *jst, eos_ids)
                logits = _mask_pad(logits)
                # Quarantine: a live row whose logits went non-finite freezes
                # exactly like an eos row (sanitized before sampling so the
                # sampler never sees NaN) and is flagged in ``pois``.
                bad = jnp.logical_and(_poisoned_logits(logits), jnp.logical_not(done))
                logits = jnp.where(bad[:, None], 0.0, logits)
                frozen = jnp.logical_or(done, bad)
                nxt, lp = sample(
                    logits,
                    None,
                    row_keys=_row_keys(req_keys, step + 1),
                    penalty=_penalty(counts),
                )
                nxt = jnp.where(frozen, pad_id, nxt).astype(jnp.int32)
                nxt = self._constraint(nxt, batch_spec())
                if jst is not None:
                    jst = advance(jt, nxt, *jst)  # pad/eos (>=256) freeze the row
                lp = jnp.where(frozen, 0.0, lp)
                toks = lax.dynamic_update_slice(toks, nxt[:, None], (0, step + 1))
                lps = lax.dynamic_update_slice(lps, lp[:, None], (0, step + 1))
                if K:
                    t_ids, t_lps = model_top_logprobs(logits, K)
                    tt = lax.dynamic_update_slice(tt, t_ids[:, None, :], (0, step + 1, 0))
                    tl = lax.dynamic_update_slice(tl, t_lps[:, None, :], (0, step + 1, 0))
                if penalized:
                    # Finished rows emit pad_id; don't count it.
                    counts = counts.at[jnp.arange(B), nxt].add(
                        jnp.where(frozen, 0.0, 1.0)
                    )
                done = jnp.logical_or(frozen, jnp.isin(nxt, eos_ids))
                pois = jnp.logical_or(pois, bad)
                if use_stops:
                    recent = jnp.concatenate([recent[:, 1:], nxt[:, None]], axis=1)
                    done = jnp.logical_or(done, _stop_match(recent))
                if use_cancel:
                    # Token-granularity cancellation: an unordered host
                    # callback polls each request's budget between steps;
                    # aborted requests' row groups freeze like eos rows
                    # (rows are request-major, hence the n_per repeat).
                    aborted = abort_poll(step)
                    done = jnp.logical_or(done, jnp.repeat(aborted, n_per))
                if use_stream:
                    done = jnp.logical_or(done, token_tap(step + 1, nxt))
                return (step + 1, nxt, done, kv, toks, lps, tt, tl, counts, jst, recent, pois)

            state = (
                jnp.int32(0), tok0, done0, kv0, tokens_buf, logprob_buf,
                tt_buf, tl_buf, counts0, jstate, recent0, bad0,
            )
            step, cur, done, kv, toks, lps, tt, tl, _, _, _, pois = lax.while_loop(
                cond, body, state
            )
            return toks, lps, done, tt, tl, pois, kv

        if paged_impl is None:

            def _loop(
                params, prefix: KVCache, prompt_lens, first_logits, req_keys,
                eos_ids, bias, stops, poison0,
            ):
                gen_cache = init_cache(config, B, max_new)
                gen_cache = KVCache(
                    k=self._constraint(gen_cache.k, cache_specs()),
                    v=self._constraint(gen_cache.v, cache_specs()),
                )

                def step_fn(params, cur, step, cache):
                    return decode_step(
                        config, params, cur, step, prompt_lens, cache, prefix,
                        sp_ring_mesh=self.mesh if sp_prefix else None,
                        mesh=self.mesh,
                    )

                toks, lps, done, tt, tl, pois, _ = _run_loop(
                    params, gen_cache, step_fn, prompt_lens, first_logits,
                    req_keys, eos_ids, bias, stops, poison0,
                )
                return toks, lps, done, tt, tl, pois

            fn = jax.jit(_loop)
        else:
            from .paging import scatter_rows

            page_size = self.kv_page_size

            def _loop(
                params, pool_k, pool_v, prefix_idx, gen_idx, prompt_lens,
                first_logits, req_keys, eos_ids, bias, stops, poison0,
            ):
                # Paged twin: rows decode through block tables into the
                # shared page pool. prefix_idx [R, P] is request-level (the
                # gathered prefix keeps the exact [R, P, KVH, D] shape the
                # dense shared-prefix einsum consumes — bit-identity);
                # gen_idx [B, G] maps gen position g to each row's reserved
                # flat slot. The pool arrays are donated and returned: the
                # scatter happens in place on device, and the caller swaps
                # them back into the pool under its lock.
                def step_fn(params, cur, step, kv):
                    pool_k, pool_v = kv
                    logits, k_cols, v_cols = paged_verify_step(
                        config, params, cur[:, None],
                        jnp.broadcast_to(step, (B,)), prompt_lens,
                        KVCache(k=pool_k, v=pool_v), prefix_idx, gen_idx,
                        attn_impl=paged_impl, page_size=page_size,
                        mesh=self.mesh,
                    )
                    slots = lax.dynamic_index_in_dim(
                        gen_idx, step, axis=1, keepdims=False
                    )
                    return logits[:, 0], scatter_rows(pool_k, pool_v, slots, k_cols, v_cols)

                toks, lps, done, tt, tl, pois, (pool_k, pool_v) = _run_loop(
                    params, (pool_k, pool_v), step_fn, prompt_lens,
                    first_logits, req_keys, eos_ids, bias, stops, poison0,
                )
                return toks, lps, done, tt, tl, pois, pool_k, pool_v

            fn = jax.jit(_loop, donate_argnums=(1, 2))
        self._decode_cache[cache_key] = fn
        return fn

    # -- speculative decode loop ------------------------------------------
    def _get_spec_decode_loop(
        self,
        num_requests: int,
        n_per: int,
        max_new: int,
        temperature: float,
        top_p: Optional[float],
        top_k: Optional[int],
        bucket: int,
        constraint: Optional[str] = None,
        top_logprobs: Optional[int] = None,
        frequency_penalty: float = 0.0,
        presence_penalty: float = 0.0,
        use_logit_bias: bool = False,
        use_stops: bool = False,
        use_cancel: bool = False,
        sp_prefix: bool = False,
    ):
        """Jitted prompt-lookup speculative loop for R requests x n_per rows
        (R=1 is the solo case; R>1 the cross-request coalesced batch, each
        row drafting from ITS OWN request's prompt table — VERDICT r3 #5).
        Runs on a mesh too — rows shard over the data axis and the K+1-wide
        verify forward is tensor-parallel like any other forward (r3 #4).

        State carries per-row buffered-token counts instead of a global step:
        each iteration drafts K tokens from the prompt, verifies the row's
        last token + drafts in ONE forward (per-row KV write offsets), samples
        every position from its own conditional, and emits the longest
        confirmed run — 1..K+1 tokens per weight-streaming pass.

        Composes with the full feature set (VERDICT r2 #4) with the SAME
        semantics as the normal loop, exploiting that the emitted prefix at
        block position j is known without sampling (it must equal the drafts):
        - grammar constraints: position j's logits are masked by the automaton
          state advanced through drafts[:j]; a grammar-invalid draft gets
          probability 0 so the sample-and-match chain stops there; the row
          state then re-advances through the actually emitted run;
        - frequency/presence penalties: position j's penalty counts = emitted
          counts + drafts[:j] (exact, closed-form per position);
        - logit_bias: subtracted via the same penalty mechanism;
        - top_logprobs: captured per verified position from the same
          post-mask logits sampling sees, scattered at the emitted offsets.
        """
        from .grammar import CompiledGrammar
        from .token_constraint import TokenConstraint

        K = self.spec_lookahead
        constraint_key = constraint
        if isinstance(constraint, TokenConstraint):
            constraint_key = ("token", constraint.digest)
        elif isinstance(constraint, CompiledGrammar):
            constraint_key = ("grammar", constraint.digest)
        elif constraint is not None and constraint != "json":
            constraint_key = ("schema", constraint.digest)
        cache_key = (
            "spec", num_requests, n_per, max_new, temperature, top_p, top_k, K,
            bucket, constraint_key, top_logprobs, frequency_penalty,
            presence_penalty, use_logit_bias, use_stops, use_cancel, sp_prefix,
        )
        fn = self._spec_decode_cache.get(cache_key)
        if fn is not None:
            return fn

        from ..ops.speculative import (
            accept_drafts,
            propose_prompt_lookup,
            scatter_rows,
            scatter_rows_k,
        )

        config = self.config
        pad_id = config.pad_token_id
        R, B = num_requests, num_requests * n_per
        BUF = max_new + K + 1
        cops = _constraint_ops(constraint)
        if cops is not None:
            jt, initial_state, mask_logits, advance = cops
        penalized = frequency_penalty != 0.0 or presence_penalty != 0.0
        KT = top_logprobs or 0
        abort_poll = self._abort_poller(R) if use_cancel else None

        def _row_keys(req_keys, step_id):
            # fold(req key, step) then row-WITHIN-request: a request's sampling
            # stream is independent of what it was batched with (and, with
            # R=1, identical to the solo loop's fold chain).
            sk = jax.vmap(jax.random.fold_in, in_axes=(0, None))(req_keys, step_id)
            rk = jax.vmap(
                lambda k: jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(n_per))
            )(sk)
            return rk.reshape(B)

        def _sel(cond, a, b):
            """where() with ``cond`` [B] broadcast over a/b's trailing dims."""
            return jnp.where(cond.reshape(cond.shape + (1,) * (a.ndim - 1)), a, b)

        def _loop(
            params, prefix, prompt_tokens, prompt_lens, first_logits, req_keys,
            eos_ids, bias, stops, poison0,
        ):
            # prompt_tokens [R, S] / prompt_lens [R]: each request's padded
            # prompt table; rows are request-major so row b drafts from table
            # b // n_per (materialized per-row below for the vmapped lookup).
            sample = partial(
                sample_logits, temperature=temperature, top_p=top_p, top_k=top_k
            )
            pad_col = jnp.where(jnp.isin(jnp.int32(pad_id), eos_ids), 0.0, -jnp.inf)

            def _mask_pad(lg):
                return lg.at[:, pad_id].add(pad_col)

            def _stop_match(window):
                return stop_window_match(window, stops)

            jstate = initial_state(B) if cops is not None else None

            prompt_row = jnp.repeat(prompt_tokens, n_per, axis=0)  # [B, S]
            plen_row = jnp.repeat(prompt_lens, n_per)  # [B]

            V = first_logits.shape[-1]
            logits0 = jnp.broadcast_to(
                first_logits[:, None, :], (R, n_per, V)
            ).reshape(B, V)
            if jstate is not None:
                logits0 = mask_logits(jt, logits0, *jstate, eos_ids)
            logits0 = _mask_pad(logits0)
            # Numeric-integrity quarantine, step 0 (see the normal loop):
            # inject, detect, sanitize, freeze.
            logits0 = jnp.where(poison0[:, None], jnp.nan, logits0)
            bad0 = _poisoned_logits(logits0)
            logits0 = jnp.where(bad0[:, None], 0.0, logits0)
            tok0, lp0 = sample(
                logits0,
                None,
                row_keys=_row_keys(req_keys, 0),
                penalty=-bias[None, :] if use_logit_bias else None,
            )
            tok0 = jnp.where(bad0, pad_id, tok0).astype(jnp.int32)
            lp0 = jnp.where(bad0, 0.0, lp0)
            tok0 = self._constraint(tok0, batch_spec())
            if jstate is not None:
                jstate = advance(jt, tok0, *jstate)
            toks = jnp.full((B, BUF), pad_id, jnp.int32).at[:, 0].set(tok0)
            lps = jnp.zeros((B, BUF), jnp.float32).at[:, 0].set(lp0)
            if KT:
                ti0, tl0 = model_top_logprobs(logits0, KT)
                tt = jnp.zeros((B, BUF, KT), jnp.int32).at[:, 0].set(ti0)
                tlb = jnp.zeros((B, BUF, KT), jnp.float32).at[:, 0].set(tl0)
            else:
                tt = jnp.zeros((B, 0, 0), jnp.int32)
                tlb = jnp.zeros((B, 0, 0), jnp.float32)
            V_counts = V if penalized else 0
            vcounts0 = jnp.zeros((B, V_counts), jnp.float32)
            if penalized:
                vcounts0 = vcounts0.at[jnp.arange(B), tok0].add(1.0)
            count0 = jnp.ones((B,), jnp.int32)
            eos0 = jnp.isin(tok0, eos_ids)
            if use_stops:
                recent0 = (
                    jnp.full((B, MAX_STOP_LEN), -1, jnp.int32).at[:, -1].set(tok0)
                )
                eos0 = eos0 | _stop_match(recent0)  # "stop" finish either way
            else:
                recent0 = jnp.zeros((B, 0), jnp.int32)
            done0 = eos0 | bad0 | (count0 >= max_new)

            gen_cache = init_cache(config, B, BUF)
            gen_cache = KVCache(
                k=self._constraint(gen_cache.k, cache_specs()),
                v=self._constraint(gen_cache.v, cache_specs()),
            )

            def cond(state):
                it, count, done, *_ = state
                return jnp.logical_and(it < max_new, jnp.logical_not(jnp.all(done)))

            def body(state):
                (
                    it, count, done, hit_eos_any, row_iters, cache, toks, lps,
                    tt, tlb, vcounts, jst, recent, pois,
                ) = state
                row_iters = row_iters + jnp.where(done, 0, 1)  # verifies entered
                cur = jnp.take_along_axis(toks, (count - 1)[:, None], axis=1)[:, 0]
                prev = jnp.where(
                    count >= 2,
                    jnp.take_along_axis(
                        toks, jnp.maximum(count - 2, 0)[:, None], axis=1
                    )[:, 0],
                    jnp.take_along_axis(
                        prompt_row, (plen_row - 1)[:, None], axis=1
                    )[:, 0],
                )
                drafts = propose_prompt_lookup(
                    prompt_row, plen_row, prev, cur, K,
                    gen=toks, gen_len=count,
                )  # [B, K]
                block = jnp.concatenate([cur[:, None], drafts], axis=1)  # [B, K+1]
                logits, cache = verify_step(
                    config, params, block, count - 1,
                    prompt_lens, cache, prefix,
                    sp_ring_mesh=self.mesh if sp_prefix else None,
                    mesh=self.mesh,
                )
                # Grammar masking per position: state after the emitted prefix
                # advanced through drafts[:j] (the only prefix under which
                # position j's draw can be emitted).
                sts = None
                if jst is not None:
                    sts = [jst]
                    for j in range(K):
                        sts.append(advance(jt, drafts[:, j], *sts[-1]))
                    logits = jnp.stack(
                        [
                            mask_logits(jt, logits[:, j], *sts[j], eos_ids)
                            for j in range(K + 1)
                        ],
                        axis=1,
                    )
                # ONE flattened sampling call for all K+1 positions (a single
                # top-p bisection instead of K+1 sequential ones). Keys fold
                # (iteration, position) then row, so every (position, row)
                # draw is independent and reproducible.
                flat = _mask_pad(logits.reshape(B * (K + 1), V))
                # Quarantine: a live row whose verify-block logits went
                # non-finite at ANY position emits nothing this iteration and
                # freezes (budget forced to 0 below); sanitized so the single
                # flattened sampling call stays well-defined.
                badrow = jnp.logical_and(
                    jnp.any(_poisoned_logits(flat).reshape(B, K + 1), axis=1),
                    jnp.logical_not(done),
                )
                flat = jnp.where(jnp.repeat(badrow, K + 1)[:, None], 0.0, flat)
                pen_flat = None
                if penalized:
                    # Position j's counts = emitted counts + drafts[:j]; the
                    # one-hot cumsum materializes [B, K+1, V] transiently —
                    # same order as the logits block itself.
                    inc = jnp.cumsum(
                        jax.nn.one_hot(drafts, V, dtype=jnp.float32), axis=1
                    )
                    cnts = jnp.concatenate(
                        [vcounts[:, None, :], vcounts[:, None, :] + inc], axis=1
                    )
                    pen = frequency_penalty * cnts + presence_penalty * (
                        cnts > 0
                    ).astype(jnp.float32)
                    if use_logit_bias:
                        pen = pen - bias[None, None, :]
                    pen_flat = pen.reshape(B * (K + 1), V)
                elif use_logit_bias:
                    pen_flat = jnp.broadcast_to(
                        -bias[None, None, :], (B, K + 1, V)
                    ).reshape(B * (K + 1), V)
                # fold(req key, iteration) -> position -> row-within-request:
                # with R=1 the chain is identical to the solo loop's.
                it_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                    req_keys, it
                )  # [R]
                jk = jax.vmap(
                    lambda j: jax.vmap(lambda kk: jax.random.fold_in(kk, j))(it_keys)
                )(jnp.arange(K + 1))  # [K+1, R]
                pos_keys = jax.vmap(
                    jax.vmap(
                        lambda kk: jax.vmap(lambda i: jax.random.fold_in(kk, i))(
                            jnp.arange(n_per)
                        )
                    )
                )(jk)  # [K+1, R, n_per]
                flat_keys = jnp.moveaxis(
                    pos_keys.reshape(K + 1, B), 0, 1
                ).reshape(B * (K + 1))
                t_flat, lp_flat = sample(flat, None, row_keys=flat_keys, penalty=pen_flat)
                sampled = self._constraint(
                    t_flat.reshape(B, K + 1), P(DATA_AXIS, None)
                )
                lp_arr = lp_flat.reshape(B, K + 1)

                budget = jnp.where(done | badrow, 0, max_new - count)
                emit, counts_new, hit_eos = accept_drafts(
                    sampled, drafts, eos_ids, budget
                )
                stop_hit = jnp.zeros((B,), bool)
                if use_stops:
                    # Stop sequences can complete MID-emission: evaluate the
                    # rolling window at every emitted position and truncate the
                    # run at the first match (the matched position itself still
                    # emits, like the normal loop's same-step halt).
                    buf2 = jnp.concatenate([recent, sampled], axis=1)  # [B, L+K+1]
                    hits = (
                        jnp.stack(
                            [
                                _stop_match(buf2[:, j + 1 : j + 1 + MAX_STOP_LEN])
                                for j in range(K + 1)
                            ],
                            axis=1,
                        )
                        & emit
                    )
                    stop_hit = jnp.any(hits, axis=1)
                    keep = jnp.where(stop_hit, jnp.argmax(hits, axis=1), K + 1)
                    emit = emit & (jnp.arange(K + 1)[None, :] <= keep[:, None])
                    counts_new = emit.sum(axis=1).astype(jnp.int32)
                    hit_eos = jnp.any(emit & jnp.isin(sampled, eos_ids), axis=1)
                    # Window after emission: the L tokens ending at the new
                    # count (counts_new == 0 leaves it unchanged).
                    recent = jax.vmap(
                        lambda b, o: lax.dynamic_slice_in_dim(
                            b, o, MAX_STOP_LEN, axis=0
                        )
                    )(buf2, counts_new)
                toks = scatter_rows(toks, jnp.where(emit, sampled, pad_id), count)
                lps = scatter_rows(lps, jnp.where(emit, lp_arr, 0.0), count)
                if KT:
                    ti, tl_ = model_top_logprobs(flat, KT)
                    tt = scatter_rows_k(tt, ti.reshape(B, K + 1, KT), count)
                    tlb = scatter_rows_k(tlb, tl_.reshape(B, K + 1, KT), count)
                if penalized:
                    vcounts = vcounts + jnp.einsum(
                        "bkv,bk->bv",
                        jax.nn.one_hot(sampled, V, dtype=jnp.float32),
                        emit.astype(jnp.float32),
                    )
                if jst is not None:
                    # Re-anchor the automaton at the last emitted token: gather
                    # the state before it (counts_new-1 accepted drafts deep),
                    # advance through the token actually emitted there.
                    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *sts)
                    c_idx = jnp.maximum(counts_new - 1, 0)
                    s_last = jax.tree.map(
                        lambda s: s[c_idx, jnp.arange(B)], stacked
                    )
                    last_tok = jnp.take_along_axis(sampled, c_idx[:, None], axis=1)[:, 0]
                    new_jst = advance(jt, last_tok, *s_last)
                    jst = jax.tree.map(
                        lambda nw, old: _sel(counts_new > 0, nw, old), new_jst, jst
                    )
                count = count + counts_new
                hit_eos_any = hit_eos_any | hit_eos | stop_hit
                done = done | hit_eos | stop_hit | badrow | (count >= max_new)
                pois = pois | badrow
                if use_cancel:
                    # Same between-step cancellation poll as the normal loop
                    # (see _abort_poller); one verify block may still complete
                    # after expiry — cancellation is block-granular here.
                    aborted = abort_poll(it)
                    done = done | jnp.repeat(aborted, n_per)
                return (
                    it + 1, count, done, hit_eos_any, row_iters, cache, toks, lps,
                    tt, tlb, vcounts, jst, recent, pois,
                )

            state = (
                jnp.int32(1), count0, done0, eos0,
                jnp.zeros((B,), jnp.int32), gen_cache, toks, lps,
                tt, tlb, vcounts0, jstate, recent0, bad0,
            )
            _, count, _, hit_eos_any, row_iters, _, toks, lps, tt, tlb, _, _, _, pois = (
                lax.while_loop(cond, body, state)
            )
            return (
                toks[:, :max_new], lps[:, :max_new], hit_eos_any, count, row_iters,
                tt[:, :max_new], tlb[:, :max_new], pois,
            )

        fn = jax.jit(_loop)
        self._spec_decode_cache[cache_key] = fn
        return fn

    def _generate_speculative(
        self,
        prompt_ids: List[int],
        prompt_len: int,
        bucket: int,
        n: int,
        n_padded: int,
        max_new_tokens: int,
        temperature: float,
        top_p: Optional[float],
        top_k: Optional[int],
        seed: int,
        eos_arr: jax.Array,
        constraint: Optional[str] = None,
        top_logprobs: Optional[int] = None,
        frequency_penalty: float = 0.0,
        presence_penalty: float = 0.0,
        logit_bias: Optional[Dict[int, float]] = None,
        stop_arr: Optional[jax.Array] = None,
        use_stops: bool = False,
        budget: Optional[RequestBudget] = None,
        sp_resident: bool = False,
    ) -> GenerationResult:
        config = self.config
        # SP-resident prompts prefill sequence-parallel and keep the prefix KV
        # sequence-sharded; verify_step then attends it via ring attention
        # (no fallback to the normal loop, no replicated gather).
        if sp_resident:
            first_logits, prefix = self._sp_prefill_routed(
                prompt_ids, prompt_len, bucket
            )
        else:
            first_logits, prefix = self._prefill_routed(prompt_ids, prompt_len, bucket)
        prompt_buf = jnp.array(
            [prompt_ids + [config.pad_token_id] * (bucket - prompt_len)], jnp.int32
        )  # [1, S] — the R=1 case of the request-major prompt tables
        loop = self._get_spec_decode_loop(
            1, n_padded, max_new_tokens, temperature, top_p, top_k, bucket,
            constraint, top_logprobs, frequency_penalty, presence_penalty,
            use_logit_bias=logit_bias is not None,
            use_stops=use_stops,
            use_cancel=budget is not None,
            sp_prefix=sp_resident,
        )
        self._active_budgets = [budget]
        try:
            toks, lps, hit_eos, count, row_iters, tt, tl, pois = loop(
                self.params, prefix, prompt_buf, jnp.array([prompt_len], jnp.int32),
                first_logits, jnp.stack([jax.random.key(seed)]), eos_arr,
                self._bias_array(logit_bias),
                stop_arr if stop_arr is not None else self._stop_array(None)[0],
                self._poison0_array(n_padded, range(n)),
            )
            toks_np, lps_np, eos_np, count_np, iters_np, tt_np, tl_np, pois_np = map(
                np.asarray,
                jax.device_get((toks, lps, hit_eos, count, row_iters, tt, tl, pois)),
            )
        finally:
            self._active_budgets = None
        toks_np, lps_np, eos_np = toks_np[:n], lps_np[:n], eos_np[:n]
        pois_np = pois_np[:n]
        spec_stats = _spec_acceptance_stats(
            count_np[:n], iters_np[:n], lookahead=self.spec_lookahead
        )
        self.spec_stats = spec_stats
        if self.on_spec_stats is not None:
            self.on_spec_stats(spec_stats)
        # Same length convention as the normal loop: count non-pad tokens, so
        # a pad-mapped-to-eos stop token is excluded identically in both modes
        # (emitted tokens are otherwise never pad — pad is masked at sampling).
        lengths = (toks_np != config.pad_token_id).sum(axis=1).astype(np.int32)
        self._note_quarantine(int(pois_np.sum()), n)
        return self._quarantine_result(
            GenerationResult(
                tokens=toks_np,
                logprobs=lps_np,
                lengths=lengths,
                finish_reasons=["stop" if d else "length" for d in eos_np],
                prompt_len=prompt_len,
                top_tokens=tt_np[:n] if top_logprobs else None,
                top_logprobs=tl_np[:n] if top_logprobs else None,
                spec_stats=spec_stats,
            ),
            pois_np,
        )

    def _finish_many_speculative(
        self, items, preps, n_per, max_new_tokens, temperature, top_p, top_k,
        constraint, top_logprobs, frequency_penalty, presence_penalty,
        logit_bias, use_stops, stop_arr, eos_arr, r_pad, bucket_max,
        prefix, prompt_bufs, prompt_lens, first_logits, req_keys,
        use_cancel=False,
    ) -> List[GenerationResult]:
        """generate_many's speculative tail: run the R-request spec loop and
        slice per-request results + acceptance stats (VERDICT r3 #5)."""
        config = self.config
        loop = self._get_spec_decode_loop(
            r_pad, n_per, max_new_tokens, temperature, top_p, top_k, bucket_max,
            constraint, top_logprobs, frequency_penalty, presence_penalty,
            use_logit_bias=logit_bias is not None,
            use_stops=use_stops,
            use_cancel=use_cancel,
        )
        live = [
            i
            for j, it in enumerate(items)
            for i in range(j * n_per, j * n_per + max(1, it.n))
        ]
        self._active_budgets = [it.budget for it in items]
        try:
            toks, lps, hit_eos, count, row_iters, tt, tl, pois = loop(
                self.params, prefix, prompt_bufs, prompt_lens, first_logits,
                req_keys, eos_arr, self._bias_array(logit_bias), stop_arr,
                self._poison0_array(r_pad * n_per, live),
            )
            toks_np, lps_np, eos_np, count_np, iters_np, tt_np, tl_np, pois_np = map(
                np.asarray,
                jax.device_get((toks, lps, hit_eos, count, row_iters, tt, tl, pois)),
            )
        finally:
            self._active_budgets = None
        results = self._slice_many_results(
            items, preps, n_per, toks_np, lps_np, eos_np, tt_np, tl_np,
            top_logprobs,
            spec_stats_fn=lambda lo, n_j: _spec_acceptance_stats(
                count_np[lo : lo + n_j], iters_np[lo : lo + n_j]
            ),
            pois_np=pois_np,
        )
        # The engine-level mirror summarizes the whole coalesced batch (real
        # rows only — per-request row padding and batch padding excluded).
        idx = np.asarray(live, np.int64)
        self._note_quarantine(int(pois_np[idx].sum()), len(idx))
        self.spec_stats = {
            "coalesced_requests": len(items),
            **_spec_acceptance_stats(
                count_np[idx], iters_np[idx], lookahead=self.spec_lookahead
            ),
        }
        if self.on_spec_stats is not None:
            self.on_spec_stats(self.spec_stats)
        return results

    def _slice_many_results(
        self, items, preps, n_per, toks_np, lps_np, finish_np, tt_np, tl_np,
        top_logprobs, spec_stats_fn, pois_np=None,
    ) -> List[GenerationResult]:
        """Shared generate_many result assembly (normal AND speculative
        coalesced paths): per-request row slices, non-pad lengths, stop/length
        finish reasons — one place for the conventions. ``pois_np`` [B] marks
        quarantined rows; each request's slice is scrubbed independently so
        one poisoned member never contaminates its batch peers."""
        results: List[GenerationResult] = []
        for j, (it, (_, prompt_len, _)) in enumerate(zip(items, preps)):
            lo, n_j = j * n_per, max(1, it.n)
            t = toks_np[lo : lo + n_j]
            lengths = (t != self.config.pad_token_id).sum(axis=1).astype(np.int32)
            res = GenerationResult(
                tokens=t,
                logprobs=lps_np[lo : lo + n_j],
                lengths=lengths,
                finish_reasons=[
                    "stop" if d else "length" for d in finish_np[lo : lo + n_j]
                ],
                prompt_len=prompt_len,
                top_tokens=tt_np[lo : lo + n_j] if top_logprobs else None,
                top_logprobs=tl_np[lo : lo + n_j] if top_logprobs else None,
                spec_stats=spec_stats_fn(lo, n_j),
            )
            if pois_np is not None:
                res = self._quarantine_result(res, pois_np[lo : lo + n_j])
            results.append(res)
        return results

    def _stop_array(
        self, stop_sequences: Optional[Sequence[Sequence[int]]]
    ) -> Tuple[jax.Array, bool]:
        """[MAX_STOP_SEQS, MAX_STOP_LEN] right-aligned -1-padded stop-token
        matrix + whether any sequence is device-matchable. Sequences longer
        than MAX_STOP_LEN are skipped here (the backend's host-side text
        truncation still honors them); the all-(-1) matrix is cached like the
        zero bias so the no-stop hot path pays no per-request transfer."""
        requested = [list(map(int, s)) for s in (stop_sequences or [])]
        seqs = [s for s in requested if 0 < len(s) <= MAX_STOP_LEN][:MAX_STOP_SEQS]
        if len(seqs) < len([s for s in requested if s]):
            # Direct engine callers have no host-side text fallback — a
            # silently ignored stop would decode to max_new_tokens.
            logger.warning(
                "%d stop sequence(s) dropped (device matching supports up to %d "
                "sequences of <= %d tokens); TpuBackend's text truncation still "
                "honors them, direct engine callers must handle them host-side",
                len([s for s in requested if s]) - len(seqs),
                MAX_STOP_SEQS,
                MAX_STOP_LEN,
            )
        if not seqs:
            cached = getattr(self, "_no_stops", None)
            if cached is None:
                cached = jnp.full((MAX_STOP_SEQS, MAX_STOP_LEN), -1, jnp.int32)
                self._no_stops = cached
            return cached, False
        arr = np.full((MAX_STOP_SEQS, MAX_STOP_LEN), -1, np.int32)
        for i, s in enumerate(seqs):
            arr[i, MAX_STOP_LEN - len(s) :] = s
        return jnp.asarray(arr), True

    def _bias_array(self, logit_bias: Optional[Dict[int, float]]) -> jax.Array:
        """Dense [V] f32 logit-bias vector (zeros when unset — the loop arg is
        uniform either way; dead when the compiled loop ignores it). The
        zeros vector is built once and reused: the no-bias hot path must not
        pay a vocab-sized host allocation + transfer per request."""
        if not logit_bias:
            cached = getattr(self, "_zero_bias", None)
            if cached is None:
                cached = jnp.zeros((self.config.vocab_size,), jnp.float32)
                self._zero_bias = cached
            return cached
        v = np.zeros((self.config.vocab_size,), np.float32)
        for tok, bias in logit_bias.items():
            t = int(tok)
            if not 0 <= t < self.config.vocab_size:
                # Direct LocalEngine callers bypass TpuBackend's validation; a
                # negative id would silently bias the wrapped vocab entry.
                raise ValueError(
                    f"logit_bias token id {t} outside vocab (0..{self.config.vocab_size - 1})"
                )
            v[t] = float(bias)
        return jnp.asarray(v)

    def _refuse_outside_loop(self, what: str) -> None:
        """The engine's own decode loops carry no recurrent state and have no
        dense decode step for the parallel block: a hybrid stack is served by
        the paged continuous loop alone, and a request that loop does not take
        (top_logprobs, penalties, logit_bias, a prompt or n beyond its bounds)
        fails here, by name."""
        if self.config.is_hybrid:
            raise NotImplementedError(
                f"{self.config.name}: {what} outside the continuous loop is not implemented "
                "for the hybrid stack (recurrent state beside the cache, or the parallel block, "
                "whose dense decode step is not written; the request asked for top_logprobs, "
                "penalties, logit_bias, or a prompt, n or max_tokens beyond the loop's bounds)"
            )

    # -- request prep -----------------------------------------------------
    def _prep_prompt(self, prompt_ids: Sequence[int]) -> Tuple[List[int], int, int]:
        """Normalize a prompt: BOS fallback, left-truncate to max_seq_len, and
        pick the power-of-two compile bucket. Returns (ids, prompt_len, bucket)."""
        config = self.config
        ids = list(prompt_ids)
        if not ids:
            ids = [config.bos_token_id]
        if len(ids) > config.max_seq_len:
            # Keep the tail — it holds the latest user turn + generation header.
            logger.warning(
                "prompt of %d tokens exceeds max_seq_len=%d; left-truncating",
                len(ids),
                config.max_seq_len,
            )
            ids = ids[-config.max_seq_len :]
        prompt_len = len(ids)
        bucket = min(_bucket(prompt_len, minimum=32), config.max_seq_len)
        return ids, prompt_len, bucket

    def _validate_constraint(self, constraint, eos: List[int]) -> None:
        """Reject malformed constraint/eos combinations before any device work
        (prefill compiles take seconds)."""
        from .grammar import CompiledGrammar
        from .schema_constraint import SchemaDFA
        from .token_constraint import TokenConstraint

        config = self.config
        if constraint is None:
            return
        if constraint != "json" and not isinstance(
            constraint, (SchemaDFA, TokenConstraint, CompiledGrammar)
        ):
            raise ValueError(
                f"Unknown constraint {constraint!r}; supported: 'json', a compiled "
                "SchemaDFA, a compiled TokenConstraint, or a CompiledGrammar"
            )
        if isinstance(constraint, (TokenConstraint, CompiledGrammar)):
            # Token-level masks carry their own vocabulary; the model head must
            # cover it, and eos must be a special (len-0) or out-of-vocab id so
            # opening its column cannot alias a grammar token.
            if config.vocab_size < constraint.vocab_size:
                raise ValueError(
                    f"model vocab {config.vocab_size} < constraint vocab "
                    f"{constraint.vocab_size}"
                )
            if any(
                0 <= e < constraint.vocab_size and constraint.token_len[e] > 0
                for e in eos
            ):
                raise ValueError(
                    "eos ids must be special tokens under a token-level constraint"
                )
        else:
            # The byte masks treat token ids 0..255 AS bytes — the caller must
            # use a byte-level tokenizer (TpuBackend gates on is_byte_level).
            # Specials (eos/pad) must live above the byte range, or the eos
            # column would alias onto a byte and corrupt the automaton.
            if config.vocab_size <= 256 or any(e < 256 for e in eos):
                raise ValueError(
                    "grammar constraints need byte-level token semantics: vocab > 256 "
                    "with eos/pad ids outside the 0..255 byte range"
                )

    # -- public API -------------------------------------------------------
    def generate(
        self,
        prompt_ids: Sequence[int],
        n: int = 1,
        max_new_tokens: int = 128,
        temperature: float = 1.0,
        top_p: Optional[float] = None,
        top_k: Optional[int] = None,
        seed: Optional[int] = None,
        eos_ids: Optional[Sequence[int]] = None,
        constraint: Optional[str] = None,
        top_logprobs: Optional[int] = None,
        frequency_penalty: float = 0.0,
        presence_penalty: float = 0.0,
        logit_bias: Optional[Dict[int, float]] = None,
        stop_sequences: Optional[Sequence[Sequence[int]]] = None,
        budget: Optional[RequestBudget] = None,
        token_sink: Optional[Callable[[int, np.ndarray], None]] = None,
    ) -> GenerationResult:
        config = self.config
        self._refuse_outside_loop("generate")
        if budget is not None:
            # Fail before any device work: a spent budget must not trigger a
            # prefill (or worse, a compile).
            budget.check("engine prefill")
        prompt_ids, prompt_len, bucket = self._prep_prompt(prompt_ids)
        stop_arr, use_stops = self._stop_array(stop_sequences)

        # Round n up so the data axis divides evenly; trim after.
        dp = self.data_parallel_size
        n_padded = ((max(1, n) + dp - 1) // dp) * dp

        eos = list(eos_ids or [config.eos_token_id])[:MAX_EOS_IDS]
        eos_arr = jnp.array(eos + [-1] * (MAX_EOS_IDS - len(eos)), jnp.int32)

        self._validate_constraint(constraint, eos)

        if seed is None:
            seed = int.from_bytes(os.urandom(4), "little")

        # Stats describe THIS request only — a fallback to the normal loop
        # must not leave a previous speculative request's numbers visible.
        # (kept local + threaded into the result; self.spec_stats mirrors it.)
        spec_stats: Dict[str, Any] = {}
        self.spec_stats = spec_stats

        # Ring-decode route (sp_decode): prompts taking the SP prefill keep
        # their KV sequence-sharded and decode against it in place. The
        # prefix cache composes fully: exact hits feed the ring loop
        # directly, and partial hits run the ring-layout continuation
        # prefill (suffix-only forward, O(S/P) per device — r3 #6).
        sp_resident = (
            self.sp_decode
            and self.mesh is not None
            and self._use_sp_prefill(prompt_len, bucket)
        )

        # Prompt-lookup speculative decode: composes with constraints,
        # penalties, top_logprobs, logit_bias (VERDICT r2 #4), device stop
        # sequences, a MESH (rows shard over data, the verify forward is
        # tensor-parallel — VERDICT r3 #4), and SP-RESIDENT prompts
        # (verify_step attends the sequence-sharded prefix via ring attention,
        # same as the ring decode loop — no fallback, no sentinel).
        if self.speculative == "prompt_lookup":
            res = self._generate_speculative(
                prompt_ids, prompt_len, bucket, n, n_padded, max_new_tokens,
                temperature, top_p, top_k, seed, eos_arr,
                constraint, top_logprobs, frequency_penalty,
                presence_penalty, logit_bias,
                stop_arr=stop_arr, use_stops=use_stops, budget=budget,
                sp_resident=sp_resident,
            )
            return self._apply_decode_faults(res, budget)

        req_keys = jnp.stack([jax.random.key(seed)])
        if sp_resident:
            first_logits, prefix = self._sp_prefill_routed(
                prompt_ids, prompt_len, bucket
            )
        else:
            first_logits, prefix = self._prefill_routed(prompt_ids, prompt_len, bucket)
        loop = self._get_decode_loop(
            1, n_padded, max_new_tokens, temperature, top_p, top_k, constraint,
            top_logprobs, frequency_penalty, presence_penalty,
            use_logit_bias=logit_bias is not None,
            use_stops=use_stops,
            sp_prefix=sp_resident,
            use_cancel=budget is not None,
            use_stream=token_sink is not None,
        )
        self._active_budgets = [budget]
        self._active_token_sinks = [token_sink] if token_sink is not None else None
        self._reset_tap_state()
        try:
            toks, lps, done, tt, tl, pois = loop(
                self.params,
                prefix,
                jnp.array([prompt_len], jnp.int32),
                first_logits,
                req_keys,
                eos_arr,
                self._bias_array(logit_bias),
                stop_arr,
                self._poison0_array(n_padded, range(n)),
            )

            # ONE host transfer for all outputs: each device_get is a
            # synchronous round trip to the device.
            toks_np, lps_np, done_np, tt_np, tl_np, pois_np = jax.device_get(
                (toks, lps, done, tt, tl, pois)
            )
        finally:
            self._active_budgets = None
            self._active_token_sinks = None
        toks_np = np.asarray(toks_np)[:n]
        lps_np = np.asarray(lps_np)[:n]
        done_np = np.asarray(done_np)[:n]
        pois_np = np.asarray(pois_np)[:n]

        lengths = (toks_np != config.pad_token_id).sum(axis=1).astype(np.int32)
        # A sample that emitted pad_id as a real token would undercount; the
        # byte tokenizer never does (pad is a reserved id) and HF pads map to eos.
        finish = ["stop" if d else "length" for d in done_np]
        result = GenerationResult(
            tokens=toks_np,
            logprobs=lps_np,
            lengths=lengths,
            finish_reasons=finish,
            prompt_len=prompt_len,
            top_tokens=np.asarray(tt_np)[:n] if top_logprobs else None,
            top_logprobs=np.asarray(tl_np)[:n] if top_logprobs else None,
            spec_stats=spec_stats,
        )
        self._note_quarantine(int(pois_np.sum()), n)
        result = self._quarantine_result(result, pois_np)
        return self._apply_decode_faults(result, budget)

    def generate_many(
        self,
        items: Sequence[GenRequestSpec],
        *,
        _oom_splits_left: int = MAX_OOM_SPLITS,
        **kwargs,
    ) -> List[Any]:
        """Decode several same-config requests as one batched XLA program,
        with device-OOM recovery: a launch that dies with RESOURCE_EXHAUSTED
        splits the group in half and retries each half at the reduced width
        (recursively, bounded by ``MAX_OOM_SPLITS``) instead of failing every
        member. A solo request that still OOMs gets a typed 503 member error —
        it genuinely does not fit. Splits are counted in ``FAILURE_EVENTS``
        and ``oom_stats``; ``on_oom``/``on_launch_ok`` notify the scheduler so
        it can back off its coalescing width (see
        ``EngineScheduler.note_oom``). See :meth:`_generate_many_attempt` for
        the decode semantics."""
        self._refuse_outside_loop("generate_many")
        if not items:
            return []
        try:
            results = self._generate_many_attempt(items, **kwargs)
        except Exception as e:
            if not is_resource_exhausted(e):
                raise
            FAILURE_EVENTS.record("engine.oom")
            self.oom_stats["splits"] += 1
            if self.on_oom is not None:
                self.on_oom()
            if len(items) == 1 or _oom_splits_left <= 0:
                self.oom_stats["unrecovered"] += len(items)
                FAILURE_EVENTS.record("engine.oom_unrecovered", len(items))
                logger.error(
                    "device OOM not recoverable by splitting (%d member(s)): %s",
                    len(items),
                    e,
                )
                return [
                    BackendUnavailableError(
                        f"device out of memory decoding this request "
                        f"(n={it.n}, prompt_len={len(it.prompt_ids)}); "
                        "reduce n or max_tokens"
                    )
                    for it in items
                ]
            mid = (len(items) + 1) // 2
            logger.warning(
                "device OOM on a %d-request coalesced launch; splitting "
                "%d/%d and retrying (%d split(s) left)",
                len(items), mid, len(items) - mid, _oom_splits_left - 1,
            )
            FAILURE_EVENTS.record("engine.oom_split")
            return self.generate_many(
                items[:mid], _oom_splits_left=_oom_splits_left - 1, **kwargs
            ) + self.generate_many(
                items[mid:], _oom_splits_left=_oom_splits_left - 1, **kwargs
            )
        if self.on_launch_ok is not None:
            self.on_launch_ok()
        return results

    def _generate_many_attempt(
        self,
        items: Sequence[GenRequestSpec],
        *,
        max_new_tokens: int = 128,
        temperature: float = 1.0,
        top_p: Optional[float] = None,
        top_k: Optional[int] = None,
        eos_ids: Optional[Sequence[int]] = None,
        constraint: Optional[str] = None,
        top_logprobs: Optional[int] = None,
        frequency_penalty: float = 0.0,
        presence_penalty: float = 0.0,
        logit_bias: Optional[Dict[int, float]] = None,
        stop_sequences: Optional[Sequence[Sequence[int]]] = None,
    ) -> List[GenerationResult]:
        """Decode several same-config requests as ONE batched XLA program.

        This is the cross-request throughput path (the reference's concurrency
        story is 5 async HTTP workers, `README_TESTS.md:214`): R queued
        requests with compatible sampling configs coalesce into a single
        decode of R × n_per rows. Each request's prompt is prefilled once at
        batch=1 (compile-cached per bucket), the prefix KVs are stacked on a
        request axis, and every row group attends to its own prefix — prompt
        KV still stored once per request. Per-request seeds keep their solo
        sampling streams.

        Partial failure: a member whose budget aborts mid-decode (or that an
        injected fault kills outright) yields an EXCEPTION instance in the
        returned list instead of a GenerationResult — the scheduler delivers
        it to just that member's caller; the rest of the batch is unaffected.
        """
        _failpoints.fire("engine.launch")
        note_device_dispatch("engine batched launch")
        if not items:
            return []
        if len(items) == 1:
            it = items[0]
            try:
                return [
                    self.generate(
                        it.prompt_ids,
                        n=it.n,
                        max_new_tokens=max_new_tokens,
                        temperature=temperature,
                        top_p=top_p,
                        top_k=top_k,
                        seed=it.seed,
                        eos_ids=eos_ids,
                        constraint=constraint,
                        top_logprobs=top_logprobs,
                        frequency_penalty=frequency_penalty,
                        presence_penalty=presence_penalty,
                        logit_bias=logit_bias,
                        stop_sequences=stop_sequences,
                        budget=it.budget,
                        token_sink=it.token_sink,
                    )
                ]
            except Exception as e:
                # Same contract as the coalesced path: member failures are
                # list elements, not batch poison — EXCEPT the device OOM
                # signal, which the generate_many guard must see to convert
                # into a typed error (or it would vanish into the member).
                if is_resource_exhausted(e):
                    raise
                return [e]

        config = self.config
        eos = list(eos_ids or [config.eos_token_id])[:MAX_EOS_IDS]
        eos_arr = jnp.array(eos + [-1] * (MAX_EOS_IDS - len(eos)), jnp.int32)
        self._validate_constraint(constraint, eos)

        self.spec_stats = {}

        preps = [self._prep_prompt(it.prompt_ids) for it in items]
        bucket_max = max(bucket for _, _, bucket in preps)

        # One row count for every request (rows must form equal groups): the
        # max n, rounded so the data axis divides the total batch evenly.
        dp = self.data_parallel_size
        n_per = max(max(1, it.n) for it in items)
        n_per = ((n_per + dp - 1) // dp) * dp

        # Paged coalesced decode (the tentpole of the paged-everywhere PR):
        # when the engine's KV layout is paged, the batch decodes against pool
        # block tables — prompt KV admitted through the same refcounted cache
        # the continuous loop uses (cache hits cost zero device work), gen
        # slots drawn from the pool per row. Speculative and sequence-parallel
        # prefixes keep their dense layouts; pool exhaustion falls through to
        # the dense body below — correctness never depends on pages being
        # available.
        if (
            self.kv_layout == "paged"
            and self.paged_generate_many
            and self.speculative is None
            and not (self.sp_decode and self.mesh is not None)
        ):
            from .paging import PagePoolExhausted

            try:
                return self._generate_many_paged(
                    items, preps, n_per,
                    max_new_tokens=max_new_tokens, temperature=temperature,
                    top_p=top_p, top_k=top_k, eos_arr=eos_arr,
                    constraint=constraint, top_logprobs=top_logprobs,
                    frequency_penalty=frequency_penalty,
                    presence_penalty=presence_penalty, logit_bias=logit_bias,
                    stop_sequences=stop_sequences,
                )
            except PagePoolExhausted:
                logger.debug(
                    "paged coalesced launch exhausted the page pool; "
                    "falling back to dense decode"
                )

        first_list, k_list, v_list = [], [], []
        for ids, prompt_len, bucket in preps:
            # Per-request routing: a coalesced batch gets the same SP and
            # prefix-cache treatment as solo requests — concurrency is
            # exactly when the repeated-extraction cache workload shows up.
            # Sequence-sharded exact hits are fine here ONLY because of the
            # reshard below (allow_seq_sharded mirrors that exact condition).
            reshard = self.sp_decode and self.mesh is not None
            fl, pref = self._prefill_routed(
                ids, prompt_len, bucket, allow_seq_sharded=reshard
            )
            if reshard:
                # Coalesced batches decode against the replicated prefix
                # layout; an SP-prefilled (sequence-sharded) KV is resharded
                # here rather than letting concat/pad pick a layout.
                sharding = NamedSharding(self.mesh, cache_specs(shared_prefix=True))
                pref = KVCache(
                    k=jax.device_put(pref.k, sharding),
                    v=jax.device_put(pref.v, sharding),
                )
            if bucket < bucket_max:
                pad = [(0, 0)] * 5
                pad[2] = (0, bucket_max - bucket)  # masked by prompt_len anyway
                pref = KVCache(k=jnp.pad(pref.k, pad), v=jnp.pad(pref.v, pad))
            first_list.append(fl)
            k_list.append(pref.k)
            v_list.append(pref.v)
        # Bucket R to the next power of two so timing-dependent batch sizes hit
        # a bounded set of compiled programs (coalescing is opportunistic — R
        # is whatever was queued). Padding replicates the LAST request's
        # already-prefilled slices; its pad rows are trimmed below and cost
        # little (decode is weight-streaming-bound, not row-bound).
        # NB: must stay the scheduler's admission model (_next_pow2 in
        # scheduler.py) for the max_rows HBM bound to hold.
        r_pad = _bucket(len(items), minimum=1)
        extra = r_pad - len(items)
        if extra:
            k_list += [k_list[-1]] * extra
            v_list += [v_list[-1]] * extra
            first_list += [first_list[-1]] * extra
        prefix = KVCache(
            k=jnp.concatenate(k_list, axis=1), v=jnp.concatenate(v_list, axis=1)
        )
        first_logits = jnp.concatenate(first_list, axis=0)  # [r_pad, V]
        lens = [p for _, p, _ in preps] + [preps[-1][1]] * extra
        prompt_lens = jnp.array(lens, jnp.int32)

        seeds = [
            it.seed if it.seed is not None else int.from_bytes(os.urandom(4), "little")
            for it in items
        ]
        seeds += [0] * extra
        req_keys = jnp.stack([jax.random.key(s) for s in seeds])

        stop_arr, use_stops = self._stop_array(stop_sequences)

        # Coalesced SPECULATIVE decode (VERDICT r3 #5): the R-request spec
        # loop drafts each row from ITS OWN request's prompt table — the
        # admission-window extraction bursts that coalesce are exactly the
        # prompt-copying workloads prompt-lookup accelerates. Same semantics
        # as the normal coalesced loop (differential-tested); stats per
        # request on each GenerationResult.
        use_cancel = any(it.budget is not None for it in items)
        if self.speculative == "prompt_lookup":
            prompt_bufs = np.full((r_pad, bucket_max), config.pad_token_id, np.int32)
            for j, (ids_j, plen_j, _) in enumerate(preps):
                prompt_bufs[j, :plen_j] = ids_j
            if extra:
                prompt_bufs[len(items):] = prompt_bufs[len(items) - 1]
            results = self._finish_many_speculative(
                items, preps, n_per, max_new_tokens, temperature, top_p, top_k,
                constraint, top_logprobs, frequency_penalty, presence_penalty,
                logit_bias, use_stops, stop_arr, eos_arr, r_pad, bucket_max,
                prefix, jnp.asarray(prompt_bufs), prompt_lens, first_logits,
                req_keys, use_cancel=use_cancel,
            )
            return self._finalize_many(items, results)

        use_stream = any(it.token_sink is not None for it in items)
        loop = self._get_decode_loop(
            r_pad, n_per, max_new_tokens, temperature, top_p, top_k, constraint,
            top_logprobs, frequency_penalty, presence_penalty,
            use_logit_bias=logit_bias is not None,
            use_stops=use_stops,
            use_cancel=use_cancel,
            use_stream=use_stream,
        )
        live = [
            i
            for j, it in enumerate(items)
            for i in range(j * n_per, j * n_per + max(1, it.n))
        ]
        self._active_budgets = [it.budget for it in items]
        self._active_token_sinks = (
            [it.token_sink for it in items] if use_stream else None
        )
        self._reset_tap_state()
        try:
            toks, lps, done, tt, tl, pois = loop(
                self.params, prefix, prompt_lens, first_logits, req_keys, eos_arr,
                self._bias_array(logit_bias), stop_arr,
                self._poison0_array(r_pad * n_per, live),
            )
            toks_np, lps_np, done_np, tt_np, tl_np, pois_np = map(
                np.asarray, jax.device_get((toks, lps, done, tt, tl, pois))
            )
        finally:
            self._active_budgets = None
            self._active_token_sinks = None
        results = self._slice_many_results(
            items, preps, n_per, toks_np, lps_np, done_np, tt_np, tl_np,
            top_logprobs, spec_stats_fn=lambda lo, n_j: {}, pois_np=pois_np,
        )
        self._note_quarantine(
            int(pois_np[np.asarray(live, np.int64)].sum()), len(live)
        )
        return self._finalize_many(items, results)

    def _generate_many_paged(
        self,
        items: Sequence[GenRequestSpec],
        preps,
        n_per: int,
        *,
        max_new_tokens: int,
        temperature: float,
        top_p: Optional[float],
        top_k: Optional[int],
        eos_arr,
        constraint: Optional[str],
        top_logprobs: Optional[int],
        frequency_penalty: float,
        presence_penalty: float,
        logit_bias: Optional[Dict[int, float]],
        stop_sequences: Optional[Sequence[Sequence[int]]],
    ) -> List[Any]:
        """The coalesced batch, decoded against ``PagedKVPool`` block tables.

        Differences from the dense body of :meth:`_generate_many_attempt` —
        the sampler, key schedule, masks, and result assembly are shared, so
        tokens and logprobs are byte-identical on the "xla" impl (pinned by
        tests/test_paged_coalesced.py):

        * Prompt KV is ADMITTED, not stacked: :meth:`paged_admit_prefix`
          returns a refcounted page run per request (a paged cache hit costs
          zero device work; an n-way fan-out's prompt is stored once
          physically). Each run is pinned for the launch and unpinned in the
          ``finally`` — a transient run's admission reference is dropped
          immediately so the pin is its only owner.
        * Every LIVE row draws ``pages_for(max_new)`` fresh gen pages; dead
          rows (group tails past a request's n, replicated pad requests)
          point their gen slots at the trash page, whose contents are
          don't-care by contract.
        * The decode dispatches under ``pool.lock`` with the pool buffers
          donated, and the returned buffers are swapped back atomically —
          the same consume-and-replace discipline as every pool mover.

        Raises :class:`~.paging.PagePoolExhausted` (after unwinding every
        reference it took) when admission or gen-page allocation cannot be
        satisfied even with eviction; the caller falls back to dense.
        """
        from ..ops.paged_attention import (
            note_paged_attn_dispatch,
            resolve_paged_attention_impl,
        )
        from .paging import TRASH_PAGE, flat_slots, pages_for

        config = self.config
        r_pad = _bucket(len(items), minimum=1)
        extra = r_pad - len(items)
        B = r_pad * n_per
        bucket_max = max(bucket for _, _, bucket in preps)
        live = [
            i
            for j, it in enumerate(items)
            for i in range(j * n_per, j * n_per + max(1, it.n))
        ]

        gp = pages_for(max_new_tokens, self.kv_page_size)
        # +1: page 0 is the pinned trash page, never allocatable.
        pool = self._ensure_kv_pool(
            min_pages=sum(pages_for(p, self.kv_page_size) for _, p, _ in preps)
            + len(live) * gp + 1
        )
        ps = pool.page_size

        pinned: List[Any] = []  # one launch reference per admitted run
        gen_pages_rows: List[Optional[List[int]]] = [None] * B
        try:
            first_list = []
            for ids, prompt_len, bucket in preps:
                fl, run, transient, _ = self.paged_admit_prefix(
                    ids, prompt_len, bucket
                )
                with self._paged_mutex:
                    run.retain()
                    if transient:
                        run.release()
                pinned.append(run)
                first_list.append(fl)

            # Fresh gen pages per live row, allocated under the mutex so the
            # reservation is atomic against the continuous loop's admissions.
            # A partial allocation propagates PagePoolExhausted; the finally
            # below returns whatever rows already got pages.
            with self._paged_mutex:
                for row in live:
                    gen_pages_rows[row] = self._alloc_pages_with_evict(gp)

            # Host-side block tables. prefix_idx is REQUEST-level [r_pad, P]
            # (the gathered prefix keeps the [R, P, KVH, D] shape the dense
            # shared-prefix einsum consumes); positions past each prompt
            # retarget into the trash page — masked before any unmasked read.
            trash = (np.arange(bucket_max) % ps + TRASH_PAGE * ps).astype(np.int32)
            prefix_np = np.empty((r_pad, bucket_max), np.int32)
            for j, run in enumerate(pinned):
                row_idx = flat_slots(run.pages, np.arange(bucket_max), ps)
                row_idx[run.plen:] = trash[run.plen:]
                prefix_np[j] = row_idx
            if extra:
                # Pad requests replicate the last request's table (their rows
                # are dead; reads stay in-bounds on pages the launch pins).
                prefix_np[len(items):] = prefix_np[len(items) - 1]

            trash_gen = (np.arange(max_new_tokens) % ps + TRASH_PAGE * ps).astype(
                np.int32
            )
            gen_np = np.empty((B, max_new_tokens), np.int32)
            for row in range(B):
                pgs = gen_pages_rows[row]
                gen_np[row] = (
                    flat_slots(pgs, np.arange(max_new_tokens), ps)
                    if pgs is not None
                    else trash_gen
                )

            if extra:
                first_list += [first_list[-1]] * extra
            first_logits = jnp.concatenate(first_list, axis=0)  # [r_pad, V]
            lens = [p for _, p, _ in preps] + [preps[-1][1]] * extra
            prompt_lens = jnp.array(lens, jnp.int32)

            seeds = [
                it.seed
                if it.seed is not None
                else int.from_bytes(os.urandom(4), "little")
                for it in items
            ]
            seeds += [0] * extra
            req_keys = jnp.stack([jax.random.key(s) for s in seeds])

            stop_arr, use_stops = self._stop_array(stop_sequences)
            use_cancel = any(it.budget is not None for it in items)
            use_stream = any(it.token_sink is not None for it in items)

            # Kernel selection happens once per launch (never per step) and
            # is counted so /metrics shows which impl production dispatched.
            impl = resolve_paged_attention_impl(
                self.paged_attention_impl, config=config
            )
            note_paged_attn_dispatch(impl)
            loop = self._get_decode_loop(
                r_pad, n_per, max_new_tokens, temperature, top_p, top_k,
                constraint, top_logprobs, frequency_penalty, presence_penalty,
                use_logit_bias=logit_bias is not None,
                use_stops=use_stops,
                use_cancel=use_cancel,
                use_stream=use_stream,
                paged_impl=impl,
            )

            self._active_budgets = [it.budget for it in items]
            self._active_token_sinks = (
                [it.token_sink for it in items] if use_stream else None
            )
            self._reset_tap_state()
            try:
                with pool.lock:
                    # Dispatch-and-swap under the pool lock: the pool buffers
                    # are donated to the loop, so self.kv must point at the
                    # returned buffers before anyone else can dispatch.
                    toks, lps, done, tt, tl, pois, new_k, new_v = loop(
                        self.params, pool.kv.k, pool.kv.v,
                        jnp.asarray(prefix_np), jnp.asarray(gen_np),
                        prompt_lens, first_logits, req_keys, eos_arr,
                        self._bias_array(logit_bias), stop_arr,
                        self._poison0_array(B, live),
                    )
                    pool.kv = KVCache(k=new_k, v=new_v)
                toks_np, lps_np, done_np, tt_np, tl_np, pois_np = map(
                    np.asarray, jax.device_get((toks, lps, done, tt, tl, pois))
                )
            finally:
                self._active_budgets = None
                self._active_token_sinks = None
        finally:
            # Unpin launch references; on success device_get has already
            # fenced the decode, and on failure the results are discarded, so
            # reuse-after-free of these pages cannot corrupt a kept result.
            with self._paged_mutex:
                for run in pinned:
                    pool.allocator.decref(run.pages)
                for pgs in gen_pages_rows:
                    if pgs is not None:
                        pool.allocator.decref(pgs)

        results = self._slice_many_results(
            items, preps, n_per, toks_np, lps_np, done_np, tt_np, tl_np,
            top_logprobs, spec_stats_fn=lambda lo, n_j: {}, pois_np=pois_np,
        )
        self._note_quarantine(
            int(pois_np[np.asarray(live, np.int64)].sum()), len(live)
        )
        return self._finalize_many(items, results)

    def _finalize_many(
        self, items: Sequence[GenRequestSpec], results: List[GenerationResult]
    ) -> List[Any]:
        """Per-member fault surfacing for a coalesced batch: each member gets
        its own _apply_decode_faults pass; a raised lifecycle/injected error
        replaces that member's result (the scheduler set_exceptions it to just
        that caller)."""
        out: List[Any] = []
        for it, res in zip(items, results):
            try:
                out.append(self._apply_decode_faults(res, it.budget))
            except Exception as e:
                out.append(e)
        return out

    # -- embeddings (similarity side-channel) -----------------------------
    def _get_embed(self, batch: int, bucket: int):
        cache_key = (batch, bucket)
        fn = self._embed_cache.get(cache_key)
        if fn is None:
            config = self.config

            def _embed(params, tokens, mask):
                hidden = encode(config, params, tokens, mask, mesh=self.mesh)
                m = mask[:, :, None].astype(jnp.float32)
                pooled = (hidden.astype(jnp.float32) * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
                return pooled

            fn = jax.jit(_embed)
            self._embed_cache[cache_key] = fn
        return fn

    def embed_tokens(self, token_lists: List[List[int]], max_tokens: int = 512) -> np.ndarray:
        """Mean-pooled final hidden states — the local replacement for the
        reference's OpenAI embeddings side-channel (`client.py:75-122`)."""
        config = self.config
        token_lists = [ids[:max_tokens] or [config.bos_token_id] for ids in token_lists]
        longest = max(len(ids) for ids in token_lists)
        bucket = _bucket(longest, minimum=32)
        dp = self.data_parallel_size
        # Power-of-two batch bucket (then dp-rounded): coalesced embedding
        # batches arrive with timing-dependent row counts, and the jit cache is
        # keyed on the exact batch — bucketing bounds the compiled-program set.
        batch = _bucket(len(token_lists), minimum=8)
        batch = ((batch + dp - 1) // dp) * dp

        tokens = np.full((batch, bucket), config.pad_token_id, np.int32)
        mask = np.zeros((batch, bucket), np.int32)
        for i, ids in enumerate(token_lists):
            tokens[i, : len(ids)] = ids
            mask[i, : len(ids)] = 1
        pooled = self._get_embed(batch, bucket)(
            self.params, jnp.asarray(tokens), jnp.asarray(mask)
        )
        return np.asarray(jax.device_get(pooled))[: len(token_lists)]
