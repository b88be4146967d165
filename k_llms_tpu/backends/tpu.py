"""TPU backend: KLLMs(backend="tpu") — the local JAX/XLA model engine.

Replaces the reference's HTTP boundary (SURVEY.md §1 "model layer"): the n-way
sample fan-out (`/root/reference/k_llms/resources/completions/completions.py:70-73`)
becomes one batched decode on the device mesh; the embeddings side-channel
(`client.py:75-122`) becomes mean-pooled hidden states from the same model; the
llm-consensus string mode (`consensus_utils.py:1026-1048`, hardcoded gpt-5-mini)
routes to the local model. Zero OpenAI calls (BASELINE.md target).
"""

from __future__ import annotations

import hashlib
import logging
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
from pydantic import BaseModel

from ..consensus.prompts import SYSTEM_PROMPT_STRING_CONSENSUS_LLM
from ..engine.engine import LocalEngine, _bucket
from ..engine.paging import row_reserve_pages
from ..engine.tokenizer import get_tokenizer
from ..models.config import get_config
from ..types import ChatCompletion
from ..utils.observability import LATENCY, current_trace
from .base import Backend, ChatRequest

# Embedding inputs crop at the same token cap as the reference (`client.py:12`).
MAX_EMBEDDING_TOKENS = 8191

logger = logging.getLogger(__name__)


def _visible_token_count(tok, ids: List[int], pos: int, text: str) -> int:
    """Shortest token prefix whose decode REPRODUCES the visible text
    ``text[:pos]`` (``text`` = the full decode of ``ids``).

    Decoded LENGTH alone is the wrong predicate: byte-level tokenizers decode
    partial UTF-8 sequences to replacement characters, so a prefix cut inside
    a multi-byte character already has length >= pos while later tokens still
    contribute to the visible characters (e.g. 'abc😀' is 7 byte tokens, but
    'abc' + the first emoji byte decodes to 4 chars) — a length-only search
    under-bills and truncates logprobs short of the returned text (ADVICE r3).
    Scans from the front comparing the decoded prefix text itself; lengths are
    completion-sized, so the linear scan is cheap.
    """
    visible = text[:pos]
    # Decoded length is USUALLY non-decreasing in the token count, which made
    # binary search look like a valid lower bound — but HF-style decode
    # cleanup (e.g. clean_up_tokenization_spaces collapsing " ," to ",") can
    # SHRINK the decode when a token is appended, so bisection may skip the
    # true boundary and a scan started from its result silently over-bills
    # (or, finding nothing, falls through to len(ids)). The front scan is the
    # only predicate correct under arbitrary decode post-processing, and ids
    # are completion-sized, so it stays cheap.
    for k in range(len(ids) + 1):
        prefix = tok.decode(ids[:k])
        if len(prefix) >= pos and prefix[:pos] == visible:
            return k
    return len(ids)


class BackendConfig(BaseModel):
    """Engine configuration (the pydantic-settings pattern of the reference's
    ConsensusSettings, SURVEY.md §5 "Config/flag system"), extended with the
    device-side knobs the reference never needed."""

    model: str = "tiny"
    checkpoint_path: Optional[str] = None
    tokenizer_path: Optional[str] = None
    model_parallel: Optional[int] = None  # TP degree (mesh "model" axis)
    max_new_tokens: int = 256
    param_seed: int = 0
    # Model-config overrides
    dtype: Optional[str] = None  # e.g. "bfloat16" | "float32"
    max_seq_len: Optional[int] = None
    # "xla" | "flash" ("flash" = the Pallas kernel on TPU, the XLA reference
    # elsewhere; see ops/attention.py::resolve_attention_impl)
    attention_impl: Optional[str] = None  # prefill
    decode_attention_impl: Optional[str] = None  # decode
    # Weight quantization: None (model dtype), "int8" (per-channel symmetric;
    # halves decode HBM traffic — the LATENCY config, ~75% of peak bandwidth
    # on v5e), or "int4" (group-wise symmetric via the Pallas w4a16 kernel —
    # the CAPACITY config: ~40% smaller footprint for larger KV/models per
    # chip, ~25% slower decode; falls back to int8 on a mesh).
    quantization: Optional[str] = None
    # Prompts at least this long prefill sequence-parallel (ring attention
    # over the mesh's data axis, O(S/P) activation memory per device) instead
    # of dense. None disables; requires a multi-device mesh.
    sp_prefill_min_tokens: Optional[int] = None
    # Context-parallel attention for SP prefill: "ring" | "ulysses".
    sp_attention: str = "ring"
    # Ring DECODE against the SP-resident prefix: the SP prefill's KV stays
    # sequence-sharded and decode attends it in place (P-1 ring hops per
    # step), keeping long-context serving O(S/P) per device end-to-end.
    sp_decode: bool = False
    # Prompt-prefix KV cache: keep the last N full-prompt KV caches on device
    # and reuse the longest common token prefix (>= prefix_cache_min_reuse
    # tokens) of any of them, prefilling only the suffix. Serves the
    # repeated-extraction pattern (one long instruction prompt, many
    # documents). 0 disables.
    prefix_cache_size: int = 0
    prefix_cache_min_reuse: int = 32
    # Speculative decoding: "prompt_lookup" drafts tokens from the prompt's
    # own text and verifies them in one forward — exact sampling at any
    # temperature; ~2x decode on prompt-copying extraction with real
    # checkpoints, ~1.4x slower at zero acceptance (see ops/speculative.py).
    speculative: Optional[str] = None
    spec_lookahead: int = 4
    # Decode-admission window (seconds): after dequeuing a request the
    # scheduler holds the batch open this long for same-key arrivals to
    # coalesce. Every request that reaches an EMPTY queue pays it — ~5 ms on
    # a ~1 s decode. Set 0.0 for latency-critical solo deployments (burst
    # coalescing then relies on queue backlog alone).
    # NB: speculative decoding composes with coalescing (the R-request spec
    # loop drafts each row from its own request's prompt table), so the
    # window no longer trades speculation away for batch throughput.
    batch_window: float = 0.005
    # -- overload protection (PR 2) --------------------------------------
    # Bounded admission: total queued weight (device rows, i.e. dp-rounded n
    # per request) above which new work is shed with a typed 429 instead of
    # queuing unboundedly. None = unbounded (the pre-PR-2 behavior).
    max_queue_weight: Optional[int] = None
    # Hard cap on the coalesced device batch (rows). None = the scheduler's
    # default (64), further tightened per request by the HBM memory model.
    max_batch_rows: Optional[int] = None
    # Per-device HBM for the memory model. None = read it from
    # device.memory_stats(): on a TPU a device that does not report raises;
    # on the CPU (test meshes, toy models) there is no HBM and the model
    # plans against 16 GiB — effectively unbounded.
    hbm_bytes: Optional[int] = None
    # Fraction of HBM the memory model may plan against; the rest absorbs
    # XLA temporaries, fragmentation, and compile-time scratch.
    hbm_headroom: float = 0.85
    # Default timeout for drain()/close() graceful shutdown.
    drain_timeout: float = 30.0
    # SSE keep-alive: the serving layer emits a ``: ping`` comment frame on
    # streaming responses whenever this many seconds pass without a data
    # event (admission queue wait, long prefill), so idle-timeout proxies
    # don't sever the connection before the first token. 0 disables.
    sse_ping_interval_s: float = 15.0
    # Debug surfaces (GET /debug/requests flight recorder, POST /debug/profile
    # jax.profiler capture): OFF by default — they expose request metadata and
    # can write profile dumps, so only operator-controlled deployments should
    # enable them (see README "Observability").
    debug_endpoints: bool = False
    # -- self-healing supervision (PR 4) ----------------------------------
    # Hung-launch watchdog budget: clamp(base + multiplier * max_new_tokens
    # * per-token EWMA) seconds per device launch. Time a launch spends
    # compiling is not charged to the budget (utils/compile_cache.py) and is
    # forgiven up to watchdog_max_budget_s; the EWMA learns steady-state
    # decode latency and tightens the budget from there.
    watchdog_base_s: float = 10.0
    watchdog_per_token_s: float = 0.5
    watchdog_multiplier: float = 8.0
    watchdog_min_budget_s: float = 60.0
    watchdog_max_budget_s: float = 900.0
    # Bounded recovery: consecutive engine rebuilds without a successful
    # launch before the backend goes STOPPED (further requests get typed
    # 503s instead of an unbounded rebuild loop).
    max_rebuilds: int = 2
    # Numeric-integrity escalation: when the aggregate poisoned-sample
    # fraction over the last poison_window launches reaches the threshold,
    # quarantine stops papering over the problem and the supervisor rebuilds
    # the engine (reload weights, fresh compile).
    poison_threshold: float = 0.5
    poison_window: int = 8
    # -- continuous in-flight batching (PR 6) -----------------------------
    # Persistent decode loop with slot admission (engine/continuous.py):
    # requests join/leave a fixed-width decode batch mid-flight instead of
    # waiting for coalesced groups to finish — the serving path's streaming
    # and tail-latency mode. Requests needing constraints, top_logprobs,
    # penalties, or logit_bias still take the coalescing scheduler.
    continuous_batching: bool = False
    # Slot count (decode batch width). Clamped by the HBM memory model's
    # row cap at (continuous_max_prompt + continuous_max_new) KV per slot.
    continuous_width: int = 8
    # Per-slot KV bounds; longer prompts / larger max_tokens fall back to
    # the coalescing path.
    continuous_max_prompt: int = 512
    continuous_max_new: int = 256
    # -- chunked prefill (PR 18) ------------------------------------------
    # Prompts longer than this many tokens are ingested into the continuous
    # loop chunk by chunk, one chunk interleaved between decode steps, so a
    # long admission no longer stalls every in-flight row for a whole
    # prefill. None = auto: HbmMemoryModel.prefill_chunk_tokens gives the
    # threshold C (4 x the loop's width), and a prompt past it is taken in
    # turns of C, 2C or 4C, the longest that what is left of it fills
    # (HbmMemoryModel.prefill_chunk_ladder). A number = that one length for
    # every turn, normalized down to a power of two >= 32 by the loop; 0 =
    # off — the whole-prompt admission path. Byte-identical output every way
    # (pinned by tests/test_chunked_prefill.py).
    prefill_chunk_tokens: Optional[int] = None
    # -- paged KV cache (PR 7) --------------------------------------------
    # Paged layout for the continuous loop's KV: a fixed pool of fixed-size
    # pages with per-row block tables; an n-way fan-out's rows SHARE the
    # prompt pages (refcounted, copy-on-write at the first divergent token)
    # instead of holding n dense copies, so admitted width at equal HBM
    # scales with the fan-out. Dense per-slot caches remain the fallback.
    paged_kv: bool = True
    # Tokens per KV page. Smaller pages waste less on partial fills but grow
    # the block tables; 64 matches the gather granularity the paged step
    # compiles well at.
    kv_page_size: int = 64
    # Total pool pages. None = sized by the continuous loop from its own
    # width/prompt/new bounds (worst-case no-sharing occupancy plus slack).
    kv_pool_pages: Optional[int] = None
    # -- paged decode everywhere (PR 11) ----------------------------------
    # Paged-attention implementation for paged decode steps: "auto" picks
    # the fused Pallas kernel on TPU (sliding windows included, on every layer
    # or on some: each layer's walk and mask take its own) and the jittable
    # XLA reference elsewhere; "pallas" requests the kernel explicitly
    # (COUNTED fallback to XLA when unavailable —
    # kernel.paged_attn_fallback.<reason>); "xla" forces the reference. See
    # ops/paged_attention.py.
    paged_attention_impl: str = "auto"
    # Route coalesced generate_many batches through the page pool too
    # (block-table decode, prompt pages shared via admission; byte-identical
    # tokens to dense). False keeps coalesced batches on dense rows.
    paged_generate_many: bool = True
    # -- on-device consensus (PR 8) ---------------------------------------
    # Route consolidation's pairwise-similarity and majority-vote kernels
    # through batched JAX on the chip (consensus/device.py), with automatic
    # per-consolidation host fallback (failpoint, busy chip, unsupported
    # payload shape, JAX unavailable). False = always the host Python path.
    device_consensus: bool = True
    # -- constrained decoding (PR 12) --------------------------------------
    # Compile response_format JSON schemas into token-level grammar masks
    # (engine/grammar.py) applied in-decode, so every sample is parse-valid
    # by construction. Compiles are memoized process-wide by (schema, vocab)
    # digest — ReplicaSet members share one cache. Unsupported schema
    # features degrade to the generic JSON mask; compile errors and the
    # engine.grammar failpoint degrade to unconstrained decode — post-hoc
    # validation in parse() stays authoritative either way (counted, see
    # GRAMMAR_EVENTS). False = the pre-PR-12 post-hoc-only posture.
    constrained_decoding: bool = True
    # -- multi-tenant isolation (PR 16) ------------------------------------
    # Per-tenant token-bucket quotas, WFQ dequeue weights, and SLO classes
    # (reliability/tenancy.py). Defaults apply to every tenant not listed in
    # ``tenants``; None rates = unlimited (the pre-PR-16 posture). ``tenants``
    # maps tenant name -> TenantSpec field overrides ({"weight": 3.0,
    # "slo": "batch", "requests_per_s": 5, ...}); ``tenant_api_keys`` maps
    # API key -> tenant name for the serving front door (unmapped keys become
    # their own dynamic tenant under the default spec).
    tenant_default_weight: float = 1.0
    tenant_default_slo: str = "interactive"
    tenant_default_requests_per_s: Optional[float] = None
    tenant_default_rows_per_s: Optional[float] = None
    tenants: Optional[Dict[str, Dict[str, Any]]] = None
    tenant_api_keys: Optional[Dict[str, str]] = None
    # Brownout trigger: queued-weight fraction of max_queue_weight at which
    # the scheduler starts shedding batch-class admissions (also armed by
    # sustained OOM backoff, width_shift >= 2). See engine/scheduler.py.
    brownout_high_water: float = 0.9
    # -- offline batch lane (serving/batch.py) --
    # Durable root for the batch job store (journal + output segments);
    # None → the serving app falls back to KLLMS_BATCH_DIR or an ephemeral
    # tempdir (no restart recovery).
    batch_store_dir: Optional[str] = None
    # Bound on concurrently-executing batch items (worker threads feeding the
    # scheduler at batch-SLO priority under the owner's quota).
    batch_max_in_flight: int = 4
    # Re-dispatches after a quota 429 before the item fails into the output.
    batch_item_retries: int = 1
    # TTL for terminal batch jobs: on store open, jobs older than this are
    # GC'd (journal gc record + directory removal). None/0 → keep forever.
    jobstore_ttl_s: Optional[float] = None


#: What the memory model plans against where there is no HBM to read (CPU
#: test meshes with toy models): far above the scheduler's max_rows, i.e. the
#: model imposes nothing.
CPU_PLANNING_BYTES = 16 * (1 << 30)


def _detect_hbm_bytes() -> int:
    """Per-device memory limit as the PJRT runtime reports it. A TPU that
    does not report one raises — planning KV rows against an assumed size
    would hide the device; the CPU has no HBM and gets
    :data:`CPU_PLANNING_BYTES`."""
    import jax

    device = jax.local_devices()[0]
    if device.platform == "cpu":
        return CPU_PLANNING_BYTES
    stats = device.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not limit:
        raise RuntimeError(
            f"{device.device_kind} reports no memory limit (memory_stats="
            f"{sorted(stats)}); pass BackendConfig.hbm_bytes"
        )
    return int(limit)


class HbmMemoryModel:
    """Static HBM accounting for the coalesced decode: how many device rows
    (samples) fit alongside the resident parameters?

    Per-device footprint of an R-row decode at sequence length S:

        params / tp                               (weights, sharded over TP)
      + (R / dp) * S * kv_bytes_per_token / tp    (cache rows; heads shard TP,
                                                   rows shard DP)
      + (R / dp) * row_margin                     (logits f32 + sampling state)

    Inverting for R against ``hbm * headroom`` gives the row cap the
    scheduler may coalesce to for a given request shape. Deliberately
    conservative and static — it exists to keep the FIRST launch from
    exceeding HBM; the engine's OOM guard (split-and-requeue) catches what
    the model underestimates.

    ``kv_bytes_per_token`` is the model's own (``ModelConfig``): K and V over
    every layer's KV heads for the GQA block, one ``kv_lora_rank +
    qk_rope_head_dim`` latent row a layer and no V for a latent (MLA) model,
    counted as the page pool stores it, in whole tiles of 128 lanes (576 ->
    640: 8,960 B a token for the 7-layer Xing4.0 cut against qwen2-7b's
    57,344), while :meth:`max_rows`, the bound for dense rows, counts the
    dense cache's own 576 (``dense_kv_bytes_per_token``: 8,064 B); a hybrid
    stack counts its attention layers alone (1,024 B a token
    for the 9-layer Nemotron-3 cut) and charges each row its fixed recurrent
    state (8.5 MB there) in the row margin."""

    def __init__(
        self,
        config,
        param_bytes: int,
        hbm_bytes: Optional[int] = None,
        headroom: float = 0.85,
        tp: int = 1,
        dp: int = 1,
    ):
        self.config = config
        self.param_bytes = int(param_bytes)
        self.hbm_bytes = int(
            hbm_bytes if hbm_bytes is not None else _detect_hbm_bytes()
        )
        self.headroom = float(headroom)
        self.tp = max(1, int(tp))
        self.dp = max(1, int(dp))
        # Every layer's cache row for one token; KV heads shard over the
        # model axis with the attention that consumes them.
        self.kv_bytes_per_token = config.kv_bytes_per_token
        self.dense_kv_bytes_per_token = config.dense_kv_bytes_per_token
        # Per-row non-KV working set: the decode loop materializes f32 logits
        # and sampling buffers per row; 4 bytes * vocab is the dominant term
        # for every model whose rows hold no recurrent state.
        self.row_margin_bytes = (
            4 * config.vocab_size + (64 << 10) + config.state_bytes_per_row
        )

    def budget_bytes(self) -> int:
        """Bytes available for per-row state after params, per device."""
        return int(self.hbm_bytes * self.headroom) - self.param_bytes // self.tp

    def max_rows(self, seq_len: int) -> int:
        """Row cap for a decode whose rows each hold ``seq_len`` tokens of KV
        (prompt + max_new). Always >= 1: a single row that doesn't fit is the
        OOM guard's problem, not admission's — failing it here would turn an
        optimistic estimate into a hard rejection."""
        seq_len = max(1, int(seq_len))
        per_row = (
            seq_len * self.dense_kv_bytes_per_token // self.tp + self.row_margin_bytes
        )
        rows = self.dp * max(0, self.budget_bytes()) // max(1, per_row)
        return max(1, int(rows))

    def paged_max_rows(
        self, prompt_len: int, max_new: int, page_size: int, fanout: int = 1
    ) -> int:
        """Row cap when rows hold paged KV and every ``fanout`` rows share
        one prompt's pages: per-row cost is the private generation reserve
        plus ``1/fanout`` of the shared prompt pages. At ``fanout == 1`` this
        is :meth:`max_rows` up to page-granularity rounding; at high fan-out
        the prompt term amortizes away and admitted width scales ~n x."""
        ps = max(1, int(page_size))
        fanout = max(1, int(fanout))
        prompt_len = max(1, int(prompt_len))
        max_new = max(1, int(max_new))
        page_bytes = ps * self.kv_bytes_per_token // self.tp
        prompt_pages = -(-prompt_len // ps)
        reserve = row_reserve_pages(prompt_len, max_new, ps)
        per_row = (
            reserve * page_bytes
            + -(-prompt_pages * page_bytes // fanout)
            + self.row_margin_bytes
        )
        rows = self.dp * max(0, self.budget_bytes()) // max(1, per_row)
        return max(1, int(rows))

    def prefill_chunk_tokens(self, width: int, max_prompt: int) -> int:
        """Auto chunk size for interleaved prefill: the shortest length of a
        lane turn, and the prompt length past which an admission takes the
        lane at all. A decode step computes one token-row per active slot
        (<= ``width``); a C-token chunk costs ~C token-rows of the same
        per-layer work, so C ~= 4*width is a stall of ~3x a steady-state
        step at most. That is the bound :meth:`prefill_chunk_ladder` trades.
        Power of two, floored at 32, capped at max_prompt // 2 so chunking
        actually splits any prompt it engages on; 0 (off) when the prompt
        bound is too small for chunking to ever help."""
        if max_prompt < 64:
            return 0
        target = min(max(32, 4 * max(1, int(width))), max_prompt // 2)
        c = 32
        while c * 2 <= target:
            c *= 2
        return c

    def prefill_chunk_ladder(self, width: int, max_prompt: int) -> Tuple[int, ...]:
        """The lengths a lane turn may take under the automatic chunk size:
        C, 2C and 4C for C = :meth:`prefill_chunk_tokens` (4, 8 and 16 x the
        loop's width), none longer than the largest prompt bucket; ``()``
        where chunking is off. Each turn the loop takes the longest while
        what is left of the prompt fills it, and the shortest that covers the
        rest in the prompt's last turn (``engine/continuous.py::pick_chunk``):
        a prompt is ceil(len / 4C) turns where chunks of C alone made it
        ceil(len / C), each a launch, a fetch and a decode step in between
        while the request's rows stood empty and the queue waited for the one
        lane (PERF.md, PR 39). The price is the stall one turn puts on the
        live rows: a 16 x width chunk is ~3-5 decode steps long, where C
        alone held it to ~3x a step at most. Nothing here reads a model or a
        prompt bound: the remainder of the prompt in hand decides."""
        c = self.prefill_chunk_tokens(width, max_prompt)
        return tuple(r for r in (c, 2 * c, 4 * c) if 0 < r <= _bucket(int(max_prompt)))

    def describe(self) -> Dict[str, Any]:
        return {
            "hbm_bytes": self.hbm_bytes,
            "headroom": self.headroom,
            "param_bytes": self.param_bytes,
            "kv_bytes_per_token": self.kv_bytes_per_token,
            "tp": self.tp,
            "dp": self.dp,
            "max_rows_at_max_seq": self.max_rows(self.config.max_seq_len),
        }


class _IncrementalDetok:
    """Turns per-step token taps into per-sample TEXT deltas for SSE.

    Byte/BPE decodes are not prefix-stable token by token: a cut inside a
    multi-byte UTF-8 character decodes to U+FFFD, and HF-style decode cleanup
    can rewrite earlier characters when a token is appended. So each feed
    re-decodes the sample's full accumulated ids, holds back any replacement-
    character tail, and emits only a grown prefix extension — a step whose
    decode shrank or diverged emits nothing and later steps recover. Stop
    strings truncate here too (nothing past the earliest occurrence reaches
    the wire), mirroring chat_completion's authoritative host-side scan.

    ``flush_final`` reconciles against the finished choices: samples that
    never produced a delta (speculative decode and SP-prefix paths have no
    token tap) get their full text as one delta — the wire contract is at
    least one delta per live sample before the final consensus event.
    """

    def __init__(self, tok, n: int, pad_id: int, stop_strings: List[str],
                 emit) -> None:
        self.tok = tok
        self.n = n
        self.pad_id = pad_id
        self.stop_strings = stop_strings
        self.emit = emit
        self.ids: List[List[int]] = [[] for _ in range(n)]
        self.sent: List[str] = ["" for _ in range(n)]
        self.stopped = [False] * n

    def feed(self, step: int, toks: np.ndarray) -> None:
        for i in range(min(self.n, len(toks))):
            t = int(toks[i])
            if t == self.pad_id or self.stopped[i]:
                continue
            self.ids[i].append(t)
            text = self.tok.decode(self.ids[i])
            while text.endswith("�"):
                # Incomplete UTF-8 tail — hold it back until the next token
                # completes the character.
                text = text[:-1]
            cuts = [
                pos for s in self.stop_strings if (pos := text.find(s)) != -1
            ]
            if cuts:
                text = text[: min(cuts)]
                self.stopped[i] = True
            if len(text) > len(self.sent[i]) and text.startswith(self.sent[i]):
                delta = text[len(self.sent[i]):]
                self.sent[i] = text
                self.emit(i, delta)

    def flush_final(self, final_texts: List[Optional[str]]) -> None:
        for i, final in enumerate(final_texts):
            if final is None:
                continue
            sent = self.sent[i]
            if not sent:
                self.emit(i, final)
            elif final.startswith(sent):
                rest = final[len(sent):]
                if rest:
                    self.emit(i, rest)
            elif final != sent:
                # Streamed text diverged from the authoritative decode (decode
                # cleanup rewrote earlier characters). The final consensus
                # event carries the correct text; don't compound the drift.
                logger.debug(
                    "streamed text diverged from final decode for sample %d", i
                )


class TpuBackend(Backend):
    def __init__(
        self,
        model: Optional[str] = None,
        config: Optional[BackendConfig] = None,
        mesh=None,
        engine: Optional[LocalEngine] = None,
        **kwargs: Any,
    ):
        if config is not None and model is not None and model != config.model:
            # An explicit config wins over kwargs — but silently dropping a
            # CONFLICTING model would load one model's weights while labeling
            # outputs with the other's name.
            raise ValueError(
                f"model={model!r} conflicts with config.model={config.model!r}; "
                "pass one or make them agree"
            )
        cfg = config or BackendConfig(model=model or "tiny", **{
            k: v for k, v in kwargs.items() if k in BackendConfig.model_fields
        })
        self.backend_config = cfg
        self.model_name = cfg.model
        try:
            model_config = get_config(cfg.model)
        except KeyError:
            # Not a registered architecture name: a local HF checkpoint dir
            # carries its own config.json — build the ModelConfig from it.
            from ..models.loader import config_from_hf

            model_config = (
                config_from_hf(cfg.checkpoint_path) if cfg.checkpoint_path else None
            )
            if model_config is None:
                raise
        overrides = {
            k: getattr(cfg, k)
            for k in ("dtype", "max_seq_len", "attention_impl", "decode_attention_impl")
            if getattr(cfg, k) is not None
        }
        if overrides:
            model_config = model_config.with_(**overrides)
        self.tokenizer = get_tokenizer(cfg.tokenizer_path)
        if cfg.quantization not in (None, "int8", "int4"):
            # Validate before the (potentially multi-GB) checkpoint load.
            raise ValueError(
                f"Unsupported quantization {cfg.quantization!r}; use 'int8' or 'int4'"
            )
        if model_config.is_hybrid and not cfg.continuous_batching:
            raise NotImplementedError(
                f"{model_config.name}: only the continuous loop serves the hybrid stack (it "
                "carries the rows' recurrent state; the parallel block has no dense decode "
                "step); build with continuous_batching=True"
            )
        self._model_config = model_config
        self._mesh = mesh
        self.param_summary: Optional[Dict[str, Any]] = None
        self.engine = engine if engine is not None else self._build_engine()
        self.default_max_new_tokens = cfg.max_new_tokens
        # HBM memory model: caps the rows any coalesced group may fuse to for
        # a given request shape (prompt + max_new KV per row), per-request via
        # the scheduler's max_rows hint. TP degree = the engine mesh's model
        # axis; params measured from the resident tree (quantization included).
        mp = 1
        if self.engine.mesh is not None:
            from ..parallel.mesh import MODEL_AXIS

            mp = self.engine.mesh.shape.get(MODEL_AXIS, 1)
        self.memory_model = HbmMemoryModel(
            self.engine.config,
            param_bytes=self.engine.param_footprint_bytes(),
            hbm_bytes=cfg.hbm_bytes,
            headroom=cfg.hbm_headroom,
            tp=mp,
            dp=self.engine.data_parallel_size,
        )
        # All device work funnels through one scheduler so concurrent clients
        # (AsyncKLLMs, threads) serialize cleanly instead of racing jit caches.
        from ..engine.scheduler import EngineScheduler

        scheduler_kwargs: Dict[str, Any] = {}
        if cfg.max_batch_rows is not None:
            scheduler_kwargs["max_rows"] = cfg.max_batch_rows
        # Multi-tenant quota/fairness registry: one per backend, shared by the
        # coalescing scheduler, the continuous loop, and the serving front
        # door's API-key resolution (backend.tenancy).
        from ..reliability.tenancy import TenancyConfig

        self.tenancy = TenancyConfig.from_options(
            default_weight=cfg.tenant_default_weight,
            default_slo=cfg.tenant_default_slo,
            default_requests_per_s=cfg.tenant_default_requests_per_s,
            default_rows_per_s=cfg.tenant_default_rows_per_s,
            tenants=cfg.tenants,
            api_keys=cfg.tenant_api_keys,
        )
        self.scheduler = EngineScheduler(
            name=self.model_name,
            batch_window=cfg.batch_window,
            max_queue_weight=cfg.max_queue_weight,
            tenancy=self.tenancy,
            brownout_high_water=cfg.brownout_high_water,
            **scheduler_kwargs,
        )
        # Consensus cache/dispatch stats ride along scheduler.stats()/health().
        self.scheduler.consensus_stats_provider = self._consensus_stats
        # Self-healing supervision: every device launch runs under the
        # watchdog; a hung or poison-escalated engine is rebuilt through
        # _rebuild_engine and the launch replayed on the new engine. The
        # hooks ARE the scheduler's RECOVERING / READY / STOPPED transitions.
        from ..reliability.supervisor import EngineSupervisor, LaunchBudgetModel

        self.supervisor = EngineSupervisor(
            rebuild_fn=self._rebuild_engine,
            budget_model=LaunchBudgetModel(
                base_s=cfg.watchdog_base_s,
                per_token_s=cfg.watchdog_per_token_s,
                multiplier=cfg.watchdog_multiplier,
                min_budget_s=cfg.watchdog_min_budget_s,
                max_budget_s=cfg.watchdog_max_budget_s,
            ),
            max_rebuilds=cfg.max_rebuilds,
            poison_threshold=cfg.poison_threshold,
            poison_window=cfg.poison_window,
            on_recovering=self.scheduler.note_recovering,
            on_rebuilt=self.scheduler.note_rebuilt,
            on_rebuild_failed=self.scheduler.note_rebuild_failed,
        )
        self._wire_engine_hooks()
        self._closed = False
        # (vocab byte strings, digest) for grammar compiles — lazy, see
        # _grammar_vocab; the compiled grammars themselves live in the
        # PROCESS-wide cache (engine/grammar.py), shared across replicas.
        self._grammar_vocab_cache = None
        # Continuous in-flight batching: a persistent slot-admission decode
        # loop beside the coalescing scheduler. Admission respects the same
        # DRAINING/STOPPED lifecycle (admission_gate) so drain() quiesces both.
        self._continuous = None
        if cfg.continuous_batching:
            self._continuous = self._build_continuous_loop()

    def _build_continuous_loop(self):
        from ..engine.continuous import ContinuousDecodeLoop

        cfg = self.backend_config
        if getattr(self.engine, "kv_layout", "dense") == "paged":
            if "continuous_width" not in cfg.model_fields_set:
                # ROADMAP: drive the admitted width to the paged HBM caps.
                # With no explicit continuous_width the dense-era static
                # default (8 slots) no longer binds — size the loop from the
                # no-sharing paged cap (never overcommits; prefix sharing
                # only adds headroom at runtime), bounded at 32 slots as a
                # compile-size guard. Setting continuous_width overrides.
                width = min(
                    self.memory_model.paged_max_rows(
                        cfg.continuous_max_prompt,
                        cfg.continuous_max_new,
                        self.engine.kv_page_size,
                        fanout=1,
                    ),
                    32,
                )
            else:
                # Paged rows share prompt pages across a fan-out; clamp
                # against the amortized cost at the loop's own width (the
                # fan-out bound) so shared-prefix requests aren't
                # under-admitted by dense math.
                width = min(
                    cfg.continuous_width,
                    self.memory_model.paged_max_rows(
                        cfg.continuous_max_prompt,
                        cfg.continuous_max_new,
                        self.engine.kv_page_size,
                        fanout=cfg.continuous_width,
                    ),
                )
        else:
            width = min(
                cfg.continuous_width,
                self.memory_model.max_rows(
                    cfg.continuous_max_prompt + cfg.continuous_max_new
                ),
            )
        # The loop gets its OWN budget model: per-step EWMA latency (one
        # decode step each observation) must not pollute the supervisor's
        # per-launch EWMA (whole coalesced decodes), and vice versa. Same
        # clamp envelope, independent learned state.
        from ..reliability.supervisor import LaunchBudgetModel

        chunk, ladder = cfg.prefill_chunk_tokens, ()
        if chunk is None:
            ladder = self.memory_model.prefill_chunk_ladder(
                max(1, width), cfg.continuous_max_prompt
            )
            chunk = ladder[0] if ladder else 0
        return ContinuousDecodeLoop(
            self.engine,
            width=max(1, width),
            max_prompt=cfg.continuous_max_prompt,
            max_new=cfg.continuous_max_new,
            eos_ids=self.tokenizer.stop_ids,
            admission_gate=self.scheduler.admission_error,
            budget_model=LaunchBudgetModel(
                base_s=cfg.watchdog_base_s,
                per_token_s=cfg.watchdog_per_token_s,
                multiplier=cfg.watchdog_multiplier,
                min_budget_s=cfg.watchdog_min_budget_s,
                max_budget_s=cfg.watchdog_max_budget_s,
            ),
            rebuild_fn=self._rebuild_loop_engine,
            max_rebuilds=cfg.max_rebuilds,
            on_recovering=self.scheduler.note_recovering,
            on_rebuilt=self.scheduler.note_rebuilt,
            on_rebuild_failed=self.scheduler.note_rebuild_failed,
            prefill_chunk_tokens=max(0, int(chunk)),
            prefill_chunk_ladder=ladder,
        )

    # -- engine lifecycle --------------------------------------------------
    def _build_engine(self) -> LocalEngine:
        """Construct (or re-construct) the engine: checkpoint reload through
        the loader — integrity-verified, so a corrupt checkpoint raises
        CheckpointCorruptError before any compile — plus fresh jit caches.
        Shared by __init__ and the supervisor's rebuild path so a recovery
        lands on exactly the weights a cold start would load (same
        checkpoint, or the same param_seed when running seeded)."""
        cfg = self.backend_config
        params = None
        self.param_summary = None
        if cfg.checkpoint_path:
            from ..models import loader as _loader

            params = _loader.load_checkpoint(cfg.checkpoint_path, self._model_config)
            self.param_summary = _loader.last_load_summary
        return LocalEngine(
            self._model_config,
            params=params,
            mesh=self._mesh,
            model_parallel=cfg.model_parallel,
            param_seed=cfg.param_seed,
            quantize=cfg.quantization or False,
            sp_prefill_min_tokens=cfg.sp_prefill_min_tokens,
            sp_attention=cfg.sp_attention,
            sp_decode=cfg.sp_decode,
            prefix_cache_size=cfg.prefix_cache_size,
            prefix_cache_min_reuse=cfg.prefix_cache_min_reuse,
            speculative=cfg.speculative,
            spec_lookahead=cfg.spec_lookahead,
            kv_layout="paged" if cfg.paged_kv else "dense",
            kv_page_size=cfg.kv_page_size,
            kv_pool_pages=cfg.kv_pool_pages,
            paged_attention_impl=cfg.paged_attention_impl,
            paged_generate_many=cfg.paged_generate_many,
        )

    def _wire_engine_hooks(self) -> None:
        """Device-OOM feedback loop (the engine's guard reports each caught
        RESOURCE_EXHAUSTED so the scheduler halves its coalescing width, and
        each clean launch so width steps back up and DEGRADED clears) plus
        the quarantine feed. Re-run after every rebuild so the feedback
        follows the NEW engine, not the wedged one."""
        self.engine.on_oom = self.scheduler.note_oom
        self.engine.on_launch_ok = self.scheduler.note_recovered
        self.engine.on_spec_stats = self.scheduler.note_spec_stats
        self.engine.on_quarantine = self._on_quarantine

    def _on_quarantine(self, poisoned: int, total: int) -> None:
        # Fires after EVERY launch (poisoned=0 when clean) so the
        # supervisor's escalation window decays under healthy traffic.
        self.scheduler.note_quarantine(poisoned)
        self.supervisor.note_poison(poisoned, total)

    def _rebuild_engine(self) -> None:
        """Supervisor rebuild_fn: drop the wedged engine and stand up a fresh
        one. The old engine is simply unreferenced — its device buffers are
        reclaimed by the runtime once the abandoned launch thread (if any)
        releases them; explicit teardown would race that thread."""
        self.engine = self._build_engine()
        self._wire_engine_hooks()
        if self._continuous is not None:
            # The loop holds device KV tied to the old engine's params. Hand
            # it the new engine: the loop journals its in-flight rows,
            # re-prefills against the fresh weights, and replays each
            # survivor byte-identically (pinned seeds + self-deterministic
            # row keys) — callers keep streaming instead of eating a 503.
            self._continuous.adopt_engine(self.engine)

    def _rebuild_loop_engine(self) -> LocalEngine:
        """Continuous-loop rebuild_fn: same reload as the supervisor path
        (checkpoint integrity re-verified, fresh jit caches, hooks rewired),
        but DRIVEN by the loop — it already holds its own journal, so this
        just returns the engine for the loop to adopt in place."""
        self.engine = self._build_engine()
        self._wire_engine_hooks()
        return self.engine

    # -- chat -------------------------------------------------------------
    supports_streaming = True

    def chat_completion_stream(self, request: ChatRequest, emit) -> ChatCompletion:
        """Streaming wire contract: per-token text deltas via ``emit(i, text)``
        while the decode runs, then the full ChatCompletion for consolidation.
        Same generation as chat_completion — only the tap differs."""
        return self.chat_completion(request, _token_emit=emit)

    def chat_completion(self, request: ChatRequest, _token_emit=None) -> ChatCompletion:
        tok = self.tokenizer
        prompt_ids = tok.apply_chat_template(request.messages, add_generation_prompt=True)
        n = max(1, request.n)

        temperature = 1.0 if request.temperature is None else float(request.temperature)
        max_new = request.max_tokens or self.default_max_new_tokens
        # Structured-output requests get grammar-constrained decoding (the
        # reference relies on the OpenAI server for this guarantee). A pydantic
        # response_format compiles to a CompiledGrammar (engine/grammar.py) —
        # a fleet-cached token-mask automaton over this tokenizer's byte
        # strings, so keys, types, and enums are enforced in-decode and every
        # sample validates into the user's model; anything the schema compiler
        # can't express degrades to the valid-JSON mask, and compile errors /
        # the engine.grammar failpoint / constrained_decoding=False degrade to
        # unconstrained decode — post-hoc validation stays authoritative.
        _req_trace = current_trace()
        if _req_trace is not None:
            with _req_trace.phase("grammar_mask"):
                constraint = self._constraint_for(request.response_format)
        else:
            constraint = self._constraint_for(request.response_format)
        # OpenAI semantics: top_logprobs only applies when logprobs is on.
        top_lp = request.top_logprobs if request.logprobs else None
        logit_bias = None
        if request.logit_bias:
            V = self.engine.config.vocab_size
            logit_bias = {}
            for tok_id, bias in request.logit_bias.items():
                t = int(tok_id)
                if not 0 <= t < V:
                    raise ValueError(f"logit_bias token id {t} outside vocab (0..{V-1})")
                logit_bias[t] = float(bias)
        stop_strings: List[str] = []
        if isinstance(request.stop, str):
            stop_strings = [request.stop]
        elif isinstance(request.stop, list):
            stop_strings = [s for s in request.stop if s]
        # Tokenized stop sequences halt rows ON DEVICE (engine suffix match);
        # the text scan below stays authoritative for BPE re-tokenization
        # boundary cases and over-long/overflow stops. Only device-matchable
        # ones (length AND count) are handed down — the engine warns on drops,
        # which would be spurious here since this path always has the host
        # fallback.
        from ..engine.engine import MAX_STOP_LEN, MAX_STOP_SEQS

        stop_seqs = [
            ids_s
            for ids_s in (tok.encode(s) for s in stop_strings)
            if 0 < len(ids_s) <= MAX_STOP_LEN
        ][:MAX_STOP_SEQS] or None

        detok = None
        if _token_emit is not None:
            detok = _IncrementalDetok(
                tok, n, self.engine.config.pad_token_id, stop_strings,
                _token_emit,
            )

        result = self._generate_batched(
            prompt_ids,
            n=n,
            max_new=max_new,
            temperature=temperature,
            top_p=request.top_p,
            seed=request.seed,
            constraint=constraint,
            top_logprobs=top_lp,
            frequency_penalty=float(request.frequency_penalty or 0.0),
            presence_penalty=float(request.presence_penalty or 0.0),
            logit_bias=logit_bias,
            stop_sequences=stop_seqs,
            budget=request.budget,
            token_sink=detok.feed if detok is not None else None,
            tenant=request.tenant,
        )

        choices: List[Dict[str, Any]] = []
        final_texts: List[Optional[str]] = []
        completion_tokens = 0
        for i in range(n):
            err = result.sample_errors[i] if result.sample_errors else None
            if err is not None:
                # Sample lost mid-decode (fault or injected kill): an empty-
                # content choice already drops out of the consensus vote; the
                # ``sample_error`` extension lets consolidation count the loss
                # and emit the response-level ``degraded`` marker.
                choices.append(
                    {
                        "finish_reason": "stop",
                        "index": i,
                        "message": {"role": "assistant", "content": ""},
                        "logprobs": None,
                        "sample_logprob": 0.0,
                        "sample_error": dict(err),
                    }
                )
                final_texts.append("")
                continue
            length = int(result.lengths[i])
            ids = [int(t) for t in result.tokens[i][:length]]
            text = tok.decode(ids)
            finish = result.finish_reasons[i]
            # OpenAI semantics: truncate at the EARLIEST stop occurrence in the
            # text, whichever stop string produced it.
            cuts = [pos for s in stop_strings if (pos := text.find(s)) != -1]
            if cuts:
                pos = min(cuts)
                finish = "stop"
                # Usage counts only tokens that contribute to the VISIBLE text
                # (OpenAI neither returns nor continues past the stop).
                length = _visible_token_count(tok, ids, pos, text)
                text = text[:pos]
            completion_tokens += length
            logprobs_payload = None
            if request.logprobs:
                # ``bytes`` carries each token's RAW bytes (OpenAI semantics:
                # concatenating the entries reproduces the text's bytes, even
                # across multi-byte UTF-8 split over several tokens); ``token``
                # stays the per-token decode, replacement chars and all.
                _tok_bytes = getattr(
                    tok, "token_bytes", lambda t: tok.decode([t]).encode("utf-8")
                )

                def _top_entries(step: int):
                    if result.top_tokens is None:
                        return []
                    entries = []
                    for tid, tlp in zip(
                        result.top_tokens[i][step].tolist(),
                        result.top_logprobs[i][step].tolist(),
                    ):
                        entries.append(
                            {
                                "token": tok.decode([int(tid)]),
                                "logprob": float(tlp),
                                "bytes": list(_tok_bytes(int(tid))),
                            }
                        )
                    return entries

                logprobs_payload = {
                    "content": [
                        {
                            "token": tok.decode([t]),
                            "logprob": float(lp),
                            "bytes": list(_tok_bytes(int(t))),
                            "top_logprobs": _top_entries(j),
                        }
                        for j, (t, lp) in enumerate(
                            zip(ids, result.logprobs[i][:length].tolist())
                        )
                    ]
                }
            choices.append(
                {
                    "finish_reason": finish,
                    "index": i,
                    "message": {"role": "assistant", "content": text},
                    "logprobs": logprobs_payload,
                    # Sequence-level sample log-likelihood (extension field; the
                    # vendored types tolerate extras). Feeds likelihood-weighted
                    # consensus (BASELINE.json config 3).
                    "sample_logprob": float(np.sum(result.logprobs[i][:length])),
                }
            )
            final_texts.append(text)

        if detok is not None:
            # Reconcile streamed deltas against the authoritative texts; this
            # also covers generation paths with no token tap (speculative,
            # SP-prefix) by emitting each sample's full text as one delta.
            detok.flush_final(final_texts)

        digest = hashlib.md5(repr((request.messages, request.seed)).encode()).hexdigest()[:12]
        payload: Dict[str, Any] = {
            "id": f"chatcmpl-tpu-{digest}",
            "choices": choices,
            "created": int(time.time()),
            "model": request.model or self.model_name,
            "object": "chat.completion",
            "system_fingerprint": f"k-llms-tpu/{self.model_name}",
            "usage": {
                "prompt_tokens": result.prompt_len,
                "completion_tokens": completion_tokens,
                "total_tokens": result.prompt_len + completion_tokens,
            },
        }
        if os.getenv("KLLMS_TRACE") == "1":
            # Engine serving stats captured AT GENERATION TIME for this
            # request (result.spec_stats rides the GenerationResult, so a
            # concurrent request can't overwrite it before tracing reads it);
            # cache/scheduler counters are cumulative snapshots.
            payload["engine_stats"] = {
                "spec": dict(result.spec_stats or {}),
                "prefix_cache": dict(self.engine.prefix_cache_stats),
                "scheduler": dict(self.scheduler.stats),
            }
        return ChatCompletion.model_validate(payload)

    def _generate_batched(
        self,
        prompt_ids: List[int],
        *,
        n: int,
        max_new: int,
        temperature: float,
        top_p: Optional[float],
        seed: Optional[int],
        constraint: Any,
        top_logprobs: Optional[int] = None,
        frequency_penalty: float = 0.0,
        presence_penalty: float = 0.0,
        logit_bias: Optional[Dict[int, float]] = None,
        stop_sequences: Optional[List[List[int]]] = None,
        budget=None,
        token_sink=None,
        tenant=None,
    ):
        """Submit one generation through the coalescing scheduler: concurrent
        requests with the same sampling config decode as ONE batched XLA
        program (`LocalEngine.generate_many`); a lone request runs solo.
        ``budget`` rides both the scheduler item (admission control, window
        bounding, queue shedding) and the GenRequestSpec (decode-loop
        cancellation); it is NOT part of the batch_key — different deadlines
        still coalesce. ``tenant`` (a name or None) bills this request's
        padded rows against that tenant's token buckets and keys WFQ dequeue;
        over-quota requests 429 here before touching either decode path."""
        from ..engine.engine import GenRequestSpec

        ckey = None
        if constraint is not None:
            ckey = (
                "json"
                if constraint == "json"
                else (type(constraint).__name__, constraint.digest)
            )
        eos_ids = self.tokenizer.stop_ids
        # The bias CONTENT is part of the compatibility key — coalesced rows
        # share one bias vector, so only identical biases may fuse.
        bias_key = tuple(sorted(logit_bias.items())) if logit_bias else None
        # Stop CONTENT keys the batch too: coalesced rows share one device
        # stop matrix, so only identical stop sets may fuse.
        stop_key = tuple(map(tuple, stop_sequences)) if stop_sequences else None
        batch_key = (
            max_new, temperature, top_p, ckey, tuple(eos_ids), top_logprobs,
            frequency_penalty, presence_penalty, bias_key, stop_key,
        )

        # Pin the sampling seed at SUBMISSION time: with seed=None the engine
        # would draw fresh entropy per launch, so a watchdog-triggered replay
        # of this request would sample different tokens than the abandoned
        # attempt. Pinning here makes replay byte-identical to an
        # uninterrupted run (same weights after reload + same key derivation).
        if seed is None:
            seed = int.from_bytes(os.urandom(4), "little")

        # Weight = this request's padded row count (the engine rounds n up to
        # a data-parallel multiple), so quota billing and the scheduler's
        # max_rows bound both track the batch the device will actually see.
        dp = self.engine.data_parallel_size
        rows = ((max(1, n) + dp - 1) // dp) * dp
        # Tenant quota: charged ONCE, up front, before path routing — a
        # continuous-loop bounds rejection that falls back to coalescing must
        # not bill the same request twice. Raises the typed 429 (retry_after =
        # this tenant's own bucket refill) on an empty bucket.
        tenant_ctx = self.scheduler.charge_tenant_quota(tenant, rows=rows)

        # Continuous in-flight batching: qualifying requests join the
        # persistent slot loop the step after admission instead of waiting
        # behind coalesced groups. Features that key the compiled program
        # (top_logprobs, penalties, bias) stay on the coalescing path;
        # CompiledGrammar constraints qualify — the loop's grammar-twin
        # programs take the mask tables as arguments, so schemas share one
        # program (a different schema than the loop's resident one raises
        # ValueError below and coalesces instead); stop SEQUENCES qualify
        # because the host text scan above is authoritative (the loop just
        # decodes to eos/max_new).
        from ..engine.grammar import CompiledGrammar

        loop_grammar = (
            constraint if isinstance(constraint, CompiledGrammar) else None
        )
        if (
            self._continuous is not None
            and (constraint is None or loop_grammar is not None)
            and top_logprobs is None
            and frequency_penalty == 0.0
            and presence_penalty == 0.0
            and logit_bias is None
            and self._continuous.qualifies(len(prompt_ids), max(1, n), max_new)
        ):
            try:
                return self._continuous.submit(
                    list(prompt_ids),
                    n=max(1, n),
                    max_new=max_new,
                    temperature=temperature,
                    top_p=top_p,
                    seed=seed,
                    budget=budget,
                    token_sink=token_sink,
                    grammar=loop_grammar,
                    tenant=tenant_ctx,
                ).result()
            except ValueError:
                # Templated prompt outgrew the loop's bounds, or the loop is
                # busy under a different grammar — coalescing path.
                pass

        def run(specs):
            dp_now = self.engine.data_parallel_size
            launch_rows = sum(
                ((max(1, s.n) + dp_now - 1) // dp_now) * dp_now for s in specs
            )
            # The lambda re-resolves self.engine at call time, so when the
            # supervisor rebuilds mid-launch the replay lands on the NEW
            # engine — that is the whole recovery contract.
            # Per-launch decode wall time (host clock around the whole
            # supervised launch — includes the fused paged-attention path).
            with LATENCY.span("engine.decode_launch"):
                return self.supervisor.supervised_launch(
                    lambda: self.engine.generate_many(
                        specs,
                        max_new_tokens=max_new,
                        temperature=temperature,
                        top_p=top_p,
                        eos_ids=eos_ids,
                        constraint=constraint,
                        top_logprobs=top_logprobs,
                        frequency_penalty=frequency_penalty,
                        presence_penalty=presence_penalty,
                        logit_bias=logit_bias,
                        stop_sequences=stop_sequences,
                    ),
                    rows=launch_rows,
                    max_new_tokens=max_new,
                )

        # max_rows = the HBM memory model's row cap for THIS request's KV
        # length — any group this item joins is clipped to the tightest
        # member hint.
        if (
            getattr(self.engine, "kv_layout", "dense") == "paged"
            and getattr(self.engine, "paged_generate_many", False)
            and self.backend_config.speculative is None
            and not self.backend_config.sp_decode
        ):
            # Coalesced batches decode paged (engine._generate_many_paged):
            # a request's n rows share its prompt pages, so the admission cap
            # is the paged per-group reserve, not the dense n-dense-copies
            # bound — shared-prefix fan-outs coalesce ~n x wider at equal HBM.
            max_rows = self.memory_model.paged_max_rows(
                len(prompt_ids), max_new, self.engine.kv_page_size,
                fanout=max(1, n),
            )
        else:
            max_rows = self.memory_model.max_rows(len(prompt_ids) + max_new)
        result = self.scheduler.call_batched(
            batch_key,
            GenRequestSpec(list(prompt_ids), n, seed, budget, token_sink),
            run,
            weight=rows,
            budget=budget,
            max_rows=max_rows,
            tenant=tenant_ctx,
        )
        if loop_grammar is not None:
            # Every generated token on this path sampled under the fused
            # mask; counted host-side after the fact (never in the loop).
            from ..utils.observability import GRAMMAR_EVENTS

            GRAMMAR_EVENTS.record(
                "grammar.masked_steps", int(np.sum(result.lengths))
            )
        return result

    def _constraint_for(self, response_format: Any):
        if response_format is None:
            return None
        schema = None
        wants_json = False
        if isinstance(response_format, type) and hasattr(response_format, "model_json_schema"):
            schema = response_format.model_json_schema()
        elif isinstance(response_format, dict):
            kind = response_format.get("type")
            if kind == "json_object":
                wants_json = True
            elif kind == "json_schema":
                # OpenAI wire form: {"type": "json_schema", "json_schema": {"schema": ...}}
                schema = (response_format.get("json_schema") or {}).get("schema")
                wants_json = True  # schema-less json_schema payload degrades to JSON mask
        if schema is None and not wants_json:
            # {"type": "text"} and unrecognized forms are unconstrained — only
            # an explicit JSON request earns the grammar mask.
            return None
        if not self.backend_config.constrained_decoding:
            # Post-hoc-only posture: decode unconstrained, parse() validates
            # after the fact (the pre-PR-12 behavior, byte-identical output).
            return None
        # Compile-or-fetch through the process-wide grammar cache: keyed by
        # (schema digest, vocab digest), so every ReplicaSet member — and
        # every concurrent request — shares one compile per schema per
        # tokenizer. Never raises; None = unconstrained + post-hoc validation
        # (failpoint/compile error, counted in GRAMMAR_EVENTS).
        from ..engine.grammar import grammar_for_schema

        vocab, vocab_digest = self._grammar_vocab()
        return grammar_for_schema(schema, vocab, vocab_digest=vocab_digest)

    def _grammar_vocab(self):
        """(per-token byte strings, digest) for this backend's tokenizer —
        computed once; the digest is the fleet-wide grammar-cache key half."""
        if getattr(self, "_grammar_vocab_cache", None) is None:
            from ..engine.grammar import grammar_vocab
            from ..engine.token_constraint import _vocab_digest

            vocab = grammar_vocab(self.tokenizer)
            self._grammar_vocab_cache = (vocab, _vocab_digest(vocab))
        return self._grammar_vocab_cache

    # -- embeddings -------------------------------------------------------
    def embeddings(self, texts: List[str]) -> List[List[float]]:
        token_lists = [
            self.tokenizer.encode(t)[:MAX_EMBEDDING_TOKENS] for t in texts
        ]

        def run(payloads):
            # Concurrent requests' embedding batches coalesce into one forward.
            flat = [tl for p in payloads for tl in p]
            # One forward, no decode loop: supervise it as a 1-token launch so
            # a wedged embedding launch heals like a wedged decode does.
            pooled = self.supervisor.supervised_launch(
                lambda: self.engine.embed_tokens(flat),
                rows=max(1, len(flat)),
                max_new_tokens=1,
            )
            out, i = [], 0
            for p in payloads:
                out.append(pooled[i : i + len(p)])
                i += len(p)
            return out

        # window=0: opportunistic coalescing only. An embedding forward takes
        # a few ms, so the scheduler's default 5 ms decode-admission window
        # would be a large relative latency cost here.
        pooled = self.scheduler.call_batched(
            ("embed",), token_lists, run, weight=max(1, len(token_lists)),
            window=0.0, trace_phase="embed",
        )
        return [[float(x) for x in row] for row in pooled]

    def crop_texts(
        self, texts: List[str], max_tokens: int, model: Optional[str] = None
    ) -> List[str]:
        # Real token-level crop per the Backend contract. embeddings() slices
        # at MAX_EMBEDDING_TOKENS anyway (its own callers pass raw strings), so
        # already-cropped client inputs just pass through the slice unchanged.
        # Fast path bound is the UTF-8 BYTE count: tokenizers here emit at most
        # one token per byte (byte tokenizer exactly; BPE merges) PLUS up to one
        # dummy-prefix token for SentencePiece, so byte-length < cap guarantees
        # token-length <= cap. Character count would not ("é"*100 is 100 chars
        # but 200 byte-tokens).
        tok = self.tokenizer
        return [
            t
            if len(t.encode("utf-8")) < max_tokens
            else tok.decode(tok.encode(t)[:max_tokens])
            for t in texts
        ]

    # -- lifecycle --------------------------------------------------------
    def health(self) -> Dict[str, Any]:
        """Serving-health snapshot: scheduler lifecycle state + queue/shed
        counters, breaker state, engine OOM stats, and the memory model's
        planning view. Cheap — no device work."""
        snap = self.scheduler.health()
        snap["device"] = self._device_facts()
        snap["breaker"] = self.circuit_breaker.state
        snap["engine_oom"] = dict(self.engine.oom_stats)
        snap["memory_model"] = self.memory_model.describe()
        snap["supervisor"] = self.supervisor.stats()
        snap["quarantine"] = dict(
            getattr(self.engine, "quarantine_stats", None) or {}
        )
        # Loader's param summary (total bytes, dtype histogram, checksum) —
        # None when the engine runs on seeded params rather than a checkpoint.
        snap["params"] = self.param_summary
        if self._continuous is not None:
            snap["continuous"] = dict(self._continuous.stats)
        # HBM accounting: params + per-token KV, and — when the engine runs
        # the paged layout — the live page-pool occupancy (reading the pool
        # stats through the loop's stats property also re-checks the page
        # conservation invariants).
        paged = getattr(self.engine, "kv_layout", "dense") == "paged"
        hbm: Dict[str, Any] = {
            "param_bytes": self.memory_model.param_bytes,
            # What a token holds where this engine keeps it: a pool row, or a
            # dense cache's (they differ for a latent model's padded pool row).
            "kv_bytes_per_token": (
                self.memory_model.kv_bytes_per_token if paged
                else self.memory_model.dense_kv_bytes_per_token
            ),
            "budget_bytes": self.memory_model.budget_bytes(),
            "paged": paged,
            "page_size": getattr(self.engine, "kv_page_size", None),
        }
        if self._continuous is not None:
            # The rows' recurrent state beside the pool (0 for most models).
            hbm["state_bytes"] = snap["continuous"]["state_bytes"]
        pool = getattr(self.engine, "_kv_pool", None)
        if pool is not None:
            hbm["page_pool"] = pool.allocator.snapshot()
            hbm["page_pool_bytes"] = pool.pool_bytes()
        snap["hbm"] = hbm
        snap["consensus"] = self._consensus_stats()
        # Constrained decoding: posture flag + the process-wide compile-cache
        # counters (merged into the scheduler's "grammar" events section when
        # present — same key, complementary views).
        from ..engine.grammar import grammar_cache_stats

        grammar = snap.setdefault("grammar", {})
        grammar["enabled"] = bool(self.backend_config.constrained_decoding)
        grammar["cache"] = grammar_cache_stats()
        return snap

    def _device_facts(self) -> Dict[str, Any]:
        """What this process runs on, as JAX and the engine report it — so a
        parent that never imports JAX can check the platform, the mesh and
        which attention implementations the configured names resolved to."""
        import jax

        from ..native import native_status
        from ..ops.attention import resolve_attention_impl
        from ..ops.paged_attention import resolve_paged_attention_impl
        from ..utils.compile_cache import compile_stats

        devices = jax.devices()
        engine = self.engine
        config = engine.config
        mesh = engine.mesh
        return {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "mesh": dict(mesh.shape) if mesh is not None else None,
            "model": config.name,
            "num_layers": config.num_layers,
            "quantization": engine.quantized or None,
            "attention": {
                "prefill": resolve_attention_impl(config.attention_impl),
                "decode": resolve_attention_impl(config.decode_attention_impl),
                "paged": (
                    resolve_paged_attention_impl(
                        engine.paged_attention_impl, config=config, record=False
                    )
                    if engine.kv_layout == "paged"
                    else None
                ),
            },
            "bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use") for d in devices
            ],
            "native": native_status(),
            "compile": compile_stats(),
        }

    # -- on-device consensus ----------------------------------------------
    def similarity_scorer(self, method: str):
        """Per-method scorer registry, like the base, but constructing the
        device-kernel scorer when ``device_consensus`` is on. Falls back to
        the plain host scorer at construction time when JAX/devices are
        unavailable (run-time fallback is per-consolidation, inside the
        device scorer itself)."""
        if not self.backend_config.device_consensus:
            return super().similarity_scorer(method)
        from ..consensus.device import DeviceConsensusUnavailable, DeviceSimilarityScorer
        from ..consensus.similarity import SimilarityScorer
        from ..utils.observability import CONSENSUS_EVENTS

        with Backend._scorer_registry_lock:
            registry = self.__dict__.setdefault("_similarity_scorers", {})
            scorer = registry.get(method)
            if scorer is None:
                try:
                    scorer = DeviceSimilarityScorer(method=method, embed_fn=self.embeddings)
                except DeviceConsensusUnavailable:
                    CONSENSUS_EVENTS.record("consensus.fallback_unavailable")
                    scorer = SimilarityScorer(method=method, embed_fn=self.embeddings)
                registry[method] = scorer
            return scorer

    def _consensus_stats(self) -> Dict[str, Any]:
        """Cache totals + per-scorer breakdown + dispatch counters, surfaced
        in scheduler stats/health and as kllms_consensus_* gauges."""
        from ..utils.observability import CONSENSUS_EVENTS

        agg = {"hits": 0, "misses": 0, "entries": 0, "evictions": 0}
        caches: Dict[str, Any] = {}
        with Backend._scorer_registry_lock:
            scorers = dict(self.__dict__.get("_similarity_scorers") or {})
        for method, scorer in scorers.items():
            stats = scorer.cache_stats()
            caches[method] = stats
            for s in stats.values():
                for k in agg:
                    agg[k] += s.get(k, 0)
        return {
            "device_consensus": bool(self.backend_config.device_consensus),
            "cache": agg,
            "caches": caches,
            "events": {
                k: v
                for k, v in CONSENSUS_EVENTS.snapshot().items()
                if k.startswith("consensus.")
            },
        }

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: close admission (new requests get a typed 503),
        finish queued + in-flight groups, join the scheduler worker. Returns
        True when everything completed within ``timeout`` (default:
        ``BackendConfig.drain_timeout``). Idempotent."""
        self._closed = True
        t = self.backend_config.drain_timeout if timeout is None else timeout
        ok = True
        if self._continuous is not None:
            # Quiesce the slot loop first: its admission gate follows the
            # scheduler lifecycle, but in-flight slot rows finish on their own
            # worker, not the scheduler's.
            ok = self._continuous.drain(timeout=t)
        return self.scheduler.drain(timeout=t) and ok

    def close(self) -> None:
        if self._closed and self.scheduler.state.value == "stopped":
            return
        self.drain()
        if self._continuous is not None:
            self._continuous.stop()

    # -- llm-consensus ----------------------------------------------------
    def llm_consensus(self, values: List[str]) -> str:
        assert len(values) > 0, "Cannot build consensus string from empty list"
        import json

        messages = [
            {"role": "system", "content": SYSTEM_PROMPT_STRING_CONSENSUS_LLM},
            {"role": "user", "content": f"Input: {[json.dumps(v) for v in values]}\nOutput:"},
        ]
        ids = self.tokenizer.apply_chat_template(messages, add_generation_prompt=True)
        # Batched like user requests: llm-consensus calls issued by concurrent
        # consolidations coalesce into one greedy decode.
        result = self._generate_batched(
            ids, n=1, max_new=128, temperature=0.0, top_p=None, seed=None, constraint=None
        )
        text = self.tokenizer.decode(
            [int(t) for t in result.tokens[0][: int(result.lengths[0])]]
        ).strip()
        return text if text else values[0]
