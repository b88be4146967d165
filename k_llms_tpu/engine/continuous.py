"""Continuous (in-flight) batching: a persistent decode loop with slot admission.

The coalescing scheduler (scheduler.py) batches requests that arrive inside an
admission window and decodes the group to completion — late arrivals wait for
the whole group to finish. This module is the Orca/vLLM-style alternative the
serving path needs for streaming: a fixed-width decode batch of W slots that
steps forever, where a request's n sample rows JOIN the batch the step after
admission and LEAVE the moment they finish, freeing their slots for queued
work. A late-arriving request therefore starts decoding mid-flight of earlier
requests instead of behind them.

Design:

- Device state follows the engine's KV layout: the engine's page pool,
  addressed through per-slot block tables (``engine/paging.py::SlotPages``:
  what every benchmarked cell runs), or dense per-slot caches
  (``_DenseSlots``). ONE step body (``_build_step``) advances all W slots
  regardless of which request each row belongs to; its per-row ``lengths``
  write offsets are exactly the mid-flight join primitive.
- A step's host inputs are ONE array (``_pack_step`` / ``_unpack_step``): a
  snapshot of the slot mirrors, a column each, then for a paged loop the
  rows' write slots and block tables, which the program spreads into gather
  indices itself (``paging.expand_tables``). The host mirrors stay the only
  truth; admission, retirement, quarantine and replay write them alone.
- Sampling is a per-ROW array sampler (temperature[W] / top_p[W]) so requests
  with different sampling configs share the batch — the coalescing scheduler's
  batch_key compatibility restriction disappears. temperature 0 is greedy per
  row; reported logprobs are the untempered model distribution's, matching
  ``ops/sampling.sample_logits``, and each row's nucleus is cut by the same
  search as there (``ops/sampling.nucleus_threshold``: a fixed 32 masked
  reductions over [W, V], no sort of the vocabulary). Row keys derive from
  ``fold_in(fold_in(key(seed), step), sample_idx)`` — self-deterministic (same
  seed → same tokens) regardless of batch composition, like the batch loop.
- The host drives the loop: eos / per-request max_new retirement, budget
  aborts (``engine.decode_abort``, same counter as the batch path), admission
  (FIFO, a request needs all n slots at once), and per-step token delivery to
  streaming sinks run between device steps. One step's host work is O(W).
- Reliability: admission evaluates the ``engine.launch`` failpoint (an ``oom``
  spec surfaces as a typed 503 — there is no split-and-requeue here, the width
  is fixed), spent budgets shed before device work, and the backend's
  DRAINING/STOPPED lifecycle gates admission via
  ``EngineScheduler.admission_error``.

Drafting. For a model with a next-token module (``ModelConfig.
num_nextn_predict_layers``: the preset decides, no option here) the paged
loop's step is the DRAFTED step (``_build_drafted_step``): a row carries
``cur`` at position P and the module's draft ``d`` for P+1, the stack verifies
both positions in one pass, ``t1`` and ``t2`` are sampled with the keys and
masks the one-token loop would use at those positions, and the row emits
``t1``, and ``t2`` too iff ``t1 == d`` (sample-and-match: every emitted token
is the draw the undrafted loop would have made). The module then runs on
``(h_P, t1)`` and ``(h_{P+1}, t2)`` and its masked argmax at the last emitted
position is the next draft. A rejected draft's cache rows are overwritten by
the next step before anything reads them; admission leaves each row its first
token and its first draft (``_admit_drafts``).

Requests that need top_logprobs, penalties, or logit_bias stay on the
coalescing path (TpuBackend routes; see ``_generate_batched``) — those
features key the compiled program, which would fragment the shared loop.
Grammar-constrained requests (ISSUE 12) DO ride the loop: the resident
:class:`CompiledGrammar`'s tables are *arguments* to grammar-twin step
programs (state axis padded to a power of two by ``device_grammar``), so one
XLA program serves every schema over the same tokenizer; per-row state/flag
vectors gate the fused mask + advance, rows without a grammar sample
byte-identically (and steps with no constrained row run the original
programs untouched), and a request under a *different* schema than the
resident one falls back to coalescing instead of fragmenting the loop.
"""

from __future__ import annotations

import logging
import queue as _queue_mod
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import (
    Any, Callable, Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np
from concurrent.futures import Future

from ..analysis.lockcheck import make_condition, note_device_dispatch, race_exempt
from ..models.llama import (
    KVCache,
    init_cache,
    init_state,
    paged_draft_step,
    paged_verify_step,
    verify_step,
)
from ..ops.paged_attention import note_paged_attn_dispatch
from ..ops.sampling import nucleus_threshold
from ..reliability import failpoints as _failpoints
from ..reliability.deadline import RequestBudget
from ..types.wire import (
    BackendUnavailableError,
    CheckpointCorruptError,
    EngineHungError,
    ServerDrainingError,
)
from ..utils.compile_cache import CompileTracker, wait_excluding_compile
from ..utils.observability import (
    FAILURE_EVENTS,
    GRAMMAR_EVENTS,
    LATENCY,
    PAGED_ATTN_PAGES,
    RECOVERY_EVENTS,
    SPEC_COUNTERS,
    current_trace,
    note_model_aux,
)
from .engine import (
    GenerationResult,
    _poisoned_logits,
    _quarantine_error,
    is_resource_exhausted,
)
from .paging import (
    PageAccountingError,
    PagePoolExhausted,
    SlotPages,
    expand_tables,
    scatter_rows,
    write_drafted_rows,
)

logger = logging.getLogger(__name__)

#: Admission's fork of a prompt's final recurrent state into a request's rows:
#: (the loop's state [W, ...] a leaf, the lane's [1, ...], rows [W] int32
#: padded with W: an index past the end is dropped) -> the loop's state.
_install_rows = jax.jit(
    lambda state, lane, rows: jax.tree.map(
        lambda s, l: s.at[rows].set(l.astype(s.dtype), mode="drop"), state, lane
    ),
    donate_argnums=(0,),
)


class _StepRows(NamedTuple):
    """What a decode step's one host array holds, unpacked: the slot mirrors
    ``[W]`` as the programs took them one by one before, then ``write_idx``
    ``[W, 1 + lookahead]`` and ``tables`` ``[W, T]`` (both zero columns wide in
    a dense loop). ``draft`` and ``room`` are None unless the loop drafts."""

    cur: Any
    gen_lens: Any
    prompt_lens: Any
    active: Any
    seeds: Any
    sample_idx: Any
    temps: Any
    top_ps: Any
    g_states: Any
    g_flags: Any
    draft: Any
    room: Any
    write_idx: Any
    tables: Any


def _unpack_step(packed, drafting: bool, writes: int) -> _StepRows:
    """The columns of ``ContinuousDecodeLoop._pack_step``'s ``int32 [W, C]``
    array, by static slices (traceable): uint32 and float32 mirrors come back
    by their bits, flags as ``!= 0``, so every value is the mirror's own."""
    def bits(column, dtype):
        return jax.lax.bitcast_convert_type(packed[:, column], dtype)

    named = 12 if drafting else 10
    return _StepRows(
        cur=packed[:, 0], gen_lens=packed[:, 1], prompt_lens=packed[:, 2],
        active=packed[:, 3] != 0, seeds=bits(4, jnp.uint32), sample_idx=packed[:, 5],
        temps=bits(6, jnp.float32), top_ps=bits(7, jnp.float32),
        g_states=packed[:, 8], g_flags=packed[:, 9] != 0,
        draft=packed[:, 10] if drafting else None,
        room=packed[:, 11] != 0 if drafting else None,
        write_idx=packed[:, named:named + writes], tables=packed[:, named + writes:],
    )


@dataclass
class _SlotRequest:
    """Host-side record of one admitted request and its slot rows.

    The journal fields (``ids`` / ``seed`` / ``temperature`` / ``top_p``,
    plus ``grammar``) are everything recovery needs to re-admit the request
    after an engine rebuild: row keys derive only from (seed, step,
    sample_idx), so replaying from the original prompt regenerates the same
    token stream byte-for-byte — ``delivered_watermark`` then suppresses the
    already-delivered prefix so streaming sinks see contiguous bytes exactly
    once."""

    future: Future
    prompt_len: int
    n: int
    max_new: int
    budget: Optional[RequestBudget]
    token_sink: Optional[Callable[[int, np.ndarray], None]]
    # Replay journal: the canonical prompt tokens and admission-pinned
    # sampling parameters, recorded at submit before any device work.
    ids: List[int]
    seed: int
    temperature: float
    top_p: float
    seq: int
    # CompiledGrammar when the request decodes under a schema mask; the loop
    # holds ONE resident grammar's tables on device, so a different-digest
    # request is rejected at submit (the backend reroutes it to coalescing).
    grammar: Optional[Any] = None
    slots: List[int] = field(default_factory=list)
    # Per-sample accumulators, index-aligned with ``slots``.
    tokens: List[List[int]] = field(default_factory=list)
    logprobs: List[List[float]] = field(default_factory=list)
    done: List[bool] = field(default_factory=list)
    finish: List[str] = field(default_factory=list)
    sample_errors: List[Optional[Dict[str, Any]]] = field(default_factory=list)
    steps_delivered: int = 0
    # Sink steps already delivered before the last fault: replayed steps
    # below this watermark are regenerated (the device needs them) but NOT
    # re-delivered.
    delivered_watermark: int = 0
    replays: int = 0
    # Chunked-prefill cursor (journal observability): how many prompt tokens
    # the PREFILLING phase has ingested so far. Replay after a rebuild resets
    # it to 0 and re-prefills from scratch — the staging KV dies with the
    # torn-down engine, and deterministic prefill + the submission-pinned
    # seed make the replayed output byte-identical anyway.
    chunk_cursor: int = 0
    # Request trace captured on the SUBMITTING thread (the loop worker does
    # not inherit contextvars), plus the enqueue timestamp for the
    # queue-wait span/histogram. Both are host-side observability only.
    trace: Optional[Any] = None
    enqueued_at: float = 0.0
    # Host clock at the request's last dequeue and at the installation of its
    # rows: the ends of its ``prefill_wall`` and ``decode_wall`` phases.
    dequeued_at: float = 0.0
    installed_at: float = 0.0
    # Resolved TenantContext (or None for the implicit default tenant):
    # drives WFQ slot selection and per-tenant queue-wait attribution.
    tenant: Optional[Any] = None


@dataclass(eq=False)
class _Prefilling:
    """The loop's single PREFILLING admission: a request whose prompt is
    being ingested chunk by chunk between decode steps instead of in one
    blocking prefill. Owns its slot rows (popped from ``_free`` but NOT in
    ``_active`` — the decode step must never see a half-prefilled row), the
    1-row staging KV the chunks extend with the lane's recurrent state
    (``state``: one row's, ``{}`` for a model without any), and, in paged mode,
    the prompt page run (n row references) plus each row's pre-reserved
    generation pages.
    All fields are guarded by the loop lock; the dispatch closure only reads
    snapshots taken under it."""

    req: "_SlotRequest"
    rows: List[int]
    ids: List[int]
    cache: Any
    plen: int
    bucket: int
    run_pages: Optional[List[int]]
    reserved: List[List[int]]
    state: Dict[str, Any]
    cursor: int = 0


def pick_chunk(rungs: Sequence[int], remainder: int, room: int) -> int:
    """The length of one PREFILLING lane turn: of the compiled chunk lengths
    ``rungs`` (ascending, each double the last) that fit the staging cache's
    ``room`` past the cursor, the longest while ``remainder`` (the prompt
    tokens still to ingest) is at least that long, else the shortest that
    covers it (the prompt's last turn). Cursors are therefore multiples of
    the longest rung until the last turn, and a power-of-two bucket past the
    prompt always has room for the rung chosen; should none fit (a bucket cut
    to a ``max_seq_len`` that is no multiple of the shortest), the shortest
    runs as a single length always has."""
    fits = [c for c in rungs if c <= room] or list(rungs[:1])
    if remainder >= fits[-1]:
        return fits[-1]
    return next(c for c in fits if c >= remainder)


def bucket_rungs(rungs: Sequence[int], bucket: int) -> Tuple[int, ...]:
    """The chunk lengths :func:`pick_chunk` can choose for the prompts of one
    staging ``bucket`` (those longer than the shortest rung and than the
    bucket below this one), by walking the rule itself over them: what the
    bucket's first chunk compiles, so that no later prompt of the bucket, with
    whatever tail, meets a length that is not built."""
    if len(rungs) < 2:
        return tuple(rungs)
    below = 1 << ((bucket - 1).bit_length() - 1)
    used: set = set()
    for plen in range(max(rungs[0], below) + 1, bucket + 1):
        cursor = 0
        while cursor < plen:
            c = pick_chunk(rungs, plen - cursor, bucket - cursor)
            used.add(c)
            cursor += c
        if len(used) == len(rungs):
            break
    return tuple(sorted(used))


def _req_tenant_name(req: "_SlotRequest") -> str:
    return req.tenant.name if req.tenant is not None else "default"


def _req_interactive(req: "_SlotRequest") -> bool:
    return req.tenant is None or req.tenant.interactive


def _req_tenant_weight(req: "_SlotRequest") -> float:
    return max(req.tenant.weight, 1e-9) if req.tenant is not None else 1.0


class _DenseSlots:
    """The dense layout's slot KV: a per-slot prompt prefix ``[L, W, P, kvh,
    d]`` and a per-slot generation cache ``[L, W, G, kvh, d]``. No cell runs
    it; it is the reference the paged differentials compare against. Freed
    slots need no clearing: the self-attention mask only exposes positions
    ``<= lengths``, and a new occupant's first step overwrites offset 0
    before attending it."""

    def __init__(self, config: Any, width: int, max_prompt: int, max_new: int) -> None:
        self.max_prompt = max_prompt
        self.prefix = init_cache(config, width, max_prompt)
        self.gen = init_cache(config, width, max_new)

        def _write_prefix(prefix, new_k, new_v, rows):
            k = prefix.k.at[:, rows].set(new_k)
            v = prefix.v.at[:, rows].set(new_v)
            return KVCache(k=k, v=v)

        self._write_prefix_fn = jax.jit(_write_prefix, donate_argnums=(0,))

    def install(self, rows: List[int], cache: KVCache, bucket: int) -> None:
        """Admission: replicate one request's prefill KV ``[L, 1, bucket,
        ...]`` into its n slots."""
        pk, pv = cache.k, cache.v
        n = len(rows)
        if bucket < self.max_prompt:
            pad = [(0, 0)] * 5
            pad[2] = (0, self.max_prompt - bucket)
            pk, pv = jnp.pad(pk, pad), jnp.pad(pv, pad)
        elif bucket > self.max_prompt:
            # A bucket is a power of two and max_prompt need not be one: the
            # prompt fits the slots, what lies past them is the bucket's padding.
            pk, pv = pk[:, :, :self.max_prompt], pv[:, :, :self.max_prompt]
        rows_arr = jnp.asarray(np.asarray(rows, np.int32))
        rep_k = jnp.broadcast_to(pk[:, 0:1], (pk.shape[0], n) + pk.shape[2:])
        rep_v = jnp.broadcast_to(pv[:, 0:1], (pv.shape[0], n) + pv.shape[2:])
        self.prefix = self._write_prefix_fn(self.prefix, rep_k, rep_v, rows_arr)


class _StepHung(RuntimeError):
    """Internal: a step dispatch overran its watchdog budget."""


class _StaleStep(RuntimeError):
    """Internal: an abandoned step thread woke into a newer loop epoch."""


class _PoolFault(RuntimeError):
    """Internal: page accounting failed; the pool must be quarantined."""


class _AdoptEngine(Exception):
    """Internal: an externally rebuilt engine is waiting to be adopted."""

    def __init__(self, engine: Any) -> None:
        super().__init__("adopt rebuilt engine")
        self.engine = engine


class _StepDispatcher:
    """Persistent dispatch thread the loop worker hands each device step to.

    The worker waits on the step's completion event under the watchdog
    budget (time the step spends compiling is not charged to it — the first
    dispatch of a program is a compile, not a hang); an overdue step is
    ABANDONED — its ticket is fenced, the inbox
    and thread are retired, and a fresh pair serves subsequent steps — so a
    wedged device dispatch blocks one disposable thread, never the loop.
    Hand-off uses a plain ``queue.Queue`` (no loop-ordered locks) and the
    thread is lazily (re)spawned, so the healthy path costs one put/get and
    one Event wait per step."""

    def __init__(self) -> None:
        self._inbox: "_queue_mod.Queue" = _queue_mod.Queue()
        self._thread: Optional[threading.Thread] = None

    def _ensure(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._serve,
                args=(self._inbox,),
                name="kllms-continuous-step",
                daemon=True,
            )
            self._thread.start()

    @staticmethod
    def _serve(inbox: "_queue_mod.Queue") -> None:
        while True:
            item = inbox.get()
            if item is None:
                return
            fn, ticket = item
            try:
                with ticket["tracker"].active():
                    ticket["started"] = time.perf_counter()
                    ticket["result"] = fn()
            except BaseException as exc:
                ticket["error"] = exc
            finally:
                ticket["ended"] = time.perf_counter()
                if ticket["abandoned"]:
                    RECOVERY_EVENTS.record("continuous.stale_steps_discarded")
                    logger.warning(
                        "discarding stale result from an abandoned "
                        "continuous step"
                    )
                ticket["done"].set()

    def run(self, fn: Callable[[], Any], budget_model: Any) -> Any:
        """Run ``fn`` on the dispatch thread under ``budget_model``'s step
        budget. Returns ``(result, run_seconds)`` — wall time with compile
        time taken out, the figure the budget model should learn from —
        re-raises its error, or raises :class:`_StepHung` after abandoning
        the thread. A step that came back observes ``continuous.handoff``: the
        host clock from the ``put`` to ``fn``'s first line on the step thread,
        plus that from ``fn``'s end to this thread running again."""
        self._ensure()
        budget_s = budget_model.step_budget()
        tracker = CompileTracker()
        ticket: Dict[str, Any] = {
            "done": threading.Event(),
            "result": None,
            "error": None,
            "abandoned": False,
            "tracker": tracker,
        }
        started = time.monotonic()
        put_at = time.perf_counter()
        self._inbox.put((fn, ticket))
        if wait_excluding_compile(
            ticket["done"], budget_s, tracker, budget_model.max_budget_s
        ):
            back_at = time.perf_counter()
            if ticket["error"] is not None:
                raise ticket["error"]
            LATENCY.observe(
                "continuous.handoff",
                (ticket["started"] - put_at) + (back_at - ticket["ended"]),
            )
            run_s = time.monotonic() - started - tracker.seconds()
            return ticket["result"], max(0.0, run_s)
        ticket["abandoned"] = True
        # Retire the inbox+thread pair: the sentinel makes the stale thread
        # exit once the hung dispatch finally returns, and the fresh pair
        # serves the rebuilt loop.
        self._inbox.put(None)
        self._inbox = _queue_mod.Queue()
        self._thread = None
        raise _StepHung(f"continuous step exceeded its {budget_s:.2f}s budget")

    def close(self) -> None:
        self._inbox.put(None)


class ContinuousDecodeLoop:
    """Persistent W-slot decode loop over one :class:`LocalEngine`.

    ``width`` is the slot count (the HBM-aware cap is the caller's job — the
    backend clamps it through its memory model); ``max_prompt`` / ``max_new``
    bound the per-slot prefix and generation KV (requests beyond either bound
    don't qualify and take the coalescing path).
    """

    def __init__(
        self,
        engine: Any,
        width: int,
        max_prompt: int,
        max_new: int,
        eos_ids: Optional[List[int]] = None,
        admission_gate: Optional[Callable[[], Optional[BaseException]]] = None,
        budget_model: Optional[Any] = None,
        rebuild_fn: Optional[Callable[[], Any]] = None,
        max_rebuilds: int = 2,
        on_recovering: Optional[Callable[[int, str], None]] = None,
        on_rebuilt: Optional[Callable[[], None]] = None,
        on_rebuild_failed: Optional[Callable[[BaseException], None]] = None,
        prefill_chunk_tokens: int = 0,
        prefill_chunk_ladder: Sequence[int] = (),
    ) -> None:
        # Only the worker swaps in an epoch-fenced replacement during
        # recovery; readers tolerate either generation, and admission
        # revalidates capacity under the loop lock before placement.
        # kllms: unguarded — single-writer epoch-fenced engine swap
        self.engine = engine
        # Runtime twin of the annotations in this __init__ plus the
        # qualifies() inline suppression: the lockset sanitizer skips what the
        # static rule skips. The device-state family (the layout's KV in
        # _dense or _pool, _step_fn, and the resolved _paged_attn_impl) is
        # handed to the disposable dispatch thread under the epoch fence
        # rather than the loop lock.
        race_exempt(
            self,
            "engine",
            "_loop_epoch",
            "_dense",
            "_step_fn",
            "_paged_attn_impl",
            "_pool",
            "_state",
            "_results_at",
            "_gap_device_s",
            "_host_annotation",
            "_host_opened_at",
            "_active_s",
        )
        self.width = int(width)
        self.max_prompt = int(max_prompt)
        self.max_new = int(max_new)
        # Chunked prefill (ISSUE 18): prompts longer than this many tokens
        # are ingested chunk by chunk between decode steps instead of one
        # blocking whole-prompt prefill. 0 = off (the whole-prompt path,
        # byte-identical by the differential in tests/test_chunked_prefill.py).
        # Normalized DOWN to a power of two >= 32: the prompt bucket is a
        # power of two >= any prompt that chunks (plen > C), so a pow2 C
        # always divides it and the paged chunk's fixed-width KV-column slice
        # (cursor + C <= bucket) can never clamp out of range.
        c = max(0, int(prefill_chunk_tokens))
        if 0 < c < 32:
            c = 32
        elif c > 32:
            c = 1 << (c.bit_length() - 1)
        self.prefill_chunk_tokens = c
        # The lengths a lane turn may take (:func:`pick_chunk` chooses one
        # each turn from what is left of the prompt): C alone, as an explicit
        # chunk size always is, or with ``prefill_chunk_ladder`` the longer
        # rungs beside it (2C, 4C: the automatic size's, from
        # ``HbmMemoryModel.prefill_chunk_ladder``). C stays the threshold of
        # the lane (``_chunk_eligible``) and what ``stats`` reports; the
        # invariant above holds for every rung, since a cursor is a multiple
        # of the longest until a prompt's last turn.
        rungs = tuple(sorted({c, *map(int, prefill_chunk_ladder)})) if c else ()
        if rungs and (rungs[0] != c or any(b != 2 * a for a, b in zip(rungs, rungs[1:]))):
            raise ValueError(
                f"prefill_chunk_ladder {tuple(prefill_chunk_ladder)} must double up from "
                f"prefill_chunk_tokens ({c})"
            )
        self._chunk_rungs: Tuple[int, ...] = rungs
        # Staging buckets whose rungs are all built (worker thread; emptied
        # with the device state, since a rebuilt engine has compiled nothing).
        self._chunk_built: set = set()
        # The single in-flight chunked admission (at most one PREFILLING
        # request at a time — one chunk rides alongside each decode step).
        self._prefilling: Optional[_Prefilling] = None
        self.eos_ids = list(eos_ids or [engine.config.eos_token_id])
        self._admission_gate = admission_gate
        # Self-healing wiring (all optional — a bare loop without a budget
        # model dispatches steps inline with no watchdog, byte-identically to
        # the unsupervised loop). ``budget_model`` is the loop's OWN
        # LaunchBudgetModel: its per-step EWMA must not pollute the coalesced
        # path's per-launch timings. ``rebuild_fn`` rebuilds and returns a
        # fresh engine after a hung step or a quarantined page pool.
        self.budget_model = budget_model
        self.rebuild_fn = rebuild_fn
        self.max_rebuilds = int(max_rebuilds)
        self.on_recovering = on_recovering
        self.on_rebuilt = on_rebuilt
        self.on_rebuild_failed = on_rebuild_failed
        self._dispatcher = _StepDispatcher()
        # Host clock at which the last device program's results reached the
        # host, for ``continuous.gap``: written where the readback ends (the
        # step thread under a watchdog), taken by the next program's first
        # line, cleared by the worker where the loop pauses.
        # kllms: unguarded — the hand-off orders the step thread's write before the worker's accesses
        self._results_at: Optional[float] = None
        # ``continuous.admit_device`` seconds since ``_results_at`` was last
        # taken: what ``continuous.wait`` leaves out of the gap they fell in.
        # kllms: unguarded — worker thread between hand-offs, the step thread inside one; the hand-off orders them
        self._gap_device_s = 0.0
        # kllms: unguarded — the open ``continuous.host`` annotation; worker thread only
        self._host_annotation: Optional[Any] = None
        # kllms: unguarded — host clock at which it was opened; worker thread only
        self._host_opened_at = 0.0
        # The worker's wall clock outside idle waits and recoveries, seconds:
        # the ``continuous.host`` intervals and the hand-offs, summed as each
        # ends (``stats["active_seconds"]``).
        # kllms: unguarded — written by the worker thread alone; a reader may see the sum one interval late
        self._active_s = 0.0
        # Epoch fence: bumped on every recovery; an abandoned step thread
        # waking into a newer epoch discards its work instead of committing
        # device state that belongs to a torn-down engine.
        # kllms: unguarded — monotonic fence value; stale reads abort via _StaleStep
        self._loop_epoch = 0
        self._consecutive_faults = 0
        self._last_recovery_reason: Optional[str] = None
        self._terminal_error: Optional[BaseException] = None
        self._pool_fault: Optional[str] = None
        self._adopted_engine: Optional[Any] = None
        self._seq = 0
        # The loop Condition is held across admission prefill and the step
        # dispatch on purpose: one decode thread owns the device, and slot
        # state must mutate atomically with the arrays it indexes.
        self._lock = make_condition("engine.continuous", allow_dispatch=True)
        self._queue: "deque[_SlotRequest]" = deque()
        # WFQ slot admission (ISSUE 16): loop-local per-tenant virtual time
        # and its floor, guarded by the loop lock. The queue stays a single
        # deque (journal replay depends on appendleft/extendleft positions);
        # fairness comes from *selection* — _admit_locked picks the earliest
        # request of the tenant with the smallest (slo_class, vtime) key.
        self._vtimes: Dict[str, float] = {}
        self._vfloor = 0.0
        self._active: List[Optional[_SlotRequest]] = [None] * self.width
        self._free: List[int] = list(range(self.width))
        self._closing = False
        self._stopped = False
        # Host mirrors of per-slot device state.
        self._cur = np.full((self.width,), engine.config.pad_token_id, np.int32)
        self._gen_lens = np.zeros((self.width,), np.int32)
        self._prompt_lens = np.ones((self.width,), np.int32)
        self._seeds = np.zeros((self.width,), np.uint32)
        self._sample_idx = np.zeros((self.width,), np.int32)
        self._temps = np.ones((self.width,), np.float32)
        self._top_ps = np.ones((self.width,), np.float32)
        self._active_mask = np.zeros((self.width,), bool)
        # The drafted loop (see the module docstring): each row's draft for the
        # position after ``_cur``'s, and its request's token limit.
        self._drafting = bool(getattr(engine.config, "num_nextn_predict_layers", 0))
        self._draft = np.full((self.width,), engine.config.pad_token_id, np.int32)
        self._max_news = np.zeros((self.width,), np.int32)
        # Grammar-constrained rows: per-slot automaton state + flag mirrors,
        # the resident CompiledGrammar (one schema's tables live on device at
        # a time; same-digest requests share them, different-digest requests
        # fall back to coalescing), and the memoized jitted grammar twins of
        # the admit/step programs (tables are arguments — swapping schemas of
        # the same padded shape reuses the compiled programs).
        self._g_states = np.zeros((self.width,), np.int32)
        self._g_flags = np.zeros((self.width,), bool)
        self._grammar: Optional[Any] = None
        self._dgrammar: Optional[Any] = None
        self._g_programs: Optional[tuple] = None
        # Device state, built lazily on first admission (compile + HBM cost
        # only when the feature is actually used). The worker thread mutates
        # it between steps; the disposable dispatch thread reads it (and
        # commits the step's new KV) mid-step with no lock held — the epoch
        # fence, not the loop lock, keeps abandoned threads from clobbering a
        # rebuilt loop.
        # kllms: unguarded — epoch-fenced handoff to the step dispatch thread
        self._step_fn = None
        self._admit_sample_fn = None
        self._admit_draft_fn = None
        self._built = False
        # The loop follows the engine's KV layout, and this is where the two
        # part. PAGED: the engine's page pool holds the KV and ``_pages``
        # keeps each slot's block table, reserve and index mirrors (the books
        # exist from here on, for qualifies(); the pool at the first
        # admission). DENSE: per-slot caches of the loop's own.
        self.paged = getattr(engine, "kv_layout", "dense") == "paged"
        self._pages: Optional[SlotPages] = None
        # kllms: unguarded — epoch-fenced handoff to the step dispatch thread
        self._pool = None
        self._paged_attn_impl = "xla"
        # kllms: unguarded — epoch-fenced handoff to the step dispatch thread
        self._dense: Optional[_DenseSlots] = None
        # The rows' recurrent state beside their pages (models/hybrid.py):
        # ``{name: (an array [W, ...] a state layer, ...)}``, passed to and returned from the
        # step program and donated like the pool's buffers; admission writes a
        # request's rows (``_install_state``), a release writes nothing. ``{}``
        # for a model without such state: no operand, no program.
        # kllms: unguarded — epoch-fenced handoff to the step dispatch thread
        self._state: Dict[str, Any] = {}
        if self.paged:
            pool = getattr(engine, "_kv_pool", None)
            self._pages = SlotPages(
                engine.kv_page_size, self.width, self.max_prompt, self.max_new,
                pool_pages=(
                    pool.allocator.total_pages if pool is not None
                    else engine.kv_pool_pages
                ),
                # A drafted step writes its draft's row and the module's one
                # position further.
                lookahead=2 if self._drafting else 0,
            )
        # Stats (reported via backend health() and the bench workload).
        self._stats: Dict[str, Any] = {
            "steps": 0,
            "row_steps": 0,
            # Host -> device arrays, and their bytes, that the decode steps'
            # stages sent (one packed array a step; the ``engine.logits``
            # failpoint's mask is a second).
            "stage_uploads": 0,
            "stage_bytes": 0,
            "admitted": 0,
            "joined_in_flight": 0,
            "completed": 0,
            "aborted": 0,
            "max_active_rows": 0,
            "restarts": 0,
            "replayed_rows": 0,
            "quarantined_rows": 0,
            # Chunked prefill: total chunks run, and how many of them ran
            # with decode rows in flight (the interleaving the feature buys).
            "prefill_chunks": 0,
            "prefill_interleaved": 0,
            # The prompt tokens those chunks ingested (a chunk's ``valid``
            # ones, its padding left out): over ``prefill_chunks``, how long
            # a lane turn was.
            "prefill_tokens": 0,
            # Times _admit_locked left a non-empty queue's head behind, by
            # what it lacked: free slots, the one PREFILLING lane, pool pages.
            "blocked_slots": 0,
            "blocked_lane": 0,
            "blocked_pages": 0,
            # What the rows' recurrent state holds on the device (0 until the
            # loop is built, and for a model without such state).
            "state_bytes": 0,
        }
        self._thread: Optional[threading.Thread] = None

    @property
    def stats(self) -> Dict[str, Any]:
        """Loop counters — and, in paged mode, the page-pool snapshot behind a
        conservation-invariant check (:meth:`PageAllocator.verify`): every
        ``health()`` read doubles as a fail-fast page-accounting audit. A
        failed audit no longer poisons every subsequent poll: the pool is
        QUARANTINED (flagged for the worker, which rebuilds the engine and
        replays the journal) and the fault is reported as data instead of an
        exception."""
        with self._lock:
            out = dict(self._stats)
            out["width"] = self.width
            out["prefill_chunk_tokens"] = self.prefill_chunk_tokens
            out["free_slots"] = len(self._free)
            active_rows = int(self._active_mask.sum())
            out["active_rows"] = active_rows
            out["occupancy"] = active_rows / self.width if self.width else 0.0
            out["queue_depth"] = len(self._queue)
            out["active_seconds"] = self._active_s
            out["last_recovery_reason"] = self._last_recovery_reason
            if self._pool is not None:  # a paged loop, once built
                if self._pool_fault is None:
                    fault = self._pool.allocator.check()
                    if fault is None:
                        out["pages"] = {
                            **self._pool.allocator.snapshot(),
                            "loop_refs": self._pages.held(),
                        }
                    else:
                        self._quarantine_pool_locked(fault)
                if self._pool_fault is not None:
                    out["pages"] = {
                        "quarantined": True,
                        "error": self._pool_fault,
                    }
        return out

    def _quarantine_pool_locked(self, fault: str) -> None:
        """Flag a page-accounting fault for the worker (lock held). The next
        worker iteration tears the pool down with the engine and replays the
        journal instead of letting every health poll keep tripping over the
        same corrupted allocator."""
        if self._pool_fault is not None:
            return
        self._pool_fault = fault
        RECOVERY_EVENTS.record("continuous.pool_quarantined")
        logger.error("continuous loop page pool quarantined: %s", fault)
        if not self._stopped:
            self._ensure_worker()
        self._lock.notify_all()

    # -- public API --------------------------------------------------------

    def qualifies(self, prompt_len: int, n: int, max_new: int) -> bool:
        """Can this request shape run in the shared loop at all?"""
        ok = (
            n <= self.width
            and prompt_len <= self.max_prompt
            and max_new <= self.max_new
        )
        if ok and self._pages is not None:
            # Admission revalidates page supply under the loop lock before
            # placement, so a stale read of the pool's size only skews this
            # hint.
            ok = self._pages.fits(prompt_len, n, max_new)
        return ok

    def submit(
        self,
        prompt_ids: List[int],
        *,
        n: int,
        max_new: int,
        temperature: float,
        top_p: Optional[float],
        seed: int,
        budget: Optional[RequestBudget] = None,
        token_sink: Optional[Callable[[int, np.ndarray], None]] = None,
        grammar: Optional[Any] = None,
        tenant: Optional[Any] = None,
    ) -> Future:
        """Queue one request for slot admission; returns a Future resolving to
        a :class:`GenerationResult` (or raising the typed lifecycle error).

        ``tenant`` is an already-resolved
        :class:`~k_llms_tpu.reliability.tenancy.TenantContext` (quota charge
        happens upstream in the backend): slot admission draws across queued
        tenants by weighted virtual time, with ``batch``-class work filling
        slots only when no ``interactive`` work is queued.

        ``grammar`` is an optional :class:`CompiledGrammar`: the request's
        rows then decode under the fused schema mask. The loop keeps one
        resident grammar; a request under a different schema while
        constrained work is queued or in flight raises ValueError (the
        backend's qualification ``except ValueError`` reroutes it to the
        coalescing path, which compiles its own loop per constraint)."""
        if self._admission_gate is not None:
            err = self._admission_gate()
            if err is not None:
                raise err
        with self._lock:
            if self._terminal_error is not None:
                raise self._terminal_error
            if self._closing or self._stopped:
                raise ServerDrainingError(
                    "continuous decode loop is draining; retry against "
                    "another replica"
                )
        if budget is not None:
            budget.check("continuous admission")
        try:
            _failpoints.fire("engine.launch")
        except Exception as e:
            if is_resource_exhausted(e):
                # Fixed-width loop: there is nothing to split, so device OOM
                # at admission is a typed unavailability, not a requeue.
                raise BackendUnavailableError(
                    f"continuous decode loop cannot admit request: {e}"
                ) from e
            raise
        ids, prompt_len, _bkt = self.engine._prep_prompt(prompt_ids)
        if not self.qualifies(prompt_len, n, max_new):
            raise ValueError(
                f"request (prompt_len={prompt_len}, n={n}, max_new={max_new}) "
                f"exceeds loop bounds (W={self.width}, P={self.max_prompt}, "
                f"G={self.max_new})"
            )
        with self._lock:
            if grammar is not None and self._grammar_busy_locked(grammar):
                raise ValueError(
                    "continuous loop is decoding under a different grammar; "
                    "take the per-constraint coalescing path"
                )
            req = _SlotRequest(
                future=Future(),
                prompt_len=prompt_len,
                n=max(1, n),
                max_new=max_new,
                budget=budget,
                token_sink=token_sink,
                ids=list(ids),
                seed=int(seed),
                temperature=float(temperature),
                top_p=1.0 if top_p is None else float(top_p),
                seq=self._seq,
                grammar=grammar,
                trace=current_trace(),
                enqueued_at=time.monotonic(),
                tenant=tenant,
            )
            self._seq += 1
            self._queue.append(req)
            self._ensure_worker()
            self._lock.notify_all()
        return req.future

    def drain(self, timeout: float = 30.0) -> bool:
        """Stop admitting, finish queued + in-flight rows. True on quiesce."""
        deadline = time.monotonic() + timeout
        with self._lock:
            self._closing = True
            self._lock.notify_all()
            while (
                self._queue
                or self._prefilling is not None
                or any(r is not None for r in self._active)
            ):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._lock.wait(timeout=min(0.1, remaining))
        return True

    def stop(self) -> None:
        """Hard stop: fail queued work, kill the worker."""
        with self._lock:
            self._closing = True
            self._stopped = True
            pending = list(self._queue)
            self._queue.clear()
            if self._prefilling is not None:
                # A PREFILLING admission has delivered nothing yet — fail it
                # like queued work (its pages die with the stopped loop).
                pending.append(self._prefilling.req)
                self._prefilling = None
            self._lock.notify_all()
        for req in pending:
            if not req.future.done():
                req.future.set_exception(
                    BackendUnavailableError("continuous decode loop stopped")
                )

    # -- device programs ---------------------------------------------------

    def _build_device_state(self) -> None:
        config = self.engine.config
        W, P, G = self.width, self.max_prompt, self.max_new
        if self.paged:
            # One flat KV pool instead of dense per-slot caches; the engine
            # owns it so prefix-cache page runs and loop rows share pages.
            self._pool = self.engine._ensure_kv_pool(
                min_pages=self._pages.planned_pages
            )
            self._pages.attach(self._pool)
            # Resolve the paged-attention implementation ONCE per loop build
            # (failpoint-aware, counted fallback) — never per step.
            from ..ops.paged_attention import resolve_paged_attention_impl

            self._paged_attn_impl = resolve_paged_attention_impl(
                getattr(self.engine, "paged_attention_impl", "auto"),
                config=config,
            )
        else:
            self._dense = _DenseSlots(config, W, P, G)
        self._state = init_state(config, W)
        self._stats["state_bytes"] = sum(int(a.nbytes) for a in jax.tree.leaves(self._state))
        self._step_fn = self._build_step(grammar=False)
        self._admit_sample_fn = self._build_first_token(grammar=False)
        if self._drafting:
            self._admit_draft_fn = self._build_admit_draft(grammar=False)
        self._built = True

    def _sampler(self) -> tuple:
        """``(row_keys, sample_rows, mask_pad)``: the key schedule, the
        per-row sampler and the pad mask every program of the loop shares, so
        rows a grammar mask does not touch sample byte-identically with and
        without one."""
        pad_id = self.engine.config.pad_token_id
        # pad must stay unsampleable on live rows unless the tokenizer maps
        # pad onto eos (then it IS the stop token) — same rule as the batch
        # decode loop.
        pad_sampleable = pad_id in self.eos_ids

        def _row_keys(seeds, steps, sample_idx):
            return jax.vmap(
                lambda s, st, i: jax.random.fold_in(
                    jax.random.fold_in(jax.random.key(s), st), i
                )
            )(seeds, steps, sample_idx)

        # Scope names (here and in models/llama.py) are metadata on the step
        # programs' ops: a profiler capture reads them, no result changes.
        @jax.named_scope("sampler")
        def _sample_rows(logits, keys, temps, top_ps):
            # Per-row temperature/top_p (the whole point of the shared loop);
            # same sanitization + untempered-logprob contract as sample_logits.
            # ``bad`` is the numeric-quarantine verdict, taken on the raw
            # logits BEFORE sanitization: a poisoned row still samples (the
            # sanitized path keeps the batch marching) but the host freezes
            # and retires it with sample_error code "numeric_poison".
            bad = _poisoned_logits(logits)
            finite = jnp.isfinite(logits)
            row_ok = jnp.any(finite, axis=-1, keepdims=True)
            logits = jnp.where(finite, logits, -jnp.inf)
            logits = jnp.where(row_ok, logits, 0.0)
            model_lps = jax.nn.log_softmax(logits, axis=-1)
            scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
            # Row-wise nucleus mask: the smallest set, by descending logit,
            # whose mass reaches top_p (boundary token and its ties kept).
            thresh = nucleus_threshold(scaled, top_ps)
            masked = jnp.where(scaled >= thresh[:, None], scaled, -jnp.inf)
            sampled = jax.vmap(jax.random.categorical)(keys, masked)
            greedy = jnp.argmax(scaled, axis=-1)
            tok = jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)
            lp = jnp.take_along_axis(model_lps, tok[:, None], axis=-1)[:, 0]
            return tok, lp, bad

        def _mask_pad(logits):
            if pad_sampleable:
                return logits
            return logits.at[:, pad_id].set(-jnp.inf)

        return _row_keys, _sample_rows, _mask_pad

    def _grammar_ops(self) -> tuple:
        """``(apply_mask, advance)`` over the resident grammar's tables, which
        the programs take as ARGUMENTS (only the vocab size is static)."""
        from .grammar import DeviceGrammar, grammar_advance, grammar_mask_logits

        vocab_size = self._dgrammar.vocab_size
        eos_arr = jnp.asarray(self.eos_ids, jnp.int32)

        def _as_grammar(tabs):
            masks, trans, terminal, token_bytes, token_len = tabs
            return DeviceGrammar(
                masks, trans, terminal, token_bytes, token_len, 0, vocab_size
            )

        @jax.named_scope("grammar_mask")
        def _apply_mask(logits, g_states, g_flags, tabs):
            masked = grammar_mask_logits(_as_grammar(tabs), logits, g_states, eos_arr)
            return jnp.where(g_flags[:, None], masked, logits)

        @jax.named_scope("grammar_advance")
        def _advance(tok, g_states, g_flags, tabs):
            nxt = grammar_advance(_as_grammar(tabs), tok, g_states)
            return jnp.where(g_flags, nxt, g_states)

        return _apply_mask, _advance

    def _build_step(self, grammar: bool):
        """The decode step, written once over two static choices: the KV
        layout and whether a grammar mask rides along. One token for all W
        slots; the four programs that come out keep the names every profile
        and the ledger's breakdown know them by: ``_step``, ``_step_paged``,
        ``_step_g``, ``_step_paged_g``. Arguments: ``(params, *layout_state,
        packed, poison, *grammar_tables, state=...)``, ``packed`` the step's
        one host array (:meth:`_pack_step`; a plain program ignores its two
        grammar columns); results ``(tok, lp, bad, *new_kv[, g_states], aux,
        state)``. ``state`` is the rows' recurrent state, by keyword (an empty
        dict, so no operand, for most models)."""
        if self._drafting:
            return self._build_drafted_step(grammar)
        config = self.engine.config
        mesh = getattr(self.engine, "mesh", None)
        paged = self.paged
        pad_id = config.pad_token_id
        row_keys, sample_rows, mask_pad = self._sampler()
        if grammar:
            apply_mask, advance = self._grammar_ops()
        if paged:
            attn_impl, page_size = self._paged_attn_impl, self._pool.page_size
            spread = (page_size, self.max_prompt, self._pages.gen_idx.shape[1])

        def _body(params, kv_a, kv_b, packed, poison, *tabs, state=None):
            rows = _unpack_step(packed, False, 1 if paged else 0)
            cur, gen_lens, prompt_lens, active = (
                rows.cur, rows.gen_lens, rows.prompt_lens, rows.active)
            # ``aux``: what the model's stack counts (router loads, cache
            # rows read: utils/observability.py::note_model_aux adds them at
            # readback); empty for a model that counts nothing.
            aux: Dict[str, Any] = {}
            state = dict(state or {})
            if paged:
                # Rows read their KV through block-table gathers into the
                # shared pool and write cur's column back at a host-computed
                # flat slot. Same masks, same sampler, same key schedule —
                # byte-identical tokens to the dense layout.
                pool_k, pool_v = kv_a, kv_b
                # A retired slot keeps its last tenant's lengths on the host;
                # the model is given none for it, so the paged kernel walks
                # no page of an idle row (its output is discarded below
                # either way), and its empty table spreads as from length 0.
                plens = jnp.where(active, prompt_lens, 0)
                prefix_idx, gen_idx = expand_tables(rows.tables, plens, *spread)
                logits, k_cols, v_cols = paged_verify_step(
                    config, params, cur[:, None],
                    jnp.where(active, gen_lens, 0), plens,
                    KVCache(k=pool_k, v=pool_v), prefix_idx, gen_idx,
                    attn_impl=attn_impl, page_size=page_size,
                    mesh=mesh, aux=aux, state=state, active=active,
                )
                with jax.named_scope("kv_write"):
                    new_kv = scatter_rows(pool_k, pool_v, rows.write_idx[:, 0], k_cols, v_cols)
            else:
                # Write cur's KV at each row's own offset (gen_lens), attend
                # row-local prefix + generated KV (``verify_step`` with Sq=1:
                # its per-row write offsets are the mid-flight join).
                prefix, gen = kv_a, kv_b
                logits, gen = verify_step(
                    config, params, cur[:, None], gen_lens, prompt_lens,
                    gen, prefix, mesh=mesh, aux=aux,
                )
                new_kv = (gen,)
            # Poison is injected BEFORE the pad and grammar masks: NaNs
            # survive the masks' allowed positions, so detection sees them
            # either way.
            logits = jnp.where(
                poison[:, None], jnp.float32(jnp.nan), logits[:, 0, :]
            )
            logits = mask_pad(logits)
            if grammar:
                g_states, g_flags = rows.g_states, rows.g_flags
                logits = apply_mask(logits, g_states, g_flags, tabs)
            keys = row_keys(rows.seeds, gen_lens + 1, rows.sample_idx)
            tok, lp, bad = sample_rows(logits, keys, rows.temps, rows.top_ps)
            tok = jnp.where(active, tok, jnp.int32(pad_id))
            lp = jnp.where(active, lp, 0.0)
            out = (tok, lp, bad & active) + new_kv
            if grammar:
                out += (advance(tok, g_states, g_flags, tabs),)
            return out + (aux, state)

        _body.__name__ = (
            "_step" + ("_paged" if paged else "") + ("_g" if grammar else "")
        )
        # The step's KV is donated (the pool's pair; the dense generation
        # cache) and so is the rows' recurrent state: their only owner
        # re-passes them every step, so the update happens in place on device.
        return jax.jit(
            _body, donate_argnums=(1, 2) if paged else (2,), donate_argnames=("state",)
        )

    def _build_drafted_step(self, grammar: bool):
        """The decode step of a model with a next-token module (paged layout
        only): ``_step_paged_mtp`` / ``_step_paged_mtp_g``. Arguments as
        :meth:`_build_step`'s; the packed array carries two more columns,
        ``draft`` and ``room`` (the row's ``max_tokens`` leaves room for two),
        and three write slots a row: positions P, P+1, P+2. Results
        ``(toks [W, 2], lps [W, 2], bad, pool_k, pool_v[, g_states], emitted
        [W], next draft [W], aux, state)``."""
        config = self.engine.config
        mesh = getattr(self.engine, "mesh", None)
        pad_id = config.pad_token_id
        row_keys, sample_rows, mask_pad = self._sampler()
        if grammar:
            apply_mask, advance = self._grammar_ops()
        attn_impl, page_size = self._paged_attn_impl, self._pool.page_size
        eos_arr = jnp.asarray(self.eos_ids, jnp.int32)
        writes = 1 + self._pages.lookahead
        spread = (page_size, self.max_prompt, self._pages.gen_idx.shape[1])

        def _body(params, pool_k, pool_v, packed, poison, *tabs, state=None):
            rows = _unpack_step(packed, True, writes)
            cur, gen_lens, prompt_lens, active = (
                rows.cur, rows.gen_lens, rows.prompt_lens, rows.active)
            seeds, sample_idx, temps, top_ps = (
                rows.seeds, rows.sample_idx, rows.temps, rows.top_ps)
            draft, room = rows.draft, rows.room
            aux: Dict[str, Any] = {}
            state = dict(state or {})
            lens = jnp.where(active, gen_lens, 0)
            plens = jnp.where(active, prompt_lens, 0)
            prefix_idx, gen_idx = expand_tables(rows.tables, plens, *spread)
            pool = KVCache(k=pool_k, v=pool_v)
            # The stack over both positions: the draft's row attends cur's
            # fresh latent beside the pages.
            logits, k_cols, _, hidden = paged_verify_step(
                config, params, jnp.stack([cur, draft], axis=1), lens, plens,
                pool, prefix_idx, gen_idx, attn_impl=attn_impl, page_size=page_size,
                mesh=mesh, aux=aux, state=state, active=active, return_hidden=True,
            )
            logits = jnp.where(poison[:, None, None], jnp.float32(jnp.nan), logits)
            after_cur, after_draft = mask_pad(logits[:, 0]), mask_pad(logits[:, 1])
            if grammar:
                g_states, g_flags = rows.g_states, rows.g_flags
                drafted = advance(draft, g_states, g_flags, tabs)
                after_cur = apply_mask(after_cur, g_states, g_flags, tabs)
                after_draft = apply_mask(after_draft, drafted, g_flags, tabs)
            # The keys of gen_len + 1 and + 2: the one-token loop's draws.
            t1, lp1, bad1 = sample_rows(
                after_cur, row_keys(seeds, gen_lens + 1, sample_idx), temps, top_ps)
            t2, lp2, bad2 = sample_rows(
                after_draft, row_keys(seeds, gen_lens + 2, sample_idx), temps, top_ps)
            with jax.named_scope("spec_accept"):
                ended = jnp.any(t1[:, None] == eos_arr[None, :], axis=-1)
                # A poisoned second position is not emitted: the next step
                # computes it again as its first and quarantines the row there.
                accept = active & (t1 == draft) & ~ended & room & ~bad1 & ~bad2
            toks = jnp.stack([t1, t2], axis=1)
            # The module on (h_P, t1) and (h_{P+1}, t2); its rows land one
            # position on, and the second pair counts only where t2 was emitted.
            mlogits, m_cols = paged_draft_step(
                config, params, hidden, toks, lens + 1, plens, pool, prefix_idx, gen_idx,
                aux=aux,
            )
            mlogits = mask_pad(jnp.where(accept[:, None], mlogits[:, 1], mlogits[:, 0]))
            out_g = ()
            if grammar:
                g_next = jnp.where(
                    accept, advance(t2, drafted, g_flags, tabs),
                    advance(t1, g_states, g_flags, tabs))
                mlogits = apply_mask(mlogits, g_next, g_flags, tabs)
                out_g = (g_next,)
            next_draft = jnp.argmax(mlogits, axis=-1).astype(jnp.int32)
            pool_k = write_drafted_rows(pool_k, k_cols, m_cols, rows.write_idx)
            toks = jnp.where(active[:, None], toks, jnp.int32(pad_id))
            lps = jnp.where(active[:, None], jnp.stack([lp1, lp2], axis=1), 0.0)
            emitted = jnp.where(active, 1 + accept.astype(jnp.int32), 0)
            return (toks, lps, bad1 & active, pool_k, pool_v) + out_g + (
                emitted, jnp.where(active, next_draft, jnp.int32(pad_id)), aux, state)

        _body.__name__ = "_step_paged_mtp" + ("_g" if grammar else "")
        return jax.jit(_body, donate_argnums=(1, 2), donate_argnames=("state",))

    def _build_admit_draft(self, grammar: bool):
        """Admission's second program for a drafting model, ``_admit_draft``
        / ``_admit_draft_g``: the module on ``(h_{L-1}, first token)`` for the
        rows just installed (every other row idles), its cache row written at
        position L of each, and the row's first draft: the module's argmax,
        pad-masked and, under a grammar, masked by the state after the first
        token."""
        config = self.engine.config
        pad_id = config.pad_token_id
        _, _, mask_pad = self._sampler()
        if grammar:
            apply_mask, _ = self._grammar_ops()

        def _body(params, pool_k, pool_v, h_last, tok0, prompt_lens, rows,
                  prefix_idx, gen_idx, write_idx, *g_args):
            mlogits, m_cols = paged_draft_step(
                config, params, h_last[:, None], tok0[:, None], jnp.zeros_like(prompt_lens),
                jnp.where(rows, prompt_lens, 0), KVCache(k=pool_k, v=pool_v),
                prefix_idx, gen_idx,
            )
            mlogits = mask_pad(mlogits[:, 0])
            if grammar:
                g_states, g_flags, *tabs = g_args
                mlogits = apply_mask(mlogits, g_states, g_flags & rows, tabs)
            draft = jnp.where(rows, jnp.argmax(mlogits, axis=-1).astype(jnp.int32),
                              jnp.int32(pad_id))
            return draft, write_drafted_rows(pool_k, None, m_cols, write_idx[:, None]), pool_v

        _body.__name__ = "_admit_draft" + ("_g" if grammar else "")
        return jax.jit(_body, donate_argnums=(1, 2))

    def _admit_drafts(self, req, rows: List[int]) -> None:
        """Leave each newly installed row its first draft (lock held, worker
        thread). The rows' positions L .. L+2 are made private first: the
        module's row for ``(h_{L-1}, first token)`` differs by sample, and it
        lies at L, on the row's own page."""
        W = self.width
        mask = np.zeros((W,), bool)
        mask[rows] = True
        write_idx, _ = self._pages.prepare_step(mask, self._prompt_lens, self._gen_lens)
        prefix_idx, gen_idx = self._pages.prefix_idx, self._pages.gen_idx
        fn, grammar_args = self._admit_draft_fn, ()
        if req.grammar is not None:
            fn = self._grammar_programs()["draft"]
            grammar_args = (
                jnp.asarray(self._g_states), jnp.asarray(self._g_flags), *self._g_tabs())
        pool = self._pool
        with pool.lock:
            note_device_dispatch("continuous admission draft")
            draft, new_k, new_v = fn(
                self.engine.params, pool.kv.k, pool.kv.v, self._state["mtp_h"][0],
                jnp.asarray(self._cur), jnp.asarray(self._prompt_lens), jnp.asarray(mask),
                jnp.asarray(prefix_idx), jnp.asarray(gen_idx), jnp.asarray(write_idx[:, 0]),
                *grammar_args,
            )
            pool.kv = KVCache(k=new_k, v=new_v)
        # kllms: ignore[host-sync-hot-path] — admission's readback of the first drafts, beside the first tokens'
        self._draft[rows] = np.asarray(jax.device_get(draft))[rows]

    def _build_first_token(self, grammar: bool):
        """The first token, sampled at admission from the prefill logits at
        step 0 — padded to W rows so every admission shares one program.
        Detection-only quarantine here (no injection arg: the
        ``engine.logits`` failpoint targets decode steps); genuinely poisoned
        prefill logits still freeze the row at step 0. Under a grammar the
        sample is masked from the start state and each row's automaton
        advanced on the device."""
        row_keys, sample_rows, mask_pad = self._sampler()
        if grammar:
            apply_mask, advance = self._grammar_ops()

        def _body(first_logits, seeds, sample_idx, temps, top_ps, *g_args):
            logits = mask_pad(first_logits)
            if grammar:
                g_states, g_flags, *tabs = g_args
                logits = apply_mask(logits, g_states, g_flags, tabs)
            keys = row_keys(seeds, jnp.zeros_like(sample_idx), sample_idx)
            out = sample_rows(logits, keys, temps, top_ps)
            if grammar:
                out += (advance(out[0], g_states, g_flags, tabs),)
            return out

        _body.__name__ = "_admit_g" if grammar else "_admit_sample"
        return jax.jit(_body)

    # -- grammar-constrained programs --------------------------------------

    def _grammar_busy_locked(self, grammar: Any) -> bool:
        """Is constrained work under a *different* schema queued or active?
        (Same digest shares the resident tables.) Lock held by the caller."""
        holders = [r for r in self._active if r is not None] + list(self._queue)
        if self._prefilling is not None:
            holders.append(self._prefilling.req)
        return any(
            r.grammar is not None and r.grammar.digest != grammar.digest
            for r in holders
        )

    def _install_grammar(self, grammar: Any) -> None:
        """Make ``grammar`` the resident constraint: upload its tables with
        the state axis padded to a power of two, so the next schema of the
        same padded shape reuses the compiled grammar-twin programs."""
        if self._grammar is not None and self._grammar.digest == grammar.digest:
            return
        from .grammar import device_grammar

        self._grammar = grammar
        self._dgrammar = device_grammar(grammar, pad_states=64)

    def _g_tabs(self) -> tuple:
        dg = self._dgrammar
        return (dg.masks, dg.trans, dg.terminal, dg.token_bytes, dg.token_len)

    def _grammar_programs(self) -> Dict[str, Any]:
        """Jitted grammar twins of the admit/step programs, memoized by table
        shape. The resident grammar's tables are ARGUMENTS (only the vocab
        size is static), so swapping schemas over the same tokenizer and
        padded state count re-dispatches the already-compiled programs; the
        mask gather and state advance are fused into the step — the per-step
        host sync stays the single result readback."""
        dg = self._dgrammar
        shape_key = (
            dg.masks.shape, dg.trans.shape, dg.token_bytes.shape, dg.vocab_size
        )
        if self._g_programs is not None and self._g_programs[0] == shape_key:
            return self._g_programs[1]
        fns = {
            "admit": self._build_first_token(grammar=True),
            "step": self._build_step(grammar=True),
        }
        if self._drafting:
            fns["draft"] = self._build_admit_draft(grammar=True)
        self._g_programs = (shape_key, fns)
        return fns

    # -- worker ------------------------------------------------------------

    def _ensure_worker(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._worker, name="kllms-continuous", daemon=True
            )
            self._thread.start()

    def _worker(self) -> None:
        """Crash-contained worker: every fault class maps to a recovery
        domain instead of a silent log line. A hung step (watchdog) or a
        quarantined page pool tears the engine down and replays the journal;
        any OTHER exception — the previously-silent worker-death path — fails
        every queued and in-flight future with a typed error, restarts the
        loop, and leaves the engine alone. All domains share the
        ``max_rebuilds`` bound before the loop goes terminal."""
        while True:
            try:
                self._worker_loop()
                return
            except _AdoptEngine as swap:
                if not self._recover("adopt_engine", new_engine=swap.engine):
                    return
            except _StepHung:
                if not self._recover("hung_step"):
                    return
            except (_PoolFault, PageAccountingError):
                if not self._recover("page_accounting"):
                    return
            except Exception:
                logger.exception("continuous decode worker crashed")
                RECOVERY_EVENTS.record("continuous.worker_crashes")
                if not self._recover("worker_crash"):
                    return
            finally:
                # A recovery is no host overhead of the loop, and neither is
                # the time after its end.
                self._pause_host_clock()

    def _worker_loop(self) -> None:
        while True:
            # Crash-injection point for the worker itself: OUTSIDE the
            # step-level fault domains, so a ``crash`` spec exercises the
            # top-level containment path (typed flush + bounded restart).
            _failpoints.fire("continuous.worker")
            with self._lock:
                if self._stopped:
                    return
                if self._adopted_engine is not None:
                    eng, self._adopted_engine = self._adopted_engine, None
                    raise _AdoptEngine(eng)
                if self._pool_fault is not None:
                    raise _PoolFault(self._pool_fault)
                if self._queue:
                    with LATENCY.span("continuous.admit"):
                        self._admit_locked()
                has_decode = bool(self._active_mask.any())
                prefilling = self._prefilling is not None
                if not has_decode and not prefilling:
                    if self._closing and not self._queue:
                        self._lock.notify_all()
                        return
                    # Wake for new arrivals; re-check queued budgets at a
                    # coarse interval so expired deadlines shed.
                    self._pause_host_clock()
                    with LATENCY.span("continuous.idle"):
                        self._lock.wait(timeout=0.05)
                    self._shed_expired_locked()
                    continue
            # The interleave: one decode step for the active batch, then one
            # prompt chunk for the (at most one) PREFILLING admission — a
            # long prompt's ingestion is spread across decode steps instead
            # of stalling every in-flight row for a whole prefill. How long
            # that chunk is, ``pick_chunk`` reads each turn from what is left
            # of the prompt: the live rows wait one turn of at most the
            # ladder's longest rung (16 x width under the automatic size).
            if has_decode:
                self._step_once()
            if prefilling:
                self._prefill_chunk_once()

    # -- the host's share of the loop, on both clocks ----------------------

    def _open_host(self) -> None:
        """Open ``continuous.host`` as a program's hand-off returns (worker
        thread). It stays open across ``_step_once`` -> ``_worker_loop`` ->
        ``_admit_locked`` -> the next program's preparation, so it is opened
        and closed by hand; ``bookkeep``, ``admit`` and ``prepare`` lie inside
        it, and a device gap that none of them covers half of is still the
        loop's on the trace."""
        self._host_annotation = jax.profiler.TraceAnnotation("continuous.host")
        self._host_annotation.__enter__()
        self._host_opened_at = time.perf_counter()

    def _close_host(self) -> None:
        annotation, self._host_annotation = self._host_annotation, None
        if annotation is not None:
            self._active_s += time.perf_counter() - self._host_opened_at
            annotation.__exit__(None, None, None)

    def _pause_host_clock(self) -> None:
        """Before an idle wait and around a recovery: what follows is not the
        host keeping the device waiting, so neither clock charges it."""
        self._close_host()
        self._results_at = None
        self._gap_device_s = 0.0

    def _observe_gap(self) -> None:
        """First line of a step's or chunk's dispatch: ``continuous.gap`` is
        the host clock since the previous program's results reached the host,
        and ``continuous.wait`` that gap less the admissions' device work
        inside it (``continuous.admit_device``): the host clock's statement
        that the chip had nothing to run."""
        results_at, self._results_at = self._results_at, None
        device_s, self._gap_device_s = self._gap_device_s, 0.0
        if results_at is not None:
            gap_s = time.perf_counter() - results_at
            LATENCY.observe("continuous.gap", gap_s)
            LATENCY.observe("continuous.wait", gap_s - device_s)

    def _readback(self, outputs: Any) -> Any:
        """A program's results brought to the host (the step thread under a
        watchdog), ``continuous.readback``: the wait for the device to finish,
        then ``continuous.fetch`` around the copy of the (already computed)
        outputs, which ends where ``_results_at`` is set. What is left of the
        readback ahead of the fetch is the device running; the fetch is the
        device waiting for the host again."""
        with LATENCY.span("continuous.readback"):
            # Queue the copies behind the program first, as ``device_get``
            # itself does: waiting for the program and only then asking for
            # them would put a round trip between the two.
            for leaf in jax.tree.leaves(outputs):
                queue_copy = getattr(leaf, "copy_to_host_async", None)
                if queue_copy is not None:
                    queue_copy()
            # kllms: ignore[host-sync-hot-path] — the by-design completion sync of a step or chunk, split from its copy so each is timed
            jax.block_until_ready(outputs)
            with LATENCY.span("continuous.fetch"):
                # kllms: ignore[host-sync-hot-path] — the per-program result readback; everything after it is host-side bookkeeping
                fetched = jax.device_get(outputs)
                self._results_at = time.perf_counter()
        return fetched

    @contextmanager
    def _admit_device_span(self) -> Iterator[None]:
        """``continuous.admit_device`` round an admission's device work (lock
        held, worker thread): from its first jitted call to the return of the
        ``device_get`` that ends it, the host work in between included (it
        overlaps the device). Its seconds are also taken out of the gap they
        fall in, for ``continuous.wait``. A stretch that raised (no pages) is
        no sample, but its seconds were still no wait."""
        span = LATENCY.span("continuous.admit_device")
        try:
            with span:
                yield
        finally:
            self._gap_device_s += span.seconds

    def _hand_off(self, dispatch: Callable[[], Any], what: str) -> Any:
        """Run one device program's ``dispatch`` closure: on the disposable
        step thread under the watchdog budget where the loop has a budget
        model, else inline. Returns ``(result, run_seconds)``, the latter None
        inline. The call's wall clock, like a ``continuous.host`` interval's,
        is the loop being active (``stats["active_seconds"]``)."""
        t0 = time.perf_counter()
        try:
            if self.budget_model is None:
                LATENCY.observe("continuous.handoff", 0.0)
                return dispatch(), None
            return self._dispatcher.run(dispatch, self.budget_model)
        except _StepHung:
            with self._lock:
                self._loop_epoch += 1
            RECOVERY_EVENTS.record("continuous.step_hangs")
            logger.error(
                "continuous %s overran its watchdog budget; abandoning the "
                "dispatch thread and rebuilding", what,
            )
            raise
        finally:
            self._active_s += time.perf_counter() - t0

    # -- recovery ----------------------------------------------------------

    def _recover(self, reason: str, new_engine: Any = None) -> bool:
        """Heal the loop after a fault; True when the worker should keep
        running. Fault domains:

        - ``hung_step`` / ``page_accounting``: journal the in-flight rows,
          rebuild the engine via ``rebuild_fn`` (fresh KV pool — the old
          pool's pages die with the torn-down engine, no decref), then
          re-queue the survivors for byte-identical replay.
        - ``worker_crash``: the engine is healthy but the host loop is not —
          fail everything with a typed error (returning every page to the
          pool on the way) and restart the loop empty.
        - ``adopt_engine``: an external supervisor already rebuilt the
          engine; journal + swap + replay without spending a fault credit.
        """
        counts = reason != "adopt_engine"
        with self._lock:
            self._loop_epoch += 1
            self._last_recovery_reason = reason
            self._stats["restarts"] += 1
            if counts:
                self._consecutive_faults += 1
            attempt = self._consecutive_faults
        RECOVERY_EVENTS.record("continuous.restarts")
        if counts and attempt > self.max_rebuilds:
            return self._terminal(EngineHungError(
                f"continuous decode loop did not recover after "
                f"{self.max_rebuilds} restart attempt(s); last fault: {reason}"
            ))
        if counts and self.on_recovering is not None:
            self.on_recovering(attempt, f"continuous_{reason}")
        if reason == "worker_crash":
            self._fail_all(BackendUnavailableError(
                "continuous decode worker crashed; in-flight requests were "
                "failed and the loop restarted"
            ))
        else:
            if new_engine is None and self.rebuild_fn is None:
                # Unsupervised loop: a wedged device or corrupt pool cannot
                # heal without a rebuild path — typed terminal, no replay.
                # (No journal/reset: _terminal's fail-all flushes in-flight
                # rows, and the quarantine evidence stays visible in stats.)
                return self._terminal(EngineHungError(
                    f"continuous decode loop fault '{reason}' is "
                    "unrecoverable without an engine rebuild path"
                ))
            with self._lock:
                survivors = self._journal_survivors_locked()
                self._reset_device_state_locked()
            if new_engine is not None:
                self.engine = new_engine
            else:
                try:
                    eng = self.rebuild_fn()
                except BaseException as exc:
                    RECOVERY_EVENTS.record("supervisor.rebuild_failures")
                    err = exc if isinstance(exc, CheckpointCorruptError) else (
                        EngineHungError(
                            f"continuous loop engine rebuild failed: {exc!r}"
                        )
                    )
                    for req in survivors:
                        if not req.future.done():
                            req.future.set_exception(err)
                    return self._terminal(err)
                if eng is not None:
                    self.engine = eng
            if survivors:
                with self._lock:
                    self._queue.extendleft(reversed(survivors))
                    self._lock.notify_all()
        if counts and self.on_rebuilt is not None:
            self.on_rebuilt()
        return True

    def _terminal(self, err: BaseException) -> bool:
        """The loop is beyond self-healing: pin the terminal error (submit
        re-raises it), fail every remaining future, and stop for good."""
        logger.error("continuous decode loop is terminal: %s", err)
        with self._lock:
            self._terminal_error = err
            self._closing = True
            self._stopped = True
        self._fail_all(err)
        if self.on_rebuild_failed is not None:
            self.on_rebuild_failed(err)
        return False

    def _journal_survivors_locked(self) -> List[_SlotRequest]:
        """Snapshot the in-flight requests for replay (lock held): reset
        their accumulators and advance the sink watermark so re-admission
        regenerates from step 0 — self-deterministic row keys make the
        regenerated stream byte-identical — while already-delivered steps
        are suppressed, not repeated."""
        seen: Dict[int, _SlotRequest] = {}
        for r in self._active:
            if r is not None and id(r) not in seen and not r.future.done():
                seen[id(r)] = r
        # A half-prefilled admission survives too: its staging KV dies with
        # the engine, so replay re-prefills from the journaled prompt ids
        # (cursor back to 0) — deterministic prefill plus the submission-
        # pinned seed make the replayed stream byte-identical regardless of
        # where the chunk cursor stood at the fault.
        pf = self._prefilling
        if pf is not None and id(pf.req) not in seen and not pf.req.future.done():
            seen[id(pf.req)] = pf.req
        survivors = sorted(seen.values(), key=lambda r: r.seq)
        for req in survivors:
            req.delivered_watermark = max(
                req.delivered_watermark, req.steps_delivered
            )
            req.steps_delivered = 0
            req.replays += 1
            req.slots = []
            req.tokens = []
            req.logprobs = []
            req.done = []
            req.finish = []
            req.sample_errors = []
            req.chunk_cursor = 0
        return survivors

    def _reset_device_state_locked(self) -> None:
        """Forget every device handle and slot mirror (lock held). Old pool
        page references are dropped WITHOUT decref on purpose: the pool dies
        with the torn-down engine, and decref against a replaced allocator
        would corrupt the new pool's accounting."""
        pad = self.engine.config.pad_token_id
        self._active = [None] * self.width
        self._free = list(range(self.width))
        self._active_mask[:] = False
        self._cur[:] = pad
        self._gen_lens[:] = 0
        self._prompt_lens[:] = 1
        self._seeds[:] = 0
        self._sample_idx[:] = 0
        self._temps[:] = 1.0
        self._top_ps[:] = 1.0
        self._g_states[:] = 0
        self._g_flags[:] = False
        self._draft[:] = pad
        self._max_news[:] = 0
        self._grammar = None
        self._dgrammar = None
        self._g_programs = None
        self._step_fn = None
        self._admit_sample_fn = None
        self._admit_draft_fn = None
        self._dense = None
        self._pool = None
        self._state = {}
        self._stats["state_bytes"] = 0
        if self._pages is not None:
            self._pages.reset()
        self._pool_fault = None
        # Like the slots' tables: the holder's page references die with the
        # pool, no decref against a replaced allocator.
        self._prefilling = None
        self._chunk_built.clear()
        self._built = False

    def adopt_engine(self, new_engine: Any) -> None:
        """Swap in an externally rebuilt engine (the supervisor's coalesced
        rebuild path). With work in flight the worker journals, swaps, and
        replays on its own thread; an idle loop swaps inline."""
        with self._lock:
            has_work = (
                bool(self._queue)
                or self._prefilling is not None
                or any(r is not None for r in self._active)
            )
            if not has_work:
                self._loop_epoch += 1
                self.engine = new_engine
                self._reset_device_state_locked()
                return
            self._adopted_engine = new_engine
            if not self._stopped:
                self._ensure_worker()
            self._lock.notify_all()

    def _shed_expired_locked(self) -> None:
        kept: "deque[_SlotRequest]" = deque()
        for req in self._queue:
            if req.budget is not None and req.budget.should_abort():
                FAILURE_EVENTS.record("scheduler.shed")
                req.future.set_exception(req.budget.error("continuous queue"))
            else:
                kept.append(req)
        self._queue = kept

    def _select_locked(self) -> Optional[int]:
        """WFQ selection over the queued requests: index of the EARLIEST
        request of the tenant with the smallest (slo_class, vtime) key —
        interactive strictly before batch, then weighted virtual time, then
        arrival order. Head-of-line within a tenant is preserved: only each
        tenant's first queued request is a candidate. None on empty queue."""
        best_idx: Optional[int] = None
        best_key = None
        seen: set = set()
        for idx, req in enumerate(self._queue):
            name = _req_tenant_name(req)
            if name in seen:
                continue
            seen.add(name)
            key = (
                0 if _req_interactive(req) else 1,
                self._vtimes.get(name, 0.0),
                idx,
            )
            if best_key is None or key < best_key:
                best_idx, best_key = idx, key
        return best_idx

    def _admit_locked(self) -> None:
        """WFQ head-of-line admission: the selected tenant's earliest request
        joins when all n of its slots are free (no skipping past it — later
        small requests must not starve a large one; no cross-tenant skipping
        either, so a big interactive head blocks batch fill rather than being
        starved by it). Called with the lock held; does device writes for the
        admitted request's prefill."""
        while self._queue:
            idx = self._select_locked()
            if idx is None:
                break
            if len(self._free) < self._queue[idx].n:
                self._stats["blocked_slots"] += 1
                break
            req = self._queue[idx]
            chunked = self._chunk_eligible(req)
            if chunked and self._prefilling is not None:
                # One chunked admission at a time: the head waits for the
                # in-flight PREFILLING to finish (no skipping past it — the
                # same no-starvation rule as the slot-shortage break above).
                self._stats["blocked_lane"] += 1
                break
            del self._queue[idx]
            if req.budget is not None and req.budget.should_abort():
                FAILURE_EVENTS.record("scheduler.shed")
                req.future.set_exception(req.budget.error("continuous queue"))
                continue
            if req.enqueued_at and not req.replays:
                wait_s = max(0.0, time.monotonic() - req.enqueued_at)
                LATENCY.observe("scheduler.queue_wait", wait_s)
                if req.tenant is not None:
                    LATENCY.observe(
                        f"scheduler.queue_wait.{_req_tenant_name(req)}", wait_s
                    )
                if req.trace is not None:
                    req.trace.add_phase("queue_wait", wait_s)
            req.dequeued_at = time.perf_counter()
            if not self._built:
                self._build_device_state()
            in_flight = self._active_mask.any()
            rows = [self._free.pop(0) for _ in range(req.n)]
            req.slots = rows
            try:
                _admit_t0 = time.perf_counter()
                if chunked:
                    # Enter the PREFILLING state instead of prefilling here:
                    # the worker runs one chunk per loop iteration alongside
                    # the decode batch (per-chunk prefill trace spans are
                    # recorded by _prefill_chunk_once, not here).
                    self._begin_prefilling_locked(req, rows)
                else:
                    self._admit_device(req, rows)
                    if req.trace is not None:
                        # To the rows' installation, where prefill_wall ends
                        # too: delivery and resolution after it are not prefill.
                        req.trace.add_phase("prefill", req.installed_at - _admit_t0)
            except PagePoolExhausted as e:
                # Pages are a transient resource: in-flight rows free theirs
                # as they retire, so park the head request and retry after the
                # next step instead of failing it. With nothing in flight the
                # pool genuinely cannot fit the request — fail it to avoid a
                # head-of-line deadlock (qualifies() makes this unreachable
                # for well-sized pools).
                for r in rows:
                    self._free.append(r)
                req.slots = []
                if in_flight:
                    self._stats["blocked_pages"] += 1
                    self._queue.appendleft(req)
                    break
                req.future.set_exception(BackendUnavailableError(
                    f"paged KV pool cannot fit request: {e}"
                ))
                continue
            except Exception as e:
                for r in rows:
                    self._free.append(r)
                req.future.set_exception(e)
                continue
            if req.replays:
                # Journal replay after a rebuild: the rows re-enter the batch
                # but the request was already counted at first admission.
                self._stats["replayed_rows"] += req.n
                RECOVERY_EVENTS.record("continuous.replayed_rows", req.n)
                if req.trace is not None:
                    # One coherent trace per request: the SAME trace object
                    # survives the rebuild, annotated rather than duplicated.
                    req.trace.annotate("replayed")
                    req.trace.bump("replayed_rows", req.n)
            else:
                self._stats["admitted"] += 1
                if in_flight:
                    self._stats["joined_in_flight"] += 1
                # WFQ pass charge: advance the tenant's virtual time by
                # rows/weight from the floor (an idle tenant re-enters at the
                # current floor, not at zero — it must not get unbounded
                # catch-up credit). Replays were charged at first admission.
                name = _req_tenant_name(req)
                start = max(self._vtimes.get(name, 0.0), self._vfloor)
                self._vfloor = start
                self._vtimes[name] = start + req.n / _req_tenant_weight(req)

    def _admit_device(self, req, rows) -> None:
        engine = self.engine
        _ids, _plen, bucket = engine._prep_prompt(req.ids)
        with self._admit_device_span():
            if self.paged:
                # The prompt KV as shared, refcounted pool pages: the prefill's
                # (or the cache entry's) page run, a reference for each row, and
                # each row's generation reserve; PagePoolExhausted leaves here
                # with everything rolled back.
                first_logits, run, transient, lane_state = engine.paged_admit_prefix(
                    _ids, _plen, bucket
                )
                try:
                    with engine._paged_mutex:
                        self._pages.admit(
                            rows, run.pages, _plen, req.max_new,
                            engine._alloc_pages_with_evict,
                        )
                finally:
                    if transient:
                        # Uncached prefill: the run was a scratch owner of the
                        # prompt pages; the rows' references now keep them alive.
                        run.release()
                self._install_state(rows, lane_state)
            else:
                first_logits, prefix = engine._prefill_routed(_ids, _plen, bucket)
                self._dense.install(rows, prefix, bucket)
            first = self._sample_first(req, len(rows), first_logits)
        self._admit_rows(req, rows, first)

    def _install_state(self, rows: List[int], lane_state: Dict[str, Any]) -> None:
        """Fork the prompt's final recurrent state (one row: the chunk lane's,
        or whole-prompt admission's) into each of the request's rows, one
        device copy (pages are shared, state is not). Admission always
        overwrites, so a slot never sees its last tenant's state and a release
        needs no device work. Nothing to do for a model without such state."""
        if not self._state:
            return
        with LATENCY.span("continuous.state_install"):
            idx = np.full((self.width,), self.width, np.int32)
            idx[: len(rows)] = rows
            self._state = _install_rows(self._state, lane_state, jnp.asarray(idx))

    def _sample_first(self, req, n: int, first_logits) -> tuple:
        """The device end of the admission tail, shared by whole-prompt
        admission and the chunked-prefill finish (inside their
        ``continuous.admit_device``): sample each of the request's ``n`` rows'
        first token from the prefill logits with the submission-pinned seed at
        step 0 (so chunked-on/off token streams are byte-identical) and bring
        them to the host: ``(tok0, lp0, bad0, st0)``, each ``[n]``."""
        seed, temperature, top_p = req.seed, req.temperature, req.top_p
        # First-token sampling at admission (step 0), padded to W rows.
        W = self.width
        V = first_logits.shape[-1]
        fl = jnp.broadcast_to(first_logits[0:1], (W, V))
        seeds = np.zeros((W,), np.uint32)
        seeds[:n] = np.uint32(seed & 0xFFFFFFFF)
        sidx = np.zeros((W,), np.int32)
        sidx[:n] = np.arange(n, dtype=np.int32)
        temps = np.full((W,), 1.0, np.float32)
        temps[:n] = temperature
        tps = np.full((W,), 1.0, np.float32)
        tps[:n] = top_p
        first_fn, grammar_args = self._admit_sample_fn, ()
        if req.grammar is not None:
            # Constrained admission: mask the first sample from the start
            # state and advance each row's automaton on device; the states
            # ride the same readback as tok0/lp0 (admission is not the hot
            # loop, but there is still only one sync here).
            self._install_grammar(req.grammar)
            first_fn = self._grammar_programs()["admit"]
            g_flags = np.zeros((W,), bool)
            g_flags[:n] = True
            grammar_args = (
                jnp.full((W,), self._dgrammar.start, jnp.int32),
                jnp.asarray(g_flags), *self._g_tabs(),
            )
        outs = first_fn(
            fl, jnp.asarray(seeds), jnp.asarray(sidx), jnp.asarray(temps),
            jnp.asarray(tps), *grammar_args,
        )
        tok0, lp0, bad0, *st0 = (np.asarray(a)[:n] for a in jax.device_get(outs))
        if st0:
            GRAMMAR_EVENTS.record("grammar.masked_steps", n)
        return tok0, lp0, bad0, st0[0] if st0 else np.zeros((n,), np.int32)

    def _admit_rows(self, req, rows, first: tuple) -> None:
        """The host end of the admission tail: install the slot mirrors from
        :meth:`_sample_first`'s tokens and run first-step retirement/delivery
        (a drafting loop leaves each row its first draft in between, device
        work of its own)."""
        prompt_len = req.prompt_len
        seed, temperature, top_p = req.seed, req.temperature, req.top_p
        n = len(rows)
        tok0, lp0, bad0, st0 = first

        quarantined = 0
        for j, slot in enumerate(rows):
            self._active[slot] = req
            self._active_mask[slot] = True
            self._cur[slot] = tok0[j]
            self._gen_lens[slot] = 0  # KV written so far; tok0's comes next step
            self._prompt_lens[slot] = prompt_len
            self._seeds[slot] = np.uint32(seed & 0xFFFFFFFF)
            self._sample_idx[slot] = j
            self._temps[slot] = temperature
            self._top_ps[slot] = top_p
            self._g_flags[slot] = req.grammar is not None
            self._g_states[slot] = st0[j]
            self._max_news[slot] = req.max_new
            req.tokens.append([int(tok0[j])])
            req.logprobs.append([float(lp0[j])])
            req.sample_errors.append(None)
            if bad0[j]:
                # Poisoned prefill logits: freeze the row before it ever
                # decodes; siblings proceed and consensus drops this member.
                self._quarantine_row(req, j)
                quarantined += 1
                continue
            done0 = int(tok0[j]) in self.eos_ids
            req.done.append(done0 or req.max_new <= 1)
            req.finish.append("stop" if done0 else "length")
        if quarantined:
            note = getattr(self.engine, "_note_quarantine", None)
            if note is not None:
                note(quarantined, n)
        if self._drafting:
            with self._admit_device_span():
                self._admit_drafts(req, rows)
        # The rows are installed: the request's prefill_wall (everything since
        # its dequeue, other requests' steps and chunks included) ends here
        # and its decode_wall begins.
        req.installed_at = time.perf_counter()
        prefill_wall_s = req.installed_at - req.dequeued_at
        LATENCY.observe("continuous.prefill_wall", prefill_wall_s)
        if req.trace is not None:
            req.trace.add_phase("prefill_wall", prefill_wall_s)
        self._deliver_sink(req)
        self._retire_finished_rows(req)
        self._resolve_if_done(req)

    def _quarantine_row(self, req: _SlotRequest, j: int) -> None:
        """Freeze sample ``j``: typed ``numeric_poison`` member error, row
        done (the caller retires it and frees the slot). The request's other
        samples keep decoding — per-ROW fault domain, not per-request."""
        if len(req.done) <= j:
            req.done.append(True)
        else:
            req.done[j] = True
        if len(req.finish) <= j:
            req.finish.append("stop")
        else:
            req.finish[j] = "stop"
        req.sample_errors[j] = _quarantine_error()
        self._stats["quarantined_rows"] += 1
        if req.trace is not None:
            req.trace.bump("quarantined_rows")

    # -- chunked prefill (ISSUE 18) ---------------------------------------

    def _chunk_eligible(self, req: _SlotRequest) -> bool:
        """Should this admission take the PREFILLING path? Only prompts
        longer than one chunk, and only when the prefix cache cannot supply
        the prompt anyway — exact and usable partial hits skip straight to
        DECODING through the (cheap) whole-prompt path. Called with the loop
        lock held; the probe takes the engine's paged mutex internally."""
        C = self.prefill_chunk_tokens
        if C <= 0 or req.prompt_len <= C:
            return False
        probe = getattr(self.engine, "prefix_cached_len", None)
        return probe is None or probe(req.ids) == 0

    def _begin_prefilling_locked(self, req: _SlotRequest, rows: List[int]) -> None:
        """Enter the PREFILLING state: take the prompt's page run and every
        row's generation reserve UP FRONT (:meth:`SlotPages.reserve_chunked`;
        :class:`PagePoolExhausted` leaves with everything rolled back, exactly
        like whole-prompt admission), build the 1-row staging KV the chunks
        extend, and hand the request to the worker's chunk phase."""
        engine = self.engine
        _ids, _plen, bucket = engine._prep_prompt(req.ids)
        run_pages: Optional[List[int]] = None
        reserved: List[List[int]] = []
        if self._pages is not None:
            with engine._paged_mutex:
                run_pages, reserved = self._pages.reserve_chunked(
                    len(rows), _plen, req.max_new, engine._alloc_pages_with_evict
                )
        cache = init_cache(engine.config, 1, bucket)
        mesh = getattr(engine, "mesh", None)
        if mesh is not None:
            from jax.sharding import NamedSharding

            from ..parallel.sharding import cache_specs

            cache = jax.device_put(
                cache,
                KVCache(
                    k=NamedSharding(mesh, cache_specs(shared_prefix=True)),
                    v=NamedSharding(mesh, cache_specs(shared_prefix=True)),
                ),
            )
        req.chunk_cursor = 0
        self._prefilling = _Prefilling(
            req, list(rows), list(_ids), cache, _plen, bucket,
            run_pages, reserved, init_state(engine.config, 1),
        )

    def _prefill_chunk_once(self) -> None:
        """Run ONE prompt chunk for the PREFILLING admission (worker thread,
        between decode steps). The chunk is dispatched under the same
        watchdog/epoch-fence discipline as a decode step — a hung chunk
        abandons its thread and rebuilds, and the journal replays the
        admission from cursor 0. The final chunk's logits feed the shared
        first-token admission tail, so the sampled stream is byte-identical
        to whole-prompt prefill."""
        with LATENCY.span("continuous.admit"):
            with self._lock:
                pf = self._prefilling
                if pf is None:
                    return
                req = pf.req
                if req.budget is not None and req.budget.should_abort():
                    # Budget abort retires the PREFILLING row through the same
                    # fault counters as a decoding abort.
                    self._retire_prefilling_locked(
                        req.budget.error("engine prefill"), abort=True
                    )
                    return
                epoch = self._loop_epoch
                chunk_no = self._stats["prefill_chunks"]
                start = pf.cursor
                C = pick_chunk(self._chunk_rungs, pf.plen - start, pf.bucket - start)
                end = min(start + C, pf.plen)
                valid = end - start
                final = end >= pf.plen
                pad_id = self.engine.config.pad_token_id
                chunk = np.full((1, C), pad_id, np.int32)
                chunk[0, :valid] = pf.ids[start:end]
                cache, lane_state, bucket = pf.cache, pf.state, pf.bucket
                # Paged: the chunk's KV columns land in the admission's page
                # run at its current offset.
                pool = self._pool
                slot_idx = (
                    self._pages.chunk_slots(pf.run_pages, start, C, valid)
                    if pool is not None else None
                )
                # A bucket's first chunk builds every length its prompts can
                # take, so a later prompt with another tail compiles nothing.
                unbuilt = () if bucket in self._chunk_built else tuple(
                    c for c in bucket_rungs(self._chunk_rungs, bucket) if c != C
                )
            fn = self.engine._get_prefill_chunk(C, bucket, pool is not None)

        def _dispatch():
            self._observe_gap()
            # Hang-injection point for the chunk itself
            # (``continuous.prefill``): fire() sleeps inline, so a ``hang``
            # spec wedges THIS disposable thread under the watchdog budget —
            # the mid-chunk twin of ``continuous.step``.
            _failpoints.fire("continuous.prefill")
            if self._loop_epoch != epoch:
                raise _StaleStep("prefill chunk fenced before dispatch")
            note_device_dispatch("continuous prefill chunk")
            with LATENCY.span("continuous.dispatch", chunk=chunk_no):
                # (logits, staging cache[, the chunk's k and v columns], aux,
                # the lane's recurrent state)
                logits, new_cache, *cols, aux, new_state = fn(
                    self.engine.params, jnp.asarray(chunk), cache,
                    jnp.int32(start), jnp.int32(valid), state=lane_state,
                )
                if self._loop_epoch != epoch:
                    raise _StaleStep("prefill chunk fenced post-dispatch")
                if pool is not None:
                    pool.scatter_tokens(*cols, slot_idx)
            # While the device runs this turn, build the bucket's other
            # lengths from what it returned: a later turn's arguments but for
            # the tokens' length.
            for c in unbuilt:
                self._compile_chunk(c, bucket, new_cache, new_state, pool, cols)
            # Synchronize on the (tiny) logits readback so the watchdog
            # budget covers the device work, like the step's readback.
            _, aux = self._readback((logits, aux))
            note_model_aux(aux)
            return logits, new_cache, new_state

        # Deliberately NOT fed to observe_step: a C-token chunk would pollute
        # the decode loop's per-step EWMA.
        self._close_host()
        with LATENCY.span("continuous.prefill_chunk") as chunk_span:
            (first_logits, new_cache, new_state), _ = self._hand_off(
                _dispatch, "prefill chunk"
            )
        self._open_host()
        with LATENCY.span("continuous.admit"):
            with self._lock:
                if self._loop_epoch != epoch or self._prefilling is not pf:
                    return
                pf.cache = new_cache
                pf.state = new_state
                pf.cursor = end
                req.chunk_cursor = end
                self._chunk_built.add(bucket)
                self._stats["prefill_chunks"] += 1
                self._stats["prefill_tokens"] += valid
                if self._active_mask.any():
                    self._stats["prefill_interleaved"] += 1
                # A completed chunk is proof of life, like a completed step.
                self._consecutive_faults = 0
                if req.trace is not None:
                    req.trace.add_phase("prefill", chunk_span.seconds)
                if final:
                    self._prefilling = None
                    self._finish_prefilling_locked(pf, first_logits)
                    self._lock.notify_all()

    def _compile_chunk(self, c: int, bucket: int, cache, lane_state, pool, cols) -> None:
        """Build the ``c``-token chunk program of ``bucket`` ahead of its
        first turn (step thread, under the watchdog's compile exemption): the
        jitted call's own lowering and executable, from a turn's arguments
        with the tokens' length changed, and for a paged loop the pool's
        scatter of that many columns (``cols``: another length's, as a turn
        hands them to ``scatter_tokens``)."""
        fn = self.engine._get_prefill_chunk(c, bucket, pool is not None)
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        fn.lower(
            self.engine.params, jax.ShapeDtypeStruct((1, c), jnp.int32), cache,
            scalar, scalar, state=lane_state,
        ).compile()
        if pool is not None:
            # A committed array's sharding is part of the jitted call's cache
            # key, an uncommitted one's is not: say what the real columns say.
            pool.compile_scatter(*(
                jax.ShapeDtypeStruct(
                    (col.shape[0], c, *col.shape[2:]), col.dtype,
                    sharding=col.sharding if col.committed else None,
                )
                for col in cols
            ))

    def _finish_prefilling_locked(self, pf: _Prefilling, first_logits) -> None:
        """Transition PREFILLING -> DECODING (lock held): install the fully
        ingested prompt KV as the rows' prefix (block tables in paged mode,
        the dense per-slot prefix otherwise), populate the prefix cache so
        followers reuse the chunked prompt like any other, then run the
        shared admission tail — first token from the LAST chunk's logits
        with the submission-pinned seed."""
        engine = self.engine
        req, rows = pf.req, pf.rows
        cached = getattr(engine, "prefix_cache_size", 0) > 0
        if self.paged:
            # The page books first: they are the host's alone, and the device
            # has nothing to run until the programs below are launched.
            self._pages.install(rows, pf.run_pages, pf.reserved, pf.plen)
            if cached:
                # The entry's reference is one more on the run.
                engine._prefix_store_paged_run(
                    pf.ids, first_logits,
                    self._pages.prefix_run(pf.run_pages, pf.plen, pf.bucket),
                )
        with self._admit_device_span():
            self._install_state(rows, pf.state)
            if not self.paged:
                self._dense.install(rows, pf.cache, pf.bucket)
                if cached:
                    engine._prefix_store(pf.ids, first_logits, pf.cache)
            first = self._sample_first(req, len(rows), first_logits)
        self._admit_rows(req, rows, first)

    def _retire_prefilling_locked(
        self, exc: BaseException, abort: bool = False
    ) -> None:
        """Retire the PREFILLING admission before it ever decoded (lock
        held): return its slots, release its pages (the run holds one
        reference per row plus each row's reserve), and fail the future.
        ``abort`` routes through the decode-abort counters — budget aborts
        on a PREFILLING row share the decoding rows' fault domain."""
        pf = self._prefilling
        if pf is None:
            return
        self._prefilling = None
        req = pf.req
        if pf.run_pages is not None:
            self._pages.drop(len(pf.rows), pf.run_pages, pf.reserved)
        for slot in pf.rows:
            self._free.append(slot)
        req.slots = []
        if abort:
            FAILURE_EVENTS.record("engine.decode_abort")
            self._stats["aborted"] += 1
        if not req.future.done():
            req.future.set_exception(exc)
        self._lock.notify_all()

    def _pack_step(self, *books: np.ndarray) -> np.ndarray:
        """A decode step's host inputs as one fresh ``int32 [W, C]`` array
        (lock held): a copy of the slot mirrors, a column each in
        :class:`_StepRows`' order (``seeds``, ``temps`` and ``top_ps`` by their
        bits, a drafting loop's ``draft`` and ``room``: ``len(tokens)`` is
        ``gen_lens + 1``, room for two more), then ``books``, what
        :meth:`SlotPages.prepare_step` returned: the rows' write slots and
        their block tables (nothing in a dense loop). The columns are fixed
        for a loop build."""
        columns = [
            self._cur, self._gen_lens, self._prompt_lens, self._active_mask,
            self._seeds.view(np.int32), self._sample_idx,
            self._temps.view(np.int32), self._top_ps.view(np.int32),
            self._g_states, self._g_flags,
        ]
        if self._drafting:
            columns += [self._draft, self._gen_lens + 3 <= self._max_news]
        named = len(columns)
        packed = np.empty((self.width, named + sum(a.shape[1] for a in books)), np.int32)
        for at, column in enumerate(columns):
            packed[:, at] = column
        at = named
        for block in books:
            packed[:, at:at + block.shape[1]] = block
            at += block.shape[1]
        return packed

    def _step_once(self) -> None:
        with LATENCY.span("continuous.prepare"):
            # The span's three parts, each a span of its own: the wait for the
            # loop lock (submitters hold it), the page books, the uploads.
            lock_wait = LATENCY.span("continuous.lock_wait")
            lock_wait.__enter__()
            with self._lock:
                lock_wait.__exit__(None, None, None)
                epoch = self._loop_epoch
                step_no = self._stats["steps"]
                live_rows = np.flatnonzero(self._active_mask)
                # Grammar twins run only when a constrained row is live: steps
                # with no grammar work dispatch the ORIGINAL programs, so the
                # unconstrained loop stays byte-identical (and
                # program-identical).
                n_masked = int((self._g_flags & self._active_mask).sum())
                step_fn = self._grammar_programs()["step"] if n_masked else self._step_fn
                # A loop with page books keeps them here, ahead of the upload:
                # table growth and copy-on-write for the rows' next write,
                # which yield the rows' tables and write slots.
                pool, dense, state = self._pool, self._dense, self._state
                books: tuple = ()
                pages = None
                if self._pages is not None:
                    with LATENCY.span("continuous.pages"):
                        lens = (self._active_mask, self._prompt_lens, self._gen_lens)
                        books = self._pages.prepare_step(*lens)
                        # The fused kernel's walk, counted here where the
                        # lengths are coherent (the XLA path gathers whole tables).
                        if self._paged_attn_impl != "xla":
                            pages = self._pages.walk_counts(
                                *lens, windows=self.engine.config.layer_windows
                            )
                # Everything the step takes from the host goes up here, as
                # one array: a snapshot of the mirrors (a copy, so the
                # dispatch thread's view of it stays coherent) and the books.
                with LATENCY.span("continuous.stage"):
                    packed = jnp.asarray(self._pack_step(*books))
                uploaded = [packed]
                # The resident grammar's tables live on the device already.
                grammar_tabs = self._g_tabs() if n_masked else ()
            # All-False in production; with an active ``engine.logits`` nan
            # failpoint, a seeded subset of the LIVE rows is poisoned — the
            # loop-scoped twin of the batch path's first-step injection.
            poison = self.engine._poison0_array(
                # kllms: ignore[host-sync-hot-path] — live_rows is np.flatnonzero output (already host memory); this tolist is pure host bookkeeping, not a device readback
                self.width, live_rows=live_rows.tolist()
            )
            if poison is not self.engine._no_poison(self.width):
                uploaded.append(poison)

        def _dispatch():
            self._observe_gap()
            # Hang-injection point for the step itself (``continuous.step``):
            # fire() sleeps inline, so a ``hang`` spec wedges THIS disposable
            # thread under the watchdog budget, exactly like a stuck device.
            _failpoints.fire("continuous.step")
            if self._loop_epoch != epoch:
                raise _StaleStep("continuous step fenced before dispatch")

            def _launch(what, *layout_state):
                note_device_dispatch(what)
                with LATENCY.span("continuous.dispatch", step=step_no):
                    out = step_fn(
                        self.engine.params, *layout_state, packed, poison,
                        *grammar_tabs, state=state,
                    )
                # An abandoned thread waking into a rebuilt loop must not
                # clobber the new KV with the old epoch's.
                if self._loop_epoch != epoch:
                    raise _StaleStep("continuous step fenced post-dispatch")
                # The donated state's successor, like the KV's below.
                *out, self._state = out
                return out

            if pool is not None:
                note_paged_attn_dispatch(self._paged_attn_impl)
                if pages is not None:
                    walked, tabled, windowed_out = pages
                    PAGED_ATTN_PAGES.record("paged_attn_pages_walked", walked)
                    PAGED_ATTN_PAGES.record("paged_attn_pages_tabled", tabled)
                    PAGED_ATTN_PAGES.record("paged_attn_pages_windowed_out", windowed_out)
                # The pool's buffers are donated to the step, so ``pool.kv``
                # must point at the returned ones before anyone else can
                # dispatch: dispatch-and-swap under the pool lock.
                with pool.lock:
                    tok, lp, bad, new_k, new_v, *new_g, aux = _launch(
                        "continuous paged step", pool.kv.k, pool.kv.v
                    )
                    pool.kv = KVCache(k=new_k, v=new_v)
            else:
                tok, lp, bad, gen, *new_g, aux = _launch(
                    "continuous dense step", dense.prefix, dense.gen
                )
                dense.gen = gen
            # The one by-design sync per step: slot bookkeeping below needs
            # the sampled token ids on the host, and it runs outside both
            # locks (advanced grammar states ride the same fetch).
            outs = (tok, lp, bad, *new_g)
            fetched, aux = self._readback((outs, aux))
            note_model_aux(aux)
            return list(map(np.asarray, fetched))

        # Host wall time for the dispatched step (includes the by-design
        # result readback); pure host-side observability, no extra syncs.
        self._close_host()
        with LATENCY.span("continuous.step") as step_span:
            fetched, run_s = self._hand_off(_dispatch, "step")
        self._open_host()
        if run_s is not None:
            self.budget_model.observe_step(run_s)
        step_s = step_span.seconds
        with LATENCY.span("continuous.bookkeep"):
            tok_np, lp_np, bad_np = fetched[0], fetched[1], fetched[2]
            if self._drafting:
                # One or two tokens a row: [W, 2] tokens and logprobs, the
                # emitted counts, and each row's next draft.
                emitted_np, draft_np = fetched[-2], fetched[-1]
            else:
                tok_np, lp_np = tok_np[:, None], lp_np[:, None]
                emitted_np = draft_np = None
            quarantined = 0
            with self._lock:
                if n_masked:
                    # .copy(): device_get may hand back a read-only view, and the
                    # mirror is written per-slot at admission/retirement.
                    self._g_states = fetched[3].copy()
                    # A drafted step masks each token it emits.
                    GRAMMAR_EVENTS.record(
                        "grammar.masked_steps",
                        n_masked if emitted_np is None
                        else int(emitted_np[self._g_flags & self._active_mask].sum()),
                    )
                if emitted_np is not None:
                    live = int(self._active_mask.sum())
                    SPEC_COUNTERS.record("spec_drafts_verified", live)
                    SPEC_COUNTERS.record("spec_drafts_accepted", int((emitted_np == 2).sum()))
                    SPEC_COUNTERS.record("spec_tokens_emitted", int(emitted_np.sum()))
                self._stats["steps"] += 1
                self._stats["row_steps"] += int(self._active_mask.sum())
                self._stats["stage_uploads"] += len(uploaded)
                self._stats["stage_bytes"] += sum(int(a.nbytes) for a in uploaded)
                self._stats["max_active_rows"] = max(
                    self._stats["max_active_rows"], int(self._active_mask.sum())
                )
                # A completed step is proof of life: recovery credits refill so
                # intermittent faults don't accumulate toward terminal.
                self._consecutive_faults = 0
                touched = set()
                for slot in range(self.width):
                    req = self._active[slot]
                    if req is None:
                        continue
                    j = req.slots.index(slot)
                    if req.done[j]:
                        continue
                    took = 1 if emitted_np is None else int(emitted_np[slot])
                    # cur's KV is now written, and an accepted draft's.
                    self._gen_lens[slot] += took
                    if bad_np[slot]:
                        # Numeric poison: freeze + retire this row only; its
                        # garbage token never reaches the accumulators or sinks.
                        self._quarantine_row(req, j)
                        quarantined += 1
                        touched.add(id(req))
                        continue
                    if draft_np is not None:
                        self._draft[slot] = draft_np[slot]
                        if req.trace is not None:
                            req.trace.bump("drafts_verified")
                            req.trace.bump("drafts_accepted", took - 1)
                    for k in range(took):
                        # The device emits a second token only where the first
                        # neither ends the row nor fills it.
                        t = int(tok_np[slot, k])
                        self._cur[slot] = t
                        req.tokens[j].append(t)
                        req.logprobs[j].append(float(lp_np[slot, k]))
                        if t in self.eos_ids:
                            req.done[j] = True
                            req.finish[j] = "stop"
                        elif len(req.tokens[j]) >= req.max_new:
                            req.done[j] = True
                            req.finish[j] = "length"
                    touched.add(id(req))
                for rid in touched:
                    req = next(
                        r for r in self._active if r is not None and id(r) == rid
                    )
                    if req.trace is not None:
                        req.trace.add_phase("decode", step_s)
                    if req.budget is not None and req.budget.should_abort():
                        self._abort_request(req)
                        continue
                    self._deliver_ready(req)
                    self._retire_finished_rows(req)
                    self._resolve_if_done(req)
                self._lock.notify_all()
            # Quarantine accounting + supervisor hook OUTSIDE the loop lock (it
            # fans out to scheduler/supervisor locks); clean steps report 0 so
            # the escalation window decays, same contract as the batch path.
            note = getattr(self.engine, "_note_quarantine", None)
            if note is not None:
                note(quarantined, int(live_rows.size))

    # -- retirement --------------------------------------------------------

    def _deliver_ready(self, req: _SlotRequest) -> None:
        """After a step: deliver every token index that each of the request's
        unfinished rows has reached (one a step in the one-token loop, where
        its rows march in lockstep; a drafted step moves a row by one or two)."""
        if req.token_sink is None:
            return
        open_rows = [len(s) for s, done in zip(req.tokens, req.done) if not done]
        ready = min(open_rows) if open_rows else max(len(s) for s in req.tokens)
        while req.token_sink is not None and req.steps_delivered < ready:
            self._deliver_sink(req)

    def _deliver_sink(self, req: _SlotRequest) -> None:
        if req.token_sink is None:
            return
        step = req.steps_delivered
        req.steps_delivered += 1
        # Replay de-duplication: steps below the watermark were already
        # delivered before the fault; the rebuilt loop regenerates them
        # byte-identically (self-deterministic keys) but must not re-send
        # them — the SSE consumer sees one contiguous stream.
        if step < req.delivered_watermark:
            return
        # Every live sample has produced its step-th token (the caller's
        # business: :meth:`_deliver_ready`); finished rows report pad
        # thereafter, which the sink's detokenizer skips.
        pad = self.engine.config.pad_token_id
        with LATENCY.span("continuous.emit"):
            row = np.array(
                [
                    s[step] if step < len(s) else pad
                    for s in req.tokens
                ],
                np.int32,
            )
            try:
                req.token_sink(step, row)
            except Exception:
                logger.exception("continuous token sink failed; dropping tap")
                req.token_sink = None

    def _retire_finished_rows(self, req: _SlotRequest) -> None:
        for j, slot in enumerate(list(req.slots)):
            if req.done[j] and self._active[slot] is req and self._active_mask[slot]:
                self._active_mask[slot] = False
                self._cur[slot] = self.engine.config.pad_token_id
                self._active[slot] = None
                self._g_flags[slot] = False
                self._g_states[slot] = 0
                self._draft[slot] = self.engine.config.pad_token_id
                if self._pages is not None:
                    self._pages.release(slot)
                self._free.append(slot)

    def _resolve_if_done(self, req: _SlotRequest) -> None:
        if not all(req.done):
            return
        # Flush any trailing sink steps (rows finish at different lengths;
        # the longest row's final tokens may not have been delivered yet).
        if req.token_sink is not None:
            longest = max(len(s) for s in req.tokens)
            while req.steps_delivered < longest:
                self._deliver_sink(req)
        pad = self.engine.config.pad_token_id
        toks = np.full((req.n, req.max_new), pad, np.int32)
        lps = np.zeros((req.n, req.max_new), np.float32)
        lengths = np.zeros((req.n,), np.int32)
        errs = list(req.sample_errors)
        while len(errs) < req.n:
            errs.append(None)
        for j in range(req.n):
            if errs[j] is not None:
                # Quarantined member: wiped like the batch path's
                # _quarantine_result (tokens→pad, logprobs→0, length→0) so
                # survivor consensus drops it from the vote.
                continue
            L = len(req.tokens[j])
            # eos is recorded in the buffer like the batch loop (lengths count
            # non-pad tokens; the backend strips stop ids from the text).
            toks[j, :L] = req.tokens[j]
            lps[j, :L] = req.logprobs[j]
            lengths[j] = L
        result = GenerationResult(
            tokens=toks,
            logprobs=lps,
            lengths=lengths,
            finish_reasons=list(req.finish),
            prompt_len=req.prompt_len,
            spec_stats={},
            sample_errors=errs if any(e is not None for e in errs) else None,
        )
        self._stats["completed"] += 1
        decode_wall_s = time.perf_counter() - req.installed_at
        LATENCY.observe("continuous.decode_wall", decode_wall_s)
        if req.trace is not None:
            req.trace.add_phase("decode_wall", decode_wall_s)
            notes = req.trace.annotations_snapshot()
            if notes.get("drafts_verified"):
                req.trace.annotate(
                    "draft_accepted_share",
                    notes.get("drafts_accepted", 0) / notes["drafts_verified"],
                )
        if not req.future.done():
            req.future.set_result(result)

    def _abort_request(self, req: _SlotRequest) -> None:
        FAILURE_EVENTS.record("engine.decode_abort")
        for j in range(req.n):
            req.done[j] = True
        self._retire_finished_rows(req)
        self._stats["aborted"] += 1
        if not req.future.done():
            req.future.set_exception(req.budget.error("engine decode"))

    def _fail_all(self, exc: BaseException) -> None:
        with self._lock:
            reqs = {id(r): r for r in self._active if r is not None}
            for req in reqs.values():
                for j in range(len(req.done)):
                    req.done[j] = True
                try:
                    self._retire_finished_rows(req)
                except PageAccountingError:
                    # Containment must complete even over a corrupt
                    # allocator: drop the slots without decref (the pool is
                    # already quarantined) so every future still resolves.
                    logger.exception(
                        "page release failed during fail-all; dropping slots"
                    )
                    for slot in list(req.slots):
                        if self._active[slot] is req:
                            self._active[slot] = None
                            self._active_mask[slot] = False
                            self._pages.forget(slot)
                            self._free.append(slot)
                if not req.future.done():
                    req.future.set_exception(exc)
            if self._prefilling is not None:
                self._retire_prefilling_locked(exc)
            for req in self._queue:
                if not req.future.done():
                    req.future.set_exception(exc)
            self._queue.clear()
            self._lock.notify_all()
