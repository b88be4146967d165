"""Tracing, metrics, and logging.

The reference has no tracing/profiling (SURVEY.md §5) — only a DEBUG logger
gated on ``ENV_NAME=dev`` (`consensus_utils.py:45-50`), which we keep. The
request-scoped tracing/histogram/flight-recorder layer lives in
``k_llms_tpu/observability/`` and is re-exported here; this module keeps the
``EventCounters`` groups (the process-wide counter vocabularies), the
``jax.profiler`` wrapper for device traces, and consensus-confidence
histograms. ``Trace`` is now an alias of the thread-safe ``RequestTrace``
(the old two-phase timer mutated ``durations`` without a lock; the stream
sink thread and the caller can time phases concurrently).
"""

from __future__ import annotations

import contextlib
import fnmatch
import logging
import os
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from ..analysis.lockcheck import make_lock
from ..observability import (  # noqa: F401  (re-exported surface)
    FLIGHT_RECORDER,
    FlightRecorder,
    LATENCY,
    LatencyHistograms,
    NOOP_TRACE,
    NoopTrace,
    RequestTrace,
    TRACER,
    Tracer,
    current_trace,
    format_traceparent,
    parse_traceparent,
    use_trace,
)

#: Back-compat alias: the request-phase timer existing call sites construct
#: directly. Same ``phase()``/``as_dict()`` surface, now lock-guarded.
Trace = RequestTrace


def configure_logging() -> logging.Logger:
    """Package logger; DEBUG iff ENV_NAME=dev (reference parity)."""
    logger = logging.getLogger("k_llms_tpu")
    if os.getenv("ENV_NAME") == "dev":
        logger.setLevel(logging.DEBUG)
    else:
        logger.setLevel(logging.INFO)
    return logger


@contextlib.contextmanager
def device_profiler(
    log_dir: Optional[str] = None,
    python_tracer: bool = False,
    host_tracer_level: int = 2,
) -> Iterator[None]:
    """jax.profiler trace around a block (view with TensorBoard/Perfetto).
    No-ops when log_dir is None and KLLMS_PROFILE_DIR is unset.

    With the Python tracer off (the default) the host planes hold the
    program's own ``TraceAnnotation`` events — every ``LATENCY.span`` and the
    loop's ``continuous.host`` — under their plain names, and the host runs at
    its own speed inside the capture; ``python_tracer=True`` adds one event per
    Python call and slows the traced threads enough to misread the device's
    idle share (PERF.md §6, PR 25). Either way the call returns only once the
    device plane is serialized, which takes far longer than the capture."""
    import jax

    log_dir = log_dir or os.getenv("KLLMS_PROFILE_DIR")
    if not log_dir:
        yield
        return
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 1 if python_tracer else 0
    options.host_tracer_level = int(host_tracer_level)
    with jax.profiler.trace(log_dir, profiler_options=options):
        yield


class EventCounters:
    """Thread-safe named counters for failure-path events (retries, circuit
    trips, deadline sheds, decode aborts, failpoint kills). Cheap enough to
    record from the scheduler worker and dispatch paths; snapshot from tests
    or a stats endpoint.

    ``declared`` is the group's counter vocabulary: literal names plus
    fnmatch wildcards for keyed families (``request.*``). Recording a name
    outside it raises — a typo'd counter that silently lands in its own
    bucket is invisible on every dashboard that queries the real name. The
    ``counter-hygiene`` lint statically checks every record() literal against
    the same patterns, so the declaration is enforced both ways."""

    def __init__(self, declared: Optional[Sequence[str]] = None) -> None:
        self._lock = make_lock("observability.counters")
        self._counts: Dict[str, int] = {}
        self.declared: Tuple[str, ...] = tuple(declared or ())
        self._exact = {
            p for p in self.declared if "*" not in p and "?" not in p
        }
        self._globs = [p for p in self.declared if p not in self._exact]

    def _check_declared(self, event: str) -> None:
        if not self.declared or event in self._exact:
            return
        if any(fnmatch.fnmatch(event, p) for p in self._globs):
            return
        raise ValueError(
            f"counter {event!r} is not declared for this group "
            f"(declared: {sorted(self.declared)})"
        )

    def record(self, event: str, n: int = 1) -> None:
        self._check_declared(event)
        with self._lock:
            self._counts[event] = self._counts.get(event, 0) + n

    def get(self, event: str) -> int:
        with self._lock:
            return self._counts.get(event, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


#: Process-wide failure-event counters shared by the reliability layer
#: (retry attempts, circuit transitions), the scheduler (deadline sheds,
#: cancellations), and the engine (decode aborts, killed samples).
FAILURE_EVENTS = EventCounters(declared=(
    "scheduler.shed",
    "scheduler.shed_stopped",
    "scheduler.shed_over_capacity",
    "scheduler.shed_draining",
    "engine.decode_abort",
    "engine.samples_killed",
    "engine.oom",
    "engine.oom_unrecovered",
    "engine.oom_split",
    "retry.attempt",
    "circuit.rejected",
    "circuit.opened",
    "consensus.zero_survivors",
))

#: Process-wide speculative-decoding counters (spec.launches, spec.drafted,
#: spec.accepted), fed by EngineScheduler.note_spec_stats from the engine's
#: per-launch on_spec_stats hook. spec.accepted / spec.drafted is the
#: fleet-level acceptance rate operators tune spec_lookahead against.
SPEC_EVENTS = EventCounters(declared=(
    "spec.launches",
    "spec.drafted",
    "spec.accepted",
))

#: Process-wide self-healing counters (supervisor.hung_launches,
#: supervisor.rebuilds, supervisor.rebuild_failures, supervisor.replayed,
#: supervisor.stale_results_discarded), fed by the EngineSupervisor, plus the
#: continuous decode loop's fault-domain counters (continuous.step_hangs —
#: per-step dispatches the loop watchdog abandoned; continuous.worker_crashes
#: — worker threads killed by an unexpected host exception;
#: continuous.restarts — loop recoveries of either kind that rebuilt/restarted
#: the decode loop; continuous.replayed_rows — journaled in-flight rows
#: re-admitted and replayed after a rebuild; continuous.stale_steps_discarded
#: — epoch-fenced results from abandoned step threads that landed late and
#: were dropped; continuous.pool_quarantined — page-accounting faults that
#: quarantined the pool for rebuild instead of poisoning health polls), fed by
#: ContinuousDecodeLoop. A nonzero rebuild count on a healthy fleet is the
#: "devices are flaking" alarm.
RECOVERY_EVENTS = EventCounters(declared=(
    "supervisor.hung_launches",
    "supervisor.rebuilds",
    "supervisor.rebuild_failures",
    "supervisor.replayed",
    "supervisor.stale_results_discarded",
    "continuous.step_hangs",
    "continuous.worker_crashes",
    "continuous.restarts",
    "continuous.replayed_rows",
    "continuous.stale_steps_discarded",
    "continuous.pool_quarantined",
))

#: Process-wide replica-routing counters (route.dispatched, route.pulled —
#: members removed from rotation, route.probes / route.probe_failures /
#: route.rejoins — probation lifecycle, route.no_healthy — requests that found
#: zero eligible members), fed by the ReplicaSet router.
ROUTE_EVENTS = EventCounters(declared=(
    "route.dispatched",
    "route.pulled",
    "route.probes",
    "route.probe_failures",
    "route.rejoins",
    "route.no_healthy",
))

#: Process-wide hedged-dispatch counters (hedge.launched, hedge.won_primary,
#: hedge.won_hedge, hedge.cancelled_losers). hedge.won_hedge / hedge.launched
#: is the rescue rate: how often duplicating the tail actually paid off.
HEDGE_EVENTS = EventCounters(declared=(
    "hedge.launched",
    "hedge.won_primary",
    "hedge.won_hedge",
    "hedge.cancelled_losers",
))

#: Process-wide mid-flight failover counters (failover.attempts,
#: failover.member_down, failover.exhausted). Nonzero failover on a healthy
#: fleet means a member is flapping faster than its probes rejoin it.
FAILOVER_EVENTS = EventCounters(declared=(
    "failover.attempts",
    "failover.member_down",
    "failover.exhausted",
))

#: Process-wide numeric-integrity counters (quarantine.samples — decode rows
#: quarantined for NaN/Inf/degenerate logits, quarantine.launches — launches
#: with at least one poisoned row, quarantine.checksum_failures — corrupted
#: checkpoints rejected at load). Poison on a healthy fleet means bad HBM or a
#: bad checkpoint, not bad luck.
QUARANTINE_EVENTS = EventCounters(declared=(
    "quarantine.samples",
    "quarantine.launches",
    "quarantine.checksum_failures",
))


#: Process-wide HTTP-serving counters (request.<route>.<status> — one per
#: completed request keyed by route and HTTP status, plus request.disconnect
#: for clients that dropped before the response finished), fed by the ASGI
#: app in ``serving/app.py`` and surfaced verbatim on ``/metrics``.
SERVE_EVENTS = EventCounters(declared=(
    "request.*",  # request.<route>.<status> + request.disconnect, keyed per route
))

#: Process-wide on-device consensus counters (consensus.device_dispatch /
#: consensus.host_dispatch — which path a consolidation's similarity prep
#: took; consensus.fallback_failpoint / consensus.fallback_error /
#: consensus.fallback_unavailable — why a device prepare degraded to host;
#: consensus.device_busy — pair batches routed to the host Levenshtein because
#: the chip lock was held; consensus.device_pairs / consensus.host_pairs /
#: consensus.cached_pairs — where pair similarities came from;
#: consensus.device_cosine — embedding pairs scored by the batched cosine
#: kernel (ISSUE 18); consensus.device_votes — vote columns tallied in the
#: batched kernel), fed by consensus/device.py and surfaced via scheduler
#: health and ``/metrics``.
CONSENSUS_EVENTS = EventCounters(declared=(
    "consensus.device_dispatch",
    "consensus.host_dispatch",
    "consensus.fallback_failpoint",
    "consensus.fallback_error",
    "consensus.fallback_unavailable",
    "consensus.device_busy",
    "consensus.device_pairs",
    "consensus.host_pairs",
    "consensus.cached_pairs",
    "consensus.device_cosine",
    "consensus.device_votes",
))

#: Process-wide accelerator-kernel counters (kernel.paged_attn_pallas_dispatch
#: / kernel.paged_attn_xla_dispatch — which paged-attention implementation a
#: decode launch or continuous paged step dispatched, recorded host-side per
#: launch, not per token; kernel.paged_attn_fallback.<reason> — an explicit
#: "pallas" request degraded to the XLA reference, with the reason suffix
#: naming what blocked it: ``failpoint`` (the ops.paged_attn failpoint),
#: ``softcap`` / ``sliding_window`` (model config the kernels don't cover —
#: capability-driven; a sliding window is served, on every layer or on some
#: unrolled ones, and a latent cache by its own kernel),
#: or ``platform`` (no TPU — environment-driven); "auto"
#: choosing XLA on CPU is the documented posture and is NOT counted as a
#: fallback), fed by ops/paged_attention.py and surfaced via scheduler
#: stats/health and ``/metrics`` as ``kllms_kernel_*``.
KERNEL_EVENTS = EventCounters(declared=(
    "kernel.paged_attn_pallas_dispatch",
    "kernel.paged_attn_xla_dispatch",
    "kernel.paged_attn_fallback.*",
))

#: Process-wide constrained-decoding counters (grammar.compile — a schema ×
#: vocabulary pair was lifted into packed token masks; grammar.hit /
#: grammar.miss — process-wide TTL-cache traffic (hits are the fleet-sharing
#: win: ReplicaSet members with the same tokenizer reuse one compile);
#: grammar.fallback_unsupported — a schema feature the byte-DFA compiler
#: doesn't cover degraded the mask to the generic JSON grammar, post-hoc
#: validation stays authoritative; grammar.fallback_failpoint /
#: grammar.fallback_error — the engine.grammar failpoint or a compile error
#: degraded the request to unconstrained decode + post-hoc validation;
#: grammar.masked_steps — decode steps that sampled under a grammar mask,
#: recorded host-side per generate/step, never inside the jitted loop), fed
#: by engine/grammar.py and the backends, surfaced via scheduler stats/health
#: and ``/metrics`` as ``kllms_grammar_*``.
GRAMMAR_EVENTS = EventCounters(declared=(
    "grammar.compile",
    "grammar.hit",
    "grammar.miss",
    "grammar.fallback_unsupported",
    "grammar.fallback_failpoint",
    "grammar.fallback_error",
    "grammar.masked_steps",
))

#: What a model's own stack counts inside the loop's programs, added up at
#: readback by :func:`note_model_aux` and exported unlabeled as
#: ``kllms_<name>`` on ``/metrics``. ``moe_layer_calls`` — expert layers run
#: (layers x program calls); ``moe_pairs`` — token-expert pairs computed;
#: ``moe_experts_touched`` — experts with at least one token, summed over
#: layers and calls (times the bytes of one expert: what the grouped products
#: streamed); ``moe_max_load`` — the busiest expert's token count, summed
#: likewise; ``mla_latent_rows_read`` — latent cache rows the paged decode
#: steps attended, summed over rows and layers; ``ssm_state_updates`` — the
#: recurrent states a program call advanced (rows with a valid token x
#: state-space layers: a decode step counts its live rows, a chunk or a whole
#: prompt one row; times the bytes of one state, read and written: what the
#: update streamed); ``ssm_tokens_scanned`` — valid tokens x state-space
#: layers; ``gdn_state_updates`` and ``gdn_tokens_scanned`` — the same two
#: counts over the gated delta-rule layers. A model with no routed experts, no
#: latent cache, no state-space or no delta-rule layer leaves those counters at
#: zero.
MODEL_COUNTERS = EventCounters(declared=(
    "moe_layer_calls",
    "moe_pairs",
    "moe_experts_touched",
    "moe_max_load",
    "mla_latent_rows_read",
    "ssm_state_updates",
    "ssm_tokens_scanned",
    "gdn_state_updates",
    "gdn_tokens_scanned",
))


#: How much of its block tables the fused paged-decode kernel walks, added up
#: a continuous decode step on the host (``engine/continuous.py::_step_once``,
#: beside the dispatch counter) and exported unlabeled as ``kllms_<name>`` on
#: ``/metrics``. All three count layer-pages: a page once for every paging
#: layer (``SlotPages.walk_counts``), each layer under its own window.
#: ``paged_attn_pages_walked`` — pages holding a position some live row
#: attends to (``live_pages``, summed over rows and layers: what the kernel
#: fetches a step); ``paged_attn_pages_tabled`` — rows x table pages x layers,
#: what a walk of whole tables would fetch; ``paged_attn_pages_windowed_out`` —
#: pages that hold a position in the pool and lie before a layer's sliding
#: window's first page, so that layer's walk starts past them (0 while no row
#: outgrows a window). Zero where the XLA path serves.
PAGED_ATTN_PAGES = EventCounters(declared=(
    "paged_attn_pages_walked",
    "paged_attn_pages_tabled",
    "paged_attn_pages_windowed_out",
))


#: What the drafted loop's steps verified (``engine/continuous.py``: a model
#: with a next-token module), added up from each step's readback and exported
#: unlabeled as ``kllms_<name>`` on ``/metrics``; zero for every other model.
#: ``spec_drafts_verified`` — drafts a step checked (one a live row a step);
#: ``spec_drafts_accepted`` — those whose second token was emitted;
#: ``spec_tokens_emitted`` — tokens the steps emitted (one or two a row step).
SPEC_COUNTERS = EventCounters(declared=(
    "spec_drafts_verified",
    "spec_drafts_accepted",
    "spec_tokens_emitted",
))


def note_model_aux(aux: Dict[str, Any]) -> None:
    """Add one program call's ``aux`` (host arrays: see models/latent.py and
    models/hybrid.py) to
    :data:`MODEL_COUNTERS`. ``moe_counts`` is ``[expert layers, experts]``
    tokens per expert over the rows the call computed."""
    counts = aux.get("moe_counts")
    if counts is not None:
        MODEL_COUNTERS.record("moe_layer_calls", int(counts.shape[0]))
        MODEL_COUNTERS.record("moe_pairs", int(counts.sum()))
        MODEL_COUNTERS.record("moe_experts_touched", int((counts > 0).sum()))
        MODEL_COUNTERS.record("moe_max_load", int(counts.max(axis=-1).sum()))
    if aux.get("mla_latent_rows_read") is not None:
        MODEL_COUNTERS.record("mla_latent_rows_read", int(aux["mla_latent_rows_read"]))
    if aux.get("ssm_rows_updated") is not None:
        MODEL_COUNTERS.record("ssm_state_updates", int(aux["ssm_rows_updated"]))
        MODEL_COUNTERS.record("ssm_tokens_scanned", int(aux["ssm_tokens_scanned"]))
    if aux.get("gdn_rows_updated") is not None:
        MODEL_COUNTERS.record("gdn_state_updates", int(aux["gdn_rows_updated"]))
        MODEL_COUNTERS.record("gdn_tokens_scanned", int(aux["gdn_tokens_scanned"]))


#: Process-wide SSE-streaming counters (streams.opened, streams.completed,
#: streams.aborted — closed before the final consensus event, whether by
#: client disconnect or a mid-stream error — and tokens.streamed, the count
#: of delta chunks put on the wire). streams.aborted / streams.opened is the
#: stream-survival rate operators watch during deploys.
STREAM_EVENTS = EventCounters(declared=(
    "streams.opened",
    "streams.completed",
    "streams.aborted",
    "tokens.streamed",
    "streams.pings",  # SSE keep-alive comment frames (idle-gap heartbeats)
))


#: Process-wide multi-tenancy counters, all keyed by tenant name
#: (ISSUE 16). ``tenant.requests.<name>`` — requests attributed to a tenant
#: at the serving front door; ``tenant.admitted.<name>`` /
#: ``tenant.served.<name>`` — work that passed quota charge and work that
#: finished; ``tenant.shed_quota.<name>`` — typed 429s from the tenant's own
#: token buckets (incl. the ``scheduler.tenant=exhaust`` failpoint);
#: ``tenant.shed_brownout.<name>`` — batch-class work shed while the
#: scheduler is in brownout; ``tenant.shed_over_capacity.<name>`` /
#: ``tenant.evicted.<name>`` — per-tenant attribution of the global cap
#: sheds and priority evictions. Fed by ``engine/scheduler.py`` and
#: ``serving/app.py``; surfaced on ``/metrics`` as
#: ``kllms_tenant_events_total`` so fairness and brownout ordering are
#: provable from scrape output alone.
TENANT_EVENTS = EventCounters(declared=(
    "tenant.requests.*",
    "tenant.admitted.*",
    "tenant.served.*",
    "tenant.shed_quota.*",
    "tenant.shed_brownout.*",
    "tenant.shed_over_capacity.*",
    "tenant.evicted.*",
))


#: Process-wide offline-batch-lane counters (ISSUE 17). Job lifecycle:
#: ``batch.job_created`` — a POST /v1/batches submission journaled durably;
#: ``batch.job_recovered`` — an unfinished job re-admitted from the journal
#: after restart; ``batch.job_completed`` / ``batch.job_completed_with_errors``
#: — terminal outcomes (a poisoned item fails alone, the job still finishes);
#: ``batch.job_cancelled`` — explicit cancels. Item lifecycle:
#: ``batch.item_completed`` / ``batch.item_failed`` — exactly-once output
#: records committed (success vs typed-error capture);
#: ``batch.item_requeued`` — in-flight items checkpointed back to pending by
#: drain, a worker crash, or startup reconciliation. Durability drills:
#: ``batch.worker_crashes`` — lane worker threads killed (the
#: ``batch.worker=crash`` failpoint or a host bug); ``batch.store_torn_tail``
#: — journal tails truncated on recovery (a kill mid-append, or the
#: ``batch.store=torn`` failpoint); ``batch.job_swept`` — terminal jobs GC'd
#: by the ``jobstore_ttl_s`` sweep on store open (ISSUE 18). Fed by
#: ``reliability/jobstore.py`` and ``serving/batch.py``; surfaced on
#: ``/metrics`` as ``kllms_batch_events_total``.
BATCH_EVENTS = EventCounters(declared=(
    "batch.job_created",
    "batch.job_recovered",
    "batch.job_completed",
    "batch.job_completed_with_errors",
    "batch.job_cancelled",
    "batch.item_completed",
    "batch.item_failed",
    "batch.item_requeued",
    "batch.worker_crashes",
    "batch.store_torn_tail",
    "batch.job_swept",
))


def _walk_confidences(node: Any, out: List[float]) -> None:
    if isinstance(node, dict):
        for v in node.values():
            _walk_confidences(v, out)
    elif isinstance(node, (list, tuple)):
        for v in node:
            _walk_confidences(v, out)
    elif isinstance(node, (int, float)) and not isinstance(node, bool):
        out.append(float(node))


def confidence_histogram(likelihoods: Any, bins: int = 10) -> Dict[str, Any]:
    """Histogram + summary stats over every confidence in a likelihoods tree."""
    values: List[float] = []
    _walk_confidences(likelihoods, values)
    if not values:
        return {"count": 0, "histogram": [0] * bins, "mean": None, "min": None}
    counts = [0] * bins
    for v in values:
        idx = min(int(max(0.0, min(1.0, v)) * bins), bins - 1)
        counts[idx] += 1
    return {
        "count": len(values),
        "histogram": counts,
        "mean": round(sum(values) / len(values), 5),
        "min": round(min(values), 5),
    }
