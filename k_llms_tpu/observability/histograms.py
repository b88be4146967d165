"""Thread-safe log-bucketed latency histograms with declared vocabularies.

Same hygiene contract as ``EventCounters``: ``declared`` names the group's
histogram vocabulary (literals plus fnmatch wildcards), ``observe()`` raises
on anything outside it, and the ``counter-hygiene`` lint statically checks
every ``observe()`` literal against the same patterns — a typo'd histogram
that silently lands in its own family is invisible to every dashboard that
queries the real name.

Buckets are log-spaced seconds shared across families (1ms → 60s), rendered
on ``/metrics`` in Prometheus histogram exposition (cumulative ``_bucket``
counts, ``_sum``, ``_count``). Exactly-declared families export even at zero
observations, so the scrape surface is stable from the first poll.
"""

from __future__ import annotations

import bisect
import fnmatch
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..analysis.lockcheck import make_lock, race_exempt

#: Log-spaced bucket upper bounds in seconds (1-2.5-5 decades, 1ms → 60s).
#: The +Inf bucket is implicit: its cumulative count is the sample count.
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


class _Span:
    """One timed region of :meth:`LatencyHistograms.span`: a
    ``jax.profiler.TraceAnnotation`` held open for the block and one
    ``observe()`` of its ``perf_counter`` duration. ``seconds`` holds that
    duration once the block has ended, for callers that also add it to a
    request's phases."""

    __slots__ = ("_hist", "_name", "_annotation", "_t0", "seconds")

    def __init__(self, hist: "LatencyHistograms", name: str, annotation: Any) -> None:
        self._hist = hist
        self._name = name
        self._annotation = annotation
        self._t0 = 0.0
        self.seconds = 0.0

    def __enter__(self) -> "_Span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        # Like the hand-written ``t0 ... observe`` pairs this replaces: a
        # block that raised is not a sample of the region's latency.
        if exc_type is None:
            self._hist.observe(self._name, self.seconds)


class LatencyHistograms:
    """A group of named latency histograms sharing one bucket layout.

    ``observe(name, seconds)`` is cheap enough for the scheduler worker and
    the continuous loop's host bookkeeping (a bisect + three dict writes
    under a leaf lock); ``snapshot()`` returns cumulative bucket counts
    ready for Prometheus exposition."""

    def __init__(
        self,
        declared: Optional[Sequence[str]] = None,
        buckets: Sequence[float] = DEFAULT_BUCKETS,
    ) -> None:
        bounds = tuple(sorted(float(b) for b in buckets))
        if not bounds or any(b <= 0 for b in bounds) or len(set(bounds)) != len(bounds):
            raise ValueError("histogram buckets must be distinct positive bounds")
        self._lock = make_lock("observability.histograms")
        self.buckets = bounds
        self.declared: Tuple[str, ...] = tuple(declared or ())
        self._exact = {p for p in self.declared if "*" not in p and "?" not in p}
        self._globs = [p for p in self.declared if p not in self._exact]
        # Exact families pre-exist so /metrics exports them at zero samples.
        self._counts: Dict[str, List[int]] = {
            name: [0] * len(bounds) for name in sorted(self._exact)
        }
        self._sums: Dict[str, float] = {}
        self._totals: Dict[str, int] = {}
        # jax.profiler.TraceAnnotation, resolved by the first span(): importing
        # this module (and the package) stays free of jax.
        # kllms: unguarded — idempotent cache of one imported class; a lost race imports it twice
        self._annotation: Any = None
        race_exempt(self, "_annotation")

    def _check_declared(self, name: str) -> None:
        if not self.declared or name in self._exact:
            return
        if any(fnmatch.fnmatch(name, p) for p in self._globs):
            return
        raise ValueError(
            f"histogram {name!r} is not declared for this group "
            f"(declared: {sorted(self.declared)})"
        )

    def observe(self, name: str, seconds: float) -> None:
        self._check_declared(name)
        v = max(0.0, float(seconds))
        with self._lock:
            counts = self._counts.get(name)
            if counts is None:
                counts = self._counts[name] = [0] * len(self.buckets)
            i = bisect.bisect_left(self.buckets, v)
            if i < len(counts):
                counts[i] += 1
            self._sums[name] = self._sums.get(name, 0.0) + v
            self._totals[name] = self._totals.get(name, 0) + 1

    def span(self, name: str, **args: Any) -> _Span:
        """Context manager: time the block on the host clock and ``observe()``
        it under ``name`` when it ends cleanly, and hold a
        ``jax.profiler.TraceAnnotation(name, **args)`` open for it, so the
        same region lies on the profiler's clock whenever a capture is
        running (with none running the annotation is a fraction of a
        microsecond). Same declared-vocabulary contract as ``observe()``,
        checked before the block runs."""
        self._check_declared(name)
        annotation = self._annotation
        if annotation is None:
            from jax.profiler import TraceAnnotation

            annotation = self._annotation = TraceAnnotation
        return _Span(self, name, annotation(name, **args))

    def count(self, name: str) -> int:
        with self._lock:
            return self._totals.get(name, 0)

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        """Per-family ``{"buckets": [(le, cumulative_count)...], "sum": s,
        "count": c}`` — bucket counts already cumulative and monotone; the
        +Inf bucket is ``count``."""
        with self._lock:
            out: Dict[str, Dict[str, Any]] = {}
            for name in sorted(self._counts):
                cum: List[Tuple[float, int]] = []
                acc = 0
                for bound, c in zip(self.buckets, self._counts[name]):
                    acc += c
                    cum.append((bound, acc))
                out[name] = {
                    "buckets": cum,
                    "sum": self._sums.get(name, 0.0),
                    "count": self._totals.get(name, 0),
                }
            return out

    def reset(self) -> None:
        with self._lock:
            for counts in self._counts.values():
                for i in range(len(counts)):
                    counts[i] = 0
            self._sums.clear()
            self._totals.clear()


#: Process-wide latency histograms for the serving stack, surfaced on
#: ``/metrics`` as ``kllms_<family>_seconds`` (dots become underscores):
#: request.e2e — full request wall time, observed when a trace finishes;
#: request.ttft — time to first streamed token, observed at the first delta
#: a ChatCompletionStream emits; scheduler.queue_wait — admission-to-dequeue
#: wait, observed at both the coalescing scheduler's group pop and the
#: continuous loop's slot admission; continuous.step — one continuous-loop
#: step's host wall time around the (possibly watchdogged) device dispatch;
#: engine.decode_launch — one coalesced decode launch (the paged-attention
#: fused path included), observed around the supervised generate_many call;
#: consensus.consolidate — consensus consolidation wall time. All observes
#: are host-side wall clock — never inside jitted step programs.
#:
#: The ``.*`` wildcard families are the per-tenant label sets (ISSUE 16):
#: ``request.e2e.<tenant>`` / ``request.ttft.<tenant>`` /
#: ``scheduler.queue_wait.<tenant>`` record the same observation a second
#: time under the request's tenant, and ``/metrics`` renders them as one
#: labeled family per base name (``kllms_request_e2e_by_tenant_seconds``
#: with a ``tenant`` label) so per-tenant SLO compliance is scrapeable
#: without pre-registering tenant names.
#: The batch-lane families (ISSUE 17): ``batch.item`` — one offline item's
#: end-to-end wall time through the lane (dequeue → committed output
#: segment); ``batch.job_e2e`` — a whole job from durable submission to
#: terminal status, wall clock, spanning restarts (the journal carries
#: ``created_at``).
#: The chunked-prefill family (ISSUE 18): ``continuous.prefill_chunk`` — one
#: interleaved prompt-chunk dispatch's host wall time (device step + paged
#: scatter + sync), observed per chunk by the continuous loop; compare its
#: max against ``continuous.step`` p50 to verify long admissions no longer
#: stall in-flight decode rows.
#: The continuous loop's own work (ISSUE 25), each a ``span()`` — so also an
#: event of the same name on a profiler capture — unless said. Per decode
#: step or chunk: ``continuous.gap`` (observe only) — host clock from one
#: program's results reaching the host to the next program's dispatch, not
#: across an idle wait; its parts ``continuous.prepare`` (staging a step's
#: arguments), ``continuous.handoff`` (observe only: the two thread hand-offs
#: through the watchdog's step thread), ``continuous.bookkeep`` (tokens,
#: sinks, retirement after a step's readback; ``continuous.emit``, one
#: request's token sink, lies inside it) and ``continuous.admit`` (dequeue
#: and admission, the host sides of a chunk); ``continuous.dispatch`` (the
#: jitted call until it returns) and ``continuous.readback`` (the
#: ``device_get``: host blocked, device busy); ``continuous.idle`` — the
#: worker's wait when it has neither a live row nor a PREFILLING admission.
#: Whose wait each millisecond of a gap is (ISSUE 36). Inside
#: ``continuous.prepare``, its three parts, a span each and one sample a step
#: each: ``continuous.lock_wait`` (``_step_once`` entry until the loop lock is
#: held: submitters and ``stats`` readers hold it), ``continuous.pages`` (the
#: page books' ``prepare_step`` and ``walk_counts``: table growth,
#: copy-on-write, the rows' write slots; no sample for a dense loop) and
#: ``continuous.stage`` (the step's one upload: the slot mirrors, a drafting
#: loop's draft and room, the grammar states and flags, the rows' write slots
#: and block tables packed into one array, and its ``jnp.asarray``). Inside
#: ``continuous.admit``: ``continuous.admit_device`` — an admission's device
#: work, from its first jitted call (the prefill, or the state install and the
#: first-token program where the last chunk finishes a prompt) to the return
#: of the ``device_get`` that ends it, host work in between included; a
#: drafting loop's first drafts leave a second sample an admission.
#: ``continuous.wait`` (observe only, one sample a ``continuous.gap``) — the
#: gap less the ``admit_device`` seconds that fell inside it: the host clock's
#: "the chip had nothing to run". Inside ``continuous.readback``, after the
#: outputs' copies are queued and ``block_until_ready`` has returned:
#: ``continuous.fetch`` — the ``device_get`` of results the device has
#: finished, ending where the next gap begins; the readback ahead of it is the
#: device running (or not yet begun).
#: ``continuous.state_install`` — an admission's copy of the prefill lane's
#: recurrent state into the request's rows (no sample for a model without
#: such state).
#: Per request: ``continuous.prefill_wall`` (dequeue -> its rows installed)
#: and ``continuous.decode_wall`` (rows installed -> future resolved), wall
#: clock, other requests' steps and chunks included; also phases of its trace.
LATENCY = LatencyHistograms(declared=(
    "request.e2e",
    "request.ttft",
    "scheduler.queue_wait",
    "continuous.step",
    "continuous.prefill_chunk",
    "continuous.gap",
    "continuous.wait",
    "continuous.prepare",
    "continuous.lock_wait",
    "continuous.pages",
    "continuous.stage",
    "continuous.handoff",
    "continuous.dispatch",
    "continuous.readback",
    "continuous.fetch",
    "continuous.bookkeep",
    "continuous.emit",
    "continuous.admit",
    "continuous.admit_device",
    "continuous.state_install",
    "continuous.idle",
    "continuous.prefill_wall",
    "continuous.decode_wall",
    "engine.decode_launch",
    "consensus.consolidate",
    "batch.item",
    "batch.job_e2e",
    "request.e2e.*",
    "request.ttft.*",
    "scheduler.queue_wait.*",
))
