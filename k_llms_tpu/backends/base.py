"""Backend protocol: what the resources layer needs from a model engine."""

from __future__ import annotations

import abc
import threading
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Callable, Dict, List, Optional, Union

from ..analysis.lockcheck import make_lock
from ..reliability import failpoints as _failpoints
from ..reliability.deadline import RequestBudget
from ..reliability.retry import CircuitBreaker, RetryPolicy
from ..types import ChatCompletion

if TYPE_CHECKING:  # pragma: no cover
    from ..consensus.similarity import SimilarityScorer


@dataclass
class ChatRequest:
    """Normalized chat-completion request (mirrors the reference's call_params,
    `/root/reference/k_llms/resources/completions/completions.py:42-64`)."""

    messages: List[Dict[str, Any]]
    model: str
    n: int = 1
    temperature: Optional[float] = None
    max_tokens: Optional[int] = None
    top_p: Optional[float] = None
    frequency_penalty: Optional[float] = None
    presence_penalty: Optional[float] = None
    stop: Optional[Union[str, List[str]]] = None
    seed: Optional[int] = None
    response_format: Optional[Any] = None
    logprobs: Optional[bool] = None
    top_logprobs: Optional[int] = None
    # OpenAI logit_bias: {token_id: bias in [-100, 100]} added to the logits
    # at sampling time (the reference forwards it to the server; the local
    # engine applies it in the decode loop).
    logit_bias: Optional[Dict[str, float]] = None
    # Lifecycle budget built from the caller's ``timeout=`` (deadline) plus a
    # cooperative cancel token; threaded into scheduler admission and the
    # engine decode loop. None = unbounded (the reference's no-timeout default).
    budget: Optional[RequestBudget] = None
    # Tenant id this request bills against (resolved from the API key at the
    # serving front door, or passed explicitly in-process). None = the
    # permissive "default" tenant. A plain string: the scheduler resolves it
    # to a TenantContext at admission so quota state lives in one place.
    tenant: Optional[str] = None
    extra: Dict[str, Any] = field(default_factory=dict)


class Backend(abc.ABC):
    """A model engine that can answer one n-way chat completion request."""

    @abc.abstractmethod
    def chat_completion(self, request: ChatRequest) -> ChatCompletion:
        """Return ONE ChatCompletion carrying n choices (the n samples)."""

    #: True when ``chat_completion_stream`` delivers incremental deltas. The
    #: resources layer checks this before opening a stream so ``stream=True``
    #: against a non-streaming backend fails as a typed 400 up front rather
    #: than deep in dispatch.
    supports_streaming: bool = False

    def chat_completion_stream(
        self, request: ChatRequest, emit: "Callable[[int, str], None]"
    ) -> ChatCompletion:
        """Run one n-way completion, calling ``emit(sample_idx, text_delta)``
        as sample text lands (sample_idx in 0..n-1, request order), then
        return the finished ChatCompletion exactly as ``chat_completion``
        would. Backends that cannot stream raise the OpenAI-shaped 400."""
        from ..types.wire import InvalidRequestError

        raise InvalidRequestError(
            f"{type(self).__name__} does not support stream=True; "
            "use a streaming-capable backend (tpu, fake) or stream=False",
            param="stream",
        )

    def dispatch_chat_completion_stream(
        self, request: ChatRequest, emit: "Callable[[int, str], None]"
    ) -> ChatCompletion:
        """``chat_completion_stream`` behind the circuit-breaker gate and the
        ``backend.dispatch`` failpoint. Deliberately NOT retried: once deltas
        have reached the client a retry would replay text mid-stream, so a
        stream gets exactly one attempt and surfaces its fault."""
        from ..types.wire import (
            RateLimitError,
            RequestCancelledError,
            RequestTimeoutError,
            ServerDrainingError,
        )

        breaker = self.circuit_breaker
        breaker.allow()
        try:
            _failpoints.fire("backend.dispatch")
            out = self.chat_completion_stream(request, emit)
        except BaseException as e:
            # Same exemptions as the non-stream path: caller deadlines/cancels
            # and admission sheds are not backend-health signals.
            if not isinstance(
                e,
                (
                    RequestTimeoutError,
                    RequestCancelledError,
                    RateLimitError,
                    ServerDrainingError,
                ),
            ):
                breaker.record_failure()
            raise
        breaker.record_success()
        return out

    #: Dispatch-layer reliability knobs, overridable per instance (pass a
    #: seeded RetryPolicy in tests to pin backoff schedules). The breaker is
    #: lazily per-instance so one flapping backend never opens another's
    #: circuit.
    retry_policy: RetryPolicy = RetryPolicy()

    @property
    def circuit_breaker(self) -> CircuitBreaker:
        breaker = self.__dict__.get("_circuit_breaker")
        if breaker is None:
            breaker = CircuitBreaker(name=type(self).__name__)
            self.__dict__["_circuit_breaker"] = breaker
        return breaker

    def dispatch_chat_completion(self, request: ChatRequest) -> ChatCompletion:
        """``chat_completion`` wrapped in the reliability layer: circuit-breaker
        gate, budget check, bounded retry with backoff (the shape the reference
        inherits from the OpenAI client's 2-retry exponential backoff), plus the
        ``backend.dispatch`` failpoint. This is what the resources layer calls;
        ``chat_completion`` stays the single-attempt primitive."""
        breaker = self.circuit_breaker

        def attempt() -> ChatCompletion:
            from ..types.wire import (
                RateLimitError,
                RequestCancelledError,
                RequestTimeoutError,
                ServerDrainingError,
            )

            breaker.allow()
            try:
                _failpoints.fire("backend.dispatch")
                out = self.chat_completion(request)
            except BaseException as e:
                # A caller's own deadline/cancel is not a backend-health
                # signal — only genuine dispatch faults trip the circuit.
                # Admission sheds (queue full, draining) are LOAD signals:
                # counting them as failures would latch the circuit open
                # exactly when the backend is healthy but busy.
                if not isinstance(
                    e,
                    (
                        RequestTimeoutError,
                        RequestCancelledError,
                        RateLimitError,
                        ServerDrainingError,
                    ),
                ):
                    breaker.record_failure()
                raise
            breaker.record_success()
            return out

        return self.retry_policy.call(attempt, budget=request.budget)

    @abc.abstractmethod
    def embeddings(self, texts: List[str]) -> List[List[float]]:
        """Similarity-side-channel embeddings (reference `client.py:75-122`)."""

    #: Model name the plain ``embeddings()`` entry point uses; the client maps a
    #: requested model of "local" to this so pricing follows the model actually hit.
    embedding_model_name: str = "local"

    #: True for backends whose embedding calls cost real money (the client then
    #: refuses default models it cannot price instead of billing them at $0).
    bills_usage: bool = False

    def embeddings_with_usage(
        self, texts: List[str], model: Optional[str] = None
    ) -> "tuple[List[List[float]], int]":
        """Embeddings plus billed prompt-token count for the batch (the reference
        accumulates `response.usage.prompt_tokens` per batch, `client.py:116`).
        ``model`` selects the embedding model on backends that have several;
        local backends have one and bill nothing."""
        return self.embeddings(texts), 0

    def crop_texts(
        self, texts: List[str], max_tokens: int, model: Optional[str] = None
    ) -> List[str]:
        """Crop each text to ``max_tokens`` in the tokenizer of ``model`` (the
        reference crops via tiktoken before embedding, `client.py:98-102`).
        Backends without a tokenizer pass texts through unchanged."""
        return list(texts)

    # One lock guards lazy scorer-registry creation across all backends; the
    # registry itself lives per-instance so caches follow the engine (and die
    # with it), like the reference's module-global TTL caches follow the process
    # (`consensus_utils.py:620-623`).
    _scorer_registry_lock = make_lock("backends.scorer_registry")

    def similarity_scorer(self, method: str) -> "SimilarityScorer":
        """The shared per-method similarity scorer for this backend. Every
        request through the same backend reuses one scorer per similarity
        method, so embedding/similarity TTL caches (1024 entries / 300 s)
        amortize across requests instead of being rebuilt per call."""
        from ..consensus.similarity import SimilarityScorer

        with Backend._scorer_registry_lock:
            registry = self.__dict__.setdefault("_similarity_scorers", {})
            scorer = registry.get(method)
            if scorer is None:
                scorer = SimilarityScorer(method=method, embed_fn=self.embeddings)
                registry[method] = scorer
            return scorer

    def llm_consensus(self, values: List[str]) -> str:
        """Build a consensus string from candidates (reference
        `consensus_utils.py:1026-1048` hardcodes gpt-5-mini; local backends answer
        with their own model). Default: medoid-free fallback to first value."""
        return values[0]

    def health(self) -> Dict[str, Any]:
        """Point-in-time serving-health snapshot (shaped for a /healthz
        endpoint). Backends without a scheduler report their breaker state;
        TpuBackend overrides with the full scheduler lifecycle view."""
        breaker = self.__dict__.get("_circuit_breaker")
        return {
            "state": "ready",
            "breaker": breaker.state if breaker is not None else "closed",
        }

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful shutdown: stop admission, finish in-flight work, release
        resources. Returns True when everything completed within ``timeout``.
        Backends without a request queue just close."""
        self.close()
        return True

    def close(self) -> None:  # pragma: no cover - optional
        pass


class UnknownBackendError(ValueError):
    """``resolve_backend`` got a name (or object) it cannot turn into a
    Backend. Subclasses ValueError so pre-existing ``except ValueError``
    callers keep working; carries the offending value and the known names so
    the message is actionable instead of a bare failure."""

    def __init__(self, backend: Any, known: List[str]):
        self.backend = backend
        self.known = list(known)
        shown = ", ".join(repr(k) for k in self.known)
        super().__init__(
            f"Unknown backend {backend!r}; expected one of {shown} "
            "(a name, case-insensitive), or a Backend instance"
        )


#: Accepted backend names (case/whitespace-insensitive) → canonical family.
_BACKEND_ALIASES: Dict[str, str] = {
    "fake": "fake",
    "tpu": "tpu",
    "jax": "tpu",
    "local": "tpu",
    "openai": "openai",
    "replicas": "replicas",
    "replica": "replicas",
    "replicaset": "replicas",
    "replica_set": "replicas",
}


def resolve_backend(backend: Union[str, Backend, None], **kwargs: Any) -> Backend:
    """Instantiate a backend from a name ("tpu" | "fake" | "openai" |
    "replicas", plus aliases; None defaults to "tpu") or pass a Backend
    instance through unchanged. Unknown names raise
    :class:`UnknownBackendError` listing what would have been accepted."""
    if isinstance(backend, Backend):
        return backend
    known = sorted(_BACKEND_ALIASES)
    if backend is not None and not isinstance(backend, str):
        raise UnknownBackendError(backend, known)
    name = _BACKEND_ALIASES.get((backend or "tpu").strip().lower())
    if name == "fake":
        from .fake import FakeBackend

        return FakeBackend(**kwargs)
    if name == "tpu":
        from .tpu import TpuBackend

        return TpuBackend(**kwargs)
    if name == "openai":
        from .openai_backend import OpenAIBackend

        return OpenAIBackend(**kwargs)
    if name == "replicas":
        from ..reliability.replicas import ReplicaSet

        return ReplicaSet(**kwargs)
    raise UnknownBackendError(backend, known)
