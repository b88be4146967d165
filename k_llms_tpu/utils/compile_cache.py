"""Where compiled programs are kept, and what compiling costs.

Every process that builds an engine calls :func:`configure_compile_cache`
before its first jit. The persistent cache directory must not move between
runs: if ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and this
module sets nothing; otherwise the cache goes to one fixed directory derived
from the package's own location (``<checkout>/.jax_cache``, git-ignored) — no
temp name, pid or time in the path, so a second process on the same checkout
hits what the first wrote. The default is applied on accelerators only: the
CPU backend serves the tests, and every XLA:CPU executable reloaded from a
cache logs machine-feature errors to stderr (jaxlib 0.9.0), which would land
in the middle of the test runner's progress lines.

The same call registers ``jax.monitoring`` listeners that (a) count compiled
programs, compile seconds and persistent-cache hits/misses for ``health()``,
and (b) feed :class:`CompileTracker`, which lets a watchdog on another thread
subtract the time a launch spent tracing/lowering/compiling from its budget.
A cold compile of a full-depth step is the same order as the hang budget; a
compile is not a hang (see ``reliability/supervisor.py`` and
``engine/continuous.py::_StepDispatcher``).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Dict, Iterator, Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

# The three phases jax wraps in dispatch.log_elapsed_time: a scalar event when
# the phase starts, a duration event when it ends (jax/_src/dispatch.py).
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_PHASES = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _BACKEND_COMPILE,
))
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

# Import-time module lock guarding the process-wide stats and the one-shot
# configuration; taken from inside JAX's compile path, so it must stay a leaf.
# kllms: ignore[lock-order] — import-time module lock, leaf by design
_lock = threading.Lock()
_configured = False
_cache_dir: Optional[str] = None
_stats = {"programs": 0, "seconds": 0.0, "cache_hits": 0, "cache_misses": 0}
_tls = threading.local()


class CompileTracker:
    """Seconds ONE thread has spent in JAX's trace/lower/compile pipeline,
    readable from another thread while the first is still inside it.

    The launching thread wraps its work in :meth:`active`; the monitoring
    listeners (which JAX calls on the compiling thread) then account phases to
    this object. Plain attribute writes, one writer: a watchdog reading a
    slightly stale value waits one more slice, never less."""

    __slots__ = ("_total", "_depth", "_since")

    def __init__(self) -> None:
        self._total = 0.0
        self._depth = 0
        self._since: Optional[float] = None

    @contextlib.contextmanager
    def active(self) -> Iterator[None]:
        prev = getattr(_tls, "tracker", None)
        _tls.tracker = self
        try:
            yield
        finally:
            _tls.tracker = prev

    def seconds(self) -> float:
        since = self._since
        running = time.monotonic() - since if since is not None else 0.0
        return self._total + running

    def _enter(self) -> None:
        if self._depth == 0:
            self._since = time.monotonic()
        self._depth += 1

    def _exit(self) -> None:
        if self._depth == 0:
            return
        self._depth -= 1
        if self._depth == 0 and self._since is not None:
            self._total += time.monotonic() - self._since
            self._since = None


def wait_excluding_compile(
    done: threading.Event, budget_s: float, tracker: CompileTracker,
    max_exempt_s: float,
) -> bool:
    """Wait for ``done`` for up to ``budget_s`` of wall time NOT spent
    compiling on the tracked thread (at most ``max_exempt_s`` is forgiven, so
    a compile that never returns is still caught). True when ``done`` fired."""
    start = time.monotonic()
    while True:
        exempt = min(tracker.seconds(), max_exempt_s)
        remaining = budget_s - (time.monotonic() - start - exempt)
        if remaining <= 0:
            return done.is_set()
        if done.wait(remaining):
            return True


def _on_phase_start(event: str, value: Any, **kwargs: Any) -> None:
    if event in _COMPILE_PHASES:
        tracker = getattr(_tls, "tracker", None)
        if tracker is not None:
            tracker._enter()


def _on_phase_end(event: str, duration: float, **kwargs: Any) -> None:
    if event not in _COMPILE_PHASES:
        return
    tracker = getattr(_tls, "tracker", None)
    if tracker is not None:
        tracker._exit()
    if event == _BACKEND_COMPILE:
        with _lock:
            _stats["programs"] += 1
            _stats["seconds"] += float(duration)


def _on_event(event: str, **kwargs: Any) -> None:
    if event == _CACHE_HIT or event == _CACHE_MISS:
        with _lock:
            _stats["cache_hits" if event == _CACHE_HIT else "cache_misses"] += 1


def configure_compile_cache() -> Optional[str]:
    """Place the persistent compilation cache and start compile accounting.
    Idempotent; returns the cache directory in force (None: no cache — the
    CPU backend without ``JAX_COMPILATION_CACHE_DIR``)."""
    global _configured, _cache_dir
    with _lock:
        if _configured:
            return _cache_dir
        import jax
        from jax import monitoring

        cache_dir: Optional[str] = os.environ.get(CACHE_ENV) or None
        if cache_dir is None and jax.default_backend() != "cpu":
            cache_dir = DEFAULT_CACHE_DIR
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        monitoring.register_scalar_listener(_on_phase_start)
        monitoring.register_event_duration_secs_listener(_on_phase_end)
        monitoring.register_event_listener(_on_event)
        _configured = True
        _cache_dir = cache_dir
        return cache_dir


def compile_stats() -> Dict[str, Any]:
    """Process-wide compile accounting since :func:`configure_compile_cache`:
    programs built (cache hits included), seconds inside the backend compile
    call, and persistent-cache hits and misses (a miss is counted when the
    entry is written, so programs under JAX's caching thresholds are neither)."""
    with _lock:
        out: Dict[str, Any] = dict(_stats)
        out["seconds"] = round(out["seconds"], 3)
        out["cache_dir"] = _cache_dir
    return out
