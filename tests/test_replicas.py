"""Replica-set serving (PR 5): health-aware routing, mid-flight failover,
hedged dispatch, and honest degradation.

Hermetic units run over FakeBackend members (routing scores, probation +
probe rejoin, seed pinning across failover, bounded failover, 429 scaling,
hedging, the typed no-healthy-replicas 503, and the resolve_backend
satellite). The ISSUE acceptance scenarios run on real CPU engines: the
greedy differential proves a failed-over request is byte-identical to an
uninterrupted run on the healthy member, and the hedge-cancel test proves the
losing launch dies through the engine's abort poller without ever touching a
circuit breaker. The slow-tagged chaos soak flaps one of three members
(down + hang via the keyed ``replica.dispatch`` / ``replica.probe``
failpoints) under concurrent traffic."""

import dataclasses
import threading
import time

import pytest

from k_llms_tpu import KLLMs
from k_llms_tpu.analysis import lockcheck
from k_llms_tpu.backends.base import (
    Backend,
    ChatRequest,
    UnknownBackendError,
    resolve_backend,
)
from k_llms_tpu.backends.fake import FakeBackend
from k_llms_tpu.reliability import failpoints as fp
from k_llms_tpu.reliability.failpoints import FailSpec
from k_llms_tpu.reliability.replicas import ReplicaSet
from k_llms_tpu.types.wire import (
    EngineHungError,
    KLLMsError,
    NoHealthyReplicasError,
    RateLimitError,
)
from k_llms_tpu.utils.observability import (
    FAILOVER_EVENTS,
    FAILURE_EVENTS,
    HEDGE_EVENTS,
    ROUTE_EVENTS,
)


def _req(content="hi", n=1, seed=None, **kw):
    return ChatRequest(
        messages=[{"role": "user", "content": content}], model="fake", n=n, seed=seed, **kw
    )


def _shutdown(rs):
    """Release the set's executor without closing member backends (tests often
    share members across several ReplicaSets)."""
    rs._executor.shutdown(wait=False)


# -- resolve_backend satellite ---------------------------------------------


def test_resolve_backend_unknown_name_is_typed_and_actionable():
    with pytest.raises(UnknownBackendError) as ei:
        resolve_backend("warp-drive")
    assert isinstance(ei.value, ValueError)  # pre-existing callers catch this
    assert ei.value.backend == "warp-drive"
    assert "replicas" in ei.value.known and "tpu" in ei.value.known
    msg = str(ei.value)
    assert "warp-drive" in msg and "'fake'" in msg and "Backend instance" in msg


def test_resolve_backend_rejects_non_string_non_backend():
    with pytest.raises(UnknownBackendError):
        resolve_backend(42)  # type: ignore[arg-type]


def test_resolve_backend_normalizes_names():
    assert isinstance(resolve_backend("  FAKE "), FakeBackend)
    assert isinstance(resolve_backend("Fake"), FakeBackend)
    rs = resolve_backend("ReplicaSet", members=[FakeBackend(["a"])], model="fake")
    assert isinstance(rs, ReplicaSet)
    _shutdown(rs)
    rs = resolve_backend("replica_set", members=[FakeBackend(["a"])], model="fake")
    assert isinstance(rs, ReplicaSet)
    _shutdown(rs)


def test_resolve_backend_passes_instances_through():
    b = FakeBackend(["x"])
    assert resolve_backend(b) is b
    rs = ReplicaSet(members=[b], model="fake")
    assert resolve_backend(rs) is rs  # a ReplicaSet IS a Backend
    _shutdown(rs)


# -- construction ----------------------------------------------------------


def test_replicaset_requires_members_and_unique_ids():
    with pytest.raises(ValueError, match="at least one member"):
        ReplicaSet(members=[])
    with pytest.raises(ValueError, match="duplicate replica ids"):
        ReplicaSet(
            members=[
                {"backend": "fake", "id": "a"},
                {"backend": "fake", "id": "a"},
            ]
        )
    with pytest.raises(ValueError, match="route_policy"):
        ReplicaSet(members=[FakeBackend()], route_policy="random")
    with pytest.raises(TypeError, match="member 0"):
        ReplicaSet(members=[object()])  # type: ignore[list-item]


def test_replicaset_member_specs_and_ids():
    rs = ReplicaSet(
        members=["fake", {"backend": "fake", "id": "east"}, FakeBackend(["z"])],
        model="fake",
        hedge=False,
    )
    assert sorted(rs.stats()) == ["east", "r0", "r2"]
    assert rs.model_name == "fake"
    _shutdown(rs)


# -- routing ---------------------------------------------------------------


def test_routing_prefers_lower_latency_member():
    rs = ReplicaSet(
        members=[FakeBackend(["slow"]), FakeBackend(["fast"])], model="fake", hedge=False
    )
    # Seed the EWMAs directly: r0 is 10x slower than r1.
    rs._by_id["r0"].note_success(0.5)
    rs._by_id["r1"].note_success(0.05)
    out = rs.dispatch_chat_completion(_req())
    assert out.choices[0].message.content == "fast"
    assert rs.stats()["r1"]["dispatched"] == 2
    _shutdown(rs)


def test_routing_skips_open_breaker_and_rejoins_via_probe_success():
    rs = ReplicaSet(members=[FakeBackend(["a"]), FakeBackend(["b"])], model="fake", hedge=False)
    breaker = rs._by_id["r0"].backend.circuit_breaker
    for _ in range(breaker.failure_threshold):
        breaker.record_failure()
    assert breaker.state == "open"
    out = rs.dispatch_chat_completion(_req())
    assert out.choices[0].message.content == "b"
    # The probe is also the breaker's recovery path: a passing probe records
    # a breaker success, so the circuit closes off synthetic traffic.
    assert rs.probe("r0") is True
    assert breaker.state == "closed"
    out = rs.dispatch_chat_completion(_req())
    assert out.choices[0].message.content in ("a", "b")
    _shutdown(rs)


class _StatefulHealthBackend(FakeBackend):
    """FakeBackend whose health() state is test-controlled (simulates a
    member whose PR-4 supervisor is rebuilding it)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.state = "ready"

    def health(self):
        snap = super().health()
        snap["state"] = self.state
        return snap


def test_recovering_member_leaves_rotation_and_rejoins_only_after_probe():
    sick = _StatefulHealthBackend(["sick"])
    rs = ReplicaSet(
        members=[sick, FakeBackend(["ok"])],
        model="fake",
        hedge=False,
        probe_interval_s=float("inf"),  # no background probes: rejoin is explicit
    )
    sick.state = "recovering"
    before = ROUTE_EVENTS.get("route.pulled")
    out = rs.dispatch_chat_completion(_req())
    assert out.choices[0].message.content == "ok"
    assert ROUTE_EVENTS.get("route.pulled") == before + 1
    snap = rs.health()
    assert snap["state"] == "degraded"
    assert snap["replicas"]["r0"]["state"] == "out_of_rotation"
    assert "recovering" in snap["replicas"]["r0"]["out_reason"]
    # Backend healthy again — but rotation membership is gated on the probe,
    # not on time passing.
    sick.state = "ready"
    assert rs.health()["replicas"]["r0"]["state"] == "out_of_rotation"
    # A probe against a still-recovering backend fails and keeps it out.
    sick.state = "recovering"
    assert rs.probe("r0") is False
    assert rs.health()["replicas"]["r0"]["in_rotation"] is False
    sick.state = "ready"
    assert rs.probe("r0") is True
    assert rs.health()["state"] == "ready"
    assert rs.health()["replicas"]["r0"]["in_rotation"] is True
    _shutdown(rs)


def test_probe_failpoint_keeps_member_out_of_rotation():
    rs = ReplicaSet(
        members=[FakeBackend(["a"]), FakeBackend(["b"])],
        model="fake",
        hedge=False,
        probe_interval_s=float("inf"),  # probes are explicit: the failpoint budget is ours
    )
    before_pf = ROUTE_EVENTS.get("route.probe_failures")
    with fp.failpoints(
        {
            "replica.dispatch": FailSpec(action="down", member="r0", times=1),
            "replica.probe": FailSpec(action="fail", member="r0", times=1),
        }
    ):
        out = rs.dispatch_chat_completion(_req())
        assert out.choices[0].message.content == "b"
        assert rs.probe("r0") is False  # consumes the probe failpoint
        assert rs.health()["replicas"]["r0"]["in_rotation"] is False
        assert rs.health()["replicas"]["r0"]["probe_failures"] >= 1
    assert ROUTE_EVENTS.get("route.probe_failures") >= before_pf + 1
    assert rs.probe("r0") is True  # spec exhausted: probe passes, member rejoins
    assert rs.health()["state"] == "ready"
    _shutdown(rs)


# -- failover --------------------------------------------------------------


def test_failover_pins_seed_so_retry_is_identical_input():
    seen = []

    def recorder(tag):
        def responder(request):
            seen.append((tag, request.seed))
            return ["resp"] * max(1, request.n)

        return responder

    rs = ReplicaSet(
        members=[FakeBackend(recorder("r0")), FakeBackend(recorder("r1"))],
        model="fake",
        hedge=False,
        probe_interval_s=float("inf"),
    )
    with fp.failpoints({"replica.dispatch": FailSpec(action="down", member="r0", times=1)}):
        rs.dispatch_chat_completion(_req(seed=None))
    # r0's attempt died at the failpoint (before its responder ran); the
    # failover attempt carries a pinned, non-None seed.
    assert len(seen) == 1 and seen[0][0] == "r1"
    assert seen[0][1] is not None
    # With the caller's own seed, the same seed reaches the survivor.
    seen.clear()
    rs._by_id["r0"].rejoin()
    with fp.failpoints({"replica.dispatch": FailSpec(action="down", member="r0", times=1)}):
        rs.dispatch_chat_completion(_req(seed=777))
    assert seen == [("r1", 777)]
    _shutdown(rs)


def test_failover_is_bounded_and_exhaustion_propagates():
    rs = ReplicaSet(
        members=[FakeBackend(["a"]), FakeBackend(["b"]), FakeBackend(["c"])],
        model="fake",
        hedge=False,
        max_failover_attempts=1,
        probe_interval_s=float("inf"),
    )
    before = FAILOVER_EVENTS.get("failover.exhausted")
    with fp.failpoints({"replica.dispatch": FailSpec(action="down")}):  # every member
        with pytest.raises(EngineHungError):
            rs.dispatch_chat_completion(_req())
    assert FAILOVER_EVENTS.get("failover.exhausted") == before + 1
    # Primary + exactly one failover attempt: only two members were tried.
    assert sum(1 for s in rs.stats().values() if not s["in_rotation"]) == 2
    _shutdown(rs)


def test_caller_errors_never_fail_over():
    def bad_request(request):
        raise ValueError("caller bug")

    rs = ReplicaSet(
        members=[FakeBackend(bad_request), FakeBackend(["never"])],
        model="fake",
        hedge=False,
    )
    with pytest.raises(ValueError, match="caller bug"):
        rs.dispatch_chat_completion(_req())
    # The member is NOT blamed for the caller's bug.
    assert rs.health()["replicas"]["r0"]["in_rotation"] is True
    assert rs.stats()["r1"]["dispatched"] == 0
    _shutdown(rs)


def test_all_members_shedding_scales_retry_after():
    def shed(request):
        raise RateLimitError("queue full", retry_after=2.0)

    rs = ReplicaSet(
        members=[FakeBackend(shed), FakeBackend(shed), FakeBackend(["ok"])],
        model="fake",
        hedge=False,
        probe_interval_s=float("inf"),
    )
    # One healthy member left: sheds from the other two route around them.
    rs._by_id["r2"].mark_down("test: simulate lost capacity")
    with pytest.raises(RateLimitError) as ei:
        rs.dispatch_chat_completion(_req())
    # 429s are load signals: nobody leaves rotation over them...
    assert rs.health()["replicas"]["r0"]["in_rotation"] is True
    assert rs.health()["replicas"]["r1"]["in_rotation"] is True
    # ...and retry_after is scaled by total/healthy (3/2 here) so callers back
    # off proportionally to the capacity actually lost.
    assert ei.value.retry_after == pytest.approx(2.0 * 3 / 2)
    _shutdown(rs)


def test_zero_healthy_members_is_typed_503_with_reasons():
    rs = ReplicaSet(
        members=[FakeBackend(["a"]), FakeBackend(["b"])],
        model="fake",
        hedge=False,
        max_failover_attempts=5,
        probe_interval_s=float("inf"),
    )
    before = ROUTE_EVENTS.get("route.no_healthy")
    with fp.failpoints(
        {
            "replica.dispatch": FailSpec(action="down", times=2),
            "replica.probe": FailSpec(action="fail"),
        }
    ):
        with pytest.raises(NoHealthyReplicasError) as ei:
            rs.dispatch_chat_completion(_req())
        assert rs.health()["state"] == "unavailable"
    err = ei.value
    assert err.status_code == 503
    assert sorted(err.reasons) == ["r0", "r1"]
    assert all("EngineHungError" in why for why in err.reasons.values())
    assert err.as_wire()["error"]["code"] == "no_healthy_replicas"
    assert err.as_wire()["error"]["replicas"] == err.reasons
    assert ROUTE_EVENTS.get("route.no_healthy") >= before + 1
    _shutdown(rs)


# -- hedging ---------------------------------------------------------------


def test_hedge_rescues_tail_and_cancels_loser():
    rs = ReplicaSet(
        members=[FakeBackend(["slowpoke"]), FakeBackend(["rescue"])],
        model="fake",
        hedge=True,
        hedge_delay_s=0.03,
        route_policy="round_robin",
        probe_interval_s=float("inf"),
    )
    before = HEDGE_EVENTS.snapshot()
    with fp.failpoints(
        {"replica.dispatch": FailSpec(action="sleep", member="r0", delay=0.5)}
    ):
        t0 = time.perf_counter()
        out = rs.dispatch_chat_completion(_req())
        elapsed = time.perf_counter() - t0
    assert out.choices[0].message.content == "rescue"
    assert elapsed < 0.4  # did not wait out the slow member
    after = HEDGE_EVENTS.snapshot()
    assert after.get("hedge.launched", 0) == before.get("hedge.launched", 0) + 1
    assert after.get("hedge.won_hedge", 0) == before.get("hedge.won_hedge", 0) + 1
    assert (
        after.get("hedge.cancelled_losers", 0)
        == before.get("hedge.cancelled_losers", 0) + 1
    )
    assert rs.stats()["r1"]["hedges_won"] == 1
    # Hedge losses are not health signals: the slow member keeps its place.
    assert rs.health()["replicas"]["r0"]["in_rotation"] is True
    assert rs._by_id["r0"].backend.circuit_breaker.state == "closed"
    _shutdown(rs)


def test_fast_primary_never_hedges():
    rs = ReplicaSet(
        members=[FakeBackend(["a"]), FakeBackend(["b"])],
        model="fake",
        hedge=True,
        hedge_delay_s=0.5,
    )
    before = HEDGE_EVENTS.get("hedge.launched")
    for _ in range(5):
        rs.dispatch_chat_completion(_req())
    assert HEDGE_EVENTS.get("hedge.launched") == before
    _shutdown(rs)


def test_hedge_failure_never_counts_against_breakers():
    """A hedge that FAILS (not just loses) must not touch the hedge member's
    circuit breaker — hedges call the raw chat_completion."""
    failures = {"n": 0}

    def flaky_hedge(request):
        failures["n"] += 1
        raise RuntimeError("hedge member exploded")

    rs2 = ReplicaSet(
        members=[FakeBackend(["primary"]), FakeBackend(flaky_hedge)],
        model="fake",
        hedge=True,
        hedge_delay_s=0.02,
        probe_interval_s=float("inf"),
    )
    with fp.failpoints(
        {"replica.dispatch": FailSpec(action="sleep", member="r0", delay=0.2)}
    ):
        out = rs2.dispatch_chat_completion(_req())
    assert out.choices[0].message.content == "primary"  # primary still won
    assert failures["n"] == 1  # the hedge really ran and really failed
    assert rs2._by_id["r1"].backend.circuit_breaker.state == "closed"
    # The failed hedge is not a rotation event either.
    assert rs2.health()["replicas"]["r1"]["in_rotation"] is True
    _shutdown(rs2)


def test_hedge_skipped_without_latency_history():
    """Adaptive mode (no fixed hedge_delay_s): no p95 history means no tail
    to hedge against — the dispatch stays single."""
    rs = ReplicaSet(members=[FakeBackend(["a"]), FakeBackend(["b"])], model="fake", hedge=True)
    before = HEDGE_EVENTS.get("hedge.launched")
    rs.dispatch_chat_completion(_req())
    assert HEDGE_EVENTS.get("hedge.launched") == before
    # After enough samples the p95-derived delay kicks in.
    for _ in range(6):
        rs.dispatch_chat_completion(_req())
    assert rs._hedge_delay(rs._by_id["r0"]) is not None
    _shutdown(rs)


# -- Backend surface / observability ---------------------------------------


def test_stats_and_health_shapes():
    rs = ReplicaSet(members=[FakeBackend(["a"]), FakeBackend(["b"])], model="fake", hedge=False)
    rs.dispatch_chat_completion(_req())
    stats = rs.stats()
    for rid in ("r0", "r1"):
        for key in ("dispatched", "failed", "hedges_won", "ewma_ms", "state"):
            assert key in stats[rid], f"stats[{rid}] missing {key}"
    h = rs.health()
    assert h["members"] == 2 and h["healthy_members"] == 2
    assert h["state"] == "ready" and h["hedge"] is False
    assert set(h["replicas"]) == {"r0", "r1"}
    assert h["replicas"]["r0"]["dispatched"] + h["replicas"]["r1"]["dispatched"] == 1
    _shutdown(rs)


def test_embeddings_and_consensus_fail_over():
    class DeadEmbed(FakeBackend):
        def embeddings(self, texts):
            raise RuntimeError("embedding engine gone")

        def llm_consensus(self, values):
            raise RuntimeError("consensus engine gone")

    rs = ReplicaSet(
        members=[DeadEmbed(), FakeBackend(["x"])],
        model="fake",
        hedge=False,
        probe_interval_s=float("inf"),
    )
    vecs = rs.embeddings(["alpha", "beta"])
    assert len(vecs) == 2 and len(vecs[0]) == 64
    rs._by_id["r0"].rejoin()
    assert rs.llm_consensus(["x", "y", "x"]) == "x"
    _shutdown(rs)


def test_client_integration_over_replicas():
    """KLLMs(backend="replicas", members=[...]) is a drop-in: consensus-first
    choice layout, likelihoods, and health()["replicas"] all flow through."""
    client = KLLMs(
        backend="replicas",
        members=[FakeBackend(["yes", "yes", "no"]), FakeBackend(["yes", "yes", "no"])],
        model="fake",
        hedge=False,
    )
    resp = client.chat.completions.create(
        messages=[{"role": "user", "content": "vote"}], model="fake", n=3
    )
    assert len(resp.choices) == 4  # consensus + 3
    assert resp.choices[0].message.content == "yes"
    assert resp.likelihoods["text"] == pytest.approx(2 / 3, abs=1e-4)
    assert set(client.health()["replicas"]) == {"r0", "r1"}
    client.close()


# -- real-engine acceptance (CPU mesh) -------------------------------------


@pytest.fixture(scope="module")
def tpu_members():
    """Two independent tiny engines with identical weights (same default
    param seed): what a dp-sliced replica deployment looks like in tests."""
    from k_llms_tpu.backends.tpu import TpuBackend

    b0 = TpuBackend(model="tiny")
    b1 = TpuBackend(model="tiny")
    yield b0, b1
    b0.close()
    b1.close()


@pytest.mark.duration_budget(30)
def test_greedy_differential_failover_is_byte_identical(tpu_members):
    """ISSUE acceptance: a request whose first attempt dies on r0 and fails
    over returns byte-identical output (consensus, choices, likelihoods) to
    an uninterrupted run on the healthy member — seeds are pinned at the set
    level before the first attempt."""
    b0, b1 = tpu_members
    kwargs = dict(
        messages=[{"role": "user", "content": "differential"}],
        model="tiny",
        n=3,
        temperature=0.0,
        seed=11,
        max_tokens=10,
    )
    baseline_client = KLLMs(backend=b1, model="tiny")
    baseline = baseline_client.chat.completions.create(**kwargs)

    rs = ReplicaSet(members=[b0, b1], model="tiny", hedge=False)
    client = KLLMs(backend=rs, model="tiny")
    before = FAILOVER_EVENTS.get("failover.attempts")
    with fp.failpoints({"replica.dispatch": FailSpec(action="down", member="r0", times=1)}):
        failed_over = client.chat.completions.create(**kwargs)
    assert FAILOVER_EVENTS.get("failover.attempts") == before + 1
    assert rs.stats()["r1"]["failovers"] == 1

    assert [c.message.content for c in failed_over.choices] == [
        c.message.content for c in baseline.choices
    ]
    assert failed_over.choices[0].message.content  # consensus is non-empty
    assert failed_over.likelihoods == baseline.likelihoods
    assert failed_over.usage.completion_tokens == baseline.usage.completion_tokens
    # The failover also shows up in the member scheduler's stats tallies.
    assert b1.scheduler.stats["failovers"] >= 1
    assert b1.scheduler.health()["routed"] >= 1
    _shutdown(rs)


@pytest.mark.duration_budget(30)
def test_hedged_dispatch_cancels_loser_through_abort_poller(tpu_members, monkeypatch):
    """ISSUE acceptance: the hedge winner's result returns while the loser is
    cancelled mid-decode through the engine's io_callback abort poller
    (engine.decode_abort increments), and neither member's circuit breaker
    records anything."""
    b0, b1 = tpu_members
    req = ChatRequest(
        messages=[{"role": "user", "content": "hedge race"}],
        model="tiny",
        n=1,
        temperature=0.0,
        seed=3,
        max_tokens=48,
    )
    # Warm both engines so the race below is not two cold starts.
    b0.chat_completion(dataclasses.replace(req))
    b1.chat_completion(dataclasses.replace(req))

    rs = ReplicaSet(members=[b0, b1], model="tiny", hedge=True, hedge_delay_s=0.05)
    # r0 (primary) must be mid-decode — launched, unfinished — when r1's hedge
    # result lands. Events order the two members, no measured wall clock (a
    # sleep sized from one decode lost this race under a loaded machine):
    # (1) r0's attempt starts once r1's decode is on the device, so r1's
    #     program is the one ahead where the platform runs sharded programs
    #     one at a time (the CPU's eight virtual devices do);
    # (2) r1's decode passes its first token boundary once r0's launch has
    #     begun, so r0 is past admission (not shed) before it can be cancelled;
    # (3) r0's decode stays at a token boundary until the set has cancelled
    #     its budget, which it does when r1's result lands.
    # Every hold has a bound well under the launch watchdog's 60 s.
    r1_decoding, r0_launched = threading.Event(), threading.Event()

    def hooked(fn, announce=None, wait_for=None):
        def call(*args, **kwargs):
            if announce is not None:
                announce.set()
            if wait_for is not None:
                wait_for.wait(10.0)
            return fn(*args, **kwargs)

        return call

    monkeypatch.setattr(
        b0, "dispatch_chat_completion",
        hooked(b0.dispatch_chat_completion, wait_for=r1_decoding),
    )
    monkeypatch.setattr(
        b0.engine, "generate_many", hooked(b0.engine.generate_many, announce=r0_launched)
    )
    monkeypatch.setattr(
        b1.engine, "_poll_abort_flags",
        hooked(b1.engine._poll_abort_flags, announce=r1_decoding, wait_for=r0_launched),
    )
    poll = b0.engine._poll_abort_flags
    hold_until = []

    def held_poll(num_requests):
        hold_until.append(time.monotonic() + 10.0)
        flags = poll(num_requests)
        while not flags.any() and time.monotonic() < hold_until[0]:
            time.sleep(0.002)
            flags = poll(num_requests)
        return flags

    monkeypatch.setattr(b0.engine, "_poll_abort_flags", held_poll)
    aborts_before = FAILURE_EVENTS.get("engine.decode_abort")
    hedge_before = HEDGE_EVENTS.get("hedge.won_hedge")
    out = rs.dispatch_chat_completion(dataclasses.replace(req))
    assert out.choices and out.choices[0].message.content
    assert HEDGE_EVENTS.get("hedge.won_hedge") == hedge_before + 1
    assert rs.stats()["r1"]["hedges_won"] == 1
    # The loser aborts at its next token boundary; give it a moment to land.
    deadline = time.monotonic() + 5.0
    while (
        FAILURE_EVENTS.get("engine.decode_abort") <= aborts_before
        and time.monotonic() < deadline
    ):
        time.sleep(0.05)
    assert FAILURE_EVENTS.get("engine.decode_abort") > aborts_before, (
        "losing hedge was not cancelled through the engine abort poller"
    )
    # Hedge cancellation is not a failure anywhere: breakers stay closed and
    # the loser keeps its rotation slot.
    assert b0.circuit_breaker.state == "closed"
    assert b1.circuit_breaker.state == "closed"
    assert rs.health()["replicas"]["r0"]["in_rotation"] is True
    assert b1.scheduler.stats["hedges_won"] >= 1
    _shutdown(rs)


# -- chaos soak ------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.duration_budget(120)
def test_chaos_soak_flapping_member_under_concurrent_traffic(monkeypatch):
    """ISSUE acceptance: a 3-member set where r1 repeatedly dies (down) and
    wedges (hang-style sleep) while concurrent traffic flows. Every request
    resolves with a typed result or typed error, zero hung futures, failovers
    stay bounded, and the flapping member rejoins after a probe passes.

    Runs under KLLMS_LOCKCHECK=1 + KLLMS_RACECHECK=1: router + per-replica +
    breaker locks are instrumented and handle/router fields go through the
    lockset sanitizer; the soak must end with a clean lock-order graph and
    zero empty-lockset findings."""
    monkeypatch.setenv("KLLMS_LOCKCHECK", "1")
    monkeypatch.setenv("KLLMS_RACECHECK", "1")
    lockcheck.reset_state()
    members = [FakeBackend(["m0"]), FakeBackend(["m1"]), FakeBackend(["m2"])]
    rs = ReplicaSet(
        members=members,
        model="fake",
        hedge=True,
        hedge_delay_s=0.05,
        probe_interval_s=0.05,
        max_failover_attempts=2,
    )
    stop = threading.Event()
    results = []
    lock = threading.Lock()

    def worker(i):
        k = 0
        while not stop.is_set():
            k += 1
            try:
                out = rs.dispatch_chat_completion(_req(content=f"soak {i}-{k}"))
                kind = ("ok", out.choices[0].message.content)
            except KLLMsError as e:
                kind = ("typed", type(e).__name__)
            with lock:
                results.append(kind)
            time.sleep(0.005)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    try:
        for round_no in range(4):
            # r1 dies hard: every dispatch to it errors for a while, probes
            # fail too, so it sits in probation while survivors serve.
            with fp.failpoints(
                {
                    "replica.dispatch": FailSpec(action="down", member="r1"),
                    "replica.probe": FailSpec(action="fail", member="r1"),
                }
            ):
                time.sleep(0.4)
            # r1 wedges (slow, not dead): hedging rescues its primaries.
            with fp.failpoints(
                {"replica.dispatch": FailSpec(action="sleep", member="r1", delay=0.3)}
            ):
                time.sleep(0.3)
            # Faults lifted: the next probe must bring r1 back.
            rs.probe("r1")
            assert rs.health()["replicas"]["r1"]["in_rotation"] is True, (
                f"round {round_no}: r1 did not rejoin after probe success"
            )
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
    # Zero hung futures: every worker retired.
    assert not any(t.is_alive() for t in threads)
    assert len(results) >= 50
    oks = [r for r in results if r[0] == "ok"]
    typed = [r for r in results if r[0] == "typed"]
    assert len(oks) >= len(results) * 0.5, "most traffic must survive the flapping"
    # Anything that failed, failed with a TYPED error (KLLMsError), by
    # construction of the worker — nothing leaked an untyped exception.
    assert len(oks) + len(typed) == len(results)
    # Failovers happened but stayed bounded: no retry storm relative to the
    # traffic actually served.
    stats = rs.stats()
    total_failovers = sum(s["failovers"] for s in stats.values())
    assert total_failovers <= len(results) * (rs.max_failover_attempts + 1)
    # Full health restored after the last probe.
    h = rs.health()
    assert h["state"] == "ready" and h["healthy_members"] == 3
    _shutdown(rs)
    lockcheck.assert_clean()
