"""Request-scoped tracing, latency histograms, flight recorder (PR 14).

The load-bearing pins:

- ``/metrics`` is VALID Prometheus 0.0.4 text: every sample belongs to a
  ``# HELP``/``# TYPE``'d family, histogram buckets are cumulative and
  monotone with ``+Inf`` equal to ``_count``, and at least six latency
  histogram families are exported (a mini scrape parser, not substring grep).
- A W3C ``traceparent`` header is ingested at the HTTP front door: its
  trace_id shows up in the KLLMS_TRACE=1 ``timings`` extension AND in the
  request's ``/debug/requests`` flight record — response and record join.
- ``/debug/*`` is OFF by default (404s indistinguishable from unknown
  routes) and gated by ``BackendConfig.debug_endpoints``.
- ``serving.trace=drop:N`` degrades the tracer to no-op spans; the request
  still completes and no flight record is written — observability can be
  load-shed without touching the serving path.
- A request healed through watchdog rebuild + journal replay produces
  EXACTLY ONE flight record, annotated ``replayed`` — not one per epoch.
- Differential: generated bytes are identical with tracing on, dropped, and
  absent, and with a profiler capture running (observability is host-side
  bookkeeping, never part of the math).
- The continuous loop names its own work (PR 25): ``LATENCY.span`` regions
  land on ``/metrics`` histograms AND, as ``TraceAnnotation`` events of the
  same name, on a ``/debug/profile`` capture; the parts of ``continuous.gap``
  add up to it; the step programs carry scope and kernel names.
"""

import asyncio
import glob
import os
import re
import threading
import time
from types import SimpleNamespace

import httpx
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import benchmark_module
from k_llms_tpu import KLLMs
from k_llms_tpu.backends.fake import FakeBackend
from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
from k_llms_tpu.reliability import failpoints as fp
from k_llms_tpu.reliability.failpoints import FailSpec
from k_llms_tpu.reliability.supervisor import LaunchBudgetModel
from k_llms_tpu.serving import ServingApp
from k_llms_tpu.utils.observability import (
    FLIGHT_RECORDER,
    LATENCY,
    NOOP_TRACE,
    TRACER,
    LatencyHistograms,
    format_traceparent,
    parse_traceparent,
    use_trace,
)

TRACEPARENT = "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01"


def _fake_client(responses=None):
    return KLLMs(
        backend=FakeBackend(responses or ["alpha beta gamma", "alpha beta", "delta"]),
        model="fake-model",
    )


def _asgi(app):
    return httpx.AsyncClient(
        transport=httpx.ASGITransport(app=app), base_url="http://testserver"
    )


def _run(coro):
    return asyncio.run(coro)


BODY = {
    "messages": [{"role": "user", "content": "say something"}],
    "model": "fake-model",
    "n": 3,
    "seed": 11,
}


# -- traceparent parsing ----------------------------------------------------


def test_traceparent_parse_and_format_roundtrip():
    trace_id, parent_span, flags = parse_traceparent(TRACEPARENT)
    assert trace_id == "4bf92f3577b34da6a3ce929d0e0e4736"
    assert parent_span == "00f067aa0ba902b7"
    assert flags == "01"
    # A trace started from the header re-emits a well-formed traceparent
    # with the SAME trace_id and a fresh span.
    trace = TRACER.start(TRACEPARENT)
    out = format_traceparent(trace.trace_id, trace.span_id, trace.flags)
    got_tid, got_parent, _ = parse_traceparent(out)
    assert got_tid == trace_id
    assert got_parent == trace.span_id != parent_span
    assert trace.parent_span_id == parent_span


@pytest.mark.parametrize(
    "bad",
    [
        None,
        "",
        "not-a-traceparent",
        "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7",  # 3 fields
        "ff-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01",  # ver ff
        "00-00000000000000000000000000000000-00f067aa0ba902b7-01",  # zero tid
        "00-4bf92f3577b34da6a3ce929d0e0e4736-0000000000000000-01",  # zero span
        "00-XYZ92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01",  # non-hex
    ],
)
def test_traceparent_rejects_malformed(bad):
    assert parse_traceparent(bad) is None
    # Malformed context never fails the request: the tracer falls back to a
    # fresh trace with a generated id.
    trace = TRACER.start(bad)
    assert not trace.noop
    assert len(trace.trace_id) == 32


def test_histogram_vocabulary_is_enforced():
    h = LatencyHistograms(declared=("a.b", "keyed.*"), buckets=(0.1, 1.0))
    h.observe("a.b", 0.05)
    h.observe("keyed.route", 5.0)
    with pytest.raises(ValueError, match="not declared"):
        h.observe("a.typo", 0.05)
    snap = h.snapshot()
    assert snap["a.b"]["count"] == 1
    # Buckets are CUMULATIVE: the 5.0 observation lands past every finite
    # bound, visible only through count.
    assert snap["keyed.route"]["buckets"] == [(0.1, 0), (1.0, 0)]
    assert snap["keyed.route"]["count"] == 1


# -- /metrics scrape validity ----------------------------------------------


def _parse_prometheus(body: str):
    """Mini Prometheus 0.0.4 text parser: {family: {"type", "help",
    "samples": [(name, labels_str, value)]}}. Raises on a sample outside a
    HELP/TYPE'd family — the scrape-validity contract."""
    families = {}
    for line in body.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            families.setdefault(name, {"samples": []})["help"] = True
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            families.setdefault(name, {"samples": []})["type"] = kind
            continue
        assert not line.startswith("#"), f"unknown comment line: {line!r}"
        metric, _, value = line.rpartition(" ")
        name, labels = metric, ""
        if "{" in metric:
            name, _, rest = metric.partition("{")
            labels = rest.rstrip("}")
        fam = name
        if fam not in families:
            for suffix in ("_bucket", "_sum", "_count", "_total"):
                if name.endswith(suffix) and name[: -len(suffix)] in families:
                    fam = name[: -len(suffix)]
                    break
        assert fam in families, f"sample {name!r} outside any HELP/TYPE'd family"
        families[fam]["samples"].append((name, labels, float(value)))
    for fam, data in families.items():
        assert data.get("help"), f"family {fam} missing # HELP"
        assert data.get("type"), f"family {fam} missing # TYPE"
    return families


def test_metrics_scrape_validity():
    client = _fake_client()
    app = ServingApp(client)

    async def go():
        async with _asgi(app) as c:
            await c.post("/v1/chat/completions", json=BODY)  # move counters
            return await c.get("/metrics")

    resp = _run(go())
    assert resp.status_code == 200
    assert resp.headers["content-type"].startswith("text/plain")
    assert "version=0.0.4" in resp.headers["content-type"]
    families = _parse_prometheus(resp.text)

    hists = {f: d for f, d in families.items() if d["type"] == "histogram"}
    assert len(hists) >= 6, sorted(hists)
    for fam in (
        "kllms_request_e2e_seconds",
        "kllms_request_ttft_seconds",
        "kllms_scheduler_queue_wait_seconds",
        "kllms_continuous_step_seconds",
        "kllms_engine_decode_launch_seconds",
        "kllms_consensus_consolidate_seconds",
    ):
        assert fam in hists, fam
    # PR 36's families are declared exactly, so a scrape holds them (full
    # triples, checked below with the rest) before any loop has run: a group
    # of the same vocabulary exports them at zero samples.
    fresh = LatencyHistograms(declared=LATENCY.declared).snapshot()
    for span in ("wait", "lock_wait", "stage", "pages", "admit_device", "fetch"):
        assert f"kllms_continuous_{span}_seconds" in hists, span
        assert fresh[f"continuous.{span}"]["count"] == 0
        assert fresh[f"continuous.{span}"]["buckets"][-1][1] == 0
    for fam, data in hists.items():
        # Labeled families (per-tenant *_by_tenant_seconds) carry one full
        # _bucket/_sum/_count triple PER label value: group by the non-le
        # label set and hold the invariants per series.
        def _series(labels: str) -> str:
            return ",".join(
                p for p in labels.split(",") if p and not p.startswith("le=")
            )

        groups: dict = {}
        for n, labels, v in data["samples"]:
            groups.setdefault(_series(labels), []).append((n, labels, v))
        assert groups, fam
        for series, samples in groups.items():
            buckets = [
                (labels, v) for n, labels, v in samples if n == fam + "_bucket"
            ]
            counts = [v for n, _, v in samples if n == fam + "_count"]
            sums = [v for n, _, v in samples if n == fam + "_sum"]
            assert buckets and len(counts) == 1 and len(sums) == 1, (fam, series)
            # Cumulative + monotone, closed by +Inf == _count.
            values = [v for _, v in buckets]
            assert values == sorted(values), (fam, series)
            assert buckets[-1][0].endswith('le="+Inf"'), (fam, series)
            assert values[-1] == counts[0], (fam, series)
    # The request above must actually have been observed end-to-end.
    e2e = families["kllms_request_e2e_seconds"]["samples"]
    assert any(n.endswith("_count") and v >= 1 for n, _, v in e2e)
    for fam, data in families.items():
        if data["type"] == "counter":
            assert fam.endswith("_total"), fam
            assert all(n == fam for n, _, _ in data["samples"]), fam


# -- traceparent end-to-end + flight recorder ------------------------------


def test_traceparent_joins_timings_and_flight_record(monkeypatch):
    monkeypatch.setenv("KLLMS_TRACE", "1")
    client = _fake_client()
    client.backend.backend_config = SimpleNamespace(debug_endpoints=True)
    app = ServingApp(client)
    e2e_before = LATENCY.count("request.e2e")

    async def go():
        async with _asgi(app) as c:
            chat = await c.post(
                "/v1/chat/completions",
                json=BODY,
                headers={"traceparent": TRACEPARENT},
            )
            debug = await c.get("/debug/requests")
            return chat, debug

    chat, debug = _run(go())
    assert chat.status_code == 200
    timings = chat.json()["timings"]
    assert timings["trace_id"] == "4bf92f3577b34da6a3ce929d0e0e4736"
    assert "consolidate" in timings

    assert debug.status_code == 200
    payload = debug.json()
    assert payload["capacity"] >= payload["held"] >= 1
    recs = [
        r
        for r in payload["requests"]
        if r["trace_id"] == "4bf92f3577b34da6a3ce929d0e0e4736"
    ]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["route"] == "chat"
    assert rec["status"] == 200
    assert rec["n"] == 3
    assert rec["parent_span_id"] == "00f067aa0ba902b7"
    assert rec["phases"].get("consolidate", 0) >= 0
    assert rec["duration_s"] > 0
    assert LATENCY.count("request.e2e") > e2e_before


def test_debug_endpoints_are_off_by_default():
    """Security posture: without BackendConfig.debug_endpoints the debug
    surfaces 404 exactly like an unknown route — they don't advertise."""
    client = _fake_client()
    app = ServingApp(client)

    async def go():
        async with _asgi(app) as c:
            dbg = await c.get("/debug/requests")
            prof = await c.post("/debug/profile", json={"duration_s": 0.01})
            unknown = await c.get("/no/such/route")
            return dbg, prof, unknown

    dbg, prof, unknown = _run(go())
    assert dbg.status_code == prof.status_code == unknown.status_code == 404
    assert dbg.content == unknown.content


def test_debug_profile_captures_to_log_dir(tmp_path):
    client = _fake_client()
    client.backend.backend_config = SimpleNamespace(debug_endpoints=True)
    app = ServingApp(client)

    async def go():
        async with _asgi(app) as c:
            ok = await c.post(
                "/debug/profile",
                json={"duration_s": 0.01, "log_dir": str(tmp_path)},
            )
            bad = await c.post("/debug/profile", content=b"{nope")
            return ok, bad

    ok, bad = _run(go())
    assert ok.status_code == 200
    payload = ok.json()
    assert payload["log_dir"] == str(tmp_path)
    assert 0.01 <= payload["duration_s"] <= 10.0
    assert any(tmp_path.iterdir())  # jax.profiler wrote a capture
    assert bad.status_code == 400


# -- serving.trace drop failpoint ------------------------------------------


def test_serving_trace_drop_degrades_to_noop_and_request_completes(monkeypatch):
    """The load-shed drill: ``serving.trace`` with action="drop" turns the
    tracer into no-op spans. The request completes normally; no flight
    record is written and finish() on the noop trace is a no-op."""
    monkeypatch.setenv("KLLMS_TRACE", "1")
    client = _fake_client()
    app = ServingApp(client)
    total_before = FLIGHT_RECORDER.stats()["recorded_total"]

    async def go():
        async with _asgi(app) as c:
            return await c.post(
                "/v1/chat/completions",
                json=BODY,
                headers={"traceparent": TRACEPARENT},
            )

    with fp.failpoints({"serving.trace": FailSpec(action="drop", times=1)}):
        resp = _run(go())
    assert resp.status_code == 200
    data = resp.json()
    assert data["choices"] and data["choices"][0]["message"]["content"]
    # No trace context survived the drop: no trace_id in timings, no record.
    assert "trace_id" not in data.get("timings", {})
    assert FLIGHT_RECORDER.stats()["recorded_total"] == total_before
    # The tracer degraded for exactly that one request.
    assert not TRACER.start().noop


def test_serving_trace_drop_parses_from_env():
    fp.configure_from_env("serving.trace=drop:2")
    try:
        assert TRACER.start() is NOOP_TRACE
        assert TRACER.start() is NOOP_TRACE
        assert not TRACER.start().noop  # times=2 exhausted
    finally:
        fp.clear()


# -- continuous loop: epoch-safe traces ------------------------------------


def _step_budget(seconds: float) -> LaunchBudgetModel:
    return LaunchBudgetModel(
        base_s=0.1, per_token_s=0.01, multiplier=1.0,
        min_budget_s=seconds, max_budget_s=seconds,
    )


@pytest.fixture(scope="module")
def eng():
    from conftest import shared_engine

    return shared_engine(model="tiny")


@pytest.fixture(scope="module")
def paged_eng():
    from conftest import shared_engine

    return shared_engine(model="tiny", kv_layout="paged", kv_page_size=16)


def test_rebuild_replay_yields_one_flight_record_annotated(eng):
    """Epoch safety: a request that survives a hung step, engine rebuild,
    and journal replay finishes with EXACTLY ONE flight record for its
    trace_id — the SAME trace object rode through the rebuild — and that
    record is annotated ``replayed`` with the replayed row count."""
    loop = ContinuousDecodeLoop(
        eng, width=4, max_prompt=64, max_new=32,
        budget_model=_step_budget(6.0), rebuild_fn=lambda: eng, max_rebuilds=3,
    )
    trace = TRACER.start()
    try:
        with fp.failpoints(
            {"continuous.step": FailSpec(action="hang", times=1, delay=20.0)}
        ):
            with use_trace(trace):
                fut = loop.submit(
                    [5, 6, 7, 8], n=2, max_new=8,
                    temperature=0.0, top_p=None, seed=23,
                )
            got = fut.result(timeout=120)
        assert int(got.lengths[0]) > 0
        assert loop.stats["replayed_rows"] >= 2
    finally:
        loop.stop()
    TRACER.finish(trace, route="continuous", status="ok", n=2)
    # Re-finishing must NOT mint a second record (owner-finishes contract).
    TRACER.finish(trace, route="continuous", status="ok", n=2)

    recs = [
        r for r in FLIGHT_RECORDER.snapshot() if r["trace_id"] == trace.trace_id
    ]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["annotations"].get("replayed") is True
    assert rec["annotations"].get("replayed_rows", 0) >= 2
    # The trace accumulated the loop's phases across BOTH epochs.
    for phase in ("queue_wait", "prefill", "decode"):
        assert phase in rec["phases"], rec["phases"]


def test_tracing_differential_byte_identity(eng, tmp_path):
    """Observability never touches the math: the same seeded request decodes
    to identical tokens with a live trace bound, with the tracer dropped by
    the ``serving.trace`` failpoint, with no trace at all, and with a
    profiler capture running."""
    outs = []
    for mode in ("traced", "dropped", "untraced", "captured"):
        loop = ContinuousDecodeLoop(eng, width=4, max_prompt=64, max_new=32)
        try:
            if mode == "captured":
                # Every span's TraceAnnotation is live while a capture runs.
                jax.profiler.start_trace(str(tmp_path))
                trace = TRACER.start()
            elif mode == "traced":
                trace = TRACER.start()
            elif mode == "dropped":
                with fp.failpoints(
                    {"serving.trace": FailSpec(action="drop", times=1)}
                ):
                    trace = TRACER.start()
                assert trace.noop
            else:
                trace = None
            with use_trace(trace):
                fut = loop.submit(
                    [5, 6, 7, 8], n=2, max_new=8,
                    temperature=0.8, top_p=0.9, seed=23,
                )
            outs.append(fut.result(timeout=120))
        finally:
            loop.stop()
            if mode == "captured":
                jax.profiler.stop_trace()
    base = outs[0]
    for other in outs[1:]:
        assert np.array_equal(base.tokens, other.tokens)
        assert np.allclose(base.logprobs, other.logprobs, atol=1e-6)
        assert list(base.lengths) == list(other.lengths)


# -- the loop names its own work (PR 25) -----------------------------------


def test_latency_span_observes_once_and_refuses_undeclared():
    h = LatencyHistograms(declared=("a.b",), buckets=(0.1, 1.0))
    with h.span("a.b", step=3) as span:
        time.sleep(0.002)
    assert h.count("a.b") == 1
    assert span.seconds >= 0.002
    assert h.snapshot()["a.b"]["sum"] == pytest.approx(span.seconds)
    # The vocabulary is checked before the block runs, like observe().
    with pytest.raises(ValueError, match="not declared"):
        h.span("a.typo")
    # A block that raises is not a sample of the region's latency.
    with pytest.raises(RuntimeError):
        with h.span("a.b"):
            raise RuntimeError("boom")
    assert h.count("a.b") == 1


LOOP_FAMILIES = (
    "gap", "prepare", "handoff", "bookkeep", "admit", "emit", "idle",
    "dispatch", "readback", "step", "prefill_chunk", "prefill_wall",
    "decode_wall",
    # PR 36: whose wait each part of a gap is.
    "wait", "lock_wait", "stage", "pages", "admit_device", "fetch",
)


def _loop_totals():
    snap = LATENCY.snapshot()
    return {
        name: (snap[f"continuous.{name}"]["count"], snap[f"continuous.{name}"]["sum"])
        for name in LOOP_FAMILIES
    }


def _submit_mix(loop, sink):
    """Two streamed and two unstreamed requests of two rows each into a loop
    four rows wide: the later ones wait for slots, and of the two prompts
    long enough to chunk the second waits for the PREFILLING lane."""
    long_a, long_b = list(range(5, 45)), list(range(45, 85))
    futs = [
        loop.submit(ids, n=2, max_new=12, temperature=0.8, top_p=0.9,
                    seed=seed, token_sink=tap)
        for seed, (ids, tap) in enumerate(
            [(long_a, sink), (long_b, None), ([5, 6, 7], sink), ([8, 9], None)]
        )
    ]
    return [f.result(timeout=120) for f in futs]


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_loop_spans_split_the_gap(eng, paged_eng, layout):
    """A run of streamed and unstreamed requests through the watchdogged
    loop: every step leaves one prepare, one bookkeep and one hand-off, every
    program but the first after a pause leaves a gap, and the four parts of
    the gap add up to it. Since PR 36 the prepare splits into the lock's wait,
    the page books and the uploads, a readback ends in a fetch, and the gap
    less the admissions' device work is the wait."""
    loop = ContinuousDecodeLoop(
        paged_eng if layout == "paged" else eng, width=4, max_prompt=64, max_new=32, prefill_chunk_tokens=32,
        budget_model=_step_budget(60.0),
    )
    sunk = []
    sink = lambda step, row: sunk.append(step)  # noqa: E731
    try:
        _submit_mix(loop, sink)  # compiles every program the mix uses
        assert loop.drain(timeout=60)
        with loop._lock:
            loop._closing = False  # drain() closes admission; reopen it
        stats0, before, sunk0 = loop.stats, _loop_totals(), len(sunk)
        results = _submit_mix(loop, sink)
        assert loop.drain(timeout=60)
        stats1, after = loop.stats, _loop_totals()
    finally:
        loop.stop()
    assert all(int(r.lengths[0]) > 0 for r in results)
    count = {n: after[n][0] - before[n][0] for n in LOOP_FAMILIES}
    total = {n: after[n][1] - before[n][1] for n in LOOP_FAMILIES}
    steps = stats1["steps"] - stats0["steps"]
    chunks = stats1["prefill_chunks"] - stats0["prefill_chunks"]
    assert steps >= 12 and chunks >= 4
    # One per step, exactly; a hand-off, a dispatch and a readback per program.
    assert count["prepare"] == count["bookkeep"] == count["step"] == steps
    assert count["prefill_chunk"] == chunks
    assert count["handoff"] == count["dispatch"] == count["readback"] == steps + chunks
    # A gap per program, except the first after each pause of the loop.
    assert steps + chunks - count["idle"] - 1 <= count["gap"] <= steps + chunks
    assert count["admit"] >= 4 + 2 * chunks
    assert count["prefill_wall"] == count["decode_wall"] == 4
    # The parts add up to the gap. What they leave out is a few lines between
    # spans; what they add is the admit and the prepare ahead of the first
    # program after a pause, which no gap covers. Stated tolerance: 35 %.
    parts = sum(total[n] for n in ("prepare", "handoff", "bookkeep", "admit"))
    assert total["gap"] > 0
    assert abs(parts - total["gap"]) <= 0.35 * total["gap"], (total, count)
    # The prepare's three parts, one of each a step (page books are the paged
    # loop's alone), and they account for it to within the lines between them.
    assert count["lock_wait"] == count["stage"] == steps
    assert count["pages"] == (steps if layout == "paged" else 0)
    prepare_parts = sum(total[n] for n in ("lock_wait", "stage", "pages"))
    assert prepare_parts <= total["prepare"]
    assert prepare_parts >= 0.8 * total["prepare"], (total, count)
    assert total["lock_wait"] <= total["stage"]  # nobody holds the lock here
    # A fetch a readback, the tail of it.
    assert count["fetch"] == count["readback"]
    assert 0 < total["fetch"] <= total["readback"]
    # An admission's device work: once for each whole-prompt admission and for
    # each prompt's last chunk, inside the admit span.
    assert count["admit_device"] == 4
    assert 0 < total["admit_device"] <= total["admit"]
    # The wait is the gap less the device work inside it: a sample a gap, and
    # with the device work it gives the gap back. What it may add is an
    # admission ahead of the first program after a pause, which no gap covers.
    # Stated tolerance: 10 %.
    assert count["wait"] == count["gap"]
    assert 0 < total["wait"] <= total["gap"]
    both = total["wait"] + total["admit_device"]
    assert total["gap"] - 1e-6 <= both <= 1.1 * total["gap"], (total, count)
    # The loop's active clock holds the programs and the waits between them.
    # A hand-off lies in both (inside its program's span and inside the gap
    # round it), so it is taken out once; what the active clock has beyond
    # that is the admissions' device work and the bookkeeping ahead of a
    # pause. Slack: the few lines a program between one clock and the other.
    active = stats1["active_seconds"] - stats0["active_seconds"]
    programs = total["step"] + total["prefill_chunk"]
    assert active >= programs, (active, total)
    assert active + 0.05 * programs >= programs + total["wait"] - total["handoff"], (
        active, total)
    # The sinks run inside the bookkeeping (the first delta's inside admit).
    assert count["emit"] >= len(sunk) - sunk0 > 0
    assert total["emit"] <= total["bookkeep"]
    # The queue's head was left behind for both reasons the mix provokes.
    assert stats1["blocked_slots"] > stats0["blocked_slots"]
    assert stats1["blocked_lane"] > stats0["blocked_lane"]
    assert stats1["blocked_pages"] == 0


def test_request_wall_phases_reach_the_flight_record(eng):
    """``prefill_wall`` (dequeue -> rows installed) and ``decode_wall`` (rows
    installed -> future resolved) are phases of the request's trace, and with
    ``queue_wait`` they fit inside the request's duration."""
    loop = ContinuousDecodeLoop(eng, width=4, max_prompt=64, max_new=32)
    trace = TRACER.start()
    try:
        with use_trace(trace):
            fut = loop.submit(
                [5, 6, 7, 8], n=2, max_new=8, temperature=0.8, top_p=0.9, seed=3,
            )
        fut.result(timeout=120)
    finally:
        loop.stop()
    rec = TRACER.finish(trace, route="continuous", status="ok", n=2)
    phases = rec["phases"]
    for phase in ("queue_wait", "prefill_wall", "decode_wall", "prefill", "decode"):
        assert phase in phases, phases
    assert phases["prefill_wall"] >= phases["prefill"] > 0
    assert phases["decode_wall"] >= phases["decode"] > 0
    walls = phases["queue_wait"] + phases["prefill_wall"] + phases["decode_wall"]
    assert walls <= rec["duration_s"] + 1e-3, (phases, rec["duration_s"])


def _host_event_names(log_dir):
    """Names of the events on the ``/host:`` planes of the capture in
    ``log_dir``, read back with ``jax.profiler.ProfileData``."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    names = set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


@pytest.mark.parametrize("python_tracer", [False, True])
def test_debug_profile_capture_holds_the_loops_spans(paged_eng, tmp_path, python_tracer):
    """A ``/debug/profile`` capture taken while the loop decodes holds the
    loop's spans under their plain names on a host plane (a paged loop's: the
    page books' span is one of them). The Python tracer
    is off unless the payload asks for it: its one-event-per-call frames
    (``$file.py:line function``) slow the traced threads enough to misread the
    device's idle share."""
    client = _fake_client()
    client.backend.backend_config = SimpleNamespace(debug_endpoints=True)
    app = ServingApp(client)
    loop = ContinuousDecodeLoop(
        paged_eng, width=4, max_prompt=64, max_new=32, budget_model=_step_budget(60.0),
    )
    stop = threading.Event()

    def feed():
        while not stop.is_set():
            loop.submit(
                [5, 6, 7, 8], n=2, max_new=16, temperature=0.8, top_p=0.9,
                seed=5, token_sink=lambda step, row: None,
            ).result(timeout=120)

    feeder = threading.Thread(target=feed, daemon=True)
    payload = {"duration_s": 0.4, "log_dir": str(tmp_path)}
    if python_tracer:
        payload["python_tracer"] = True

    async def go():
        async with _asgi(app) as c:
            return await c.post("/debug/profile", json=payload)

    try:
        loop.submit(  # warm: the capture must see steps, not compiles
            [5, 6, 7, 8], n=2, max_new=4, temperature=0.8, top_p=0.9, seed=5,
        ).result(timeout=120)
        feeder.start()
        resp = _run(go())
    finally:
        stop.set()
        feeder.join(timeout=120)
        loop.stop()
    assert not feeder.is_alive()
    assert resp.status_code == 200
    assert resp.json()["python_tracer"] is python_tracer
    assert resp.json()["host_tracer_level"] == 2
    names = _host_event_names(str(tmp_path))
    for span in ("step", "prepare", "dispatch", "readback", "bookkeep", "emit", "host",
                 # PR 36: the parts, under their own names.
                 "lock_wait", "stage", "pages", "admit_device", "fetch"):
        assert f"continuous.{span}" in names, sorted(n for n in names if "continuous" in n)
    python_frames = {n for n in names if n.startswith("$")}
    assert bool(python_frames) is python_tracer, sorted(python_frames)[:5]


def test_debug_profile_rejects_bad_options():
    client = _fake_client()
    client.backend.backend_config = SimpleNamespace(debug_endpoints=True)
    app = ServingApp(client)

    async def go():
        async with _asgi(app) as c:
            return [
                await c.post("/debug/profile", json=bad)
                for bad in ({"python_tracer": "yes"}, {"host_tracer_level": "deep"})
            ]

    assert [r.status_code for r in _run(go())] == [400, 400]


# -- what the benchmark makes of the new spans (PR 36) ---------------------------------


_MS = 1_000_000


def _planes(host_events):
    """A device that ran 0–10 ms and 20–30 ms (one idle gap of 10 ms) beside
    the given host events, as ``reduce_trace.reduce`` takes them."""
    return {
        "/device:TPU:0": {"XLA Ops": [("%a = f32[] add()", 0, 10 * _MS),
                                      ("%a = f32[] add()", 20 * _MS, 30 * _MS)]},
        "/host:CPU": {"kllms-continuous": host_events},
    }


@pytest.mark.parametrize("children, owner", [
    # A child that covers 60 % of the gap by itself takes it from its parent.
    ([("continuous.stage", 11 * _MS, 17 * _MS)], "continuous.stage"),
    # Three children of a third each: none covers half, the parent keeps it.
    ([("continuous.lock_wait", 10 * _MS, 13 * _MS), ("continuous.pages", 13 * _MS, 16 * _MS),
      ("continuous.stage", 16 * _MS, 19 * _MS)], "continuous.prepare"),
    # Exactly half is enough, and of two that qualify the shorter wins.
    ([("continuous.pages", 10 * _MS, 15 * _MS), ("continuous.stage", 13 * _MS, 19 * _MS)],
     "continuous.pages"),
    # A fetch that is the tail of its readback owns the gap after the device
    # finished; the readback alone would have.
    ([("continuous.readback", 2 * _MS, 18 * _MS), ("continuous.fetch", 10 * _MS, 18 * _MS)],
     "continuous.fetch"),
    # A lock's wait is a span of ours, not one of the waits the reduction
    # skips by its last word ("acquire", "wait", ...).
    ([("continuous.lock_wait", 10 * _MS, 19 * _MS)], "continuous.lock_wait"),
    # No child at all: as before this PR.
    ([], "continuous.prepare"),
], ids=["one_child_60", "three_thirds", "half_and_shorter", "fetch_in_readback",
        "lock_wait_is_no_skipped_wait", "no_child"])
def test_nested_spans_narrow_an_idle_gaps_owner_and_never_widen_it(children, owner):
    """The contract the nesting relies on (``benchmark/reduce_trace.py``, the
    benchmark's: only read here): a gap goes to the shortest host event that
    covers half of it, so a span inside ``continuous.prepare`` wins a gap only
    when it covers half of it by itself, and else leaves it where it was."""
    reduce_trace = benchmark_module("reduce_trace")
    host = [("continuous.host", 0, 30 * _MS), ("continuous.prepare", 10 * _MS, 20 * _MS)]
    out = reduce_trace.reduce(_planes(host + children))
    assert out["gaps"] == 1 and out["gap_s"] == pytest.approx(0.010)
    assert out["breakdown"]["idle_gaps"] == [[owner, pytest.approx(0.010)]]


NEW_LOOP_METRICS = (
    "loop_wait_ms", "host_wait_share", "loop_admit_device_ms", "loop_lock_wait_ms",
    "loop_stage_ms", "loop_pages_ms", "loop_launch_ms", "loop_fetch_ms",
)


def test_new_loop_metrics_read_what_they_say():
    """Each new ``read`` through the benchmark's own evaluator on a canned
    pair of scrapes; the manifest stays without a fault; a program without the
    families (this PR's parent) yields nothing and does not raise."""
    run = benchmark_module("run")
    assert run.check_manifest() == []
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    kernel_cells = {"qwen2-7b.extract", "qwen2-7b.chat", "mistral-7b.chat",
                    "nemotron3-nano-30b-a3b.chat", "olmo-hybrid-7b.chat"}
    for w in manifest["workloads"]:
        names = {m["name"] for m in run.load_cell(w["name"])[5]}
        assert set(NEW_LOOP_METRICS) <= names, w["name"]
        assert ("paged_attn_walked_share" in names) is (w["name"] in kernel_cells)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[n]["moves"] == "tokens_per_s" for n in NEW_LOOP_METRICS)
    assert by_name["loop_pages_ms"]["layer"] == "KV page manager"
    assert by_name["host_wait_share"]["layer"] == "Device"
    # What they add must lie at the end: nothing that was there moved.
    # (The upload counter's metric behind them, in every cell as they are;
    # the long-document cell's seven behind that; the prefill-chunk counter's
    # metric, in every cell; the olmo chat cell's one; the latent kernel's two
    # for the two latent cells, last.)
    assert [m["name"] for m in manifest["per_layer"]][-21:-11] == [
        *NEW_LOOP_METRICS, "paged_attn_walked_share", "loop_stage_uploads"]
    assert all(m["workloads"] == ["command-a-plus.extract-long"]
               + ["olmo-hybrid-7b.chat"] * (m["name"] == "paged_attn_kv_stream_share")
               for m in manifest["per_layer"][-11:-4])
    assert manifest["per_layer"][-3]["workloads"] == ["olmo-hybrid-7b.chat"]
    assert all(m["workloads"] == ["xing4-29b-a4b.extract", "joyai-llm-flash.extract"]
               and m["layer"] == "Kernels" for m in manifest["per_layer"][-2:])
    assert manifest["per_layer"][-4] == {
        "name": "prefill_tokens_per_chunk", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "Model step: prefill", "moves": "tokens_per_s"}
    assert by_name["loop_stage_uploads"] == {
        "name": "loop_stage_uploads", "unit": "arrays", "better": "lower",
        "source": "program_counter", "layer": "Admission and schedulers", "moves": "tokens_per_s"}

    def sums(**seconds):
        return {f"kllms_continuous_{k}_seconds_sum": v for k, v in seconds.items()}

    start = dict(sums(wait=1.0, admit_device=0.5, lock_wait=0.01, stage=0.4, pages=0.2,
                      dispatch=0.3, fetch=0.1),
                 kllms_continuous_steps=100.0, kllms_continuous_active_seconds=10.0,
                 kllms_continuous_stage_uploads=100.0,
                 kllms_continuous_dispatch_seconds_count=150.0,
                 kllms_continuous_fetch_seconds_count=150.0,
                 kllms_paged_attn_pages_walked=1000.0, kllms_paged_attn_pages_tabled=10000.0)
    end = dict(sums(wait=6.0, admit_device=1.5, lock_wait=0.03, stage=2.4, pages=1.2,
                    dispatch=1.8, fetch=0.7),
               kllms_continuous_steps=1100.0, kllms_continuous_active_seconds=50.0,
               kllms_continuous_stage_uploads=1102.0,  # two steps under the failpoint's mask
               kllms_continuous_dispatch_seconds_count=1650.0,
               kllms_continuous_fetch_seconds_count=1650.0,
               kllms_paged_attn_pages_walked=101000.0, kllms_paged_attn_pages_tabled=410000.0)
    src = {"metrics_start": start, "metrics_end": end}
    read = {n: run.load_json(run.HERE, "layer_metrics", n + ".json")["read"]
            for n in (*NEW_LOOP_METRICS, "paged_attn_walked_share", "loop_stage_uploads")}
    values = {n: run.evaluate(r, src) for n, r in read.items()}
    assert values == {
        "loop_wait_ms": pytest.approx(5.0), "host_wait_share": pytest.approx(12.5),
        "loop_admit_device_ms": pytest.approx(1.0), "loop_lock_wait_ms": pytest.approx(0.02),
        "loop_stage_ms": pytest.approx(2.0), "loop_pages_ms": pytest.approx(1.0),
        "loop_launch_ms": pytest.approx(1.0), "loop_fetch_ms": pytest.approx(0.4),
        "paged_attn_walked_share": pytest.approx(25.0),
        "loop_stage_uploads": pytest.approx(1.002),
    }
    # The parent: no such families, no such gauge; the kernel's counters at
    # zero where the XLA path serves. Left out, never zero, never an error.
    bare = {"metrics_start": {"kllms_continuous_steps": 100.0},
            "metrics_end": {"kllms_continuous_steps": 1100.0,
                            "kllms_paged_attn_pages_walked": 0.0,
                            "kllms_paged_attn_pages_tabled": 0.0}}
    assert all(run.evaluate(r, bare) is None for r in read.values())


def test_paged_step_program_carries_scope_names():
    """Names on the device side are metadata of the step programs: the paged
    step's lowered text carries every scope (the chip's profiler writes them
    into the device plane's event metadata)."""
    from conftest import shared_engine

    paged = shared_engine(model="tiny", kv_layout="paged", kv_page_size=16)
    loop = ContinuousDecodeLoop(paged, width=4, max_prompt=64, max_new=32)
    try:
        loop.submit(
            [5, 6, 7, 8], n=2, max_new=2, temperature=0.8, top_p=0.9, seed=1,
        ).result(timeout=120)
        W = loop.width
        packed = loop._pack_step(np.zeros((W, 1), np.int32), loop._pages.tables)
        lowered = loop._step_fn.lower(
            paged.params, loop._pool.kv.k, loop._pool.kv.v, jnp.asarray(packed),
            jnp.zeros((W,), bool),
        ).as_text(debug_info=True)
    finally:
        loop.stop()
    # Ops of the layer scan's body are located relative to it ("mlp/..."),
    # the others from the program's root ("jit(_step_paged)/sampler/...").
    located = set(re.findall(r'loc\("([^"]+)"', lowered))
    for scope in (
        "embed", "attn_qkv", "paged_attn", "attn_out", "mlp",
        "lm_head", "kv_write", "sampler",
    ):
        assert any(
            name.startswith(scope + "/") or f"/{scope}/" in name for name in located
        ), scope
    # The pool is read in place, by layer number: the scope that named the
    # per-layer slice of it is gone with the slice.
    assert not any("kv_slice" in name for name in located)


def _kernel_call(kernel):
    """A traceable call of one Pallas kernel at a toy shape."""
    from k_llms_tpu.ops.attention import decode_prefix_attention, flash_attention
    from k_llms_tpu.ops.paged_attention import (
        paged_attention_page_tables,
        paged_decode_attention_pallas,
    )
    from k_llms_tpu.ops.w4matmul import pack_int4, w4_matmul

    f32 = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    if kernel == "paged_attention_decode":
        tables = paged_attention_page_tables(
            jnp.zeros((2, 16), jnp.int32), jnp.zeros((2, 8), jnp.int32), 8
        )
        return lambda: paged_decode_attention_pallas(
            f32(2, 4, 16), f32(1, 32, 2, 16), f32(1, 32, 2, 16), jnp.int32(0),
            *tables,
            f32(2, 2, 16), f32(2, 2, 16), jnp.ones((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32), page_size=8, sm_scale=0.25, interpret=True,
        )
    if kernel in ("flash_prefill", "flash_continue"):
        offset = {"q_offset": 8} if kernel == "flash_continue" else {}
        return lambda: flash_attention(
            f32(1, 4, 16, 16), f32(1, 2, 32, 16), f32(1, 2, 32, 16),
            causal=True, interpret=True, **offset,
        )
    if kernel == "decode_prefix_attention":
        return lambda: decode_prefix_attention(
            f32(8, 4, 16), f32(1, 32, 2, 16), f32(1, 32, 2, 16),
            jnp.full((1,), 32, jnp.int32), interpret=True,
        )
    assert kernel == "w4_matmul"
    return lambda: w4_matmul(f32(16, 256), pack_int4(f32(256, 256)), interpret=True)


@pytest.mark.parametrize(
    "kernel",
    ["paged_attention_decode", "flash_prefill", "flash_continue",
     "decode_prefix_attention", "w4_matmul"],
)
def test_pallas_kernels_carry_names(kernel):
    """Each ``pl.pallas_call`` site names its kernel; a CPU lowering shows the
    name on the ``pallas_call`` equation of the jaxpr (the chip's compiler
    writes it into the custom call, where a capture reads it)."""
    jaxpr = str(jax.make_jaxpr(_kernel_call(kernel))())
    assert "pallas_call" in jaxpr
    assert re.search(rf"name={kernel}\b", jaxpr), re.findall(r"name=\S+", jaxpr)


@pytest.mark.parametrize("model", ["nemotron3-tiny", "olmo-hybrid-tiny"])
def test_delta_rule_counters_count_rows_times_layers_and_stay_zero_elsewhere(model):
    """``kllms_gdn_state_updates`` / ``kllms_gdn_tokens_scanned`` through
    ``note_model_aux``, as the loop adds them up at readback: a 20-token chunk
    of a 32-token program (one row) and a step of three rows, two of them live,
    count rows x delta-rule layers and valid tokens x layers for the
    delta-rule model; for the Mamba-2 model they stay at zero while its own
    ``ssm_*`` pair counts the same way."""
    from conftest import shared_params
    from k_llms_tpu.models import get_config, llama
    from k_llms_tpu.models.llama import KVCache
    from k_llms_tpu.utils.observability import MODEL_COUNTERS, note_model_aux

    cfg = get_config(model)
    params = shared_params(cfg)
    layers = cfg.layer_pattern.count("D") or cfg.layer_pattern.count("M")
    mine = "gdn" if "D" in cfg.layer_pattern else "ssm"
    other = "ssm" if mine == "gdn" else "gdn"
    heads, kw, vw = cfg.cache_widths
    pool = KVCache(k=jnp.zeros((cfg.paging_layers, 64, heads, kw)),
                   v=jnp.zeros((cfg.paging_layers, 64, heads, vw)))

    @jax.jit
    def chunk_then_step(params, pool):
        aux, state = {}, {}
        llama.prefill_chunk_step_paged(cfg, params, jnp.zeros((1, 32), jnp.int32),
                                       llama.init_cache(cfg, 1, 64), jnp.int32(0), jnp.int32(20),
                                       aux=aux, state=state)
        step_aux, state = {}, jax.tree.map(lambda a: jnp.repeat(a, 3, axis=0), state)
        llama.paged_verify_step(cfg, params, jnp.ones((3, 1), jnp.int32), jnp.zeros((3,), jnp.int32),
                                jnp.full((3,), 20), pool, jnp.zeros((3, 32), jnp.int32),
                                jnp.zeros((3, 8), jnp.int32), aux=step_aux, state=state,
                                active=jnp.asarray([True, False, True]))
        return aux, step_aux

    before = MODEL_COUNTERS.snapshot()
    for aux in jax.device_get(chunk_then_step(params, pool)):
        note_model_aux(aux)
    grew = {k: v - before.get(k, 0) for k, v in MODEL_COUNTERS.snapshot().items()}
    assert grew[f"{mine}_state_updates"] == (1 + 2) * layers
    assert grew[f"{mine}_tokens_scanned"] == (20 + 2) * layers
    assert grew.get(f"{other}_state_updates", 0) == grew.get(f"{other}_tokens_scanned", 0) == 0
