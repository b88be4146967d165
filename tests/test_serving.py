"""Wire-surface tests for the HTTP front door (k_llms_tpu/serving/).

The in-process tier runs the ASGI app under httpx.ASGITransport — no sockets,
byte-level assertions against the same client library the server wraps. The
socket tier stands up the stdlib HTTP/1.1 runner (ServerThread) on loopback.
No pytest-asyncio in the image: async test bodies run via asyncio.run().
"""

import asyncio
import json
import time

import jax

import httpx
import pytest

from k_llms_tpu import KLLMs
from k_llms_tpu.backends.fake import FakeBackend
from k_llms_tpu.reliability import failpoints as fp
from k_llms_tpu.reliability.failpoints import FailSpec
from k_llms_tpu.serving import ServerThread, ServingApp
from k_llms_tpu.serving.sse import parse_stream
from k_llms_tpu.types.wire import (
    BackendUnavailableError,
    RateLimitError,
    RequestTimeoutError,
    ServerDrainingError,
)
from k_llms_tpu.utils.observability import FAILURE_EVENTS, SERVE_EVENTS, STREAM_EVENTS


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


# -- in-process: non-stream ------------------------------------------------
def test_nonstream_byte_parity_with_inprocess_create(monkeypatch):
    """The wire bytes of stream=false must be exactly the client library's
    model_dump of the same call — the HTTP layer adds nothing and loses
    nothing. `created` is frozen so both paths see one clock."""
    client = _fake_client()
    app = ServingApp(client)
    frozen = int(time.time())
    monkeypatch.setattr(time, "time", lambda: frozen)

    async def go():
        async with _asgi(app) as c:
            return await c.post("/v1/chat/completions", json=BODY)

    wire = _run(go())
    assert wire.status_code == 200
    direct = _fake_client().chat.completions.create(**BODY)
    assert wire.content == json.dumps(
        direct.model_dump(mode="json"), separators=(",", ":")
    ).encode()


def test_nonstream_consensus_shape():
    app = ServingApp(_fake_client())

    async def go():
        async with _asgi(app) as c:
            return await c.post("/v1/chat/completions", json=BODY)

    payload = _run(go()).json()
    assert payload["object"] == "chat.completion"
    assert len(payload["choices"]) == BODY["n"] + 1  # consensus + samples
    assert payload["choices"][0]["index"] == 0
    assert payload["likelihoods"]


# -- in-process: SSE -------------------------------------------------------
def test_sse_event_ordering_and_final_consensus():
    app = ServingApp(_fake_client())

    async def go():
        async with _asgi(app) as c:
            return await c.post(
                "/v1/chat/completions", json={**BODY, "stream": True}
            )

    resp = _run(go())
    assert resp.status_code == 200
    assert resp.headers["content-type"].startswith("text/event-stream")
    events = list(parse_stream(resp.content))
    assert events[-1] == ("done", None)
    datas = [d for kind, d in events if kind == "data"]
    chunks = [d for d in datas if d["object"] == "chat.completion.chunk"]
    finals = [d for d in datas if d["object"] == "chat.completion"]
    assert len(finals) == 1
    # Ordering: every chunk precedes the single final consensus event.
    assert datas.index(finals[0]) == len(datas) - 1
    # Per-sample streams: wire choice indices 1..n, each with >=1 content
    # delta and role on the FIRST delta only.
    per_sample = {}
    for ch in chunks:
        c = ch["choices"][0]
        per_sample.setdefault(c["index"], []).append(c["delta"])
    assert set(per_sample) >= {1, 2, 3}
    for idx in (1, 2, 3):
        deltas = per_sample[idx]
        assert deltas[0].get("role") == "assistant"
        assert all("role" not in d for d in deltas[1:])
    # Streamed text reassembles to the final per-sample choices.
    final = finals[0]
    for idx in (1, 2, 3):
        text = "".join(d.get("content") or "" for d in per_sample[idx])
        assert text == final["choices"][idx]["message"]["content"]
    # Final consensus event is consolidated: choices[0] + likelihoods.
    assert final["choices"][0]["index"] == 0
    assert final["likelihoods"]


def test_stream_counters_move():
    app = ServingApp(_fake_client())
    before = STREAM_EVENTS.snapshot()

    async def go():
        async with _asgi(app) as c:
            await c.post("/v1/chat/completions", json={**BODY, "stream": True})

    _run(go())
    after = STREAM_EVENTS.snapshot()
    assert after.get("streams.opened", 0) > before.get("streams.opened", 0)
    assert after.get("streams.completed", 0) > before.get("streams.completed", 0)
    assert after.get("tokens.streamed", 0) > before.get("tokens.streamed", 0)


# -- error mapping ---------------------------------------------------------
class _ErrorBackend(FakeBackend):
    def __init__(self, exc):
        super().__init__(["x"])
        self._exc = exc

    def chat_completion(self, request):
        raise self._exc


@pytest.mark.parametrize(
    "exc,status",
    [
        (RateLimitError("queue full", retry_after=7.0), 429),
        (ServerDrainingError("draining"), 503),
        (BackendUnavailableError("engine down"), 503),
        (RequestTimeoutError("deadline exceeded"), 408),
    ],
)
def test_typed_wire_errors_map_to_http(exc, status):
    app = ServingApp(KLLMs(backend=_ErrorBackend(exc), model="m"))

    async def go():
        async with _asgi(app) as c:
            return await c.post("/v1/chat/completions", json=BODY)

    resp = _run(go())
    assert resp.status_code == status
    err = resp.json()["error"]
    assert err["message"]
    assert err["type"] == exc.as_wire()["error"]["type"]
    if isinstance(exc, RateLimitError):
        assert resp.headers["retry-after"] == "7"


def test_bad_json_and_missing_messages_are_400():
    app = ServingApp(_fake_client())

    async def go():
        async with _asgi(app) as c:
            r1 = await c.post("/v1/chat/completions", content=b"{nope")
            r2 = await c.post("/v1/chat/completions", json={"messages": []})
            r3 = await c.get("/unknown/route")
            return r1, r2, r3

    r1, r2, r3 = _run(go())
    assert r1.status_code == 400
    assert r1.json()["error"]["type"] == "invalid_request_error"
    assert r2.status_code == 400
    assert r2.json()["error"]["param"] == "messages"
    assert r3.status_code == 404


def test_stream_unsupported_backend_is_typed_400():
    """A non-streaming backend yields the OpenAI-shaped invalid_request_error
    with param=stream — in-process (raise) and over the wire (400)."""

    class NoStream(FakeBackend):
        supports_streaming = False

    client = KLLMs(backend=NoStream(["x"]), model="m")
    from k_llms_tpu.types.wire import InvalidRequestError

    with pytest.raises(InvalidRequestError) as ei:
        client.chat.completions.create(**BODY, stream=True)
    assert ei.value.param == "stream"
    assert ei.value.status_code == 400

    app = ServingApp(client)

    async def go():
        async with _asgi(app) as c:
            return await c.post(
                "/v1/chat/completions", json={**BODY, "stream": True}
            )

    resp = _run(go())
    assert resp.status_code == 400
    assert resp.json()["error"]["param"] == "stream"


def test_parse_rejects_stream():
    from pydantic import BaseModel

    from k_llms_tpu.types.wire import InvalidRequestError

    class Out(BaseModel):
        x: int

    client = _fake_client()
    with pytest.raises(InvalidRequestError):
        client.chat.completions.parse(
            messages=BODY["messages"], response_format=Out, stream=True
        )


# -- healthz / metrics -----------------------------------------------------
def test_healthz_and_metrics_fake():
    app = ServingApp(_fake_client())

    async def go():
        async with _asgi(app) as c:
            h = await c.get("/healthz")
            m = await c.get("/metrics")
            return h, m

    h, m = _run(go())
    assert h.status_code == 200
    assert m.status_code == 200
    assert "kllms_serve_events_total" in m.text
    assert 'event="request.healthz.200"' in m.text


# -- serving.request failpoint --------------------------------------------
def test_serving_request_failpoint_raise_maps_to_500():
    app = ServingApp(_fake_client())

    async def go():
        async with _asgi(app) as c:
            return await c.post("/v1/chat/completions", json=BODY)

    with fp.failpoints({"serving.request": FailSpec(action="raise", times=1)}):
        resp = _run(go())
    assert resp.status_code == 500
    # Next request is clean (times=1 consumed).
    assert _run(go()).status_code == 200


def test_serving_request_disconnect_failpoint_truncates_stream():
    """KLLMS_FAILPOINTS='serving.request=disconnect:1' semantics: the server
    drops the response after the first delta exactly as if the client hung up,
    and the stream's budget is cancelled."""
    app = ServingApp(_fake_client())

    async def go():
        async with _asgi(app) as c:
            return await c.post(
                "/v1/chat/completions", json={**BODY, "stream": True}
            )

    before = SERVE_EVENTS.snapshot().get("request.disconnect", 0)
    with fp.failpoints({"serving.request": FailSpec(action="disconnect", times=1)}):
        resp = _run(go())
    events = list(parse_stream(resp.content))
    datas = [d for kind, d in events if kind == "data"]
    # Truncated: deltas only — no final consensus event, no [DONE].
    assert all(d["object"] == "chat.completion.chunk" for d in datas)
    assert ("done", None) not in events
    assert SERVE_EVENTS.snapshot()["request.disconnect"] == before + 1


def test_serving_request_disconnect_parses_from_env():
    from k_llms_tpu.reliability import failpoints as _fpmod

    _fpmod.configure_from_env("serving.request=disconnect:2")
    try:
        spec = _fpmod._registry["serving.request"]
        assert spec.action == "disconnect"
        assert spec.times == 2
    finally:
        _fpmod.clear()


# -- TPU backend over the wire --------------------------------------------
def _tpu_client(**cfg):
    import jax
    from conftest import shared_engine

    from k_llms_tpu.backends.tpu import TpuBackend

    engine = (
        shared_engine("tiny", mesh_shape=(8, 1)) if len(jax.devices()) == 8 else None
    )
    backend = TpuBackend(model="tiny", max_new_tokens=12, engine=engine, **cfg)
    return KLLMs(backend=backend, model="tiny")


@pytest.fixture(scope="module")
def tpu_app():
    client = _tpu_client()
    yield ServingApp(client), client
    client.close()


def test_tpu_nonstream_byte_parity(tpu_app, monkeypatch):
    """Acceptance: non-stream JSON over the wire is byte-identical to the
    in-process client result for a pinned seed (deterministic ids + frozen
    clock; ASGITransport shares the process, so the same engine serves both)."""
    app, client = tpu_app
    body = {**BODY, "model": "tiny", "max_tokens": 8}
    frozen = int(time.time())
    monkeypatch.setattr(time, "time", lambda: frozen)

    async def go():
        async with _asgi(app) as c:
            return await c.post("/v1/chat/completions", json=body)

    wire = _run(go())
    assert wire.status_code == 200
    direct = client.chat.completions.create(**body)
    assert wire.content == json.dumps(
        direct.model_dump(mode="json"), separators=(",", ":")
    ).encode()


def test_tpu_stream_deltas_before_final(tpu_app):
    """Acceptance: stream=true over the TPU-CPU backend produces >=1 content
    delta for every live sample before the final consensus event."""
    app, _ = tpu_app
    body = {**BODY, "model": "tiny", "n": 2, "max_tokens": 8, "stream": True}

    async def go():
        async with _asgi(app) as c:
            return await c.post("/v1/chat/completions", json=body)

    resp = _run(go())
    assert resp.status_code == 200
    datas = [d for kind, d in parse_stream(resp.content) if kind == "data"]
    finals = [d for d in datas if d["object"] == "chat.completion"]
    assert len(finals) == 1 and datas[-1] is finals[0]
    seen = set()
    for d in datas[:-1]:
        c = d["choices"][0]
        if c["delta"].get("content"):
            seen.add(c["index"])
    assert seen >= {1, 2}


def test_tpu_healthz_lifecycle(tpu_app):
    """healthz follows the scheduler lifecycle: 200 while READY, 503 after
    drain(). Runs last-ish in this module's fixture lifetime — it kills the
    module-scoped backend, so it builds its own."""
    client = _tpu_client()
    app = ServingApp(client)

    async def go(path="/healthz"):
        async with _asgi(app) as c:
            return await c.get(path)

    r = _run(go())
    assert r.status_code == 200
    assert r.json()["state"] == "ready"
    # The device facts a JAX-free parent (chip_smoke.py) reads: what the
    # process runs on and what the configured attention names resolved to.
    device = r.json()["device"]
    assert device["platform"] == "cpu" and device["device_count"] == len(jax.devices())
    assert device["device_kind"] == jax.devices()[0].device_kind
    # Off-TPU every configured name resolves to the XLA reference ("paged" is
    # None on the shared dense-layout engine the mesh run injects).
    assert device["attention"]["prefill"] == device["attention"]["decode"] == "xla"
    assert device["attention"]["paged"] in ("xla", None)
    assert device["native"]["loaded"] is True
    assert "mesh" in device and device["compile"]["programs"] >= 0
    client.backend.drain(timeout=30)
    r = _run(go())
    assert r.status_code == 503
    assert r.json()["state"] in ("draining", "stopped")
    # Post-drain chat requests get the typed 503, not a hang.
    async def chat():
        async with _asgi(app) as c:
            return await c.post(
                "/v1/chat/completions", json={**BODY, "model": "tiny"}
            )

    resp = _run(chat())
    assert resp.status_code == 503
    client.close()


# -- real socket -----------------------------------------------------------
def test_real_socket_smoke():
    client = _fake_client()
    with ServerThread(ServingApp(client)) as srv:
        h = httpx.get(srv.base_url + "/healthz", timeout=10)
        assert h.status_code == 200
        r = httpx.post(
            srv.base_url + "/v1/chat/completions", json=BODY, timeout=30
        )
        assert r.status_code == 200
        assert len(r.json()["choices"]) == BODY["n"] + 1
        with httpx.stream(
            "POST",
            srv.base_url + "/v1/chat/completions",
            json={**BODY, "stream": True},
            timeout=30,
        ) as resp:
            assert resp.status_code == 200
            raw = b"".join(resp.iter_raw())
        events = list(parse_stream(raw))
        assert events[-1] == ("done", None)
        assert any(
            d["object"] == "chat.completion" for kind, d in events if kind == "data"
        )


def test_real_socket_sse_keepalive_pings_during_idle_gap():
    """Keep-alive contract (PR 13): when the backend goes quiet longer than
    ``sse_ping_interval_s``, the stream emits ``: ping`` comment frames so
    idle-timeout proxies don't sever a healthy long decode — and the SSE
    parser treats them as invisible (comment lines, not events)."""
    from types import SimpleNamespace

    client = _fake_client()
    backend = client.backend
    backend.backend_config = SimpleNamespace(sse_ping_interval_s=0.15)
    orig = backend.chat_completion_stream

    def slow_stream(request, emit):
        time.sleep(0.7)  # idle gap before the first delta: ~4 ping windows
        return orig(request, emit)

    backend.chat_completion_stream = slow_stream
    pings_before = STREAM_EVENTS.snapshot().get("streams.pings", 0)
    with ServerThread(ServingApp(client)) as srv:
        with httpx.stream(
            "POST",
            srv.base_url + "/v1/chat/completions",
            json={**BODY, "stream": True},
            timeout=30,
        ) as resp:
            assert resp.status_code == 200
            raw = b"".join(resp.iter_raw())
    assert raw.count(b": ping\n\n") >= 2
    assert STREAM_EVENTS.snapshot()["streams.pings"] >= pings_before + 2
    # Comment frames are transparent to consumers: the event stream parses
    # exactly as if they were never sent.
    events = list(parse_stream(raw))
    assert events[-1] == ("done", None)
    assert any(
        d["object"] == "chat.completion" for kind, d in events if kind == "data"
    )
    client.close()


@pytest.mark.slow
def test_real_socket_tpu_stream_and_disconnect_soak():
    """Acceptance soak: a real-socket client that drops the TCP connection
    mid-stream cancels the decode (engine.decode_abort moves), the scheduler
    ends READY, and no futures are left hung — repeated to shake out races."""
    client = _tpu_client(continuous_batching=True, continuous_width=4,
                         continuous_max_prompt=128, continuous_max_new=64)
    backend = client.backend
    with ServerThread(ServingApp(client)) as srv:
        # Clean stream first: >=1 delta per live sample before the final.
        body = {**BODY, "model": "tiny", "n": 2, "max_tokens": 12, "stream": True}
        with httpx.stream(
            "POST", srv.base_url + "/v1/chat/completions", json=body, timeout=120
        ) as resp:
            raw = b"".join(resp.iter_raw())
        datas = [d for kind, d in parse_stream(raw) if kind == "data"]
        assert datas[-1]["object"] == "chat.completion"
        streamed = {
            d["choices"][0]["index"]
            for d in datas[:-1]
            if d["choices"][0]["delta"].get("content")
        }
        assert streamed >= {1, 2}

        aborts_before = FAILURE_EVENTS.snapshot().get("engine.decode_abort", 0)
        for trial in range(5):
            body = {
                **BODY, "model": "tiny", "n": 2, "max_tokens": 48,
                "seed": 100 + trial, "stream": True,
            }
            try:
                with httpx.stream(
                    "POST",
                    srv.base_url + "/v1/chat/completions",
                    json=body,
                    timeout=120,
                ) as resp:
                    # Read just the first frame, then slam the connection shut.
                    for _chunk in resp.iter_raw():
                        break
            except httpx.HTTPError:
                pass
            # Give the server's EOF watcher + abort poller time to land.
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if (
                    FAILURE_EVENTS.snapshot().get("engine.decode_abort", 0)
                    > aborts_before + trial
                ):
                    break
                time.sleep(0.1)
        aborts_after = FAILURE_EVENTS.snapshot().get("engine.decode_abort", 0)
        assert aborts_after > aborts_before, (
            "mid-stream disconnects never aborted the decode "
            f"({aborts_before} -> {aborts_after})"
        )
        # The loop and scheduler both quiesce: no hung slot rows, no queued
        # futures, lifecycle back to READY.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            cont = backend._continuous
            idle = cont is None or (
                not cont._queue and all(r is None for r in cont._active)
            )
            snap = backend.scheduler.health()
            if idle and snap["queue_depth"] == 0 and snap["in_flight"] == 0:
                break
            time.sleep(0.1)
        snap = backend.scheduler.health()
        assert snap["state"] == "ready"
        assert snap["queue_depth"] == 0 and snap["in_flight"] == 0
        cont = backend._continuous
        assert not cont._queue and all(r is None for r in cont._active)
    # ServerThread.stop drains the backend on exit; a follow-up request now
    # gets the typed 503 rather than hanging.
    with pytest.raises((ServerDrainingError, BackendUnavailableError)):
        client.chat.completions.create(**{**BODY, "model": "tiny"})
