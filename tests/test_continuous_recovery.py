"""Self-healing continuous serving (PR 13): fault domains for the W-slot loop.

The load-bearing pins, one per fault domain:

- ``continuous.worker=crash`` (the previously-silent worker-death path) fails
  every queued and in-flight future with a TYPED error and restarts the loop —
  the regression this PR exists to close is a future that hangs forever.
- ``continuous.step=hang`` under the loop's own watchdog budget epoch-fences
  the abandoned dispatch thread, rebuilds the engine through ``rebuild_fn``,
  and REPLAYS the journaled in-flight rows byte-identically (greedy, sampled,
  and grammar-constrained alike), with sink deltas de-duplicated up to the
  delivery watermark so streaming clients see one contiguous stream.
- A loop-scoped ``engine.logits=nan`` quarantines exactly the poisoned row
  (typed ``sample_error.code="numeric_poison"``) while its batch neighbors
  decode on untouched — in BOTH the dense and paged step programs.
- Faults on a bare loop (no rebuild path) and faults past ``max_rebuilds``
  go TERMINAL with a typed EngineHungError instead of a rebuild storm.
"""

import json
import re
import time

import numpy as np
import pytest

from k_llms_tpu.engine.continuous import ContinuousDecodeLoop
from k_llms_tpu.reliability import failpoints as fp
from k_llms_tpu.reliability.failpoints import FailSpec
from k_llms_tpu.reliability.supervisor import LaunchBudgetModel
from k_llms_tpu.types.wire import BackendUnavailableError, EngineHungError
from k_llms_tpu.utils.observability import RECOVERY_EVENTS


def _step_budget(seconds: float) -> LaunchBudgetModel:
    """Pinned per-step watchdog budget for drills (min == max: the EWMA can
    neither loosen nor tighten it mid-test)."""
    return LaunchBudgetModel(
        base_s=0.1, per_token_s=0.01, multiplier=1.0,
        min_budget_s=seconds, max_budget_s=seconds,
    )


@pytest.fixture(scope="module")
def eng():
    from conftest import shared_engine

    return shared_engine(model="tiny")


# -- worker crash containment ----------------------------------------------


def test_worker_crash_fails_futures_typed_and_restarts(eng):
    """Regression for the silent worker-death path: the crashed worker must
    flush its futures with a typed error (not strand them forever) and the
    restarted loop must serve follow-up traffic on the same engine."""
    loop = ContinuousDecodeLoop(eng, width=2, max_prompt=64, max_new=32)
    try:
        crashes = RECOVERY_EVENTS.snapshot().get("continuous.worker_crashes", 0)
        # The failpoint is process-wide and every live loop's worker evaluates
        # it, so a loop another test left idling in this process can take the
        # one crash (seen under xdist, PR 30): arm it again until this loop's
        # worker is the one that dies.
        for _ in range(4):
            with fp.failpoints(
                {"continuous.worker": FailSpec(action="crash", times=1)}
            ):
                fut = loop.submit(
                    [1, 2, 3], n=1, max_new=8, temperature=0.0, top_p=None, seed=1
                )
                # The old code logged the crash and returned — this .result()
                # hung forever. The contract now: typed failure, promptly.
                try:
                    fut.result(timeout=30)
                except BackendUnavailableError as e:
                    assert "worker crashed" in str(e)
                    break
        else:
            pytest.fail("this loop's worker never took the injected crash")
        assert (
            RECOVERY_EVENTS.snapshot()["continuous.worker_crashes"] > crashes
        )
        st = loop.stats
        assert st["restarts"] >= 1
        assert st["last_recovery_reason"] == "worker_crash"
        # The engine was never at fault: the restarted loop decodes cleanly.
        ok = loop.submit(
            [1, 2, 3], n=1, max_new=4, temperature=0.0, top_p=None, seed=1
        ).result(timeout=120)
        assert int(ok.lengths[0]) > 0
        assert loop._terminal_error is None
    finally:
        loop.stop()


# -- hung step: watchdog + rebuild + byte-identical replay -----------------


@pytest.mark.parametrize(
    "label,kw",
    [
        ("greedy", dict(temperature=0.0, top_p=None)),
        ("sampled", dict(temperature=0.8, top_p=0.9)),
    ],
)
def test_hung_step_rebuild_replay_differential(eng, label, kw):
    """The acceptance differential: a request interrupted by a hung step and
    healed through journal + rebuild + replay returns EXACTLY the bytes of an
    uninterrupted run (pinned seed + self-deterministic row keys), and its
    token sink sees each step once — no duplicates across the fault."""
    baseline = ContinuousDecodeLoop(eng, width=4, max_prompt=64, max_new=32)
    try:
        base = baseline.submit(
            [5, 6, 7, 8], n=2, max_new=8, seed=23, **kw
        ).result(timeout=120)
    finally:
        baseline.stop()

    sunk = []
    loop = ContinuousDecodeLoop(
        eng, width=4, max_prompt=64, max_new=32,
        budget_model=_step_budget(6.0), rebuild_fn=lambda: eng, max_rebuilds=3,
    )
    try:
        hangs = RECOVERY_EVENTS.snapshot().get("continuous.step_hangs", 0)
        with fp.failpoints(
            {"continuous.step": FailSpec(action="hang", times=1, delay=20.0)}
        ):
            got = loop.submit(
                [5, 6, 7, 8], n=2, max_new=8, seed=23,
                token_sink=lambda s, t: sunk.append((s, t.copy())), **kw
            ).result(timeout=120)
        assert RECOVERY_EVENTS.snapshot()["continuous.step_hangs"] > hangs
        st = loop.stats
        assert st["restarts"] >= 1, label
        assert st["replayed_rows"] >= 2
        assert st["last_recovery_reason"] == "hung_step"
        # Byte-identical recovery.
        assert np.array_equal(got.tokens, base.tokens), label
        assert np.allclose(got.logprobs, base.logprobs, atol=1e-5)
        assert list(got.lengths) == list(base.lengths)
        # Watermark de-dup: step indices delivered exactly once, in order,
        # and each delivered token matches the authoritative buffers.
        steps = [s for s, _ in sunk]
        assert steps == sorted(set(steps))
        for step, row in sunk:
            for j in range(2):
                if step < got.lengths[j]:
                    assert row[j] == got.tokens[j, step]
    finally:
        loop.stop()


@pytest.mark.slow
@pytest.mark.duration_budget(90)
def test_hung_step_grammar_row_resumes(eng):
    """A grammar-constrained row survives the rebuild too: automaton state is
    journaled as data (prompt + grammar handle), re-admission re-derives it,
    and the replayed output still validates under the schema byte-for-byte."""
    from pydantic import BaseModel

    from k_llms_tpu.engine.grammar import (
        grammar_for_schema,
        grammar_vocab,
        validate_grammar_tokens,
    )
    from k_llms_tpu.engine.tokenizer import ByteTokenizer

    class Rec(BaseModel):
        name: str
        count: int

    tok = ByteTokenizer()
    g = grammar_for_schema(
        Rec.model_json_schema(), grammar_vocab(tok), vocab_digest="bytetok-rec"
    )
    prompt = tok.apply_chat_template([{"role": "user", "content": "extract"}])

    baseline = ContinuousDecodeLoop(eng, width=2, max_prompt=64, max_new=96)
    try:
        base = baseline.submit(
            prompt, n=1, max_new=96, temperature=1.0, top_p=None, seed=23,
            grammar=g,
        ).result(timeout=120)
    finally:
        baseline.stop()

    loop = ContinuousDecodeLoop(
        eng, width=2, max_prompt=64, max_new=96,
        budget_model=_step_budget(8.0), rebuild_fn=lambda: eng, max_rebuilds=3,
    )
    try:
        with fp.failpoints(
            {"continuous.step": FailSpec(action="hang", times=1, delay=20.0)}
        ):
            got = loop.submit(
                prompt, n=1, max_new=96, temperature=1.0, top_p=None, seed=23,
                grammar=g,
            ).result(timeout=240)
        assert loop.stats["last_recovery_reason"] == "hung_step"
        assert np.array_equal(got.tokens, base.tokens)
        body = [int(t) for t in got.tokens[0][: int(got.lengths[0])] if t < 256]
        ok, _ = validate_grammar_tokens(g, body)
        assert ok, bytes(body)
        if got.finish_reasons[0] == "stop":
            Rec.model_validate(json.loads(bytes(body)))
    finally:
        loop.stop()


# -- bounded recovery / terminal states ------------------------------------


def test_fault_without_rebuild_path_goes_terminal(eng):
    """A bare loop (no rebuild_fn) cannot heal a wedged device: the hung step
    drives a typed terminal state instead of an unbounded restart spin, and
    submit() re-raises it."""
    loop = ContinuousDecodeLoop(
        eng, width=2, max_prompt=64, max_new=32,
        budget_model=_step_budget(1.0),
    )
    try:
        with fp.failpoints(
            {"continuous.step": FailSpec(action="hang", times=1, delay=15.0)}
        ):
            fut = loop.submit(
                [1, 2, 3], n=1, max_new=8, temperature=0.0, top_p=None, seed=2
            )
            with pytest.raises(EngineHungError, match="without an engine rebuild"):
                fut.result(timeout=60)
        assert isinstance(loop._terminal_error, EngineHungError)
        with pytest.raises(EngineHungError):
            loop.submit(
                [1, 2], n=1, max_new=2, temperature=0.0, top_p=None, seed=2
            )
    finally:
        loop.stop()


def test_repeated_hangs_exhaust_rebuilds_then_terminal(eng):
    """Every replay's first step hangs again: fault credits never refill
    (no step completes), so after max_rebuilds attempts the loop goes
    terminal with the bounded-recovery error instead of rebuilding forever."""
    rebuilds = {"n": 0}

    def rebuild():
        rebuilds["n"] += 1
        return eng

    loop = ContinuousDecodeLoop(
        eng, width=2, max_prompt=64, max_new=32,
        budget_model=_step_budget(1.0), rebuild_fn=rebuild, max_rebuilds=1,
    )
    try:
        with fp.failpoints(
            {"continuous.step": FailSpec(action="hang", times=10, delay=15.0)}
        ):
            fut = loop.submit(
                [1, 2, 3], n=1, max_new=8, temperature=0.0, top_p=None, seed=3
            )
            with pytest.raises(EngineHungError, match="did not recover"):
                fut.result(timeout=60)
        assert rebuilds["n"] <= loop.max_rebuilds
        assert isinstance(loop._terminal_error, EngineHungError)
    finally:
        loop.stop()


# -- per-row numeric quarantine --------------------------------------------


def test_numeric_poison_quarantines_only_the_poisoned_row(eng):
    """Loop-scoped engine.logits=nan: the poisoned row freezes with a typed
    ``numeric_poison`` sample_error (its garbage token never reaches the
    accumulators) while the healthy neighbor decodes to completion."""
    loop = ContinuousDecodeLoop(eng, width=4, max_prompt=64, max_new=32)
    try:
        with fp.failpoints(
            {"engine.logits": FailSpec(action="nan", kill=1, seed=5, times=1)}
        ):
            res = loop.submit(
                [2, 3, 4], n=2, max_new=6, temperature=0.7, top_p=0.9, seed=9
            ).result(timeout=120)
        errs = res.sample_errors
        assert errs is not None
        assert sum(e is not None for e in errs) == 1
        j = next(i for i, e in enumerate(errs) if e is not None)
        assert errs[j]["code"] == "numeric_poison"
        assert int(res.lengths[j]) == 0
        k = 1 - j
        assert int(res.lengths[k]) > 0 and errs[k] is None
        assert loop.stats["quarantined_rows"] == 1
        # Quarantine is not a fault: no restart, no terminal, loop healthy.
        assert loop.stats["restarts"] == 0
        ok = loop.submit(
            [2, 3], n=1, max_new=4, temperature=0.0, top_p=None, seed=9
        ).result(timeout=120)
        assert int(ok.lengths[0]) > 0
    finally:
        loop.stop()


def test_numeric_poison_quarantine_paged_returns_pages():
    """Same contract through the PAGED step program, plus the pool side: the
    quarantined row's pages are decref'd on retirement, so the allocator
    stays conserved (loop_refs drains to 0, no pool quarantine)."""
    from conftest import shared_params

    from k_llms_tpu.engine.engine import LocalEngine
    from k_llms_tpu.models import get_config

    cfg = get_config("tiny")
    eng = LocalEngine(
        cfg, params=shared_params(cfg, 0), use_mesh=False,
        kv_layout="paged", kv_page_size=8,
    )
    loop = ContinuousDecodeLoop(eng, width=2, max_prompt=32, max_new=8)
    try:
        with fp.failpoints(
            {"engine.logits": FailSpec(action="nan", kill=1, seed=3, times=1)}
        ):
            res = loop.submit(
                [3, 1, 4, 1, 5], n=2, max_new=4, temperature=0.6, top_p=0.9,
                seed=4,
            ).result(timeout=120)
        errs = res.sample_errors
        assert errs is not None and sum(e is not None for e in errs) == 1
        assert loop.stats["quarantined_rows"] == 1
        pages = loop.stats["pages"]
        assert "quarantined" not in pages  # conservation held: full snapshot
        assert pages["loop_refs"] == 0
    finally:
        loop.stop()


# -- backend integration: adopt_engine + health + /metrics -----------------


def _cont_backend(**cfg):
    import jax
    from conftest import shared_engine

    from k_llms_tpu.backends.tpu import TpuBackend

    engine = (
        shared_engine("tiny", mesh_shape=(8, 1)) if len(jax.devices()) == 8 else None
    )
    return TpuBackend(
        model="tiny", max_new_tokens=8, engine=engine,
        continuous_batching=True, continuous_width=4,
        continuous_max_prompt=128, continuous_max_new=64, **cfg,
    )


@pytest.mark.slow
@pytest.mark.duration_budget(60)
def test_supervisor_rebuild_adopts_engine_into_loop():
    """The coalesced path's rebuild no longer kills the loop: the rebuilt
    engine is ADOPTED (same loop object, fresh device state) and an identical
    follow-up request reproduces the pre-rebuild bytes — _build_engine lands
    on exactly the weights a cold start would."""
    from k_llms_tpu import KLLMs

    backend = _cont_backend()
    client = KLLMs(backend=backend, model="tiny")
    try:
        msgs = [{"role": "user", "content": "adopt"}]
        before = client.chat.completions.create(
            messages=msgs, model="tiny", n=2, seed=41, temperature=0.8
        )
        loop = backend._continuous
        backend._rebuild_engine()
        assert backend._continuous is loop  # same loop, not a replacement
        assert loop.engine is backend.engine
        after = client.chat.completions.create(
            messages=msgs, model="tiny", n=2, seed=41, temperature=0.8
        )
        assert [c.message.content for c in before.choices] == [
            c.message.content for c in after.choices
        ]
    finally:
        client.close()


def test_health_and_metrics_surface_continuous_recovery_state():
    """health()['continuous'] carries the self-healing gauges and /metrics
    exports only the NUMERIC ones (strings/None/dicts in the stats snapshot
    must not become malformed Prometheus lines)."""
    import asyncio

    import httpx

    from k_llms_tpu import KLLMs
    from k_llms_tpu.serving import ServingApp

    backend = _cont_backend()
    client = KLLMs(backend=backend, model="tiny")
    try:
        client.chat.completions.create(
            messages=[{"role": "user", "content": "gauge"}], model="tiny",
            n=2, seed=7,
        )
        cont = backend.health()["continuous"]
        for key in (
            "width", "free_slots", "active_rows", "occupancy", "queue_depth",
            "restarts", "replayed_rows", "quarantined_rows",
            "last_recovery_reason",
        ):
            assert key in cont, key
        assert cont["last_recovery_reason"] is None  # healthy so far

        app = ServingApp(client)

        async def go():
            transport = httpx.ASGITransport(app=app)
            async with httpx.AsyncClient(
                transport=transport, base_url="http://testserver"
            ) as c:
                return await c.get("/metrics")

        body = asyncio.run(go()).text
        assert "kllms_continuous_restarts 0" in body
        assert "kllms_continuous_quarantined_rows 0" in body
        assert "kllms_continuous_width" in body
        assert "kllms_continuous_last_recovery_reason" not in body
        # The nested dict is skipped: no gauge of that name (the histogram
        # ``kllms_continuous_pages_seconds`` is the page books' span, PR 36).
        assert not re.search(r"^kllms_continuous_pages[ {]", body, re.M)
        for line in body.splitlines():
            if line.startswith("kllms_continuous_"):
                float(line.split()[-1])  # every exported sample is numeric
    finally:
        client.close()


@pytest.mark.slow
@pytest.mark.duration_budget(90)
def test_streamed_request_rebuild_replay_differential():
    """The streaming half of the acceptance differential: a create(stream=True)
    interrupted by a hung step mid-decode delivers the SAME deltas and final
    response as an uninterrupted stream — the watermark suppresses replayed
    steps, so the client never sees a duplicate or a gap."""
    from k_llms_tpu import KLLMs

    backend = _cont_backend(
        watchdog_base_s=0.5, watchdog_per_token_s=0.01,
        watchdog_multiplier=1.0, watchdog_min_budget_s=8.0,
        watchdog_max_budget_s=8.0, max_rebuilds=3,
    )
    client = KLLMs(backend=backend, model="tiny")
    try:
        msgs = [{"role": "user", "content": "stream heal"}]

        def run_stream():
            deltas = []
            with client.chat.completions.create(
                messages=msgs, model="tiny", n=2, seed=37, temperature=0.8,
                stream=True,
            ) as stream:
                for chunk in stream:
                    for ch in chunk.get("choices", []):
                        c = ch.get("delta", {}).get("content")
                        if c:
                            deltas.append((ch["index"], c))
                return deltas, stream.response

        base_deltas, base = run_stream()
        restarts = backend.health()["continuous"]["restarts"]
        with fp.failpoints(
            {"continuous.step": FailSpec(action="hang", times=1, delay=30.0)}
        ):
            healed_deltas, healed = run_stream()
        assert backend.health()["continuous"]["restarts"] > restarts
        assert healed_deltas == base_deltas
        assert [c.message.content for c in base.choices] == [
            c.message.content for c in healed.choices
        ]
    finally:
        client.close()


@pytest.mark.slow
@pytest.mark.duration_budget(90)
def test_backend_hung_step_recovers_through_scheduler_lifecycle():
    """End to end through the backend: a hung loop step mid-request drives
    READY -> RECOVERING -> READY via the scheduler hooks, the request still
    succeeds (replayed on the rebuilt engine), and restart gauges move."""
    from k_llms_tpu import KLLMs

    backend = _cont_backend(
        watchdog_base_s=0.5, watchdog_per_token_s=0.01,
        watchdog_multiplier=1.0, watchdog_min_budget_s=8.0,
        watchdog_max_budget_s=8.0, max_rebuilds=3,
    )
    client = KLLMs(backend=backend, model="tiny")
    try:
        msgs = [{"role": "user", "content": "hang drill"}]
        base = client.chat.completions.create(
            messages=msgs, model="tiny", n=2, seed=19, temperature=0.8
        )
        with fp.failpoints(
            {"continuous.step": FailSpec(action="hang", times=1, delay=30.0)}
        ):
            healed = client.chat.completions.create(
                messages=msgs, model="tiny", n=2, seed=19, temperature=0.8
            )
        assert [c.message.content for c in base.choices] == [
            c.message.content for c in healed.choices
        ]
        h = backend.health()
        assert h["continuous"]["restarts"] >= 1
        assert h["continuous"]["last_recovery_reason"] == "hung_step"
        assert h["state"] in ("ready", "degraded")
    finally:
        client.close()
