#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still starts on the chip.

Starts the server the way its docstring says (``python -m k_llms_tpu.serving
--backend tpu --model qwen2-7b --quantization int8 --continuous-batching``:
published width, all 28 layers, int8 weights made from a seed — no checkpoint
ships), sends the requests a user would send through the HTTP front door,
checks what comes back and which path each request took, then SIGTERMs the
server and checks a clean drain.

One process holds the chip: this parent imports nothing but the standard
library — not ``jax``, not ``k_llms_tpu`` — and the server is its only child.
Everything it knows about the device it reads from ``/healthz`` and
``/metrics``.

Requests (each sent twice with the same seed: a cold pass that compiles, a
warm pass that must return the same text — wall times are labelled as such
and are not performance records):

  short        n=1, unconstrained            continuous loop, paged step
  short-sse    n=8, streamed                 same loop, SSE deltas + consensus
  prompt-400   ~400-token prompt, n=8        chunked prefill inside the loop
  long-solo    ~1.4k-token prompt, n=32      over continuous_max_prompt: the
               temperature 0.8, top-p 0.95   scheduler's solo generate (flash
                                             prefill, dense decode)
  long-pair    two such, sent together       coalesced generate_many over the
                                             page pool; embeddings + consensus
  schema       response_format JSON schema   grammar mask in the loop
  greedy       temperature 0 with logprobs,  loop (paged kernel) against the
               via both schedulers           scheduler (XLA attention)

It exits non-zero and prints no result line when the serving process is not on
the expected platform, when any request fails, when any fallback, OOM, rebuild
or quarantine counter is non-zero at the end, when the native library did not
build and load, or when the path counters say a request went somewhere else.
The last line of stdout is then the only JSON object it prints:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--platform cpu --model tiny`` runs the same script end to end on the CPU for
debugging; without it a CPU is refused before a model is built.
"""

import argparse
import http.client
import json
import math
import os
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

SCHEMA = {
    "type": "object",
    "properties": {
        "kind": {"type": "string", "enum": ["invoice", "receipt", "quote"]},
        "paid": {"type": "boolean"},
        "currency": {"type": "string", "enum": ["USD", "EUR", "GBP"]},
    },
    "required": ["kind", "paid", "currency"],
    "additionalProperties": False,
}
INSTRUCTION = (
    "You are an extraction engine. Read the document and return the vendor, "
    "the invoice number, the issue date, the total due, the currency and the "
    "payment terms. Answer with the fields only. "
)
# Random weights over a 152k vocabulary almost never emit a byte token, so the
# long requests bias the printable ASCII ids up: the samples become 64-char
# strings, long enough for consolidation's embeddings route (> 50 chars).
PRINTABLE_BIAS = {str(i): 100 for i in range(32, 127)}

# Counters that must read zero when the run ends (Prometheus event names).
ZERO_EVENTS = (
    "consensus.fallback_error", "consensus.fallback_unavailable",
    "grammar.fallback_error", "engine.oom", "engine.oom_split",
    "engine.oom_unrecovered", "supervisor.hung_launches", "supervisor.rebuilds",
    "supervisor.rebuild_failures", "continuous.step_hangs",
    "continuous.worker_crashes", "continuous.restarts",
    "continuous.pool_quarantined", "quarantine.samples",
)


class SmokeFailure(Exception):
    pass


def log(msg):
    print(msg, flush=True)


def prompt_of(tokens, tag):
    """A user message of about ``tokens`` byte-tokenizer tokens."""
    body = f"[{tag}] " + INSTRUCTION
    doc = "ACME Corp invoice INV-2024-00417 issued March 3rd total $4,310.55 net 30. "
    while len(body) < tokens:
        body += doc
    return [{"role": "user", "content": body[:tokens]}]


# -- HTTP --------------------------------------------------------------------

class Client:
    def __init__(self, port, timeout):
        self.port, self.timeout = port, timeout

    def _conn(self, timeout=None):
        return http.client.HTTPConnection(
            "127.0.0.1", self.port, timeout=timeout or self.timeout
        )

    def get(self, path, timeout=None):
        conn = self._conn(timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def health(self):
        status, body = self.get("/healthz")
        return status, json.loads(body)

    def embedding_lookups(self):
        """Lookups (hits + misses) of the scorers' embedding caches: each one
        is a string consolidation embedded through the engine."""
        caches = self.health()[1]["consensus"]["caches"]
        return sum(
            c["embeddings"]["hits"] + c["embeddings"]["misses"]
            for c in caches.values()
        )

    def counters(self):
        """/metrics as {event-or-gauge name: value}: event counters by their
        ``event`` label, unlabeled gauges by metric name."""
        status, body = self.get("/metrics")
        if status != 200:
            raise SmokeFailure(f"/metrics answered {status}")
        out = {}
        for line in body.decode().splitlines():
            if not line or line.startswith("#"):
                continue
            name, _, value = line.rpartition(" ")
            if '{event="' in name:
                name = name.split('{event="', 1)[1].split('"', 1)[0]
            elif "{" in name:
                continue
            out[name] = float(value)
        return out

    def chat(self, body):
        """POST /v1/chat/completions -> (completion dict, seconds)."""
        conn = self._conn()
        t0 = time.monotonic()
        try:
            conn.request(
                "POST", "/v1/chat/completions", json.dumps(body),
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            raw = resp.read()
        finally:
            conn.close()
        if resp.status != 200:
            raise SmokeFailure(f"HTTP {resp.status}: {raw[:400]!r}")
        return json.loads(raw), time.monotonic() - t0

    def chat_stream(self, body):
        """Streamed POST -> (delta events, final completion dict, seconds)."""
        conn = self._conn()
        t0 = time.monotonic()
        deltas, final, done = [], None, False
        try:
            conn.request(
                "POST", "/v1/chat/completions", json.dumps(dict(body, stream=True)),
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            if resp.status != 200:
                raise SmokeFailure(f"HTTP {resp.status}: {resp.read()[:400]!r}")
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data:"):
                    continue
                payload = line[5:].strip()
                if payload == "[DONE]":
                    done = True
                    break
                event = json.loads(payload)
                if event.get("object") == "chat.completion.chunk":
                    deltas.append(event)
                else:
                    final = event
        finally:
            conn.close()
        if final is None or not done:
            raise SmokeFailure("stream ended without the consensus event and [DONE]")
        return deltas, final, time.monotonic() - t0


# -- checks on one completion ---------------------------------------------------

def texts(completion):
    return [c["message"]["content"] for c in completion["choices"]]


def check_completion(name, completion, n, likelihoods=False):
    want = 1 if n == 1 else n + 1  # consolidated choice + the n samples
    choices = completion.get("choices") or []
    if len(choices) != want:
        raise SmokeFailure(f"{name}: {len(choices)} choices, expected {want}")
    if completion.get("degraded"):
        raise SmokeFailure(f"{name}: degraded {completion['degraded']}")
    if completion["usage"]["completion_tokens"] <= 0:
        raise SmokeFailure(f"{name}: no completion tokens")
    for c in choices[1:] if n > 1 else choices:
        if c.get("sample_error"):
            raise SmokeFailure(f"{name}: sample error {c['sample_error']}")
        lp = c.get("sample_logprob")  # the n samples carry it, a lone choice does not
        if n > 1 and not (isinstance(lp, float) and math.isfinite(lp) and lp <= 0):
            raise SmokeFailure(f"{name}: sample_logprob {lp!r} is not a finite log-probability")
    # Random weights without the printable bias decode to (nearly) empty
    # strings, which carry no likelihoods; the biased long requests must.
    if likelihoods and completion.get("likelihoods") is None:
        raise SmokeFailure(f"{name}: no likelihoods")


# -- the run ---------------------------------------------------------------------

def run_requests(client, model, paged_impl, chunk_tokens):
    """Every request of the list, once. Returns {name: (seconds, texts)}."""
    results = {}
    dispatch = f"kernel.paged_attn_{paged_impl}_dispatch"

    def delta(before, after, key):
        return after.get(key, 0) - before.get(key, 0)

    def base(tag, tokens, n, seed, **extra):
        body = {
            "model": model, "messages": prompt_of(tokens, tag), "n": n,
            "seed": seed, "temperature": 0.8, "top_p": 0.95, "max_tokens": 32,
        }
        body.update(extra)
        return body

    def in_loop(name, before, after, chunks):
        if delta(before, after, "kllms_continuous_admitted") != 1:
            raise SmokeFailure(f"{name}: did not go through the continuous loop")
        if delta(before, after, dispatch) <= 0:
            raise SmokeFailure(f"{name}: no {dispatch} recorded")
        got = delta(before, after, "kllms_continuous_prefill_chunks")
        if got < chunks or (chunks == 0 and got):
            raise SmokeFailure(f"{name}: {got:.0f} prefill chunks, expected {chunks or 'none'}")

    # short, n=1
    before = client.counters()
    out, secs = client.chat(base("short", 48, 1, 11))
    check_completion("short", out, 1)
    in_loop("short", before, client.counters(), 0)
    results["short"] = (secs, texts(out))

    # the same streamed at n=8
    before = client.counters()
    deltas, out, secs = client.chat_stream(base("short", 48, 8, 11))
    check_completion("short-sse", out, 8)
    seen = {c["index"] for e in deltas for c in e["choices"]}
    if not seen or not seen <= set(range(1, 9)):
        raise SmokeFailure(f"short-sse: delta indices {sorted(seen)} not within 1..8")
    after = client.counters()
    in_loop("short-sse", before, after, 0)
    if delta(before, after, "streams.completed") != 1:
        raise SmokeFailure("short-sse: stream not counted as completed")
    results["short-sse"] = (secs, texts(out))

    # ~400-token prompt, n=8: chunked prefill in the loop
    before = client.counters()
    out, secs = client.chat(base("p400", 400, 8, 12))
    check_completion("prompt-400", out, 8)
    in_loop("prompt-400", before, client.counters(), -(-400 // chunk_tokens))
    results["prompt-400"] = (secs, texts(out))

    # ~1.4k-token prompt, n=32: first alone (solo generate), then two at once.
    # The pair is sent once the scheduler reports the solo launch in flight,
    # so both are queued when it finishes and coalesce into one paged launch.
    long_kw = dict(max_tokens=64, logit_bias=PRINTABLE_BIAS)
    before = client.counters()
    embedded_before = client.embedding_lookups()
    box = {}

    def send(key, body):
        try:
            box[key] = client.chat(body)
        except Exception as e:  # surfaced below, on the main thread
            box[key] = e

    threads = [
        threading.Thread(target=send, args=(k, base(k, 1400, 32, s, **long_kw)), daemon=True)
        for k, s in (("long-solo", 21), ("long-pair-a", 22), ("long-pair-b", 23))
    ]
    threads[0].start()
    deadline = time.monotonic() + 60
    while client.health()[1]["in_flight"] < 1:
        if "long-solo" in box or time.monotonic() > deadline:
            raise SmokeFailure("long-solo: never observed in flight in the scheduler")
        time.sleep(0.02)
    threads[1].start()
    threads[2].start()
    for t in threads:
        t.join()
    after = client.counters()
    for key in ("long-solo", "long-pair-a", "long-pair-b"):
        if isinstance(box[key], Exception):
            raise SmokeFailure(f"{key}: {box[key]}")
        out, secs = box[key]
        check_completion(key, out, 32, likelihoods=True)
        if min(len(t) for t in texts(out)[1:]) <= 50:
            raise SmokeFailure(f"{key}: samples too short for the embeddings route")
        results[key] = (secs, texts(out))
    if delta(before, after, "kllms_continuous_admitted") != 0:
        raise SmokeFailure("long: a 1.4k-token prompt entered the continuous loop")
    if delta(before, after, dispatch) != 1:
        raise SmokeFailure(
            f"long-pair: {delta(before, after, dispatch):.0f} paged launches, "
            "expected exactly 1 (the pair coalesced over the page pool)"
        )
    if delta(before, after, "consensus.device_dispatch") < 3:
        raise SmokeFailure("long: consolidation did not take the device path")
    if client.embedding_lookups() <= embedded_before:
        raise SmokeFailure("long: consolidation never asked for an embedding")

    # JSON schema, n=8: grammar mask in the loop
    before = client.counters()
    out, secs = client.chat(base(
        "schema", 64, 8, 13, max_tokens=64,
        response_format={
            "type": "json_schema", "json_schema": {"name": "doc", "schema": SCHEMA},
        },
    ))
    check_completion("schema", out, 8)
    after = client.counters()
    in_loop("schema", before, after, 0)
    if delta(before, after, "grammar.masked_steps") <= 0:
        raise SmokeFailure("schema: no grammar-masked steps")
    for text in texts(out):
        doc = json.loads(text)
        if set(doc) != set(SCHEMA["required"]) or doc["kind"] not in SCHEMA[
            "properties"]["kind"]["enum"] or not isinstance(doc["paid"], bool):
            raise SmokeFailure(f"schema: {text!r} does not satisfy the schema")
    results["schema"] = (secs, texts(out))

    # greedy with logprobs through both schedulers: the loop's paged kernel
    # against the scheduler's XLA attention. Position 0 is the same prefill;
    # position 1 is the first decode step over the prompt's pages. Later
    # positions only compare while both paths chose the same token (random
    # weights make near-ties, so a later divergence is not an error).
    body = base("greedy", 48, 1, 14, temperature=0, max_tokens=8, logprobs=True)
    before = client.counters()
    loop_out, secs = client.chat(body)
    in_loop("greedy", before, client.counters(), 0)
    sched_out, _ = client.chat(dict(body, top_logprobs=1))
    lps = [
        [e["logprob"] for e in o["choices"][0]["logprobs"]["content"]]
        for o in (loop_out, sched_out)
    ]
    agree = 0
    for a, b in zip(*lps):
        if abs(a - b) > 0.05:
            break
        agree += 1
    if agree < 2:
        raise SmokeFailure(f"greedy: loop {lps[0]} and scheduler {lps[1]} logprobs disagree")
    log(f"  greedy: loop and scheduler logprobs agree on {agree}/{len(lps[0])} positions")
    results["greedy"] = (secs, texts(loop_out))
    return results


def final_checks(client, expect_platform):
    status, health = client.health()
    if status != 200:
        raise SmokeFailure(f"/healthz answered {status} ({health.get('state')})")
    device = health["device"]
    counters = client.counters()
    bad = {k: v for k, v in counters.items() if v and (
        k in ZERO_EVENTS or k.startswith("kernel.paged_attn_fallback."))}
    sup = health["supervisor"]
    cont = health.get("continuous") or {}
    facts = {
        "scheduler errors": health["errors"],
        "engine_oom": sum(health["engine_oom"].values()),
        "supervisor rebuilds": sup["rebuilds"] + sup["hung_launches"],
        "loop restarts": cont.get("restarts", 0),
        "quarantined rows": cont.get("quarantined_rows", 0)
        + sum((health.get("quarantine") or {}).values()),
        "pool quarantined": int(bool((cont.get("pages") or {}).get("quarantined"))),
    }
    bad.update({k: v for k, v in facts.items() if v})
    if bad:
        raise SmokeFailure(f"counters that must be zero are not: {bad}")
    if not device["native"]["loaded"]:
        raise SmokeFailure(f"native library not loaded: {device['native']['error']}")
    if device["platform"] != expect_platform:
        raise SmokeFailure(f"serving process is on {device['platform']!r}")
    return health, counters


def wait_ready(client, proc, timeout):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SmokeFailure(f"server exited with code {proc.returncode} before it was ready")
        try:
            status, health = client.health()
            if status == 200:
                return health
        except (OSError, http.client.HTTPException, ValueError):
            pass
        time.sleep(1.0)
    raise SmokeFailure(f"server not ready after {timeout:.0f}s")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="qwen2-7b")
    ap.add_argument("--quantization", default="int8")
    ap.add_argument("--model-parallel", type=int, default=None)
    ap.add_argument("--platform", default="tpu",
                    help="platform the serving process must be on (cpu: debugging only)")
    ap.add_argument("--startup-timeout", type=float, default=600.0)
    ap.add_argument("--request-timeout", type=float, default=600.0)
    ap.add_argument("--log", default=os.path.join(HERE, "chiprun_out", "chip_smoke_server.log"),
                    help="where the server's output goes")
    args = ap.parse_args()

    if not os.path.isfile(os.path.join(HERE, "k_llms_tpu", "serving", "__main__.py")):
        sys.exit("chip_smoke.py: no k_llms_tpu package beside this script; run it from a checkout")
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu" and args.platform != "cpu":
        sys.exit("chip_smoke.py: JAX_PLATFORMS=cpu — this smoke needs the accelerator")

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cmd = [sys.executable, "-m", "k_llms_tpu.serving", "--backend", "tpu",
           "--model", args.model, "--continuous-batching", "--port", str(port)]
    if args.quantization and args.quantization != "none":
        cmd += ["--quantization", args.quantization]
    if args.model_parallel:
        cmd += ["--model-parallel", str(args.model_parallel)]
    # The child must find the expected platform or fail while JAX starts up,
    # before it builds a model; it never falls back to the CPU silently.
    env = dict(os.environ, JAX_PLATFORMS=args.platform, PYTHONUNBUFFERED="1")
    os.makedirs(os.path.dirname(args.log), exist_ok=True)
    log(f"chip_smoke: {' '.join(cmd[1:])}")
    # A terminated parent must still stop its child: turn SIGTERM into an
    # exit that unwinds through the finally below.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    t_start = time.monotonic()
    failure = None
    with open(args.log, "wb") as server_log:
        proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=server_log,
                                stderr=subprocess.STDOUT)
        try:
            client = Client(port, args.request_timeout)
            health = wait_ready(client, proc, args.startup_timeout)
            device = health["device"]
            log(f"  ready in {time.monotonic() - t_start:.1f}s: "
                f"{device['device_count']} x {device['device_kind']} "
                f"({device['platform']}), mesh={device['mesh']}, "
                f"model={device['model']} x{device['num_layers']} layers "
                f"{device['quantization']}, attention={device['attention']}, "
                f"compile cache={device['compile']['cache_dir']}")
            if device["platform"] != args.platform:
                raise SmokeFailure(f"serving process is on {device['platform']!r}")
            cont = health.get("continuous")
            if not cont:
                raise SmokeFailure("the continuous loop is not running")
            log(f"  loop width={cont['width']}, hbm={health['hbm']['param_bytes'] / 1e9:.2f} GB "
                f"params, bytes_in_use={device['bytes_in_use']}")
            paged_impl = device["attention"]["paged"]
            chunk = cont["prefill_chunk_tokens"]
            if not 0 < chunk < 400:
                raise SmokeFailure(f"chunked prefill would not split 400 tokens (chunk={chunk})")

            passes = {}
            for label in ("cold", "warm"):
                t0 = time.monotonic()
                passes[label] = run_requests(client, args.model, paged_impl, chunk)
                log(f"  {label} pass: {time.monotonic() - t0:.1f}s")
            for name, (cold_s, cold_text) in passes["cold"].items():
                warm_s, warm_text = passes["warm"][name]
                log(f"  {name:<12} cold {cold_s:7.2f}s  warm {warm_s:7.2f}s")
                if cold_text != warm_text:
                    raise SmokeFailure(f"{name}: same seed, different text on the second pass")

            health, counters = final_checks(client, args.platform)
            device = health["device"]
            shown = sorted(
                k for k in counters if k.startswith(("kernel.", "grammar.", "consensus."))
                or k in ("kllms_continuous_prefill_chunks", "kllms_continuous_steps",
                         "kllms_continuous_admitted")
            )
            log("  counters: " + ", ".join(f"{k}={counters[k]:.0f}" for k in shown))
            log(f"  compile: {device['compile']}")
            log(f"  bytes_in_use per device: {device['bytes_in_use']}")
        except (SmokeFailure, OSError, http.client.HTTPException, KeyError, ValueError) as e:
            failure = f"{type(e).__name__}: {e}"
        finally:
            # SIGTERM is the server's graceful shutdown: socket closes, the
            # backend drains, exit code 0.
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
                    failure = failure or "server did not drain within 60s of SIGTERM"
            if failure is None and proc.returncode != 0:
                failure = f"server exited with code {proc.returncode} after SIGTERM"
    if failure is not None:
        with open(args.log, "rb") as f:
            tail = f.read()[-3000:].decode(errors="replace")
        print(f"chip_smoke FAILED: {failure}\n--- server log tail ---\n{tail}", file=sys.stderr)
        sys.exit(1)
    log(f"  drained cleanly; total {time.monotonic() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"],
    }}), flush=True)


if __name__ == "__main__":
    main()
