#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json against the served path.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the server child (``serve.py <config>``: the HTTP front door over
``create_app(**config["serve"])``), warms up with the cell's own traffic,
offers the cell's closed loop for ``--seconds``, checks every answer, SIGTERMs
the child and prints one JSON object as the last line of stdout: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.
``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` captures a
profiler trace inside the window and prints the cell's per-layer metrics.

One process holds the chip: this parent imports only the standard library —
never ``jax``, never ``k_llms_tpu`` — and all it knows about the device it
reads from ``/healthz``, ``/metrics`` and ``/debug/requests``. Everything that
belongs to one cell, one configuration or one per-layer metric is a data file
found by its name in BENCHMARK.json (see README.md); this file holds the one
traffic generator, the one metric evaluator and the checks.

**The window rule.** Every client sends its next request when its last one
returned, until the window closes. Latencies, first-token times and tokens are
taken over the answers that *returned inside* the window, and the rate divides
by the whole window. Requests still in flight at the close are waited for,
checked like any other and counted in ``attempted`` and ``failed`` — and in
nothing else.

``--platform cpu`` is the rehearsal switch (the smoke's own): the child serves
``tiny`` without quantization on the CPU, and the line says ``"platform":
"cpu"``. It is never a record. Without it, a child that is not on a TPU, or
that finds fewer chips than the cell asks for, ends the run non-zero with no
result line.
"""

import argparse
import http.client
import itertools
import json
import math
import os
import random
import re
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import threading
import time

T_PROCESS_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# Counters that must read zero when the run ends (copied from chip_smoke.py).
ZERO_EVENTS = (
    "consensus.fallback_error", "consensus.fallback_unavailable",
    "grammar.fallback_error", "engine.oom", "engine.oom_split",
    "engine.oom_unrecovered", "supervisor.hung_launches", "supervisor.rebuilds",
    "supervisor.rebuild_failures", "continuous.step_hangs",
    "continuous.worker_crashes", "continuous.restarts",
    "continuous.pool_quarantined", "quarantine.samples",
)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PERCENTILE_RE = re.compile(r"^(latency|ttft)_p(\d+)_ms$")
# The traced part of a --trace 1 window: some 60 decode steps. It is the window's
# last seconds but one, so that what the profiler does once it stops (it
# serializes for 20-90 s while the server goes on serving) falls after the close.
CAPTURE_S = 3.0
WORDS = ("invoice", "total", "due", "net", "thirty", "vendor", "ACME", "Corp",
         "issued", "March", "payment", "terms", "EUR", "USD", "number", "date",
         "amount", "tax", "line", "item", "quantity", "unit", "price", "paid")
INSTRUCTION = (
    "You are an extraction engine. Read the document and return the vendor, "
    "the invoice number, the issue date, the total due, the currency and the "
    "payment terms. Answer with the fields only. "
)


class BenchFailure(Exception):
    pass


def log(msg):
    print(msg, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# -- the manifest ---------------------------------------------------------------

def applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(cell):
    """Everything one cell needs, found by name from BENCHMARK.json: (workload
    entry, configuration file's path, configuration, traffic, {end-to-end
    metric: unit}, per-layer entries each joined with its file's ``read``)."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise BenchFailure(f"no workload {cell!r} in BENCHMARK.json")
    config_file = os.path.join(
        ROOT, next(c["file"] for c in manifest["configs"] if c["name"] == entry["config"]))
    return (
        entry, config_file, load_json(config_file),
        load_json(HERE, "workloads", entry["traffic"] + ".json"),
        {m["name"]: m["unit"] for m in manifest["end_to_end"] if applies(m, cell)},
        [dict(m, **load_json(HERE, "layer_metrics", m["name"] + ".json"))
         for m in manifest["per_layer"] if applies(m, cell)],
    )


def check_manifest():
    """The manifest's own consistency: names, units, and that every entry
    resolves to the files the harness will open. Returns a list of faults."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    faults = []
    e2e = {m["name"] for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not NAME_RE.match(m["name"]) or not UNIT_RE.match(m["unit"]):
            faults.append(f"metric {m['name']!r}: bad name or unit {m['unit']!r}")
        if not set(m.get("workloads", [])) <= cells:
            faults.append(f"metric {m['name']!r}: lists a cell that does not exist")
    for m in manifest["per_layer"]:
        path = os.path.join(HERE, "layer_metrics", m["name"] + ".json")
        if not os.path.isfile(path):
            faults.append(f"per-layer metric {m['name']!r}: no {path}")
            continue
        if load_json(path).get("name") != m["name"] or "read" not in load_json(path):
            faults.append(f"per-layer metric {m['name']!r}: its file lacks the name or a read")
        if m["moves"] not in e2e:
            faults.append(f"per-layer metric {m['name']!r}: moves unknown {m['moves']!r}")
    configs = {c["name"]: c for c in manifest["configs"]}
    for c in manifest["configs"]:
        if not NAME_RE.match(c["name"]) or not os.path.isfile(os.path.join(ROOT, c["file"])):
            faults.append(f"config {c['name']!r}: bad name or missing file")
    for w in manifest["workloads"]:
        if not NAME_RE.match(w["name"]) or not NAME_RE.match(w["traffic"]):
            faults.append(f"cell {w['name']!r}: bad name or traffic")
        if w["config"] not in configs:
            faults.append(f"cell {w['name']!r}: unknown config")
        if not os.path.isfile(os.path.join(HERE, "workloads", w["traffic"] + ".json")):
            faults.append(f"cell {w['name']!r}: no traffic file")
        if len(w["why"]) > 200:
            faults.append(f"cell {w['name']!r}: why is over 200 characters")
    return faults


# -- the traffic generator: a pure function of (traffic file, seed, stream, k) -----

def length_pool(dist):
    """``pool`` (64 unless the cell says) evenly spaced quantiles of the cell's
    length distribution, clipped. A cell whose window holds only tens of
    requests takes a smaller pool, so that a window goes round it."""
    normal, size = statistics.NormalDist(), dist.get("pool", 64)
    if dist["dist"] != "lognormal":
        raise BenchFailure(f"unknown length distribution {dist['dist']!r}")
    return [
        int(min(max(dist["median"] * math.exp(dist["sigma"] * normal.inv_cdf((i + 0.5) / size)),
                    dist["min"]), dist["max"]))
        for i in range(size)
    ]


def shuffled_lengths(traffic, seed, stream):
    """The pool in this (seed, stream)'s order: every seed offers the same
    work, in another order."""
    pool = length_pool(traffic["doc_tokens"])
    random.Random(f"{seed}/{stream}/lengths").shuffle(pool)
    return pool


def text_of(rng, tokens):
    """``tokens`` ASCII characters of seeded words (the byte tokenizer makes
    one token of each)."""
    parts, size = [], -1  # the joined length: words plus the spaces between them
    while size < tokens:
        parts.append(rng.choice(WORDS))
        size += len(parts[-1]) + 1
    return " ".join(parts)[:tokens]


def make_request(traffic, model, seed, stream, k, lengths=None):
    """Request number ``k`` of one stream of one seed."""
    lengths = lengths or shuffled_lengths(traffic, seed, stream)
    rng = random.Random(f"{seed}/{stream}/{k}")
    messages = []
    prefix = traffic.get("shared_prefix_tokens") or 0
    if prefix:
        reps = -(-prefix // len(INSTRUCTION))
        messages.append({"role": "system", "content": (INSTRUCTION * reps)[:prefix]})
    messages.append({"role": "user", "content": text_of(rng, lengths[k % len(lengths)])})
    body = {
        "model": model, "messages": messages, "n": traffic["n"],
        "seed": rng.randrange(2 ** 31), "temperature": traffic["temperature"],
        "top_p": traffic["top_p"], "max_tokens": traffic["max_tokens"],
    }
    if traffic["stream"]:
        body["stream"] = True
    if traffic.get("response_format"):
        body["response_format"] = {
            "type": "json_schema",
            "json_schema": {"name": "doc", "schema": traffic["response_format"]},
        }
    return body


# -- arithmetic ------------------------------------------------------------------

def percentile(values, q):
    """Linear interpolation between order statistics, q in [0, 100]."""
    ordered = sorted(values)
    pos = (len(ordered) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def end_to_end(name, answered, window_s, chips, setup_s):
    """One end-to-end metric over the answers that returned inside the window."""
    if name == "setup_s":
        return setup_s
    if name == "tokens_per_s":
        return sum(r["tokens"] for r in answered) / window_s / chips
    m = PERCENTILE_RE.match(name)
    if not m:
        raise BenchFailure(f"no arithmetic for end-to-end metric {name!r}")
    values = [r[m.group(1)] for r in answered if r[m.group(1)] is not None]
    if not values:
        raise BenchFailure(f"{name}: no sample in the window")
    return 1000.0 * percentile(values, float(m.group(2)))


# -- HTTP (copied from chip_smoke.py; failures are values here) ----------------------

class Client:
    def __init__(self, port, timeout):
        self.port, self.timeout = port, timeout

    def _conn(self, timeout=None):
        return http.client.HTTPConnection("127.0.0.1", self.port, timeout=timeout or self.timeout)

    def request(self, method, path, body=None, timeout=None):
        conn = self._conn(timeout)
        try:
            conn.request(method, path, None if body is None else json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def get_json(self, path):
        status, body = self.request("GET", path)
        if status != 200:
            raise BenchFailure(f"{path} answered {status}")
        return json.loads(body)

    def counters(self):
        status, body = self.request("GET", "/metrics")
        if status != 200:
            raise BenchFailure(f"/metrics answered {status}")
        return parse_metrics(body.decode())

    def chat(self, body):
        """POST /v1/chat/completions -> (completion, seconds to first delta or None)."""
        if not body.get("stream"):
            status, raw = self.request("POST", "/v1/chat/completions", body)
            if status != 200:
                raise BenchFailure(f"HTTP {status}: {raw[:300]!r}")
            return json.loads(raw), None
        conn = self._conn()
        t0 = time.monotonic()
        first, final, done = None, None, False
        try:
            conn.request("POST", "/v1/chat/completions", json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                raise BenchFailure(f"HTTP {resp.status}: {resp.read()[:300]!r}")
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
                    if first is None:
                        first = time.monotonic() - t0
                else:
                    final = event
        finally:
            conn.close()
        if final is None or not done:
            raise BenchFailure("stream ended without the final event and [DONE]")
        return final, first


def parse_metrics(text):
    """/metrics as {name: value}: event counters by their ``event`` label,
    unlabeled samples (gauges, histogram ``_sum``/``_count``) by metric name."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        if '{event="' in name:
            name = name.split('{event="', 1)[1].split('"', 1)[0]
        elif "{" in name:
            continue
        out[name] = float(value)
    return out


# -- checks on one answer -------------------------------------------------------------

def validate(value, schema, where="$"):
    """The JSON-schema subset the cells use; raises BenchFailure."""
    if "enum" in schema and value not in schema["enum"]:
        raise BenchFailure(f"{where}: {value!r} not in {schema['enum']}")
    kind = schema.get("type")
    python = {"object": dict, "string": str, "boolean": bool, "array": list,
              "number": (int, float), "integer": int}.get(kind)
    if python and (not isinstance(value, python)
                   or (kind in ("number", "integer") and isinstance(value, bool))):
        raise BenchFailure(f"{where}: {value!r} is not a {kind}")
    if kind == "object":
        props = schema.get("properties", {})
        missing = set(schema.get("required", [])) - set(value)
        extra = set(value) - set(props)
        if missing or (extra and schema.get("additionalProperties") is False):
            raise BenchFailure(f"{where}: missing {sorted(missing)} extra {sorted(extra)}")
        for key in value:
            if key in props:
                validate(value[key], props[key], f"{where}.{key}")
    if kind == "array" and "items" in schema:
        for i, item in enumerate(value):
            validate(item, schema["items"], f"{where}[{i}]")


def check_completion(completion, traffic):
    """chip_smoke.py's check_completion plus the schema clause; raises BenchFailure."""
    n, schema = traffic["n"], traffic.get("response_format")
    want = 1 if n == 1 else n + 1  # consolidated choice + the n samples
    choices = completion.get("choices") or []
    if len(choices) != want:
        raise BenchFailure(f"{len(choices)} choices, expected {want}")
    if completion.get("degraded"):
        raise BenchFailure(f"degraded {completion['degraded']}")
    if completion["usage"]["completion_tokens"] <= 0:
        raise BenchFailure("no completion tokens")
    for c in choices[1:] if n > 1 else choices:
        if c.get("sample_error"):
            raise BenchFailure(f"sample error {c['sample_error']}")
        lp = c.get("sample_logprob")  # the n samples carry it, a lone choice does not
        if n > 1 and not (isinstance(lp, float) and math.isfinite(lp) and lp <= 0):
            raise BenchFailure(f"sample_logprob {lp!r} is not a finite log-probability")
    if schema:
        for c in choices:
            validate(json.loads(c["message"]["content"]), schema)
        if n > 1 and set(completion.get("likelihoods") or {}) != set(schema["properties"]):
            raise BenchFailure(f"likelihoods {completion.get('likelihoods')!r} lack the schema's keys")


def send_one(client, body, traffic):
    """One exchange as a record; a non-200 answer or a timeout is ``error``
    (a failed request), an answer that fails its checks is ``wrong``."""
    rec = {"sent": time.monotonic(), "ttft": None, "tokens": 0, "error": None,
           "wrong": None, "text": None}
    try:
        completion, rec["ttft"] = client.chat(body)
        rec["done"] = time.monotonic()
        try:
            check_completion(completion, traffic)
            rec["tokens"] = completion["usage"]["completion_tokens"]
            rec["text"] = [c["message"]["content"] for c in completion["choices"]]
        except (BenchFailure, KeyError, TypeError, ValueError) as e:
            rec["wrong"] = f"{type(e).__name__}: {e}"
    except (BenchFailure, OSError, http.client.HTTPException, ValueError) as e:
        rec["done"] = time.monotonic()
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["latency"] = rec["done"] - rec["sent"]
    return rec


def closed_loop(client, traffic, model, seed, stream, keep_going):
    """``clients`` threads; each sends the stream's next request when its last
    returned, while ``keep_going(requests this client has sent)``."""
    if traffic["loop"] != "closed":
        raise BenchFailure(f"loop {traffic['loop']!r}: only 'closed' is written yet")
    lengths = shuffled_lengths(traffic, seed, stream)
    counter, records = itertools.count(), []

    def worker():
        mine = 0
        while keep_going(mine):
            k = next(counter)
            body = make_request(traffic, model, seed, stream, k, lengths)
            records.append(dict(send_one(client, body, traffic), k=k))
            mine += 1

    threads = [threading.Thread(target=worker, daemon=True) for _ in range(traffic["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records


# -- the per-layer metric evaluator ------------------------------------------------------

def dotted(obj, path):
    for part in path.split("."):
        obj = obj.get(part) if isinstance(obj, dict) else None
    return obj if isinstance(obj, (int, float)) and not isinstance(obj, bool) else None


def term(spec, src):
    """One term of a ``read`` -> number, or None when its source is absent."""
    (kind, key), = ((k, v) for k, v in spec.items() if k != "of")
    if kind == "const":
        return key
    if kind == "delta":
        a, b = src["metrics_start"].get(key), src["metrics_end"].get(key)
        return None if b is None else b - (a or 0.0)
    if kind == "drained_delta":  # once the requests in flight at the close have returned
        a, b = src["metrics_start"].get(key), src["metrics_drained"].get(key)
        return None if b is None else b - (a or 0.0)
    if kind == "capture_delta":
        # The snapshots are taken as the capture is asked for and that long
        # after; the difference is scaled to what the trace really covers.
        cap, traced = src.get("capture") or {}, (src.get("trace") or {}).get("window_s")
        if "end" not in cap or traced is None:
            return None
        a, b = cap["start"].get(key), cap["end"].get(key)
        return None if b is None else (b - (a or 0.0)) * traced / cap["seconds"]
    if kind == "gauge":
        return src["metrics_end"].get(key)
    if kind == "health":
        end = dotted(src["health_end"], key)
        if spec.get("of") != "delta" or end is None:
            return end
        return end - (dotted(src["health_start"], key) or 0.0)
    if kind == "client":
        return src["client"].get(key)
    if kind == "phase":
        values = [r["phases"][key] for r in src["requests"] if key in r.get("phases", {})]
        if not values:
            return None
        return len(values) if spec.get("of") == "count" else sum(values)
    if kind == "trace":
        return (src.get("trace") or {}).get(key)
    if kind == "config":
        return dotted(src["config"], key)
    if kind == "peak":
        return dotted(src["peaks"], key)
    raise BenchFailure(f"unknown term kind {kind!r}")


def quotient(spec, src):
    num = [term(t, src) for t in spec.get("num", [])]
    den = [term(t, src) for t in spec.get("den", [])]
    if None in num or None in den or math.prod(den) == 0:
        return None
    return math.prod(num) / math.prod(den)


def evaluate(read, src):
    """``scale x num / den``, optionally ``minus`` a second such quotient.
    None when any source is absent: the metric is then left out, never zero."""
    value = quotient(read, src)
    if value is not None and "minus" in read:
        other = quotient(read["minus"], src)
        value = None if other is None else value - other
    return None if value is None else read.get("scale", 1) * value


# -- the server child ---------------------------------------------------------------------

def wait_ready(client, proc, timeout):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise BenchFailure(f"server exited with code {proc.returncode} before it was ready")
        try:
            status, body = client.request("GET", "/healthz", timeout=10)
            if status == 200:
                return json.loads(body)
        except (OSError, http.client.HTTPException, ValueError):
            pass
        time.sleep(0.5)
    raise BenchFailure(f"server not ready after {timeout:.0f}s")


def warm_up(client, traffic, model, seed):
    """The cell's own generator on a disjoint stream: at least two requests a
    client, until two seconds pass in which no program compiled."""
    state = {"programs": None, "grew": time.monotonic(), "stop": False}

    def poll():
        while not state["stop"]:
            try:
                programs = dotted(client.get_json("/healthz"), "device.compile.programs")
            except (BenchFailure, OSError, http.client.HTTPException, ValueError):
                programs = None
            if programs is not None and programs != state["programs"]:
                state["programs"], state["grew"] = programs, time.monotonic()
            time.sleep(0.5)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    deadline = time.monotonic() + 900
    records = closed_loop(
        client, traffic, model, seed, "warmup",
        lambda mine: mine < 2 or (time.monotonic() - state["grew"] < 2.0
                                  and time.monotonic() < deadline),
    )
    state["stop"] = True
    poller.join()
    bad = [r["error"] or r["wrong"] for r in records if r["error"] or r["wrong"]]
    if bad:
        raise BenchFailure(f"warm-up: {len(bad)} of {len(records)} requests bad, first: {bad[0]}")
    return len(records)


def greedy_pair(client, traffic, model):
    """Outside the window: one greedy request of the cell's shape, twice."""
    body = make_request(traffic, model, 0, "greedy", 0)
    body.update(temperature=0, seed=7)
    answers = [send_one(client, body, traffic) for _ in range(2)]
    for a in answers:
        if a["error"] or a["wrong"]:
            raise BenchFailure(f"greedy request: {a['error'] or a['wrong']}")
    return answers[0]["text"] == answers[1]["text"]


def snapshot_at(client, when, box):
    """/metrics and /healthz as the window closes, while the requests still in
    flight go on: they are not the window's."""
    time.sleep(max(0.0, when - time.monotonic()))
    try:
        box["metrics"], box["health"] = client.counters(), client.get_json("/healthz")
    except (BenchFailure, OSError, http.client.HTTPException, ValueError) as e:
        box["error"] = f"snapshot at the close: {type(e).__name__}: {e}"


def capture(client, log_dir, delay, duration, box):
    """The traced run's profiler capture, from a thread of its own. The call
    returns long after the traced part ends (the profiler serializes for tens
    of seconds), so the second /metrics snapshot is taken by a timer when the
    traced part ends, not when the call returns."""
    time.sleep(delay)
    try:
        box["start"], t0 = client.counters(), time.monotonic()

        def snapshot_end():
            box["end"], box["seconds"] = client.counters(), time.monotonic() - t0

        timer = threading.Timer(duration, snapshot_end)
        timer.start()
        status, body = client.request(
            "POST", "/debug/profile", {"log_dir": log_dir, "duration_s": duration}, timeout=600)
        box["returned_s"] = time.monotonic() - t0
        timer.join()
        if status != 200:
            box["error"] = f"/debug/profile answered {status}: {body[:200]!r}"
    except (BenchFailure, OSError, http.client.HTTPException) as e:
        box["error"] = f"{type(e).__name__}: {e}"


def measure(args, client, health, entry, traffic, model, trace_dir):
    """What is done while the server is up: warm-up, the greedy pair, the
    window. Returns the run's observations for :func:`result_line`."""
    t_ready = time.monotonic()
    device = health["device"]
    if device["platform"] != args.platform:
        raise BenchFailure(f"the serving process is on {device['platform']!r}")
    if device["device_count"] < entry["chips"]:
        raise BenchFailure(f"{device['device_count']} chips, the cell asks for {entry['chips']}")
    if not health.get("continuous"):
        raise BenchFailure("the continuous loop is not running")
    warmed = warm_up(client, traffic, model, args.seed)
    t_warm = time.monotonic()
    greedy_equal = greedy_pair(client, traffic, model)
    obs = {"device": device, "health_start": client.get_json("/healthz"),
           "metrics_start": client.counters(), "capture": {}}
    obs["setup_s"] = time.monotonic() - T_PROCESS_START
    log(f"  setup {obs['setup_s']:.1f}s: ready {t_ready - T_PROCESS_START:.1f}s, warm-up "
        f"{t_warm - t_ready:.1f}s ({warmed} requests), greedy pair "
        f"{time.monotonic() - t_warm:.1f}s; compile {obs['health_start']['device']['compile']}")
    log(f"  loop width {health['continuous']['width']}, chunk "
        f"{health['continuous']['prefill_chunk_tokens']}, param_bytes "
        f"{health['hbm']['param_bytes']}, bytes_in_use {obs['health_start']['device']['bytes_in_use']}")

    wall_start, t_start = time.time(), time.monotonic()
    t_end = t_start + args.seconds
    close = {}
    helpers = [threading.Thread(target=snapshot_at, args=(client, t_end, close), daemon=True)]
    if args.trace:
        helpers.append(threading.Thread(
            target=capture, daemon=True,
            args=(client, trace_dir, max(0.0, args.seconds - CAPTURE_S - 1.0), CAPTURE_S,
                  obs["capture"])))
    for t in helpers:
        t.start()
    records = closed_loop(client, traffic, model, args.seed, "window",
                          lambda mine: time.monotonic() < t_end)
    for t in helpers:
        t.join()
    for box in (close, obs["capture"]):
        if box.get("error"):
            raise BenchFailure(box["error"])
    obs["metrics_end"], obs["health_end"] = close["metrics"], close["health"]
    obs["metrics_drained"] = client.counters()
    obs["requests"] = [
        r for r in client.get_json("/debug/requests")["requests"]
        if r.get("route") == "chat" and r["started_at"] >= wall_start
        and r["started_at"] + r["duration_s"] <= wall_start + args.seconds]

    answered = [r for r in records if r["done"] <= t_end and not r["error"] and not r["wrong"]]
    failed = [r for r in records if r["error"]]
    wrong = [r for r in records if r["wrong"]]
    nonzero = {k: v for k, v in obs["metrics_end"].items() if v and k in ZERO_EVENTS}
    log(f"  window: {len(records)} sent, {len(answered)} answered inside it, {len(failed)} failed, "
        f"{len(wrong)} wrong; document lengths {sorted(length_pool(traffic['doc_tokens']))}")
    if traffic["stream"]:
        firsts = [r["ttft"] / r["latency"] for r in answered if r["ttft"] is not None]
        log(f"  stream: {len(firsts)} of {len(answered)} answers sent a text delta before the final "
            f"event, the first at a median {statistics.median(firsts or [0]):.2f} of the latency")
    for r in (failed + wrong)[:3]:
        log(f"  bad request {r['k']}: {r['error'] or r['wrong']}")
    if nonzero:
        log(f"  counters that must be zero are not: {nonzero}")
    if not greedy_equal:
        log("  the greedy pair disagreed")
    if not answered:
        raise BenchFailure("no answer returned inside the window")
    obs.update(
        answered=answered, attempted=len(records), failed=len(failed),
        correct=bool(not wrong and not nonzero and greedy_equal
                     and (device["platform"] == "tpu" or args.platform == "cpu")),
        client={"sent": len(records), "answered": len(answered),
                "latency_sum_s": sum(r["latency"] for r in answered)})
    return obs


def result_line(args, obs, entry, config, e2e_units, layer_specs, trace_dir, peak_file):
    """The one JSON object, built once the server has exited."""
    device = obs["device"]
    result = {
        "correct": obs["correct"], "attempted": obs["attempted"], "failed": obs["failed"],
        "device": {"platform": device["platform"], "kind": device["device_kind"],
                   "count": device["device_count"],
                   "memory_peak_bytes": load_json(peak_file)["peak_bytes_in_use"]},
    }
    e2e = {name: end_to_end(name, obs["answered"], args.seconds, entry["chips"], obs["setup_s"])
           for name in e2e_units}
    log("  end to end: " + json.dumps(e2e))
    if not args.trace:
        result["metrics"] = {name: {"value": e2e[name], "unit": unit} for name, unit in e2e_units.items()}
        return result
    reduced = subprocess.run(
        [sys.executable, os.path.join(HERE, "reduce_trace.py"), trace_dir],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600)
    if reduced.returncode != 0:
        raise BenchFailure(f"reduce_trace.py failed: {reduced.stderr[-800:]}")
    trace = json.loads(reduced.stdout.strip().splitlines()[-1])
    log("  trace: " + json.dumps({k: v for k, v in trace.items() if k != "breakdown"})
        + f"; the capture call returned after {obs['capture']['returned_s']:.1f}s")
    peaks = load_json(HERE, "peaks.json")
    if device["device_kind"] not in peaks and args.platform != "cpu":
        raise BenchFailure(f"no peaks for device kind {device['device_kind']!r} in peaks.json")
    src = dict(obs, trace=trace, config=config, peaks=peaks.get(device["device_kind"], {}))
    result["metrics"] = {}
    for spec in layer_specs:
        value = evaluate(spec["read"], src)
        if value is None:
            log(f"  per-layer metric {spec['name']}: source absent, left out")
        else:
            result["metrics"][spec["name"]] = {"value": value, "unit": spec["unit"]}
    result["device"].update(busy_s=trace["busy_s"], window_s=trace["window_s"])
    result["breakdown"] = trace["breakdown"]
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", default="tpu", help="cpu: a rehearsal at toy size, never a record")
    args = ap.parse_args()

    if not os.path.isfile(os.path.join(ROOT, "k_llms_tpu", "serving", "app.py")):
        sys.exit("benchmark/run.py: no k_llms_tpu package in this checkout")
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu" and args.platform != "cpu":
        sys.exit("benchmark/run.py: JAX_PLATFORMS=cpu — a record needs the accelerator")
    try:
        entry, config_file, config, traffic, e2e_units, layer_specs = load_cell(args.workload)
    except (BenchFailure, OSError, ValueError, KeyError, StopIteration) as e:
        sys.exit(f"benchmark/run.py: {type(e).__name__}: {e}")
    model = "tiny" if args.platform == "cpu" else config["serve"]["model"]
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", args.workload)
    trace_dir, peak_file = os.path.join(out_dir, "trace"), os.path.join(out_dir, "memory_peak.json")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cmd = [sys.executable, os.path.join(HERE, "serve.py"), config_file, str(port), peak_file]
    if args.platform == "cpu":
        cmd.append("cpu")
    # The child finds the expected platform or fails while JAX starts up.
    env = dict(os.environ, JAX_PLATFORMS=args.platform, PYTHONUNBUFFERED="1")
    # A terminated parent must still stop its child: unwind through the finally below.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    log(f"benchmark: cell {args.workload} seed {args.seed} seconds {args.seconds:g} trace {args.trace}")
    failure, obs, result = None, None, None
    server_log = os.path.join(out_dir, "server.log")
    with open(server_log, "wb") as logf:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=logf, stderr=subprocess.STDOUT)
        try:
            client = Client(port, 300.0)
            health = wait_ready(client, proc, 1000.0)
            obs = measure(args, client, health, entry, traffic, model, trace_dir)
        except (BenchFailure, OSError, http.client.HTTPException, KeyError, ValueError) as e:
            failure = f"{type(e).__name__}: {e}"
        finally:
            # SIGTERM is the server's graceful shutdown: the backend drains, exit code 0.
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
    if failure is None:
        try:
            result = result_line(args, obs, entry, config, e2e_units, layer_specs, trace_dir, peak_file)
        except (BenchFailure, OSError, KeyError, ValueError, subprocess.TimeoutExpired) as e:
            failure = f"{type(e).__name__}: {e}"
    if failure is not None:
        with open(server_log, "rb") as f:
            tail = f.read()[-3000:].decode(errors="replace")
        print(f"benchmark FAILED: {failure}\n--- server log tail ---\n{tail}", file=sys.stderr)
        sys.exit(1)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
