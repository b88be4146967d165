"""benchmark/reduce_trace.py — a profiler capture reduced to device busy/idle, top ops, idle gaps.

    JAX_PLATFORMS=cpu python3 benchmark/reduce_trace.py <log_dir> [--dump]

Reads the newest ``*.xplane.pb`` under ``<log_dir>`` with
``jax.profiler.ProfileData`` (the benchmark's only use of ``jax``; run.py
starts this as a short child after the server has exited, so it never asks for
the chip) and prints one JSON object as its last line:

  busy_s     seconds in which an operation ran on the device: the union of the
             event intervals of each device plane's op line, averaged over
             the device planes
  window_s   first device event's start to the last one's end
  breakdown  device_ops: the three programs (``XLA Modules``) with the most
             summed time, then the seven operations with the most *exclusive*
             time (a ``while`` over the layers keeps only what its body's ops
             leave); idle_gaps: the idle time of device 0 in gaps of 0.1 ms
             or more, summed by what the host was doing in each — the
             shortest host-thread event that covers at least half of the gap
             and is not itself a wait; ``host`` when there is none

A device plane is one named ``/device:...``; its op line is ``XLA Ops`` where
the plane has one, else every line. ``--dump`` lists planes, lines and the
commonest event names instead: look at a trace by hand before trusting this.
"""

import collections
import glob
import json
import os
import re
import sys

OPS_LINE, PROGRAMS_LINE = "XLA Ops", "XLA Modules"
MIN_GAP_NS = 100_000
WAITING = ("acquire", "wait", "select", "sleep", "poll", "result", "get", "join")


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def gaps(merged):
    """The idle intervals between merged busy intervals, longest first."""
    out = [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
    return sorted(out, reverse=True)


def covering(events, start, end):
    """Name of the shortest (name, start, end) event that covers at least half
    of [start, end): the most specific thing the host was doing then."""
    best, best_length = None, None
    for name, s, e in events:
        if 2 * (min(e, end) - max(s, start)) >= end - start and (best is None or e - s < best_length):
            best, best_length = name, e - s
    return best


def self_times(events):
    """Summed exclusive nanoseconds by name: an op that encloses others on its
    line (a ``while`` over the layers, a ``call``) keeps only what they leave."""
    totals, stack = collections.Counter(), []
    for name, start, end in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            totals[stack[-1][0]] -= min(end, stack[-1][1]) - start
        totals[name] += end - start
        stack.append((name, end))
    return totals


def short(name):
    """``%name kind shape`` of an HLO instruction's text; other names as they are."""
    m = re.match(r"(%\S+) = (.*?) ([a-z][a-z0-9\-]*)\(", re.sub(r"\{[^{}]*\}", "", name))
    if not m:
        return name.split("(")[0][:80]
    return f"{m.group(1)} {m.group(3)} {m.group(2)[:40]}"


def waiting(name):
    return name.rsplit(" ", 1)[-1] in WAITING


def reduce(planes):
    """``planes``: {plane name: {line name: [(event name, start_ns, end_ns)]}}."""
    devices = {p: lines for p, lines in planes.items() if p.startswith("/device:")}
    per_device, ops, programs = [], collections.Counter(), collections.Counter()
    for _, lines in sorted(devices.items()):
        events = lines.get(OPS_LINE) or [e for line in lines.values() for e in line]
        if events:
            per_device.append(union([(s, e) for _, s, e in events]))
            ops.update(self_times(events))
            for name, s, e in lines.get(PROGRAMS_LINE, []):
                programs[name] += e - s
    if not per_device:
        return {"busy_s": None, "window_s": None, "devices": 0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    n = len(per_device)
    busy = sum(e - s for merged in per_device for s, e in merged) / n / 1e9
    window = sum(merged[-1][1] - merged[0][0] for merged in per_device) / n / 1e9
    # Idle time of device 0 by what the host was doing: every gap of at least
    # MIN_GAP_NS goes to the shortest host-thread event that covers half of it
    # and is not itself a wait (a lock, a sleep, a poll).
    long_gaps = [g for g in gaps(per_device[0]) if g[0] >= MIN_GAP_NS]
    host = [e for p, lines in planes.items() if p.startswith("/host:")
            for line in lines.values() for e in line
            if 2 * (e[2] - e[1]) >= MIN_GAP_NS and not waiting(e[0])]
    idle = collections.Counter()
    for length, start, end in long_gaps:
        idle[covering(host, start, end) or "host"] += length
    return {
        "busy_s": busy, "window_s": window, "devices": n,
        "gaps": len(long_gaps), "gap_s": sum(g[0] for g in long_gaps) / 1e9,
        "breakdown": {
            "device_ops": [[f"program {name.split('(')[0]}", ns / n / 1e9]
                           for name, ns in programs.most_common(3)]
            + [[short(name), ns / n / 1e9] for name, ns in ops.most_common(7)],
            "idle_gaps": [[name, ns / 1e9] for name, ns in idle.most_common(5)],
        },
    }


def read_planes(log_dir):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        sys.exit(f"reduce_trace.py: no .xplane.pb under {log_dir}")
    planes = {}
    for plane in ProfileData.from_file(files[-1]).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events)
    return planes


def main():
    planes = read_planes(sys.argv[1])
    if "--dump" in sys.argv[2:]:
        for plane, lines in planes.items():
            print(f"plane {plane!r}")
            for line, events in lines.items():
                span = (max(e for _, _, e in events) - min(s for _, s, _ in events)) / 1e9 if events else 0
                top = collections.Counter()
                for name, s, e in events:
                    top[name] += e - s
                print(f"  line {line!r}: {len(events)} events over {span:.3f}s; most time: "
                      + ", ".join(f"{n[:60]}={ns / 1e6:.1f}ms" for n, ns in top.most_common(8)))
        return
    print(json.dumps(reduce(planes)))


if __name__ == "__main__":
    main()
