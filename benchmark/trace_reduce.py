"""From a ``jax.profiler`` trace (``.xplane.pb``) to device busy seconds,
per-operation time and idle gaps.

``load_events`` reads the file with ``jax.profiler.ProfileData`` (the
only use of jax here); ``reduce_events`` is plain Python over its rows,
so the reduction is tested on a small recorded list of rows
(tests/data/).  A row is ``[plane, line, name, start_ns, duration_ns]``.

What counts, as the trace of a TPU v5e lays it out (PR 26, looked at by
hand): device planes are named ``/device:TPU:<n>``; their line
``XLA Ops`` holds one event for every operation that ran on the device,
``XLA Modules`` one for every program.  Busy time is the union of the
``XLA Ops`` intervals; the traced window is the extent of the harness's
own ``bench_request:<entry>`` spans, which sit on the host plane on the
same clock.  An idle gap is named by the request that was in flight
across its middle and the host span (any thread) that covers that
instant most tightly.

    python3 benchmark/trace_reduce.py <dir-or-file> [--dump]
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
_HOST_MIN_NS = 20_000  # host spans shorter than this name no gap
_LOOK_BACK = 400
_TYPE = re.compile(r"^\w+\[[^\]]*\](\{[^}]*\})?\s+")  # f32[8,128]{1,0:T(8,128)}


def find_xplane(trace_dir: str) -> str | None:
    if os.path.isfile(trace_dir):
        return trace_dir
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_events(path: str, span_prefix: str) -> list[list]:
    """Rows of the device planes' operations and programs, the harness's
    request spans and the host spans long enough to name a gap."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if (device or ev.duration_ns >= _HOST_MIN_NS
                        or ev.name.startswith(span_prefix)):
                    rows.append([plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)])
    return rows


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def short_name(name: str, width: int = 96) -> str:
    """An HLO instruction's text without its result type: ``%fusion.3 =
    (f32[...], ...) fusion(...)`` becomes ``%fusion.3 = fusion(...)``,
    cut to ``width``.  Other names are only cut."""
    lhs, eq, rhs = name.partition(" = ")
    if eq and lhs.startswith("%"):
        if rhs.startswith("("):
            depth = 0
            for i, ch in enumerate(rhs):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    rhs = rhs[i + 1:].lstrip()
                    break
        else:
            rhs = _TYPE.sub("", rhs, count=1)
        name = f"{lhs} = {rhs}"
    return name[:width]


def _top(totals: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def reduce_events(rows: list[list], span_prefix: str) -> dict | None:
    """None when the trace holds no request span or no device operation:
    there is then nothing to read, and no metric is made up."""
    spans = [(r[3], r[3] + r[4], r[2]) for r in rows
             if r[0] == HOST_PLANE and r[2].startswith(span_prefix)]
    ops = [r for r in rows
           if r[0].startswith(DEVICE_PLANE) and r[1] == OPS_LINE]
    if not spans or not ops:
        return None
    w0, w1 = min(s[0] for s in spans), max(s[1] for s in spans)
    planes = sorted({r[0] for r in ops})
    busy_ns = 0.0
    op_ns: dict[str, float] = {}
    gap_ns: dict[str, float] = {}
    host = sorted((r[3], r[3] + r[4], r[2]) for r in rows
                  if r[0] == HOST_PLANE and not r[2].startswith(span_prefix))
    spans.sort()
    n_gaps = 0
    longest_gap = 0.0
    for plane in planes:
        clipped = []
        for r in ops:
            if r[0] != plane:
                continue
            s, e = max(r[3], w0), min(r[3] + r[4], w1)
            if e > s:
                clipped.append((s, e))
                key = short_name(r[2])
                op_ns[key] = op_ns.get(key, 0.0) + (e - s)
        merged = union(clipped)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            n_gaps += 1
            longest_gap = max(longest_gap, g1 - g0)
            name = _name_gap((g0 + g1) / 2, spans, host, span_prefix)
            gap_ns[name] = gap_ns.get(name, 0.0) + (g1 - g0)
    modules: dict[str, float] = {}
    for r in rows:
        if r[0].startswith(DEVICE_PLANE) and r[1] == MODULES_LINE:
            s, e = max(r[3], w0), min(r[3] + r[4], w1)
            if e > s:
                modules[r[2]] = modules.get(r[2], 0.0) + (e - s)
    n = len(planes)
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "requests": len(spans),
        "devices": n,
        "idle_gaps": n_gaps,
        "longest_gap_s": longest_gap / 1e9,
        "modules": [[k, v / n / 1e9] for k, v in _top(modules)],
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in _top(op_ns)],
            "idle_gaps": [[k, v / n / 1e9] for k, v in _top(gap_ns)],
        },
    }


def _name_gap(t: float, spans, host, span_prefix: str) -> str:
    """``spans`` and ``host`` are sorted by start; the host span is
    looked for among the ``_LOOK_BACK`` that started last before ``t``."""
    entry = "between_requests"
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    if i >= 0 and spans[i][1] > t:
        entry = spans[i][2][len(span_prefix):].lstrip(":")
    best, best_len = "host_without_span", float("inf")
    j = bisect.bisect_right(host, (t, float("inf"), ""))
    for s, e, name in host[max(0, j - _LOOK_BACK):j]:
        if e > t and e - s < best_len:
            best, best_len = name, e - s
    return f"{entry}|{best}"[:120]


def reduce_dir(trace_dir: str, span_prefix: str) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_events(load_events(path, span_prefix), span_prefix)


def dump(path: str) -> None:
    """What a hand look needs: every plane and line with its event count,
    its first names and its busiest names."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            total: dict[str, float] = {}
            n = 0
            for ev in line.events:
                n += 1
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns
            print("  LINE", repr(line.name), n, json.dumps(_top(total, 8)))


if __name__ == "__main__":
    target = find_xplane(sys.argv[1])
    if target is None:
        sys.exit(f"no .xplane.pb under {sys.argv[1]}")
    if "--dump" in sys.argv:
        dump(target)
    else:
        print(json.dumps(reduce_dir(target, "bench_request"), indent=1))
