"""From a `jax.profiler` trace to busy time, idle gaps and a table of
device operations.  Code with the benchmark, so that every PR computes
the same numbers in the same way.

Two stages.  `load` reads an `.xplane.pb` through
`jax.profiler.ProfileData` and keeps what the reduction needs as plain
lists (this is also the form of the recorded fixture under
`tests/fixtures/`).  `reduce` works on those lists alone.

What a v5e trace holds (looked at by hand, PR 24): one plane per chip
named `/device:TPU:<n>`, with the lines `Steps`, `XLA Modules` (one event
per executed program), `XLA Ops` (one event per executed HLO operation,
a few of them nested) and `Async XLA Ops` (the spans from an async
operation's start to its done, which overlap the others and are not
counted here); and the plane `/host:CPU`, whose lines are host threads
and carry the benchmark's `dispatch` and `wait` `TraceAnnotation`s by
name.  Times are nanoseconds on one clock.  An operation's event name is
its whole HLO line (`%fusion.14 = (f32[256]{...}, ...) fusion(...),
kind=kOutput, calls=...`), which `op_name` and `op_group` shorten.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, host_spans=("dispatch", "wait")) -> dict:
    """{"devices": {"0": {"ops": [[hlo line, start_ns, dur_ns], ...]}, ...},
    "host": [[span name, start_ns, dur_ns], ...]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            out["devices"][m.group(1)] = {"ops": [
                [e.name, e.start_ns, e.duration_ns]
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.duration_ns]
                    for e in line.events if e.name in host_spans)
    out["host"].sort(key=lambda e: e[1])
    return out


def op_name(text: str) -> str:
    """`%fusion.14 = ... fusion(...)` -> `fusion.14`."""
    return text.split(" = ", 1)[0].lstrip("%")


_KIND = re.compile(r"kind=(k\w+)")
_CUSTOM = re.compile(r'custom_call_target="([^"]+)"')


def op_group(text: str) -> str:
    """The operation's name without its number, with the fusion kind or the
    custom call's target where the HLO line gives one: `fusion kOutput`
    (on a TPU a convolution or matrix multiplication with what was fused
    into it), `fusion kLoop` (elementwise), `fusion kInput` (reductions),
    `custom-call tpu_custom_call` (a Pallas kernel), `all-gather`, ..."""
    base = re.sub(r"\.\d+$", "", op_name(text))
    m = _KIND.search(text) or _CUSTOM.search(text)
    return f"{base} {m.group(1)}" if m else base


def union(intervals) -> list:
    """Sorted, disjoint [start, end) covering the same points."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def self_times(ops) -> dict:
    """Per operation name, the time its events ran minus the time their
    nested children ran (events of one line nest properly)."""
    total: dict = {}
    stack: list = []   # [name, end, start, time of children]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, start, child = stack.pop()
            dur = end - start
            total[name] = total.get(name, 0.0) + max(dur - child, 0.0)
            if stack:
                stack[-1][3] += dur

    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, start + dur, start, 0.0])
    close(float("inf"))
    return total


def window_of(tables: dict) -> tuple:
    """The traced window: first host span's start to last host span's end
    (the loop's first dispatch to its closing block)."""
    host = tables["host"]
    if not host:
        raise ValueError("the trace holds none of the benchmark's host spans")
    return (min(s for _, s, _ in host), max(s + d for _, s, d in host))


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce(tables: dict, top: int = 10) -> dict:
    """busy_s (mean over devices) and window_s; per device busy seconds;
    device 0's operations by self time, by name and by `op_group` (the
    `top` groups are `device_ops`); device 0's idle time by which host
    span covered each gap's midpoint.  Readers sum `ops_by_name`."""
    lo, hi = window_of(tables)
    if not tables["devices"]:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    busy = {}
    for dev, t in sorted(tables["devices"].items(), key=lambda kv: int(kv[0])):
        spans = clip(union([s, s + d] for _, s, d in t["ops"]), lo, hi)
        busy[dev] = spans
    dev0 = min(busy, key=int)
    first = busy[dev0]
    ops0 = [e for e in tables["devices"][dev0]["ops"]
            if e[1] + e[2] > lo and e[1] < hi]
    by_text = self_times(ops0)
    by_name: dict = {}
    by_group: dict = {}
    for text, t in by_text.items():
        by_name[op_name(text)] = by_name.get(op_name(text), 0.0) + t
        by_group[op_group(text)] = by_group.get(op_group(text), 0.0) + t

    host = [(n, s, s + d) for n, s, d in tables["host"]]
    gaps: dict = {}
    edges = [lo] + [x for s, e in first for x in (s, e)] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) / 2
        # the innermost span that covers the midpoint: the latest started
        inside = [n for n, s, e in host if s <= mid < e]
        name = f"host in {inside[-1]}" if inside else "host outside spans"
        gaps[name] = gaps.get(name, 0.0) + (g1 - g0)

    ns = 1e-9
    busy_s = {d: sum(e - s for s, e in v) * ns for d, v in busy.items()}
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy_s.values()) / len(busy_s),
        "busy_s_by_device": busy_s,
        "busy_s_first_device": busy_s[dev0],
        "ops_by_name": {k: v * ns for k, v in by_name.items()},
        "ops_by_group": {k: v * ns for k, v in by_group.items()},
        "device_ops": [[k, v * ns] for k, v in rank(by_group)],
        "top_ops": [[k, v * ns] for k, v in rank(by_name)],
        "idle_gaps": [[k, v * ns] for k, v in rank(gaps)],
    }
