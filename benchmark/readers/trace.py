"""Metrics read from the reduced device trace (`trace_reduce.reduce`).
A run without a trace gives these readers nothing to read."""

from __future__ import annotations

import re


def busy_ms_per_step(ctx: dict, args: dict):
    t = ctx.get("trace")
    if not t:
        return None
    return 1e3 * t["busy_s_first_device"] / ctx["window"].steps


def idle_pct(ctx: dict, args: dict):
    t = ctx.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def ops_ms_per_step(ctx: dict, args: dict):
    """Summed self time, on device 0, of the operations whose trace name
    matches `pattern`, per step."""
    t = ctx.get("trace")
    if not t:
        return None
    pat = re.compile(args["pattern"])
    total = sum(s for name, s in t["ops_by_name"].items() if pat.search(name))
    return 1e3 * total / ctx["window"].steps
