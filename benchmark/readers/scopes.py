"""Metrics read from the device trace by the program's own scopes
(`trace_scopes.reduce_scopes`, handed over as `ctx["scopes"]`).  A run
without a trace, or a traced program that carries no scope at all, gives
these readers nothing to read: they return None, never zero.

`include` and `exclude` are regular expressions on the scope path
(`trace_scopes.scope_path`); which scopes make up a metric is the metric
file's business, not this module's.
"""

from __future__ import annotations

import importlib
import re


def _table(ctx: dict):
    s = ctx.get("scopes")
    return s if s and s["scopes_found"] else None


def _rows(scopes: dict, args: dict):
    include = re.compile(args["include"])
    exclude = re.compile(args["exclude"]) if args.get("exclude") else None
    return [row for path, row in scopes["by_scope"].items()
            if include.search(path)
            and not (exclude and exclude.search(path))]


def ms_per_step(ctx: dict, args: dict):
    """Summed self time, on device 0, of the operations whose scope path
    matches `include` and not `exclude`, per step."""
    s = _table(ctx)
    if s is None:
        return None
    return 1e3 * sum(r["s"] for r in _rows(s, args)) / ctx["window"].steps


def gbytes_per_step(ctx: dict, args: dict):
    """The same operations' `bytes_accessed` (XLA's own count, every run
    of every operation), per step: a count, not a time."""
    s = _table(ctx)
    if s is None:
        return None
    return sum(r["bytes"] for r in _rows(s, args)) / ctx["window"].steps / 1e9


def unscoped_pct(ctx: dict, args: dict):
    """Busy time in operations that no scope owns / busy time."""
    s = _table(ctx)
    if s is None or not s["busy_s"]:
        return None
    return 100.0 * s["unscoped_s"] / s["busy_s"]


def exposed_collective_ms_per_step(ctx: dict, args: dict):
    """Time a collective ran (start to done, for an async one) while no
    other operation ran on device 0, per step."""
    s = _table(ctx)
    if s is None:
        return None
    return 1e3 * s["collectives"]["exposed_s"] / ctx["window"].steps


def collective_bytes_per_step(ctx: dict, args: dict):
    """Bytes device 0 received in the scoped collectives, from the
    executed operations' own shapes, per step."""
    s = _table(ctx)
    if s is None:
        return None
    return s["collectives"]["received_bytes"] / ctx["window"].steps


def roofline_pct(ctx: dict, args: dict):
    """The larger of (operations / bf16 peak) and (bytes / HBM bandwidth)
    over the scope's time: the share of its roofline the kernel reached.
    `ops` names a function under `benchmark/flops/` that gives the
    operations and bytes of one step from the cell's own sizes."""
    s = _table(ctx)
    if s is None:
        return None
    seconds = sum(r["s"] for r in _rows(s, args)) / ctx["window"].steps
    if not seconds:
        return None
    module, func = args["ops"].split(":")
    ops, nbytes = getattr(importlib.import_module(
        f"benchmark.flops.{module}"), func)(ctx["config"], ctx["traffic"])
    floor = max(ops / (ctx["peaks"]["bf16_tflops"] * 1e12),
                nbytes / (ctx["peaks"]["hbm_gbytes_per_s"] * 1e9))
    return 100.0 * floor / seconds
