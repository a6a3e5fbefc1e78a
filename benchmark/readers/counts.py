"""Metrics that are counts: computed from shapes, or read from the
runtime's own counters."""

from __future__ import annotations

import importlib

from benchmark.readers.window import rate_per_chip


def mfu_pct(ctx: dict, args: dict):
    """Operations the forward and backward passes need per item (the
    configuration's `ops_per_item` function under `benchmark/flops/`)
    x the window's rate per chip / the device kind's bf16 peak."""
    module, func = ctx["config"]["ops_per_item"].split(":")
    ops = getattr(importlib.import_module(f"benchmark.flops.{module}"),
                  func)(ctx["config"], ctx["traffic"])
    return (100.0 * ops * rate_per_chip(ctx, {})
            / (ctx["peaks"]["bf16_tflops"] * 1e12))


def wire_bytes_per_step(ctx: dict, args: dict):
    """Bytes each device receives in one gradient reduction, by
    `parallel/ring.py`'s own formulas, at the cell's parameter count,
    world size, format and transport."""
    from cpd_tpu.parallel.ring import (gather_transport_bytes,
                                       ring_transport_bytes)

    r = ctx["traffic"]["reduce"]
    n, world = ctx["param_count"], ctx["chips"]
    exp, man = r.get("grad_exp", 8), r.get("grad_man", 23)
    if r.get("mode", "faithful") == "ring":
        return float(ring_transport_bytes(n, world, exp, man))
    if r.get("mode", "faithful") == "faithful":
        return float(gather_transport_bytes(
            n, world, exp, man, compressed=bool(r.get("use_aps"))))
    return None


def peak_hbm_mb(ctx: dict, args: dict):
    return ctx["memory_peak_bytes"] / 1e6


def step_metric(ctx: dict, args: dict):
    """What the step itself reported under `key` in its last step of the
    window (`loop.Window.last_metrics`): a counter a model puts into its
    step's metrics.  Nothing when the step reports no such key."""
    return ctx.get("last_metrics", {}).get(args["key"])


def reading(ctx: dict, args: dict):
    """A reading of `benchmark/check.py` (the first steps against the
    plain reference's), by name: a distance, not a time."""
    value = ctx.get("readings", {}).get(args["name"])
    return value if value is not None and value == value else None
