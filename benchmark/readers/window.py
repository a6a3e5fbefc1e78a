"""Metrics read from the measured window's host clock and host spans."""

from __future__ import annotations

import statistics


def rate_per_chip(ctx: dict, args: dict):
    """Items completed in the window / window seconds / chips: all the
    work and all the time of the window."""
    w = ctx["window"]
    return w.steps * ctx["items_per_step"] / w.seconds / ctx["chips"]


def step_ms_percentile(ctx: dict, args: dict):
    """The `q`-th percentile of the step-time samples (each the time one
    group of steps took to complete, over the group's size)."""
    samples = ctx["window"].step_samples
    if len(samples) < 2:
        return None
    cuts = statistics.quantiles(samples, n=100, method="inclusive")
    return 1e3 * cuts[args["q"] - 1]


def step_ms_median(ctx: dict, args: dict):
    samples = ctx["window"].step_samples
    return 1e3 * statistics.median(samples) if samples else None


def setup_s(ctx: dict, args: dict):
    return ctx["setup_seconds"]


def span_ms_per_step(ctx: dict, args: dict):
    """Mean host time per step inside the benchmark's own span `span`."""
    w = ctx["window"]
    return 1e3 * w.spans.total(args["span"]) / w.steps
