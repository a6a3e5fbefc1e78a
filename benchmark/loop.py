"""The one measured loop, shared by every cell.

The trainer CLIs' shape: one dispatch a step (not `make_multi_train_step`),
in groups of `group` steps, one group in flight.  After enqueuing group
g+1 the host blocks on the losses of group g and takes a timestamp; the
differences between timestamps, over `group`, are the step-time samples.
The window ends at the first timestamp past `seconds`; a final block on
the state closes it, so every step dispatched has completed inside the
window.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax

from cpd_tpu.obs.timing import now


class Spans:
    """Host spans `(name, start, end)` on `obs.timing.now`, kept in memory.
    With `annotate` they are also written into the profiler's trace as
    `jax.profiler.TraceAnnotation`s, so that the device's idle gaps can be
    laid against what the host was doing."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        note = (jax.profiler.TraceAnnotation(name) if self.annotate
                else contextlib.nullcontext())
        with note:
            t0 = now()
            try:
                yield
            finally:
                self.records.append((name, t0, now()))

    def total(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.records if n == name)


@dataclasses.dataclass
class Window:
    state: object
    steps: int                 # dispatched, and completed, in the window
    seconds: float             # first dispatch to the closing block
    step_samples: list         # seconds per step, one per group after the first
    losses: list               # device scalars, one per step
    spans: Spans
    last_metrics: dict = None  # the last step's whole `metrics`, on the device


def measure(step, state, batches, group: int, seconds: float,
            annotate: bool = False) -> Window:
    spans = Spans(annotate)
    losses, stamps = [], []
    pending = None
    steps = 0
    t_start = now()
    while True:
        with spans.span("dispatch"):
            current = []
            for _ in range(group):
                a, b = batches[steps % len(batches)]
                state, metrics = step(state, a, b)
                current.append(metrics["loss"])
                steps += 1
        losses.extend(current)
        if pending is not None:
            with spans.span("wait"):
                jax.block_until_ready(pending)
            stamps.append(now())
            if stamps[-1] - t_start >= seconds:
                break
        pending = current
    with spans.span("wait"):
        jax.block_until_ready(state)
    t_end = now()
    edges = [t_start] + stamps
    samples = [(b - a) / group for a, b in zip(edges, edges[1:])]
    return Window(state=state, steps=steps, seconds=t_end - t_start,
                  step_samples=samples, losses=losses, spans=spans,
                  last_metrics=metrics)
