"""Tracer — nested spans and instant events on the step clock AND the
wall clock (ISSUE 11 tentpole, leg 1).

Design constraints, in priority order:

1. **Provably free when off.**  Call sites hold ``tracer = None`` (or
   `NULL_TRACER`) and the hot loops guard with one ``is not None``
   check; nothing is allocated, formatted or timestamped.  The obs-on
   path only *observes* — it never touches values that feed a jitted
   program, so step outputs are bitwise identical either way (pinned in
   tests/test_obs.py and the obs-smoke gate).
2. **Two clocks per record.**  Every span/event carries the *step*
   (the deterministic logical clock every counter and fault plan runs
   on) and the *wall* time (`obs.timing.now`, the one monotonic clock).
   Exports can strip the wall fields to get byte-reproducible artifacts
   (export.py), while latency metrics keep the real timings.
3. **Bounded by construction.**  ``max_records`` caps both streams;
   past it the oldest records age out (counted, never silent) — a
   tracer left attached to a long-running engine cannot grow host
   memory without limit, same doctrine as `serve.ResultStore`.

Record shapes (plain tuples — export.py owns the serialization):

* span:  ``(seq, name, cat, step, wall_t0, dur_s, depth, args)``
* event: ``(seq, name, cat, step, wall, args)`` — instant occurrences;
  the serve per-request timeline rides these with ``cat="req"`` and
  ``args["rid"]`` (engine.py stamps submit/admit/first_chunk/
  first_token/complete/shed/deadline_miss plus verdict/SLA/ladder
  annotations; docs/OBSERVABILITY.md has the taxonomy).

``seq`` is a per-tracer monotone ordinal — the deterministic total
order exports sort by, so two runs of the same (trace, plan, seed)
produce identical streams modulo the wall fields.
"""

from __future__ import annotations

from collections import deque
from typing import Optional

from .timing import now

__all__ = ["Tracer", "Span", "NULL_TRACER", "NULL_SPAN"]


class Span:
    """Context manager handed out by `Tracer.span` — records on exit."""

    __slots__ = ("_tracer", "name", "cat", "step", "args", "_t0", "_note")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 step: Optional[int], args: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.step = step
        self.args = args
        self._t0 = 0.0
        self._note = None

    def __enter__(self) -> "Span":
        self._tracer._depth += 1
        if self._tracer._annotation is not None:
            # the same span on the profiler's clock (Tracer(annotate=True))
            self._note = self._tracer._annotation(self.name)
            self._note.__enter__()
        self._t0 = now()
        return self

    def __exit__(self, *exc) -> None:
        t1 = now()
        if self._note is not None:
            self._note.__exit__(*exc)
        tr = self._tracer
        tr._depth -= 1
        tr._push(tr.spans, (tr._next_seq(), self.name, self.cat,
                            self.step, self._t0, t1 - self._t0,
                            tr._depth, self.args))


class _NullSpan:
    """Reusable no-op context manager — the disabled path allocates
    nothing per call.  Exported as `NULL_SPAN` so call sites that
    branch on ``tracer is None`` themselves (e.g. the serve engine's
    per-phase spans) share THE one null context instead of growing
    local copies."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_NULL_SPAN = NULL_SPAN = _NullSpan()


class Tracer:
    """Span/event collector for one run (module docstring).

    Parameters
    ----------
    run : label stamped into exports ("train", "serve", "bench", ...).
    max_records : bound on EACH stream (spans, events); the oldest age
        out past it, counted in ``spans_dropped``/``events_dropped``.
    meta : free-form run metadata carried into the export headers
        (model shape, flags, world size) — keep it JSON-serializable.
    annotate : also enter a `jax.profiler.TraceAnnotation` of the same
        name around every span, so that a `jax.profiler` trace taken
        meanwhile holds the host's `data` / `step` / `checkpoint` / serve
        phases on the device's clock.  jax is imported here, on request
        only: ``import cpd_tpu.obs`` stays stdlib-only, and with it off a
        span pays one ``is not None`` test.
    """

    def __init__(self, run: str = "run", *, max_records: int = 65536,
                 meta: Optional[dict] = None, annotate: bool = False):
        if max_records < 1:
            raise ValueError(f"max_records must be >= 1, got "
                             f"{max_records}")
        self.run = run
        self.meta = dict(meta or {})
        self.max_records = int(max_records)
        self.spans: deque = deque()
        self.events: deque = deque()
        self.spans_dropped = 0
        self.events_dropped = 0
        self._seq = 0
        self._depth = 0
        self._annotation = None
        if annotate:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation

    # -- recording --------------------------------------------------------

    def span(self, name: str, *, step: Optional[int] = None,
             cat: str = "phase", **args) -> Span:
        """``with tracer.span("data", step=it): ...`` — nested spans
        carry their depth so exports reconstruct the hierarchy."""
        return Span(self, name, cat, step, args)

    def event(self, name: str, *, step: Optional[int] = None,
              cat: str = "mark", wall: Optional[float] = None,
              **args) -> None:
        """Instant occurrence.  ``wall`` lets a caller that already
        timestamped the moment (loadgen's step_wall, the engine's event
        log) record the SAME float — that shared value is what makes
        timeline reconstruction exact."""
        self._push(self.events,
                   (self._next_seq(), name, cat, step,
                    now() if wall is None else wall, args))

    def request_event(self, rid: int, kind: str, step: int, *,
                      wall: Optional[float] = None, **args) -> None:
        """One serve per-request timeline record (cat="req")."""
        self.event(kind, step=step, cat="req", wall=wall, rid=rid,
                   **args)

    # -- internals --------------------------------------------------------

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _push(self, stream: deque, record: tuple) -> None:
        stream.append(record)
        if len(stream) > self.max_records:
            stream.popleft()
            if stream is self.spans:
                self.spans_dropped += 1
            else:
                self.events_dropped += 1

    def summary(self) -> dict:
        return {"run": self.run, "spans": len(self.spans),
                "events": len(self.events),
                "spans_dropped": self.spans_dropped,
                "events_dropped": self.events_dropped}


class _NullTracer:
    """The zero-cost disabled tracer: every method is a no-op and
    `span` returns one shared reusable context manager.  Call sites
    that prefer not to branch on ``None`` can hold this instead."""

    run = "off"
    meta: dict = {}
    spans: tuple = ()
    events: tuple = ()
    spans_dropped = events_dropped = 0

    def span(self, name, *, step=None, cat="phase", **args):
        return _NULL_SPAN

    def event(self, name, *, step=None, cat="mark", wall=None, **args):
        return None

    def request_event(self, rid, kind, step, *, wall=None, **args):
        return None

    def summary(self) -> dict:
        return {"run": "off", "spans": 0, "events": 0,
                "spans_dropped": 0, "events_dropped": 0}

    def __bool__(self) -> bool:
        # `if tracer:` reads as "is tracing live?" at call sites
        return False


NULL_TRACER = _NullTracer()
