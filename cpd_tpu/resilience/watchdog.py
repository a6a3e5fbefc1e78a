"""Step watchdog — a hung step becomes a diagnosis, not a silent wedge.

Pod-scale reality: a step that normally takes 300ms occasionally never
returns — a wedged collective, a straggler host, a dead interconnect
link.  The blocking call cannot time itself out, so a background timer
thread does: on expiry it (1) dumps the last-known context and every
thread's stack to stderr, (2) marks itself ``tripped``, and (3) sends
the process a real SIGINT (``os.kill`` — an actual OS signal, which
wakes a blocked ``time.sleep``/select immediately; NOT
``_thread.interrupt_main``, whose simulated flag is only noticed at the
main thread's next bytecode, i.e. never while it is blocked).  With the
default handler that raises ``KeyboardInterrupt``; the training loop
catches it, sees ``tripped``, checkpoints the last *good* state, and
exits cleanly — distinguishable from a real Ctrl-C, which it re-raises.

The interrupt path has two honest limitations.  (1) A PreemptionGuard
traps SIGINT, so the watchdog's signal sets ITS flag
instead of raising — the trainers therefore also check
``watchdog.tripped`` at the step boundary.  (2) A step wedged inside
native code (a dead collective rendezvous, a hung device sync) never
returns to the interpreter at all, so NO Python-level signal can
unblock it.  ``hard_exit_after`` covers both: if the trip is not
acknowledged (disarm/boundary) within that many extra seconds, the
watchdog prints a final line and ``os._exit(124)``s — the run dies
with diagnostics and the last periodic checkpoint intact instead of
hanging forever; the cluster supervisor restarts it.

Arm/disarm around the blocking region only (the step call + the metric
device-sync); host-side data loading gets its own budget if needed.
"""

from __future__ import annotations

import faulthandler
import os
import signal
import sys
import threading
from typing import Optional

__all__ = ["StepWatchdog"]


class StepWatchdog:
    def __init__(self, timeout: float, *, rank: int = 0,
                 interrupt: bool = True,
                 hard_exit_after: Optional[float] = None,
                 on_trip=None):
        if timeout <= 0:
            raise ValueError(f"watchdog timeout must be > 0, got {timeout}")
        if hard_exit_after is not None and hard_exit_after <= 0:
            raise ValueError(f"hard_exit_after must be > 0, got "
                             f"{hard_exit_after}")
        self.timeout = float(timeout)
        self.rank = rank
        self.interrupt = interrupt
        self.hard_exit_after = hard_exit_after
        # ``on_trip(context_dict)`` runs on the timer thread at fire
        # time, BEFORE the interrupt is sent — the obs flight recorder
        # hooks its dump here so even a wedge that ends in the
        # hard-exit path leaves the recent-event ring on disk
        # (cpd_tpu/obs/flight.py).  Best-effort: a failing hook must
        # not stop the interrupt.
        self.on_trip = on_trip
        self.tripped = False
        self.trips = 0
        self._timer: Optional[threading.Timer] = None
        self._exit_timer: Optional[threading.Timer] = None
        self._context: dict = {}
        self._lock = threading.Lock()

    def arm(self, step: int, **context) -> None:
        """Start (or restart) the countdown for ``step``.  ``context`` is
        whatever the loop knows (last metrics, phase) — it goes verbatim
        into the diagnostic dump.

        Arming CLEARS ``tripped``: a fresh deadline is a fresh verdict.
        Without this a loop that recovers and continues (a guarded
        rollback, an elastic shrink) would see the PREVIOUS step's stale
        trip at its next boundary check and abort a perfectly healthy
        recovery step (ISSUE 19 bugfix).  A trip fired DURING a step
        stays visible at that step's boundary — arm precedes the step —
        and the cumulative ``trips`` total is never reset."""
        with self._lock:
            self._cancel_locked()
            self.tripped = False
            self._context = {"step": step, **context}
            self._timer = threading.Timer(self.timeout, self._fire)
            self._timer.daemon = True
            self._timer.start()

    def disarm(self) -> None:
        with self._lock:
            self._cancel_locked()

    close = disarm

    def _cancel_locked(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if self._exit_timer is not None:
            # the trip was acknowledged in time: call off the hard exit
            self._exit_timer.cancel()
            self._exit_timer = None

    def _fire(self) -> None:
        with self._lock:
            # trip verdict AND context snapshot under the same lock
            # arm() holds while clearing `tripped` / swapping _context
            # in — this timer thread races the main loop re-arming for
            # the next step (host-race, ISSUE 16); everything below
            # works on the snapshot
            self.tripped = True
            self.trips += 1
            context = dict(self._context)
        ctx = dict(context)
        print(f"=> watchdog: step {ctx.pop('step', '?')} exceeded "
              f"{self.timeout:.1f}s; last known: {ctx}", file=sys.stderr,
              flush=True)
        try:
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        except Exception as e:
            # diagnostics are best-effort; the interrupt below must
            # still fire even when stderr is a closed pipe
            print(f"=> watchdog: stack dump failed: {e}", file=sys.stderr)
        if self.hard_exit_after is not None:
            # armed BEFORE the on_trip hook: a hook that BLOCKS (its
            # dump path living on the same hung filesystem that wedged
            # the step) must not defeat the backstop — the try/except
            # below only covers a raising hook, not a hanging one
            with self._lock:
                self._exit_timer = threading.Timer(self.hard_exit_after,
                                                   self._hard_exit)
                self._exit_timer.daemon = True
                self._exit_timer.start()
        if self.on_trip is not None:
            try:
                self.on_trip(dict(context))
            except Exception as e:
                print(f"=> watchdog: on_trip hook failed: {e}",
                      file=sys.stderr)
        if self.interrupt:
            # a REAL SIGINT (not _thread.interrupt_main, which only sets
            # a flag the main thread notices at its next bytecode — i.e.
            # never, while it is blocked): the OS signal wakes a blocked
            # time.sleep/select immediately, exactly like a Ctrl-C
            os.kill(os.getpid(), signal.SIGINT)

    def _hard_exit(self) -> None:
        # the interrupt was never honored: the main thread is wedged in
        # native code (or a SIGINT-trapping guard absorbed the signal
        # and the boundary never came).  Dying loudly with the last
        # periodic checkpoint intact beats hanging forever.
        print(f"=> watchdog: trip unacknowledged after "
              f"{self.hard_exit_after:.1f}s — hard exit (124)",
              file=sys.stderr, flush=True)
        os._exit(124)
