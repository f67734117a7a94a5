"""Drive ``ContinuousBatchingEngine.serve`` with arrivals, through its hooks.

The engine takes its whole request list at once and has no clock of
arrivals. ``OpenLoopDriver`` is a ``FaultInjector`` that gives it one:

- ``admit_fault`` holds each request until its due time, so nothing is
  admitted early (the engine's queue is FIFO, so later requests wait
  behind it, as they would arrive behind it);
- ``step_begin`` sleeps to the next due time when nothing is live, so
  the engine's idle-spin guard never fails a queue that is only early;
- ``step_begin`` opens the measured window (at once for arrivals; for a
  backlog once the first batch, one request per slot, is past its
  prefill, or once every live request is past its prefill and the pool
  has no room for the next) and closes it after ``seconds``
  by ``engine.cancel`` on everything still live. With arrivals it first
  waits, at most ``drain_s``, until every request due in the window has
  its first token, so that the time-to-first-token tail is of all of them.

It also stamps each step's start on the host clock, which places the
engine's ``step_log`` and step histograms in time, and can wrap each step
in a ``jax.profiler.TraceAnnotation`` so that a device trace shows what
the host was doing.
"""

from __future__ import annotations

import time

from repro.serving.faults import FaultInjector
from repro.serving.lifecycle import TERMINAL_STATES


class OpenLoopDriver(FaultInjector):
    def __init__(self, due_s: dict[int, float], *, seconds: float,
                 backlog: bool, slots: int, drain_s: float = 60.0,
                 pages: dict[int, int] | None = None, pool_pages: int = 0,
                 on_open=None, annotate: bool = False):
        self.due_s = dict(due_s)
        self.pages = pages or {}   # rid -> pages its admission reserves
        self.pool_pages = pool_pages
        self.order = sorted(self.due_s, key=lambda r: (self.due_s[r], r))
        self.seconds = float(seconds)
        self.backlog = backlog
        self.slots = slots
        self.drain_s = drain_s
        self.on_open = on_open
        self.annotate = annotate
        self.t0: float | None = None       # arrival clock start
        self.w0: float | None = None       # window open
        self.w1: float | None = None       # window close
        self.done = False                  # everything cancelled
        self.admitted: dict[int, float] = {}
        self.steps: list[tuple[float, int]] = []  # (start, len(step_log))
        self.late_s: list[float] = []      # oversleep past a due time
        self._next = 0                     # index into order
        self._ann = None

    # -- hooks ------------------------------------------------------------

    def admit_fault(self, step: int, rid: int) -> bool:
        now = time.perf_counter()
        if self.done or now < self.t0 + self.due_s[rid]:
            return True
        self.admitted[rid] = now
        return False

    def step_begin(self, engine, step: int) -> None:
        now = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self.t0 is None:
            self.t0 = now
            if not self.backlog:
                self._open(now)
        if self.w0 is None and self.backlog and self._filled(engine):
            self._open(now)
        if self.w0 is not None and self.w1 is None \
                and now >= self.w0 + self.seconds:
            self.w1 = now
        if self.w1 is not None and not self.done and (
                self.backlog or now >= self.w1 + self.drain_s
                or self._all_due_started(engine)):
            self.done = True
            for rid, rec in engine.results.items():
                if rec.state not in TERMINAL_STATES:
                    engine.cancel(rid)
        if not self.done and not self._live(engine):
            self._sleep_to_next(engine)
        self.steps.append((time.perf_counter(), len(engine.step_log)))
        if self.annotate:
            import jax

            self._ann = jax.profiler.TraceAnnotation(
                f"bench_step {len(engine.step_log)}")
            self._ann.__enter__()

    def finish(self) -> None:
        """After ``serve`` has returned: close the last step's annotation,
        and a window that the offered work ended early at its full length
        (the device idles to the end of it)."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self.w0 is not None and self.w1 is None:
            wait = self.w0 + self.seconds - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.w1 = self.w0 + self.seconds

    # -- helpers ----------------------------------------------------------

    def _open(self, now: float) -> None:
        self.w0 = now
        if self.on_open is not None:
            self.on_open(now)

    def _filled(self, engine) -> bool:
        """The backlog has filled the engine: every request of the first
        batch (one per slot) is past its prefill, or the pages that live
        requests reserve leave no room for the next in the queue."""
        res = engine.results
        if all(res[r].tokens or res[r].state in TERMINAL_STATES
               for r in self.order[:self.slots]):
            return True
        live = [r for r in self.admitted
                if res[r].state not in TERMINAL_STATES]
        head = next((r for r in self.order if r not in self.admitted), None)
        return bool(live) and head is not None and all(
            res[r].tokens for r in live) and sum(
                self.pages.get(r, 0) for r in live) + self.pages.get(
                    head, 0) > self.pool_pages
    def _live(self, engine) -> bool:
        return any(engine.results[r].state not in TERMINAL_STATES
                   for r in self.admitted)

    def _all_due_started(self, engine) -> bool:
        end = self.w1 - self.t0
        return all(engine.results[r].tokens
                   or engine.results[r].state in TERMINAL_STATES
                   for r, d in self.due_s.items() if d <= end)

    def _sleep_to_next(self, engine) -> None:
        """Nothing live: sleep until the first queued request is due, or
        the window closes, whichever comes first."""
        while self._next < len(self.order) and (
                self.order[self._next] in self.admitted
                or engine.results[self.order[self._next]].state
                in TERMINAL_STATES):
            self._next += 1
        if self._next == len(self.order):
            return
        due = self.t0 + self.due_s[self.order[self._next]]
        if self.w0 is not None and self.w1 is None:
            due = min(due, self.w0 + self.seconds)
        wait = due - time.perf_counter()
        if wait > 0:
            if self.annotate:
                import jax

                with jax.profiler.TraceAnnotation("bench_arrival_wait"):
                    time.sleep(wait)
            else:
                time.sleep(wait)
            self.late_s.append(max(0.0, time.perf_counter() - due))
