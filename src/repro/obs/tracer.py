"""Structured span tracer: the time-resolved twin of ``TierStats``.

``TierStats``/``IOLedger`` answer *how much* (seconds stalled, bytes
moved); they cannot answer *when* — which superstep stalled, which shard's
queue backed up, why ``merge_stall_s`` was nonzero.  :class:`Tracer` records
that: begin/end spans, complete spans, instant events, and counter samples
into a bounded ring buffer, exported as Chrome/Perfetto ``trace_event``
JSON (:mod:`repro.obs.export`) and summarized by ``python -m repro.obs
report`` (:mod:`repro.obs.report`).

Design constraints (and how they are met):

* **Low overhead.**  One event is one tuple appended to a
  ``collections.deque(maxlen=capacity)`` — no dict building, no I/O, no
  locking on the hot path (CPython's deque append is atomic, which is all
  the single-producer-per-lane usage here needs).  When tracing is off the
  plumbing holds the :data:`NOOP` singleton, so instrumented code pays one
  attribute check (``tracer.enabled``) or one no-op method call — and a
  ``span()`` one profiler annotation (below).
* **Bounded memory.**  The ring drops the *oldest* events past
  ``capacity`` (``dropped`` counts them) — a week-long run cannot OOM on
  its own telemetry.
* **Monotonic clock.**  Timestamps are ``time.perf_counter()`` relative to
  a shared ``epoch``, immune to wall-clock steps.  Tracers that should
  share a timeline (the executor's per-shard tracers) are constructed with
  the same ``epoch`` so their events merge onto comparable timestamps.
  ``epoch_time_ns`` is the same instant as a ``time.time_ns()`` reading,
  which places the ring's events on a profiler trace's clock.
* **One name on both clocks.**  Every :meth:`Tracer.span` — on
  :data:`NOOP` too — also enters a ``jax.profiler.TraceAnnotation`` of
  its name, so a ``jax.profiler`` session records the span in its host
  plane beside the device's operations; without a session that is one
  flag check.  :meth:`complete`, :meth:`instant` and :meth:`counter` go
  to the ring only.
* **Exact agreement with the stats.**  :meth:`Tracer.complete` takes the
  *caller's* ``t0``/``t1`` perf_counter readings — the executor passes the
  very same values it adds into ``TierStats``, so a report derived from
  spans can never disagree with the counters.

Spans must stay **outside jitted code**: a span inside a traced function
fires once at trace time (the ``trace-purity`` invariant).  Inside jitted
code the names are ``jax.named_scope`` scopes instead, which the profiler
reports on the device's operations.
"""

from __future__ import annotations

import collections
import time
from typing import Optional

__all__ = ["Tracer", "NoopTracer", "NOOP"]


def _annotate(name: str, args: Optional[dict]):
    """An entered ``jax.profiler.TraceAnnotation`` named ``name``.  JAX is
    imported here, not with the module, so the report CLI stays
    stdlib-only."""
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name, **args) if args else TraceAnnotation(name)
    ann.__enter__()
    return ann


# Event tuples: (ph, name, tid, ts_s, dur_s, cat, args)
#   ph  — Chrome trace_event phase: "X" complete, "B"/"E" begin/end,
#         "i" instant, "C" counter
#   ts_s/dur_s — seconds since the tracer's epoch / span length
#   args — small dict of attributes (None when empty)


class _Span:
    """Context manager for one complete ("X") span, annotated on the
    profiler's clock too.  ``duration_s`` is available after exit —
    benchmarks time *through* the span so their numbers and the trace can
    never disagree."""

    __slots__ = ("_tracer", "name", "tid", "cat", "args", "t0", "t1", "_ann")

    def __init__(self, tracer: "Tracer", name: str, tid: str,
                 cat: Optional[str], args: Optional[dict]):
        self._tracer = tracer
        self.name = name
        self.tid = tid
        self.cat = cat
        self.args = args
        self.t0 = 0.0
        self.t1 = 0.0

    def __enter__(self) -> "_Span":
        self._ann = _annotate(self.name, self.args)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        self._tracer.complete(self.name, self.t0, self.t1, tid=self.tid,
                              cat=self.cat, **(self.args or {}))
        return False

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0


class Tracer:
    """Bounded ring-buffer span/event recorder (one per process lane)."""

    enabled = True

    def __init__(self, capacity: int = 1 << 16,
                 epoch: Optional[float] = None, name: str = "main"):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.name = name
        self.capacity = capacity
        now, now_ns = time.perf_counter(), time.time_ns()
        self.epoch = now if epoch is None else epoch
        self.epoch_time_ns = now_ns - round((now - self.epoch) * 1e9)
        self._events = collections.deque(maxlen=capacity)
        self.dropped = 0        # advisory: events evicted by the ring

    # ---------------------------------------------------------------- clock
    def now(self) -> float:
        """Raw ``time.perf_counter()`` — pair with :meth:`complete`."""
        return time.perf_counter()

    # --------------------------------------------------------------- events
    def _push(self, ev: tuple) -> None:
        if len(self._events) == self.capacity:
            self.dropped += 1   # advisory count; benign under races
        self._events.append(ev)

    def span(self, name: str, tid: str = "main", cat: Optional[str] = None,
             **args) -> _Span:
        """``with tracer.span("stage:merge", tid="stages"): ...`` — records
        one complete span from enter to exit, and annotates the same
        interval for the profiler."""
        return _Span(self, name, tid, cat, args or None)

    def complete(self, name: str, t0: float, t1: float, tid: str = "main",
                 cat: Optional[str] = None, **args) -> None:
        """Record an already-timed region: ``t0``/``t1`` are the caller's
        ``time.perf_counter()`` readings (the same values it billed into
        its stats counters)."""
        self._push(("X", name, tid, t0 - self.epoch, t1 - t0, cat,
                    args or None))

    def begin(self, name: str, tid: str = "main",
              cat: Optional[str] = None, **args) -> None:
        """Open a nested span; close it with :meth:`end` on the same lane.
        For spans confined to one scope prefer :meth:`span` — the
        ``trace-balance`` lint rule flags a ``begin`` without a matching
        ``end`` in the same scope."""
        self._push(("B", name, tid, time.perf_counter() - self.epoch,
                    None, cat, args or None))

    def end(self, name: str, tid: str = "main") -> None:
        self._push(("E", name, tid, time.perf_counter() - self.epoch,
                    None, None, None))

    def instant(self, name: str, tid: str = "events",
                cat: Optional[str] = None, **args) -> None:
        """Zero-duration marker (fault injections, sanitizer findings,
        drain timeouts)."""
        self._push(("i", name, tid, time.perf_counter() - self.epoch,
                    None, cat, args or None))

    def counter(self, name: str, value, tid: str = "counters") -> None:
        """One sample of a counter track (e.g. engine queue depth)."""
        self._push(("C", name, tid, time.perf_counter() - self.epoch,
                    None, None, {"value": value}))

    # ------------------------------------------------------------ inspection
    def events(self) -> list:
        """Snapshot of the ring's event tuples, oldest first."""
        return list(self._events)

    def __len__(self) -> int:
        return len(self._events)

    def clear(self) -> None:
        self._events.clear()
        self.dropped = 0


class _NoopSpan:
    """A disabled ``span()``: records nothing in a ring, and annotates the
    profiler only (a ``jax.profiler`` session decides whether that is
    kept)."""

    __slots__ = ("name", "args", "_ann")
    t0 = 0.0
    t1 = 0.0
    duration_s = 0.0

    def __init__(self, name: str, args: Optional[dict]):
        self.name = name
        self.args = args

    def __enter__(self) -> "_NoopSpan":
        self._ann = _annotate(self.name, self.args)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        return False


class NoopTracer:
    """Disabled tracer: every method is a no-op but :meth:`span`, which
    annotates the profiler only; ``enabled`` is False so hot paths can skip
    even argument construction.  Use the shared :data:`NOOP` singleton."""

    enabled = False
    name = "noop"
    epoch = 0.0
    epoch_time_ns = 0
    capacity = 0
    dropped = 0

    def now(self) -> float:
        return time.perf_counter()

    def span(self, name: str, tid: str = "main", cat=None, **args):
        return _NoopSpan(name, args or None)

    def complete(self, name, t0, t1, tid="main", cat=None, **args) -> None:
        pass

    def begin(self, name, tid="main", cat=None, **args) -> None:
        pass

    def end(self, name, tid="main") -> None:
        pass

    def instant(self, name, tid="events", cat=None, **args) -> None:
        pass

    def counter(self, name, value, tid="counters") -> None:
        pass

    def events(self) -> list:
        return []

    def __len__(self) -> int:
        return 0

    def clear(self) -> None:
        pass


NOOP = NoopTracer()
