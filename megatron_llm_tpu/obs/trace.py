"""Per-request span tracing with Chrome trace-event export.

A ``TraceRecorder`` is a lock-guarded bounded ring buffer of completed
spans.  The serving engine records one span per request phase (queued,
prefix_match, prefill / prefill_chunk[i], decode, retire) and one span
per scheduler iteration (engine_step, carrying batch size and
paged/fallback routing as args), so a single stalled chunked-prefill
admission that aggregate p50s hide shows up as an obvious gap on the
timeline.

Export is Chrome trace-event JSON (``chrome://tracing`` / Perfetto's
legacy loader): complete events (``ph="X"``) with microsecond timestamps
relative to the recorder's creation, ``tid`` = request id so each
request gets its own track, and ``args.request_id`` for correlation
with the structured event log.

Joining a device profile: ``otherData`` carries ``epoch_perf_counter``
(``ts`` 0 on the ``perf_counter`` clock) and, once a profile session
(``obs/profile.py``) has run, its ``clock_sync`` pair, so every span —
retrospective ``add()`` ones included — maps onto the profile's clock
with ``profile.to_trace_ns``.  ``span(..., annotate=True)`` and
``device_annotation`` also write the span into the profile itself as a
``jax.profiler.TraceAnnotation``; ``add()`` spans are not mirrored.

Compilations: ``chrome_trace()`` also carries the process's compilation
records (``obs/compile.py``) that overlap what the recorder retains —
from its oldest span's start to its newest one's end — as ``compile``
complete events on a track of their own, each with its ``cause``: the
innermost retained span that contains it (for the engine a ``prefill``
with its request id and padded width, or the ``engine_step``; for the
train loop the ``train-step`` or ``setup``; never one of the recorder's
``phases``, the spans that only divide another).  Found at export, so the
paths that record spans pay nothing; a compilation that ran while the
recorder held nothing around it (``TRAIN_TRACE`` switched off) has no
place on its timeline and is left out.

Collections: a ``GcWatch`` put on ``gc.callbacks`` records every Python
collection of a millisecond or more as a ``gc`` span on a track of its
own (``GC_TID``), whatever thread ran it: a collection holds the
interpreter lock, so every thread of the process stands still under it.

``TRAIN_TRACE`` is the train loop's recorder (``utils/timers.py`` feeds
it from the loop's timers).  It is off unless a profile session or a
reader switches it on.

Overhead discipline: when ``enabled`` is False every record path returns
before taking the lock or allocating, and the recorder stores compact
tuples — dict construction is deferred to export time.
"""

from __future__ import annotations

import contextlib
import gc
import os
import time
from collections import deque

from typing import Dict, Iterator, List, Optional

from ..analysis.sanitizers import make_lock
from . import compile as compiles
from . import profile

#: ``tid`` of the ``compile`` events' track (request ids count up from 0)
COMPILE_TID = 1 << 30
#: ``tid`` of the ``gc`` spans' track
GC_TID = COMPILE_TID + 1
#: a collection shorter than this leaves no span (seconds)
GC_MIN_S = 1e-3

_PROFILER_SENTINEL = object()
_profiler = _PROFILER_SENTINEL  # lazily resolved jax.profiler module (or None)


def device_annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``, or a no-op context manager.

    Lazy so importing obs never forces JAX backend initialization; the
    annotation itself is a no-op unless a device profile is being taken.
    """
    global _profiler
    if _profiler is _PROFILER_SENTINEL:
        try:
            from jax import profiler as _p  # noqa: PLC0415
            _profiler = _p
        except Exception:
            _profiler = None
    if _profiler is None:
        return contextlib.nullcontext()
    try:
        return _profiler.TraceAnnotation(name)
    except Exception:
        return contextlib.nullcontext()


class TraceRecorder:
    """Bounded ring buffer of completed spans; Chrome-trace JSON export."""

    def __init__(self, capacity: int = 8192, enabled: bool = True,
                 phases=()):
        self.enabled = enabled
        self.capacity = capacity
        # names of spans that subdivide another span of this recorder: a
        # compilation's cause is the span they divide, never one of them
        self._phases = frozenset(phases)
        self._lock = make_lock("obs.trace")
        # (name, ph, t0, dur, tid, request_id, args) — compact on the hot
        # path; the ring drops the oldest spans once capacity is reached.
        self._events: deque = deque(maxlen=capacity)
        self._dropped = 0
        self._epoch = time.perf_counter()
        self._pid = os.getpid()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def add(self, name: str, t0: float, t1: float, *,
            request_id: Optional[str] = None, tid: int = 0,
            args: Optional[Dict] = None) -> None:
        """Record a completed span; ``t0``/``t1`` are perf_counter times."""
        if not self.enabled:
            return
        with self._lock:
            if len(self._events) == self.capacity:
                self._dropped += 1
            self._events.append((name, "X", t0, max(0.0, t1 - t0), tid,
                                 request_id, args))

    def add_unlocked(self, name: str, t0: float, t1: float, *, tid: int = 0,
                     args: Optional[Dict] = None) -> None:
        """``add`` for a caller that may already hold this recorder's
        lock: a ``gc`` callback runs in whatever thread allocated last —
        inside ``add`` itself among other places — and would wait for a
        lock its own thread holds.  The append alone, which the
        interpreter lock makes atomic; the eviction it may cause is not
        counted in ``dropped``."""
        if self.enabled:
            self._events.append((name, "X", t0, max(0.0, t1 - t0), tid,
                                 None, args))

    def instant(self, name: str, *, request_id: Optional[str] = None,
                tid: int = 0, args: Optional[Dict] = None) -> None:
        """Record a zero-duration marker event (``ph="i"``)."""
        if not self.enabled:
            return
        with self._lock:
            if len(self._events) == self.capacity:
                self._dropped += 1
            self._events.append((name, "i", time.perf_counter(), 0.0, tid,
                                 request_id, args))

    @contextlib.contextmanager
    def span(self, name: str, *, request_id: Optional[str] = None,
             tid: int = 0, annotate: bool = False,
             args: Optional[Dict] = None) -> Iterator[None]:
        """Time a block; optionally mirror it as a device TraceAnnotation."""
        if not self.enabled:
            if annotate:
                with device_annotation(name):
                    yield
            else:
                yield
            return
        ctx = device_annotation(name) if annotate else contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            self.add(name, t0, time.perf_counter(),
                     request_id=request_id, tid=tid, args=args)

    def chrome_trace(self) -> Dict:
        """The retained spans as a Chrome trace-event JSON object."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        out: List[Dict] = []
        tids = set()
        for name, ph, t0, dur, tid, request_id, args in events:
            tids.add(tid)
            ev: Dict = {
                "name": name,
                "ph": ph,
                "ts": round((t0 - self._epoch) * 1e6, 3),
                "pid": self._pid,
                "tid": tid,
            }
            if ph == "X":
                ev["dur"] = round(dur * 1e6, 3)
            if ph == "i":
                ev["s"] = "t"  # instant scope: thread
            ev_args = dict(args) if args else {}
            if request_id is not None:
                ev_args["request_id"] = request_id
            if ev_args:
                ev["args"] = ev_args
            out.append(ev)
        out.extend(self._compile_events(events))
        if GC_TID in tids:
            out.append({"name": "thread_name", "ph": "M", "pid": self._pid,
                        "tid": GC_TID, "args": {"name": "gc"}})
        other = {"dropped_events": dropped,
                 "epoch_perf_counter": self._epoch}
        session = profile.last()
        if session is not None:
            other["clock_sync"] = session.clock_sync()
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": other}


    def _compile_events(self, events) -> List[Dict]:
        """The compilation records that overlap ``events``' extent, as
        complete events with their cause (see the module docstring)."""
        spans = [e for e in events
                 if e[1] == "X" and e[0] not in self._phases]
        if not spans:
            return []
        lo = min(e[2] for e in spans)
        hi = max(e[2] + e[3] for e in spans)
        out: List[Dict] = []
        for rec in compiles.COMPILES.records():
            c0, c1 = rec["t0"], rec["t1"]
            if c1 < lo or c0 > hi:
                continue
            cause = None
            for e in spans:
                if e[2] <= c0 and c1 <= e[2] + e[3] \
                        and (cause is None or e[3] < cause[3]):
                    cause = e
            args: Dict = {"program": rec["program"],
                          "stage_s": compiles.stage_seconds(rec),
                          "cache": rec["cache"]}
            if cause is not None:
                args["cause"] = {"span": cause[0], **(cause[6] or {})}
                if cause[5] is not None:
                    args["cause"]["request_id"] = cause[5]
            out.append({"name": "compile", "ph": "X",
                        "ts": round((c0 - self._epoch) * 1e6, 3),
                        "dur": round((c1 - c0) * 1e6, 3), "pid": self._pid,
                        "tid": COMPILE_TID, "args": args})
        if out:
            out.append({"name": "thread_name", "ph": "M", "pid": self._pid,
                        "tid": COMPILE_TID, "args": {"name": "compile"}})
        return out


class GcWatch:
    """A ``gc.callbacks`` entry that records the collections of
    ``GC_MIN_S`` or more into ``recorder`` as ``gc`` spans (``args``:
    ``generation``, ``collected``).  The interpreter calls it with
    ``"start"`` and ``"stop"`` around every collection, in the thread
    that triggered it and with the interpreter lock held, so one pending
    start is all the state there is — and that thread may be inside the
    recorder's own ``add`` (the ring's tuple is an allocation), holding
    its lock: the span goes in by ``add_unlocked``.  ``install`` /
    ``remove`` put it on and take it off the list; whoever owns the
    recorder calls both."""

    def __init__(self, recorder: TraceRecorder, clock=time.perf_counter):
        self.recorder = recorder
        self._clock = clock
        self._t0: Optional[float] = None

    def __call__(self, phase: str, info: Dict) -> None:
        now = self._clock()
        if phase == "start":
            self._t0 = now
        elif self._t0 is not None:
            t0, self._t0 = self._t0, None
            if now - t0 >= GC_MIN_S:
                self.recorder.add_unlocked(
                    "gc", t0, now, tid=GC_TID,
                    args={"generation": info["generation"],
                          "collected": info["collected"]})

    def install(self) -> None:
        if self not in gc.callbacks:
            gc.callbacks.append(self)

    def remove(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)


# the train loop's spans (driver.pretrain's timers); see the module docstring
TRAIN_TRACE = TraceRecorder(enabled=False)
profile.while_profiling(TRAIN_TRACE)
