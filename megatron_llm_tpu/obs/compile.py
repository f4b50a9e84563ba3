"""Every compilation of the process, as records — its one ``jax.monitoring``
listener.

jax reports each stage of building an executable to its listeners when
the stage ends, on the thread that ran it, with the seconds it took and
the program's name: ``trace`` (Python tracing to a jaxpr), ``lower``
(jaxpr to an MLIR module) and ``backend`` (XLA's compile, or the read
from the persistent cache that takes its place: under jax 0.9.0 a cache
hit is still a ``backend`` stage, and the hit shows as a
``/jax/compilation_cache/cache_hits`` event inside it).  ``CompileLog``
folds the stages of one executable into one record, by thread and order,
and keeps the records in a bounded ring:

    ``program``  the stage's ``fun_name`` (the last stage's: ``jit(step)``)
    ``t0, t1``   on ``time.perf_counter``, taken in the callback
                 (``t0 = t1 - seconds`` of the first stage): the clock of
                 ``TraceRecorder`` spans and of ``profile.to_trace_ns``
    ``trace_s, lower_s, backend_s``  a stage's own seconds — what ran
                 inside it and is a record itself is taken off — or None
                 for a stage that never came (an AOT ``lower()``, a
                 function traced for ``eval_shape``)
    ``cache``    ``hit`` | ``miss`` | ``off``, from the cache events seen
                 on the thread since the backend stage began; None
                 without a backend stage.  ``retrieval_s`` on a hit
    ``thread``   the compiling thread's ident

A jitted function traced as a call inside another's trace reports a
``trace`` stage of its own, inside the outer one's: it is part of the
outer program and is absorbed, not kept (``absorbed`` counts them).

Three views read the records and nothing else stamps a compilation:
``TraceRecorder.chrome_trace()`` merges them as ``compile`` spans with
their cause, ``install()`` registers the counters
``compilations_total{program, cache}`` and ``compile_seconds_total
{program, stage}`` with the metrics registry, and every executable is one
``EVENT_LOG`` line (``component="obs"``, ``event="compile"``).
``analysis/sanitizers.py`` (the recompilation guard, the watchdog's
compile clock) reads ``COMPILES`` too.

Nothing here imports JAX until ``install()``; the lock is ``threading``'s
own (``analysis.sanitizers`` reads this module).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from .logging import EVENT_LOG
from .registry import REGISTRY, MetricFamily

STAGES = ("trace", "lower", "backend")
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_OUTCOME = {"/jax/compilation_cache/cache_hits": "hit",
                  "/jax/compilation_cache/cache_misses": "miss"}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

# jax times a stage on time.time() and the callback reads perf_counter a
# moment after the stage's end, so a start is known to some microseconds:
# containment is judged with this slack
_EPS = 2e-5
# stages a thread may hold unfolded: the calls traced inside one outer
# function wait here until its own trace stage ends and absorbs them
_MAX_OPEN = 4096

# a record, and an open one's first five fields (a list while it is open)
_FIELDS = ("seq", "program", "t0", "t1", "trace_s", "lower_s", "backend_s",
           "cache", "retrieval_s", "thread")
_PROGRAM, _T0, _T1, _LOWER = 0, 1, 2, 4


class CompileLog:
    """Bounded ring of compilation records, fed by jax's monitoring
    events (``on_event``, ``on_duration``: the listener's two halves)."""

    def __init__(self, capacity: int = 1024,
                 clock: Callable[[], float] = time.perf_counter,
                 sink: Optional[Callable[[Dict], None]] = None):
        self.capacity = capacity
        self._clock = clock
        self._sink = sink            # called with every finished executable
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)
        self.dropped = 0             # records the ring has let go
        self.seq = 0                 # records finished so far
        self.executables = 0         # of them, those with a backend stage
        self.callbacks = 0           # stage events handled
        self.absorbed = 0            # trace stages that were part of another
        # by thread ident: the stages not yet folded into a finished
        # record, the finished records' intervals (for a stage's own
        # seconds), the last cache event, the last backend stage's end
        self._open: Dict[int, List[list]] = {}
        self._done: Dict[int, deque] = {}
        self._cache: Dict[int, list] = {}
        self._backend_end: Dict[int, float] = {}
        self._counts: Dict[Tuple[str, str], int] = {}
        self._seconds: Dict[Tuple[str, str], float] = {}

    # -- the listener --------------------------------------------------------

    def on_event(self, event: str, **_kw) -> None:
        outcome = _CACHE_OUTCOME.get(event)
        if outcome is not None:
            with self._lock:
                self._cache[threading.get_ident()] = [
                    outcome, None, self._clock()]

    def on_duration(self, event: str, seconds: float, fun_name: str = "",
                    **_kw) -> None:
        stage = _STAGE_OF.get(event)
        ident = threading.get_ident()
        if stage is None:
            if event == _RETRIEVAL_EVENT:
                with self._lock:
                    seen = self._cache.get(ident)
                    if seen is not None and seen[0] == "hit":
                        seen[1] = seconds
            return
        t1 = self._clock()
        t0 = t1 - seconds
        finished = None
        with self._lock:
            self.callbacks += 1
            own = self._own_seconds(ident, t0, seconds)
            held = self._open.setdefault(ident, [])
            # what began inside this stage was part of it: the jitted
            # functions an outer one calls while it is traced or lowered
            while held and held[-1][_T0] >= t0 - _EPS:
                held.pop()
                self.absorbed += 1
            last = held[-1] if held else None
            if stage == "trace":
                held.append([fun_name, t0, t1, own, None])
                if len(held) > _MAX_OPEN:
                    self._finish(ident, held.pop(0), in_order=False)
            elif stage == "lower":
                # its trace stage is the thread's newest, by order; the
                # name guards against one whose own trace was cached
                if last is not None and last[_LOWER] is None \
                        and last[_PROGRAM] in fun_name:
                    last[_PROGRAM], last[_T1], last[_LOWER] = \
                        fun_name, t1, own
                else:
                    held.append([fun_name, t0, t1, None, own])
            else:
                if last is not None and last[_LOWER] is not None \
                        and last[_PROGRAM] == fun_name:
                    rec = held.pop()
                    rec[_T1] = t1
                else:
                    rec = [fun_name, t0, t1, None, None]
                seen = self._cache.pop(ident, None)
                if seen is None or seen[2] < t0 - _EPS:
                    seen = ["off", None]
                self._backend_end[ident] = t1
                finished = self._finish(ident, rec, own, seen[0], seen[1])
        if finished is not None and self._sink is not None:
            self._sink(finished)

    def _own_seconds(self, ident: int, t0: float, seconds: float) -> float:
        """``seconds`` less the finished records that ran inside
        [t0, now] on this thread (their whole intervals)."""
        inner = 0.0
        for d0, d1 in reversed(self._done.get(ident, ())):
            if d0 < t0 - _EPS:
                break
            inner += d1 - d0
        return max(0.0, seconds - inner)

    def _finish(self, ident: int, rec: list, backend_s=None, cache=None,
                retrieval_s=None, in_order: bool = True) -> Dict:
        """An open record → the ring and the totals (lock held).  One
        let go long after its time (``in_order`` False) is no part of a
        stage still running."""
        program, t0, t1, trace_s, lower_s = rec
        if in_order:
            # the thread's finished intervals stay disjoint: this one
            # takes the place of those inside it
            done = self._done.setdefault(ident, deque(maxlen=64))
            while done and done[-1][0] >= t0 - _EPS:
                done.pop()
            done.append((t0, t1))
        self.seq += 1
        if len(self._ring) == self.capacity:
            self.dropped += 1
        row = (self.seq, program, t0, t1, trace_s, lower_s, backend_s, cache,
               retrieval_s, ident)
        self._ring.append(row)
        for name, s in zip(STAGES, (trace_s, lower_s, backend_s)):
            if s is not None:
                key = (program, name)
                self._seconds[key] = self._seconds.get(key, 0.0) + s
        if backend_s is not None:
            self.executables += 1
            key = (program, cache)
            self._counts[key] = self._counts.get(key, 0) + 1
        return dict(zip(_FIELDS, row))

    # -- what the views read -------------------------------------------------

    def records(self) -> List[Dict]:
        """The retained records, oldest first, as dicts; then the stages
        still held open (``seq`` None), with the stages they have."""
        with self._lock:
            rows = list(self._ring)
            held = [(None, *rec, None, None, None, ident)
                    for ident, recs in self._open.items() for rec in recs]
        return [dict(zip(_FIELDS, row)) for row in rows + held]

    def executables_since(self, seq: int) -> Tuple[int, Dict[str, int]]:
        """(the newest ``seq``, executables finished after ``seq`` by
        program) — as far back as the ring reaches."""
        with self._lock:
            out: Dict[str, int] = {}
            for row in reversed(self._ring):
                if row[0] <= seq:
                    break
                if row[6] is not None:
                    out[row[1]] = out.get(row[1], 0) + 1
            return self.seq, out

    def last_backend_end(self, thread_ident: Optional[int] = None) -> float:
        """perf_counter of the newest backend stage's end — on one thread
        or on any; 0.0 if there was none."""
        with self._lock:
            if thread_ident is not None:
                return self._backend_end.get(thread_ident, 0.0)
            return max(self._backend_end.values(), default=0.0)

    def families(self) -> List[MetricFamily]:
        """The scrape-time collector: cumulative, so they outlive the
        ring."""
        with self._lock:
            counts, seconds = dict(self._counts), dict(self._seconds)
        n = MetricFamily(
            "compilations_total", "counter",
            "executables built (cache miss or off) or read from the "
            "persistent cache (hit), by program")
        for (program, cache), v in sorted(counts.items()):
            n.add(v, labels={"program": program, "cache": cache})
        s = MetricFamily(
            "compile_seconds_total", "counter",
            "seconds in jax's trace, lower and backend stages, by program")
        for (program, stage), v in sorted(seconds.items()):
            s.add(v, labels={"program": program, "stage": stage})
        return [n, s]


def stage_seconds(rec: Dict) -> Dict[str, float]:
    """A record's stages as ``{stage: seconds}``, absent ones left out."""
    return {name: rec[f"{name}_s"] for name in STAGES
            if rec[f"{name}_s"] is not None}


def _log_executable(rec: Dict) -> None:
    EVENT_LOG.emit(
        "obs", "compile", program=rec["program"], cache=rec["cache"],
        stage_s={k: round(v, 6) for k, v in stage_seconds(rec).items()},
        **({} if rec["retrieval_s"] is None
           else {"retrieval_s": round(rec["retrieval_s"], 6)}))


#: The process's compilations; fed once ``install()`` has run.
COMPILES = CompileLog(sink=_log_executable)

_install_lock = threading.Lock()
_installed = False


def install() -> CompileLog:
    """Hand ``COMPILES`` to jax's monitoring (once) and its counters to
    the metrics registry; every entry point and every reader calls it."""
    global _installed
    with _install_lock:
        if not _installed:
            from jax import monitoring

            monitoring.register_event_listener(COMPILES.on_event)
            monitoring.register_event_duration_secs_listener(
                COMPILES.on_duration)
            _installed = True
    REGISTRY.register_collector("compile", COMPILES.families)
    return COMPILES
