"""From the profiler's ``.xplane.pb`` to device busy/idle, per-executable
and per-operation time, exposed collectives and attributed idle gaps.

What a TPU trace of this repository holds (read by hand, PR 23): one
plane ``/device:TPU:<n>`` a chip, with the lines ``XLA Modules`` (one
event an executable run, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops``
(one event an HLO operation, named by its HLO text, control flow such as
``%while`` enclosing its body's operations) and ``Async XLA Ops``
(``copy-start`` .. ``-done`` spans, which overlap compute); and one plane
``/host:CPU`` with a line a thread, holding the runtime's events, the
Python tracer's and every ``jax.profiler.TraceAnnotation``.  All lines
share one clock, in nanoseconds from the start of the profile.

Busy time is the union of the ``XLA Ops`` intervals.  Nothing here reads
the program; ``tests/benchmark/test_trace_reduce.py`` checks the
arithmetic on a small synthetic trace kept beside it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]           # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


@dataclasses.dataclass
class Event:
    name: str
    start: float      # ns
    end: float        # ns
    line: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]        # device ordinal -> XLA Ops events
    modules: Dict[int, List[Event]]    # device ordinal -> XLA Modules
    host: List[Event]                  # every event of the host plane


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Optional[str] = None, *, text_proto: Optional[str] = None
         ) -> Trace:
    from jax.profiler import ProfileData

    data = (ProfileData.from_text_proto(text_proto) if text_proto is not None
            else ProfileData.from_file(path))
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                into = ops if line.name == "XLA Ops" else modules
                into.setdefault(int(m.group(1)), []).extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif plane.name == "/host:CPU":
                host.extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                          line.name) for e in line.events)
    for evs in list(ops.values()) + list(modules.values()):
        evs.sort(key=lambda e: (e.start, -e.end))
    return Trace(ops, modules, host)


# --- interval arithmetic ----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def hlo_name(event_name: str) -> str:
    """``%fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].strip()


def short(event_name: str, width: int = 96) -> str:
    """A name for the breakdown: the HLO name and the start of its shape,
    without characters a ledger line would have to escape."""
    return re.sub(r"\s+", " ", event_name)[:width]


# --- reductions -------------------------------------------------------------

def window_of(trace: Trace, module_like: Optional[str] = None,
              whole_periods: bool = False) -> Interval:
    """The traced window on the device clock.  By default from the first
    device operation to the last one's end.  With ``whole_periods``, from
    the start of the first run of the executables that match
    ``module_like`` to the start of the last: whole periods of a loop,
    each with the gap that follows its step."""
    if whole_periods:
        starts = sorted(e.start for evs in trace.modules.values() for e in evs
                        if module_like is None or module_like in e.name)
        if len(starts) < 2:
            raise ValueError("fewer than two runs of the step in the trace")
        return starts[0], starts[-1]
    evs = [e for v in trace.ops.values() for e in v]
    if not evs:
        raise ValueError("no operation ran on a device in this trace")
    return min(e.start for e in evs), max(e.end for e in evs)


def busy_seconds(trace: Trace, window: Interval) -> Dict[int, float]:
    """Seconds in which an operation ran, by device, inside ``window``."""
    lo, hi = window
    return {d: length(union(clip(((e.start, e.end) for e in evs), lo, hi)))
            / 1e9 for d, evs in trace.ops.items()}


def idle_share(trace: Trace, window: Interval) -> float:
    """1 - busy / window on the device that was busy least (worst), %."""
    span = (window[1] - window[0]) / 1e9
    return 100.0 * (1.0 - min(busy_seconds(trace, window).values()) / span)


def module_seconds(trace: Trace, window: Optional[Interval] = None,
                   device: Optional[int] = None) -> Dict[str, Tuple[int, float]]:
    """``{executable name without its fingerprint: (runs, seconds)}`` on
    one device (the lowest by default), for runs that start in ``window``."""
    device = min(trace.modules) if device is None else device
    out: Dict[str, Tuple[int, float]] = {}
    for e in trace.modules.get(device, []):
        if window and not (window[0] <= e.start < window[1]):
            continue
        key = re.sub(r"\(\d+\)$", "", e.name)
        n, s = out.get(key, (0, 0.0))
        out[key] = (n + 1, s + e.dur / 1e9)
    return out


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float, bool]]:
    """→ ``(event, self_ns, is_leaf)`` for events sorted by (start, -end):
    an event's own time is its duration less that of the events directly
    inside it (a ``%while`` encloses its body's operations)."""
    out, stack = [], []      # stack of indices into out
    for e in events:
        # an event lies inside the stack's top only if it ends there too;
        # one that merely overlaps it (an async collective's tail) does not
        while stack and (out[stack[-1]][0].end <= e.start
                         or e.end > out[stack[-1]][0].end):
            stack.pop()
        if stack:
            p = stack[-1]
            out[p] = (out[p][0], out[p][1] - e.dur, False)
        out.append((e, e.dur, True))
        stack.append(len(out) - 1)
    return out


def top_ops(trace: Trace, window: Interval, n: int = 10,
            device: Optional[int] = None) -> List[List]:
    """The ``n`` operations with most own time on one device, as
    ``[name, seconds]``, same-named runs summed."""
    device = min(trace.ops) if device is None else device
    total: Dict[str, float] = {}
    for e, own, _leaf in self_times(trace.ops[device]):
        if window[0] <= e.start < window[1] and own > 0:
            total[short(e.name)] = total.get(short(e.name), 0.0) + own / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def collective_exposed_share(trace: Trace, window: Interval) -> float:
    """Time in collectives while no compute operation runs on that
    device, over the window, on the worst device, %.  Compute is every
    leaf operation that is no collective."""
    lo, hi = window
    worst = 0.0
    for evs in trace.ops.values():
        leaves = [e for e, _own, leaf in self_times(evs) if leaf]
        coll = union(clip(((e.start, e.end) for e in leaves
                           if COLLECTIVE.search(hlo_name(e.name))), lo, hi))
        comp = union(clip(((e.start, e.end) for e in leaves
                           if not COLLECTIVE.search(hlo_name(e.name))),
                          lo, hi))
        worst = max(worst, length(subtract(coll, comp)))
    return 100.0 * worst / (hi - lo)


def _name_for(gap: Interval, pool: Sequence[Event]) -> Optional[str]:
    """What the host was doing in ``gap``: the event that overlaps it
    most among those no longer than twice the gap (the finest activity
    that explains it); failing that, the shortest event that covers it."""
    s, e = gap
    best, best_overlap, cover = None, 0.0, None
    for ev in pool:
        overlap = min(e, ev.end) - max(s, ev.start)
        if overlap <= 0:
            continue
        if ev.dur <= 2 * (e - s) and overlap > best_overlap:
            best, best_overlap = ev, overlap
        if ev.start <= s and ev.end >= e and (
                cover is None or ev.dur < cover.dur):
            cover = ev
    chosen = best or cover
    return None if chosen is None else short(chosen.name, 64)


def idle_gaps(trace: Trace, window: Interval, spans: Sequence[Event] = (),
              n: int = 10, device: Optional[int] = None) -> List[List]:
    """The longest gaps between device operations as ``[what the host was
    doing, seconds]``, gaps of one name summed, at most ``n`` names.  A
    gap is named from ``spans`` (the program's own spans, already on the
    trace's clock) where one fits, else from the Python tracer's events,
    else from the rest of the host plane."""
    device = min(trace.ops) if device is None else device
    lo, hi = window
    busy = union(clip(((e.start, e.end) for e in trace.ops[device]), lo, hi))
    gaps = subtract([(lo, hi)], busy)
    # the Python tracer's events say more than the runtime's internals
    python = [e for e in trace.host if e.line == "python"]
    total: Dict[str, float] = {}
    for gap in sorted(gaps, key=lambda g: g[0] - g[1])[:50]:
        name = (_name_for(gap, spans) or _name_for(gap, python)
                or _name_for(gap, trace.host) or "no host span")
        total[name] = total.get(name, 0.0) + (gap[1] - gap[0]) / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]
