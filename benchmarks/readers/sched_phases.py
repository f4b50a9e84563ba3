"""Reads the scheduler thread's iteration by phase: the spans the serving
engine records on its own track from clock readings it takes anyway
(``serving/engine.py:SCHED_PHASES``, name -> ``"own"`` — the host's own
work — or ``"blocked"`` — the host waited for the device).  The program
keeps that table, this reader none: from a program without it, None.

``stat``:

* ``own_ms_per`` — for every span named ``within`` that began in the
  measured window, the summed durations of the own-kind phases named in
  ``phases`` that began inside it, divided by the count of the spans
  named ``per`` inside it where ``per`` is given (a ``within`` that holds
  none is left out); the median over those, ms.  A collection (``gc``)
  that ran under a phase is part of that phase's duration already.
* ``blocked_share`` — summed durations of the blocked-kind phases that
  began in the window over the window's seconds, %.
* ``idle_own_share`` — over the traced window on the device's clock:
  the idle intervals of the device that was busy least (``xplane``'s
  ``idle_share``), less what falls under a blocked phase, over the
  window, %: the device idle while the scheduler was doing its own work,
  a collection ran, or no phase was open.  None without a device plane.

Once a run, with the first of its metrics read, the reader prints the
window's host time by phase and, where there is a device plane, one
table of the traced window's idle time cut by the phases: phase, kind,
idle seconds under it, share of the window, number of gaps, the three
longest gaps with their offsets into the window.  Each idle instant is
put under one name, in this order: a blocked phase, ``gc``, an own
phase, then what of an ``admit`` or an ``engine_step`` no phase covers
(the loop's own overhead), then ``(none)``.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import stats, trace_reduce
from benchmarks.common import say

Interval = Tuple[float, float]
ENCLOSING = ("admit", "engine_step")      # the spans the phases divide


def program_phases() -> Optional[Dict[str, str]]:
    """The program's own table of its phases, or None where it has none."""
    try:
        from megatron_llm_tpu.serving.engine import SCHED_PHASES
    except ImportError:        # a program from before it recorded them
        return None
    return dict(SCHED_PHASES)


# --- the measured window: (name, start, seconds) on the host's clock --------

def own_per(spans, kinds: Dict[str, str], within: str,
            phases: Sequence[str], per: Optional[str] = None) -> List[float]:
    """→ for each ``within`` span, the seconds of the own-kind ``phases``
    that began inside it, over the ``per`` spans inside it if given."""
    wanted = {p for p in phases if kinds.get(p) == "own"}
    inner = sorted((t0, d, n) for n, t0, d in spans
                   if n in wanted or n == per)
    starts = [t0 for t0, _d, _n in inner]
    out = []
    for name, t0, d in spans:
        if name != within:
            continue
        lo = bisect.bisect_left(starts, t0)
        hi = bisect.bisect_left(starts, t0 + d)
        held = inner[lo:hi]
        own = sum(dd for _t, dd, n in held if n in wanted)
        if per is None:
            out.append(own)
            continue
        count = sum(1 for _t, _d, n in held if n == per)
        if count:
            out.append(own / count)
    return out


def host_table(spans, kinds: Dict[str, str], seconds: float) -> str:
    """The window's host time by phase: count, seconds, median ms, share
    of the window."""
    by: Dict[str, List[float]] = {}
    for n, _t0, d in spans:
        if n in kinds:
            by.setdefault(n, []).append(d)
    rows = sorted(by.items(), key=lambda kv: -sum(kv[1]))
    return ("the scheduler's time by phase over the window's "
            f"{seconds:.1f} s (phase kind: spans, seconds, median ms, % of "
            "the window): " + "; ".join(
                f"{n} {kinds[n]}: {len(ds)}, {sum(ds):.3f}, "
                f"{1e3 * stats.median(ds):.3f}, "
                f"{100.0 * sum(ds) / seconds:.2f}" for n, ds in rows))


# --- the traced window: intervals in ns on the trace's clock ----------------

def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """The part of merged ``a`` that merged ``b`` covers."""
    return trace_reduce.subtract(a, trace_reduce.subtract(a, b))


def idle_intervals(trace, window: Interval) -> List[Interval]:
    """The idle intervals of the device that was busy least in ``window``:
    those ``trace_reduce.idle_share`` counts."""
    lo, hi = window
    busy = trace_reduce.busy_seconds(trace, window)
    least = min(busy, key=busy.get)
    return trace_reduce.subtract([(lo, hi)], trace_reduce.union(
        trace_reduce.clip(((e.start, e.end) for e in trace.ops[least]),
                          lo, hi)))


def idle_by_phase(idle: Sequence[Interval], host_spans,
                  kinds: Dict[str, str]) -> List[Tuple[str, List[Interval]]]:
    """``idle`` cut by the phases among ``host_spans`` → ``(name, the
    idle pieces under it)``, every instant under one name, in the order
    the module docstring gives; names with nothing under them left out."""
    by: Dict[str, List[Interval]] = {}
    for e in host_spans:
        if e.name in kinds or e.name in ENCLOSING:
            by.setdefault(e.name, []).append((e.start, e.end))
    order = ([n for n in kinds if kinds[n] == "blocked"] + ["gc"]
             + [n for n in kinds if kinds[n] == "own" and n != "gc"]
             + list(ENCLOSING))
    out, rest = [], list(idle)
    for name in order:
        under = intersect(rest, trace_reduce.union(by.get(name, ())))
        if under:
            out.append((name, under))
            rest = trace_reduce.subtract(rest, under)
    if rest:
        out.append(("(none)", rest))
    return out


def idle_table(rows, kinds: Dict[str, str], window: Interval) -> str:
    lo, hi = window
    span = hi - lo

    def label(name):
        if name in kinds:
            return f"{name} {kinds[name]}"
        return f"{name} outside its phases" if name in ENCLOSING else name

    def longest(pieces):
        top = sorted(pieces, key=lambda p: p[0] - p[1])[:3]
        return ", ".join(f"{(e - s) / 1e6:.2f} ms at {(s - lo) / 1e9:.3f} s"
                         for s, e in top)

    total = sum(trace_reduce.length(p) for _n, p in rows)
    return ("the device's idle time by what the scheduler was doing, "
            f"{total / 1e9:.4f} s of the traced {span / 1e9:.3f} s (phase "
            "kind: idle seconds, % of the window, gaps, the longest): "
            + "; ".join(
                f"{label(n)}: {trace_reduce.length(p) / 1e9:.4f}, "
                f"{100.0 * trace_reduce.length(p) / span:.3f}, {len(p)}, "
                f"{longest(p)}" for n, p in sorted(
                    rows, key=lambda r: -trace_reduce.length(r[1]))))


def idle_rows(evidence: dict, kinds: Dict[str, str]):
    """``idle_by_phase`` of the evidence's traced window — None without a
    device plane or without a phase among its spans — cut once a run,
    when both tables are printed."""
    if "sched_idle_rows" in evidence:
        return evidence["sched_idle_rows"]
    spans = evidence.get("recorder_spans", ())
    if any(n in kinds for n, _t0, _d in spans):
        say(host_table(spans, kinds, evidence["ctx"].seconds))
    trace, window = evidence.get("trace"), evidence.get("trace_window")
    traced = evidence.get("host_spans", ())
    rows = None
    if (trace is not None and trace.ops and window is not None
            and any(e.name in kinds for e in traced)):
        rows = idle_by_phase(idle_intervals(trace, window), traced, kinds)
        say(idle_table(rows, kinds, window))
    evidence["sched_idle_rows"] = rows
    return rows


def read(evidence: dict, params: dict):
    kinds = program_phases()
    if kinds is None:
        return None
    spans = evidence.get("recorder_spans", ())
    rows = idle_rows(evidence, kinds)
    stat = params["stat"]
    if stat == "own_ms_per":
        values = own_per(spans, kinds, params["within"], params["phases"],
                         params.get("per"))
        if not values:
            return None
        return 1e3 * stats.median(values)
    if stat == "blocked_share":
        blocked = [d for n, _t0, d in spans if kinds.get(n) == "blocked"]
        if not blocked:
            return None
        return 100.0 * sum(blocked) / evidence["ctx"].seconds
    if stat == "idle_own_share":
        if rows is None:
            return None
        lo, hi = evidence["trace_window"]
        own = sum(trace_reduce.length(p) for n, p in rows
                  if kinds.get(n) != "blocked")
        return 100.0 * own / (hi - lo)
    raise ValueError(f"sched_phases reader: unknown stat {stat!r}")
