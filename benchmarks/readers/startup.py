"""Reads what the program recorded of its own start: the compilation
records of ``megatron_llm_tpu/obs/compile.py`` (one an executable: the
program's name, the seconds of jax's ``trace``, ``lower`` and ``backend``
stages, whether the persistent cache had it) and, for a training job, the
train loop's ``setup`` span (``obs/trace.py:TRAIN_TRACE``, which the
``train`` kind leaves filled).

The records taken are those that ended before the traced phase began
(``obs.profile.last().t_sync``; all of them where no session ran): the
start's, since the measured window compiles nothing or the run is not
``correct``.  ``stat``:

``backend_s``      summed ``backend`` seconds: fresh XLA compiles, or
                   reads from the persistent cache
``trace_lower_s``  summed ``trace`` + ``lower`` seconds: what a start with
                   a warm cache pays again
``cache_misses``   executables the cache did not have (``miss``, or ``off``
                   where it was not asked): 0 says the start was warm
``executables``    records with a ``backend`` stage: what a start builds
                   or loads
``state_init_s``   the ``setup`` span's own time: its duration less the
                   compilation records inside it

The first call of a run also prints the start's ten costliest programs
and the longest stretches of the start that no compilation record covers,
each with the programs before and after it: what imports, weights, the
warm-up's execution and the check cost is timed by nothing else inside
the program.  None where the program keeps no such records (a parent without
``obs/compile.py``)."""

from __future__ import annotations

from benchmarks.common import recorder_spans, say

STAGES = ("trace", "lower", "backend")


def summarize(records) -> dict:
    """The four sums over ``records`` (dicts as ``CompileLog.records()``
    gives them)."""
    built = [r for r in records if r["backend_s"] is not None]
    return {
        "backend_s": sum(r["backend_s"] for r in built),
        "trace_lower_s": sum((r["trace_s"] or 0.0) + (r["lower_s"] or 0.0)
                             for r in records),
        "cache_misses": sum(r["cache"] != "hit" for r in built),
        "executables": len(built)}


def own_time(span, records) -> float:
    """``span`` = (t0, t1) less the parts of it that ``records`` cover."""
    lo, hi = span
    covered = sum(max(0.0, min(hi, r["t1"]) - max(lo, r["t0"]))
                  for r in records)
    return max(0.0, (hi - lo) - covered)


def table(records, top: int = 10) -> str:
    """The costliest programs: seconds a stage and the cache's answer,
    executables of one name summed."""
    by_program: dict = {}
    for r in records:
        row = by_program.setdefault(
            r["program"], {"n": 0, "trace": 0.0, "lower": 0.0,
                           "backend": 0.0, "cache": {}})
        row["n"] += 1
        for s in STAGES:
            row[s] += r[f"{s}_s"] or 0.0
        if r["cache"] is not None:
            row["cache"][r["cache"]] = row["cache"].get(r["cache"], 0) + 1
    rows = sorted(by_program.items(), key=lambda kv: -sum(
        kv[1][s] for s in STAGES))[:top]
    return "; ".join(
        f"{name} x{row['n']}: trace {row['trace']:.2f} lower "
        f"{row['lower']:.2f} backend {row['backend']:.2f} s "
        + "/".join(f"{n} {c}" for c, n in sorted(row["cache"].items()))
        for name, row in rows)


def uncovered(records, t_start: float, top: int = 5) -> str:
    """The longest stretches from ``t_start`` to the last record's end
    that no record covers, as ``seconds (from-to s after the start)
    before <program>``."""
    gaps, at, last = [], t_start, "the process's start"
    for r in sorted(records, key=lambda r: r["t0"]):
        if r["t0"] > at:
            gaps.append((r["t0"] - at, at - t_start, last, r["program"]))
        if r["t1"] > at:
            at, last = r["t1"], r["program"]
    gaps.sort(reverse=True)
    return "; ".join(
        f"{d:.2f} s ({off:.1f}-{off + d:.1f}) between {a} and {b}"
        for d, off, a, b in gaps[:top]) + (
        f"; the last record ends {at - t_start:.1f} s after the start")


def _program_records():
    """(the start's records, the log) from the running program, or None."""
    try:
        from megatron_llm_tpu.obs import compile as obs_compile
        from megatron_llm_tpu.obs import profile
    except ImportError:
        return None
    session = profile.last()
    cut = float("inf") if session is None else session.t_sync
    log = obs_compile.COMPILES
    return [r for r in log.records() if r["t1"] <= cut], log


def _setup_span():
    """The train loop's first ``setup`` span as (t0, t1) on the
    perf_counter clock, or None."""
    from megatron_llm_tpu.obs.trace import TRAIN_TRACE

    for name, t0, seconds, _args in recorder_spans(
            TRAIN_TRACE, float("-inf"), float("inf")):
        if name == "setup":
            return t0, t0 + seconds
    return None


def read(evidence: dict, params: dict):
    if "startup" not in evidence:
        got = _program_records()
        evidence["startup"] = None if got is None else got[0]
        if got is not None:
            records, log = got
            sums = summarize(records)
            say(f"the start's compilations: {sums['executables']} "
                f"executables, {sums['cache_misses']} not in the cache, "
                f"backend {sums['backend_s']:.2f} s, trace + lower "
                f"{sums['trace_lower_s']:.2f} s; {log.callbacks} stage "
                f"events so far, {log.absorbed} of them traces inside "
                f"another; costliest: {table(records)}")
            ctx = evidence.get("ctx")
            if records and ctx is not None:
                say("the start outside every compilation record: "
                    + uncovered(records, ctx.t0))
    records = evidence["startup"]
    if records is None:
        return None
    stat = params["stat"]
    if stat == "state_init_s":
        span = _setup_span()
        return None if span is None else own_time(span, records)
    return summarize(records)[stat]
