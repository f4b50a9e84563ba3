"""Reads the reduced device trace (``trace_reduce.py``) of the traced
window.  ``stat``:

* ``idle_share`` — 1 - union of operation intervals over the window, on
  the device that was busy least, %.
* ``collective_exposed_share`` — time in collectives while no compute
  operation runs on that device, over the window, worst device, %.
* ``module_ms`` — device time a run of the executables whose name
  holds ``module``, ms.
* ``tokens_per_s`` — ``evidence[tokens]`` over the device time of the
  executables whose name holds ``module``.
* ``hbm_share`` — ``evidence[bytes]`` a run over the published HBM
  bandwidth, over the device time a run of ``module``, %."""

from __future__ import annotations

from benchmarks import device, trace_reduce


def _module(evidence, like):
    per = trace_reduce.module_seconds(evidence["trace"],
                                      evidence["trace_window"])
    runs = sum(n for k, (n, _s) in per.items() if like in k)
    secs = sum(s for k, (_n, s) in per.items() if like in k)
    return runs, secs


def read(evidence: dict, params: dict):
    trace, window = evidence.get("trace"), evidence.get("trace_window")
    if trace is None or not trace.ops or window is None:
        return None
    stat = params["stat"]
    if stat == "idle_share":
        return trace_reduce.idle_share(trace, window)
    if stat == "collective_exposed_share":
        return trace_reduce.collective_exposed_share(trace, window)
    runs, secs = _module(evidence, params["module"])
    if not runs:
        return None
    if stat == "module_ms":
        return 1e3 * secs / runs
    if stat == "tokens_per_s":
        tokens = evidence.get(params["tokens"])
        return None if not tokens else tokens / secs
    if stat == "hbm_share":
        ctx = evidence["ctx"]
        if ctx.rehearsal or evidence.get(params["bytes"]) is None:
            return None
        bw = device.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
        return 100.0 * (evidence[params["bytes"]] / bw) / (secs / runs)
    raise ValueError(f"xplane reader: unknown stat {stat!r}")
