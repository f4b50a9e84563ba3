"""Reads the serving engine's own ``TraceRecorder`` spans (``obs/trace.py``;
``queued``, ``prefill``, ``engine_step``, ...) that began inside the
measured window.  ``span`` names them; ``stat`` is ``median_ms`` or
``p95_ms`` of their durations."""

from __future__ import annotations

from benchmarks import stats


def read(evidence: dict, params: dict):
    spans = [d for name, _t0, d in evidence.get("recorder_spans", ())
             if name == params["span"]]
    if not spans:
        return None
    p = {"median_ms": 50.0, "p95_ms": 95.0}[params["stat"]]
    return 1e3 * stats.percentile(spans, p)
