"""Reads the open loop's own latency report of the measured window
(``serving.latency_report``: host clock, profiler off): a percentile of
TTFT or of the gaps that the cell records beside the ones it is judged
by.  ``name`` is the report's key, ``ttft_p<NN>_ms`` or ``itl_p<NN>_ms``."""

from __future__ import annotations


def read(evidence: dict, params: dict):
    return evidence.get("latency", {}).get(params["name"])
