"""Reads samples of the serving engine's gauges (``serving/metrics.py``)
taken during the measured window.  ``gauge`` names one; ``stat`` is
``peak_share`` — the largest sample over ``evidence[of]``, in %."""

from __future__ import annotations


def read(evidence: dict, params: dict):
    samples = evidence.get("gauges", {}).get(params["gauge"])
    total = evidence.get(params["of"])
    if not samples or not total:
        return None
    if params["stat"] == "peak_share":
        return 100.0 * max(samples) / total
    raise ValueError(f"engine_gauges reader: unknown stat {params['stat']!r}")
