"""Reads the trainer's ``log_window`` events of the measured window
(``training/driver.py:training_log``, one a step at ``log_interval=1``).

``stat``: ``median_step_ms`` — the median ``step_time_s``; ``mfu`` —
tokens a second over the window's summed step time, times the
benchmark's own FLOPs a token (``flops.py``; recomputation not counted),
over chips times the published bf16 peak (``peaks.json``), in %."""

from __future__ import annotations

from benchmarks import device, stats


def read(evidence: dict, params: dict):
    events = evidence.get("log_window")
    if not events:
        return None
    steps = [e["step_time_s"] for e in events]
    if params["stat"] == "median_step_ms":
        return 1e3 * stats.median(steps)
    if params["stat"] == "mfu":
        ctx = evidence["ctx"]
        if ctx.rehearsal:
            return None           # a CPU has no published peak to share
        rate = evidence["tokens_per_step"] * len(steps) / sum(steps)
        peak = device.peaks(ctx.device["kind"])["bf16_flops_per_s"]
        return 100.0 * rate * evidence["train_flops_per_token"] \
            / (ctx.chips * peak)
    raise ValueError(f"log_window reader: unknown stat {params['stat']!r}")
