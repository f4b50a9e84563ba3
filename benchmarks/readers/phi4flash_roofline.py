"""Shares of the published peaks for the phi-4-mini-flash-reasoning cell:
the work the algorithm needs (``flops_phi4flash.py``, from the
configuration file's numbers) for what the engine did, over a device time
read from the trace, over the bf16 peak or the HBM bandwidth, in %.

What the engine did comes from its own spans (found as the state-space
cells' reader finds them, ``nemotron_h_roofline.traced_spans``): the
``prefill`` spans whose later layers ran one row (``cross_rows`` 1: the
timed program) give the prompts' lengths; the ``decode`` spans whose
``state_kinds`` hold ``ssm1`` the steps, each step's live slots and the
cached positions it attended (``live_positions``).

``work``: ``prefill`` — what the timed prefills of the traced prompts
compute (``prefill_flops``: every row through the first decoder, one row
through the second) over the device time of the executables whose name
holds ``module``, over the bf16 peak; ``decode_ms`` — the device time of
``module`` a run, ms; ``decode_bytes`` — a step's least bytes (at the
steps' mean live slots and cached positions) over the bandwidth, over
``module``'s device time a run; ``walk_bytes`` — the cached rows the
steps' eight paged walks read over the bandwidth, over the own time of
the leaf operations under ``scopes`` (the walk's kernel); ``ring_bytes``
— the live rows of the window layers' rings the steps read, the same way
(the ring's kernel).

**The decode works' window.**  They are taken over the traced window
where it holds a decode step, else over the whole profile session (first
device operation to last: a backlog's traced window begins at a prefill
and may hold prefills alone), and are left out (None) where the session
holds none either.

None where the trace, the session's recorders, the spans' arguments, the
scopes or the peaks are not there (a rehearsal, a program from before
them, another configuration)."""

from __future__ import annotations

from benchmarks import device, flops_phi4flash as fp, trace_reduce
from benchmarks.readers.deepseek_v3_roofline import _scope_seconds
from benchmarks.readers.nemotron_h_roofline import traced_spans

DECODE_WORKS = ("decode_ms", "decode_bytes", "walk_bytes", "ring_bytes")


def traced_work(spans) -> dict:
    """-> the timed prompts' lengths, and the decode steps' live slots
    and cached positions (a step is the decode spans that share a
    start)."""
    steps = {t0: (a["live"], a["live_positions"])
             for name, t0, _d, a in spans
             if name == "decode" and "ssm1" in a.get("state_kinds", "")
             and "live" in a and "live_positions" in a}
    return {"prompts": [a["prompt_len"] for name, _t0, _d, a in spans
                        if name == "prefill" and a.get("cross_rows") == 1],
            "steps": list(steps.values())}


def _spans_in(evidence: dict, window):
    if window == evidence["trace_window"]:
        return traced_spans(evidence)
    return traced_spans({**{k: v for k, v in evidence.items()
                            if k != "nemotron_spans"},
                         "trace_window": window})


def read(evidence: dict, params: dict):
    trace, window = evidence.get("trace"), evidence.get("trace_window")
    ctx = evidence["ctx"]
    if trace is None or not trace.ops or window is None or ctx.rehearsal \
            or ctx.config.get("model_type") != "phi4flash":
        return None
    kind = params["work"]
    spans = traced_spans(evidence)
    if spans is None:
        return None
    did = traced_work(spans)
    if kind in DECODE_WORKS and not did["steps"]:
        window = trace_reduce.window_of(trace)
        did = traced_work(_spans_in(evidence, window) or ())
    prompts, steps = did["prompts"], did["steps"]
    if not (steps if kind in DECODE_WORKS else prompts):
        return None
    if "module" in params:
        per = trace_reduce.module_seconds(trace, window)
        runs = sum(n for k, (n, _s) in per.items() if params["module"] in k)
        secs = sum(t for k, (_n, t) in per.items() if params["module"] in k)
        if kind in DECODE_WORKS and runs:
            secs /= runs                     # the work is a step's
    else:
        secs = _scope_seconds(evidence, window, params["scopes"])
    if not secs:
        return None
    if kind == "decode_ms":
        return 1e3 * secs
    s = fp.sizes_of(ctx.config)
    peaks = device.peaks(ctx.device["kind"])
    if kind == "prefill":
        return 100.0 * fp.prefill_flops(s, prompts) / secs \
            / peaks["bf16_flops_per_s"]
    live = sum(n for n, _p in steps) / len(steps)
    positions = sum(p for _n, p in steps) / len(steps)
    if kind == "decode_bytes":
        work = fp.decode_step_bytes(s, live, positions)
    elif kind == "walk_bytes":
        work = sum(fp.walk_bytes(s, p) for _n, p in steps)
    elif kind == "ring_bytes":
        work = sum(fp.ring_read_bytes(s, n, p) for n, p in steps)
    else:
        raise ValueError(f"phi4flash_roofline reader: unknown work {kind!r}")
    return 100.0 * work / secs / peaks["hbm_bytes_per_s"]
