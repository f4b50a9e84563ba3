"""Shares of the published peaks for the granite-4.0-h-micro cell: the work
the algorithm needs (``flops_granite_hybrid.py``, from the configuration
file's numbers) for what the engine did in the traced window, over a
device time read from the trace, over the bf16 peak or the HBM
bandwidth, in %.

What the engine did comes from its own spans, found as the other
state-space cell's reader finds them (``nemotron_h_roofline.
traced_spans``, ``traced_work``): the ``prefill`` spans that began in
the window give the prompts' lengths, the ``decode`` spans the steps and
each step's live slots.

``work``: ``prefill`` — the whole forward pass for the traced prompts
over the device time of the executables whose name holds ``module``,
over the bf16 peak; ``ssd`` — the chunked state-space products of those
prompts over the own time of the leaf operations under ``scopes``, over
the bf16 peak; ``decode_bytes`` — a step's least bytes (at the traced
steps' mean live slots, the cached positions from the window's block
gauge) over the bandwidth, over ``module``'s device time a run;
``state_bytes`` — the states read and written by the traced steps' live
slots over the bandwidth, over the own time under ``scopes``.

None where the trace, the session's recorders, the spans' arguments, the
scopes or the peaks are not there (a rehearsal, a program from before
them, another configuration)."""

from __future__ import annotations

from benchmarks import device, flops_granite_hybrid as fg, trace_reduce
from benchmarks.common import depth_of
from benchmarks.readers import xplane_scope
from benchmarks.readers.nemotron_h_roofline import traced_spans, traced_work


def _work(evidence: dict, kind: str):
    """→ operations or bytes: a step's for ``decode_bytes``, else the
    traced window's."""
    ctx = evidence["ctx"]
    if "mamba_n_heads" not in ctx.config:
        return None
    spans = traced_spans(evidence)
    if not spans:
        return None
    did = traced_work(spans)
    s = fg.sizes_of(ctx.config, depth_of(ctx.config, ctx.mix["kind"]))
    prompts, live = did["prompts"], did["live"]
    if kind in ("prefill", "ssd"):
        tokens = sum(prompts)
        if not tokens:
            return None
        if kind == "ssd":
            return tokens * s["mamba_layers"] * fg.ssd_flops_per_token(s)
        return fg.prefill_flops(s, tokens, len(prompts),
                                sum(n * n for n in prompts) / tokens)
    if not live:
        return None
    if kind == "state_bytes":
        return fg.mamba_step_bytes(s, sum(live))
    if kind == "decode_bytes":
        used = evidence.get("gauges", {}).get("blocks_used") or [0]
        tokens = (sum(used) / len(used)
                  * ctx.config["serve"]["engine"]["kv_block_size"])
        return fg.decode_step_bytes(s, sum(live) / len(live), tokens)
    raise ValueError(f"granite_hybrid_roofline reader: unknown work "
                     f"{kind!r}")


def read(evidence: dict, params: dict):
    trace, window = evidence.get("trace"), evidence.get("trace_window")
    if trace is None or not trace.ops or window is None \
            or evidence["ctx"].rehearsal:
        return None
    kind = params["work"]
    work = _work(evidence, kind)
    if work is None:
        return None
    if "module" in params:
        per = trace_reduce.module_seconds(trace, window)
        runs = sum(n for k, (n, _s) in per.items() if params["module"] in k)
        secs = sum(s for k, (_n, s) in per.items() if params["module"] in k)
        if kind == "decode_bytes" and runs:
            secs /= runs                 # the work is a step's
    else:
        leaves = xplane_scope.leaves_of(
            evidence, xplane_scope.program_scopes() or tuple(params["scopes"]))
        secs = sum(own for _n, s, own in leaves
                   if xplane_scope._holds(s, params["scopes"])) / 1e9
    if not secs:
        return None
    peaks = device.peaks(evidence["ctx"].device["kind"])
    peak = peaks["hbm_bytes_per_s" if kind.endswith("_bytes")
                 else "bf16_flops_per_s"]
    return 100.0 * work / secs / peak
