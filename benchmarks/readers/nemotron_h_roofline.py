"""Shares of the published peaks for the Nemotron-3-Super cell: the work
the algorithm needs (``flops_nemotron_h.py``, from the configuration
file's numbers) for what the engine did in the traced window, over a
device time read from the trace, over the bf16 peak or the HBM
bandwidth, in %.

What the engine did comes from its own spans, which the program's profile
session keeps with their arguments (``obs/profile.py:Session.recorders``):
the ``prefill`` spans that began in the window give the prompts' lengths,
the ``decode`` spans (one a slot a step, all of a step starting together)
the steps and each step's live slots.

``work``: ``prefill`` — the whole forward pass of this chip's share for
the traced prompts over the device time of the executables whose name
holds ``module``, over the bf16 peak; ``ssd`` — the chunked state-space
products of those prompts over the own time of the leaf operations under
``scopes``, over the bf16 peak; ``decode_bytes`` — a step's least bytes
(at the traced steps' mean live slots, the cached positions from the
window's block gauge) over the bandwidth, over ``module``'s device time a
run; ``state_bytes`` — the states read and written by the traced steps'
live slots over the bandwidth, over the own time under ``scopes``.

The held assignments a token, and how many of a layer's held experts a
step's live slots choose among them, are the engine's own counts
(``expert_load``'s family: a router that favours few experts reads fewer
of them a step), else the even spread's.  None where the
trace, the session's recorders, the spans' arguments, the scopes or the
peaks are not there (a rehearsal, a program from before them)."""

from __future__ import annotations

from benchmarks import common, device, flops_nemotron_h as fn, trace_reduce
from benchmarks.common import depth_of
from benchmarks.readers import expert_load, xplane_scope


def traced_spans(evidence: dict):
    """The engine's spans that began in the traced window, as ``(name,
    start, seconds, args)``, memoised in the evidence; None where the
    program's session kept no recorder or the clocks cannot be joined."""
    if "nemotron_spans" in evidence:
        return evidence["nemotron_spans"]
    try:
        from megatron_llm_tpu.obs import profile
    except ImportError:
        return None
    session = profile.last()
    recorders = getattr(session, "recorders", ())
    spans = None
    if recorders:
        off, _ = common.on_trace_clock(evidence["trace"], session, ())
        if off is not None:
            lo, hi = ((t - off) / 1e9 for t in evidence["trace_window"])
            spans = [sp for rec in recorders
                     for sp in common.recorder_spans(rec, lo, hi)]
    evidence["nemotron_spans"] = spans
    return spans


def traced_work(spans) -> dict:
    """→ the traced prompts' lengths and the traced decode steps' live
    slots (a step is the decode spans that share a start)."""
    steps = {t0: a["live"] for name, t0, _d, a in spans
             if name == "decode" and "live" in a}
    return {"prompts": [a["prompt_len"] - a.get("cached_tokens", 0)
                        for name, _t0, _d, a in spans
                        if name == "prefill" and "state_kinds" in a],
            "live": list(steps.values())}


def chosen_experts(s: dict, live: float):
    """The held experts a layer's step of ``live`` slots reads, by the
    engine's per-expert counts (the mean over the layers that route), or
    None where it counted none."""
    by_layer: dict = {}
    for smp in expert_load._samples(expert_load.FAMILY):
        if "layer" in smp.labels:
            by_layer.setdefault(smp.labels["layer"], []).append(
                (smp.value, smp.labels.get("held") == "1"))
    chosen = []
    for rows in by_layer.values():
        tokens = sum(v for v, _held in rows) / s["top_k"]
        if tokens:
            chosen.append(fn.chosen_held_experts(
                s, live, [v / tokens for v, held in rows if held]))
    return sum(chosen) / len(chosen) if chosen else None


def _work(evidence: dict, kind: str):
    """→ (operations or bytes, per run of the module or in all)."""
    ctx = evidence["ctx"]
    if "mamba_num_heads" not in ctx.config:
        return None
    spans = traced_spans(evidence)
    if not spans:
        return None
    did = traced_work(spans)
    s = fn.sizes_of(ctx.config, depth_of(ctx.config, ctx.mix["kind"]))
    prompts, live = did["prompts"], did["live"]
    if kind in ("prefill", "ssd"):
        tokens = sum(prompts)
        if not tokens:
            return None
        if kind == "ssd":
            return tokens * s["mamba_layers"] * fn.ssd_flops_per_token(s)
        share = expert_load.held_share()
        held = None if share is None else s["top_k"] * share
        return fn.prefill_flops(s, tokens, len(prompts),
                                sum(n * n for n in prompts) / tokens, held)
    if not live:
        return None
    if kind == "state_bytes":
        return fn.mamba_step_bytes(s, sum(live))
    if kind == "decode_bytes":
        used = evidence.get("gauges", {}).get("blocks_used") or [0]
        tokens = (sum(used) / len(used)
                  * ctx.config["serve"]["engine"]["kv_block_size"])
        mean = sum(live) / len(live)
        return fn.decode_step_bytes(s, mean, tokens,
                                    chosen_experts(s, mean))
    raise ValueError(f"nemotron_h_roofline reader: unknown work {kind!r}")


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
