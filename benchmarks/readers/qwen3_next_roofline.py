"""Shares of the published bf16 peak for the Qwen3-Next cell: the work
the algorithm needs for the prompts prefilled in the traced window
(``flops_qwen3_next.py``, from the configuration file's numbers and
``evidence["traced_prefill_tokens"]``) over a device time read from the
trace, over the peak, in %.

``work``: ``prefill`` — the whole forward pass of this chip's share over
the device time of the executables whose name holds ``module``;
``experts`` — the held assignments' expert matmuls, and ``delta_rule``
— the chunked rule's products, each over the own time of the leaf
operations under ``scopes`` (``xplane_scope``'s reduction).

The evidence carries the traced prompts' token sum only, so the number of
prompts and the attention term (sum n^2 / sum n) are taken from the
mix's own lengths.  The held assignments a token are the engine's own
count where the program exposes it (``expert_load.held_share``), else
the even spread.  Decode steps inside the traced window add to the scope
times and add no counted work, so a share can only read low by them.
None where the trace, the scopes or the peak are not there (a
rehearsal, a program from before the scopes)."""

from __future__ import annotations

from benchmarks import device, flops_qwen3_next as fq, trace_reduce, traffic
from benchmarks.common import depth_of
from benchmarks.readers import expert_load, xplane_scope


def _work(evidence: dict, kind: str):
    ctx, tokens = evidence["ctx"], evidence.get("traced_prefill_tokens")
    if not tokens or "full_attention_interval" not in ctx.config:
        return None
    s = fq.sizes_of(ctx.config, depth_of(ctx.config, ctx.mix["kind"]))
    share = expert_load.held_share()
    held = (fq.held_assignments_per_token(s) if share is None
            else s["top_k"] * share)
    if kind == "experts":
        return tokens * s["layers"] * held * fq.expert_flops_per_assignment(s)
    if kind == "delta_rule":
        return tokens * s["linear_layers"] * fq.delta_rule_flops_per_token(s)
    if kind == "prefill":
        lengths = traffic.stratified(ctx.mix["prompt_tokens"], 64)
        mean = sum(lengths) / len(lengths)
        return fq.prefill_flops(
            s, tokens, tokens / mean,
            sum(n * n for n in lengths) / sum(lengths), held)
    raise ValueError(f"qwen3_next_roofline reader: unknown work {kind!r}")


def read(evidence: dict, params: dict):
    trace, window = evidence.get("trace"), evidence.get("trace_window")
    if trace is None or not trace.ops or window is None \
            or evidence["ctx"].rehearsal:
        return None
    work = _work(evidence, params["work"])
    if work is None:
        return None
    if "module" in params:
        per = trace_reduce.module_seconds(trace, window)
        secs = sum(s for k, (_n, s) in per.items() if params["module"] in k)
    else:
        leaves = xplane_scope.leaves_of(
            evidence, xplane_scope.program_scopes() or tuple(params["scopes"]))
        secs = sum(own for _n, s, own in leaves
                   if xplane_scope._holds(s, params["scopes"])) / 1e9
    if not secs:
        return None
    peak = device.peaks(evidence["ctx"].device["kind"])["bf16_flops_per_s"]
    return 100.0 * work / secs / peak
