"""Shares of the published peaks for the kanana-2 cell (``model_type:
deepseek_v3``, latent attention): the work the algorithm needs
(``flops_deepseek_v3.py``, from the configuration file's numbers) for
what the engine did, over a device time read from the trace, over the
bf16 peak or the HBM bandwidth, in %.

What the engine did comes from its own spans (found as the state-space
cells' reader finds them, ``nemotron_h_roofline.traced_spans``): the
``prefill`` spans that ran the expanded form (``attn: "mla_expanded"``)
give the prompts' lengths; the ``decode`` spans of the absorbed form
(``attn: "mla_absorbed"``) the steps, each step's live slots and the
cached positions it attended (``live_positions``).

**Two windows of the one profile.**  A backlog's traced window runs from
the start of one prefill to the start of a later one
(``kinds/serve_backlog.py``).  This cell's slots are centred so that the
51 s end inside a decode phase: the session then records the rest of
that phase and a batch of prefills, and its traced window, which begins
at the first prefill, holds no decode step.  So the prefill's works are
taken over the traced window, as ``prefill_tok_per_s`` is, and a decode
step's over the whole session (first device operation to last).

``work``: ``prefill`` — the whole forward pass for the traced prompts
over the device time of the executables whose name holds ``module``, over
the bf16 peak; ``flash`` — the expanded form's causal attention of those
prompts over the own time of the leaf operations under ``scopes``, over
the bf16 peak; ``experts`` — the routed experts' products for the traced
prompts' positions over the own time under ``scopes``, over the bf16 peak
(the traced window holds prefills; a decode step in it would add time and
no counted work); ``decode_ms`` — the device time of ``module`` a run, ms;
``decode_bytes`` — a step's least bytes (at the session's steps' mean
live slots and cached positions) over the bandwidth, over ``module``'s
device time a run; ``latent_walk`` — the least time the absorbed
attention of the session's steps takes (the larger of the rows' bytes
over the bandwidth and its products over the peak) over the own time
under ``scopes``.

None where the trace, the session's recorders, the spans' arguments, the
scopes or the peaks are not there (a rehearsal, a program from before
them, another configuration) — and, for the three decode works, where the
session holds no decode step: a window that ends among prefills and a
session cut before the next decode phase (``traced_prefills`` stops after
one slot batch of prefills) record none, and the line then lacks them."""

from __future__ import annotations

from benchmarks import device, flops_deepseek_v3 as fd, trace_reduce
from benchmarks.common import depth_of
from benchmarks.readers import xplane_scope
from benchmarks.readers.nemotron_h_roofline import traced_spans

DECODE_WORKS = ("decode_ms", "decode_bytes", "latent_walk")


def traced_work(spans) -> dict:
    """→ the prompts' lengths, and the decode steps' live slots and
    cached positions (a step is the decode spans that share a start)."""
    steps = {t0: (a["live"], a["live_positions"])
             for name, t0, _d, a in spans
             if name == "decode" and a.get("attn") == "mla_absorbed"
             and "live" in a and "live_positions" in a}
    return {"prompts": [a["prompt_len"] for name, _t0, _d, a in spans
                        if name == "prefill"
                        and a.get("attn") == "mla_expanded"],
            "steps": list(steps.values())}


def _scope_seconds(evidence: dict, window, scopes) -> float:
    """Own time of the leaf operations under ``scopes`` in ``window``."""
    if window == evidence["trace_window"]:
        leaves = xplane_scope.leaves_of(
            evidence, xplane_scope.program_scopes() or tuple(scopes))
    else:
        if "session_scope_ops" not in evidence:
            per_device = xplane_scope.device_ops(
                trace_reduce.find_xplane(evidence["ctx"].trace_dir))
            evidence["session_scope_ops"] = xplane_scope.scoped_own_times(
                per_device[min(per_device)], window) if per_device else []
        leaves = evidence["session_scope_ops"]
    return sum(own for _n, s, own in leaves
               if xplane_scope._holds(s, scopes)) / 1e9


def read(evidence: dict, params: dict):
    trace, window = evidence.get("trace"), evidence.get("trace_window")
    ctx = evidence["ctx"]
    if trace is None or not trace.ops or window is None or ctx.rehearsal \
            or "kv_lora_rank" not in ctx.config:
        return None
    kind = params["work"]
    if kind in DECODE_WORKS:
        window = trace_reduce.window_of(trace)
        spans = traced_spans({**{k: v for k, v in evidence.items()
                                 if k != "nemotron_spans"},
                              "trace_window": window})
    else:
        spans = traced_spans(evidence)
    if not spans:
        return None
    did = traced_work(spans)
    s = fd.sizes_of(ctx.config, depth_of(ctx.config, ctx.mix["kind"]))
    peaks = device.peaks(ctx.device["kind"])
    flops, hbm = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
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
    if kind in ("prefill", "flash", "experts"):
        squares = float(sum(n * n for n in prompts))
        work = (fd.flash_flops(s, squares) if kind == "flash" else
                fd.expert_flops(s, sum(prompts)) if kind == "experts" else
                fd.prefill_flops(s, sum(prompts), len(prompts), squares))
        least = work / flops
    elif kind == "decode_bytes":
        live = sum(n for n, _p in steps) / len(steps)
        positions = sum(p for _n, p in steps) / len(steps)
        least = fd.decode_step_bytes(s, live, positions) / hbm
    elif kind == "latent_walk":
        least = sum(fd.latent_walk_seconds(s, p, hbm, flops)
                    for _n, p in steps)
    else:
        raise ValueError(f"deepseek_v3_roofline reader: unknown work "
                         f"{kind!r}")
    return 100.0 * least / secs
