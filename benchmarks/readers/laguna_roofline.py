"""Shares of the published peaks for the Laguna-XS.2 cell: the work the
algorithm needs (``flops_laguna.py``, from the configuration file's
numbers) for what the engine did, over a device time read from the trace,
over the bf16 peak or the HBM bandwidth, in %.

What the engine did comes from its own spans (found as the state-space
cells' reader finds them, ``nemotron_h_roofline.traced_spans``): the
``prefill`` spans whose ``state_kinds`` hold ``window`` give the prompts'
lengths; the ``decode`` spans of such a stack the steps, each step's live
slots (``live``), the cached positions its full layers walked
(``live_positions``) and the live rows of its rings (``ring_rows``).  The
routed experts a step touches are reckoned from the engine's per-layer,
per-expert counters (``expert_load``): each expert's share of all choices
gives the chance that none of a step's ``live x top_k`` choices fell on
it (``flops_laguna.touched_experts``).

``work``: ``prefill`` — what the traced prompts' prefills compute
(``prefill_flops``: the band in the window layers, the triangle in the
full ones, one row of logits a prompt) over the device time of the
executables whose name holds ``module``, over the bf16 peak;
``window_flash`` — the window layers' band attention of those prompts
(``window_flops``) over the own time of the leaf operations whose scope
path holds EVERY name of ``scopes`` (``flash_fwd`` under ``swa``);
``experts`` — the least time of the routed experts' work in the window
(a prompt's products over the peak; a step's touched experts' bytes over
the bandwidth: a step's few rows are bound by the read) over the own time
under ``scopes``; ``decode_ms`` — the device time of ``module`` a run, ms;
``decode_bytes`` — a step's least bytes (at the steps' mean live slots,
positions, ring rows and touched experts) over the bandwidth, over
``module``'s device time a run; ``walk_bytes`` — the cached rows the
steps' paged walks read (two full layers) over the bandwidth, over the
own time under ``scopes`` (the walk's kernel); ``ring_bytes`` — the live
ring rows the steps read (three window layers), the same way (the ring's
kernel).

**The decode works' window.**  They are taken over the traced window
where it holds a decode step, else over the whole profile session (first
device operation to last: a backlog's traced window begins at a prefill
and may hold prefills alone), and are left out (None) where the session
holds none either.

None where the trace, the session's recorders, the spans' arguments, the
scopes or the peaks are not there (a rehearsal, a program from before
them, another configuration)."""

from __future__ import annotations

from benchmarks import device, flops_laguna as fl, trace_reduce
from benchmarks.common import depth_of
from benchmarks.readers import expert_load, xplane_scope
from benchmarks.readers.nemotron_h_roofline import traced_spans
from benchmarks.readers.phi4flash_roofline import _spans_in

DECODE_WORKS = ("decode_ms", "decode_bytes", "walk_bytes", "ring_bytes")


def traced_work(spans) -> dict:
    """-> the prompts' lengths, and the decode steps' ``(live slots,
    cached positions, live ring rows)`` (a step is the decode spans that
    share a start)."""
    steps = {t0: (a["live"], a["live_positions"], a["ring_rows"])
             for name, t0, _d, a in spans
             if name == "decode" and "window" in a.get("state_kinds", "")
             and all(k in a for k in ("live", "live_positions",
                                      "ring_rows"))}
    return {"prompts": [a["prompt_len"] for name, _t0, _d, a in spans
                        if name == "prefill"
                        and "window" in a.get("state_kinds", "")],
            "steps": list(steps.values())}


def expert_shares():
    """Each expert's share of all counted choices, a list a layer that
    routes (the engine's counters), or None where it counted none."""
    by_layer: dict = {}
    for smp in expert_load._samples(expert_load.FAMILY):
        if "layer" in smp.labels:
            by_layer.setdefault(smp.labels["layer"], []).append(smp.value)
    shares = [[v / sum(row) for v in row]
              for row in by_layer.values() if sum(row)]
    return shares or None


def touched(s: dict, live: float, shares) -> float:
    """The routed experts a layer's step of ``live`` slots reads: the
    mean over the layers that route, by their experts' ``shares`` of the
    choices (``expert_shares``, read once a metric: the registry fetches
    the counters from the device); an even router's where it is None."""
    if shares is None:
        return fl.touched_experts(s, live)
    return sum(fl.touched_experts(s, live, row) for row in shares) \
        / len(shares)


def _leaves(evidence: dict, window):
    """``(HLO text, scope path, own ns)`` of the leaf operations in
    ``window``: the traced window's (decoded once a run by whichever
    reader asks first) or the whole session's."""
    if window == evidence["trace_window"]:
        return xplane_scope.leaves_of(
            evidence, xplane_scope.program_scopes() or ("swa",))
    if "session_scope_ops" not in evidence:
        per_device = xplane_scope.device_ops(
            trace_reduce.find_xplane(evidence["ctx"].trace_dir))
        evidence["session_scope_ops"] = xplane_scope.scoped_own_times(
            per_device[min(per_device)], window) if per_device else []
    return evidence["session_scope_ops"]


def _scope_seconds(evidence: dict, window, scopes) -> float:
    """Own time of the leaf operations in ``window`` whose scope path
    holds every name of ``scopes``."""
    return sum(own for _n, path, own in _leaves(evidence, window)
               if all(xplane_scope._holds_one(path, name)
                      for name in scopes)) / 1e9


def read(evidence: dict, params: dict):
    trace, window = evidence.get("trace"), evidence.get("trace_window")
    ctx = evidence["ctx"]
    if trace is None or not trace.ops or window is None or ctx.rehearsal \
            or ctx.config.get("model_type") != "laguna":
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
    s = fl.sizes_of(ctx.config, depth_of(ctx.config, ctx.mix["kind"]))
    peaks = device.peaks(ctx.device["kind"])
    flops, hbm = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    if kind == "prefill":
        least = fl.prefill_flops(s, prompts) / flops
    elif kind == "window_flash":
        least = fl.window_flops(s, prompts) / flops
    elif kind == "experts":
        expert_layers, shares = s["dense"].count(False), expert_shares()
        least = fl.expert_flops(s, sum(prompts)) / flops + sum(
            expert_layers * touched(s, n, shares) * fl.expert_params(s)
            * fl.BF16 for n, _p, _r in steps) / hbm
    elif kind == "decode_bytes":
        live = sum(n for n, _p, _r in steps) / len(steps)
        positions = sum(p for _n, p, _r in steps) / len(steps)
        rows = sum(r for _n, _p, r in steps) / len(steps)
        least = fl.decode_step_bytes(
            s, live, positions, rows,
            touched(s, live, expert_shares())) / hbm
    elif kind == "walk_bytes":
        least = sum(fl.walk_bytes(s, p) for _n, p, _r in steps) / hbm
    elif kind == "ring_bytes":
        least = sum(fl.ring_read_bytes(s, r) for _n, _p, r in steps) / hbm
    else:
        raise ValueError(f"laguna_roofline reader: unknown work {kind!r}")
    return 100.0 * least / secs
