"""The traced run's line: the cell's per-layer metrics, each read by the
reader its file names, the device's busy time, and the breakdown.
``--trace 2``: the device-trace readers find the traced phase's profile
in the evidence, the program-span and gauge readers the measured
window's spans and samples; the runner put each there."""

from __future__ import annotations

from benchmarks import trace_reduce
from benchmarks.common import Ctx, Result, say


def fill(ctx: Ctx, result: Result, line: dict) -> None:
    man, cell = ctx.manifest, ctx.cell["name"]
    ev = result.evidence
    ev["ctx"] = ctx
    if "trace" not in ev:
        ev["trace"] = trace_reduce.load(
            trace_reduce.find_xplane(ctx.trace_dir))
    if not ev["trace"].ops:
        if not ctx.rehearsal:
            raise RuntimeError("no operation ran on a device in the trace")
        say("the CPU backend's trace has no device plane: device metrics "
            "are left out of this rehearsal's line")
        line["device"].update(busy_s=0.0, window_s=0.0)
        ev["trace"] = None
    else:
        # the traced window on the device clock: the runner's own, else
        # whole periods of a training loop, else first to last operation
        if "trace_window" not in ev:
            step = ev.get("step_module")
            ev["trace_window"] = trace_reduce.window_of(
                ev["trace"], step, whole_periods=step is not None)
        lo, hi = ev["trace_window"]
        busy = trace_reduce.busy_seconds(ev["trace"], (lo, hi))
        used = sorted(busy)[:ctx.chips]
        line["device"]["busy_s"] = sum(busy[d] for d in used) / len(used)
        line["device"]["window_s"] = (hi - lo) / 1e9
    for entry in man.metrics_of(cell, "per_layer"):
        spec = man.layer_metric(entry["name"])
        value = man.reader(spec["reader"]).read(ev, spec.get("params", {}))
        if value is None:
            say(f"{entry['name']}: its reader found nothing to read")
            continue
        line["metrics"][entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
    if ev["trace"] is None:
        return
    line["breakdown"] = {
        "device_ops": trace_reduce.top_ops(ev["trace"], (lo, hi), 10),
        "idle_gaps": trace_reduce.idle_gaps(
            ev["trace"], (lo, hi), ev.get("host_spans", ()), 10)}
