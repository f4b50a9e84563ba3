#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1|2>

runs one cell of BENCHMARK.json on the machine it is started on, in this
one process, and prints as its last line of standard output one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, traced, ``breakdown``.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a traced run of its
own.  ``--trace 2`` is a ``--trace 0`` run up to the moment the measured
window closes and its numbers are taken; only then does it start the
program's profile session (``obs/profile.py``) over a few seconds of the
same traffic, and its one line holds both kinds of metric.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result; ``--cpu-rehearsal`` runs the same code at tiny widths on the CPU
backend and can by construction never report ``"platform": "tpu"``.
``--sweep`` (serving cells with a ``rate_rps``) finds the knee: one
set-up, the mix's ``sweep_rates`` one after another, a table, no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()     # process start, for setup_s

import argparse
import json
import math
import os
import shutil
import sys
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: the "
                         "manifest's run_seconds)")
    ap.add_argument("--trace", type=int, choices=[0, 1, 2], default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from benchmarks.manifest import Manifest

    man = Manifest(ROOT)
    cell = man.cell(args.workload)
    config = man.config(cell["config"])
    mix = man.traffic(cell["traffic"])
    chips = int(cell["chips"])
    if args.cpu_rehearsal:
        # before the backend starts: the rehearsal sees the CPU only
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    if not (ROOT / "megatron_llm_tpu").is_dir():
        print("the program (megatron_llm_tpu/) is not in this directory",
              file=sys.stderr)
        return 3

    import importlib

    import jax

    from benchmarks import device as device_lib
    from benchmarks.common import Ctx, say

    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
    try:
        dev = device_lib.describe(chips, args.cpu_rehearsal)
        if not args.cpu_rehearsal:
            device_lib.peaks(dev["kind"])
    except (device_lib.NoAccelerator, KeyError, RuntimeError) as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    say(f"jax {jax.__version__}; {dev['count']} x {dev['kind']} "
        f"({dev['platform']}); compile cache: "
        f"{device_lib.enable_compile_cache(ROOT)}")
    trace_dir = str(ROOT / ".bench_trace" / args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Ctx(manifest=man, cell=cell, config=config, mix=mix,
              seed=args.seed,
              seconds=float(args.seconds if args.seconds is not None
                            else man.doc["run_seconds"]),
              trace=args.trace, rehearsal=args.cpu_rehearsal, t0=_T0,
              device=dev, clock=device_lib.CompileClock(),
              trace_dir=trace_dir, sweep=args.sweep)
    runner = importlib.import_module(f"benchmarks.kinds.{mix['kind']}")
    try:
        result = runner.run(ctx)
    except BaseException:  # noqa: BLE001 — reported, then a non-zero exit
        traceback.print_exc()
        sys.stderr.flush()
        say("FAILED: no result")
        return 1
    if result is None:          # --sweep prints its table and no result
        return 0
    for note in result.notes:
        say(note)
    dev["memory_peak_bytes"] = device_lib.memory_peak_bytes(chips)
    say(f"peak device memory (fullest chip): {dev['memory_peak_bytes']}")
    line = {"correct": bool(result.correct),
            "attempted": int(result.attempted),
            "failed": int(result.failed), "metrics": {}, "device": dev}
    if ctx.trace != 1:
        for m in man.metrics_of(cell["name"], "end_to_end"):
            line["metrics"][m["name"]] = {
                "value": float(result.end_to_end[m["name"]]),
                "unit": m["unit"]}
    if ctx.trace:
        from benchmarks import layer_report

        layer_report.fill(ctx, result, line)
    shutil.rmtree(trace_dir, ignore_errors=True)
    # what decided ``correct``, each number beside its limit: the last key
    # of the line and the last lines on standard error
    # (a number that is not finite has failed; 1e300 keeps the line JSON)
    line["compared"] = {
        k: {"value": float(v) if math.isfinite(v) else 1e300,
            "limit": float(lim)}
        for k, (v, lim) in result.compared.items()}
    for k, c in line["compared"].items():
        print(f"compared: {k} {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
