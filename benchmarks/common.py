"""What every runner shares: the run's context, the result it hands
back, and the program's model configuration built from a config file."""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


@dataclasses.dataclass
class Ctx:
    manifest: Any              # manifest.Manifest
    cell: dict                 # the workload's entry in BENCHMARK.json
    config: dict               # benchmarks/configs/<config>.json
    mix: dict                  # benchmarks/traffic/<traffic>.json
    seed: int
    seconds: float
    trace: int                 # 0: end-to-end only; 1: a traced run of its
                               # own; 2: a --trace 0 run, then a traced phase
    rehearsal: bool
    t0: float                  # perf_counter at process start
    device: dict               # {"platform", "kind", "count"}
    clock: Any                 # device.CompileClock
    trace_dir: str             # where a traced run writes its profile
    sweep: bool = False

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])


@dataclasses.dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]           # every metric the run took
    evidence: Dict[str, Any]               # what the per-layer readers read
    notes: List[str] = dataclasses.field(default_factory=list)
    # what decided ``correct``: name -> (the number, the limit it may
    # not pass); printed last on standard error and in the result line
    compared: Dict[str, tuple] = dataclasses.field(default_factory=dict)


# --- the program's spans and its profile session (--trace 2) ----------------

def recorder_spans(recorder, lo: float, hi: float):
    """The spans of a ``TraceRecorder`` (``obs/trace.py``) that began in
    [lo, hi) on the perf_counter clock, as ``(name, start, seconds,
    args)``.  The recorder exports its epoch, so nothing is injected."""
    doc = recorder.chrome_trace()
    epoch = doc["otherData"]["epoch_perf_counter"]
    out = []
    for e in doc["traceEvents"]:
        if e.get("ph") != "X":
            continue
        t0 = epoch + e["ts"] / 1e6
        if lo <= t0 < hi:
            out.append((e["name"], t0, e["dur"] / 1e6, e.get("args", {})))
    return out


def before_traced_phase() -> str:
    """Called by a ``--trace 2`` runner right after it has taken the
    window's numbers: what of the traced phase exists at that moment
    (nothing may — up to here the run is a ``--trace 0`` run)."""
    import threading

    from megatron_llm_tpu.obs import profile

    own = [t.name for t in threading.enumerate()
           if t.name.startswith("bench")]
    return (f"when the window's numbers were taken: profile sessions so far "
            f"{0 if profile.last() is None else 1}, threads of the "
            f"benchmark's own {own}")


def stop_profiler():
    """Stop the program's profile session → the session; says how long
    collecting and writing the trace took."""
    from megatron_llm_tpu.obs import profile

    session = profile.stop()
    say(f"profile session: {session.t_stop - session.t_sync:.2f} s traced, "
        f"written in {time.perf_counter() - session.t_stop:.1f} s")
    return session


def on_trace_clock(trace, session, spans):
    """``(name, start, seconds, args)`` spans on the perf_counter clock →
    ``(offset_ns, [trace_reduce.Event])`` on the clock of ``trace``, the
    reduced profile of ``session``: joined by the session's
    ``obs_clock_sync`` annotation.  ``(None, [])`` where the trace does
    not hold it."""
    from megatron_llm_tpu.obs import profile

    from benchmarks import trace_reduce

    sync = [e for e in trace.host if e.name == profile.SYNC_NAME]
    if not sync:
        return None, []
    off = profile.to_trace_ns(0.0, session.t_sync, sync[0].start)
    return off, [trace_reduce.Event(n, t0 * 1e9 + off, (t0 + d) * 1e9 + off)
                 for n, t0, d, _a in spans]


def depth_of(config: dict, kind: str) -> int:
    return int(config.get("by_kind", {}).get(kind, {}).get(
        "num_hidden_layers", config["num_hidden_layers"]))


def build_model(ctx: Ctx, section: str, **overrides):
    """The program's ``ModelConfig`` for this cell: the preset the config
    file names, at the depth it gives this traffic kind, checked against
    the widths the file states.  ``--cpu-rehearsal`` swaps in the file's
    tiny ``rehearsal.model`` sizes and nothing else."""
    from megatron_llm_tpu import config as program_config

    doc, kind = ctx.config, ctx.mix["kind"]
    preset = doc["preset"]
    make = getattr(program_config, f"{preset['family']}_config")
    kw = dict(num_layers=depth_of(doc, kind),
              attention_impl=doc[section]["attention_impl"])
    if "recompute" in doc[section]:
        kw["recompute"] = doc[section]["recompute"]
    kw.update(overrides)
    if ctx.rehearsal:
        kw.update(doc["rehearsal"]["model"])
        kw["num_layers"] = min(kw["num_layers"], 2)
    model = make(preset["size"], **kw)
    if not ctx.rehearsal:
        stated = {"hidden_size": model.hidden_size,
                  "num_attention_heads": model.num_attention_heads,
                  "vocab_size": model.vocab_size,
                  "num_kv_heads": model.kv_heads,
                  "head_dim": model.head_dim,
                  "ffn_hidden_size": model.ffn_size}
        for key, got in stated.items():
            want = doc.get(key, doc.get("derived", {}).get(key))
            if want is not None and int(want) != int(got):
                raise ValueError(f"{key}: the program's preset has {got}, "
                                 f"the configuration file {want}")
    return model


def scaled(mix: dict, rehearsal: bool) -> dict:
    """The mix as run.  A rehearsal divides every token count by 8."""
    if not rehearsal:
        return mix

    def shrink(v):
        if isinstance(v, dict):
            return {k: (max(4, int(x) // 8)
                        if k in ("median", "min", "max", "value",
                                 "prompt_tokens", "output_tokens",
                                 "seq_length") and not isinstance(x, dict)
                        else shrink(x)) for k, x in v.items()}
        return v

    out = shrink(mix)
    if "requests" in out:
        out["requests"] = min(int(out["requests"]), 96)
    if "dataset_steps" in out:
        out["dataset_steps"] = min(int(out["dataset_steps"]), 8)
    return out
