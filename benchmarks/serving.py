"""What the serving kinds share: the engine as a deployment starts it,
its warm-up, the comparison with the plain reference, and the open-loop
and backlog drivers of ``ServingEngine.submit(..., on_token=...)`` — the
call ``MegatronServer`` makes for every request.

Time stamps are taken in the ``on_token`` callback (the idea of
``serving/bench.py:_itl_recorder``, copied), so a latency runs from when
a request was *due* to when its token reached the caller, queueing and
host dispatch included.
"""

from __future__ import annotations

import dataclasses
import importlib
import threading
import time
from typing import Dict, List, Optional

from benchmarks import common, flops, stats, traffic, trace_reduce
from benchmarks.common import Ctx, build_model, say, scaled

# max |engine log-prob - reference log-prob| over every position (prompt
# and generated) of the check sequences.  The engine runs 32 layers in
# bf16 through prefill and the paged decode path, the reference in
# float32 ("highest") from the same bf16 weights; at random init a
# token's log-probability is near -ln(vocab) and the bf16 path lands
# within a few hundredths of the reference (PERF.md, PR 23).  A mean
# bound beside the maximum catches a systematic shift that single
# positions hide: 8-bit weights or activations move every position.
LOGPROB_MAX_TOL = 0.15
LOGPROB_MEAN_TOL = 0.03


@dataclasses.dataclass
class Served:
    """One request as the load generator saw it."""
    due: float                      # perf_counter
    prompt_len: int
    max_new: int
    counted: bool                   # due inside the measured window
    submitted: Optional[float] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    handle: object = None

    @property
    def done(self) -> bool:
        return len(self.stamps) >= self.max_new


class Serving:
    """The engine under test, started, warmed up and checked."""

    def __init__(self, ctx: Ctx):
        import jax
        from megatron_llm_tpu.models import model as model_lib
        from megatron_llm_tpu.serving import EngineConfig, ServingEngine

        self.ctx = ctx
        self.mix = scaled(ctx.mix, ctx.rehearsal)
        self.model = build_model(ctx, "serve")
        kw = dict(ctx.config["serve"]["engine"])
        if ctx.rehearsal:
            kw.update(ctx.config["rehearsal"]["engine"])
        self.engine_kw = kw
        m = self.model
        say(f"serve: hidden {m.hidden_size}, {m.num_attention_heads} heads "
            f"x {m.head_dim}, {m.kv_heads} KV heads, ffn {m.ffn_size}, "
            f"vocab {m.vocab_size}, {m.params_dtype}, {m.num_layers} "
            f"layers; engine {kw}")
        t = time.perf_counter()
        self.params = jax.jit(lambda k: model_lib.init_params(k, m))(
            jax.random.key(traffic.device_seed(ctx.seed)))
        jax.block_until_ready(self.params)
        say(f"weights on the device in {time.perf_counter() - t:.1f} s")
        self.engine = ServingEngine(m, self.params, EngineConfig(**kw))
        self.engine.start()
        pool = self.engine.slots.pool
        self.block_size = pool.block_size
        self.pool_blocks = pool.usable_blocks
        self.gauges: Dict[str, List[int]] = {"blocks_used": []}
        self.correct_notes: List[str] = []
        self.compared: Dict[str, tuple] = {}   # name: (number, its limit)
        self.correct = True

    # -- requests ----------------------------------------------------------

    def submit(self, s: Served, prompt, logprobs: bool = False):
        metrics, gauge = self.engine.metrics, self.gauges["blocks_used"]

        def on_token(_tok, s=s):
            s.stamps.append(time.perf_counter())
            gauge.append(metrics.blocks_used)

        s.submitted = time.perf_counter()
        # greedy: the sampling seed is never used, but left unset the
        # engine reads os.urandom at every submission
        s.handle = self.engine.submit(
            prompt, s.max_new, use_eos_stop=False, return_logprobs=logprobs,
            on_token=on_token, seed=0)
        return s.handle

    # -- set-up ------------------------------------------------------------

    def buckets(self, lengths) -> Dict[int, int]:
        """{padded prefill width: the longest prompt that pads to it}."""
        b, cap = self.engine_kw["prefill_bucket"], self.engine_kw["max_seq_len"]
        out: Dict[int, int] = {}
        for n in lengths:
            w = min(-(-n // b) * b, cap)
            out[w] = max(out.get(w, 0), n)
        return out

    def warm_up(self, prompt_lengths) -> None:
        """Every prefill shape the mix can produce, first alone and then
        joining a request that is already decoding — the three ways the
        scheduler feeds a decode step (host tokens, device tokens, and
        ``_merge_pending`` when a request joins a step in flight) — until
        a round compiles nothing."""
        rng = traffic.host_seed(self.ctx.seed, 3)
        vocab, new = self.model.vocab_size, int(self.mix["warmup_output_tokens"])
        shapes = sorted(self.buckets(prompt_lengths).items())
        say(f"warm-up: prefill widths {[w for w, _n in shapes]}")

        def send(n, out):
            s = Served(0.0, n, out, False)
            self.submit(s, rng.integers(1, vocab - 1, size=n).tolist())
            return s

        for rnd in range(1, 5):
            c0, t = self.ctx.clock.backend_compiles, time.perf_counter()
            for _w, n in shapes:
                send(n, new).handle.result(timeout=900)
            carrier = send(shapes[0][1], 3 * (len(shapes) + 2))
            joined = []
            for _w, n in shapes:
                seen = len(carrier.stamps) + 2
                while len(carrier.stamps) < seen and not carrier.done:
                    time.sleep(0.002)
                joined.append(send(n, new))
            for s in joined + [carrier]:
                s.handle.result(timeout=900)
            n = self.ctx.clock.backend_compiles - c0
            say(f"warm-up round {rnd}: {time.perf_counter() - t:.1f} s, "
                f"{n} executables compiled or loaded")
            if n == 0:
                return
        raise RuntimeError("four warm-up rounds and still compiling")

    def prepare(self) -> None:
        """Everything between a started engine and the measured window."""
        self.warm_up(traffic.stratified(self.mix["prompt_tokens"], 64))
        self.check()
        self.gauges["blocks_used"].clear()

    def check(self) -> None:
        """A few seeded sequences through prefill and paged decode
        against the reference's full forward, position by position."""
        import numpy as np

        ref = importlib.import_module(
            f"benchmarks.reference.{self.ctx.config['reference']}")
        spec = self.mix["check"]
        rng = traffic.host_seed(self.ctx.seed, 4)
        t = time.perf_counter()
        served = []
        for _ in range(int(spec["sequences"])):
            s = Served(0.0, int(spec["prompt_tokens"]),
                       int(spec["output_tokens"]), False)
            self.submit(s, rng.integers(1, self.model.vocab_size - 1,
                                        size=s.prompt_len).tolist(),
                        logprobs=True)
            served.append(s)
        worst, total, count, bad = 0.0, 0.0, 0, 0
        meta = ref.meta_of(self.model)
        for s in served:
            got = s.handle.result(timeout=900)
            ok = (got.finish_reason == "length"
                  and len(got.tokens) == s.prompt_len + s.max_new)
            want = np.asarray(ref.token_logprobs(self.params, got.tokens, meta))
            d = np.abs(np.asarray(got.logprobs, np.float32) - want)
            bad += not (ok and bool(np.all(np.isfinite(d))))
            worst = max(worst, float(d.max()))
            total, count = total + float(d.sum()), count + d.size
        mean = total / count
        self.compared = {"logprob_max_gap": (worst, LOGPROB_MAX_TOL),
                         "logprob_mean_gap": (mean, LOGPROB_MEAN_TOL),
                         "check_sequences_cut_or_not_finite": (bad, 0)}
        self.correct = (self.correct and bad == 0
                        and worst <= LOGPROB_MAX_TOL
                        and mean <= LOGPROB_MEAN_TOL)
        self.correct_notes.append(
            f"engine vs reference log-probs over {count} positions of "
            f"{len(served)} sequences: max distance {worst:.4f} (tolerance "
            f"{LOGPROB_MAX_TOL}), mean {mean:.5f} (tolerance "
            f"{LOGPROB_MEAN_TOL}); {time.perf_counter() - t:.1f} s")

    def wait_idle(self, served: List[Served], until: float) -> None:
        for s in served:
            while not s.done and time.perf_counter() < until:
                time.sleep(0.01)

    def close(self, served: List[Served]) -> None:
        for s in served:
            if s.handle is not None and not s.handle.done():
                s.handle.cancel()
        self.engine.shutdown(timeout=60.0)


# -- traced slice ------------------------------------------------------------

class TraceSlice:
    """``--trace 1``: a few seconds of profiler trace in the middle of the
    window, taken from a helper thread so that the load generator is not
    held up.  (``--trace 2`` traces after the window, through the
    program's own session: the kinds' traced phases.)"""

    SECONDS = 3.0

    def __init__(self, ctx: Ctx, t_start: float, seconds: float):
        self.ctx = ctx
        self.begin = t_start + 0.4 * seconds
        self.length = min(self.SECONDS, 0.4 * seconds)
        self.t_sync = self.t_stop = None
        self.thread = threading.Thread(target=self._run, name="bench-trace")
        self.thread.start()

    def _run(self) -> None:
        import jax

        time.sleep(max(0.0, self.begin - time.perf_counter()))
        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # the engine's spans suffice
            jax.profiler.start_trace(self.ctx.trace_dir,
                                     profiler_options=options)
        except (AttributeError, TypeError):
            jax.profiler.start_trace(self.ctx.trace_dir)
        with jax.profiler.TraceAnnotation("bench_sync"):
            self.t_sync = time.perf_counter()
        time.sleep(self.length)
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def join(self) -> None:
        self.thread.join(600)


def recorder_spans(engine, lo: float, hi: float):
    """The engine's TraceRecorder spans that began in [lo, hi) on the
    perf_counter clock, as ``(name, start, seconds, args)``."""
    return common.recorder_spans(engine.trace, lo, hi)


def window_evidence(sv: Serving, spans) -> dict:
    """What the program-span and gauge readers read: ``spans`` (the
    engine's, of the measured window) and the gauge samples so far."""
    sizes = flops.sizes_of(sv.model)
    used = list(sv.gauges["blocks_used"])
    live = (sum(used) / len(used) if used else 0.0) * sv.block_size
    return {"recorder_spans": [(n, t0, d) for n, t0, d, _a in spans],
            "gauges": {"blocks_used": used}, "pool_blocks": sv.pool_blocks,
            # live tokens taken as mean blocks in use x block size, which
            # rounds up to whole blocks and counts cached prefixes: under
            # half a percent of the weights' bytes for this model
            "decode_step_bytes": flops.decode_step_bytes(sizes, live)}


def trace_evidence(sv: Serving, spans, lo: float, hi: float,
                   off_of) -> dict:
    """What the device-trace readers read: the reduced profile, its
    window [lo, hi) (perf_counter) on the trace's clock, the prompt
    tokens prefilled in it and the engine's spans that overlap it.
    ``off_of(trace)`` gives perf_counter → trace clock in ns, or None."""
    trace = trace_reduce.load(trace_reduce.find_xplane(sv.ctx.trace_dir))
    ev = {"trace": trace}
    off = off_of(trace)
    if off is not None and trace.ops:
        ev["trace_window"] = (lo * 1e9 + off, hi * 1e9 + off)
        ev["traced_prefill_tokens"] = sum(
            a.get("prompt_len", 0) - a.get("cached_tokens", 0)
            for n, t0, _d, a in spans if n == "prefill" and lo <= t0 < hi)
        ev["host_spans"] = [
            trace_reduce.Event(n, t0 * 1e9 + off, (t0 + d) * 1e9 + off)
            for n, t0, d, _a in spans if lo - d <= t0 < hi]
    return ev


def layer_evidence(sv: Serving, sl: Optional[TraceSlice], window) -> dict:
    """What the per-layer readers read, for a ``--trace 1`` run: the
    slice lies inside the window, and its own ``bench_sync`` annotation
    joins the clocks."""
    if sl is None:
        return {}
    spans = recorder_spans(sv.engine, *window)

    def off_of(trace):
        sync = [e for e in trace.host if e.name == "bench_sync"]
        return sync[0].start - sl.t_sync * 1e9 if sync else None

    return {**window_evidence(sv, spans),
            **trace_evidence(sv, spans, sl.t_sync, sl.t_stop, off_of)}


def traced_phase_evidence(sv: Serving, session, window=None) -> dict:
    """``--trace 2``: the device-trace readers' part, from the program's
    profile session that ran after the window, over ``window``
    (perf_counter) or else the whole session."""
    spans = recorder_spans(sv.engine, session.t_sync - 60.0, session.t_stop)
    lo, hi = window or (session.t_sync, session.t_stop)
    return trace_evidence(
        sv, spans, lo, hi,
        lambda trace: common.on_trace_clock(trace, session, ())[0])


# the percentiles a cell may judge or record: an end-to-end metric of a
# ``serve_open`` cell is ``ttft_p<NN>_ms`` or ``itl_p<NN>_ms`` with NN
# here, or the mean over all of the window's samples, ``<kind>_mean_ms``
PERCENTILES = (25, 50, 75, 90, 95, 97, 98, 99)


def latency_report(served: List[Served]) -> dict:
    """TTFT from when a request was due, and the gaps between its
    tokens, pooled over the counted requests: ``ttft_p<NN>_ms`` and
    ``itl_p<NN>_ms`` for every NN of ``PERCENTILES``, ``ttft_mean_ms``
    and ``itl_mean_ms``, and the counts ``n_ttft`` and ``n_gaps`` they
    were taken over."""
    ttft = [1e3 * (s.stamps[0] - s.due) for s in served if s.stamps]
    gaps = [1e3 * (b - a) for s in served
            for a, b in zip(s.stamps, s.stamps[1:])]
    out = {"n_ttft": len(ttft), "n_gaps": len(gaps)}
    for name, values in (("ttft", ttft), ("itl", gaps)):
        if values:
            xs = sorted(values)
            out.update({f"{name}_p{p}_ms": stats.percentile(xs, p)
                        for p in PERCENTILES})
            out[f"{name}_mean_ms"] = sum(xs) / len(xs)
    return out


def tail_counts(rep: dict, names) -> str:
    """For each latency metric in ``names`` (``<kind>_p<NN>_ms`` or
    ``<kind>_mean_ms``): how many samples it was taken over and, for a
    percentile, how many lie beyond it (``stats.samples_beyond``; under
    ten, a maximum and no tail)."""
    out = []
    for name in names:
        kind, stat = name.split("_")[:2]
        n = rep["n_ttft" if kind == "ttft" else "n_gaps"]
        beyond = (f", {stats.samples_beyond(n, int(stat[1:]))} beyond it"
                  if stat != "mean" else "")
        out.append(f"{name} {rep[name]:.3f} ms over {n} samples{beyond}")
    return "; ".join(out)


def stall_report(served: List[Served], spans, t_start: float,
                 seconds: float, top: int = 3) -> str:
    """Where a window's tail came from: the longest silences between any
    two tokens of the window (all streams pooled; with streams running a
    silence is a step, or a step and the prefills of one admission) and
    the engine's longest spans, each with its offset into the window.
    A run that stalled is reported as one; no bound is widened for it."""
    t_end = t_start + seconds
    stamps = sorted(t for s in served for t in s.stamps
                    if t_start <= t < t_end)
    quiet = sorted(((b - a, a - t_start)
                    for a, b in zip(stamps, stamps[1:])), reverse=True)
    # (a step's span is recorded once a stream: one entry a start)
    longest = sorted({(round(d, 4), round(t0 - t_start, 2), n)
                      for n, t0, d, _a in spans if n != "queued"},
                     reverse=True)

    def some(rows, fmt):
        return ", ".join(fmt(r) for r in rows[:top]) or "none"

    return ("longest silences between tokens: "
            + some(quiet, lambda r: f"{1e3 * r[0]:.0f} ms at {r[1]:.1f} s")
            + f" ({sum(1 for r in quiet if r[0] > 0.5)} over 500 ms); "
            + "longest engine spans: "
            + some(longest, lambda r: f"{r[2]} {1e3 * r[0]:.0f} ms at "
                                      f"{r[1]:.1f} s"))


def bad_finishes(served: List[Served]) -> int:
    """How many of ``served`` did not end ``"length"`` with every token."""
    bad = 0
    for s in served:
        ok = s.done and s.handle.done()
        if ok:
            r = s.handle.result(timeout=60)
            ok = (r.finish_reason == "length"
                  and len(r.tokens) == s.prompt_len + s.max_new)
        bad += not ok
    return bad
