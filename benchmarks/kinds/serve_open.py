"""``kind: serve_open`` — an open loop at a fixed rate.

Poisson arrivals at the mix's ``rate_rps`` start ``lead_s`` before the
measured window, so that it opens in steady state; a request counts if
it was due inside the window, and one that has not finished ``drain_s``
after the window's end has failed.  The load generator is this thread;
how late it ran is printed.  ``--sweep`` runs the mix's ``sweep_rates``
one after another on one set-up and prints the table the knee is read
from.

``--trace 2``: once the counted requests have finished (or hit the drain
limit) and the latencies are computed, the schedule is offered again
from its start, shifted to now, up to the end of the slice that
``--trace 1`` traces — [0.4 S, 0.4 S + 3 s] — and the program's profile
session runs over that slice.  The same arrivals since the same empty
start put the engine where the measured window had it: the same slots
busy, the same requests queued.  Nothing in the replay is counted.
"""

from __future__ import annotations

import time

from benchmarks import common, serving, stats, traffic
from benchmarks.common import Ctx, Result, say


def drive(sv: serving.Serving, requests, t_start: float, seconds: float,
          drain_s: float):
    """Submit ``requests`` when due (relative to ``t_start``), wait for
    the counted ones → (served, lateness in s, queue depths)."""
    served, late = [], []
    depth = {}
    for r in requests:
        due = t_start + r.due_s
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if "start" not in depth and r.due_s >= 0:
            depth["start"] = len(sv.engine.queue)
        s = serving.Served(due, len(r.prompt), r.max_new_tokens,
                           0.0 <= r.due_s < seconds)
        sv.submit(s, r.prompt)
        late.append(s.submitted - due)
        served.append(s)
    time.sleep(max(0.0, t_start + seconds - time.perf_counter()))
    depth["end"] = len(sv.engine.queue)
    depth.setdefault("start", 0)
    sv.wait_idle([s for s in served if s.counted],
                 t_start + seconds + drain_s)
    return served, late, depth


def traced_replay(ctx: Ctx, sv: serving.Serving, requests, served):
    """Offer the schedule again up to the traced slice's end and profile
    the slice → the session.  The prompts keep their lengths and get new
    tokens: one offered twice would be found in the prefix cache.  The
    load generator and the session's start and stop share this thread:
    nothing here is counted, so a submission held up by the profiler's
    start costs nothing."""
    from megatron_llm_tpu.obs import profile

    begin = 0.4 * ctx.seconds
    length = min(serving.TraceSlice.SECONDS, begin)
    again = [r for r in requests if r.due_s <= begin + length]
    rng, vocab = traffic.host_seed(ctx.seed, 5), sv.model.vocab_size
    t0 = time.perf_counter() + float(sv.mix["lead_s"]) + 0.1
    session = None
    for r in again + [None]:
        at = r.due_s if r is not None else begin + length
        if session is None and at >= begin:
            time.sleep(max(0.0, t0 + begin - time.perf_counter()))
            session = profile.start(ctx.trace_dir)
        time.sleep(max(0.0, t0 + at - time.perf_counter()))
        if r is not None:
            s = serving.Served(t0 + at, len(r.prompt), r.max_new_tokens,
                               False)
            sv.submit(s, rng.integers(1, vocab - 1,
                                      size=len(r.prompt)).tolist())
            served.append(s)
    say(f"traced replay: the schedule's {len(again)} requests up to "
        f"{begin + length:.1f} s offered again with new tokens, its last "
        f"{length:.1f} s under the profile session")
    return common.stop_profiler()


def sweep(ctx: Ctx, sv: serving.Serving) -> None:
    mix = sv.mix
    seconds = float(mix["sweep_seconds"])
    cols = ("ttft_p50_ms", "ttft_p95_ms", "itl_p50_ms", "itl_p95_ms")
    more = ("ttft_p90_ms", "itl_p99_ms")
    say("sweep: rate_rps offered completed_in_window completed_rps "
        f"queue_start queue_end {' '.join(cols)} unfinished late_max_ms "
        f"{' '.join(more)} n_ttft n_gaps")
    for rate in mix["sweep_rates"]:
        reqs = traffic.serve_requests(mix, ctx.seed, seconds,
                                      sv.model.vocab_size, rate_rps=rate)
        t_start = time.perf_counter() + float(mix["lead_s"]) + 0.2
        served, late, depth = drive(sv, reqs, t_start, seconds,
                                    float(mix["drain_s"]))
        counted = [s for s in served if s.counted]
        in_window = sum(1 for s in served if s.done
                        and t_start <= s.stamps[-1] < t_start + seconds)
        rep = serving.latency_report(counted)

        def ms(names):
            return " ".join(f"{rep.get(n, float('nan')):.1f}" for n in names)

        say(f"sweep: {rate} {len(counted)} {in_window} "
            f"{in_window / seconds:.3f} {depth['start']} {depth['end']} "
            f"{ms(cols)} {sum(not s.done for s in counted)} "
            f"{1e3 * max(late):.1f} {ms(more)} {rep['n_ttft']} "
            f"{rep['n_gaps']}")
        sv.wait_idle(served, time.perf_counter() + 120.0)


def run(ctx: Ctx):
    sv = serving.Serving(ctx)
    mix = sv.mix
    requests = traffic.serve_requests(mix, ctx.seed, ctx.seconds,
                                      sv.model.vocab_size)
    served = []
    try:
        sv.prepare()
        if ctx.sweep:
            sweep(ctx, sv)
            return None
        lead = float(mix["lead_s"])
        c0 = ctx.clock.backend_compiles
        t_start = time.perf_counter() + lead + 0.1
        sl = serving.TraceSlice(ctx, t_start, ctx.seconds) \
            if ctx.trace == 1 else None
        served, late, depth = drive(sv, requests, t_start, ctx.seconds,
                                    float(mix["drain_s"]))
        compiles = ctx.clock.backend_compiles - c0
        if sl is not None:
            sl.join()
        counted = [s for s in served if s.counted]
        failed = serving.bad_finishes(counted)
        evidence = serving.layer_evidence(
            sv, sl, (t_start, t_start + ctx.seconds))
        rep = serving.latency_report(counted)
        spans = serving.recorder_spans(sv.engine, t_start,
                                       t_start + ctx.seconds)
        stalls = serving.stall_report(served, spans, t_start, ctx.seconds)
        if ctx.trace == 2:
            untouched = common.before_traced_phase()
            evidence = serving.window_evidence(sv, spans)
            evidence.update(serving.traced_phase_evidence(
                sv, traced_replay(ctx, sv, requests, served)))
        evidence["latency"] = rep      # the percentiles a cell only records
    finally:
        sv.close(served)
    judged = [m["name"] for m in ctx.manifest.metrics_of(
        ctx.cell["name"], "end_to_end") if m["name"] != "setup_s"]

    def ladder(kind, fmt):
        return " ".join([f"p{p} {rep[f'{kind}_p{p}_ms']:{fmt}}"
                         for p in serving.PERCENTILES]
                        + [f"mean {rep[f'{kind}_mean_ms']:.3f}"])

    say(f"window: {len(counted)} requests due in {ctx.seconds:.0f} s at "
        f"{mix['rate_rps']} a second (of {len(served)} sent); ttft "
        f"{ladder('ttft', '.1f')} ms; gap {ladder('itl', '.2f')} ms")
    say(f"load generator ran late by at most {1e3 * max(late):.2f} ms "
        f"(p95 {1e3 * stats.percentile(late, 95):.2f} ms); queue depth "
        f"{depth['start']} at the window's start, {depth['end']} at its end")
    worst = max(range(len(late)), key=late.__getitem__)
    say(f"its latest submission was the one due at "
        f"{served[worst].due - t_start:.1f} s; {stalls}")
    notes = sv.correct_notes + [
        f"n_ttft {rep['n_ttft']}, n_gaps {rep['n_gaps']}; "
        + serving.tail_counts(rep, judged),
        f"compilations inside the window: {compiles}",
        f"requests that did not end 'length' with every token inside the "
        f"drain limit: {failed} of {len(counted)}"]
    if ctx.trace == 2:
        notes.append(untouched)
    return Result(
        correct=sv.correct and compiles == 0 and failed == 0,
        attempted=len(counted), failed=failed,
        end_to_end={**{name: rep[name] for name in judged},
                    "setup_s": t_start - ctx.t0},
        evidence=evidence, notes=notes,
        compared={**sv.compared, "compiles_in_window": (compiles, 0),
                  "bad_finishes": (failed, 0)})
