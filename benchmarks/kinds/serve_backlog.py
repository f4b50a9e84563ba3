"""``kind: serve_backlog`` — every request due at t = 0, more than the
window can finish.  The measured quantity is work done inside the
window: the prompt tokens of every request whose first token arrived in
it (its prefill was done) plus every generated token that arrived in it,
over the window.  Counting whole requests instead moves in steps of a
slot batch: the engine admits 16 prompts back to back and retires them
together, 11 times in 51 s, so the count cannot see a change under 9 %
(PERF.md, PR 23).  ``attempted`` is the requests that finished inside
the window; what is still queued or running at its end is cancelled.

``--trace 2``: the counts are taken at the window's end as always; the
backlog, which outlasts the window by construction, is then left running
under the program's profile session for one slot batch of prefills more
(``traced_prefills``), and cancelled after that."""

from __future__ import annotations

import time

from benchmarks import common, serving, traffic
from benchmarks.common import Ctx, Result, say


def traced_prefills(ctx: Ctx, sv: serving.Serving, served) -> dict:
    """Profile the running backlog over a fixed count of prefills — one
    slot batch, so the engine's whole period of admitting and decoding —
    and not over a fixed time: the traced window runs from the start of
    one ``prefill`` span to the start of the one ``count`` later.  A
    prefill's device work lies inside its span (the span ends with the
    first token on the host), so the window holds exactly the prefills
    whose tokens it counts; a window cut by the clock holds a prefill
    more or less than it counts every few runs, which is 6 % of a
    dozen."""
    from megatron_llm_tpu.obs import profile

    count = int(sv.engine_kw["max_batch_size"])

    def first_tokens() -> int:
        return sum(1 for s in served if s.stamps)

    session = profile.start(ctx.trace_dir)
    # count + 2 prefills ended since the start: at most one of them began
    # before it, so count + 1 began under the session
    n0, limit = first_tokens(), session.t_sync + 4 * serving.TraceSlice.SECONDS
    want = min(n0 + count + 2, len(served))
    while first_tokens() < want and time.perf_counter() < limit:
        time.sleep(0.005)
    session = common.stop_profiler()
    begun = sorted(t0 for n, t0, _d, _a in serving.recorder_spans(
        sv.engine, session.t_sync, session.t_stop) if n == "prefill")
    if len(begun) < 2:
        say("fewer than two prefills began under the session (the backlog "
            "had run out): the traced window is the whole session")
        return serving.traced_phase_evidence(sv, session)
    begun = begun[:count + 1]
    say(f"traced window: {len(begun) - 1} prefills, from the start of one "
        f"to the start of the next after them, "
        f"{begun[-1] - begun[0]:.2f} s")
    return serving.traced_phase_evidence(sv, session,
                                         (begun[0], begun[-1]))


def run(ctx: Ctx):
    sv = serving.Serving(ctx)
    mix = sv.mix
    requests = traffic.serve_requests(mix, ctx.seed, ctx.seconds,
                                      sv.model.vocab_size)
    served = []
    try:
        sv.prepare()
        c0 = ctx.clock.backend_compiles
        t_start = time.perf_counter()
        sl = serving.TraceSlice(ctx, t_start, ctx.seconds) \
            if ctx.trace == 1 else None
        for r in requests:
            s = serving.Served(t_start, len(r.prompt), r.max_new_tokens, True)
            sv.submit(s, r.prompt)
            served.append(s)
        t_sent = time.perf_counter()
        time.sleep(max(0.0, t_start + ctx.seconds - time.perf_counter()))
        t_end = t_start + ctx.seconds
        done = [s for s in served if s.done and s.stamps[-1] <= t_end]
        compiles = ctx.clock.backend_compiles - c0
        if sl is not None:
            sl.join()
        unfinished = len(served) - sum(s.done for s in served)
        evidence = serving.layer_evidence(sv, sl, (t_start, t_end))
        if ctx.trace == 2:
            # the window's numbers exist (stamps up to t_end decide the
            # rest); from here on nothing is counted
            untouched = common.before_traced_phase()
            evidence = serving.window_evidence(
                sv, serving.recorder_spans(sv.engine, t_start, t_end))
            evidence.update(traced_prefills(ctx, sv, served))
    finally:
        sv.close(served)
    failed = serving.bad_finishes(done)
    prompt_tokens = sum(s.prompt_len for s in served
                        if s.stamps and s.stamps[0] <= t_end)
    new_tokens = sum(sum(t <= t_end for t in s.stamps) for s in served)
    tokens = prompt_tokens + new_tokens
    say(f"window: {prompt_tokens} prompt tokens prefilled and {new_tokens} "
        f"tokens generated in {ctx.seconds:.0f} s; {len(done)} of "
        f"{len(served)} requests finished; all were submitted "
        f"{1e3 * (t_sent - t_start):.1f} ms after it began; {unfinished} "
        f"were still queued or running at its end")
    notes = sv.correct_notes + [
        f"compilations inside the window: {compiles}",
        f"finished requests that did not end 'length' with every token: "
        f"{failed} of {len(done)}",
        f"the backlog outlasted the window: {unfinished > 0}"]
    if ctx.trace == 2:
        notes.append(untouched)
    return Result(
        correct=(sv.correct and compiles == 0 and failed == 0
                 and unfinished > 0),
        attempted=len(done), failed=failed,
        end_to_end={"serve_tokens_per_s": tokens / ctx.seconds,
                    "setup_s": t_start - ctx.t0},
        evidence=evidence, notes=notes,
        compared={**sv.compared, "compiles_in_window": (compiles, 0),
                  "bad_finishes": (failed, 0),
                  "backlog_ran_out": (int(unfinished == 0), 0)})
