"""``kind: serve_backlog`` — every request due at t = 0, more than the
window can finish.  The measured quantity is work done inside the
window: the prompt tokens of every request whose first token arrived in
it (its prefill was done) plus every generated token that arrived in it,
over the window.  Counting whole requests instead moves in steps of a
slot batch: the engine admits 16 prompts back to back and retires them
together, 11 times in 51 s, so the count cannot see a change under 9 %
(PERF.md, PR 23).  ``attempted`` is the requests that finished inside
the window; what is still queued or running at its end is cancelled."""

from __future__ import annotations

import time

from benchmarks import serving, traffic
from benchmarks.common import Ctx, Result, say


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
        sl = serving.TraceSlice(ctx, t_start, ctx.seconds) if ctx.trace \
            else None
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
    return Result(
        correct=(sv.correct and compiles == 0 and failed == 0
                 and unfinished > 0),
        attempted=len(done), failed=failed,
        end_to_end={"serve_tokens_per_s": tokens / ctx.seconds,
                    "setup_s": t_start - ctx.t0},
        evidence=evidence, notes=notes)
