"""obs/compile.py: jax's monitoring events folded into one record an
executable.  The listener is fed synthetic events on a clock the test
moves (no real persistent cache: XLA:CPU's is off by default,
utils/compile_cache.py says why), then one real ``jax.jit`` on the CPU."""

import threading

import pytest

from megatron_llm_tpu.obs import compile as obs_compile
from megatron_llm_tpu.obs.compile import CompileLog
from megatron_llm_tpu.obs.registry import MetricsRegistry

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


class Feed:
    """A ``CompileLog`` on a hand-moved clock.  ``stage`` lets ``seconds``
    pass and then reports the stage, as jax does at a stage's end."""

    def __init__(self, **kw):
        self.clock = Clock()
        self.finished = []
        self.log = CompileLog(clock=self.clock, sink=self.finished.append,
                              **kw)

    def stage(self, event, seconds, name, inside=()):
        for step in inside:        # what runs while this stage is open
            step()
        self.clock.now += seconds
        self.log.on_duration(event, seconds + sum(self._took(inside)),
                             fun_name=name)

    def _took(self, inside):
        return [getattr(step, "seconds", 0.0) for step in inside]

    def executable(self, name, trace=0.5, lower=0.25, backend=2.0,
                   cache=None, retrieval=0.125):
        self.stage(TRACE, trace, name)
        self.stage(LOWER, lower, f"jit({name})")

        def cache_events():
            if cache is not None:
                self.log.on_event(cache)
            if cache == HIT:
                self.log.on_duration(RETRIEVAL, retrieval)

        self.stage(BACKEND, backend, f"jit({name})", inside=[cache_events])


def test_stages_fold_into_one_record_by_thread():
    f = Feed()
    f.executable("step")
    (rec,) = f.log.records()
    assert rec["program"] == "jit(step)" and rec["seq"] == 1
    assert (rec["trace_s"], rec["lower_s"], rec["backend_s"]) == \
        (0.5, 0.25, 2.0)
    assert rec["t0"] == pytest.approx(100.0) and \
        rec["t1"] == pytest.approx(102.75)
    assert rec["thread"] == threading.get_ident()
    assert f.finished == [rec]          # one line an executable
    # another thread's stages fold among themselves
    other = threading.Thread(target=f.stage, args=(TRACE, 1.0, "theirs"))
    other.start()
    other.join(10)
    f.stage(LOWER, 0.25, "jit(theirs)")   # this thread traced no such thing
    held = [r for r in f.log.records() if r["seq"] is None]
    assert sorted((r["program"], r["trace_s"], r["lower_s"])
                  for r in held) == [("jit(theirs)", None, 0.25),
                                     ("theirs", 1.0, None)]


@pytest.mark.parametrize("event, cache, retrieval", [
    (HIT, "hit", 0.125), (MISS, "miss", None), (None, "off", None)])
def test_cache_outcome_comes_from_the_events_inside_the_backend_stage(
        event, cache, retrieval):
    f = Feed()
    f.executable("step", cache=event)
    (rec,) = f.log.records()
    assert rec["cache"] == cache and rec["retrieval_s"] == retrieval
    # an outcome seen before the stage began is not this executable's
    f.log.on_event(HIT)
    f.clock.now += 1.0
    f.executable("next")
    assert f.log.records()[-1]["cache"] == "off"


def test_a_lone_trace_stage_is_kept_and_a_nested_one_absorbed():
    f = Feed()
    f.stage(TRACE, 0.5, "shape_only")              # eval_shape: no successor

    def inner(name):
        def run():
            f.stage(TRACE, 0.125, name)
        run.seconds = 0.125
        return run

    # two jitted functions called while ``outer`` is traced
    f.stage(TRACE, 1.0, "outer", inside=[inner("sin"), inner("matmul")])
    f.stage(LOWER, 0.25, "jit(outer)")
    f.stage(BACKEND, 2.0, "jit(outer)")
    recs = f.log.records()
    assert [(r["program"], r["trace_s"], r["backend_s"]) for r in recs] == [
        ("jit(outer)", 1.25, 2.0), ("shape_only", 0.5, None)]
    assert recs[1]["seq"] is None and recs[1]["cache"] is None
    assert f.log.absorbed == 2 and f.log.callbacks == 6
    assert obs_compile.stage_seconds(recs[1]) == {"trace": 0.5}


def test_a_stage_counts_its_own_seconds_only():
    """An executable built while another is traced (eager work in a
    lowering rule, ``ensure_compile_time_eval``) is a record of its own,
    and the outer stage's seconds leave its interval out."""
    f = Feed()

    def eager():
        f.executable("eager", trace=0.25, lower=0.25, backend=0.5)
    eager.seconds = 1.0

    f.stage(TRACE, 2.0, "outer", inside=[eager])
    f.stage(LOWER, 0.5, "jit(outer)")
    f.stage(BACKEND, 4.0, "jit(outer)")
    inner, outer = f.log.records()
    assert inner["program"] == "jit(eager)"
    assert outer["trace_s"] == pytest.approx(2.0)
    assert sum(sum(obs_compile.stage_seconds(r).values())
               for r in (inner, outer)) == pytest.approx(7.5)
    assert outer["t1"] - outer["t0"] == pytest.approx(7.5)


def test_ring_drops_the_oldest_and_counts_it():
    f = Feed(capacity=3)
    for i in range(5):
        f.executable(f"p{i}")
    assert [r["program"] for r in f.log.records()] == [
        "jit(p2)", "jit(p3)", "jit(p4)"]
    assert f.log.dropped == 2 and f.log.seq == 5 and f.log.executables == 5
    # the counters are cumulative: they outlive the ring
    reg = MetricsRegistry()
    reg.register_collector("compile", f.log.families)
    text = reg.prometheus_text()
    assert 'compilations_total{program="jit(p0)",cache="off"} 1' in text
    assert 'compile_seconds_total{program="jit(p4)",stage="backend"} 2' \
        in text
    seq, since = f.log.executables_since(3)
    assert seq == 5 and since == {"jit(p3)": 1, "jit(p4)": 1}
    assert f.log.last_backend_end() == pytest.approx(f.clock.now)
    assert f.log.last_backend_end(thread_ident=-1) == 0.0


def test_a_real_jit_is_one_record_named_after_the_function():
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.obs.logging import EVENT_LOG

    log = obs_compile.install()
    assert obs_compile.install() is log          # idempotent
    x = jnp.ones((3, 5))

    @jax.jit
    def only_here_in_test_compile(a):
        return jnp.tanh(a) @ a.T

    seq = log.seq
    only_here_in_test_compile(x).block_until_ready()
    mine = [r for r in log.records()
            if "only_here_in_test_compile" in r["program"]]
    assert [r["program"] for r in mine] == ["jit(only_here_in_test_compile)"]
    (rec,) = mine
    assert rec["seq"] > seq and rec["cache"] in ("off", "miss", "hit")
    assert set(obs_compile.stage_seconds(rec)) == {"trace", "lower",
                                                   "backend"}
    assert rec["t0"] < rec["t1"]
    lines = [l for l in EVENT_LOG.recent(event="compile")
             if l["program"] == rec["program"]]
    assert len(lines) == 1 and lines[0]["component"] == "obs"
    assert set(lines[0]["stage_s"]) == {"trace", "lower", "backend"}
    # the second call finds its executable: no record, no line
    seq, n = log.seq, log.executables
    only_here_in_test_compile(x).block_until_ready()
    assert (log.seq, log.executables) == (seq, n)


def test_threads_fold_their_own_stages_under_contention():
    """More compiling threads than cores, a short switch interval: every
    executable is one record made of its own thread's stages, and the
    totals lose no update."""
    import sys

    now = threading.local()             # every thread moves its own clock
    log = CompileLog(capacity=4096, clock=lambda: now.t)
    threads, each = 16, 100

    def compile_many(k):
        now.t = 1000.0 * k
        for i in range(each):
            name = f"t{k}_{i}"
            for event, stage_name in ((TRACE, name), (LOWER, f"jit({name})"),
                                      (BACKEND, f"jit({name})")):
                if event == BACKEND:
                    log.on_event(MISS if i % 2 else HIT)
                now.t += 0.25
                log.on_duration(event, 0.25, fun_name=stage_name)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=compile_many, args=(k,))
                   for k in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(60)
    finally:
        sys.setswitchinterval(was)
    assert not any(w.is_alive() for w in workers)
    recs = log.records()
    assert log.executables == threads * each == len(recs)
    assert log.callbacks == 3 * threads * each and log.absorbed == 0
    assert {r["program"] for r in recs} == {
        f"jit(t{k}_{i})" for k in range(threads) for i in range(each)}
    for r in recs:
        assert None not in (r["trace_s"], r["lower_s"], r["backend_s"])
        i = int(r["program"][:-1].rsplit("_", 1)[1])
        assert r["cache"] == ("miss" if i % 2 else "hit")
    assert sorted(r["seq"] for r in recs) == list(range(1, len(recs) + 1))
