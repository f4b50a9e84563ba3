"""benchmarks/readers/startup.py on hand-made compilation records: a cold
and a warm start, the ``setup`` span's own time, and the cut at the traced
phase's beginning."""

import pytest

from benchmarks.readers import startup


def rec(program, t0, trace=None, lower=None, backend=None, cache=None):
    t1 = t0 + sum(s or 0.0 for s in (trace, lower, backend))
    return {"seq": 1, "program": program, "t0": t0, "t1": t1,
            "trace_s": trace, "lower_s": lower, "backend_s": backend,
            "cache": cache, "retrieval_s": None, "thread": 1}


def start(cache, backend_of):
    """Two prefill widths and a decode step, plus a function that was only
    traced (``eval_shape``) and an AOT lowering that nobody compiled."""
    return [
        rec("jit(_prefill_impl)", 10.0, 2.0, 1.0, backend_of(40.0), cache),
        rec("jit(_prefill_impl)", 60.0, 2.5, 1.5, backend_of(50.0), cache),
        rec("jit(_decode_impl)", 120.0, 1.0, 0.5, backend_of(20.0), cache),
        rec("init_params", 5.0, trace=0.75),
        rec("jit(step)", 7.0, trace=0.5, lower=0.25),
    ]


COLD = start("miss", lambda s: s)
WARM = start("hit", lambda s: s / 100.0)


@pytest.mark.parametrize("records, want", [
    (COLD, {"backend_s": 110.0, "trace_lower_s": 10.0, "cache_misses": 3,
            "executables": 3}),
    (WARM, {"backend_s": 1.1, "trace_lower_s": 10.0, "cache_misses": 0,
            "executables": 3}),
    # the cache was not asked (XLA:CPU, or a process that turned it off):
    # a start that built everything
    (start("off", lambda s: s), {"backend_s": 110.0, "trace_lower_s": 10.0,
                                 "cache_misses": 3, "executables": 3}),
    ([], {"backend_s": 0, "trace_lower_s": 0, "cache_misses": 0,
          "executables": 0}),
])
def test_sums_of_a_cold_and_a_warm_start(records, want):
    got = startup.summarize(records)
    assert got == pytest.approx(want)
    # what a warm start pays again is the same on both
    assert startup.summarize(COLD)["trace_lower_s"] == \
        startup.summarize(WARM)["trace_lower_s"]


def test_own_time_leaves_out_what_the_records_cover():
    span = (100.0, 160.0)
    inside = rec("jit(init_opt_state)", 110.0, 1.0, 1.0, 8.0, "miss")
    straddles = rec("jit(step)", 155.0, 2.0, 2.0, 6.0, "miss")  # to 165
    before = rec("jit(init_params)", 50.0, 1.0, 1.0, 10.0, "miss")
    assert startup.own_time(span, [inside, straddles, before]) == \
        pytest.approx(60.0 - 10.0 - 5.0)
    assert startup.own_time(span, []) == pytest.approx(60.0)
    assert startup.own_time((0.0, 1.0), [rec("x", 0.0, 5.0)]) == 0.0


def test_the_table_sums_executables_of_one_name():
    text = startup.table(COLD, top=2)
    assert text.startswith("jit(_prefill_impl) x2: trace 4.50 lower 2.50 "
                           "backend 90.00 s 2 miss")
    assert "jit(_decode_impl) x1" in text and "init_params" not in text


def test_uncovered_names_the_stretches_between_records():
    text = startup.uncovered(COLD, t_start=0.0, top=2)
    # [5, 5.75] [7, 7.75] [10, 53] [60, 114] [120, 141.5]
    assert text.startswith(
        "7.00 s (53.0-60.0) between jit(_prefill_impl) and "
        "jit(_prefill_impl); 6.00 s (114.0-120.0) between "
        "jit(_prefill_impl) and jit(_decode_impl); ")
    assert text.endswith("the last record ends 141.5 s after the start")
    assert "5.00 s (0.0-5.0) between the process's start and init_params" \
        in startup.uncovered(COLD, t_start=0.0)


class _Log:
    callbacks, absorbed = 40, 30

    def __init__(self, records):
        self._records = records

    def records(self):
        return self._records


def test_records_after_the_traced_phase_began_are_left_out(monkeypatch,
                                                           capsys):
    """``read`` through the program's own modules: a record that ended
    after the profile session's ``t_sync`` is the traced phase's, not the
    start's; the train loop's ``setup`` span comes from ``TRAIN_TRACE``."""
    from megatron_llm_tpu.obs import compile as obs_compile
    from megatron_llm_tpu.obs import profile
    from megatron_llm_tpu.obs.trace import TRAIN_TRACE

    late = rec("jit(replayed_width)", 500.0, 1.0, 1.0, 30.0, "miss")
    monkeypatch.setattr(obs_compile, "COMPILES", _Log(COLD + [late]))
    monkeypatch.setattr(profile, "_last", profile.Session("d", t_sync=400.0))
    monkeypatch.setattr(profile, "_active", None)
    evidence = {}
    read = lambda stat: startup.read(evidence, {"stat": stat})  # noqa: E731
    assert read("executables") == 3
    assert read("cache_misses") == 3
    assert read("backend_s") == pytest.approx(110.0)
    assert read("trace_lower_s") == pytest.approx(10.0)
    out = capsys.readouterr().out
    assert out.count("the start's compilations") == 1     # said once a run
    assert "40 stage events" in out and "jit(_prefill_impl) x2" in out
    # no session ran: every record is the start's
    monkeypatch.setattr(profile, "_last", None)
    assert startup.read({}, {"stat": "executables"}) == 4

    # the setup span [t, t + 50] holds one record of 43.0 s
    monkeypatch.setattr(TRAIN_TRACE, "_events", TRAIN_TRACE._events.copy())
    TRAIN_TRACE.clear()
    assert startup.read({}, {"stat": "state_init_s"}) is None
    was, TRAIN_TRACE.enabled = TRAIN_TRACE.enabled, True
    try:
        TRAIN_TRACE.add("setup", 5.0, 55.0)
    finally:
        TRAIN_TRACE.enabled = was
    # [5, 55] less init_params [5, 5.75], jit(step) [7, 7.75] and the
    # first prefill [10, 53]
    assert startup.read({}, {"stat": "state_init_s"}) == \
        pytest.approx(50.0 - 0.75 - 0.75 - 43.0)
    TRAIN_TRACE.clear()


def test_a_program_without_the_records_reads_nothing(monkeypatch):
    import builtins

    real = builtins.__import__

    def no_compile_module(name, globals=None, locals=None, fromlist=(),
                          level=0):
        if name == "megatron_llm_tpu.obs" and "compile" in (fromlist or ()):
            raise ImportError("cannot import name 'compile'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_compile_module)
    for stat in ("backend_s", "trace_lower_s", "cache_misses", "executables",
                 "state_init_s"):
        assert startup.read({}, {"stat": stat}) is None
