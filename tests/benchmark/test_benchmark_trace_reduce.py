"""The reduction from a profiler trace to metrics, on the synthetic trace
beside this file (``data/synthetic_xplane.txt``, an XSpace text proto;
its comments in the generator say what it holds, times in microseconds):

device 0 runs ``jit_step`` over [100,400) and [500,800) and ``jit_other``
over [850,860).  In a step, ``%while`` [t,t+200) encloses ``%fusion.1``
[t+10,t+100), ``%all-reduce`` [t+100,t+160) and ``%fusion.2``
[t+140,t+200); ``%all-gather-start`` runs alone over [t+220,t+280).
Device 1 is busy through both steps.
"""

from pathlib import Path

import pytest

from benchmarks import trace_reduce as T

US = 1e3    # ns in a microsecond


@pytest.fixture(scope="module")
def trace():
    text = (Path(__file__).parent / "data" / "synthetic_xplane.txt").read_text()
    return T.load(text_proto=text)


def test_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == [(0, 3), (5, 8)]
    assert T.length([(0, 3), (5, 8)]) == 6
    assert T.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert T.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == [
        (0, 2), (4, 8), (22, 29)]
    assert T.subtract([(0, 10)], []) == [(0, 10)]
    assert T.hlo_name("%fusion.12 = bf16[8]{0} fusion(x)") == "%fusion.12"


def test_planes_and_lines_are_found(trace):
    assert sorted(trace.ops) == [0, 1] and sorted(trace.modules) == [0, 1]
    assert len(trace.ops[0]) == 11 and len(trace.modules[0]) == 3
    assert {e.name for e in trace.host} >= {"bench_sync",
                                            "$driver.py:197 _put_batch"}


def test_windows(trace):
    assert T.window_of(trace) == (100 * US, 860 * US)
    assert T.window_of(trace, "jit_step", whole_periods=True) == (
        100 * US, 500 * US)
    with pytest.raises(ValueError):
        T.window_of(trace, "jit_other", whole_periods=True)


def test_busy_and_idle(trace):
    w = T.window_of(trace, "jit_step", whole_periods=True)    # 400 us
    busy = T.busy_seconds(trace, w)
    # device 0: while [100,300) + all-gather [320,380) = 260 us
    assert busy[0] == pytest.approx(260e-6) and busy[1] == pytest.approx(300e-6)
    assert T.idle_share(trace, w) == pytest.approx(100 * (1 - 260 / 400))
    whole = T.busy_seconds(trace, T.window_of(trace))
    assert whole[0] == pytest.approx((260 + 260 + 10) * 1e-6)


def test_per_executable_time(trace):
    per = T.module_seconds(trace)
    assert per["jit_step"] == (2, pytest.approx(600e-6))
    assert per["jit_other"] == (1, pytest.approx(10e-6))
    w = T.window_of(trace, "jit_step", whole_periods=True)
    assert T.module_seconds(trace, w) == {"jit_step": (1, pytest.approx(300e-6))}


def test_self_time_and_top_ops(trace):
    own = {T.hlo_name(e.name): (s, leaf)
           for e, s, leaf in T.self_times(trace.ops[0][:5])}
    # the while's own time: 200 less its three children (90 + 60 + 60)
    assert own["%while.1"] == (pytest.approx(-10 * US), False)
    assert own["%fusion.1"] == (90 * US, True)
    top = T.top_ops(trace, T.window_of(trace), n=2)
    assert top[0][0].startswith("%fusion.1") and top[0][1] == pytest.approx(
        190e-6)        # 90 + 90 + the 10 us of jit_other's run
    assert len(top) == 2


def test_exposed_collectives(trace):
    w = T.window_of(trace, "jit_step", whole_periods=True)
    # all-reduce [200,260) is hidden over [240,260) by fusion.2: 40 exposed;
    # all-gather-start [320,380) runs alone: 60 exposed; device 1 has none
    assert T.collective_exposed_share(trace, w) == pytest.approx(
        100 * (40 + 60) / 400)


def test_idle_gaps_take_the_name_of_what_the_host_did(trace):
    w = T.window_of(trace, "jit_step", whole_periods=True)
    gaps = dict(T.idle_gaps(trace, w))
    # [380,500): _put_batch [440,510) overlaps it most among the events
    # no longer than twice the gap; [300,320): nothing that short, so the
    # shortest event that covers it whole
    assert gaps == {"$driver.py:197 _put_batch": pytest.approx(120e-6),
                    "PjitFunction(step)": pytest.approx(20e-6)}
    spans = [T.Event("engine_step", 370 * US, 520 * US)]
    assert dict(T.idle_gaps(trace, w, spans)) == {
        "engine_step": pytest.approx(120e-6),
        "PjitFunction(step)": pytest.approx(20e-6)}
