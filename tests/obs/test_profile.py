"""obs/profile.py: the process's one profiler session — start/stop,
the clock-sync pair that joins TraceRecorder spans to the profile, the
recorders it switches, and the step-trace request a train loop polls."""

import glob
import os
import time

import pytest

from megatron_llm_tpu.obs import profile
from megatron_llm_tpu.obs.trace import TRAIN_TRACE, TraceRecorder
from megatron_llm_tpu.utils.timers import Timers


@pytest.fixture(autouse=True)
def no_session_left_over():
    assert profile.active() is None
    yield
    if profile.active() is not None:
        profile.stop()
    profile.cancel_step_request()


def xplanes(d):
    return glob.glob(os.path.join(str(d), "plugins", "profile", "*",
                                  "*.xplane.pb"))


def host_events(d):
    from jax.profiler import ProfileData

    (path,) = xplanes(d)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_session_twice_in_one_process(tmp_path):
    t0 = time.perf_counter()
    first = profile.start(tmp_path / "a")
    assert profile.active() is first and profile.last() is first
    closed = profile.stop()
    assert closed is first and profile.active() is None
    assert t0 <= first.t_sync <= first.t_stop
    second = profile.start(tmp_path / "b")
    profile.stop()
    assert second is not first and profile.last() is second
    assert xplanes(tmp_path / "a") and xplanes(tmp_path / "b")


def test_second_start_is_refused_and_leaves_the_first_running(tmp_path):
    first = profile.start(tmp_path / "a")
    with pytest.raises(RuntimeError, match="already"):
        profile.start(tmp_path / "b")
    assert profile.active() is first
    profile.stop()
    with pytest.raises(RuntimeError, match="no profile session"):
        profile.stop()
    assert not (tmp_path / "b").exists()


@pytest.mark.parametrize("was_on", [False, True])
def test_registered_recorder_is_on_only_while_profiling(tmp_path, was_on):
    rec = TraceRecorder(enabled=was_on)
    profile.while_profiling(rec)
    profile.start(tmp_path)
    assert rec.enabled and TRAIN_TRACE.enabled
    profile.stop()
    # put back as it was: a reader's own switch outlives the session
    assert rec.enabled is was_on and TRAIN_TRACE.enabled is False


def test_recorder_exports_its_epoch_and_the_last_sync_pair(tmp_path):
    rec = TraceRecorder()
    before = time.perf_counter()
    rec.add("queued", before, before + 0.25)
    session = profile.start(tmp_path)
    profile.stop()
    other = rec.chrome_trace()["otherData"]
    (ev,) = rec.chrome_trace()["traceEvents"]
    # ts stays relative to the epoch; the epoch says where that is
    assert other["epoch_perf_counter"] + ev["ts"] / 1e6 == \
        pytest.approx(before, abs=1e-6)
    assert other["clock_sync"] == {
        "annotation": "obs_clock_sync", "perf_counter": session.t_sync,
        "stop_perf_counter": session.t_stop, "dir": str(tmp_path)}


@pytest.mark.parametrize("t_perf,want_ns", [
    (100.0, 5_000.0), (100.001, 1_005_000.0), (99.9995, -495_000.0)])
def test_to_trace_ns_on_a_synthetic_clock(t_perf, want_ns):
    """The annotation started 5 us into the trace; perf_counter read
    100.0 s inside it."""
    assert profile.to_trace_ns(t_perf, 100.0, 5_000.0) == \
        pytest.approx(want_ns, abs=1.0)


def test_retrospective_span_lands_where_its_annotation_twin_does(tmp_path):
    """One block recorded both ways: as a TraceAnnotation in the profile
    and, after the fact, as an add() span on the perf_counter clock.  The
    sync pair maps the second onto the first."""
    rec = TraceRecorder()
    session = profile.start(tmp_path)
    with rec.span("twin", annotate=True):
        time.sleep(0.02)
    t0 = time.perf_counter()
    time.sleep(0.01)
    rec.add("late", t0, time.perf_counter())      # never annotated
    profile.stop()
    events = host_events(tmp_path)
    (sync,) = [e for e in events if e[0] == profile.SYNC_NAME]
    (twin,) = [e for e in events if e[0] == "twin"]
    assert not [e for e in events if e[0] == "late"]
    doc = rec.chrome_trace()
    epoch = doc["otherData"]["epoch_perf_counter"]
    span = next(e for e in doc["traceEvents"] if e["name"] == "twin")
    start = profile.to_trace_ns(epoch + span["ts"] / 1e6, session.t_sync,
                                sync[1])
    # the two clocks are read a few microseconds apart
    assert abs(start - twin[1]) < 2e6
    assert abs(span["dur"] * 1e3 - (twin[2] - twin[1])) < 2e6


# --- step-trace requests ------------------------------------------------------

@pytest.mark.parametrize("steps,first,next_step,want", [
    (3, None, 7, (3, 7)),        # the next three
    (3, 9, 7, None),             # not yet due: stays pending
    (3, 7, 7, (3, 7)),           # due now
    (3, 6, 7, (2, 7)),           # resumed inside the window: the rest
    (3, 4, 7, "dropped"),        # resumed past it
])
def test_take_step_request(steps, first, next_step, want):
    profile.request_steps(steps, "/tmp/x", first=first)
    got = profile.take_step_request(next_step)
    if want in (None, "dropped"):
        assert got is None
        # a dropped request is gone, one not yet due is still there
        later = profile.take_step_request(max(next_step, first or 0))
        assert (later is None) == (want == "dropped")
    else:
        assert (got.steps, got.first, got.dir) == (*want, "/tmp/x")
        assert profile.take_step_request(next_step) is None


def test_a_request_waits_while_a_session_is_active(tmp_path):
    profile.start(tmp_path)
    profile.request_steps(2, str(tmp_path / "later"))
    assert profile.take_step_request(5) is None
    profile.stop()
    assert profile.take_step_request(6).steps == 2
    with pytest.raises(ValueError):
        profile.request_steps(0, "/tmp/x")


# --- the train loop's timers as spans -----------------------------------------

def test_timers_feed_the_recorder_from_their_own_clock_readings():
    rec = TraceRecorder()
    timers = Timers(log_level=0, spans=rec)
    timers.cause = 12
    timers("train-step", log_level=0).start()
    timers("dispatch", log_level=2).start()       # above the level
    timers("dispatch").stop()
    line = timers.log(printer=None, reset=False)  # read while running
    timers("train-step").stop()
    events = rec.chrome_trace()["traceEvents"]
    assert [e["name"] for e in events] == ["dispatch", "train-step"]
    assert all(e["args"] == {"iteration": 12} for e in events)
    inner, outer = events
    assert outer["ts"] <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    # the span-only timer is not timed, logged or written
    assert "dispatch" not in line and "train-step" in line
    assert timers("dispatch").elapsed() == 0.0
    assert timers("train-step").elapsed() > 0.0


@pytest.mark.parametrize("spans", [None, TraceRecorder(enabled=False)])
def test_timers_without_an_enabled_recorder_are_what_they_were(spans):
    timers = Timers(log_level=0, spans=spans)
    timers("a").start()
    timers("a").stop()
    timers("b", log_level=1).start()
    timers("b").stop()
    assert timers("a").count == 1
    assert timers("b").elapsed() == 0.0
    if spans is not None:
        assert spans.chrome_trace()["traceEvents"] == []


def test_device_scopes_is_every_name_the_source_gives_device_work():
    """One tuple in the program is what a profile's reader sorts by: a
    scope or a kernel named in the source and left out of it (or the
    other way round) fails here, not in a reader."""
    import re
    from pathlib import Path

    import megatron_llm_tpu

    root = Path(megatron_llm_tpu.__file__).parent
    found = set()
    for path in root.rglob("*.py"):
        text = path.read_text()
        found.update(re.findall(r'named_scope\("(\w+)"\)', text))
        if path.parent.name == "kernels" and "pallas_call" in text:
            found.update(re.findall(r'^\s+name="(\w+)",$', text, re.M))
    assert found == set(profile.DEVICE_SCOPES)
    assert len(set(profile.DEVICE_SCOPES)) == len(profile.DEVICE_SCOPES)
