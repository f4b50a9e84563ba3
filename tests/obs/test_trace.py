"""TraceRecorder: span/instant recording, ring-buffer bounds, Chrome
trace-event JSON schema, and the disabled-recorder fast path."""

import json

import pytest

from megatron_llm_tpu.obs.trace import TraceRecorder, device_annotation


def test_span_records_complete_event():
    tr = TraceRecorder()
    with tr.span("prefill", request_id="req-1", tid=1,
                 args={"prompt_len": 64}):
        pass
    trace = tr.chrome_trace()
    assert trace["displayTimeUnit"] == "ms"
    assert trace["otherData"]["dropped_events"] == 0
    (ev,) = trace["traceEvents"]
    assert ev["name"] == "prefill" and ev["ph"] == "X"
    assert ev["tid"] == 1 and ev["pid"] > 0
    assert ev["ts"] >= 0 and ev["dur"] >= 0  # µs relative to epoch
    assert ev["args"] == {"prompt_len": 64, "request_id": "req-1"}
    json.dumps(trace)  # the export must be JSON-serializable as-is


def test_instant_event_schema():
    tr = TraceRecorder()
    tr.instant("retire", request_id="req-2", tid=2, args={"reason": "eos"})
    (ev,) = tr.chrome_trace()["traceEvents"]
    assert ev["ph"] == "i" and ev["s"] == "t"
    assert "dur" not in ev
    assert ev["args"]["reason"] == "eos"
    assert ev["args"]["request_id"] == "req-2"


def test_ring_drops_oldest_and_counts():
    tr = TraceRecorder(capacity=3)
    for i in range(5):
        tr.add(f"s{i}", 0.0, 1.0)
    trace = tr.chrome_trace()
    names = [e["name"] for e in trace["traceEvents"]]
    assert names == ["s2", "s3", "s4"]  # oldest two evicted
    assert trace["otherData"]["dropped_events"] == 2
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_disabled_recorder_is_inert():
    tr = TraceRecorder(enabled=False)
    ran = []
    with tr.span("x"):
        ran.append(1)
    tr.add("y", 0.0, 1.0)
    tr.instant("z")
    assert ran == [1]  # the guarded block still executes
    assert tr.chrome_trace()["traceEvents"] == []


def test_span_records_even_when_body_raises():
    tr = TraceRecorder()
    try:
        with tr.span("failing", request_id="req-3"):
            raise RuntimeError("x")
    except RuntimeError:
        pass
    (ev,) = tr.chrome_trace()["traceEvents"]
    assert ev["name"] == "failing"


def test_device_annotation_is_a_context_manager():
    # On CPU (or with jax absent) this must degrade to a no-op context —
    # never raise at engine steady state.
    with device_annotation("decode"):
        pass


def test_negative_duration_clamped():
    tr = TraceRecorder()
    tr.add("clock_skew", 2.0, 1.0)  # t1 < t0 must not export dur < 0
    (ev,) = tr.chrome_trace()["traceEvents"]
    assert ev["dur"] == 0


def test_chrome_trace_carries_compilations_with_their_cause(monkeypatch):
    """A compilation record (obs/compile.py) that overlaps what the
    recorder retains is a ``compile`` event on the recorder's clock whose
    ``cause`` is the innermost span around it; one that ran outside is
    left out."""
    import time

    from megatron_llm_tpu.obs import compile as obs_compile
    from megatron_llm_tpu.obs import trace as trace_mod

    now = time.perf_counter()
    log = obs_compile.CompileLog(clock=lambda: clock[0])
    monkeypatch.setattr(obs_compile, "COMPILES", log)
    backend = "/jax/core/compile/backend_compile_duration"
    clock = [now + 4.0]
    log.on_event("/jax/compilation_cache/cache_misses")
    log.on_duration(backend, 2.0, fun_name="jit(_prefill_impl)")  # [2, 4]
    clock[0] = now + 20.0
    log.on_duration(backend, 1.0, fun_name="jit(later)")          # [19, 20]

    tr = TraceRecorder()
    tr.add("engine_step", now + 1.0, now + 6.0, args={"iter": 7})
    tr.add("prefill", now + 1.5, now + 5.0, request_id="req-9", tid=9,
           args={"prompt_len": 300, "padded": 512})
    tr.add("decode", now + 2.5, now + 3.0, request_id="req-8", tid=8)
    doc = tr.chrome_trace()
    (ev,) = [e for e in doc["traceEvents"] if e["name"] == "compile"]
    assert ev["ph"] == "X" and ev["tid"] == trace_mod.COMPILE_TID
    assert ev["args"]["program"] == "jit(_prefill_impl)"
    assert ev["args"]["stage_s"] == {"backend": 2.0}
    assert ev["args"]["cache"] == "miss"
    assert ev["args"]["cause"] == {"span": "prefill", "request_id": "req-9",
                                   "prompt_len": 300, "padded": 512}
    # on the recorder's clock: microseconds from its epoch
    epoch = doc["otherData"]["epoch_perf_counter"]
    assert epoch + ev["ts"] / 1e6 == pytest.approx(now + 2.0, abs=1e-5)
    assert ev["dur"] == pytest.approx(2e6, abs=1.0)
    (track,) = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert track["tid"] == ev["tid"] and track["args"] == {"name": "compile"}
    json.dumps(doc)
    # outside every span but inside the recorder's extent: no cause
    tr.add("engine_step", now + 21.0, now + 22.0, args={"iter": 8})
    late = [e for e in tr.chrome_trace()["traceEvents"]
            if e["name"] == "compile"][-1]
    assert late["args"]["program"] == "jit(later)"
    assert "cause" not in late["args"]


# --- collections (GcWatch) and phases -----------------------------------------

@pytest.mark.parametrize("seconds,recorded", [(0.0002, False), (0.00099, False),
                                             (0.001, True), (0.25, True)])
def test_gc_watch_records_a_collection_of_a_millisecond_or_more(seconds,
                                                                recorded):
    """The threshold, on the callback itself with a clock of the test's:
    a start and a stop 0.2 ms apart leave nothing, 1 ms apart a ``gc``
    span with the collector's ``generation`` and ``collected``."""
    from megatron_llm_tpu.obs import trace as trace_mod

    tr = TraceRecorder()
    now = [100.0]
    watch = trace_mod.GcWatch(tr, clock=lambda: now[0])
    watch("start", {"generation": 2, "collected": 0, "uncollectable": 0})
    now[0] += seconds
    watch("stop", {"generation": 2, "collected": 41, "uncollectable": 0})
    events = tr.chrome_trace()["traceEvents"]
    if not recorded:
        assert events == [] and len(tr) == 0
        return
    span, track = events
    assert span["name"] == "gc" and span["ph"] == "X"
    assert span["tid"] == trace_mod.GC_TID != trace_mod.COMPILE_TID
    assert span["dur"] == pytest.approx(seconds * 1e6, abs=1.0)
    assert span["args"] == {"generation": 2, "collected": 41}
    assert track == {"name": "thread_name", "ph": "M", "pid": span["pid"],
                     "tid": trace_mod.GC_TID, "args": {"name": "gc"}}


def test_gc_watch_goes_on_and_off_the_interpreters_list_once():
    """``install`` twice is one entry, ``remove`` twice is none; a stop
    without its start (the watch went on mid-collection) records nothing;
    a disabled recorder stays empty."""
    import gc

    from megatron_llm_tpu.obs import trace as trace_mod

    tr = TraceRecorder(enabled=False)
    now = [5.0]
    watch = trace_mod.GcWatch(tr, clock=lambda: now[0])
    n = len(gc.callbacks)
    try:
        watch.install()
        watch.install()
        assert gc.callbacks.count(watch) == 1 and len(gc.callbacks) == n + 1
    finally:
        watch.remove()
        watch.remove()
    assert len(gc.callbacks) == n and watch not in gc.callbacks
    tr.enabled = True
    now[0] = 9.0
    watch("stop", {"generation": 0, "collected": 0})
    assert len(tr) == 0
    watch("start", {"generation": 1, "collected": 0})
    now[0] = 9.5
    tr.enabled = False
    watch("stop", {"generation": 1, "collected": 3})
    assert len(tr) == 0


def test_a_phase_is_never_a_compilations_cause(monkeypatch):
    """A recorder's ``phases`` only divide another span: the compilation
    inside ``prefill_dispatch`` inside ``prefill`` is the ``prefill``'s,
    with the request's prompt length, as before the phases came."""
    import time

    from megatron_llm_tpu.obs import compile as obs_compile

    now = time.perf_counter()
    clock = [now + 4.0]
    log = obs_compile.CompileLog(clock=lambda: clock[0])
    monkeypatch.setattr(obs_compile, "COMPILES", log)
    log.on_event("/jax/compilation_cache/cache_misses")
    log.on_duration("/jax/core/compile/backend_compile_duration", 2.0,
                    fun_name="jit(_prefill_impl)")                # [2, 4]

    def record(tr):
        tr.add("prefill_dispatch", now + 1.6, now + 4.5, request_id="req-9",
               args={"padded": 512})
        tr.add("prefill", now + 1.5, now + 5.0, request_id="req-9", tid=9,
               args={"prompt_len": 300, "padded": 512})
        (ev,) = [e for e in tr.chrome_trace()["traceEvents"]
                 if e["name"] == "compile"]
        return ev["args"]["cause"]["span"]

    assert record(TraceRecorder(phases={"prefill_dispatch": "own"})) == \
        "prefill"
    assert record(TraceRecorder()) == "prefill_dispatch"


def test_a_collection_inside_the_recorders_own_lock_does_not_deadlock():
    """The interpreter starts a collection wherever a thread allocates,
    the recorder's ``add`` among the places (found on the chip: a serving
    cell's scheduler stood still for good in mid-window).  The callback
    fed a long collection while its own thread holds the recorder's lock
    returns, and the span is in the ring."""
    import threading

    from megatron_llm_tpu.obs import trace as trace_mod

    tr = TraceRecorder(capacity=4)
    now = [1.0]
    watch = trace_mod.GcWatch(tr, clock=lambda: now[0])
    done = threading.Event()

    def collect_under_the_lock():
        with tr._lock:
            watch("start", {"generation": 2, "collected": 0})
            now[0] = 1.5
            watch("stop", {"generation": 2, "collected": 7})
        done.set()

    t = threading.Thread(target=collect_under_the_lock, daemon=True)
    t.start()
    assert done.wait(10.0), "the callback waited for its own thread's lock"
    (span, _track) = tr.chrome_trace()["traceEvents"]
    assert span["name"] == "gc" and span["args"]["collected"] == 7
    # the ring stays bounded through the unlocked door too
    for i in range(6):
        tr.add_unlocked("gc", float(i), float(i) + 0.5)
    assert len(tr) == 4
