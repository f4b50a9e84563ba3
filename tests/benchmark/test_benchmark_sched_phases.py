"""The reader of the scheduler's phases (``benchmarks/readers/
sched_phases.py``) on synthetic spans and on the synthetic trace beside
this file (``data/synthetic_xplane.txt``, times in microseconds): over
the whole period [100,500) device 0 — the one busy least — is idle over
[300,320) and [380,500), 140 of 400 us.
"""

import json
from types import SimpleNamespace

import pytest

from benchmarks import trace_reduce as T
from benchmarks.manifest import ROOT, Manifest
from benchmarks.readers import sched_phases, xplane

US = 1e3    # ns in a microsecond
MS = 1e-3   # s in a millisecond

NAMES = ["step_host_ms", "admit_turnaround_ms", "sched_blocked_share",
         "idle_host_share"]
CELLS = {"chat": "falcon7b-serve-chat-knee", "batch": "falcon7b-serve-batch"}


@pytest.fixture(scope="module")
def kinds():
    from megatron_llm_tpu.serving.engine import SCHED_PHASES
    return dict(SCHED_PHASES)


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def _params(man, name):
    return man.layer_metric(name)["params"]


def window_spans():
    """Two iterations on the host's clock, seconds: an admission of two
    prompts, then two decode steps."""
    return [
        # (name, start, seconds)
        ("admit", 10.0, 40 * MS),
        ("admit_setup", 10.0, 1 * MS), ("prefill_dispatch", 10.001, 3 * MS),
        ("slot_insert", 10.004, 2 * MS), ("prefill_wait", 10.006, 10 * MS),
        ("admit_commit", 10.016, 1 * MS),
        ("admit_setup", 10.020, 1 * MS), ("prefill_dispatch", 10.021, 5 * MS),
        ("slot_insert", 10.026, 2 * MS), ("prefill_wait", 10.028, 10 * MS),
        ("admit_commit", 10.038, 2 * MS),
        ("engine_step", 10.050, 20 * MS),
        ("step_inputs", 10.051, 1 * MS), ("dispatch", 10.052, 2 * MS),
        ("fetch", 10.054, 14 * MS), ("gc", 10.0685, 1 * MS),
        ("commit", 10.068, 2 * MS),
        ("engine_step", 10.080, 20 * MS),
        ("step_inputs", 10.081, 0.5 * MS), ("dispatch", 10.0815, 1.5 * MS),
        ("fetch", 10.083, 16 * MS), ("commit", 10.099, 0.5 * MS),
    ]


def evidence_of(spans, seconds=0.2, **more):
    return {"recorder_spans": spans, "ctx": SimpleNamespace(seconds=seconds),
            **more}


# --- the measured window -------------------------------------------------------

@pytest.mark.parametrize("tag", sorted(CELLS))
def test_own_ms_per_a_decode_step(man, tag):
    """``step_host_ms``: the own-kind phases inside an ``engine_step``
    (5 and 2.5 ms here; ``fetch`` is blocked and not counted, the ``gc``
    under ``commit`` is in ``commit`` already), the median over the steps."""
    got = sched_phases.read(evidence_of(window_spans()),
                            _params(man, f"step_host_ms.{tag}"))
    assert got == pytest.approx((5.0 + 2.5) / 2)


@pytest.mark.parametrize("tag", sorted(CELLS))
def test_own_ms_per_prefill_of_an_admission(man, tag):
    """``admit_turnaround_ms``: the four own-kind admission phases inside
    an ``admit`` over its ``prefill_wait`` spans: (7 + 10) ms over two."""
    got = sched_phases.read(evidence_of(window_spans()),
                            _params(man, f"admit_turnaround_ms.{tag}"))
    assert got == pytest.approx(17.0 / 2)


def test_a_phase_counts_for_the_span_it_began_in(kinds):
    """A phase that began before its ``within`` (or before the window,
    which holds no such span) is left out of it, one that began inside
    counts whole; a ``within`` without a ``per`` span is left out; a
    blocked phase named in ``phases`` is not the host's own time."""
    spans = [("commit", 0.999, 3 * MS),          # began before the step
             ("engine_step", 1.0, 10 * MS),
             ("step_inputs", 1.001, 1 * MS), ("fetch", 1.002, 6 * MS),
             ("commit", 1.009, 4 * MS),          # began inside, ends after
             ("engine_step", 2.0, 10 * MS)]      # holds no phase: 0
    assert sched_phases.own_per(
        spans, kinds, "engine_step",
        ["step_inputs", "commit", "fetch"]) == pytest.approx([5 * MS, 0.0])
    assert sched_phases.own_per(spans, kinds, "engine_step", ["commit"],
                                per="fetch") == pytest.approx([4 * MS])
    assert sched_phases.own_per(spans, kinds, "admit", ["commit"]) == []
    assert sched_phases.read(
        evidence_of(spans), {"stat": "own_ms_per", "within": "admit",
                             "phases": ["admit_setup"]}) is None


@pytest.mark.parametrize("tag", sorted(CELLS))
def test_blocked_share_is_taken_over_the_windows_seconds(man, tag):
    """``fetch`` 14 + 16 ms and ``prefill_wait`` 10 + 10 ms of a window of
    0.2 s: 25 %."""
    params = _params(man, f"sched_blocked_share.{tag}")
    assert sched_phases.read(evidence_of(window_spans()), params) == \
        pytest.approx(25.0)
    assert sched_phases.read(evidence_of(window_spans(), seconds=0.5),
                             params) == pytest.approx(10.0)
    own_only = [s for s in window_spans()
                if s[0] not in ("fetch", "prefill_wait")]
    assert sched_phases.read(evidence_of(own_only), params) is None


# --- the traced window ---------------------------------------------------------

@pytest.fixture(scope="module")
def trace():
    return T.load(text_proto=(ROOT / "tests" / "benchmark" / "data"
                              / "synthetic_xplane.txt").read_text())


def traced_evidence(trace, host_spans, **more):
    window = T.window_of(trace, "jit_step", whole_periods=True)   # [100,500)
    return evidence_of(window_spans(), trace=trace, trace_window=window,
                       host_spans=[T.Event(n, s * US, e * US)
                                   for n, s, e in host_spans], **more)


HOST = [("engine_step", 290, 480),      # on the trace's clock, us
        ("dispatch", 295, 325),         # own: covers the gap [300,320)
        ("fetch", 380, 420),            # blocked: 40 of the gap [380,500)
        ("commit", 420, 470),           # own, with a collection inside it
        ("gc", 430, 440),
        ("decode", 100, 470)]           # no phase: names nothing here


@pytest.mark.parametrize("tag", sorted(CELLS))
def test_idle_own_share_cuts_the_idle_time_by_the_phases(man, trace, kinds,
                                                         tag, capsys):
    """Idle 140 us of 400: 40 under ``fetch`` (blocked), 10 under the
    ``gc`` nested in ``commit``, 40 under the rest of ``commit``, 20 under
    ``dispatch``, 10 under the ``engine_step`` outside its phases, 20
    under nothing: 100 of 400 are not the device's own wait."""
    ev = traced_evidence(trace, HOST)
    params = _params(man, f"idle_host_share.{tag}")
    got = sched_phases.read(ev, params)
    assert got == pytest.approx(100.0 * 100 / 400)
    idle = xplane.read(ev, {"stat": "idle_share"})
    assert idle == pytest.approx(100.0 * 140 / 400) and got <= idle
    rows = {n: [(s / US, e / US) for s, e in p]
            for n, p in ev["sched_idle_rows"]}
    assert rows == {"fetch": [(380, 420)], "gc": [(430, 440)],
                    "dispatch": [(300, 320)],
                    "commit": [(420, 430), (440, 470)],
                    "engine_step": [(470, 480)], "(none)": [(480, 500)]}
    out = capsys.readouterr().out
    assert out.count("the device's idle time by what the scheduler") == 1
    assert "fetch blocked: 0.0000, 10.000, 1, 0.04 ms at 0.000 s" in out
    assert "commit own: 0.0000, 10.000, 2, 0.03 ms at 0.000 s, " \
        "0.01 ms at 0.000 s" in out
    assert "engine_step outside its phases: " in out
    assert out.count("the scheduler's time by phase over the window") == 1
    # the tables are printed once a run, whatever is read after
    sched_phases.read(ev, params)
    sched_phases.read(ev, {"stat": "blocked_share"})
    assert capsys.readouterr().out == ""


def test_idle_under_a_blocked_phase_alone_is_not_the_hosts(trace):
    """Every gap under a ``fetch``: the device idled while the host
    itself waited, and ``idle_own_share`` reads 0; every gap under own
    phases or nothing: all of ``device_idle_share``."""
    blocked = traced_evidence(trace, [("fetch", 290, 510)])
    assert sched_phases.read(blocked, {"stat": "idle_own_share"}) == 0.0
    own = traced_evidence(trace, [("commit", 290, 330),
                                  ("prefill_dispatch", 400, 450)])
    assert sched_phases.read(own, {"stat": "idle_own_share"}) == \
        pytest.approx(xplane.read(own, {"stat": "idle_share"}))


# --- where there is nothing to read --------------------------------------------

def test_none_from_a_program_without_the_table(man, trace, monkeypatch,
                                               capsys):
    """The parent of this reader's PR has no ``SCHED_PHASES``: every stat
    reads None and nothing is printed or raised."""
    from megatron_llm_tpu.serving import engine

    monkeypatch.delattr(engine, "SCHED_PHASES")
    assert sched_phases.program_phases() is None
    ev = traced_evidence(trace, HOST)
    for name in NAMES:
        assert sched_phases.read(ev, _params(man, f"{name}.batch")) is None
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("more", [{}, {"trace": None}],
                         ids=["no-trace-at-all", "no-device-plane"])
def test_no_device_metric_without_a_device_plane(man, more):
    """The CPU rehearsal: the span metrics read, the device one does
    not; nor does it where the traced spans hold no phase."""
    ev = evidence_of(window_spans(), **more)
    assert sched_phases.read(ev, _params(man, "idle_host_share.chat")) is None
    assert sched_phases.read(ev, _params(man, "step_host_ms.chat")) > 0
    assert sched_phases.read(ev, _params(man, "sched_blocked_share.chat")) > 0


def test_no_device_metric_where_the_traced_spans_hold_no_phase(trace):
    ev = traced_evidence(trace, [("decode", 100, 470),
                                 ("engine_step", 290, 480)])
    assert sched_phases.read(ev, {"stat": "idle_own_share"}) is None


def test_an_unknown_stat_is_an_error(trace):
    with pytest.raises(ValueError, match="unknown stat"):
        sched_phases.read(traced_evidence(trace, HOST), {"stat": "p95"})


# --- the manifest ---------------------------------------------------------------

@pytest.mark.parametrize("name", NAMES)
def test_the_files_agree_with_their_manifest_entries(man, kinds, name):
    """A file each under ``layer_metrics/``, an entry each in the
    manifest, the two Falcon serving cells only (the hybrid cells' sets
    are pinned by their own tests), each moving an end-to-end metric its
    cell reports, every phase it names one of the program's."""
    for tag, cell in CELLS.items():
        spec = man.layer_metric(f"{name}.{tag}")
        (entry,) = [m for m in man.doc["per_layer"]
                    if m["name"] == f"{name}.{tag}"]
        for key in ("layer", "unit", "better", "source", "moves",
                    "workloads"):
            assert spec[key] == entry[key], (name, tag, key)
        assert spec["workloads"] == [cell] and spec["reader"] == "sched_phases"
        assert spec["moves"] in {m["name"] for m in man.metrics_of(
            cell, "end_to_end")}
        assert spec["source"] == ("device_trace" if name == "idle_host_share"
                                  else "program_span")
        assert spec["layer"] in {m["layer"] for m in man.doc["per_layer"]
                                 if m["name"] in ("engine_step_ms.chat",
                                                  "admit_stall_p95_ms.chat",
                                                  "device_idle_share.chat")}
        named = spec["params"].get("phases", []) + \
            [spec["params"][k] for k in ("per",) if k in spec["params"]]
        assert set(named) <= set(kinds)
        assert all(kinds[p] == "own" for p in spec["params"].get("phases", []))
        assert len(spec["what"]) > 40 and callable(
            man.reader(spec["reader"]).read)


def test_the_eight_entries_end_the_list(man):
    tail = [m["name"] for m in man.doc["per_layer"][-8:]]
    assert tail == [f"{n}.{t}" for n in NAMES for t in ("chat", "batch")]
    assert len(json.dumps(man.doc)) < 64 * 1024


# --- the rehearsal -------------------------------------------------------------

def test_a_rehearsed_trace2_line_holds_the_span_metrics_alone():
    """``--cpu-rehearsal --trace 2`` of the batch cell: the three
    program-span metrics are in the line, the device one is not (no
    device metric on the CPU), and the host's table by phase is printed."""
    from test_benchmark_trace2 import rehearse

    line, out = rehearse("falcon7b-serve-batch", 2)
    got = line["metrics"]
    for name in ("step_host_ms.batch", "admit_turnaround_ms.batch",
                 "sched_blocked_share.batch"):
        assert got[name]["value"] > 0, name
    assert got["sched_blocked_share.batch"]["value"] < 100.0
    assert got["step_host_ms.batch"]["unit"] == "ms"
    assert "idle_host_share.batch" not in got
    assert "idle_host_share.batch: its reader found nothing to read" in out
    assert "the scheduler's time by phase over the window" in out
    assert "the device's idle time by what the scheduler" not in out
