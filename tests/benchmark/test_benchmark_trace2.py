"""``--trace 2`` on the rehearsal path, and the scope reader's arithmetic.

The synthetic trace beside this file (``data/synthetic_xplane_scopes.txt``;
times in microseconds): device 0 runs ``jit_step`` at 100 and at 500.  In a
run starting at t, ``%while`` [t,t+200) encloses ``%fusion.1`` [t+10,t+100)
under ``lm_head`` and ``%fusion.2`` [t+100,t+160) under
``transpose(jvp(cross_entropy))``; then ``%custom-call.3`` [t+220,t+280)
under ``attention/flash_fwd``, ``%fusion.4`` [t+280,t+300) under
``optimizer`` and ``%copy.5`` [t+300,t+330) under no scope.  The scope is
the ``tf_op`` statistic of the event's metadata.
"""

import functools
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import trace_reduce
from benchmarks.manifest import ROOT, Manifest
from benchmarks.readers import xplane_scope

DATA = Path(__file__).parent / "data"
US = 1e3


# --- the rehearsal: --trace 2 is --trace 0 plus a traced phase ------------------

@functools.lru_cache(maxsize=None)
def rehearse(workload, trace, seconds="2"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", workload,
         "--seed", "3000000019", "--seconds", seconds, "--trace", str(trace),
         "--cpu-rehearsal"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=280)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    results = [l for l in lines if l.startswith("{")]
    assert results == lines[-1:], "one result, and it is the last line"
    return json.loads(lines[-1]), proc.stdout


CELLS = {"falcon7b-train-1chip": {"step_ms.train", "input_wait_ms.train",
                                  "log_ms.train"},
         "falcon7b-serve-batch": set()}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_trace2_line_has_the_keys_of_a_trace0_line(workload):
    plain, _ = rehearse(workload, 0)
    both, _ = rehearse(workload, 2)
    assert set(plain) == {"correct", "attempted", "failed", "metrics",
                          "device", "compared"}
    assert set(both) - {"breakdown"} == set(plain)
    # what decided ``correct``, each number beside its limit, comes last
    assert list(plain)[-1] == list(both)[-1] == "compared"
    assert "compiles_in_window" in plain["compared"]
    for line in (plain, both):
        assert all(set(c) == {"value", "limit"}
                   for c in line["compared"].values())
        assert line["correct"] == all(
            c["value"] <= c["limit"] for c in line["compared"].values())
    man = Manifest(ROOT)
    e2e = {m["name"] for m in man.metrics_of(workload, "end_to_end")}
    assert set(plain["metrics"]) == e2e
    assert e2e <= set(both["metrics"])
    for m in both["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert {"busy_s", "window_s", "memory_peak_bytes"} <= set(both["device"])


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_trace2_reports_program_span_metrics_from_the_window(workload):
    both, _ = rehearse(workload, 2)
    man = Manifest(ROOT)
    layer = {m["name"] for m in man.metrics_of(workload, "per_layer")}
    got = set(both["metrics"]) & layer
    assert CELLS[workload] <= got
    # no device plane on the CPU: nothing under a device metric's name
    device = {m["name"] for m in man.metrics_of(workload, "per_layer")
              if m["source"] == "device_trace"}
    assert not got & device
    assert both["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_nothing_of_the_traced_phase_exists_before_the_numbers(workload):
    """The runner reports, at the moment it has taken the window's
    numbers, whether a profile session had been started and which of its
    own threads exist: none may."""
    _, out = rehearse(workload, 2)
    (note,) = [l for l in out.splitlines()
               if "when the window's numbers were taken" in l]
    assert "profile sessions so far 0" in note
    assert "threads of the benchmark's own []" in note
    said = {"falcon7b-train-1chip": "traced steps' step_time_s",
            "falcon7b-serve-batch": "profile session:"}[workload]
    assert said in out
    _, plain = rehearse(workload, 0)
    assert said not in plain


def test_a_backlog_is_traced_over_one_slot_batch_of_whole_prefills():
    """Not over a fixed time: the traced window runs from the start of
    one prefill to the start of the one a slot batch (4 in a rehearsal)
    later.  A window short enough that the rehearsal's 96 requests
    outlast it."""
    line, out = rehearse("falcon7b-serve-batch", 2, seconds="0.3")
    assert line["correct"], out[-2000:]
    assert "traced window: 4 prefills, from the start of one" in out


def test_the_open_loop_replays_its_schedule_from_the_start(tmp_path):
    """The engine is at the traced slice's start where the measured
    window had it only if everything due before it is offered again;
    the prompts keep their lengths and get new tokens (the old ones are
    in the prefix cache), and the session opens at 0.4 S."""
    from benchmarks import serving, traffic
    from benchmarks.kinds import serve_open

    sent = []

    def submit(s, prompt):
        s.submitted = time.perf_counter()
        sent.append((s, prompt))

    sv = SimpleNamespace(mix={"lead_s": 0.2}, submit=submit,
                         model=SimpleNamespace(vocab_size=512))
    ctx = SimpleNamespace(seconds=1.0, seed=7, trace_dir=str(tmp_path / "t"))
    requests = [traffic.Request(due, [3] * n, 5) for due, n in
                [(-0.2, 9), (0.1, 17), (0.39, 8), (0.5, 30), (0.9, 11)]]
    session = serve_open.traced_replay(ctx, sv, requests, [])
    assert [len(p) for _s, p in sent] == [9, 17, 8, 30]   # due <= 0.8
    assert all(p != [3] * len(p) for _s, p in sent)
    before = [s.submitted < session.t_sync for s, _p in sent]
    assert before == [True, True, True, False]
    # it closes at 0.4 S + the slice's length (a first start can be slow)
    assert 0.0 < session.t_stop - session.t_sync <= 0.45
    assert not any(s.counted for s, _p in sent)


# --- the scope reader ---------------------------------------------------------------

@pytest.fixture(scope="module")
def evidence(tmp_path_factory):
    from jax.profiler import ProfileData

    text = (DATA / "synthetic_xplane_scopes.txt").read_text()
    d = tmp_path_factory.mktemp("trace")
    run = d / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return {"ctx": SimpleNamespace(trace_dir=str(d)),
            "trace": trace_reduce.load(text_proto=text),
            "trace_window": (100 * US, 900 * US)}


def test_the_wire_decoder_reads_what_profile_data_reads(evidence):
    path = trace_reduce.find_xplane(evidence["ctx"].trace_dir)
    ops = xplane_scope.device_ops(path)
    assert sorted(ops) == [0]
    assert [(n, s, e) for n, s, e, _ in ops[0]] == [
        (e.name, e.start, e.end) for e in evidence["trace"].ops[0]]
    scopes = {trace_reduce.hlo_name(n): s for n, _s, _e, s in ops[0]}
    assert scopes["%while.1"] == "" and scopes["%copy.5"] == ""
    assert scopes["%fusion.2"].endswith(
        "transpose(jvp(cross_entropy))/reduce_sum")


@pytest.mark.parametrize("params,want", [
    # busy: [t,t+200) + [t+220,t+330) = 310 us a run
    ({"stat": "share", "scopes": ["lm_head", "cross_entropy"]},
     100 * (90 + 60) / 310),
    ({"stat": "share", "scopes": ["optimizer"]}, 100 * 20 / 310),
    ({"stat": "share", "scopes": ["attention", "flash_fwd"]},
     100 * 60 / 310),                             # one op, counted once
    ({"stat": "ms_per_run", "module": "jit_step",
      "scopes": ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]}, 0.060),
    ({"stat": "ms_per_run", "module": "jit_step", "scopes": ["kv_cache"]},
     0.0),
    ({"stat": "ms_per_run", "module": "jit_absent", "scopes": ["mlp"]},
     None),
])
def test_scope_sums(evidence, params, want):
    got = xplane_scope.read(dict(evidence), params)
    assert got == (None if want is None else pytest.approx(want))


def test_the_unscoped_remainder_is_printed_below_90_percent(evidence,
                                                            capsys):
    xplane_scope.read(dict(evidence), {"stat": "share",
                                       "scopes": ["optimizer"]})
    out = capsys.readouterr().out
    # leaves: 90 + 60 + 60 + 20 under a scope, 30 under none
    assert "scopes cover 88.5 %" in out and "%copy.5" in out


def test_the_table_by_scope_is_printed_once_a_run(evidence, capsys):
    """Each leaf operation under the innermost of the program's names in
    its path (``flash_fwd`` inside ``attention``); the names are the
    program's own tuple, the reader keeps none."""
    ev = dict(evidence)
    for scopes in (["optimizer"], ["lm_head"]):
        xplane_scope.read(ev, {"stat": "share", "scopes": scopes})
    (table,) = [l for l in capsys.readouterr().out.splitlines()
                if "device time by scope" in l]
    # two runs of 90 + 60 + 60 + 20 + 30 us
    assert "0.52 ms: lm_head 34.62, cross_entropy 23.08, flash_fwd 23.08, " \
        "(-) 11.54, optimizer 7.69" in table
    from megatron_llm_tpu.obs.profile import DEVICE_SCOPES
    assert xplane_scope.program_scopes() == DEVICE_SCOPES


def test_a_run_counts_where_it_ends(evidence):
    """On a mesh the whole-period window closes at the latest device's
    last start: the lowest device's last run starts just inside it, and
    its operations do not."""
    ev = dict(evidence, trace_window=(100 * US, 500.001 * US))
    ev.pop("scope_ops", None)
    got = xplane_scope.read(ev, {"stat": "ms_per_run", "module": "jit_step",
                                 "scopes": ["flash_fwd"]})
    assert got == pytest.approx(0.060)


@pytest.mark.parametrize("scope,names,held", [
    ("jit(step)/transpose(jvp(lm_head))/dot_general", ["lm_head"], True),
    ("jit(step)/lm_head_bias/add", ["lm_head"], False),
    ("jit(step)/mlp/dot_general", ["attention", "mlp"], True),
    ("jit(step)/checkpoint/rematted_computation/attention/mul",
     ["attention"], True),
    ("", ["attention"], False),
])
def test_a_scope_is_matched_as_a_whole_word(scope, names, held):
    assert xplane_scope._holds(scope, names) is held


def test_a_trace_without_scopes_reports_nothing(tmp_path):
    """The parent's program names no scope: the reader returns None and
    the line leaves the metric out."""
    from jax.profiler import ProfileData

    text = (DATA / "synthetic_xplane.txt").read_text()
    run = tmp_path / "plugins" / "profile" / "x"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    ev = {"ctx": SimpleNamespace(trace_dir=str(tmp_path)),
          "trace": trace_reduce.load(text_proto=text),
          "trace_window": (100 * US, 500 * US)}
    assert xplane_scope.read(ev, {"stat": "share",
                                  "scopes": ["lm_head"]}) is None
    assert xplane_scope.read({"trace": None}, {"stat": "share",
                                               "scopes": ["x"]}) is None
