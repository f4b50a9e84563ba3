"""bench.py partial-failure behavior: any single point failing must still
yield one parsed JSON record.

Round 2's bench measured the whole train curve, then lost it when the
decode point crashed before the single end-of-run print; round 5's first
run had the 32k row's HBM footprint leak into every later in-process
point.  The orchestration now runs each point in a subprocess; these
tests pin the parent's aggregation/partial-record behavior (with
``_point`` stubbed), the child protocol, and the real subprocess error
path.
"""

import contextlib
import io
import json
import sys

import pytest

import bench


def _stub_point(train=None, decode=None, pld=None, prefill=None,
                serving=None):
    """A fake bench._point dispatching on the spec kind."""
    def point(label, spec, timeout_s=900, env=None):
        kind = spec["kind"]
        try:
            if kind == "train":
                return train(spec)
            if kind == "decode":
                return decode(spec)
            if kind == "pld":
                return pld(spec) if pld else None
            if kind == "prefill":
                return prefill(spec) if prefill else None
            if kind == "serving":
                return serving(spec) if serving else None
        except Exception as e:  # noqa: BLE001 — mirrors subprocess crash
            print(f"# bench point {label} FAILED: {type(e).__name__}: {e}")
            return None
        return None
    return point


def _ok_train(spec):
    return [1000.0 * 1024 / spec["seq"], 0.5, 2.0, 123456]


def _ok_decode(spec):
    tps = 3000.0 if spec.get("quantize") else 2000.0
    return {"tokens_per_sec": tps, "roofline_tokens_per_sec": 7000.0,
            "roofline_frac": round(tps / 7000.0, 4),
            "prefill_tokens_per_sec": 9000.0, "model_params": 1}


def _run_main(monkeypatch, **stubs):
    monkeypatch.setattr(bench, "_point", _stub_point(**stubs))
    monkeypatch.setattr(bench, "_detect_device",
                        lambda: ("TPU v5 lite", 1))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.main()
    lines = [l for l in buf.getvalue().splitlines() if not l.startswith("#")]
    assert len(lines) == 1, lines
    return json.loads(lines[0])


def test_all_points_ok(monkeypatch):
    rec = _run_main(
        monkeypatch, train=_ok_train, decode=_ok_decode,
        pld=lambda s: {"pld_tokens_per_verify_repetitive": 4.0},
        prefill=lambda s: {"prefill_long_tokens_per_sec": 30000.0,
                           "prefill_long_mfu": 0.3},
        serving=lambda s: {"serving_requests_per_sec": 2.5,
                           "serving_token_latency_ms_p95": 11.0,
                           "serving_max_decode_batch": 8})
    assert rec["metric"] == "mfu" and rec["value"] == 0.5
    assert rec["serving"]["serving_requests_per_sec"] == 2.5
    assert rec["serving"]["serving_max_decode_batch"] == 8
    assert rec["decode_tokens_per_sec"] == 2000.0
    assert rec["decode_roofline_frac"] == round(2000.0 / 7000.0, 4)
    assert rec["decode_tokens_per_sec_int8"] == 3000.0
    assert rec["prefill_tokens_per_sec"] == 9000.0
    assert rec["decode_7b_width"]["tokens_per_sec"] == 2000.0
    assert rec["pld_tokens_per_verify_repetitive"] == 4.0
    assert rec["prefill_long_mfu"] == 0.3
    # 5 seq points + the 7B-width point
    assert len(rec["mfu_vs_seq"]) == 6
    assert any(p.get("config", "").startswith("7b-width")
               for p in rec["mfu_vs_seq"])


def test_decode_crash_keeps_headline(monkeypatch):
    def bad_decode(spec):
        raise NameError("boom")  # the round-2 failure class

    rec = _run_main(monkeypatch, train=_ok_train, decode=bad_decode)
    assert rec["value"] == 0.5 and rec["vs_baseline"] is not None
    assert "decode_tokens_per_sec" not in rec
    assert "decode_7b_width" not in rec
    assert "serving" not in rec  # serving point absent → key omitted
    assert len(rec["mfu_vs_seq"]) == 6


def test_one_curve_point_crash_keeps_rest(monkeypatch):
    def train(spec):
        if spec["seq"] == 16384:
            raise TypeError("deterministic bug at one seq")
        return _ok_train(spec)

    rec = _run_main(monkeypatch, train=train, decode=_ok_decode)
    assert rec["value"] == 0.5
    seqs = [p["seq_length"] for p in rec["mfu_vs_seq"]]
    assert 16384 not in seqs and 32768 in seqs


def test_headline_crash_uses_fallback_then_partial(monkeypatch):
    calls = []

    def train(spec):
        calls.append((spec["seq"], spec["mb"]))
        raise ValueError("always fails")

    rec = _run_main(monkeypatch, train=train, decode=_ok_decode)
    # primary + fallback headline attempted, then every curve point
    assert (1024, 12) in calls and (1024, 8) in calls
    assert rec["value"] is None and rec["mfu_vs_seq"] == []
    assert rec["decode_tokens_per_sec"] == 2000.0


def test_child_protocol_roundtrip(monkeypatch, capsys):
    """_child_main prints the marker line _point parses."""
    monkeypatch.setattr(bench, "_train_point",
                        lambda *a, **kw: [1.0, 0.5, 2.0, 7])
    bench._child_main(json.dumps(
        {"kind": "train", "platform": "TPU v5 lite", "seq": 1024,
         "mb": 1, "rc": "full", "iters": 1}))
    out = capsys.readouterr().out
    marked = [l for l in out.splitlines()
              if l.startswith(bench._CHILD_MARK)]
    assert len(marked) == 1
    assert json.loads(marked[0][len(bench._CHILD_MARK):]) == [1.0, 0.5,
                                                              2.0, 7]


def test_point_subprocess_failure_returns_none(capsys):
    """A real subprocess with a bad spec fails cleanly → None + a line."""
    out = bench._point("bogus", {"kind": "no-such-kind",
                                 "platform": "TPU v5 lite"}, timeout_s=60)
    assert out is None
    assert "bogus" in capsys.readouterr().out


def test_deterministic_error_not_retried(monkeypatch):
    calls = []

    def bad():
        calls.append(1)
        raise NameError("not transient")

    with pytest.raises(NameError):
        bench._retry(bad)
    assert len(calls) == 1


def test_transient_error_retried(monkeypatch):
    import time

    import jax

    monkeypatch.setattr(time, "sleep", lambda s: None)  # retry backoff
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) == 1:
            raise jax.errors.JaxRuntimeError("transient")
        return "ok"

    assert bench._retry(flaky) == "ok"
    assert len(calls) == 2


def test_unreachable_device_yields_structured_record(monkeypatch, capsys):
    """An unreachable accelerator must produce ONE parseable JSON error
    record and exit 1 — not a stack trace (the round-3 driver failure)."""
    def probe():
        raise TimeoutError("device probe exceeded 240s")

    monkeypatch.setattr(bench, "_detect_device", probe)
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1
    lines = [l for l in capsys.readouterr().out.splitlines()
             if not l.startswith("#")]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["value"] is None and "TimeoutError" in rec["error"]
