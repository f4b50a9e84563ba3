"""Observability spine through the serving stack (docs/observability.md):

- ``GET /metrics?format=prometheus`` serves every subsystem — serving
  counters/summaries, SLO gauges, resilience events — from the one shared
  registry, parseable by a minimal 0.0.4 text parser (round-trip).
- ``GET /metrics`` (JSON) keeps its pre-existing shape.
- ``GET /trace`` returns Chrome trace-event JSON where one request id
  links its ``queued`` → prefill → ``decode`` → ``retire`` spans.
- The structured event log, the trace spans, and the HTTP response all
  carry the same ``request_id`` (end-to-end correlation).
"""

import json
import re
import urllib.request

import jax
import pytest

from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.generation.server import (
    GenerationService,
    MegatronServer,
)
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.obs.logging import EVENT_LOG
from megatron_llm_tpu.tokenizer.tokenizer import NullTokenizer

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text):
    """Minimal 0.0.4 parser → (types, samples); asserts on bad lines."""
    types, samples = {}, {}
    for line in text.splitlines():
        if not line.strip() or line.startswith("# HELP"):
            continue
        if line.startswith("# TYPE"):
            _, _, name, mtype = line.split(maxsplit=3)
            types[name] = mtype.strip()
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        name, labelstr, value = m.groups()
        labels = dict(_LABEL_RE.findall(labelstr)) if labelstr else {}
        samples[(name, frozenset(labels.items()))] = float(value)
    return types, samples


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config(num_layers=1, vocab_size=256,
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def _generate(port, prompts, ttg=4):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api",
        data=json.dumps({"prompts": prompts, "tokens_to_generate": ttg,
                         "no_early_termination": True}).encode(),
        method="PUT", headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read())


def test_prometheus_endpoint_round_trip(model):
    """After real traffic, the text endpoint carries serving counters,
    latency summaries, SLO gauges, and the resilience counter family —
    all from one scrape of the shared registry."""
    cfg, params = model
    server = MegatronServer(cfg, params,
                            NullTokenizer(vocab_size=cfg.vocab_size),
                            max_batch_size=2)
    server.run("127.0.0.1", 0, block=False)
    try:
        _generate(server.port, ["5 9 3", "7 2"], ttg=4)
        url = f"http://127.0.0.1:{server.port}/metrics?format=prometheus"
        with urllib.request.urlopen(url, timeout=60) as resp:
            assert resp.status == 200
            ctype = resp.headers["Content-Type"]
            assert ctype.startswith("text/plain")
            assert "version=0.0.4" in ctype
            text = resp.read().decode()
    finally:
        server.shutdown()

    types, samples = parse_prometheus(text)
    assert types["serving_completed_total"] == "counter"
    assert samples[("serving_completed_total", frozenset())] == 2.0
    assert samples[("serving_submitted_total", frozenset())] == 2.0
    # host-computed reservoir percentiles export as a summary
    assert types["serving_ttft_seconds"] == "summary"
    assert samples[("serving_ttft_seconds_count", frozenset())] == 2.0
    assert ("serving_ttft_seconds",
            frozenset({("quantile", "0.5")})) in samples
    # SLO gauges ride in the same scrape, one row per dimension
    assert types["serving_slo_burn_rate"] == "gauge"
    for dim in ("ttft", "itl", "availability"):
        assert ("serving_slo_compliance",
                frozenset({("slo", dim)})) in samples
    assert samples[("serving_slo_healthy", frozenset())] in (0.0, 1.0)
    # paged KV pool gauges + the COW counter ride the same scrape; after
    # traffic retires, used goes back to 0 but free reflects the pool
    assert types["serving_blocks_free"] == "gauge"
    assert types["serving_blocks_used"] == "gauge"
    assert types["serving_kv_cache_util"] == "gauge"
    assert types["serving_cow_copies_total"] == "counter"
    assert samples[("serving_blocks_free", frozenset())] > 0
    assert samples[("serving_cow_copies_total", frozenset())] == 0.0
    # the resilience collector (metrics.py RESILIENCE_EVENTS) shares it
    assert types["resilience_events_total"] == "counter"


def test_json_metrics_shape_unchanged(model):
    """The original JSON endpoint keeps its keys; Prometheus is opt-in
    via the query parameter, not a format change."""
    cfg, params = model
    server = MegatronServer(cfg, params,
                            NullTokenizer(vocab_size=cfg.vocab_size),
                            max_batch_size=2)
    server.run("127.0.0.1", 0, block=False)
    try:
        _generate(server.port, ["5 9 3"], ttg=3)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics",
                timeout=60) as resp:
            assert resp.headers["Content-Type"] == "application/json"
            snap = json.loads(resp.read())
    finally:
        server.shutdown()
    assert snap["completed"] == 1
    for key in ("submitted", "decode_iterations", "ttft",
                "per_token_latency", "device_idle_frac", "prefix_hit_rate",
                "blocks_free", "blocks_used", "kv_cache_util",
                "cow_copies_total"):
        assert key in snap
    assert snap["ttft"]["count"] == 1  # unified snapshot keys
    assert "p99_s" in snap["ttft"] and "total_count" in snap["ttft"]
    assert snap["slo"]["healthy"] in (True, False)


def test_trace_endpoint_schema_and_request_lifecycle(model):
    """GET /trace after a multi-request run: valid Chrome trace JSON, and
    at least one request id whose queued → prefill → decode → retire
    spans all share that id; engine_step spans carry batch + routing."""
    cfg, params = model
    server = MegatronServer(cfg, params,
                            NullTokenizer(vocab_size=cfg.vocab_size),
                            max_batch_size=2)
    server.run("127.0.0.1", 0, block=False)
    try:
        out = _generate(server.port, ["5 9 3", "7 2", "11 12"], ttg=4)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/trace",
                timeout=60) as resp:
            assert resp.headers["Content-Type"] == "application/json"
            trace = json.loads(resp.read())
    finally:
        server.shutdown()

    assert trace["displayTimeUnit"] == "ms"
    assert "dropped_events" in trace["otherData"]
    events = trace["traceEvents"]
    assert events, "multi-request run produced no trace events"
    for ev in events:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
        assert ev["ph"] in ("X", "i", "M")
        # a metadata event (the compile row's ``thread_name``) names a
        # row and has no time, as the Chrome trace format has it
        if ev["ph"] == "M":
            assert ev["name"] == "thread_name" and ev["args"]["name"]
            continue
        assert "ts" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0

    rids = out["request_ids"]
    assert len(rids) == 3 and len(set(rids)) == 3

    def phases(rid):
        return {e["name"] for e in events
                if e.get("args", {}).get("request_id") == rid}

    for rid in rids:
        ph = phases(rid)
        assert "queued" in ph, f"{rid}: {ph}"
        assert any(p == "prefill" or p.startswith("prefill_chunk")
                   for p in ph), f"{rid}: {ph}"
        assert "decode" in ph and "retire" in ph, f"{rid}: {ph}"

    steps = [e for e in events if e["name"] == "engine_step"]
    assert steps, "no per-iteration engine_step spans"
    assert all(e["args"]["batch"] >= 1 for e in steps)
    assert all(e["args"]["route"] in ("paged", "fallback") for e in steps)


def test_request_id_correlates_log_lines_and_spans(model):
    """One id, three views: the HTTP response's request_ids, the
    structured event log's lifecycle lines, and the trace spans."""
    cfg, params = model
    EVENT_LOG.clear()
    svc = GenerationService(cfg, params,
                            NullTokenizer(vocab_size=cfg.vocab_size),
                            max_batch_size=2)
    try:
        status, out = svc.handle({"prompts": ["5 9 3"],
                                  "tokens_to_generate": 3,
                                  "no_early_termination": True})
        assert status == 200
        (rid,) = out["request_ids"]
        lines = EVENT_LOG.recent(request_id=rid)
        seen = [l["event"] for l in lines]
        for event in ("submitted", "first_token", "finished"):
            assert event in seen, f"missing {event} in {seen}"
        finished = next(l for l in lines if l["event"] == "finished")
        assert finished["component"] == "engine"
        assert finished["reason"] in ("length", "eos")
        assert finished["generated"] == 3
        first = next(l for l in lines if l["event"] == "first_token")
        assert first["ttft_s"] > 0

        span_rids = {e.get("args", {}).get("request_id")
                     for e in svc.engine.trace.chrome_trace()["traceEvents"]}
        assert rid in span_rids
    finally:
        svc.close()


def test_no_trace_escape_hatch(model):
    """trace=False (the --no_trace server flag): requests serve normally
    and /trace returns an empty-but-valid document."""
    cfg, params = model
    svc = GenerationService(cfg, params,
                            NullTokenizer(vocab_size=cfg.vocab_size),
                            max_batch_size=2, trace=False)
    try:
        status, out = svc.handle({"prompts": ["5 9"],
                                  "tokens_to_generate": 3,
                                  "no_early_termination": True})
        assert status == 200 and len(out["text"]) == 1
        trace = svc.trace_snapshot()
        assert trace["traceEvents"] == []
        assert not svc.engine.trace.enabled
    finally:
        svc.close()


# --- POST /profile: the operator's handle on obs/profile.py -----------------

def _post_profile(port, body):
    import urllib.error

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/profile", data=json.dumps(body).encode(),
        method="POST", headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_profile_route_traces_a_serving_engine_twice(model, tmp_path):
    """Two profiles of one running server, each under the configured
    directory; GET /trace then carries the last one's clock-sync pair,
    which maps the engine's spans onto the profile's clock."""
    import glob

    cfg, params = model
    server = MegatronServer(cfg, params,
                            NullTokenizer(vocab_size=cfg.vocab_size),
                            max_batch_size=2, profile_dir=str(tmp_path))
    server.run("127.0.0.1", 0, block=False)
    try:
        _generate(server.port, ["5 9 3"], ttg=3)
        replies = []
        for _ in range(2):
            status, reply = _post_profile(server.port, {"seconds": 1.1})
            assert status == 200, reply
            replies.append(reply)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/trace", timeout=60) as resp:
            other = json.loads(resp.read())["otherData"]
    finally:
        server.shutdown()
    a, b = replies
    assert a["dir"] != b["dir"]
    for reply in replies:
        assert reply["dir"].startswith(str(tmp_path))
        assert glob.glob(reply["dir"] + "/plugins/profile/*/*.xplane.pb")
        sync = reply["clock_sync"]
        assert sync["annotation"] == "obs_clock_sync"
        assert sync["stop_perf_counter"] - sync["perf_counter"] >= 1.0
    assert other["clock_sync"] == b["clock_sync"]
    assert other["epoch_perf_counter"] < b["clock_sync"]["perf_counter"]


@pytest.mark.parametrize("profile_dir,body,active,status", [
    (None, {"seconds": 1}, False, 403),          # no directory configured
    ("d", {"seconds": 1}, True, 409),            # a session is running
    ("d", {"seconds": 0}, False, 400),
    ("d", {"seconds": 1e9}, False, 400),
    ("d", {"seconds": "soon"}, False, 400),
])
def test_profile_route_refusals(model, tmp_path, profile_dir, body, active,
                                status):
    from megatron_llm_tpu.obs import profile

    cfg, params = model
    service = GenerationService(
        cfg, params, NullTokenizer(vocab_size=cfg.vocab_size),
        profile_dir=profile_dir and str(tmp_path / profile_dir))
    if active:
        profile.start(tmp_path / "other")
    try:
        got, _payload = service.profile(body)
    finally:
        if active:
            profile.stop()
    assert got == status
    assert profile.active() is None
    assert not (tmp_path / "d").exists()


# --- the spans a token's gap is put down to ------------------------------------

@pytest.mark.parametrize("prefill_chunk", [None, 4])
def test_admit_and_first_token_spans_share_the_scheduler_iteration(
        model, prefill_chunk):
    """``admit`` (track 0) covers an ``_admit()`` call that admitted at
    least one request and says how many and how many prompt tokens;
    ``engine_step``, ``prefill`` and ``decode`` carry the same ``iter``,
    so a stretched gap is traced to the admission that caused it."""
    from megatron_llm_tpu.serving import EngineConfig, ServingEngine

    cfg, params = model
    engine = ServingEngine(cfg, params, EngineConfig(
        max_batch_size=2, max_seq_len=64, kv_block_size=8,
        prefill_chunk=prefill_chunk)).start()
    try:
        prompts = [[5, 9, 3, 7, 2, 8], [7, 2, 4], [11, 12, 13, 14, 15]]
        handles = [engine.submit(p, 4, use_eos_stop=False, seed=0)
                   for p in prompts]
        for h in handles:
            h.result(timeout=300)
    finally:
        engine.shutdown(timeout=60.0)
    events = [e for e in engine.trace.chrome_trace()["traceEvents"]
              if e["ph"] == "X"]
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    admits = by["admit"]
    assert all(e["tid"] == 0 for e in admits)
    assert sum(e["args"]["admitted"] for e in admits) == len(prompts)
    assert sum(e["args"]["prompt_tokens"] for e in admits) == \
        sum(len(p) for p in prompts)
    iters = [e["args"]["iter"] for e in admits]
    assert iters == sorted(set(iters)) and iters[0] >= 1
    steps = {e["args"]["iter"]: e for e in by["engine_step"]}
    assert len(steps) == len(by["engine_step"])      # one a scheduler turn
    for e in by["decode"]:
        assert e["args"]["iter"] in steps
    if prefill_chunk is None:
        # whole-prompt admission: the prefill and the first token's own
        # cost lie inside their admit span, under its iteration
        assert len(by["first_token"]) == len(prompts)
        for name in ("prefill", "first_token"):
            for e in by[name]:
                (a,) = [a for a in admits if a["ts"] <= e["ts"] and
                        e["ts"] + e["dur"] <= a["ts"] + a["dur"] + 1]
                if name == "prefill":
                    assert e["args"]["iter"] == a["args"]["iter"]


def test_a_compile_inside_a_request_is_named_in_all_three_views():
    """A prompt width that was never warmed compiles inside the request's
    ``prefill``: ``GET /trace`` shows a ``compile`` event whose cause
    carries the request's id, ``/metrics`` counts the program under
    ``compilations_total`` and the event log holds its line
    (obs/compile.py; docs/observability.md "Compilations")."""
    # a width no other test serves, so that the executable is new here
    cfg = tiny_config(num_layers=1, vocab_size=264,
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(1), cfg)
    svc = GenerationService(cfg, params,
                            NullTokenizer(vocab_size=cfg.vocab_size),
                            max_batch_size=2, prefill_bucket=8)
    try:
        status, out = svc.handle({"prompts": ["5 9 3 4 1"],
                                  "tokens_to_generate": 2,
                                  "no_early_termination": True})
        assert status == 200
        (rid,) = out["request_ids"]
        events = svc.trace_snapshot()["traceEvents"]
        prefills = [e for e in events if e["name"] == "compile"
                    and e["args"].get("cause", {}).get("span") == "prefill"]
        assert prefills, [e["args"] for e in events
                          if e["name"] == "compile"]
        by_program = {e["args"]["program"]: e for e in prefills}
        ev = by_program["jit(_prefill_impl)"]
        assert ev["args"]["cause"]["request_id"] == rid
        assert ev["args"]["cause"]["padded"] == 8
        assert set(ev["args"]["stage_s"]) == {"trace", "lower", "backend"}
        # the span lies inside its cause on the recorder's clock
        (pf,) = [e for e in events if e["name"] == "prefill"
                 and e["args"]["request_id"] == rid]
        assert pf["ts"] <= ev["ts"] and \
            ev["ts"] + ev["dur"] <= pf["ts"] + pf["dur"] + 1
        _types, samples = parse_prometheus(svc.prometheus_metrics())
        counted = {dict(k[1])["program"]: v for k, v in samples.items()
                   if k[0] == "compilations_total"}
        assert counted["jit(_prefill_impl)"] >= 1
        assert any(k[0] == "compile_seconds_total"
                   and dict(k[1]) == {"program": "jit(_prefill_impl)",
                                      "stage": "backend"} for k in samples)
        lines = [l for l in EVENT_LOG.recent(event="compile")
                 if l["program"] == "jit(_prefill_impl)"]
        assert lines and lines[-1]["component"] == "obs"
        # the same width again: served from the executable, nothing new
        n = len([e for e in events if e["name"] == "compile"])
        status, _ = svc.handle({"prompts": ["7 2 6 8"],
                                "tokens_to_generate": 2,
                                "no_early_termination": True})
        assert status == 200
        assert len([e for e in svc.trace_snapshot()["traceEvents"]
                    if e["name"] == "compile"]) == n
    finally:
        svc.close()


# --- the scheduler's iteration in phases (engine.py:SCHED_PHASES) --------------

def _serve(model, prompts, new_tokens, **engine_kw):
    """A served batch → the engine (shut down) and its complete events."""
    from megatron_llm_tpu.serving import EngineConfig, ServingEngine

    cfg, params = model
    kw = dict(max_batch_size=2, max_seq_len=64, kv_block_size=8)
    kw.update(engine_kw)
    engine = ServingEngine(cfg, params, EngineConfig(**kw)).start()
    try:
        handles = [engine.submit(p, new_tokens, use_eos_stop=False, seed=0)
                   for p in prompts]
        for h in handles:
            h.result(timeout=300)
    finally:
        engine.shutdown(timeout=60.0)
    return engine, [e for e in engine.trace.chrome_trace()["traceEvents"]
                    if e["ph"] == "X"]


def _inside(e, outer, slack=1.0):
    """``e`` lies inside ``outer`` (microseconds; the export rounds)."""
    return (outer["ts"] - slack <= e["ts"]
            and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + slack)


def _phases_of(events, outer, names):
    return [e for e in events if e["name"] in names and e["tid"] == 0
            and _inside(e, outer)]


STEP_PHASES = ("step_inputs", "dispatch", "fetch", "commit")
ADMIT_PHASES = ("admit_setup", "prefill_dispatch", "slot_insert",
                "prefill_wait", "admit_commit")
PROMPTS = [[5, 9, 3, 7, 2, 8], [7, 2, 4], [11, 12, 13, 14, 15]]


@pytest.mark.parametrize("pipelined", [True, False])
def test_every_decode_step_holds_its_phases_in_order(model, pipelined):
    """``step_inputs``, ``dispatch``, ``fetch`` and ``commit`` lie inside
    their ``engine_step`` in that order, none overlapping another, all
    under its ``iter``.  Pipelined, an iteration fetches the step the one
    before dispatched (``fetch``'s ``dispatched``), so a step that found
    no step in flight has no ``fetch``; not pipelined, every step fetches
    its own."""
    _engine, events = _serve(model, PROMPTS, 6, pipeline_decode=pipelined)
    steps = sorted((e for e in events if e["name"] == "engine_step"),
                   key=lambda e: e["ts"])
    assert len(steps) >= 6
    unfetched = []
    for step in steps:
        it = step["args"]["iter"]
        inner = sorted(_phases_of(events, step, STEP_PHASES),
                       key=lambda e: e["ts"])
        names = [e["name"] for e in inner]
        if names != list(STEP_PHASES):
            assert names == ["step_inputs", "dispatch"], (it, names)
            unfetched.append(it)
        assert {e["args"]["iter"] for e in inner} == {it}
        for a, b in zip(inner, inner[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1.0, (a, b)
        assert sum(e["dur"] for e in inner) <= step["dur"] + 2.0
        by = {e["name"]: e for e in inner}
        assert by["step_inputs"]["args"]["live"] == step["args"]["batch"]
        if "fetch" in by:
            assert by["fetch"]["args"]["dispatched"] == \
                it - (1 if pipelined else 0)
            assert 0 <= by["commit"]["args"]["committed"] <= 2
    if pipelined:
        # only the first step of a run of steps finds nothing in flight
        assert steps[0]["args"]["iter"] in unfetched
        assert all(it - 1 not in {s["args"]["iter"] for s in steps}
                   for it in unfetched)
    else:
        assert not unfetched
    committed = sum(e["args"]["committed"] for e in events
                    if e["name"] == "commit")
    # every token but each request's first (the admission's) is a step's
    assert committed == len(PROMPTS) * (6 - 1)


def test_the_phases_cover_most_of_the_median_step(model):
    """Over a served batch of twenty steps and more, the median step's
    phases cover four fifths of it: what no phase covers is the loop's
    own overhead, and it is small.  (No bound in milliseconds: the test
    machine is shared.)"""
    _engine, events = _serve(model, PROMPTS[:2], 24)
    steps = [e for e in events if e["name"] == "engine_step"]
    assert len(steps) >= 20
    shares = sorted(
        sum(e["dur"] for e in _phases_of(events, s, STEP_PHASES)) / s["dur"]
        for s in steps if s["dur"] > 0)
    assert shares[len(shares) // 2] >= 0.8, shares


@pytest.mark.parametrize("prefix_cache_blocks", [0, 16])
def test_every_admission_holds_its_five_phases(model, prefix_cache_blocks):
    """A whole-prompt admission: ``admit_setup``, ``prefill_dispatch``,
    ``slot_insert``, ``prefill_wait`` and ``admit_commit`` follow one
    another without a hole inside the ``admit`` span, under the request's
    id; ``prefill_wait`` ends where the request's ``prefill`` ends, and
    ``prefill_dispatch`` begins where it begins."""
    prompts = PROMPTS + [PROMPTS[0] + [1, 2, 3, 4, 5, 6]]
    _engine, events = _serve(model, prompts, 3,
                             prefix_cache_blocks=prefix_cache_blocks)
    admits = [e for e in events if e["name"] == "admit"]
    prefills = {e["args"]["request_id"]: e for e in events
                if e["name"] == "prefill"}
    assert len(prefills) == len(prompts)
    seen = 0
    for rid, pf in prefills.items():
        mine = sorted((e for e in events if e["name"] in ADMIT_PHASES
                       and e["args"].get("request_id") == rid),
                      key=lambda e: e["ts"])
        assert [e["name"] for e in mine] == list(ADMIT_PHASES)
        assert all(e["tid"] == 0 for e in mine)
        (admit,) = [a for a in admits if _inside(pf, a)]
        assert all(_inside(e, admit) for e in mine)
        for a, b in zip(mine, mine[1:]):    # one reading ends a, begins b
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1.0)
        by = {e["name"]: e for e in mine}
        assert by["prefill_dispatch"]["ts"] == pytest.approx(pf["ts"],
                                                             abs=1.0)
        assert by["prefill_wait"]["ts"] + by["prefill_wait"]["dur"] == \
            pytest.approx(pf["ts"] + pf["dur"], abs=1.0)
        assert by["admit_setup"]["args"]["iter"] == pf["args"]["iter"]
        assert by["prefill_dispatch"]["args"]["padded"] == \
            pf["args"]["padded"]
        seen += 1
    assert seen == len(prompts)


def test_sched_phases_is_the_vocabulary_the_engine_records():
    """``SCHED_PHASES`` is ``gc`` and the names ``engine.py`` records on
    the scheduler's track other than ``engine_step`` and ``admit``: a
    phase added to the source and left out of the table (or the other way
    round) fails here, not in the benchmark's reader."""
    from pathlib import Path

    from megatron_llm_tpu.serving import engine as engine_mod

    text = Path(engine_mod.__file__).read_text()
    calls = re.findall(
        r'self\.trace\.add\(\s*"(\w+)",((?:[^()]|\([^()]*\))*)\)', text)
    assert len(calls) >= 20
    track0 = {name for name, rest in calls
              if "tid=req.id" not in rest and "tid=st.req.id" not in rest
              and "tid=sus.req.id" not in rest}
    assert {"engine_step", "admit"} <= track0
    on_requests = {name for name, _rest in calls} - track0
    assert on_requests >= {"queued", "prefill", "first_token", "decode"}
    assert track0 - {"engine_step", "admit"} | {"gc"} == \
        set(engine_mod.SCHED_PHASES)
    assert set(engine_mod.SCHED_PHASES.values()) == {"own", "blocked"}
    assert [k for k, v in engine_mod.SCHED_PHASES.items()
            if v == "blocked"] == ["fetch", "prefill_wait"]


def test_a_wait_is_recorded_ahead_of_the_spans_that_cover_it(model):
    """``fetch`` enters the ring before its step's ``decode`` spans and
    its ``engine_step``, and an admission's phases before its ``prefill``:
    the benchmark's gap namer breaks a tie of overlaps by that order."""
    _engine, events = _serve(model, PROMPTS[:2], 5)
    order = {}
    for i, e in enumerate(events):
        order.setdefault((e["name"], e["args"].get("iter"),
                          e["args"].get("request_id")), i)
    fetches = [e for e in events if e["name"] == "fetch"]
    assert fetches
    for f in fetches:
        it = f["args"]["iter"]
        later = [i for (n, k, _r), i in order.items()
                 if k == it and n in ("decode", "engine_step", "commit")]
        assert later and order[("fetch", it, None)] < min(later)
    for e in events:
        if e["name"] == "prefill":
            rid = e["args"]["request_id"]
            assert order[("prefill_wait", None, rid)] < \
                order[("prefill", e["args"]["iter"], rid)]


def test_a_verify_step_carries_the_phases_its_readings_bound(model):
    """The n-gram verify step (synchronous) takes no reading between its
    call and its fetch: ``step_inputs``, ``fetch`` from the call to the
    tokens on the host, ``commit``; no ``dispatch``."""
    prompts = [[7, 7, 7, 7, 7, 7, 7], [5, 9, 3, 5, 9, 3, 5, 9, 3, 5, 9]]
    _engine, events = _serve(model, prompts, 20, spec_draft_len=3)
    spec = [e for e in events if e["name"] == "engine_step"
            and e["args"]["route"] == "spec_fallback"]
    assert spec
    for step in spec:
        inner = sorted(_phases_of(events, step, STEP_PHASES),
                       key=lambda e: e["ts"])
        assert [e["name"] for e in inner] == ["step_inputs", "fetch",
                                              "commit"]
        assert inner[0]["ts"] == pytest.approx(step["ts"], abs=1.0)
        assert {e["args"]["iter"] for e in inner} == {step["args"]["iter"]}
        assert inner[2]["args"]["committed"] >= 1


def test_trace_off_records_no_phase_and_watches_no_collection(model):
    """``trace=False``: nothing is recorded and nothing is put on
    ``gc.callbacks``."""
    import gc

    before = list(gc.callbacks)
    engine, events = _serve(model, PROMPTS[:1], 3, trace=False)
    assert events == [] and len(engine.trace) == 0
    assert engine._gc_watch is None
    assert gc.callbacks == before


def test_a_long_collection_under_a_running_engine_is_a_gc_span(model):
    """A full collection over a few hundred thousand cyclic objects while
    an engine runs leaves a ``gc`` span of generation 2 on the track of
    its own; once the engine has shut down ``gc.callbacks`` is as long as
    it was before the engine started."""
    import gc

    from megatron_llm_tpu.obs.trace import GC_TID, GcWatch
    from megatron_llm_tpu.serving import EngineConfig, ServingEngine

    cfg, params = model
    n_before = len(gc.callbacks)
    engine = ServingEngine(cfg, params, EngineConfig(
        max_batch_size=2, max_seq_len=64, kv_block_size=8))
    assert len(gc.callbacks) == n_before       # built, not yet started
    engine.start()
    try:
        assert sum(isinstance(cb, GcWatch) for cb in gc.callbacks) >= 1
        assert engine._gc_watch in gc.callbacks
        h = engine.submit(PROMPTS[0], 4, use_eos_stop=False, seed=0)
        junk = []
        for _ in range(300_000):
            a = []
            a.append(a)                        # a cycle: the collector's
            junk.append(a)
        del junk, a
        gc.collect()
        h.result(timeout=300)
    finally:
        engine.shutdown(timeout=60.0)
    assert len(gc.callbacks) == n_before
    assert engine._gc_watch not in gc.callbacks
    doc = engine.trace.chrome_trace()
    spans = [e for e in doc["traceEvents"] if e["name"] == "gc"]
    assert spans and all(e["tid"] == GC_TID and e["ph"] == "X"
                         and e["dur"] >= 1000.0 for e in spans)
    full = [e for e in spans if e["args"]["generation"] == 2]
    assert full and max(e["args"]["collected"] for e in full) >= 300_000
    assert {"name": "thread_name", "ph": "M", "pid": spans[0]["pid"],
            "tid": GC_TID, "args": {"name": "gc"}} in doc["traceEvents"]
    # a second start puts the watch back, a second shutdown takes it off
    engine.start()
    assert engine._gc_watch in gc.callbacks
    engine.shutdown(timeout=60.0)
    assert len(gc.callbacks) == n_before


def test_collections_recorded_from_inside_the_engines_own_spans(model,
                                                                 monkeypatch):
    """Collections forced every few allocations, each one recorded (the
    threshold at zero): many start while the scheduler is inside the
    recorder's ``add``, holding its lock.  The batch is served to its end
    and the ring holds their spans — a callback that took the lock again
    stood the scheduler still here within a few steps."""
    import gc

    from megatron_llm_tpu.obs import trace as trace_mod

    monkeypatch.setattr(trace_mod, "GC_MIN_S", 0.0)
    old = gc.get_threshold()
    gc.set_threshold(5, 1, 1)
    try:
        _engine, events = _serve(model, PROMPTS * 2, 8)
    finally:
        gc.set_threshold(*old)
    spans = [e for e in events if e["name"] == "gc"]
    assert len(spans) >= 20
    assert {e["args"]["generation"] for e in spans} >= {0, 1}
    # (every result came back, or ``_serve`` had raised; the ring is
    # overrun by the collections, so early spans are gone)
    assert _engine.metrics.snapshot()["completed"] == 6


# --- the forward attention kernel's schedule on a prefill span -----------------

@pytest.mark.parametrize("impl,bound,prompt_len", [
    ("flash", 128, 100),    # a bucket of 128: one tile, masked
    ("flash", 128, 130),    # a bucket of 256 under a bound of 128: 3 of 4
    ("flash", 1024, 130),   # the same bucket in one tile
    ("dot", 128, 130),      # no such kernel, no such field
], ids=["one_tile", "two_row_blocks", "wide_bound", "dot"])
def test_a_prefill_span_carries_its_buckets_tile_plan(impl, bound,
                                                      prompt_len):
    """A whole-prompt prefill's span says what ``flash_fwd`` did for its
    bucket, ``flash_tiles = "live/masked/padded_rows"``, and that is
    ``tile_plan`` of the padded length under the configuration's bounds."""
    from megatron_llm_tpu.kernels.flash_attention import tile_plan

    cfg = tiny_config(num_layers=1, vocab_size=256,
                      make_vocab_size_divisible_by=8, attention_impl=impl,
                      max_position_embeddings=384, flash_block_q=bound,
                      flash_block_k=bound)
    params = model_lib.init_params(jax.random.key(0), cfg)
    prompt = [1 + i % 200 for i in range(prompt_len)]
    _engine, events = _serve((cfg, params), [prompt, prompt[:prompt_len - 1]],
                             2, max_seq_len=384, prefill_bucket=128,
                             prefix_cache_blocks=0)
    prefills = [e for e in events if e["name"] == "prefill"]
    assert len(prefills) == 2
    for pf in prefills:
        padded = pf["args"]["padded"]
        assert padded == -(-prompt_len // 128) * 128
        if impl != "flash":
            assert "flash_tiles" not in pf["args"]
            continue
        plan = tile_plan(padded, padded, bound, bound)
        assert pf["args"]["flash_tiles"] == \
            f"{plan.live}/{plan.masked}/{plan.padded_rows}"
    if impl == "flash":
        want = {(128, 100): "1/1/0", (128, 130): "3/2/0",
                (1024, 130): "1/1/0"}[bound, prompt_len]
        assert prefills[0]["args"]["flash_tiles"] == want
