"""Cluster self-healing tests (docs/robustness.md, "Cluster
self-healing"): the supervised replica lifecycle under injected faults.

- **rebuild after crash** — a chaos scheduler-step crash kills a replica
  raw; the supervisor rebuilds it on its original submesh, re-warms it
  off-rotation, and rejoins it at a bumped generation, while the
  in-flight requests fail over with bitwise client streams.
- **poison quarantine** — a request whose admission deterministically
  crashes its host engine is finished with ``finish_reason=
  "quarantined"`` after its second crash instead of being resubmitted to
  kill a third replica; both crashed replicas rebuild and subsequent
  traffic runs at full capacity with zero post-warmup recompiles.
- **hung-step watchdog** — a wedged device dispatch (thread alive,
  iteration heartbeat stale) is detected, killed, and rebuilt.
- **shipment I/O faults** — chaos ``fail_io`` on the export/import
  ``device_put`` paths: the request keeps decoding at home (export) or
  reinstalls at the source (import), ledgers balanced on both submeshes
  and client streams bitwise.
- **router backpressure** — an all-draining cluster surfaces as HTTP
  503 + Retry-After with a ``router_queue_full`` EVENT_LOG line.
- **deadline-aware failover** — a request whose wall-clock budget
  expired before failover finishes with ``"timeout"`` instead of
  burning a slot on a dead-on-arrival resubmit; a live budget is passed
  through as the *remaining* time, never a fresh one.
- **compound-fault soak** — the randomized kill/hang/ship-fault storm
  over ≥ 64 mixed requests (tests/serving/chaos_soak.py):
  exactly-once delivery, balanced ledgers on every incarnation, cluster
  back at full strength.
"""

import time

import jax
import numpy as np
import pytest

from megatron_llm_tpu.analysis.sanitizers import no_recompiles
from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.obs.logging import EVENT_LOG
from megatron_llm_tpu.resilience import chaos
from megatron_llm_tpu.serving import (
    EngineConfig,
    ReplicaSupervisor,
    RouterConfig,
    ServingEngine,
    SupervisorConfig,
    build_cluster,
    build_disagg_cluster,
)

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def clean_chaos():
    # tests/serving has no chaos bootstrap (unlike tests/resilience) —
    # the controller is process-global, so disarm around every test
    chaos().reset()
    EVENT_LOG.clear()
    yield
    chaos().reset()


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(num_layers=2, vocab_size=64,
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def _prompts(cfg, n, seed=0, lo=4, hi=12):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size,
                         int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def _run(engine_or_router, specs, timeout=300):
    handles = engine_or_router.submit_many(specs)
    return [h.result(timeout) for h in handles]


def _reference_tokens(cfg, params, specs, **cfg_overrides):
    """Uninterrupted single-chip engine run — the parity baseline."""
    kw = dict(max_batch_size=2, max_seq_len=64, max_queue_size=32)
    kw.update(cfg_overrides)
    engine = ServingEngine(cfg, params, EngineConfig(**kw)).start()
    try:
        return [list(r.tokens) for r in _run(engine, specs)]
    finally:
        engine.shutdown()


def _heal(router, sup, rebuilt=1, timeout=300.0) -> bool:
    """Wait until the supervisor has rebuilt ``rebuilt`` replicas and
    every replica is alive again.  (Alive alone is also what a replica
    looks like before the prober has seen its crash.)"""
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        if sup.rebuilt_total >= rebuilt and all(
                r.alive() and not r.dead for r in router.replicas):
            return True
        time.sleep(0.02)
    return False


def _ec(**kw):
    base = dict(max_batch_size=1, max_seq_len=64, max_queue_size=32,
                prefill_bucket=16, sanitize=True)
    base.update(kw)
    return EngineConfig(**base)


def _supervise(router, **kw):
    kw.setdefault("interval_s", 0.02)
    kw.setdefault("warm_specs", [dict(prompt=[1, 2, 3, 4],
                                      max_new_tokens=2,
                                      use_eos_stop=False)] * 3)
    return ReplicaSupervisor(router, SupervisorConfig(**kw)).start()


# ---------------------------------------------------------------------------
# tentpole: crash rebuild, watchdog, poison quarantine
# ---------------------------------------------------------------------------

def test_supervisor_rebuilds_crashed_replica(tiny):
    cfg, params = tiny
    specs = [dict(prompt=p, max_new_tokens=10, seed=i, use_eos_stop=False)
             for i, p in enumerate(_prompts(cfg, 4, seed=1))]
    ref = _reference_tokens(cfg, params, specs)
    router = build_cluster(
        cfg, params, _ec(), replicas=2,
        router_config=RouterConfig(probe_interval_s=0.02)).start()
    sup = _supervise(router, hang_timeout_s=0)
    try:
        handles = router.submit_many(specs)
        time.sleep(0.1)  # let both schedulers take work
        # raw scheduler-step crash: no cleanup, no request failed by the
        # engine — probe-detected, exactly like a real kill
        chaos().crash_at("serve-step")
        results = [h.result(300) for h in handles]

        # zero lost accepted tokens: bitwise the uninterrupted run
        assert [list(r.tokens) for r in results] == ref
        assert ("crash", "serve-step") in chaos().events

        # capacity restored: the dead replica rebuilt on its submesh and
        # rejoined at a bumped generation
        assert _heal(router, sup)
        assert sup.rebuilt_total >= 1
        assert sum(r.generation for r in router.replicas) \
            == sup.rebuilt_total
        assert EVENT_LOG.recent(event="replica_rebuilding")
        rejoined = EVENT_LOG.recent(event="replica_rejoined")
        assert rejoined and rejoined[-1]["generation"] >= 1
        assert any(ev["name"] == "rebuild"
                   for ev in router.trace.chrome_trace()["traceEvents"])

        # the rebuilt cluster serves a fresh wave at full strength
        again = _run(router, specs)
        assert [list(r.tokens) for r in again] == ref
        snap = router.snapshot()
        assert snap["router"]["usable"] == 2
        assert snap["router"]["replicas_rebuilt_total"] == \
            sup.rebuilt_total
    finally:
        router.shutdown()
    # ledgers balanced on every incarnation, dead ones included
    for r in router.replicas:
        assert r.engine.sanitizer_report == []
    for reports in sup.incarnation_reports.values():
        for rep in reports:
            assert rep == []


def test_watchdog_kills_wedged_replica(tiny):
    cfg, params = tiny
    specs = [dict(prompt=p, max_new_tokens=24, seed=i, use_eos_stop=False)
             for i, p in enumerate(_prompts(cfg, 4, seed=2))]
    ref = _reference_tokens(cfg, params, specs)
    router = build_cluster(
        cfg, params, _ec(), replicas=2,
        router_config=RouterConfig(probe_interval_s=0.02)).start()
    # every executable compiled before the watchdog is armed: a first
    # compilation outlasts a hang_timeout_s of 0.4 s, and in a process that
    # has not run these shapes yet (the first test a worker draws, or the
    # first after tests/conftest.py dropped jax's caches) the watchdog
    # killed both healthy replicas in turn and the request came back
    # quarantined, cut short
    assert [list(r.tokens) for r in _run(router, specs)] == ref
    sup = _supervise(router, hang_timeout_s=0.4)
    try:
        handles = router.submit_many(specs)
        # in flight, and far from done: a fixed sleep here let warm
        # executables finish every request before the hang was armed
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not any(
                r.engine.metrics.snapshot()["decode_iterations"]
                for r in router.replicas):
            time.sleep(0.001)
        # wedge one dispatch: thread stays alive, the iteration
        # heartbeat goes stale — only the watchdog can see this
        chaos().hang_at("serve-dispatch", seconds=2.0)
        results = [h.result(300) for h in handles]
        assert [list(r.tokens) for r in results] == ref
        assert ("hang", "serve-dispatch") in chaos().events
        assert _heal(router, sup)
        assert sup.watchdog_trips_total >= 1
        assert sup.rebuilt_total >= 1
        assert EVENT_LOG.recent(event="watchdog_trip")
        snap = router.snapshot()
        assert snap["router"]["usable"] == 2
        assert snap["router"]["watchdog_trips_total"] >= 1
    finally:
        router.shutdown()


def test_poison_request_quarantined_then_full_capacity(tiny):
    cfg, params = tiny
    wave = [dict(prompt=p, max_new_tokens=8, seed=i, use_eos_stop=False)
            for i, p in enumerate(_prompts(cfg, 6, seed=3, lo=8, hi=17))]
    ref = _reference_tokens(cfg, params, wave)
    warm = [dict(prompt=list(wave[0]["prompt"]), max_new_tokens=4,
                 use_eos_stop=False)] * 3
    router = build_cluster(
        cfg, params, _ec(max_batch_size=2), replicas=3,
        router_config=RouterConfig(probe_interval_s=0.02, max_resubmits=4,
                                   quarantine_after=2)).start()
    sup = _supervise(router, hang_timeout_s=0, warm_specs=warm)
    try:
        # warm every original replica with workload-shaped traffic
        for _ in range(2):
            _run(router, wave)

        # the poison request: crashes whichever engine ADMITS it, keyed
        # to its resolved seed so the crash follows it across failover
        poison_seed = 1234
        chaos().crash_at(f"serve-admit:{poison_seed}", times=2)
        [h] = router.submit_many([dict(prompt=wave[0]["prompt"],
                                       max_new_tokens=8,
                                       seed=poison_seed,
                                       use_eos_stop=False)])
        res = h.result(300)
        # quarantined after exactly 2 crash-correlated incarnations —
        # never resubmitted to take down the third replica
        assert res.finish_reason == "quarantined"
        assert h._rr.crashes == 2
        q = EVENT_LOG.recent(event="request_quarantined")
        assert q and q[-1]["crashes"] == 2
        assert router.quarantined_total == 1

        # both crashed replicas rebuilt; cluster back to 3/3
        assert _heal(router, sup, rebuilt=2)
        assert sup.rebuilt_total == 2
        assert sorted(r.generation for r in router.replicas) == [0, 1, 1]
        snap = router.snapshot()
        assert snap["router"]["usable"] == 3
        assert snap["router"]["quarantined_total"] == 1

        # full capacity, zero post-warmup recompiles: the rebuilt
        # replicas were re-warmed off-rotation with workload-shaped
        # specs, so the serving window never pays a compile
        with no_recompiles():
            results = _run(router, wave)
        assert [list(r.tokens) for r in results] == ref
    finally:
        router.shutdown()
    for r in router.replicas:
        assert r.engine.sanitizer_report == []
    for reports in sup.incarnation_reports.values():
        for rep in reports:
            assert rep == []


# ---------------------------------------------------------------------------
# shipment I/O faults: keep-local fallback, balanced ledgers (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site,event", [
    ("ship-export", "ship_export_failed"),
    ("ship-import", "ship_failed"),
])
def test_ship_io_fault_keeps_streams_bitwise(tiny, site, event):
    cfg, params = tiny
    specs = [dict(prompt=p, max_new_tokens=8, seed=i, use_eos_stop=False)
             for i, p in enumerate(_prompts(cfg, 3, seed=4))]
    ref = _reference_tokens(cfg, params, specs)
    streams = {i: [] for i in range(len(specs))}
    router = build_disagg_cluster(cfg, params, _ec(max_batch_size=2),
                                  prefill_replicas=1,
                                  decode_replicas=1).start()
    try:
        # first shipment hits the fault: export failure keeps the
        # request decoding on the prefill replica; import failure
        # reinstalls it there after the destination's unwind.  The
        # remaining shipments go through clean.
        chaos().fail_io(site)
        results = _run(router, [dict(s, on_token=streams[i].append)
                                for i, s in enumerate(specs)])
        assert ("fail_io", site) in chaos().events
        assert EVENT_LOG.recent(event=event)
        assert [list(r.tokens) for r in results] == ref
        for i, r in enumerate(results):
            assert streams[i] == list(map(int, r.tokens[r.prompt_len:]))
        if site == "ship-export":
            # the engine's own fallback counter; import failures are
            # observed (and recovered) router-side instead
            pre = router.replicas[0].engine
            assert pre.metrics.snapshot()["ship_failures_total"] >= 1
    finally:
        router.shutdown()
    # balanced ledgers on BOTH submeshes after the fallback
    for r in router.replicas:
        assert r.engine.sanitizer_report == []


# ---------------------------------------------------------------------------
# router backpressure -> 503 (satellite)
# ---------------------------------------------------------------------------

def test_router_queue_full_surfaces_as_503(tiny):
    from megatron_llm_tpu.generation.server import GenerationService
    from megatron_llm_tpu.tokenizer.tokenizer import NullTokenizer

    cfg, params = tiny
    svc = GenerationService(cfg, params,
                            NullTokenizer(vocab_size=cfg.vocab_size),
                            max_batch_size=2, engine_max_seq_len=64,
                            replicas=2, router=True)
    try:
        svc.engine.drain(timeout=60)  # all replicas draining
        EVENT_LOG.clear()
        status, resp = svc.handle({"prompts": ["3 4 5"],
                                   "tokens_to_generate": 4})
        assert status == 503
        assert resp["retry_after"] >= 1  # -> Retry-After header
        assert "draining" in resp["message"]
        full = EVENT_LOG.recent(event="router_queue_full")
        assert full and full[-1]["reason"] == "draining"
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# deadline-aware failover (satellite)
# ---------------------------------------------------------------------------

def test_failover_expires_dead_budget_instead_of_resubmitting(tiny):
    cfg, params = tiny
    # slow probe: by the time the crash is detected, the request's
    # wall-clock budget is long gone — the old behavior resubmitted it
    # anyway, burning a slot on a dead-on-arrival retry
    router = build_cluster(
        cfg, params, _ec(sanitize=False), replicas=2,
        router_config=RouterConfig(probe_interval_s=0.5)).start()
    try:
        [h] = router.submit_many([dict(prompt=[1, 2, 3, 4],
                                       max_new_tokens=58,
                                       deadline_s=0.25, seed=0,
                                       use_eos_stop=False)])
        victim = h._rr.replica
        victim.engine.shutdown(timeout=30)  # crash before the deadline
        res = h.result(120)
        assert res.finish_reason == "timeout"
        snap = router.snapshot()
        assert snap["router"]["resubmitted_total"] == 0
        exp = EVENT_LOG.recent(event="failover_expired")
        assert exp and exp[-1]["replica"] == victim.id
    finally:
        router.shutdown()


def test_failover_passes_remaining_deadline(tiny):
    cfg, params = tiny
    router = build_cluster(
        cfg, params, _ec(sanitize=False), replicas=2,
        router_config=RouterConfig(probe_interval_s=0.02)).start()
    try:
        [h] = router.submit_many([dict(prompt=[1, 2, 3, 4],
                                       max_new_tokens=40,
                                       deadline_s=120.0, seed=0,
                                       use_eos_stop=False)])
        rr = h._rr
        original = rr.deadline
        assert original is not None
        router.kill_replica(rr.replica.id)
        if not rr.done_event.is_set():
            # the resubmitted engine request carries the ORIGINAL
            # absolute deadline (remaining budget), not a fresh 120s
            assert rr.handle._req.deadline == pytest.approx(original,
                                                            abs=1.0)
        assert h.result(120).finish_reason in ("length", "stop")
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# compound-fault chaos soak (slow tier; the CI chaos job runs it)
# ---------------------------------------------------------------------------

def test_chaos_soak_compound_faults(tiny):
    from chaos_soak import run_chaos_soak

    cfg, params = tiny
    # hang_timeout_s must clear the worst-case iteration latency of 3
    # schedulers sharing the host CPU, or slow-but-healthy iterations
    # trip the watchdog (docs/robustness.md: sizing the hang timeout)
    out = run_chaos_soak(cfg, params, num_requests=64, gen_len=10,
                         slots=2, max_prompt_len=32, replicas=3,
                         n_adapters=2, rank=4, draft_len=2,
                         hang_timeout_s=2.0, hang_s=6.0, seed=0)
    # every accepted token delivered exactly once, across every crash,
    # replay, shipment, and migration
    assert out["serving_chaos_delivery_violations"] == 0
    # ledgers balance on all incarnations — live and dead
    assert out["serving_chaos_leaked_blocks"] == 0
    # the cluster ends at full strength, with rebuilt generations
    assert out["serving_chaos_ended_full_strength"]
    assert out["serving_chaos_replicas_rebuilt"] >= 2
    assert out["serving_chaos_watchdog_trips"] >= 1
    assert {"serve-step", "serve-dispatch"} <= \
        set(out["serving_chaos_fired"])
    reasons = out["serving_chaos_finish_reasons"]
    assert set(reasons) <= {"length", "stop", "quarantined", "timeout"}
    # the storm may legitimately quarantine a few crash-correlated
    # bystanders; the overwhelming majority completes normally
    assert reasons.get("length", 0) + reasons.get("stop", 0) >= 56
