"""Serving decode fast-path tests (CPU, tiny model).

Covers the pipelined scheduler (one-step decode pipeline with lagged
retirement), chunked prefill admission, the condition-variable wakeups,
and the device/host metrics breakdown.  The load-bearing invariant is
the same bar the engine met at birth: greedy requests must be bitwise
identical to the one-shot ``generate_tokens`` trajectory — pipelined or
not, chunked or not — and a lagged-retirement slot must never leak its
masked speculative token into results or streaming callbacks.
"""

import threading
import time

import jax
import numpy as np
import pytest

from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.serving import EngineConfig, ServingEngine
from tests.serving.one_shot import reference as _reference


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(num_layers=2, vocab_size=64,
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def _engine(cfg, params, **overrides):
    kw = dict(max_batch_size=4, max_seq_len=64, max_queue_size=16)
    kw.update(overrides)
    return ServingEngine(cfg, params, EngineConfig(**kw))


def _run_batch(engine, prompts, max_news):
    handles = []
    try:
        for p, n in zip(prompts, max_news):
            handles.append(engine.submit(p, max_new_tokens=n,
                                         use_eos_stop=False))
            time.sleep(0.002)
        return [h.result(timeout=600) for h in handles]
    finally:
        engine.shutdown()


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["pipelined", "sync"])
def test_decode_matches_one_shot(tiny, pipeline):
    """Bitwise one-shot equivalence for both scheduler modes; ragged
    budgets force staggered lagged retirements mid-batch."""
    cfg, params = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size,
                            int(rng.integers(3, 11))).tolist()
               for _ in range(6)]
    max_news = [int(rng.integers(4, 14)) for _ in range(6)]
    engine = _engine(cfg, params, pipeline_decode=pipeline).start()
    results = _run_batch(engine, prompts, max_news)
    for p, n, r in zip(prompts, max_news, results):
        assert r.finish_reason == "length"
        assert r.tokens == _reference(cfg, params, p, n)
    assert engine.metrics.snapshot()["max_decode_batch"] >= 2


@pytest.fixture(scope="module")
def tiny_int8(tiny):
    """The tiny model fully int8-resident: quantized weights + int8 KV."""
    import dataclasses

    from megatron_llm_tpu.ops.quant import quantize_params

    cfg, params = tiny
    cfg_q = dataclasses.replace(cfg, kv_cache_quant="int8")
    return cfg_q, quantize_params(params)


def test_int8_decode_matches_one_shot_pipelined(tiny_int8):
    """Bitwise one-shot equivalence for a fully int8 model (int8 weights
    + int8 KV dict cache) under the pipelined scheduler, and the
    routing counters: on CPU the route predicate declines (platform), so
    every step must count as fallback."""
    cfg, params = tiny_int8
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size,
                            int(rng.integers(3, 11))).tolist()
               for _ in range(5)]
    max_news = [int(rng.integers(4, 12)) for _ in range(5)]
    engine = _engine(cfg, params, pipeline_decode=True).start()
    results = _run_batch(engine, prompts, max_news)
    for p, n, r in zip(prompts, max_news, results):
        assert r.finish_reason == "length"
        assert r.tokens == _reference(cfg, params, p, n)
    snap = engine.metrics.snapshot()
    assert snap["max_decode_batch"] >= 2
    assert snap["paged_steps"] == 0
    # counts DISPATCHED steps: may exceed committed decode_iterations by
    # the pipeline's final speculative step, never undercount them
    assert snap["fallback_steps"] >= snap["decode_iterations"] > 0


def test_chunked_prefill_matches_one_shot(tiny):
    """Chunked admission (prefill_chunk smaller than most prompts) must
    not change a single committed token, including for prompts shorter
    than one chunk and prompts arriving mid-decode."""
    cfg, params = tiny
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size,
                            int(rng.integers(2, 25))).tolist()
               for _ in range(6)]
    max_news = [int(rng.integers(4, 12)) for _ in range(6)]
    engine = _engine(cfg, params, prefill_chunk=4).start()
    results = _run_batch(engine, prompts, max_news)
    for p, n, r in zip(prompts, max_news, results):
        assert r.finish_reason == "length"
        assert r.tokens == _reference(cfg, params, p, n)
    snap = engine.metrics.snapshot()
    assert snap["prefills"] == 6
    # chunked admission really ran chunk-at-a-time: more chunks than
    # prefills because prompts longer than one chunk took several
    expected_chunks = sum(-(-min(-(-len(p) // 4) * 4, 64) // 4)
                          for p in prompts)
    assert snap["prefill_chunks"] == expected_chunks
    assert snap["max_decode_batch"] >= 2


def test_long_prompt_admission_interleaves_with_decode(tiny):
    """A long prompt arriving while another request is decoding must be
    admitted chunk-by-chunk without corrupting the active stream."""
    cfg, params = tiny
    short = [5, 9, 3]
    long = list(range(1, 33))  # 32 tokens = 8 chunks of 4
    engine = _engine(cfg, params, prefill_chunk=4).start()
    try:
        h1 = engine.submit(short, max_new_tokens=20, use_eos_stop=False)
        time.sleep(0.05)  # let decode get going
        h2 = engine.submit(long, max_new_tokens=6, use_eos_stop=False)
        r1 = h1.result(timeout=600)
        r2 = h2.result(timeout=600)
    finally:
        engine.shutdown()
    assert r1.tokens == _reference(cfg, params, short, 20)
    assert r2.tokens == _reference(cfg, params, long, 6)


def test_lagged_retirement_never_leaks_speculative_token(tiny):
    """In pipelined mode the step after a slot's last committed token has
    already sampled one speculative token for it.  Neither the result
    tokens nor the streaming callback may ever see it — for any request,
    across staggered retirements."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, 4).tolist()
               for _ in range(4)]
    max_news = [3, 5, 8, 11]  # retire at different iterations
    streamed = {i: [] for i in range(4)}
    engine = _engine(cfg, params, pipeline_decode=True).start()
    try:
        handles = []
        for i, (p, n) in enumerate(zip(prompts, max_news)):
            handles.append(engine.submit(
                p, max_new_tokens=n, use_eos_stop=False,
                on_token=streamed[i].append))
        results = [h.result(timeout=600) for h in handles]
        # the engine keeps running (other slots still active) after each
        # early retirement — exactly when a leak would happen
    finally:
        engine.shutdown()
    for i, (p, n, r) in enumerate(zip(prompts, max_news, results)):
        ref = _reference(cfg, params, p, n)
        assert r.tokens == ref, f"request {i} trajectory diverged"
        # result holds EXACTLY max_new generated tokens: no speculative
        # extra, and the stream saw the same tokens in the same order
        assert len(r.tokens) == len(p) + n
        assert streamed[i] == ref[len(p):], (
            f"request {i} streamed tokens diverged from committed ones")


def test_cancelled_slot_discards_inflight_token(tiny):
    """Cancellation while a pipelined step is in flight: the cancelled
    request's stream must stop at the committed prefix (no token from the
    already-dispatched step) and keep a valid one-shot prefix."""
    cfg, params = tiny
    prompt = [7, 3, 11, 2]
    got = []
    hold = threading.Event()

    def on_token(t):
        got.append(t)
        if len(got) == 3:
            hold.set()
        time.sleep(0.01)  # throttle so cancel lands mid-generation

    engine = _engine(cfg, params).start()
    try:
        h = engine.submit(prompt, max_new_tokens=50, use_eos_stop=False,
                          on_token=on_token)
        assert hold.wait(timeout=600)
        h.cancel()
        r = h.result(timeout=600)
    finally:
        engine.shutdown()
    assert r.finish_reason == "cancelled"
    ref = _reference(cfg, params, prompt, 50)
    n = len(r.tokens) - len(prompt)
    assert 0 < n < 50
    assert r.tokens == ref[:len(prompt) + n]  # a prefix, nothing bolted on
    assert got == r.tokens[len(prompt):]


def test_metrics_step_breakdown(tiny):
    """The device/host breakdown must show the pipeline overlapping host
    work: a pipelined run never observes device idle between steps (a
    step is always in flight), a sync run always does."""
    cfg, params = tiny
    prompts = [[3, 5, 7], [2, 4, 6]]

    def run(pipeline):
        engine = _engine(cfg, params, pipeline_decode=pipeline).start()
        _run_batch(engine, prompts, [16, 16])
        return engine.metrics.snapshot()

    sync_snap = run(False)
    pipe_snap = run(True)
    for snap in (sync_snap, pipe_snap):
        assert snap["device_step_time"]["count"] > 0
        assert snap["sched_host_time"]["count"] > 0
        assert snap["device_step_time"]["mean_s"] > 0.0
    assert sync_snap["device_idle_frac"] > 0.0
    assert pipe_snap["device_idle_frac"] == 0.0
    assert pipe_snap["device_idle_frac"] < sync_snap["device_idle_frac"]


def test_idle_wakeup_is_not_sleep_bound(tiny):
    """With condition-variable wakeups an idle engine must pick up a new
    request immediately even when idle_wait_s is huge."""
    cfg, params = tiny
    engine = _engine(cfg, params, idle_wait_s=30.0).start()
    try:
        # first submission compiles the forwards; do it before timing
        engine.submit([1, 2, 3], max_new_tokens=2,
                      use_eos_stop=False).result(timeout=600)
        time.sleep(0.1)  # let the scheduler park itself in the idle wait
        t0 = time.perf_counter()
        engine.submit([4, 5, 6], max_new_tokens=2,
                      use_eos_stop=False).result(timeout=600)
        dt = time.perf_counter() - t0
    finally:
        engine.shutdown()
    assert dt < 5.0  # << idle_wait_s: woken by notify, not by timeout


def test_drain_wakes_without_polling(tiny):
    """drain() must return promptly once the last request finishes even
    with a huge idle_wait_s (it is notified, not sleep-polled)."""
    cfg, params = tiny
    engine = _engine(cfg, params, idle_wait_s=30.0).start()
    try:
        h = engine.submit([1, 2, 3], max_new_tokens=4, use_eos_stop=False)
        assert engine.drain(timeout=600.0)
        assert h.done()
    finally:
        engine.shutdown()


def test_pause_resume_with_pipeline(tiny):
    """pause() flushes the in-flight step; resume() continues the exact
    trajectory (the post-pause dispatch re-feeds host-known tokens)."""
    cfg, params = tiny
    prompt = [9, 1, 4]
    engine = _engine(cfg, params).start()
    try:
        seen = threading.Event()
        h = engine.submit(prompt, max_new_tokens=16, use_eos_stop=False,
                          on_token=lambda _t: seen.set())
        assert seen.wait(timeout=600)
        engine.pause()
        time.sleep(0.05)
        engine.resume()
        r = h.result(timeout=600)
    finally:
        engine.shutdown()
    assert r.tokens == _reference(cfg, params, prompt, 16)


# ---------------------------------------------------------------------------
# The composed decode step's third route: KV read through the block
# tables inside the paged attention kernel (forward_cached_paged)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_d64():
    """A geometry the paged route accepts: head width 64, MQA; the engines
    below give it pool blocks of 128 rows."""
    cfg = tiny_config(num_layers=2, vocab_size=64, hidden_size=128,
                      num_attention_heads=2, num_kv_heads=1,
                      max_position_embeddings=256)
    return cfg, model_lib.init_params(jax.random.key(3), cfg)


def _paged_geometry_requests(cfg):
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size,
                            int(rng.integers(3, 12))).tolist()
               for _ in range(4)]
    return prompts, [int(rng.integers(4, 10)) for _ in range(4)]


def _paged_geometry_run(cfg, params, **overrides):
    """Four ragged greedy requests through a 4-slot engine with 128-row
    blocks → (results, metrics snapshot, engine_step routes, Prometheus
    text, final pools)."""
    from megatron_llm_tpu.obs import REGISTRY

    prompts, max_news = _paged_geometry_requests(cfg)
    # not pipelined: a pipelined engine's last, masked step leaves a row
    # behind or not as the threads fall, and the pools are compared below
    engine = _engine(cfg, params, max_seq_len=256, kv_block_size=128,
                     pipeline_decode=False, **overrides).start()
    results = _run_batch(engine, prompts, max_news)
    routes = [e["args"]["route"]
              for e in engine.trace.chrome_trace()["traceEvents"]
              if e["name"] == "engine_step"]
    pools = jax.tree.map(np.asarray,
                         (engine.slots.k_pool, engine.slots.v_pool))
    return (results, engine.metrics.snapshot(), routes,
            REGISTRY.prometheus_text(), pools, engine)


def test_cpu_decode_keeps_the_gather_route(tiny_d64):
    """On the CPU backend the route predicate declines (platform), so a
    geometry the paged kernel would take still decodes over the gathered
    dense view: every step counts and is traced as ``fallback``, and the
    tokens are the one-shot trajectory bit for bit."""
    cfg, params = tiny_d64
    results, snap, routes, prom, _, engine = _paged_geometry_run(cfg, params)
    assert not engine._paged_decode and engine._decode_route == "fallback"
    assert routes and set(routes) == {"fallback"}
    assert snap["paged_steps"] == 0
    assert snap["fallback_steps"] >= snap["decode_iterations"] > 0
    assert snap["paged_steps_by_precision"] == {"fp32": 0}
    assert "serving_paged_steps_total 0" in prom
    for p, n, r in zip(*_paged_geometry_requests(cfg), results):
        assert r.finish_reason == "length"
        assert r.tokens == _reference(cfg, params, p, n)


@pytest.mark.parametrize("spec_draft_len,route", [(0, "paged"),
                                                  (2, "fallback")])
def test_engine_reports_the_paged_route(tiny_d64, monkeypatch,
                                        spec_draft_len, route):
    """With the backend reported as a TPU (the kernel itself runs in
    interpret mode) the engine takes the paged route — every decode step
    says so in its ``engine_step`` span, in ``paged_steps`` and in
    ``/metrics`` — and commits the tokens the gather route commits, with
    the same rows in the pool.  An engine that speculates keeps the
    gather route for decode and verify alike."""
    from megatron_llm_tpu.ops import attention as attn_ops

    cfg, params = tiny_d64
    want, _, _, _, want_pools, _ = _paged_geometry_run(
        cfg, params, spec_draft_len=spec_draft_len)
    monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
    got, snap, routes, prom, pools, engine = _paged_geometry_run(
        cfg, params, spec_draft_len=spec_draft_len)
    assert engine._decode_route == route
    assert [r.tokens for r in got] == [r.tokens for r in want]
    plain = [r for r in routes if not r.startswith("spec")]
    assert plain and set(plain) == {route}
    other = "fallback" if route == "paged" else "paged"
    assert snap[f"{other}_steps"] == 0
    assert snap[f"{route}_steps"] >= snap["decode_iterations"] > 0
    assert (f'serving_{route}_steps_by_precision_total{{precision="fp32"}} '
            f'{snap[f"{route}_steps"]}') in prom
    # live rows agree to float32 rounding (the kernel and the einsum sum
    # in different orders); the trash block holds whatever idle slots wrote
    for a, b in zip(jax.tree.leaves(want_pools), jax.tree.leaves(pools)):
        np.testing.assert_allclose(b[:, 1:], a[:, 1:], rtol=1e-4, atol=1e-5)
