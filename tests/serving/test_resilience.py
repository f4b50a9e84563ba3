"""Serving-side robustness: per-request deadlines and graceful drain.

A request past its wall-clock deadline must stop occupying capacity —
whether it is still queued or mid-decode — and finish with reason
"timeout".  A draining engine must finish what it accepted and reject
what it didn't, so a SIGTERM'd server never drops in-flight responses.
"""

import threading
import time

import jax
import numpy as np
import pytest

from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.serving import EngineConfig, QueueFull, ServingEngine

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(num_layers=2, vocab_size=64,
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def _engine(cfg, params, **overrides):
    kw = dict(max_batch_size=4, max_seq_len=64, max_queue_size=16,
              idle_wait_s=0.005)
    kw.update(overrides)
    return ServingEngine(cfg, params, EngineConfig(**kw))


def test_queued_request_expires_under_pressure(tiny):
    """A request that spends its whole deadline waiting in the queue is
    expired by the scheduler without ever taking a slot."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    engine.start()
    engine.pause()  # deterministic queue pressure: nothing admits
    try:
        h = engine.submit([5, 9, 3], max_new_tokens=4, deadline_s=0.05)
        r = h.result(timeout=60)
        assert r.finish_reason == "timeout"
        assert r.tokens == [5, 9, 3]  # nothing generated
        snap = engine.metrics.snapshot()
        assert snap["timeouts"] == 1
        assert snap["admitted"] == 0
        assert len(engine.queue) == 0
    finally:
        engine.shutdown()


def test_active_request_expires_mid_generation(tiny):
    """A slow in-flight generation is retired at its deadline with the
    tokens produced so far."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_seq_len=128)
    engine.start()
    try:
        # warm the compile caches so the deadline clock measures decode
        # time, not XLA compile time
        engine.submit([1, 2, 3], max_new_tokens=2,
                      use_eos_stop=False).result(timeout=600)
        # pace the decode from the token callback so a 0.3s deadline
        # reliably lands in the middle of the 100-token budget
        h = engine.submit([1, 2, 3], max_new_tokens=100, deadline_s=0.3,
                          use_eos_stop=False,
                          on_token=lambda t: time.sleep(0.02))
        r = h.result(timeout=600)
        assert r.finish_reason == "timeout"
        generated = len(r.tokens) - r.prompt_len
        assert 0 < generated < 100  # partial progress, then expiry
        assert engine.metrics.snapshot()["timeouts"] == 1
    finally:
        engine.shutdown()


def test_default_deadline_from_engine_config(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params, default_deadline_s=0.05)
    engine.start()
    engine.pause()
    try:
        # no per-request deadline: the config default applies
        h = engine.submit([5, 9, 3], max_new_tokens=4)
        assert h.result(timeout=60).finish_reason == "timeout"
        # an explicit per-request deadline overrides the default
        h2 = engine.submit([5, 9, 3], max_new_tokens=4, deadline_s=3600)
        time.sleep(0.2)
        assert not h2.done()
        h2.cancel()
    finally:
        engine.shutdown()


def test_drain_completes_in_flight_then_rejects(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    engine.start()
    try:
        handles = [engine.submit([i + 1, 2, 3], max_new_tokens=6,
                                 use_eos_stop=False) for i in range(6)]
        assert engine.drain(timeout=600) is True
        # everything accepted before the drain completed normally
        for h in handles:
            assert h.result(timeout=1).finish_reason == "length"
        # post-drain submissions are backpressure-rejected
        with pytest.raises(QueueFull):
            engine.submit([7, 8, 9], max_new_tokens=2)
        assert engine.metrics.snapshot()["rejected_draining"] == 1
    finally:
        engine.shutdown()


def test_drain_never_started_engine(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    assert engine.drain(timeout=1) is True


def test_drain_timeout_returns_false(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    engine.start()
    engine.pause()  # requests can never finish
    try:
        engine.submit([5, 9, 3], max_new_tokens=4)
        assert engine.drain(timeout=0.1) is False
    finally:
        engine.shutdown()


def test_server_graceful_shutdown_drains(tiny):
    """Server-level contract: graceful_shutdown() lets the in-flight
    request finish (not 'error', not dropped) before the listener dies."""
    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.tokenizer.tokenizer import NullTokenizer

    cfg, params = tiny
    server = MegatronServer(cfg, params,
                            NullTokenizer(vocab_size=cfg.vocab_size),
                            max_batch_size=2, engine_max_seq_len=64)
    server.run(host="127.0.0.1", port=0, block=False,
               graceful_sigterm=False)
    try:
        results = {}

        def client():
            results["resp"] = server.service.handle(
                {"prompts": ["5 9 3"], "tokens_to_generate": 4})

        t = threading.Thread(target=client)
        t.start()
        # let the request reach the engine (built lazily by the first one)
        deadline = time.time() + 60
        while time.time() < deadline:
            eng = server.service._engine
            if eng is not None and eng.metrics.counters["submitted"]:
                break
            time.sleep(0.005)
        assert server.graceful_shutdown(drain_timeout_s=600) is True
        t.join(timeout=600)
        status, payload = results["resp"]
        assert status == 200
        assert payload["text"]
        # drained service rejects new work with backpressure, not a crash
        status2, _ = server.service.handle(
            {"prompts": ["1 2 3"], "tokens_to_generate": 2})
        assert status2 == 503
    finally:
        server.shutdown()


def test_service_request_deadline_plumbs_to_engine(tiny):
    from megatron_llm_tpu.generation.server import GenerationService
    from megatron_llm_tpu.tokenizer.tokenizer import NullTokenizer

    cfg, params = tiny
    svc = GenerationService(cfg, params,
                            NullTokenizer(vocab_size=cfg.vocab_size),
                            max_batch_size=2, engine_max_seq_len=64,
                            request_deadline_s=12.5)
    try:
        assert svc.engine.config.default_deadline_s == 12.5
    finally:
        svc.close()
