"""The Laguna stack through the serving engine: paged K/V for the two
full layers, a ring of rotated rows a slot for the three window layers,
the experts' counters; a pool set smaller than its slots' worst case, so
that an admission parks on blocks while its slot (and the slot's rings)
wait free; and everything the engine refuses for a hybrid stack."""

import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from benchmarks.reference import laguna as ref
from megatron_llm_tpu.config import laguna_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.obs.registry import REGISTRY
from megatron_llm_tpu.serving import EngineConfig, ServingEngine
from megatron_llm_tpu.serving.adapters.registry import AdapterRegistry
from tests.models.test_laguna_stack import TINY

ENGINE = dict(max_batch_size=2, max_seq_len=128, kv_block_size=16,
              prefill_bucket=32, prefix_cache_blocks=0, max_queue_size=64)


@pytest.fixture(scope="module")
def model():
    cfg = laguna_config(**TINY)
    return cfg, jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))


def serve(cfg, params, prompts, new=12, logprobs=True, **kw):
    eng = ServingEngine(cfg, params, EngineConfig(**{**ENGINE, **kw})).start()
    try:
        handles = [eng.submit(p, new, use_eos_stop=False,
                              return_logprobs=logprobs, seed=0)
                   for p in prompts]
        return [h.result(timeout=300) for h in handles], eng
    finally:
        eng.shutdown()


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, size=n).tolist() for n in lengths]


def test_a_reused_slot_serves_as_a_fresh_engine_does(model):
    """Three requests over two slots, pipelined: a slot's rings are
    replaced whole at admission, so its last tenant (and the speculative
    step that wrote a row after it retired) leaves nothing behind; and
    what is served is the reference's forward."""
    cfg, params = model
    prompts = prompts_of([40, 57, 33])
    shared, eng = serve(cfg, params, prompts)
    meta = ref.meta_of(cfg)
    longest = max(len(r.tokens) for r in shared)
    for p, got in zip(prompts, shared):
        (alone,), _ = serve(cfg, params, [p])
        assert got.tokens == alone.tokens
        np.testing.assert_allclose(got.logprobs, alone.logprobs, atol=2e-5)
        # float32 on both sides: the engine's prefill and its 12 decode
        # steps through pool and rings (which wrap: window 8), every key
        # rotated once at its own position, against every row through
        # every layer
        # (at one length for every request, the reference's forward being
        # compiled a length: a position depends on no token behind it)
        padded = got.tokens + [1] * (longest - len(got.tokens))
        want = np.asarray(ref.token_logprobs(params, padded, meta))[
            :len(got.tokens) - 1]
        np.testing.assert_allclose(got.logprobs, want, atol=2e-5)
    rec = eng.slots.rec
    assert rec["win_k"].shape == rec["win_v"].shape == (3, 2, 2, 8, 16)
    # the pool pages the two full layers' K/V only
    k_pool, v_pool = eng.slots.k_pool, eng.slots.v_pool
    assert k_pool.shape[0] == v_pool.shape[0] == cfg.kv_layers == 2
    assert k_pool.shape[2:] == v_pool.shape[2:] == (2, 16, 16)


def test_the_state_and_the_pool_are_gauged_by_kind(model):
    cfg, params = model
    _, eng = serve(cfg, params, prompts_of([40, 50], seed=1), new=5)
    snap = eng.metrics.snapshot()
    rec = eng.slots.rec
    by_kind = {"window": rec["win_k"].nbytes + rec["win_v"].nbytes}
    assert snap["rec_state_bytes_by_kind"] == by_kind
    # a slot: 3 layers x 8 rows x (2 x 16 keys + 2 x 16 values), float32
    assert by_kind == {"window": 2 * 3 * 8 * 64 * 4}
    pool = eng.slots.k_pool.nbytes + eng.slots.v_pool.nbytes
    assert snap["kv_pool_bytes_by_kind"] == {"kv": pool}
    assert pool == eng.slots.k_pool.shape[1] * 16 * 64 * 4 * 2   # 2 layers
    fams = {f.name: f for f in REGISTRY.collect()}
    assert {s.labels["kind"]: s.value for s in
            fams["serving_rec_state_bytes"].samples} == by_kind
    assert {s.labels["kind"]: s.value for s in
            fams["serving_kv_pool_bytes"].samples} == {"kv": pool}
    # the walks: a step's live positions, once by each full layer
    assert set(snap["kv_walks"]) == {"full"}
    assert snap["kv_walks"]["full"] >= 2 * 4 * 90
    # the experts' counters: four layers route, the dense one does not
    layers = {s.labels["layer"] for s in
              fams["serving_expert_assignments_total"].samples if s.value}
    assert layers == {"1", "2", "3", "4"}


def test_the_spans_say_what_a_prefill_and_a_step_did(model):
    cfg, params = model
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        a = eng.submit(prompts_of([40])[0], 4, use_eos_stop=False,
                       seed=0).result(timeout=300)
        b = eng.submit(prompts_of([5])[0], 6, use_eos_stop=False,
                       seed=0).result(timeout=300)
    finally:
        eng.shutdown()
    assert len(a.tokens) == 44 and len(b.tokens) == 11
    spans = eng.trace.chrome_trace()["traceEvents"]
    prefills = [e for e in spans if e["name"] == "prefill"]
    decodes = [e for e in spans if e["name"] == "decode"]
    assert len(prefills) == 2 and decodes
    assert all(e["args"]["state_kinds"] == "window"
               for e in prefills + decodes)
    assert all(e["args"]["experts"] == "grouped" for e in prefills)
    assert all(e["args"]["state_installed_bytes"] == 3 * 8 * 64 * 4
               for e in prefills)
    assert all(e["args"]["live"] == 1 for e in decodes)
    # a step's walk of the two pool layers sees every cached position;
    # its rings hold at most the window's 8 rows, fewer in a short slot
    by_positions = {e["args"]["live_positions"]: e["args"]["ring_rows"]
                    for e in decodes}
    assert by_positions[40] == 8 and by_positions[41] == 8
    assert by_positions[5] == 5 and by_positions[7] == 7
    assert by_positions[9] == 8
    assert all("walk_steps" in e["args"] for e in decodes)
    assert not any("parked_ms" in e["args"] for e in prefills)


def test_an_admission_parks_on_blocks_while_its_ring_is_free(model):
    """A pool of 9 usable blocks under two slots whose worst case is 8
    each: the first request reserves 6, the second needs 5 and finds a
    free slot, a free ring and 3 blocks: it parks at the queue's head,
    is counted and timed, and runs when the first retires; what it is
    served is what a fresh engine serves it."""
    cfg, params = model
    first, second = prompts_of([70, 60], seed=2)
    eng = ServingEngine(cfg, params, EngineConfig(
        **{**ENGINE, "kv_pool_blocks": 10})).start()
    try:
        assert eng.slots.pool.usable_blocks == 9
        started = threading.Event()
        h1 = eng.submit(first, 24, use_eos_stop=False, seed=0,
                        on_token=lambda _t: started.set())
        assert started.wait(300)
        h2 = eng.submit(second, 12, use_eos_stop=False,
                        return_logprobs=True, seed=0)
        deadline = time.time() + 300
        while (not eng.metrics.snapshot()["admissions_parked"]
               and not h1.done() and time.time() < deadline):
            time.sleep(0.001)
        snap = eng.metrics.snapshot()
        assert snap["admissions_parked"] == 1
        # parked with a slot free: the ring of the free slot is there,
        # the blocks are not
        assert eng.slots.free_slots and not h2.done()
        r1, r2 = h1.result(timeout=300), h2.result(timeout=300)
    finally:
        eng.shutdown()
    assert len(r1.tokens) == 94 and len(r2.tokens) == 72
    snap = eng.metrics.snapshot()
    assert snap["admissions_parked"] == 1
    assert snap["admission_parked_seconds_total"] > 0
    prefills = [e for e in eng.trace.chrome_trace()["traceEvents"]
                if e["name"] == "prefill"]
    parked = [e["args"].get("parked_ms") for e in prefills]
    assert parked[0] is None and parked[1] > 0
    fams = {f.name: f for f in REGISTRY.collect()}
    assert fams["serving_admissions_parked_total"].samples[0].value == 1
    assert fams["serving_admission_parked_seconds_total"].samples[
        0].value == snap["admission_parked_seconds_total"]
    (alone,), _ = serve(cfg, params, [second], new=12)
    assert r2.tokens == alone.tokens
    np.testing.assert_allclose(r2.logprobs, alone.logprobs, atol=2e-5)


REFUSED = {
    "prefix_cache": (dict(prefix_cache_blocks=8), {}, "prefix_cache_blocks"),
    "speculation": (dict(spec_draft_len=2), {}, "speculation"),
    "draft_model": ({}, dict(draft=True), "speculation"),
    "mesh": ({}, dict(mesh=True), "mesh"),
    "adapters": ({}, dict(adapters=True), "adapters"),
    "chunked_prefill": (dict(prefill_chunk=32), {}, "prefill_chunk"),
    "host_tier": (dict(host_kv_blocks=8), {}, "host_kv_blocks"),
    "disaggregation": (dict(role="prefill"), {}, "role"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_moves_kv_alone_is_refused_at_construction(model, case):
    cfg, params = model
    engine_kw, extra, said = REFUSED[case]
    kw = {}
    if extra.get("draft"):
        kw.update(draft_cfg=cfg, draft_params=params)
    if extra.get("mesh"):
        from megatron_llm_tpu.config import ParallelConfig
        from megatron_llm_tpu.parallel.mesh import build_mesh

        kw["mesh"] = build_mesh(ParallelConfig(tensor_parallel=2),
                                devices=jax.devices()[:2])
    if extra.get("adapters"):
        kw["adapters"] = AdapterRegistry.__new__(AdapterRegistry)
    with pytest.raises(ValueError, match="hybrid stack") as err:
        ServingEngine(cfg, params, EngineConfig(**{**ENGINE, **engine_kw}),
                      **kw)
    assert said in str(err.value)


def test_an_int8_pool_is_refused_by_the_configuration(model):
    """The eighth refusal: a window layer of the period scan keeps its
    ring in the weights' precision, and ``validate`` says so before an
    engine is built."""
    cfg, _params = model
    with pytest.raises(AssertionError, match="8-bit"):
        dataclasses.replace(cfg, kv_cache_quant="int8").validate()


def test_a_slot_is_not_shipped(model):
    cfg, params = model
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        with pytest.raises(RuntimeError, match="no recurrent state"):
            eng.call_in_scheduler(lambda: eng._extract_slot(0))
    finally:
        eng.shutdown()


def test_the_normal_entry_point_names_the_family():
    """``run_text_generation_server --model laguna --size
    xs.2-pp8-stage0`` builds the preset the benchmark runs."""
    import inspect

    from megatron_llm_tpu.models import families
    from megatron_llm_tpu.tools import run_text_generation_server as tool

    assert families.laguna().cfg == laguna_config()
    assert families.laguna("xs.2-pp8-stage0", **TINY).cfg \
        == laguna_config(**TINY)
    source = inspect.getsource(tool.main)
    assert '"laguna": families.laguna' in source
    assert '"laguna", "phi4flash"],' in source
