"""Nemotron-H through the serving engine: a third kind of slot state (a
Mamba-2 layer's state-space state and convolution tail a slot) beside the
paged K/V, its gauges, counter and spans, the expert counter of a stack in
which not every layer routes, and everything the engine refuses for it."""

import dataclasses

import jax
import numpy as np
import pytest

from megatron_llm_tpu.config import nemotron_h_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.obs import profile
from megatron_llm_tpu.obs.registry import REGISTRY
from megatron_llm_tpu.serving import EngineConfig, ServingEngine
from megatron_llm_tpu.serving.adapters.registry import AdapterRegistry

TINY = dict(num_layers=4, layer_pattern=("attention", "mlp", "mamba", "mlp"),
            hidden_size=64, num_attention_heads=4, num_kv_heads=2,
            kv_channels=16, ffn_hidden_size=32, moe_shared_expert_size=48,
            moe_latent_size=32, num_experts=4, moe_router_experts=16,
            moe_top_k=6, vocab_size=512, mamba_num_heads=4,
            mamba_head_dim=8, mamba_n_groups=2, mamba_state_size=16,
            mamba_chunk_size=8, params_dtype="float32",
            max_position_embeddings=512, make_vocab_size_divisible_by=8,
            moe_group_size=64)
ENGINE = dict(max_batch_size=2, max_seq_len=128, kv_block_size=16,
              prefill_bucket=32, prefix_cache_blocks=0, max_queue_size=64)


@pytest.fixture(scope="module")
def model():
    cfg = nemotron_h_config("3-super-120b-a12b-ep4-rank0", **TINY)
    return cfg, jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))


def serve(cfg, params, prompts, new=12, **kw):
    eng = ServingEngine(cfg, params, EngineConfig(**{**ENGINE, **kw})).start()
    try:
        handles = [eng.submit(p, new, use_eos_stop=False,
                              return_logprobs=True, seed=0) for p in prompts]
        return [h.result(timeout=300) for h in handles], eng
    finally:
        eng.shutdown()


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, size=n).tolist() for n in lengths]


def test_a_reused_slot_serves_as_a_fresh_engine_does(model):
    """Five requests over two slots, pipelined: a slot's state is replaced
    whole at admission (a release moves no device memory), so its last
    tenant, and the speculative step that advanced it after it retired,
    leave nothing behind; and a slot's neighbour never touches it."""
    cfg, params = model
    prompts = prompts_of([40, 57, 33, 64, 21])
    shared, eng = serve(cfg, params, prompts)
    for p, got in zip(prompts, shared):
        (alone,), _ = serve(cfg, params, [p])
        assert got.tokens == alone.tokens
        np.testing.assert_allclose(got.logprobs, alone.logprobs, atol=2e-5)
    snap = eng.metrics.snapshot()
    rec = eng.slots.rec
    assert sorted(rec) == ["load", "rows", "ssm", "ssm_conv"]
    assert rec["ssm"].shape == (1, 2, 4, 8, 16)     # [mamba layers, slots,
    assert rec["ssm_conv"].shape == (1, 2, 3, 96)   #  ...], fixed size
    assert snap["rec_state_slots"] == 2
    assert snap["rec_state_bytes_by_kind"] == {
        "mamba": rec["ssm"].nbytes + rec["ssm_conv"].nbytes}
    assert snap["rec_state_bytes"] == rec["ssm"].nbytes \
        + rec["ssm_conv"].nbytes
    # the pool pages the one attention layer's K/V only
    assert eng.slots.k_pool.shape[0] == cfg.kv_layers == 1


def test_installing_one_slot_leaves_the_other_as_it_was(model):
    cfg, params = model
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        first = eng.submit(prompts_of([30])[0], 1, use_eos_stop=False,
                           seed=0)
        first.result(timeout=300)
        before = eng.call_in_scheduler(lambda: jax.tree.map(
            np.asarray, model_lib.rec_states(eng.slots.rec)))
        assert float(np.abs(before["ssm"][:, 0]).max()) > 0
        np.testing.assert_array_equal(before["ssm"][:, 1], 0)
        # the next tenant of slot 0 replaces its rows whole; slot 1's stay
        eng.submit(prompts_of([50], seed=3)[0], 1, use_eos_stop=False,
                   seed=0).result(timeout=300)
        after = eng.call_in_scheduler(lambda: jax.tree.map(
            np.asarray, model_lib.rec_states(eng.slots.rec)))
        for key in ("ssm", "ssm_conv"):
            np.testing.assert_array_equal(after[key][:, 1], before[key][:, 1])
            assert float(np.abs(after[key][:, 0]
                                - before[key][:, 0]).max()) > 0
    finally:
        eng.shutdown()


def test_the_state_is_gauged_by_kind_and_its_positions_counted(model):
    cfg, params = model
    prompts = prompts_of([40, 50], seed=1)
    _, eng = serve(cfg, params, prompts, new=5)
    snap = eng.metrics.snapshot()
    # every prompt position once at its prefill; every fed token of every
    # live slot a step (the pipelined step's one speculative token a
    # request may be counted too)
    assert snap["ssm_positions"]["prefill"] == 90
    assert 2 * 4 <= snap["ssm_positions"]["decode"] <= 2 * 4 + 2
    fams = {f.name: f for f in REGISTRY.collect()}
    assert {s.labels["kind"]: s.value for s in
            fams["serving_rec_state_bytes"].samples} == {
        "mamba": snap["rec_state_bytes"]}
    assert {s.labels["phase"]: s.value for s in
            fams["serving_ssm_positions_total"].samples} == \
        snap["ssm_positions"]
    spans = eng.trace.chrome_trace()["traceEvents"]
    prefills = [e for e in spans if e["name"] == "prefill"]
    decodes = [e for e in spans if e["name"] == "decode"]
    assert len(prefills) == 2 and decodes
    assert all(e["args"]["state_kinds"] == "mamba"
               for e in prefills + decodes)
    # a decode span says how many slots its step moved, and that the
    # step's state-space layers ran as one kernel between their two
    # projections (a prefill's do not)
    assert {e["args"]["live"] for e in decodes} <= {1, 2}
    assert 2 in {e["args"]["live"] for e in decodes}
    assert all(e["args"]["ssm_step"] == "mixer" for e in decodes)
    assert not any("ssm_step" in e["args"] for e in prefills)


def test_a_stack_without_state_space_layers_says_nothing_of_their_step():
    from megatron_llm_tpu.config import tiny_config

    cfg = tiny_config(num_layers=1, vocab_size=64, params_dtype="float32",
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(0), cfg)
    _, eng = serve(cfg, params, [list(range(1, 10))], new=3, max_seq_len=32,
                   kv_block_size=8, prefill_bucket=16)
    decodes = [e for e in eng.trace.chrome_trace()["traceEvents"]
               if e["name"] == "decode"]
    assert decodes and not any(
        key in e["args"] for e in decodes
        for key in ("ssm_step", "state_kinds"))


def test_only_the_layers_that_route_are_counted(model):
    cfg, params = model
    prompts = prompts_of([40, 50], seed=1)
    _, eng = serve(cfg, params, prompts, new=5)
    counts, lo, held = eng.expert_load()
    assert counts.shape == (cfg.num_layers, cfg.router_experts)
    assert (lo, held) == (0, cfg.num_experts)
    assert cfg.moe_layer_ids == (1, 3)
    assert counts[[0, 2]].sum() == 0 and (counts[[1, 3]].sum(axis=1) > 0).all()
    snap = eng.metrics.snapshot()["expert_load"]
    assert len(snap["max_over_mean_by_layer"]) == 2
    (fam,) = [f for f in REGISTRY.collect()
              if f.name == "serving_expert_assignments_total"]
    assert {s.labels["layer"] for s in fam.samples} == {"1", "3"}
    assert len(fam.samples) == 2 * cfg.router_experts
    assert sum(s.value for s in fam.samples) == counts.sum()
    spans = [e for e in eng.trace.chrome_trace()["traceEvents"]
             if e["name"] in ("prefill", "engine_step")]
    assert all(e["args"]["experts"] == "grouped" for e in spans)


def test_a_profile_session_keeps_the_engines_recorder(model, tmp_path,
                                                      monkeypatch):
    """What the benchmark's readers rely on: a session started while an
    engine lives holds that engine's recorder, arguments and all."""
    cfg, params = model
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE))
    import jax.profiler

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    session = profile.start(str(tmp_path))
    profile.stop()
    assert eng.trace in session.recorders
    assert profile.last().recorders == session.recorders
    del eng


REFUSED = {
    "prefix_cache": (dict(prefix_cache_blocks=8), {}, "prefix_cache_blocks"),
    "speculation": (dict(spec_draft_len=2), {}, "speculation"),
    "draft_model": ({}, dict(draft=True), "speculation"),
    "int8_pool": ({}, dict(model=dict(kv_cache_quant="int8")), "int8"),
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
    cfg = dataclasses.replace(cfg, **extra.get("model", {}))
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


def test_a_slot_is_not_shipped(model):
    cfg, params = model
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        with pytest.raises(RuntimeError, match="no recurrent state"):
            eng.call_in_scheduler(lambda: eng._extract_slot(0))
        with pytest.raises(RuntimeError, match="no recurrent state"):
            eng.call_in_scheduler(lambda: eng.install_shipment(None))
    finally:
        eng.shutdown()
