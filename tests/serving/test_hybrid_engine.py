"""A hybrid stack through the serving engine: two kinds of slot state in
one manager (paged K/V for the full layers, a fixed-size recurrent state
a slot for the linear ones), and everything the engine refuses for it."""

import dataclasses

import jax
import numpy as np
import pytest

from megatron_llm_tpu.config import qwen3_next_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.obs.registry import REGISTRY
from megatron_llm_tpu.serving import EngineConfig, ServingEngine
from megatron_llm_tpu.serving.adapters.registry import AdapterRegistry

TINY = dict(num_layers=2, layer_pattern=("linear", "full"), hidden_size=64,
            num_attention_heads=4, num_kv_heads=2, kv_channels=32,
            ffn_hidden_size=32, moe_shared_expert_size=32, num_experts=8,
            moe_router_experts=16, moe_top_k=4, vocab_size=512,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16,
            params_dtype="float32", max_position_embeddings=512,
            make_vocab_size_divisible_by=8, moe_group_size=64)
ENGINE = dict(max_batch_size=2, max_seq_len=128, kv_block_size=16,
              prefill_bucket=32, prefix_cache_blocks=0, max_queue_size=64)


@pytest.fixture(scope="module")
def model():
    cfg = qwen3_next_config("80b-a3b-ep2-rank0", **TINY)
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
    whole at admission, so its last tenant (and the speculative step that
    advanced it after it retired) leaves nothing behind."""
    cfg, params = model
    prompts = prompts_of([40, 57, 33, 64, 21])
    shared, eng = serve(cfg, params, prompts)
    for p, got in zip(prompts, shared):
        (alone,), _ = serve(cfg, params, [p])
        assert got.tokens == alone.tokens
        np.testing.assert_allclose(got.logprobs, alone.logprobs, atol=2e-5)
    snap = eng.metrics.snapshot()
    rec = eng.slots.rec
    assert snap["rec_state_slots"] == 2
    assert snap["rec_state_bytes"] == rec["S"].nbytes + rec["conv"].nbytes
    assert rec["S"].shape == (1, 2, 4, 16, 16)       # [linear layers, slots,
    assert rec["conv"].shape == (1, 2, 3, 128)       #  ...], fixed size
    # the pool pages the one full layer's K/V only
    assert eng.slots.k_pool.shape[0] == cfg.kv_layers == 1


def test_the_expert_counter_rides_on_the_device_and_is_read_on_request(model):
    cfg, params = model
    prompts = prompts_of([40, 50], seed=1)
    _, eng = serve(cfg, params, prompts, new=5)
    counts, lo, held = eng.expert_load()
    assert counts.shape == (cfg.num_layers, cfg.router_experts)
    assert (lo, held) == (0, cfg.num_experts)
    # every prompt position and every fed token chose top_k experts a
    # layer; the pipelined step's one speculative token a request may be
    # counted too, a padded position never
    fed = sum(len(p) for p in prompts) + 2 * 4
    per_layer = counts.sum(axis=1) / cfg.moe_top_k
    assert np.all((per_layer >= fed) & (per_layer <= fed + 2)), per_layer
    snap = eng.metrics.snapshot()["expert_load"]
    assert snap["assignments"] == counts.sum()
    assert 0.2 < snap["held_share"] < 0.8
    fam = [f for f in REGISTRY.collect()
           if f.name == "serving_expert_assignments_total"]
    assert len(fam) == 1 and len(fam[0].samples) == counts.size
    assert sum(s.value for s in fam[0].samples
               if s.labels["held"] == "1") == counts[:, :held].sum()


def test_the_experts_rows_are_counted_by_outcome_and_the_spans_say_how(model):
    """``serving_expert_rows_total``: every row of a held expert is
    ``multiplied``, every other ``skipped`` (about half, by the share),
    padded positions and free slots included; the ``prefill`` and
    ``engine_step`` spans name the implementation."""
    cfg, params = model
    prompts = prompts_of([40, 50], seed=1)
    _, eng = serve(cfg, params, prompts, new=5)
    rows = eng.expert_rows()
    counts, lo, held = eng.expert_load()
    # a prompt is padded to whole buckets of 32 and a step feeds both
    # slots: no fewer rows than counted choices, and held ones among both
    assert rows["multiplied"] >= counts[:, lo:lo + held].sum() > 0
    assert rows["skipped"] >= counts.sum() - counts[:, lo:lo + held].sum()
    total, per_position = divmod(rows["multiplied"] + rows["skipped"],
                                 cfg.num_layers * cfg.moe_top_k)
    assert per_position == 0 and total >= (64 + 64) + 2 * 4
    assert 0.2 < rows["skipped"] / (rows["multiplied"]
                                    + rows["skipped"]) < 0.8
    assert eng.metrics.snapshot()["expert_load"]["rows"] == rows
    (fam,) = [f for f in REGISTRY.collect()
              if f.name == "serving_expert_rows_total"]
    assert {s.labels["outcome"]: s.value for s in fam.samples} == rows
    spans = [e for e in eng.trace.chrome_trace()["traceEvents"]
             if e["name"] in ("prefill", "engine_step")]
    assert {e["name"] for e in spans} == {"prefill", "engine_step"}
    assert all(e["args"]["experts"] == "grouped" for e in spans)


def test_a_prompts_linear_layers_say_they_ran_as_the_kernel(model):
    """A ``prefill`` span of a stack with Gated DeltaNet layers carries
    ``gdn: "fused"`` (between their projections one kernel,
    ``kernels/gdn_scan.py``) and its decode spans ``gdn_step: "mixer"``
    (the one position's kernel, ``kernels/gdn_step.py``, on the stacked
    states where they lie); neither the other's field, nor any span of a
    Falcon stack either."""
    from megatron_llm_tpu.config import falcon_config

    cfg, params = model
    _, eng = serve(cfg, params, prompts_of([40, 50], seed=2), new=3)
    spans = eng.trace.chrome_trace()["traceEvents"]
    prefills = [e for e in spans if e["name"] == "prefill"]
    assert len(prefills) == 2
    assert all(e["args"]["gdn"] == "fused" for e in prefills)
    assert not any("gdn" in e.get("args", {}) for e in spans
                   if e["name"] != "prefill")
    decodes = [e for e in spans if e["name"] == "decode"]
    assert decodes and all(e["args"]["gdn_step"] == "mixer"
                           and "ssm_step" not in e["args"] for e in decodes)
    assert not any("gdn_step" in e.get("args", {}) for e in spans
                   if e["name"] != "decode")
    falcon = falcon_config(
        "7b", num_layers=1, hidden_size=64, num_attention_heads=4,
        ffn_hidden_size=128, vocab_size=64, params_dtype="float32",
        make_vocab_size_divisible_by=8, max_position_embeddings=128)
    _, eng = serve(falcon, model_lib.init_params(jax.random.key(0), falcon),
                   [list(range(1, 10))], new=3, max_seq_len=32,
                   kv_block_size=8, prefill_bucket=16)
    spans = eng.trace.chrome_trace()["traceEvents"]
    assert [e for e in spans if e["name"] == "prefill"]
    assert not any(key in e.get("args", {}) for e in spans
                   for key in ("gdn", "gdn_step"))


def test_a_dense_engine_counts_no_experts_and_keeps_no_state():
    from megatron_llm_tpu.config import tiny_config

    cfg = tiny_config()
    params = model_lib.init_params(jax.random.key(0), cfg)
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch_size=2, max_seq_len=64)).start()
    eng.shutdown()
    assert eng.slots.rec is None and eng.metrics.expert_load is None
    assert eng.metrics.expert_rows is None
    assert not any("experts" in e.get("args", {})
                   for e in eng.trace.chrome_trace()["traceEvents"])
    assert "expert_load" not in eng.metrics.snapshot()
    assert eng.metrics.snapshot()["rec_state_bytes"] == 0


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
