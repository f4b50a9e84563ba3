"""A latent-attention stack through the serving engine: the pool of
latent rows under the block pool's ledger as K/V blocks are, both forms of
the layer on the spans, and everything the engine refuses for it."""

import dataclasses

import jax
import numpy as np
import pytest

from benchmarks.reference import deepseek_v3 as reference
from megatron_llm_tpu.config import deepseek_v3_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.serving import EngineConfig, ServingEngine
from megatron_llm_tpu.serving.adapters.registry import AdapterRegistry

TINY = dict(num_layers=3, hidden_size=64, num_attention_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, ffn_hidden_size=32, moe_dense_ffn_size=96,
            num_experts=8, moe_top_k=2, moe_shared_expert_size=64,
            vocab_size=512, make_vocab_size_divisible_by=8,
            max_position_embeddings=512, moe_group_size=64,
            params_dtype="float32")
ENGINE = dict(max_batch_size=2, max_seq_len=128, kv_block_size=16,
              prefill_bucket=32, prefix_cache_blocks=0, max_queue_size=64)


@pytest.fixture(scope="module")
def model():
    cfg = deepseek_v3_config("kanana-2-30b-a3b-pp8-stage0", **TINY)
    return cfg, jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))


def test_five_requests_over_two_slots_against_the_reference(model):
    """Prompts that end inside a block and on its edge, slots reused: every
    position's log-probability, prompt (the expanded form) and generated
    (the absorbed form through the pool) alike, against the reference's
    full forward.  float32 weights: 3e-5 is rounding; a row lost, shifted
    or left behind by a slot's last tenant reads 1e-1."""
    cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 500, size=n).tolist()
               for n in (40, 57, 33, 64, 21)]
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        handles = [eng.submit(p, 8, use_eos_stop=False,
                              return_logprobs=True, seed=0) for p in prompts]
        results = [h.result(timeout=300) for h in handles]
        snap = eng.metrics.snapshot()
        gauge = {tuple(sm.labels.items()): sm.value
                 for fam in eng.metrics.collect()
                 if fam.name == "serving_kv_pool_bytes"
                 for sm in fam.samples}
        load = eng.expert_load()[0]
        spans = eng.trace.chrome_trace()["traceEvents"]
        pool = eng.slots.pool
        held = sum(int(a.nbytes) for a in jax.tree.leaves(
            (pool.k_pool, pool.v_pool)))
    finally:
        eng.shutdown()
    meta = reference.meta_of(cfg)
    longest = max(len(r.tokens) for r in results)
    for p, got in zip(prompts, results):
        assert got.finish_reason == "length" and len(got.tokens) == len(p) + 8
        # (at one length for every request, the reference's forward being
        # compiled a length: a position depends on no token behind it)
        padded = got.tokens + [1] * (longest - len(got.tokens))
        want = np.asarray(reference.token_logprobs(params, padded, meta))[
            :len(got.tokens) - 1]
        np.testing.assert_allclose(got.logprobs, want, atol=3e-5)
    # the pool's bytes, by its kind: 40 bf16... float32 values a position
    # a layer here, and no leaf beside the latent and the rotated key part
    blocks = 1 + 2 * 8
    assert held == 3 * blocks * 16 * (32 + 8) * 4
    assert snap["kv_pool_bytes_by_kind"] == {"latent": held}
    assert gauge == {(("kind", "latent"),): held}
    # which form ran, and what a step attended
    pre = [e["args"] for e in spans if e["name"] == "prefill"]
    dec = [e["args"] for e in spans if e["name"] == "decode"]
    assert len(pre) == 5 and {a["attn"] for a in pre} == {"mla_expanded"}
    assert dec and {a["attn"] for a in dec} == {"mla_absorbed"}
    assert all(a["live_positions"] >= 21 * a["live"] for a in dec)
    # the latent rows' walk: a grid step a slot, nothing started ahead
    assert all((a["walk_steps"], a["walk_prefetched"]) == (a["live"], 0)
               for a in dec)
    assert {a["experts"] for a in pre} == {"grouped"}
    # the experts were counted: none for the dense first layer
    assert load.shape == (3, 8) and load[0].sum() == 0
    # (two choices a position; a retired slot's step in flight counts on)
    tokens = sum(len(p) + 7 for p in prompts)
    assert load[1].sum() == load[2].sum() >= 2 * tokens


REFUSED = {
    "int8_pool": ({}, dict(model=dict(kv_cache_quant="int8")), "int8"),
    "speculation": (dict(spec_draft_len=3), {}, "speculation"),
    "draft_model": ({}, dict(draft=True), "speculation"),
    "chunked_prefill": (dict(prefill_chunk=32), {}, "prefill_chunk"),
    "prefix_cache": (dict(prefix_cache_blocks=8), {},
                     "prefix_cache_blocks"),
    "host_tier": (dict(host_kv_blocks=8), {}, "host_kv_blocks"),
    "disaggregation": (dict(role="prefill"), {}, "role"),
    "adapters": ({}, dict(adapters=True), "adapters"),
    "mesh": ({}, dict(mesh=True), "mesh"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_is_not_carried_is_refused_at_construction(model, case):
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
    with pytest.raises(ValueError, match="latent-attention stack") as err:
        ServingEngine(cfg, params, EngineConfig(**{**ENGINE, **engine_kw}),
                      **kw)
    assert said in str(err.value)
