"""Opt-in real-TPU integration tier (SURVEY §4's hardware tier, the
analogue of the reference's torchrun GPU tests).

Run on a machine with a TPU attached:

    python -m pytest tests_tpu/ -q

Unlike tests/ (which pins an 8-device CPU mesh in its conftest), this
directory requires a real TPU and skips entirely on any other platform.
Timing assertions wait for the result inside the timed region (a host
fetch or ``block_until_ready``): dispatch is asynchronous.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

if jax.devices()[0].platform != "tpu":  # pragma: no cover
    pytest.skip("requires a TPU device", allow_module_level=True)


def test_flash_kernel_matches_einsum_bf16():
    from megatron_llm_tpu.kernels.flash_attention import flash_attention
    from megatron_llm_tpu.ops.attention import dot_product_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 1024, 8, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 1024, 4, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 1024, 4, 64)), jnp.bfloat16)
    got = np.asarray(jax.jit(
        lambda a, b, c: flash_attention(a, b, c, causal=True))(q, k, v),
        np.float32)
    want = np.asarray(dot_product_attention(q, k, v, causal=True),
                      np.float32)
    assert np.max(np.abs(got - want)) < 3e-2  # bf16 kernel vs fp32 softmax


def test_flash_kernel_32k_long_context():
    """BASELINE config 4's hard part: 32k causal attention fwd+bwd."""
    from megatron_llm_tpu.kernels.flash_attention import flash_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 32768, 4, 128)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 32768, 4, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 32768, 4, 128)), jnp.bfloat16)
    g = jax.jit(jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, causal=True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))
    gq, gk, gv = g(q, k, v)
    for arr in (gq, gk, gv):
        assert bool(jnp.isfinite(arr.astype(jnp.float32)).all())


def _train_setup(mb, seq, lr, **model_overrides):
    from megatron_llm_tpu.config import (
        OptimizerConfig, ParallelConfig, RuntimeConfig, TrainConfig,
        tiny_config,
    )
    from megatron_llm_tpu.training.driver import setup_train_state

    cfg = RuntimeConfig(
        model=tiny_config(params_dtype="bfloat16", **model_overrides),
        parallel=ParallelConfig(),
        optimizer=OptimizerConfig(lr=lr, clip_grad=1.0),
        train=TrainConfig(train_iters=10, micro_batch_size=mb,
                          global_batch_size=mb, seq_length=seq, save=None),
    ).validate()
    art = setup_train_state(cfg)
    toks = np.random.default_rng(0).integers(
        0, cfg.model.vocab_size, (1, mb, seq))
    batch = {
        "tokens": jnp.asarray(toks, jnp.int32),
        "labels": jnp.asarray(np.roll(toks, -1, -1), jnp.int32),
        "loss_mask": jnp.ones((1, mb, seq), jnp.float32),
    }
    return art, batch


def test_train_step_loss_decreases():
    # head_dim 16 (tiny_config) deliberately exercises a sub-128-lane
    # Pallas flash shape on hardware — validated passing on v5e
    art, batch = _train_setup(mb=4, seq=128, lr=1e-2,
                              attention_impl="flash")
    state = art.state
    losses = []
    for _ in range(8):
        state, m = art.step_fn(state, batch, jax.random.key(0))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_moe_train_step_runs():
    art, batch = _train_setup(mb=2, seq=64, lr=1e-3,
                              num_experts=4, moe_top_k=2)
    state, m = art.step_fn(art.state, batch, None)
    state, m = art.step_fn(state, batch, None)  # re-donation
    assert np.isfinite(float(m["loss"]))


def test_generation_greedy():
    from megatron_llm_tpu.config import tiny_config
    from megatron_llm_tpu.generation.generation import generate_tokens
    from megatron_llm_tpu.models import model as model_lib

    cfg = tiny_config(params_dtype="bfloat16")
    params = model_lib.init_params(jax.random.key(0), cfg)
    buf = jnp.zeros((1, 16), jnp.int32).at[0, :4].set(
        jnp.asarray([5, 6, 7, 8]))
    out = generate_tokens(cfg, params, buf, jnp.asarray([4]),
                          use_eos_stop=False)
    toks = np.asarray(out.tokens)
    assert toks.shape == (1, 16)
    assert (toks[0, :4] == [5, 6, 7, 8]).all()


def test_flash_decode_kernel_parity_on_hw():
    """flash_decode (Pallas) vs a numpy reference on the real chip, across
    GQA/MQA configs.  Tolerance covers the MXU's default bf16-pass rounding
    of f32 operands; exact-math parity is covered in interpret mode by
    tests/kernels/test_flash_decode.py."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.ops.attention import decode_attention

    rng = np.random.default_rng(0)
    for (h, kv, M, cl) in ((8, 8, 1024, 700), (8, 2, 512, 17),
                           (4, 1, 256, 255)):
        q = rng.normal(size=(2, 1, h, 128)).astype(np.float32)
        k = rng.normal(size=(2, kv, M, 128)).astype(np.float32)
        v = rng.normal(size=(2, kv, M, 128)).astype(np.float32)
        got = jax.jit(
            lambda q, k, v: decode_attention(q, k, v, jnp.int32(cl))
        )(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g = h // kv
        qg = q.reshape(2, 1, kv, g, 128)
        want = np.zeros((2, 1, h, 128), np.float32)
        for b in range(2):
            for hh in range(kv):
                for gg in range(g):
                    s = (k[b, hh] @ qg[b, 0, hh, gg]) / np.sqrt(128)
                    s[cl + 1:] = -np.inf
                    p = np.exp(s - s.max())
                    p /= p.sum()
                    want[b, 0, hh * g + gg] = p @ v[b, hh]
        d = float(np.max(np.abs(np.asarray(got) - want)))
        assert d < 0.02, (h, kv, M, cl, d)


# slots, table width, query heads, key heads x width, value heads x width,
# fills, softmax scale: the long-generation cell's packed pairs (two grid
# steps a slot), the short-request cell's, the chat cell's
_WALKS = {
    "pairs40x64kv20v10": (64, 64, 40, (20, 64), (10, 128), (1024, 6000),
                          0.125),
    "gqa32x64kv8": (64, 24, 32, (8, 64), (8, 64), (64, 3000), 1.0 / 64),
    "falcon71x64mqa": (16, 16, 71, (1, 64), (1, 64), (50, 700), 0.125),
}


@pytest.mark.parametrize("geometry", list(_WALKS))
def test_paged_walk_matches_float32_composition_on_hw(geometry):
    """The paged walk's copies cross grid steps (a step's last iteration
    starts the next live step's first blocks), which interpret mode
    orders trivially and the chip does not: at the cells' geometries,
    with empty slots first, last, between live ones and two in a row,
    one-iteration slots and whole iterations, every slot's output lies
    as close to the plain float32 composition over its gathered rows as
    bf16 allows, and an empty slot's is its own value row."""
    from megatron_llm_tpu.kernels.flash_decode import (flash_decode_paged,
                                                       pool_walk)

    S, T, H, (KV, dk), (VH, dv), (lo, hi), scale = _WALKS[geometry]
    bk, nb = 128, S * T + 1
    key = jax.random.key(57)
    rnd = lambda i, shape: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape, jnp.float32).astype(jnp.bfloat16)
    kp, vp = rnd(0, (1, nb, KV, bk, dk)), rnd(1, (1, nb, VH, bk, dv))
    q, kn, vn = rnd(2, (S, H, dk)), rnd(3, (S, KV, 1, dk)), rnd(
        4, (S, VH, 1, dv))
    n = pool_walk(kp, vp, T)[2]
    rng = np.random.default_rng(57)
    fills = rng.integers(lo, hi, S).astype(np.int32)
    fills[[0, 2, 9, 10, S - 1]] = 0
    fills[3:9] = [n * bk, n * bk + 1, 1, bk, n * bk - 1, 7]
    tables, ids, used = np.zeros((S, T), np.int32), rng.permutation(
        np.arange(1, nb)), 0
    for i, f in enumerate(fills):
        own = min(T, -(-int(f + 1) // bk))
        tables[i, :own] = ids[used:used + own]
        used += own
    got = jax.jit(lambda *a: flash_decode_paged(
        *a[:5], new_rows=a[5:], layer=jnp.int32(0), softmax_scale=scale))(
            q, kp, vp, jnp.asarray(tables), jnp.asarray(fills), kn, vn)

    def one(args):
        q, t, f, kn, vn = args
        kd = jnp.moveaxis(kp[0][t], 1, 0).reshape(KV, -1, dk)
        vd = jnp.moveaxis(vp[0][t], 1, 0).reshape(VH, -1, dv)
        kd = jnp.concatenate([kd, kn], axis=1).astype(jnp.float32)
        vd = jnp.concatenate([vd, vn], axis=1).astype(jnp.float32)
        cols = jnp.arange(kd.shape[1])
        keep = (cols < f) | (cols == kd.shape[1] - 1)
        s = jnp.einsum("kgd,ktd->kgt", q.astype(jnp.float32).reshape(
            KV, H // KV, dk), kd, precision="highest") * scale
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("vgt,vtd->vgd", p.reshape(VH, H // VH, -1), vd,
                          precision="highest").reshape(H, dv)

    want = jax.jit(lambda *a: jax.lax.map(one, a))(
        q, jnp.asarray(tables), jnp.asarray(fills), kn, vn)
    got, want = np.asarray(got, np.float32), np.asarray(want)
    assert np.isfinite(got).all()
    worst = max(np.linalg.norm(got[i] - want[i]) / np.linalg.norm(want[i])
                for i in range(S))
    assert worst < 0.006, worst
    for i in np.flatnonzero(fills == 0):
        np.testing.assert_array_equal(
            got[i].reshape(VH, H // VH, dv),
            np.broadcast_to(np.asarray(vn[i], np.float32),
                            (VH, H // VH, dv)))


def test_int8_decode_runs_and_kernel_matches_einsum():
    """Full int8 decode (weights + KV cache) runs on the real chip, and
    the Pallas int8 decode kernel matches an independently-computed
    einsum attention reference on the same int8 cache.  (int8 tokens are
    not compared with bf16 ones: on a random-init model every argmax is
    borderline, so quantization noise legitimately flips tokens.)  Speed
    is the benchmark's business."""
    import dataclasses

    from megatron_llm_tpu.config import llama2_config
    from megatron_llm_tpu.generation.generation import generate_tokens
    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.ops.quant import quantize_params

    b, prompt_len, gen_len = 8, 128, 64
    cfg = dataclasses.replace(llama2_config(
        "7b", hidden_size=1024, num_layers=4, num_attention_heads=8,
        num_kv_heads=8, ffn_hidden_size=2816,
        seq_length=prompt_len + gen_len,
        max_position_embeddings=prompt_len + gen_len,
        params_dtype="bfloat16", attention_impl="flash"),
        kv_cache_quant="int8").validate()
    params = quantize_params(model_lib.init_params(jax.random.key(0), cfg))

    rng = np.random.default_rng(1)
    tokens = np.zeros((b, prompt_len + gen_len), np.int32)
    tokens[:, :prompt_len] = rng.integers(1, cfg.vocab_size,
                                          (b, prompt_len))
    out = np.asarray(generate_tokens(
        cfg, params, jnp.asarray(tokens),
        jnp.full((b,), prompt_len, jnp.int32), use_eos_stop=False).tokens)
    assert ((out >= 0) & (out < cfg.padded_vocab_size())).all()
    assert (out[:, :prompt_len] == tokens[:, :prompt_len]).all()

    # fidelity: the Pallas int8 decode KERNEL against plain attention on
    # the SAME quantized cache — deterministic, isolates kernel numerics
    from megatron_llm_tpu.kernels.flash_decode import flash_decode_int8
    from megatron_llm_tpu.ops.kv_quant import quantize_rows

    kv, d, L = cfg.kv_heads, cfg.head_dim, 256
    g = cfg.num_attention_heads // kv
    r = np.random.default_rng(7)
    q = jnp.asarray(r.standard_normal((b, kv * g, d)), jnp.bfloat16)
    kc = quantize_rows(jnp.asarray(r.standard_normal((b, kv, L, d)),
                                   jnp.bfloat16))
    vc = quantize_rows(jnp.asarray(r.standard_normal((b, kv, L, d)),
                                   jnp.bfloat16))
    clen = 200
    kernel_out = flash_decode_int8(q, kc["q"], kc["scale"], vc["q"],
                                   vc["scale"], jnp.int32(clen))
    # Independent reference computed here (decode_attention would dispatch
    # to the same Pallas kernel on TPU — comparing against it is vacuous):
    # dequantize the cache and run plain masked softmax attention in fp32.
    kd = np.asarray(kc["q"], np.float32) * np.asarray(kc["scale"])[..., None]
    vd = np.asarray(vc["q"], np.float32) * np.asarray(vc["scale"])[..., None]
    qg = np.asarray(q, np.float32).reshape(b, kv, g, d)
    s = np.einsum("bkgd,bkld->bkgl", qg, kd) / np.sqrt(d)
    s[:, :, :, clen:] = -np.inf
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bkgl,bkld->bkgd", p, vd).reshape(b, kv * g, d)
    delta = np.abs(np.asarray(kernel_out, np.float32) - ref).max()
    print(f"int8 kernel vs independent einsum max|delta|: {delta:.5f}")
    assert delta < 0.05, delta


def test_decode_step_moves_no_weight_and_matches_reference():
    """The engine's decode executable at Falcon-7B width (2 layers, 16
    slots, the composed paged route), compiled on the chip: no copy,
    transpose or stand-alone slice of 8 MiB or more is in it — every
    weight is read once, where it lies (obs/hlo_audit.py; before PR 30
    each layer's ``wq`` was sliced out and re-laid, 2 x 41.3 MB, and the
    tied table copied for the embedding gather, 591 MB) — and the
    log-probs the same executable gives its greedy tokens are the plain
    reference's, inside the serving cells' tolerance.  The sampler's
    sort of the vocabulary lies under the ``conditional`` XLA kept, and
    the same executable, handed one sampling slot, gives the greedy rows
    the tokens of the all-greedy run."""
    from benchmarks.reference import falcon as reference
    from benchmarks.serving import LOGPROB_MAX_TOL, LOGPROB_MEAN_TOL
    from megatron_llm_tpu.config import falcon_config
    from megatron_llm_tpu.models import model as model_lib
    from megatron_llm_tpu.obs.hlo_audit import (
        ops_by_conditional,
        relayout_bytes,
    )
    from megatron_llm_tpu.serving import engine as engine_lib

    slots, t, bk, steps = 16, 16, 128, 12
    cfg = falcon_config("7b", num_layers=2, attention_impl="flash")
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))
    pools = model_lib.init_kv_pool(cfg, 1 + slots * t, bk)
    assert model_lib.paged_decode_eligible(cfg, pools[0])
    tables = 1 + jnp.arange(slots * t, dtype=jnp.int32).reshape(slots, t)
    zeros = jnp.zeros((slots,), jnp.int32)
    first = jax.random.randint(jax.random.key(1), (slots,), 1,
                               cfg.vocab_size - 1)
    all_greedy = (jnp.ones((slots,), bool), jnp.ones((slots,), jnp.float32),
                  zeros, jnp.zeros((slots,), jnp.float32))
    step = engine_lib._decode_donated.lower(
        cfg, params, *pools, tables, first, zeros, zeros, zeros,
        *all_greedy).compile()
    text = step.as_text()
    assert "tpu_custom_call" in text
    assert relayout_bytes(text) == {}
    sorts, always = ops_by_conditional(text, "sort")
    assert 1 <= len(sorts) <= 2 and not always, (sorts, always)

    def rollout(knobs):
        pending, pools = first, model_lib.init_kv_pool(cfg, 1 + slots * t, bk)
        tokens, logprobs = [np.asarray(pending)], []
        for i in range(steps):
            counters = jnp.full((slots,), i, jnp.int32)
            pending, lp, *pools = step(params, *pools, tables, pending,
                                       counters, zeros + 7, counters, *knobs)
            tokens.append(np.asarray(pending))
            logprobs.append(np.asarray(lp, np.float32))
        return np.stack(tokens, 1), np.stack(logprobs, 1)

    del pools
    tokens, logprobs = rollout(all_greedy)
    meta = reference.meta_of(cfg)
    d = np.stack([
        np.abs(logprobs[s] - np.asarray(
            reference.token_logprobs(params, tokens[s], meta)))
        for s in range(3)])
    assert np.isfinite(d).all()
    assert d.max() <= LOGPROB_MAX_TOL and d.mean() <= LOGPROB_MEAN_TOL, (
        d.max(), d.mean())

    row = 5
    one_samples = (all_greedy[0].at[row].set(False),
                   all_greedy[1].at[row].set(0.8),
                   all_greedy[2].at[row].set(50),
                   all_greedy[3].at[row].set(0.9))
    mixed_tokens, mixed_logprobs = rollout(one_samples)
    rest = np.arange(slots) != row
    np.testing.assert_array_equal(mixed_tokens[rest], tokens[rest])
    np.testing.assert_allclose(mixed_logprobs[rest], logprobs[rest],
                               rtol=0, atol=1e-5)
    assert (mixed_tokens[row] != tokens[row]).any()
    assert (mixed_tokens[row] < cfg.vocab_size).all()
    assert np.isfinite(mixed_logprobs[row]).all()
