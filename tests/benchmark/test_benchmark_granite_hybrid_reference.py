"""The plain Granite 4.0-H reference against ``models/`` and the serving
engine at tiny widths: a state-space two-part layer and an attention
two-part layer, one B/C group, the four scalar multipliers."""

import ast
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite_hybrid as reference
from megatron_llm_tpu.config import granite_hybrid_config
from megatron_llm_tpu.models import mamba2
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.serving import EngineConfig, ServingEngine

TINY = dict(num_layers=4, layer_pattern=("ssm", "full"), hidden_size=64,
            num_attention_heads=4, num_kv_heads=2, kv_channels=16,
            ffn_hidden_size=96, vocab_size=500, mamba_num_heads=8,
            mamba_head_dim=8, mamba_n_groups=1, mamba_state_size=16,
            mamba_chunk_size=8, max_position_embeddings=512,
            make_vocab_size_divisible_by=4)
# all four away from what a program that ignored them would use
FOUR = dict(embedding_multiplier=3.0, residual_multiplier=0.5,
            attention_multiplier=0.1, logits_scaling=2.0)
# each alone: every other one at the value that changes nothing
NEUTRAL = dict(embedding_multiplier=1.0, residual_multiplier=1.0,
               attention_multiplier=None, logits_scaling=1.0)


def tiny(dtype, **kw):
    cfg = granite_hybrid_config("4.0-h-micro", params_dtype=dtype,
                                **{**TINY, **FOUR, **kw})
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))
    # norm weights, D and the biases away from their initial values
    noise = iter(jax.random.split(jax.random.key(1), 256))
    params = jax.tree.map(
        lambda a: (a + 0.1 * jax.random.normal(next(noise), a.shape)
                   ).astype(a.dtype) if a.ndim <= 2 and a.shape[-1] <= 64
        else a, params)
    # the Mamba-2 input projection at the size it has at the published
    # width (0.02 x sqrt(2048)): at 0.02 x sqrt(64) the state adds a
    # hundredth of what the skip ``D x`` does, and nothing would see it;
    # and q and k large enough that the softmax is not flat whatever
    # its scale
    def times(tree, keys, by):
        for k in keys:
            tree[k] = (by * tree[k]).astype(tree[k].dtype)

    for layer in params["layers"]:
        if "mamba" in layer:
            times(layer["mamba"], ("w_in",), 6.0)
        if "attn" in layer:
            times(layer["attn"], ("wq", "wk"), 12.0)
    return cfg, params


def program_logits(cfg, params, toks):
    return np.asarray(jax.jit(lambda p, t: model_lib.forward(cfg, p, t))(
        params, jnp.asarray(toks[None]))[0, :, :cfg.vocab_size])


def program_logprobs(cfg, params, toks):
    lp = jax.nn.log_softmax(jnp.asarray(
        program_logits(cfg, params, toks[:-1]), jnp.float32), -1)
    return np.take_along_axis(np.asarray(lp), toks[1:, None], 1)[:, 0]


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(Path(reference.__file__).read_text())
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names} | {n.module for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)}
    assert names == {"__future__", "functools", "jax", "jax.numpy"}
    cfg, _ = tiny("float32")
    meta = reference.meta_of(cfg)
    hash(meta)                               # a static argument of its jits
    m = dict(meta)
    assert m["pattern"] == ("ssm", "full") and m["groups"] == 1
    assert (m["embedding_multiplier"], m["residual_multiplier"],
            m["attention_multiplier"], m["logits_scaling"]) == (
        3.0, 0.5, 0.1, 2.0)
    # without an attention multiplier the scale is the usual one
    usual = dict(reference.meta_of(tiny("float32",
                                        attention_multiplier=None)[0]))
    assert usual["attention_multiplier"] == 16 ** -0.5


def test_logits_match_the_program_in_float32():
    """Logits, not tokens: every position, float32 against float32, the
    recurrence against the chunked form: rounding only.  150 is no
    multiple of the 8-position chunk."""
    cfg, params = tiny("float32")
    toks = np.random.default_rng(0).integers(0, 500, size=150)
    meta = reference.meta_of(cfg)
    want = np.asarray(reference.logits_of(params, toks, meta))
    assert float(np.abs(want).max()) > 0.1
    np.testing.assert_allclose(program_logits(cfg, params, toks), want,
                               atol=1e-4)
    got = np.asarray(reference.token_logprobs(params, toks, meta))
    np.testing.assert_allclose(got, program_logprobs(cfg, params, toks),
                               atol=2e-5)
    assert reference.loss(params, [toks, toks[:40]], meta) == pytest.approx(
        -(got.sum() + got[:39].sum()) / (149 + 39), rel=1e-5)


@pytest.mark.parametrize("name", sorted(FOUR))
def test_each_multiplier_alone_is_applied_where_the_equations_put_it(name):
    """That multiplier alone away from 1 (the softmax scale: from
    1/sqrt(16)).  The program matches the reference that applies it, and
    is far from the reference that does not: a program that ignored it
    would be that one."""
    cfg, params = tiny("float32", **{**NEUTRAL, name: FOUR[name]})
    toks = np.random.default_rng(3).integers(0, 500, size=60)
    got = program_logits(cfg, params, toks)
    meta = reference.meta_of(cfg)
    np.testing.assert_allclose(
        got, reference.logits_of(params, toks, meta), atol=1e-4)
    ignored = tuple((k, (16 ** -0.5 if name == "attention_multiplier"
                         else 1.0) if k == name else v) for k, v in meta)
    off = np.abs(got - np.asarray(
        reference.logits_of(params, toks, ignored))).max()
    assert off > 100 * 1e-4, off


def in_bf16(fn):
    """``fn``'s result rounded to bfloat16: what a program that kept this
    quantity in the weights' precision would compute."""
    def rounded(*a, **k):
        out = fn(*a, **k)
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16).astype(x.dtype), out)
    return rounded


def stepwise_logprobs(cfg, params, toks, prompt=30):
    """A prefill of ``prompt`` positions, then one position a step through
    the carried states: the cached path without the engine around it."""
    t = jnp.asarray(toks[None])
    k, v = model_lib.init_kv_cache(cfg, 1, 128)
    logits, k, v, rec = model_lib.forward_cached_hybrid(
        cfg, params, t[:, :prompt], k, v, jnp.int32(0),
        model_lib.init_rec_state(cfg, 1), empty_cache=True)
    step = jax.jit(lambda t, k, v, n, rec: model_lib.forward_cached_hybrid(
        cfg, params, t, k, v, n, rec))
    out = [logits[0]]
    for i in range(prompt, len(toks) - 1):
        l, k, v, rec = step(t[:, i:i + 1], k, v,
                            jnp.full((1,), i, jnp.int32), rec)
        out.append(l[0])
    lp = np.asarray(jax.nn.log_softmax(
        jnp.concatenate(out)[:, :cfg.vocab_size], -1))
    return np.take_along_axis(lp, toks[1:, None], 1)[:, 0]


@pytest.mark.parametrize("what", ["as_stated", "state"])
def test_a_bf16_state_where_the_configuration_states_float32_is_told_apart(
        monkeypatch, what):
    """Prefill, then 89 decode steps through two state-space layers,
    against the reference's one forward pass.  As the configuration
    states it (the state in float32) the two differ by summation order
    alone; a state rounded to bfloat16 at every step loses what small
    steps add to it and fails that tolerance (the residual multiplier
    and the logits' divisor damp what reaches a log-probability)."""
    cfg, params = tiny("float32")
    toks = np.random.default_rng(0).integers(1, 500, size=120)
    want = np.asarray(reference.token_logprobs(params, toks,
                                               reference.meta_of(cfg)))
    if what == "state":
        monkeypatch.setattr(mamba2, "ssd_step", in_bf16(mamba2.ssd_step))
    jax.clear_caches()
    try:
        off = np.abs(stepwise_logprobs(cfg, params, toks) - want).max()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    if what == "as_stated":
        assert off < 5e-6, off               # 5e-7 measured
    else:
        assert off > 2e-5, off               # 3e-5


def test_a_lower_precision_is_told_apart():
    """bf16 weights stay close to their own float32 reference, and a
    model whose weights were rounded to 8 bits does not: by the mean
    limit of the harness (0.03)."""
    cfg, params = tiny("bfloat16")
    toks = np.random.default_rng(1).integers(0, 500, size=130)
    want = np.asarray(reference.token_logprobs(params, toks,
                                               reference.meta_of(cfg)))

    def to_8_bits(a):
        if a.ndim < 2 or a.dtype != jnp.bfloat16:
            return a
        scale = jnp.max(jnp.abs(a.astype(jnp.float32))) / 7.0
        return (jnp.round(a.astype(jnp.float32) / scale) * scale
                ).astype(a.dtype)

    near = np.abs(program_logprobs(cfg, params, toks) - want).mean()
    far = np.abs(program_logprobs(cfg, jax.tree.map(to_8_bits, params),
                                  toks) - want).mean()
    assert near < 0.03 < far, (near, far)


@pytest.mark.parametrize("dtype,atol", [("float32", 5e-5),
                                        ("bfloat16", 0.15)])
def test_the_engine_prefills_and_decodes_to_the_reference(dtype, atol):
    """Through submit, the queue, admission into a padded bucket, the
    block pool and the slot state's install, then paged decode steps
    through pool and state: every position's log-probability, prompt and
    generated, against the reference's one full forward.  130 and 77 are
    no multiple of the 8-position chunk or of the bucket."""
    cfg, params = tiny(dtype)
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch_size=2, max_seq_len=256, kv_block_size=16,
        prefill_bucket=64, prefix_cache_blocks=0)).start()
    try:
        rng = np.random.default_rng(2)
        handles = [eng.submit(rng.integers(1, 499, size=n).tolist(), 9,
                              use_eos_stop=False, return_logprobs=True,
                              seed=0) for n in (130, 77, 64)]
        for h in handles:
            got = h.result(timeout=300)
            want = np.asarray(reference.token_logprobs(
                params, got.tokens, reference.meta_of(cfg)))
            assert len(got.logprobs) == len(got.tokens) - 1
            np.testing.assert_allclose(got.logprobs, want, atol=atol)
    finally:
        eng.shutdown()
