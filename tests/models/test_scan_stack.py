"""The fold is real: a stack neither the period scan nor the run scan
could carry goes through ``transformer.scan_stack``: Laguna's published
shape at a tiny size, a leading dense layer, two periods of three window
layers and a full one and a LAST RUN of three window layers, dropless
experts in every scanned layer (a test's override of the preset's
``layer_runs``; the preset itself still serves a stage of five layers).
Against the same layers applied one at a time with ``layer_forward``,
float32."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import laguna_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models import transformer
from tests.models.test_laguna_stack import TINY, _shake

W = ("window",) * 3
RUNS = ((W + ("full",), 2), (W, 1))
PROMPT, BUCKET, STEPS = 11, 16, 3       # a prompt past a window of 8
# (the reference reads PROMPT + STEPS tokens: the last step's logits are
# of a position it does not hold)


@pytest.fixture(scope="module")
def stack():
    flat = tuple(kind for period, times in RUNS for kind in period * times)
    # (softmax scores: a seeded tree's sigmoid routers are levelled over
    # 2048 tokens at init, which is most of such a test's time)
    cfg = laguna_config(**{**TINY, "layer_runs": RUNS, "layer_pattern": flat,
                           "num_layers": 1 + len(flat),
                           "moe_router_scoring": "softmax"})
    # the tree's shapes filled from numpy (a seeded init of twelve layers
    # is ten seconds of threefry on this backend): norms 1, matrices 0.02,
    # the attention's 12- and 4-fold as test_laguna_stack.py has them
    rng = np.random.default_rng(0)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: _shake(path, jnp.asarray(
            np.ones(a.shape) if "scale" in jax.tree_util.keystr(path)
            else rng.normal(0.0, 0.02, a.shape), a.dtype)),
        jax.eval_shape(lambda: model_lib.init_params(jax.random.key(0),
                                                     cfg)))
    tokens = jnp.asarray(rng.integers(1, 500, (1, PROMPT + STEPS)),
                         jnp.int32)
    return cfg, params, tokens, *_one_at_a_time(cfg, params, tokens)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _one_layer(cfg, kind, p, x):
    """(jitted a kind: three small programs, not one of twelve layers)"""
    side = transformer.AttnSideInputs(position_ids=jnp.arange(
        x.shape[1], dtype=jnp.int32)[None])
    x, aux = transformer.layer_forward(cfg, p, x, side, kind=kind)
    return x, (aux["load"] if isinstance(aux, dict) else jnp.zeros((8,)))


def _one_at_a_time(cfg, params, tokens):
    x = model_lib.embed(cfg, params, tokens).astype(jnp.float32)
    layers = [(cfg.lead_layer_config, "full",
               jax.tree.map(lambda a: a[0], params["lead_layers"]))]
    for (period, times), trees in zip(cfg.layer_runs, params["layers"]):
        layers += [(cfg, kind, jax.tree.map(lambda a, i=i: a[i], tree))
                   for i in range(times) for kind, tree in zip(period, trees)]
    loads = []
    for c, kind, p in layers:
        x, load = _one_layer(c, kind, p, x)
        loads.append(load)
    x = transformer.norm_apply(cfg.norm_type, x, params["final_norm"],
                               cfg.norm_eps)
    return model_lib.unembed(cfg, params, x)[0], jnp.stack(loads)


def test_the_published_shape_is_a_stack_of_two_runs(stack):
    cfg, params, tokens, want, loads = stack
    assert cfg.stack_runs == RUNS and cfg.num_layers == 12
    assert (cfg.kv_layers, cfg.window_layers) == (3, 9)     # lead + 2, 6 + 3
    assert [len(run) for run in params["layers"]] == [4, 3]
    assert params["layers"][0][3]["mlp"]["w_up"].shape[:2] == (2, 8)
    got, aux = jax.jit(lambda p, t: model_lib.forward(
        cfg, p, t, return_aux=True))(params, tokens)
    np.testing.assert_allclose(got[0], want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(aux["load"], loads.sum(0))


def test_a_prompt_and_three_steps_through_the_one_scan(stack):
    cfg, params, tokens, want, loads = stack
    k, v = model_lib.init_kv_cache(cfg, 1, 32)
    rec = model_lib.init_rec_state(cfg, 1)
    padded = jnp.zeros((1, BUCKET), jnp.int32).at[:, :PROMPT].set(
        tokens[:, :PROMPT])
    logits, k, v, rec = jax.jit(
        lambda p, t, k, v, rec: model_lib.forward_cached_hybrid(
            cfg, p, t, k, v, jnp.int32(0), rec,
            valid=jnp.arange(BUCKET)[None, :] < PROMPT, empty_cache=True,
            logit_rows=jnp.array([PROMPT - 1])))(params, padded, k, v, rec)
    got = [logits[0, 0]]
    step = jax.jit(lambda p, t, k, v, n, rec: model_lib.forward_cached_hybrid(
        cfg, p, t, k, v, n, rec))
    for t in range(PROMPT, PROMPT + STEPS):
        logits, k, v, rec = step(params, tokens[:, t:t + 1], k, v,
                                 jnp.array([t]), rec)
        got.append(logits[0, 0])
    np.testing.assert_allclose(np.stack(got[:-1]), want[PROMPT - 1:-1],
                               atol=1e-5, rtol=0)
    # every expert layer counted every position fed, the leading layer none
    assert rec["load"].shape == (12, 8) and not rec["load"][0].any()
    np.testing.assert_array_equal(rec["load"][1:].sum(-1),
                                  (PROMPT + STEPS) * cfg.moe_top_k)
    np.testing.assert_allclose(rec["load"], loads)
    assert rec["win_k"].shape == (9, 1, 2, 8, 16)
