"""The plain ``deepseek_v3`` reference: against a single layer written out
by hand in float64 (loops over positions, heads and experts), and
against ``models/`` at tiny widths."""

import ast
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import deepseek_v3 as reference
from megatron_llm_tpu.config import deepseek_v3_config
from megatron_llm_tpu.models import model as model_lib

TINY = dict(num_layers=3, hidden_size=64, num_attention_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, ffn_hidden_size=32, moe_dense_ffn_size=96,
            num_experts=8, moe_top_k=2, moe_shared_expert_size=64,
            vocab_size=500, make_vocab_size_divisible_by=4,
            max_position_embeddings=512, moe_group_size=64,
            params_dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = deepseek_v3_config("kanana-2-30b-a3b-pp8-stage0", **TINY)
    params = jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))
    # norm weights and the selection bias away from what they start at;
    # q and the latent projections large enough for a softmax that is
    # not flat
    noise = iter(jax.random.split(jax.random.key(1), 64))
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(next(noise), a.shape)
        if a.shape[-1] in (32, 64) and a.ndim == 2 else a, params)
    for tree in (params["lead_layers"], params["layers"][0]):
        for k in ("wq", "wkv_b"):
            tree["attn"][k] = 6.0 * tree["attn"][k]
    return cfg, params


def test_the_reference_imports_nothing_of_the_program(model):
    tree = ast.parse(Path(reference.__file__).read_text())
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names} | {n.module for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)}
    assert names == {"__future__", "functools", "math", "jax", "jax.numpy"}
    hash(reference.meta_of(model[0]))        # a static argument of its jits


def by_hand(p, x, m, dense):
    """One layer in float64, written from ISSUE 52's equations: every
    position, head and expert in a loop of its own."""
    f = lambda a: np.asarray(a, np.float64)   # noqa: E731
    rms = lambda v, w: f(w) * v / np.sqrt(np.mean(v * v) + m["eps"])  # noqa: E731
    silu = lambda v: v / (1 + np.exp(-v))     # noqa: E731
    T, H = x.shape[0], m["heads"]
    r, dn, dr, dv = m["rank"], m["nope"], m["rope"], m["v"]
    a = p["attn"]

    def rotate(v, t):          # adjacent pairs, position t; pairs stay
        out = v.copy()
        for i in range(dr // 2):
            ang = t / m["theta"] ** (2 * i / dr)
            x0, x1 = v[2 * i], v[2 * i + 1]
            out[2 * i] = x0 * np.cos(ang) - x1 * np.sin(ang)
            out[2 * i + 1] = x1 * np.cos(ang) + x0 * np.sin(ang)
        return out

    q, c, kpe = [], [], []
    for t in range(T):
        n = rms(x[t], a_in := p["input_norm"]["scale"])
        qt = (n @ f(a["wq"])).reshape(H, dn + dr)
        qt[:, dn:] = [rotate(qt[h, dn:], t) for h in range(H)]
        kva = n @ f(a["wkv_a"])
        q.append(qt)
        c.append(rms(kva[:r], a["kv_norm"]["scale"]))
        kpe.append(rotate(kva[r:], t))
    h_out = np.zeros_like(x)
    for t in range(T):
        heads = []
        for h in range(H):
            scores, values = [], []
            for s in range(t + 1):
                kv = (c[s] @ f(a["wkv_b"])).reshape(H, dn + dv)[h]
                scores.append((q[t][h, :dn] @ kv[:dn]
                               + q[t][h, dn:] @ kpe[s]) / np.sqrt(dn + dr))
                values.append(kv[dn:])
            w = np.exp(np.array(scores) - max(scores))
            heads.append((w / w.sum()) @ np.array(values))
        h_out[t] = x[t] + np.concatenate(heads) @ f(a["wo"])
    out = np.zeros_like(x)
    mlp = p["mlp"]
    gated = lambda v, g, u, d: (silu(v @ f(g)) * (v @ f(u))) @ f(d)  # noqa: E731
    for t in range(T):
        n = rms(h_out[t], p["post_attn_norm"]["scale"])
        if dense:
            y = gated(n, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
        else:
            s = 1 / (1 + np.exp(-(n @ f(mlp["router"]))))
            chosen = np.argsort(-(s + f(mlp["router_bias"])))[:m["top_k"]]
            y = gated(n, *(mlp["shared"][k] for k in
                           ("w_gate", "w_up", "w_down")))
            for e in chosen:
                y = y + (s[e] / s[chosen].sum() * m["routed_scaling"]
                         * gated(n, mlp["w_gate"][e], mlp["w_up"][e],
                                 mlp["w_down"][e]))
        out[t] = h_out[t] + y
    return out


@pytest.mark.parametrize("dense", [True, False])
def test_a_layer_against_the_same_layer_written_by_hand(model, dense):
    cfg, params = model
    meta = reference.meta_of(cfg)
    stacked = params["lead_layers"] if dense else params["layers"][0]
    x = np.asarray(jax.random.normal(jax.random.key(3), (11, 64)))
    with jax.default_matmul_precision("highest"):
        got = reference._layer(stacked, jnp.int32(0), jnp.asarray(x),
                               dense=dense, meta=meta)
    one = jax.tree.map(lambda a: np.asarray(a[0]), stacked)
    want = by_hand(one, x.astype(np.float64), dict(meta), dense)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert np.abs(want - x).max() > 0.05      # the layer does something


def test_the_program_against_the_reference(model):
    """The uncached forward (the expanded form) at float32: the two were
    written apart and agree to rounding; the rotation conventions differ
    (the program leaves a pair where it lies, the reference moves the
    halves apart as the published forward does) and the scores do not."""
    cfg, params = model
    toks = np.asarray(jax.random.randint(jax.random.key(4), (48,), 1, 499))
    lp = jax.nn.log_softmax(jax.jit(
        lambda p, t: model_lib.forward(cfg, p, t))(
            params, jnp.asarray(toks[None, :-1]))[0, :, :cfg.vocab_size], -1)
    got = np.take_along_axis(np.asarray(lp), toks[1:, None], 1)[:, 0]
    want = reference.token_logprobs(params, toks, reference.meta_of(cfg))
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
