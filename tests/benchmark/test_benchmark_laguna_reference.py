"""``benchmarks/reference/laguna.py``: that it is its own (nothing of the
program imported), that its parts are ISSUE 58's equations (against
``numpy`` loops written here from them), and that its entry points agree
with each other."""

import ast
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as ref

RNG = np.random.default_rng(0)


def _r(*shape, scale=1.0):
    return (scale * RNG.standard_normal(shape)).astype(np.float32)


def test_the_reference_imports_nothing_of_the_program():
    text = Path(ref.__file__).read_text()
    names = set()
    for node in ast.walk(ast.parse(text)):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"__future__", "functools", "math", "jax"}
    assert "megatron_llm_tpu" not in text.replace(
        "``megatron_llm_tpu/models``", "")


def test_yarn_is_the_published_computation():
    """The full layers' frequencies over 64 rotated dimensions, from the
    paper's equations written out: dimension ``m`` turns ``original x
    theta^(-2m/64) / 2 pi`` times over the original length; it keeps its
    frequency at 64 turns or more, is divided by 64 at one or fewer, and
    is blended linearly between the two (truncated) dimensions."""
    rot, theta, factor, original = 64, 500000.0, 64.0, 4096
    got = np.asarray(ref.yarn_inv_freq(rot, theta, factor, original, 64.0,
                                       1.0))
    dim_of = lambda turns: rot * math.log(  # noqa: E731
        original / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low, high = math.floor(dim_of(64.0)), math.ceil(dim_of(1.0))
    assert (low, high) == (5, 16)
    for m in range(rot // 2):
        bare = theta ** (-2 * m / rot)
        ramp = min(max((m - low) / (high - low), 0.0), 1.0)
        want = bare / factor * ramp + bare * (1 - ramp)
        assert got[m] == pytest.approx(want, rel=1e-5), m
    assert 0.1 * math.log(64) + 1 == pytest.approx(1.4158883083359672)


def test_a_rotation_is_rotate_half_over_the_rotated_dimensions_alone():
    x = _r(5, 3, 8)
    inv_freq = jnp.asarray([0.5, 0.1])          # 4 rotated of 8
    got = np.asarray(ref.rotate(jnp.asarray(x), inv_freq, 1.5))
    for t in range(5):
        for m in range(2):
            c, s = (1.5 * f(t * float(inv_freq[m]))
                    for f in (math.cos, math.sin))
            np.testing.assert_allclose(
                got[t, :, m], x[t, :, m] * c - x[t, :, m + 2] * s, atol=1e-5)
            np.testing.assert_allclose(
                got[t, :, m + 2], x[t, :, m + 2] * c + x[t, :, m] * s,
                atol=1e-5)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])


META = dict(heads=4, window_heads=8, kv_heads=2, head_dim=8, window=3,
            theta=500000.0, rot=4, factor=64.0, original=16, beta_fast=64.0,
            beta_slow=1.0, attention_factor=1.4158883, window_theta=10000.0,
            window_rot=8)


@pytest.mark.parametrize("kind", ["full", "window"])
def test_attention_is_the_issues_equations(kind):
    """Grouped heads (query head ``j`` on KV head ``j // (H / 2)``), the
    kind's own head count, rotation and mask, one sigmoid gate a head."""
    t, h, d, G = 7, 16, 8, 2
    H = 8 if kind == "window" else 4
    p = {"wq": _r(h, H * d, scale=0.5), "wk": _r(h, G * d, scale=0.5),
         "wv": _r(h, G * d), "wo": _r(H * d, h), "wg": _r(h, H)}
    u = _r(t, h)
    got = np.asarray(ref.attention(
        {k: jnp.asarray(v) for k, v in p.items()}, jnp.asarray(u), META,
        kind))
    if kind == "window":
        inv_freq = [10000.0 ** (-2 * m / 8) for m in range(4)]
        scale, rot = 1.0, 8
    else:
        inv_freq = np.asarray(ref.yarn_inv_freq(4, 500000.0, 64.0, 16,
                                                64.0, 1.0)).tolist()
        scale, rot = 1.4158883, 4

    def rotated(x, pos):
        out = x.copy()
        for m in range(rot // 2):
            c, s = (scale * f(pos * inv_freq[m])
                    for f in (math.cos, math.sin))
            out[m] = x[m] * c - x[m + rot // 2] * s
            out[m + rot // 2] = x[m + rot // 2] * c + x[m] * s
        return out

    q = (u @ p["wq"]).reshape(t, H, d)
    k = (u @ p["wk"]).reshape(t, G, d)
    v = (u @ p["wv"]).reshape(t, G, d)
    gate = 1.0 / (1.0 + np.exp(-(u @ p["wg"])))
    want = np.zeros((t, H * d), np.float32)
    for i in range(t):
        seen = [s_ for s_ in range(i + 1)
                if kind == "full" or s_ > i - META["window"]]
        for j in range(H):
            g = j // (H // G)
            qi = rotated(q[i, j], i)
            sc = np.array([qi @ rotated(k[s_, g], s_) for s_ in seen]) \
                / math.sqrt(d)
            w = np.exp(sc - sc.max())
            a = (w / w.sum()) @ v[seen, g]
            want[i, j * d:(j + 1) * d] = gate[i, j] * a
    np.testing.assert_allclose(got, want @ p["wo"], atol=2e-4)


def test_the_router_chooses_by_the_bias_and_weighs_by_the_scores():
    t, h, E, f = 6, 16, 8, 4
    m = dict(top_k=2, routed_scaling=2.5, held=E, expert_offset=0)
    p = {"router": _r(h, E), "router_bias": _r(E, scale=0.5),
         "w_gate": _r(E, h, f), "w_up": _r(E, h, f), "w_down": _r(E, f, h),
         "shared": {"w_gate": _r(h, f), "w_up": _r(h, f),
                    "w_down": _r(f, h)}}
    x = _r(t, h)
    got = np.asarray(ref.moe(jax.tree.map(jnp.asarray, p), jnp.asarray(x),
                             m))
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    mlp = lambda a, g, u, dn: (silu(a @ g) * (a @ u)) @ dn  # noqa: E731
    for i in range(t):
        s = 1.0 / (1.0 + np.exp(-(x[i] @ p["router"])))
        chosen = np.argsort(-(s + p["router_bias"]))[:2]
        w = 2.5 * s[chosen] / s[chosen].sum()
        want = sum(w_e * mlp(x[i], p["w_gate"][e], p["w_up"][e],
                             p["w_down"][e])
                   for w_e, e in zip(w, chosen))
        want = want + mlp(x[i], *(p["shared"][k_] for k_ in
                                  ("w_gate", "w_up", "w_down")))
        np.testing.assert_allclose(got[i], want, atol=2e-4)


@pytest.fixture(scope="module")
def model():
    from megatron_llm_tpu.config import laguna_config
    from megatron_llm_tpu.models import model as model_lib

    cfg = laguna_config(
        hidden_size=32, num_attention_heads=4, window_attention_heads=8,
        num_kv_heads=2, kv_channels=8, ffn_hidden_size=16,
        moe_dense_ffn_size=48, moe_shared_expert_size=16, num_experts=8,
        moe_top_k=2, sliding_window=4, vocab_size=128,
        make_vocab_size_divisible_by=8, max_position_embeddings=64,
        rope_original_max_positions=16, moe_group_size=64,
        params_dtype="float32", num_layers=5)
    return cfg, jax.jit(lambda k: model_lib.init_params(k, cfg))(
        jax.random.key(0))


def test_every_row_goes_through_every_layer(model, monkeypatch):
    cfg, params = model
    meta = ref.meta_of(cfg)
    m = dict(meta)
    assert m["kinds"] == cfg.layer_kinds == (
        "full", "window", "window", "window", "full")
    assert (m["heads"], m["window_heads"], m["rot"], m["window_rot"]) == (
        4, 8, 4, 8)
    seen = []
    layer = ref._layer
    monkeypatch.setattr(ref, "_layer", lambda st, i, x, **kw: (
        seen.append((kw["kind"], kw["dense"], x.shape[0])),
        layer(st, i, x, **kw))[1])
    tokens = list(range(1, 15))
    logits = ref.logits_of(params, tokens, meta)
    assert logits.shape == (14, 128)
    assert [k for k, _d, _t in seen] == list(cfg.layer_kinds)
    assert [d for _k, d, _t in seen] == [True] + [False] * 4
    assert {t for _k, _d, t in seen} == {14}
    # causal: a position's logits do not move with what follows it
    again = ref.logits_of(params, tokens[:9] + [77] * 5, meta)
    np.testing.assert_allclose(logits[:9], again[:9], atol=1e-6)
    assert float(jnp.abs(logits[9:] - again[9:]).max()) > 1e-4


def test_the_entry_points_agree(model):
    cfg, params = model
    meta = ref.meta_of(cfg)
    seq = [5, 9, 2, 77, 31, 8, 100, 64, 1, 12, 3, 44, 90, 17]
    lp = ref.token_logprobs(params, seq, meta)
    logits = ref.logits_of(params, seq[:-1], meta)
    want = jax.nn.log_softmax(logits, axis=-1)[
        jnp.arange(len(seq) - 1), jnp.asarray(seq[1:])]
    np.testing.assert_allclose(lp, want, atol=2e-6)
    assert float(ref.loss(params, seq, meta)) == pytest.approx(
        -float(lp.mean()), rel=1e-6)
    # near ln(vocab) at a seeded start
    assert abs(float(ref.loss(params, seq, meta)) - math.log(128)) < 0.3
