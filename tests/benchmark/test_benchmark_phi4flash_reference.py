"""``benchmarks/reference/phi4flash.py``: that it is its own (nothing of
the program imported), that its parts are the issue's equations (against
``numpy`` loops written here from them), and that its three entry points
agree with each other."""

import ast
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4flash as ref

RNG = np.random.default_rng(0)


def _r(*shape, scale=1.0):
    return (scale * RNG.standard_normal(shape)).astype(np.float32)


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(Path(ref.__file__).read_text())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"__future__", "functools", "jax"}
    assert "megatron_llm_tpu" not in Path(ref.__file__).read_text().replace(
        "``megatron_llm_tpu/models``", "")


def test_mamba1_is_the_recurrence_a_position_at_a_time():
    t, h, di, n, r, taps = 11, 8, 12, 3, 2, 4
    p = dict(w_in=_r(h, 2 * di, scale=0.3), conv=_r(taps, di, scale=0.5),
             conv_bias=_r(di, scale=0.1), w_x=_r(di, r + 2 * n, scale=0.5),
             w_dt=_r(r, di, scale=0.5), dt_bias=_r(di, scale=0.3),
             A_log=np.log(np.tile(np.arange(1, n + 1, dtype=np.float32),
                                  (di, 1))) + _r(di, n, scale=0.1),
             D=_r(di), w_out=_r(di, h, scale=0.3))
    u = _r(t, h)
    with jax.default_matmul_precision("highest"):
        out, mem = ref.mamba1(jax.tree.map(jnp.asarray, p), jnp.asarray(u))
    silu = lambda a: a / (1 + np.exp(-a))           # noqa: E731
    xz = u @ p["w_in"]
    xs_in, z = xz[:, :di], xz[:, di:]
    state = np.zeros((di, n))
    want_y = np.zeros((t, di))
    for i in range(t):
        conv = sum(p["conv"][j] * (xs_in[i - (taps - 1 - j)]
                                   if i - (taps - 1 - j) >= 0 else 0.0)
                   for j in range(taps)) + p["conv_bias"]
        x = silu(conv)
        sel = x @ p["w_x"]
        dt = np.log1p(np.exp(sel[:r] @ p["w_dt"] + p["dt_bias"]))
        B, C = sel[r:r + n], sel[r + n:]
        A = -np.exp(p["A_log"])
        for c in range(di):
            state[c] = np.exp(dt[c] * A[c]) * state[c] + dt[c] * x[c] * B
            want_y[i, c] = state[c] @ C + p["D"][c] * x[c]
    np.testing.assert_allclose(mem, want_y, atol=2e-5)          # before
    np.testing.assert_allclose(out, (want_y * silu(z)) @ p["w_out"],
                               atol=2e-5)                       # the gate


@pytest.mark.parametrize("window", [0, 4])
def test_differential_attention_pairs_heads_as_the_issue_says(window):
    t, h, heads, kv, d, layer = 9, 16, 8, 4, 4, 5
    m = dict(heads=heads, kv_heads=kv, head_dim=d, eps=1e-5)
    p = dict(wq=_r(h, heads * d, scale=0.5), bq=_r(heads * d, scale=0.1),
             wo=_r(heads * d, h, scale=0.3), bo=_r(h, scale=0.1),
             lam=_r(4, d, scale=0.3),
             pair_norm={"scale": 1 + _r(2 * d, scale=0.1)})
    u, k, v = _r(t, h), _r(t, kv, d), _r(t, kv, d)
    with jax.default_matmul_precision("highest"):
        got = ref.diff_attention(jax.tree.map(jnp.asarray, p),
                                 jnp.asarray(u), jnp.asarray(k),
                                 jnp.asarray(v), jnp.float32(layer), m,
                                 window)
    q = (u @ p["wq"] + p["bq"]).reshape(t, heads, d)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (math.exp(p["lam"][0] @ p["lam"][1])
           - math.exp(p["lam"][2] @ p["lam"][3]) + lam0)
    outs = []
    for pair in range(heads // 2):
        g = pair // 2
        V = np.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], -1)
        A = []
        for j in range(2):
            rows = []
            for i in range(t):
                lo = max(0, i - (window - 1)) if window else 0
                s = q[i, 2 * pair + j] @ k[lo:i + 1, 2 * g + j].T / 2.0
                w = np.exp(s - s.max())
                rows.append((w / w.sum()) @ V[lo:i + 1])
            A.append(np.stack(rows))
        x = A[0] - lam * A[1]
        x = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)
        outs.append((1 - lam0) * x * p["pair_norm"]["scale"])
    want = np.concatenate(outs, -1) @ p["wo"] + p["bo"]
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.fixture(scope="module")
def model():
    from megatron_llm_tpu.config import phi4flash_config
    from megatron_llm_tpu.models import model as model_lib

    cfg = phi4flash_config(
        layer_runs=((("ssm1", "window"), 1), (("ssm1", "full"), 1),
                    (("gmu", "cross"), 2)),
        hidden_size=32, num_attention_heads=4, num_kv_heads=2,
        kv_channels=8, ffn_hidden_size=48, sliding_window=4,
        mamba1_inner=64, mamba1_state_size=4, mamba1_dt_rank=2,
        vocab_size=128, params_dtype="float32",
        make_vocab_size_divisible_by=8, max_position_embeddings=64)
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def test_every_row_goes_through_every_layer(model, monkeypatch):
    cfg, params = model
    meta = ref.meta_of(cfg)
    assert dict(meta)["runs"] == cfg.layer_runs
    seen = []
    layer = ref._layer
    monkeypatch.setattr(ref, "_layer", lambda st, i, n, x, *a, **kw: (
        seen.append((kw["kind"], int(n), x.shape[0])),
        layer(st, i, n, x, *a, **kw))[1])
    tokens = list(range(1, 15))
    logits = ref.logits_of(params, tokens, meta)
    assert logits.shape == (14, 128)
    assert [k for k, _n, _t in seen] == list(cfg.layer_kinds)
    assert [n for _k, n, _t in seen] == list(range(cfg.num_layers))
    assert {t for _k, _n, t in seen} == {14}     # no cut of rows
    # causal: a position's logits do not move with what follows it
    again = ref.logits_of(params, tokens[:9] + [77] * 5, meta)
    np.testing.assert_allclose(logits[:9], again[:9], atol=1e-6)
    assert float(jnp.abs(logits[9:] - again[9:]).max()) > 1e-4


def test_the_three_entry_points_agree(model):
    cfg, params = model
    meta = ref.meta_of(cfg)
    seqs = [list(range(3, 20)), [5, 9, 2, 77, 31, 8, 100, 64, 1, 12]]
    total = count = 0
    for seq in seqs:
        lp = ref.token_logprobs(params, seq, meta)
        logits = ref.logits_of(params, seq[:-1], meta)
        want = jax.nn.log_softmax(logits, axis=-1)[
            jnp.arange(len(seq) - 1), jnp.asarray(seq[1:])]
        np.testing.assert_allclose(lp, want, atol=2e-6)
        total, count = total - float(lp.sum()), count + len(seq) - 1
    assert ref.loss(params, seqs, meta) == pytest.approx(total / count,
                                                         rel=1e-6)
    # near ln(vocab) at a seeded start
    assert abs(total / count - math.log(128)) < 0.2
