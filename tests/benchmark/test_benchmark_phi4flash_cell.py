"""The cell ``phi4flash-serve-longgen``: its configuration file against
the published one (nothing cut), its operation and byte counts against a
hand count, its traffic under the ``serve_backlog`` rules, its reader's
arithmetic, and a rehearsal of the cell to its result line."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import flops_phi4flash as fp
from benchmarks import trace_reduce, traffic
from benchmarks.manifest import ROOT, Manifest
from benchmarks.readers import phi4flash_roofline

CELL, CONFIG = "phi4flash-serve-longgen", "phi-4-mini-flash-reasoning"
BIG = 3_000_000_019
DATA = Path(__file__).parent / "data"

# the catalog's ``config`` of microsoft/Phi-4-mini-flash-reasoning: every
# key that shapes the language model
PUBLISHED = dict(
    embd_pdrop=0, hidden_act="silu", hidden_size=2560,
    intermediate_size=10240, layer_norm_eps=1e-05,
    max_position_embeddings=262144, mb_per_layer=2, model_type="phi4flash",
    num_attention_heads=40, num_hidden_layers=32, num_key_value_heads=20,
    resid_pdrop=0, sliding_window=512, tie_word_embeddings=True,
    mlp_bias=False, lm_head_bias=False, vocab_size=200064)

METRICS = ("prefill_mfu", "prefill_tok_per_s", "decode_step_ms",
           "decode_hbm_share", "xattn_walk_hbm_share", "xattn_share",
           "mamba1_share", "swa_share", "gmu_share", "mlp_share",
           "kv_pool_peak_share", "device_idle_share", "ring_decode_roofline")


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def doc(man):
    return man.config(CONFIG)


def test_the_file_is_the_published_config_and_nothing_is_cut(man, doc):
    entry = next(c for c in man.doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == doc["reduced"] == []
    assert entry["source"] == doc["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
        "main/config.json")
    for key, want in PUBLISHED.items():
        assert doc[key] == want, key
    assert "published" not in doc and "by_kind" not in doc
    derived = doc["derived"]
    kinds = derived["layer_types"]
    # every second layer of the first decoder is Mamba (mb_per_layer 2),
    # layer 17 the one full-attention layer, then units and cross layers
    assert [k for k in kinds[:18:2]] == ["mamba"] * 9
    assert kinds[1:16:2] == ["window"] * 8 and kinds[17] == "full"
    assert kinds[18::2] == ["gmu"] * 7 and kinds[19::2] == ["cross"] * 7
    flat = [k for period, times in derived["layer_runs"]
            for k in period * times]
    assert [{"ssm1": "mamba"}.get(k, k) for k in flat] == kinds
    assert derived["num_kv_heads"] == doc["num_key_value_heads"]
    assert derived["head_dim"] * doc["num_attention_heads"] \
        == doc["hidden_size"]
    assert derived["ffn_hidden_size"] == doc["intermediate_size"]
    assert derived["mamba_d_inner"] == 2 * doc["hidden_size"]
    assert (derived["value_heads"] * derived["value_head_dim"]
            == derived["num_kv_heads"] * derived["head_dim"])
    for said in ("stands_for", "left_out", "assumed"):
        assert doc[said]
    assert "chips that share a layer: 1" in doc["stands_for"]
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank", "mamba_conv_bias", "positions",
                "differential_attention", "attention_biases", "window",
                "memory_and_pool", "A_log", "D", "dt_bias", "W_dt",
                "lambda_vectors", "norm_weights", "weights", "mamba_state",
                "residual_stream"):
        assert doc["assumed"][key], key
    engine = doc["serve"]["engine"]
    assert set(doc["serve"]["engine_why"]) == set(engine)
    assert engine["prefix_cache_blocks"] == 0
    assert engine["max_batch_size"] % 8 == 0
    assert 64 <= engine["max_batch_size"] <= 96
    assert "TO BE FILLED" not in json.dumps(doc)
    # the rehearsal holds a period of each of the three runs
    tiny = doc["rehearsal"]["model"]
    assert tiny["layer_runs"] == [[["ssm1", "window"], 1],
                                  [["ssm1", "full"], 1],
                                  [["gmu", "cross"], 1]]
    assert tiny["sliding_window"] == 8


def test_the_program_preset_has_the_files_sizes(doc):
    from megatron_llm_tpu import config as config_lib
    from megatron_llm_tpu.config import phi4flash_config

    cfg = phi4flash_config(doc["preset"]["size"],
                           num_layers=doc["num_hidden_layers"])
    assert config_lib.get_preset(CONFIG) == cfg
    assert [list(p) for p, _n in cfg.layer_runs] == [
        p for p, _n in doc["derived"]["layer_runs"]]
    assert [{"ssm1": "mamba"}.get(k, k) for k in cfg.layer_kinds] \
        == doc["derived"]["layer_types"]
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.ffn_size, cfg.num_layers,
            cfg.sliding_window, cfg.norm_eps) == (
        doc["hidden_size"], doc["num_attention_heads"],
        doc["num_key_value_heads"], doc["derived"]["head_dim"],
        doc["vocab_size"], doc["intermediate_size"],
        doc["num_hidden_layers"], doc["sliding_window"],
        doc["layer_norm_eps"])
    s = fp.sizes_of(doc)
    assert (cfg.mamba1_inner, cfg.mamba1_state_size, cfg.mamba1_conv_kernel,
            cfg.mamba1_dt_rank) == (s["inner"], s["state"], s["conv_taps"],
                                    s["dt_rank"]) == (5120, 16, 4, 160)
    assert (cfg.v_heads, cfg.v_head_width) == (
        doc["derived"]["value_heads"], doc["derived"]["value_head_dim"])
    assert cfg.tie_embed_logits and cfg.num_experts == 0
    assert cfg.position_embedding_type == "none" and cfg.is_glu
    assert cfg.norm_type == "layernorm" and cfg.diff_attention
    # ONE layer keeps keys and values; eight read them
    assert (cfg.kv_layers, cfg.cross_layers, cfg.mamba1_layers,
            cfg.window_layers, cfg.mamba_layers, cfg.linear_layers) == (
        1, 7, 9, 8, 0, 0)
    assert cfg.row_cut_layer == 17
    with pytest.raises(ValueError, match="three runs"):
        phi4flash_config(doc["preset"]["size"], num_layers=12)


def test_the_programs_tree_holds_the_hand_counted_parameters(doc):
    """The builder's recount: the preset's parameter tree, leaf by leaf,
    against ``flops_phi4flash.py`` and the issue's sum."""
    import jax

    from megatron_llm_tpu.config import phi4flash_config
    from megatron_llm_tpu.models import model as model_lib

    cfg = phi4flash_config(doc["preset"]["size"])
    tree = jax.eval_shape(lambda k: model_lib.init_params(k, cfg),
                          jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(tree)) == 3_852_562_944 \
        == fp.param_count(fp.sizes_of(doc))
    rec = jax.eval_shape(lambda: model_lib.init_rec_state(cfg, 1))
    state = sum(rec[n].size * rec[n].dtype.itemsize
                for n in ("ssm1", "ssm1_conv"))
    ring = sum(rec[n].size * rec[n].dtype.itemsize
               for n in ("win_k", "win_v"))
    assert (state, ring) == (3_502_080, 20_971_520)       # 24.5 MB a slot
    pool = jax.eval_shape(lambda: model_lib.init_kv_pool(cfg, 2, 128))
    assert sum(a.size * a.dtype.itemsize for a in pool) == 2 * 128 * 5120
    assert [a.shape for a in pool] == [(1, 2, 20, 128, 64),
                                       (1, 2, 10, 128, 128)]


def test_weights_operations_and_bytes_against_a_hand_count(doc):
    s = fp.sizes_of(doc)
    assert (s["mamba_layers"], s["window_layers"], s["full_layers"],
            s["gmu_layers"], s["cross_layers"], s["layers"],
            s["boundary"]) == (9, 8, 1, 7, 7, 32, 17)
    p = fp.layer_params(s)
    # by hand (ISSUE 56).  Mamba-1: in 2560 x 10240, conv 4 x 5120 and
    # its bias, x_proj 5120 x 192, dt_proj 160 x 5120 and its bias, A_log
    # 5120 x 16, D, out 5120 x 2560
    assert p["mamba"] == (26_214_400 + 20_480 + 5120 + 983_040 + 819_200
                          + 5120 + 81_920 + 5120 + 13_107_200) == 41_241_600
    # self-attention: q and o 2560 x 2560, k and v 2560 x 1280, their
    # biases, four lambda vectors of 64, the pair norm's 128
    assert p["window"] == p["full"] == (
        2 * 6_553_600 + 2 * 3_276_800 + 2560 + 1280 + 1280 + 2560 + 256
        + 128) == 19_668_864
    # cross-attention: q and o alone
    assert p["cross"] == 2 * 6_553_600 + 2 * 2560 + 256 + 128 == 13_112_704
    assert p["gmu"] == 2 * 2560 * 5120 == 26_214_400
    m = fp.matmul_params(s)
    assert m["mlp"] == 3 * 2560 * 10240 == 78_643_200
    params = (9 * 41_241_600 + 9 * 19_668_864 + 7 * 13_112_704
              + 7 * 26_214_400 + 32 * (78_643_200 + 4 * 2560) + 2 * 2560
              + 200_064 * 2560)
    assert fp.param_count(s) == params == 3_852_562_944
    assert fp.weight_bytes(s) == 7_705_125_888                  # 7.71 GB
    assert 0.65 < 32 * m["mlp"] / params < 0.66
    # a slot: 9 x (5120 x 16 + 3 x 5120) float32, 8 x 512 x 5120 B
    assert fp.state_bytes_per_slot(s) == 9 * 4 * 5120 * 19 == 3_502_080
    assert fp.ring_bytes_per_slot(s) == 8 * 512 * 5120 == 20_971_520
    # a cached position: ONE layer, 20 x 64 keys + 10 x 128 values, bf16;
    # sixteen attention layers with K/V of their own would keep 81 920 B
    assert fp.kv_bytes_per_position(s) == 5120
    assert 16 * fp.kv_bytes_per_position(s) == 81_920
    # 64 slots: 7.71 + 1.57 + 2.68 GB of arrays
    arrays = (fp.weight_bytes(s) + 64 * (3_502_080 + 20_971_520)
              + 64 * 8192 * 5120)
    assert round(64 * 24_473_600 / 1e9, 2) == 1.57
    assert round(64 * 8192 * 5120 / 1e9, 2) == 2.68
    assert round(arrays / 1e9, 1) == 12.0
    # a step of 64 slots at 3.5 k positions each: the eight walks read
    # more than the weights
    positions = 64 * 3500
    assert fp.walk_bytes(s, positions) == positions * 5120 * 8
    assert fp.walk_bytes(s, positions) > fp.weight_bytes(s)
    assert fp.state_bytes(s, 64) == 2 * 64 * 3_502_080
    step = fp.decode_step_bytes(s, 64, positions)
    assert step == (7_705_125_888 + 2 * 64 * 3_502_080
                    + 64 * 20_971_520 + positions * 5120 * 8)
    # a young step: 10 positions a slot hold 10 ring rows a slot
    assert fp.decode_step_bytes(s, 64, 640) == (
        7_705_125_888 + 2 * 64 * 3_502_080 + 640 * 8 * 5120
        + 640 * 5120 * 8)
    assert 22e-3 < step / 819e9 < 24e-3
    # one timed prefill of 2560 positions
    assert fp.band_keys(2560, 512) == 512 * 513 / 2 + 2048 * 512
    assert fp.band_keys(100, 512) == 100 * 101 / 2
    assert fp.attention_flops_per_key(s) == 2 * 40 * (64 + 128)
    mamba_f = 2 * (26_214_400 + 983_040 + 819_200 + 13_107_200) \
        + 2 * 4 * 5120 + 9 * 5120 * 16
    window_f = 2 * 19_660_800
    every = 2560 * (9 * mamba_f + 8 * window_f + 17 * 2 * 78_643_200
                    + 2 * 2 * 3_276_800) \
        + 15_360 * 8 * fp.band_keys(2560, 512)
    one = (8 * 2 * 2 * 6_553_600 + 7 * 2 * 26_214_400
           + 15 * 2 * 78_643_200 + 2 * 2560 * 200_064
           + 15_360 * 8 * 2560)
    assert fp.prefill_flops(s, [2560]) == every + one
    assert 3.7e9 < every / 2560 < 3.85e9                      # a token
    # every row through the second decoder too would be about twice it
    whole = every + 2560 * (one - 2 * 2560 * 200_064)
    assert 1.8 < whole / every < 2.1
    assert fp.prefill_flops(s, [1000, 1560]) < fp.prefill_flops(s, [2560]) \
        + one


def test_the_mix_under_the_backlog_rules(man, doc):
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longgen", 1)
    assert CELL in next(m for m in man.doc["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    mix = man.traffic("longgen")
    assert mix["kind"] == "serve_backlog" and mix["schedule_seed"] == 23
    assert mix["requests"] == 600 and mix["warmup_output_tokens"] == 4
    assert mix["prompt_tokens"] == dict(dist="uniform", min=1024, max=4096)
    assert mix["output_tokens"] == dict(dist="uniform", min=1024, max=4096)
    reqs = traffic.serve_requests(mix, BIG, 51.0, doc["vocab_size"])
    assert len(reqs) == 600 and {r.due_s for r in reqs} == {0.0}
    lengths = [len(r.prompt) for r in reqs]
    outs = [r.max_new_tokens for r in reqs]
    assert 1024 <= min(lengths) < 1030 and 4096 >= max(lengths) > 4090
    assert 1024 <= min(outs) < 1030 and 4096 >= max(outs) > 4090
    assert 2540 < sum(lengths) / 600 < 2580
    # about a sixth of the outputs end within ~1500 steps
    assert 0.14 < sum(o < 1500 for o in outs) / 600 < 0.17
    assert all(0 < t < doc["vocab_size"] - 1 for t in reqs[0].prompt)
    again = traffic.serve_requests(mix, BIG + 1, 51.0, doc["vocab_size"])
    assert [len(r.prompt) for r in again] == lengths
    assert again[0].prompt != reqs[0].prompt
    # the engine holds the longest request, queues the whole backlog and
    # compiles few prefill shapes, each whole windows; the check
    # sequences pad to one of them, end inside a bucket, and decode past
    # the window: the rings wrap
    engine = doc["serve"]["engine"]
    assert engine["max_seq_len"] == 4096 + 4096
    assert engine["max_queue_size"] > mix["requests"]
    bucket = engine["prefill_bucket"]
    assert bucket % doc["sliding_window"] == 0
    shapes = {-(-n // bucket) * bucket for n in lengths}
    assert len(shapes) <= 7 and shapes <= set(range(1024, 4097, 512))
    check = mix["check"]
    assert (check["sequences"], check["prompt_tokens"],
            check["output_tokens"]) == (3, 1500, 32)
    assert check["prompt_tokens"] % bucket
    assert -(-check["prompt_tokens"] // bucket) * bucket in shapes
    assert check["prompt_tokens"] > 2 * doc["sliding_window"]
    assert engine["kv_block_size"] == 128
    # the spans of a window (a decode span a token) fit the recorder
    assert engine["trace_capacity"] >= 400_000


def test_every_metric_of_the_cell_moves_its_throughput(man):
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert names == {f"{n}.longgen" for n in METRICS}
    for m in man.metrics_of(CELL, "per_layer"):
        assert m["moves"] == "serve_tokens_per_s"
        assert m["workloads"] == [CELL]
        spec = man.layer_metric(m["name"])
        assert spec["what"] and "stub" not in spec["what"]
        for key in ("layer", "unit", "better", "source", "moves",
                    "workloads"):
            assert spec[key] == m[key], (m["name"], key)
        # the harness's own and the other hybrids' counts are not this
        # model's: no reader is pointed at them
        assert spec["reader"] in ("xplane", "xplane_scope", "engine_gauges",
                                  "phi4flash_roofline")
        assert "decode_step_bytes" not in json.dumps(spec["params"])
    assert {m["name"] for m in man.metrics_of(CELL, "end_to_end")} == {
        "serve_tokens_per_s", "setup_s"}
    # no cell the benchmark had reports a metric of this one
    for w in man.doc["workloads"]:
        if w["name"] != CELL:
            assert not names & {m["name"] for m in
                                man.metrics_of(w["name"], "per_layer")}
    # the manifest holds this PR's entries (by membership: a later PR
    # appends behind them)
    assert CONFIG in {c["name"] for c in man.doc["configs"]}
    assert CELL in {w["name"] for w in man.doc["workloads"]}
    assert names <= {m["name"] for m in man.doc["per_layer"]}
    # every scope a metric reads is one the program names
    from megatron_llm_tpu.obs.profile import DEVICE_SCOPES

    for name in names:
        for scope in man.layer_metric(name)["params"].get("scopes", ()):
            assert scope in DEVICE_SCOPES, (name, scope)


# --- the reader -------------------------------------------------------------

SPANS = (
    [("prefill", 1.0 + i, 0.1, {"prompt_len": n, "cached_tokens": 0,
                                "state_kinds": "ssm1+window",
                                "cross_rows": 1})
     for i, n in enumerate((1300, 2900))]
    # the check's log-prob pass ran every row through both decoders: not
    # the timed program, not counted
    + [("prefill", 1.5, 0.1, {"prompt_len": 1500, "cached_tokens": 0,
                              "state_kinds": "ssm1+window",
                              "cross_rows": 1536})]
    # three steps; a step's spans share a start; 3, 2 and 2 live slots
    + [("decode", 2.0, 0.02, {"slot": s, "live": 3, "live_positions": 9000,
                              "state_kinds": "ssm1+window"})
       for s in range(3)]
    + [("decode", 2.1, 0.02, {"slot": s, "live": 2, "live_positions": 6000,
                              "state_kinds": "ssm1+window"})
       for s in range(2)]
    + [("decode", 2.2, 0.02, {"slot": s, "live": 2, "live_positions": 6002,
                              "state_kinds": "ssm1+window"})
       for s in range(2)]
    + [("engine_step", 2.0, 0.1, {"batch": 3})])


@pytest.fixture(scope="module")
def evidence(man, doc, tmp_path_factory):
    """The synthetic trace of ``test_benchmark_trace2.py``: two runs of
    ``jit_step``, in each 60 us under ``attention/flash_fwd``; the engine's
    spans as the reader finds them in a session's recorders."""
    from jax.profiler import ProfileData

    text = (DATA / "synthetic_xplane_scopes.txt").read_text()
    d = tmp_path_factory.mktemp("trace")
    run = d / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    ctx = SimpleNamespace(trace_dir=str(d), config=doc, rehearsal=False,
                          mix=man.traffic("longgen"),
                          device={"kind": "TPU v5 lite"})
    return {"ctx": ctx, "trace": trace_reduce.load(text_proto=text),
            "trace_window": (100e3, 900e3), "nemotron_spans": list(SPANS),
            "gauges": {"blocks_used": [10, 30]}}


def test_a_share_is_counted_work_over_device_time_over_the_peak(evidence):
    s = fp.sizes_of(evidence["ctx"].config)
    read = phi4flash_roofline.read
    per = trace_reduce.module_seconds(evidence["trace"],
                                      evidence["trace_window"])
    runs, secs = per["jit_step"]
    got = read(dict(evidence), {"work": "prefill", "module": "jit_step"})
    assert got == pytest.approx(
        100 * fp.prefill_flops(s, [1300, 2900]) / secs / 197e12)
    got = read(dict(evidence), {"work": "decode_ms", "module": "jit_step"})
    assert got == pytest.approx(1e3 * secs / runs)
    # a step's least bytes at the steps' mean of 7/3 live slots and 7000.67
    # cached positions
    got = read(dict(evidence), {"work": "decode_bytes",
                                "module": "jit_step"})
    assert got == pytest.approx(
        100 * fp.decode_step_bytes(s, 7 / 3, 21_002 / 3) / (secs / runs)
        / 819e9)
    # the three steps' walks over the kernel's own time
    got = read(dict(evidence), {"work": "walk_bytes",
                                "scopes": ["flash_fwd"]})
    assert got == pytest.approx(
        100 * 21_002 * 5120 * 8 / 120e-6 / 819e9)
    # the three steps' ring reads (every slot past the window: 3 + 2 + 2
    # slots' rings of 8 x 512 rows of 5120 B) over a kernel's own time
    got = read(dict(evidence), {"work": "ring_bytes",
                                "scopes": ["flash_fwd"]})
    assert fp.ring_read_bytes(s, 2, 6000) == 2 * 20_971_520
    assert fp.ring_read_bytes(s, 2, 700) == 700 * 8 * 5120
    assert got == pytest.approx(100 * 7 * 20_971_520 / 120e-6 / 819e9)
    with pytest.raises(ValueError, match="unknown work"):
        read(dict(evidence), {"work": "else", "module": "jit_step"})


@pytest.mark.parametrize("change", [
    {"nemotron_spans": None}, {"trace": None}, {"trace_window": None},
    "rehearsal", "another_config", "absent_scope", "no_prefill"])
def test_with_nothing_to_read_the_reader_says_none(evidence, man, change):
    ev, params = dict(evidence), {"work": "walk_bytes",
                                  "scopes": ["flash_fwd"]}
    if change == "rehearsal":
        ev["ctx"] = SimpleNamespace(**{**vars(ev["ctx"]), "rehearsal": True})
    elif change == "another_config":
        ev["ctx"] = SimpleNamespace(**{
            **vars(ev["ctx"]), "config": man.config("granite-4.0-h-micro")})
    elif change == "absent_scope":
        # (a program from before the ring's kernel reads so too)
        params = {"work": "ring_bytes", "scopes": ["ring_decode"]}
    elif change == "no_prefill":
        ev["nemotron_spans"] = [sp for sp in SPANS if sp[0] != "prefill"]
        params = {"work": "prefill", "module": "jit_step"}
    else:
        ev.update(change)
    assert phi4flash_roofline.read(ev, params) is None


def test_a_window_without_a_step_reads_the_session_and_else_nothing(
        evidence, monkeypatch):
    """The decode works: over the traced window where it holds a step,
    else over the whole session, else left out."""
    asked = []

    def spans_of(ev):
        asked.append(ev["trace_window"])
        if ev["trace_window"] == evidence["trace_window"]:
            return [sp for sp in SPANS if sp[0] != "decode"]
        return list(SPANS) if len(asked) < 3 else []

    monkeypatch.setattr(phi4flash_roofline, "traced_spans", spans_of)
    ev = {k: v for k, v in evidence.items() if k != "nemotron_spans"}
    params = {"work": "decode_ms", "module": "jit_step"}
    whole = trace_reduce.window_of(evidence["trace"])
    got = phi4flash_roofline.read(dict(ev), params)
    assert asked == [evidence["trace_window"], whole]
    per = trace_reduce.module_seconds(evidence["trace"], whole)
    runs, secs = per["jit_step"]
    assert got == pytest.approx(1e3 * secs / runs)
    assert phi4flash_roofline.read(dict(ev), params) is None


def test_a_program_without_the_sessions_recorders_gives_no_share(
        evidence, monkeypatch):
    """A program whose profile session keeps no recorders (the parent's,
    for a cell it cannot run anyway): no span, no share, no error."""
    from megatron_llm_tpu.obs import profile

    ev = {k: v for k, v in evidence.items() if k != "nemotron_spans"}
    monkeypatch.setattr(profile, "last", lambda: SimpleNamespace(
        t_sync=0.0, t_stop=1.0))
    for params in ({"work": "prefill", "module": "jit_step"},
                   {"work": "decode_bytes", "module": "jit_step"}):
        assert phi4flash_roofline.read(dict(ev), params) is None


# --- the rehearsal ----------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 2])
def test_the_cell_rehearses_to_its_result_line(man, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         str(BIG), "--seconds", "0.3", "--trace", str(trace),
         "--cpu-rehearsal"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=280)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    assert [l for l in lines if l.startswith("{")] == lines[-1:]
    line = json.loads(lines[-1])
    assert line["correct"], proc.stdout[-3000:]
    assert set(line["compared"]) == {
        "logprob_max_gap", "logprob_mean_gap",
        "check_sequences_cut_or_not_finite", "compiles_in_window",
        "bad_finishes", "backlog_ran_out"}
    assert line["compared"]["logprob_max_gap"]["limit"] == 0.15
    assert line["compared"]["logprob_mean_gap"]["limit"] == 0.03
    # on the CPU no device metric is read: the line holds the two
    # end-to-end metrics, traced or not (the pool's peak share is a gauge
    # of the program's, read anywhere)
    assert {"serve_tokens_per_s", "setup_s"} <= set(line["metrics"]) <= {
        "serve_tokens_per_s", "setup_s", "kv_pool_peak_share.longgen"}
    assert "6 layers" in proc.stdout and "vocab 512" in proc.stdout
    if trace == 2:
        assert "traced window: 4 prefills" in proc.stdout
