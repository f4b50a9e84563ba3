"""The cell ``granite4hmicro-serve-shortchat``: its configuration file
against the published one (nothing cut), its operation and byte counts
against a hand count, its traffic under the ``serve_backlog`` rules, its
reader's arithmetic, and a rehearsal of the cell to its result line."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import flops_granite_hybrid as fg
from benchmarks import trace_reduce, traffic
from benchmarks.manifest import ROOT, Manifest
from benchmarks.readers import granite_hybrid_roofline

CELL, CONFIG = "granite4hmicro-serve-shortchat", "granite-4.0-h-micro"
BIG = 3_000_000_019
DATA = Path(__file__).parent / "data"

# config.json of ibm-granite/granite-4.0-h-micro, every key that shapes
# the language model
PUBLISHED = dict(
    attention_bias=False, attention_multiplier=0.015625,
    embedding_multiplier=12, hidden_act="silu", hidden_size=2048,
    intermediate_size=8192,
    layer_types=(["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    logits_scaling=8, mamba_chunk_size=256, mamba_conv_bias=True,
    mamba_d_conv=4, mamba_d_head=64, mamba_d_state=128, mamba_expand=2,
    mamba_n_groups=1, mamba_n_heads=64, mamba_proj_bias=False,
    max_position_embeddings=131072, model_type="granitemoehybrid",
    normalization_function="rmsnorm", num_attention_heads=32,
    num_experts_per_tok=0, num_hidden_layers=40, num_key_value_heads=8,
    num_local_experts=0, position_embedding_type="nope",
    residual_multiplier=0.22, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=10000, shared_intermediate_size=8192,
    tie_word_embeddings=True, vocab_size=100352)


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
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
        "config.json")
    for key, want in PUBLISHED.items():
        assert doc[key] == want, key
    assert "published" not in doc and "by_kind" not in doc
    assert [i for i, k in enumerate(doc["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    derived = doc["derived"]
    kinds = {"full": "*", "ssm": "M"}
    period = "".join(kinds[k] for k in derived["layer_pattern"])
    assert period == derived["period"] == "MMMMM*MMMM"
    assert [{"M": "mamba", "*": "attention"}[c] for c in period * 4] \
        == doc["layer_types"]
    assert derived["num_kv_heads"] == doc["num_key_value_heads"]
    assert derived["head_dim"] * doc["num_attention_heads"] \
        == doc["hidden_size"]
    assert derived["ffn_hidden_size"] == doc["shared_intermediate_size"]
    assert derived["mamba_d_inner"] == 64 * 64 \
        == doc["mamba_expand"] * doc["hidden_size"]
    assert derived["mamba_conv_channels"] == 4096 + 2 * 1 * 128
    for said in ("stands_for", "left_out", "assumed"):
        assert doc[said]
    for key in ("A_log", "dt_bias", "D", "conv1d", "time_step_limit",
                "weights", "mamba_state", "residual_stream"):
        assert doc["assumed"][key], key
    engine = doc["serve"]["engine"]
    assert set(doc["serve"]["engine_why"]) == set(engine)
    assert engine["prefix_cache_blocks"] == 0
    assert engine["max_batch_size"] % 8 == 0
    assert 56 <= engine["max_batch_size"] <= 72
    # the rehearsal holds every mechanism in its two layers
    tiny = doc["rehearsal"]["model"]
    assert tiny["layer_pattern"] == ["ssm", "full"]
    assert tiny["mamba_n_groups"] == 1
    assert (tiny["embedding_multiplier"] != 1
            and tiny["residual_multiplier"] != 1
            and tiny["logits_scaling"] != 1
            and tiny["attention_multiplier"] != tiny["kv_channels"] ** -0.5)


def test_the_program_preset_has_the_files_sizes(doc):
    from megatron_llm_tpu.config import granite_hybrid_config

    cfg = granite_hybrid_config(doc["preset"]["size"],
                                num_layers=doc["num_hidden_layers"])
    assert list(cfg.layer_pattern) == doc["derived"]["layer_pattern"]
    assert [{"ssm": "mamba", "full": "attention"}[k]
            for k in cfg.layer_kinds] == doc["layer_types"]
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.ffn_size, cfg.num_layers) == (
        doc["hidden_size"], doc["num_attention_heads"],
        doc["num_key_value_heads"], doc["derived"]["head_dim"],
        doc["vocab_size"], doc["shared_intermediate_size"],
        doc["num_hidden_layers"])
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.mamba_state_size, cfg.mamba_conv_kernel,
            cfg.mamba_chunk_size, cfg.norm_eps) == (
        doc["mamba_n_heads"], doc["mamba_d_head"], doc["mamba_n_groups"],
        doc["mamba_d_state"], doc["mamba_d_conv"], doc["mamba_chunk_size"],
        doc["rms_norm_eps"])
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
        doc["embedding_multiplier"], doc["residual_multiplier"],
        doc["attention_multiplier"], doc["logits_scaling"])
    assert cfg.tie_embed_logits and cfg.num_experts == 0
    assert cfg.position_embedding_type == "none" and cfg.is_glu
    assert (cfg.mamba_layers, cfg.kv_layers, cfg.linear_layers) == (36, 4, 0)
    assert cfg.mamba_inner == doc["derived"]["mamba_d_inner"]
    assert cfg.mamba_conv_channels == doc["derived"]["mamba_conv_channels"]


def test_weights_operations_and_bytes_against_a_hand_count(doc):
    s = fg.sizes_of(doc)
    assert (s["attention_layers"], s["mamba_layers"], s["layers"]) == (
        4, 36, 40)
    assert fg.sizes_of(doc, 10)["mamba_layers"] == 9
    with pytest.raises(ValueError, match="whole periods"):
        fg.sizes_of(doc, 12)
    p = fg.layer_params(s)
    # by hand (ISSUE 49).  Mamba-2: in 2048 x 8512 (4096 | 4352 | 64),
    # conv 4 x 4352 and its bias, A_log dt_bias D, the gated norm, out
    # 4096 x 2048, its RMSNorm
    assert p["mamba"] == (17_432_576 + 21_760 + 192 + 4096 + 8_388_608
                          + 2048) == 25_849_280
    # attention: q and o 2048 x 2048, k and v 2048 x 512, its RMSNorm
    assert p["attention"] == 2 * 4_194_304 + 2 * 1_048_576 + 2048 \
        == 10_487_808
    # the gated MLP: 2048 x 16384 in, 8192 x 2048 out, its RMSNorm
    assert p["mlp"] == 33_554_432 + 16_777_216 + 2048 == 50_333_696
    params = (36 * (25_849_280 + 50_333_696) + 4 * (10_487_808 + 50_333_696)
              + 2048 + 100_352 * 2048)
    assert fg.param_count(s) == params == 3_191_396_096
    assert fg.weight_bytes(s) == 6_382_792_192                   # 6.38 GB
    assert fg.layer_params(s)["mlp"] * 40 / params > 0.63
    # a slot's state: 64 heads x 64 x 128 and a tail of 3 x 4352, float32
    assert fg.state_bytes_per_slot(s) == 2_097_152 + 52_224
    assert 36 * fg.state_bytes_per_slot(s) == 77_377_536         # 77.4 MB
    assert fg.kv_bytes_per_position(s) == 8192                   # 8 KiB
    # 64 slots x 3072 positions: 6.38 + 4.95 + 1.61 = 12.9 GB of arrays
    arrays = (fg.weight_bytes(s) + 64 * 77_377_536
              + 64 * 3072 * fg.kv_bytes_per_position(s))
    assert round(64 * 77_377_536 / 1e9, 2) == 4.95
    assert round(64 * 3072 * 8192 / 1e9, 2) == 1.61
    assert round(arrays / 1e9, 1) == 12.9
    # a 64-slot step: the weights once + the states read and written.
    # The issue's 16.0 GB counts the states alone (2 x 64 x 36 x 2 MiB =
    # 9.66 GB); with the tails, which the step moves too, 9.90 and 16.3
    assert fg.mamba_step_bytes(s, 64) == 2 * 64 * 77_377_536
    assert round(2 * 64 * 36 * 2_097_152 / 1e9, 2) == 9.66
    step = fg.decode_step_bytes(s, 64, 0)
    assert step == 6_382_792_192 + 9_904_324_608
    assert round((step - 2 * 64 * 36 * 52_224) / 1e9, 1) == 16.0
    assert 19.5e-3 < step / 819e9 < 20e-3          # >= 19.9 ms a step
    assert fg.decode_step_bytes(s, 64, 64 * 1000) == step + 64_000 * 8192
    # the state step is 60 % of a step's bytes
    assert 0.6 < fg.mamba_step_bytes(s, 64) / step < 0.62
    # a chunk of 256 positions: C B^T the one group and its product with
    # dt x a head, causal halves; the chunk's state and C S_prev a head
    chunk = (1 * 256 * 256 * 128 + 64 * 256 * 256 * 64
             + 4 * 64 * 256 * 64 * 128)
    assert fg.ssd_flops_per_token(s) == chunk / 256 == 3_178_496
    # one prompt of 768 positions: a position attends 384 on average
    m = fg.matmul_params(s)
    assert m == {"mamba": 17_432_576 + 8_388_608,
                 "attention": 10_485_760, "mlp": 50_331_648}
    mamba_f = 2 * 25_821_184 + 2 * 4 * 4352 + 3_178_496
    attn_f = 2 * 10_485_760 + 2 * 32 * 64 * 768
    want = 768 * (36 * mamba_f + 4 * attn_f + 40 * 2 * 50_331_648) \
        + 2 * 2048 * 100_352
    assert fg.prefill_flops(s, 768, 1, 768) == want
    assert 6.0e9 < (want - 2 * 2048 * 100_352) / 768 < 6.2e9   # a token


def test_the_mix_under_the_backlog_rules(man, doc):
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "shortchat", 1)
    assert next(m for m in man.doc["end_to_end"]
                if m["name"] == "serve_tokens_per_s")["workloads"][-1] == CELL
    mix = man.traffic("shortchat")
    assert mix["kind"] == "serve_backlog" and mix["schedule_seed"] == 23
    assert mix["requests"] == 1200 and mix["warmup_output_tokens"] == 4
    assert mix["prompt_tokens"] == dict(dist="lognormal", median=384,
                                        sigma=0.8, min=64, max=2048)
    assert mix["output_tokens"] == dict(dist="lognormal", median=256,
                                        sigma=0.6, min=32, max=1024)
    reqs = traffic.serve_requests(mix, BIG, 51.0, doc["vocab_size"])
    assert len(reqs) == 1200 and {r.due_s for r in reqs} == {0.0}
    lengths = [len(r.prompt) for r in reqs]
    outs = [r.max_new_tokens for r in reqs]
    assert min(lengths) == 64 and max(lengths) == 2048
    assert 32 <= min(outs) < 40 and 1024 >= max(outs) > 1000
    # short requests: a mean of ~530 + ~300 tokens
    assert 500 < sum(lengths) / 1200 < 560
    assert 290 < sum(outs) / 1200 < 320
    assert all(0 < t < doc["vocab_size"] - 1 for t in reqs[0].prompt)
    again = traffic.serve_requests(mix, BIG + 1, 51.0, doc["vocab_size"])
    assert [len(r.prompt) for r in again] == lengths
    assert again[0].prompt != reqs[0].prompt
    # the engine holds the longest request, queues the whole backlog and
    # compiles eight prefill shapes, each whole 256-position chunks; the
    # check sequences end inside a chunk and inside a bucket
    engine = doc["serve"]["engine"]
    assert engine["max_seq_len"] == 2048 + 1024
    assert engine["max_queue_size"] > mix["requests"]
    bucket = engine["prefill_bucket"]
    assert bucket % doc["mamba_chunk_size"] == 0
    assert len({-(-n // bucket) for n in lengths}) == 8
    check = mix["check"]
    assert (check["sequences"], check["prompt_tokens"],
            check["output_tokens"]) == (3, 700, 32)
    assert check["prompt_tokens"] % doc["mamba_chunk_size"]
    assert check["prompt_tokens"] % bucket
    assert -(-check["prompt_tokens"] // bucket) * bucket <= 2048
    # the spans of a window (a decode span a token) fit the recorder
    assert engine["trace_capacity"] >= 400_000


def test_every_metric_of_the_cell_moves_its_throughput(man):
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert names == {
        f"{n}.shortchat" for n in (
            "device_idle_share", "prefill_tok_per_s", "prefill_mfu",
            "decode_step_ms", "decode_hbm_share", "mamba_share",
            "mamba_step_hbm_share", "ssd_scan_roofline", "mlp_share")}
    for m in man.metrics_of(CELL, "per_layer"):
        assert m["moves"] == "serve_tokens_per_s"
        assert m["workloads"] == [CELL]
        spec = man.layer_metric(m["name"])
        assert spec["what"] and "stub" not in spec["what"]
        # the harness's own and the other hybrids' counts are not this
        # model's: no reader is pointed at them
        assert spec["reader"] in ("xplane", "xplane_scope",
                                  "granite_hybrid_roofline")
        assert "decode_step_bytes" not in json.dumps(spec["params"])
    assert {m["name"] for m in man.metrics_of(CELL, "end_to_end")} == {
        "serve_tokens_per_s", "setup_s"}
    # no cell the benchmark had reports a metric of this one
    for w in man.doc["workloads"]:
        if w["name"] != CELL:
            assert not names & {m["name"] for m in
                                man.metrics_of(w["name"], "per_layer")}
    # and the manifest's entries for this PR stand last in their lists
    assert man.doc["configs"][-1]["name"] == CONFIG
    assert man.doc["workloads"][-1]["name"] == CELL
    assert {m["name"] for m in man.doc["per_layer"][-9:]} == names


# --- the reader -------------------------------------------------------------

SPANS = (
    [("prefill", 1.0 + i, 0.1, {"prompt_len": n, "cached_tokens": 0,
                                "state_kinds": "mamba",
                                "state_installed_bytes": 77_377_536})
     for i, n in enumerate((300, 900))]
    # three steps; a step's spans share a start; 3, 2 and 2 live slots
    + [("decode", 2.0, 0.02, {"slot": s, "live": 3, "state_kinds": "mamba",
                              "ssm_tile": 32}) for s in range(3)]
    + [("decode", 2.1, 0.02, {"slot": s, "live": 2, "state_kinds": "mamba",
                              "ssm_tile": 32}) for s in range(2)]
    + [("decode", 2.2, 0.02, {"slot": s, "live": 2, "state_kinds": "mamba",
                              "ssm_tile": 32}) for s in range(2)]
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
                          mix=man.traffic("shortchat"),
                          device={"kind": "TPU v5 lite"})
    return {"ctx": ctx, "trace": trace_reduce.load(text_proto=text),
            "trace_window": (100e3, 900e3), "nemotron_spans": list(SPANS),
            "gauges": {"blocks_used": [10, 30]}}


def test_a_share_is_counted_work_over_device_time_over_the_peak(evidence):
    s = fg.sizes_of(evidence["ctx"].config)
    read = granite_hybrid_roofline.read
    got = read(dict(evidence), {"work": "ssd", "scopes": ["flash_fwd"]})
    assert got == pytest.approx(
        100 * 1200 * 36 * 3_178_496 / 120e-6 / 197e12)
    per = trace_reduce.module_seconds(evidence["trace"],
                                      evidence["trace_window"])
    runs, secs = per["jit_step"]
    msom = (300 ** 2 + 900 ** 2) / 1200
    got = read(dict(evidence), {"work": "prefill", "module": "jit_step"})
    assert got == pytest.approx(
        100 * fg.prefill_flops(s, 1200, 2, msom) / secs / 197e12)
    # 7 (slot, step) pairs moved their states; a step's least bytes at the
    # steps' mean of 7/3 live slots and 20 blocks x 128 cached positions
    got = read(dict(evidence), {"work": "state_bytes",
                                "scopes": ["flash_fwd"]})
    assert got == pytest.approx(
        100 * 2 * 7 * 77_377_536 / 120e-6 / 819e9)
    got = read(dict(evidence), {"work": "decode_bytes",
                                "module": "jit_step"})
    assert got == pytest.approx(
        100 * fg.decode_step_bytes(s, 7 / 3, 20 * 128) / (secs / runs)
        / 819e9)
    with pytest.raises(ValueError, match="unknown work"):
        read(dict(evidence), {"work": "else", "module": "jit_step"})


@pytest.mark.parametrize("change", [
    {"nemotron_spans": None}, {"nemotron_spans": []}, {"trace": None},
    {"trace_window": None}, "rehearsal", "another_config", "absent_scope",
    "no_decode", "no_prefill"])
def test_with_nothing_to_read_the_reader_says_none(evidence, man, change):
    ev, params = dict(evidence), {"work": "state_bytes",
                                  "scopes": ["flash_fwd"]}
    if change == "rehearsal":
        ev["ctx"] = SimpleNamespace(**{**vars(ev["ctx"]), "rehearsal": True})
    elif change == "another_config":
        ev["ctx"] = SimpleNamespace(**{
            **vars(ev["ctx"]),
            "config": man.config("nemotron-3-super-120b-a12b")})
    elif change == "absent_scope":
        params = {"work": "state_bytes", "scopes": ["mamba_step"]}
    elif change == "no_decode":
        ev["nemotron_spans"] = [sp for sp in SPANS if sp[0] != "decode"]
    elif change == "no_prefill":
        ev["nemotron_spans"] = [sp for sp in SPANS if sp[0] != "prefill"]
        params = {"work": "prefill", "module": "jit_step"}
    else:
        ev.update(change)
    assert granite_hybrid_roofline.read(ev, params) is None


def test_a_program_without_the_sessions_recorders_gives_no_share(
        evidence, monkeypatch):
    """A program whose profile session keeps no recorders: the reader
    finds no span and every share of this cell is left out."""
    from megatron_llm_tpu.obs import profile

    ev = {k: v for k, v in evidence.items() if k != "nemotron_spans"}
    monkeypatch.setattr(profile, "last", lambda: SimpleNamespace(
        t_sync=0.0, t_stop=1.0))
    for params in ({"work": "ssd", "scopes": ["flash_fwd"]},
                   {"work": "decode_bytes", "module": "jit_step"}):
        assert granite_hybrid_roofline.read(dict(ev), params) is None


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
    # end-to-end metrics, traced or not
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "2 layers" in proc.stdout and "vocab 512" in proc.stdout
    if trace == 2:
        assert "traced window: 4 prefills" in proc.stdout
