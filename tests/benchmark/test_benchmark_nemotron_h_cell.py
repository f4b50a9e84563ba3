"""The cell ``nemotron3super-serve-reasoning``: its configuration file
against the published one, its operation and byte counts against a hand
count, its traffic under the ``serve_backlog`` rules, its readers'
arithmetic, and a rehearsal of the cell to its result line."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import flops_nemotron_h as fn
from benchmarks import trace_reduce, traffic
from benchmarks.manifest import ROOT, Manifest
from benchmarks.readers import expert_load, nemotron_h_roofline

CELL, CONFIG = "nemotron3super-serve-reasoning", "nemotron-3-super-120b-a12b"
BIG = 3_000_000_019
DATA = Path(__file__).parent / "data"

# config.json of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, every key
# that shapes the language model
PUBLISHED = dict(
    attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
    head_dim=128, hidden_size=4096,
    hybrid_override_pattern=(
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM"
        "*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
    intermediate_size=2688, layer_norm_epsilon=1e-05, mamba_head_dim=64,
    mamba_hidden_act="silu", mamba_num_heads=128, mamba_proj_bias=False,
    max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", moe_intermediate_size=2688,
    moe_latent_size=1024, moe_shared_expert_intermediate_size=5376,
    moe_shared_expert_overlap=False, mtp_hybrid_override_pattern="*E",
    n_group=1, n_groups=8, n_routed_experts=512, n_shared_experts=1,
    norm_eps=1e-05, norm_topk_prob=True, num_attention_heads=32,
    num_experts_per_tok=22, num_hidden_layers=88, num_key_value_heads=2,
    num_logits_to_keep=1, num_nextn_predict_layers=1,
    partial_rotary_factor=1, rescale_prenorm_residual=True,
    residual_in_fp32=False, rope_theta=10000, routed_scaling_factor=5,
    sliding_window=None, ssm_state_size=128, tie_word_embeddings=False,
    time_step_floor=0.0001, time_step_max=0.1, time_step_min=0.001,
    topk_group=1, use_bias=False, use_conv_bias=True,
    use_mamba_kernels=True, vocab_size=131072)


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def doc(man):
    return man.config(CONFIG)


def test_the_file_is_the_published_config_but_for_the_three_cuts(man, doc):
    entry = next(c for c in man.doc["configs"] if c["name"] == CONFIG)
    cut = {"num_hidden_layers": 11, "n_routed_experts": 128,
           "vocab_size": 32768}
    assert sorted(entry["reduced"]) == sorted(cut) == sorted(doc["reduced"])
    assert entry["source"] == doc["source"]
    for key, want in PUBLISHED.items():
        assert doc[key] == cut.get(key, want), key
        if key in cut:
            assert doc["published"][key] == want
    # the floors: a whole period and four layers, 8 experts, an eighth of
    # the vocabulary
    derived = doc["derived"]
    kinds = {"attention": "*", "mlp": "E", "mamba": "M"}
    period = "".join(kinds[k] for k in derived["layer_pattern"])
    assert period == derived["period"] == "*EMEMEMEMEM"
    assert PUBLISHED["hybrid_override_pattern"][25:36] == period
    assert doc["num_hidden_layers"] == len(period) >= 4
    assert doc["n_routed_experts"] >= 8
    assert 8 * doc["vocab_size"] >= PUBLISHED["vocab_size"]
    assert derived["router_outputs"] == PUBLISHED["n_routed_experts"]
    assert derived["mamba_d_inner"] == 128 * 64 == 2 * doc["hidden_size"]
    assert derived["mamba_conv_channels"] == 8192 + 2 * 8 * 128
    for said in ("stands_for", "left_out", "assumed"):
        assert doc[said]
    for key in ("no_rotary", "A_log", "dt_bias", "D", "conv1d",
                "e_score_correction_bias", "mamba_state", "residual_stream",
                "time_step_limit"):
        assert doc["assumed"][key], key
    engine = doc["serve"]["engine"]
    assert set(doc["serve"]["engine_why"]) == set(engine)
    assert engine["prefix_cache_blocks"] == 0
    assert engine["max_batch_size"] % 8 == 0


def test_the_program_preset_has_the_files_sizes(doc):
    from megatron_llm_tpu.config import nemotron_h_config

    cfg = nemotron_h_config(doc["preset"]["size"],
                            num_layers=doc["num_hidden_layers"])
    assert list(cfg.layer_pattern) == doc["derived"]["layer_pattern"]
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.kv_heads,
            cfg.head_dim, cfg.vocab_size) == (
        doc["hidden_size"], doc["num_attention_heads"],
        doc["num_key_value_heads"], doc["head_dim"], doc["vocab_size"])
    assert (cfg.num_experts, cfg.router_experts, cfg.moe_top_k,
            cfg.ffn_size, cfg.moe_latent_size, cfg.moe_shared_expert_size,
            cfg.moe_routed_scaling) == (
        doc["n_routed_experts"], doc["published"]["n_routed_experts"],
        doc["num_experts_per_tok"], doc["moe_intermediate_size"],
        doc["moe_latent_size"], doc["moe_shared_expert_intermediate_size"],
        doc["routed_scaling_factor"])
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.mamba_state_size, cfg.mamba_conv_kernel,
            cfg.mamba_chunk_size, cfg.norm_eps) == (
        doc["mamba_num_heads"], doc["mamba_head_dim"], doc["n_groups"],
        doc["ssm_state_size"], doc["conv_kernel"], doc["chunk_size"],
        doc["norm_eps"])


def test_weights_operations_and_bytes_against_a_hand_count(doc):
    s = fn.sizes_of(doc)
    assert (s["attention_layers"], s["moe_layers"], s["mamba_layers"]) == (
        1, 5, 5)
    p = fn.layer_params(s)
    # by hand.  Mamba-2: in 4096 x 18560 (8192 | 10240 | 128), conv 4 x
    # 10240 and its bias, out 8192 x 4096; attention: q and o 4096 x 4096,
    # k and v 4096 x 256
    assert p["mamba"] == 76_021_760 + 40_960 + 10_240 + 33_554_432
    assert p["attention"] == 2 * 16_777_216 + 2 * 1_048_576
    assert p["router"] == 4096 * 512 and p["latent"] == 2 * 4096 * 1024
    assert p["expert"] == 2 * 1024 * 2688 == 5_505_024
    assert p["shared_expert"] == 2 * 4096 * 5376
    moe = 2_097_152 + 8_388_608 + 44_040_192 + 128 * 5_505_024   # a layer
    params = 5 * 109_627_392 + 35_651_584 + 5 * moe + 2 * 32768 * 4096
    assert params == 4_648_069_120
    assert fn.weight_bytes(s) == 2 * params == 9_296_138_240     # 9.30 GB
    # the published model, by the same count: 120.7 B
    whole = fn.sizes_of({**doc, **doc["published"], "derived": {
        "layer_pattern": ["mamba"] * 40 + ["mlp"] * 40 + ["attention"] * 8}})
    assert round(fn.weight_bytes(whole, 1) / 1e9, 1) == 120.7
    # a chunk of 128 positions: C B^T a group and its product with dt x a
    # head, causal halves; the chunk's state and C S_prev a head
    chunk = (8 * 128 * 128 * 128 + 128 * 128 * 128 * 64
             + 4 * 128 * 128 * 64 * 128)
    assert fn.ssd_flops_per_token(s) == chunk / 128 == 5_373_952
    assert fn.held_assignments_per_token(s) == 5.5
    assert fn.expert_flops_per_assignment(s) == 4 * 1024 * 2688
    # one prompt of 1152 positions: a position attends 576 on average
    moe_f = 2 * (2_097_152 + 8_388_608 + 44_040_192) + 5.5 * 11_010_048
    mamba_f = 2 * 109_627_392 + 5_373_952
    attn_f = 2 * 35_651_584 + 2 * 32 * 128 * 1152
    want = 1152 * (5 * mamba_f + attn_f + 5 * moe_f) + 2 * 4096 * 32768
    assert fn.prefill_flops(s, 1152, 1, 1152) == want
    assert 2.0e9 < want / 1152 < 2.1e9           # ~2 GFLOP a token
    assert fn.prefill_flops(s, 1152, 1, 1152, held_per_token=4.0) == \
        want - 1152 * 5 * 1.5 * 11_010_048
    # a slot's state a layer: 128 heads x 64 x 128 and 3 x 10240, float32
    assert fn.state_bytes_per_slot(s) == 4 * (1_048_576 + 30_720) == 4_317_184
    assert fn.mamba_step_bytes(s, 128) == 2 * 5 * 128 * 4_317_184   # 5.5 GB
    # 128 live slots choose all but half an expert of a layer's 128
    assert 127.4 < fn.chosen_held_experts(s, 128) < 127.7
    assert fn.chosen_held_experts(s, 1) == pytest.approx(5.5)
    step = fn.decode_step_bytes(s, 128, 128 * 2000)
    weights = 2 * (5 * 109_627_392 + 35_651_584 + 5 * (
        2_097_152 + 8_388_608 + 44_040_192
        + fn.chosen_held_experts(s, 128) * 5_505_024) + 32768 * 4096)
    assert step == pytest.approx(
        weights + fn.mamba_step_bytes(s, 128) + 128 * 2000 * 1024)
    assert 17e-3 < step / 819e9 < 19e-3          # >= 18 ms a step


def test_the_mix_under_the_backlog_rules(man, doc):
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reasoning", 1)
    assert CELL in next(m for m in man.doc["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    mix = man.traffic("reasoning")
    assert mix["kind"] == "serve_backlog" and mix["schedule_seed"] == 23
    assert mix["requests"] % 100 == 0
    reqs = traffic.serve_requests(mix, BIG, 51.0, doc["vocab_size"])
    assert len(reqs) == mix["requests"] >= 600
    assert {r.due_s for r in reqs} == {0.0}
    outs = [r.max_new_tokens for r in reqs]
    assert 512 <= min(outs) < 520 and 2040 < max(outs) <= 2048
    lengths = [len(r.prompt) for r in reqs]
    assert 256 <= min(lengths) < 262 and 2040 < max(lengths) <= 2048
    # decode-heavy: a request generates more than it reads
    assert sum(outs) > 1.05 * sum(lengths)
    assert all(0 < t < doc["vocab_size"] - 1 for t in reqs[0].prompt)
    again = traffic.serve_requests(mix, BIG + 1, 51.0, doc["vocab_size"])
    assert [len(r.prompt) for r in again] == lengths
    assert again[0].prompt != reqs[0].prompt
    # the engine holds the longest request, queues the whole backlog and
    # compiles few prefill shapes; the check sequences end inside a
    # 128-position chunk and inside a bucket
    engine = doc["serve"]["engine"]
    assert engine["max_seq_len"] >= 2048 + 2048
    assert engine["max_queue_size"] > mix["requests"]
    bucket = engine["prefill_bucket"]
    assert bucket % doc["chunk_size"] == 0
    assert len({-(-n // bucket) for n in lengths}) <= 8
    check = mix["check"]
    assert (check["sequences"], check["prompt_tokens"],
            check["output_tokens"]) == (3, 1500, 32)
    assert check["prompt_tokens"] % doc["chunk_size"]
    assert check["prompt_tokens"] % bucket
    # the spans of a window (a decode span a token) fit the recorder
    assert engine["trace_capacity"] >= 400_000


def test_every_metric_of_the_cell_moves_its_throughput(man):
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert names == {
        f"{n}.reasoning" for n in (
            "device_idle_share", "prefill_tok_per_s", "prefill_mfu",
            "decode_step_ms", "decode_hbm_share", "mamba_share",
            "ssd_scan_roofline", "mamba_step_hbm_share", "moe_share",
            "expert_load_max_over_mean")}
    for m in man.metrics_of(CELL, "per_layer"):
        assert m["moves"] == "serve_tokens_per_s"
        assert m["workloads"] == [CELL]
        spec = man.layer_metric(m["name"])
        assert spec["what"] and "stub" not in spec["what"]
        # the harness's decode_step_bytes is Falcon-shaped for this model:
        # no reader is pointed at it
        assert "decode_step_bytes" not in json.dumps(spec["params"])
    assert {m["name"] for m in man.metrics_of(CELL, "end_to_end")} == {
        "serve_tokens_per_s", "setup_s"}
    # no cell the benchmark had reports a metric of this one
    for w in man.doc["workloads"]:
        if w["name"] != CELL:
            assert not names & {m["name"] for m in
                                man.metrics_of(w["name"], "per_layer")}


# --- the reader -------------------------------------------------------------

SPANS = (
    [("prefill", 1.0 + i, 0.1, {"prompt_len": n, "cached_tokens": 0,
                                "state_kinds": "mamba"})
     for i, n in enumerate((600, 1400))]
    # three steps; a step's spans share a start; 3, 2 and 2 live slots
    + [("decode", 2.0, 0.02, {"slot": s, "live": 3, "state_kinds": "mamba"})
       for s in range(3)]
    + [("decode", 2.1, 0.02, {"slot": s, "live": 2, "state_kinds": "mamba"})
       for s in range(2)]
    + [("decode", 2.2, 0.02, {"slot": s, "live": 2, "state_kinds": "mamba"})
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
                          mix=man.traffic("reasoning"),
                          device={"kind": "TPU v5 lite"})
    return {"ctx": ctx, "trace": trace_reduce.load(text_proto=text),
            "trace_window": (100e3, 900e3), "nemotron_spans": list(SPANS),
            "gauges": {"blocks_used": [10, 30]}}


def test_the_traced_work_is_read_off_the_spans():
    did = nemotron_h_roofline.traced_work(SPANS)
    assert did == {"prompts": [600, 1400], "live": [3, 2, 2]}
    # spans of a program from before the arguments: nothing
    bare = [(n, t, d, {}) for n, t, d, _a in SPANS]
    assert nemotron_h_roofline.traced_work(bare) == {"prompts": [],
                                                     "live": []}


def test_a_share_is_counted_work_over_device_time_over_the_peak(
        evidence, monkeypatch):
    # (no engine's counter: another test of this process may have left one)
    monkeypatch.setattr(expert_load, "_samples", lambda family: [])
    s = fn.sizes_of(evidence["ctx"].config)
    read = nemotron_h_roofline.read
    got = read(dict(evidence), {"work": "ssd", "scopes": ["flash_fwd"]})
    assert got == pytest.approx(
        100 * 2000 * 5 * 5_373_952 / 120e-6 / 197e12)
    per = trace_reduce.module_seconds(evidence["trace"],
                                      evidence["trace_window"])
    runs, secs = per["jit_step"]
    msom = (600 ** 2 + 1400 ** 2) / 2000
    got = read(dict(evidence), {"work": "prefill", "module": "jit_step"})
    assert got == pytest.approx(
        100 * fn.prefill_flops(s, 2000, 2, msom) / secs / 197e12)
    # the engine's own count where there is one: 6 of a token's 22 held
    counted = [SimpleNamespace(value=v, labels={"held": h})
               for v, h in ((6.0, "1"), (16.0, "0"))]
    monkeypatch.setattr(expert_load, "_samples", lambda family: counted)
    assert read(dict(evidence), {"work": "prefill", "module": "jit_step"}
                ) == pytest.approx(100 * fn.prefill_flops(
                    s, 2000, 2, msom, 6.0) / secs / 197e12)
    monkeypatch.setattr(expert_load, "_samples", lambda family: [])
    # 7 (slot, step) pairs moved their states; a step's least bytes at the
    # steps' mean of 7/3 live slots and 20 blocks x 128 cached positions
    got = read(dict(evidence), {"work": "state_bytes",
                                "scopes": ["flash_fwd"]})
    assert got == pytest.approx(
        100 * 2 * 5 * 7 * 4_317_184 / 120e-6 / 819e9)
    got = read(dict(evidence), {"work": "decode_bytes",
                                "module": "jit_step"})
    assert got == pytest.approx(
        100 * fn.decode_step_bytes(s, 7 / 3, 20 * 128) / (secs / runs)
        / 819e9)


def test_a_router_that_favours_few_experts_reads_fewer_a_step(
        evidence, monkeypatch):
    """Two layers counted 44 tokens each: in one every token chose held
    expert 0 and the other 21 choices fell on experts that are not here,
    in the other held experts 0 and 1 were each chosen by half."""
    s = fn.sizes_of(evidence["ctx"].config)

    def sample(layer, expert, held, value):
        return SimpleNamespace(value=value, labels={
            "layer": str(layer), "expert": str(expert), "held": held})

    counted = ([sample(1, 0, "1", 44.0), sample(1, 200, "0", 44.0 * 21)]
               + [sample(3, 0, "1", 22.0), sample(3, 1, "1", 22.0),
                  sample(3, 300, "0", 44.0 * 21)])
    monkeypatch.setattr(expert_load, "_samples", lambda family: counted)
    got = nemotron_h_roofline.chosen_experts(s, 2.0)
    assert got == pytest.approx((1.0 + 2 * (1 - 0.5 ** 2)) / 2)
    assert fn.decode_step_bytes(s, 2.0, 0.0, got) < fn.decode_step_bytes(
        s, 2.0, 0.0)
    monkeypatch.setattr(expert_load, "_samples", lambda family: [])
    assert nemotron_h_roofline.chosen_experts(s, 2.0) is None


@pytest.mark.parametrize("change", [
    {"nemotron_spans": None}, {"nemotron_spans": []}, {"trace": None},
    {"trace_window": None}, "rehearsal", "another_config", "absent_scope",
    "no_decode"])
def test_with_nothing_to_read_the_reader_says_none(evidence, change):
    ev, params = dict(evidence), {"work": "state_bytes",
                                  "scopes": ["flash_fwd"]}
    if change == "rehearsal":
        ev["ctx"] = SimpleNamespace(**{**vars(ev["ctx"]), "rehearsal": True})
    elif change == "another_config":
        ev["ctx"] = SimpleNamespace(**{**vars(ev["ctx"]),
                                       "config": {"hidden_size": 4544}})
    elif change == "absent_scope":
        params = {"work": "state_bytes", "scopes": ["mamba_step"]}
    elif change == "no_decode":
        ev["nemotron_spans"] = [sp for sp in SPANS if sp[0] != "decode"]
    else:
        ev.update(change)
    assert nemotron_h_roofline.read(ev, params) is None


def test_a_program_without_the_sessions_recorders_gives_no_spans(
        evidence, monkeypatch):
    """The parent commit: its profile session keeps no recorders, so the
    reader finds no span and every share of this cell is left out."""
    from megatron_llm_tpu.obs import profile

    ev = {k: v for k, v in evidence.items() if k != "nemotron_spans"}
    monkeypatch.setattr(profile, "last", lambda: SimpleNamespace(
        t_sync=0.0, t_stop=1.0))
    assert nemotron_h_roofline.traced_spans(ev) is None
    assert nemotron_h_roofline.read(ev, {"work": "ssd",
                                         "scopes": ["flash_fwd"]}) is None
    monkeypatch.setattr(profile, "last", lambda: None)
    ev.pop("nemotron_spans")
    assert nemotron_h_roofline.traced_spans(ev) is None


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
    e2e = {"serve_tokens_per_s", "setup_s"}
    if trace == 0:
        assert set(line["metrics"]) == e2e
        return
    # the program counter is read; on the CPU no device metric is
    assert set(line["metrics"]) == e2e | {
        "expert_load_max_over_mean.reasoning"}
    assert line["metrics"]["expert_load_max_over_mean.reasoning"][
        "value"] >= 1
    assert "traced window: 4 prefills" in proc.stdout
