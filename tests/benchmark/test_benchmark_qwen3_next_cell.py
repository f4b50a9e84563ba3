"""The cell ``qwen3next-serve-longdoc``: its configuration file against the
published one, its operation counts against a hand count, its traffic
under the ``serve_backlog`` rules, its readers' arithmetic, and a
rehearsal of the cell to its result line."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import flops_qwen3_next as fq
from benchmarks import trace_reduce, traffic
from benchmarks.manifest import ROOT, Manifest
from benchmarks.readers import expert_load, qwen3_next_roofline

CELL, CONFIG = "qwen3next-serve-longdoc", "qwen3-next-80b-a3b"
BIG = 3_000_000_019
DATA = Path(__file__).parent / "data"

# config.json of Qwen/Qwen3-Next-80B-A3B-Instruct, every key that shapes
# the language model
PUBLISHED = dict(
    decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
    hidden_act="silu", hidden_size=2048, intermediate_size=5120,
    linear_conv_kernel_dim=4, linear_key_head_dim=128,
    linear_num_key_heads=16, linear_num_value_heads=32,
    linear_value_head_dim=128, max_position_embeddings=262144,
    mlp_only_layers=[], model_type="qwen3_next", moe_intermediate_size=512,
    norm_topk_prob=True, num_attention_heads=16, num_experts=512,
    num_experts_per_tok=10, num_hidden_layers=48, num_key_value_heads=2,
    partial_rotary_factor=0.25, rms_norm_eps=1e-06, rope_scaling=None,
    rope_theta=10000000, shared_expert_intermediate_size=512,
    tie_word_embeddings=False, use_sliding_window=False, vocab_size=151936)


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def doc(man):
    return man.config(CONFIG)


def test_the_file_is_the_published_config_but_for_the_three_cuts(man, doc):
    entry = next(c for c in man.doc["configs"] if c["name"] == CONFIG)
    cut = {"num_hidden_layers": 4, "num_experts": 256, "vocab_size": 75968}
    assert sorted(entry["reduced"]) == sorted(cut) == sorted(doc["reduced"])
    for key, want in PUBLISHED.items():
        assert doc[key] == cut.get(key, want), key
        if key in cut:
            assert doc["published"][key] == want
    # the floors: a whole period and four layers, 8 experts, an eighth of
    # the vocabulary
    assert doc["num_hidden_layers"] % doc["full_attention_interval"] == 0
    assert doc["num_experts"] >= 8
    assert 8 * doc["vocab_size"] >= PUBLISHED["vocab_size"]
    assert doc["derived"]["router_outputs"] == PUBLISHED["num_experts"]
    for said in ("stands_for", "left_out", "assumed"):
        assert doc[said]
    engine = doc["serve"]["engine"]
    assert set(doc["serve"]["engine_why"]) == set(engine)
    assert engine["prefix_cache_blocks"] == 0


def test_weights_and_operations_against_a_hand_count(doc):
    s = fq.sizes_of(doc)
    p = fq.layer_params(s)
    # by hand.  DeltaNet: qkvz 2048 x 12288, ba 2048 x 64, conv 4 x 8192,
    # out 4096 x 2048; attention: q|gate 2048 x 8192, k and v 2048 x 512,
    # o 4096 x 2048
    assert p["deltanet"] == 25_165_824 + 131_072 + 32_768 + 8_388_608
    assert p["attention"] == 16_777_216 + 2 * 1_048_576 + 8_388_608
    assert p["router"] == 2048 * 512 and p["expert"] == 3 * 2048 * 512
    assert p["shared_expert"] == 3 * 2048 * 512 + 2048
    common = 1_048_576 + 3_147_776 + 256 * 3_145_728       # a layer
    params = (3 * 33_718_272 + 27_262_976 + 4 * common
              + 2 * 75968 * 2048)
    assert params == 3_677_593_600
    assert fq.weight_bytes(s) == 2 * params == 7_355_187_200     # 7.36 GB
    # the published model, by the same count: 80 B
    whole = fq.sizes_of({**doc, **doc["published"]})
    assert round(fq.weight_bytes(whole, 1) / 1e9, 1) == 79.7
    # one chunk of 64 positions of one head: K K^T 2 x 64 x 64 x 128; the
    # triangular solve 64 x 64 x (128 + 128); three products with the
    # state 3 x 2 x 64 x 128 x 128; Q K^T and its product, causal halves
    chunk = 1_048_576 + 1_048_576 + 6_291_456 + 524_288 + 524_288
    assert fq.delta_rule_flops_per_token(s) == 32 * chunk / 64 == 4_718_592
    assert fq.held_assignments_per_token(s) == 5.0
    assert fq.expert_flops_per_assignment(s) == 6_291_456
    # one prompt of 10240 positions: a position attends 5120 on average
    moe = 2 * (1_048_576 + 3_147_776) + 5 * 6_291_456
    linear = 2 * 33_718_272 + 4_718_592 + moe
    full = 2 * 27_262_976 + 2 * 16 * 256 * 10240 + moe
    want = 10240 * (3 * linear + full) + 2 * 2048 * 75968
    assert fq.prefill_flops(s, 10240, 1, 10240) == want
    assert 0.50e9 < want / 10240 < 0.53e9        # ~0.5 GFLOP a token
    # the engine's own count of held choices replaces the even spread
    assert fq.prefill_flops(s, 10240, 1, 10240, held_per_token=4.0) == \
        want - 10240 * 4 * 6_291_456


def test_the_mix_under_the_backlog_rules(man, doc):
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc", 1)
    assert CELL in next(m for m in man.doc["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    mix = man.traffic("longdoc")
    assert mix["kind"] == "serve_backlog" and mix["schedule_seed"] == 23
    assert mix["requests"] % 100 == 0
    reqs = traffic.serve_requests(mix, BIG, 51.0, doc["vocab_size"])
    assert len(reqs) == mix["requests"]
    assert {r.due_s for r in reqs} == {0.0}
    assert {r.max_new_tokens for r in reqs} == {256}
    lengths = [len(r.prompt) for r in reqs]
    assert 4096 <= min(lengths) < 4200 and 16300 < max(lengths) <= 16384
    assert all(0 < t < doc["vocab_size"] - 1 for t in reqs[0].prompt)
    again = traffic.serve_requests(mix, BIG + 1, 51.0, doc["vocab_size"])
    assert [len(r.prompt) for r in again] == lengths
    assert again[0].prompt != reqs[0].prompt
    # the engine holds the longest request, queues the whole backlog and
    # compiles few prefill shapes; the check sequences end inside a
    # 64-position chunk and inside a bucket
    engine = doc["serve"]["engine"]
    assert engine["max_seq_len"] >= 16384 + 256
    assert engine["max_batch_size"] >= 16
    assert engine["max_queue_size"] > mix["requests"]
    bucket = engine["prefill_bucket"]
    assert bucket % fq.CHUNK == 0
    assert len({-(-n // bucket) for n in lengths}) <= 8
    check = mix["check"]
    assert check["prompt_tokens"] % fq.CHUNK
    assert check["prompt_tokens"] % bucket


def test_every_metric_of_the_cell_moves_its_throughput(man):
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert names == {
        "prefill_tok_per_s.longdoc", "device_idle_share.longdoc",
        "gdn_share.longdoc", "moe_share.longdoc", "prefill_mfu.longdoc",
        "moe_expert_roofline.longdoc", "gdn_scan_roofline.longdoc",
        "expert_load_max_over_mean.longdoc"}
    for m in man.metrics_of(CELL, "per_layer"):
        assert m["moves"] == "serve_tokens_per_s"
        assert m["workloads"] == [CELL]
        # decode_step_bytes is Falcon-shaped for this model: nobody reads it
        assert "decode_step_bytes" not in json.dumps(
            man.layer_metric(m["name"]))
    assert {m["name"] for m in man.metrics_of(CELL, "end_to_end")} == {
        "serve_tokens_per_s", "setup_s"}


# --- the readers ------------------------------------------------------------

@pytest.fixture(scope="module")
def evidence(man, doc, tmp_path_factory):
    """The synthetic trace of ``test_benchmark_trace2.py``: two runs of
    ``jit_step``, in each 60 us under ``attention/flash_fwd``."""
    from jax.profiler import ProfileData

    text = (DATA / "synthetic_xplane_scopes.txt").read_text()
    d = tmp_path_factory.mktemp("trace")
    run = d / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    ctx = SimpleNamespace(trace_dir=str(d), config=doc, rehearsal=False,
                          mix=man.traffic("longdoc"),
                          device={"kind": "TPU v5 lite"})
    return {"ctx": ctx, "trace": trace_reduce.load(text_proto=text),
            "trace_window": (100e3, 900e3), "traced_prefill_tokens": 1000}


def test_a_share_is_counted_work_over_scope_time_over_the_peak(
        evidence, monkeypatch):
    # (no engine's counter: another test of this process may have left one)
    monkeypatch.setattr(expert_load, "_samples", lambda family: [])
    s = fq.sizes_of(evidence["ctx"].config)
    got = qwen3_next_roofline.read(dict(evidence), {
        "work": "delta_rule", "scopes": ["flash_fwd"]})
    work = 1000 * 3 * fq.delta_rule_flops_per_token(s)
    assert got == pytest.approx(100 * work / 120e-6 / 197e12)
    got = qwen3_next_roofline.read(dict(evidence), {
        "work": "experts", "scopes": ["flash_fwd"]})
    assert got == pytest.approx(
        100 * 1000 * 4 * 5 * 6_291_456 / 120e-6 / 197e12)
    # the engine's own count where there is one: 6 of a token's 10 held
    counted = [SimpleNamespace(value=v, labels={"held": h})
               for v, h in ((60.0, "1"), (40.0, "0"))]
    monkeypatch.setattr(expert_load, "_samples", lambda family: counted)
    assert qwen3_next_roofline.read(dict(evidence), {
        "work": "experts", "scopes": ["flash_fwd"]}) == pytest.approx(
        got * 6 / 5)
    monkeypatch.setattr(expert_load, "_samples", lambda family: [])
    lengths = traffic.stratified(evidence["ctx"].mix["prompt_tokens"], 64)
    mean = sum(lengths) / len(lengths)
    work = fq.prefill_flops(s, 1000, 1000 / mean,
                            sum(n * n for n in lengths) / sum(lengths))
    per = trace_reduce.module_seconds(evidence["trace"],
                                      evidence["trace_window"])
    got = qwen3_next_roofline.read(dict(evidence), {
        "work": "prefill", "module": "jit_step"})
    assert got == pytest.approx(
        100 * work / per["jit_step"][1] / 197e12)


@pytest.mark.parametrize("change", [
    {"traced_prefill_tokens": 0}, {"trace": None}, {"trace_window": None},
    "rehearsal", "another_config", "absent_scope"])
def test_with_nothing_to_read_a_reader_says_none(evidence, change):
    ev, params = dict(evidence), {"work": "experts", "scopes": ["flash_fwd"]}
    if change == "rehearsal":
        ev["ctx"] = SimpleNamespace(**{**vars(ev["ctx"]), "rehearsal": True})
    elif change == "another_config":
        ev["ctx"] = SimpleNamespace(**{**vars(ev["ctx"]),
                                       "config": {"hidden_size": 4544}})
    elif change == "absent_scope":
        params = {"work": "experts", "scopes": ["moe_experts"]}
    else:
        ev.update(change)
    assert qwen3_next_roofline.read(ev, params) is None


def test_a_program_that_counts_no_experts_reports_none():
    # (no engine of this process has registered the family)
    assert expert_load.read({}, {"family": "no_such_family_total"}) is None
    assert expert_load.held_share("no_such_family_total") is None


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
    device = {m["name"] for m in man.metrics_of(CELL, "per_layer")
              if m["source"] == "device_trace"}
    assert set(line["metrics"]) == e2e | {
        "expert_load_max_over_mean.longdoc"}
    assert not set(line["metrics"]) & device
    assert line["metrics"]["expert_load_max_over_mean.longdoc"]["value"] >= 1
    assert "traced window: 4 prefills" in proc.stdout
