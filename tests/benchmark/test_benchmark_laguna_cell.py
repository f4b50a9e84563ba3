"""The cell ``lagunaxs2-serve-codectx``: its configuration file against
the catalog row's published config, its operation and byte counts against
ISSUE 58's hand count, its traffic under the ``serve_backlog`` rules (the
quantiles' shares under 2048 and over 15 k among them), its per-layer
entries and their files, its reader's arithmetic, and a rehearsal of the
cell to its result line.  Lists are held by membership, never to their
end or to an exact set."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import flops_laguna as fl
from benchmarks import trace_reduce, traffic
from benchmarks.manifest import ROOT, Manifest
from benchmarks.readers import laguna_roofline as reader

CELL, CONFIG, MIX = "lagunaxs2-serve-codectx", "laguna-xs.2", "codectx"
BIG = 3_000_000_019
DATA = Path(__file__).parent / "data"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")

# config.json of poolside/Laguna-XS.2: the scalar keys of the catalog
# row's ``config`` (the two 40-entry lists and rope_parameters are held
# below, and against the catalog itself where it is installed)
PUBLISHED = dict(
    model_type="laguna", vocab_size=100352, hidden_size=2048,
    intermediate_size=8192, num_hidden_layers=40, num_attention_heads=48,
    num_key_value_heads=8, head_dim=128, max_position_embeddings=262144,
    attention_bias=False, rms_norm_eps=1e-06, num_experts=256,
    num_experts_per_tok=8, moe_intermediate_size=512,
    shared_expert_intermediate_size=512, tie_word_embeddings=False,
    gating=True, sliding_window=512, moe_apply_router_weight_on_input=False,
    partial_rotary_factor=0.5, moe_routed_scaling_factor=2.5)

METRICS = {f"{name}.codectx" for name in (
    "prefill_mfu", "prefill_tok_per_s", "decode_step_ms",
    "decode_hbm_share", "device_idle_share", "swa_share", "attn_full_share",
    "moe_share", "expert_load_max_over_mean", "kv_pool_peak_share",
    "flash_window_roofline", "ring_decode_roofline", "walk_hbm_share",
    "moe_expert_roofline")}


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def doc(man):
    return man.config(CONFIG)


def test_the_file_is_the_published_config_but_for_its_depth(man, doc):
    entry = next(c for c in man.doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == doc["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == doc["source"] == (
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")
    for key, want in PUBLISHED.items():
        assert doc[key] == (5 if key == "num_hidden_layers" else want), key
    assert doc["published"]["num_hidden_layers"] == 40
    assert doc["by_kind"]["serve_backlog_routed"]["num_hidden_layers"] == 5
    # the two 40-entry lists and the rotations, as published
    assert doc["layer_types"] == [
        "full_attention" if i % 4 == 0 else "sliding_attention"
        for i in range(40)]
    assert doc["num_attention_heads_per_layer"] == [
        48 if i % 4 == 0 else 64 for i in range(40)]
    assert doc["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    rope = doc["rope_parameters"]
    assert rope["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
    assert rope["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    # the floors: the leading dense layer and one whole period of four
    # layers behind it, every expert, the whole vocabulary, no layer
    # shared by chips
    assert doc["layer_types"][:5].count("sliding_attention") == 3
    assert "chips that share a layer: 1" in doc["stands_for"]
    for said in ("gate", "qk_norm", "router", "router_bias",
                 "shared_expert", "rotation", "residual_stream",
                 "state_precision", "initialisation"):
        assert doc["assumed"][said], said
    assert doc["left_out"].startswith("nothing")
    engine = doc["serve"]["engine"]
    assert set(doc["serve"]["engine_why"]) == set(engine)
    assert engine["prefix_cache_blocks"] == 0
    # ISSUE 58: 32 to 48 in eights, a pool set and not auto-sized, under
    # its slots' worst case of 144 blocks each
    assert engine["max_batch_size"] in (32, 40, 48)
    assert 0 < engine["kv_pool_blocks"] < engine["max_batch_size"] * 144


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_in_the_file(doc):
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Laguna-XS.2")
    assert doc["source"] == row["source_url"]
    for key, want in row["config"].items():
        if key not in doc["reduced"]:
            assert doc[key] == want, key


def test_the_program_preset_has_the_files_sizes(doc):
    from megatron_llm_tpu.config import laguna_config

    model = laguna_config(doc["preset"]["size"])
    assert (model.num_layers, model.hidden_size, model.head_dim) == (
        5, doc["hidden_size"], doc["head_dim"])
    assert model.kv_heads == doc["derived"]["num_kv_heads"] == 8
    assert model.ffn_size == doc["derived"]["ffn_hidden_size"] == \
        doc["moe_intermediate_size"]
    heads = {"full": model.num_attention_heads,
             "window": model.window_attention_heads}
    kinds = ["window" if t == "sliding_attention" else "full"
             for t in doc["layer_types"][:5]]
    assert list(model.layer_kinds) == kinds
    assert [heads[k] for k in kinds] == \
        doc["num_attention_heads_per_layer"][:5]
    assert (model.num_experts, model.moe_top_k, model.moe_dense_ffn_size,
            model.moe_shared_expert_size, model.moe_routed_scaling) == (
        256, 8, 8192, 512, 2.5)
    assert model.sliding_window == doc["sliding_window"]
    full = doc["rope_parameters"]["full_attention"]
    assert (model.rope_theta, model.rotary_percent, model.rope_scaling_type,
            model.rope_scaling_factor, model.rope_original_max_positions,
            model.rope_beta_fast, model.rope_beta_slow,
            model.rope_attention_factor) == (
        full["rope_theta"], full["partial_rotary_factor"], "yarn",
        full["factor"], full["original_max_position_embeddings"],
        full["beta_fast"], full["beta_slow"], full["attention_factor"])
    assert model.window_rope == (10000.0, 1.0, 1.0)


def test_the_programs_tree_holds_the_hand_counted_parameters(doc):
    """``init_params``' shapes count what ``flops_laguna.py`` counts: the
    cut's 3 869 858 816."""
    import jax

    from megatron_llm_tpu.config import laguna_config
    from megatron_llm_tpu.models import model as model_lib

    cfg = laguna_config(doc["preset"]["size"])
    tree = jax.eval_shape(lambda k: model_lib.init_params(k, cfg),
                          jax.random.key(0))
    held = sum(a.size for a in jax.tree.leaves(tree))
    assert held == fl.param_count(fl.sizes_of(doc, 5)) == 3_869_858_816


def test_weights_operations_and_bytes_against_a_hand_count(doc):
    s = fl.sizes_of(doc, 5)
    assert s["kinds"] == ["full", "window", "window", "window", "full"]
    assert s["heads"] == [48, 64, 64, 64, 48]
    # ISSUE 58's count, part by part
    assert 2 * 100352 * 2048 + 2048 == 411_043_840
    assert fl.attention_params(s, 48) == 29_458_432      # 98 304 of gate
    assert fl.attention_params(s, 64) == 37_879_808
    assert fl.ffn_params(s, True) == 50_331_648
    assert fl.expert_params(s) == 3_145_728
    assert fl.layer_param_counts(s) == [
        79_794_176, 846_860_544, 846_860_544, 846_860_544, 838_439_168]
    assert fl.param_count(s) == 3_869_858_816
    assert fl.weight_bytes(s) == 7_739_717_632           # 7.74 GB
    # the whole model, by the same functions: the published 33.4 B
    whole = fl.sizes_of({**doc, "num_hidden_layers": 40}, 40)
    assert fl.param_count(whole) == 33_442_606_848
    # a slot's fixed state and a position's pool rows (ISSUE 58)
    assert fl.ring_bytes_per_slot(s) == 6_291_456 == \
        doc["derived"]["ring_bytes_per_slot"]
    assert fl.kv_bytes_per_position(s) == 8192 == \
        doc["derived"]["kv_bytes_per_position"]
    assert fl.ring_bytes_per_slot(whole) == 62_914_560
    assert fl.kv_bytes_per_position(whole) == 40_960
    # the band: a query keeps min(position + 1, 512) keys
    assert fl.band_pairs(3, 512) == 6 and fl.band_pairs(512, 512) == 131_328
    assert fl.band_pairs(1000, 512) == 131_328 + 488 * 512
    assert fl.triangle_pairs(1000) == 500_500
    assert fl.window_flops(s, [1000]) == 3 * 64 * 4 * 128 * fl.band_pairs(
        1000, 512)
    assert fl.full_attention_flops(s, [1000]) == 2 * 48 * 4 * 128 * 500_500
    assert fl.expert_flops(s, 1000) == 1000 * 4 * 8 * 2 * 3 * 2048 * 512
    # a prompt token's products: every matrix it meets, twice
    per_token = 2 * (2 * 29_458_432 + 3 * 37_879_808 + 50_331_648
                     + 4 * (2048 * 256 + 3_145_728 + 8 * 3_145_728))
    assert fl.prefill_flops(s, [1000]) == pytest.approx(
        1000 * per_token + fl.window_flops(s, [1000])
        + fl.full_attention_flops(s, [1000]) + 2 * 2048 * 100352)
    # ISSUE 58's arithmetic at 40 slots of 5.6 k positions: ~184 touched
    # experts a layer, ~7.6 GB a step
    assert fl.touched_experts(s, 40) == pytest.approx(184.1, abs=0.1)
    step = fl.decode_step_bytes(s, 40, 40 * 5600, 40 * 512)
    assert step == pytest.approx(7.6e9, rel=0.01)
    outside = fl.decode_step_bytes(s, 40, 0, 0, touched=0)
    assert outside == pytest.approx(0.89e9, rel=0.02)
    assert fl.walk_bytes(s, 40 * 5600) == 40 * 5600 * 8192
    assert fl.ring_read_bytes(s, 40 * 512) == 40 * 6_291_456
    # an uneven router touches fewer experts than an even one
    skew = [2.0 / 256] * 64 + [(1 - 0.5) / 192] * 192
    assert fl.touched_experts(s, 40, skew) < fl.touched_experts(s, 40)


def test_the_mix_under_the_backlog_rules(man, doc):
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert CELL in next(m for m in man.doc["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    mix = man.traffic(MIX)
    assert mix["kind"] == "serve_backlog_routed" and mix["kind_why"]
    assert mix["schedule_seed"] == 23
    assert mix["requests"] == 480 and mix["warmup_output_tokens"] == 4
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                    "sigma": 0.8, "min": 512, "max": 16384}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.6, "min": 64, "max": 2048}
    check = dict(mix["check"])
    limits, why = check.pop("limits"), check.pop("limits_why")
    assert check == {"sequences": 3, "prompt_tokens": 1500,
                     "output_tokens": 32}
    assert set(limits) == {"median_prompt", "median_decode", "mean"}
    assert set(why) == set(limits) and all(why.values())
    # three times the window: the install wraps, the steps go on behind it
    assert check["prompt_tokens"] > 2 * doc["sliding_window"]
    reqs = traffic.serve_requests({**mix, "kind": "serve_backlog"}, BIG,
                                  51.0, doc["vocab_size"])
    assert len(reqs) == 480 and {r.due_s for r in reqs} == {0.0}
    lengths = sorted(len(r.prompt) for r in reqs)
    outputs = sorted(r.max_new_tokens for r in reqs)
    assert lengths[0] == 512 and lengths[-1] == 16384
    assert 64 <= outputs[0] < 100 and outputs[-1] == 2048
    assert 3900 < lengths[240] < 4300 and 490 < outputs[240] < 540
    # "short and long in one queue": a fifth of the prompts under 2048, a
    # twentieth over 15 k (ISSUE 58)
    under = sum(n < 2048 for n in lengths) / 480
    over = sum(n > 15000 for n in lengths) / 480
    assert 0.17 < under < 0.22 and 0.04 < over < 0.065
    assert all(0 < t < doc["vocab_size"] - 1 for t in reqs[0].prompt)
    # the same work whatever the seed
    again = traffic.serve_requests({**mix, "kind": "serve_backlog"}, 7,
                                   51.0, doc["vocab_size"])
    assert [len(r.prompt) for r in again] == [len(r.prompt) for r in reqs]
    engine = doc["serve"]["engine"]
    assert engine["max_seq_len"] == 16384 + 2048
    assert engine["max_queue_size"] > mix["requests"]
    buckets = {-(-n // engine["prefill_bucket"]) for n in lengths}
    assert len(buckets) == 8
    assert -(-check["prompt_tokens"] // engine["prefill_bucket"]) in buckets
    assert engine["prefill_bucket"] % doc["sliding_window"] == 0
    assert engine["kv_block_size"] == 128
    # a block is 128 positions x 8192 B = 1 MiB; a slot's worst case 144
    assert engine["kv_block_size"] * fl.kv_bytes_per_position(
        fl.sizes_of(doc, 5)) == 2 ** 20
    assert -(-engine["max_seq_len"] // engine["kv_block_size"]) == 144
    # the pool holds the mean request's blocks for every slot, not the
    # worst case's: admission parks on blocks under the long ones
    need = [-(-(len(r.prompt) + r.max_new_tokens) // 128) for r in reqs]
    mean = sum(need) / len(need)
    assert 40 < mean < 55
    assert engine["max_batch_size"] * mean < engine["kv_pool_blocks"] \
        < engine["max_batch_size"] * 144
    assert max(need) < engine["kv_pool_blocks"]


def test_every_metric_of_the_cell_has_its_entry_and_its_file(man):
    entries = {m["name"]: m for m in man.metrics_of(CELL, "per_layer")}
    assert set(entries) >= METRICS
    for name in METRICS:
        m = entries[name]
        assert m["moves"] == "serve_tokens_per_s"
        assert m["workloads"] == [CELL]
        spec = man.layer_metric(name)
        for key in ("layer", "unit", "better", "source", "moves",
                    "workloads"):
            assert spec[key] == m[key], (name, key)
        assert hasattr(man.reader(spec["reader"]), "read")
        if name.split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%" and m["better"] == "higher"
    assert {m["name"] for m in man.metrics_of(CELL, "end_to_end")} >= {
        "serve_tokens_per_s", "setup_s"}
    # a layer the benchmark already names keeps that name
    older = {m["layer"] for m in man.doc["per_layer"]
             if CELL not in m.get("workloads", [])}
    for name in ("swa_share", "ring_decode_roofline", "walk_hbm_share",
                 "moe_share", "prefill_mfu", "decode_step_ms",
                 "device_idle_share", "kv_pool_peak_share",
                 "flash_window_roofline"):
        assert entries[f"{name}.codectx"]["layer"] in older, name


def test_the_cell_is_appended_and_nothing_before_it_moved(man):
    names = [w["name"] for w in man.doc["workloads"]]
    assert CELL in names and names.index(CELL) >= 9
    assert names[:9] == [
        "falcon7b-train-1chip", "falcon7b-serve-chat-knee",
        "falcon7b-serve-batch", "falcon40b-train-dp2tp2",
        "qwen3next-serve-longdoc", "nemotron3super-serve-reasoning",
        "granite4hmicro-serve-shortchat", "kanana2-serve-longqa",
        "phi4flash-serve-longgen"]
    configs = [c["name"] for c in man.doc["configs"]]
    assert CONFIG in configs and configs.index(CONFIG) >= 7
    assert len(json.dumps(man.doc, indent=1)) < 64 * 1024


# --- the reader -------------------------------------------------------------

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
                          mix=man.traffic(MIX),
                          device={"kind": "TPU v5 lite"})
    return {"ctx": ctx, "trace": trace_reduce.load(text_proto=text),
            "trace_window": (100e3, 900e3)}


STEP = {"state_kinds": "window", "live": 2, "live_positions": 9000,
        "ring_rows": 1024}
SPANS = [
    ("prefill", 1.0, 0.2, {"prompt_len": 700, "state_kinds": "window"}),
    ("prefill", 1.3, 0.3, {"prompt_len": 9000, "state_kinds": "window"}),
    # a step is the decode spans that share a start: one a live slot
    ("decode", 2.0, 0.01, STEP), ("decode", 2.0, 0.01, STEP),
    ("decode", 2.1, 0.01, {**STEP, "live_positions": 9002,
                           "ring_rows": 1024}),
    # another stack's spans carry no such arguments
    ("prefill", 2.5, 0.1, {"prompt_len": 777, "state_kinds": "ssm1"}),
    ("decode", 2.6, 0.01, {"live": 5, "state_kinds": "ssm1+window",
                           "live_positions": 7}),
]


def test_what_the_engine_did_comes_from_its_spans():
    did = reader.traced_work(SPANS)
    assert did["prompts"] == [700, 9000]
    assert sorted(did["steps"]) == [(2, 9000, 1024), (2, 9002, 1024)]


def test_a_share_is_counted_work_over_device_time_over_the_peak(
        evidence, monkeypatch):
    monkeypatch.setattr(reader, "traced_spans", lambda ev: SPANS)
    monkeypatch.setattr(reader, "expert_shares", lambda: None)
    s = fl.sizes_of(evidence["ctx"].config, 5)
    ev = dict(evidence)
    runs, secs = trace_reduce.module_seconds(
        ev["trace"], ev["trace_window"])["jit_step"]
    assert runs == 2
    got = reader.read(dict(ev), {"work": "prefill", "module": "jit_step"})
    assert got == pytest.approx(
        100 * fl.prefill_flops(s, [700, 9000]) / secs / 197e12)
    # the synthetic trace's 2 x 60 us lie under attention/flash_fwd: a
    # work that asks for EVERY one of its scopes finds them under both
    # names and under no third
    got = reader.read(dict(ev), {"work": "window_flash",
                                 "scopes": ["attention", "flash_fwd"]})
    assert got == pytest.approx(
        100 * fl.window_flops(s, [700, 9000]) / 120e-6 / 197e12)
    assert reader.read(dict(ev), {"work": "window_flash",
                                  "scopes": ["swa", "flash_fwd"]}) is None
    got = reader.read(dict(ev), {"work": "decode_ms", "module": "jit_step"})
    assert got == pytest.approx(1e3 * secs / runs)
    touched = fl.touched_experts(s, 2)
    got = reader.read(dict(ev), {"work": "decode_bytes",
                                 "module": "jit_step"})
    assert got == pytest.approx(
        100 * fl.decode_step_bytes(s, 2, 9001, 1024, touched) / 819e9
        / (secs / runs))
    got = reader.read(dict(ev), {"work": "walk_bytes",
                                 "scopes": ["flash_fwd"]})
    assert got == pytest.approx(
        100 * (9000 + 9002) * 8192 / 819e9 / 120e-6)
    got = reader.read(dict(ev), {"work": "ring_bytes",
                                 "scopes": ["flash_fwd"]})
    assert got == pytest.approx(
        100 * 2 * 1024 * 3 * 4096 / 819e9 / 120e-6)
    got = reader.read(dict(ev), {"work": "experts",
                                 "scopes": ["flash_fwd"]})
    least = fl.expert_flops(s, 9700) / 197e12 \
        + 2 * 4 * touched * 3_145_728 * 2 / 819e9
    assert got == pytest.approx(100 * least / 120e-6)


def test_the_touched_experts_come_from_the_counters(monkeypatch):
    """An uneven router's step reads fewer experts than an even one's:
    the engine's per-expert counts say how uneven."""
    s = fl.sizes_of(Manifest(ROOT).config(CONFIG), 5)
    sample = lambda layer, e, v: SimpleNamespace(  # noqa: E731
        labels={"layer": str(layer), "expert": str(e), "held": "1"},
        value=v)
    even = [sample(layer, e, 10.0) for layer in (1, 2) for e in range(256)]
    monkeypatch.setattr(reader.expert_load, "_samples", lambda fam: even)
    assert reader.touched(s, 40, reader.expert_shares()) == pytest.approx(
        fl.touched_experts(s, 40))
    skew = [sample(1, e, 100.0 if e < 32 else 1.0) for e in range(256)]
    monkeypatch.setattr(reader.expert_load, "_samples", lambda fam: skew)
    assert reader.touched(s, 40, reader.expert_shares()) \
        < 0.8 * fl.touched_experts(s, 40)
    monkeypatch.setattr(reader.expert_load, "_samples", lambda fam: [])
    assert reader.expert_shares() is None
    assert reader.touched(s, 40, None) == fl.touched_experts(s, 40)


@pytest.mark.parametrize("change", [
    "no_spans", "no_steps", {"trace": None}, {"trace_window": None},
    "rehearsal", "another_config", "absent_scope"])
def test_with_nothing_to_read_the_reader_says_none(evidence, monkeypatch,
                                                   change):
    """A program from before the spans (the parent, laid under this PR's
    benchmark files) reports none of these metrics and raises nothing."""
    ev, params = dict(evidence), {"work": "ring_bytes",
                                  "scopes": ["flash_fwd"]}
    spans = SPANS
    if change == "no_spans":
        spans = None
    elif change == "no_steps":
        spans = [sp for sp in SPANS if sp[0] == "prefill"]
    elif change == "rehearsal":
        ev["ctx"] = SimpleNamespace(**{**vars(ev["ctx"]), "rehearsal": True})
    elif change == "another_config":
        ev["ctx"] = SimpleNamespace(**{**vars(ev["ctx"]),
                                       "config": {"hidden_size": 4544}})
    elif change == "absent_scope":
        params = {"work": "ring_bytes", "scopes": ["ring_decode"]}
    else:
        ev.update(change)
    monkeypatch.setattr(reader, "traced_spans", lambda ev: spans)
    monkeypatch.setattr(reader, "_spans_in", lambda ev, window: spans)
    assert reader.read(ev, params) is None


def test_a_window_without_a_step_reads_the_session_and_else_nothing(
        evidence, monkeypatch):
    """The decode works: over the traced window where it holds a step,
    else over the whole session, else left out; their files say so."""
    prefills = [sp for sp in SPANS if sp[0] == "prefill"]
    monkeypatch.setattr(reader, "expert_shares", lambda: None)
    monkeypatch.setattr(reader, "traced_spans", lambda ev: prefills)
    monkeypatch.setattr(reader, "_spans_in", lambda ev, window: SPANS)
    ev = dict(evidence, trace_window=(100e3, 500e3))     # one run of two
    got = reader.read(dict(ev), {"work": "decode_ms", "module": "jit_step"})
    runs, secs = trace_reduce.module_seconds(
        ev["trace"], trace_reduce.window_of(ev["trace"]))["jit_step"]
    assert runs == 2 and got == pytest.approx(1e3 * secs / runs)
    monkeypatch.setattr(reader, "_spans_in", lambda ev, window: prefills)
    assert reader.read(dict(ev), {"work": "decode_ms",
                                  "module": "jit_step"}) is None
    for name in ("decode_step_ms", "decode_hbm_share",
                 "ring_decode_roofline", "walk_hbm_share"):
        what = Manifest(ROOT).layer_metric(f"{name}.codectx")["what"]
        assert "left out" in what and "whole profile session" in what


# --- the rehearsal ----------------------------------------------------------

def test_the_cell_rehearses_to_its_result_line(man, trace=2):
    """``--trace 2``: a ``--trace 0`` run up to the window's end, then the
    traced phase: one rehearsal holds both."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         str(BIG), "--seconds", "0.3", "--trace", str(trace),
         "--cpu-rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    assert [l for l in lines if l.startswith("{")] == lines[-1:]
    line = json.loads(lines[-1])
    assert line["correct"], proc.stdout[-3000:]
    assert set(line["compared"]) >= {
        "logprob_median_gap_prompt", "logprob_median_gap_decode",
        "logprob_mean_gap", "check_sequences_cut_or_not_finite",
        "compiles_in_window", "bad_finishes", "backlog_ran_out"}
    limits = man.traffic(MIX)["check"]["limits"]
    assert line["compared"]["logprob_median_gap_prompt"]["limit"] == \
        limits["median_prompt"]
    assert line["compared"]["logprob_mean_gap"]["limit"] == limits["mean"]
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) >= {"serve_tokens_per_s", "setup_s"}
    assert line["failed"] == 0
    if trace:
        # of the per-layer metrics the one that reads the program's
        # counters whatever the window held (the pool's gauge is sampled
        # at the window's tokens, and 0.3 s may hold none)
        assert "expert_load_max_over_mean.codectx" in line["metrics"]
        assert "prefill_mfu.codectx" not in line["metrics"]
    # five layers at tiny widths, whatever depth the harness asked for
    assert "5 layers" in proc.stdout
