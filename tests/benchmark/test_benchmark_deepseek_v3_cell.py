"""The cell ``kanana2-serve-longqa``: its configuration file against the
catalog row's published config, its operation and byte counts against a
hand count, its traffic under the ``serve_backlog`` rules, the comparison
its kind decides ``correct`` by, its per-layer entries and their files,
its reader's arithmetic, and a rehearsal of the cell to its result line."""

import difflib
import inspect
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import flops_deepseek_v3 as fd
from benchmarks import serving, trace_reduce, traffic
from benchmarks.kinds import serve_backlog, serve_backlog_routed as routed
from benchmarks.manifest import ROOT, Manifest
from benchmarks.readers import deepseek_v3_roofline as reader

CELL, CONFIG = "kanana2-serve-longqa", "kanana-2-30b-a3b"
BIG = 3_000_000_019
DATA = Path(__file__).parent / "data"

# config.json of kakaocorp/kanana-2-30b-a3b-instruct-2601, every key of
# the catalog row's ``config``
PUBLISHED = dict(
    attention_bias=False, first_k_dense_replace=1, head_dim=64,
    hidden_act="silu", hidden_size=2048, intermediate_size=6144,
    kv_lora_rank=512, max_position_embeddings=32768,
    model_type="deepseek_v3", moe_intermediate_size=768, moe_layer_freq=1,
    n_group=1, n_routed_experts=128, n_shared_experts=2,
    norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=6,
    num_hidden_layers=48, num_key_value_heads=32, q_lora_rank=None,
    qk_head_dim=192, qk_nope_head_dim=128, qk_rope_head_dim=64,
    rms_norm_eps=1e-06, rope_interleave=True, rope_scaling=None,
    rope_theta=1000000, routed_scaling_factor=2.448, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
    v_head_dim=128, vocab_size=128256)

METRICS = {
    "device_idle_share.longqa", "prefill_tok_per_s.longqa",
    "decode_step_ms.longqa", "moe_share.longqa",
    "expert_load_max_over_mean.longqa", "latent_pool_peak_share.longqa",
    "prefill_mfu.longqa", "decode_hbm_share.longqa", "mla_share.longqa",
    "flash_fwd_roofline.longqa", "mla_decode_roofline.longqa",
    "moe_expert_roofline.longqa"}


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def doc(man):
    return man.config(CONFIG)


def test_the_file_is_the_published_config_but_for_its_depth(man, doc):
    entry = next(c for c in man.doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == doc["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == doc["source"] and "kanana-2-30b-a3b" in \
        entry["source"]
    for key, want in PUBLISHED.items():
        assert doc[key] == (6 if key == "num_hidden_layers" else want), key
    assert doc["published"]["num_hidden_layers"] == 48
    # the floors: the leading dense layer and four expert layers or more,
    # every expert, the whole vocabulary
    assert doc["num_hidden_layers"] - doc["first_k_dense_replace"] >= 4
    assert doc["derived"]["latent_row_width"] == 512 + 64 == 576
    for said in ("stands_for", "left_out", "assumed"):
        assert doc[said]
    engine = doc["serve"]["engine"]
    assert set(doc["serve"]["engine_why"]) == set(engine)
    assert engine["prefix_cache_blocks"] == 0
    # ISSUE 52: 32 to 48, a multiple of 8
    assert engine["max_batch_size"] in (32, 40, 48)
    assert "independent" in doc["assumed"]["experts"]
    # what the harness checks the program's preset against
    from megatron_llm_tpu.config import deepseek_v3_config

    model = deepseek_v3_config(doc["preset"]["size"])
    assert (model.num_layers, model.hidden_size, model.head_dim) == (
        6, doc["hidden_size"], doc["head_dim"])
    assert model.kv_heads == doc["derived"]["num_kv_heads"]
    assert model.ffn_size == doc["derived"]["ffn_hidden_size"] == \
        doc["moe_intermediate_size"]
    assert (model.kv_lora_rank, model.qk_nope_head_dim,
            model.qk_rope_head_dim, model.v_head_dim) == (512, 128, 64, 128)
    assert (model.num_experts, model.moe_top_k, model.moe_dense_ffn_size,
            model.moe_shared_expert_size, model.moe_routed_scaling) == (
        128, 6, 6144, 1536, 2.448)
    assert model.latent_row_width == 576


def test_weights_operations_and_bytes_against_a_hand_count(doc):
    s = fd.sizes_of(doc)
    p = fd.layer_params(s)
    # by hand.  W_q 2048 x 6144, W_kva 2048 x 576, W_kvb 512 x 8192, W_o
    # 4096 x 2048, the latent's norm 512
    assert p["attention"] == (12_582_912 + 1_179_648 + 4_194_304
                              + 8_388_608 + 512) == 26_345_984
    assert p["dense_mlp"] == 3 * 2048 * 6144 == 37_748_736
    assert p["router"] == 2048 * 128 + 128 == 262_272
    assert p["expert"] == 3 * 2048 * 768 == 4_718_592
    assert p["shared_expert"] == 3 * 2048 * 1536 == 9_437_184
    dense_layer = 26_345_984 + 4096 + 37_748_736
    expert_layer = (26_345_984 + 4096 + 262_272 + 128 * 4_718_592
                    + 9_437_184)
    assert (dense_layer, expert_layer) == (64_098_816, 640_029_312)
    params = dense_layer + 5 * expert_layer + 2 * 128256 * 2048 + 2048
    assert fd.param_count(s) == params == 3_789_584_000
    assert fd.weight_bytes(s) == 2 * params == 7_579_168_000   # 7.58 GB
    # the published model by the same count: 30.7 B
    whole = fd.sizes_of({**doc, **doc["published"]})
    assert round(fd.param_count(whole) / 1e9, 2) == 30.67
    assert fd.row_bytes(s) == 1152
    # one prompt of 10240 positions: a position attends 5120 on average,
    # a score over 192 columns and a weighted sum over 128
    attn = 2 * (26_345_984 - 512)
    dense = attn + 2 * 37_748_736
    expert = attn + 2 * (2048 * 128 + 9_437_184) + 6 * 2 * 4_718_592
    flash = 6 * 32 * (192 + 128) * 10240 * 10240
    assert fd.flash_flops(s, 10240 ** 2) == flash
    want = 10240 * (dense + 5 * expert) + flash + 2 * 2048 * 128256
    assert fd.prefill_flops(s, 10240, 1, 10240 ** 2) == want
    assert 1.39e9 < want / 10240 < 1.41e9         # ~1.4 GFLOP a token
    # a decode step of 40 slots reads ~85 % of a layer's experts
    assert fd.chosen_experts(s, 40) == pytest.approx(
        128 * (1 - (1 - 6 / 128) ** 40))
    assert 0.84 < fd.chosen_experts(s, 40) / 128 < 0.86
    step = fd.decode_step_bytes(s, 40, 40 * 10500)
    rows = 40 * 10500 * 1152 * 6
    held = 5 * fd.chosen_experts(s, 40) * 4_718_592
    rest = (dense_layer + 5 * (26_345_984 + 4096 + 262_272 + 9_437_184)
            + 128256 * 2048 + 2048)
    assert step == pytest.approx(2 * (held + rest) + rows)
    # the latent walk: bytes bound it on a v5e (60 FLOP a byte)
    by_bytes = 6 * 40 * 10500 * 1152 / 819e9
    by_flops = 6 * 40 * 10500 * 32 * 1088 * 2 / 197e12
    assert by_bytes > by_flops
    assert fd.latent_walk_seconds(s, 40 * 10500, 819e9, 197e12) == \
        pytest.approx(by_bytes)


def test_the_mix_under_the_backlog_rules(man, doc):
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longqa", 1)
    assert CELL in next(m for m in man.doc["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    mix = man.traffic("longqa")
    assert mix["kind"] == "serve_backlog_routed" and mix["kind_why"]
    assert mix["schedule_seed"] == 23
    assert mix["requests"] == 400 and mix["warmup_output_tokens"] == 4
    check = dict(mix["check"])
    limits, why = check.pop("limits"), check.pop("limits_why")
    assert check == {"sequences": 3, "prompt_tokens": 1500,
                     "output_tokens": 32}
    assert set(limits) == {"median_prompt", "median_decode", "mean"}
    assert set(why) == set(limits) and all(why.values())
    assert doc["by_kind"]["serve_backlog_routed"]["num_hidden_layers"] == 6
    # the long-document cell's prompts: two configurations, one prompt mix
    assert mix["prompt_tokens"] == man.traffic("longdoc")["prompt_tokens"]
    reqs = traffic.serve_requests({**mix, "kind": "serve_backlog"}, BIG,
                                  51.0, doc["vocab_size"])
    assert len(reqs) == 400 and {r.due_s for r in reqs} == {0.0}
    assert {r.max_new_tokens for r in reqs} == {512}
    lengths = [len(r.prompt) for r in reqs]
    assert 4096 <= min(lengths) < 4200 and 16300 < max(lengths) <= 16384
    assert all(0 < t < doc["vocab_size"] - 1 for t in reqs[0].prompt)
    engine = doc["serve"]["engine"]
    assert engine["max_seq_len"] == 16384 + 512
    assert engine["max_queue_size"] > mix["requests"]
    assert len({-(-n // engine["prefill_bucket"]) for n in lengths}) <= 8
    assert engine["kv_block_size"] % 128 == 0     # the latent walk's tile


def test_every_metric_of_the_cell_has_its_entry_and_its_file(man):
    entries = man.metrics_of(CELL, "per_layer")
    assert {m["name"] for m in entries} == METRICS
    for m in entries:
        assert m["moves"] == "serve_tokens_per_s"
        assert m["workloads"] == [CELL]
        spec = man.layer_metric(m["name"])
        for key in ("layer", "unit", "better", "source", "moves",
                    "workloads"):
            assert spec[key] == m[key], (m["name"], key)
        assert hasattr(man.reader(spec["reader"]), "read")
    assert {m["name"] for m in man.metrics_of(CELL, "end_to_end")} == {
        "serve_tokens_per_s", "setup_s"}


def test_the_cell_before_this_one_stands_as_its_own_tests_hold_it(man):
    """PR 49's cell test holds its manifest entries to the END of their
    lists, which a later PR's appended entries end: since this cell was
    appended, ``test_benchmark_granite_hybrid_cell.py::
    test_the_mix_under_the_backlog_rules`` and ``::
    test_every_metric_of_the_cell_moves_its_throughput`` fail on those four
    lines, visibly (a file under tests/benchmark/ is the benchmark's own:
    a ``benchmark`` PR drops the four assertions).  Here their whole
    bodies run, unedited, against the manifest cut behind PR 49's entries:
    nothing before this PR's entries moved, and this PR's follow them."""
    import copy

    import test_benchmark_granite_hybrid_cell as before

    then = copy.copy(man)
    then.doc = copy.deepcopy(man.doc)

    def cut_behind(entries, name):
        at = max(i for i, e in enumerate(entries) if e["name"] == name)
        return entries[:at + 1], entries[at + 1:]

    then.doc["configs"], later = cut_behind(man.doc["configs"],
                                            before.CONFIG)
    assert [c["name"] for c in later] == [CONFIG]
    then.doc["workloads"], later = cut_behind(man.doc["workloads"],
                                              before.CELL)
    assert [w["name"] for w in later] == [CELL]
    then.doc["per_layer"], later = cut_behind(man.doc["per_layer"],
                                              "mlp_share.shortchat")
    assert {m["name"] for m in later} == METRICS
    for m in then.doc["end_to_end"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL
            m["workloads"].remove(CELL)
    before.test_the_mix_under_the_backlog_rules(
        then, then.config(before.CONFIG))
    before.test_every_metric_of_the_cell_moves_its_throughput(then)


# --- the kind's comparison --------------------------------------------------

def test_the_kind_runs_as_a_backlog_does():
    """``run`` is ``serve_backlog.run`` but for whose ``Serving`` it
    builds and the kind it hands the traffic generator."""
    a, b = (inspect.getsource(m.run).splitlines()
            for m in (serve_backlog, routed))
    diff = list(difflib.ndiff(a, b))
    assert [line[2:] for line in diff if line[:2] == "- "] == [
        "    sv = serving.Serving(ctx)",
        "    mix = sv.mix"]
    assert [line[2:] for line in diff if line[:2] == "+ "] == [
        "    sv = Serving(ctx)",
        "    # the requests are a backlog's",
        '    mix = {**sv.mix, "kind": "serve_backlog"}']
    assert issubclass(routed.Serving, serving.Serving)
    assert {name for name in vars(routed.Serving)
            if not name.startswith("__")} == {"check"}


def judged(gaps_of):
    """``Serving.check`` over three sequences of 40 + 9 tokens whose
    engine log-probs lie ``gaps_of(sequence)`` off the reference's."""
    import numpy as np

    sv = object.__new__(routed.Serving)
    sv.ctx = SimpleNamespace(seed=7, config={"reference": "deepseek_v3"})
    sv.mix = {"check": {"sequences": 3, "prompt_tokens": 40,
                        "output_tokens": 9,
                        "limits": {"median_prompt": 0.01,
                                   "median_decode": 0.02, "mean": 0.04}}}
    sv.model = SimpleNamespace(vocab_size=512)
    sv.params, sv.correct, sv.correct_notes, sv.compared = None, True, [], {}
    want = np.linspace(-7.0, -5.0, 48)
    n = iter(range(3))

    def submit(s, prompt, logprobs=False):
        assert logprobs and len(prompt) == 40
        k = next(n)
        s.handle = SimpleNamespace(result=lambda timeout: SimpleNamespace(
            finish_reason="length", tokens=list(prompt) + [1] * 9,
            logprobs=(want + gaps_of(k)).tolist()))

    sv.submit = submit
    ref = SimpleNamespace(meta_of=lambda model: (),
                          token_logprobs=lambda p, tokens, meta: want)
    sys.modules["benchmarks.reference.deepseek_v3"], before = ref, \
        sys.modules.get("benchmarks.reference.deepseek_v3")
    try:
        sv.check()
    finally:
        if before is None:
            del sys.modules["benchmarks.reference.deepseek_v3"]
        else:
            sys.modules["benchmarks.reference.deepseek_v3"] = before
    return sv


def _gaps(prompt=0.005, decode=0.005, changed=()):
    import numpy as np

    def of(k):
        d = np.concatenate([np.full(40, prompt), np.full(8, decode)])
        d[list(changed)] = 1.0
        return d * (-1) ** k
    return of


@pytest.mark.parametrize("case, gaps_of, over", [
    # rounding everywhere and a changed choice at 1 position in 48: the
    # worst position is 1.0, far past serving.py's 0.15, and it stands
    ("changed_choices", _gaps(changed=(3,)), set()),
    ("coarser_prefill", _gaps(prompt=0.012), {"logprob_median_gap_prompt"}),
    ("a_wrong_row_in_the_pool", _gaps(decode=0.03),
     {"logprob_median_gap_decode"}),
    ("a_fault_in_a_minority", _gaps(changed=range(0, 40, 8)),
     {"logprob_mean_gap"}),
])
def test_the_comparison_holds_under_changed_choices_and_no_further(
        case, gaps_of, over):
    sv = judged(gaps_of)
    assert set(sv.compared) == {
        "logprob_median_gap_prompt", "logprob_median_gap_decode",
        "logprob_mean_gap", "check_sequences_cut_or_not_finite"}
    assert {k for k, (got, limit) in sv.compared.items()
            if got > limit} == over
    assert sv.correct == (not over)
    assert "worst position 1.0000" in sv.correct_notes[0] or not (
        case == "changed_choices")


def test_the_decode_positions_are_the_steps_alone():
    """Position ``prompt_len - 1`` holds the first generated token's
    log-prob, which the prefill computed: 40 prefill and 8 decode
    positions a sequence."""
    import numpy as np

    def of(k):
        d = np.zeros(48)
        d[40:] = 0.5          # the steps' positions, and no other
        return d
    sv = judged(of)
    assert sv.compared["logprob_median_gap_prompt"][0] < 1e-6
    assert sv.compared["logprob_median_gap_decode"][0] == pytest.approx(0.5)
    assert "120 prefill and 24 decode positions" in sv.correct_notes[0]


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
                          mix=man.traffic("longqa"),
                          device={"kind": "TPU v5 lite"})
    return {"ctx": ctx, "trace": trace_reduce.load(text_proto=text),
            "trace_window": (100e3, 900e3)}


SPANS = [
    ("prefill", 1.0, 0.2, {"prompt_len": 6000, "attn": "mla_expanded"}),
    ("prefill", 1.3, 0.3, {"prompt_len": 9000, "attn": "mla_expanded"}),
    # a step is the decode spans that share a start: one a live slot
    ("decode", 2.0, 0.01, {"live": 2, "live_positions": 15002,
                           "attn": "mla_absorbed"}),
    ("decode", 2.0, 0.01, {"live": 2, "live_positions": 15002,
                           "attn": "mla_absorbed"}),
    ("decode", 2.1, 0.01, {"live": 2, "live_positions": 15004,
                           "attn": "mla_absorbed"}),
    # another stack's spans carry no such arguments
    ("prefill", 2.5, 0.1, {"prompt_len": 777}),
    ("decode", 2.6, 0.01, {"live": 5}),
]


def test_what_the_engine_did_comes_from_its_spans():
    did = reader.traced_work(SPANS)
    assert did["prompts"] == [6000, 9000]
    assert sorted(did["steps"]) == [(2, 15002), (2, 15004)]


def test_a_share_is_counted_work_over_device_time_over_the_peak(
        evidence, monkeypatch):
    monkeypatch.setattr(reader, "traced_spans", lambda ev: SPANS)
    s = fd.sizes_of(evidence["ctx"].config)
    squares = 6000 ** 2 + 9000 ** 2
    # a traced window that holds the first of the trace's two runs: the
    # prefill's works are read inside it
    ev = dict(evidence, trace_window=(100e3, 500e3))
    runs, secs = trace_reduce.module_seconds(
        ev["trace"], ev["trace_window"])["jit_step"]
    assert runs == 1
    got = reader.read(dict(ev), {"work": "prefill", "module": "jit_step"})
    assert got == pytest.approx(
        100 * fd.prefill_flops(s, 15000, 2, squares) / secs / 197e12)
    got = reader.read(dict(ev), {"work": "flash", "scopes": ["flash_fwd"]})
    assert got == pytest.approx(
        100 * fd.flash_flops(s, squares) / 60e-6 / 197e12)
    got = reader.read(dict(ev), {"work": "experts",
                                 "scopes": ["flash_fwd"]})
    assert fd.expert_flops(s, 15000) == 15000 * 5 * 6 * 2 * 3 * 2048 * 768
    assert got == pytest.approx(
        100 * fd.expert_flops(s, 15000) / 60e-6 / 197e12)
    # a decode step's works over the whole session, first operation to
    # last: both runs (the traced window of this cell holds no step)
    runs, secs = trace_reduce.module_seconds(
        ev["trace"], trace_reduce.window_of(ev["trace"]))["jit_step"]
    assert runs == 2
    got = reader.read(dict(ev), {"work": "decode_ms", "module": "jit_step"})
    assert got == pytest.approx(1e3 * secs / runs)
    got = reader.read(dict(ev), {"work": "decode_bytes",
                                 "module": "jit_step"})
    assert got == pytest.approx(
        100 * fd.decode_step_bytes(s, 2, 15003) / 819e9 / (secs / runs))
    got = reader.read(dict(ev), {"work": "latent_walk",
                                 "scopes": ["flash_fwd"]})
    least = sum(fd.latent_walk_seconds(s, n, 819e9, 197e12)
                for n in (15002, 15004))
    assert got == pytest.approx(100 * least / 120e-6)


@pytest.mark.parametrize("change", [
    "no_spans", "no_steps", {"trace": None}, {"trace_window": None},
    "rehearsal", "another_config", "absent_scope"])
def test_with_nothing_to_read_the_reader_says_none(evidence, monkeypatch,
                                                   change):
    """A program from before the spans (the parent, laid under this
    PR's benchmark files) reports none of these metrics and raises
    nothing."""
    ev, params = dict(evidence), {"work": "latent_walk",
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
        params = {"work": "latent_walk", "scopes": ["mla_decode"]}
    else:
        ev.update(change)
    monkeypatch.setattr(reader, "traced_spans", lambda ev: spans)
    assert reader.read(ev, params) is None


@pytest.mark.parametrize("params", [
    {"work": "decode_ms", "module": "jit_step"},
    {"work": "decode_bytes", "module": "jit_step"},
    {"work": "latent_walk", "scopes": ["flash_fwd"]}])
def test_a_session_without_a_decode_step_reports_no_decode_metric(
        evidence, monkeypatch, params):
    """A window that ends among prefills and a session cut after one slot
    batch of them: the three decode metrics are left out of the line (their
    files say so), the prefill's are read as ever."""
    monkeypatch.setattr(reader, "traced_spans", lambda ev: [
        sp for sp in SPANS if sp[0] == "prefill"])
    assert reader.read(dict(evidence), params) is None
    assert reader.read(dict(evidence), {"work": "flash",
                                        "scopes": ["flash_fwd"]}) > 0
    for name in ("decode_step_ms", "decode_hbm_share",
                 "mla_decode_roofline"):
        what = Manifest(ROOT).layer_metric(f"{name}.longqa")["what"]
        assert "not reported" in what and "no decode step" in what


# --- the rehearsal ----------------------------------------------------------

def test_the_cell_rehearses_to_its_result_line(man):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         str(BIG), "--seconds", "0.3", "--trace", "2", "--cpu-rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    assert [l for l in lines if l.startswith("{")] == lines[-1:]
    line = json.loads(lines[-1])
    assert line["correct"], proc.stdout[-3000:]
    assert set(line["compared"]) == {
        "logprob_median_gap_prompt", "logprob_median_gap_decode",
        "logprob_mean_gap", "check_sequences_cut_or_not_finite",
        "compiles_in_window", "bad_finishes", "backlog_ran_out"}
    limits = man.traffic("longqa")["check"]["limits"]
    assert {k: v["limit"] for k, v in line["compared"].items()
            if k.startswith("logprob")} == {
        "logprob_median_gap_prompt": limits["median_prompt"],
        "logprob_median_gap_decode": limits["median_decode"],
        "logprob_mean_gap": limits["mean"]}
    assert line["device"]["platform"] == "cpu"
    # the end-to-end metrics, and of the per-layer ones those that read
    # no device trace (the CPU backend's has no device plane; the pool's
    # gauge is sampled at a token, and 0.3 s may see none)
    assert set(line["metrics"]) - {"latent_pool_peak_share.longqa"} == {
        "serve_tokens_per_s", "setup_s", "expert_load_max_over_mean.longqa"}
    assert "serve: hidden 64, 4 heads" in proc.stdout
