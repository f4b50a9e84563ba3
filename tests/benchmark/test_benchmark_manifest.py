import json
import re
import shutil

import pytest

from benchmarks.manifest import ROOT, Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def all_metrics(man):
    return man.doc["end_to_end"] + man.doc["per_layer"]


def test_manifest_keys_and_limits(man):
    doc = man.doc
    assert set(doc) - {"trace_in_run"} == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert doc.get("trace_in_run", True) is True
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert doc["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= doc["run_seconds"] <= 51 and isinstance(doc["run_seconds"], int)
    assert 1 <= len(doc["workloads"]) <= 24 and 1 <= len(doc["configs"]) <= 24
    assert 1 <= len(doc["end_to_end"]) <= 16 and len(doc["per_layer"]) <= 128
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 4)


def test_every_name_and_unit_is_legal(man):
    doc = man.doc
    names = [x["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in doc[group]]
    names += [w["traffic"] for w in doc["workloads"]]
    names += [k for c in doc["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for group in ("configs", "workloads"):
        got = [x["name"] for x in doc[group]]
        assert len(got) == len(set(got))
    got = [m["name"] for m in all_metrics(man)]
    assert len(got) == len(set(got))
    for m in all_metrics(man):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in doc["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for text in ([w["why"] for w in doc["workloads"]]
                 + [c["why"] for c in doc["configs"]]
                 + [c["source"] for c in doc["configs"]]
                 + [m["layer"] for m in doc["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_each_cell_finds_its_files_by_name(man):
    used = set()
    for w in man.doc["workloads"]:
        config = man.config(w["config"])
        used.add(w["config"])
        mix = man.traffic(w["traffic"])
        assert (man.bench_dir / "kinds" / f"{mix['kind']}.py").is_file()
        assert (man.bench_dir / "reference"
                / f"{config['reference']}.py").is_file()
        e2e = {m["name"] for m in man.metrics_of(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = man.metrics_of(w["name"], "per_layer")
        assert layer
        for m in layer:
            spec = man.layer_metric(m["name"])
            assert callable(man.reader(spec["reader"]).read)
            assert m["moves"] in e2e, (w["name"], m["name"])
    assert used == {c["name"] for c in man.doc["configs"]}
    files = [c["file"] for c in man.doc["configs"]]
    assert len(files) == len(set(files))
    for c in man.doc["configs"]:
        assert any(c["file"].startswith(p + "/") for p in man.doc["paths"])
        assert set(c["reduced"]) == set(man.config(c["name"])["reduced"])


def test_metric_files_agree_with_the_manifest(man):
    for m in man.doc["per_layer"]:
        spec = man.layer_metric(m["name"])
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec.get("workloads") == m.get("workloads")
    on_disk = {p.stem for p in (man.bench_dir / "layer_metrics").glob("*.json")}
    assert on_disk == {m["name"] for m in man.doc["per_layer"]}
    layers = {}
    for m in man.doc["per_layer"]:       # one spelling a layer
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_no_width_is_reduced(man):
    width = re.compile(r"hidden_size|intermediate|ffn|latent|state|proj|_dim$|"
                       r"_rank$|head_dim|expansion|experts_per")
    for c in man.doc["configs"]:
        assert not [k for k in c["reduced"] if width.search(k)]


def test_additions_need_no_edit_of_an_existing_file(tmp_path):
    """A configuration, a mix of an existing kind, a cell and a per-layer
    metric with a reader of its own: new files and manifest entries."""
    root = tmp_path / "copy"
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    b = root / "benchmarks"
    cfg = json.loads((b / "configs" / "falcon-7b.json").read_text())
    cfg["by_kind"]["serve_open"]["num_hidden_layers"] = 16
    (b / "configs" / "falcon-7b-half.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "chat-knee.json").read_text())
    mix["rate_rps"] = 0.5
    (b / "traffic" / "chat-slow.json").write_text(json.dumps(mix))
    (b / "readers" / "always_seven.py").write_text(
        "def read(evidence, params):\n    return 7.0 * params['times']\n")
    (b / "layer_metrics" / "sevens.chat-slow.json").write_text(json.dumps({
        "layer": "scheduler (engine.py:_loop_body)", "unit": "ms",
        "better": "lower", "source": "program_counter",
        "moves": "itl_p50_ms", "workloads": ["half-chat-slow"],
        "reader": "always_seven", "params": {"times": 3}}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "falcon-7b-half", "source": cfg["source"],
        "file": "benchmarks/configs/falcon-7b-half.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    doc["workloads"].append({
        "name": "half-chat-slow", "config": "falcon-7b-half",
        "traffic": "chat-slow", "chips": 1, "why": "test"})
    for m in doc["end_to_end"]:
        if m["name"] in ("ttft_mean_ms", "itl_p50_ms", "itl_p98_ms"):
            m["workloads"].append("half-chat-slow")
    doc["per_layer"].append({
        "name": "sevens.chat-slow", "unit": "ms", "better": "lower",
        "source": "program_counter",
        "layer": "scheduler (engine.py:_loop_body)", "moves": "itl_p50_ms",
        "workloads": ["half-chat-slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    man = Manifest(root)
    cell = man.cell("half-chat-slow")
    assert man.config(cell["config"])["by_kind"]["serve_open"][
        "num_hidden_layers"] == 16
    assert man.traffic(cell["traffic"])["rate_rps"] == 0.5
    (entry,) = man.metrics_of("half-chat-slow", "per_layer")
    spec = man.layer_metric(entry["name"])
    import importlib.util
    loaded = importlib.util.spec_from_file_location(
        "always_seven", b / "readers" / f"{spec['reader']}.py")
    mod = importlib.util.module_from_spec(loaded)
    loaded.loader.exec_module(mod)
    assert mod.read({}, spec["params"]) == 21.0
    assert {m["name"] for m in man.metrics_of("half-chat-slow", "end_to_end")
            } == {"ttft_mean_ms", "itl_p50_ms", "itl_p98_ms", "setup_s"}
    assert all(p.read_bytes() == data for p, data in before.items())
