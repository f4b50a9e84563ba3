"""An open-loop cell judges only a tail it has the samples for: the
highest percentile with ten samples beyond it (``stats.samples_beyond``),
at a stated share of a knee that its mix file's sweep table shows."""

import math
import re
import time

import numpy as np
import pytest

from benchmarks import serving, stats, traffic
from benchmarks.manifest import ROOT, Manifest

MAN = Manifest(ROOT)
OPEN_CELLS = [w["name"] for w in MAN.doc["workloads"]
              if MAN.traffic(w["traffic"])["kind"] == "serve_open"]
LATENCY = re.compile(r"^(ttft|itl)_(p\d+|mean)_ms$")
SHARE = re.compile(r"\b(0\.\d+) of the knee")


def parsed(metric, key):
    """(metric name, "ttft" | "itl", percentile, or None for the mean)."""
    kind, stat = LATENCY.match(key).groups()
    return metric, kind, None if stat == "mean" else int(stat[1:])


def judged(cell):
    """The cell's end-to-end latency metrics, as ``parsed`` gives them."""
    return [parsed(m["name"], m["name"])
            for m in MAN.metrics_of(cell, "end_to_end")
            if LATENCY.match(m["name"])]


def test_there_is_an_open_loop_cell():
    assert OPEN_CELLS


@pytest.mark.parametrize("cell", OPEN_CELLS)
def test_every_judged_percentile_has_ten_samples_beyond_it(cell):
    mix = MAN.traffic(MAN.cell(cell)["traffic"])
    seconds = MAN.doc["run_seconds"]
    metrics = judged(cell)
    assert {kind for _n, kind, _p in metrics} == {"ttft", "itl"}
    # what the schedule itself puts into the window (the same for every
    # seed), beside the issue's rate x seconds
    reqs = [r for r in traffic.serve_requests(mix, 1, seconds, 65024)
            if 0.0 <= r.due_s < seconds]
    n = {"ttft": min(len(reqs), int(mix["rate_rps"] * seconds)),
         "itl": sum(r.max_new_tokens - 1 for r in reqs)}
    for name, kind, p in metrics:
        if p is None:        # a mean is taken over all of the samples
            assert n[kind] >= 100, (name, n[kind])
            continue
        assert stats.samples_beyond(n[kind], p) >= 10, (name, n[kind])
        assert p in serving.PERCENTILES, name


def recorded(cell):
    """The percentiles the cell records beside them: its per-layer
    metrics read by ``readers/latency.py``, as ``judged`` gives them."""
    specs = [(m["name"], MAN.layer_metric(m["name"]))
             for m in MAN.metrics_of(cell, "per_layer")]
    return [parsed(name, spec["params"]["name"]) for name, spec in specs
            if spec["reader"] == "latency"]


@pytest.mark.parametrize("cell", OPEN_CELLS)
def test_the_highest_tail_the_window_can_carry_is_reported(cell):
    """Of the percentiles the harness computes, the highest TTFT one with
    ten samples beyond it is judged or at least recorded, and none above
    it is; a recorded percentile moves the judged one of its kind."""
    mix = MAN.traffic(MAN.cell(cell)["traffic"])
    n = int(mix["rate_rps"] * MAN.doc["run_seconds"])
    best = max(p for p in serving.PERCENTILES
               if stats.samples_beyond(n, p) >= 10)
    both = judged(cell) + recorded(cell)
    assert max(p for _n, kind, p in both
               if kind == "ttft" and p is not None) == best
    names = {n for n, _k, _p in judged(cell)}
    for name, kind, _p in recorded(cell):
        moves = MAN.layer_metric(name)["moves"]
        assert moves in names and moves.startswith(kind)


def test_the_latency_reader_reads_the_report_and_nothing_else():
    from benchmarks.readers import latency

    ev = {"latency": {"ttft_p90_ms": 158.0, "n_ttft": 119}}
    assert latency.read(ev, {"name": "ttft_p90_ms"}) == 158.0
    assert latency.read(ev, {"name": "itl_p99_ms"}) is None
    assert latency.read({}, {"name": "ttft_p90_ms"}) is None


@pytest.mark.parametrize("cell", OPEN_CELLS)
def test_the_cell_and_its_mix_name_the_same_share_of_the_knee(cell):
    why = MAN.cell(cell)["why"]
    mix = MAN.traffic(MAN.cell(cell)["traffic"])
    (in_cell,), (in_mix,) = SHARE.findall(why), set(SHARE.findall(
        mix["rate_why"]))
    assert in_cell == in_mix == str(mix["knee_share"])
    assert f"{mix['rate_rps']}/s" in why
    # the rate is that share of the knee the sweep's table shows,
    # rounded down to 0.1 a second
    assert mix["rate_rps"] == pytest.approx(
        math.floor(10 * mix["knee_share"] * mix["knee_rps"] + 1e-9) / 10)
    rows = {row["rate_rps"]: row for row in mix["sweep_table"]}
    assert sorted(rows) == sorted(mix["sweep_rates"])
    assert mix["knee_rps"] in rows
    kept_up = [r for r, row in rows.items()
               if row["queue_end"] <= row["queue_start"]
               and row["unfinished"] == 0]
    assert mix["knee_rps"] == max(kept_up)


@pytest.mark.parametrize("cell", OPEN_CELLS)
def test_the_window_opens_on_a_steady_queue(cell):
    """``lead_s`` is at least three mean request lifetimes (the mix says
    how long one is), and the drain outlasts the longest answer."""
    mix = MAN.traffic(MAN.cell(cell)["traffic"])
    assert mix["lead_s"] >= 3 * mix["request_lifetime_s"]
    assert mix["lead_s"] >= 12.0
    longest = mix["output_tokens"]["max"] * mix["step_ms"] / 1e3
    assert mix["drain_s"] >= 2 * longest


def test_latency_report_gives_the_percentiles_and_the_counts():
    rng = np.random.default_rng(34)
    served = []
    for _ in range(150):
        s = serving.Served(due=100.0, prompt_len=8, max_new=30, counted=True)
        first = 100.0 + rng.lognormal(-2.0, 0.5)
        s.stamps = list(first + np.cumsum(
            np.r_[0.0, rng.lognormal(-3.9, 0.1, size=29)]))
        served.append(s)
    served.append(serving.Served(100.0, 8, 30, True))    # never answered
    rep = serving.latency_report(served)
    assert rep["n_ttft"] == 150 and rep["n_gaps"] == 150 * 29
    ttft = [1e3 * (s.stamps[0] - s.due) for s in served if s.stamps]
    gaps = [1e3 * (b - a) for s in served
            for a, b in zip(s.stamps, s.stamps[1:])]
    for p in serving.PERCENTILES:
        assert rep[f"ttft_p{p}_ms"] == pytest.approx(np.percentile(ttft, p))
        assert rep[f"itl_p{p}_ms"] == pytest.approx(np.percentile(gaps, p))
    assert rep["ttft_mean_ms"] == pytest.approx(np.mean(ttft))
    assert rep["itl_mean_ms"] == pytest.approx(np.mean(gaps))
    assert {"ttft_p50_ms", "ttft_p90_ms", "itl_p50_ms", "itl_p98_ms",
            "itl_p99_ms"} <= set(rep)
    said = serving.tail_counts(
        rep, ["ttft_p90_ms", "itl_p99_ms", "ttft_mean_ms"])
    assert "over 150 samples, 15 beyond it" in said
    assert f"over {150 * 29} samples, 43 beyond it" in said
    assert said.endswith("ms over 150 samples")
    assert serving.latency_report([]) == {"n_ttft": 0, "n_gaps": 0}


def test_the_sweeps_table_keeps_its_columns_and_adds_the_new_ones(capsys):
    """One rate of a sweep through a stand-in engine that answers at
    once: the header names the old columns first, in their old order."""
    from types import SimpleNamespace

    from benchmarks.kinds import serve_open

    class Engine:
        queue = ()

    def submit(s, prompt):
        s.submitted = time.perf_counter()
        s.stamps = [s.submitted + 0.001 * i for i in range(s.max_new)]

    mix = dict(MAN.traffic(MAN.cell(OPEN_CELLS[0])["traffic"]),
               sweep_rates=[40.0], sweep_seconds=0.5, lead_s=0.1,
               drain_s=1.0)
    sv = SimpleNamespace(mix=mix, engine=Engine(), submit=submit,
                         model=SimpleNamespace(vocab_size=512),
                         wait_idle=lambda served, until: None)
    serve_open.sweep(SimpleNamespace(seed=3), sv)
    head, row = [l.split("sweep: ")[1].split() for l in
                 capsys.readouterr().out.splitlines() if "sweep: " in l]
    assert head[:12] == [
        "rate_rps", "offered", "completed_in_window", "completed_rps",
        "queue_start", "queue_end", "ttft_p50_ms", "ttft_p95_ms",
        "itl_p50_ms", "itl_p95_ms", "unfinished", "late_max_ms"]
    assert head[12:] == ["ttft_p90_ms", "itl_p99_ms", "n_ttft", "n_gaps"]
    assert len(row) == len(head) and float(row[0]) == 40.0
    assert int(row[head.index("n_ttft")]) == int(row[1])
