import collections

import pytest

from benchmarks import traffic
from benchmarks.manifest import ROOT, Manifest

BIG = 3_000_000_019          # the driver's seeds pass 2**31


@pytest.fixture(scope="module")
def chat():
    return Manifest(ROOT).traffic("chat-knee")


def shape(reqs):
    return [(round(r.due_s, 9), len(r.prompt), r.max_new_tokens) for r in reqs]


def test_same_seed_same_requests(chat):
    a = traffic.serve_requests(chat, BIG, 20.0, 65024)
    b = traffic.serve_requests(chat, BIG, 20.0, 65024)
    assert shape(a) == shape(b)
    assert [r.prompt for r in a] == [r.prompt for r in b]


def test_another_seed_same_schedule_other_tokens(chat):
    a = traffic.serve_requests(chat, BIG, 20.0, 65024)
    b = traffic.serve_requests(chat, BIG + 1, 20.0, 65024)
    assert shape(a) == shape(b)
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    assert len(a) == round(chat["rate_rps"] * (chat["lead_s"] + 20))


def test_another_schedule_seed_same_work_in_another_order(chat):
    a = traffic.serve_requests(chat, BIG, 20.0, 65024)
    b = traffic.serve_requests(dict(chat, schedule_seed=1), BIG, 20.0, 65024)
    assert shape(a) != shape(b)
    for pick in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert collections.Counter(map(pick, a)) == collections.Counter(
            map(pick, b))

    def gaps(rs):
        due = [-chat["lead_s"]] + [r.due_s for r in rs]
        return sorted(round(y - x, 6) for x, y in zip(due, due[1:]))

    assert gaps(a) == gaps(b)


def test_open_loop_schedule(chat):
    reqs = traffic.serve_requests(chat, 7, 30.0, 65024)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] < 0 < due[-1]
    assert -chat["lead_s"] <= due[0] and due[-1] < 30.0 * 1.3
    p, o = chat["prompt_tokens"], chat["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in reqs)
    assert all(1 <= t < 65023 for r in reqs for t in r.prompt)
    assert all(len(r.prompt) + r.max_new_tokens <= 2048 for r in reqs)


def test_stratified_quantiles():
    xs = traffic.stratified({"dist": "lognormal", "median": 256,
                             "sigma": 0.9, "min": 32, "max": 1536}, 101)
    assert xs == sorted(xs) and xs[50] == 256 and xs[0] >= 32
    assert traffic.stratified({"dist": "uniform", "min": 10, "max": 20},
                              5) == [11, 13, 15, 17, 19]
    assert traffic.stratified({"dist": "fixed", "value": 32}, 3) == [32] * 3
    gaps = traffic.exponential_gaps(2.0, 1000)
    assert abs(sum(gaps) / 1000 - 0.5) < 0.01


def test_backlog_and_train_mixes():
    man = Manifest(ROOT)
    reqs = traffic.serve_requests(man.traffic("batch"), BIG, 51.0, 65024)
    assert len(reqs) == 400 and {r.due_s for r in reqs} == {0.0}
    assert {r.max_new_tokens for r in reqs} == {32}
    mix = dict(man.traffic("pretrain-6x2048"), dataset_steps=2, seq_length=64)
    a, b = (traffic.train_dataset(mix, s, 512) for s in (BIG, BIG))
    c = traffic.train_dataset(mix, BIG + 1, 512)
    assert len(a) == 12 and a[0]["text"].shape == (65,)
    assert all((x["text"] == y["text"]).all() for x, y in zip(a, b))
    assert any((x["text"] != y["text"]).any() for x, y in zip(a, c))
    assert 0 <= traffic.device_seed(BIG) < 2 ** 31
