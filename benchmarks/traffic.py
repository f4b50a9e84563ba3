"""The one traffic generator: a mix is a data file under ``traffic/``.

A mix names a ``kind`` and that kind's parameters.  The lengths and
arrival gaps are the stratified quantiles of the mix's distributions,
put in an order that the mix's own ``schedule_seed`` fixes: the schedule
belongs to the mix.  ``--seed`` draws the tokens (and, in the runners,
the weights).  So every seed offers the same work at the same times, and
two runs differ by the system's own noise; on the chip, seeds that also
reordered the requests moved a 51 s window's tails and token counts by
far more than two runs of one seed (PERF.md, PR 23).  Another schedule
is another mix: a new data file.

Kinds:

* ``train`` — token sequences for ``training.driver.pretrain``.
* ``serve_open`` — an open loop: arrivals by a Poisson process of a fixed
  ``rate_rps``, starting ``lead_s`` before the measured window.
* ``serve_backlog`` — ``requests`` requests all due at t = 0.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np


def host_seed(seed: int, salt: int = 0) -> np.random.Generator:
    """A generator for any whole-number ``--seed`` (they pass 2**31)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), salt]))


def device_seed(seed: int) -> int:
    """``--seed`` folded into the 31 bits ``jax.random.key`` takes."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def stratified(dist: dict, n: int) -> List[int]:
    """``n`` whole-number draws that are the quantiles (i + 1/2) / n of
    ``dist``, in rising order: the same multiset for every seed."""
    us = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "fixed":
        xs = [float(dist["value"])] * n
    elif kind == "uniform":
        xs = [dist["min"] + u * (dist["max"] - dist["min"]) for u in us]
    elif kind == "lognormal":
        nd = statistics.NormalDist()
        mu = math.log(dist["median"])
        xs = [math.exp(mu + dist["sigma"] * nd.inv_cdf(u)) for u in us]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", -math.inf), dist.get("max", math.inf)
    return [int(round(min(max(x, lo), hi))) for x in xs]


def exponential_gaps(rate: float, n: int) -> List[float]:
    """The quantiles (i + 1/2) / n of the gap between Poisson arrivals
    at ``rate`` a second."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


@dataclasses.dataclass
class Request:
    due_s: float          # relative to the start of the measured window
    prompt: List[int]
    max_new_tokens: int


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int,
                   rate_rps: float | None = None) -> List[Request]:
    """The requests of one run of a ``serve_*`` mix, in order of due time.
    Tokens avoid id 0 and the last id (pad and the null tokenizer's
    end-of-document)."""
    kind = mix["kind"]
    rng = host_seed(seed, 1)                          # the tokens
    order = host_seed(int(mix["schedule_seed"]), 1)   # the schedule
    if kind == "serve_open":
        rate = float(rate_rps if rate_rps is not None else mix["rate_rps"])
        lead = float(mix["lead_s"])
        n = max(1, int(round(rate * (lead + seconds))))
        gaps = np.asarray(exponential_gaps(rate, n))
        due = np.cumsum(order.permutation(gaps)) - lead
    elif kind == "serve_backlog":
        n = int(mix["requests"])
        due = np.zeros(n)
    else:
        raise ValueError(f"{kind!r} is not a serving mix")
    prompts = order.permutation(stratified(mix["prompt_tokens"], n))
    outputs = order.permutation(stratified(mix["output_tokens"], n))
    return [Request(float(due[i]),
                    rng.integers(1, vocab - 1, size=int(prompts[i])).tolist(),
                    int(outputs[i]))
            for i in range(n)]


def train_dataset(mix: dict, seed: int, vocab: int) -> list:
    """``dataset_steps`` global batches of ``seq_length + 1`` tokens, as
    the driver's ``BatchIterator`` takes them.  Token ranks follow 1/rank
    (as ``chip_smoke.py``): a few steps can learn the marginal, so a
    falling loss shows that the optimizer is wired."""
    if mix["kind"] != "train":
        raise ValueError(f"{mix['kind']!r} is not a training mix")
    rng = host_seed(seed, 2)
    p = 1.0 / np.arange(1, vocab + 1)
    p /= p.sum()
    n = int(mix["sequences_per_step"]) * int(mix["dataset_steps"])
    text = rng.choice(vocab, size=(n, int(mix["seq_length"]) + 1), p=p)
    text = rng.permutation(vocab)[text]     # rank -> a token id of its own
    return [{"text": row.astype(np.int32)} for row in text]
