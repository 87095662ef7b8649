"""The traffic generator: deterministic per seed, the same work for every
seed, and lengths that follow the stated distributions."""
import json
from pathlib import Path
from statistics import NormalDist

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name, **kw):
    m = json.loads((MIXES / f"{name}.json").read_text())
    m.update(kw)
    return m


@pytest.mark.parametrize("name", ["batch-decode", "chat"])
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), 3_000_000_123, 30, 1000)
    b = traffic.generate(mix(name), 3_000_000_123, 30, 1000)
    c = traffic.generate(mix(name), 3_000_000_124, 30, 1000)
    assert [(s.rid, s.arrival_s, s.max_new_tokens, s.prompt.tolist())
            for s in a] == [(s.rid, s.arrival_s, s.max_new_tokens,
                             s.prompt.tolist()) for s in b]
    assert [s.prompt.tolist() for s in a] != [s.prompt.tolist() for s in c]


def test_every_seed_gets_the_same_lengths():
    m = mix("chat", rate_per_s=3.0, order="seeded")
    a = traffic.generate(m, 1, 30, 1000)
    b = traffic.generate(m, 2**40 + 7, 30, 1000)
    assert len(a) == len(b) == 90
    assert sorted(s.prompt.size for s in a) == sorted(s.prompt.size
                                                      for s in b)
    assert sorted(s.max_new_tokens for s in a) == sorted(
        s.max_new_tokens for s in b)
    assert [s.prompt.size for s in a] != [s.prompt.size for s in b]


def same_order_every_seed(m):
    assert m["order"] == "fixed"
    a = traffic.generate(m, 1, 30, 1000)
    b = traffic.generate(m, 2**40 + 7, 30, 1000)
    assert [(s.arrival_s, s.prompt.size, s.max_new_tokens) for s in a] == [
        (s.arrival_s, s.prompt.size, s.max_new_tokens) for s in b]
    assert [s.prompt.tolist() for s in a] != [s.prompt.tolist() for s in b]


def test_a_fixed_order_is_the_same_for_every_seed():
    same_order_every_seed(mix("batch-decode"))


def test_the_chat_arrivals_are_the_same_for_every_seed():
    """A window that cuts the requests due late does the same work for
    every seed only when the order is one."""
    same_order_every_seed(mix("chat", rate_per_s=2.8))


def test_lognormal_lengths_follow_the_distribution():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64,
         "max": 2048}
    v = traffic.quantiles(d, 2001)
    assert v.min() >= 64 and v.max() <= 2048
    assert np.median(v) == 512
    # the share clipped at the top is the normal tail beyond ln(4)/0.8
    tail = 1 - NormalDist().cdf(np.log(2048 / 512) / 0.8)
    assert abs(np.mean(v == 2048) - tail) < 2e-3


def test_uniform_lengths_follow_the_distribution():
    v = traffic.quantiles({"dist": "uniform", "min": 512, "max": 2048}, 160)
    assert v.min() >= 512 and v.max() <= 2048
    assert abs(v.mean() - 1280) < 1


def test_poisson_arrivals_fill_the_window_at_the_rate():
    m = mix("chat", rate_per_s=3.0)
    reqs = traffic.generate(m, 5, 40, 1000)
    due = np.array([s.arrival_s for s in reqs])
    assert np.all(np.diff(due) >= 0) and due.max() < 40
    assert 110 <= len(reqs) <= 120
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert abs(gaps.mean() - 1 / 3.0) < 0.05


def test_at_once_arrivals_and_token_range():
    reqs = traffic.generate(mix("batch-decode"), 9, 30, 77)
    assert len(reqs) == mix("batch-decode")["n_requests"]
    assert all(s.arrival_s == 0.0 for s in reqs)
    assert all(0 <= s.prompt.min() and s.prompt.max() < 77 for s in reqs)


@pytest.mark.parametrize("name", ["batch-decode", "chat"])
def test_every_request_fits_the_cells_context(name):
    """The longest prompt and the longest output together fit ``max_len``
    of every cell that serves the mix, so no request is refused."""
    root = MIXES.parents[1]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    m = mix(name)
    for w in spec["workloads"]:
        if w["traffic"] == name:
            cell = json.loads((MIXES.parent / "cells" /
                               f"{w['name']}.json").read_text())
            assert m["prompt"]["max"] + m["output"]["max"] \
                <= cell["engine"]["max_len"]
