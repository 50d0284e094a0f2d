"""The benchmark's generators hit the published counts and repeat per seed."""

import json

import numpy as np

from conftest import BENCH
from traffic import corpus, graph

BIG_SEED = 2**31 + 12345
SMALL_GRAPH = {"in_exponent": 2.1, "in_max": 400, "out_exponent": 2.72, "out_max": 80,
               "dangling_share": 0.15}


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def degrees(src, dst, n):
    return np.bincount(dst, minlength=n), np.bincount(src, minlength=n)


def test_graph_counts_exact_and_repeat():
    src, dst = graph.web_edges(5000, 40000, seed=BIG_SEED, **SMALL_GRAPH)
    assert src.size == dst.size == 40000
    assert np.unique(src * 5000 + dst).size == 40000 and not (src == dst).any()
    assert np.unique(np.concatenate([src, dst])).size == 5000
    again = graph.web_edges(5000, 40000, seed=BIG_SEED, **SMALL_GRAPH)
    other = graph.web_edges(5000, 40000, seed=BIG_SEED + 1, **SMALL_GRAPH)
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1])
    assert not np.array_equal(dst, other[1])


def test_degree_sequence_exact_sum_max_and_tail():
    d = graph.degree_sequence(100_000, 600_000, 2000, 2.1)
    assert d.sum() == 600_000 and d[0] == 2000 and np.all(np.diff(d) <= 0)
    x = d[d >= 20].astype(float)  # discrete power-law MLE of the tail
    assert abs(1 + x.size / np.log(x / 19.5).sum() - 2.1) < 0.05


def test_webgoogle_published_counts_same_degrees_every_seed():
    c = config("webgoogle")
    n, e, g = c["n_nodes"], c["n_edges"], c["graph"]
    assert (n, e) == (875_713, 5_105_039)
    src, dst = graph.web_edges(n, e, seed=BIG_SEED, **g)
    assert src.size == e and np.unique(src * n + dst).size == e
    assert np.unique(np.concatenate([src, dst])).size == n
    din, dout = degrees(src, dst, n)
    assert din.max() == g["in_max"] and dout.max() == g["out_max"]
    assert (dout == 0).sum() == round(g["dangling_share"] * n)
    din2, dout2 = degrees(*graph.web_edges(n, e, seed=BIG_SEED + 1, **g), n)
    assert np.array_equal(np.sort(din), np.sort(din2))
    assert np.array_equal(np.sort(dout), np.sort(dout2))
    assert not np.array_equal(din, din2)


def test_newsgroups_fit_and_same_work_every_seed():
    c = config("newsgroups20")["corpus"]
    a = corpus.documents(c, BIG_SEED)
    b = corpus.documents(c, BIG_SEED + 1)
    assert len(a) == len(b) == 18_846
    tokens = [sum(len(d.split()) for d in docs) for docs in (a, b)]
    assert tokens[0] == tokens[1] == corpus.n_tokens(c)
    assert a == corpus.documents(c, BIG_SEED) and a != b
    words = [d.split() for d in a]
    # the published figures the configuration is fitted to (configs/newsgroups20.json)
    assert abs(np.mean([len(set(w)) for w in words]) - 159.0) < 2.0
    assert abs(len(set().union(*map(set, words[:11_314]))) - 130_107) < 1_500

