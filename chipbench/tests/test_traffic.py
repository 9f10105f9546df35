"""The generators reproduce from a seed, and a seed changes only the
order of the work, not its amount."""
import json
from pathlib import Path

import numpy as np

from chipbench import inputs
from chipbench.systems import serve_lm

TRAFFIC = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                      / "chat.json").read_text())


def test_schedule_reproduces_from_its_seed():
    a = serve_lm.schedule(TRAFFIC, 2**31 + 12345, 1, 5.0, 60.0, 1000)
    b = serve_lm.schedule(TRAFFIC, 2**31 + 12345, 1, 5.0, 60.0, 1000)
    assert [(r.due, r.max_new) for r in a] == [(r.due, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = serve_lm.schedule(TRAFFIC, 7, 1, 5.0, 60.0, 1000)
    assert [r.due for r in a] != [r.due for r in c]
    # the same work for every seed: the multisets of sizes and of gaps
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    gaps = lambda rs: np.sort(np.diff([r.due for r in rs] + [65.0]))
    assert np.allclose(gaps(a), gaps(c))
    assert a[0].due == 5.0 and all(5.0 <= r.due < 65.0 for r in a)


def test_seeds_share_the_multiset_of_sizes():
    def sizes(seed):
        n = 400
        rng = np.random.default_rng(seed)
        return np.sort(inputs.stratified(rng, n, lambda p: np.exp(p)))

    assert np.array_equal(sizes(1), sizes(2))


def test_chat_mix_shape():
    reqs = serve_lm.schedule(TRAFFIC, 3, 0, 0.0, 600.0, 1000)
    rate = TRAFFIC["arrivals"]["rate_rps"]
    due = np.array([r.due for r in reqs])
    gaps = np.diff(due)
    assert len(reqs) == round(600.0 * rate)
    assert 1.5 < gaps.std() / gaps.mean() < 2.5  # burstier than Poisson
    plen = np.array([len(r.prompt) for r in reqs])
    olen = np.array([r.max_new for r in reqs])
    p, o = TRAFFIC["prompt_tokens"], TRAFFIC["output_tokens"]
    assert plen.min() >= p["min"] and plen.max() <= p["max"]
    assert olen.min() >= o["min"] and olen.max() <= o["max"]
    assert abs(np.median(plen) - p["median"]) / p["median"] < 0.1
    assert abs(np.median(olen) - o["median"]) / o["median"] < 0.1
    assert all(r.prompt.max() < 1000 for r in reqs)


def test_solver_inputs_reproduce():
    a = inputs.regression(3, 5, 50, 4, 0.1, seed=9)
    b = inputs.regression(3, 5, 50, 4, 0.1, seed=9)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    idx, val, _ = a
    assert np.allclose(np.linalg.norm(val, axis=-1), 1.0, atol=1e-6)
    assert all(len(set(row)) == 4 for row in idx.reshape(-1, 4))
    s1 = inputs.index_stream(100, 3, 5, np.random.SeedSequence([2**33, 0]))
    s2 = inputs.index_stream(100, 3, 5, np.random.SeedSequence([2**33, 0]))
    assert np.array_equal(s1, s2) and s1.max() < 5
    edges = inputs.erdos_renyi_edges(10, 0.4, 0)
    w = inputs.laplacian_mixing(10, edges)
    assert np.allclose(w, w.T) and np.allclose(w.sum(1), 1.0)
    assert inputs.ring_edges(4) == ((0, 1), (0, 3), (1, 2), (2, 3))
