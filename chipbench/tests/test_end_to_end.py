"""The end-to-end arithmetic: a stall inside the window shows in
``solve_s``, ``out_tok_s``, the time to first token and the gap tail, and
an algorithmic gain (fewer iterations to the target) shows in
``solve_s``."""
import numpy as np
import pytest

from chipbench.systems import dsba_solve, serve_lm


def solver(window_s, to_target, n=5, iters=720):
    s = object.__new__(dsba_solve.System)
    s.cfg = {"steps": iters, "n_nodes": 10, "d": 100, "k": 4}
    s.traffic = {"comm": "dense"}
    s.itemsize = 4
    s.window_s = window_s
    s.solves = [{"j": j, "iters": iters, "to_target": to_target, "z": None}
                for j in range(n)]
    return s


def test_solve_s_is_the_window_rate_times_the_iterations_to_target():
    base = solver(10.0, 600).end_to_end()["solve_s"]
    assert base == pytest.approx(10.0 / (5 * 720) * 600)
    # a 2 s stall inside the same window of work
    assert solver(12.0, 600).end_to_end()["solve_s"] == pytest.approx(
        base * 1.2)
    # fewer iterations to the target at the same rate
    assert solver(10.0, 300).end_to_end()["solve_s"] == pytest.approx(
        base / 2)
    # a solve that never reaches the target counts its whole length
    assert solver(10.0, None).end_to_end()["solve_s"] == pytest.approx(
        10.0 / 5)


def server(stall_at=None, stall=0.0, every=None):
    """Requests due every 0.1 s over 30 s; the first token 0.1 s after the
    due time, then one every 0.05 s, 20 tokens; the window is [10, 20)."""
    s = object.__new__(serve_lm.System)
    s.t_base, s.window = 0.0, (10.0, 20.0)
    reqs = []
    for i in range(300):
        due = 0.1 * i
        t = due + 0.1 + 0.05 * np.arange(20)
        if stall_at is not None:
            t = np.where(t >= stall_at, t + stall, t)
        if every is not None:  # a stall of `stall` s in every `every` s
            t = t + stall * np.floor(t / every)
        reqs.append(serve_lm.Tracked(i, due, np.zeros(4, np.int32), 20,
                                     stamps=list(t)))
    s.reqs, s.next = reqs, len(reqs)
    return {**s.end_to_end(), **s._ttft_quantiles()}


def test_a_stall_moves_throughput_and_the_first_token_tail():
    """Throughput falls; the first-token tail (reported, not bounded)
    rises."""
    base, hit = server(), server(stall_at=15.0, stall=1.0)
    assert base["out_tok_s"] == pytest.approx(200.0)
    assert base["p95_ms"] == pytest.approx(100.0)
    assert base["itl_p95_ms"] == pytest.approx(50.0)
    assert base["ttft_p50_ms"] == pytest.approx(100.0)
    assert hit["out_tok_s"] < 0.95 * base["out_tok_s"]
    assert hit["p95_ms"] > 5 * base["p95_ms"]


def test_a_long_stall_moves_the_median_first_token():
    """A 5 s stall half a second into the window delays the first token
    of most requests due in it."""
    base, hit = server(), server(stall_at=10.5, stall=5.0)
    assert hit["ttft_p50_ms"] > 10 * base["ttft_p50_ms"]
    assert hit["p50_ms"] == pytest.approx(hit["ttft_p50_ms"])


def test_repeated_stalls_move_the_gap_tail():
    base, hit = server(), server(stall=0.2, every=0.5)
    assert hit["itl_p95_ms"] > 3 * base["itl_p95_ms"]
    assert hit["out_tok_s"] < base["out_tok_s"]


def test_a_request_without_a_first_token_counts_its_wait():
    s = object.__new__(serve_lm.System)
    s.t_base, s.window = 0.0, (0.0, 1.0)
    s.reqs = [serve_lm.Tracked(i, 0.01 * i, np.zeros(4, np.int32), 2,
                               stamps=[0.01 * i + 0.01, 0.01 * i + 0.02])
              for i in range(100)]
    for r in s.reqs[-10:]:
        r.stamps = []  # never served
    s.next = len(s.reqs)
    assert s._ttft_quantiles()["p95_ms"] > 100.0
    assert s._ttft_quantiles()["p50_ms"] == pytest.approx(10.0)
