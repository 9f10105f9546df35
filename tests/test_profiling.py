"""Profiler spans and per-request records of the serving and solver loops.

A recording stand-in takes the place of ``repro.obs.span``: it logs each
span's entry and exit, so the tests read the order and nesting that a
profile would show without running a profiler. One test runs the real
profiler on the CPU and reads the spans back from its trace.
"""
import contextlib
import dataclasses

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_reduced
from repro.core import mixing
from repro.core.solvers import make_problem, solve
from repro.data.synthetic import make_regression
from repro.models import transformer as T
from repro.models.params import tree_materialize
from repro.serve import PoolConfig, Request, Scheduler


class SpanLog:
    """Stand-in for ``obs.span``: records (name, meta, children) trees."""

    def __init__(self):
        self.roots = []
        self._open = []

    def __call__(self, name, **meta):
        @contextlib.contextmanager
        def span():
            node = (name, meta, [])
            (self._open[-1][2] if self._open else self.roots).append(node)
            self._open.append(node)
            try:
                yield
            finally:
                self._open.pop()

        return span()


def names(nodes):
    return [n[0] for n in nodes]


@pytest.fixture
def spans(monkeypatch):
    log = SpanLog()
    monkeypatch.setattr(obs, "span", log)
    return log


def _scheduler(pc=None, **kw):
    cfg = get_reduced("minitron_8b")
    params = tree_materialize(
        T.model_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype
    )
    pc = pc or PoolConfig(
        max_batch=3, block_size=8, n_blocks=24, max_len=32, prompt_pad=16
    )
    return Scheduler(cfg, params, pc, **kw)


def _request(rid, plen, max_new):
    return Request(rid, np.arange(1, plen + 1, dtype=np.int64), max_new)


# -- serving ------------------------------------------------------------------


def test_scheduler_step_spans_nest_in_order(spans):
    sch = _scheduler()
    sch.submit(_request(0, 5, 4))
    sch.submit(_request(1, 7, 4))
    sch.step()  # admits both, then decodes
    sch.step()  # nothing to admit: decode only
    assert names(spans.roots) == ["serve.step", "serve.step"]
    (first, meta0, kids0), (_, meta1, kids1) = spans.roots
    assert (meta0, meta1) == ({"step": 0}, {"step": 1})
    assert names(kids0) == ["serve.prefill", "serve.prefill", "serve.decode",
                            "serve.fetch", "serve.sample"]
    assert [k[1] for k in kids0[:2]] == [{"rid": 0, "prompt_len": 5},
                                         {"rid": 1, "prompt_len": 7}]
    assert names(kids1) == ["serve.decode", "serve.fetch", "serve.sample"]
    # the leaves hold no spans of their own
    assert all(not k[2] for k in kids0 + kids1)


def test_an_idle_step_opens_only_its_own_span(spans):
    sch = _scheduler()
    sch.step()
    assert [(n, kids) for n, _, kids in spans.roots] == [("serve.step", [])]


def test_request_records_are_ordered():
    sch = _scheduler()
    results, stats = sch.run([_request(i, 3 + i, 2 + i) for i in range(5)])
    assert set(stats.requests) == set(results) == set(range(5))
    for rec in stats.requests.values():
        assert (rec.submitted_s <= rec.admitted_s <= rec.first_token_s
                <= rec.finished_s)
        assert rec.preemptions == 0


def test_request_finished_at_admission_has_its_record():
    sch = _scheduler()
    _, stats = sch.run([_request(7, 5, 1)])
    rec = stats.requests[7]
    assert rec.submitted_s <= rec.admitted_s <= rec.first_token_s
    assert rec.first_token_s <= rec.finished_s


def test_a_preempted_request_keeps_its_first_admission():
    """Two sequences that cannot coexist at full length: the younger is
    preempted back to the queue and readmitted later. Its record keeps the
    first admission and first token, and counts the preemptions."""
    pc = PoolConfig(max_batch=2, block_size=4, n_blocks=8, max_len=16,
                    prompt_pad=8)
    sch = _scheduler(pc)
    for i in range(2):
        sch.submit(_request(i, 6, 10))
    sch.step()
    first = {rid: dataclasses.replace(r)
             for rid, r in sch.stats.requests.items()}
    assert all(r.admitted_s is not None for r in first.values())
    results, stats = sch.run()
    assert set(results) == {0, 1}
    assert stats.preempt_counts  # the pool forced a preemption
    for rid, rec in stats.requests.items():
        assert rec.preemptions == stats.preempt_counts.get(rid, 0)
        assert rec.admitted_s == first[rid].admitted_s
        assert rec.first_token_s == first[rid].first_token_s
        assert (rec.submitted_s <= rec.admitted_s <= rec.first_token_s
                <= rec.finished_s)
    victim = next(iter(stats.preempt_counts))
    other = 1 - victim
    # the victim finished after the request that displaced it
    assert stats.requests[victim].finished_s > stats.requests[other].finished_s


# -- solvers ------------------------------------------------------------------


def _problem():
    return make_problem("ridge", make_regression(4, 8, 12, 3, seed=0),
                        mixing.ring_graph(4))


def test_dense_solve_spans(spans):
    solve(_problem(), "dsba", comm="dense", steps=12, record_every=5,
          alpha=0.05)
    assert names(spans.roots) == ["solve"]
    _, meta, kids = spans.roots[0]
    assert meta == {"method": "dsba", "comm": "dense"}
    # record points 5, 10, 12 in one segment: one dispatch (the two
    # 5-iteration chunks, then the 2-iteration tail) and one read-out
    assert names(kids) == ["solve.run", "solve.readout"]
    assert all(not k[2] for k in kids)


def test_relay_solve_spans(spans):
    solve(_problem(), "dsba", comm="sparse", steps=12, record_every=5,
          alpha=0.05)
    _, meta, kids = spans.roots[0]
    assert meta == {"method": "dsba", "comm": "sparse"}
    # one dispatch of the whole-run scan; the trajectory copied to the
    # host, joined to z^0, and recorded at each record point
    assert names(kids) == ["solve.run"] + ["solve.readout"] * 3
    assert all(not k[2] for k in kids)


def test_spans_show_in_a_profile(tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        solve(_problem(), "dsba", comm="dense", steps=4, record_every=2,
              alpha=0.05)
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    seen = {ev.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert {"repro.solve", "repro.solve.run", "repro.solve.readout"} <= seen
