"""Open-loop serving of a dense decoder through ``repro.serve.Scheduler``.

Requests arrive on a schedule drawn from the seed (Gamma-distributed gaps
at the traffic's fixed rate and coefficient of variation; log-normal
prompt and output lengths, clipped; the pre-roll and the window each get
the same multiset of gaps and lengths for every seed, in the seed's
order), are submitted when due, and are
served by stepping the scheduler (admission with one prefill each, then
one paged decode step for every active slot). The load runs for the
traffic's ``preroll_s`` before the window opens, so that the window starts
at a steady occupancy; that pre-roll is set-up.

Tokens are stamped with the host clock when the ``step()`` that produced
them returns. End to end, over the window:
  ttft_p50_ms  median, over every request due in the window, of the time
               from its due time to its first token's stamp (the
               scheduler is stepped on after the window closes, up to the
               traffic's ``drain_limit_s``, until each has one; one never
               served counts its wait until then);
  itl_p95_ms   95th percentile of every gap between consecutive tokens of
               one request, both stamped in the window.
The other quantiles of the time to first token go to standard error.
``out_tok_s``, the output tokens stamped in the window over its seconds,
is the offered load below the knee; the knee sweep (``sweep.py``) reads
it.

Correctness, after the window, once the program's state is freed: a
sample of finished requests drawn from the seed, the longest among them,
holding at least the traffic's ``check_tokens`` served tokens, is run by
the float32 reference (``references/dense_lm.py``) over prompt + served
tokens; the widest gap by which a served (greedy) token's logit lies below
the reference's largest is held to the cell's limit. Every finished
request must also carry exactly the tokens it asked for. The control
(``check(..., quant="fp8")``) puts in the served tokens' place the tokens
that the reference computed in float8 puts first.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from scipy import stats

from chipbench import inputs, lm_weights


@dataclasses.dataclass
class Tracked:
    """One request of the schedule and what the host saw of it."""

    rid: int
    due: float  # seconds after the schedule's start
    prompt: np.ndarray
    max_new: int
    stamps: list = dataclasses.field(default_factory=list)
    submitted: float | None = None


def schedule(traffic: dict, seed: int, part: int, start_s: float,
             span_s: float, vocab: int, first_rid: int = 0,
             rate: float | None = None) -> list[Tracked]:
    """round(rate x span_s) requests due over [start_s, start_s + span_s).

    Gaps, prompt lengths and output lengths are stratified draws, and the
    gaps are scaled to fill the span exactly: every seed sends the same
    multiset of sizes at the same arrival instants' spacings, in another
    order. ``part`` tells the pre-roll (0) from the window (1) apart.
    """
    a = traffic["arrivals"]
    rate = rate or a["rate_rps"]
    n = max(1, int(round(rate * span_s)))
    rng = np.random.default_rng(np.random.SeedSequence([seed % 2**64, 1, part]))
    shape = 1.0 / a["gap_cv"] ** 2  # Gamma: cv = 1/sqrt(shape)
    gaps = inputs.stratified(
        rng, n, lambda p: stats.gamma.ppf(p, shape, scale=1.0 / (rate * shape)))
    gaps *= span_s / gaps.sum()

    def lengths(spec):
        lo, hi = spec["min"], spec["max"]
        ppf = lambda p: stats.lognorm.ppf(p, spec["sigma"], scale=spec["median"])
        return np.clip(np.rint(inputs.stratified(rng, n, ppf)), lo, hi
                       ).astype(int)

    plen = lengths(traffic["prompt_tokens"])
    olen = lengths(traffic["output_tokens"])
    due = start_s + np.cumsum(gaps) - gaps  # the first one at start_s
    toks = np.random.default_rng(np.random.SeedSequence([seed % 2**64, 2, part]))
    return [Tracked(first_rid + i, float(due[i]),
                    toks.integers(0, vocab, plen[i], dtype=np.int32),
                    int(olen[i]))
            for i in range(n)]


#: the one dense block the program serves (and the reference computes)
BLOCK = {"mlp": "swiglu", "norm": "rmsnorm", "rotary_fraction": 1.0}


def model_config(m: dict):
    """The program's ModelConfig for configuration ``m``."""
    import jax.numpy as jnp
    from repro.models.config import ModelConfig

    if any(m[k] != v for k, v in BLOCK.items()):
        raise ValueError(f"the program serves only the dense block {BLOCK}")

    return ModelConfig(
        name=m["name"], family=m["family"], n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
        head_dim=m["head_dim"], d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"],
        tie_embeddings=False, param_dtype=jnp.dtype(m["dtype"]),
        compute_dtype=jnp.dtype(m["dtype"]),
    )


class System:
    """One dense decoder behind the continuous-batching scheduler."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices, spans,
                 *, rate: float | None = None):
        import jax
        from repro.models import transformer as T
        from repro.models.params import tree_sds
        from repro.serve import PoolConfig, Scheduler

        self.cfg, self.traffic, self.seed, self.spans = cfg, traffic, seed, spans
        self.rate = rate or traffic["arrivals"]["rate_rps"]
        self.reference_setup_s = 0.0
        self.mcfg = model_config(cfg)
        params = lm_weights.make(cfg, seed, cfg["dtype"])
        want = jax.tree_util.tree_structure(
            tree_sds(T.model_defs(self.mcfg), self.mcfg.param_dtype))
        if jax.tree_util.tree_structure(params) != want:
            raise ValueError("the program's parameter layout is not the one "
                             "chipbench/lm_weights.py makes")
        s = cfg["serving"]
        pages = -(-s["max_len"] // s["block_size"])
        self.pc = PoolConfig(
            max_batch=s["max_batch"], block_size=s["block_size"],
            n_blocks=s["max_batch"] * pages + 1, max_len=s["max_len"],
            prompt_pad=s["prompt_pad"],
        )
        self.sch = Scheduler(self.mcfg, params, self.pc)
        self.t_base = None
        self.reqs: list[Tracked] = []
        self.next = 0  # next request of the schedule to submit
        self.live: dict[int, Tracked] = {}  # submitted, not finished
        # (t_end, active slots, admitted, context tokens, tokens, queue)
        self.steps: list[tuple] = []
        self.window = (0.0, 0.0)
        self.preempted = 0

    # -- the open loop --------------------------------------------------------

    def _progress(self) -> dict[int, int]:
        """rid -> tokens generated so far, for every request still active."""
        return {st.req.rid: len(st.generated) for st in self.sch.active.values()}

    def _step(self) -> None:
        sch = self.sch
        with self.spans("scheduler.step"):
            st = sch.step()
        now = time.perf_counter()
        with self.spans("observe"):
            prog = self._progress()
            for rid in list(self.live):
                r = self.live[rid]
                if rid in prog:
                    n = prog[rid]
                elif rid in sch.results:
                    n = len(sch.results[rid])
                else:
                    n = 0  # queued, or preempted back to the queue
                if n < len(r.stamps):
                    self.preempted += 1
                    del r.stamps[n:]
                r.stamps.extend([now] * (n - len(r.stamps)))
                if rid in sch.results:
                    del self.live[rid]
            kv = int(sum(int(sch.pool.lengths[s]) for s in sch.active))
        self.steps.append((now, st.active_slots, st.admitted, kv,
                           st.tokens_generated, st.queue_depth))

    def _submit_due(self, now: float) -> None:
        with self.spans("submit"):
            from repro.serve import Request

            while self.next < len(self.reqs) and (
                    self.t_base + self.reqs[self.next].due <= now):
                r = self.reqs[self.next]
                self.sch.submit(Request(rid=r.rid, tokens=r.prompt,
                                        max_new_tokens=r.max_new))
                r.submitted = now
                self.live[r.rid] = r
                self.next += 1

    def _loop(self, t_end: float) -> None:
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            self._submit_due(now)
            if self.sch.queue or self.sch.active:
                self._step()
            else:
                nxt = (self.t_base + self.reqs[self.next].due
                       if self.next < len(self.reqs) else t_end)
                time.sleep(max(0.0, min(nxt, t_end) - now))

    # -- the benchmark's phases -----------------------------------------------

    def warm(self) -> None:
        """Compile prefill, the decode step and the pool writes, then run
        the load for ``preroll_s`` so that the window opens at steady
        occupancy."""
        from repro.serve import Request

        rng = np.random.default_rng(np.random.SeedSequence([self.seed % 2**64, 3]))
        self.sch.submit(Request(rid=-1, tokens=rng.integers(
            0, self.cfg["vocab_size"], 64, dtype=np.int32), max_new_tokens=3))
        while self.sch.queue or self.sch.active:
            self.sch.step()
        self.sch.results.clear()
        self.warm_traces = dict(self.sch.trace_counts)
        t = self.traffic
        self.reqs = schedule(t, self.seed, 0, 0.0, t["preroll_s"],
                             self.cfg["vocab_size"], rate=self.rate)
        self.t_base = time.perf_counter()
        self._loop(self.t_base + t["preroll_s"])

    def run_window(self, seconds: float) -> None:
        """The measured window: the open loop for ``seconds``, ending at
        the first step boundary after them."""
        t0 = time.perf_counter()
        self.reqs += schedule(self.traffic, self.seed, 1, t0 - self.t_base,
                              seconds, self.cfg["vocab_size"],
                              first_rid=len(self.reqs), rate=self.rate)
        self._loop(t0 + seconds)
        self.window = (t0, time.perf_counter())

    def finish(self) -> None:
        """After the window: step on, submitting nothing new, until every
        request due in the window has its first token and what is in
        flight is done, up to the traffic's drain limit."""
        self._submit_due(self.window[1])  # all that fell due in the window
        limit = self.window[1] + self.traffic["drain_limit_s"]
        while (self.sch.queue or self.sch.active) and time.perf_counter() < limit:
            self._step()

    def _due_in_window(self) -> list[Tracked]:
        t0, t1 = self.window
        return [r for r in self.reqs[: self.next]
                if t0 <= self.t_base + r.due < t1]

    # -- numbers --------------------------------------------------------------

    def counters(self) -> dict:
        t0, t1 = self.window
        win = [s for s in self.steps if t0 < s[0] <= t1]
        due = self._due_in_window()
        late = [r.submitted - (self.t_base + r.due) for r in self.reqs
                if r.submitted is not None]
        m, s = self.cfg, self.cfg["serving"]
        admitted = [len(r.prompt) for r in self.reqs
                    if r.stamps and t0 < r.stamps[0] <= t1]
        return {
            "window_s": t1 - t0,
            "steps": len(win),
            "decode_steps": sum(1 for w in win if w[1] > 0 or w[4] > 0),
            "prefills": sum(w[2] for w in win),
            "prompt_tokens": sum(admitted),
            "prompt_sq": sum(p * p for p in admitted),
            "active_mean": float(np.mean([w[1] for w in win])) if win else 0.0,
            "decode_tokens": sum(w[4] for w in win),
            "kv_tokens": sum(w[3] for w in win),
            "max_batch": s["max_batch"], "prompt_pad": s["prompt_pad"],
            "model": {k: m[k] for k in ("n_layers", "d_model", "n_heads",
                                         "n_kv_heads", "head_dim", "d_ff",
                                         "vocab_size")},
            "requests_due": len(due),
            "queue_first": win[0][5] if win else 0,
            "queue_last": win[-1][5] if win else 0,
            "queue_max": max((w[5] for w in win), default=0),
            "preempted": self.preempted,
            "report": {
                "generator lateness": {
                    "mean_s": float(np.mean(late)) if late else 0.0,
                    "max_s": float(np.max(late)) if late else 0.0,
                    "submitted": len(late)},
                "time to first token": self._ttft_quantiles()},
        }

    def _ttft(self) -> list[float]:
        """Seconds from due time to first token, for every request due in
        the window; one never served counts its wait until now."""
        due = self._due_in_window()
        now = time.perf_counter()
        return [(r.stamps[0] if r.stamps else now) - (self.t_base + r.due)
                for r in due]

    def _ttft_quantiles(self) -> dict:
        ttft = np.asarray(self._ttft() or [0.0]) * 1e3
        return {"n": len(self._due_in_window()),
                **{f"p{q}_ms": float(np.percentile(ttft, q))
                   for q in (50, 90, 95, 99)}}

    def end_to_end(self) -> dict:
        t0, t1 = self.window
        stamps = [t for r in self.reqs for t in r.stamps if t0 <= t < t1]
        gaps = [b - a for r in self.reqs
                for a, b in zip(r.stamps, r.stamps[1:]) if t0 <= a and b < t1]
        return {
            "out_tok_s": len(stamps) / (t1 - t0),
            "ttft_p50_ms": self._ttft_quantiles()["p50_ms"],
            "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
        }

    def release(self) -> None:
        """Free the scheduler, its pool and the weights."""
        self.final_traces = dict(self.sch.trace_counts)
        self.results = dict(self.sch.results)
        self.sch = None

    def check(self, limits: dict, quant: str | None = None) -> tuple:
        """The widest gap of served tokens under the float32 reference;
        with ``quant``, the control: the gap of the tokens that the
        reference computed in ``quant`` puts first, at the same positions."""
        import jax.numpy as jnp

        from chipbench.references import dense_lm

        due = self._due_in_window()
        missing = sum(1 for r in due if not r.stamps)
        by_rid = {r.rid: r for r in self.reqs}
        done = [rid for rid in self.results if rid in by_rid]
        wrong_len = sum(1 for rid in done
                        if len(self.results[rid]) != by_rid[rid].max_new)
        rng = np.random.default_rng(np.random.SeedSequence([self.seed % 2**64, 4]))
        order = list(rng.permutation(sorted(done)))
        longest = max(done, key=lambda rid: (len(self.results[rid]), rid))
        pick, n_tok = [longest], len(self.results[longest])
        for rid in order:
            if n_tok >= self.traffic["check_tokens"]:
                break
            if rid != longest:
                pick.append(rid)
                n_tok += len(self.results[rid])
        m = self.cfg
        model = dense_lm.LM(m["n_layers"], m["d_model"], m["norm_eps"],
                            m["rope_theta"])
        params = lm_weights.make(m, self.seed, m["dtype"])
        s = m["serving"]
        n_out = s["max_len"] - s["prompt_pad"]
        gaps = []
        for rid in pick:
            r, out = by_rid[rid], np.asarray(self.results[rid], np.int32)
            seq = np.zeros(s["max_len"], np.int32)
            seq[: len(r.prompt)] = r.prompt
            seq[len(r.prompt): len(r.prompt) + len(out)] = out
            seq = jnp.asarray(seq)
            if quant is None:
                g = dense_lm.served_gaps(params, seq, len(r.prompt), n_out,
                                         model)
            else:
                g = dense_lm.choice_gaps(params, seq, len(r.prompt), n_out,
                                         model, quant)
            gaps.append(np.asarray(g)[: len(out)])
        checks = {
            "served_gap_max": {"value": float(np.max(np.concatenate(gaps))),
                               "limit": float(limits["served_gap_max"])},
            "wrong_lengths": {"value": float(wrong_len), "limit": 0.0},
            "recompiles": {"value": float(sum(self.final_traces.values())
                                          - sum(self.warm_traces.values())),
                           "limit": 0.0},
        }
        self.checked_tokens = n_tok
        return checks, len(due), missing
