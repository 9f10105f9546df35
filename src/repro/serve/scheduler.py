"""Continuous-batching scheduler over the paged cache pool.

Between decode steps the scheduler admits queued requests into free
slots (prefill at a fixed ``(1, prompt_pad)`` shape), evicts finished
sequences, and — when the page pool runs dry mid-decode — preempts the
youngest active sequence back to the queue.  Decode always runs at the
fixed ``(max_batch, 1)`` shape with padding lanes masked by length 0
and null block tables, so the warm runner NEVER recompiles: every jit
in the loop is shape-stable and trace-counted (``trace_counts``).

Admission policy (documented in docs/serving.md): FIFO, admit while a
free slot exists and the pool can cover the prompt; a request larger
than ``prompt_pad`` is rejected at submit.  Preemption restarts the
victim from scratch — generated tokens are discarded, the original
request returns to the FRONT of the queue (it was admitted first).  A
request preempted ``max_preempts`` times is exempt from further
preemption (oldest-first fallback among exempt slots) so no request
thrashes forever.

Per-step counters (queue depth, active slots, pool occupancy,
admissions/evictions/preemptions, tokens generated and, where the chip
holds a share of the experts, the routes to each held expert of the step's
decode and of its prefills) accumulate in a ``ServeStats`` record, beside one ``RequestRecord`` per request (when it
was submitted, admitted, produced its first token and finished).
``step()`` and each admission's prefill are wrapped in profiler spans
(``repro.obs``; docs/serving.md).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serve.cache import CachePool, PoolConfig, TracedJit


@dataclasses.dataclass
class Request:
    """One generation request.

    enc_embeds (encoder_len, d_model) is required for the encdec
    family (whisper) and ignored otherwise.
    """

    rid: int
    tokens: np.ndarray  # (prompt_len,) int token ids
    max_new_tokens: int
    enc_embeds: np.ndarray | None = None


@dataclasses.dataclass
class StepStats:
    """Counters for one scheduler step (recorded after admission).

    ``decode_routes`` and ``prefill_routes`` are (n_moe_layers, held)
    int32 route counts to each held expert, of the step's decode and of
    its admissions' prefills (their prompt tokens only); None where the
    model holds no share of experts or nothing of the kind ran."""

    step: int
    queue_depth: int
    active_slots: int
    pool_occupancy: float
    admitted: int
    finished: int
    preempted: int
    tokens_generated: int
    decode_routes: np.ndarray | None = None
    prefill_routes: np.ndarray | None = None


@dataclasses.dataclass
class RequestRecord:
    """One request's life, in ``time.perf_counter()`` seconds.

    ``admitted_s`` is the start of the first prefill that admitted the
    request and ``first_token_s`` the moment its first token was sampled
    then: a preempted request keeps both and counts ``preemptions`` (its
    regenerated tokens are the same under greedy sampling). ``None``
    until the event happens.
    """

    submitted_s: float
    admitted_s: float | None = None
    first_token_s: float | None = None
    finished_s: float | None = None
    preemptions: int = 0


@dataclasses.dataclass
class ServeStats:
    """Per-step counter trace for a scheduler run.

    ``preempt_counts`` maps request id -> how many times that request was
    preempted over the run (the starvation-guard witness: no entry may
    exceed ``Scheduler.max_preempts`` unless the oldest-first fallback had
    no non-exempt victim left). ``requests`` maps request id -> its
    ``RequestRecord``; queue wait is ``admitted_s - submitted_s``.
    """

    steps: list[StepStats] = dataclasses.field(default_factory=list)
    preempt_counts: dict[int, int] = dataclasses.field(default_factory=dict)
    requests: dict[int, RequestRecord] = dataclasses.field(
        default_factory=dict)

    @property
    def total_tokens(self) -> int:
        return sum(s.tokens_generated for s in self.steps)

    @property
    def peak_active(self) -> int:
        return max((s.active_slots for s in self.steps), default=0)

    @property
    def peak_occupancy(self) -> float:
        return max((s.pool_occupancy for s in self.steps), default=0.0)

    @property
    def preemptions(self) -> int:
        return sum(s.preempted for s in self.steps)

    @property
    def decode_routes(self) -> np.ndarray | None:
        """Routes to each held expert over every decode step (summed)."""
        got = [s.decode_routes for s in self.steps
               if s.decode_routes is not None]
        return np.sum(got, axis=0) if got else None

    @property
    def prefill_routes(self) -> np.ndarray | None:
        """Routes to each held expert over every prefill (summed)."""
        got = [s.prefill_routes for s in self.steps
               if s.prefill_routes is not None]
        return np.sum(got, axis=0) if got else None


@dataclasses.dataclass
class _Active:
    req: Request
    generated: list[int]
    target: int  # total tokens to generate (capped by pool max_len)


class Scheduler:
    """Continuous batching: fixed-shape decode, dynamic membership."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        pool_cfg: PoolConfig,
        *,
        temperature: float = 0.0,
        seed: int = 0,
        max_preempts: int = 3,
    ):
        self.cfg = cfg
        self.params = params
        self.pool = CachePool(cfg, pool_cfg)
        self.temperature = temperature
        self.max_preempts = max_preempts
        self._rng = np.random.default_rng(seed)
        self.queue: deque[Request] = deque()
        self.active: dict[int, _Active] = {}
        self._admit_order: list[int] = []  # slots, oldest admission first
        self._cur_tok = np.zeros((pool_cfg.max_batch, 1), np.int32)
        self.results: dict[int, np.ndarray] = {}
        self.stats = ServeStats()
        self._step_idx = 0
        self._prefill = TracedJit(functools.partial(T.prefill, cfg))
        # a latent pool is updated in place (pools donated): it is sized to
        # fill what the weights leave of the device
        self._decode = TracedJit(functools.partial(T.decode_step_paged, cfg),
                                 donate_argnums=(2,) if cfg.is_mla else ())
        self._prefill_routes = None  # summed over this step's admissions
        self._encode = TracedJit(
            functools.partial(T.encode_cross_cache, cfg, batch=1)
        )

    @property
    def trace_counts(self) -> dict[str, int]:
        """Jit trace counts — the zero-recompile-after-warmup witness."""
        return {
            "prefill": self._prefill.traces,
            "decode": self._decode.traces,
            "encode": self._encode.traces,
            "pool": self.pool.trace_count,
        }

    # -- request intake -----------------------------------------------------

    def submit(self, req: Request) -> None:
        plen = len(req.tokens)
        pc = self.pool.pc
        if not 1 <= plen <= pc.prompt_pad:
            raise ValueError(
                f"prompt length {plen} not in [1, prompt_pad={pc.prompt_pad}]"
            )
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.cfg.family == "encdec" and req.enc_embeds is None:
            raise ValueError("encdec requests need enc_embeds")
        self.queue.append(req)
        self.stats.requests[req.rid] = RequestRecord(time.perf_counter())

    # -- sampling -----------------------------------------------------------

    def _sample(self, logits_row: np.ndarray) -> int:
        if self.temperature > 0:
            g = self._rng.gumbel(size=logits_row.shape)
            return int(np.argmax(logits_row / self.temperature + g))
        return int(np.argmax(logits_row))

    # -- admission ----------------------------------------------------------

    def _finish(self, slot: int) -> None:
        st = self.active.pop(slot)
        self._admit_order.remove(slot)
        self.results[st.req.rid] = np.asarray(st.generated, np.int32)
        self.stats.requests[st.req.rid].finished_s = time.perf_counter()
        self.pool.release(slot)

    def _admit_one(self) -> bool:
        req = self.queue[0]
        plen = len(req.tokens)
        slot = self.pool.alloc_slot()
        if slot is None:
            return False
        if not self.pool.ensure(slot, plen):
            self.pool.release(slot)  # returns the empty slot
            return False
        self.queue.popleft()
        pc = self.pool.pc
        record = self.stats.requests[req.rid]
        if record.admitted_s is None:
            record.admitted_s = time.perf_counter()

        with obs.span("serve.prefill", rid=req.rid, prompt_len=plen):
            padded = np.zeros((1, pc.prompt_pad), np.int64)
            padded[0, :plen] = np.asarray(req.tokens)
            cache = T.init_cache(self.cfg, 1, pc.prompt_pad)
            if self.cfg.family == "encdec":
                cache["cross"] = self._encode(
                    self.params, jnp.asarray(req.enc_embeds)[None]
                )
            cache, logits = self._prefill(
                self.params, jnp.asarray(padded), cache,
                valid_len=jnp.asarray([plen], jnp.int32),
            )
            self.pool.write_prefill(slot, cache)
            self.pool.set_length(slot, plen)

            # the prefill logits already yield the first generated token:
            # a decode step per NEW token, not per request token
            if "routes" in cache:  # fetched with the logits, one transfer
                logits_np, routes = jax.device_get((logits, cache["routes"]))
                self._prefill_routes = routes if self._prefill_routes is None \
                    else self._prefill_routes + routes
            else:
                logits_np = np.asarray(logits)
            g0 = self._sample(logits_np[0])
        if record.first_token_s is None:
            record.first_token_s = time.perf_counter()
        target = min(req.max_new_tokens, pc.max_len - plen + 1)
        st = _Active(req, [g0], target)
        if target <= 1:
            self.results[req.rid] = np.asarray(st.generated, np.int32)
            record.finished_s = time.perf_counter()
            self.pool.release(slot)
            return True
        self.active[slot] = st
        self._admit_order.append(slot)
        self._cur_tok[slot, 0] = g0
        return True

    def _admit(self) -> int:
        admitted = 0
        while self.queue and self._admit_one():
            admitted += 1
        return admitted

    # -- preemption ---------------------------------------------------------

    def _preempt_youngest(self, protect: int) -> bool:
        """Evict an active slot (except `protect`) back to the queue
        front, discarding its progress.

        Starvation guard: plain youngest-first can thrash a request
        forever at high load (admit -> immediately re-preempt, every
        step). A request preempted ``max_preempts`` times becomes EXEMPT:
        the victim search is youngest-first over non-exempt slots, and
        only when every candidate is exempt does it fall back to the
        OLDEST candidate (which has been resident longest, so evicting
        it lets the exempt cohort drain before it thrashes anew)."""
        candidates = [s for s in self._admit_order if s != protect]
        victim = next(
            (s for s in reversed(candidates)
             if self.stats.preempt_counts.get(self.active[s].req.rid, 0)
             < self.max_preempts),
            candidates[0] if candidates else None,
        )
        if victim is None:
            return False
        st = self.active.pop(victim)
        self._admit_order.remove(victim)
        self.pool.release(victim)
        self._cur_tok[victim, 0] = 0
        self.queue.appendleft(st.req)
        rid = st.req.rid
        self.stats.preempt_counts[rid] = (
            self.stats.preempt_counts.get(rid, 0) + 1
        )
        self.stats.requests[rid].preemptions += 1
        return True

    def _ensure_capacity(self) -> int:
        """Every active slot gets a page for this step's K/V write —
        preempting youngest-first when the pool runs dry."""
        preempted = 0
        for slot in list(self._admit_order):
            if slot not in self.active:
                continue
            need = int(self.pool.lengths[slot]) + 1
            while not self.pool.ensure(slot, need):
                if not self._preempt_youngest(protect=slot):
                    raise RuntimeError(
                        "page pool too small for a single sequence: "
                        f"slot {slot} needs {need} tokens, "
                        f"{self.pool.free_page_count} pages free"
                    )
                preempted += 1
        return preempted

    # -- the step -----------------------------------------------------------

    def step(self) -> StepStats:
        """Admit, ensure capacity (preempting if needed), decode one
        token for every active slot, evict finished sequences."""
        with obs.span("serve.step", step=self._step_idx):
            return self._step()

    def _step(self) -> StepStats:
        self._prefill_routes = None
        admitted = self._admit()
        preempted = self._ensure_capacity()
        finished = 0
        tokens_generated = 0
        decode_routes = None

        if self.active:
            with obs.span("serve.decode"):
                pools, logits = self._decode(
                    self.params,
                    jnp.array(self._cur_tok),  # a copy: mutated below
                    self.pool.pools,
                    self.pool.device_table(),
                    self.pool.device_lengths(),
                )
                self.pool.pools = pools
            with obs.span("serve.fetch"):
                if "routes" in pools:  # one transfer with the logits
                    logits_np, decode_routes = jax.device_get(
                        (logits, pools["routes"]))
                else:
                    logits_np = np.asarray(logits)
            with obs.span("serve.sample"):
                slots = list(self._admit_order)
                self.pool.bump_lengths(slots)
                for slot in slots:
                    st = self.active[slot]
                    nxt = self._sample(logits_np[slot])
                    st.generated.append(nxt)
                    self._cur_tok[slot, 0] = nxt
                    tokens_generated += 1
                    if len(st.generated) >= st.target:
                        self._finish(slot)
                        finished += 1

        stats = StepStats(
            step=self._step_idx,
            queue_depth=len(self.queue),
            active_slots=len(self.active),
            pool_occupancy=self.pool.occupancy(),
            admitted=admitted,
            finished=finished,
            preempted=preempted,
            tokens_generated=tokens_generated,
            decode_routes=decode_routes,
            prefill_routes=self._prefill_routes,
        )
        self.stats.steps.append(stats)
        self._step_idx += 1
        return stats

    def run(
        self,
        requests: list[Request] | None = None,
        *,
        max_steps: int | None = None,
    ) -> tuple[dict[int, np.ndarray], ServeStats]:
        """Drain the queue: step until every request completes.

        Returns ({rid: generated token ids}, per-step ServeStats).
        """
        for req in requests or ():
            self.submit(req)
        limit = max_steps if max_steps is not None else 100_000
        steps = 0
        while (self.queue or self.active) and steps < limit:
            self.step()
            steps += 1
        if self.queue or self.active:
            raise RuntimeError(f"scheduler did not drain in {limit} steps")
        return self.results, self.stats
