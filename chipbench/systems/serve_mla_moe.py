"""Open-loop serving of Kimi-K2's block through ``repro.serve.Scheduler``.

The open loop, the schedule, the window, the drain and the end-to-end
numbers are ``serve_lm``'s (this system subclasses it). What differs is the
model: latent attention (MLA) over the paged latent pool and the held
experts' dropless MoE layer (``chipbench/configs/kimi_k2.json``), its seeded
weights (``mla_moe_weights.py``), its counters and its check.

Counters add, over the window's steps, the routes the program counted to
each held expert (``ServeStats``), read by ``expert_matmul_roofline.serve``
and the ``moe_*_mfu`` metrics.

Correctness, after the window, once the program's state is freed: a
sample of finished requests drawn from the seed, the longest among them,
holding at least the traffic's ``check_tokens`` served tokens, is run by
the float32 reference (``references/mla_moe_lm.py``, the same share of the
experts) over prompt + served tokens; the widest gap by which a served
(greedy) token's logit lies below the reference's largest is held to the
cell's limit, and so is the 99th percentile of those gaps; every finished
request must carry exactly the tokens it asked for, and nothing may
compile in the window. Both gap limits are needed: in bf16 a token's
router scores move by rounding, and where two experts' scores nearly tie
and one of them is held, the program and the float32 reference choose
differently in that layer now and then. That is a discrete change of the
layer's output, and it leaves a gap of up to a few tenths at a few tokens
in a thousand (at a mid size on the CPU they vanish with no choice to
make, 8 experts of 8, and in float32). The widest gap bounds those; the
99th percentile, which they do not reach, is what a lower precision
moves for many tokens. The control
(``check(..., quant="fp8")``) puts in the served tokens' place the tokens
that the reference computed in float8 puts first. ``gap_report`` gives,
besides, what a served token altered by one id would read at each checked
position (``altered``): how surely the widest gap's limit catches one
wrong token alone.

A program without latent attention or an expert share cannot run this
configuration: the system refuses before it makes any weight.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import mla_moe_weights
from chipbench.systems import serve_lm


def _program_can_serve() -> None:
    from repro.models.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    missing = {"kv_lora_rank", "n_experts_held", "router_scoring"} - fields
    if missing:
        raise RuntimeError("the program cannot serve this configuration: its "
                           f"ModelConfig lacks {sorted(missing)}")


def model_config(m: dict):
    """The program's ModelConfig for configuration ``m``."""
    import jax.numpy as jnp
    from repro.models.config import ModelConfig

    k = mla_moe_weights.dims(m)
    return ModelConfig(
        name=m["name"], family="moe", n_layers=k["n_layers"], d_model=k["d"],
        n_heads=k["heads"], n_kv_heads=k["heads"], head_dim=k["v"],
        d_ff=k["ff"], vocab_size=k["vocab"], rope_theta=k["theta"],
        q_lora_rank=k["q_lora"], kv_lora_rank=k["kv_lora"],
        qk_nope_head_dim=k["nope"], qk_rope_head_dim=k["rope"],
        v_head_dim=k["v"], yarn_factor=k["yarn_factor"],
        yarn_original_len=k["yarn_original"],
        yarn_beta_fast=k["yarn_beta_fast"], yarn_beta_slow=k["yarn_beta_slow"],
        yarn_mscale=k["yarn_mscale"], n_dense_layers=k["n_dense"],
        n_experts=k["experts"], experts_per_token=k["top_k"],
        moe_d_ff=k["f"], shared_expert_d_ff=k["shared"],
        router_scoring="sigmoid", routed_scaling=k["scaling"],
        n_experts_held=k["held"], expert_offset=k["offset"],
        norm_eps=k["eps"], tie_embeddings=False,
        param_dtype=jnp.dtype(m["dtype"]), compute_dtype=jnp.dtype(m["dtype"]),
    )


class System(serve_lm.System):
    """Kimi-K2's block behind the continuous-batching scheduler."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices, spans,
                 *, rate: float | None = None):
        _program_can_serve()
        import jax
        from repro.models import transformer as T
        from repro.models.params import tree_sds
        from repro.serve import PoolConfig, Scheduler

        k = mla_moe_weights.dims(cfg)
        # the names serve_lm's counters report the model under
        self.cfg = dict(cfg, d_model=k["d"], n_heads=k["heads"],
                        n_kv_heads=k["heads"], head_dim=k["v"], d_ff=k["ff"])
        self.traffic, self.seed, self.spans = traffic, seed, spans
        self.rate = rate or traffic["arrivals"]["rate_rps"]
        self.reference_setup_s = 0.0
        self.mcfg = model_config(cfg)
        params = mla_moe_weights.make(cfg, seed, cfg["dtype"])
        want = jax.tree_util.tree_structure(
            tree_sds(T.model_defs(self.mcfg), self.mcfg.param_dtype))
        if jax.tree_util.tree_structure(params) != want:
            raise ValueError("the program's parameter layout is not the one "
                             "chipbench/mla_moe_weights.py makes")
        s = cfg["serving"]
        pages = -(-s["max_len"] // s["block_size"])
        self.pc = PoolConfig(
            max_batch=s["max_batch"], block_size=s["block_size"],
            n_blocks=s["max_batch"] * pages + 1, max_len=s["max_len"],
            prompt_pad=s["prompt_pad"],
        )
        self.sch = Scheduler(self.mcfg, params, self.pc)
        self.t_base = None
        self.reqs = []
        self.next = 0
        self.live = {}
        self.steps = []
        self.routes = []  # (t_end, decode routes, prefill routes) a step
        self.window = (0.0, 0.0)
        self.preempted = 0

    def _step(self) -> None:
        super()._step()
        st = self.sch.stats.steps[-1]
        self.routes.append((self.steps[-1][0], st.decode_routes,
                            st.prefill_routes))

    def counters(self) -> dict:
        c = super().counters()
        t0, t1 = self.window
        win = [r for r in self.routes if t0 < r[0] <= t1]
        dec = [r[1] for r in win if r[1] is not None]
        pre = [r[2] for r in win if r[2] is not None]
        c["mla_moe"] = mla_moe_weights.dims(self.cfg)
        c["routes_decode"] = int(sum(int(r.sum()) for r in dec))
        # (layer, held expert) pairs with a route, summed over decode steps
        c["experts_hit_decode"] = int(sum(int(np.count_nonzero(r))
                                          for r in dec))
        c["routes_prefill"] = int(sum(int(r.sum()) for r in pre))
        c["report"]["routes"] = {
            "decode": c["routes_decode"], "prefill": c["routes_prefill"],
            "experts_hit_decode": c["experts_hit_decode"],
            "decode_steps_with_routes": len(dec)}
        return c

    def check(self, limits: dict, quant: str | None = None) -> tuple:
        """The widest gap of served tokens under the float32 reference;
        with ``quant``, the control: the gap of the tokens that the
        reference computed in ``quant`` puts first, at the same positions."""
        import jax.numpy as jnp

        from chipbench.references import mla_moe_lm

        due = self._due_in_window()
        missing = sum(1 for r in due if not r.stamps)
        by_rid = {r.rid: r for r in self.reqs}
        done = [rid for rid in self.results if rid in by_rid]
        wrong_len = sum(1 for rid in done
                        if len(self.results[rid]) != by_rid[rid].max_new)
        rng = np.random.default_rng(np.random.SeedSequence([self.seed % 2**64,
                                                            4]))
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
        model = mla_moe_lm.LM.of(mla_moe_weights.dims(m))
        params = mla_moe_weights.make(m, self.seed, m["dtype"])
        s = m["serving"]
        n_out = s["max_len"] - s["prompt_pad"]
        gaps, altered = [], []
        for rid in pick:
            r, out = by_rid[rid], np.asarray(self.results[rid], np.int32)
            seq = np.zeros(s["max_len"], np.int32)
            seq[: len(r.prompt)] = r.prompt
            seq[len(r.prompt): len(r.prompt) + len(out)] = out
            seq = jnp.asarray(seq)
            if quant is None:
                g, a = mla_moe_lm.served_gaps(params, seq, len(r.prompt),
                                              n_out, model)
                altered.append(np.asarray(a)[: len(out)])
            else:
                g = mla_moe_lm.choice_gaps(params, seq, len(r.prompt), n_out,
                                           model, quant)
            gaps.append(np.asarray(g)[: len(out)])
        gaps = np.concatenate(gaps)
        self.gap_report = {
            "n": int(gaps.size), "over_0.05": int((gaps > 0.05).sum()),
            **{f"p{q}": float(np.percentile(gaps, q)) for q in (50, 90, 95, 99)},
            "max": float(gaps.max())}
        if altered:
            # what one served token altered by one id would read at each
            # checked position: the share above the max limit is the
            # chance that the limit catches one such token alone
            a = np.concatenate(altered)
            self.gap_report["altered"] = {
                "min": float(a.min()), "p1": float(np.percentile(a, 1)),
                "p50": float(np.median(a)),
                "over_max_limit": float(np.mean(
                    a > float(limits["served_gap_max"])))}
        checks = {
            "served_gap_max": {"value": float(gaps.max()),
                               "limit": float(limits["served_gap_max"])},
            "served_gap_p99": {"value": float(np.percentile(gaps, 99)),
                               "limit": float(limits["served_gap_p99"])},
            "wrong_lengths": {"value": float(wrong_len), "limit": 0.0},
            "recompiles": {"value": float(sum(self.final_traces.values())
                                          - sum(self.warm_traces.values())),
                           "limit": 0.0},
        }
        self.checked_tokens = n_tok
        return checks, len(due), missing
