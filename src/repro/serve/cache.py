"""Paged KV cache: a shared page pool with per-slot block tables.

Device memory for attention K/V is one preallocated pool of
``(n_blocks, block_size, KV, Dh)`` pages per layer (see
``transformer.paged_cache_defs``); a latent-attention (MLA) model keeps
one pool of ``(n_blocks, block_size, kv_lora_rank + rope)`` latent rows
per layer in their place, written in place (donated).  A sequence occupies a *slot*
(0..max_batch) and references pages through a host-side
``(max_batch, n_pages)`` block table — pool memory scales with live
tokens across all sequences, not ``max_batch * max_len``.

Page 0 is the reserved **null page**: it is never handed out, inactive
slots point every table entry at it, and prefill scatters pad blocks
into it.  Reads through the null page are masked out by the decode
kernel (length 0 ⇒ fully masked), so padding lanes stay harmless at a
fixed compiled shape.

State that is length-independent — SSM recurrent state, conv history,
whisper cross K/V — does not need paging; it lives in per-slot arrays
indexed by slot id.  ``write_prefill`` hides the difference: it takes
a contiguous batch-1 prefill cache (from ``transformer.prefill``) and
lands it in the pool, whatever the family.

All device writes go through ``TracedJit`` wrappers so the scheduler
can assert zero recompiles after warmup.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.models.config import ModelConfig


class TracedJit:
    """jax.jit wrapper that counts traces.

    The counter increments inside the traced function — a Python side
    effect that only fires at trace time — so ``traces`` is exactly the
    number of compilations this instance has triggered. The compiled
    program is named after ``fn`` (``jit_prefill``, ``jit_decode_step_paged``;
    a ``functools.partial`` by the function it wraps), so a profile shows
    which program ran.
    """

    def __init__(self, fn, **jit_kwargs):
        self.traces = 0

        def counted(*args, **kwargs):
            self.traces += 1
            return fn(*args, **kwargs)

        counted.__name__ = getattr(fn, "__name__", None) or fn.func.__name__
        self._fn = jax.jit(counted, **jit_kwargs)

    def __call__(self, *args, **kwargs):
        return self._fn(*args, **kwargs)


@dataclasses.dataclass(frozen=True)
class PoolConfig:
    """Sizing for a CachePool.

    max_batch   scheduler slots (fixed decode batch shape)
    block_size  tokens per KV page
    n_blocks    total pages in the pool, INCLUDING the reserved null
                page 0 (so n_blocks - 1 are allocatable)
    max_len     per-sequence token capacity (prompt + generated)
    prompt_pad  fixed padded prompt length for prefill; must be a
                multiple of block_size so prompt K/V tiles onto pages
    """

    max_batch: int = 8
    block_size: int = 16
    n_blocks: int = 64
    max_len: int = 128
    prompt_pad: int = 32

    def __post_init__(self):
        if self.prompt_pad % self.block_size != 0:
            raise ValueError("prompt_pad must be a multiple of block_size")
        if self.max_len < self.prompt_pad:
            raise ValueError("max_len must cover prompt_pad")
        if self.n_blocks < 2:
            raise ValueError("need at least the null page + one real page")

    @property
    def n_pages(self) -> int:
        """Block-table width: pages needed to cover max_len tokens."""
        return -(-self.max_len // self.block_size)


def _scatter_blocks(pool, vals, page_ids):
    """Write a contiguous (n, P, KV, Dh) K/V slab into pool pages.

    page_ids has P // block_size entries; entries equal to 0 dump their
    (pad) block into the null page.  Duplicate indices only ever occur
    at page 0, where the result is garbage either way.
    """
    n, P = vals.shape[0], vals.shape[1]
    bs = pool.shape[2]
    blocks = vals.reshape(n, P // bs, bs, *vals.shape[2:])
    return pool.at[:, page_ids].set(blocks.astype(pool.dtype))


def _set_slot(arr, val, slot):
    """Write a batch-1 per-slot state (n, 1, ...) into row `slot`."""
    return arr.at[:, slot].set(val[:, 0].astype(arr.dtype))


class CachePool:
    """Page pool + block tables + slot accounting for one served model.

    Host side: free-page and free-slot lists, the block table, and
    per-slot lengths (all numpy).  Device side: the pool arrays from
    ``paged_cache_defs`` (mutated functionally each step — the
    scheduler reassigns ``self.pools``).

    Typical life of a sequence:
        slot = pool.alloc_slot()
        pool.ensure(slot, prompt_len)        # pages for the prompt
        pool.write_prefill(slot, cache)      # land prefill K/V + state
        pool.set_length(slot, prompt_len)
        ... per decode step: pool.ensure(slot, length + 1) ...
        pool.release(slot)                   # pages back to the free list
    """

    def __init__(self, cfg: ModelConfig, pc: PoolConfig):
        self.cfg = cfg
        self.pc = pc
        self.n_pages = pc.n_pages
        self.pools = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            T.paged_cache_defs(
                cfg, pc.max_batch, pc.n_blocks, pc.block_size, self.n_pages
            ),
        )
        # attention-free families (ssm) never touch the page pool; the
        # null table still feeds decode_step_paged's (ignored) args
        self.paged = cfg.family in ("dense", "moe", "hybrid", "encdec")
        self.table = np.zeros((pc.max_batch, self.n_pages), np.int32)
        self.lengths = np.zeros((pc.max_batch,), np.int32)
        self._pages_of: list[list[int]] = [[] for _ in range(pc.max_batch)]
        self._free_pages = list(range(pc.n_blocks - 1, 0, -1))  # 0 = null
        self._free_slots = list(range(pc.max_batch - 1, -1, -1))
        self._dirty = True
        self._table_dev = None
        self._lengths_dev = None
        self._scatter = TracedJit(_scatter_blocks)
        self._set_slot = TracedJit(_set_slot)
        # the latent pool is written in place: a second pool would not fit
        # beside the weights of a model that needs one
        self._scatter_latent = TracedJit(_scatter_blocks, donate_argnums=(0,))

    # -- accounting ---------------------------------------------------------

    @property
    def free_page_count(self) -> int:
        return len(self._free_pages)

    @property
    def used_page_count(self) -> int:
        return (self.pc.n_blocks - 1) - len(self._free_pages)

    @property
    def free_slot_count(self) -> int:
        return len(self._free_slots)

    @property
    def active_slots(self) -> list[int]:
        free = set(self._free_slots)
        return [s for s in range(self.pc.max_batch) if s not in free]

    def occupancy(self) -> float:
        """Fraction of allocatable pages currently held by slots."""
        denom = self.pc.n_blocks - 1
        return self.used_page_count / denom if denom else 0.0

    @property
    def trace_count(self) -> int:
        return (self._scatter.traces + self._set_slot.traces
                + self._scatter_latent.traces)

    def pages_needed(self, n_tokens: int) -> int:
        if not self.paged:
            return 0
        return -(-n_tokens // self.pc.block_size)

    # -- slot / page lifecycle ----------------------------------------------

    def alloc_slot(self) -> int | None:
        """Claim a free scheduler slot (or None if the batch is full)."""
        if not self._free_slots:
            return None
        return self._free_slots.pop()

    def ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow slot's page allocation to cover n_tokens; False on OOM.

        On failure nothing changes — the caller preempts a victim and
        retries, or gives up.
        """
        if n_tokens > self.pc.max_len:
            raise ValueError(
                f"n_tokens={n_tokens} exceeds max_len={self.pc.max_len}"
            )
        need = self.pages_needed(n_tokens) - len(self._pages_of[slot])
        if need <= 0:
            return True
        if need > len(self._free_pages):
            return False
        for _ in range(need):
            page = self._free_pages.pop()
            self.table[slot, len(self._pages_of[slot])] = page
            self._pages_of[slot].append(page)
        self._dirty = True
        return True

    def release(self, slot: int) -> None:
        """Return slot's pages to the free list and reset its table row.

        Per-slot state (ssm/conv/cross) is NOT zeroed — the next
        write_prefill into this slot overwrites it entirely.
        """
        self._free_pages.extend(reversed(self._pages_of[slot]))
        self._pages_of[slot] = []
        self.table[slot, :] = 0
        self.lengths[slot] = 0
        self._free_slots.append(slot)
        self._dirty = True

    def set_length(self, slot: int, n_tokens: int) -> None:
        self.lengths[slot] = n_tokens
        self._dirty = True

    def bump_lengths(self, slots: list[int]) -> None:
        """Advance lengths after a decode step appended one token/slot."""
        for s in slots:
            self.lengths[s] += 1
        self._dirty = True

    # -- device views -------------------------------------------------------

    def device_table(self) -> jax.Array:
        self._refresh()
        return self._table_dev

    def device_lengths(self) -> jax.Array:
        self._refresh()
        return self._lengths_dev

    def _refresh(self) -> None:
        if self._dirty or self._table_dev is None:
            # copies: on the CPU jnp.asarray may alias the numpy buffers,
            # which the host mutates in place while dispatched work that
            # read them can still be running
            self._table_dev = jnp.array(self.table)
            self._lengths_dev = jnp.array(self.lengths)
            self._dirty = False

    # -- landing prefill results --------------------------------------------

    def _prompt_page_ids(self, slot: int) -> jax.Array:
        """Page ids for the prompt_pad // block_size prefill blocks.

        Blocks past the slot's allocation (prompt padding) target the
        null page; their garbage K/V is never read back.
        """
        n_prompt = self.pc.prompt_pad // self.pc.block_size
        ids = np.zeros((n_prompt,), np.int32)
        own = self._pages_of[slot][:n_prompt]
        ids[: len(own)] = own
        return jnp.asarray(ids)

    def write_prefill(self, slot: int, cache: dict) -> None:
        """Land a batch-1 contiguous prefill cache into the pool.

        `cache` comes from ``transformer.prefill`` run at shape
        (1, prompt_pad).  Attention K/V slabs are scattered onto this
        slot's pages; slot-indexed state (ssm/conv/cross) is written at
        row `slot`.  Call ``set_length`` afterwards with the TRUE
        prompt length (pad blocks land in the null page and pad
        positions within the last valid block are masked by length).
        """
        fam = self.cfg.family
        slot_dev = jnp.int32(slot)
        if self.cfg.is_mla:
            lat = cache["latent"][:, 0]
            pad = self.pools["latent"].shape[-1] - lat.shape[-1]
            self.pools = {**self.pools, "latent": self._scatter_latent(
                self.pools["latent"], jnp.pad(lat, ((0, 0), (0, 0), (0, pad))),
                self._prompt_page_ids(slot))}
        elif fam in ("dense", "moe"):
            ids = self._prompt_page_ids(slot)
            self.pools = {
                "k": self._scatter(self.pools["k"], cache["k"][:, 0], ids),
                "v": self._scatter(self.pools["v"], cache["v"][:, 0], ids),
            }
        elif fam == "ssm":
            self.pools = {
                k: self._set_slot(self.pools[k], cache[k], slot_dev)
                for k in ("state", "conv")
            }
        elif fam == "hybrid":
            ids = self._prompt_page_ids(slot)
            self.pools = {
                "ssm": {
                    k: self._set_slot(
                        self.pools["ssm"][k], cache["ssm"][k], slot_dev
                    )
                    for k in ("state", "conv")
                },
                "attn": {
                    k: self._scatter(
                        self.pools["attn"][k], cache["attn"][k][:, 0], ids
                    )
                    for k in ("k", "v")
                },
            }
        elif fam == "encdec":
            ids = self._prompt_page_ids(slot)
            self.pools = {
                "self": {
                    k: self._scatter(
                        self.pools["self"][k], cache["self"][k][:, 0], ids
                    )
                    for k in ("k", "v")
                },
                "cross": {
                    k: self._set_slot(
                        self.pools["cross"][k], cache["cross"][k], slot_dev
                    )
                    for k in ("k", "v")
                },
            }
        else:
            raise ValueError(fam)

    # -- debugging / parity helpers -----------------------------------------

    def gather_kv(self, slot: int, n_tokens: int) -> dict | None:
        """Read back slot's K/V as contiguous (n, n_tokens, KV, Dh) numpy
        arrays, or its latent rows as (n, n_tokens, R) under 'latent'
        (dense/moe only) — parity-test convenience, host-side."""
        if self.cfg.family not in ("dense", "moe"):
            return None
        names = ("latent",) if self.cfg.is_mla else ("k", "v")
        pages = self._pages_of[slot]
        bs = self.pc.block_size
        out = {}
        for name in names:
            pool = np.asarray(self.pools[name])
            slab = pool[:, pages]  # (n, P, bs, KV, Dh)
            n = slab.shape[0]
            slab = slab.reshape(n, len(pages) * bs, *slab.shape[3:])
            out[name] = slab[:, :n_tokens]
        if self.cfg.is_mla:  # without the lane padding
            out["latent"] = out["latent"][..., : self.cfg.latent_dim]
        return out
