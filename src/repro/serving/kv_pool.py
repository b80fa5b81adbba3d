"""Unified paged KV pool for the serving engine (paper §5.1).

One physical pool of ``num_pages`` K and V pages is shared by **all** of a
node's paged attention layers — the paper's "pool of pages unified for all
local layers".  A token occupies one slot in one page *per paged layer*, so a
logical sequence block costs ``num_paged_layers`` physical pages.  Page 0 is
a scratch page: empty block-table entries point at it, so inactive batch
slots write/read it harmlessly inside the jitted decode step.

Pools are stored as ``kernels.paged_attention.layout.page_layout`` lays
them out, ``(num_pages, rows, lanes)``: rows of ``lcm(head_dim, 128)``
lanes, tokens major, each page whole ``(8, 128)`` tiles (OLMo-1B
``(P, 256, 128)``, SmolLM-360M ``(P, 40, 128)``).  That module holds the
rule the pool, its writes, the decode kernel and the sizing below share.

Pool-sizing math (see ``pages_for_vram``), per KV dtype, in the bytes the
chip stores (``PagePool.bytes_stored``):

    | quantity             | param dtype (bf16)        | kv_dtype="int8"     |
    |----------------------|---------------------------|---------------------|
    | kv element bytes     | 2                         | 1                   |
    | page_bytes           | 2 * rows * lanes * 2      | 2 * rows * lanes    |
    |                      |  (= 2 * page * KH * D * 2)|  (= 2 * page*KH*D)  |
    | scale_bytes / page   | 0                         | 2 * tiled(KH) * 4   |
    | num_pages            | pool_bytes // page_bytes  | pool_bytes //       |
    |                      |                           |  (page_bytes        |
    |                      |                           |   + scale_bytes)    |
    | token capacity       | (num_pages - 1) * page / n_paged_layers         |

plus the dtype-independent rows:

    | param_bytes (node)    | param_count * b * layers_on_node / num_layers |
    | pool bytes available  | vram_bytes - param_bytes                      |
    | per-seq budget (NP)   | ceil(max_seq_len / page_size) blocks          |
    | min viable pool       | 1 + NP * n_paged_layers pages                 |

The int8 scales are ``(num_pages, KH)`` float32, which XLA stores with the
pages minor and ``KH`` rounded up to a tile's 8 rows (``tiled``: 5 kv heads
hold 8 rows of scales; 1, 2, 4 and multiples of 8 are exact).
With ``kv_dtype="int8"`` a page stores int8 elements plus one float32 absmax
scale per (page, kv_head) for K and V each, so page cost drops from
``4*page*KH*D`` bytes (K+V bf16) to ``2*page*KH*D + 8*tiled(KH)`` — ≈2× the
token capacity at fixed VRAM.  Unlike the dense engine's
``max_batch * max_len`` rectangle, capacity is shared: many short sequences
or a few long ones fit the same pool, which is exactly the
asymmetric-memory slack Helix's placement exploits on heterogeneous nodes.

Allocation is on-demand (a block per ``page_size`` tokens, across layers),
freed on request completion/preemption; admission control blocks new
requests — and decode preempts the newest running request — when the pool is
exhausted, instead of overflowing.  The free list is a preallocated numpy
stack: growing a slot by ``n`` blocks is one vectorized slice pop covering
all ``n * num_layers`` pages (``alloc_ops`` counts these bulk operations,
not pages — tests pin the O(1) behaviour).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..kernels.paged_attention.layout import PageLayout, page_layout, tiled
from ..models.paged import num_paged_layers
from .trace import TRACER


def cfg_layout(cfg: ModelConfig, page: int) -> PageLayout:
    return page_layout(page, cfg.num_kv_heads, cfg.resolved_head_dim)


class PoolExhausted(RuntimeError):
    """Raised when a request needs more pages than the pool can ever hold."""


class PagePool:
    """Shared K/V page pool + per-slot block tables and a free list.

    Device arrays ``k``/``v`` are stored as ``page_layout`` lays them out,
    ``(num_pages, rows, lanes)`` (``layout.to_tokens`` views a page as
    ``(page_size, kv_heads, head_dim)``), and are updated functionally by
    the jitted model steps (the engine stores the returned arrays back).
    With ``kv_dtype="int8"`` they are int8 and ``k_scales``/``v_scales``
    hold the (num_pages, kv_heads) float32 per-page absmax scales (None
    otherwise).  ``bytes_stored`` and ``bytes_logical`` are what the chip
    holds for the pool and what its tokens need; each pool adds them to the
    tracer's ``kv_pool.bytes_stored`` / ``kv_pool.bytes_logical`` counters.
    The block table is a host ``(num_paged_layers, max_batch,
    blocks_per_seq)`` int32 array; row order is prologue layers first, then
    pattern positions repeat-major, matching ``models.paged`` layer
    numbering.
    """

    def __init__(self, cfg: ModelConfig, *, num_pages: int, page_size: int,
                 max_batch: int, max_seq_len: int, dtype=None,
                 paged_layers: Optional[int] = None,
                 kv_dtype: Optional[str] = None):
        self.cfg = cfg
        self.page = page_size
        # a stage engine's pool covers only the node's layer slice: pass the
        # slice's paged-block count so a token costs one page per *local*
        # paged layer, not per model layer
        self.num_layers = paged_layers if paged_layers is not None \
            else num_paged_layers(cfg)
        if self.num_layers == 0:
            raise ValueError(f"{cfg.name}: no full-attention GQA blocks — "
                             "nothing to page; use the dense engine")
        self.blocks_per_seq = -(-max_seq_len // page_size)
        min_pages = 1 + self.blocks_per_seq * self.num_layers
        if num_pages < min_pages:
            raise ValueError(
                f"pool of {num_pages} pages cannot hold one full request: "
                f"need >= {min_pages} (1 scratch + {self.blocks_per_seq} "
                f"blocks x {self.num_layers} layers)")
        if kv_dtype not in (None, "param", "int8"):
            raise ValueError(f"kv_dtype must be 'param' or 'int8', "
                             f"got {kv_dtype!r}")
        self.kv_dtype = "int8" if kv_dtype == "int8" else "param"
        self.quantized = self.kv_dtype == "int8"
        self.layout = cfg_layout(cfg, page_size)
        kh = cfg.num_kv_heads
        if self.quantized:
            dtype = jnp.int8
            self.k_scales = jnp.zeros((num_pages, kh), jnp.float32)
            self.v_scales = jnp.zeros((num_pages, kh), jnp.float32)
        else:
            if dtype is None:
                dtype = {"bfloat16": jnp.bfloat16,
                         "float32": jnp.float32}[cfg.param_dtype]
            self.k_scales = None
            self.v_scales = None
        self.num_pages = num_pages
        self.k = jnp.zeros(self.layout.shape(num_pages), dtype)
        self.v = jnp.zeros(self.layout.shape(num_pages), dtype)
        itemsize = jnp.dtype(dtype).itemsize
        self.bytes_stored = num_pages * _page_bytes(self.layout, itemsize,
                                                    self.quantized)
        self.bytes_logical = num_pages * 2 * (
            self.layout.logical_bytes(itemsize)
            + (4 * kh if self.quantized else 0))
        TRACER.count("kv_pool.bytes_stored", self.bytes_stored)
        TRACER.count("kv_pool.bytes_logical", self.bytes_logical)
        # page 0 reserved as scratch; the free list is a preallocated stack
        # whose live region is _free[:_free_top] (top of stack at the end,
        # matching the old list.pop() order: page 1 first, then 2, ...)
        self._free = np.arange(num_pages - 1, 0, -1, dtype=np.int32)
        self._free_top = num_pages - 1
        self.alloc_ops = 0          # bulk ensure/release ops (not pages)
        self.table = np.zeros((self.num_layers, max_batch,
                               self.blocks_per_seq), np.int32)
        self._nblocks = np.zeros((max_batch,), np.int64)

    # ------------------------------------------------------------------
    @property
    def used(self) -> int:
        """Pages currently allocated (scratch page excluded)."""
        return (self.num_pages - 1) - self._free_top

    @property
    def tokens_used(self) -> int:
        """Token capacity currently allocated (block granularity) — what the
        scheduler's KVEstimator should see as this node's true occupancy."""
        return int(self._nblocks.sum()) * self.page

    @property
    def tokens_capacity(self) -> int:
        """Total token capacity of the pool (block granularity)."""
        return ((self.num_pages - 1) // self.num_layers) * self.page

    def capacity_tokens(self, slot: int) -> int:
        return int(self._nblocks[slot]) * self.page

    def pages_needed(self, slot: int, tokens: int) -> int:
        blocks = -(-tokens // self.page) - int(self._nblocks[slot])
        return max(0, blocks) * self.num_layers

    def can_fit(self, slot: int, tokens: int) -> bool:
        return self.pages_needed(slot, tokens) <= self._free_top

    def ensure(self, slot: int, tokens: int) -> bool:
        """Grow ``slot``'s allocation to hold ``tokens``.  Returns False if
        the pool is currently exhausted (caller blocks or preempts); raises
        PoolExhausted if ``tokens`` exceeds the per-sequence budget.

        Doubles as the in-flight reservation primitive: the ClusterRuntime
        calls it on every stage node when it *launches* a decode pass, so by
        the time the token reaches a mid-pipeline node its block is already
        held — allocated blocks can only be taken back by release or
        preemption, never by another request's growth.

        One call is one batched pop from the free-list stack no matter how
        many blocks x layers it covers."""
        target = -(-tokens // self.page)
        if target > self.blocks_per_seq:
            raise PoolExhausted(
                f"{tokens} tokens > per-sequence budget "
                f"{self.blocks_per_seq * self.page}")
        if not self.can_fit(slot, tokens):
            return False
        j0 = int(self._nblocks[slot])
        grow = target - j0
        if grow <= 0:
            return True
        n = grow * self.num_layers
        # stack pop order matches the old per-page loop: layer index fastest,
        # block index outer — popped[i] is the i-th page the loop would take
        popped = self._free[self._free_top - n:self._free_top][::-1]
        self._free_top -= n
        self.table[:, slot, j0:j0 + grow] = \
            popped.reshape(grow, self.num_layers).T
        self._nblocks[slot] = target
        self.alloc_ops += 1
        return True

    def release(self, slot: int) -> None:
        """Return all of ``slot``'s pages to the free list (one batched
        push)."""
        nb = int(self._nblocks[slot])
        if nb:
            n = nb * self.num_layers
            # push order matches the old loop: block outer, layer fastest
            self._free[self._free_top:self._free_top + n] = \
                self.table[:, slot, :nb].T.reshape(-1)
            self._free_top += n
            self.alloc_ops += 1
        self.table[:, slot, :] = 0
        self._nblocks[slot] = 0

    def truncate(self, slot: int, tokens: int) -> None:
        """Shrink ``slot``'s allocation to hold exactly ``tokens`` rows —
        the page-frontier rollback primitive for rejected speculative
        drafts.  Blocks past the new frontier go back to the free list in
        one batched push (``alloc_ops`` counts it like ensure/release).

        Rolled-back rows inside the *kept* frontier block are not zeroed
        here: param-dtype attention masks them by position, and on the int8
        path ``quantized_append`` recomputes a page's scale purely from its
        live rows (zeroing rows past the append window first), so a freed
        page self-cleans on reuse.  The int8-exact restore of the kept
        frontier page's bytes+scales is the engine's job (it snapshots the
        page after each verify sub-step — see PagedStageEngine.rollback)."""
        target = -(-tokens // self.page)
        nb = int(self._nblocks[slot])
        if target >= nb:
            return
        n = (nb - target) * self.num_layers
        # push order matches release: block outer, layer fastest
        self._free[self._free_top:self._free_top + n] = \
            self.table[:, slot, target:nb].T.reshape(-1)
        self._free_top += n
        self.table[:, slot, target:nb] = 0
        self._nblocks[slot] = target
        self.alloc_ops += 1


def full_rectangle_pages(cfg: ModelConfig, *, max_batch: int, max_len: int,
                         page_size: int,
                         paged_layers: Optional[int] = None) -> int:
    """Pages for a dense-equivalent full allocation — every slot holding its
    whole ``max_len`` budget — plus the scratch page.  Pools this size can
    never block or preempt; smaller pools oversubscribe.  ``paged_layers``
    overrides the model-wide paged-block count for stage-slice pools.
    (Page *counts* are dtype-independent — int8 shrinks page_bytes, not the
    block math.)"""
    blocks = -(-max_len // page_size)
    layers = paged_layers if paged_layers is not None \
        else num_paged_layers(cfg)
    return 1 + blocks * layers * max_batch


def _page_bytes(layout: PageLayout, itemsize: int, quantized: bool) -> int:
    """Bytes the chip stores for one page of K and V (and its scales)."""
    scales = 2 * tiled(layout.kv_heads) * 4 if quantized else 0
    return 2 * layout.page_bytes(itemsize) + scales


def page_bytes(cfg: ModelConfig, page_size: int,
               kv_dtype: Optional[str] = None) -> int:
    """Bytes the chip stores for one pool page (K + V + int8 scale rows,
    if any), as ``page_layout`` lays it out."""
    quantized = kv_dtype == "int8"
    itemsize = 1 if quantized else \
        {"bfloat16": 2, "float32": 4}[cfg.param_dtype]
    return _page_bytes(cfg_layout(cfg, page_size), itemsize, quantized)


def pages_for_vram(cfg: ModelConfig, vram_bytes: float, *, page_size: int,
                   layers_on_node: Optional[int] = None,
                   max_pages: Optional[int] = None,
                   kv_dtype: Optional[str] = None) -> int:
    """Size a pool from node VRAM the way ``sim.Simulator`` sizes its KV
    capacity: whatever VRAM the node's parameter slice does not use becomes
    pages.  ``layers_on_node`` is the Helix layer-slice size (defaults to the
    whole model); ``max_pages`` caps the result (useful for smoke models
    whose tiny pages would otherwise number in the millions).
    ``kv_dtype="int8"`` halves the per-page cost (1-byte elements plus
    ``2 * tiled(kv_heads) * 4`` scale bytes per page) — ≈2x the pages from
    the same VRAM.  Pages cost what the chip stores (``page_bytes``)."""
    elt = {"bfloat16": 2, "float32": 4}[cfg.param_dtype]
    pb = page_bytes(cfg, page_size, kv_dtype)
    layers = layers_on_node if layers_on_node is not None else cfg.num_layers
    param_bytes = cfg.param_count() * elt * layers / max(cfg.num_layers, 1)
    free = max(0.0, vram_bytes - param_bytes)
    pages = int(free // pb)
    if max_pages is not None:
        pages = min(pages, max_pages)
    return pages
