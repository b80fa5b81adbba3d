"""Per-node stage engines: the execution half of a Helix compute node.

``Engine``/``PagedEngine`` (engine.py) own the whole request lifecycle for a
single full-model node.  A *stage engine* is the same machinery split at the
stage boundary: it holds only the params (``models.stage.stage_params``) and
KV for one node's assigned ``LayerRange`` and exposes a stage-level API the
``ClusterRuntime`` drives:

  prefill_stage(slot, x, entry)    prompt pass for one request; ``x`` is
                                   token ids (entry layer 0) or incoming
                                   activations; returns activations, or
                                   last-token logits at the final stage
  prefill_chunk(slot, x, entry, start)   chunked paged prefill (all-paged)
  decode_stage(items)              ONE batched decode step over whatever
                                   stage-work is resident this iteration —
                                   per-node continuous batching; items may
                                   mix requests entering at different layers
  sample(logits, temperature)      final-stage sampling at temperature > 0

At the final stage each decode step also takes the greedy token of every
row on the device (``DecodeOut.token``) and copies back only those ids;
the full-vocabulary logits are copied only when some item of the batch
asked for them (``DecodeItem.want_logits``).  Off the final stage a step
copies back the activations the next stage reads, and nothing else.

Slot mechanics: caches (and the paged pool's block table) carry
``max_batch + 1`` rows; the extra row is scratch — decode batches are padded
to a fixed width with scratch rows so every step hits one compiled program,
and scratch writes land in cache rows (or page 0) nothing ever reads.

The paged engine's ``PagePool`` is sized from the node's own VRAM with the
page cost of its *local* paged-layer count, so memory heterogeneity shows up
as genuinely different pool depths per node.

Device: an engine computes on the default device in effect when it is built
(``ClusterRuntime`` builds node *i* under ``jax.default_device`` of device
*i*), and commits its params, caches and pool there; host inputs — tokens,
block tables, activations from the previous stage — are put on that device
before each step.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.placement import LayerRange
from ..kernels.paged_attention import streamed_pages_per_step
from ..models.paged import all_blocks_paged, is_paged_block
from ..models.stage import (stage_absorb_dense_prefill, stage_blocks,
                            stage_cache_init, stage_cache_init_paged,
                            stage_decode, stage_decode_paged,
                            stage_num_paged_layers, stage_params,
                            stage_prefill, stage_prefill_chunk_paged)
from .engine import EngineConfig, _active_blocks_bucket
from .kv_pool import PagePool, full_rectangle_pages
from .sampling import sample_token
from .trace import TRACER


@dataclasses.dataclass
class DecodeItem:
    """One request's decode-step input resident at a node this iteration.

    A single-token item carries ``token`` (entry 0) or ``h`` of shape
    (1, 1, d).  A speculative verify pass carries ``tokens`` — the last
    confirmed token followed by the draft proposals, consumed at positions
    ``pos .. pos+n-1`` — or, downstream of the entry stage, ``h`` of shape
    (n, 1, d).  The engines run multi-token items as ``n`` position-ordered
    sub-steps, so the KV write history (and on int8 pools the per-page
    requantization history) is byte-identical to ``n`` ordinary decode
    steps — acceptance rate can only change speed, never bytes.

    ``want_logits`` says the caller reads this row's full-vocabulary
    logits.  The runtime sets it exactly for rows that sample at
    ``temperature > 0``; a greedy row reads ``DecodeOut.token`` alone, and
    a step whose rows are all greedy copies no logits off the device.  It
    defaults to True so a caller that checks logits gets them."""

    slot: int
    pos: int                      # absolute position of the FIRST token
    entry: int                    # request's entry layer at this node
    token: int = 0                # consumed only when entry == 0
    h: Optional[np.ndarray] = None  # (n, 1, d) incoming activations
    tokens: Optional[Sequence[int]] = None  # verify pass (entry == 0 only)
    want_logits: bool = True      # final stage: return this row's logits

    @property
    def n(self) -> int:
        """Token count of this item (1 for ordinary decode)."""
        if self.tokens is not None:
            return len(self.tokens)
        if self.h is not None and getattr(self.h, "ndim", 0) == 3:
            return int(self.h.shape[0])
        return 1

    def substep(self, s: int) -> "DecodeItem":
        """The single-token item for sub-step ``s`` (position ``pos + s``)."""
        return DecodeItem(
            slot=self.slot, pos=self.pos + s, entry=self.entry,
            token=int(self.tokens[s]) if self.tokens is not None
            else self.token,
            h=None if self.h is None else np.asarray(self.h[s:s + 1]),
            want_logits=self.want_logits)


@dataclasses.dataclass
class DecodeOut:
    """One item's decode result.  Off the final stage: ``h``.  At the
    final stage: ``token``, the device's argmax of the row's logits (the
    first maximum, as ``np.argmax`` picks it), and ``logits`` when the item
    asked for them."""
    h: Optional[np.ndarray]       # (n, 1, d) outgoing activations
    logits: Optional[np.ndarray]  # (V,) — or (n, V) for a verify pass
    token: Optional[Union[int, np.ndarray]] = None  # int — or (n,) int32


def _greedy(logits):
    """Each row's greedy token, inside the step's program (None off the
    final stage)."""
    return None if logits is None else \
        jnp.argmax(logits, axis=-1).astype(jnp.int32)


class _StageEngineBase:
    """Slot bookkeeping shared by the dense and paged stage engines."""

    def __init__(self, cfg: ModelConfig, params, layers: LayerRange,
                 engine_cfg: EngineConfig, rng_seed: int = 0):
        self.cfg = cfg
        self.layers = layers
        self.ec = engine_cfg
        dev = jax.config.jax_default_device
        self.device = dev if isinstance(dev, jax.Device) else jax.devices()[0]
        # slice where the full params live, then move only the slice
        with jax.default_device(_home_device(params) or self.device):
            sparams = stage_params(cfg, params, layers)
        self.sparams = self._put(sparams)
        self.is_first = layers.start == 0
        self.is_last = layers.end == cfg.num_layers
        self.slots: List[Optional[int]] = [None] * engine_cfg.max_batch
        self._scratch = engine_cfg.max_batch   # padding row, never allocated
        self._rng = np.random.RandomState(rng_seed)
        self.decode_calls = 0                  # index of the next decode step

    def _put(self, tree):
        """Commit ``tree`` (host or device arrays) to this engine's device."""
        return jax.device_put(tree, self.device)

    # -- slots ----------------------------------------------------------
    def alloc_slot(self, request_id: int) -> Optional[int]:
        for i, r in enumerate(self.slots):
            if r is None:
                self.slots[i] = request_id
                return i
        return None

    def free_slot(self, slot: int) -> None:
        self.slots[slot] = None

    @property
    def free_slots(self) -> int:
        return sum(r is None for r in self.slots)

    # -- sampling (final stage) -----------------------------------------
    def sample(self, logits: np.ndarray, temperature: float) -> int:
        return int(sample_token(logits, temperature, self._rng))

    # -- KV feedback -----------------------------------------------------
    def kv_tokens_used(self) -> int:
        raise NotImplementedError

    def kv_tokens_capacity(self) -> int:
        raise NotImplementedError

    def pool_used(self) -> Optional[int]:
        """Allocated page count, or None for engines without a page pool —
        uniform across local and remote engines so the runtime's drain
        checks work over RPC."""
        pool = getattr(self, "pool", None)
        return pool.used if pool is not None else None

    # -- batch assembly ---------------------------------------------------
    def _assemble(self, items: List[DecodeItem]):
        B = self.ec.max_batch + 1
        if not 0 < len(items) <= self.ec.max_batch:
            raise ValueError(f"{len(items)} decode items for "
                             f"{self.ec.max_batch} slots")
        # one batched step gathers/scatters each cache row once, so a batch
        # holding tokens t and t+1 of one request would lose t's KV write.
        # Multi-token speculation is handled above this guard: decode_stage
        # splits verify items into position-ordered sub-batches, each of
        # which reaches _assemble with one token per request — so within
        # any assembled batch slots are still unique by construction.
        slots = [it.slot for it in items]
        if len(set(slots)) != len(slots):
            raise ValueError(
                "duplicate cache slot in one decode batch: in-flight tokens "
                "of a request must decode in separate, position-ordered "
                f"batches (slots={slots})")
        d = self.cfg.d_model
        idx = np.full((B,), self._scratch, np.int32)
        tok = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        entry = np.full((B,), self.layers.end, np.int32)  # pads: all masked
        h_in = np.zeros((B, 1, d), np.float32)
        for i, it in enumerate(items):
            idx[i] = it.slot
            tok[i] = it.token
            pos[i] = it.pos
            entry[i] = it.entry
            if it.h is not None:
                h_in[i] = it.h
        return self._put((idx, tok, pos, entry, h_in))

    # -- decode orchestration ---------------------------------------------
    def _decode_span(self, items: List[DecodeItem]):
        """The ``helix.engine.decode`` span of one step: its call index and
        rows, the K/V pages its kernel streams (paged engines), and (in
        memory only) each row's context after the step."""
        i = self.decode_calls
        self.decode_calls += 1
        ctx = tuple(it.pos + 1 for it in items)
        return TRACER.span("helix.engine.decode", call=i, rows=len(items),
                           ctx=ctx, **self._streamed(ctx))

    def _streamed(self, ctx) -> Dict[str, int]:
        """Span attrs for the K/V a step over contexts ``ctx`` streams."""
        return {}

    @staticmethod
    def _fetch(*outs):
        """Wait for a step's outputs on the device, then copy them to the
        host (``None`` stays ``None``); the fetch span's ``bytes`` is what
        the copies move."""
        with TRACER.span("helix.engine.wait"):
            jax.block_until_ready(outs)
        nbytes = sum(o.nbytes for o in outs if o is not None)
        with TRACER.span("helix.engine.fetch", bytes=nbytes):
            return tuple(None if o is None else np.asarray(o) for o in outs)

    def _fetch_decode(self, items: List[DecodeItem], h, logits, ids):
        """Copy back what a decode step's callers read, as (h, logits,
        ids): ``h`` off the final stage; at the final stage the ids, and
        the (B, V) logits only when some item asked for them."""
        if not self.is_last:
            return self._fetch(h) + (None, None)
        if not any(it.want_logits for it in items):
            return (None, None) + self._fetch(ids)
        TRACER.count("decode.logits_rows", int(logits.shape[0]))
        return (None,) + self._fetch(logits, ids)

    def _decode_step(self, items: List[DecodeItem]):
        """One batched single-token decode step.  Returns (h, logits, ids)
        as numpy arrays of shape (B, 1, d), (B, V) and (B,), rows in item
        order, or None where ``_fetch_decode`` copies nothing."""
        raise NotImplementedError

    def _spec_begin(self, it: DecodeItem) -> None:
        """Hook before a multi-token item's first sub-step (clears any
        stale rollback snapshots for the slot)."""

    def _snap_substep(self, it: DecodeItem, s: int) -> None:
        """Hook after a multi-token item's sub-step ``s`` committed its KV
        write — int8 pools snapshot the frontier page for exact rollback."""

    def rollback(self, slot: int, tokens: int) -> None:
        """Forget ``slot``'s rows >= ``tokens`` (rejected draft suffix)."""
        raise NotImplementedError

    def decode_stage(self, items: List[DecodeItem]) -> List[DecodeOut]:
        """ONE batched decode step over the stage-work resident this
        iteration.  Multi-token (speculative verify) items are run as
        position-ordered sub-batches: sub-step ``s`` batches the s-th token
        of every item that has one, so a request's token at ``pos+s``
        decodes strictly after its KV write at ``pos+s-1`` — the same write
        history as ``n`` ordinary decode steps, which is what keeps greedy
        speculative output byte-identical (dense, paged and int8 alike)."""
        n = max(it.n for it in items)
        if n == 1:
            # normalize length-1 ``tokens`` items into plain token items
            items = [it if it.tokens is None else it.substep(0)
                     for it in items]
            h, l, t = self._decode_step(items)
            return [DecodeOut(
                h=None if h is None else h[i:i + 1],
                logits=l[i] if l is not None and it.want_logits else None,
                token=None if t is None else int(t[i]))
                for i, it in enumerate(items)]
        for it in items:
            if it.n > 1:
                self._spec_begin(it)
        hs: List[List[np.ndarray]] = [[] for _ in items]
        ls: List[List[np.ndarray]] = [[] for _ in items]
        ts: List[List[int]] = [[] for _ in items]
        for s in range(n):
            sel = [i for i, it in enumerate(items) if s < it.n]
            sub = [items[i].substep(s) for i in sel]
            h, l, t = self._decode_step(sub)
            for k, i in enumerate(sel):
                if h is not None:
                    hs[i].append(h[k:k + 1])
                if l is not None and items[i].want_logits:
                    ls[i].append(l[k])
                if t is not None:
                    ts[i].append(int(t[k]))
                if items[i].n > 1:
                    self._snap_substep(items[i], s)
        outs = []
        for i, it in enumerate(items):
            if it.n == 1:   # single-token shapes: (1,1,d) / (V,) / int
                outs.append(DecodeOut(h=hs[i][0] if hs[i] else None,
                                      logits=ls[i][0] if ls[i] else None,
                                      token=ts[i][0] if ts[i] else None))
            else:
                outs.append(DecodeOut(
                    h=np.concatenate(hs[i], axis=0) if hs[i] else None,
                    logits=np.stack(ls[i], axis=0) if ls[i] else None,
                    token=np.asarray(ts[i], np.int32) if ts[i] else None))
        return outs


def _home_device(params):
    """The one device ``params`` live on, or None for host arrays."""
    leaf = jax.tree.leaves(params)[0]
    if isinstance(leaf, jax.Array) and len(leaf.devices()) == 1:
        return next(iter(leaf.devices()))
    return None


def _splice(full, one, slot: int):
    """Copy a batch-1 cache leaf into row ``slot`` of the engine leaf."""
    return full.at[slot].set(one[0])


class StageEngine(_StageEngineBase):
    """Dense per-slot caches over the node's layer slice."""

    def __init__(self, cfg: ModelConfig, params, layers: LayerRange,
                 engine_cfg: EngineConfig, rng_seed: int = 0):
        super().__init__(cfg, params, layers, engine_cfg, rng_seed)
        ec = engine_cfg
        self.caches = self._put(stage_cache_init(cfg, layers,
                                                 ec.max_batch + 1,
                                                 ec.max_len))
        self._prefill = jax.jit(
            lambda sp, x, entry: stage_prefill(cfg, sp, layers, x, entry,
                                               max_len=ec.max_len),
            static_argnums=(2,))

        def decode_fn(sp, caches, tok, h_in, entry, pos, idx):
            cg = jax.tree.map(lambda c: c[idx], caches)
            h, logits, nc = stage_decode(cfg, sp, layers, tok, h_in, entry,
                                         cg, pos)
            new = jax.tree.map(lambda full, n: full.at[idx].set(n),
                               caches, nc)
            return h, logits, _greedy(logits), new

        self._decode = jax.jit(decode_fn)
        self._active_tokens = np.zeros((ec.max_batch,), np.int64)

    def prefill_stage(self, slot: int, x, entry: int):
        """Prompt pass for one request.  x: (S,) int token ids when
        ``entry == 0`` else (1, S, d) activations.  Returns (1, S, d)
        activations, or (V,) last-token logits at the final stage."""
        if entry == 0:
            S = len(x)
            xin = self._put(np.asarray(x, np.int32)[None, :])
        else:
            S = x.shape[1]
            xin = self._put(x)
        out, caches1 = self._prefill(self.sparams, xin, entry)
        self.caches = jax.tree.map(
            lambda full, one: _splice(full, one, slot), self.caches, caches1)
        self._active_tokens[slot] = S
        return np.asarray(out)[0] if self.is_last else np.asarray(out)

    def _decode_step(self, items: List[DecodeItem]):
        with self._decode_span(items):
            with TRACER.span("helix.engine.inputs"):
                idx, tok, pos, entry, h_in = self._assemble(items)
            with TRACER.span("helix.engine.launch"):
                h, logits, ids, self.caches = self._decode(
                    self.sparams, self.caches, tok, h_in, entry, pos, idx)
            for it in items:
                self._active_tokens[it.slot] = it.pos + 1
            return self._fetch_decode(items, h, logits, ids)

    def rollback(self, slot: int, tokens: int) -> None:
        """Dense caches are positional and attention masks rows >= pos, so
        forgetting a rejected draft suffix is pure bookkeeping — relaunched
        tokens overwrite their rows in place."""
        self._active_tokens[slot] = tokens

    def release(self, slot: int) -> None:
        self._active_tokens[slot] = 0
        self.free_slot(slot)

    def ensure(self, slot: int, tokens: int) -> bool:
        return tokens <= self.ec.max_len   # rectangle is pre-reserved

    def kv_tokens_used(self) -> int:
        return int(self._active_tokens.sum())

    def kv_tokens_capacity(self) -> int:
        return self.ec.max_batch * self.ec.max_len

    # -- KV handoff (disaggregated prefill -> decode replicas) -----------
    def export_kv(self, slot: int, tokens: int, layers: List[int]):
        """Snapshot this slot's filled caches for the given *global* layer
        indices as a wire tree ``{layer: cache subtree}`` (batchless
        leaves) — the decode replica splices them with ``import_kv``."""
        want = set(layers)
        out = {}
        for (l, _), c in zip(stage_blocks(self.cfg, self.layers),
                             self.caches):
            if l in want:
                out[l] = jax.tree.map(lambda a: np.asarray(a[slot]), c)
        return out

    def import_kv(self, slot: int, tokens: int, payload) -> None:
        new = []
        for (l, _), c in zip(stage_blocks(self.cfg, self.layers),
                             self.caches):
            one = payload.get(l)
            if one is None:
                new.append(c)
            else:
                new.append(jax.tree.map(
                    lambda full, a: full.at[slot].set(self._put(a)),
                    c, one))
        self.caches = new
        self._active_tokens[slot] = tokens


def paged_decode_step(cfg: ModelConfig, layers: LayerRange,
                      interpret: bool):
    """The paged engine's decode step over ``layers``, to be jitted: the
    batch's cache rows and block-table columns gathered by ``idx``, the
    slice's layers, and at the final stage the logits and their greedy
    ids, in one program.  Returns ``(h, logits, ids, caches, k_pages,
    v_pages, k_scales, v_scales)``."""
    def step(sp, caches, tok, h_in, entry, pos, idx, kp, vp, ks, vs,
             tables):
        cg = jax.tree.map(lambda c: c[idx], caches)
        tb = tables[:, idx]
        h, logits, nc, kp, vp, ks, vs = stage_decode_paged(
            cfg, sp, layers, tok, h_in, entry, cg, pos, kp, vp, tb,
            k_scales=ks, v_scales=vs, interpret=interpret)
        new = jax.tree.map(lambda full, n: full.at[idx].set(n), caches, nc)
        return h, logits, _greedy(logits), new, kp, vp, ks, vs
    return step


class PagedStageEngine(_StageEngineBase):
    """Paged-KV stage engine: the node's paged blocks share one ``PagePool``
    sized from its VRAM; everything else keeps dense fallback caches."""

    def __init__(self, cfg: ModelConfig, params, layers: LayerRange,
                 engine_cfg: EngineConfig, *, num_pages: Optional[int] = None,
                 page_size: int = 16, kv_dtype: Optional[str] = None,
                 interpret: Optional[bool] = None, rng_seed: int = 0):
        super().__init__(cfg, params, layers, engine_cfg, rng_seed)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = interpret
        ec = engine_cfg
        self.n_paged = stage_num_paged_layers(cfg, layers)
        if self.n_paged == 0:
            raise ValueError(f"slice {layers} of {cfg.name} holds no paged "
                             "blocks; use the dense StageEngine")
        self._chunked = all_blocks_paged(cfg)
        if num_pages is None:
            num_pages = full_rectangle_pages(cfg, max_batch=ec.max_batch,
                                             max_len=ec.max_len,
                                             page_size=page_size,
                                             paged_layers=self.n_paged)
        # the scratch slot never allocates, so the pool only needs capacity
        # for the real max_batch; the extra table column stays on page 0
        self.pool = PagePool(cfg, num_pages=num_pages, page_size=page_size,
                             max_batch=ec.max_batch + 1, max_seq_len=ec.max_len,
                             paged_layers=self.n_paged, kv_dtype=kv_dtype)
        self.caches = self._put(stage_cache_init_paged(
            cfg, layers, ec.max_batch + 1, ec.max_len))
        pool = self.pool
        pool.k, pool.v, pool.k_scales, pool.v_scales = self._put(
            (pool.k, pool.v, pool.k_scales, pool.v_scales))
        on_cpu = jax.default_backend() == "cpu"
        if self._chunked:
            def _chunk(sp, x, entry, start, kp, vp, ks, vs, tb, *,
                       n_act: int):
                return stage_prefill_chunk_paged(
                    cfg, sp, layers, x, entry, start, kp, vp, tb,
                    k_scales=ks, v_scales=vs, active_blocks=n_act)
            self._prefill_chunk = jax.jit(
                _chunk, static_argnums=(2,), static_argnames=("n_act",),
                donate_argnums=() if on_cpu else (4, 5, 6, 7))
        else:
            self._prefill_one = jax.jit(
                lambda sp, x, entry: stage_prefill(cfg, sp, layers, x, entry,
                                                   max_len=ec.max_len),
                static_argnums=(2,))

        self._decode = jax.jit(
            paged_decode_step(cfg, layers, interpret),
            donate_argnums=() if on_cpu else (7, 8, 9, 10))
        # per-slot {kept_tokens: {page_id: (k, v, ks, vs)}} verify snapshots
        self._spec_snaps: Dict[int, Dict[int, dict]] = {}

    # -- pool ------------------------------------------------------------
    def _streamed(self, ctx) -> Dict[str, int]:
        return {"pages": self.n_paged
                * streamed_pages_per_step(ctx, self.pool.page)}

    def ensure(self, slot: int, tokens: int) -> bool:
        return self.pool.ensure(slot, tokens)

    def release(self, slot: int) -> None:
        self._spec_snaps.pop(slot, None)
        self.pool.release(slot)
        self.free_slot(slot)

    def kv_tokens_used(self) -> int:
        return self.pool.tokens_used

    def kv_tokens_capacity(self) -> int:
        return self.pool.tokens_capacity

    # -- prefill ---------------------------------------------------------
    def prefill_chunk(self, slot: int, x, entry: int, start: int):
        """One prompt chunk through the slice (all-paged stacks).  x: (C,)
        tokens or (1, C, d) activations.  Returns chunk activations
        (1, C, d), or last-token logits (V,) at the final stage."""
        C = len(x) if entry == 0 else x.shape[1]
        pool = self.pool
        with TRACER.span("helix.engine.prefill", request=self.slots[slot],
                         start=int(start), width=int(C)):
            with TRACER.span("helix.engine.inputs"):
                if entry == 0:
                    xin = self._put(np.asarray(x, np.int32)[None, :])
                else:
                    xin = self._put(x)
                tb = self._put(pool.table[:, slot:slot + 1])
                start_in = self._put(np.asarray([start], np.int32))
            n_act = _active_blocks_bucket(start + C, pool.page,
                                          pool.blocks_per_seq)
            with TRACER.span("helix.engine.launch"):
                out, pool.k, pool.v, pool.k_scales, pool.v_scales = \
                    self._prefill_chunk(
                        self.sparams, xin, entry, start_in,
                        pool.k, pool.v, pool.k_scales, pool.v_scales, tb,
                        n_act=n_act)
            out, = self._fetch(out)
        return out[0] if self.is_last else out

    def prefill_stage(self, slot: int, x, entry: int):
        """Single-shot prompt pass (hybrid stacks): dense prefill of the
        slice, then the paged blocks' K/V is scattered into this slot's
        pages and the dense fallback caches spliced into the slot."""
        if self._chunked:
            raise RuntimeError("all-paged slice: drive prefill_chunk instead")
        if entry == 0:
            S = len(x)
            xin = self._put(np.asarray(x, np.int32)[None, :])
        else:
            S = x.shape[1]
            xin = self._put(x)
        out, caches1 = self._prefill_one(self.sparams, xin, entry)
        pool = self.pool
        caches1, pool.k, pool.v, pool.k_scales, pool.v_scales = \
            stage_absorb_dense_prefill(
                self.cfg, self.layers, caches1, pool.k, pool.v,
                pool.table, slot, S, pool.page,
                k_scales=pool.k_scales, v_scales=pool.v_scales)
        self.caches = jax.tree.map(
            lambda full, one: _splice(full, one, slot), self.caches, caches1)
        return np.asarray(out)[0] if self.is_last else np.asarray(out)

    # -- KV handoff (disaggregated prefill -> decode replicas) -----------
    def export_kv(self, slot: int, tokens: int, layers: List[int]):
        """Snapshot this slot's KV for the given *global* layer indices:
        paged blocks ship their live pages (int8 pages + per-page scales
        travel as-is, no requantization), hybrid dense blocks ship their
        cache subtree."""
        want = set(layers)
        nb = -(-tokens // self.pool.page)
        out = {}
        li = 0
        for (l, b), c in zip(stage_blocks(self.cfg, self.layers),
                             self.caches):
            paged = is_paged_block(self.cfg, b)
            if l in want:
                if paged:
                    pids = self.pool.table[li, slot, :nb]
                    p = {"k": np.asarray(self.pool.k[pids]),
                         "v": np.asarray(self.pool.v[pids])}
                    if self.pool.quantized:
                        p["ks"] = np.asarray(self.pool.k_scales[pids])
                        p["vs"] = np.asarray(self.pool.v_scales[pids])
                    out[l] = p
                else:
                    out[l] = jax.tree.map(lambda a: np.asarray(a[slot]), c)
            if paged:
                li += 1
        return out

    def import_kv(self, slot: int, tokens: int, payload) -> None:
        """Scatter a shipped KV snapshot into this slot.  The runtime
        reserves the slot's blocks at admission; ``ensure`` here is a
        defensive no-op growth in the common case."""
        if not self.pool.ensure(slot, tokens):
            raise RuntimeError(
                f"import_kv: pool cannot hold {tokens} tokens in slot "
                f"{slot}")
        nb = -(-tokens // self.pool.page)
        pool = self.pool
        new = []
        li = 0
        for (l, b), c in zip(stage_blocks(self.cfg, self.layers),
                             self.caches):
            paged = is_paged_block(self.cfg, b)
            p = payload.get(l)
            if p is None:
                new.append(c)
            elif paged:
                pids = self._put(pool.table[li, slot, :nb])
                pool.k = pool.k.at[pids].set(
                    self._put(p["k"]).astype(pool.k.dtype))
                pool.v = pool.v.at[pids].set(
                    self._put(p["v"]).astype(pool.v.dtype))
                if pool.quantized:
                    pool.k_scales = pool.k_scales.at[pids].set(
                        self._put(p["ks"]))
                    pool.v_scales = pool.v_scales.at[pids].set(
                        self._put(p["vs"]))
                new.append(c)
            else:
                new.append(jax.tree.map(
                    lambda full, a: full.at[slot].set(self._put(a)),
                    c, p))
            if paged:
                li += 1
        self.caches = new

    # -- decode ----------------------------------------------------------
    def _decode_step(self, items: List[DecodeItem]):
        pool = self.pool
        with self._decode_span(items):
            with TRACER.span("helix.engine.inputs"):
                idx, tok, pos, entry, h_in = self._assemble(items)
                tables = self._put(pool.table)
            with TRACER.span("helix.engine.launch"):
                (h, logits, ids, self.caches, pool.k, pool.v,
                 pool.k_scales, pool.v_scales) = self._decode(
                    self.sparams, self.caches, tok, h_in, entry, pos, idx,
                    pool.k, pool.v, pool.k_scales, pool.v_scales, tables)
            return self._fetch_decode(items, h, logits, ids)

    # -- speculative rollback --------------------------------------------
    def _spec_begin(self, it: DecodeItem) -> None:
        if self.pool.quantized:
            self._spec_snaps[it.slot] = {}

    def _snap_substep(self, it: DecodeItem, s: int) -> None:
        """After verify sub-step ``s`` wrote row ``it.pos + s``, snapshot
        each paged layer's frontier page (bytes + scales), keyed by the
        token count a rollback to this sub-step would keep.

        Needed because ``quantized_append`` requantizes the whole touched
        page: a later — ultimately rejected — sub-step landing in the same
        page can raise its absmax scale and perturb the kept rows' bytes.
        Truncation alone cannot undo that; restoring this snapshot can."""
        if not self.pool.quantized:
            return           # row-granular writes: truncation is byte-exact
        pool = self.pool
        pos = it.pos + s
        snaps = {}
        for li in range(pool.num_layers):
            pid = int(pool.table[li, it.slot, pos // pool.page])
            snaps[pid] = (np.asarray(pool.k[pid]), np.asarray(pool.v[pid]),
                          np.asarray(pool.k_scales[pid]),
                          np.asarray(pool.v_scales[pid]))
        self._spec_snaps.setdefault(it.slot, {})[pos + 1] = snaps

    def rollback(self, slot: int, tokens: int) -> None:
        """Truncate ``slot``'s KV to ``tokens`` rows after a partially
        rejected verify pass.  int8 pools additionally restore the kept
        frontier pages from the matching sub-step snapshot, leaving the
        pool byte-identical to a history that only ever decoded the
        accepted prefix; freed blocks self-clean on reuse because
        ``quantized_append`` zeroes rows past the append window before
        computing scales."""
        pool = self.pool
        snaps = self._spec_snaps.pop(slot, None)
        if pool.quantized and snaps:
            snap = snaps.get(tokens)
            if snap is not None:
                for pid, (k, v, ks, vs) in snap.items():
                    pool.k = pool.k.at[pid].set(self._put(k))
                    pool.v = pool.v.at[pid].set(self._put(v))
                    pool.k_scales = pool.k_scales.at[pid].set(
                        self._put(ks))
                    pool.v_scales = pool.v_scales.at[pid].set(
                        self._put(vs))
        pool.truncate(slot, tokens)


def make_stage_engine(cfg: ModelConfig, params, layers: LayerRange,
                      engine_cfg: EngineConfig, *, paged: bool = True,
                      **kw) -> _StageEngineBase:
    if paged:
        return PagedStageEngine(cfg, params, layers, engine_cfg, **kw)
    kw.pop("num_pages", None)
    kw.pop("page_size", None)
    kw.pop("kv_dtype", None)
    kw.pop("interpret", None)
    return StageEngine(cfg, params, layers, engine_cfg, **kw)
