"""Stage-level model execution: run a contiguous layer slice of the stack.

Helix's MILP assigns each node a contiguous ``LayerRange``; a *stage engine*
executes only those blocks, receiving token ids (first stage) or incoming
activations and emitting activations (or sampling-ready logits at the final
stage).  This module is the model-side counterpart of
``repro.serving.stage_engine``:

  stage_params(cfg, params, layers)      param slice a stage engine holds
  stage_cache_init[_paged]               per-block decode caches for the slice
  stage_prefill                          prompt pass over the slice (dense)
  stage_decode                           one decode step, batched + row-masked
  stage_prefill_chunk_paged              chunked paged prefill over the slice
  stage_decode_paged                     paged decode over the slice
  stage_absorb_dense_prefill             hybrid: dense prefill K/V -> pages

Per-row entry masking: §3.3 *partial inference* means a request may enter a
node mid-range (layers already inferred upstream are skipped), and per-node
continuous batching mixes requests with different entry layers in one decode
step.  Each block therefore applies only to rows with ``row_start <= layer``;
masked rows pass their hidden state through unchanged.  Masked rows still
write their (meaningless) K/V into their own cache rows / pages — those
entries are never read, because a request's entry layer is fixed for its
lifetime at a node.

Unlike the full-model path, the slice runs as an unrolled Python loop over at
most ``layers.num_layers`` blocks (no ``lax.scan`` over stacked params): each
node holds only its slice, so compiled size stays proportional to the slice.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import BlockSpec, ModelConfig
from ..core.placement import LayerRange
from .common import apply_norm
from .model import (_apply_block, _apply_block_decode, _cache_init_for_block,
                    _embed, _logits, fill_prefill_cache)
from .paged import (_block_decode_paged, _block_prefill_paged, absorb_layer,
                    is_paged_block)


def _dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.param_dtype]


# ---------------------------------------------------------------------------
# Slice layout
# ---------------------------------------------------------------------------

def stage_blocks(cfg: ModelConfig, layers: LayerRange
                 ) -> List[Tuple[int, BlockSpec]]:
    """(global layer index, BlockSpec) for every block in the slice."""
    blocks = cfg.blocks
    if not (0 <= layers.start < layers.end <= cfg.num_layers):
        raise ValueError(f"layer range {layers} outside [0, {cfg.num_layers})")
    return [(l, blocks[l]) for l in range(layers.start, layers.end)]


def stage_num_paged_layers(cfg: ModelConfig, layers: LayerRange) -> int:
    return sum(is_paged_block(cfg, b) for _, b in stage_blocks(cfg, layers))


def stage_all_paged(cfg: ModelConfig, layers: LayerRange) -> bool:
    return all(is_paged_block(cfg, b) for _, b in stage_blocks(cfg, layers))


def stage_params(cfg: ModelConfig, params, layers: LayerRange) -> Dict:
    """Extract the param subtree one stage needs: per-block params for
    [start, end) plus the embedding table (first stage, and the last stage
    when embeddings are tied), final norm + LM head (last stage).

    Block params come out of the stacked ``super`` tree as per-layer slices,
    so a node materializes only its share of the repeated stack.
    """
    if cfg.is_encoder_decoder:
        raise NotImplementedError(
            "stage execution covers decoder-only stacks; "
            f"{cfg.name} is encoder-decoder")
    P = len(cfg.prologue)
    pat = max(1, len(cfg.pattern))
    first = layers.start == 0
    last = layers.end == cfg.num_layers
    out: Dict[str, Any] = {"blocks": []}
    for l, _ in stage_blocks(cfg, layers):
        if l < P:
            out["blocks"].append(params["prologue"][l])
        else:
            r, i = divmod(l - P, pat)
            out["blocks"].append(jax.tree.map(lambda x, r=r: x[r],
                                              params["super"][f"pos{i}"]))
    if first or (last and cfg.tie_embeddings):
        out["embed"] = params["embed"]
    if last:
        out["final_norm"] = params["final_norm"]
        if not cfg.tie_embeddings:
            out["lm_head"] = params["lm_head"]
    return out


def stage_cache_init(cfg: ModelConfig, layers: LayerRange, batch: int,
                     max_len: int) -> List:
    """Dense per-block decode caches for the slice (batch-major leaves)."""
    dt = _dtype(cfg)
    return [_cache_init_for_block(cfg, b, batch, max_len, dt)
            for _, b in stage_blocks(cfg, layers)]


def stage_cache_init_paged(cfg: ModelConfig, layers: LayerRange, batch: int,
                           max_len: int) -> List:
    """Like ``stage_cache_init`` but paged blocks hold ``{}`` — their KV
    lives in the node's page pool."""
    dt = _dtype(cfg)
    return [{} if is_paged_block(cfg, b)
            else _cache_init_for_block(cfg, b, batch, max_len, dt)
            for _, b in stage_blocks(cfg, layers)]


# ---------------------------------------------------------------------------
# Dense prefill / decode over the slice
# ---------------------------------------------------------------------------

def stage_prefill(cfg: ModelConfig, sparams, layers: LayerRange, x,
                  entry: int, *, max_len: int):
    """Prompt pass over blocks [entry, layers.end).

    ``entry`` is the request's entry layer at this node (static;
    ``layers.start <= entry < layers.end``).  ``x`` is token ids (B,S) when
    ``entry == 0`` else incoming activations (B,S,d).  Returns
    ``(out, caches)`` where ``out`` is last-token logits (B,V) when the slice
    ends the model, else outgoing activations (B,S,d); ``caches`` covers all
    local blocks (skipped prefix blocks get fresh inits so the pytree matches
    the engine's slot layout).
    """
    last = layers.end == cfg.num_layers
    if entry == 0:
        B, S = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h = _embed(cfg, sparams, x, positions)
    else:
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h = x
    dt = _dtype(cfg)
    caches: List = []
    for (l, b), p in zip(stage_blocks(cfg, layers), sparams["blocks"]):
        if l < entry:
            caches.append(_cache_init_for_block(cfg, b, B, max_len, dt))
            continue
        h, raw, _ = _apply_block(cfg, b, p, h, positions, None,
                                 collect_cache=True)
        caches.append(fill_prefill_cache(cfg, b, raw, B, S, max_len, dt))
    if last:
        h = apply_norm(cfg, sparams["final_norm"], h)
        return _logits(cfg, sparams, h[:, -1:])[:, 0], caches
    return h, caches


def stage_decode(cfg: ModelConfig, sparams, layers: LayerRange, tok, h_in,
                 row_start, caches, cache_pos):
    """One batched decode step over the slice with per-row entry masking.

    tok: (B,) int32 token ids (consumed only by rows entering at layer 0 —
    possible only when ``layers.start == 0``); h_in: (B,1,d) incoming
    activations; row_start: (B,) int32 entry layer per row; cache_pos: (B,).
    Returns ``(h_out (B,1,d), logits (B,V) | None, new_caches)`` — logits are
    computed iff the slice ends the model.
    """
    positions = cache_pos[:, None]
    if layers.start == 0:
        emb = _embed(cfg, sparams, tok[:, None], positions)
        h = jnp.where((row_start == 0)[:, None, None], emb,
                      h_in.astype(emb.dtype))
    else:
        h = h_in.astype(_dtype(cfg))
    new_caches: List = []
    for (l, b), p, c in zip(stage_blocks(cfg, layers), sparams["blocks"],
                            caches):
        h_new, nc = _apply_block_decode(cfg, b, p, h, c, cache_pos, None)
        h = jnp.where((row_start <= l)[:, None, None], h_new, h)
        new_caches.append(nc)
    logits = None
    if layers.end == cfg.num_layers:
        hn = apply_norm(cfg, sparams["final_norm"], h)
        logits = _logits(cfg, sparams, hn)[:, 0]
    return h, logits, new_caches


# ---------------------------------------------------------------------------
# Paged prefill / decode over the slice
# ---------------------------------------------------------------------------

def stage_prefill_chunk_paged(cfg: ModelConfig, sparams, layers: LayerRange,
                              x, entry: int, start_pos, k_pages, v_pages,
                              tables, *, k_scales=None, v_scales=None,
                              active_blocks=None):
    """Prefill one prompt chunk through the slice, appending K/V to the
    node's pool.  Only valid when every block in [entry, layers.end) is paged
    (use ``stage_prefill`` + ``stage_absorb_dense_prefill`` for hybrids).

    x: (B,C) tokens when ``entry == 0`` else (B,C,d); start_pos: (B,)
    absolute position of x[:, 0]; tables: (n_local_paged, B, NP) block
    tables in local paged-layer order; ``active_blocks``: static gather cap
    (see ``gqa_prefill_paged``).  Returns ``(out, k_pages, v_pages,
    k_scales, v_scales)`` with ``out`` = last-token logits when the slice
    ends the model, else outgoing chunk activations (B,C,d).
    """
    C = x.shape[1]
    positions = start_pos[:, None] + jnp.arange(C)[None, :]
    h = _embed(cfg, sparams, x, positions) if entry == 0 else x
    li = sum(is_paged_block(cfg, b) for l, b in stage_blocks(cfg, layers)
             if l < entry)
    for (l, b), p in zip(stage_blocks(cfg, layers), sparams["blocks"]):
        if l < entry:
            continue
        if not is_paged_block(cfg, b):
            raise ValueError(f"layer {l} of {cfg.name} is not paged; chunked "
                             "stage prefill requires an all-paged slice")
        h, k_pages, v_pages, k_scales, v_scales = _block_prefill_paged(
            cfg, p, h, k_pages, v_pages, k_scales, v_scales, tables[li],
            positions, active_blocks)
        li += 1
    if layers.end == cfg.num_layers:
        h = apply_norm(cfg, sparams["final_norm"], h)
        return (_logits(cfg, sparams, h[:, -1:])[:, 0], k_pages, v_pages,
                k_scales, v_scales)
    return h, k_pages, v_pages, k_scales, v_scales


def stage_decode_paged(cfg: ModelConfig, sparams, layers: LayerRange, tok,
                       h_in, row_start, caches, cache_pos, k_pages, v_pages,
                       tables, *, k_scales=None, v_scales=None,
                       interpret: bool = False):
    """Paged analogue of ``stage_decode``: paged blocks run the Pallas
    paged_attention kernel over their block-table row; other blocks use their
    dense fallback caches.  Returns ``(h_out, logits | None, new_caches,
    k_pages, v_pages, k_scales, v_scales)``."""
    positions = cache_pos[:, None]
    if layers.start == 0:
        emb = _embed(cfg, sparams, tok[:, None], positions)
        h = jnp.where((row_start == 0)[:, None, None], emb,
                      h_in.astype(emb.dtype))
    else:
        h = h_in.astype(_dtype(cfg))
    new_caches: List = []
    li = 0
    for (l, b), p, c in zip(stage_blocks(cfg, layers), sparams["blocks"],
                            caches):
        if is_paged_block(cfg, b):
            h_new, k_pages, v_pages, k_scales, v_scales = _block_decode_paged(
                cfg, p, h, k_pages, v_pages, k_scales, v_scales, tables[li],
                cache_pos, interpret)
            nc: Any = {}
            li += 1
        else:
            h_new, nc = _apply_block_decode(cfg, b, p, h, c, cache_pos, None)
        h = jnp.where((row_start <= l)[:, None, None], h_new, h)
        new_caches.append(nc)
    logits = None
    if layers.end == cfg.num_layers:
        hn = apply_norm(cfg, sparams["final_norm"], h)
        logits = _logits(cfg, sparams, hn)[:, 0]
    return h, logits, new_caches, k_pages, v_pages, k_scales, v_scales


def stage_absorb_dense_prefill(cfg: ModelConfig, layers: LayerRange, caches,
                               k_pages, v_pages, table, slot: int,
                               seq_len: int, page: int, *, k_scales=None,
                               v_scales=None):
    """Move a single-request dense stage prefill's GQA K/V into the pool.

    Hybrid slices prefill single-shot with ``stage_prefill`` (correct at any
    prompt length), then scatter each paged block's K/V into this slot's
    pages and drop those leaves (replaced by ``{}``).  table: host
    (n_local_paged, max_batch, NP) int32.  Int8 pools quantize each
    destination page exactly once.  Returns (caches', k_pages, v_pages,
    k_scales, v_scales)."""
    nblk = -(-seq_len // page)
    out: List = []
    li = 0
    for (l, b), c in zip(stage_blocks(cfg, layers), caches):
        if not is_paged_block(cfg, b):
            out.append(c)
            continue
        k_pages, v_pages, k_scales, v_scales = absorb_layer(
            k_pages, v_pages, k_scales, v_scales, table[li, slot, :nblk],
            c["k"][0, :seq_len], c["v"][0, :seq_len], page)
        out.append({})
        li += 1
    return out, k_pages, v_pages, k_scales, v_scales
