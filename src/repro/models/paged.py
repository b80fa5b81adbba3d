"""Paged-KV model paths: prefill/decode over a unified page pool.

The serving engine's page pool (``repro.serving.kv_pool.PagePool``) holds one
physical K and V array shared by *all* of a node's local attention layers
(the paper's §5.1 "pool of pages unified for all local layers").  This module
is the model-side counterpart: it runs the layer stack with

  * full-attention GQA blocks reading/writing the shared pool through their
    per-layer block tables (decode goes through the Pallas paged_attention
    kernel), and
  * a dense fallback for everything else — MLA, SSM (mamba/xLSTM), windowed
    attention and encoder-decoder blocks keep their existing per-slot caches.

Paged layers are numbered prologue-first, then pattern positions in
repeat-major order; block tables follow the same layout so the super-block
``lax.scan`` can consume them as ``(repeats, paged_per_pattern, B, NP)``.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import BlockSpec, ModelConfig
from ..kernels.paged_attention.layout import page_layout, write_tokens
from .attention import gqa_decode_paged, gqa_prefill_paged
from .common import apply_norm
from .model import (_apply_block_decode, _cache_init_for_block, _embed,
                    _logits)
from .moe import ffn_apply, moe_apply


# ---------------------------------------------------------------------------
# Layout helpers
# ---------------------------------------------------------------------------

def is_paged_block(cfg: ModelConfig, b: BlockSpec) -> bool:
    """True if this block's KV lives in the page pool (full-attention GQA).
    MLA / SSM / windowed / cross-attention blocks use the dense fallback."""
    return (b.kind == "attn" and b.attn == "full"
            and not cfg.mla_kv_lora_rank and not cfg.is_encoder_decoder)


def paged_layer_counts(cfg: ModelConfig) -> Tuple[int, int]:
    """(paged prologue blocks, paged blocks per pattern repeat)."""
    n_pro = sum(is_paged_block(cfg, b) for b in cfg.prologue)
    n_pp = sum(is_paged_block(cfg, b) for b in cfg.pattern)
    return n_pro, n_pp


def num_paged_layers(cfg: ModelConfig) -> int:
    n_pro, n_pp = paged_layer_counts(cfg)
    return n_pro + n_pp * cfg.repeats


def all_blocks_paged(cfg: ModelConfig) -> bool:
    """True if the whole stack is paged — enables chunked prefill (no dense
    caches at all); hybrid stacks prefill single-shot instead."""
    return all(is_paged_block(cfg, b) for b in cfg.blocks)


def init_caches_paged(cfg: ModelConfig, batch: int, max_len: int):
    """Dense-fallback caches: same pytree shape as ``init_caches`` but paged
    blocks hold an empty dict — their KV lives in the pool."""
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.param_dtype]
    caches: Dict[str, Any] = {}
    if cfg.prologue:
        caches["prologue"] = [
            {} if is_paged_block(cfg, b)
            else _cache_init_for_block(cfg, b, batch, max_len, dtype)
            for b in cfg.prologue]
    per_pos = {f"pos{i}": ({} if is_paged_block(cfg, b)
                           else _cache_init_for_block(cfg, b, batch, max_len,
                                                      dtype))
               for i, b in enumerate(cfg.pattern)}
    caches["super"] = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.repeats,) + x.shape), per_pos)
    return caches


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------

def _mlp(cfg, p, h):
    if "moe" in p:
        hn = apply_norm(cfg, p["norm2"], h)
        out, _ = moe_apply(cfg, p["moe"], hn)
        return h + out
    if "ffn" in p:
        hn = apply_norm(cfg, p["norm2"], h)
        return h + ffn_apply(p["ffn"], hn)
    return h


def _block_decode_paged(cfg, p, h, kp, vp, ks, vs, table, cache_pos,
                        interpret):
    hn = apply_norm(cfg, p["norm1"], h)
    out, kp, vp, ks, vs = gqa_decode_paged(cfg, p["mix"], hn, kp, vp, table,
                                           cache_pos, k_scales=ks,
                                           v_scales=vs, interpret=interpret)
    return _mlp(cfg, p, h + out), kp, vp, ks, vs


def _block_prefill_paged(cfg, p, h, kp, vp, ks, vs, table, positions,
                         active_blocks=None):
    hn = apply_norm(cfg, p["norm1"], h)
    out, kp, vp, ks, vs = gqa_prefill_paged(cfg, p["mix"], hn, kp, vp, table,
                                            positions, k_scales=ks,
                                            v_scales=vs,
                                            active_blocks=active_blocks)
    return _mlp(cfg, p, h + out), kp, vp, ks, vs


# ---------------------------------------------------------------------------
# Model-level paged decode / chunked prefill
# ---------------------------------------------------------------------------

def decode_step_paged(cfg: ModelConfig, params, tokens, caches, cache_pos,
                      k_pages, v_pages, tables_pro, tables_super, *,
                      k_scales=None, v_scales=None, interpret: bool = False):
    """One autoregressive step over the paged pool.

    tokens/cache_pos: (B,); k/v_pages: (P,R,L) stored pages; tables_pro:
    (n_paged_prologue, B, NP); tables_super: (repeats, n_paged_pattern, B, NP);
    k/v_scales: (P, KH) f32 when the pool is int8, else None.
    Returns (logits (B,V), new dense-fallback caches, k_pages, v_pages,
    k_scales, v_scales).
    """
    positions = cache_pos[:, None]
    h = _embed(cfg, params, tokens[:, None], positions)

    new_caches: Dict[str, Any] = {}
    li = 0
    if cfg.prologue:
        new_caches["prologue"] = []
        for i, b in enumerate(cfg.prologue):
            if is_paged_block(cfg, b):
                h, k_pages, v_pages, k_scales, v_scales = _block_decode_paged(
                    cfg, params["prologue"][i], h, k_pages, v_pages,
                    k_scales, v_scales, tables_pro[li], cache_pos, interpret)
                new_caches["prologue"].append({})
                li += 1
            else:
                h, nc = _apply_block_decode(cfg, b, params["prologue"][i], h,
                                            caches["prologue"][i], cache_pos,
                                            None)
                new_caches["prologue"].append(nc)

    def superblock(carry, xs):
        h, kp, vp, ks, vs = carry
        layer_params, layer_cache, layer_tables = xs
        new_layer_cache = {}
        ti = 0
        for i, b in enumerate(cfg.pattern):
            if is_paged_block(cfg, b):
                h, kp, vp, ks, vs = _block_decode_paged(
                    cfg, layer_params[f"pos{i}"], h, kp, vp, ks, vs,
                    layer_tables[ti], cache_pos, interpret)
                new_layer_cache[f"pos{i}"] = {}
                ti += 1
            else:
                h, nc = _apply_block_decode(cfg, b, layer_params[f"pos{i}"],
                                            h, layer_cache[f"pos{i}"],
                                            cache_pos, None)
                new_layer_cache[f"pos{i}"] = nc
        return (h, kp, vp, ks, vs), new_layer_cache

    (h, k_pages, v_pages, k_scales, v_scales), new_super = jax.lax.scan(
        superblock, (h, k_pages, v_pages, k_scales, v_scales),
        (params["super"], caches["super"], tables_super))
    new_caches["super"] = new_super
    h = apply_norm(cfg, params["final_norm"], h)
    logits = _logits(cfg, params, h)[:, 0]
    return logits, new_caches, k_pages, v_pages, k_scales, v_scales


def prefill_chunk_paged(cfg: ModelConfig, params, tokens, start_pos,
                        k_pages, v_pages, tables_pro, tables_super, *,
                        k_scales=None, v_scales=None, active_blocks=None):
    """Prefill one prompt chunk, appending its K/V to the pool.

    Only valid when ``all_blocks_paged(cfg)`` — every layer's history lives
    in the pool, so chunk N attends over chunks 0..N via the block tables and
    no dense caches are needed.  tokens: (B,C); start_pos: (B,) absolute
    position of tokens[:, 0].  ``active_blocks``: static per-layer gather cap
    (>= ceil((start+C)/page)); None gathers the whole NP budget.  Returns
    (last-token logits, k_pages, v_pages, k_scales, v_scales).
    """
    B, C = tokens.shape
    positions = start_pos[:, None] + jnp.arange(C)[None, :]
    h = _embed(cfg, params, tokens, positions)

    li = 0
    for i, b in enumerate(cfg.prologue):
        h, k_pages, v_pages, k_scales, v_scales = _block_prefill_paged(
            cfg, params["prologue"][i], h, k_pages, v_pages, k_scales,
            v_scales, tables_pro[li], positions, active_blocks)
        li += 1

    def superblock(carry, xs):
        h, kp, vp, ks, vs = carry
        layer_params, layer_tables = xs
        for i in range(len(cfg.pattern)):
            h, kp, vp, ks, vs = _block_prefill_paged(
                cfg, layer_params[f"pos{i}"], h, kp, vp, ks, vs,
                layer_tables[i], positions, active_blocks)
        return (h, kp, vp, ks, vs), None

    (h, k_pages, v_pages, k_scales, v_scales), _ = jax.lax.scan(
        superblock, (h, k_pages, v_pages, k_scales, v_scales),
        (params["super"], tables_super))
    h = apply_norm(cfg, params["final_norm"], h)
    logits = _logits(cfg, params, h[:, -1:])[:, 0]
    return logits, k_pages, v_pages, k_scales, v_scales


# ---------------------------------------------------------------------------
# Dense-prefill absorption (hybrid stacks)
# ---------------------------------------------------------------------------

def absorb_layer(k_pages, v_pages, k_scales, v_scales, pids, k, v,
                 page: int):
    """Write one layer's prompt K/V (S, KH, D), from position 0, into the
    stored pages ``pids`` (host ids of its first ``ceil(S/page)`` pages).
    Int8 pools quantize each destination page exactly once — no RMW drift
    on this path.  Returns (k_pages, v_pages, k_scales, v_scales)."""
    S, KH, D = k.shape
    pids = jnp.asarray(pids)
    if k_scales is None:
        start = jnp.zeros((1,), jnp.int32)
        return (write_tokens(k_pages, pids[None], start, k[None], page),
                write_tokens(v_pages, pids[None], start, v[None], page),
                None, None)
    from ..kernels.paged_attention import quantize_kv_pages
    lay = page_layout(page, KH, D)
    pad = len(pids) * page - S

    def quantized(x):
        x = jnp.pad(x.astype(jnp.float32), ((0, pad), (0, 0), (0, 0)))
        q, s = quantize_kv_pages(x.reshape(len(pids), page, KH, D))
        return lay.to_rows(q), s
    (kq, ks), (vq, vs) = quantized(k), quantized(v)
    return (k_pages.at[pids].set(kq), v_pages.at[pids].set(vq),
            k_scales.at[pids].set(ks), v_scales.at[pids].set(vs))


def absorb_dense_prefill(cfg: ModelConfig, caches, k_pages, v_pages,
                         table, slot: int, seq_len: int, page: int, *,
                         k_scales=None, v_scales=None):
    """Move a single-request dense prefill's GQA K/V into the page pool.

    Hybrid stacks (MLA/SSM/windowed blocks present) prefill single-shot with
    the dense ``prefill`` — correct at any prompt length — then scatter the
    full-attention layers' K/V into this slot's pages and drop those leaves
    (replaced by ``{}``), keeping only the fallback caches dense.

    caches: prefill output with batch 1; table: host (L, max_batch, NP) int32
    page-id array.  Int8 pools (``k_scales``/``v_scales`` given) quantize
    each destination page exactly once — no RMW drift on this path.
    Returns (caches', k_pages, v_pages, k_scales, v_scales).
    """
    n_pro, n_pp = paged_layer_counts(cfg)
    nblk = -(-seq_len // page)

    def scatter(layer_idx, k, v):
        nonlocal k_pages, v_pages, k_scales, v_scales
        k_pages, v_pages, k_scales, v_scales = absorb_layer(
            k_pages, v_pages, k_scales, v_scales,
            table[layer_idx, slot, :nblk], k, v, page)

    out: Dict[str, Any] = {}
    if cfg.prologue:
        out["prologue"] = []
        li = 0
        for i, b in enumerate(cfg.prologue):
            c = caches["prologue"][i]
            if is_paged_block(cfg, b):
                scatter(li, c["k"][0, :seq_len], c["v"][0, :seq_len])
                out["prologue"].append({})
                li += 1
            else:
                out["prologue"].append(c)
    out["super"] = {}
    ti = 0
    for i, b in enumerate(cfg.pattern):
        c = caches["super"][f"pos{i}"]
        if is_paged_block(cfg, b):
            for r in range(cfg.repeats):
                scatter(n_pro + r * n_pp + ti,
                        c["k"][r, 0, :seq_len], c["v"][r, 0, :seq_len])
            out["super"][f"pos{i}"] = {}
            ti += 1
        else:
            out["super"][f"pos{i}"] = c
    return out, k_pages, v_pages, k_scales, v_scales
