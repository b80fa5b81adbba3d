"""Attention blocks: GQA (full / sliding-window / local), MLA (DeepSeek),
cross-attention (whisper) — prefill (chunked, flash-style) and decode
(dense-over-cache) paths.

The chunked prefill path is pure JAX (lax.scan online-softmax) so the
multi-pod dry-run lowers on any backend; the Pallas flash kernel
(repro.kernels.flash_attention) is a drop-in replacement on TPU, selected via
``use_kernel="pallas"``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.paged_attention.layout import pool_layout, write_tokens
from .common import ParamSpec, apply_rope, rope_angles

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def attn_spec(cfg) -> Dict[str, ParamSpec]:
    d, h = cfg.d_model, cfg.resolved_head_dim
    if cfg.mla_kv_lora_rank:
        r_kv, r_q = cfg.mla_kv_lora_rank, cfg.mla_q_lora_rank
        nope, rope_d, vd = cfg.mla_qk_nope_dim, cfg.mla_qk_rope_dim, cfg.mla_v_dim
        nh = cfg.num_heads
        return {
            "q_down": ParamSpec((d, r_q), ("embed", "lora")),
            "q_up": ParamSpec((r_q, nh, nope + rope_d), ("lora", "heads", None)),
            "kv_down": ParamSpec((d, r_kv + rope_d), ("embed", None)),
            "kv_up": ParamSpec((r_kv, nh, nope + vd), ("lora", "heads", None)),
            "o": ParamSpec((nh, vd, d), ("heads", None, "embed")),
        }
    return {
        "q": ParamSpec((d, cfg.num_heads, h), ("embed", "heads", "head_dim")),
        "k": ParamSpec((d, cfg.num_kv_heads, h), ("embed", "kv_heads", "head_dim")),
        "v": ParamSpec((d, cfg.num_kv_heads, h), ("embed", "kv_heads", "head_dim")),
        "o": ParamSpec((cfg.num_heads, h, d), ("heads", "head_dim", "embed")),
    }


def cross_attn_spec(cfg) -> Dict[str, ParamSpec]:
    d, h = cfg.d_model, cfg.resolved_head_dim
    return {
        "q": ParamSpec((d, cfg.num_heads, h), ("embed", "heads", "head_dim")),
        "k": ParamSpec((d, cfg.num_heads, h), ("embed", "heads", "head_dim")),
        "v": ParamSpec((d, cfg.num_heads, h), ("embed", "heads", "head_dim")),
        "o": ParamSpec((cfg.num_heads, h, d), ("heads", "head_dim", "embed")),
    }


# ---------------------------------------------------------------------------
# Chunked flash-style attention (pure JAX)
# ---------------------------------------------------------------------------

def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      *, causal: bool = True, window: int = 0,
                      q_chunk: int = 512, kv_chunk: int = 1024,
                      skip_masked_chunks: bool = False) -> jax.Array:
    """q: (B,S,H,D), k/v: (B,S,KH,D) -> (B,S,H,D).  Online-softmax over kv
    chunks; memory O(S * chunk) instead of O(S^2).

    ``skip_masked_chunks``: causal/windowed runs only the kv chunks that can
    be visible to each q chunk (halves causal FLOPs; beyond-paper perf knob).
    """
    B, S, H, D = q.shape
    S_kv = k.shape[1]               # may differ from S (cross-attention)
    KH = k.shape[2]
    G = H // KH
    DV = v.shape[-1]                # may differ from D (MLA)
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, S_kv)
    # pad S to multiples
    def pad_to(x, c, axis):
        r = (-x.shape[axis]) % c
        if r == 0:
            return x
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, r)
        return jnp.pad(x, pad)

    qp = pad_to(q, q_chunk, 1)
    kp = pad_to(k, kv_chunk, 1)
    vp = pad_to(v, kv_chunk, 1)
    Sq, Sk = qp.shape[1], kp.shape[1]
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    scale = 1.0 / math.sqrt(D)

    qp = qp.reshape(B, nq, q_chunk, KH, G, D)
    kp = kp.reshape(B, nk, kv_chunk, KH, D)
    vp = vp.reshape(B, nk, kv_chunk, KH, DV)

    q_pos = jnp.arange(Sq).reshape(nq, q_chunk)
    k_pos = jnp.arange(Sk).reshape(nk, kv_chunk)
    k_valid = (jnp.arange(Sk) < S_kv).reshape(nk, kv_chunk)

    def q_step(qi):
        qc = qp[:, qi] * scale                     # (B,cq,KH,G,D)
        qpos = q_pos[qi]                           # (cq,)

        def kv_step(carry, ki):
            acc, m, l = carry
            kc, vc = kp[:, ki], vp[:, ki]          # (B,ck,KH,D)
            kpos, kval = k_pos[ki], k_valid[ki]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qc, kc,
                           preferred_element_type=jnp.float32)
            mask = kval[None, :]
            if causal:
                mask = mask & (qpos[:, None] >= kpos[None, :])
            if window:
                mask = mask & (qpos[:, None] - kpos[None, :] < window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, KH, G, q_chunk, DV), jnp.float32)
        m0 = jnp.full((B, KH, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KH, G, q_chunk), jnp.float32)

        if skip_masked_chunks and (causal or window):
            # static bounds per q chunk: kv chunks fully in the future are
            # skipped; with a window, chunks fully before the window too.
            lo = 0
            hi = nk
            q_first, q_last = int(qi) * q_chunk, (int(qi) + 1) * q_chunk - 1
            if causal:
                hi = min(nk, q_last // kv_chunk + 1)
            if window:
                lo = max(0, (q_first - window + 1) // kv_chunk)
            ks = jnp.arange(lo, hi)
        else:
            ks = jnp.arange(nk)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), ks)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out                                  # (B,KH,G,cq,D)

    if skip_masked_chunks and (causal or window):
        outs = [q_step(qi) for qi in range(nq)]     # static unroll (varying bounds)
        out = jnp.stack(outs, axis=0)
    else:
        out = jax.lax.map(q_step, jnp.arange(nq))
    # (nq,B,KH,G,cq,DV) -> (B, S, H, DV)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, DV)
    return out[:, :S].astype(q.dtype)


def _gqa_decode_scores(q, k_cache):
    """q: (B,1,H,D); k_cache: (B,S,KH,D) -> (B,KH,G,S) fp32 scores."""
    B, _, H, D = q.shape
    KH = k_cache.shape[2]
    G = H // KH
    qg = q.reshape(B, KH, G, D)
    return jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                      preferred_element_type=jnp.float32) / math.sqrt(D)


# ---------------------------------------------------------------------------
# Standard GQA attention block
# ---------------------------------------------------------------------------

def gqa_prefill(cfg, params, x, positions, *, causal=True, window=0,
                cross_kv=None, skip_masked_chunks=False):
    """x: (B,S,d).  Returns (out, cache) where cache=(k,v) with rope applied.

    ``cross_kv``: (k,v) from an encoder — used for whisper cross-attention
    (no causal mask, positions ignored for kv).
    """
    B, S, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["q"])
    if cross_kv is None:
        k = jnp.einsum("bsd,dhk->bshk", x, params["k"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["v"])
        if cfg.rope_theta > 0:
            cos, sin = rope_angles(positions, cfg.resolved_head_dim,
                                   cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    else:
        k, v = cross_kv
        if cfg.rope_theta > 0:
            cos, sin = rope_angles(positions, cfg.resolved_head_dim,
                                   cfg.rope_theta)
            q = apply_rope(q, cos, sin)
        causal = False
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            skip_masked_chunks=skip_masked_chunks)
    out = jnp.einsum("bshk,hkd->bsd", out, params["o"])
    return out, (k, v)


def gqa_decode(cfg, params, x, cache, cache_pos, *, window=0, cross_kv=None):
    """Single-token decode.  x: (B,1,d); cache: dict(k,v,(pos)) ring buffers
    of length W (windowed) or max_len; cache_pos: (B,) absolute position of
    the token being generated.

    Returns (out, new_cache).  Keys in the cache already have rope applied.
    """
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, params["q"])
    if cross_kv is not None:
        k_cache, v_cache = cross_kv
        scores = _gqa_decode_scores(q, k_cache)
        attn = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhgs,bshd->bhgd", attn.astype(x.dtype), v_cache)
        ctx = ctx.reshape(B, 1, cfg.num_heads, cfg.resolved_head_dim)
        return jnp.einsum("bshk,hkd->bsd", ctx, params["o"]), cache

    k_new = jnp.einsum("bsd,dhk->bshk", x, params["k"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, params["v"])
    if cfg.rope_theta > 0:
        cos, sin = rope_angles(cache_pos[:, None], cfg.resolved_head_dim,
                               cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k_new = apply_rope(k_new, cos, sin)

    W = cache["k"].shape[1]
    slot = (cache_pos % W) if window else jnp.minimum(cache_pos, W - 1)
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, slot].set(k_new[:, 0])
    v_cache = cache["v"].at[bidx, slot].set(v_new[:, 0])
    slot_pos = cache["pos"].at[bidx, slot].set(cache_pos)

    scores = _gqa_decode_scores(q, k_cache)
    valid = (slot_pos <= cache_pos[:, None])
    if window:
        valid &= (cache_pos[:, None] - slot_pos < window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    attn = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhgs,bshd->bhgd", attn.astype(x.dtype), v_cache)
    ctx = ctx.reshape(B, 1, cfg.num_heads, cfg.resolved_head_dim)
    out = jnp.einsum("bshk,hkd->bsd", ctx, params["o"])
    return out, {"k": k_cache, "v": v_cache, "pos": slot_pos}


# ---------------------------------------------------------------------------
# Paged GQA paths (decode via the Pallas paged_attention kernel)
# ---------------------------------------------------------------------------

def _gqa_qkv_rope(cfg, params, x, positions):
    """Project q/k/v for a chunk and apply rope at absolute ``positions``.
    x: (B,C,d); positions: (B,C) -> q (B,C,H,D), k/v (B,C,KH,D)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["q"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["k"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["v"])
    if cfg.rope_theta > 0:
        cos, sin = rope_angles(positions, cfg.resolved_head_dim,
                               cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _append_kv(k_pages, v_pages, k_scales, v_scales, block_table, start,
               k_new, v_new, page):
    """Write (B, C, KH, D) K/V at positions ``start..start+C-1`` of each
    row's pages: int8 pools requantize the pages touched, others write the
    runs of whole tiles touched (``layout.write_tokens``)."""
    if k_scales is not None:
        from ..kernels.paged_attention import quantized_append
        k_pages, k_scales = quantized_append(k_pages, k_scales, block_table,
                                             start, k_new)
        v_pages, v_scales = quantized_append(v_pages, v_scales, block_table,
                                             start, v_new)
    else:
        k_pages = write_tokens(k_pages, block_table, start, k_new, page)
        v_pages = write_tokens(v_pages, block_table, start, v_new, page)
    return k_pages, v_pages, k_scales, v_scales


def gqa_decode_paged(cfg, params, x, k_pages, v_pages, block_table, cache_pos,
                     *, k_scales=None, v_scales=None, interpret=False):
    """Single-token decode against a shared page pool.

    x: (B,1,d); k/v_pages: (P,R,L) pool shared across layers, stored as
    ``kernels.paged_attention.layout`` lays it out; block_table: (B,NP) page
    ids for this layer; cache_pos: (B,) absolute position of the token
    being generated.  Writes the new K/V into the page holding
    ``cache_pos`` and runs the Pallas paged_attention kernel over the
    sequence's pages.  ``k_scales``/``v_scales``: (P, KH) f32 when the pool
    is int8 (per-page per-kv-head absmax; the append requantizes the touched
    page and the kernel dequantizes in-VMEM).  Returns
    (out, k_pages, v_pages, k_scales, v_scales).
    """
    from ..kernels.paged_attention import paged_attention_op

    page = pool_layout(k_pages.shape, cfg.resolved_head_dim,
                       kv_heads=cfg.num_kv_heads).page
    q, k_new, v_new = _gqa_qkv_rope(cfg, params, x, cache_pos[:, None])
    k_pages, v_pages, k_scales, v_scales = _append_kv(
        k_pages, v_pages, k_scales, v_scales, block_table, cache_pos,
        k_new, v_new, page)
    ctx = paged_attention_op(q[:, 0], k_pages, v_pages, block_table,
                             cache_pos + 1, k_scales, v_scales, page=page,
                             interpret=interpret)
    out = jnp.einsum("bshk,hkd->bsd", ctx[:, None].astype(x.dtype),
                     params["o"])
    return out, k_pages, v_pages, k_scales, v_scales


def gqa_prefill_paged(cfg, params, x, k_pages, v_pages, block_table,
                      positions, *, k_scales=None, v_scales=None,
                      active_blocks=None):
    """Chunked paged prefill: write this chunk's K/V into the pool and attend
    the chunk's queries causally over everything the sequence has written so
    far (earlier chunks included — pure-JAX gather over the block table; the
    Pallas kernel covers the decode side).

    x: (B,C,d); positions: (B,C) absolute positions of the chunk tokens.
    ``active_blocks``: static cap on the gather — only the first
    ``active_blocks`` table entries (>= ceil((pos+C)/page), the pages that
    actually hold tokens) are materialized, instead of the whole per-sequence
    ``NP`` budget; masked-out entries contributed exactly 0 to the softmax
    (NEG_INF underflows), so capping is numerically identical.
    ``k_scales``/``v_scales``: (P, KH) f32 for int8 pools — the chunk is
    appended via page-granular requantization and the gather dequantizes.
    Returns (out (B,C,d), k_pages, v_pages, k_scales, v_scales).
    """
    B, C, d = x.shape
    KH, D = cfg.num_kv_heads, cfg.resolved_head_dim
    lay = pool_layout(k_pages.shape, D, kv_heads=KH)
    page = lay.page
    NP = block_table.shape[1]
    H = cfg.num_heads
    G = H // KH
    nact = NP if active_blocks is None else max(1, min(active_blocks, NP))
    q, k_new, v_new = _gqa_qkv_rope(cfg, params, x, positions)
    k_pages, v_pages, k_scales, v_scales = _append_kv(
        k_pages, v_pages, k_scales, v_scales, block_table, positions[:, 0],
        k_new, v_new, page)
    bt = block_table[:, :nact]
    # the request's pages only, seen as tokens: no pool-sized relayout
    k_all = lay.to_tokens(k_pages[bt])
    v_all = lay.to_tokens(v_pages[bt])
    if k_scales is not None:
        from ..kernels.paged_attention import dequantize_kv_pages
        k_all = dequantize_kv_pages(k_all, k_scales[bt], x.dtype)
        v_all = dequantize_kv_pages(v_all, v_scales[bt], x.dtype)
    k_all = k_all.reshape(B, nact * page, KH, D)
    v_all = v_all.reshape(B, nact * page, KH, D)
    qg = q.reshape(B, C, KH, G, D)
    s = jnp.einsum("bchgd,bshd->bhgcs", qg, k_all,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    kpos = jnp.arange(nact * page)
    mask = kpos[None, None, :] <= positions[:, :, None]        # (B,C,S)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    attn = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhgcs,bshd->bchgd", attn.astype(x.dtype), v_all)
    ctx = ctx.reshape(B, C, H, D)
    out = jnp.einsum("bshk,hkd->bsd", ctx, params["o"])
    return out, k_pages, v_pages, k_scales, v_scales


def gqa_cache_init(cfg, batch: int, max_len: int, window: int, dtype):
    W = min(window, max_len) if window else max_len
    kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, W, kh, hd), dtype),
        "v": jnp.zeros((batch, W, kh, hd), dtype),
        "pos": jnp.full((batch, W), jnp.iinfo(jnp.int32).max, jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank compressed KV cache
# ---------------------------------------------------------------------------

def mla_prefill(cfg, params, x, positions, *, skip_masked_chunks=False):
    B, S, d = x.shape
    nope, rope_d = cfg.mla_qk_nope_dim, cfg.mla_qk_rope_dim
    vd, nh = cfg.mla_v_dim, cfg.num_heads

    q_lat = jnp.einsum("bsd,dr->bsr", x, params["q_down"])
    q = jnp.einsum("bsr,rhk->bshk", q_lat, params["q_up"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    kv = jnp.einsum("bsd,dr->bsr", x, params["kv_down"])
    c_kv, k_rope = kv[..., :cfg.mla_kv_lora_rank], kv[..., cfg.mla_kv_lora_rank:]
    k_up = jnp.einsum("bsr,rhk->bshk", c_kv, params["kv_up"])
    k_nope, v = k_up[..., :nope], k_up[..., nope:]

    cos, sin = rope_angles(positions, rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)  # single shared head
    k_rope_b = jnp.broadcast_to(k_rope, (B, S, nh, rope_d))

    q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_cat = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    out = chunked_attention(q_cat, k_cat, v, causal=True,
                            skip_masked_chunks=skip_masked_chunks)
    out = jnp.einsum("bshv,hvd->bsd", out, params["o"])
    cache = (c_kv, k_rope[:, :, 0, :])
    return out, cache


def mla_decode(cfg, params, x, cache, cache_pos):
    """Absorbed MLA decode: scores/values computed against the compressed
    cache, with kv_up folded into the query/output (DeepSeek-V2 §"matrix
    absorption") — per-step FLOPs scale with kv_lora_rank, not heads*dim."""
    B = x.shape[0]
    nope, rope_d = cfg.mla_qk_nope_dim, cfg.mla_qk_rope_dim
    vd, nh = cfg.mla_v_dim, cfg.num_heads
    r = cfg.mla_kv_lora_rank

    q_lat = jnp.einsum("bsd,dr->bsr", x, params["q_down"])
    q = jnp.einsum("bsr,rhk->bshk", q_lat, params["q_up"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    cos, sin = rope_angles(cache_pos[:, None], rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    kv = jnp.einsum("bsd,dr->bsr", x, params["kv_down"])
    c_new, k_rope_new = kv[..., :r], kv[..., r:]
    k_rope_new = apply_rope(k_rope_new[:, :, None, :], cos, sin)[:, :, 0, :]

    W = cache["c"].shape[1]
    slot = jnp.minimum(cache_pos, W - 1)
    bidx = jnp.arange(B)
    c_cache = cache["c"].at[bidx, slot].set(c_new[:, 0])
    rope_cache = cache["r"].at[bidx, slot].set(k_rope_new[:, 0])
    slot_pos = cache["pos"].at[bidx, slot].set(cache_pos)

    w_uk = params["kv_up"][..., :nope]           # (r, H, nope)
    w_uv = params["kv_up"][..., nope:]           # (r, H, vd)
    q_c = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)
    s = jnp.einsum("bshr,btr->bhst", q_c, c_cache,
                   preferred_element_type=jnp.float32)
    s += jnp.einsum("bshp,btp->bhst", q_rope, rope_cache,
                    preferred_element_type=jnp.float32)
    s /= math.sqrt(nope + rope_d)
    valid = slot_pos <= cache_pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    attn = jax.nn.softmax(s, axis=-1)
    ctx_c = jnp.einsum("bhst,btr->bshr", attn.astype(x.dtype), c_cache)
    ctx = jnp.einsum("bshr,rhv->bshv", ctx_c, w_uv)
    out = jnp.einsum("bshv,hvd->bsd", ctx, params["o"])
    return out, {"c": c_cache, "r": rope_cache, "pos": slot_pos}


def mla_cache_init(cfg, batch: int, max_len: int, dtype):
    return {
        "c": jnp.zeros((batch, max_len, cfg.mla_kv_lora_rank), dtype),
        "r": jnp.zeros((batch, max_len, cfg.mla_qk_rope_dim), dtype),
        "pos": jnp.full((batch, max_len), jnp.iinfo(jnp.int32).max, jnp.int32),
    }
