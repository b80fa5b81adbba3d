"""Int8 KV-page quantization: per-page, per-kv-head absmax scales.

Same absmax idiom as ``dist.collectives.quantize_int8`` (the compressed
pipeline-parallel collectives), but at page granularity: pages seen as
tokens, (..., page, KH, D), quantize to int8 with one float32 scale per
(page, kv_head) — K and V separately — so the decode kernel dequantizes
in-VMEM with a (KH,) scale row that rides the same block-table index as the
page itself.  The pool stores the int8 pages as ``layout.page_layout``
lays them out, (P, R, L) rows; ``quantized_append`` reads and writes them
so.

Appends are read-modify-write at page granularity (``quantized_append``):
the touched window of pages is gathered, dequantized, the new rows inserted,
and the window requantized.  Rows past the append point are zeroed before
requantization, so a freshly allocated page never inherits a stale absmax
from its previous owner, and a page's scale is a function of its live
contents only.  Since appends only add rows, a page's absmax — hence its
scale — is non-decreasing over a sequence's lifetime: requantizing already
quantized rows with an unchanged scale is exact, so drift is bounded by the
handful of steps where a new row actually raises the page's absmax.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .layout import pool_layout

_TINY = 1e-8


def quantize_kv_pages(pages: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(..., page, KH, D) float -> (int8 pages, (..., KH) float32 scales)."""
    f = pages.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=(-3, -1))
    scales = jnp.maximum(amax / 127.0, _TINY)
    q = jnp.clip(jnp.round(f / scales[..., None, :, None]),
                 -127, 127).astype(jnp.int8)
    return q, scales


def dequantize_kv_pages(q: jax.Array, scales: jax.Array,
                        dtype=jnp.float32) -> jax.Array:
    """Inverse of ``quantize_kv_pages``; scales broadcast over (page, D)."""
    return (q.astype(jnp.float32)
            * scales[..., None, :, None].astype(jnp.float32)).astype(dtype)


def quantized_append(pages: jax.Array, scales: jax.Array,
                     block_table: jax.Array, start: jax.Array,
                     rows: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Append ``rows`` (B, C, KH, D) at contiguous positions
    ``start .. start+C-1`` of each sequence's paged KV.

    pages: (P, R, L) int8, stored as ``layout.page_layout`` lays them out;
    scales: (P, KH) f32; block_table: (B, NP) int32; start: (B,) int32.
    Returns (pages, scales) updated.

    The touched window is at most ``1 + ceil((C-1+page-1)/page)`` pages per
    row (static), gathered with the straddle handled by masking: window
    slots holding no appended row — and slots past the table — are redirected
    to scratch page 0, so real untouched pages are never requantized.
    Positions ``>= start + C`` inside the window are zeroed before
    requantization (stale data from a page's previous owner must not inflate
    the fresh scale).
    """
    B, C, KH, D = rows.shape
    lay = pool_layout(pages.shape, D, kv_heads=KH)
    page = lay.page
    NP = block_table.shape[1]
    NT = 1 + (C + page - 2) // page          # touched pages incl. straddle
    loc0 = start // page                     # (B,) first touched block
    w = start % page                         # (B,) offset inside it
    locs = loc0[:, None] + jnp.arange(NT)[None, :]            # (B, NT)
    touched = (jnp.arange(NT)[None, :] * page) < (w[:, None] + C)
    valid = touched & (locs < NP)
    pids = jnp.take_along_axis(block_table, jnp.clip(locs, 0, NP - 1), axis=1)
    pids = jnp.where(valid, pids, 0)                          # (B, NT)

    win = dequantize_kv_pages(lay.to_tokens(pages[pids]),
                              scales[pids])                   # (B,NT,pg,KH,D)
    win = win.reshape(B, NT * page, KH, D)
    gpos = loc0[:, None] * page + jnp.arange(NT * page)[None, :]
    win = jnp.where((gpos < start[:, None] + C)[..., None, None], win, 0.0)
    idx = w[:, None] + jnp.arange(C)[None, :]                 # (B, C)
    win = win.at[jnp.arange(B)[:, None], idx].set(rows.astype(jnp.float32))

    qw, sw = quantize_kv_pages(win.reshape(B, NT, page, KH, D))
    pages = pages.at[pids.reshape(-1)].set(
        lay.to_rows(qw).reshape(-1, lay.rows, lay.lanes))
    scales = scales.at[pids.reshape(-1)].set(sw.reshape(-1, KH))
    return pages, scales
