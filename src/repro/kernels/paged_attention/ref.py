"""Pure-jnp oracle for paged attention decode."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .layout import pool_layout

NEG_INF = -1e30


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_tables: jax.Array, lengths: jax.Array,
                        k_scales: jax.Array | None = None,
                        v_scales: jax.Array | None = None, *,
                        page: int) -> jax.Array:
    """Decode attention over a paged KV pool.

    q:            (B, H, D)        one query token per sequence
    k/v_pages:    (P, R, L)        global page pool, ``page`` tokens a page
                                   stored tokens-major as R rows of L lanes
    block_tables: (B, NP) int32    page ids per sequence (sequential fill)
    lengths:      (B,) int32       tokens in each sequence's KV
    k/v_scales:   (P, KH) f32      optional int8 per-page per-head scales
    returns:      (B, H, D)
    """
    B, H, D = q.shape
    lay = pool_layout(k_pages.shape, D, page=page)
    KH = lay.kv_heads
    NP = block_tables.shape[1]
    G = H // KH

    k = lay.to_tokens(k_pages[block_tables])     # (B, NP, page, KH, D)
    v = lay.to_tokens(v_pages[block_tables])
    if k_scales is not None:
        from .quant import dequantize_kv_pages
        k = dequantize_kv_pages(k, k_scales[block_tables], q.dtype)
        v = dequantize_kv_pages(v, v_scales[block_tables], q.dtype)
    k = k.reshape(B, NP * page, KH, D)
    v = v.reshape(B, NP * page, KH, D)
    qg = q.reshape(B, KH, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    pos = jnp.arange(NP * page)[None, :]
    mask = pos < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, D).astype(q.dtype)
