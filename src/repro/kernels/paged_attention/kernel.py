"""Pallas TPU paged attention (decode) — TPU-native vLLM PagedAttention.

Pipeline.  One grid step per sequence, ``(B,)``, run in order.  The K/V
pools stay in HBM (``memory_space=pl.ANY``) and the kernel gathers each
sequence's pages itself, a *block* of ``ppb`` pages at a time, into a
double-buffered VMEM scratch: one async copy per page (a page is one
contiguous copy that covers every kv head) and one DMA semaphore per
buffer slot.  The block table and lengths ride in SMEM as scalar-prefetch
operands.

Live blocks only.  A sequence of ``len`` tokens has ``ceil(len/page)`` live
pages, i.e. ``ceil(ceil(len/page)/ppb)`` blocks, and the kernel loops over
exactly those; the last block copies only the pages below
``ceil(len/page)`` and zeroes the V slots it leaves stale.  Block ``i+1``'s
copies start before block ``i`` is computed, and the next sequence's first
block starts before this sequence's last block is computed, so copies run
ahead of the compute across sequence boundaries too.  Per launch the kernel
streams ``sum_b ceil(len_b/page)`` pages (``ops.streamed_pages_per_step``)
and never copies a dead page.

Block size.  ``ppb`` follows from the shapes (``pages_per_block``): about
``BLOCK_TOKENS`` tokens a block, capped at the table width ``NP`` and at a
VMEM budget for the two slots of K and V.  On one TPU v5e at the OLMo-1B
batch cell's shapes (``benchmarks/paged_attention_kernel.py``), 128 tokens
a block take the same time as 256 or 512, and 64 are ~10% slower.

Page rows.  The pools come stored as ``layout.page_layout`` lays them out,
``(P, R, L)``: rows of ``L = lcm(D, 128)`` lanes, each holding ``k = L/D``
consecutive (token, kv head) records, tokens major.  For the
usual ``D = 128`` a row is one record (``R = page*KH``); SmolLM-360M's 5
kv heads of 64 pack two records a row (``R = 40`` at page 16).  The rule
only admits pages of whole ``(8, 128)`` tiles, which the chip keeps in the
written order, so one DMA copies a page as it lies and no program
relayouts the pool.

Compute, on the block as it landed: the ``(ppb, R, L)`` block is viewed as
``(ppb*R, L)`` (a free reshape: no relayout, and no dot_general batched
over a tile's middle axis) and one MXU product ``Q @ K^T`` gives every
query row's score against every page row.  ``Q`` holds each of the ``k``
record positions of a row in its own stack of ``G*KH`` query rows (the
queries in that position's ``D`` lanes, zeros elsewhere).  A query keeps
only the records of its own kv head — the rest are masked like dead tokens
— so the softmax runs lane-dense on ``(k*G*KH, ppb*R)`` with one max and
sum per query across its ``k`` stacks, and ``P @ V`` over the same view
sums each head's own values in its stack's lanes.  Numerics follow
``ref.py``: operands in the KV dtype with float32 accumulation,
``1/sqrt(D)`` applied in float32, ``p`` cast to the KV dtype for ``p·V``,
and an online softmax whose max, sum and accumulator are float32.

Int8 KV: with per-page, per-kv-head scales, pages are int8 and enter the
products as exact integers in the query dtype; the K scale multiplies the
scores and the V scale the probabilities, each through a ``(k*G*KH, ppb)``
table of the block's scales (gathered by the wrapper from the block table)
spread over the page rows' lanes by a 0/1 product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import pool_layout

NEG_INF = -1e30
BLOCK_TOKENS = 128
# the two slots of the K and V buffers together stay inside this much VMEM
KV_BUFFER_BYTES = 8 * 1024 * 1024
SUBLANES = 8


def pages_per_block(page: int, num_pages: int, page_bytes: int) -> int:
    """Pages a block gathers: ``BLOCK_TOKENS`` tokens' worth, at most the
    table width and at most what fits ``KV_BUFFER_BYTES`` as two slots of
    K and V."""
    want = max(1, BLOCK_TOKENS // page)
    fit = max(1, KV_BUFFER_BYTES // (4 * page_bytes))
    return max(1, min(want, num_pages, fit))


def _kernel(block_tables, lengths, q_ref, *refs, page: int, num_pages: int,
            ppb: int, kv_heads: int, records: int, head_dim: int,
            scale: float, quantized: bool, compute_dtype):
    if quantized:
        (ks_ref, vs_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, sems, slot_ref) = refs
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref = refs
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    R, L = k_buf.shape[-2:]
    KH, k = kv_heads, records
    GK = q_ref.shape[1] // k              # query rows of one record stack
    W = ppb * R                           # rows of a block's (W, L) view

    def live_pages(row):
        return jnp.minimum((lengths[row] + page - 1) // page, num_pages)

    def block_pages(row, blk):
        return jnp.minimum(ppb, live_pages(row) - blk * ppb)

    def page_copies(row, blk, slot, j):
        pid = block_tables[row, blk * ppb + j]
        return (pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[slot, j],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[slot, j],
                                      sems.at[1, slot]))

    def for_pages(row, blk, slot, op):
        def body(j, c):
            for cp in page_copies(row, blk, slot, j):
                getattr(cp, op)()
            return c
        jax.lax.fori_loop(0, block_pages(row, blk), body, 0)

    # invariant: on entry, this row's first block is in flight in slot s0
    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        for_pages(0, 0, 0, "start")

    s0 = slot_ref[0]
    nblk = (live_pages(b) + ppb - 1) // ppb
    has_next = b + 1 < nb

    @pl.when(jnp.logical_and(nblk == 0, has_next))
    def _pass_on():
        for_pages(b + 1, 0, s0, "start")

    q = q_ref[0].astype(compute_dtype)                   # (k*GK, L)
    srow = jax.lax.broadcasted_iota(jnp.int32, (k * GK, W), 0)
    record = k * jax.lax.broadcasted_iota(jnp.int32, (k * GK, W), 1) \
        + srow // GK                                     # within the block
    own_head = record % KH == srow % GK % KH
    token = record // KH
    if quantized:
        # spreads a (k*GK, ppb) table of page scales over the page rows
        spread = (jax.lax.broadcasted_iota(jnp.int32, (ppb, W), 1) // R
                  == jax.lax.broadcasted_iota(jnp.int32, (ppb, W), 0)
                  ).astype(jnp.float32)

    def per_row(scales, i):
        # a dead page's scale is never read: a NaN there must not spread
        page_live = jax.lax.broadcasted_iota(jnp.int32, (k * GK, ppb), 1) \
            < block_pages(b, i)
        return jnp.dot(jnp.where(page_live, scales[0, i], 0.0), spread,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    def stacks(x):                    # (k*GK, n) -> k arrays of (GK, n)
        return [x[h * GK:(h + 1) * GK] for h in range(k)]

    def block(i, carry):
        m_prev, l_prev, acc = carry
        slot = (s0 + i) % 2

        @pl.when(i + 1 < nblk)
        def _next_block():
            for_pages(b, i + 1, 1 - slot, "start")

        @pl.when(jnp.logical_and(i + 1 == nblk, has_next))
        def _next_row():
            for_pages(b + 1, 0, 1 - slot, "start")

        for_pages(b, i, slot, "wait")

        def zero(j, c):       # stale slots past the last live page
            v_buf[slot, j] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
            return c
        jax.lax.fori_loop(block_pages(b, i), ppb, zero, 0)

        kb = k_buf[slot].astype(compute_dtype).reshape(W, L)
        vb = v_buf[slot].astype(compute_dtype).reshape(W, L)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quantized:
            s = s * per_row(ks_ref, i)
        live = jnp.logical_and(own_head,
                               i * ppb * page + token < lengths[b])
        s = jnp.where(live, s, NEG_INF)                  # (k*GK, W)
        m_blk = functools.reduce(jnp.maximum, [
            jnp.max(x, axis=1, keepdims=True) for x in stacks(s)])
        m_new = jnp.maximum(m_prev, m_blk)               # (GK, 1)
        p = jnp.exp(s - jnp.concatenate([m_new] * k))
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + sum(
            jnp.sum(x, axis=1, keepdims=True) for x in stacks(p))
        if quantized:
            p = p * per_row(vs_ref, i)
        pv = jnp.dot(p.astype(compute_dtype), vb,
                     preferred_element_type=jnp.float32)  # (k*GK, L)
        return m_new, l_new, acc * jnp.concatenate([corr] * k) + pv

    init = (jnp.full((GK, 1), NEG_INF, jnp.float32),
            jnp.zeros((GK, 1), jnp.float32),
            jnp.zeros((k * GK, L), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, nblk, block, init)
    slot_ref[0] = (s0 + nblk) % 2
    # stack h's sums sit in its own lanes [h*D, (h+1)*D): bring them to 0
    out = sum(x if h == 0 else pltpu.roll(x, L - h * head_dim, 1)
              for h, x in enumerate(stacks(acc)))
    o_ref[0] = (out[:, :head_dim] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    page: int, k_scales: jax.Array | None = None,
                    v_scales: jax.Array | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B,H,D); k/v_pages: (P,R,L) stored pages of ``page`` tokens;
    block_tables: (B,NP); lengths: (B,) -> (B,H,D).

    ``k_scales``/``v_scales``: optional (P, KH) float32 per-page per-kv-head
    absmax scales — when given, pages are int8 and dequantized in-VMEM.
    A sequence of length 0 attends to nothing and gets zeros.
    """
    lay = pool_layout(k_pages.shape, q.shape[-1], page=page)
    ppb = pages_per_block(page, block_tables.shape[1],
                          lay.page_bytes(k_pages.dtype.itemsize))
    return _paged_attention(q, k_pages, v_pages, block_tables, lengths,
                            page=page, k_scales=k_scales, v_scales=v_scales,
                            ppb=ppb, interpret=interpret)


def _paged_attention(q, k_pages, v_pages, block_tables, lengths, *, page,
                     k_scales, v_scales, ppb: int, interpret: bool):
    """``paged_attention`` with ``ppb`` pages a block."""
    B, H, D = q.shape
    P, R, L = k_pages.shape
    NP = block_tables.shape[1]
    lay = pool_layout(k_pages.shape, D, page=page)
    KH, k = lay.kv_heads, lay.records      # k: records in a page row
    if H % KH:
        raise ValueError(f"{H} query heads over {KH} kv heads")
    G = H // KH
    quantized = k_scales is not None
    if quantized and v_scales is None:
        raise ValueError("k_scales given without v_scales")
    GK = G * KH
    GKp = -(-GK // SUBLANES) * SUBLANES    # one record stack, whole tiles
    # stack h, row g*KH + kh: query head kh*G + g in lanes [h*D, (h+1)*D)
    qg = q.reshape(B, KH, G, D).transpose(0, 2, 1, 3).reshape(B, GK, D)
    qg = jnp.pad(qg, ((0, 0), (0, GKp - GK), (0, 0)))
    eye = jnp.eye(k, dtype=q.dtype)[None, :, None, :, None]
    qg = (qg[:, None, :, None, :] * eye).reshape(B, k * GKp, L)
    compute_dtype = q.dtype if quantized else k_pages.dtype

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, k * GKp, L), lambda b, bt, ln: (b, 0, 0))]
    operands = [qg]
    if quantized:
        # each query row's page scales, block by block: (B, NB, k*GKp, ppb)
        NB = -(-NP // ppb)
        tables = jnp.pad(block_tables, ((0, 0), (0, NB * ppb - NP)))

        def by_block(s):
            s = s[tables].reshape(B, NB, ppb, KH).transpose(0, 1, 3, 2)
            s = jnp.pad(jnp.tile(s, (1, 1, G, 1)),
                        ((0, 0), (0, 0), (0, GKp - GK), (0, 0)))
            return jnp.tile(s, (1, 1, k, 1))
        spec = pl.BlockSpec((1, NB, k * GKp, ppb),
                            lambda b, bt, ln: (b, 0, 0, 0))
        in_specs += [spec, spec]
        operands += [by_block(k_scales), by_block(v_scales)]
    in_specs += [hbm, hbm]
    operands += [k_pages, v_pages]
    buf = pltpu.VMEM((2, ppb, R, L), k_pages.dtype)
    kernel = functools.partial(
        _kernel, page=page, num_pages=NP, ppb=ppb, kv_heads=KH, records=k,
        head_dim=D, scale=1.0 / math.sqrt(D), quantized=quantized,
        compute_dtype=compute_dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, GKp, D), lambda b, bt, ln: (b, 0, 0)),
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, GKp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables, lengths, *operands)
    out = out[:, :GK].reshape(B, G, KH, D)
    return out.transpose(0, 2, 1, 3).reshape(B, H, D)
