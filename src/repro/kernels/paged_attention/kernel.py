"""Pallas TPU paged attention (decode) — TPU-native vLLM PagedAttention.

Hardware adaptation: the CUDA kernel's warp-level gather has no TPU
analogue; instead the page table rides in SMEM as a *scalar-prefetch*
operand (PrefetchScalarGridSpec) and the BlockSpec index_map dereferences it,
so the pipeline's async copies stream exactly the pages each sequence needs
HBM->VMEM.  Online-softmax accumulators live in VMEM scratch across the
(sequential) page axis of the grid.

Grid: (B, NP).  Per step the kernel sees one (page, KH, D) K/V tile and the
(H, D) query for that sequence; all query heads for a kv head are processed
together (GQA groups stay in VREGs).

Variable-context streaming: the grid stays the static worst case (B, NP) —
jit-friendly, one compiled program for any batch mix — but the K/V index
maps clamp the page coordinate at each sequence's last *active* page
(``ceil(length / page) - 1``).  Pallas elides the HBM->VMEM copy whenever an
index map returns the same block index as the previous grid step, so steps
past a sequence's live context re-reference the last active page and move no
bytes; ``@pl.when(ip * page < length)`` already skipped their compute.  Per
launch the kernel therefore streams ``sum_b max(ceil(len_b/page), 1)`` pages
instead of ``B * NP`` (see ``ops.streamed_pages_per_step``).

Int8 KV: when per-page, per-kv-head scales are passed, K/V pages are int8
and dequantized in-VMEM inside ``_compute`` (one (KH,)-scale row per page,
riding the same clamped index map), halving decode HBM traffic again.  The
(P, KH) scale arrays are viewed as (P, 1, KH) so each block is (1, 1, KH):
its last two dims equal the array's, which the TPU's (8, 128) tiling rule
accepts — a (1, KH) block over (P, KH) is refused by the compiler.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(block_tables, lengths, q_ref, *refs, page: int, num_pages: int,
            groups: int, scale: float, quantized: bool):
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    ip = pl.program_id(1)
    length = lengths[b]

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ip * page < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (H, D)
        k = k_ref[0].astype(jnp.float32)                  # (page, KH, D)
        if quantized:
            k = k * ks_ref[0][:, :, None]                 # in-VMEM dequant
        H, D = q.shape
        KH = k.shape[1]
        qg = q.reshape(KH, groups, D)
        # scores: (KH, G, page)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)           # (KH, G, page)
        pos = ip * page + jax.lax.broadcasted_iota(
            jnp.int32, (KH, groups, page), 2)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_scr[...]                               # (KH, G)
        m_new = jnp.maximum(m_prev, s.max(axis=2))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=2)
        v = v_ref[0].astype(jnp.float32)                  # (page, KH, D)
        if quantized:
            v = v * vs_ref[0][:, :, None]
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)           # (KH, G, D)
        acc_scr[...] = acc_scr[...] * corr[..., None] + pv
        m_scr[...] = m_new

    @pl.when(ip == num_pages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)            # (KH, G)
        out = acc_scr[...] / denom[..., None]             # (KH, G, D)
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    k_scales: jax.Array | None = None,
                    v_scales: jax.Array | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B,H,D); k/v_pages: (P,page,KH,D); block_tables: (B,NP);
    lengths: (B,) -> (B,H,D).

    ``k_scales``/``v_scales``: optional (P, KH) float32 per-page per-kv-head
    absmax scales — when given, pages are int8 and dequantized in-VMEM.
    """
    B, H, D = q.shape
    P, page, KH, _ = k_pages.shape
    NP = block_tables.shape[1]
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    quantized = k_scales is not None
    if quantized and v_scales is None:
        raise ValueError("k_scales given without v_scales")

    def page_id(b, ip, bt, ln):
        # clamp at the last active page: steps past ceil(len/page) re-issue
        # the same index, so the pipeline elides their HBM->VMEM copy
        last = jnp.maximum((ln[b] + page - 1) // page - 1, 0)
        return bt[b, jnp.minimum(ip, last)]

    kv_spec = pl.BlockSpec(
        (1, page, KH, D), lambda b, ip, bt, ln: (page_id(b, ip, bt, ln),
                                                 0, 0, 0))
    scale_spec = pl.BlockSpec(
        (1, 1, KH), lambda b, ip, bt, ln: (page_id(b, ip, bt, ln), 0, 0))
    q_spec = pl.BlockSpec((1, H, D), lambda b, ip, bt, ln: (b, 0, 0))

    kernel = functools.partial(_kernel, page=page, num_pages=NP,
                               groups=G, scale=scale, quantized=quantized)
    if quantized:
        in_specs = [q_spec, kv_spec, scale_spec, kv_spec, scale_spec]
        operands = (q, k_pages, k_scales.reshape(P, 1, KH), v_pages,
                    v_scales.reshape(P, 1, KH))
    else:
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (q, k_pages, v_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, D), lambda b, ip, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KH, G), jnp.float32),
            pltpu.VMEM((KH, G), jnp.float32),
            pltpu.VMEM((KH, G, D), jnp.float32),
        ],
    )
    out_dtype = q.dtype
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), out_dtype),
        interpret=interpret,
    )(block_tables, lengths, *operands)
