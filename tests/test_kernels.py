"""Pallas kernel validation (interpret=True on CPU) against jnp oracles.

Per assignment: sweep shapes/dtypes per kernel, assert_allclose vs ref.py.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_ref)
from repro.kernels.paged_attention import (dense_to_pages, paged_attention,
                                           paged_attention_ref,
                                           quantize_kv_pages,
                                           streamed_pages_per_step)
from repro.kernels.paged_attention.layout import (page_layout, pool_layout,
                                                  write_tokens)

TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _quantized(pages, page, KH, D):
    """Int8 stored pages and their scales, quantized from the token view."""
    lay = page_layout(page, KH, D)
    q, scales = quantize_kv_pages(lay.to_tokens(pages))
    return lay.to_rows(q), scales


def _mk_qkv(key, B, H, KH, Sq, Sk, D, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, H, Sq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (B, KH, Sk, D), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (B, KH, Sk, D), jnp.float32).astype(dtype)
    return q, k, v


# --- flash attention sweeps --------------------------------------------------

FLASH_SHAPES = [
    # B, H, KH, Sq, Sk, D, causal, window
    (1, 4, 4, 128, 128, 64, True, 0),
    (2, 8, 2, 256, 256, 128, True, 0),       # GQA
    (1, 4, 1, 128, 128, 128, True, 0),       # MQA
    (2, 4, 4, 128, 128, 64, False, 0),       # bidirectional
    (1, 4, 2, 256, 256, 64, True, 100),      # sliding window
    (1, 2, 2, 200, 200, 64, True, 0),        # ragged (pad to blocks)
    (1, 2, 2, 96, 160, 64, False, 0),        # cross lengths
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", FLASH_SHAPES)
def test_flash_attention_matches_ref(shape, dtype):
    B, H, KH, Sq, Sk, D, causal, window = shape
    q, k, v = _mk_qkv(jax.random.key(0), B, H, KH, Sq, Sk, D, dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_kv=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_flash_attention_block_shape_independence():
    """Output must not depend on the BlockSpec tiling."""
    q, k, v = _mk_qkv(jax.random.key(1), 1, 4, 2, 256, 256, 64, jnp.float32)
    outs = []
    for bq, bk in [(64, 64), (128, 64), (64, 128), (128, 128), (256, 256)]:
        outs.append(flash_attention(q, k, v, causal=True, block_q=bq,
                                    block_kv=bk, interpret=True))
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(0, 3), st.booleans())
def test_flash_attention_property(b, g_pow, causal):
    """Random GQA configs vs oracle (hypothesis sweep)."""
    KH = 2
    H = KH * (2 ** g_pow)
    q, k, v = _mk_qkv(jax.random.key(b * 7 + g_pow), b, H, KH, 128, 128, 64,
                      jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# --- paged attention sweeps --------------------------------------------------

PAGED_SHAPES = [
    # B, H, KH, S(max), page, D
    (2, 4, 4, 256, 64, 64),
    (3, 8, 2, 256, 64, 128),                 # GQA
    (1, 4, 1, 512, 128, 64),                 # MQA
    (4, 2, 2, 128, 32, 128),
    (2, 16, 16, 192, 16, 128),               # OLMo-1B heads; NP=12, 8 a block
    (2, 15, 5, 128, 16, 64),                 # SmolLM-360M: 2 records a row
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", PAGED_SHAPES)
def test_paged_attention_matches_ref(shape, dtype):
    B, H, KH, S, page, D = shape
    key = jax.random.key(42)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q = jax.random.normal(k1, (B, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (B, S, KH, D), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (B, S, KH, D), jnp.float32).astype(dtype)
    lengths = jax.random.randint(k4, (B,), 1, S + 1)
    k_pages, v_pages, tables = dense_to_pages(k, v, lengths, page)
    out = paged_attention(q, k_pages, v_pages, tables, lengths, page=page,
                          interpret=True)
    ref = paged_attention_ref(q, k_pages, v_pages, tables, lengths,
                              page=page)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_paged_attention_scrambled_pages():
    """Same logical KV, different physical page layout -> same output
    (the whole point of paging)."""
    B, H, KH, S, page, D = 2, 4, 2, 256, 64, 64
    key = jax.random.key(7)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, H, D))
    k = jax.random.normal(k2, (B, S, KH, D))
    v = jax.random.normal(k3, (B, S, KH, D))
    lengths = jnp.array([200, 130], jnp.int32)
    k_pages, v_pages, tables = dense_to_pages(k, v, lengths, page)
    out1 = paged_attention(q, k_pages, v_pages, tables, lengths, page=page,
                           interpret=True)
    # scramble physical page order with a permutation
    P = k_pages.shape[0]
    perm = jax.random.permutation(jax.random.key(9), P)
    inv = jnp.argsort(perm)
    out2 = paged_attention(q, k_pages[perm], v_pages[perm], inv[tables],
                           lengths, page=page, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 4),                       # batch
       st.sampled_from([16, 32, 64]),           # page size
       st.integers(1, 6),                       # blocks per sequence budget
       st.integers(0, 2 ** 30))                 # length seed
def test_paged_attention_ragged_property(b, page, nblk, seed):
    """Variable-context kernel == oracle over ragged lengths x page counts.

    The clamped index_map only schedules copies for a sequence's live pages;
    this sweep pins that the truncation never drops a live token or lets a
    dead one leak in, across arbitrary ragged length mixes."""
    H, KH, D = 4, 2, 64
    S = page * nblk
    key = jax.random.key(seed % (2 ** 31 - 1))
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q = jax.random.normal(k1, (b, H, D))
    k = jax.random.normal(k2, (b, S, KH, D))
    v = jax.random.normal(k3, (b, S, KH, D))
    lengths = jax.random.randint(k4, (b,), 1, S + 1)
    k_pages, v_pages, tables = dense_to_pages(k, v, lengths, page)
    out = paged_attention(q, k_pages, v_pages, tables, lengths, page=page,
                          interpret=True)
    ref = paged_attention_ref(q, k_pages, v_pages, tables, lengths,
                              page=page)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # live-page traffic accounting: never more than the dense grid
    streamed = streamed_pages_per_step(np.asarray(lengths), page)
    assert streamed <= b * nblk


@pytest.mark.parametrize("shape", PAGED_SHAPES)
def test_paged_attention_int8_matches_ref(shape):
    """Quantized kernel == oracle run on the *dequantized* pages — the
    in-VMEM dequant must be numerically transparent."""
    B, H, KH, S, page, D = shape
    key = jax.random.key(3)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q = jax.random.normal(k1, (B, H, D))
    k = jax.random.normal(k2, (B, S, KH, D))
    v = jax.random.normal(k3, (B, S, KH, D))
    lengths = jax.random.randint(k4, (B,), 1, S + 1)
    k_pages, v_pages, tables = dense_to_pages(k, v, lengths, page)
    kq, ks = _quantized(k_pages, page, KH, D)
    vq, vs = _quantized(v_pages, page, KH, D)
    out = paged_attention(q, kq, vq, tables, lengths, page=page,
                          k_scales=ks, v_scales=vs, interpret=True)
    ref = paged_attention_ref(q, kq, vq, tables, lengths,
                              k_scales=ks, v_scales=vs, page=page)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_paged_attention_matches_dense_decode():
    """Paged decode == dense cache attention at the same positions."""
    import math
    B, H, KH, S, page, D = 2, 8, 4, 128, 32, 64
    key = jax.random.key(11)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, H, D))
    k = jax.random.normal(k2, (B, S, KH, D))
    v = jax.random.normal(k3, (B, S, KH, D))
    lengths = jnp.array([100, 64], jnp.int32)
    k_pages, v_pages, tables = dense_to_pages(k, v, lengths, page)
    out = paged_attention(q, k_pages, v_pages, tables, lengths, page=page,
                          interpret=True)
    # dense reference
    G = H // KH
    qg = q.reshape(B, KH, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k) / math.sqrt(D)
    mask = jnp.arange(S)[None] < lengths[:, None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhgs,bshd->bhgd", p, v).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# --- paged attention: edge lengths and dead pages -----------------------------

# B rows of the edge lengths below; NP = 12 table entries of page 16, so
# the kernel's 8-page blocks split each table into a full and a partial one
EDGE_LAYOUTS = {
    # H, KH, D
    "olmo_mha": (16, 16, 128),
    "gqa": (8, 2, 128),
    "mqa": (4, 1, 64),
    "smollm_gqa": (15, 5, 64),        # G = 3 over 5 kv heads, 2 records a row
}
EDGE_PAGE, EDGE_NP = 16, 12


def _edge_inputs(layout, dtype, seed):
    """Rows of length 1, a page, one block, one block -1 and +1, the whole
    table, and an empty row between them, over a pool whose pages are
    scattered and shared by no row."""
    from repro.kernels.paged_attention.kernel import pages_per_block
    H, KH, D = EDGE_LAYOUTS[layout]
    page, NP = EDGE_PAGE, EDGE_NP
    itemsize = 1 if dtype == "int8" else jnp.dtype(dtype).itemsize
    blk = page * pages_per_block(page, NP, page * KH * D * itemsize)
    lens = [1, page, blk, 0, blk - 1, blk + 1, NP * page]
    B = len(lens)
    P = B * NP + 1
    k1, k2, k3, k4 = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(k1, (B, H, D), jnp.float32)
    k = jax.random.normal(k2, (P, page, KH, D), jnp.float32)
    v = jax.random.normal(k3, (P, page, KH, D), jnp.float32)
    tables = jax.random.permutation(k4, P)[:B * NP].reshape(B, NP)
    lengths = jnp.asarray(lens, jnp.int32)
    lay = page_layout(page, KH, D)
    if dtype == "int8":
        (k, ks), (v, vs) = quantize_kv_pages(k), quantize_kv_pages(v)
        return (q, lay.to_rows(k), lay.to_rows(v), tables.astype(jnp.int32),
                lengths, ks, vs)
    return (q.astype(dtype), lay.to_rows(k).astype(dtype),
            lay.to_rows(v).astype(dtype), tables.astype(jnp.int32), lengths,
            None, None)


def _check_edge(out, q, k, v, tables, lengths, ks, vs, dtype):
    ref = paged_attention_ref(q, k, v, tables, lengths,
                              k_scales=ks, v_scales=vs, page=EDGE_PAGE)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    live = np.asarray(lengths) > 0
    tol = TOL[jnp.bfloat16] if dtype == jnp.bfloat16 else TOL[jnp.float32]
    np.testing.assert_allclose(out[live], ref[live], **tol)
    assert np.all(out[~live] == 0.0)         # an empty row attends nothing


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, "int8"],
                         ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("layout", sorted(EDGE_LAYOUTS))
def test_paged_attention_edge_lengths(layout, dtype):
    """Lengths at every page and block boundary, through the same kernel
    for MHA, GQA and MQA heads in float32, bfloat16 and int8 pages."""
    q, k, v, tables, lengths, ks, vs = _edge_inputs(layout, dtype, 5)
    out = paged_attention(q, k, v, tables, lengths, page=EDGE_PAGE,
                          k_scales=ks, v_scales=vs, interpret=True)
    _check_edge(out, q, k, v, tables, lengths, ks, vs, dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, "int8"],
                         ids=["bf16", "int8"])
def test_paged_attention_never_reads_dead_pages(dtype):
    """Every page outside a row's live set is NaN (an int8 page's scales):
    the output still equals the oracle on the clean pool and is finite, so
    no dead page was read — not even those in a row's last block."""
    q, k, v, tables, lengths, ks, vs = _edge_inputs("olmo_mha", dtype, 6)
    live = np.zeros(k.shape[0], bool)
    for row, n in zip(np.asarray(tables), np.asarray(lengths)):
        live[row[:-(-int(n) // EDGE_PAGE)]] = True
    dead = jnp.asarray(~live)
    if dtype == "int8":
        pk, pv = k, v
        pks = jnp.where(dead[:, None], jnp.nan, ks)
        pvs = jnp.where(dead[:, None], jnp.nan, vs)
    else:
        pk = jnp.where(dead[:, None, None], jnp.nan, k)
        pv = jnp.where(dead[:, None, None], jnp.nan, v)
        pks = pvs = None
    out = paged_attention(q, pk, pv, tables, lengths, page=EDGE_PAGE,
                          k_scales=pks, v_scales=pvs, interpret=True)
    assert np.all(np.isfinite(np.asarray(out, np.float32)))
    _check_edge(out, q, k, v, tables, lengths, ks, vs, dtype)


# --- stored pool layout: writes -------------------------------------------

@pytest.mark.parametrize("C", [1, 3, 16, 17, 40])
@pytest.mark.parametrize("heads", [(5, 64, 16), (16, 128, 16), (2, 16, 8),
                                   (5, 64, 32)],
                         ids=["smollm", "olmo", "small", "smollm_page32"])
def test_write_tokens_matches_token_view(heads, C):
    """A write of C tokens at any start into the stored pool equals the same
    write into the token view: runs of one token (OLMo-1B's heads) and runs
    of a whole page whose tokens straddle rows (SmolLM-360M's), from
    random starts, with writes that span several runs and pages.  Every
    page but the scratch page 0 is compared, so a token the write should
    have kept is caught."""
    KH, D, page = heads
    lay = page_layout(page, KH, D)
    B, NP = 3, 8
    P = 1 + B * NP
    rng = np.random.default_rng(C)
    pool = rng.standard_normal(lay.shape(P)).astype(np.float32)
    table = 1 + rng.permutation(P - 1)[:B * NP].reshape(B, NP)
    start = rng.integers(0, NP * page - C + 1, B)
    new = rng.standard_normal((B, C, KH, D)).astype(np.float32)
    got = write_tokens(jnp.asarray(pool), jnp.asarray(table, jnp.int32),
                       jnp.asarray(start, jnp.int32), jnp.asarray(new), page)
    want = pool.reshape(P, page, KH, D).copy()
    for b in range(B):
        for c in range(C):
            pos = start[b] + c
            want[table[b, pos // page], pos % page] = new[b, c]
    assert pool_layout(got.shape, D, kv_heads=KH) == lay
    np.testing.assert_array_equal(np.asarray(got)[1:],
                                  lay.to_rows(want)[1:])
