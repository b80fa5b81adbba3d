"""Int8 KV pages + one-launch variable-context paged decode.

Pins the perf-PR invariants without hypothesis (test_kernels.py carries the
hypothesis ragged-property sweep where that dependency exists):

  * int8 kernel output == a plain-numpy quantized oracle, and stays within
    an absolute bound of the exact (unquantized) attention;
  * the variable-context kernel is exact on ragged batches and its streamed
    page count is the live-page sum, not B x blocks_per_seq;
  * quantized_append round-trips chunked writes against a numpy requantize
    reference and zeroes stale rows in freshly allocated pages;
  * PagePool ensure/release are O(1) bulk free-list ops;
  * pool/profile sizing gives int8 >= 1.8x token capacity at fixed VRAM;
  * default (param-dtype) paged serving stays byte-identical to the dense
    engine through the differential harness, and int8 cluster serving
    completes with every pool drained.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ModelProfile
from repro.kernels.paged_attention import (dense_to_pages,
                                           dequantize_kv_pages,
                                           paged_attention,
                                           quantize_kv_pages,
                                           quantized_append,
                                           streamed_pages_per_step)
from repro.serving import (EngineConfig, PagedEngine, PagePool, Request,
                           page_bytes, pages_for_vram)
from repro.kernels.paged_attention.layout import page_layout

from harness import (EC, assert_pools_drained, assert_serves_like_reference,
                     make_disagg_plan, make_plan, random_prompts,
                     serve_on_cluster)


# --- kernel: int8 parity -----------------------------------------------------

def _quantized(pages, page, KH, D):
    """Int8 stored pages and their scales, quantized from the token view."""
    lay = page_layout(page, KH, D)
    q, scales = quantize_kv_pages(lay.to_tokens(pages))
    return lay.to_rows(q), scales


def _numpy_quantized_oracle(q, kq, ks, vq, vs, tables, lengths, page):
    """Dequantize with numpy, gather logical KV, exact softmax attention."""
    q, kq, ks, vq, vs = map(np.asarray, (q, kq, ks, vq, vs))
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    B, H, D = q.shape
    KH = ks.shape[1]
    G = H // KH
    kq = kq.reshape(kq.shape[0], page, KH, D)
    vq = vq.reshape(vq.shape[0], page, KH, D)
    k = kq.astype(np.float32) * ks[:, None, :, None]
    v = vq.astype(np.float32) * vs[:, None, :, None]
    out = np.zeros((B, H, D), np.float32)
    for b in range(B):
        L = int(lengths[b])
        nb = -(-L // page)
        kb = k[tables[b, :nb]].reshape(nb * page, KH, D)[:L]
        vb = v[tables[b, :nb]].reshape(nb * page, KH, D)[:L]
        qg = q[b].reshape(KH, G, D)
        s = np.einsum("hgd,shd->hgs", qg, kb) / math.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[b] = np.einsum("hgs,shd->hgd", p, vb).reshape(H, D)
    return out


def test_int8_kernel_matches_numpy_oracle():
    B, H, KH, S, page, D = 3, 8, 2, 256, 32, 64
    key = jax.random.key(0)
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
    oracle = _numpy_quantized_oracle(q, kq, ks, vq, vs, tables, lengths, page)
    # kernel vs same-quantization oracle: only fp accumulation differs
    np.testing.assert_allclose(np.asarray(out), oracle, rtol=2e-4, atol=2e-4)


def test_int8_kernel_bounded_error_vs_exact():
    """Quantization error stays bounded: int8 output within atol of the
    exact f32 attention over the same KV (unit-normal values)."""
    B, H, KH, S, page, D = 2, 4, 2, 128, 32, 64
    key = jax.random.key(1)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, H, D))
    k = jax.random.normal(k2, (B, S, KH, D))
    v = jax.random.normal(k3, (B, S, KH, D))
    lengths = jnp.array([100, 64], jnp.int32)
    k_pages, v_pages, tables = dense_to_pages(k, v, lengths, page)
    exact = paged_attention(q, k_pages, v_pages, tables, lengths, page=page,
                            interpret=True)
    kq, ks = _quantized(k_pages, page, KH, D)
    vq, vs = _quantized(v_pages, page, KH, D)
    quant = paged_attention(q, kq, vq, tables, lengths, page=page,
                            k_scales=ks, v_scales=vs, interpret=True)
    err = np.abs(np.asarray(quant) - np.asarray(exact)).max()
    assert err < 0.08, f"int8 KV error {err:.4f} vs exact attention"


def test_quantize_roundtrip_bound():
    """Per-page per-head absmax: round-trip error <= amax/127 elementwise."""
    pages = jax.random.normal(jax.random.key(2), (5, 16, 3, 32)) * 3.0
    qp, sc = quantize_kv_pages(pages)
    back = dequantize_kv_pages(qp, sc)
    amax = np.abs(np.asarray(pages)).max(axis=(-3, -1), keepdims=False)
    bound = (amax / 127.0)[:, None, :, None] * 1.001 + 1e-7
    assert (np.abs(np.asarray(back - pages)) <= bound).all()


# --- kernel: variable context ------------------------------------------------

RAGGED = [
    (16, [1, 16, 7]),
    (32, [17, 200, 96, 256]),
    (64, [64, 63, 65, 1, 128]),
]


@pytest.mark.parametrize("page,lens", RAGGED)
def test_variable_context_ragged_exact(page, lens):
    """Live-block gathering drops no live token and leaks no dead one."""
    B = len(lens)
    H, KH, D = 4, 2, 64
    S = max(-(-max(lens) // page), 1) * page
    key = jax.random.key(3)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, H, D))
    k = jax.random.normal(k2, (B, S, KH, D))
    v = jax.random.normal(k3, (B, S, KH, D))
    lengths = jnp.asarray(lens, jnp.int32)
    k_pages, v_pages, tables = dense_to_pages(k, v, lengths, page)
    out = paged_attention(q, k_pages, v_pages, tables, lengths, page=page,
                          interpret=True)
    # exact dense oracle over the logical (unpadded) KV
    G = H // KH
    qg = q.reshape(B, KH, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k) / math.sqrt(D)
    mask = jnp.arange(S)[None] < lengths[:, None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    ref = jnp.einsum("bhgs,bshd->bhgd",
                     jax.nn.softmax(s, -1), v).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_streamed_pages_live_only():
    """Per step the kernel schedules ceil(len/page) copies per sequence —
    strictly fewer than the dense B x blocks_per_seq grid on ragged loads,
    equal only when every sequence fills its budget."""
    page = 32
    lens = np.array([17, 200, 96], np.int32)
    blocks_per_seq = -(-int(lens.max()) // page)      # 7 (224-token budget)
    live = streamed_pages_per_step(lens, page)
    assert live == 1 + 7 + 3 == 11
    assert live < len(lens) * blocks_per_seq
    full = np.full((4,), 8 * page, np.int32)
    assert streamed_pages_per_step(full, page) == 4 * 8
    # empty sequences stream nothing
    assert streamed_pages_per_step(np.zeros((2,), np.int32), page) == 0


# --- quantized append --------------------------------------------------------

def test_quantized_append_matches_numpy_requantize():
    """Chunked appends == numpy oracle that requantizes each touched page
    from the exact running history."""
    rng = np.random.RandomState(0)
    page, NP, KH, D, B = 8, 6, 2, 16, 2
    P = 1 + B * NP
    lay = page_layout(page, KH, D)
    pages = jnp.zeros(lay.shape(P), jnp.int8)
    scales = jnp.zeros((P, KH), jnp.float32)
    table = jnp.asarray(
        np.arange(1, P).reshape(B, NP).astype(np.int32))
    hist = np.zeros((B, NP * page, KH, D), np.float32)
    start = np.zeros((B,), np.int64)
    for C in (3, 8, 5, 1, 7):
        rows = rng.randn(B, C, KH, D).astype(np.float32)
        pages, scales = quantized_append(
            pages, scales, table, jnp.asarray(start, jnp.int32),
            jnp.asarray(rows))
        for b in range(B):
            hist[b, start[b]:start[b] + C] = rows[b]
        start += C
        # oracle: re-quantize every page from the exact history
        back = np.asarray(dequantize_kv_pages(lay.to_tokens(pages), scales))
        for b in range(B):
            nb = -(-int(start[b]) // page)
            for j in range(nb):
                exact = hist[b, j * page:(j + 1) * page]
                amax = np.abs(exact).max(axis=(0, 2))
                got = back[int(np.asarray(table)[b, j])]
                # written rows within one quantization step of exact
                bound = np.maximum(amax / 127.0, 1e-8)[None, :, None]
                assert (np.abs(got - exact) <= bound * 2.01).all()
    # rows past the write frontier must be exactly zero (no stale garbage
    # inflating a freshly allocated page's absmax)
    b, L = 0, int(start[0])
    nb = -(-L // page)
    tail = np.asarray(dequantize_kv_pages(lay.to_tokens(pages), scales))[
        int(np.asarray(table)[b, nb - 1])].reshape(page, KH, D)
    w = L - (nb - 1) * page
    assert (tail[w:] == 0).all()


# --- pool: O(1) alloc + sizing ----------------------------------------------

def _tiny_cfg():
    from repro.configs import get_smoke_config
    return get_smoke_config("smollm_360m")


def test_pool_bulk_alloc_is_one_op():
    """Growing a slot by 64 blocks is ONE free-list operation, not 64 x
    layers pops; release is one push."""
    cfg = _tiny_cfg()
    page = 4
    pool = PagePool(cfg, num_pages=4096, page_size=page, max_batch=4,
                    max_seq_len=64 * page)
    before = pool.alloc_ops
    assert pool.ensure(0, 64 * page)          # 64 blocks in one call
    assert pool.alloc_ops == before + 1
    got = pool.table[:, 0, :64]
    assert (got > 0).all() and len(np.unique(got)) == got.size
    used = pool.used
    assert used == 64 * pool.num_layers
    pool.release(0)
    assert pool.alloc_ops == before + 2
    assert pool.used == 0 and (pool.table[:, 0] == 0).all()


def test_pool_alloc_order_matches_sequential():
    """Bulk pops hand out the same pages, in the same order, as the old
    one-page-at-a-time loop (layer fastest, block outer)."""
    cfg = _tiny_cfg()
    pool = PagePool(cfg, num_pages=512, page_size=4, max_batch=4,
                    max_seq_len=64)
    L = pool.num_layers
    pool.ensure(0, 9)                          # 3 blocks
    expect = np.arange(1, 1 + 3 * L).reshape(3, L).T
    np.testing.assert_array_equal(pool.table[:, 0, :3], expect)


def test_int8_pool_capacity_ratio():
    cfg = _tiny_cfg()
    vram = 4e9
    base = pages_for_vram(cfg, vram, page_size=16)
    quant = pages_for_vram(cfg, vram, page_size=16, kv_dtype="int8")
    assert quant / max(base, 1) >= 1.8
    # page_bytes math: int8 = elements at 1 byte + 2 f32 scale rows
    kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    assert page_bytes(cfg, 16, "int8") == 2 * 16 * kh * hd + 8 * kh
    elt = {"bfloat16": 2, "float32": 4}[cfg.param_dtype]
    assert page_bytes(cfg, 16) == 2 * 16 * kh * hd * elt


def test_model_profile_int8_kv_sizing():
    """Planner/simulator capacity model sees the same ~2x the engines get."""
    kw = dict(num_layers=8, d_model=512, d_ff=2048, vocab=1000,
              n_kv_heads=4, head_dim=64)
    base = ModelProfile.from_dims("m", **kw)
    quant = ModelProfile.from_dims("m", kv_dtype="int8", kv_page_size=16,
                                   **kw)
    r = base.kv_bytes_per_token_layer / quant.kv_bytes_per_token_layer
    assert r >= 1.8
    with pytest.raises(ValueError):
        ModelProfile.from_dims("m", kv_dtype="fp4", **kw)


def test_pool_rejects_unknown_kv_dtype():
    cfg = _tiny_cfg()
    with pytest.raises(ValueError):
        PagePool(cfg, num_pages=512, page_size=4, max_batch=2,
                 max_seq_len=16, kv_dtype="fp8")


# --- engines -----------------------------------------------------------------

def test_paged_engine_int8_completes_and_drains(gqa_model):
    cfg, params = gqa_model
    ec = EngineConfig(max_batch=4, max_len=64, prompt_len=16)
    eng = PagedEngine(cfg, params, ec, page_size=16, kv_dtype="int8")
    assert eng.pool.quantized and eng.pool.k.dtype == jnp.int8
    prompts = random_prompts(cfg, (10, 5, 16, 12), seed=0)
    reqs = [Request(i, p, max_new_tokens=6) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done(max_iters=200)
    assert all(r.done and len(r.output) == 6 for r in reqs)
    assert eng.pool.used == 0


def test_default_paged_serving_stays_byte_identical(gqa_model, reference):
    """The PR's do-no-harm pin: with kv_dtype unset, multi-stage paged
    serving through the differential harness is still byte-identical to the
    single dense engine."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    assert_serves_like_reference(cfg, params, p, prompts, ref, paged=True)


def test_cluster_int8_completes_and_drains(gqa_model):
    cfg, params = gqa_model
    prompts = random_prompts(cfg, (10, 5, 16), seed=1)
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    rt, reqs = serve_on_cluster(cfg, params, p, prompts, paged=True,
                                kv_dtype="int8", max_new_tokens=5)
    assert all(r.done and len(r.output) == 5 for r in reqs)
    assert_pools_drained(rt)


def test_int8_disaggregated_matches_mixed_cluster(gqa_model):
    """The int8 handoff tolerance check: quantized pages + scales travel
    verbatim over the peer link, so a disaggregated int8 run must emit
    token-for-token what a mixed int8 cluster with the same decode split
    emits (quantization error is identical — the pages are the same
    bytes)."""
    from repro.serving import InProcessTransport
    cfg, params = gqa_model
    prompts = random_prompts(cfg, (10, 5, 16), seed=1)
    pm = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    _, reqm = serve_on_cluster(cfg, params, pm, prompts, paged=True,
                               kv_dtype="int8", max_new_tokens=5)
    refq = [r.output for r in reqm]
    pd = make_disagg_plan(cfg, {"n0": (0, 4)}, {"n1": (0, 2), "n2": (2, 4)})
    rt, reqd = serve_on_cluster(cfg, params, pd, prompts, paged=True,
                                kv_dtype="int8", max_new_tokens=5,
                                transport=InProcessTransport(
                                    default_delay_s=1e-3))
    assert rt.disaggregated
    assert [r.output for r in reqd] == refq
    assert_pools_drained(rt)
