"""Jit'd wrapper + page-pool utilities for paged attention decode.

The serving-side allocator that feeds this kernel (on-demand pages, block
tables, admission control) lives in ``repro.serving.kv_pool.PagePool``;
``repro.models.paged`` is the model-level consumer (``gqa_decode_paged``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import paged_attention
from .layout import page_layout
from .ref import paged_attention_ref


@functools.partial(jax.jit, static_argnames=("page", "interpret"))
def paged_attention_op(q, k_pages, v_pages, block_tables, lengths,
                       k_scales=None, v_scales=None, *, page: int,
                       interpret: bool = False):
    return paged_attention(q, k_pages, v_pages, block_tables, lengths,
                           page=page, k_scales=k_scales, v_scales=v_scales,
                           interpret=interpret)


def streamed_pages_per_step(lengths, page: int) -> int:
    """Pages the kernel copies HBM->VMEM per launch.

    A sequence's pages are gathered in blocks, and the kernel loops over its
    live blocks only: the last block copies only the pages below
    ``ceil(len/page)``, so traffic follows the *live* context,
    ``sum_b ceil(len_b / page)`` pages, not ``B * NP``; an empty sequence
    copies none."""
    l = np.asarray(lengths)
    return int((-(-l // page)).sum())


def dense_to_pages(k: jax.Array, v: jax.Array, lengths, page: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pack dense (B,S,KH,D) caches into a page pool stored as
    ``layout.page_layout`` lays it out, plus block tables (testing/migration
    helper; a real server allocates pages on demand)."""
    B, S, KH, D = k.shape
    assert S % page == 0
    npages = S // page
    lay = page_layout(page, KH, D)
    k_pages = lay.to_rows(k.reshape(B * npages, page, KH, D))
    v_pages = lay.to_rows(v.reshape(B * npages, page, KH, D))
    block_tables = jnp.arange(B * npages, dtype=jnp.int32).reshape(B, npages)
    return k_pages, v_pages, block_tables
