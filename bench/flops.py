"""Operations and bytes the served work needs, computed from shapes.

Counted for a dense decoder of the configuration file's sizes (attention with
``num_key_value_heads`` KV heads, SwiGLU feed-forward, tied LM head).  A
multiply-add is two operations.  "Needs" means the work of the live tokens:
padding rows, masked attention and gathered pages past a sequence's length
count for nothing, so a shape-padded program reads below its true share.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16 = 2   # bytes per element of the bfloat16 KV pages


def dims(conf: Dict) -> Tuple[int, int, int, int, int, int, int]:
    """(layers, d_model, heads, kv_heads, head_dim, d_ff, vocab)."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return (conf["num_hidden_layers"], d, h, conf["num_key_value_heads"],
            d // h, conf["intermediate_size"], conf["vocab_size"])


def matmul_params(conf: Dict) -> int:
    """Weights one token multiplies through, LM head excluded."""
    L, d, H, KH, D, F, _ = dims(conf)
    return L * (d * H * D + 2 * d * KH * D + H * D * d + 3 * d * F)


def attention_flops(conf: Dict, ctx: int) -> int:
    """Scores and weighted values of one query over ``ctx`` keys, all
    layers: 2 * (H * D * ctx) twice."""
    L, _, H, _, D, _, _ = dims(conf)
    return 4 * L * H * D * ctx


def decode_flops(conf: Dict, contexts: Iterable[int]) -> int:
    """One decode call: every live row runs the whole model and the LM
    head, and attends over its ``ctx`` keys (its own token included)."""
    L, d, _, _, _, _, V = dims(conf)
    per_row = 2 * (matmul_params(conf) + d * V)
    return sum(per_row + attention_flops(conf, c) for c in contexts)


def prefill_flops(conf: Dict, start: int, width: int) -> int:
    """One prefill chunk of ``width`` tokens at positions start..: the
    model's matmuls for each token plus causal attention (the token at
    position p attends over p + 1 keys).  The LM head is not needed for a
    prompt's positions and is not counted."""
    L, _, H, _, D, _, _ = dims(conf)
    keys = width * start + width * (width + 1) // 2
    return 2 * matmul_params(conf) * width + 4 * L * H * D * keys


def kv_page_bytes(conf: Dict, page: int) -> int:
    """Bytes of one layer's K and V page (bfloat16)."""
    _, _, _, KH, D, _, _ = dims(conf)
    return 2 * page * KH * D * BF16


def paged_attention_need(conf: Dict, contexts: Iterable[int], page: int
                         ) -> Tuple[int, int]:
    """(operations, bytes) the decode kernel needs for one call over all
    layers: each live row reads the K and V pages that hold its context
    once, and computes scores and weighted values over it."""
    L = dims(conf)[0]
    ops = nbytes = 0
    for c in contexts:
        ops += attention_flops(conf, c)
        nbytes += L * -(-c // page) * kv_page_bytes(conf, page)
    return ops, nbytes
