"""Plain float32 reference of a Llama-style decoder, and the control.

Written from the published architecture (``model_type: llama``,
``LlamaForCausalLM``), not from the program: it imports only JAX.  The whole
forward pass over full sequences, causal grouped-query attention without a
cache (query head ``i`` reads kv head ``i // G``), every matmul at
``Precision.HIGHEST``, one layer at a time under ``lax.scan``.  Weights are
the benchmark's own (``models/llama.py`` layout: an RMSNorm's ``scale`` is
its gain less one), upcast to float32 layer by layer.

``scores(conf, w, tokens, picks)`` returns, at every position p of every
sequence, the reference's best logit and its logit for ``picks[p]``.  The
harness passes the served continuation as ``picks`` (the token that followed
position p), so ``best - picked`` is how far below the reference's best each
served token lies.

``control_picks`` is the control: the same forward pass with every weight
matrix rounded to float8_e4m3 (one scale per output channel) and bfloat16
activations, the lower precision a later change might be tempted to serve
in.  Its greedy pick at each position is judged by the same ``scores``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _rms(x, scale, eps: float):
    """RMSNorm in float32 with gain ``1 + scale``."""
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
    return y * (1.0 + scale.astype(jnp.float32))


def _rope(x, theta: float):
    """Rotary embedding over the two halves of each head.  x: (K,S,H,D)."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq     # (S, half)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _forward(hp: Tuple, w: Dict, tokens, *, dtype, precision, prep):
    """Final hidden states (K, S, d) in float32.  ``prep`` maps a weight to
    the array the matmuls read; activations are kept in ``dtype``."""
    theta, eps = hp
    blk = w["super"]["pos0"]
    mix, ffn = blk["mix"], blk["ffn"]
    h = prep(w["embed"], (1,))[tokens].astype(jnp.float32)
    S = tokens.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def mm(spec, x, m):
        # the weight's contracted axes: those its letters share with x
        ins, wsub = spec.split("->")[0].split(",")
        contract = tuple(i for i, c in enumerate(wsub) if c in ins)
        return jnp.einsum(spec, x.astype(dtype),
                          prep(m, contract).astype(dtype),
                          precision=precision,
                          preferred_element_type=jnp.float32)

    def layer(h, lw):
        n1, q_w, k_w, v_w, o_w, n2, g_w, u_w, d_w = lw
        x = _rms(h, n1, eps)
        q = _rope(mm("ksd,dhe->kshe", x, q_w), theta)
        k = _rope(mm("ksd,dhe->kshe", x, k_w), theta)
        v = mm("ksd,dhe->kshe", x, v_w)
        KH, D = k.shape[2], k.shape[3]
        q = q.reshape(q.shape[0], S, KH, -1, D)
        s = jnp.einsum("kqhgd,kshd->khgqs", q.astype(dtype), k.astype(dtype),
                       precision=precision,
                       preferred_element_type=jnp.float32) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        ctx = jnp.einsum("khgqs,kshd->kqhgd", p.astype(dtype),
                         v.astype(dtype), precision=precision,
                         preferred_element_type=jnp.float32)
        ctx = ctx.reshape(ctx.shape[0], S, -1, D)
        h = h + mm("kshe,hed->ksd", ctx, o_w)
        x = _rms(h, n2, eps)
        g, u = mm("ksd,df->ksf", x, g_w), mm("ksd,df->ksf", x, u_w)
        h = h + mm("ksf,fd->ksd", jax.nn.silu(g) * u, d_w)
        return h.astype(dtype).astype(jnp.float32), None

    lws = (blk["norm1"]["scale"], mix["q"], mix["k"], mix["v"], mix["o"],
           blk["norm2"]["scale"], ffn["w_gate"], ffn["w_up"], ffn["w_down"])
    h, _ = jax.lax.scan(layer, h, lws)
    return _rms(h, w["final_norm"]["scale"], eps)


def _f32(a, contract=None):
    return a.astype(jnp.float32)


def _fp8(a, contract):
    """Round a weight to float8_e4m3 with one absmax scale per output
    channel (the absmax over the ``contract`` axes) and return it
    dequantized."""
    a = a.astype(jnp.float32)
    scale = jnp.abs(a).max(axis=contract, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(hp, w, tokens, control: bool):
    """Every position's logits: float32 at ``HIGHEST``, or the control's."""
    if control:
        x = _forward(hp, w, tokens, dtype=jnp.bfloat16, precision=None,
                     prep=_fp8)
        return jnp.einsum("ksd,vd->ksv", x.astype(jnp.bfloat16),
                          _fp8(w["embed"], (1,)).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    x = _forward(hp, w, tokens, dtype=jnp.float32, precision=HI, prep=_f32)
    return jnp.einsum("ksd,vd->ksv", x, _f32(w["embed"]), precision=HI)


@functools.partial(jax.jit, static_argnums=(0,))
def _scores(hp, w, tokens, picks):
    logits = _logits(hp, w, tokens, False)
    best = logits.max(-1)
    picked = jnp.take_along_axis(logits, picks[..., None], -1)[..., 0]
    return best, picked


@functools.partial(jax.jit, static_argnums=(0,))
def _picks(hp, w, tokens):
    return jnp.argmax(_logits(hp, w, tokens, True), -1).astype(jnp.int32)


def _hp(conf: Dict) -> Tuple:
    return (float(conf["rope_theta"]), float(conf["rms_norm_eps"]))


def scores(conf: Dict, w: Dict, tokens: np.ndarray, picks: np.ndarray
           ) -> Tuple[np.ndarray, np.ndarray]:
    """(best, picked) reference logits, each (K, S) float32."""
    best, picked = _scores(_hp(conf), w, jnp.asarray(tokens, jnp.int32),
                           jnp.asarray(picks, jnp.int32))
    return np.asarray(best), np.asarray(picked)


def control_picks(conf: Dict, w: Dict, tokens: np.ndarray) -> np.ndarray:
    """The control's greedy token at every position, (K, S) int32."""
    return np.asarray(_picks(_hp(conf), w, jnp.asarray(tokens, jnp.int32)))


def logits(conf: Dict, w: Dict, tokens: np.ndarray,
           control: bool = False) -> np.ndarray:
    """Every position's logits (K, S, V) float32: the reference's, or with
    ``control`` the control's."""
    return np.asarray(_logits(_hp(conf), w, jnp.asarray(tokens, jnp.int32),
                              control))
