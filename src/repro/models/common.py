"""Shared model utilities: param specs, norms, RoPE, initializers.

Params are plain nested dicts of jnp arrays.  The single source of truth for
shapes/sharding is ``ParamSpec`` — ``abstract_params`` builds a ParamSpec
tree, ``init_params`` materializes it, and the distribution layer reads the
``axes`` (logical axis names) off the same tree to derive PartitionSpecs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Logical axis vocabulary (mapped to mesh axes in repro.dist.sharding):
#   batch, seq, embed, heads, kv_heads, head_dim, ff, experts, vocab,
#   layers (scan axis), state, conv, lora, null (replicated)


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"            # normal | zeros | ones | alog (mamba A)
    scale: Optional[float] = None   # stddev override for "normal"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def init_param(spec: ParamSpec, key: jax.Array, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "alog":
        # mamba A: -log-spaced state matrix, stacked per channel
        n = spec.shape[-1]
        a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32),
                     spec.shape[:-1] + (1,))
        return jnp.log(a).astype(dtype)
    std = spec.scale if spec.scale is not None \
        else 1.0 / math.sqrt(fan_in(spec))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)


def fan_in(spec: ParamSpec) -> int:
    """Inputs summed into each output of the weight: the leading axis, or
    every axis but the last for projections back to ``embed`` (an
    attention ``o`` sums over heads and head_dim).  Stacking axes
    (``layers``, ``experts``) are not inputs — counting them made every
    stacked weight ~5x too large at d_model 960, so a random-weight model
    amplified bf16 rounding into 30-50% logit error over 32 layers."""
    dims = [n for n, a in zip(spec.shape, spec.axes)
            if a not in ("layers", "experts")]
    if len(dims) < 2:
        return max(dims[-1] if dims else 1, 1)
    if spec.axes[-1] == "embed":
        return math.prod(dims[:-1])
    return dims[0]


def init_params(spec_tree, key: jax.Array, dtype_name: str = "bfloat16"):
    """Materialize a ParamSpec tree into arrays (deterministic per-leaf keys)."""
    dtype = _dtype(dtype_name)
    leaves, treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    vals = [init_param(s, k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_shapes(spec_tree, dtype_name: str = "bfloat16"):
    """ShapeDtypeStruct tree for dry-runs (no allocation)."""
    dtype = _dtype(dtype_name)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def logical_axes(spec_tree):
    return jax.tree.map(lambda s: s.axes, spec_tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Norms (computed in fp32, cast back)
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: Optional[jax.Array], eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * (1.0 + scale.astype(jnp.float32))
    return y.astype(x.dtype)


def layernorm(x: jax.Array, scale: Optional[jax.Array],
              bias: Optional[jax.Array], eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def norm_spec(cfg) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": ParamSpec((d,), ("embed",), init="zeros")}
    if cfg.norm == "layernorm":
        return {"scale": ParamSpec((d,), ("embed",), init="ones"),
                "bias": ParamSpec((d,), ("embed",), init="zeros")}
    if cfg.norm == "nonparam_ln":
        return {}
    raise ValueError(cfg.norm)


def apply_norm(cfg, params: Dict, x: jax.Array) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, params["scale"], cfg.rms_norm_eps)
    if cfg.norm == "layernorm":
        return layernorm(x, params["scale"], params["bias"])
    if cfg.norm == "nonparam_ln":
        return layernorm(x, None, None)
    raise ValueError(cfg.norm)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_angles(positions: jax.Array, dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions: (..., seq) int32 -> cos/sin of shape (..., seq, dim//2)."""
    half = dim // 2
    freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., seq, heads, dim); cos/sin: (..., seq, dim//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s],
                           axis=-1).astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)
