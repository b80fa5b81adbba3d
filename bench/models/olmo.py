"""OLMo-style decoder for the system under test: the program's model
configuration and seeded weights, built from a configuration file's keys.

Architecture (``model_type: olmo``): pre-norm decoder, non-parametric
LayerNorm (no scale, no bias, eps 1e-5), rotary embeddings over the two
halves of each head, causal multi-head attention without biases, SwiGLU
feed-forward, tied input embedding and LM head.

The weights are random, made on the device from the seed in one jitted call,
in the dtype they are served in (``torch_dtype``) and in the layout the
program's ``ClusterRuntime`` takes: ``embed`` (V, d) and one stacked
``super.pos0`` block of ``num_hidden_layers`` layers.  ``references/olmo.py``
reads the same tree; nothing in it comes from the program.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

import flops

EMBED_STD = 0.02   # the published initializer_range; the LM head is tied


def weight_shapes(conf: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Flat name -> (shape, init std) of every weight.  Stds follow the
    fan-in of each projection, so a random model keeps unit-scale
    activations through every layer."""
    L, d, H, KH, D, F, V = flops.dims(conf)
    return {
        "embed": ((V, d), EMBED_STD),
        "q": ((L, d, H, D), 1 / math.sqrt(d)),
        "k": ((L, d, KH, D), 1 / math.sqrt(d)),
        "v": ((L, d, KH, D), 1 / math.sqrt(d)),
        "o": ((L, H, D, d), 1 / math.sqrt(H * D)),
        "w_gate": ((L, d, F), 1 / math.sqrt(d)),
        "w_up": ((L, d, F), 1 / math.sqrt(d)),
        "w_down": ((L, F, d), 1 / math.sqrt(F)),
    }


def tree(flat: Dict[str, jax.Array]) -> Dict:
    """The program's parameter layout of a flat weight dict."""
    return {
        "embed": flat["embed"],
        "final_norm": {},
        "super": {"pos0": {
            "norm1": {},
            "mix": {k: flat[k] for k in ("q", "k", "v", "o")},
            "norm2": {},
            "ffn": {k: flat[k] for k in ("w_gate", "w_up", "w_down")},
        }},
    }


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, including ones past 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_weights(conf: Dict, seed: int) -> Dict:
    """Random weights from ``seed``, on the default device, one call."""
    shapes = weight_shapes(conf)
    dtype = jnp.dtype(conf["torch_dtype"])

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, len(shapes))
        return {name: (jax.random.normal(k, shape, jnp.float32) * std
                       ).astype(dtype)
                for k, (name, (shape, std)) in zip(keys, shapes.items())}

    return tree(gen(seed_key(seed)))


def program_config(conf: Dict):
    """The program's ``ModelConfig`` for this configuration file."""
    from repro.configs.base import BlockSpec, ModelConfig
    if conf.get("layer_norm_eps", 1e-5) != 1e-5:
        raise ValueError("the program's non-parametric LayerNorm uses eps "
                         "1e-5")
    return ModelConfig(
        name=conf["name"], family="dense", d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        pattern=(BlockSpec(kind="attn", attn="full"),),
        repeats=conf["num_hidden_layers"], norm="nonparam_ln",
        mlp_kind="gated", tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]),
        param_dtype=conf["torch_dtype"], compute_dtype=conf["torch_dtype"])
