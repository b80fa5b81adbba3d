"""Llama-style decoder for the system under test: the program's model
configuration and seeded weights, built from a configuration file's keys.

Architecture (``model_type: llama``): pre-norm decoder, RMSNorm with a
learned gain (eps ``rms_norm_eps``) before attention, before the MLP and
after the last layer, rotary embeddings over the two halves of each head,
causal grouped-query attention (``num_attention_heads`` query heads over
``num_key_value_heads`` kv heads, query head ``i`` reading kv head
``i // G``) without biases, SwiGLU feed-forward, tied input embedding and
LM head.

The weights are random, made on the device from the seed in one jitted call,
in the dtype they are served in (``torch_dtype``) and in the layout the
program's ``ClusterRuntime`` takes: ``embed`` (V, d), one stacked
``super.pos0`` block of ``num_hidden_layers`` layers, and ``final_norm``.
An RMSNorm's ``scale`` holds its gain less one (the program multiplies by
``1 + scale``); the gains are drawn away from 1, so a norm whose gain were
dropped would show in ``correct``.  ``references/llama.py`` reads the same
tree; nothing in it comes from the program.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

import flops

EMBED_STD = 0.02   # the published initializer_range; the LM head is tied
GAIN_STD = 0.25    # RMSNorm gains are 1 + N(0, GAIN_STD)


def weight_shapes(conf: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Flat name -> (shape, init std) of every weight.  Projection stds
    follow the fan-in, so a random model keeps unit-scale activations
    through every layer; ``*_norm`` entries are gains less one."""
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
        "attn_norm": ((L, d), GAIN_STD),
        "mlp_norm": ((L, d), GAIN_STD),
        "final_norm": ((d,), GAIN_STD),
    }


def tree(flat: Dict[str, jax.Array]) -> Dict:
    """The program's parameter layout of a flat weight dict."""
    return {
        "embed": flat["embed"],
        "final_norm": {"scale": flat["final_norm"]},
        "super": {"pos0": {
            "norm1": {"scale": flat["attn_norm"]},
            "mix": {k: flat[k] for k in ("q", "k", "v", "o")},
            "norm2": {"scale": flat["mlp_norm"]},
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
    if conf["hidden_act"] != "silu" or conf.get("rope_scaling") \
            or conf.get("attention_bias") or conf.get("mlp_bias"):
        raise ValueError("the program's Llama block is SwiGLU with plain "
                         "RoPE and no biases")
    return ModelConfig(
        name=conf["name"], family="dense", d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        pattern=(BlockSpec(kind="attn", attn="full"),),
        repeats=conf["num_hidden_layers"], norm="rmsnorm",
        rms_norm_eps=float(conf["rms_norm_eps"]), mlp_kind="gated",
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]),
        param_dtype=conf["torch_dtype"], compute_dtype=conf["torch_dtype"])
