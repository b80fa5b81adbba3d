"""Main-path programs compiled for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed even where no chip is
attached, compiles the decode kernel at SmolLM-360M's and OLMo-1B's
published widths, and the stage engine's steps at both, for one chip of a
described ``v5e:2x2`` topology.  This catches what interpret mode cannot —
block shapes the TPU's tiling refuses, programs that do not fit the chip,
a pool the compiled program copies or relayouts whole — at no chip time.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.placement import LayerRange
from repro.kernels.paged_attention import paged_attention
from repro.models import init
from repro.models.stage import (stage_decode_paged, stage_params,
                                stage_prefill_chunk_paged)
from repro.serving.kv_pool import (cfg_layout, full_rectangle_pages,
                                   page_bytes)
from repro.serving.stage_engine import paged_decode_step

CFG = get_config("smollm_360m")
PAGE = 16
MAX_BATCH = 8            # the served engine adds one scratch row
MAX_LEN = 2048
NP = MAX_LEN // PAGE
# the full rectangle of a one-node engine: every slot holds max_len tokens
NUM_PAGES = 1 + NP * CFG.num_layers * MAX_BATCH
HBM_BYTES = 16e9         # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _stage_params_shapes(sharding, layers, cfg=CFG):
    full = jax.eval_shape(lambda: init(cfg, jax.random.key(0)))
    sliced = jax.eval_shape(lambda p: stage_params(cfg, p, layers), full)
    return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), sliced)


def _pool_shapes(sharding, quantized, cfg=CFG, num_pages=NUM_PAGES):
    dt = jnp.int8 if quantized else jnp.bfloat16
    pages = _spec(sharding, cfg_layout(cfg, PAGE).shape(num_pages), dt)
    scales = _spec(sharding, (num_pages, cfg.num_kv_heads), jnp.float32) \
        if quantized else None
    return pages, scales


# (config, decode rows, pool pages): SmolLM-360M's one-node engine above;
# OLMo-1B as the benchmark serves it, 32 slots and the scratch row over a
# full-rectangle pool of 65,537 pages
KERNEL_SHAPES = {"smollm_360m": (CFG, MAX_BATCH, NUM_PAGES),
                 "olmo_1b": (get_config("olmo_1b"), 33, 65537)}


@pytest.mark.parametrize("arch,quantized", [
    ("smollm_360m", False), ("smollm_360m", True),
    ("olmo_1b", False), ("olmo_1b", True)],
    ids=["bf16", "int8", "olmo_1b-bf16", "olmo_1b-int8"])
def test_paged_attention_compiles(one_chip, arch, quantized):
    cfg, B, num_pages = KERNEL_SHAPES[arch]
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pages, scales = _pool_shapes(one_chip, quantized, cfg, num_pages)
    q = _spec(one_chip, (B, H, D), jnp.bfloat16)
    tables = _spec(one_chip, (B, NP), jnp.int32)
    lengths = _spec(one_chip, (B,), jnp.int32)
    compiled = jax.jit(
        lambda q, k, v, t, n, ks, vs: paged_attention(
            q, k, v, t, n, page=PAGE, k_scales=ks, v_scales=vs)
    ).lower(q, pages, pages, tables, lengths, scales, scales).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_stage_decode_step_compiles(one_chip, quantized):
    """The one-node engine's decode step: all 32 layers, the Pallas kernel
    compiled (not interpreted), pool donated as the engine donates it."""
    layers = LayerRange(0, CFG.num_layers)
    sp = _stage_params_shapes(one_chip, layers)
    pages, scales = _pool_shapes(one_chip, quantized)
    B = MAX_BATCH + 1
    row = lambda dt: _spec(one_chip, (B,), dt)
    h_in = _spec(one_chip, (B, 1, CFG.d_model), jnp.float32)
    tables = _spec(one_chip, (CFG.num_layers, B, NP), jnp.int32)

    def step(sp, tok, h, entry, pos, kp, vp, ks, vs, tb):
        caches = [{}] * CFG.num_layers
        return stage_decode_paged(CFG, sp, layers, tok, h, entry, caches,
                                  pos, kp, vp, tb, k_scales=ks, v_scales=vs,
                                  interpret=False)

    compiled = jax.jit(step, donate_argnums=(5, 6, 7, 8)).lower(
        sp, row(jnp.int32), h_in, row(jnp.int32), row(jnp.int32), pages,
        pages, scales, scales, tables).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_stage_prefill_chunk_compiles(one_chip):
    """A 256-token chunked-prefill step over all 32 layers, at the gather
    cap of a 512-token prompt, fits one chip."""
    layers = LayerRange(0, CFG.num_layers)
    sp = _stage_params_shapes(one_chip, layers)
    pages, _ = _pool_shapes(one_chip, False)
    C = 256
    compiled = jax.jit(
        lambda sp, x, start, kp, vp, tb: stage_prefill_chunk_paged(
            CFG, sp, layers, x, 0, start, kp, vp, tb, active_blocks=32),
        donate_argnums=(3, 4),
    ).lower(sp, _spec(one_chip, (1, C), jnp.int32),
            _spec(one_chip, (1,), jnp.int32), pages, pages,
            _spec(one_chip, (CFG.num_layers, 1, NP), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


# The served shapes: SmolLM-360M at 128 slots, OLMo-1B at 32, each with the
# scratch row and its full-rectangle pool (what ``bench/configs`` serve)
SERVED = {"smollm_360m": 128, "olmo_1b": 32}


def _relayouts(hlo: str, pool_shape) -> list:
    """Copies and transposes in ``hlo`` whose result is pool-sized."""
    n = math.prod(pool_shape)
    found = []
    for line in hlo.splitlines():
        op = re.search(r"\s(copy|copy-start|copy-done|transpose)\(", line)
        if not op:
            continue
        out = line[:op.start()]
        if any(math.prod(int(x) for x in dims.split(",")) >= n
               for dims in re.findall(r"\[([\d,]+)\]", out)):
            found.append(line.strip()[:160])
    return found


def _served(arch):
    cfg = get_config(arch)
    max_batch = SERVED[arch]
    num_pages = full_rectangle_pages(cfg, max_batch=max_batch,
                                     max_len=MAX_LEN, page_size=PAGE)
    return cfg, max_batch + 1, num_pages


@functools.lru_cache(maxsize=None)
def _served_decode(one_chip, arch):
    """The paged engine's decode program over every layer, at the
    benchmark's slots and pool, lowered and compiled for the chip (the
    engine's own step, pool donated as the engine donates it)."""
    cfg, B, num_pages = _served(arch)
    layers = LayerRange(0, cfg.num_layers)
    sp = _stage_params_shapes(one_chip, layers, cfg)
    pages, _ = _pool_shapes(one_chip, False, cfg, num_pages)
    row = lambda dt: _spec(one_chip, (B,), dt)
    h_in = _spec(one_chip, (B, 1, cfg.d_model), jnp.float32)
    tables = _spec(one_chip, (cfg.num_layers, B, NP), jnp.int32)
    lowered = jax.jit(paged_decode_step(cfg, layers, interpret=False),
                      donate_argnums=(7, 8)).lower(
        sp, [{}] * cfg.num_layers, row(jnp.int32), h_in, row(jnp.int32),
        row(jnp.int32), row(jnp.int32), pages, pages, None, None, tables)
    return B, pages, lowered, lowered.compile()


@pytest.mark.parametrize("arch", sorted(SERVED))
def test_served_decode_step_has_no_pool_relayout(one_chip, arch):
    """The served decode step over every layer, at the benchmark's slots
    and pool, compiles, fits the chip and copies no pool-sized array."""
    _, pages, _, compiled = _served_decode(one_chip, arch)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _relayouts(hlo, pages.shape) == []
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("arch", sorted(SERVED))
def test_served_decode_step_returns_greedy_ids(one_chip, arch):
    """The final stage's decode program returns each row's greedy token as
    an ``s32[B]`` output of the same program, beside the logits: one
    compiled module, whose entry returns the ids."""
    B, _, lowered, compiled = _served_decode(one_chip, arch)
    h, logits, ids = lowered.out_info[:3]
    assert (ids.shape, ids.dtype) == ((B,), jnp.int32)
    assert logits.shape[0] == B
    hlo = compiled.as_text()
    assert hlo.count("HloModule ") == 1
    root = re.search(r"ENTRY .*?ROOT [^\n]*", hlo, re.S).group(0)
    assert f"s32[{B}]" in root.splitlines()[-1]


@pytest.mark.parametrize("arch", sorted(SERVED))
def test_served_prefill_chunk_has_no_pool_relayout(one_chip, arch):
    """A 256-token chunk at the end of a 2048-token prompt (the gather over
    the whole table), at the benchmark's pool: compiles, fits, and copies
    no pool-sized array."""
    cfg, _, num_pages = _served(arch)
    layers = LayerRange(0, cfg.num_layers)
    sp = _stage_params_shapes(one_chip, layers, cfg)
    pages, _ = _pool_shapes(one_chip, False, cfg, num_pages)
    compiled = jax.jit(
        lambda sp, x, start, kp, vp, tb: stage_prefill_chunk_paged(
            cfg, sp, layers, x, 0, start, kp, vp, tb, active_blocks=NP),
        donate_argnums=(3, 4),
    ).lower(sp, _spec(one_chip, (1, 256), jnp.int32),
            _spec(one_chip, (1,), jnp.int32), pages, pages,
            _spec(one_chip, (cfg.num_layers, 1, NP), jnp.int32)).compile()
    assert _relayouts(compiled.as_text(), pages.shape) == []
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("arch", sorted(SERVED))
def test_stored_pool_bytes(one_chip, arch):
    """What the chip holds for the served bf16 pool is within 2% of its
    logical bytes, and is what the sizing counts."""
    cfg, _, num_pages = _served(arch)
    pages, _ = _pool_shapes(one_chip, False, cfg, num_pages)
    held = jax.jit(lambda k, v: (k, v), donate_argnums=(0, 1)).lower(
        pages, pages).compile().memory_analysis().argument_size_in_bytes
    logical = 2 * num_pages * PAGE * cfg.num_kv_heads \
        * cfg.resolved_head_dim * 2
    assert abs(held / logical - 1) < 0.02
    assert abs(held / (num_pages * page_bytes(cfg, PAGE)) - 1) < 1e-3


def test_relayout_check_sees_a_token_view_pool(one_chip):
    """The check above finds the relayout XLA adds when SmolLM-360M's pool
    is kept in the token view ``(P, page, KH, D)`` and read as rows."""
    cfg = get_config("smollm_360m")
    lay = cfg_layout(cfg, PAGE)
    tok = _spec(one_chip, (4097, PAGE, lay.kv_heads, lay.head_dim),
                jnp.bfloat16)
    hlo = jax.jit(lambda k, t, n, q: paged_attention(
        q, lay.to_rows(k), lay.to_rows(k), t, n, page=PAGE)).lower(
        tok, _spec(one_chip, (4, NP), jnp.int32),
        _spec(one_chip, (4,), jnp.int32),
        _spec(one_chip, (4, cfg.num_heads, lay.head_dim), jnp.bfloat16)
    ).compile().as_text()
    assert _relayouts(hlo, tok.shape)
