"""Distribution layer tests: sharding rules, pipeline parallelism vs
reference forward, Helix placement -> stage mapping, gradient compression.

Runs on CPU with a small forced device count (separate process would be
cleaner, but tests set XLA_FLAGS before the first jax import via conftest
ordering — see conftest.py)."""
import os

import numpy as np
import pytest

# must run before jax initializes a backend in this process
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.configs.base import BlockSpec, ModelConfig
from repro.core.placement import LayerRange, Placement
from repro.dist import (SERVE_RULES, TRAIN_RULES, PipelineConfig,
                        compressed_psum, make_pipeline_loss,
                        pipeline_param_specs, sharding_for,
                        stage_units_from_placement)
from repro.launch.mesh import make_mesh
from repro.models import forward, init, loss_fn
from repro.models.common import init_params, logical_axes


def need_devices(n):
    if jax.device_count() < n:
        pytest.skip(f"needs {n} host devices, have {jax.device_count()}")


def test_sharding_rules_basic():
    need_devices(8)
    mesh = make_mesh((4, 2), ("data", "model"))
    s = sharding_for((64, 16, 8), ("embed", "heads", "head_dim"),
                     TRAIN_RULES, mesh)
    assert s.spec == P("data", "model")
    # non-divisible dims fall back to replication (trailing Nones stripped)
    s = sharding_for((15, 30), ("heads", "embed"), TRAIN_RULES, mesh)
    assert len(s.spec) == 0 or s.spec[0] is None


def test_sharding_no_duplicate_axes():
    need_devices(8)
    mesh = make_mesh((4, 2), ("data", "model"))
    s = sharding_for((8, 64, 32), ("experts", "embed", "ff"),
                     TRAIN_RULES, mesh)
    flat = []
    for e in s.spec:
        if isinstance(e, tuple):
            flat.extend(e)
        elif e is not None:
            flat.append(e)
    assert len(flat) == len(set(flat))


def test_compressed_psum_accuracy():
    need_devices(8)
    import functools
    mesh = make_mesh((8,), ("pod",))
    x = jax.random.normal(jax.random.key(0), (8, 128)) * 0.01

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("pod"),
                       out_specs=P("pod"), check_vma=False)
    def f(x):
        return compressed_psum(x[0], "pod")[None]

    out = f(x)
    expected = x.sum(axis=0)
    rel = np.abs(np.asarray(out[0]) - np.asarray(expected)).max() / (
        np.abs(np.asarray(expected)).max() + 1e-9)
    assert rel < 0.02, rel


def test_stage_units_from_placement():
    cfg = get_smoke_config("smollm_360m")          # pattern len 1, repeats 4
    placement = Placement({"n0": LayerRange(0, 3), "n1": LayerRange(3, 4)}, 4)
    units = stage_units_from_placement(placement, cfg, ["n0", "n1"])
    assert sum(units) == cfg.repeats
    assert units == [3, 1]


def _tiny_cfg():
    return ModelConfig(
        name="pipe-test", family="dense", d_model=32, num_heads=4,
        num_kv_heads=2, d_ff=64, vocab_size=128,
        pattern=(BlockSpec(kind="attn", attn="full"),), repeats=4,
        norm="rmsnorm", tie_embeddings=True, param_dtype="float32",
        compute_dtype="float32")


def test_pipeline_loss_matches_reference():
    """Pipelined loss (2 stages x 4 data, unequal stages 3+1) must equal the
    single-program loss on identical params."""
    need_devices(8)
    cfg = _tiny_cfg()
    mesh = make_mesh((2, 4), ("stage", "data"))
    pipe = PipelineConfig(num_stages=2, stage_units=(3, 1),
                          num_microbatches=4)

    specs = pipeline_param_specs(cfg, pipe)
    params = init_params(specs, jax.random.key(0), "float32")

    # reference params: unroll stage-stacked blocks into the flat layer stack
    ref_params = init(cfg, jax.random.key(1))
    flat_layers = jax.tree.map(
        lambda x: jnp.concatenate(
            [x[0, :3], x[1, :1]], axis=0), params["super"])
    ref_params = dict(ref_params)
    ref_params["embed"] = params["embed"]
    ref_params["final_norm"] = params["final_norm"]
    ref_params["super"] = flat_layers

    tokens = jax.random.randint(jax.random.key(2), (16, 16), 0, 128)
    batch = {"tokens": tokens, "labels": tokens}

    ref_loss, _ = loss_fn(cfg, ref_params, batch, aux_weight=0.0)

    pl = make_pipeline_loss(cfg, pipe, mesh)
    pipe_loss = pl(params, batch)
    np.testing.assert_allclose(np.asarray(pipe_loss), np.asarray(ref_loss),
                               rtol=2e-4, atol=2e-4)


def test_pipeline_grad_runs():
    need_devices(8)
    cfg = _tiny_cfg()
    mesh = make_mesh((2, 4), ("stage", "data"))
    pipe = PipelineConfig(num_stages=2, stage_units=(2, 2),
                          num_microbatches=2)
    specs = pipeline_param_specs(cfg, pipe)
    params = init_params(specs, jax.random.key(0), "float32")
    tokens = jax.random.randint(jax.random.key(2), (16, 8), 0, 128)
    batch = {"tokens": tokens, "labels": tokens}
    pl = make_pipeline_loss(cfg, pipe, mesh)
    grads = jax.grad(lambda p: pl(p, batch))(params)
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
    # embedding gradient must be nonzero (flows through first+last stage)
    assert float(jnp.abs(grads["embed"]).sum()) > 0
