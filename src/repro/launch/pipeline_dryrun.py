import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Helix-integration dry-run: MILP placement -> unequal pipeline stages ->
shard_map pipeline loss lowered on the production mesh.

This is the paper's technique driving the TPU distribution layer end to
end: a heterogeneous cluster of TPU slices is planned with the max-flow
MILP; the resulting per-node layer ranges become the (unequal) stage sizes
of a ("stage","data") pipeline; the GPipe-style loss lowers + compiles at
512 chips.

  PYTHONPATH=src python -m repro.launch.pipeline_dryrun \
      [--arch starcoder2_7b] [--stages 16]
"""
import argparse
import json
import math
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import MILPOptions, ModelProfile, solve_placement
from repro.core.cluster import (COORDINATOR, DEVICE_PROFILES, ClusterSpec,
                                NodeSpec, _full_mesh_links)
from repro.dist.pipeline import (PipelineConfig, make_pipeline_loss,
                                 pipeline_param_specs,
                                 stage_units_from_placement)
from repro.launch.mesh import make_mesh
from repro.models.common import abstract_shapes
from repro.roofline.hlo import collective_totals


def make_tpu_stage_cluster(num_nodes: int, model: ModelProfile,
                           headroom: float = 1.25,
                           param_frac: float = 0.5) -> ClusterSpec:
    """Heterogeneous TPU-slice cluster: alternating 4-chip and 1-chip v5e
    slices (incremental fleet), one Helix node per slice; VRAM forces a
    genuine pipeline (no slice can hold the whole model).

    Slice HBM is derated so the whole fleet holds ``headroom`` x the model:
    4-chip slices get a 2:1 layer budget over 1-chip ones, which is what
    makes the MILP hand out *unequal* stage sizes."""
    import dataclasses as dc
    kinds = ["TPUv5e-4", "TPUv5e"]
    weights = [2 if i % 2 == 0 else 1 for i in range(num_nodes)]
    total_w = sum(weights)
    nodes, regions = {}, {COORDINATOR: "r0"}
    for i in range(num_nodes):
        name = f"slice-{i}"
        cap_layers = max(1, math.ceil(
            model.num_layers * headroom * weights[i] / total_w))
        cap_layers = min(cap_layers, model.num_layers - 1) \
            if num_nodes > 1 else model.num_layers
        dev = dc.replace(
            DEVICE_PROFILES[kinds[i % 2]],
            vram_bytes=cap_layers * model.layer_param_bytes / param_frac)
        nodes[name] = NodeSpec(name, dev, region="r0")
        regions[name] = "r0"
    links = _full_mesh_links(list(nodes), regions, 6.25e9, 1e-4,
                             6.25e9, 1e-4)
    return ClusterSpec(nodes=nodes, links=links)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chameleon_34b")
    ap.add_argument("--stages", type=int, default=16)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--out", default="results/dryrun/pipeline.json")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    profile = ModelProfile.from_dims(
        cfg.name, cfg.repeats, cfg.d_model, max(cfg.d_ff, 1),
        cfg.vocab_size, cfg.num_kv_heads, cfg.resolved_head_dim)
    cluster = make_tpu_stage_cluster(args.stages, profile)

    print(f"planning {args.stages}-slice heterogeneous chain for {cfg.name}")
    result = solve_placement(cluster, profile, MILPOptions(
        time_limit_s=15.0, lns_rounds=0, fgls_rounds=30))
    order = sorted(result.placement.assignment,
                   key=lambda n: result.placement.assignment[n].start)
    units = stage_units_from_placement(result.placement, cfg, order)
    print(f"stage units from MILP placement (4-chip slices get more): "
          f"{units}")
    # placements may use fewer nodes than requested stages; zero-unit
    # stages are identity pass-throughs in the pipeline
    units = units + [0] * (args.stages - len(units))

    if 512 % args.stages:
        raise SystemExit(f"--stages {args.stages} must divide the 512-chip "
                         f"mesh")
    data_dim = 512 // args.stages
    if args.batch % data_dim:
        raise SystemExit(f"--batch {args.batch} must be divisible by the "
                         f"data-axis size ({data_dim})")
    microbatches = math.gcd(args.microbatches, args.batch // data_dim)
    if microbatches != args.microbatches:
        print(f"clamping microbatches {args.microbatches} -> {microbatches} "
              f"(per-data-shard batch is {args.batch // data_dim})")
    pipe = PipelineConfig(num_stages=args.stages, stage_units=tuple(units),
                          num_microbatches=microbatches)
    mesh = make_mesh((args.stages, data_dim), ("stage", "data"))
    specs = pipeline_param_specs(cfg, pipe)
    params_abs = abstract_shapes(specs, cfg.param_dtype)
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
    }
    loss = make_pipeline_loss(cfg, pipe, mesh)
    t0 = time.time()
    with mesh:
        lowered = jax.jit(loss).lower(params_abs, batch_abs)
        compiled = lowered.compile()
    hlo = compiled.as_text()
    coll, count, _ = collective_totals(hlo)
    rec = {
        "arch": args.arch, "stages": args.stages,
        "stage_units": units,
        "mesh": {"stage": args.stages, "data": 512 // args.stages},
        "placement_throughput": result.actual_throughput,
        "compile_s": round(time.time() - t0, 1),
        "collective_bytes": coll, "collective_count": count,
        "status": "ok",
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"compiled in {rec['compile_s']}s; collectives/dev: "
          f"{ {k: f'{v/1e9:.2f}GB' for k, v in coll.items()} }")
    print("pipeline dry-run OK")


if __name__ == "__main__":
    main()
