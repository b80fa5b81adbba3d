"""Distributed serving driver: sharded prefill + decode for an assigned arch.

CPU validation:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m --smoke \
      --mesh 2,4 --batch 4 --prompt 16 --new-tokens 8

Paged-KV engine (per-node worker; pool sized from node VRAM like the
simulator sizes KV capacity; Pallas kernel interpreted off-TPU):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m --smoke \
      --paged --vram-gb 16 --batch 4 --prompt 40 --new-tokens 8

Multi-node cluster serving (MILP placement -> IWRR pipelines -> stage
engines under the ClusterRuntime; one process plays every node):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m --smoke \
      --cluster A100,L4,T4 --stages 2 --batch 4 --prompt 10 --new-tokens 8

Multi-process cluster serving (one StageWorker process per node behind the
SocketTransport; add --connect HOST:PORT to use externally started
``python -m repro.launch.worker`` processes, e.g. on other hosts):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m --smoke \
      --cluster A100,L4 --stages 2 --transport socket --new-tokens 8

Online front door (OpenAI-compatible HTTP API + SSE streaming over the
cluster runtime; drive it with examples/openloop_client.py):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m --smoke \
      --cluster A100,L4 --stages 2 --serve 127.0.0.1:8000
"""
from __future__ import annotations

import argparse
import json
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core import (MILPOptions, ModelProfile, make_serving_cluster,
                        plan)
from repro.dist.sharding import SERVE_RULES, tree_shardings
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import abstract_params
from repro.models import decode_step, init, init_caches, prefill
from repro.models import model as M
from repro.serving import (ClusterRuntime, EngineConfig, PagedEngine, Request,
                           full_rectangle_pages, pages_for_vram)


def run_paged(cfg, args) -> None:
    """Single-node paged-KV serving: VRAM-derived pool, chunked prefill for
    prompts past the bucket, paged_attention decode."""
    ec = EngineConfig(max_batch=args.batch, max_len=args.max_len,
                      prompt_len=min(16, args.max_len))
    kv_dtype = args.kv_dtype if args.kv_dtype != "param" else None
    vram_pages = pages_for_vram(cfg, args.vram_gb * 1e9,
                                page_size=args.page_size, kv_dtype=kv_dtype)
    rect = full_rectangle_pages(cfg, max_batch=ec.max_batch,
                                max_len=ec.max_len, page_size=args.page_size)
    num_pages = min(vram_pages, rect) if args.vram_gb > 0 else rect
    print(f"pool: {num_pages} pages x {args.page_size} tokens, "
          f"kv_dtype={args.kv_dtype} "
          f"(VRAM budget {vram_pages}, full rectangle {rect})")
    params = init(cfg, jax.random.key(0))
    eng = PagedEngine(cfg, params, ec, num_pages=num_pages,
                      page_size=args.page_size, kv_dtype=kv_dtype)
    rng = np.random.RandomState(0)
    reqs = [Request(i, rng.randint(0, cfg.vocab_size, size=(args.prompt,)),
                    max_new_tokens=args.new_tokens)
            for i in range(args.batch)]
    t0 = time.time()
    for r in reqs:
        eng.submit(r)
    eng.run_until_done(max_iters=10000)
    dt = time.time() - t0
    toks = sum(len(r.output) for r in reqs)
    assert all(r.done for r in reqs)
    assert eng.pool.used == 0, "pages leaked"
    print(f"paged: {len(reqs)} reqs, {toks} tokens in {dt:.2f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s); pool clean")
    print("sampled ids:", [r.output for r in reqs[:2]])


def cluster_plan(cfg, devs, *, stages: int = 0, kv_dtype=None,
                 page_size: int = 16):
    """MILP placement of ``cfg`` over a full-mesh cluster of ``devs``
    (device-profile names), VRAM-derated to force >= ``stages`` stages."""
    profile = ModelProfile.from_dims(
        cfg.name, cfg.num_layers, cfg.d_model, max(cfg.d_ff, 1),
        cfg.vocab_size, cfg.num_kv_heads, cfg.resolved_head_dim,
        kv_dtype=kv_dtype, kv_page_size=page_size)
    cluster = make_serving_cluster(profile, devs=devs, force_stages=stages)
    return plan(cluster, profile, MILPOptions(time_limit_s=10.0,
                                              lns_rounds=0, fgls_rounds=20))


def run_cluster(cfg, args) -> None:
    """Multi-node serving: MILP placement over a (VRAM-derated) cluster, one
    stage engine per node, requests walking IWRR pipelines through the
    ClusterRuntime."""
    kv_dtype = args.kv_dtype if args.kv_dtype != "param" else None
    p = cluster_plan(cfg, args.cluster.split(","), stages=args.stages,
                     kv_dtype=kv_dtype, page_size=args.page_size)
    for node, rng_ in sorted(p.placement.assignment.items()):
        print(f"  {node}: layers [{rng_.start}, {rng_.end})")
    params = init(cfg, jax.random.key(0))
    ec = EngineConfig(max_batch=args.batch, max_len=args.max_len,
                      prompt_len=min(16, args.max_len))
    spec_kw = {}
    if args.draft:
        # coordinator-side draft model for speculative decoding: any arch
        # sharing the target's vocab works; quality only changes speed
        dcfg = (get_smoke_config(args.draft) if args.smoke
                else get_config(args.draft))
        print(f"draft: {dcfg.name} ({dcfg.num_layers}L d={dcfg.d_model}), "
              f"spec_tokens={args.spec_tokens}")
        spec_kw = dict(draft_cfg=dcfg,
                       draft_params=init(dcfg, jax.random.key(0)),
                       spec_tokens=args.spec_tokens)
    if args.transport == "socket":
        rt = ClusterRuntime.spawn_workers(
            cfg, params, p, ec, paged=args.paged or not args.dense,
            page_size=args.page_size, kv_dtype=kv_dtype,
            max_inflight=args.max_inflight,
            connect=args.connect or None, stall_timeout_s=120.0,
            direct_links=args.direct_links, **spec_kw)
    else:
        rt = ClusterRuntime(cfg, params, p, ec,
                            paged=args.paged or not args.dense,
                            page_size=args.page_size, kv_dtype=kv_dtype,
                            max_inflight=args.max_inflight,
                            # the front door needs wall-clock arrivals even
                            # over the in-process transport
                            realtime=True if args.serve else None,
                            **spec_kw)
    if args.serve:
        run_frontdoor(cfg, rt, args, plan_obj=p)
        return
    rng = np.random.RandomState(0)
    reqs = [Request(i, rng.randint(0, cfg.vocab_size, size=(args.prompt,)),
                    max_new_tokens=args.new_tokens)
            for i in range(args.batch)]
    t0 = time.time()
    for r in reqs:
        rt.submit(r)
    rt.run_until_done()
    dt = time.time() - t0
    toks = sum(len(r.output) for r in reqs)
    assert all(r.done for r in reqs)
    for r in reqs:
        print(f"req{r.request_id} -> "
              + " -> ".join(s.node for s in rt.served[r.request_id].stages))
    print(f"cluster: {len(reqs)} reqs, {toks} tokens in {dt:.2f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s)")
    if args.draft:
        print(f"  {rt._spec_note()}")
    print("sampled ids:", [r.output for r in reqs[:2]])
    rt.shutdown()                      # reap worker processes (socket runs)


def run_frontdoor(cfg, rt, args, plan_obj=None) -> None:
    """Serve the runtime behind the OpenAI-compatible HTTP front door
    until SIGINT/SIGTERM, then drain gracefully and print the
    server-side TTFT/TPOT/SLO summary."""
    import dataclasses as _dc

    from repro.serving.frontend import Frontend

    host, _, port = args.serve.rpartition(":")
    fe = Frontend(rt, max_pending=args.max_pending,
                  slo_ttft_s=args.slo_ttft_ms / 1e3
                  if args.slo_ttft_ms > 0 else None,
                  slo_tpot_s=args.slo_tpot_ms / 1e3
                  if args.slo_tpot_ms > 0 else None)
    scaler = None
    if getattr(args, "autoscale", False) and plan_obj is not None:
        from repro.core.cluster import COORDINATOR
        from repro.serving.autoscaler import Autoscaler

        catalog = None
        if args.autoscale_node_rate > 0:
            # cap every device's modeled token rate so the mix planner
            # sees a small, known per-node capacity — smoke runs on tiny
            # CPU models would otherwise look infinitely fast on paper and
            # never scale
            catalog = {n.device.name:
                       _dc.replace(n.device,
                                   max_tokens_per_s=args.autoscale_node_rate)
                       for name, n in rt.cluster.nodes.items()
                       if name != COORDINATOR}
        scaler = Autoscaler(rt, plan_obj, frontend=fe, catalog=catalog,
                            patience=args.autoscale_patience,
                            window_s=args.autoscale_window_s)
        scaler.start(args.autoscale_interval_s)
        print(f"autoscaler: interval={args.autoscale_interval_s}s "
              f"patience={args.autoscale_patience} "
              f"window={args.autoscale_window_s}s "
              f"catalog={sorted(scaler.catalog)}", flush=True)
    bhost, bport = fe.serve(host or "127.0.0.1", int(port))
    print(f"serving {cfg.name} on http://{bhost}:{bport} "
          f"(POST /v1/completions, GET /healthz; SIGINT drains)",
          flush=True)
    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    while not stop.is_set():
        stop.wait(0.2)
    print("draining ...", flush=True)
    if scaler is not None:
        scaler.stop()
    fe.shutdown(drain=True)
    if scaler is not None:
        print("autoscale events: " + json.dumps(
            [_dc.asdict(e) for e in scaler.events], default=float),
            flush=True)
    print("served summary: "
          + json.dumps(fe.summary(), default=float), flush=True)
    rt.shutdown()
    if fe.loop_error is not None:
        raise SystemExit(f"runtime loop died: {fe.loop_error!r}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged-KV engine (single node)")
    ap.add_argument("--dense", action="store_true",
                    help="with --cluster: dense stage engines, not paged")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kv-dtype", choices=["param", "int8"], default="param",
                    help="KV page storage: 'param' keeps the model dtype, "
                         "'int8' quantizes pages (per-page per-head absmax "
                         "scales) for ~2x pool capacity at fixed VRAM")
    ap.add_argument("--vram-gb", type=float, default=16.0,
                    help="node VRAM for pool sizing (0 = full rectangle)")
    ap.add_argument("--cluster", default="",
                    help="comma-separated device types: serve a multi-node "
                         "cluster through the ClusterRuntime")
    ap.add_argument("--stages", type=int, default=0,
                    help="with --cluster: derate VRAM to force >= N stages")
    ap.add_argument("--max-inflight", type=int, default=1,
                    help="with --cluster: per-request in-flight decode "
                         "window (pipelined decode at >= 2)")
    ap.add_argument("--transport", choices=["inproc", "socket"],
                    default="inproc",
                    help="with --cluster: socket runs one StageWorker "
                         "process per node behind the SocketTransport")
    ap.add_argument("--connect", default="",
                    help="with --transport socket: listen on HOST:PORT and "
                         "wait for externally started workers (python -m "
                         "repro.launch.worker --connect HOST:PORT) instead "
                         "of spawning local subprocesses")
    ap.add_argument("--draft", default="",
                    help="with --cluster: arch name of a coordinator-side "
                         "draft model for greedy speculative decoding "
                         "(must share the target's vocab)")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="with --draft: draft tokens proposed per verify "
                         "round-trip (gamma)")
    ap.add_argument("--serve", default="",
                    help="with --cluster: HOST:PORT for the OpenAI-"
                         "compatible HTTP front door (SSE streaming; "
                         "port 0 picks an ephemeral port, printed on "
                         "startup) instead of a one-shot batch")
    ap.add_argument("--max-pending", type=int, default=64,
                    help="with --serve: 429 past this many accepted-but-"
                         "unfinished requests")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="with --serve: TTFT SLO for the served summary "
                         "(0 = none)")
    ap.add_argument("--slo-tpot-ms", type=float, default=0.0,
                    help="with --serve: mean-TPOT SLO for the served "
                         "summary (0 = none)")
    ap.add_argument("--direct-links", action="store_true",
                    help="with --transport socket: stage workers forward "
                         "activation frames to the next stage's worker over "
                         "peer TCP links; only tokens return to the "
                         "coordinator")
    ap.add_argument("--autoscale", action="store_true",
                    help="with --serve: run the live autoscaler (mix-solve "
                         "measured traffic, grow/shrink/reweight through "
                         "apply_plan)")
    ap.add_argument("--autoscale-interval-s", type=float, default=2.0,
                    help="with --autoscale: sampling interval")
    ap.add_argument("--autoscale-patience", type=int, default=2,
                    help="with --autoscale: consecutive overloaded samples "
                         "before scaling")
    ap.add_argument("--autoscale-window-s", type=float, default=15.0,
                    help="with --autoscale: arrival-rate trailing window")
    ap.add_argument("--autoscale-node-rate", type=float, default=0.0,
                    help="with --autoscale: cap each device type's modeled "
                         "tokens/s at this value (smoke runs on tiny CPU "
                         "models look infinitely fast to the paper-profile "
                         "table otherwise; 0 = use real device profiles)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.cluster:
        run_cluster(cfg, args)
        return
    if args.paged:
        run_paged(cfg, args)
        return
    dims = tuple(int(x) for x in args.mesh.split(",")) if args.mesh \
        else (jax.device_count(), 1)
    axes = ("data", "model")[:len(dims)] if len(dims) == 2 \
        else ("pod", "data", "model")
    mesh = make_mesh(dims, axes)
    print(f"mesh {dict(zip(axes, dims))}; serving {cfg.name}")

    params_abs, params_axes = abstract_params(cfg)
    params_sh = tree_shardings(params_abs, params_axes, SERVE_RULES, mesh)

    with mesh:
        params = jax.jit(lambda k: init(cfg, k),
                         out_shardings=params_sh)(jax.random.key(0))
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                         size=(args.batch, args.prompt)),
                             jnp.int32)
        kw = {}
        if cfg.is_encoder_decoder:
            kw["encoder_frames"] = jnp.asarray(
                rng.randn(args.batch, 16, cfg.d_model), jnp.bfloat16)

        t0 = time.time()
        logits, caches = jax.jit(
            lambda p, t: prefill(cfg, p, t, max_len=args.max_len, **kw)
        )(params, tokens)
        print(f"prefill: {time.time() - t0:.2f}s")
        dec = jax.jit(lambda p, t, c, pos: decode_step(cfg, p, t, c, pos))
        out = [np.asarray(jnp.argmax(logits, -1))]
        pos = jnp.full((args.batch,), args.prompt, jnp.int32)
        t0 = time.time()
        for i in range(args.new_tokens - 1):
            logits, caches = dec(params, jnp.asarray(out[-1]), caches,
                                 pos + i)
            out.append(np.asarray(jnp.argmax(logits, -1)))
        dt = time.time() - t0
        print(f"decode: {args.new_tokens - 1} steps in {dt:.2f}s "
              f"({(args.new_tokens - 1) * args.batch / max(dt, 1e-9):.1f} tok/s)")
        print("sampled ids:", np.stack(out, 1)[:2].tolist())


if __name__ == "__main__":
    main()
