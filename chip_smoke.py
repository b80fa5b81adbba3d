#!/usr/bin/env python3
"""Bring-up check: the served path on TPU at SmolLM-360M's published widths.

SmolLM-360M (32 layers, d_model 960, 15 heads over 5 KV heads, d_ff 2560,
vocab 49152) with seeded random weights runs through the entry points a
user calls: ``cluster_plan`` -> ``ClusterRuntime`` -> paged stage engines
-> the Pallas ``paged_attention`` decode kernel, compiled, not interpreted.

  python chip_smoke.py               one chip: streamed HTTP requests, then
                                     bf16- and int8-KV logits against a
                                     float32 reference
  python chip_smoke.py --four-chips  a 4-stage placement, one stage engine
                                     per chip, against one engine on chip 0

The script refuses to run, and prints no result, unless JAX's first device
is a TPU.  Any failed check exits non-zero.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Sequence

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import cluster_plan  # noqa: E402
from repro.models import forward, init  # noqa: E402
from repro.serving import ClusterRuntime, EngineConfig, Request  # noqa: E402
from repro.serving.frontend import Frontend  # noqa: E402
from repro.serving.stage_engine import DecodeItem  # noqa: E402

SEED = 0
ARCH = "smollm_360m"
EC = EngineConfig(max_batch=8, max_len=2048, prompt_len=256)
# 64 .. 1536 tokens: one short request and chunked prefills of 2 to 6
# chunks; multiples of the 256-token chunk keep the compiled chunk shapes
# to a handful
PROMPT_LENS = (64, 512, 1024, 1536)
# --four-chips: every stage engine compiles its own programs and each
# chip-second costs four, so two prompts (three chunk shapes) suffice
FOUR_CHIP_PROMPT_LENS = (64, 512)
MAX_TOKENS = 16
CHECK_PROMPT_LEN = 512      # a served prompt: its first token is re-checked
DECODE_STEPS = 8

# Tolerances are on the largest per-position relative L2 error of a logit
# row, ||paged - ref|| / ||ref||, against the float32 forward pass.
#
# bf16: params and activations are bf16 (8 significant bits, a relative
# rounding of 2^-9 per op), KV pages bf16; over 32 residual layers the
# error grows to about a percent.  0.03 leaves room for that and still
# fails a path computed in a lower precision: the same forward with its
# weights rounded to float8_e4m3 (4 significant bits) — checked below as
# a control on every run.
BF16_TOL = 0.03
# int8 KV: the pages hold K and V at one absmax scale per page and KV head,
# a relative rounding of up to 2^-8 of the page's largest value, on top of
# the bf16 error above.
INT8_TOL = 0.05


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and programs
    compiled, from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            self.programs += name.endswith("backend_compile_duration")

    def mark(self):
        return self.seconds, self.programs


def phase(clock: CompileClock, name: str, t0: float, c0, **fields) -> None:
    secs, progs = clock.mark()
    parts = [f"wall_s={time.monotonic() - t0:.1f}",
             f"compile_s={secs - c0[0]:.1f}", f"programs={progs - c0[1]}"]
    parts += [f"{k}={v}" for k, v in fields.items()]
    print(f"[{name}] " + " ".join(parts), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def prompts_for(cfg, lens: Sequence[int], seed: int) -> List[np.ndarray]:
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in lens]


def assert_compiled_kernels(rt: ClusterRuntime) -> None:
    for node, eng in rt.engines.items():
        check(getattr(eng, "interpret", None) is False,
              f"engine {node} runs the Pallas kernel in interpret mode")


# ---------------------------------------------------------------------------
# streamed HTTP serving


def stream_completion(port: int, prompt: np.ndarray, max_tokens: int,
                      timeout_s: float) -> List[int]:
    """One streamed ``/v1/completions`` request; returns the token ids,
    checking the SSE chunks arrive in output order and end properly."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        conn.request("POST", "/v1/completions", headers={
            "Content-Type": "application/json"}, body=json.dumps({
                "prompt": [int(t) for t in prompt],
                "max_tokens": max_tokens, "stream": True}))
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {resp.read()[:300]!r}")
        toks: List[int] = []
        finish = None
        done = False
        for raw in resp:
            line = raw.decode("utf-8").strip()
            if not line.startswith("data: "):
                continue
            data = line[len("data: "):]
            if data == "[DONE]":
                done = True
                break
            choice = json.loads(data)["choices"][0]
            if choice["finish_reason"] is not None:
                finish = choice["finish_reason"]
                continue
            if finish is not None or choice["output_index"] != len(toks):
                raise RuntimeError(f"chunk {choice['output_index']} out of "
                                   f"order after {len(toks)} tokens")
            toks.append(int(choice["token_id"]))
        if not done or finish != "length" or len(toks) != max_tokens:
            raise RuntimeError(f"stream ended early: {len(toks)} tokens, "
                               f"finish={finish!r}, [DONE]={done}")
        return toks
    finally:
        conn.close()


def serve_over_http(rt: ClusterRuntime, prompts: Sequence[np.ndarray],
                    max_tokens: int, timeout_s: float) -> List[List[int]]:
    """Serve ``prompts`` as concurrent streamed requests through the front
    door; every request must finish and every pool drain."""
    fe = Frontend(rt, request_timeout_s=timeout_s)
    _, port = fe.serve("127.0.0.1", 0)
    outs: Dict[int, List[int]] = {}
    errors: List[str] = []

    def client(i: int) -> None:
        try:
            outs[i] = stream_completion(port, prompts[i], max_tokens,
                                        timeout_s)
        except (OSError, http.client.HTTPException, RuntimeError,
                ValueError, KeyError) as e:
            errors.append(f"request {i} ({len(prompts[i])} tokens): {e!r}")

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(len(prompts))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout_s)
    finally:
        fe.shutdown(drain=True, timeout_s=60.0)
    check(fe.loop_error is None, f"runtime loop died: {fe.loop_error!r}")
    check(not errors and len(outs) == len(prompts),
          "; ".join(errors) or "a client thread did not finish")
    pools = rt.pool_pages_used()
    check(all(v == 0 for v in pools.values()), f"pages leaked: {pools}")
    return [outs[i] for i in range(len(prompts))]


def serve_offline(rt: ClusterRuntime, prompts: Sequence[np.ndarray],
                  max_tokens: int) -> List[List[int]]:
    reqs = [Request(i, p, max_new_tokens=max_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        rt.submit(r)
    rt.run_until_done()
    check(all(r.done and len(r.output) == max_tokens for r in reqs),
          "an offline request did not finish")
    pools = rt.pool_pages_used()
    check(all(v == 0 for v in pools.values()), f"pages leaked: {pools}")
    return [list(r.output) for r in reqs]


# ---------------------------------------------------------------------------
# logits against the float32 reference


def stage_order(rt: ClusterRuntime) -> list:
    return sorted(rt.engines.values(), key=lambda e: e.layers.start)


def paged_logits(engines: Sequence, prompt: np.ndarray, cont: np.ndarray,
                 chunk: int) -> np.ndarray:
    """Final-stage logits of the paged path: chunked prefill of ``prompt``
    through ``engines`` (in stage order), then one decode step per token of
    ``cont`` through the cache.  Row 0 is the prefill's last-position row;
    row i + 1 is the row at position len(prompt) + i."""
    S = len(prompt)
    slots = []
    try:
        for e in engines:
            s = e.alloc_slot(-1)
            check(s is not None, "no free slot for the logit check")
            slots.append(s)
            check(e.ensure(s, S + len(cont)), "pool too small for the check")
        x = None
        for off in range(0, S, chunk):
            x = prompt[off:off + chunk]
            for e, s in zip(engines, slots):
                x = e.prefill_chunk(s, x, e.layers.start, off)
        rows = [np.asarray(x, np.float32)]
        for i, tok in enumerate(cont):
            h = None
            for e, s in zip(engines, slots):
                out = e.decode_stage([DecodeItem(
                    slot=s, pos=S + i, entry=e.layers.start, token=int(tok),
                    h=h)])[0]
                h = out.h
            rows.append(np.asarray(out.logits, np.float32))
        return np.stack(rows)
    finally:
        for e, s in zip(engines, slots):
            e.release(s)


def reference_logits(cfg, params, tokens: np.ndarray,
                     first: int) -> np.ndarray:
    """Rows ``first:`` of the float32 forward pass over ``tokens``, every
    matmul at full float32 precision."""
    c32 = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, t: forward(c32, p, t)[0][0, first:])(
            p32, jnp.asarray(tokens, jnp.int32)[None])
    return np.asarray(out, np.float32)


def lower_precision_logits(cfg, params, tokens: np.ndarray,
                           first: int) -> np.ndarray:
    """The control: the bf16 forward pass with every weight rounded to
    float8_e4m3, i.e. computed in a lower precision than the config's."""
    p8 = jax.tree.map(   # rounded on the host: no fp8 op reaches the chip
        lambda a: jnp.asarray(np.asarray(a).astype(jnp.float8_e4m3fn)
                              .astype(a.dtype)), params)
    out = jax.jit(lambda p, t: forward(cfg, p, t)[0][0, first:])(
        p8, jnp.asarray(tokens, jnp.int32)[None])
    return np.asarray(out, np.float32)


def rel_err(a: np.ndarray, ref: np.ndarray) -> float:
    """Largest per-row relative L2 error of ``a`` against ``ref``."""
    num = np.linalg.norm(a - ref, axis=-1)
    return float((num / np.linalg.norm(ref, axis=-1)).max())


def check_logits(cfg, params, engines, prompt, cont, chunk, tol, label,
                 ref=None):
    """Paged logits of ``engines`` against the float32 reference; returns
    the paged rows and the reference rows."""
    got = paged_logits(engines, prompt, cont, chunk)
    if ref is None:
        ref = reference_logits(cfg, params, np.concatenate([prompt, cont]),
                               len(prompt) - 1)
    err = rel_err(got, ref)
    print(f"[logits {label}] rows={len(got)} max_rel_err={err:.5f} "
          f"tol={tol} argmax_agree="
          f"{float((got.argmax(-1) == ref.argmax(-1)).mean()):.3f}",
          flush=True)
    check(np.isfinite(got).all(), f"{label}: non-finite logits")
    check(err <= tol, f"{label}: logit error {err:.5f} > {tol}")
    return got, ref


# ---------------------------------------------------------------------------
# phases


def one_chip(cfg, params, clock: CompileClock, *, ec: EngineConfig = EC,
             prompt_lens: Sequence[int] = PROMPT_LENS,
             check_len: int = CHECK_PROMPT_LEN) -> None:
    """Serve over HTTP, then check bf16 and int8 KV logits."""
    t0, c0 = time.monotonic(), clock.mark()
    p = cluster_plan(cfg, ["TPUv5e"])
    rt = ClusterRuntime(cfg, params, p, ec, paged=True, realtime=True,
                        rng_seed=SEED)
    assert_compiled_kernels(rt)
    phase(clock, "build", t0, c0, nodes=len(rt.engines),
          pool_pages=rt.engines["n0"].pool.num_pages)

    t0, c0 = time.monotonic(), clock.mark()
    prompts = prompts_for(cfg, prompt_lens, SEED)
    outs = serve_over_http(rt, prompts, MAX_TOKENS, timeout_s=900.0)
    tokens = sum(len(o) for o in outs)
    phase(clock, "serve-http", t0, c0, requests=len(outs), tokens=tokens,
          prompt_tokens=sum(prompt_lens))

    t0, c0 = time.monotonic(), clock.mark()
    i = prompt_lens.index(check_len)
    prompt = prompts[i]
    cont = np.random.RandomState(SEED + 1).randint(
        0, cfg.vocab_size, size=(DECODE_STEPS,)).astype(np.int32)
    got, ref = check_logits(cfg, params, stage_order(rt), prompt, cont,
                            ec.prompt_len, BF16_TOL, "bf16-kv")
    # the served stream's first token is the greedy pick of these logits
    check(int(got[0].argmax()) == outs[i][0],
          "served first token differs from the checked prefill logits")
    low = rel_err(lower_precision_logits(cfg, params,
                                         np.concatenate([prompt, cont]),
                                         len(prompt) - 1), ref)
    print(f"[logits control] fp8-e4m3 weights max_rel_err={low:.5f} "
          f"must exceed bf16 tol={BF16_TOL}", flush=True)
    check(low > BF16_TOL, "the bf16 tolerance does not catch fp8 weights")

    rt8 = ClusterRuntime(cfg, params, p, ec, paged=True, kv_dtype="int8",
                         rng_seed=SEED)
    assert_compiled_kernels(rt8)
    check_logits(cfg, params, stage_order(rt8), prompt, cont, ec.prompt_len,
                 INT8_TOL, "int8-kv", ref=ref)
    phase(clock, "logits", t0, c0)


def four_chips(cfg, params, clock: CompileClock, *, ec: EngineConfig = EC,
               prompt_lens: Sequence[int] = FOUR_CHIP_PROMPT_LENS,
               check_len: int = CHECK_PROMPT_LEN) -> None:
    """One stage engine per chip against one engine on chip 0.

    Both run a float32 copy of the model.  In bf16, XLA keeps excess
    precision inside a program but rounds at its outputs, so cutting the
    model into stages alone moves the logits by about 1% (0.0107 relative
    on the smoke config, CPU) and can flip a greedy near-tie; in float32
    they agree exactly, which isolates what this phase checks: that placing
    each stage on its own chip changes nothing."""
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, have {len(devs)}")
    t0, c0 = time.monotonic(), clock.mark()
    p4 = cluster_plan(cfg, ["TPUv5e"] * 4, stages=4)
    check(len(p4.placement.assignment) == 4,
          f"placement uses {len(p4.placement.assignment)} nodes, not 4")
    rt4 = ClusterRuntime(cfg, params, p4, ec, paged=True, rng_seed=SEED)
    rt1 = ClusterRuntime(cfg, params, cluster_plan(cfg, ["TPUv5e"]), ec,
                         paged=True, rng_seed=SEED)
    for rt in (rt4, rt1):
        assert_compiled_kernels(rt)
    stages = stage_order(rt4)
    check(len({e.device for e in stages}) == 4,
          f"stage engines share devices: {[e.device for e in stages]}")
    check(stage_order(rt1)[0].device == devs[0], "single engine not on chip 0")
    for e in stages + stage_order(rt1):
        held = {d for a in jax.tree.leaves((e.sparams, e.caches, e.pool.k,
                                            e.pool.v))
                for d in a.devices()}
        check(held == {e.device}, f"engine {e.layers} arrays on {held}, "
              f"not only {e.device}")
    print("[placement] " + " ".join(
        f"[{e.layers.start},{e.layers.end})@{e.device.id}" for e in stages),
        flush=True)
    phase(clock, "build", t0, c0, nodes=len(stages))

    t0, c0 = time.monotonic(), clock.mark()
    prompts = prompts_for(cfg, prompt_lens, SEED)
    out4 = serve_offline(rt4, prompts, MAX_TOKENS)
    check(all(len(rt4.served[i].stages) == 4 for i in range(len(prompts))),
          "a request was not served across all four stages")
    out1 = serve_offline(rt1, prompts, MAX_TOKENS)
    same = sum(a == b for a, b in zip(out4, out1))
    phase(clock, "serve-4-stage-vs-1", t0, c0, requests=len(prompts),
          tokens=sum(len(o) for o in out4), identical=f"{same}/{len(out4)}")
    check(same == len(out4), "4-stage greedy output differs from 1 engine")

    t0, c0 = time.monotonic(), clock.mark()
    prompt = prompts[prompt_lens.index(check_len)]
    cont = np.random.RandomState(SEED + 1).randint(
        0, cfg.vocab_size, size=(DECODE_STEPS,)).astype(np.int32)
    one = paged_logits(stage_order(rt1), prompt, cont, ec.prompt_len)
    check_logits(cfg, params, stages, prompt, cont, ec.prompt_len, BF16_TOL,
                 "4-stage-vs-1", ref=one)
    phase(clock, "logits", t0, c0)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-stage, one-engine-per-chip check")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r}; nothing was run")
    use_compile_cache()
    clock = CompileClock()
    t_start = time.monotonic()
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={jax.device_count()}", flush=True)
    cfg = get_config(ARCH)
    params = init(cfg, jax.random.key(SEED))
    if args.four_chips:
        four_chips(cfg, params, clock)
    else:
        one_chip(cfg, params, clock)
    secs, progs = clock.mark()
    print(f"[total] wall_s={time.monotonic() - t_start:.1f} "
          f"compile_s={secs:.1f} programs={progs}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
