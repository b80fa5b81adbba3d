"""Time the paged decode kernel alone at the ``olmo-batch`` cell's shapes.

One layer call of OLMo-1B's decode: B = 33 rows (32 slots and the scratch
row), 16 MHA heads of 128, page 16, NP = 128 table entries, bf16 pages in a
full 65,537-page pool.  The 32 live rows hold contexts drawn from the Azure
lengths of ``bench/traffic/batch.json`` (a prompt, plus a uniform share of
its output already decoded); the scratch row holds one token on page 0.
Each row's pages are drawn at random from the pool, which is stored as
``layout.page_layout`` lays it out (``(P, 256, 128)`` for OLMo-1B).
``--shapes smollm`` takes SmolLM-360M's heads instead (15 query heads on 5
kv heads of 64: pages of 40 rows of two records).

For each candidate it prints us per call (median over repeats of
back-to-back calls of the jitted op, any pool relayout XLA adds included,
ended by ``block_until_ready``), the live K/V bytes the call must read, and
their share of the chip's HBM bandwidth (``bench/peaks.json``).
Candidates are the kernel as callers get it (``derived``) and, where the
kernel module has the private ``_paged_attention`` entry, each
pages-per-block in ``PPB``.

    python benchmarks/paged_attention_kernel.py [--seed N] [--out FILE]

On a chip host only (it refuses to time the CPU); ``--rehearse`` runs tiny
shapes in interpret mode to check the script, and prints no timing.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "bench")]

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

import traffic                                          # noqa: E402
from repro.kernels.paged_attention import kernel as pa  # noqa: E402
from repro.kernels.paged_attention import paged_attention_ref as pa_ref  # noqa: E402,E501
from repro.kernels.paged_attention.layout import page_layout  # noqa: E402

PPB = (4, 8, 16, 32)
HEADS = {"olmo": dict(H=16, KH=16, D=128), "smollm": dict(H=15, KH=5, D=64)}


def contexts(seed: int, rows: int, max_len: int) -> np.ndarray:
    spec = json.load(open(os.path.join(ROOT, "bench/traffic/batch.json")))
    rng = np.random.default_rng(seed)
    out = []
    for p, o in traffic.request_lengths(spec, seed, rows):
        out.append(min(max_len, p + int(rng.integers(1, o + 1))))
    return np.asarray(out, np.int32)


def inputs(seed, B, H, KH, D, page, NP, pool_pages, max_len):
    lengths = np.concatenate([contexts(seed, B - 1, max_len), [1]])
    rng = np.random.default_rng(seed)
    # page 0 is the scratch row's; live rows take distinct scattered pages
    ids = rng.permutation(np.arange(1, pool_pages))[:(B - 1) * NP]
    tables = np.concatenate([ids.reshape(B - 1, NP),
                             np.zeros((1, NP), ids.dtype)]).astype(np.int32)
    key = jax.random.key(seed % (2 ** 31))
    kq, kk, kv = jax.random.split(key, 3)
    shape = page_layout(page, KH, D).shape(pool_pages)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)
    q = jax.random.normal(kq, (B, H, D), jnp.bfloat16)
    return q, k, v, jnp.asarray(tables), jnp.asarray(lengths)


def candidates(interpret: bool, sweep: bool, page: int):
    """(name, fn(q, k, v, tables, lengths)) for every candidate."""
    out = [("derived", lambda *a: pa.paged_attention(
        *a, page=page, interpret=interpret))]
    if sweep and hasattr(pa, "_paged_attention"):
        for ppb in PPB:
            out.append((f"ppb{ppb}", lambda *a, ppb=ppb: pa._paged_attention(
                *a, page=page, k_scales=None, v_scales=None, ppb=ppb,
                interpret=interpret)))
    return out


def time_call(fn, args, calls: int, repeats: int) -> float:
    """Median seconds per call over ``repeats`` runs of ``calls`` calls."""
    f = jax.jit(fn)
    f(*args).block_until_ready()
    per = []
    for _ in range(repeats):
        t = time.perf_counter()
        for _ in range(calls):
            out = f(*args)
        out.block_until_ready()
        per.append((time.perf_counter() - t) / calls)
    return statistics.median(per)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--out", default=None, help="append JSON lines here")
    ap.add_argument("--shapes", choices=sorted(HEADS), default="olmo")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, interpret mode, no timing")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if a.rehearse:
        dims = dict(B=3, page=16, NP=10, pool_pages=64, max_len=160)
    elif dev.platform != "tpu":
        sys.exit(f"no TPU: {dev.platform}; use --rehearse off the chip")
    else:
        dims = dict(B=33, page=16, NP=128, pool_pages=65537, max_len=2048)
    dims.update(HEADS[a.shapes])
    if not a.rehearse:
        peaks = json.load(open(os.path.join(ROOT, "bench/peaks.json")))
        hbm_bytes_per_s = peaks[dev.device_kind]["hbm_bytes_per_s"]
    q, k, v, tables, lengths = inputs(a.seed, **dims)
    page, KH, D = dims["page"], dims["KH"], dims["D"]
    pages = int(np.sum(-(-np.asarray(lengths) // page)))
    live = 2 * pages * page * KH * D * 2
    ref = np.asarray(jax.jit(functools.partial(pa_ref, page=page))(
        q, k, v, tables, lengths), np.float32)
    for name, fn in candidates(a.rehearse, a.shapes == "olmo", page):
        args = (q, k, v, tables, lengths)
        try:
            out = np.asarray(jax.jit(fn)(*args), np.float32)
        except Exception as e:                 # a candidate the chip refuses
            print(json.dumps({"candidate": name, "error": str(e)[:300]}))
            continue
        rec = {"candidate": name, "shapes": a.shapes,
               "device": dev.device_kind,
               "max_abs_diff_vs_ref": float(np.max(np.abs(out - ref))),
               "live_bytes": live, "live_pages": pages}
        if not a.rehearse:
            s = time_call(fn, args, a.calls, a.repeats)
            rec.update(us_per_call=s * 1e6,
                       hbm_share=live / s / hbm_bytes_per_s)
        line = json.dumps(rec)
        print(line, flush=True)
        if a.out:
            with open(a.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
