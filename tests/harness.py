"""Differential test harness for the serving stack.

One set of builders for random heterogeneous clusters, placement-driven
plans, and request traces, shared by the runtime / paged-engine / scheduler
/ simulator tests (they used to carry copy-pasted variants).  On top of the
builders sit the differential assertions the pipelined-decode work hangs
off: a ``ClusterRuntime`` at ANY in-flight depth, dense or paged, must
produce greedy output byte-identical to a single full-model ``Engine``, and
every stage node's page pool must drain to zero afterwards.
"""
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.configs import get_smoke_config
from repro.core import (LayerRange, ModelProfile, Placement,
                        disaggregated_placement, full_mesh_cluster, plan)
from repro.core.cluster import ClusterSpec
from repro.serving import ClusterRuntime, Engine, EngineConfig, Request

# one engine shape shared by the runtime tests: small enough to be fast,
# big enough for preemption/budget scenarios
EC = EngineConfig(max_batch=4, max_len=48, prompt_len=16)


def f32(cfg):
    """float32 copy so paged (Pallas online-softmax) and dense (plain jnp)
    logits agree to argmax precision for greedy equivalence checks."""
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


def draft_model(cfg, params=None, *, seed: int = 0):
    """A ``(draft_cfg, draft_params)`` pair for the speculation axis.
    ``seed=0`` re-inits the target's own architecture at the standard key —
    in these tests that reproduces the target's params exactly, giving a
    near-perfect-acceptance draft; any other seed gives a low-quality
    draft.  Either way greedy output must be byte-identical to the
    non-speculative reference — draft quality only changes speed."""
    if params is None:
        import jax

        from repro.models import init
        params = init(cfg, jax.random.key(seed))
    return cfg, params


# ---------------------------------------------------------------------------
# cluster / model / plan builders
# ---------------------------------------------------------------------------

def make_cluster(devs: Union[int, Sequence[str]], *,
                 inter_bw: float = 10e9 / 8,
                 latency_s: float = 1e-3) -> ClusterSpec:
    """Full-mesh single-region cluster.  ``devs`` is a device-name list
    (heterogeneous) or an int (that many A100s)."""
    return full_mesh_cluster(devs, bandwidth=inter_bw, latency_s=latency_s)


def small_model(num_layers: int = 8) -> ModelProfile:
    """Toy analytic model profile for scheduler/simulator tests."""
    return ModelProfile.from_dims("toy", num_layers=num_layers, d_model=4096,
                                  d_ff=11008, vocab=32000, n_kv_heads=32,
                                  head_dim=128)


def model_profile(cfg) -> ModelProfile:
    return ModelProfile.from_dims(
        cfg.name, cfg.num_layers, cfg.d_model, max(cfg.d_ff, 1),
        cfg.vocab_size, cfg.num_kv_heads, cfg.resolved_head_dim)


def make_plan(cfg, assignment: Dict[str, Tuple[int, int]], *,
              devs: Optional[Sequence[str]] = None):
    """Plan for an explicit layer assignment ({node: (start, end)}) on a
    full-mesh cluster (A100s unless ``devs`` names heterogeneous devices)."""
    placement = Placement({n: LayerRange(*r) for n, r in assignment.items()},
                          cfg.num_layers)
    assert placement.validate() == []
    cluster = make_cluster(devs if devs is not None else len(assignment))
    return plan(cluster, model_profile(cfg), placement=placement)


def make_disagg_plan(cfg, prefill: Dict[str, Tuple[int, int]],
                     decode: Dict[str, Tuple[int, int]], *,
                     devs: Optional[Sequence[str]] = None):
    """Plan for a disaggregated placement: ``prefill`` and ``decode`` are
    each {node: (start, end)} groups covering the full model on their own
    (a node in both groups with the same range becomes ``mixed``)."""
    placement = disaggregated_placement(
        {n: LayerRange(*r) for n, r in prefill.items()},
        {n: LayerRange(*r) for n, r in decode.items()}, cfg.num_layers)
    n = len(placement.assignment)
    cluster = make_cluster(devs if devs is not None else n)
    return plan(cluster, model_profile(cfg), placement=placement)


def random_assignment(rng: np.random.RandomState, num_layers: int,
                      n_stages: int) -> Dict[str, Tuple[int, int]]:
    """Random contiguous abutting layer ranges over ``num_layers`` for
    ``n_stages`` nodes — a random heterogeneous pipeline shape."""
    assert 1 <= n_stages <= num_layers
    cuts = sorted(rng.choice(np.arange(1, num_layers), size=n_stages - 1,
                             replace=False).tolist())
    bounds = [0] + cuts + [num_layers]
    return {f"n{i}": (bounds[i], bounds[i + 1]) for i in range(n_stages)}


# ---------------------------------------------------------------------------
# traces + reference outputs
# ---------------------------------------------------------------------------

def random_prompts(cfg, lengths: Sequence[int], *,
                   seed: int = 0) -> List[np.ndarray]:
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, size=(int(n),)) for n in lengths]


def prefill_slots(eng, prompts, *, paged: bool, chunk: int,
                  budget: int) -> List[int]:
    """Seat prompt ``i`` in slot ``i`` of a whole-model stage engine (local
    or remote), reserve ``budget`` tokens and prefill it (paged engines by
    chunks of ``chunk``); returns each prompt's greedy first token."""
    first = []
    for i, p in enumerate(prompts):
        s = eng.alloc_slot(i)
        assert s == i and eng.ensure(s, budget)
        if paged:
            for off in range(0, len(p), chunk):
                out = eng.prefill_chunk(s, p[off:off + chunk], 0, off)
        else:
            out = eng.prefill_stage(s, p, 0)
        first.append(int(np.argmax(np.asarray(out, np.float64))))
    return first


def decode_rows(eng, prompts, toks, t: int, want_logits) -> list:
    """Decode step ``t`` of every seated prompt (slot ``i``), feeding
    ``toks[i]``; ``want_logits[i]`` says whether row ``i`` asks for its
    logits."""
    from repro.serving.stage_engine import DecodeItem
    return eng.decode_stage([
        DecodeItem(slot=i, pos=len(p) + t, entry=0, token=int(tok),
                   want_logits=bool(w))
        for i, (p, tok, w) in enumerate(zip(prompts, toks, want_logits))])


def _as_requests(prompts, max_new_tokens) -> List[Request]:
    if isinstance(max_new_tokens, int):
        max_new_tokens = [max_new_tokens] * len(prompts)
    return [Request(i, p, max_new_tokens=int(m))
            for i, (p, m) in enumerate(zip(prompts, max_new_tokens))]


def reference_outputs(cfg, params, prompts, *, ec: EngineConfig = EC,
                      max_new_tokens=6, engine: Optional[Engine] = None
                      ) -> List[List[int]]:
    """Greedy outputs from a single full-model dense engine — the
    correctness anchor every cluster configuration must reproduce."""
    eng = engine if engine is not None else Engine(cfg, params, ec)
    reqs = _as_requests(prompts, max_new_tokens)
    for r in reqs:
        eng.submit(r)
    eng.run_until_done(2000)
    assert all(r.done for r in reqs)
    return [r.output for r in reqs]


# ---------------------------------------------------------------------------
# differential serving
# ---------------------------------------------------------------------------

def serve_on_cluster(cfg, params, p, prompts, *, paged: bool,
                     max_inflight: int = 1, max_new_tokens=6,
                     ec: EngineConfig = EC, steps: Optional[int] = None,
                     **kw) -> Tuple[ClusterRuntime, List[Request]]:
    """Run ``prompts`` through a ClusterRuntime built from plan ``p``.
    ``steps`` runs a bounded number of iterations (for mid-flight fault
    injection) instead of to completion."""
    rt = ClusterRuntime(cfg, params, p, ec, paged=paged,
                        max_inflight=max_inflight, **kw)
    reqs = _as_requests(prompts, max_new_tokens)
    for r in reqs:
        rt.submit(r)
    if steps is None:
        rt.run_until_done()
        assert all(r.done for r in reqs)
    else:
        for _ in range(steps):
            rt.step()
    return rt, reqs


def step_until(rt: ClusterRuntime, pred, max_steps: int = 2000) -> None:
    """Step the runtime until ``pred(rt)`` holds — the hook the
    cancellation / autoscaler tests use to catch a request at a precise
    lifecycle point (mid-decode, mid KV handoff) before injecting."""
    for _ in range(max_steps):
        if pred(rt):
            return
        rt.step()
    raise AssertionError(f"predicate never held within {max_steps} steps")


def assert_pools_drained(rt: ClusterRuntime) -> None:
    """Every paged stage node must return to zero allocated pages — an
    in-flight token cancelled by eos/preemption/failover may never leak.
    When a draft model is attached its slots must all be free too: a
    speculative rollback or early eos may never strand a draft slot."""
    for node, used in rt.pool_pages_used().items():
        assert used == 0, f"{node} leaked {used} pages"
    if getattr(rt, "draft", None) is not None:
        free = rt.draft.free_slots
        assert free == rt.ec.max_batch, (
            f"draft engine leaked {rt.ec.max_batch - free} slots")


def assert_serves_like_reference(cfg, params, p, prompts, ref, *,
                                 paged: bool, max_inflight: int = 1,
                                 max_new_tokens=6, ec: EngineConfig = EC,
                                 spec: Optional[Tuple] = None,
                                 **kw) -> ClusterRuntime:
    """The differential anchor: byte-identical greedy output at any
    in-flight depth, pools drained on every node.  ``spec`` turns on
    speculative decoding: ``(draft_cfg, draft_params)`` or
    ``(draft_cfg, draft_params, spec_tokens)`` — greedy output must still
    match the non-speculative reference byte-for-byte."""
    if spec is not None:
        kw["draft_cfg"], kw["draft_params"] = spec[0], spec[1]
        if len(spec) > 2:
            kw["spec_tokens"] = spec[2]
    rt, reqs = serve_on_cluster(cfg, params, p, prompts, paged=paged,
                                max_inflight=max_inflight,
                                max_new_tokens=max_new_tokens, ec=ec, **kw)
    got = [r.output for r in reqs]
    assert got == ref, (f"depth={max_inflight} paged={paged} "
                        f"spec={spec is not None} diverged:\n"
                        f"  got {got}\n  ref {ref}")
    assert_pools_drained(rt)
    return rt


def pool_for_one_request(cfg, layers: LayerRange, *,
                         ec: EngineConfig = EC, page_size: int = 16) -> int:
    """Page count that fits exactly one full-budget request on a stage
    slice — the smallest legal pool, used to force preemption."""
    from repro.models.stage import stage_num_paged_layers
    n_paged = stage_num_paged_layers(cfg, layers)
    blocks = -(-ec.max_len // page_size)
    return 1 + blocks * n_paged
