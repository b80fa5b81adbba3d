"""ClusterRuntime tests: multi-stage pipelines over per-node stage engines
must serve token-for-token identically to a single full-model engine (the
correctness anchor for the cross-node execution layer) at EVERY in-flight
decode depth, pools must drain on completion on every stage node, and
preemption / transport delays / partial inference / failover / eos arriving
mid-window must not change outputs or leak pages.  Builders and the
differential assertions live in tests/harness.py."""
import dataclasses

import numpy as np
import pytest

from repro.core import (COORDINATOR, LayerRange, MILPOptions,
                        replan_after_failure)
from repro.models.stage import stage_num_paged_layers
from repro.serving import (ClusterRuntime, DecodeItem, Engine,
                           EngineConfig, InProcessTransport,
                           PagedStageEngine, Request)
from repro.serving import runtime as runtime_mod
from repro.serving.trace import TRACER

from harness import (EC, assert_pools_drained, assert_serves_like_reference,
                     f32, make_disagg_plan, make_plan, pool_for_one_request,
                     random_assignment, random_prompts, reference_outputs,
                     serve_on_cluster)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # only the property test skips
    HAVE_HYPOTHESIS = False


# --- greedy equivalence: the correctness anchor ------------------------------

@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_two_stage_matches_single_engine(gqa_model, reference, paged):
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    rt = assert_serves_like_reference(cfg, params, p, prompts, ref,
                                      paged=paged)
    # each engine holds only its slice
    assert [len(e.sparams["blocks"]) for _, e in sorted(rt.engines.items())] \
        == [2, 2]
    for i in range(len(prompts)):
        assert len(rt.served[i].stages) == 2


@pytest.mark.parametrize("max_inflight", [1, 2], ids=["depth1", "depth2"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_three_stage_matches_single_engine(gqa_model, reference, paged,
                                           max_inflight):
    """3 uneven stages, with a modelled per-link transport delay — neither
    the extra hop, delivery timing, nor a pipelined in-flight window may
    change a single token."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 3), "n2": (3, 4)})
    rt = assert_serves_like_reference(
        cfg, params, p, prompts, ref, paged=paged, max_inflight=max_inflight,
        transport=InProcessTransport(default_delay_s=2e-3))
    for i in range(len(prompts)):
        assert len(rt.served[i].stages) == 3
    assert rt._now > 0.0          # the virtual clock actually advanced


def test_inflight_depth2_reduces_decode_latency(gqa_model, reference):
    """The acceptance bar for pipelined decode: on a 3-stage placement with
    per-link delay d > 0, depth 2 launches pass t+1 from the final stage
    (1 hop to stage 0) instead of round-tripping through the coordinator
    (2 hops) — per-token decode latency must drop from (k+1)d to k*d while
    output stays byte-identical to the single full-model engine."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 3), "n2": (3, 4)})
    d = 2e-3
    lat = {}
    for depth in (1, 2):
        rt = assert_serves_like_reference(
            cfg, params, p, prompts, ref, paged=True, max_inflight=depth,
            transport=InProcessTransport(default_delay_s=d))
        lat[depth] = rt.mean_decode_latency()
    assert lat[1] == pytest.approx(4 * d)      # final->coord->s0 + 2 hops
    assert lat[2] == pytest.approx(3 * d)      # final->s0 + 2 hops
    assert lat[2] < 0.8 * lat[1]


def test_partial_inference_entry_mid_node(gqa_model, reference):
    """Replicated placement: a request reaching a node that holds [0, 4) at
    layer 2 must infer only [2, 4) there (§3.3) — outputs unchanged, also
    with an in-flight window."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (0, 4), "n2": (2, 4)})
    # pin the flows so every request routes n0 -> n1: n1 holds [0, 4) but
    # must start inferring at layer 2 (max-flow might otherwise avoid the
    # replicated path entirely)
    p = dataclasses.replace(p, flows={(COORDINATOR, "n0"): 1.0,
                                      ("n0", "n1"): 1.0,
                                      ("n1", COORDINATOR): 1.0})
    rt = assert_serves_like_reference(cfg, params, p, prompts, ref,
                                      paged=True, max_inflight=2)
    mid_entry = any(
        st_.layers.start > rt.placement.assignment[st_.node].start
        for pipe in rt.served.values() for st_ in pipe.stages)
    assert mid_entry, "no pipeline exercised a mid-node entry"


@pytest.mark.parametrize("max_inflight", [1, 2], ids=["depth1", "depth2"])
def test_pool_exhaustion_preempts_pipeline_wide(gqa_model, reference,
                                                max_inflight):
    """A mid-stage pool that fits one full-budget request forces preemption
    — with depth 2 that includes cancelling speculative in-flight tokens;
    recompute-on-readmit must keep outputs identical and drain every pool."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 3), "n2": (3, 4)})
    small = pool_for_one_request(cfg, LayerRange(2, 3))
    rt, reqs = serve_on_cluster(cfg, params, p, prompts, paged=True,
                                max_inflight=max_inflight,
                                pool_pages={"n1": small})
    assert [r.output for r in reqs] == ref
    assert any(r.preemptions > 0 for r in reqs)
    assert_pools_drained(rt)


def test_hybrid_stack_multi_stage_paged(gqa_model):
    """Hybrid (mamba/MoE + GQA) slices: paged attention + dense fallback
    split across stages still matches the full dense engine at depth 2.
    n0's slice holds *no* paged block at all (jamba's attn blocks sit at
    layers 3 and 7) — the runtime must give it a dense stage engine even in
    paged mode instead of crashing at construction."""
    from repro.configs import get_smoke_config
    from repro.models import init
    import jax
    cfg = f32(get_smoke_config("jamba_1_5_large_398b"))
    params = init(cfg, jax.random.key(2))
    assert stage_num_paged_layers(cfg, LayerRange(0, 3)) == 0
    prompts = random_prompts(cfg, (11,), seed=1)
    ec = EngineConfig(max_batch=2, max_len=48, prompt_len=16)
    ref = reference_outputs(cfg, params, prompts, ec=ec, max_new_tokens=6)
    p = make_plan(cfg, {"n0": (0, 3), "n1": (3, 5), "n2": (5, 8)})
    rt = assert_serves_like_reference(cfg, params, p, prompts, ref,
                                      paged=True, max_inflight=2, ec=ec)
    assert not isinstance(rt.engines["n0"], PagedStageEngine)
    assert isinstance(rt.engines["n1"], PagedStageEngine)


# --- routed forwarding: hop accounting ---------------------------------------

def test_direct_links_reduce_decode_hops(gqa_model, reference):
    """The tentpole's measurable claim: on a k=3 stage pipeline with
    per-link delay d, star routing charges 2k hops per decode token (every
    stage output bounces through the coordinator) while direct links charge
    k+1 (k-1 peer hops + the token's coordinator round trip) — and the
    per-token latency drops accordingly.  Counters come from the
    transport's per-(src,dst) ledger, which also feeds describe()."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 3), "n2": (3, 4)})
    d = 2e-3
    hops, lat = {}, {}
    for direct in (False, True):
        tr = InProcessTransport(default_delay_s=d, direct_links=direct)
        rt = assert_serves_like_reference(cfg, params, p, prompts, ref,
                                          paged=True, transport=tr)
        n_tokens = sum(len(r) for r in ref)
        hops[direct] = sum(tr.transfers.values()) / n_tokens
        lat[direct] = rt.mean_decode_latency()
        peer = {k: v for k, v in tr.transfers.items()
                if COORDINATOR not in k}
        if direct:
            assert peer.get(("n0", "n1")) and peer.get(("n1", "n2")), peer
        else:
            assert not peer, f"star mode must not use peer links: {peer}"
        assert "hops[" in tr.describe()
    assert hops[False] == pytest.approx(6.0)       # 2k
    assert hops[True] == pytest.approx(4.0)        # k+1
    assert lat[False] == pytest.approx(6 * d)
    assert lat[True] == pytest.approx(4 * d)


# --- disaggregated prefill/decode --------------------------------------------

@pytest.mark.parametrize("max_inflight", [1, 2], ids=["depth1", "depth2"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_disaggregated_matches_single_engine(gqa_model, reference, paged,
                                             max_inflight):
    """One prefill replica holding the full model, a 2-stage decode
    replica: prompts run on n0, the filled KV ships over peer links to
    n1/n2, decode runs only there — outputs byte-identical to the single
    full-model engine, pools drained everywhere."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_disagg_plan(cfg, {"n0": (0, 4)}, {"n1": (0, 2), "n2": (2, 4)})
    tr = InProcessTransport(default_delay_s=1e-3)
    rt = assert_serves_like_reference(cfg, params, p, prompts, ref,
                                      paged=paged, max_inflight=max_inflight,
                                      transport=tr)
    assert rt.disaggregated
    # every request's KV actually travelled prefill -> decode
    assert tr.transfers[("n0", "n1")] >= len(prompts)
    assert tr.transfers[("n0", "n2")] >= len(prompts)
    # decode stage-work only ever ran on the decode replica
    for pipe in rt.served.values():
        assert {st.node for st in pipe.stages} <= {"n1", "n2"}


def test_disaggregated_mixed_node_keeps_kv_home(gqa_model, reference):
    """A node in both groups (``mixed``) decodes from the KV its own
    prefill pass filled: no handoff is shipped for its layers."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_disagg_plan(cfg, {"n0": (0, 2), "n1": (2, 4)},
                         {"n2": (0, 2), "n1": (2, 4)})
    assert p.placement.meta["roles"] == {"n0": "prefill", "n1": "mixed",
                                         "n2": "decode"}
    tr = InProcessTransport(default_delay_s=1e-3)
    rt = assert_serves_like_reference(cfg, params, p, prompts, ref,
                                      paged=True, max_inflight=2,
                                      transport=tr)
    assert tr.transfers[("n0", "n2")] >= len(prompts)   # layers [0, 2) ship
    # n1's KV stays home: its outgoing peer traffic is speculative-launch
    # tokens only (token_bytes each), never a KV payload
    assert tr.bytes_sent[("n1", "n2")] == \
        tr.transfers[("n1", "n2")] * rt.profile.token_bytes
    assert rt.disaggregated


def test_disaggregated_failover_replans_to_mixed(gqa_model, reference):
    """Kill a decode-replica node mid-flight: in-flight requests requeue,
    the generic replan returns a role-less placement (disaggregation is
    dropped, not wedged), and outputs still match the reference."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_disagg_plan(cfg, {"n0": (0, 4)},
                         {"n1": (0, 2), "n2": (2, 4), "n3": (0, 4)})
    rt, reqs = serve_on_cluster(cfg, params, p, prompts, paged=True,
                                max_inflight=2, steps=8,
                                transport=InProcessTransport(
                                    default_delay_s=1e-3))
    assert rt.jobs, "nothing in flight before the failure"
    rt.fail_node("n1")
    new = replan_after_failure(p, "n1", MILPOptions(time_limit_s=5.0,
                                                    lns_rounds=0,
                                                    fgls_rounds=10))
    rt.apply_plan(new)
    rt.run_until_done()
    assert [r.output for r in reqs] == ref
    assert "n1" not in rt.engines
    assert_pools_drained(rt)


# --- property: any placement x depth x trace ---------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=5, deadline=None)
    @given(data=st.data())
    def test_property_any_depth_matches_single_engine(gqa_model, data):
        """Random stage count / layer cuts / in-flight depth / trace: the
        runtime's greedy output is identical to single-engine decode and
        every pool drains to zero."""
        cfg, params = gqa_model
        n_stages = data.draw(st.integers(1, 3), label="n_stages")
        seed = data.draw(st.integers(0, 2**16), label="seed")
        depth = data.draw(st.integers(1, 3), label="max_inflight")
        lengths = data.draw(st.lists(st.integers(1, 16), min_size=2,
                                     max_size=3), label="prompt_lengths")
        max_new = data.draw(st.lists(st.integers(1, 8),
                                     min_size=len(lengths),
                                     max_size=len(lengths)),
                            label="max_new_tokens")
        rng = np.random.RandomState(seed)
        assignment = random_assignment(rng, cfg.num_layers, n_stages)
        prompts = random_prompts(cfg, lengths, seed=seed)
        ref = reference_outputs(cfg, params, prompts, ec=EC,
                                max_new_tokens=max_new)
        p = make_plan(cfg, assignment)
        assert_serves_like_reference(cfg, params, p, prompts, ref,
                                     paged=True, max_inflight=depth,
                                     max_new_tokens=max_new)


# --- scheduler feedback ------------------------------------------------------

def test_kv_estimator_sees_true_pool_occupancy(gqa_model):
    """The runtime must report real PagePool usage (and capacity) into the
    scheduler's KVEstimator — not arrival-time reservations."""
    cfg, params = gqa_model
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    rt = ClusterRuntime(cfg, params, p, EC, paged=True)
    kv = rt.scheduler.kv
    for node, eng in rt.engines.items():
        assert kv.capacity_tokens[node] == eng.pool.tokens_capacity
    rt.submit(Request(0, np.arange(10) % cfg.vocab_size, max_new_tokens=8))
    for _ in range(4):
        rt.step()
    assert any(eng.pool.tokens_used > 0 for eng in rt.engines.values())
    for node, eng in rt.engines.items():
        assert kv.usage[node] == eng.pool.tokens_used
    rt.run_until_done()
    for node in rt.engines:
        assert kv.usage[node] == 0


# --- what a final stage's decode step brings back ----------------------------

def _logits_rows() -> int:
    return TRACER.counters.get("decode.logits_rows", 0)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_greedy_decode_copies_no_logits(gqa_model, reference, paged):
    """Greedy requests take the final stage's device argmax: no decode step
    brings full-vocabulary logits to the host, and the output still
    matches the single engine's."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    before = _logits_rows()
    assert_serves_like_reference(cfg, params, p, prompts, ref, paged=paged)
    assert _logits_rows() == before


def test_sampled_rows_fetch_logits_and_keep_their_tokens(gqa_model,
                                                         reference,
                                                         monkeypatch):
    """Requests at temperature > 0 beside greedy ones: their rows bring
    logits back, greedy requests still match the single engine, and every
    request's tokens are those of a run in which every row's logits come
    back."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    temps = (0.0, 0.9, 0.0, 1.2)

    def serve():
        rt = ClusterRuntime(cfg, params, p, EC, paged=True, rng_seed=5)
        reqs = [Request(i, q, max_new_tokens=6, temperature=T)
                for i, (q, T) in enumerate(zip(prompts, temps))]
        for r in reqs:
            rt.submit(r)
        rt.run_until_done()
        assert all(r.done for r in reqs)
        assert_pools_drained(rt)
        return [r.output for r in reqs]

    before = _logits_rows()
    got = serve()
    assert _logits_rows() > before
    assert [got[i] for i in (0, 2)] == [ref[i] for i in (0, 2)]
    monkeypatch.setattr(runtime_mod, "DecodeItem", lambda **kw: DecodeItem(
        **{**kw, "want_logits": True}))
    assert serve() == got


# --- fault injection on the in-flight window ---------------------------------

def test_eos_mid_window_cancels_inflight_cleanly(gqa_model, reference):
    """eos confirmed at the coordinator while the speculative pass for
    token t+1 is still mid-pipeline: the pass must be cancelled (epoch),
    no page may leak, and the truncated output must equal the reference cut
    at eos — then the SAME runtime must serve a fresh request correctly
    (caches uncorrupted by the cancelled write)."""
    cfg, params = gqa_model
    prompts, ref = reference
    # make the token greedy decode emits mid-stream (index 2 of request 0)
    # the eos token; requests whose outputs contain it stop there
    eos = ref[0][2]
    ec = dataclasses.replace(EC, eos_token=eos)

    def cut(out):
        return out[:out.index(eos) + 1] if eos in out else out

    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 3), "n2": (3, 4)})
    rt, reqs = serve_on_cluster(
        cfg, params, p, prompts, paged=True, max_inflight=3, ec=ec,
        transport=InProcessTransport(default_delay_s=1e-3))
    assert [r.output for r in reqs] == [cut(o) for o in ref]
    assert reqs[0].finish_reason == "stop"
    assert rt.cancelled_inflight > 0, \
        "no speculative pass was in flight when eos confirmed"
    assert_pools_drained(rt)
    # the runtime keeps serving correctly after the cancellations
    extra = Request(99, prompts[1], max_new_tokens=6)
    rt.submit(extra)
    rt.run_until_done()
    assert extra.output == cut(ref[1])
    assert_pools_drained(rt)


class _ReorderingTransport(InProcessTransport):
    """The first delivery to the coordinator is slower than later ones, so
    a speculative pass's token (output index 1) overtakes prefill's token
    (index 0) on the return path — legal under the base Transport contract
    ('send must eventually deliver'), never produced by the FIFO
    InProcessTransport."""

    def __init__(self):
        super().__init__(default_delay_s=1e-3)
        self._slowed = set()

    def delay(self, src, dst, nbytes):
        d = super().delay(src, dst, nbytes)
        if dst == COORDINATOR and src not in self._slowed:
            self._slowed.add(src)
            return d + 5e-3
        return d


def test_out_of_order_token_arrival_confirms_in_order(gqa_model, reference):
    """Decode tokens reaching the coordinator before the prefill token must
    wait in the inbox and confirm in output order once it lands — not
    strand the request (regression: _on_first_token used to skip the inbox
    drain)."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    assert_serves_like_reference(cfg, params, p, prompts, ref, paged=False,
                                 max_inflight=2,
                                 transport=_ReorderingTransport())


def test_failover_replan_re_prefills_in_flight(gqa_model, reference):
    """Kill a stage node mid-decode with an active in-flight window: the
    speculative passes die with the epoch bump, survivors release the
    victims' KV, the replanned placement is adopted, in-flight requests
    re-prefill (keeping generated tokens) and finish with unchanged
    outputs."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4), "n2": (0, 4)})
    rt, reqs = serve_on_cluster(cfg, params, p, prompts, paged=True,
                                max_inflight=2, steps=6)
    assert rt.jobs, "nothing in flight before the failure"
    rt.fail_node("n1")
    new = replan_after_failure(p, "n1", MILPOptions(time_limit_s=5.0,
                                                    lns_rounds=0,
                                                    fgls_rounds=10))
    rt.apply_plan(new)
    rt.run_until_done()
    assert [r.output for r in reqs] == ref
    assert "n1" not in rt.engines
    assert_pools_drained(rt)


# --- guards ------------------------------------------------------------------

def test_runtime_rejects_oversized_prompt(gqa_model):
    cfg, params = gqa_model
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    rt = ClusterRuntime(cfg, params, p, EC, paged=True)
    with pytest.raises(ValueError, match="truncate"):
        rt.submit(Request(0, np.arange(EC.max_len + 1) % cfg.vocab_size))
    with pytest.raises(ValueError, match="empty"):
        rt.submit(Request(1, np.zeros((0,), np.int32)))
    with pytest.raises(ValueError, match="max_inflight"):
        ClusterRuntime(cfg, params, p, EC, paged=False, max_inflight=0)


def test_run_until_done_exhaustion_raises_with_diagnostics(gqa_model):
    """Regression: exhausting max_iters must raise with queue/in-flight
    diagnostics, never return silently with requests outstanding — for the
    ClusterRuntime AND the single-node engines."""
    cfg, params = gqa_model
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    rt = ClusterRuntime(cfg, params, p, EC, paged=True)
    rt.submit(Request(0, np.arange(10) % cfg.vocab_size, max_new_tokens=8))
    with pytest.raises(RuntimeError, match=r"not done after 2.*queued="):
        rt.run_until_done(max_iters=2)
    eng = Engine(cfg, params, EC)
    eng.submit(Request(0, np.arange(10) % cfg.vocab_size, max_new_tokens=8))
    with pytest.raises(RuntimeError, match=r"not done after 1.*active=1"):
        eng.run_until_done(max_iters=1)
    # fencepost: finishing exactly on the last allowed iteration is success
    eng2 = Engine(cfg, params, EC)
    done_in_one = Request(1, np.arange(10) % cfg.vocab_size,
                          max_new_tokens=1)
    eng2.submit(done_in_one)
    eng2.run_until_done(max_iters=1)
    assert done_in_one.done


def test_stage_engine_holds_only_its_slice(gqa_model):
    cfg, params = gqa_model
    eng = PagedStageEngine(cfg, params, LayerRange(1, 3), EC)
    assert len(eng.sparams["blocks"]) == 2
    assert "embed" not in eng.sparams       # neither first nor last stage
    assert "final_norm" not in eng.sparams
    assert eng.pool.num_layers == 2         # pool priced at *local* layers


# --- one process, one device per stage ---------------------------------------

def test_four_stages_on_four_devices(gqa_model, reference):
    """Rehearsal of the four-chip path on host devices: a 4-stage placement
    in one process puts node i's params, caches and pool on device i,
    serves byte-identically to the single engine, and drains every pool."""
    import jax
    if jax.device_count() < 4:
        pytest.skip(f"needs 4 host devices, have {jax.device_count()}")
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {f"n{i}": (i, i + 1) for i in range(4)})
    rt = assert_serves_like_reference(cfg, params, p, prompts, ref,
                                      paged=True)
    nodes = sorted(rt.engines)
    assert [rt.engines[n].device for n in nodes] == jax.devices()[:4]
    for n in nodes:
        eng = rt.engines[n]
        arrays = jax.tree.leaves((eng.sparams, eng.caches, eng.pool.k,
                                  eng.pool.v))
        assert {d for a in arrays for d in a.devices()} == {eng.device}, n


def test_spawn_workers_refused_on_accelerator(gqa_model, monkeypatch):
    """A parent on an accelerator holds the chip, so local worker children
    could not open it: spawn_workers refuses before starting any."""
    import jax
    import subprocess
    cfg, params = gqa_model
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(subprocess, "Popen", None)   # must never be reached
    with pytest.raises(RuntimeError, match="holds the tpu device"):
        ClusterRuntime.spawn_workers(cfg, params, p, EC)


@pytest.mark.parametrize("env_dir", [None, "elsewhere"], ids=["unset", "set"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """Entry points keep the compile cache where JAX_COMPILATION_CACHE_DIR
    says, else at one fixed path in the checkout; importing sets nothing."""
    import importlib

    import jax

    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    importlib.reload(compile_cache)
    for mod in ("repro.launch.serve", "repro.launch.worker"):
        importlib.import_module(mod)
    assert jax.config.jax_compilation_cache_dir == before
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        compile_cache.use_compile_cache()
        want = before if env_dir else str(compile_cache.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache.CACHE_DIR.parent.joinpath(
            "src", "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
