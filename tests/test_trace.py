"""The tracer (``serving/trace.py``) and the spans the served path records.

Tests of the process-wide ``TRACER`` look only at spans that began after
the test started and at counter deltas, so tracer state left by other
tests cannot change them."""
import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from repro.serving import ClusterRuntime, Frontend
from repro.serving.trace import TRACER, Tracer

from harness import EC, make_plan, random_prompts, serve_on_cluster

ENGINE_CHILDREN = {"helix.engine.inputs", "helix.engine.launch",
                   "helix.engine.wait", "helix.engine.fetch"}


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def since(t0: float):
    return [s for s in TRACER.spans() if s.t0 >= t0]


# ---------------------------------------------------------------------------
# the tracer


def test_nesting_and_parent_ids_per_thread():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    inner_done = threading.Event()
    go = threading.Event()

    def other():
        with tr.span("b.outer"):
            go.wait(5)
            with tr.span("b.inner"):
                pass
        inner_done.set()

    th = threading.Thread(target=other)
    with tr.span("a.outer", call=3, node="n0"):
        th.start()
        clk.t = 1.0
        with tr.span("a.inner") as a_inner:
            go.set()
            assert inner_done.wait(5)
        clk.t = 2.0
    th.join(5)
    by = {s.name: s for s in tr.spans()}
    assert by["a.outer"].parent is None
    assert by["a.inner"].parent == by["a.outer"].id
    # the other thread's spans nest on their own stack, not under a.outer
    assert by["b.outer"].parent is None
    assert by["b.inner"].parent == by["b.outer"].id
    assert len({s.id for s in by.values()}) == 4
    assert (by["a.outer"].t0, by["a.outer"].t1) == (0.0, 2.0)
    assert (a_inner.t0, a_inner.t1) == (1.0, 1.0)
    assert by["a.outer"].attrs == {"call": 3, "node": "n0"}
    # record() spans were stamped elsewhere: no parent, even inside a span
    with tr.span("c"):
        tr.record("d", -5.0, 1.0, request=7)
    d = [s for s in tr.spans() if s.name == "d"][0]
    assert d.parent is None and (d.t0, d.t1) == (-5.0, 1.0)
    assert d.attrs == {"request": 7}


def test_ring_bound_dropped_and_eviction_time():
    tr = Tracer(capacity=4, clock=FakeClock())
    for i in range(4):
        tr.record("s", float(i), float(i) + 0.5)
    assert tr.dropped == 0 and tr.evicted_t1 is None
    # out of order end times: the newest evicted end is kept, not the last
    tr.record("s", 10.0, 10.5)
    tr.record("s", 11.0, 11.5)
    assert tr.dropped == 2
    assert tr.evicted_t1 == 1.5
    assert [s.t0 for s in tr.spans()] == [2.0, 3.0, 10.0, 11.0]
    # totals outlive the ring
    assert tr.summary()["spans"]["s"] == {"count": 6, "seconds": 3.0}
    assert tr.summary()["dropped"] == 2


def test_counters_and_compiles_charged_to_the_innermost_span():
    tr = Tracer()
    tr.count("preemptions")
    tr.count("preemptions", 2)
    assert tr.summary()["counters"] == {"preemptions": 3}

    before = dict(TRACER.summary()["counters"])
    with TRACER.span("helix.test.outer"):
        with TRACER.span("helix.test.compile"):
            # a fresh function always compiles (or loads) a program
            jax.jit(lambda a: a * 3 + 1)(jnp.ones((7,))).block_until_ready()
    after = TRACER.summary()["counters"]
    assert after["compiles"] - before.get("compiles", 0) >= 1
    assert after["compiles.helix.test.compile"] \
        - before.get("compiles.helix.test.compile", 0) >= 1
    assert "compiles.helix.test.outer" not in after


# ---------------------------------------------------------------------------
# the served path


def test_runtime_span_tree_matches_served_requests(gqa_model, reference):
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    t0 = time.monotonic()
    rt, reqs = serve_on_cluster(cfg, params, p, prompts, paged=True)
    assert [r.output for r in reqs] == ref
    spans = since(t0)
    by_id = {s.id: s for s in spans}
    names = [s.name for s in spans]
    ids = {r.request_id for r in reqs}

    # one submit marker and one first admission per request, paired by
    # (request, submit time); nothing was preempted, so nothing resumed
    submits = {(s.attrs["request"], s.t0) for s in spans
               if s.name == "helix.request.submit"}
    queued = [s for s in spans if s.name == "helix.request.queued"]
    assert {(s.attrs["request"], s.t0) for s in queued} == submits
    assert sorted(s.attrs["request"] for s in queued) == sorted(ids)
    assert all(s.attrs["resumed"] == 0 and s.t1 >= s.t0 for s in queued)

    for name in ("helix.step", "helix.admit", "helix.deliver",
                 "helix.decode", "helix.sample", "helix.sync_kv",
                 "helix.engine.decode", "helix.engine.prefill"):
        assert name in names, name
    for s in spans:
        if s.name in ("helix.admit", "helix.decode", "helix.sample",
                      "helix.sync_kv"):
            assert by_id[s.parent].name == "helix.step"
        if s.name == "helix.engine.decode":
            assert by_id[s.parent].name == "helix.decode"
            assert s.attrs["rows"] == len(s.attrs["ctx"])
        if s.name in ENGINE_CHILDREN:
            parent = by_id[s.parent]
            assert parent.name in ("helix.engine.decode",
                                   "helix.engine.prefill")
            assert parent.t0 <= s.t0 <= s.t1 <= parent.t1
    for eng_name in ("helix.engine.decode", "helix.engine.prefill"):
        for s in spans:
            if s.name == eng_name:
                kids = sorted((c for c in spans if c.parent == s.id),
                              key=lambda c: c.t0)
                assert [c.name for c in kids] == [
                    "helix.engine.inputs", "helix.engine.launch",
                    "helix.engine.wait", "helix.engine.fetch"]
    # every prefill chunk names its request, and each engine numbers its
    # decode calls 0, 1, 2, ...
    assert {s.attrs["request"] for s in spans
            if s.name == "helix.engine.prefill"} == ids
    for eng in rt.engines.values():
        assert eng.decode_calls > 0
    calls = sorted(s.attrs["call"] for s in spans
                   if s.name == "helix.engine.decode")
    assert calls == sorted(c for e in rt.engines.values()
                           for c in range(e.decode_calls))


def test_node_decode_seconds_are_the_decode_spans(gqa_model):
    cfg, params = gqa_model
    prompts = random_prompts(cfg, (9, 14, 6), seed=3)
    p = make_plan(cfg, {"n0": (0, 1), "n1": (1, 4)})
    t0 = time.monotonic()
    rt, reqs = serve_on_cluster(cfg, params, p, prompts, paged=True,
                                max_inflight=2)
    decode = [s for s in since(t0) if s.name == "helix.decode"]
    for node in ("n0", "n1"):
        mine = [s for s in decode if s.attrs["node"] == node]
        assert rt.node_decode_s[node] == pytest.approx(
            sum(s.t1 - s.t0 for s in mine), rel=1e-12, abs=0)
        assert rt.node_decode_tokens[node] == sum(s.attrs["rows"]
                                                  for s in mine)


def test_preemptions_are_counted(gqa_model, reference):
    from repro.core import LayerRange
    from harness import pool_for_one_request
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 3), "n2": (3, 4)})
    small = pool_for_one_request(cfg, LayerRange(2, 3))
    before = TRACER.summary()["counters"].get("preemptions", 0)
    t0 = time.monotonic()
    rt, reqs = serve_on_cluster(cfg, params, p, prompts, paged=True,
                                pool_pages={"n1": small})
    n = sum(r.preemptions for r in reqs)
    assert n > 0
    assert TRACER.summary()["counters"]["preemptions"] - before == n
    resumed = [s for s in since(t0) if s.name == "helix.request.queued"
               and s.attrs["resumed"]]
    assert len(resumed) == n


# ---------------------------------------------------------------------------
# the front door


@pytest.fixture
def frontend(gqa_model):
    cfg, params = gqa_model
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 4)})
    rt = ClusterRuntime(cfg, params, p, EC, paged=True, realtime=True)
    fe = Frontend(rt, max_pending=8)
    host, port = fe.serve("127.0.0.1", 0)
    yield f"http://{host}:{port}"
    fe.shutdown(drain=True)
    rt.shutdown()
    assert fe.loop_error is None, f"runtime loop died: {fe.loop_error!r}"


def test_stream_writes_one_span_per_token(frontend):
    t0 = time.monotonic()
    req = urllib.request.Request(
        frontend + "/v1/completions",
        data=json.dumps({"prompt": [3, 1, 4, 1, 5], "max_tokens": 5,
                         "stream": True}).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    chunks = 0
    with urllib.request.urlopen(req, timeout=120) as resp:
        for raw in resp:
            line = raw.strip()
            if line == b"data: [DONE]":
                break
            if line.startswith(b"data: ") and \
                    json.loads(line[6:])["choices"][0].get("token_id") \
                    is not None:
                chunks += 1
    assert chunks == 5
    spans = since(t0)
    rid = [s.attrs["request"] for s in spans
           if s.name == "helix.request.submit"]
    assert len(rid) == 1
    writes = [s for s in spans if s.name == "helix.frontend.write"
              and s.attrs["request"] == rid[0]]
    assert len(writes) == 5
    assert all(s.t1 >= s.t0 for s in writes)


def test_healthz_carries_the_trace(frontend):
    body = json.dumps({"prompt": [2, 7, 1], "max_tokens": 2})
    req = urllib.request.Request(frontend + "/v1/completions",
                                 data=body.encode("utf-8"),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.status == 200
    with urllib.request.urlopen(frontend + "/healthz", timeout=30) as r:
        h = json.load(r)
    tr = h["trace"]
    assert set(tr) == {"counters", "spans", "dropped"}
    for name in ("helix.step", "helix.engine.decode", "helix.idle"):
        assert tr["spans"][name]["count"] > 0
        assert tr["spans"][name]["seconds"] >= 0
