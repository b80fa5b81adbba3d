"""Transport chaos + wire-format tests.

Three layers of hardening for the PR-5 transport work:

1. **Chaos suite** — ``FlakyTransport`` is a Transport double that delays,
   reorders, duplicates, and drops-then-retransmits every payload on the
   runtime's virtual clock.  The differential anchor must hold anyway:
   greedy output byte-identical to a single full-model engine at in-flight
   depths 1-3, and every page pool drained to zero.  This pins down the
   runtime's delivery guards (dedup keys, per-stage chunk ordering, the
   coordinator inbox).

2. **Wire format** — round-trip property tests for
   ``encode_payload``/``decode_payload`` (bit-exact arrays across dtypes
   and ranks, nested trees) and the guarantee that malformed or truncated
   frames *raise* ``FrameError`` instead of hanging or mis-decoding.

3. **Backpressure** — a ``SocketTransport`` link to a worker that stops
   acking must block senders at the bounded queue (never buffer
   unboundedly), raise ``TransportStalled`` naming the link once the send
   timeout passes, and surface the stalled link through
   ``ClusterRuntime._state()`` diagnostics.
"""
import dataclasses
import socket
import threading
import time

import numpy as np
import pytest

import jax

from repro.core import LayerRange
from repro.launch.worker import serve_connection
from repro.serving import (ClusterRuntime, EngineConfig, FrameError,
                           InProcessTransport, PagedStageEngine,
                           RemoteStageEngine, SocketTransport, StagedRef,
                           TransportStalled, WorkerChannel, decode_payload,
                           encode_payload, payload_bytes, recv_frame,
                           send_frame)

from harness import (EC, assert_serves_like_reference, decode_rows,
                     make_disagg_plan, make_plan, prefill_slots,
                     random_prompts)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # only the property tests skip
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# chaos transport double
# ---------------------------------------------------------------------------

class FlakyTransport(InProcessTransport):
    """Delivers every payload at least once, but badly: random per-message
    jitter (reordering), random duplication, and random first-copy drops
    followed by a retransmission after a retry timeout.  Legal under the
    Transport contract ('send must eventually deliver'); never produced by
    the FIFO InProcessTransport."""

    def __init__(self, seed: int = 0, *, base_delay_s: float = 1e-3,
                 jitter_s: float = 4e-3, dup_p: float = 0.25,
                 drop_p: float = 0.25, retry_s: float = 8e-3):
        super().__init__(default_delay_s=base_delay_s)
        self._chaos_rng = np.random.RandomState(seed)
        self.jitter_s = jitter_s
        self.dup_p = dup_p
        self.drop_p = drop_p
        self.retry_s = retry_s
        self.duplicated = 0
        self.dropped = 0

    def send(self, src, dst, payload, nbytes, deliver):
        self.transfers[(src, dst)] += 1
        rng = self._chaos_rng
        d = self.delay(src, dst, nbytes) + rng.uniform(0.0, self.jitter_s)
        if rng.rand() < self.drop_p:
            # first copy lost on the wire; the link retransmits
            self.dropped += 1
            d += self.retry_s
        self._schedule(d, lambda: deliver(payload))
        if rng.rand() < self.dup_p:
            self.duplicated += 1
            self._schedule(d + rng.uniform(0.0, self.jitter_s),
                           lambda: deliver(payload))


# prompt_len=4 forces multi-chunk prefill (the session prompts are 5-16
# tokens), so chunk reordering across stage hops is actually exercised
CHAOS_EC = dataclasses.replace(EC, prompt_len=4)


@pytest.mark.parametrize("paged,depth",
                         [(True, 1), (True, 2), (True, 3), (False, 3)],
                         ids=["paged-d1", "paged-d2", "paged-d3",
                              "dense-d3"])
def test_chaos_transport_keeps_outputs_identical(gqa_model, reference,
                                                 paged, depth):
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 3), "n2": (3, 4)})
    tr = FlakyTransport(seed=17 * depth + paged)
    assert_serves_like_reference(cfg, params, p, prompts, ref, paged=paged,
                                 max_inflight=depth, ec=CHAOS_EC,
                                 transport=tr)
    # the chaos must actually have happened for the run to mean anything
    assert tr.duplicated > 0 and tr.dropped > 0


@pytest.mark.parametrize("paged,depth", [(True, 1), (True, 2), (False, 2)],
                         ids=["paged-d1", "paged-d2", "dense-d2"])
def test_chaos_disaggregated_keeps_outputs_identical(gqa_model, reference,
                                                     paged, depth):
    """Chaos over the disaggregated dataflow: the prefill->decode KV
    handoff payloads are delayed, reordered, duplicated, and dropped (then
    retransmitted) along with everything else.  The handoff dedup key +
    the kv_pending launch gate must keep outputs byte-identical — a
    duplicated handoff may not double-import, a delayed one may not let
    decode start on an empty cache."""
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_disagg_plan(cfg, {"n0": (0, 4)}, {"n1": (0, 2), "n2": (2, 4)})
    tr = FlakyTransport(seed=29 * depth + paged)
    rt = assert_serves_like_reference(cfg, params, p, prompts, ref,
                                      paged=paged, max_inflight=depth,
                                      ec=CHAOS_EC, transport=tr)
    assert rt.disaggregated
    assert tr.duplicated > 0 and tr.dropped > 0
    assert tr.transfers[("n0", "n1")] >= len(prompts)   # handoffs happened


def test_chaos_transport_with_preemption(gqa_model, reference):
    """Chaos + a pool that only fits one full-budget request: preemption's
    epoch bumps and the delivery guards must compose (dedup state resets on
    readmission, stale duplicates die on the epoch check)."""
    from harness import (assert_pools_drained, pool_for_one_request,
                        serve_on_cluster)
    from repro.core import LayerRange
    cfg, params = gqa_model
    prompts, ref = reference
    p = make_plan(cfg, {"n0": (0, 2), "n1": (2, 3), "n2": (3, 4)})
    small = pool_for_one_request(cfg, LayerRange(2, 3), ec=CHAOS_EC)
    rt, reqs = serve_on_cluster(cfg, params, p, prompts, paged=True,
                                max_inflight=2, ec=CHAOS_EC,
                                pool_pages={"n1": small},
                                transport=FlakyTransport(seed=5))
    assert [r.output for r in reqs] == ref
    assert any(r.preemptions > 0 for r in reqs)
    assert_pools_drained(rt)


# ---------------------------------------------------------------------------
# wire format: fixed cases
# ---------------------------------------------------------------------------

def _roundtrip(obj):
    return decode_payload(payload_bytes(obj))


def test_wire_roundtrip_scalars_and_trees():
    cases = [
        None, True, False, 0, -1, 1 << 40, 3.5, float("inf"), "",
        "tøkens", b"\x00\xff", (), [], {},
        ("prefill_stage", [3, StagedRef(7), 0]),
        {"cfg": {"layers": (0, 4), "paged": True}, "xs": [1, 2.0, None]},
    ]
    for obj in cases:
        got = _roundtrip(obj)
        assert got == obj and type(got) is type(obj), obj
    # NaN compares unequal to itself
    assert np.isnan(_roundtrip(float("nan")))
    # numpy scalars normalize to python scalars
    assert _roundtrip(np.int32(-7)) == -7
    assert _roundtrip(np.float64(2.5)) == 2.5
    assert _roundtrip(np.bool_(True)) is True


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
@pytest.mark.parametrize("shape", [(), (0,), (5,), (3, 4), (2, 1, 4),
                                   (2, 3, 2, 2)])
def test_wire_roundtrip_arrays_bit_exact(dtype, shape):
    rng = np.random.RandomState(hash((dtype, shape)) % (1 << 31))
    arr = np.asarray(rng.standard_normal(size=shape)).astype(np.dtype(dtype)) \
        if dtype != "int32" \
        else rng.randint(-2**31, 2**31 - 1, size=shape, dtype=np.int32)
    got = _roundtrip(arr)
    assert got.dtype == arr.dtype and got.shape == arr.shape
    assert got.tobytes() == arr.tobytes()          # bit-exact, NaNs included


def test_wire_roundtrip_scratch_padded_batch():
    """The shapes the runtime actually ships: scratch-row-padded decode
    activations (max_batch+1, 1, d) in bf16 and a token chunk."""
    bf16 = np.dtype("bfloat16")
    h = np.random.RandomState(0).randn(EC.max_batch + 1, 1, 64).astype(bf16)
    toks = np.arange(13, dtype=np.int32)
    items = [(2, 17, 0, 0, h), (0, 3, 2, 441, None)]
    got = decode_payload(payload_bytes(("decode_stage", [items, toks])))
    m, (gi, gt) = got
    assert m == "decode_stage"
    assert gi[0][4].tobytes() == h.tobytes() and gi[0][4].dtype == bf16
    assert gi[1][4] is None
    assert np.array_equal(gt, toks)


def test_wire_normalizes_byte_order():
    """dtype names drop endianness, so a big-endian array must be swapped
    to the little-endian wire layout on encode — not silently reinterpreted
    on decode."""
    be = np.array([1.0, 2.0, -3.5], dtype=">f8")
    got = _roundtrip(be)
    assert np.array_equal(got, be.astype("<f8"))
    assert np.array_equal(_roundtrip(np.array([7, -9], dtype=">i4")),
                          np.array([7, -9], np.int32))


def test_wire_rejects_malformed():
    with pytest.raises(FrameError):
        decode_payload(b"")                        # no tag at all
    with pytest.raises(FrameError):
        decode_payload(b"Z")                       # unknown tag
    with pytest.raises(FrameError):
        decode_payload(payload_bytes(7) + b"x")    # trailing garbage
    with pytest.raises(FrameError):
        encode_payload(object())                   # unserializable
    # array whose header promises more bytes than shape*itemsize
    body = payload_bytes(np.zeros(4, np.float32))
    corrupt = bytearray(body)
    corrupt[-17] ^= 0xFF                           # flip a length byte
    with pytest.raises(FrameError):
        decode_payload(bytes(corrupt))


def test_wire_truncation_always_raises():
    payloads = [
        {"a": [1, 2.5, "x"], "b": np.arange(6, dtype=np.int32)},
        ("stage", [9, np.zeros((2, 3), np.dtype("bfloat16"))]),
        [None, True, b"bytes", StagedRef(3)],
    ]
    for obj in payloads:
        frame = payload_bytes(obj)
        for cut in range(len(frame)):
            with pytest.raises(FrameError):
                decode_payload(frame[:cut])


def test_frame_layer_rejects_bad_magic_and_truncation():
    a, b = socket.socketpair()
    try:
        a.sendall(b"GARBAGE!")                     # exactly one header
        with pytest.raises(FrameError, match="magic"):
            recv_frame(b)
    finally:
        a.close()
        b.close()
    # a peer that dies mid-frame raises instead of hanging
    a, b = socket.socketpair()
    try:
        send_frame(a, encode_payload(np.arange(100)))
        a.close()                                  # frame fully buffered...
        b2, c = socket.socketpair()
        try:
            # ...so replay only a prefix of it to a fresh reader
            whole = b.recv(1 << 16)
            b2.sendall(whole[:40])
            b2.close()
            with pytest.raises(FrameError, match="closed mid-frame"):
                recv_frame(c)
        finally:
            b2.close()
            c.close()
    finally:
        a.close()
        b.close()


def test_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        obj = {"h": np.random.RandomState(1).randn(2, 5).astype(np.float32),
               "meta": ("ok", [1, 2, 3])}
        send_frame(a, encode_payload(obj))
        got = decode_payload(recv_frame(b))
        assert got["meta"] == obj["meta"]
        assert np.array_equal(got["h"], obj["h"])
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# wire format: hypothesis round-trip property
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    _dtypes = st.sampled_from(["float32", "bfloat16", "int32"])
    _shapes = st.lists(st.integers(0, 4), min_size=0, max_size=4)

    @st.composite
    def _arrays(draw):
        dtype = np.dtype(draw(_dtypes))
        shape = tuple(draw(_shapes))
        seed = draw(st.integers(0, 2**16))
        rng = np.random.RandomState(seed)
        if dtype.kind == "i":
            return rng.randint(-2**31, 2**31 - 1, size=shape,
                               dtype=np.int32)
        scale = 10.0 ** rng.randint(-3, 4)
        return np.asarray(rng.standard_normal(size=shape) * scale,
                          dtype=dtype)

    _leaves = st.one_of(
        st.none(), st.booleans(), st.integers(-2**62, 2**62), st.floats(
            allow_nan=False), st.text(max_size=20),
        st.binary(max_size=32),
        st.builds(StagedRef, st.integers(0, 2**40)), _arrays())

    _payloads = st.recursive(
        _leaves,
        lambda inner: st.one_of(
            st.lists(inner, max_size=4),
            st.lists(inner, max_size=4).map(tuple),
            st.dictionaries(st.text(max_size=8), inner, max_size=4)),
        max_leaves=12)

    def _eq(a, b):
        if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
            return (isinstance(a, np.ndarray) and isinstance(b, np.ndarray)
                    and a.dtype == b.dtype and a.shape == b.shape
                    and a.tobytes() == b.tobytes())
        if isinstance(a, (list, tuple)):
            return (type(a) is type(b) and len(a) == len(b)
                    and all(_eq(x, y) for x, y in zip(a, b)))
        if isinstance(a, dict):
            return (isinstance(b, dict) and a.keys() == b.keys()
                    and all(_eq(a[k], b[k]) for k in a))
        return type(a) is type(b) and a == b

    @settings(max_examples=120, deadline=None)
    @given(obj=_payloads)
    def test_property_wire_roundtrip_bit_exact(obj):
        assert _eq(decode_payload(payload_bytes(obj)), obj)

    @settings(max_examples=60, deadline=None)
    @given(obj=_payloads, data=st.data())
    def test_property_truncated_frames_raise(obj, data):
        frame = payload_bytes(obj)
        cut = data.draw(st.integers(0, max(0, len(frame) - 1)),
                        label="cut")
        with pytest.raises(FrameError):
            decode_payload(frame[:cut])


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------

def _silent_worker_link(node="n9", *, queue_depth=2, send_timeout_s=0.5):
    """A SocketTransport wired to a 'worker' that never acks: the pump
    thread wedges in its first staging call and the bounded queue backs
    up."""
    a, b = socket.socketpair()
    ch = WorkerChannel(a, node=node, timeout_s=60.0)
    tr = SocketTransport({node: ch}, queue_depth=queue_depth,
                         send_timeout_s=send_timeout_s,
                         stalled_after_s=0.05)
    tr.bind(lambda d, fn: fn())
    return tr, b


def test_socket_backpressure_blocks_and_reports():
    tr, peer = _silent_worker_link()
    delivered = []
    payload = np.zeros(4096, np.float32)
    try:
        # first send wedges the pump in the unacked stage call; the next
        # queue_depth sends fill the bounded queue
        for _ in range(1 + tr.queue_depth):
            tr.send("c", "n9", payload, payload.nbytes, delivered.append)
        deadline = time.monotonic() + 5.0
        while ("c", "n9") not in tr._busy_since:
            assert time.monotonic() < deadline, "pump never started"
            time.sleep(0.01)
        # memory stays bounded at the queue depth
        assert tr._queues[("c", "n9")].qsize() <= tr.queue_depth
        t0 = time.monotonic()
        with pytest.raises(TransportStalled, match=r"c->n9"):
            tr.send("c", "n9", payload, payload.nbytes, delivered.append)
        # the sender genuinely blocked for the timeout before raising
        assert time.monotonic() - t0 >= tr.send_timeout_s * 0.9
        assert delivered == []                     # nothing faked through
        desc = tr.describe()
        assert "c->n9" in desc and "STALLED" in desc
    finally:
        tr.close()
        peer.close()


def test_runtime_state_reports_stalled_link(gqa_model):
    """run_until_done's stall diagnostics must name the wedged link: the
    _state() string carries the transport's per-link report."""
    cfg, params = gqa_model
    tr, peer = _silent_worker_link()
    try:
        p = make_plan(cfg, {"n0": (0, 4)})
        rt = ClusterRuntime(cfg, params, p, EC, paged=False, transport=tr,
                            stall_timeout_s=0.1)
        payload = np.zeros(16, np.float32)
        for _ in range(1 + tr.queue_depth):
            tr.send("c", "n9", payload, payload.nbytes, lambda x: None)
        deadline = time.monotonic() + 5.0
        while ("c", "n9") not in tr._busy_since:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        time.sleep(0.1)                            # exceed stalled_after_s
        state = rt._state()
        assert "c->n9" in state and "STALLED" in state
    finally:
        tr.close()
        peer.close()


def test_socket_transport_delivers_after_ack():
    """Happy path: a peer that acks staging frames gets payloads staged
    once and the runtime-side delivery is the StagedRef handle; scalars
    deliver by value."""
    a, b = socket.socketpair()
    ch = WorkerChannel(a, node="n0", timeout_s=10.0)
    tr = SocketTransport({"n0": ch}, queue_depth=4)
    got = []
    tr.bind(lambda d, fn: fn())
    staged = {}

    def fake_worker():
        while True:
            try:
                method, args = decode_payload(recv_frame(b))
            except FrameError:
                return
            assert method == "stage"
            staged[args[0]] = args[1]
            send_frame(b, encode_payload(("ok", None)))

    t = threading.Thread(target=fake_worker, daemon=True)
    t.start()
    try:
        arr = np.arange(12, dtype=np.float32).reshape(3, 4)
        tr.send("c", "n0", arr, arr.nbytes, got.append)
        tr.send("n0", "c", (3, 1234), 8.0, got.append)     # scalar: by value
        deadline = time.monotonic() + 10.0
        while len(got) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(got) == 2
        ref = next(g for g in got if isinstance(g, StagedRef))
        val = next(g for g in got if not isinstance(g, StagedRef))
        assert np.array_equal(staged[ref.tag], arr)
        assert val == (3, 1234)
    finally:
        tr.close()
        b.close()


def test_remote_engine_returns_local_tokens(gqa_model):
    """A stage engine behind a worker connection answers as the same
    engine in process: greedy rows get the worker's device argmax and no
    logits over the wire, rows at temperature > 0 get logits the proxy's
    RNG samples exactly as the local engine's RNG does."""
    cfg, params = gqa_model
    ec = EngineConfig(max_batch=3, max_len=48, prompt_len=16)
    temps = (0.0, 0.7, 0.0)
    a, b = socket.socketpair()
    worker = threading.Thread(target=serve_connection, args=(b,),
                              daemon=True)
    worker.start()
    remote = RemoteStageEngine(WorkerChannel(a, "n0"), "n0", rng_seed=3)
    try:
        remote.channel.call("init", {
            "node": "n0", "cfg": dataclasses.asdict(cfg),
            "ec": dataclasses.asdict(ec), "layers": (0, cfg.num_layers),
            "params": jax.tree.map(np.asarray, params), "paged": True,
            "num_pages": None, "page_size": 16, "kv_dtype": None,
            "rng_seed": 3})
        local = PagedStageEngine(cfg, params, LayerRange(0, cfg.num_layers),
                                 ec, page_size=16, rng_seed=3)
        prompts = random_prompts(cfg, (16, 16, 32), seed=2)
        runs = []
        for eng in (local, remote):
            toks = prefill_slots(eng, prompts, paged=True,
                                 chunk=ec.prompt_len, budget=40)
            seen = [toks]
            for t in range(3):
                outs = decode_rows(eng, prompts, seen[-1], t,
                                   [T > 0 for T in temps])
                assert [o.logits is not None for o in outs] == \
                    [T > 0 for T in temps]
                assert all(o.h is None for o in outs)
                seen.append([o.token if T <= 0 else eng.sample(o.logits, T)
                             for o, T in zip(outs, temps)])
            runs.append(seen)
        assert runs[0] == runs[1]
    finally:
        remote.close()
        worker.join(10)
        b.close()
    assert not worker.is_alive()
