"""The reduction from a profiler trace to device numbers: on intervals
worked by hand, and on a small trace recorded on a TPU v5e (one prefill
chunk and two decode calls of OLMo-1B's served path, max_batch 2,
annotated as the harness annotates them), committed beside this file."""
import gzip
import shutil
from pathlib import Path

import pytest

import xplane

TRACE = Path(__file__).resolve().parent / "small_trace.xplane.pb.gz"


def test_union_and_gaps():
    ops = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (35, 36, "d")]
    assert xplane.union([(s, e) for s, e, _ in ops]) == [(0, 20), (30, 40)]
    assert xplane.busy_ns(ops) == 30
    assert xplane.gaps(ops, (-5, 50)) == [(-5, 0), (20, 30), (40, 50)]


def test_op_kind():
    assert xplane.op_kind("%paged_attention_op.22 = bf16[33,16,128]{2,1,0} "
                          "custom-call(s32[33,128] %fusion.3)") == \
        "paged_attention_op"
    assert xplane.op_kind("%fusion.568 = bf16[33,50304] fusion(...)") == \
        "fusion"
    assert xplane.op_kind("%copy-start.32.1 = (s32[33]) copy-start()") == \
        "copy-start"


def test_gaps_are_named_by_the_innermost_span():
    spans = [(0, 100, "bench.step", {}), (10, 50, "bench.decode_stage",
                                           {"call": 0}),
             (60, 90, "bench.prefill_chunk", {"call": 0})]
    idx = xplane.SpanIndex(spans)
    assert idx.at(5) == "bench.step"
    assert idx.at(20) == "bench.decode_stage"
    assert idx.at(70) == "bench.prefill_chunk"
    assert idx.at(150) == xplane.HOST_OTHER
    # idle: (0, 12) in the step, (20, 30) in the decode call, (50, 100)
    # named by its midpoint 75, inside the prefill chunk
    tr = xplane.Trace({"/device:TPU:0": [(12, 20, "%paged_attention_op.1 = "
                                                  "x"),
                                         (30, 50, "%fusion.1 = y")]}, spans)
    idle = dict(xplane.idle_by_host(tr, "/device:TPU:0", (0, 100)))
    assert idle == pytest.approx({"bench.step": 12e-9,
                                  "bench.decode_stage": 10e-9,
                                  "bench.prefill_chunk": 50e-9})
    assert xplane.kernel_ns_by_call(tr, "/device:TPU:0",
                                    "paged_attention_op",
                                    "bench.decode_stage") == {0: 8}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(TRACE, "rb") as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return xplane.Trace.load(str(path))


def test_recorded_trace(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    ops = recorded.ops["/device:TPU:0"]
    names = [n for s, e, n, st in recorded.spans]
    assert names.count("bench.decode_stage") == 2
    assert names.count("bench.prefill_chunk") == 1
    lo, hi = recorded.extent()
    busy = xplane.busy_ns(ops)
    assert 0 < busy < hi - lo
    # every one of the 16 layers runs the decode kernel once per call
    per_call = xplane.kernel_ns_by_call(recorded, "/device:TPU:0",
                                        "paged_attention_op",
                                        "bench.decode_stage")
    kernel_ops = [o for o in ops
                  if xplane.op_kind(o[2]) == "paged_attention_op"]
    # calls 1 and 2 of the recording; call 0 ran before the trace started
    assert sorted(per_call) == [1, 2] and len(kernel_ops) == 32
    assert sum(per_call.values()) == pytest.approx(
        sum(e - s for s, e, _ in kernel_ops))
    # idle time splits over what the host did; it adds up to the gaps
    idle = xplane.idle_by_host(recorded, "/device:TPU:0", (lo, hi))
    assert sum(v for _, v in idle) == pytest.approx(
        (hi - lo - busy) / 1e9)
    assert {k for k, _ in idle} <= set(xplane.SPAN_ORDER) | {
        xplane.HOST_OTHER}
    top = xplane.top_ops(ops)
    assert len(top) <= 10 and top[0][1] >= top[-1][1] > 0
