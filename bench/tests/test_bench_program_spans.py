"""The readers of the program's own spans (``program_spans.py``) on
hand-made spans: window clipping, self time, p90, and silence when the
tracer dropped a span inside the window or the program has no tracer."""
from types import SimpleNamespace

import pytest

import program_spans
import run
from repro.serving.trace import Tracer
from stats import percentile

WINDOW = (10.0, 20.0)
REC = SimpleNamespace(window=WINDOW)


class Clock:
    t = 0.0

    def __call__(self) -> float:
        return self.t


def tracer(capacity: int = 1024):
    clk = Clock()
    return Tracer(capacity=capacity, clock=clk), clk


def nest(tr, clk, name, t0, t1, *kids):
    """Span ``name`` from ``t0`` to ``t1`` with ``kids`` nested inside."""
    clk.t = t0
    with tr.span(name):
        for k in kids:
            nest(tr, clk, *k)
        clk.t = t1


def test_host_share_clips_to_the_window_and_takes_self_time():
    tr, clk = tracer()
    # straddles the window's start: 2 s inside, of which the wait is 1.5
    nest(tr, clk, "helix.step", 8.0, 12.0,
         ("helix.engine.decode", 8.5, 11.8,
          ("helix.engine.wait", 9.0, 11.5)))
    # the wait sits two levels down: 2 s less 0.4
    nest(tr, clk, "helix.step", 14.0, 16.0,
         ("helix.decode", 14.5, 15.5,
          ("helix.engine.decode", 14.6, 15.4,
           ("helix.engine.launch", 14.6, 15.0),
           ("helix.engine.wait", 15.0, 15.4))))
    # outside any step (a delivery run from the idle wait): not counted,
    # and its wait is not taken off a step
    nest(tr, clk, "helix.engine.prefill", 17.0, 18.0,
         ("helix.engine.wait", 17.2, 17.9))
    nest(tr, clk, "helix.idle", 18.0, 19.0)
    # straddles the window's end: 1 s inside
    nest(tr, clk, "helix.step", 19.0, 22.0)
    assert program_spans.self_time(tr.spans(), "helix.step",
                                   "helix.engine.wait", WINDOW) \
        == pytest.approx(3.1)
    assert program_spans.host_share(REC, tr) == pytest.approx(31.0)


def test_queue_wait_p90_pairs_submit_with_first_admission():
    tr, clk = tracer()
    tr.record("helix.request.submit", 9.0, 9.0, request=0)   # before
    tr.record("helix.request.queued", 9.0, 9.5, request=0, resumed=0)
    waits = {}
    for rid in range(1, 11):
        t = 10.0 + 0.5 * rid
        waits[rid] = 0.01 * rid
        tr.record("helix.request.submit", t, t, request=rid)
        tr.record("helix.request.queued", t, t + waits[rid], request=rid,
                  resumed=0)
    # a preempted request's readmission does not count again
    tr.record("helix.request.queued", 16.0, 19.0, request=3, resumed=1)
    # a second runtime reusing request id 4: a different submit time
    tr.record("helix.request.queued", 30.0, 31.0, request=4, resumed=0)
    # never admitted: waits until the tracer's last span ends (31.0)
    tr.record("helix.request.submit", 19.5, 19.5, request=99)
    want = percentile(list(waits.values()) + [31.0 - 19.5], 90) * 1e3
    assert program_spans.queue_wait_p90_ms(REC, tr) == pytest.approx(want)


def test_stream_lag_p90_over_tokens_confirmed_in_the_window():
    tr, clk = tracer()
    tr.record("helix.frontend.write", 9.99, 10.5, request=0)  # before
    lags = [0.001 * i for i in range(1, 21)]
    for i, lag in enumerate(lags):
        t = 10.0 + 0.4 * i
        tr.record("helix.frontend.write", t, t + lag, request=1)
    tr.record("helix.frontend.write", 20.0, 20.5, request=2)  # after
    assert program_spans.stream_lag_p90_ms(REC, tr) == pytest.approx(
        percentile(lags, 90) * 1e3)


READERS = [program_spans.host_share, program_spans.queue_wait_p90_ms,
           program_spans.stream_lag_p90_ms]


def fill(tr, clk, t0: float) -> None:
    """One of each span the readers read, starting at ``t0``."""
    nest(tr, clk, "helix.step", t0, t0 + 1.0,
         ("helix.engine.wait", t0 + 0.2, t0 + 0.6))
    tr.record("helix.request.submit", t0, t0, request=1)
    tr.record("helix.request.queued", t0, t0 + 0.03, request=1, resumed=0)
    tr.record("helix.frontend.write", t0, t0 + 0.002, request=1)


@pytest.mark.parametrize("read", READERS, ids=lambda r: r.__name__)
def test_silent_after_a_drop_inside_the_window(read):
    tr, clk = tracer(capacity=8)
    for t in (1.0, 3.0, 5.0):            # evicted, all before the window
        fill(tr, clk, t)
    fill(tr, clk, 12.0)
    assert tr.dropped > 0 and tr.evicted_t1 < WINDOW[0]
    assert read(REC, tr) > 0
    fill(tr, clk, 14.0)                  # evicts spans of 12.0: in-window
    assert tr.evicted_t1 >= WINDOW[0]
    assert read(REC, tr) is None


@pytest.mark.parametrize("read", READERS, ids=lambda r: r.__name__)
def test_silent_without_the_programs_tracer(monkeypatch, read):
    monkeypatch.setattr(program_spans, "tracer", lambda: None)
    assert read(REC) is None
    tr, clk = tracer()
    assert read(REC, tr) is None         # a tracer holding nothing


@pytest.mark.parametrize("name,reader", [
    ("host_share.chat", "host_share"), ("host_share.batch", "host_share"),
    ("queue_wait_p90_ms.chat", "queue_wait_p90_ms"),
    ("stream_lag_p90_ms.chat", "stream_lag_p90_ms")])
def test_metric_files_bind_the_readers(name, reader):
    mod = run.load_module(run.BENCH / "metrics" / f"{name}.py")
    assert mod.read is getattr(program_spans, reader)
