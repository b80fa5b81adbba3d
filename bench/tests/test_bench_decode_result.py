"""The ``decode_result_ms.*`` readers on hand-made spans: a decode step's
fetch and the sampling after it, per decode step ending in the window;
prefill's fetches left out; silence without the program's tracer or after
a dropped span inside the window."""
from types import SimpleNamespace

import pytest

import program_spans
import run
from repro.serving.trace import Tracer

WINDOW = (10.0, 20.0)
REC = SimpleNamespace(window=WINDOW)
NAMES = ["decode_result_ms.batch", "decode_result_ms.chat",
         "decode_result_ms.smollm-chat"]


class Clock:
    t = 0.0

    def __call__(self) -> float:
        return self.t


def nest(tr, clk, name, t0, t1, *kids):
    """Span ``name`` from ``t0`` to ``t1`` with ``kids`` nested inside."""
    clk.t = t0
    with tr.span(name):
        for k in kids:
            nest(tr, clk, *k)
        clk.t = t1


def step(tr, clk, t0, fetch_s, sample_s):
    """One decode call as the runtime makes it: the engine's step, whose
    fetch takes ``fetch_s``, then the sampling, ``sample_s``."""
    nest(tr, clk, "helix.decode", t0, t0 + 0.01 + sample_s,
         ("helix.engine.decode", t0, t0 + 0.01,
          ("helix.engine.wait", t0, t0 + 0.01 - fetch_s),
          ("helix.engine.fetch", t0 + 0.01 - fetch_s, t0 + 0.01)),
         ("helix.sample", t0 + 0.01, t0 + 0.01 + sample_s))


def reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py").read


@pytest.mark.parametrize("name", NAMES)
def test_fetch_and_sampling_per_decode_step(name):
    clk = Clock()
    tr = Tracer(clock=clk)
    step(tr, clk, 9.0, 0.004, 0.004)        # before the window
    step(tr, clk, 11.0, 0.002, 0.001)
    step(tr, clk, 12.0, 0.003, 0.002)
    # a prefill's fetch is not a decode step's result
    nest(tr, clk, "helix.engine.prefill", 13.0, 13.5,
         ("helix.engine.fetch", 13.2, 13.5))
    step(tr, clk, 25.0, 0.004, 0.004)       # after it
    assert reader(name)(REC, tr) == pytest.approx((0.003 + 0.005) / 2 * 1e3)


@pytest.mark.parametrize("name", NAMES)
def test_silent_without_a_whole_window(name, monkeypatch):
    read = reader(name)
    clk = Clock()
    tr = Tracer(capacity=4, clock=clk)
    assert read(REC, tr) is None            # nothing recorded
    step(tr, clk, 11.0, 0.002, 0.001)
    step(tr, clk, 12.0, 0.002, 0.001)       # evicts a span of the window
    assert tr.evicted_t1 >= WINDOW[0]
    assert read(REC, tr) is None
    monkeypatch.setattr(program_spans, "tracer", lambda: None)
    assert reader(name)(REC) is None        # a program with no tracer
