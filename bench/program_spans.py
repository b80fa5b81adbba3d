"""Per-layer readers over the spans the program records itself
(``repro.serving.trace.TRACER``), for the ``metrics/<name>.py`` files whose
source is ``program_span``.

The tracer and ``Record.window`` share ``time.monotonic()``.  Each reader
takes the run's ``Record`` (and, for tests, a tracer) and returns None when
there is nothing to read: a program without the tracer, no span of its kind
in the window, or a tracer that dropped a span ending inside the window (its
ring evicts the oldest spans first, so ``evicted_t1 < w0`` means the window
is whole).
"""
from __future__ import annotations

from typing import Dict, List, Optional

from stats import percentile


def tracer():
    """The program's tracer, or None for a program that has none."""
    try:
        from repro.serving.trace import TRACER
    except ImportError:
        return None
    return TRACER


def whole_spans(rec, tr) -> Optional[List]:
    """Every span the tracer holds, or None when it holds none or has
    dropped one that ended inside the window."""
    if tr is None:
        return None
    spans = tr.spans()
    if not spans:
        return None
    if tr.dropped and tr.evicted_t1 is not None \
            and tr.evicted_t1 >= rec.window[0]:
        return None
    return spans


def clipped(sp, window) -> float:
    """Seconds of ``sp`` inside ``window``."""
    return max(0.0, min(sp.t1, window[1]) - max(sp.t0, window[0]))


def self_time(spans, outer: str, inner: str, window) -> float:
    """Seconds inside ``window`` spent in ``outer`` spans but not in their
    descendants named ``inner``."""
    by_id: Dict[int, object] = {s.id: s for s in spans}

    def under_outer(s) -> bool:
        p = by_id.get(s.parent)
        while p is not None:
            if p.name == outer:
                return True
            p = by_id.get(p.parent)
        return False

    total = sum(clipped(s, window) for s in spans if s.name == outer)
    return total - sum(clipped(s, window) for s in spans
                       if s.name == inner and under_outer(s))


def host_share(rec, tr=None) -> Optional[float]:
    """Share of the window the runtime's loop spent in its steps but not
    waiting for the device (``helix.step`` less its ``helix.engine.wait``),
    in percent."""
    spans = whole_spans(rec, tracer() if tr is None else tr)
    if spans is None or not any(s.name == "helix.step" for s in spans):
        return None
    w0, w1 = rec.window
    own = self_time(spans, "helix.step", "helix.engine.wait", rec.window)
    return own / (w1 - w0) * 100


def queue_wait_p90_ms(rec, tr=None) -> Optional[float]:
    """90th percentile of the time from ``submit()`` to the first
    admission, over requests submitted in the window; a request never
    admitted waits until the tracer's last span ends."""
    spans = whole_spans(rec, tracer() if tr is None else tr)
    if spans is None:
        return None
    w0, w1 = rec.window
    admitted = {(s.attrs["request"], s.t0): s.t1 for s in spans
                if s.name == "helix.request.queued"
                and not s.attrs.get("resumed")}
    last = max(s.t1 for s in spans)
    waits = [admitted.get((s.attrs["request"], s.t0), last) - s.t0
             for s in spans
             if s.name == "helix.request.submit" and w0 <= s.t0 < w1]
    p = percentile(waits, 90)
    return None if p is None else p * 1e3


def stream_lag_p90_ms(rec, tr=None) -> Optional[float]:
    """90th percentile of the time from a token's confirmation to the
    flush of its SSE chunk, over tokens confirmed in the window."""
    spans = whole_spans(rec, tracer() if tr is None else tr)
    if spans is None:
        return None
    w0, w1 = rec.window
    p = percentile([s.t1 - s.t0 for s in spans
                    if s.name == "helix.frontend.write" and w0 <= s.t0 < w1],
                   90)
    return None if p is None else p * 1e3
