"""decode_result_ms.batch: ms per decode step spent bringing a step's result
to the runtime: the summed time of the ``helix.engine.fetch`` spans whose
parent is a ``helix.engine.decode`` (prefill's fetches are not counted)
and of the ``helix.sample`` spans, over the ``helix.engine.decode`` spans
that end in the window (``program_spans`` conventions: nothing to read
without the tracer, or after a dropped span inside the window)."""
from typing import Optional

from program_spans import tracer, whole_spans


def read(rec, tr=None) -> Optional[float]:
    spans = whole_spans(rec, tracer() if tr is None else tr)
    if spans is None:
        return None
    w0, w1 = rec.window
    steps = {s.id for s in spans
             if s.name == "helix.engine.decode" and w0 <= s.t1 < w1}
    if not steps:
        return None
    secs = sum(s.t1 - s.t0 for s in spans
               if (s.name == "helix.engine.fetch" and s.parent in steps)
               or (s.name == "helix.sample" and w0 <= s.t1 < w1))
    return secs / len(steps) * 1e3
