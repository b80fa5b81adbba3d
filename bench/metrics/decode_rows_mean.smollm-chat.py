"""decode_rows_mean.smollm-chat: mean ``rows`` attr of the program's
``helix.engine.decode`` spans that end in the window: the requests each
decode step carries (``program_spans`` conventions: nothing to read without
the tracer, or after a dropped span inside the window)."""
from typing import Optional

from program_spans import tracer, whole_spans
from stats import mean


def read(rec, tr=None) -> Optional[float]:
    spans = whole_spans(rec, tracer() if tr is None else tr)
    if spans is None:
        return None
    w0, w1 = rec.window
    return mean([s.attrs["rows"] for s in spans
                 if s.name == "helix.engine.decode" and w0 <= s.t1 < w1])
