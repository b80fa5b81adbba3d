"""copy_share.smollm-chat: device seconds of ``copy``, ``copy-start`` and
``copy-done`` operations over the traced window's busy seconds, in percent.

A pool-sized relayout shows here first: SmolLM-360M's KV pool kept in the
token view made XLA copy it whole for every decode step.  The record's
breakdown lists the ten operation kinds that took longest; a copy kind not
among them took less than the tenth and is read as 0."""
from typing import Optional

COPY_KINDS = ("copy", "copy-start", "copy-done")


def read(rec) -> Optional[float]:
    tr = rec.trace
    if not tr or tr["busy_s"] <= 0:
        return None
    secs = sum(s for kind, s in tr["breakdown"]["device_ops"]
               if kind in COPY_KINDS)
    return secs / tr["busy_s"] * 100
