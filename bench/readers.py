"""What the per-layer metric files read.  Each ``metrics/<name>.py`` binds
``read`` to one of these; a reader returns None where the run holds
nothing for it to read, and the harness then leaves the metric out.

A reader gets the run's ``Record`` (see ``run.py``): the configuration and
traffic files, the measured window, the load generator's request records,
the benchmark's host spans around the program's calls, the program's
counters at the window edges, the reduced device trace (traced runs only),
and the chip's peaks.
"""
from __future__ import annotations

from typing import Optional

import flops
from stats import mean, percentile


def _in_window(rec, name):
    w0, w1 = rec.window
    return [s for s in rec.spans[name] if w0 <= s[0] < w1]


def _seconds(spans) -> float:
    return sum(t1 - t0 for t0, t1, _ in spans)


def send_lag_p90_ms(rec) -> Optional[float]:
    """90th percentile of how late the load generator sent the requests due
    in the window (open loop only)."""
    if rec.traffic["loop"] != "open":
        return None
    w0, w1 = rec.window
    lags = [r["sent"] - r["due"] for r in rec.requests
            if w0 <= r["due"] < w1 and r["sent"] is not None]
    p = percentile(lags, 90)
    return None if p is None else p * 1e3


def coordinator_ms_per_token(rec) -> Optional[float]:
    """Host time of the runtime's steps outside the engine calls, per token
    the coordinator confirmed in the window."""
    tokens = rec.counters.get("tokens_confirmed", 0)
    if tokens <= 0:
        return None
    own = (_seconds(_in_window(rec, "step"))
           - _seconds(_in_window(rec, "decode_stage"))
           - _seconds(_in_window(rec, "prefill_chunk")))
    return own / tokens * 1e3


def decode_rows_mean(rec) -> Optional[float]:
    """Live rows per decode call in the window."""
    return mean([len(info) for _, _, info in _in_window(rec, "decode_stage")])


def prefill_mfu(rec) -> Optional[float]:
    """Operations the window's prefill chunks need over their host time at
    the chip's bf16 peak, in percent."""
    spans = _in_window(rec, "prefill_chunk")
    secs = _seconds(spans)
    if secs <= 0:
        return None
    ops = sum(flops.prefill_flops(rec.conf, start, width)
              for _, _, (start, width) in spans)
    return ops / secs / rec.peak["bf16_flops_per_s"] * 100


def decode_mfu(rec) -> Optional[float]:
    """Operations the window's decode calls need for their live rows over
    the calls' host time at the chip's bf16 peak, in percent: the whole
    decode step's share of the chip."""
    spans = _in_window(rec, "decode_stage")
    secs = _seconds(spans)
    if secs <= 0:
        return None
    ops = sum(flops.decode_flops(rec.conf, ctxs) for _, _, ctxs in spans)
    return ops / secs / rec.peak["bf16_flops_per_s"] * 100


def paged_attention_roofline(rec) -> Optional[float]:
    """Least time the chip needs for the decode kernel's work (the larger of
    operations at peak FLOP/s and live K/V page bytes at peak bandwidth)
    over the kernel's device time, for the decode calls inside the trace,
    in percent."""
    tr = rec.trace
    if not tr or not tr["kernel_ns_by_call"]:
        return None
    calls = rec.spans["decode_stage"]
    page = rec.conf["serving"]["page_size"]
    ops = nbytes = 0
    for i in tr["kernel_ns_by_call"]:
        o, b = flops.paged_attention_need(rec.conf, calls[i][2], page)
        ops += o
        nbytes += b
    kernel_s = sum(tr["kernel_ns_by_call"].values()) / 1e9
    least = max(ops / rec.peak["bf16_flops_per_s"],
                nbytes / rec.peak["hbm_bytes_per_s"])
    return least / kernel_s * 100


def device_idle_share(rec) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device,
    in percent."""
    tr = rec.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100
