#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the same set-up as a run, then one
phase per arrival rate through the same front door.

  python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
      --rates 1.0,1.3,1.6

Each phase sends the cell's traffic at one rate (pre-roll, window, drain,
as a run does) and prints one JSON line: the requests in the system
(sent, not yet finished or cut) sampled each second of the window, their
rise from the window's first quarter to its last, ``ttft_p90_ms``,
``tpot_p90_ms`` and the failed count.  The knee is the highest rate whose
backlog does not rise and whose requests all get a first token before the
drain ends (``knee``); a cell's fixed rate, 0.8 x the knee, is then
written into its traffic file.  The last line printed names both.
"""
import argparse
import json
import subprocess
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from stats import mean  # noqa: E402


def in_system(recs, t: float) -> int:
    """Requests sent by ``t`` whose last token had not arrived (or that
    were still open when the drain cut them)."""
    n = 0
    for r in recs:
        if r["due"] > t:
            continue
        finished = r["finish"] is not None and r["times"]
        if not finished or r["times"][-1] > t:
            n += 1
    return n


def rises(p, max_batch: int) -> bool:
    """The backlog grew through the window: its mean over the last quarter
    exceeds the first quarter's by more than a quarter of the decode slots
    or a fifth of the window's mean.  Poisson arrivals alone move a
    quarter's mean by several requests (4.4 at 1.6 req/s in my sweep of
    olmo-chat, whose backlog did not grow)."""
    return p["rise"] > max(0.25 * max_batch, 0.2 * mean(p["in_system"]))


def knee(phases, max_batch: int):
    """The highest rate below which no swept rate's backlog rose or left a
    request without a first token (None if the lowest already did)."""
    k = None
    for p in sorted(phases, key=lambda p: p["rate_per_s"]):
        if rises(p, max_batch) or p["failed"]:
            break
        k = p["rate_per_s"]
    return k


def phase(cell, rt, port, seed, seconds, rate, tmp: Path):
    tr = dict(cell.traffic, rate_per_s=rate)
    sched = traffic_mod.schedule(tr, seed, seconds,
                                 cell.conf["serving"]["max_batch"])
    t_go = time.monotonic() + run.GO_DELAY_S
    w0 = t_go + float(tr["preroll_s"])
    w1 = w0 + seconds
    drain_until = w1 + float(tr["drain_s"])
    sched.update(t_go=t_go, window=[w0, w1], stop_send=w1,
                 drain_until=drain_until, seed=seed,
                 vocab=cell.conf["vocab_size"])
    (tmp / "schedule.json").write_text(json.dumps(sched))
    subprocess.run([sys.executable, str(run.BENCH / "loadgen.py"),
                    "--schedule", str(tmp / "schedule.json"), "--port",
                    str(port), "--out", str(tmp / "served.json")],
                   stdout=subprocess.DEVNULL, check=True,
                   timeout=drain_until - time.monotonic() + 60)
    recs = json.loads((tmp / "served.json").read_text())["requests"]
    counts = [in_system(recs, w0 + i) for i in range(int(seconds) + 1)]
    q = max(1, len(counts) // 4)
    e2e = run.end_to_end(recs, (w0, w1), drain_until, 0.0)
    attempted, failed = run.attempted_failed(recs, (w0, w1))
    return {"rate_per_s": rate, "attempted": attempted, "failed": failed,
            "in_system": counts,
            "rise": mean(counts[-q:]) - mean(counts[:q]),
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "tpot_p90_ms": e2e["tpot_p90_ms"],
            "output_tokens_per_s": e2e["output_tokens_per_s"]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.resolve(spec, args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("sweep: only an open-loop cell has a knee")
    run.require_accelerator(cell.chips)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.use_compile_cache(run.ROOT)
    from repro.serving.frontend import Frontend
    arch, rt = run.build(cell, args.seed)
    lens = set()
    for r in rates:
        lens |= set(traffic_mod.prompt_lengths(traffic_mod.schedule(
            dict(cell.traffic, rate_per_s=r), args.seed, args.seconds,
            cell.conf["serving"]["max_batch"])))
    run.warm_up(rt, sorted(lens), cell.conf["vocab_size"],
                cell.conf["serving"]["chunk"])
    fe = Frontend(rt, max_pending=1 << 30,
                  request_timeout_s=run.SHUTDOWN_S)
    _, port = fe.serve("127.0.0.1", 0)
    phases = []
    try:
        with tempfile.TemporaryDirectory(prefix="bench-sweep-") as tmp:
            for rate in rates:
                out = phase(cell, rt, port, args.seed, args.seconds, rate,
                            Path(tmp))
                phases.append(out)
                print(json.dumps(out), flush=True)
                deadline = time.monotonic() + run.SHUTDOWN_S
                while rt.pending() and time.monotonic() < deadline:
                    time.sleep(0.1)     # cut streams are torn down
    finally:
        fe.shutdown(drain=True, timeout_s=run.SHUTDOWN_S)
    k = knee(phases, cell.conf["serving"]["max_batch"])
    print(json.dumps({"knee_per_s": k,
                      "rate_per_s": None if k is None else round(0.8 * k, 3)}),
          flush=True)


if __name__ == "__main__":
    main()
