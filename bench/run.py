#!/usr/bin/env python3
"""On-chip benchmark: one run of one cell of ``BENCHMARK.json``.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<file>``) and a traffic mix
(``bench/traffic/<name>.json``); the per-layer metrics are read by
``bench/metrics/<metric>.py``.  All of them are found by name, so a new cell,
mix or metric is new files and new entries, never an edit.

One run, in one process that holds the chip:

1. set-up: weights made on the device from the seed, the program's runtime
   built (``cluster_plan`` -> ``ClusterRuntime(realtime=True, paged=True)``),
   every prompt length the cell's traffic can send prefilled once and one
   decode step run (so every program the window uses is compiled or loaded
   from the compile cache in ``.jax_cache/``), the front door started
   (``serving.frontend.Frontend``), the load generator started;
2. the load generator (``bench/loadgen.py``, a child process without JAX)
   streams ``POST /v1/completions`` requests: a pre-roll, the measured
   window of ``--seconds``, then a drain, after which open streams are cut;
3. the program's state is freed, and what was served is compared with the
   float32 reference (``bench/references/<model_type>.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of the window's start is taken and the result
carries the per-layer metrics, the device's busy time and a breakdown.  The
last line of stdout is the result (JSON); the last lines of stderr are the
numbers compared for ``correct``, each with its limit.  Without a TPU, or
with fewer chips than the cell asks for, the run stops before any work and
prints no result.
"""
import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import traffic as traffic_mod  # noqa: E402
from stats import percentile  # noqa: E402

GO_DELAY_S = 1.0       # from spawning the load generator to its first send
TRACE_SECONDS = 10.0   # traced runs: the profiler covers the window's start
SHUTDOWN_S = 120.0     # bound on waiting for cut streams to be torn down


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding a cell's files by name


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: Dict                 # the configuration file
    traffic: Dict              # the traffic file
    end_to_end: List[Dict]     # BENCHMARK.json entries reported by this cell
    per_layer: List[Dict]


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _named(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: BENCHMARK.json has no {what} {name!r}")


def resolve(spec: Dict, workload: str) -> Cell:
    """The cell ``workload`` of a parsed ``BENCHMARK.json``."""
    wl = _named(spec["workloads"], workload, "workload")
    cfg = _named(spec["configs"], wl["config"], "config")
    conf = json.loads((ROOT / cfg["file"]).read_text())
    traffic = json.loads((BENCH / "traffic"
                          / f"{wl['traffic']}.json").read_text())

    def mine(m):
        return workload in m.get("workloads", [workload])
    e2e = [m for m in spec["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if mine(m) and m["moves"] in e2e_names]
    return Cell(workload, int(wl["chips"]), conf, traffic, e2e, layer)


# ---------------------------------------------------------------------------
# the chip


def require_accelerator(chips: int):
    """The devices to run on; exits before any work without ``chips`` TPU
    chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s). Nothing was run.")
        sys.exit(3)
    return devs[:chips]


def peak_table(kind: str) -> Dict:
    """The chip's published peaks; a device not in the table is an error."""
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return peaks[kind]


def use_compile_cache(root: Path) -> None:
    """Every program goes to one fixed directory in the checkout, so only a
    cell's first run there compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Programs the backend compiled or loaded from the persistent cache,
    and how many of them came from the cache, from JAX's monitoring
    events."""

    def __init__(self):
        import jax
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, name: str, secs: float, **_) -> None:
        if name.endswith("backend_compile_duration"):
            self.programs += 1

    def _on_event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __str__(self) -> str:
        return f"{self.programs} programs ({self.cache_hits} from the cache)"


# ---------------------------------------------------------------------------
# spans around the program's calls


class Probe:
    """Host spans around ``rt.step`` and each engine's ``decode_stage`` and
    ``prefill_chunk``, by wrapping the instance methods (the program is not
    changed).  Each span is (start, end, info): the live contexts of a
    decode call, (start position, width) of a prefill chunk.  With
    ``annotate`` each call also opens a profiler annotation
    ``bench.<name>`` carrying its index in the span list."""

    def __init__(self, rt, annotate: bool):
        self.annotate = annotate
        self.spans: Dict[str, List] = {"step": [], "decode_stage": [],
                                       "prefill_chunk": []}
        self._wrap(rt, "step", lambda a: None)
        for eng in rt.engines.values():
            self._wrap(eng, "decode_stage",
                       lambda a: tuple(it.pos + it.n for it in a[0]))
            self._wrap(eng, "prefill_chunk",
                       lambda a: (int(a[3]), len(a[1])))

    def _wrap(self, obj, attr: str, info) -> None:
        import jax
        fn = getattr(obj, attr)
        spans = self.spans[attr]
        name = f"bench.{attr}"

        def wrapped(*a, **k):
            i = len(spans)
            t0 = time.monotonic()
            if self.annotate:
                with jax.profiler.TraceAnnotation(name, call=i):
                    out = fn(*a, **k)
            else:
                out = fn(*a, **k)
            spans.append((t0, time.monotonic(), info(a)))
            return out

        setattr(obj, attr, wrapped)


# ---------------------------------------------------------------------------
# set-up


def build(cell: Cell, seed: int):
    """Weights from the seed, and the program's runtime over them."""
    from repro.launch.serve import cluster_plan
    from repro.serving import ClusterRuntime, EngineConfig
    conf = cell.conf
    arch = load_module(BENCH / "models" / f"{conf['model_type']}.py")
    pcfg = arch.program_config(conf)
    sv = conf["serving"]
    ec = EngineConfig(max_batch=sv["max_batch"], max_len=sv["max_len"],
                      prompt_len=sv["chunk"], eos_token=-1)
    params = arch.make_weights(conf, seed)
    plan = cluster_plan(pcfg, [sv["device_profile"]] * cell.chips)
    rt = ClusterRuntime(pcfg, params, plan, ec, paged=True,
                        page_size=sv["page_size"], realtime=True, rng_seed=0)
    return arch, rt


def warm_up(rt, prompt_lens, vocab: int, chunk: int) -> None:
    """Prefill each prompt length the traffic can send, chunk by chunk as
    the runtime does, and decode one token after it: every program the
    window drives is then compiled (or loaded from the cache) before it
    opens."""
    from repro.serving.stage_engine import DecodeItem
    engines = sorted(rt.engines.values(), key=lambda e: e.layers.start)
    rng = random.Random(0)
    for n in prompt_lens:
        toks = [rng.randrange(vocab) for _ in range(n)]
        slots = [e.alloc_slot(-1) for e in engines]
        try:
            for e, s in zip(engines, slots):
                if s is None or not e.ensure(s, n + 1):
                    raise RuntimeError("no room to warm up a prompt")
            for off in range(0, n, chunk):
                x = toks[off:off + chunk]
                for e, s in zip(engines, slots):
                    x = e.prefill_chunk(s, x, e.layers.start, off)
            h = None
            for e, s in zip(engines, slots):
                h = e.decode_stage([DecodeItem(slot=s, pos=n,
                                               entry=e.layers.start,
                                               token=1, h=h)])[0].h
        finally:
            for e, s in zip(engines, slots):
                if s is not None:
                    e.release(s)


# ---------------------------------------------------------------------------
# the window


@dataclasses.dataclass
class Record:
    """What one run measured; the per-layer readers take it."""
    conf: Dict
    traffic: Dict
    window: tuple                      # (start, end) on time.monotonic()
    requests: List[Dict]               # load generator records
    spans: Dict[str, List]             # Probe spans
    counters: Dict[str, int]
    trace: Optional[Dict]              # reduced trace (traced runs)
    peak: Dict                         # peaks.json entry of the chip


def serve_window(cell: Cell, rt, sched: Dict, seed: int, seconds: float,
                 traced: bool, tmp: Path, clock: CompileClock):
    """Start the front door and the load generator; return the records,
    the window, counters and the trace's file (traced runs)."""
    import jax
    from repro.serving.frontend import Frontend
    tr = cell.traffic
    fe = Frontend(rt, max_pending=1 << 30, request_timeout_s=SHUTDOWN_S)
    _, port = fe.serve("127.0.0.1", 0)
    t_go = time.monotonic() + GO_DELAY_S
    w0 = t_go + float(tr["preroll_s"])
    w1 = w0 + seconds
    sched.update(t_go=t_go, window=[w0, w1], stop_send=w1,
                 drain_until=w1 + float(tr["drain_s"]), seed=seed,
                 vocab=cell.conf["vocab_size"])
    (tmp / "schedule.json").write_text(json.dumps(sched))
    out = tmp / "served.json"
    child = subprocess.Popen(
        [sys.executable, str(BENCH / "loadgen.py"), "--schedule",
         str(tmp / "schedule.json"), "--port", str(port), "--out", str(out)],
        stdout=subprocess.DEVNULL)
    setup_s = t_go - T_PROC
    trace_dir = tmp / "trace"
    counters = {}
    try:
        _sleep_until(w0)
        c0, tok0 = clock.programs, rt.tokens_produced
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            _sleep_until(min(w1, time.monotonic() + TRACE_SECONDS))
            jax.profiler.stop_trace()
        _sleep_until(w1)
        counters["tokens_confirmed"] = rt.tokens_produced - tok0
        counters["window_compiles"] = clock.programs - c0
        log(f"window: programs compiled or loaded inside the window = "
            f"{counters['window_compiles']}")
        child.wait(timeout=sched["drain_until"] - time.monotonic() + 60)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        fe.shutdown(drain=True, timeout_s=SHUTDOWN_S)
    if fe.loop_error is not None:
        raise RuntimeError(f"runtime loop died: {fe.loop_error!r}")
    recs = json.loads(out.read_text())["requests"]
    return recs, (w0, w1), setup_s, counters, trace_dir


def free_device_memory() -> None:
    """Free every device buffer once the window's numbers are read, before
    the reference runs.  A front-door handler thread whose client was cut
    can outlive the window by its timeout and keep the runtime, and so its
    weights and KV pool, reachable; the harness needs none of them."""
    import jax
    gc.collect()
    for a in jax.live_arrays():
        if not a.is_deleted():
            a.delete()


def _sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def reduce_trace(trace_dir: Path, n_chips: int) -> Optional[Dict]:
    """Busy time, breakdown and decode-kernel time per call of the trace;
    None if it holds no TPU plane.  The traced window is the trace's own
    extent: from its first event to its last."""
    import xplane
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    tr = xplane.Trace.load(str(files[-1]))
    planes = sorted(tr.ops)[:n_chips]
    if not planes:
        return None
    extent = tr.extent()
    busy = [xplane.busy_ns(tr.ops[p]) / 1e9 for p in planes]
    kernel = {}
    for p in planes:
        for i, ns in xplane.kernel_ns_by_call(
                tr, p, "paged_attention_op", "bench.decode_stage").items():
            kernel[i] = kernel.get(i, 0.0) + ns
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (extent[1] - extent[0]) / 1e9,
        "kernel_ns_by_call": kernel,
        "breakdown": {
            "device_ops": xplane.top_ops(tr.ops[planes[0]]),
            "idle_gaps": xplane.idle_by_host(tr, planes[0], extent),
        },
    }


# ---------------------------------------------------------------------------
# end-to-end metrics


def end_to_end(recs: List[Dict], window, drain_until: float,
               setup_s: float) -> Dict[str, float]:
    """Every end-to-end metric this harness knows, from the load generator's
    records: tails over all requests due in the window, rates over the
    whole window."""
    w0, w1 = window
    due = [r for r in recs if w0 <= r["due"] < w1]
    ttft = [((r["times"][0] if r["times"] else drain_until) - r["due"]) * 1e3
            for r in due]
    tpot = [(r["times"][-1] - r["times"][0]) / (len(r["times"]) - 1) * 1e3
            for r in due if len(r["times"]) > 1]
    tokens = sum(1 for r in recs for t in r["times"] if w0 <= t < w1)
    return {
        "setup_s": setup_s,
        "ttft_p90_ms": percentile(ttft, 90),
        "tpot_p90_ms": percentile(tpot, 90),
        "output_tokens_per_s": tokens / (w1 - w0),
    }


def attempted_failed(recs: List[Dict], window) -> tuple:
    w0, w1 = window
    due = [r for r in recs if w0 <= r["due"] < w1]
    failed = [r for r in due if r["error"] is not None or not r["times"]]
    return len(due), len(failed)


# ---------------------------------------------------------------------------
# correct


def served_sample(recs: List[Dict], k: int, seed: int) -> List[Dict]:
    """``k`` finished requests drawn from the seed, the longest among them."""
    done = [r for r in recs if r["error"] is None and r["finish"] == "length"
            and len(r["tokens"]) == r["max_tokens"]]
    if not done:
        return []
    done.sort(key=lambda r: (r["prompt_len"] + r["max_tokens"], r["idx"]))
    longest, rest = done[-1], done[:-1]
    random.Random(seed).shuffle(rest)
    return [longest] + rest[:k - 1]


def sequences(cell: Cell, sample: List[Dict], seed: int):
    """(tokens, picks, spans): each sampled prompt + served tokens padded to
    ``max_len``; ``picks[p]`` is the token that followed position p; spans
    are the positions whose next token was served."""
    import numpy as np
    S = cell.conf["serving"]["max_len"]
    tokens = np.zeros((len(sample), S), np.int32)
    picks = np.zeros((len(sample), S), np.int32)
    spans = []
    for i, r in enumerate(sample):
        seq = traffic_mod.prompt_tokens(seed, r["idx"], r["prompt_len"],
                                        cell.conf["vocab_size"]) + r["tokens"]
        tokens[i, :len(seq)] = seq
        picks[i, :len(seq) - 1] = seq[1:]
        spans.append((r["prompt_len"] - 1, len(seq) - 1))
    return tokens, picks, spans


def widest_gap(best, picked, spans) -> float:
    return max(float((best[i, a:b] - picked[i, a:b]).max())
               for i, (a, b) in enumerate(spans))


def check(cell: Cell, arch, recs: List[Dict], seed: int):
    """The widest gap by which a served token's logit lies below the float32
    reference's best, over a seeded sample of finished requests."""
    ref = load_module(BENCH / "references" / f"{cell.conf['model_type']}.py")
    sample = served_sample(recs, cell.conf["correct"]["sample_requests"],
                           seed)
    if not sample:
        return None, 0, None
    tokens, picks, spans = sequences(cell, sample, seed)
    w = arch.make_weights(cell.conf, seed)
    best, picked = ref.scores(cell.conf, w, tokens, picks)
    n = sum(b - a for a, b in spans)
    return widest_gap(best, picked, spans), n, (ref, w, tokens, spans, best)


# ---------------------------------------------------------------------------
# one run


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        keep_check_state: bool = False) -> Dict:
    """One run of ``cell``; returns the result line as a dict (with
    ``keep_check_state``, also what ``calibrate.py`` needs)."""
    devs = require_accelerator(cell.chips)
    peak = peak_table(devs[0].device_kind)
    sys.path.insert(0, str(ROOT / "src"))
    use_compile_cache(ROOT)
    clock = CompileClock()
    arch, rt = build(cell, seed)
    log(f"built at {time.monotonic() - T_PROC:.1f} s: {clock}")
    sched = traffic_mod.schedule(cell.traffic, seed, seconds,
                                 cell.conf["serving"]["max_batch"])
    warm_up(rt, traffic_mod.prompt_lengths(sched), cell.conf["vocab_size"],
            cell.conf["serving"]["chunk"])
    log(f"warm at {time.monotonic() - T_PROC:.1f} s: {clock}")
    probe = Probe(rt, annotate=traced)
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        recs, window, setup_s, counters, trace_dir = serve_window(
            cell, rt, sched, seed, seconds, traced, Path(tmp), clock)
        peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devs)
        trace = reduce_trace(trace_dir, cell.chips) if traced else None
    spans = probe.spans
    del probe, rt
    free_device_memory()
    drain_until = window[1] + float(cell.traffic["drain_s"])
    attempted, failed = attempted_failed(recs, window)
    gap, n_compared, state = check(cell, arch, recs, seed)
    limit = cell.conf["correct"]["max_logit_gap"]
    correct = gap is not None and gap <= limit
    rec = Record(cell.conf, cell.traffic, window, recs, spans, counters,
                 trace, peak)
    if traced:
        metrics = {}
        for m in cell.per_layer:
            mod = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = mod.read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = end_to_end(recs, window, drain_until, setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_mem}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    log(f"check: {n_compared} served tokens of "
        f"{cell.conf['correct']['sample_requests']} finished requests "
        "compared with the float32 reference")
    result["check"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    if keep_check_state:
        result["_state"] = state
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = resolve(spec, args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    c = result["check"]
    log(f"check: max_logit_gap={c['max_logit_gap']['value']} "
        f"limit={c['max_logit_gap']['limit']}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
