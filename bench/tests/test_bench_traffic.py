"""The traffic generator's schedule, and the load generator's lag and
records against a stdlib stand-in for the front door."""
import json
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

import traffic
from stats import percentile

BENCH = Path(__file__).resolve().parents[1]
CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
BATCH = json.loads((BENCH / "traffic" / "batch.json").read_text())
SEEDS = (0, 7, 2 ** 31 + 11)


def test_lengths_follow_the_mix():
    pool = traffic.length_pool(BATCH)
    assert len(pool) == BATCH["pool"]
    for p, o in pool:
        assert p % BATCH["prompt_multiple"] == 0
        assert 1 <= o and p + o <= BATCH["max_total"]
    # the lognormal's quantiles keep its mean within the rounding and caps
    mean_in = sum(traffic.lognormal_quantiles(1000, **CHAT["input"])) / 1000
    assert 700 < mean_in < 820


@pytest.mark.parametrize("spec", [CHAT, BATCH], ids=["open", "closed"])
def test_every_seed_gets_the_same_work(spec):
    scheds = [traffic.schedule(spec, s, 51, 32) for s in SEEDS]
    again = traffic.schedule(spec, SEEDS[0], 51, 32)
    assert again == scheds[0]

    def sizes(sch, n):
        return sorted((r["prompt_len"], r["max_tokens"])
                      for r in sch["requests"][:n])
    # an open loop's pool is all its arrivals; a closed loop's, one block
    n = spec.get("pool", len(scheds[0]["requests"]))
    assert sizes(scheds[0], n) == sizes(scheds[1], n) == sizes(scheds[2], n)
    assert scheds[0]["requests"] != scheds[1]["requests"]


def test_open_loop_arrivals():
    horizon = CHAT["preroll_s"] + 51
    t = traffic.arrival_times(CHAT, horizon)
    assert len(t) == traffic.arrival_count(CHAT, horizon)
    assert t == sorted(t) and t[-1] == pytest.approx(horizon)
    assert len(t) == pytest.approx(CHAT["rate_per_s"] * horizon, abs=1)
    # every seed has the same arrival times
    for s in SEEDS:
        assert [r["due"] for r in traffic.schedule(CHAT, s, 51, 32)
                ["requests"]] == t


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_spreads_long_outputs(seed):
    """Every block of 16 arrivals carries close to the run's mean output,
    and the same lengths as under any other seed, so the load a request
    meets does not hang on the seed."""
    out = [r["max_tokens"] for r in traffic.schedule(CHAT, seed, 51, 32)
           ["requests"]]
    ref = [r["max_tokens"] for r in traffic.schedule(CHAT, 1, 51, 32)
           ["requests"]]
    mean = sum(out) / len(out)
    for i in range(0, len(out) - 16, 4):
        assert 0.75 * mean < sum(out[i:i + 16]) / 16 < 1.3 * mean
        assert sorted(out[i:i + 4]) == sorted(ref[i:i + 4])


def test_spread_order_is_a_permutation():
    for n in (1, 7, 127):
        assert sorted(traffic.spread_order(n)) == list(range(n))


def test_prompts_are_seeded():
    a = traffic.prompt_tokens(5, 3, 100, 50304)
    assert a == traffic.prompt_tokens(5, 3, 100, 50304)
    assert a != traffic.prompt_tokens(6, 3, 100, 50304)
    assert all(0 <= t < 50304 for t in a)


# ---------------------------------------------------------------------------
# the load generator against a stand-in server


class _FakeFrontDoor(BaseHTTPRequestHandler):
    """Streams ``max_tokens`` SSE chunks; prompt id 0 swaps two chunks."""
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Connection", "close")
        self.end_headers()
        order = list(range(body["max_tokens"]))
        if body["prompt"][0] == 0:
            order[0], order[1] = order[1], order[0]
        for i in order:
            time.sleep(0.005)
            chunk = {"choices": [{"token_id": 7, "output_index": i,
                                  "finish_reason": None}]}
            self.wfile.write(b"data: " + json.dumps(chunk).encode() + b"\n\n")
            self.wfile.flush()
        end = {"choices": [{"finish_reason": "length"}]}
        self.wfile.write(b"data: " + json.dumps(end).encode()
                         + b"\n\ndata: [DONE]\n\n")


@pytest.fixture
def front_door():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _FakeFrontDoor)
    srv.daemon_threads = True
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    yield srv.server_address[1]
    srv.shutdown()
    srv.server_close()


def _run_loadgen(tmp_path, port, sched):
    (tmp_path / "s.json").write_text(json.dumps(sched))
    subprocess.run([sys.executable, str(BENCH / "loadgen.py"), "--schedule",
                    str(tmp_path / "s.json"), "--port", str(port), "--out",
                    str(tmp_path / "o.json")], check=True, timeout=60)
    return json.loads((tmp_path / "o.json").read_text())["requests"]


def test_open_loop_sends_on_time(tmp_path, front_door):
    t_go = time.monotonic() + 1.5
    reqs = [{"idx": i, "due": 0.05 * i, "prompt_len": 4, "max_tokens": 3}
            for i in range(20)]
    sched = {"loop": "open", "clients": 0, "requests": reqs, "t_go": t_go,
             "stop_send": t_go + 10, "drain_until": t_go + 10, "seed": 1,
             "vocab": 1000}
    recs = _run_loadgen(tmp_path, front_door, sched)
    assert len(recs) == 20
    lags = [r["sent"] - r["due"] for r in recs]
    assert min(lags) >= 0 and percentile(lags, 90) < 0.05
    for r in recs:
        assert r["due"] == pytest.approx(t_go + 0.05 * r["idx"])
        if r["error"] is None:
            assert r["tokens"] == [7, 7, 7] and r["finish"] == "length"
            assert r["times"] == sorted(r["times"]) and r["done"]


def test_out_of_order_chunk_is_an_error(tmp_path, front_door):
    # prompt_tokens(seed, idx, ...) of this seed and index starts with id 0
    seed, vocab = 1, 2
    idx = next(i for i in range(100)
               if traffic.prompt_tokens(seed, i, 1, vocab)[0] == 0)
    t_go = time.monotonic() + 1.0
    sched = {"loop": "open", "clients": 0, "t_go": t_go, "seed": seed,
             "vocab": vocab, "stop_send": t_go + 5, "drain_until": t_go + 5,
             "requests": [{"idx": idx, "due": 0.0, "prompt_len": 1,
                           "max_tokens": 3}]}
    rec, = _run_loadgen(tmp_path, front_door, sched)
    assert rec["error"].startswith("out-of-order")


def test_closed_loop_keeps_clients_busy(tmp_path, front_door):
    t_go = time.monotonic() + 1.0
    reqs = [{"idx": i, "prompt_len": 2, "max_tokens": 4}
            for i in range(1000)]
    sched = {"loop": "closed", "clients": 3, "requests": reqs,
             "t_go": t_go, "stop_send": t_go + 1.0,
             "drain_until": t_go + 3.0, "seed": 2, "vocab": 1000}
    recs = _run_loadgen(tmp_path, front_door, sched)
    # each request takes ~4 x 5 ms: three clients send many in a second
    assert 3 * 10 < len(recs) < 3 * 60
    assert [r["idx"] for r in recs] == list(range(len(recs)))
    assert all(r["sent"] - r["due"] < 0.05 for r in recs)
