#!/usr/bin/env python3
"""Load generator: streams ``POST /v1/completions`` requests at the front
door on the schedule the harness wrote, and records what came back.

Stdlib only, and it never imports JAX: it runs as a child process of the
harness, so it neither holds the chip nor contends for the harness's
interpreter lock.  One asyncio loop drives every stream (no thread per
request).  Both processes read ``time.monotonic()``, so the window edges in
the schedule and the times recorded here share one clock.

  python3 bench/loadgen.py --schedule <file> --port <n> --out <file>

Open loop: request i is due at ``t_go + due_i``; its send lag (send time -
due time) is recorded, and latencies are taken from the due time, so a
stalled generator cannot hide a stall of the server.  Closed loop:
``clients`` clients each send their next request as soon as the previous
one ends.  Sending stops at ``stop_send``; at ``drain_until`` every stream
still open is cut.  The record of each request: when it was due and sent,
the arrival time and id of every streamed token, the finish reason, and an
error (HTTP status, transport, or an out-of-order chunk), if any.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path
from typing import Dict, List

sys.path.insert(0, str(Path(__file__).resolve().parent))

import traffic  # noqa: E402


async def stream(port: int, body: bytes, rec: Dict) -> None:
    """One streamed completion; fills ``rec`` as chunks arrive."""
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                     b"Content-Type: application/json\r\nContent-Length: "
                     + str(len(body)).encode() + b"\r\nConnection: close"
                     b"\r\n\r\n" + body)
        await writer.drain()
        rec["sent"] = time.monotonic()
        status = (await reader.readline()).split()
        code = int(status[1]) if len(status) > 1 else 0
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if code != 200:
            text = (await reader.read(300)).decode("utf-8", "replace")
            rec["error"] = f"HTTP {code}: {text}"
            return
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            data = line[6:].strip()
            if data == b"[DONE]":
                rec["done"] = True
                break
            t = time.monotonic()
            choice = json.loads(data)["choices"][0]
            tok = choice.get("token_id")
            if tok is not None:
                if choice.get("output_index") != len(rec["tokens"]):
                    rec["error"] = (f"out-of-order chunk: output_index "
                                    f"{choice.get('output_index')} after "
                                    f"{len(rec['tokens'])} tokens")
                    return
                rec["times"].append(t)
                rec["tokens"].append(int(tok))
            if choice.get("finish_reason"):
                rec["finish"] = choice["finish_reason"]
    except (OSError, ValueError, IndexError) as e:
        rec["error"] = f"transport: {e!r}"
    finally:
        if writer is not None:
            writer.close()


def new_record(req: Dict, due: float) -> Dict:
    return {"idx": req["idx"], "prompt_len": req["prompt_len"],
            "max_tokens": req["max_tokens"], "due": due, "sent": None,
            "times": [], "tokens": [], "finish": None, "done": False,
            "error": None}


def body_of(sch: Dict, req: Dict) -> bytes:
    prompt = traffic.prompt_tokens(sch["seed"], req["idx"],
                                   req["prompt_len"], sch["vocab"])
    return json.dumps({"prompt": prompt, "max_tokens": req["max_tokens"],
                       "stream": True, "temperature": 0.0}).encode()


async def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        await asyncio.sleep(d)


async def open_loop(sch: Dict, port: int, recs: List[Dict],
                    tasks: List[asyncio.Task]) -> None:
    for req in sch["requests"]:
        due = sch["t_go"] + req["due"]
        if due >= sch["stop_send"]:
            break
        body = body_of(sch, req)
        await sleep_until(due)
        rec = new_record(req, due)
        recs.append(rec)
        tasks.append(asyncio.create_task(stream(port, body, rec)))


async def closed_loop(sch: Dict, port: int, recs: List[Dict]) -> None:
    queue = iter(sch["requests"])

    async def client() -> None:
        for req in queue:
            now = time.monotonic()
            if now >= sch["stop_send"]:
                return
            rec = new_record(req, now)
            recs.append(rec)
            await stream(port, body_of(sch, req), rec)

    await sleep_until(sch["t_go"])
    await asyncio.gather(*(client() for _ in range(sch["clients"])))


async def main_async(sch: Dict, port: int) -> List[Dict]:
    recs: List[Dict] = []
    tasks: List[asyncio.Task] = []
    if sch["loop"] == "open":
        sender = asyncio.create_task(open_loop(sch, port, recs, tasks))
    else:
        sender = asyncio.create_task(closed_loop(sch, port, recs))
    try:
        await asyncio.wait_for(asyncio.shield(sender),
                               max(0.0, sch["drain_until"] - time.monotonic()))
    except asyncio.TimeoutError:
        pass
    if tasks:
        await asyncio.wait(tasks, timeout=max(
            0.0, sch["drain_until"] - time.monotonic()))
    for t in tasks + [sender]:
        t.cancel()
    await asyncio.gather(*tasks, sender, return_exceptions=True)
    return recs


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sch = json.loads(Path(args.schedule).read_text())
    recs = asyncio.run(main_async(sch, args.port))
    Path(args.out).write_text(json.dumps({"requests": recs}))


if __name__ == "__main__":
    main()
