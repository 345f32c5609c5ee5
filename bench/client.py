"""Load-generating client: streams completions from the gateway over HTTP.

Runs as a child process of `run.py` and never imports JAX, so the chip
stays with the parent.  It reads one JSON line on stdin:

    {"host", "port", "loop", "t_load", "t0", "t1", "drain_until",
     "schedule": <path>, "out": <path>}

(times on the shared monotonic clock) and writes one record per request
sent to `out`.  Open loop: each request is sent at its intended time
`t0 + due`, whatever the server's state, and its latency counts from
that intended time, so a stall of the event loop or the server delays
every later request's clock too.  Closed loop: each client sends its
next request as soon as the previous one finishes.  Token times are
taken as the bytes arrive.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time
from typing import Dict, List


async def _stream(host: str, port: int, req: Dict, rec: Dict) -> None:
    body = json.dumps({"prompt": req["prompt"],
                       "max_tokens": req["max_tokens"],
                       "stream": True}).encode()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode()
                     + body)
        await writer.drain()
        rec["status"] = int((await reader.readline()).split()[1])
        while (await reader.readline()) not in (b"\r\n", b""):
            pass
        if rec["status"] != 200:
            return
        while True:
            line = await reader.readline()
            if not line:
                return
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[6:]
            if data == b"[DONE]":
                rec["done"] = time.monotonic()
                return
            event = json.loads(data)
            if "token" in event:
                rec["times"].append(time.monotonic())
                rec["tokens"].append(event["token"])
            elif "finish_reason" in event:
                rec["finish"] = event["finish_reason"]
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, OSError):
            pass


def _record(req: Dict, due: float) -> Dict:
    return {"id": req["id"], "client": req.get("client"),
            "segment": req.get("segment"), "prompt_len": len(req["prompt"]),
            "max_tokens": req["max_tokens"], "due": due, "sent": None,
            "status": None, "times": [], "tokens": [], "finish": None,
            "done": None, "error": None}


async def _run_one(cfg: Dict, req: Dict, rec: Dict) -> None:
    rec["sent"] = time.monotonic()
    try:
        await _stream(cfg["host"], cfg["port"], req, rec)
    except (ConnectionError, OSError, ValueError, IndexError) as e:
        rec["error"] = repr(e)


async def _sleep_until(t: float) -> None:
    await asyncio.sleep(max(0.0, t - time.monotonic()))


async def open_loop(cfg: Dict, schedule: List[Dict]) -> List[Dict]:
    records, tasks = [], []
    window = [r for r in schedule if r["segment"] == "window"]

    async def fire():
        for req in schedule:
            due = cfg["t0"] + req["due"]
            await _sleep_until(due)
            rec = _record(req, due)
            records.append(rec)
            tasks.append(asyncio.ensure_future(_run_one(cfg, req, rec)))

    firing = asyncio.ensure_future(fire())
    # the run ends once every request due in the window has finished, or
    # at the drain deadline; the requests still open are then cut
    await _sleep_until(cfg["t1"])
    while time.monotonic() < cfg["drain_until"]:
        due = {r["id"] for r in window}
        open_ = [r for r in records if r["id"] in due and r["done"] is None
                 and r["error"] is None and r["status"] in (None, 200)]
        if len([r for r in records if r["id"] in due]) == len(due) \
                and not open_:
            break
        await asyncio.sleep(0.05)
    firing.cancel()
    for t in tasks:
        t.cancel()
    await asyncio.gather(firing, *tasks, return_exceptions=True)
    return records


async def closed_loop(cfg: Dict, per_client: List[List[Dict]]
                      ) -> List[Dict]:
    records: List[Dict] = []

    async def client(reqs: List[Dict]) -> None:
        for req in reqs:
            if time.monotonic() >= cfg["t1"]:
                return
            rec = _record(req, time.monotonic())
            records.append(rec)
            await _run_one(cfg, req, rec)
            if rec["done"] is None:
                return

    await _sleep_until(cfg["t_load"])
    tasks = [asyncio.ensure_future(client(r)) for r in per_client]
    await _sleep_until(cfg["t1"])
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return records


def main() -> int:
    cfg = json.loads(sys.stdin.readline())
    with open(cfg["schedule"]) as f:
        schedule = json.load(f)
    loop = open_loop if cfg["loop"] == "open" else closed_loop
    records = asyncio.run(loop(cfg, schedule))
    with open(cfg["out"], "w") as f:
        json.dump({"records": records, "t_stop": time.monotonic()}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
