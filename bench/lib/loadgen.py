"""Load generator: a child process that never imports JAX.

    python3 loadgen.py <schedule.json> <results.json>

The schedule (bench/lib/gen.py) holds the server's port, the window's
start `t0` and close on the monotonic clock (shared with the parent on
Linux), the deadline, and the requests. Every request is a POST
/v1/completions with `stream: true` on a connection of its own. In open
mode request i is due at t0 + its offset; in closed mode each client sends
its next request as soon as its last has finished, and none after the
window has closed. The SSE events are read as they come and every
timestamp is taken here, on the client side. Results: per request sent,
its index, the time it was due, the time it was sent, the arrival time of
each token and the error, if any.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time


async def one(port: int, due: float, i: int, req: dict, deadline: float):
    await asyncio.sleep(max(due - time.monotonic(), 0.0))
    out = {"i": i, "due": due, "sent": time.monotonic(), "tokens": [],
           "times": [], "error": None}
    body = json.dumps({"prompt": req["prompt"],
                       "max_tokens": req["max_tokens"], "temperature": 0.0,
                       "stream": True}).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: localhost\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(head + body)
        await writer.drain()
        status = (await asyncio.wait_for(
            reader.readline(), deadline - time.monotonic())).split()
        if len(status) < 2 or status[1] != b"200":
            out["error"] = f"status {status[1:2]}"
            return out
        while True:
            line = await asyncio.wait_for(reader.readline(),
                                          deadline - time.monotonic())
            if not line:
                out["error"] = "closed before [DONE]"
                return out
            if not line.startswith(b"data: "):
                continue
            data = line[6:].strip()
            if data == b"[DONE]":
                return out
            now = time.monotonic()
            for tok in json.loads(data)["choices"][0]["tokens"]:
                out["tokens"].append(tok)
                out["times"].append(now)
    except (OSError, asyncio.TimeoutError, ValueError) as e:
        out["error"] = repr(e)
        return out
    finally:
        if writer is not None:
            writer.close()


async def client(port: int, sched: dict, order: list) -> list:
    """One closed-loop user: its requests in turn until the window
    closes."""
    out = []
    for i in order:
        now = time.monotonic()
        if now >= sched["close"]:
            break
        out.append(await one(port, now, i, sched["requests"][i],
                             sched["deadline"]))
    return out


async def main(sched: dict) -> list:
    port, t0, deadline = sched["port"], sched["t0"], sched["deadline"]
    reqs = sched["requests"]
    if sched["mode"] == "open":
        return await asyncio.gather(*(one(port, t0 + r["t"], i, r, deadline)
                                      for i, r in enumerate(reqs)))
    await asyncio.sleep(max(t0 - time.monotonic(), 0.0))
    per = await asyncio.gather(*(client(port, sched, order)
                                 for order in sched["clients"]))
    return sorted((r for c in per for r in c), key=lambda r: r["i"])


if __name__ == "__main__":
    sched = json.loads(open(sys.argv[1]).read())
    results = asyncio.run(main(sched))
    with open(sys.argv[2], "w") as f:
        json.dump(results, f)
