#!/usr/bin/env python3
"""The load generator: a child process that never imports JAX (nor the
program). It is handed the generated requests and the schedule and
nothing else:

    python3 loadgen.py <spec.json>

spec: {"url", "t0" (CLOCK_MONOTONIC seconds: the window's start),
"window_s", "warm_s", "drain_s", "loop": "open"|"closed", "clients":
[ids], "bodies_file", "requests": [{"client", "due", "offset",
"length"}], "results_file"}.

Open loop: request i is sent at ``t0 + due`` whatever happened to the
others; its latency counts from the moment it was DUE, so a stall is
charged to every request it delays. Closed loop: each client sends its
next request when the last one returned, from ``t0 - warm_s`` until
``t0 + window_s``. One result line per request: {"i", "due", "sent",
"done", "status", "verdicts" | "error"}, times on the same clock
(``time.perf_counter`` is CLOCK_MONOTONIC on Linux, as in the parent).
"""

import asyncio
import json
import sys
import time

import aiohttp

HEADERS = {"Content-Type": "application/json"}


async def _send(session, url, body, rec, timeout_s):
    rec["sent"] = time.perf_counter()
    try:
        async with session.post(url, data=body, headers=HEADERS,
                                timeout=aiohttp.ClientTimeout(
                                    total=timeout_s)) as resp:
            doc = await resp.json(content_type=None)
            rec["status"] = resp.status
            if resp.status == 200 and doc.get("status") == "OK":
                rec["verdicts"] = doc["verdicts"]
            else:
                rec["error"] = (doc.get("reason") or doc.get("status")
                                or "http") if isinstance(doc, dict) \
                    else "http"
    except asyncio.TimeoutError:
        rec["error"] = "timeout"
    except (aiohttp.ClientError, ValueError, OSError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["done"] = time.perf_counter()


async def _sleep_until(t):
    while True:
        dt = t - time.perf_counter()
        if dt <= 0:
            return
        # coarse sleep, then a short spin-free tail: asyncio timers are
        # good to well under a millisecond on an idle loop
        await asyncio.sleep(dt if dt < 0.002 else dt - 0.001)


async def main(spec):
    url = spec["url"] + "/v1/verify"
    t0, window_s = spec["t0"], spec["window_s"]
    t_end = t0 + window_s
    bodies = open(spec["bodies_file"], "rb")

    def body(r):
        bodies.seek(r["offset"])
        return bodies.read(r["length"])

    results = []
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:
        for cid in spec["clients"]:
            async with session.post(spec["url"] + "/v1/client/register",
                                    json={"client": cid}) as resp:
                if resp.status != 200:
                    raise RuntimeError(f"register {cid}: {resp.status}")
        if spec["loop"] == "open":
            tasks = []
            timeout_s = window_s + spec["drain_s"] + spec["warm_s"] + 5
            for i, r in enumerate(spec["requests"]):
                due = t0 + r["due"]
                await _sleep_until(due)
                rec = {"i": i, "due": due}
                results.append(rec)
                tasks.append(asyncio.ensure_future(
                    _send(session, url, body(r), rec, timeout_s)))
            if tasks:
                await asyncio.wait(tasks, timeout=max(
                    t_end + spec["drain_s"] - time.perf_counter(), 0.1))
            for t in tasks:
                if not t.done():
                    t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        else:
            per_client = {}
            for i, r in enumerate(spec["requests"]):
                per_client.setdefault(r["client"], []).append((i, r))

            async def client(items):
                await _sleep_until(t0 - spec["warm_s"])
                for i, r in items:
                    if time.perf_counter() >= t_end:
                        break
                    rec = {"i": i, "due": time.perf_counter()}
                    results.append(rec)
                    await _send(session, url, body(r), rec,
                                window_s + spec["drain_s"])
                else:
                    results.append({"i": -1, "error": "ran out of "
                                    "prepared requests before the "
                                    "window ended"})

            await asyncio.gather(*(client(v) for v in per_client.values()))
    for rec in results:
        if "done" not in rec and "error" not in rec:
            rec["error"] = "no reply before the drain limit"
    with open(spec["results_file"], "w") as f:
        for rec in results:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        asyncio.run(main(json.load(f)))
