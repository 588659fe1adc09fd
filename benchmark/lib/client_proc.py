#!/usr/bin/env python3
"""The load generator: a process of its own, one thread, no JAX.

    python3 benchmark/lib/client_proc.py <plan.json> <results.json>

The plan names the server, the absolute ``time.monotonic()`` instant ``t0``
at which the measured window opens (CLOCK_MONOTONIC is one clock for every
process of the machine), and either requests with due times (open loop) or
one sequence of requests per client (closed loop). Every request is a
streamed /v1/chat/completions call; for each the client records when it was
due, when it was sent, and the arrival time and token count of every SSE
chunk that carries content. It prints nothing: the results file is its
whole output.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

import aiohttp


#: Word ids from here on replace a prompt's first word on a later lap; they
#: are below the ids prompts are drawn from (lib/tokenizer.py RESERVED).
LAP_WORD_BASE = 6


def _tokens(text: str) -> int:
    """The benchmark's tokenizer has one whitespace-separated word per id,
    so the words of a streamed piece of text are its tokens."""
    return len(text.split())


async def one_request(session, plan: dict, req: dict, due: float | None
                      ) -> dict:
    rec = {"id": req["id"], "due": due, "sent": None, "chunk_t": [],
           "chunk_n": [], "usage": None, "ok": False, "status": None,
           "error": None, "done": None, "finish": None, "aborted": False,
           "measured": bool(req.get("measured", True)),
           "prompt_len": req["prompt_len"], "max_tokens": req["max_tokens"]}
    body = {"model": plan["model"], "max_tokens": req["max_tokens"],
            "messages": [{"role": "user", "content": req["content"]}],
            "temperature": 0.0, "ignore_eos": True, "stream": True,
            "stream_options": {"include_usage": True}}
    rec["sent"] = time.monotonic()
    try:
        async with session.post(plan["base"] + "/v1/chat/completions",
                                json=body,
                                headers=plan.get("headers") or None) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = (await resp.text())[:300]
                return rec
            saw_done = False
            async for raw in resp.content:
                now = time.monotonic()
                line = raw.decode("utf-8", "replace").strip()
                if not line.startswith("data:"):
                    continue
                data = line[5:].strip()
                if data == "[DONE]":
                    saw_done = True
                    continue
                chunk = json.loads(data)
                if chunk.get("usage"):
                    rec["usage"] = chunk["usage"]
                for choice in chunk.get("choices") or []:
                    piece = (choice.get("delta") or {}).get("content")
                    if piece:
                        rec["chunk_t"].append(now)
                        rec["chunk_n"].append(_tokens(piece))
                    if choice.get("finish_reason"):
                        rec["finish"] = choice["finish_reason"]
            rec["ok"] = saw_done and rec["usage"] is not None
            if not rec["ok"]:
                rec["error"] = "stream ended without [DONE] or usage"
    except asyncio.CancelledError:
        rec["aborted"] = True
        rec["error"] = "cut by the client at the end of the run"
        raise
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as exc:
        rec["error"] = f"{type(exc).__name__}: {exc}"[:300]
    finally:
        rec["done"] = time.monotonic()
        plan["_records"].append(rec)
    return rec


async def run_open(session, plan: dict) -> None:
    t0 = plan["t0"]
    tasks = []
    for req in sorted(plan["requests"], key=lambda r: r["due"]):
        due = t0 + req["due"]
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(
            one_request(session, plan, req, due)))
    if not tasks:
        return
    # Requests due late in the window need time to finish; one that has
    # not ended when the drain is over is cut and counts as failed.
    left = t0 + plan["seconds"] + plan["drain_seconds"] - time.monotonic()
    _, pending = await asyncio.wait(tasks, timeout=max(left, 0.0))
    for task in pending:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def run_closed(session, plan: dict) -> None:
    t_end = plan["t0"] + plan["seconds"]

    async def client(seq: list[dict]) -> None:
        k = 0
        while time.monotonic() < t_end:
            req = dict(seq[k % len(seq)])
            lap = k // len(seq)
            if lap:
                # A client that has used up its sizes starts over; a new
                # first word keeps the prompt from hitting the prefix
                # cache of its first lap.
                req["id"] += lap * 1000
                req["content"] = (f"w{LAP_WORD_BASE + lap} "
                                  + req["content"].split(" ", 1)[-1])
            k += 1
            await one_request(session, plan, req, None)

    tasks = [asyncio.create_task(client(seq)) for seq in plan["sequences"]]
    await asyncio.sleep(max(t_end - time.monotonic(), 0.0))
    # The window is over: what is still streaming is cut, not waited for.
    for task in tasks:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def heartbeat(beat: dict, period: float = 0.05) -> None:
    """How late this process's own loop ever woke: where every stream
    stands still for seconds, it tells a machine that stood still (this
    reads as late as the streams) from a server that did (this reads
    nothing)."""
    while True:
        before = time.monotonic()
        await asyncio.sleep(period)
        late = time.monotonic() - before - period
        if late > beat["late_max_s"]:
            beat.update(late_max_s=late, at=before)


async def main(plan_path: str, results_path: str) -> None:
    with open(plan_path, encoding="utf-8") as fh:
        plan = json.load(fh)
    plan["_records"] = []
    started = time.monotonic()
    beat = {"late_max_s": 0.0, "at": None}
    pulse = asyncio.create_task(heartbeat(beat))
    connector = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    async with aiohttp.ClientSession(connector=connector,
                                     timeout=timeout) as session:
        if plan["mode"] == "open":
            await run_open(session, plan)
        elif plan["mode"] == "closed":
            await run_closed(session, plan)
        else:
            raise SystemExit(f"unknown mode {plan['mode']!r}")
    pulse.cancel()
    out = {"started": started, "ended": time.monotonic(),
           "heartbeat": beat, "records": plan["_records"]}
    with open(results_path, "w", encoding="utf-8") as fh:
        json.dump(out, fh)


if __name__ == "__main__":
    asyncio.run(main(sys.argv[1], sys.argv[2]))
