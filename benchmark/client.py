"""The load generator: asyncio + aiohttp in the benchmark's parent, streaming
``/v1/completions`` requests timed from the client's side.

One request is correct (``ok``) only if it returned HTTP 200, a
``finish_reason`` of ``length``, ``usage`` with exactly its prompt and
``max_tokens`` counts, and finished before the drain ended.
"""

from __future__ import annotations

import asyncio
import json
import random
import time

import aiohttp

from . import stats, traffic

DRAIN_FLOOR_S = 15.0


def request_body(model: str, prompt: str, max_tokens: int, logprobs: int = None) -> dict:
    body = {
        "model": model, "prompt": prompt, "max_tokens": max_tokens,
        "temperature": 0, "stream": True,
        "stream_options": {"include_usage": True},
        "nvext": {"ignore_eos": True},
    }
    if logprobs is not None:  # only the answer held against the reference asks
        body["logprobs"] = logprobs
    return body


async def stream_one(session, url: str, body: dict, rec: dict, clock) -> None:
    """POST one streaming request; fill ``rec`` with arrival times on
    ``clock`` (seconds from the start of the window)."""
    rec["sent_s"] = clock()
    rec.setdefault("due_s", rec["sent_s"])
    token_times, texts = rec["token_times"], []
    finish, usage = None, None
    try:
        async with session.post(url, json=body) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = (await resp.text())[:300]
                return
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                now = clock()
                data = raw[5:].strip()
                if data == b"[DONE]":
                    break
                chunk = json.loads(data)
                if chunk.get("usage"):
                    usage = chunk["usage"]
                for choice in chunk.get("choices") or ():
                    if choice.get("finish_reason"):
                        finish = choice["finish_reason"]
                    if choice.get("logprobs"):
                        rec.setdefault("top_logprobs", []).extend(
                            choice["logprobs"]["top_logprobs"])
                    text = choice.get("text")
                    if text:
                        # the word-level tokenizer: one word per token
                        token_times.append((now, max(1, len(text.split()))))
                        texts.append(text)
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
        return
    rec["text"] = "".join(texts)
    rec["finish_reason"], rec["usage"] = finish, usage
    if token_times:
        rec["first_s"], rec["last_s"] = token_times[0][0], token_times[-1][0]
    rec["got_tokens"] = (usage or {}).get("completion_tokens", 0)
    rec["ok"] = bool(
        finish == "length" and usage and token_times
        and usage.get("prompt_tokens") == rec["prompt_tokens"]
        and usage.get("completion_tokens") == rec["max_tokens"]
    )


def new_record(i: int, prompt_tokens: int, max_tokens: int) -> dict:
    return {
        "i": i, "prompt_tokens": prompt_tokens, "max_tokens": max_tokens,
        "ok": False, "got_tokens": 0, "token_times": [], "in_window": False,
    }


async def run_window(port: int, model: str, plain_words: list, schedule: dict,
                     seconds: float, on_window=None, background=()) -> dict:
    """Pre-roll, window and drain. Returns the records of the requests sent
    and how long the drain took. ``on_window`` is called at the start of the
    window; ``background`` are coroutine functions ``f(clock, stop_event,
    session)`` that run beside the load (samplers, the trace trigger)."""
    url = f"http://127.0.0.1:{port}/v1/completions"
    text_rng = random.Random(schedule["text_seed"])
    bodies = [
        request_body(model, traffic.prompt_text(plain_words, p, text_rng), o)
        for p, o in zip(schedule["prompt_tokens"], schedule["output_tokens"])
    ]
    records = [new_record(i, p, o) for i, (p, o) in enumerate(
        zip(schedule["prompt_tokens"], schedule["output_tokens"]))]
    preroll = schedule["preroll_s"]
    timeout = aiohttp.ClientTimeout(total=None, sock_read=None)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn, timeout=timeout) as session:
        t_origin = time.perf_counter() + preroll + 0.05  # start of the window

        def clock() -> float:
            return time.perf_counter() - t_origin

        stop = asyncio.Event()
        side = [asyncio.ensure_future(f(clock, stop, session)) for f in background]
        tasks = []

        async def mark_window():
            await asyncio.sleep(max(0.0, -clock()))
            if on_window is not None:
                on_window()

        marker = asyncio.ensure_future(mark_window())
        if schedule["due"] is not None:
            # open loop: every request goes at its due time, whatever the
            # state of the earlier ones
            for i, due in enumerate(schedule["due"]):
                delay = due - clock()
                if delay > 0:
                    await asyncio.sleep(delay)
                rec = records[i]
                rec["due_s"], rec["in_window"] = due, due >= 0.0
                tasks.append(asyncio.ensure_future(
                    stream_one(session, url, bodies[i], rec, clock)))
            await asyncio.sleep(max(0.0, seconds - clock()))
        else:
            next_i = iter(range(schedule["n"]))

            async def caller():
                for i in next_i:
                    if clock() >= seconds:
                        return
                    rec = records[i]
                    rec["in_window"] = clock() >= 0.0
                    await stream_one(session, url, bodies[i], rec, clock)

            tasks = [asyncio.ensure_future(caller())
                     for _ in range(schedule["clients"])]
            await asyncio.sleep(max(0.0, seconds - clock()))
        await marker
        # drain: until all are done, at most the longest answer at twice the
        # gap measured in the window (15 s or more)
        if tasks:
            limit = stats.drain_limit_s(
                [r for r in records if "sent_s" in r], DRAIN_FLOOR_S)
            _, pending = await asyncio.wait(tasks, timeout=limit)
            for t in pending:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        drain_s = clock() - seconds
        stop.set()
        await asyncio.gather(*side, return_exceptions=True)
    sent = [r for r in records if "sent_s" in r]
    return {"records": sent, "drain_s": drain_s}


async def probe(port: int, model: str, prompt: str, max_tokens: int, logprobs: int = None,
                timeout_s: float = 120.0) -> dict:
    """One greedy request alone; returns its record (with ``text``, and with
    ``logprobs`` asked ``top_logprobs``: per token, word -> log-probability).
    ``timeout_s`` is the whole request's limit: 120 s for a request whose
    programs the warm-up compiled; the one that asks ``logprobs`` compiles its
    programs on the served path and is given the server's start-up patience."""
    rec = new_record(-1, len(prompt.split()), max_tokens)
    t0 = time.perf_counter()
    timeout = aiohttp.ClientTimeout(total=timeout_s)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        await stream_one(
            session, f"http://127.0.0.1:{port}/v1/completions",
            request_body(model, prompt, max_tokens, logprobs), rec,
            lambda: time.perf_counter() - t0,
        )
    return rec
