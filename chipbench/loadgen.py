"""Offer a plan's requests to the frontend over HTTP and record what came
back. One process, one thread (asyncio + aiohttp): the generator shares
the machine's cores with the server it measures.

Times are ``time.monotonic()`` seconds. An open-loop request is timed
from when it was due, not from when it was sent, so a stalled generator
or server charges the wait to the requests behind it; how late the
generator itself ran is recorded per request."""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass

import aiohttp

from chipbench.generators.common import Plan, Request


CUT = "cut: begun after the window closed"


@dataclass
class Record:
    req: Request
    due_abs: float            # open loop: absolute due time; closed: send time
    sent: float = 0.0
    first: float | None = None          # first chunk: the first token
    finished: float | None = None       # the chunk with finish_reason: the last token
    done: float | None = None           # [DONE] seen
    status: int = 0
    finish: str | None = None
    prompt_tokens: int | None = None
    completion_tokens: int | None = None
    cached_tokens: int = 0
    error: str | None = None

    @property
    def ok(self) -> bool:
        return (self.error is None and self.status == 200 and self.done is not None
                and self.first is not None and self.finished is not None)


def chat_body(req: Request, model: str, temperature: float) -> dict:
    return {
        "model": model,
        "messages": [{"role": "user", "content": req.prompt}],
        "max_tokens": req.max_tokens,
        "temperature": temperature,
        "seed": req.seed,
        "stream": True,
        "stream_options": {"include_usage": True},
        # Random weights can sample EOS; output lengths must be exactly
        # what the generator drew.
        "dyn": {"ignore_eos": True},
    }


async def send(session: aiohttp.ClientSession, url: str, body: dict, rec: Record) -> None:
    """One streaming chat completion; fills ``rec``. Never raises for a
    failed request: the failure is the record.

    The stream's first chunk (the role delta) is sent with the engine's
    first output, so it times the first token; the chunk that carries
    ``finish_reason`` is sent with the last. Chunks between them are not
    timed: the frontend sends one only when the new tokens decode to
    text, and random weights over a 152k vocabulary produce ids the byte
    tokenizer drops."""
    rec.sent = time.monotonic()
    try:
        async with session.post(url, json=body) as resp:
            rec.status = resp.status
            if resp.status != 200:
                rec.error = (await resp.text())[:300]
                return
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                payload = raw[5:].strip()
                if payload == b"[DONE]":
                    rec.done = time.monotonic()
                    continue
                now = time.monotonic()
                chunk = json.loads(payload)
                usage = chunk.get("usage")
                if usage:
                    rec.prompt_tokens = usage.get("prompt_tokens")
                    rec.completion_tokens = usage.get("completion_tokens")
                    rec.cached_tokens = (usage.get("prompt_tokens_details")
                                         or {}).get("cached_tokens", 0) or 0
                for choice in chunk.get("choices", ()):
                    if rec.first is None:
                        rec.first = now
                    if choice.get("finish_reason"):
                        rec.finish, rec.finished = choice["finish_reason"], now
    except asyncio.CancelledError:
        rec.error = CUT
        raise
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"[:300]


@dataclass
class LoadResult:
    records: list[Record]
    t_open: float
    t_close: float
    unfinished: int = 0       # measured open-loop requests the drain gave up on


async def run_plan(plan: Plan, base_url: str, model: str, seconds: float, *,
                   drain_timeout: float, at_open=None, at_close=None,
                   at_mid=None, mid_offset: float = 0.0) -> LoadResult:
    """Run ramp and window. ``at_open`` / ``at_mid`` / ``at_close`` are
    coroutines functions called at the window's open, middle and close
    (scrapes, the trace request; ``mid_offset`` shifts the middle one); they
    run beside the load."""
    url = f"{base_url}/v1/chat/completions"
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30, sock_read=300)
    conn = aiohttp.TCPConnector(limit=0)
    records: list[Record] = []
    hooks: list[asyncio.Task] = []
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        t_open = time.monotonic() + plan.ramp_seconds
        t_close = t_open + seconds

        async def hook_at(when: float, fn) -> None:
            if fn is not None:
                await asyncio.sleep(max(0.0, when - time.monotonic()))
                await fn()

        hooks = [asyncio.create_task(hook_at(t_open, at_open)),
                 asyncio.create_task(hook_at((t_open + t_close) / 2 + mid_offset, at_mid)),
                 asyncio.create_task(hook_at(t_close, at_close))]
        tasks: list[asyncio.Task] = []
        unfinished = 0
        if plan.loop == "open":
            for req in plan.requests:
                due_abs = t_open + req.due
                delay = due_abs - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                rec = Record(req, due_abs)
                records.append(rec)
                tasks.append(asyncio.create_task(
                    send(session, url, chat_body(req, model, plan.temperature), rec)))
            await asyncio.sleep(max(0.0, t_close - time.monotonic()))
            measured = [t for t, r in zip(tasks, records) if r.req.due >= 0]
            if measured:
                _, pending = await asyncio.wait(measured, timeout=drain_timeout)
                unfinished = len(pending)
        else:
            async def client(reqs: list[Request]) -> None:
                for req in reqs:
                    rec = Record(req, time.monotonic())
                    records.append(rec)
                    await send(session, url, chat_body(req, model, plan.temperature), rec)

            # The clients keep sending past the close, so that the batch
            # stays as full for the last measured stream as for the first;
            # the run ends when every stream begun inside the window has
            # ended, and what was begun after the close is cut.
            tasks = [asyncio.create_task(client(c)) for c in plan.clients]
            await asyncio.sleep(max(0.0, t_close - time.monotonic()))
            deadline = time.monotonic() + drain_timeout
            while time.monotonic() < deadline and any(
                    r.done is None and r.error is None
                    for r in records if r.due_abs < t_close):
                await asyncio.sleep(0.05)
            unfinished = sum(r.done is None and r.error is None
                             for r in records if t_open <= r.due_abs < t_close)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        await asyncio.gather(*hooks)
    return LoadResult(records, t_open, t_close, unfinished)
