"""JSON-lines client of the serving front end, kept with the benchmark so
that a change to the program's own client cannot move the yardstick.

Wire protocol (the front end's): one JSON object per line.  The client sends
``{"prompt": [...], "max_tokens": n, "temperature": 0.0, "ignore_eos":
true}``; the server answers ``{"uid": u}`` (or a terminal rejection line
with ``"finished": true``) and then one line per token, the last with
``"finished": true``.  The client stamps every line with the host clock as
it arrives.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import List, Optional


@dataclasses.dataclass
class Record:
    """What the client saw of one request."""
    index: int
    prompt: List[int]
    max_tokens: int
    due: float                      # host clock, when the request was due
    sent: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    finish: Optional[str] = None    # finish_reason of the last line
    done: Optional[float] = None    # host clock of the last line
    session: int = -1
    ask: int = 0

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None

    def seen(self, end: float) -> List[float]:
        """Arrival times of the tokens streamed before ``end``."""
        return [t for t in self.times if t <= end]

    def complete(self, end: float) -> bool:
        """Served to its end, with every token, before ``end``."""
        return (self.finish in ("length", "stop") and self.done is not None
                and self.done <= end and bool(self.tokens))

    def failed(self, end: float) -> bool:
        """No token before ``end``, or ended by an error before it.  A
        request still streaming when measuring stops has not failed."""
        ended_badly = (self.finish not in (None, "length", "stop")
                       and self.done is not None and self.done <= end)
        return not self.seen(end) or ended_badly


class Connection:
    """One client connection; requests on it are sequential."""

    def __init__(self, port: int, line_limit: int = 1 << 20):
        self.port, self.line_limit = port, line_limit
        self.reader = self.writer = None

    async def __aenter__(self) -> "Connection":
        self.reader, self.writer = await asyncio.open_connection(
            "127.0.0.1", self.port, limit=self.line_limit)
        return self

    async def __aexit__(self, *exc) -> None:
        self.writer.close()
        try:
            await self.writer.wait_closed()
        except (ConnectionResetError, BrokenPipeError):
            pass

    async def request(self, prompt: List[int], rec: Record) -> None:
        """Send one greedy request and read its stream into ``rec``."""
        msg = {"prompt": prompt, "max_tokens": rec.max_tokens,
               "temperature": 0.0, "ignore_eos": True}
        self.writer.write(json.dumps(msg).encode() + b"\n")
        rec.sent = time.perf_counter()
        await self.writer.drain()
        while True:
            line = await self.reader.readline()
            now = time.perf_counter()
            if not line:
                rec.finish = rec.finish or "disconnected"
                rec.done = now
                return
            ev = json.loads(line)
            if "uid" in ev and "token" not in ev:
                continue                        # the acknowledgement
            if ev.get("token", -1) >= 0:
                rec.tokens.append(int(ev["token"]))
                rec.times.append(now)
            if ev.get("finished"):
                rec.finish = ev.get("finish_reason") or "unknown"
                rec.done = now
                return
