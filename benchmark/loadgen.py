"""The benchmark's client: token-id prompts from a seed, one SSE completion
per call over localhost HTTP, every token's arrival time kept.

One thread, one asyncio loop: the generator shares the machine's cores with
the server it measures, so it stays small. Nothing here imports JAX or the
program; the wire format is the gateway's public one (``/v1/completions``
with ``"stream": true``, ``serving/protocol.py``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import math
import statistics
import time
from typing import List, Optional

import numpy as np

#: Per-request deadline sent in the body: the gateway's own maximum, so that
#: only the benchmark's drain decides what counts as failed.
REQUEST_TIMEOUT_S = 600.0


@dataclasses.dataclass
class Record:
    """One request as the client saw it. Times are ``time.monotonic()``."""

    index: int
    phase: str                      # "warm" | "traffic"
    prompt_len: int
    max_tokens: int
    due: Optional[float] = None     # open loop: when it should be sent
    sent: Optional[float] = None
    status: Optional[int] = None
    arrivals: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    ended: Optional[float] = None
    error: Optional[str] = None
    client: Optional[int] = None

    @property
    def first_t(self) -> Optional[float]:
        return self.arrivals[0] if self.arrivals else None

    def ok(self, vocab: int) -> bool:
        """Ended with 200, exactly the tokens asked, every id in range."""
        return (
            self.ended is not None and self.status == 200
            and self.error is None
            and len(self.tokens) == self.max_tokens
            and all(0 <= t < vocab for t in self.tokens)
        )


def seeded_prompt(seed: int, index: int, length: int, vocab: int) -> List[int]:
    rng = np.random.default_rng([seed, 7, index])
    return [int(t) for t in rng.integers(1, vocab, size=length)]


def draw_lengths(rng, spec: dict, n: int) -> List[int]:
    """``n`` lengths from a distribution given as data: ``lognormal``
    (median, sigma), ``uniform`` (min, max) or ``fixed`` (value), clipped to
    [min, max].

    Stratified: the values are the distribution's quantiles at
    ``(i + 0.5) / n`` and only their ORDER comes from the seed. Every seed
    then offers the same amount of work, tail included, and runs differ by
    when the long requests fall, not by whether there are any: that is what
    lets a window of some tens of requests repeat within a few percent."""
    kind = spec["dist"]
    quantiles = [(i + 0.5) / n for i in range(n)]
    if kind == "lognormal":
        normal = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
        values = [math.exp(normal.inv_cdf(q)) for q in quantiles]
    elif kind == "uniform":
        values = [spec["min"] + q * (spec["max"] - spec["min"]) for q in quantiles]
    elif kind == "fixed":
        return [int(spec["value"])] * n
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    clipped = [int(min(spec["max"], max(spec["min"], round(x)))) for x in values]
    return [clipped[i] for i in rng.permutation(n)]


class Client:
    def __init__(self, port: int, vocab: int, seed: int):
        self.port, self.vocab, self.seed = port, vocab, seed
        self.records: List[Record] = []
        self._tasks: List[asyncio.Task] = []

    def start(self, rec: Record) -> asyncio.Task:
        """Send ``rec`` now, as a task the caller may await or leave."""
        self.records.append(rec)
        task = asyncio.get_running_loop().create_task(self._complete(rec))
        self._tasks.append(task)
        return task

    async def wait_all(self, timeout: float) -> None:
        pending = [t for t in self._tasks if not t.done()]
        if pending:
            await asyncio.wait(pending, timeout=timeout)

    async def cancel_pending(self) -> int:
        """Hang up on every unfinished stream (the gateway then frees the
        slot); returns how many there were."""
        pending = [t for t in self._tasks if not t.done()]
        for t in pending:
            t.cancel()
        if pending:
            await asyncio.wait(pending, timeout=30.0)
        return len(pending)

    async def get(self, path: str) -> bytes:
        reader, writer = await asyncio.open_connection("127.0.0.1", self.port)
        try:
            writer.write(
                f"GET {path} HTTP/1.1\r\nHost: localhost\r\n\r\n".encode()
            )
            await writer.drain()
            raw = await reader.read(-1)
        finally:
            writer.close()
        return raw.split(b"\r\n\r\n", 1)[1]

    async def _complete(self, rec: Record) -> None:
        prompt = seeded_prompt(self.seed, rec.index, rec.prompt_len, self.vocab)
        body = json.dumps({
            "prompt": prompt, "max_tokens": rec.max_tokens, "stream": True,
            "timeout_s": REQUEST_TIMEOUT_S,
        }).encode()
        head = (
            "POST /v1/completions HTTP/1.1\r\nHost: localhost\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n"
        ).encode()
        writer = None
        try:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", self.port
            )
            rec.sent = time.monotonic()
            writer.write(head + body)
            await writer.drain()
            status_line = await reader.readline()
            rec.status = int(status_line.split()[1])
            while (await reader.readline()) not in (b"\r\n", b"\n", b""):
                pass
            if rec.status != 200:
                rec.error = (await reader.read(-1)).decode(errors="replace")[:300]
                return
            done = False
            while True:
                line = await reader.readline()
                if not line:
                    break
                if not line.startswith(b"data: "):
                    continue
                now = time.monotonic()
                data = line[6:].strip()
                if data == b"[DONE]":
                    done = True
                    break
                choice = json.loads(data)["choices"][0]
                for tok in choice["token_ids"]:
                    rec.tokens.append(tok)
                    rec.arrivals.append(now)
                rec.finish_reason = choice["finish_reason"] or rec.finish_reason
            if not done:
                rec.error = "stream ended without [DONE]"
        except asyncio.CancelledError:
            rec.error = "cancelled by the client"
            raise
        except (OSError, ValueError, KeyError, IndexError) as e:
            rec.error = repr(e)
        finally:
            rec.ended = time.monotonic()
            if writer is not None:
                writer.close()
