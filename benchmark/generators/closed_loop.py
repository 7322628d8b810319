"""Closed loop: a fixed number of clients, each sending its next request
when the last one has ended.

Parameters (the traffic file): ``clients``, ``prompt`` and ``output`` length
distributions, ``ramp_s`` (clients start spread evenly over this long) or
``start_on: "first_token"`` (each client starts when the one before it has
its first token: an event of the server and not an instant of the clock, so
that no send falls on the boundary of an engine tick by a few milliseconds
and lands in this tick in one run and the next in another, from where a
closed loop of few clients keeps another order for the whole window),
``stagger_first_wave`` (the first request of each client asks a seeded
uniform (0, 1] share of its drawn output, so that finishes do not come in
waves), ``set_size`` (clients replay one shared seeded set of that many
requests in turn, instead of a stream of their own of ``stream_length``
requests, which a client that gets through it starts again), ``lead_in_s`` (the
least time traffic flows before the window opens; it opens once every client
also has a stream running) and ``drain`` (``"finish"``: requests in flight at
the window's end run to their end, for at most ``drain_s``; ``"cancel"``:
the client hangs up on them, for requests that would outlast the window by
most of a minute).
"""

from __future__ import annotations

import asyncio
import itertools
import time

import numpy as np

from benchmark.loadgen import Record, draw_lengths

LOOP = "closed"


def plan(params: dict, seed: int, seconds: float) -> dict:
    """The requests each client will send, from the seed alone: a shared
    cyclic set, or one stream per client (drawn as far as any run can get)."""
    n = int(params["clients"])

    def draw(rng, count):
        return [
            {"prompt_len": p, "max_tokens": o}
            for p, o in zip(draw_lengths(rng, params["prompt"], count),
                            draw_lengths(rng, params["output"], count))
        ]

    if params.get("set_size"):
        streams = {"shared": draw(
            np.random.default_rng([seed, 3]), int(params["set_size"])
        )}
    else:
        # one stratified set over all clients, dealt round: what any prefix
        # of the run draws from is spread over the whole distribution
        per_client = int(params.get("stream_length", 8))
        dealt = draw(np.random.default_rng([seed, 3]), n * per_client)
        streams = {c: dealt[c::n] for c in range(n)}
    first = np.random.default_rng([seed, 4])
    share = [
        float(1.0 - first.random()) if params.get("stagger_first_wave") else 1.0
        for _ in range(n)
    ]
    return {"streams": streams, "first_wave_share": share}


async def drive(ctx, params: dict, seed: int, seconds: float) -> None:
    n = int(params["clients"])
    planned = plan(params, seed, seconds)
    shared = (
        itertools.cycle(planned["streams"]["shared"])
        if "shared" in planned["streams"] else None
    )
    streaming = [asyncio.Event() for _ in range(n)]
    stop = asyncio.Event()
    start = time.monotonic()

    async def client(c: int) -> None:
        if params.get("start_on") == "first_token":
            if c:
                await streaming[c - 1].wait()
        else:
            await asyncio.sleep(c * float(params.get("ramp_s", 0.0)) / n)
        own = None if shared is not None else itertools.cycle(
            planned["streams"][c]
        )
        first = True
        while not stop.is_set():
            spec = next(shared if shared is not None else own)
            asked = spec["max_tokens"]
            if first:
                asked = max(1, round(asked * planned["first_wave_share"][c]))
            rec = Record(
                index=ctx.next_index(), phase="traffic", client=c,
                prompt_len=spec["prompt_len"], max_tokens=asked,
            )
            task = ctx.client.start(rec)
            if first:
                while not rec.arrivals and not task.done():
                    await asyncio.sleep(0.01)
                streaming[c].set()
                first = False
            await asyncio.wait([task])

    clients = [asyncio.get_running_loop().create_task(client(c)) for c in range(n)]
    await asyncio.gather(*(e.wait() for e in streaming))
    await asyncio.sleep(max(
        0.0, start + float(params.get("lead_in_s", 0.0)) - time.monotonic()
    ))
    t0 = time.monotonic()
    await ctx.open_window(t0)
    await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
    await ctx.close_window()
    stop.set()
    if params.get("drain", "finish") == "cancel":
        for t in clients:
            t.cancel()
        await asyncio.wait(clients, timeout=30.0)
        ctx.cancelled += await ctx.client.cancel_pending()
    else:
        await asyncio.wait(clients, timeout=float(params["drain_s"]))
