"""Open loop: requests are due on a seeded Poisson schedule at a fixed rate,
and are sent whether or not earlier ones have finished.

The schedule is a Poisson process CONDITIONED ON ITS COUNT: exactly
``round(rate * seconds)`` arrivals fall in the window, at seeded uniform
instants (which is what a Poisson process looks like given how many points
it has), and the lengths are stratified (``loadgen.draw_lengths``). The
arrivals are as bursty as Poisson arrivals are; the amount of work offered is
the same for every seed.

Parameters (the traffic file): ``rate_rps``, ``prompt`` and ``output``
length distributions, ``lead_in_s`` (traffic that flows before the window
opens, so that the window starts on a warm queue) and ``drain_s`` (how long
after the window a request due inside it may still finish).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark.loadgen import Record, draw_lengths

LOOP = "open"


def plan(params: dict, seed: int, seconds: float) -> list:
    """Every request of the run, from the seed alone: due time relative to
    the window's start (negative in the lead-in), and lengths."""
    rng = np.random.default_rng([seed, 1])
    lengths = np.random.default_rng([seed, 2])
    rate, lead = float(params["rate_rps"]), float(params["lead_in_s"])
    due = sorted(
        [float(t) for t in rng.uniform(-lead, 0.0, round(rate * lead))]
        + [float(t) for t in rng.uniform(0.0, seconds, round(rate * seconds))]
    )
    prompts = draw_lengths(lengths, params["prompt"], len(due))
    outputs = draw_lengths(lengths, params["output"], len(due))
    return [
        {"due": t, "prompt_len": p, "max_tokens": o}
        for t, p, o in zip(due, prompts, outputs)
    ]


async def drive(ctx, params: dict, seed: int, seconds: float) -> None:
    requests = plan(params, seed, seconds)
    start = time.monotonic()
    t0 = start + float(params["lead_in_s"])
    opened = False
    for i, spec in enumerate(requests):
        due = t0 + spec["due"]
        if not opened and spec["due"] >= 0:
            await asyncio.sleep(max(0.0, t0 - time.monotonic()))
            await ctx.open_window(t0)
            opened = True
        await asyncio.sleep(max(0.0, due - time.monotonic()))
        ctx.client.start(Record(
            index=ctx.next_index(), phase="traffic", due=due,
            prompt_len=spec["prompt_len"], max_tokens=spec["max_tokens"],
        ))
    if not opened:
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        await ctx.open_window(t0)
    await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
    await ctx.close_window()
    await ctx.client.wait_all(float(params["drain_s"]))
