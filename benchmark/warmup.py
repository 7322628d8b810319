"""Warm-up: real requests over HTTP that make the engine compile (or read
from the persistent cache) every program the cell's traffic can reach,
before the window opens.

The engine keys its executables by (rows, pad width, page-table width). The
table widens along a ladder as contexts grow and narrows again only when the
engine is idle, and the program has no warm-up call, so the only way to
reach a shape from outside is traffic that needs it:

* ``"table": "all"`` walks every rung of the ladder the traffic can reach
  with one request sized to it (for traffic that lets the engine fall idle
  inside the window, so that any rung can come back);
* then an ANCHOR request sized to the widest rung pins the table there for as
  long as it decodes (``anchor_tokens``), and while it does, every pad width
  the traffic's prompts fall into is sent once alone, once chunked where the
  traffic has prompts over one chunk, and once as a group of each of
  ``group_rows`` requests at the same instant (the engine batches same-bucket
  admissions of one tick into one dispatch padded to 2, 4 or 8 rows).

The anchor is left running: the traffic's lead-in starts beneath it, so the
table never narrows between warm-up and window.
"""

from __future__ import annotations

import asyncio

from benchmark import prom
from benchmark.loadgen import Record


def rung_for(slots, page_size, tokens):
    return next((w for w in slots if w * page_size >= tokens), slots[-1])


async def warm(ctx, shapes: dict, traffic: dict) -> dict:
    spec = traffic["warm"]
    ps, slots = shapes["page_size"], shapes["table_slots"]
    k = shapes["decode_steps"]
    # one decode tick after the prefill, and room for the engine's look-ahead
    # of two ticks inside the same rung of the table
    short = k + 1
    cap = shapes["max_seq_len"] - short - 2 * k - 1
    p_lo, p_hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    reach = p_hi + traffic["output"]["max"] + 2 * k + 1
    w_max = rung_for(slots, ps, min(reach, shapes["max_seq_len"]))

    def sized_to(w):
        below = max([0] + [s for s in slots if s < w])
        return max(2, min(cap, below * ps + 1))

    async def one(prompt_len, tokens, wait=True):
        rec = Record(index=ctx.next_index(), phase="warm",
                     prompt_len=prompt_len, max_tokens=tokens)
        task = ctx.client.start(rec)
        if wait:
            await task
        return rec, task

    async def batched():
        text = (await ctx.client.get("/metrics")).decode()
        return prom.parse(text).get("batched_prefills", 0.0)

    sent = {"rungs": [], "groups": [], "retries": 0}
    if spec["table"] == "all":
        for w in [s for s in slots if s < w_max]:
            await one(sized_to(w), short)
            sent["rungs"].append(w)
    # the anchor itself must stay inside the rung it pins, look-ahead and all
    anchor_tokens = max(short, min(
        int(spec["anchor_tokens"]), w_max * ps - sized_to(w_max) - 3 * k - 2
    ))
    anchor, anchor_task = await one(sized_to(w_max), anchor_tokens, wait=False)
    while not anchor.arrivals and not anchor_task.done():
        await asyncio.sleep(0.005)
    sent["rungs"].append(w_max)

    chunk = shapes["chunk_tokens"]
    classes, below = [], 0
    for width in shapes["pad_widths"]:
        lo, hi = max(p_lo, below + 1), min(p_hi, width, chunk)
        if lo <= hi:
            classes.append(hi)
        below = width
    for length in classes:
        await one(length, short)
        # a mesh engine admits one row a dispatch: it has no groups to warm
        for rows in spec["group_rows"] if shapes["group_admission"] else ():
            for attempt in range(4):
                before = await batched()
                group = [await one(length, short, wait=False) for _ in range(rows)]
                await asyncio.wait([t for _, t in group])
                # all of them in ONE dispatch, or a tick boundary fell
                # between their arrivals: send them again
                if await batched() - before == rows:
                    break
                sent["retries"] += 1
            sent["groups"].append([length, rows])
    if p_hi > chunk:
        await one(min(p_hi, cap, chunk + chunk // 2), short)
        sent["chunked"] = True
    sent["anchors"] = 1
    while anchor_task.done():
        # the anchor ended before the rounds did: the idle engine has
        # narrowed its table again, so pin it once more before the lead-in
        anchor, anchor_task = await one(sized_to(w_max), anchor_tokens, wait=False)
        while not anchor.arrivals and not anchor_task.done():
            await asyncio.sleep(0.005)
        sent["anchors"] += 1
        if anchor.arrivals:
            break
    return sent
