"""The dispatches the DEVICE ran inside a traced span, from the tick records'
dispatch clock (``dispatch_clock``, index for index with ``dispatches``: the
flight recorder's enqueue, return and device-ready stamps, armed while the
ticks are polled, as a ``--trace 1`` run polls them).

A reader that sets a kernel's events in a trace beside what its calls needed
takes the dispatches of the same span. By the tick's own stamp (``t0_ns``:
when the host began the tick) that holds where the device keeps up with the
host. Where it runs seconds behind (``glm-5.2.codebase``: a 4096-wide chunk
is 0.5-1 s of device time and the host enqueues four of them in a tick), the
dispatches a tick enqueued inside the span are not the ones the device ran
there: 11 against the 6 whose kernel events the trace holds (my chip run,
PR 44). The ready stamp says when a dispatch's program ended on the device.
A program without the clock (the parent of PR 41), or a run that did not
poll the ticks, falls back to the tick's stamp.
"""

from __future__ import annotations


def dispatches_in_span(run, span) -> list:
    """Every dispatch record ``(kind, shape, valid tokens, ...)`` whose
    program ended on the device inside ``span`` (epoch seconds)."""
    out = []
    for t in run.ticks.values():
        records = t.get("dispatches", ())
        clock = t.get("dispatch_clock") or ()
        for i, d in enumerate(records):
            ready = clock[i].get("ready_ns") if i < len(clock) else None
            at = (ready if ready else t["t0_ns"]) / 1e9
            if span[0] <= at < span[1]:
                out.append(d)
    return out
