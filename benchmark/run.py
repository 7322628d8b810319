#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process is the load generator and never imports JAX: a process that has
touched JAX holds the chip. It starts ``benchmark/server.py`` as a child,
which holds the cell's chip or chips and serves the program's own gateway,
and speaks ``/v1/completions`` with ``"stream": true`` to it over localhost
HTTP, as the system's users do. Set-up (weights, engine, the logits check,
warm-up of this cell's shapes, the traffic's lead-in) ends when the window
opens; the window lasts ``--seconds``. ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` is a run of its own that records a
profiler trace of a few seconds in the middle of the window, polls the
flight recorder, and prints the cell's per-layer metrics.

The LAST line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``) and, last, ``compared``: every number that
``correct`` compared beside its limit, which are also the last lines of
standard error. A ``--trace 1`` run whose trace holds no device operation is
traced once more inside the same window, and if that is empty too the run
prints no result line and exits non-zero. Everything else — the schedule's
summary, lateness, the per-request table, the reduced trace — goes to
earlier lines and to ``<out>/``.

Everything that belongs to one configuration, traffic mix or metric is a
file found by the name ``BENCHMARK.json`` gives: ``configs/<config>.json``
(with the ``weights/``, ``reference/`` and ``kernels/`` modules it needs),
``traffic/<mix>.json`` (with the ``generators/`` module it names),
``end_to_end/<metric>.py`` and ``layer_metrics/<metric>.py``.

``--rehearse-cpu`` walks the same control flow on the CPU at the tiny size
the configuration and traffic files carry under ``"rehearse"``; it names the
device ``cpu`` and prints counts only, never a device metric.
``--rates a,b,c`` is the sweep that finds an open-loop cell's knee: one
set-up, then one window at each rate.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import queue
import subprocess
import sys
import threading
import time

STARTED = time.monotonic()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import prom, warmup  # noqa: E402
from benchmark.loadgen import Client  # noqa: E402

TRACE_SECONDS = 6.0
#: the least span worth a second trace, and what a second trace leaves of the
#: window behind it
TRACE_MARGIN_S = 0.5


class Child:
    """``benchmark/server.py`` and the line protocol with it."""

    def __init__(self, argv, env):
        self.proc = subprocess.Popen(
            argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True, bufsize=1,
        )
        self.lines: "queue.Queue" = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.proc.stdout:
            line = line.strip()
            if line.startswith("{"):
                try:
                    self.lines.put(json.loads(line))
                except ValueError:
                    pass
        self.lines.put(None)

    def expect(self, key: str, value: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            try:
                msg = self.lines.get(timeout=max(0.1, deadline - time.monotonic()))
            except queue.Empty:
                raise RuntimeError(f"no {key}={value} from the server in {timeout}s")
            if msg is None:
                raise RuntimeError(
                    f"the server ended (exit {self.proc.wait()}) before {key}={value}"
                )
            if msg.get(key) == value:
                return msg

    def call(self, cmd: str, timeout: float = 300.0) -> dict:
        self.proc.stdin.write(json.dumps({"cmd": cmd}) + "\n")
        self.proc.stdin.flush()
        return self.expect("reply", cmd, timeout)

    def stop(self) -> int:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write('{"cmd": "quit"}\n')
                self.proc.stdin.close()
            except OSError:
                pass
            try:
                self.proc.wait(timeout=90.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        return self.proc.returncode


class Run:
    """What one run collected; the metric readers take it whole."""

    def __init__(self, cell, conf, traffic, generator, ready, args):
        self.cell, self.conf, self.traffic = cell, conf, traffic
        self.loop = generator.LOOP
        self.shapes, self.device = ready["shapes"], ready["device"]
        self.numerics, self.child_setup = ready["numerics"], ready["setup"]
        self.seconds, self.trace = float(args.seconds), bool(args.trace)
        self.rehearse = args.rehearse_cpu
        self.client: Client = None
        self.child: Child = None
        self.t0 = None
        self.setup_s = None
        # the flight recorder stamps ticks with time.time(); everything here
        # is on time.monotonic()
        self.epoch_offset = time.time() - time.monotonic()
        self.metrics_open = self.metrics_close = self.metrics_end = None
        self.closed = None          # the child's reply to "close"
        self.ticks = {}             # tick id -> record, polled in traced runs
        self.trace_missing = None   # what the child's last trace lacked
        self.cancelled = 0
        self.warm = None
        self._index = 0
        self._background = []
        self._closing = None        # the "close" call, answered during the drain

    @property
    def records(self):
        return self.client.records

    def next_index(self) -> int:
        self._index += 1
        return self._index

    async def _metrics(self) -> dict:
        return prom.parse((await self.client.get("/metrics")).decode())

    async def _call(self, cmd: str) -> dict:
        return await asyncio.get_running_loop().run_in_executor(
            None, self.child.call, cmd
        )

    async def open_window(self, t0: float) -> None:
        self.t0 = t0
        self.setup_s = t0 - STARTED
        self.metrics_open, _ = await asyncio.gather(
            self._metrics(), self._call("open")
        )
        if self.trace:
            loop = asyncio.get_running_loop()
            self._background = [
                loop.create_task(self._poll_ticks()),
                loop.create_task(self._trace()),
            ]

    async def _trace(self) -> None:
        # a one-token-a-dispatch engine runs a hundred programs a second on
        # every chip: half the span holds more than enough of them
        span = min(TRACE_SECONDS, self.seconds / 3)
        if self.shapes["decode_steps"] == 1:
            span /= 2
        await asyncio.sleep(max(0.0, self.t0 + (self.seconds - span) / 2 - time.monotonic()))
        for attempt in (1, 2):
            await self._call("trace_start")
            await asyncio.sleep(span)
            self.trace_missing = (await self._call("trace_stop")).get("missing")
            # a CPU trace has no device plane and needs none
            if not self.trace_missing or self.rehearse or attempt == 2:
                return
            # an empty trace (PR 36's refusal: a line without busy_s): once
            # more in what is left of the window, which the first span ends
            # 19 s before the end of; a second empty one ends the run
            span = min(span, self.t0 + self.seconds - time.monotonic() - TRACE_MARGIN_S)
            if span < TRACE_MARGIN_S:
                self.trace_missing += "; the window had no room for a second trace"
                return

    async def _poll_ticks(self) -> None:
        every = 0.25 if self.shapes["decode_steps"] == 1 else 1.0
        while True:
            for t in json.loads(await self.client.get("/debug/ticks"))["ticks"]:
                self.ticks[t["tick"]] = t
            await asyncio.sleep(every)

    async def close_window(self) -> None:
        self.metrics_close = await self._metrics()
        for task in self._background[:1]:
            task.cancel()
        if self.trace:
            for t in json.loads(await self.client.get("/debug/ticks"))["ticks"]:
                self.ticks[t["tick"]] = t
            await asyncio.wait(self._background[1:], timeout=120.0)
            tracer = self._background[1]
            if not tracer.done():
                self.trace_missing = "the trace had not ended 120 s after the window"
            elif tracer.exception() is not None:
                self.trace_missing = f"the trace's calls failed: {tracer.exception()!r}"
        self._closing = asyncio.get_running_loop().create_task(self._call("close"))

    async def finish(self) -> None:
        """After the drain: the child's window report, then the gateway's
        counters once nothing is in flight."""
        self.closed = await self._closing
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            health = json.loads(await self.client.get("/healthz"))
            if not health["active_sessions"] and not health["queue_depth"]:
                break
            await asyncio.sleep(0.1)
        await asyncio.sleep(0.2)
        self.metrics_end = await self._metrics()


def served_path_check(run: Run) -> dict:
    """``correct``, part (a): every finished request returned exactly the
    tokens asked with ids in range, and the gateway's own counters equal the
    client's counts. Where the drain hung up on streams, what those streams
    received is a lower bound of the gateway's counts."""
    vocab = run.shapes["vocab_size"]
    recs = run.records
    ended = [r for r in recs if r.error != "cancelled by the client"]
    accepted = [r for r in recs if r.status == 200]
    got = run.metrics_end
    mine = {
        "http_requests": sum(1 for r in recs if r.status is not None),
        "sessions_submitted": len(accepted),
        "prefill_tokens": sum(r.prompt_len for r in accepted),
        "gateway_tokens": sum(len(r.tokens) for r in recs),
        "decode_tokens": sum(max(0, len(r.tokens) - 1) for r in recs),
    }
    theirs = {k: got.get(k, 0.0) for k in mine}
    if run.cancelled == 0:
        counters_ok = all(theirs[k] == mine[k] for k in mine)
    else:
        # a stream hung up on before its status line was read may or may
        # not have reached the engine
        started = sum(r.prompt_len for r in accepted if r.tokens)
        sent = sum(1 for r in recs if r.sent is not None)
        unread = sum(r.prompt_len for r in recs if r.sent and r.status is None)
        counters_ok = (
            mine["http_requests"] <= theirs["http_requests"] <= sent
            and mine["sessions_submitted"] <= theirs["sessions_submitted"] <= sent
            and started <= theirs["prefill_tokens"] <= mine["prefill_tokens"] + unread
            and theirs["gateway_tokens"] >= mine["gateway_tokens"]
            and theirs["decode_tokens"] >= mine["decode_tokens"]
        )
    bad_replies = sum(1 for r in ended if not r.ok(vocab))
    replies_ok = bad_replies == 0
    return {
        "ok": bool(counters_ok and replies_ok), "replies_ok": replies_ok,
        "bad_replies": bad_replies,
        "counters_ok": bool(counters_ok), "client": mine, "gateway": theirs,
        "hung_up_on": run.cancelled,
    }


def attempted_failed(run: Run):
    """Open loop: requests DUE inside the window; failed, those that did not
    end with 200 and the asked tokens by the end of the drain. Closed loop:
    requests that ENDED inside the window, whenever sent; failed, those that
    ended badly."""
    vocab = run.shapes["vocab_size"]
    lo, hi = run.t0, run.t0 + run.seconds
    if run.loop == "open":
        mine = [r for r in run.records if r.due is not None and lo <= r.due < hi]
    else:
        mine = [
            r for r in run.records
            if r.phase == "traffic" and r.ended is not None
            and lo <= r.ended < hi and r.error != "cancelled by the client"
        ]
    return len(mine), sum(1 for r in mine if not r.ok(vocab))


def reduced_trace(run: Run):
    """The child's reduction of the trace. A ``--trace 1`` run on the chip
    that has none raises, and ``main`` then prints no result line: never a
    traced run's line without ``busy_s`` and ``window_s``."""
    trace = run.closed.get("trace")
    if run.trace and not run.rehearse and not trace:
        raise RuntimeError(
            "the traced run has no trace to reduce: "
            + (run.trace_missing or "the trace was never taken")
        )
    return trace


def compared_numbers(run: Run, served: dict) -> dict:
    """Every number ``correct`` compared, as ``[number, limit]`` under a
    short name: the logit distances the configuration judges (``server.
    judged_numbers``) beside its tolerance, the replies that were not what
    was asked and the counter check, beside 0."""
    out = {
        f"logit_distance_{i}": [value, run.numerics["tolerance"]]
        for i, value in enumerate(run.numerics["judged"])
    }
    out["bad_replies"] = [served["bad_replies"], 0]
    out["counters_off"] = [int(not served["counters_ok"]), 0]
    return out


def read_metrics(run: Run, entries, package: str) -> dict:
    out = {}
    for entry in entries:
        cells = entry.get("workloads")
        if cells is not None and run.cell["name"] not in cells:
            continue
        reader = importlib.import_module(f"benchmark.{package}.{entry['name']}")
        if run.rehearse and getattr(reader, "DEVICE_METRIC", True):
            continue        # a CPU number is never written under its name
        value = reader.read(run)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


async def one_window(run: Run, generator, params, seed) -> None:
    # ``schedule_seed`` in the traffic file makes the schedule (instants,
    # lengths, order) part of the cell, as a replayed trace is; ``--seed``
    # then makes the token ids and the weights, not the amount of work.
    await generator.drive(
        run, params, params.get("schedule_seed", seed), run.seconds
    )
    await run.finish()


def report(run: Run, bench: dict, out_dir: str, tag: str = "") -> dict:
    served = served_path_check(run)
    attempted, failed = attempted_failed(run)
    if run.trace:
        metrics = read_metrics(run, bench["per_layer"], "layer_metrics")
    else:
        metrics = read_metrics(run, bench["end_to_end"], "end_to_end")
    device = dict(run.device)
    if not run.rehearse:
        device["memory_peak_bytes"] = run.closed["memory"]["peak_bytes"]
    trace = reduced_trace(run)
    line = {
        "correct": bool(served["ok"] and run.numerics["ok"]),
        "attempted": attempted, "failed": failed, "metrics": metrics,
        "device": device,
    }
    if trace and not run.rehearse:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        line["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
        }
    # last in the line, and in ``main`` last on standard error: what a run
    # that is not correct leaves in the driver's record
    line["compared"] = compared_numbers(run, served)
    lo = run.t0
    table = [{
        "i": r.index, "phase": r.phase, "client": r.client,
        "due": None if r.due is None else r.due - lo,
        "sent": None if r.sent is None else r.sent - lo,
        "first": None if r.first_t is None else r.first_t - lo,
        "ended": None if r.ended is None else r.ended - lo,
        "prompt": r.prompt_len, "asked": r.max_tokens, "got": len(r.tokens),
        "status": r.status, "error": r.error,
    } for r in run.records]
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"requests{tag}.json"), "w") as f:
        json.dump(table, f)
    detail = {
        "cell": run.cell["name"], "loop": run.loop, "setup_s": run.setup_s,
        "child_setup": run.child_setup, "numerics": run.numerics,
        "served_path": served, "warm": run.warm,
        "requests": {"all": len(run.records), "attempted": attempted, "failed": failed},
        "compiles_in_window": run.closed["compiles_in_window"],
        "compile_requests": run.closed["compile_requests"],
        "persistent_cache_hits": run.closed["cache_hits"],
        "compile_s": run.closed["compile_s"],
        "ticks_polled": len(run.ticks),
    }
    if trace:
        detail["trace"] = {
            k: v for k, v in trace.items() if k != "modules_device0_s"
        }
        detail["trace"]["modules_device0"] = {
            k: {"n": len(v), "sum_s": sum(v)}
            for k, v in trace["modules_device0_s"].items()
        }
    print(json.dumps(detail))
    return line


async def session(run: Run, generator, params, seed, bench, out_dir, rates):
    run.warm = await warmup.warm(run, run.shapes, params)
    if not rates:
        await one_window(run, generator, params, seed)
        return report(run, bench, out_dir)
    line = None
    for rate in rates:                      # the knee sweep: one set-up
        params = {**params, "rate_rps": rate}
        await one_window(run, generator, params, seed)
        line = report(run, bench, out_dir, tag=f"_rate{rate}")
        slo = importlib.import_module("benchmark.layer_metrics.slo_ok_pct")
        end = run.t0 + run.seconds
        print(json.dumps({
            "sweep_rate_rps": rate, "slo_ok_pct": slo.read(run),
            # a backlog that grows: requests due in the window and still
            # unfinished at its end
            "unfinished_at_window_end": sum(
                1 for r in run.records
                if r.due is not None and run.t0 <= r.due < end
                and (r.ended is None or r.ended > end)
            ),
            **line,
        }))
        # counters are compared over the process's life: keep the records,
        # let what is still in flight end before the next rate starts
        await run.client.wait_all(120.0)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--rates", default="", help="sweep: comma-separated rates")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    assert "jax" not in sys.modules, "the load generator must stay off JAX"

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"benchmark: no workload {args.workload!r}", file=sys.stderr)
        return 2
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(REPO, config["file"])) as f:
        conf = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if args.rehearse_cpu:
        traffic = {**traffic, **traffic["rehearse"]}
    generator = importlib.import_module(f"benchmark.generators.{traffic['generator']}")
    out_dir = args.out or os.path.join(
        REPO, "chiprun_out", "benchmark",
        f"{cell['name']}.seed{args.seed}.trace{args.trace}",
    )
    os.makedirs(out_dir, exist_ok=True)

    argv_child = [
        sys.executable, os.path.join(REPO, "benchmark", "server.py"),
        "--config", os.path.join(REPO, config["file"]), "--seed", str(args.seed),
        "--chips", str(cell["chips"]), "--out", out_dir,
    ] + (["--rehearse-cpu"] if args.rehearse_cpu else [])
    child = Child(argv_child, dict(os.environ))
    try:
        ready = child.expect("event", "ready", timeout=1150.0)
        run = Run(cell, conf, traffic, generator, ready, args)
        run.child = child
        run.client = Client(ready["port"], ready["shapes"]["vocab_size"], args.seed)
        rates = [float(r) for r in args.rates.split(",") if r]
        line = asyncio.run(
            session(run, generator, traffic, args.seed, bench, out_dir, rates)
        )
    except RuntimeError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        child.stop()
        return 1
    rc = child.stop()
    if rc != 0:
        print(f"benchmark: the server exited with {rc}", file=sys.stderr)
        return 1
    assert "jax" not in sys.modules, "the load generator must stay off JAX"
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name} {value} limit {limit}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
