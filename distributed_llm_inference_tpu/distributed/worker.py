"""Block worker: serve a layer block over the relay, with health + leases.

Completes what the reference left as stubs: the worker skeleton
(``/root/reference/distributed_llm_inference/server/worker.py:9-23`` — load a
block ``[block_index_start, block_index_end]`` and expose it) and the server
health/rebalance pseudocode (``server/server.py:5-24`` — register, monitor,
heartbeat, restart). One ``ServingNode`` =

* a :class:`BlockBackend` holding the layers this node serves,
* a consume loop on the node's relay queue (source-routed frames:
  ``hops[0]`` is the next destination — forward the block output there),
* a heartbeat thread renewing the directory lease (failure detection:
  a dead node's lease lapses and routing drops it),
* a watchdog that restarts the consume loop if it dies (the
  ``module.restart()`` intent of ``server.py:23``).

Frame header ops: ``forward`` (run the block), ``end`` (free the session),
``shutdown`` (stop the node; used by tests).
"""

from __future__ import annotations

import threading
import traceback
import uuid
from typing import Dict, List, Optional

import numpy as np

from ..config import ModelConfig
from .backend import BlockBackend, SchemaError
from .directory import DirectoryClient
from ..utils.metrics import Metrics
from .messages import pack_frame, unpack_frame
from .relay import RelayClient
from .task_pool import TaskPool

__all__ = ["ServingNode", "error_code"]


def error_code(e: Exception) -> str:
    """Machine-readable error classification for error frames. Clients key
    retry/failover decisions on this, never on message text (a reworded
    message must not silently disable replay)."""
    if isinstance(e, KeyError):
        return "unknown_generation"
    if isinstance(e, SchemaError):
        return "schema"
    if isinstance(e, RuntimeError) and "node full" in str(e):
        return "node_full"
    return "internal"


class ServingNode:
    def __init__(
        self,
        relay_port: int,
        cfg: ModelConfig,
        layer_params,
        first_layer: int,
        last_layer: int,
        host: str = "127.0.0.1",
        node_id: Optional[str] = None,
        max_sessions: int = 8,
        max_seq_len: int = 512,
        heartbeat_s: float = 2.0,
        lease_ttl: float = 10.0,
        dtype=None,
        batch_window_s: float = 0.002,
        quantize=None,
        kv_quant=None,
        cache_cfg=None,
        mesh_cfg=None,
        epoch: int = 1,
    ):
        self.node_id = node_id or f"node-{uuid.uuid4().hex[:8]}"
        self.queue = f"block.{self.node_id}"
        self.host, self.relay_port = host, relay_port
        self.heartbeat_s, self.lease_ttl = heartbeat_s, lease_ttl
        # Incarnation number for lease fencing: a restart must register
        # with a HIGHER epoch than any previous life of this node_id, or
        # the directory (rightly) treats it as a zombie.
        self.epoch = int(epoch)
        kw = {} if dtype is None else {"dtype": dtype}
        self.backend = BlockBackend(
            cfg, layer_params, first_layer, last_layer, max_sessions,
            max_seq_len, quantize=quantize, kv_quant=kv_quant,
            cache_cfg=cache_cfg, mesh_cfg=mesh_cfg, **kw,
        )
        self._stop = threading.Event()
        # Crash log: consume + pool threads append (GIL-atomic), tests read
        # after join — no torn state to guard.
        # distcheck: unguarded-ok(list.append is atomic; read after join)
        self.errors: List[str] = []
        # distcheck: unguarded-ok(health thread is the only writer)
        self.restarts = 0
        self.metrics = Metrics()  # /metrics surface for chaos observability
        # Highest hop seq applied per generation (pool thread only). An
        # at-least-once transport (duplicated PUT) must not advance a
        # session's KV cache twice — the duplicate is skipped, no reply.
        self._applied_seq: Dict[str, int] = {}
        # Prune threshold precomputed once: the per-batch check is a bare
        # len() compare, and small dicts are never scanned at all.
        self._seq_prune_at = 4 * max_sessions + 16

        # Register FIRST: a directory/relay failure here must not leak the
        # pool thread or relay sockets (there is no node object to stop()).
        self._directory = DirectoryClient(relay_port, host)
        try:
            if not self._directory.register(
                self.node_id, first_layer, last_layer, self.queue,
                ttl=lease_ttl, epoch=self.epoch,
            ):
                raise RuntimeError(
                    f"registration fenced: node {self.node_id} epoch "
                    f"{self.epoch} is stale — restart with a higher epoch"
                )
            # All backend work flows through the task pool (one thread): N
            # concurrent sessions' compatible hops (same op + padded length)
            # group into ONE batched device call instead of N serial ones,
            # and backend state needs no locking. Replies are sent from the
            # pool thread over its own relay connection.
            self._out = RelayClient(host, relay_port)
        except Exception:
            self._directory.close()
            raise
        try:
            self._pool = TaskPool(
                self._process_batch, max_batch=max_sessions,
                window_s=batch_window_s, signature=lambda item: item[0],
                name=f"{self.node_id}.pool", metrics=self.metrics,
            )
        except Exception:
            self._out.close()
            self._directory.close()
            raise
        # Rebound by the health watchdog when a consumer dies; readers only
        # probe .is_alive() on whichever generation they observe.
        # distcheck: unguarded-ok(single rebinding writer; stale reads safe)
        self._consume_thread = self._spawn_consumer()
        self._health_thread = threading.Thread(
            target=self._health_loop, daemon=True
        )
        self._health_thread.start()

    # -- serve loop -----------------------------------------------------------

    def _spawn_consumer(self) -> threading.Thread:
        t = threading.Thread(target=self._consume, daemon=True,
                             name=f"{self.node_id}.consume")
        t.start()
        return t

    def _consume(self) -> None:
        client = RelayClient(self.host, self.relay_port)
        try:
            while not self._stop.is_set():
                try:
                    frame = client.get(self.queue, timeout=0.5)
                except TimeoutError:
                    continue
                header, arr = unpack_frame(frame)
                op = header.get("op")
                if op == "shutdown":
                    return  # distcheck: reply-ok(shutdown frames are fire-and-forget)
                if op == "end":
                    # Through the pool so backend state stays single-threaded.
                    self._pool.submit((("end",), header, None),
                                      eager=bool(header.get("gens")))
                    continue
                if op != "forward":
                    # An op this node doesn't speak: the drop must at least
                    # be visible on /metrics, or a protocol skew between
                    # client and worker looks like silent request loss.
                    self.metrics.counter("unknown_ops_dropped")
                    continue
                if not header.get("hops"):
                    continue  # distcheck: reply-ok(frame carries no reply address)
                # Group key: hops of equal padded length batch together
                # (decode steps with decode steps, like-bucketed prefills
                # with each other). Stacked multi-generation frames
                # (``gens`` header, ``[N, S, H]`` payload) share the key
                # space — axis 1 is the padded length for both layouts, so
                # a stacked decode frame co-batches with single decode hops.
                # Malformed payloads (missing / wrong-rank tensor) get a
                # degenerate key and fail per-item in backend.validate →
                # error reply, never the consume loop.
                shape = getattr(arr, "shape", ())
                s_key = shape[1] if len(shape) >= 2 else -1
                # Stacked frames were co-batched at the source: dispatching
                # them without the linger is what keeps the lockstep decode
                # loop's per-hop cost at compute + transit, not + window_s.
                self._pool.submit((("fwd", s_key), header, arr),
                                  eager=bool(header.get("gens")))
        except (ConnectionError, OSError):
            # Relay gone: health loop notices / tests tear down.
            return  # distcheck: reply-ok(no transport left to reply over)
        except Exception:
            # Record the real cause here, where the exception is live — the
            # watchdog thread only sees that the loop died.
            self.errors.append(traceback.format_exc())
            raise
        finally:
            client.close()

    def _process_batch(self, items) -> List[None]:
        """Task-pool fn: one batch of same-signature frames → one backend
        call; replies/errors go straight back over the relay (futures are
        fire-and-forget).

        A frame is either a single hop (``gen_id`` header, ``[1, S, H]``
        payload) or a stacked multi-generation hop from a batched client
        (``gens``/``num_new`` lists, ``[N, S, H]`` payload). Stacked frames
        flatten into the same ``forward_many`` group as the singles —
        everything in the pool batch runs as ONE backend call — and each
        stacked frame is re-stacked into one reply (failed rows peel off as
        individual error frames). All replies for the batch then leave in
        one pipelined ``put_many`` (a single syscall for the whole fan-out).
        """
        try:
            if items[0][0] == ("end",):
                for _, header, _ in items:
                    for gid in header.get("gens") or [header.get("gen_id", "")]:
                        self.backend.end(gid)
                        self._applied_seq.pop(gid, None)
                return [None] * len(items)
            # Flatten every frame into per-generation rows, with hop-seq
            # dedup (pool thread serializes, so no lock): a row whose seq
            # this node already applied is a duplicated delivery — skip it
            # with NO reply (the original's reply already went out; a second
            # reply would itself be a duplicate downstream).
            # Invariant reply fields computed once per batch, not per item.
            node = self.node_id
            shipments = []  # (queue, frame bytes) for ONE pipelined send
            reqs = []    # flattened forward_many items
            frames = []  # (header, rows) — rows: (req_idx | None, gid, nn)
            for _, header, arr in items:
                gens = header.get("gens")
                if gens is not None:
                    nns = header.get("num_new")
                    n_rows = (getattr(arr, "shape", None) or (0,))[0]
                    if (not isinstance(nns, (list, tuple))
                            or len(nns) != len(gens)
                            or n_rows != len(gens)):
                        # Malformed stacked frame: every row gets an explicit
                        # error reply — silently dropping rows would leave
                        # the client blocked for its full hop timeout.
                        self.metrics.counter("malformed_frames")
                        hops = header.get("hops") or []
                        if hops:
                            for gid in gens:
                                shipments.append((hops[-1], pack_frame({
                                    "op": "error", "gen_id": gid,
                                    "error": "stacked frame: gens/num_new/"
                                             "payload row counts disagree",
                                    "code": "schema", "from": node,
                                })))
                        continue
                    metas = list(zip(gens, nns))
                else:
                    metas = [(header.get("gen_id", ""),
                              header.get("num_new", 0))]
                seq = header.get("seq")
                new = bool(header.get("new", False))
                rows = []
                for i, (gid, nn) in enumerate(metas):
                    if seq is not None:
                        last = self._applied_seq.get(gid)
                        if last is not None and seq <= last:
                            self.metrics.counter("duplicate_hops_skipped")
                            rows.append((None, gid, nn))
                            continue
                        self._applied_seq[gid] = seq
                    x = arr[i : i + 1] if gens is not None else arr
                    rows.append((len(reqs), gid, nn))
                    reqs.append((gid, x, nn, new))
                frames.append((header, rows))
            if len(self._applied_seq) > self._seq_prune_at:
                # "end" frames are best-effort, so entries can leak; prune
                # against the backend's live session table.
                live = self.backend.sessions
                self._applied_seq = {
                    g: s for g, s in self._applied_seq.items() if g in live
                }
            outs = self.backend.forward_many(reqs) if reqs else []
            for header, rows in frames:
                hops = header.get("hops") or []
                fresh = [(ri, gid, nn) for ri, gid, nn in rows
                         if ri is not None]
                if not fresh or not hops:
                    continue  # wholly-duplicated frame: no reply
                ok_rows = []
                for ri, gid, nn in fresh:
                    y = outs[ri]
                    if isinstance(y, Exception):
                        # Protocol/session errors go back to the client's
                        # reply queue (last hop) so generate() fails fast
                        # instead of hanging; surviving rows of a stacked
                        # frame still travel on below.
                        err = {"op": "error", "gen_id": gid,
                               "error": f"{type(y).__name__}: {y}",
                               "code": error_code(y), "from": node}
                        shipments.append((hops[-1], pack_frame(err)))
                    else:
                        ok_rows.append((gid, nn, y))
                if not ok_rows:
                    continue
                if header.get("gens") is not None:
                    reply = {"op": "forward",
                             "gens": [g for g, _, _ in ok_rows],
                             "num_new": [n for _, n, _ in ok_rows],
                             "new": header.get("new", False),
                             "seq": header.get("seq"),
                             "hops": hops[1:], "from": node}
                    y = np.concatenate([y for _, _, y in ok_rows], axis=0)
                else:
                    reply = {**header, "hops": hops[1:], "from": node}
                    y = ok_rows[0][2]
                shipments.append((hops[0], pack_frame(reply, y)))
            if shipments:
                self._out.put_many(shipments)
            return [None] * len(items)
        except (ConnectionError, OSError):
            return [None] * len(items)  # relay gone mid-reply: teardown
        except Exception:
            self.errors.append(traceback.format_exc())
            raise

    # -- health / leases ------------------------------------------------------

    def _health_loop(self) -> None:
        while not self._stop.is_set():
            # Event.wait, not time.sleep: stop() must return promptly, not
            # block up to a full heartbeat interval.
            if self._stop.wait(self.heartbeat_s):
                return
            try:
                alive = self._directory.heartbeat(
                    self.node_id, load=self.backend.load,
                    ttl=self.lease_ttl, epoch=self.epoch,
                )
                if not alive:  # lease lapsed (e.g. directory restart)
                    if not self._directory.register(
                        self.node_id, self.backend.first_layer,
                        self.backend.last_layer, self.queue,
                        ttl=self.lease_ttl, epoch=self.epoch,
                    ):
                        # Fenced: this incarnation was declared dead and
                        # its work re-homed. Serving on would split-brain
                        # the fleet — wind the node down instead.
                        self._stop.set()
                        return
            except (ConnectionError, OSError, TimeoutError, RuntimeError):
                continue
            if not self._consume_thread.is_alive():
                # The cause was recorded by _consume's own except hook; the
                # watchdog just restarts (``module.restart()`` intent,
                # reference server.py:23).
                self.restarts += 1
                self.metrics.counter("worker_restarts")
                self._consume_thread = self._spawn_consumer()

    def is_healthy(self) -> bool:
        return self._consume_thread.is_alive() and not self._stop.is_set()

    # -- lifecycle ------------------------------------------------------------

    def stop(self) -> None:
        if self._stop.is_set():
            return  # idempotent: fixtures and tests may both stop a node
        self._stop.set()
        try:
            self._directory.remove(self.node_id)
        except (ConnectionError, OSError, TimeoutError, RuntimeError):
            pass
        self._directory.close()
        self._consume_thread.join(timeout=5)
        self._health_thread.join(timeout=5)
        self._pool.stop()
        self._out.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
