"""Python driver for the native activation relay (``native/relay.cc``).

The relay is the cross-host (DCN) tier of the communication backend — the
role hivemind's libp2p/gRPC fabric plays in the reference (SURVEY §2.2 row 5,
``/root/reference/distributed_llm_inference/server/backend.py:4-7``). The hub
is C++ (epoll, zero-copy forwarding); endpoints speak a length-prefixed
binary protocol over plain TCP sockets.

``RelayServer`` loads the compiled ``.so`` via ctypes (built on demand with
``g++`` — no pybind11 in this image) and runs the hub in-process.
``RelayClient`` is a blocking endpoint with raw-bytes and numpy-tensor
framing; pipeline stages use queue names like ``"stage3.in"``.
"""

from __future__ import annotations

import ctypes
import os
import random
import socket
import struct
import subprocess
import time
import zlib
from typing import Optional, Tuple

import numpy as np

from ..utils.native_build import build_shared

__all__ = ["RelayServer", "RelayClient", "build_native", "native_available"]

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_SRC = os.path.join(_NATIVE_DIR, "relay.cc")
_SO = os.path.join(_NATIVE_DIR, "_relay.so")

OP_PUT, OP_GET, OP_PING, OP_CANCEL = 1, 2, 3, 4
CANCEL_ACK = (1 << 64) - 1
# Ceiling on how long a frame already in flight may stall between bytes
# before the client treats it as lost and recycles the connection. Far
# above any legit hub→client delivery (frames cap at a few MiB), so the
# only thing it ever fires on is a wedged or fault-injected stream.
MID_FRAME_STALL_S = 30.0


def frame_crc(payload: bytes) -> int:
    """CRC-32 of a frame payload (zlib/IEEE — matches the hub's table)."""
    return zlib.crc32(payload) & 0xFFFFFFFF


def build_native(force: bool = False) -> str:
    """Compile ``relay.cc`` → ``_relay.so`` (reused while the source's hash
    is unchanged; see :func:`utils.native_build.build_shared`)."""
    return build_shared(_SRC, _SO, force)


def native_available() -> bool:
    try:
        build_native()
        return True
    except (OSError, subprocess.CalledProcessError):
        return False


class RelayServer:
    """In-process relay hub (the C++ epoll loop on a background thread)."""

    def __init__(self, port: int = 0):
        lib = ctypes.CDLL(build_native())
        lib.relay_start.restype = ctypes.c_void_p
        lib.relay_start.argtypes = [ctypes.c_int]
        lib.relay_port.restype = ctypes.c_int
        lib.relay_port.argtypes = [ctypes.c_void_p]
        lib.relay_stop.argtypes = [ctypes.c_void_p]
        self._lib = lib
        self._handle = lib.relay_start(port)
        if not self._handle:
            raise OSError(f"relay failed to bind port {port}")
        self.port = lib.relay_port(self._handle)

    def stop(self) -> None:
        if self._handle:
            self._lib.relay_stop(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


class RelayClient:
    """Blocking relay endpoint.

    One TCP connection; ``get`` parks server-side until a message arrives, so
    use one client per consumer thread. On ``get`` timeout the connection is
    recycled (the server drops dead waiters), keeping FIFO semantics clean.
    """

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        reconnect_timeout_s: float = 10.0,
        backoff_base_s: float = 0.05,
        backoff_max_s: float = 1.0,
    ):
        self.host, self.port = host, port
        self.reconnect_timeout_s = reconnect_timeout_s
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        # distcheck: unguarded-ok(one client = one consumer thread)
        self.reconnects = 0  # successful re-dials (observability)
        # close() flips this from any thread while _reconnect polls it;
        # a bool store is atomic and one stale read only costs one dial.
        # distcheck: unguarded-ok(atomic flag; stale read is benign)
        self._closed = False
        self._sock: Optional[socket.socket] = None
        self._connect()

    def _connect(self) -> None:
        self._sock = socket.create_connection((self.host, self.port))
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def _reconnect(self) -> None:
        """Drop the (dead) connection and dial again with bounded
        exponential backoff + jitter — the transparent retry path for
        control-plane restarts (SURVEY §5.3: a hub restart of a few seconds
        must not permanently wedge long-lived clients like the worker's
        reply connection or the directory handle, so one failed dial is not
        the end: keep trying inside ``reconnect_timeout_s``)."""
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None
        deadline = time.monotonic() + self.reconnect_timeout_s
        attempt = 0
        while True:
            if self._closed:
                raise ConnectionError("relay client is closed")
            try:
                self._connect()
                self.reconnects += 1
                return
            except OSError as e:
                attempt += 1
                delay = min(
                    self.backoff_max_s, self.backoff_base_s * (2 ** (attempt - 1))
                ) * (0.5 + 0.5 * random.random())  # jitter: desync herds
                if time.monotonic() + delay >= deadline:
                    raise ConnectionError(
                        f"relay {self.host}:{self.port} unreachable after "
                        f"{attempt} attempts: {e}"
                    ) from e
                time.sleep(delay)

    def close(self) -> None:
        self._closed = True  # a concurrent _reconnect must stop dialing
        if self._sock is not None:
            self._sock.close()
            self._sock = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- raw frames -----------------------------------------------------------

    def _require_open(self) -> None:
        if self._sock is None:
            raise ConnectionError("relay client is closed")

    @staticmethod
    def _encode_put(queue: str, payload: bytes) -> bytes:
        """One PUT frame: ``[op][qlen][queue][len:8][crc:4][payload]``. The
        CRC travels in the header so the hub can reject a payload damaged
        in flight at ingress (and the chaos layer can damage the wire bytes
        AFTER the crc is computed — a true corruption, not a re-signed one).
        """
        q = queue.encode()
        return (
            struct.pack(">BH", OP_PUT, len(q)) + q
            + struct.pack(">QI", len(payload), frame_crc(payload))
            + payload
        )

    def put(self, queue: str, payload: bytes) -> None:
        self._require_open()
        frame = self._encode_put(queue, payload)
        try:
            self._sock.sendall(frame)
        except (ConnectionError, OSError):
            # Reconnect so the NEXT op runs on a live connection, but do NOT
            # resend: the hub may have fully received the frame before the
            # connection died, and an at-least-once PUT would double-apply a
            # decode hop (the worker advances its cache twice and the stale
            # duplicate reply silently corrupts the client's token stream).
            # Callers treat the raise as a lost frame: workers drop the
            # reply (the client times out and replays), clients fail over
            # with a fresh generation_id.
            self._reconnect()
            raise

    def put_many(self, items) -> None:
        """Pipelined PUT: encode every ``(queue, payload)`` frame and ship
        them in ONE ``sendall`` — a node's whole fan-out of replies costs a
        single syscall, and the hub parses back-to-back frames straight off
        the stream (its ``process_input`` already loops over complete
        frames, so no protocol change is needed).

        Same no-resend contract as :meth:`put`: on a connection error the
        whole group is treated as lost (any prefix may have been applied, so
        resending could double-apply hops); callers fail over / replay.
        """
        self._require_open()
        data = b"".join(self._encode_put(q, p) for q, p in items)
        if not data:
            return
        try:
            self._sock.sendall(data)
        except (ConnectionError, OSError):
            self._reconnect()
            raise

    def _recv_exact(self, n: int) -> bytes:
        chunks = []
        while n:
            # Re-read self._sock each round: a concurrent close() nulls it,
            # and that race must surface as ConnectionError (the condition
            # callers already handle), never AttributeError.
            sock = self._sock
            if sock is None:
                raise ConnectionError("relay client is closed")
            chunk = sock.recv(min(n, 1 << 20))
            if not chunk:
                raise ConnectionError("relay connection closed")
            chunks.append(chunk)
            n -= len(chunk)
        return b"".join(chunks)

    def _recv_payload(self, length: int, queue: str) -> bytes:
        """Read ``[crc:4][payload:length]`` and verify. A mismatch means the
        hub→client leg damaged the bytes: recycle the connection (the
        stream may be desynced if framing itself was hit) and surface a
        LOST frame — callers time out / fail over and replay; garbage never
        reaches a model layer."""
        (crc,) = struct.unpack(">I", self._recv_exact(4))
        payload = self._recv_exact(length)
        if frame_crc(payload) != crc:
            self._reconnect()
            raise ConnectionError(
                f"corrupt frame on {queue!r} (crc mismatch): treated as lost"
            )
        return payload

    def get(self, queue: str, timeout: Optional[float] = None) -> bytes:
        self._require_open()
        try:
            return self._get_once(queue, timeout)
        except TimeoutError:
            raise  # a timed-out GET is not a broken connection
        except (ConnectionError, OSError):
            self._reconnect()
            return self._get_once(queue, timeout)

    def _get_once(self, queue: str, timeout: Optional[float]) -> bytes:
        sock = self._sock
        if sock is None:
            raise ConnectionError("relay client is closed")
        q = queue.encode()
        sock.sendall(struct.pack(">BH", OP_GET, len(q)) + q)
        # The caller's timeout applies only to the FIRST byte: once the hub
        # has started a reply it is expected to deliver the whole frame, so
        # a mid-frame timeout would normally desync the stream (discarded
        # partial length/payload bytes).
        sock.settimeout(timeout)
        try:
            first = sock.recv(1)
        except socket.timeout:
            self._settimeout(None)
            return self._cancel_pending(queue, timeout)
        finally:
            self._settimeout(None)
        if not first:
            raise ConnectionError("relay connection closed")
        # A started frame must keep flowing. With unbounded mid-frame reads,
        # a half-delivered frame (fault-injected truncation, wedged hub)
        # blocks the caller forever — even `get(timeout=...)` hangs. Bound
        # the remainder generously and surface a stall as a reconnectable
        # ConnectionError; the fresh connection cures the desync.
        self._settimeout(MID_FRAME_STALL_S)
        try:
            (length,) = struct.unpack(">Q", first + self._recv_exact(7))
            return self._recv_payload(length, queue)
        except socket.timeout as exc:
            self._reconnect()
            raise ConnectionError(
                f"frame on {queue!r} stalled mid-delivery: treated as lost"
            ) from exc
        finally:
            self._settimeout(None)

    def _settimeout(self, value) -> None:
        sock = self._sock
        if sock is not None:
            try:
                sock.settimeout(value)
            except OSError:
                pass  # closed concurrently; the next recv raises cleanly

    def _cancel_pending(self, queue: str, timeout) -> bytes:
        """Race-free GET timeout: CANCEL the parked waiter and read frames
        until the ack sentinel. A real reply that raced ahead of the CANCEL
        arrives before the ack — return it (arrived late beats lost). The
        ack sentinel is the bare 8-byte length ``CANCEL_ACK`` (no crc)."""
        sock = self._sock
        if sock is None:
            raise ConnectionError("relay client is closed")
        sock.sendall(struct.pack(">BH", OP_CANCEL, 0))
        self._settimeout(10.0)
        (length,) = struct.unpack(">Q", self._recv_exact(8))
        if length == CANCEL_ACK:
            raise TimeoutError(f"get({queue!r}) timed out after {timeout}s")
        payload = self._recv_payload(length, queue)
        (ack,) = struct.unpack(">Q", self._recv_exact(8))
        assert ack == CANCEL_ACK, "protocol desync after GET cancel"
        return payload

    def ping(self, timeout: float = 5.0) -> bool:
        self._require_open()
        self._sock.sendall(struct.pack(">BH", OP_PING, 0))
        self._settimeout(timeout)
        try:
            (length,) = struct.unpack(">Q", self._recv_exact(8))
            return self._recv_payload(length, "<ping>") == b"PONG"
        finally:
            self._settimeout(None)

    # -- tensor framing -------------------------------------------------------
    # [dtype_len:1][dtype str][ndim:1][dims:8 each][raw bytes]; bfloat16
    # travels as its raw uint16 bits with dtype tag "bfloat16".

    @staticmethod
    def encode_array(arr: np.ndarray, tag: Optional[str] = None) -> bytes:
        dtype = (tag or arr.dtype.str).encode()
        header = struct.pack(">B", len(dtype)) + dtype + struct.pack(
            ">B", arr.ndim
        ) + b"".join(struct.pack(">Q", d) for d in arr.shape)
        return header + arr.tobytes()

    @staticmethod
    def decode_array(buf: bytes) -> Tuple[np.ndarray, str]:
        (dlen,) = struct.unpack_from(">B", buf, 0)
        dtype = buf[1 : 1 + dlen].decode()
        off = 1 + dlen
        (ndim,) = struct.unpack_from(">B", buf, off)
        off += 1
        shape = tuple(
            struct.unpack_from(">Q", buf, off + 8 * i)[0] for i in range(ndim)
        )
        off += 8 * ndim
        raw = np.frombuffer(
            buf, dtype="<u2" if dtype == "bfloat16" else dtype, offset=off
        )
        return raw.reshape(shape), dtype

    def put_array(self, queue: str, arr, tag: Optional[str] = None) -> None:
        a = np.asarray(arr)
        if a.dtype.name == "bfloat16":  # ml_dtypes: send raw bits
            self.put(queue, self.encode_array(a.view(np.uint16), "bfloat16"))
        else:
            self.put(queue, self.encode_array(a, tag))

    def get_array(self, queue: str, timeout: Optional[float] = None):
        arr, dtype = self.decode_array(self.get(queue, timeout))
        if dtype == "bfloat16":
            import ml_dtypes

            return arr.view(ml_dtypes.bfloat16)
        return arr
