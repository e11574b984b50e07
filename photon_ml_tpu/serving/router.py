"""Replica fleet router: a thin asyncio front over N netserver
replicas, speaking the binary framing as pure PASSTHROUGH.

One serving process is one core's worth of throughput; the fleet shape
is N single-core replicas (each its own process, its own GIL, its own
observability plane — PR 15's aggregator merges them) behind a router
that spreads connections' REQUESTS, not connections: every frame is
routed independently, so one pipelined client saturates the whole
fleet instead of the one replica its connection happened to land on.

Design constraints, in order:

- **Never decode payloads.** The router reads exactly the 8-byte frame
  head (magic + length) per request, forwards the frame bytes
  verbatim, and pairs response frames back by FIFO order per backend
  connection (the netserver writes responses in request order — that
  ordering IS the router's correlation mechanism; no request ids on
  the wire, no payload inspection). Router cost per request: one
  dict/deque op and two stream writes.
- **Least-pending routing** (round-robin tie-break): each backend's
  in-flight count is the router's own bookkeeping (frames forwarded
  minus responses returned) — no health polling on the hot path. A
  backend that slows accumulates in-flight and stops being picked; a
  dead one fails its in-flight requests with a typed ``internal``
  error frame (clients see the error, never a hang) and is retried on
  the next pick via reconnect.
- **Per-client response order.** A client pipelines frames that may
  fan out across backends; responses are written back in REQUEST
  order per client connection (FIFO future queue per connection —
  same discipline the netserver's binary writer keeps).

The router is binary-only by design: HTTP traffic goes through a
stock L7 balancer; this exists for the hot path, where the point is
that nothing between client and engine parses JSON.
"""

from __future__ import annotations

import asyncio
import dataclasses
import struct
from collections import deque
from typing import Optional, Sequence, Tuple

from photon_ml_tpu import telemetry
from photon_ml_tpu.serving.netserver import (
    REQUEST_MAGIC,
    RESPONSE_MAGIC,
    encode_response,
)

_U4 = struct.Struct("<I")

_M_FORWARDED = telemetry.counter("serving.router.forwarded")
_M_RETURNED = telemetry.counter("serving.router.returned")
_M_ERRORS = telemetry.counter("serving.router.backend_errors")


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    host: str = "127.0.0.1"
    port: int = 0
    max_body_bytes: int = 8 * 1024 * 1024
    policy: str = "least_pending"  # or "round_robin"


class _Backend:
    """One replica: lazy persistent connection + FIFO of in-flight
    futures + a response pump pairing frames back in order."""

    __slots__ = ("host", "port", "reader", "writer", "inflight",
                 "pump", "forwarded", "errors", "connect_lock")

    def __init__(self, host: str, port: int):
        self.host = host
        self.port = int(port)
        self.reader: Optional[asyncio.StreamReader] = None
        self.writer: Optional[asyncio.StreamWriter] = None
        self.inflight: deque = deque()
        self.pump: Optional[asyncio.Task] = None
        self.forwarded = 0
        self.errors = 0
        # Serialises reconnects: without it, N client handlers racing
        # through _ensure_connected each see writer=None and open N
        # connections + N pumps to the SAME backend — the duplicate
        # pumps then fight over one reader and tear the framing.
        self.connect_lock = asyncio.Lock()

    @property
    def addr(self) -> str:
        return f"{self.host}:{self.port}"


#: error frame sent to a client whose request was in flight on a
#: backend connection that died (typed: clients never hang).
_BACKEND_LOST = encode_response(
    None, ("internal", "backend connection lost", None))


class ReplicaRouter:
    """``await ReplicaRouter(backends, cfg).start()`` then
    :meth:`close` (drains: every forwarded frame gets a response or a
    typed error before the listener goes away)."""

    def __init__(self, backends: Sequence[Tuple[str, int]],
                 config: Optional[RouterConfig] = None):
        if not backends:
            raise ValueError("router needs at least one backend")
        self.config = config if config is not None else RouterConfig()
        if self.config.policy not in ("least_pending", "round_robin"):
            raise ValueError(f"unknown policy {self.config.policy!r}")
        self.backends = [_Backend(h, p) for h, p in backends]
        self._rr = 0
        self._server: Optional[asyncio.AbstractServer] = None
        self._conns: set = set()
        self._stats = {"connections": 0, "forwarded": 0, "returned": 0,
                       "backend_errors": 0, "malformed": 0}

    @property
    def port(self) -> Optional[int]:
        if self._server is None or not self._server.sockets:
            return None
        return self._server.sockets[0].getsockname()[1]

    async def start(self) -> "ReplicaRouter":
        if self._server is not None:
            raise RuntimeError("router already started")
        self._server = await asyncio.start_server(
            self._on_conn, host=self.config.host, port=self.config.port)
        return self

    async def close(self) -> None:
        if self._server is None:
            return
        self._server.close()
        for task in list(self._conns):
            task.cancel()
        if self._conns:
            await asyncio.gather(*list(self._conns),
                                 return_exceptions=True)
        # After the handlers: on Python 3.12 wait_closed() returns only
        # once every client connection is gone.
        await self._server.wait_closed()
        for b in self.backends:
            if b.pump is not None:
                b.pump.cancel()
                try:
                    await b.pump
                except (asyncio.CancelledError, ConnectionError):
                    pass
                b.pump = None
            if b.writer is not None:
                b.writer.close()
                b.reader = b.writer = None
        self._server = None

    # -- backend side ------------------------------------------------------

    def _fail_inflight(self, b: _Backend) -> None:
        while b.inflight:
            fut = b.inflight.popleft()
            if not fut.done():
                fut.set_result(_BACKEND_LOST)
            b.errors += 1
            self._stats["backend_errors"] += 1
            _M_ERRORS.inc()

    async def _pump(self, b: _Backend, reader: asyncio.StreamReader,
                    writer: asyncio.StreamWriter) -> None:
        """Read response frames off one backend connection, resolve the
        FIFO futures. Frames are paired by ORDER — the netserver's
        in-order response writer is the contract this leans on. The
        pump owns the (reader, writer) pair it was started with; on
        exit it only tears down the backend's shared state if that pair
        is still the backend's current connection."""
        try:
            while True:
                head = await reader.readexactly(8)
                if head[:4] != RESPONSE_MAGIC:
                    raise ConnectionError(
                        f"backend {b.addr} broke framing "
                        f"({head[:4]!r})")
                (n,) = _U4.unpack(head[4:])
                payload = await reader.readexactly(n)
                if not b.inflight:
                    raise ConnectionError(
                        f"backend {b.addr} sent an unpaired response")
                fut = b.inflight.popleft()
                if not fut.done():
                    fut.set_result(head + payload)
                self._stats["returned"] += 1
                _M_RETURNED.inc()
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        finally:
            try:
                writer.close()
            except Exception:  # noqa: BLE001 — already-dead transport
                pass
            if b.reader is reader:
                self._fail_inflight(b)
                b.reader = b.writer = None
                b.pump = None

    async def _ensure_connected(self, b: _Backend) -> bool:
        if b.writer is not None:
            return True
        async with b.connect_lock:
            if b.writer is not None:  # another handler connected first
                return True
            try:
                reader, writer = await asyncio.open_connection(
                    b.host, b.port)
            except OSError:
                return False
            b.reader, b.writer = reader, writer
            b.pump = asyncio.get_running_loop().create_task(
                self._pump(b, reader, writer))
            return True

    async def _pick(self) -> Optional[_Backend]:
        """Least-pending with round-robin tie-break (pure round-robin
        under ``policy="round_robin"``); reconnects lazily, skipping
        backends that refuse. None = whole fleet unreachable."""
        n = len(self.backends)
        order = [self.backends[(self._rr + i) % n] for i in range(n)]
        self._rr = (self._rr + 1) % n
        if self.config.policy == "least_pending":
            order.sort(key=lambda b: len(b.inflight))
        for b in order:
            if await self._ensure_connected(b):
                return b
        return None

    # -- client side -------------------------------------------------------

    async def _on_conn(self, reader: asyncio.StreamReader,
                       writer: asyncio.StreamWriter) -> None:
        task = asyncio.current_task()
        self._conns.add(task)
        self._stats["connections"] += 1
        # Per-client in-order response writer (requests may fan out
        # across backends; the client sees request order).
        queue: asyncio.Queue = asyncio.Queue()

        async def respond() -> None:
            while True:
                fut = await queue.get()
                if fut is None:
                    return
                frame = await fut
                writer.write(frame)
                try:
                    await writer.drain()
                except ConnectionError:
                    return

        responder = asyncio.get_running_loop().create_task(respond())
        try:
            while True:
                try:
                    head = await reader.readexactly(8)
                except (asyncio.IncompleteReadError, ConnectionError):
                    return  # client done
                if head[:4] != REQUEST_MAGIC:
                    self._stats["malformed"] += 1
                    await queue.put(_done_future(encode_response(
                        None, ("malformed",
                               f"bad frame magic {head[:4]!r}", None))))
                    return
                (n,) = _U4.unpack(head[4:])
                if n > self.config.max_body_bytes:
                    self._stats["malformed"] += 1
                    await queue.put(_done_future(encode_response(
                        None, ("too_large",
                               f"frame of {n} bytes exceeds router "
                               f"bound", None))))
                    return
                try:
                    payload = await reader.readexactly(n)
                except (asyncio.IncompleteReadError, ConnectionError):
                    return  # mid-frame disconnect; nothing to answer
                # _pick awaits (reconnects), so a backend it returns
                # can lose its connection before we write: grab the
                # writer while it's live and retry the pick if the
                # pump tore it down under us.
                for _ in range(len(self.backends) + 1):
                    b = await self._pick()
                    bw = None if b is None else b.writer
                    if b is None or bw is not None:
                        break
                if b is None or bw is None:
                    await queue.put(_done_future(_BACKEND_LOST))
                    continue
                fut = asyncio.get_running_loop().create_future()
                b.inflight.append(fut)
                b.forwarded += 1
                self._stats["forwarded"] += 1
                _M_FORWARDED.inc()
                bw.write(head + payload)
                await queue.put(fut)
                try:
                    await bw.drain()
                except (ConnectionError, OSError):
                    pass  # the pump notices and fails the FIFO
        except asyncio.CancelledError:
            pass
        finally:
            await queue.put(None)
            try:
                await responder
            except asyncio.CancelledError:
                pass
            self._conns.discard(task)
            try:
                writer.close()
            except Exception:  # noqa: BLE001 — already-dead transport
                pass

    def stats(self) -> dict:
        return {
            **dict(self._stats),
            "port": self.port,
            "policy": self.config.policy,
            "backends": [{"addr": b.addr,
                          "connected": b.writer is not None,
                          "inflight": len(b.inflight),
                          "forwarded": b.forwarded,
                          "errors": b.errors}
                         for b in self.backends],
        }


def _done_future(frame: bytes) -> asyncio.Future:
    fut = asyncio.get_running_loop().create_future()
    fut.set_result(frame)
    return fut
