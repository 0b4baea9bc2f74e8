"""Client side of a run: connections to the server, an open-loop sender and
a closed-loop refiller, one receiver thread for every connection together,
and a record of each request's due, send and reply times.

Each request goes out as the program's v2 frame (``protocol.encode_frame``)
written in three pieces, so that no 2.4 MB body is copied into a new buffer
per send: on a TPU v5e host, stacks dumped during pauses of 100 ms or more
of the whole process found the sender in that copy, which holds the
interpreter lock.

Latency is taken from a request's *due* time, so a stall of the server or of
the sender counts against every request it delays. Replies are read as
whole frames of the server's protocol; ``decode`` turns a reply payload into
what the check compares.
"""
from __future__ import annotations

import itertools
import selectors
import socket
import struct
import threading
import time
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax

from repro.serving import protocol as proto


def send_frame(sock: socket.socket, kind: proto.Msg, body: bytes,
               request_id: int) -> None:
    """Write the bytes ``proto.encode_frame(kind, body, request_id)`` makes,
    without joining them first."""
    head = proto.HEADER.pack(proto.MAGIC, int(kind) | proto.V2_BIT,
                             len(body)) + proto.EXT.pack(request_id, 0)
    crc = zlib.crc32(body, zlib.crc32(head)) & 0xFFFFFFFF
    sock.sendall(head)
    sock.sendall(body)
    sock.sendall(struct.pack("<I", crc))


@dataclass
class Record:
    spec: Any
    due: float                  # perf_counter seconds
    sent: float = 0.0
    done: float = 0.0
    ok: bool = False
    units: int = 0              # images or tokens in the reply
    out: Any = None
    error: str = ""


class Load:
    """Requests on ``connections`` sockets to ``address``. ``payload(spec)``
    builds a request body; ``decode(payload)`` returns ``(units, out)`` of a
    reply."""

    def __init__(self, address: tuple, connections: int,
                 payload: Callable, decode: Callable):
        self.payload = payload
        self.decode = decode
        self.socks = []
        for _ in range(connections):
            s = socket.create_connection(address)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self.socks.append(s)
        self.locks = [threading.Lock() for _ in self.socks]
        self.records: dict = {}          # request id -> Record
        self.rids = itertools.count(1)
        self.lock = threading.Lock()
        self.all_done = threading.Condition(self.lock)
        self.outstanding = 0
        self.closing = False
        self.refill: Optional[Callable] = None     # closed loop: conn -> None
        self.receiver = threading.Thread(target=self._receive, daemon=True,
                                         name="bench-recv")
        self.receiver.start()

    # ------------------------------------------------------------ sending
    def send(self, spec, due: float) -> Record:
        rec = Record(spec, due)
        body = self.payload(spec)
        rid = next(self.rids)
        with self.lock:
            self.records[rid] = rec
            self.outstanding += 1
        conn = spec.conn
        with jax.profiler.TraceAnnotation("bench.send"):
            with self.locks[conn]:
                rec.sent = time.perf_counter()
                send_frame(self.socks[conn], proto.Msg.INFER_REQUEST, body,
                           rid)
        return rec

    # ---------------------------------------------------------- receiving
    def _receive(self) -> None:
        sel = selectors.DefaultSelector()
        for i, s in enumerate(self.socks):
            sel.register(s, selectors.EVENT_READ, i)
        try:
            while True:
                for key, _ in sel.select(timeout=0.2):
                    try:
                        f = proto.recv_frame_ex(key.fileobj)
                    except (OSError, ConnectionError, proto.ProtocolError):
                        sel.unregister(key.fileobj)
                        continue
                    self._finish(f, key.data)
                with self.lock:
                    if self.closing and not sel.get_map():
                        return
                    if self.closing and self.outstanding == 0:
                        return
        finally:
            sel.close()

    def _finish(self, f, conn: int) -> None:
        t = time.perf_counter()
        with self.lock:
            rec = self.records.get(f.request_id)
        if rec is None or rec.done:
            return
        backoff = 0.0
        with jax.profiler.TraceAnnotation("bench.recv"):
            if f.kind == proto.Msg.INFER_RESPONSE:
                try:
                    rec.units, rec.out = self.decode(f.payload)
                    rec.ok = True
                except Exception as e:         # a malformed reply fails
                    rec.error = f"undecodable reply: {e!r}"
            else:
                try:
                    info = proto.unpack_json(f.payload)
                    rec.error = str(info)
                    backoff = float(info.get("retry_after_ms") or 0) / 1e3
                except Exception:
                    rec.error = f"error frame, flags {f.flags}"
        rec.done = t
        with self.lock:
            self.outstanding -= 1
            self.all_done.notify_all()
        refill = self.refill
        if refill is not None and not self.closing:
            if backoff > 0:
                # a refused request's connection waits as the server asks
                t = threading.Timer(backoff, refill, args=(conn,))
                t.daemon = True
                t.start()
            else:
                refill(conn)

    # ------------------------------------------------------------- drivers
    def open_loop(self, specs: list, t0: float,
                  stop: threading.Event) -> None:
        """Send each spec at ``t0 + spec.due`` until ``stop`` is set or the
        specs run out. Runs on the caller's thread."""
        for spec in specs:
            due = t0 + spec.due
            while True:
                if stop.is_set():
                    return
                wait = due - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05))
            self.send(spec, due)

    def closed_loop(self, specs: list, pipeline: int) -> None:
        """Keep ``pipeline`` requests in flight on every connection: each
        reply sends the connection's next spec, and a refusal does so once
        the ``retry_after_ms`` it carries has passed. Returns once primed."""
        per_conn = [iter([s for s in specs if s.conn == c])
                    for c in range(len(self.socks))]

        def refill(conn: int) -> None:
            spec = next(per_conn[conn], None)
            if spec is None or self.closing:
                return
            try:
                self.send(spec, time.perf_counter())
            except OSError:             # closed under a late refill
                if not self.closing:
                    raise

        self.refill = refill
        for c in range(len(self.socks)):
            for _ in range(pipeline):
                refill(c)

    def wait(self, records: list, deadline: float) -> bool:
        """Wait until every record is done or ``deadline`` passes."""
        with self.lock:
            while any(not r.done for r in records):
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self.all_done.wait(min(left, 0.5))
        return True

    def close(self) -> None:
        with self.lock:
            self.closing = True
            self.refill = None
        for s in self.socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            s.close()
        self.receiver.join(timeout=10)

    def snapshot(self) -> list:
        with self.lock:
            return list(self.records.values())
