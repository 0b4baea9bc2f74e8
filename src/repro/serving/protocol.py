"""CRC-32-framed wire protocol (the paper's lwIP + CRC-32 message layer).

v1 frame layout (little-endian):

  [0:4]  magic  b"AEGW"
  [4:5]  type   (Msg enum)
  [5:9]  payload length
  [9:..] payload
  [-4:]  CRC-32 (IEEE 0x04C11DB7 == zlib.crc32) over magic..payload

v2 keeps the same magic/type/length prefix but sets bit 7 of the type
byte and inserts an 8-byte extension word after the length:

  [9:13]  request_id  (u32) — correlates pipelined requests with their
                      out-of-order responses on one connection
  [13:17] flags       (u32) — F_SHED / F_BUSY / F_DRAINING on replies
  [17:..] payload
  [-4:]   CRC-32 over everything before it

A decoder that understands v2 accepts both versions (``decode_frame_ex``
/ ``recv_frame_ex``); v1-only peers never see the version bit unless
they send it. The length field is *payload* length in both versions and
is attacker-/corruption-controlled, so every receive path enforces
``MAX_FRAME`` BEFORE allocating the payload buffer.

The paper's design note applies verbatim: CRC detects accidental corruption;
confidentiality/authentication are explicitly out of scope (terminate TLS at
a gateway for untrusted networks — §5.5).
"""
from __future__ import annotations

import enum
import io
import json
import socket
import struct
import zlib
from typing import Any, NamedTuple, Optional

import numpy as np

MAGIC = b"AEGW"
HEADER = struct.Struct("<4sBI")
EXT = struct.Struct("<II")            # v2 extension: request_id, flags
V2_BIT = 0x80                         # set on the type byte for v2 frames

#: Hard ceiling on the payload length field. A corrupted / hostile length
#: would otherwise make the receiver try to allocate up to 4 GiB before
#: the CRC ever gets a chance to reject the frame.
MAX_FRAME = 64 << 20

# Reply flags (v2 flags word).
F_SHED = 1 << 0        # request shed by the admission policy (verdict in payload)
F_BUSY = 1 << 1        # bounded dispatch queue full — backpressure, retry later
F_DRAINING = 1 << 2    # server draining after SHUTDOWN; no new work accepted
F_CANARY = 1 << 3      # response bytes produced by a canary shadow binding


class Msg(enum.IntEnum):
    PROVISION = 1          # payload: RIMFS image (+ program blob)
    INFER_REQUEST = 2      # payload: npz tensors
    INFER_RESPONSE = 3
    TELEMETRY = 4          # payload: json
    HEARTBEAT = 5
    ERROR = 6
    SHUTDOWN = 7


class ProtocolError(ValueError):
    pass


class Frame(NamedTuple):
    kind: "Msg"
    payload: bytes
    request_id: int = 0
    flags: int = 0
    version: int = 1


def _kind(raw: int) -> Msg:
    try:
        return Msg(raw & ~V2_BIT)
    except ValueError:
        raise ProtocolError(f"unknown message type {raw & ~V2_BIT}")


def _check_len(n: int, max_frame: Optional[int]) -> None:
    cap = MAX_FRAME if max_frame is None else max_frame
    if n > cap:
        raise ProtocolError(f"frame payload {n}B exceeds MAX_FRAME {cap}B")


def encode_frame(kind: Msg, payload: bytes, request_id: Optional[int] = None,
                 flags: int = 0) -> bytes:
    """v1 frame by default; passing a ``request_id`` (or flags) emits v2."""
    if request_id is None and not flags:
        head = HEADER.pack(MAGIC, int(kind), len(payload))
    else:
        head = HEADER.pack(MAGIC, int(kind) | V2_BIT, len(payload)) + \
            EXT.pack(request_id or 0, flags)
    crc = zlib.crc32(head + payload) & 0xFFFFFFFF
    return head + payload + struct.pack("<I", crc)


def decode_frame_ex(data: bytes, max_frame: Optional[int] = None) -> Frame:
    """Decode one frame (either version) from a complete byte string."""
    if len(data) < HEADER.size:
        raise ProtocolError(f"truncated frame ({len(data)}B)")
    magic, raw_kind, n = HEADER.unpack_from(data, 0)
    if magic != MAGIC:
        raise ProtocolError(f"bad magic {magic!r}")
    _check_len(n, max_frame)
    kind = _kind(raw_kind)
    rid, flags, version, off = 0, 0, 1, HEADER.size
    if raw_kind & V2_BIT:
        version = 2
        if len(data) < off + EXT.size:
            raise ProtocolError("truncated v2 extension")
        rid, flags = EXT.unpack_from(data, off)
        off += EXT.size
    end = off + n
    if len(data) < end + 4:
        raise ProtocolError(f"truncated frame body ({len(data)}B < {end + 4}B)")
    payload = data[off:end]
    (crc,) = struct.unpack_from("<I", data, end)
    if crc != (zlib.crc32(data[:end]) & 0xFFFFFFFF):
        raise ProtocolError("frame CRC mismatch")
    return Frame(kind, payload, rid, flags, version)


def decode_frame(data: bytes, max_frame: Optional[int] = None) -> tuple:
    f = decode_frame_ex(data, max_frame=max_frame)
    return f.kind, f.payload


# --------------------------------------------------------------- tensor io
def pack_tensors(tensors: dict) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **{k: np.asarray(v) for k, v in tensors.items()})
    return buf.getvalue()


def unpack_tensors(payload: bytes) -> dict:
    with np.load(io.BytesIO(payload)) as z:
        return {k: z[k] for k in z.files}


def pack_json(obj: Any) -> bytes:
    return json.dumps(obj).encode()


def unpack_json(payload: bytes) -> Any:
    return json.loads(payload.decode())


# --------------------------------------------------------------- socket io
def send_frame(sock: socket.socket, kind: Msg, payload: bytes,
               request_id: Optional[int] = None, flags: int = 0) -> None:
    sock.sendall(encode_frame(kind, payload, request_id=request_id,
                              flags=flags))


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    while n > 0:
        c = sock.recv(min(n, 1 << 20))
        if not c:
            raise ConnectionError("peer closed")
        chunks.append(c)
        n -= len(c)
    return b"".join(chunks)


class Head(NamedTuple):
    """A frame's header, read ahead of its body."""
    kind: "Msg"
    length: int                # payload bytes
    request_id: int
    flags: int
    version: int
    raw: bytes                 # the header's bytes, which the CRC covers


def recv_head(sock: socket.socket, max_frame: Optional[int] = None) -> Head:
    """Receive one frame's header (either version); blocks until it has
    arrived. The length cap is enforced here, before any payload is read —
    a hostile length field never triggers a multi-GiB allocation."""
    head = _recv_exact(sock, HEADER.size)
    magic, raw_kind, n = HEADER.unpack(head)
    if magic != MAGIC:
        raise ProtocolError(f"bad magic {magic!r}")
    _check_len(n, max_frame)
    kind = _kind(raw_kind)
    rid, flags, version = 0, 0, 1
    if raw_kind & V2_BIT:
        version = 2
        ext = _recv_exact(sock, EXT.size)
        rid, flags = EXT.unpack(ext)
        head += ext
    return Head(kind, n, rid, flags, version, head)


def recv_body(sock: socket.socket, head: Head) -> Frame:
    """Receive the payload and CRC that follow ``head`` and check them."""
    rest = _recv_exact(sock, head.length + 4)
    payload = rest[:head.length]
    (crc,) = struct.unpack_from("<I", rest, head.length)
    if crc != (zlib.crc32(head.raw + payload) & 0xFFFFFFFF):
        raise ProtocolError("frame CRC mismatch")
    return Frame(head.kind, payload, head.request_id, head.flags,
                 head.version)


def recv_frame_ex(sock: socket.socket,
                  max_frame: Optional[int] = None) -> Frame:
    """Receive one frame (either version)."""
    return recv_body(sock, recv_head(sock, max_frame=max_frame))


def recv_frame(sock: socket.socket, max_frame: Optional[int] = None) -> tuple:
    f = recv_frame_ex(sock, max_frame=max_frame)
    return f.kind, f.payload
