"""Length-prefixed transport frames with CRC32C trailers (DESIGN.md §8.1).

A frame wraps one wire message (repro.wire buffer) or a control payload:

    [u16 magic = 0x4652 ("FR")] [u8 version] [u8 ftype]
    [u32 seq] [u32 length]                      <- 12-byte header
    [payload: length bytes]
    [u32 crc32c over header + payload]          <- 4-byte trailer

All integers little-endian. ``seq`` is a per-link monotonic counter for
DATA/SYNC frames (control frames carry the seq they refer to). The CRC is
CRC32C (Castagnoli, reflected poly 0x82F63B78) over everything before the
trailer, so a single flipped bit anywhere in the frame is detected.

Decode failures reuse the repro.wire exception hierarchy — a short buffer
raises :class:`~repro.wire.TruncatedFrame`, a bad magic/version/CRC raises
:class:`~repro.wire.CorruptFrame` — so receivers classify transport- and
codec-level damage uniformly.
"""
from __future__ import annotations

import dataclasses
import enum
import struct

import google_crc32c

from repro.wire.spec import CorruptFrame, TruncatedFrame

FRAME_MAGIC = 0x4652  # "FR"
FRAME_VERSION = 1

_HEADER = struct.Struct("<HBBII")
HEADER_BYTES = _HEADER.size  # 12
CRC_BYTES = 4
FRAME_OVERHEAD = HEADER_BYTES + CRC_BYTES  # 16 bytes per frame


class FrameType(enum.IntEnum):
    DATA = 1     # incremental payload; only valid at seq == expected
    SYNC = 2     # self-contained payload; repairs any sequence gap
    ACK = 3      # cumulative: "I have delivered everything below seq"
    NAK = 4      # "retransmit from seq" (corrupt frame or gap detected)
    RESYNC = 5   # "I cannot be repaired by replay; promote to a SYNC"


@dataclasses.dataclass(frozen=True)
class Frame:
    ftype: FrameType
    seq: int
    payload: bytes = b""

    @property
    def is_control(self) -> bool:
        return self.ftype in (FrameType.ACK, FrameType.NAK, FrameType.RESYNC)


# -- CRC32C (Castagnoli) ------------------------------------------------------


def crc32c(data: bytes, crc: int = 0) -> int:
    """CRC32C of ``data``; chainable via the ``crc`` argument.

    google_crc32c's C implementation (GB/s): a full-sync frame of a real
    model is over a gigabyte, far past what a Python loop can checksum.
    """
    return google_crc32c.extend(crc, data)


# -- encode / decode ----------------------------------------------------------


def encode_frame(ftype: FrameType, seq: int, payload: bytes = b"") -> bytes:
    head = _HEADER.pack(FRAME_MAGIC, FRAME_VERSION, int(ftype), seq & 0xFFFFFFFF,
                        len(payload))
    body = head + payload
    return body + struct.pack("<I", crc32c(body))


def is_frame(buf: bytes) -> bool:
    """True if ``buf`` starts with the transport frame magic (cheap peek —
    lets endpoints accept both framed and bare wire messages)."""
    return len(buf) >= 2 and struct.unpack_from("<H", buf, 0)[0] == FRAME_MAGIC


def decode_frame(buf: bytes, offset: int = 0) -> tuple[Frame, int]:
    """Decode one frame at ``offset``; returns (frame, next_offset).

    Raises :class:`TruncatedFrame` when the buffer ends early and
    :class:`CorruptFrame` on magic/version/type/length/CRC damage.
    """
    if len(buf) < offset + HEADER_BYTES:
        raise TruncatedFrame("truncated transport frame (no header)")
    magic, version, ftype, seq, length = _HEADER.unpack_from(buf, offset)
    if magic != FRAME_MAGIC:
        raise CorruptFrame(f"bad frame magic {magic:#x}")
    if version != FRAME_VERSION:
        raise CorruptFrame(f"unsupported frame version {version}")
    try:
        ftype = FrameType(ftype)
    except ValueError as e:
        raise CorruptFrame(f"unknown frame type {ftype}") from e
    end = offset + HEADER_BYTES + length + CRC_BYTES
    if len(buf) < end:
        raise TruncatedFrame(
            f"truncated transport frame ({len(buf) - offset} of {end - offset} bytes)"
        )
    body = buf[offset : end - CRC_BYTES]
    (want,) = struct.unpack_from("<I", buf, end - CRC_BYTES)
    got = crc32c(body)
    if got != want:
        raise CorruptFrame(f"frame CRC mismatch ({got:#x} != {want:#x})")
    return Frame(ftype=ftype, seq=seq, payload=bytes(buf[offset + HEADER_BYTES : end - CRC_BYTES])), end
