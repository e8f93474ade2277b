"""Superpost compaction codec (paper §IV-C).

Two block kinds persist on cloud storage:

  * superpost blocks — serialized superposts back to back, so each bin is
    retrievable with a single range read given (block, offset, length);
  * one header block — hash seeds, bin-pointer dictionary, the string
    table that compresses repeated blob names to integer keys, common-word
    table, profile metadata.

Postings are (blob_key, offset, length) triples (paper §III-A), delta +
LEB128-varint encoded in sorted order. The paper uses Protocol Buffers;
offline we implement an equivalent compact encoding by hand — same role,
measurably smaller, zero dependencies. The header rides on msgpack.
"""

from __future__ import annotations

from dataclasses import dataclass

import msgpack
import numpy as np

MAGIC = b"AIRP"
VERSION = 3


# --------------------------------------------------------------------- varint
def encode_varints(values: np.ndarray) -> bytes:
    """LEB128 encode a non-negative int64/uint64 array."""
    v = np.asarray(values, dtype=np.uint64)
    out = bytearray()
    for x in v:
        x = int(x)
        while True:
            b = x & 0x7F
            x >>= 7
            if x:
                out.append(b | 0x80)
            else:
                out.append(b)
                break
    return bytes(out)


# LEB128 spends 7 bits a byte, so a u64 takes at most 10 bytes
_MAX_BYTES = 10
_CONT = np.uint8(0x80)
_LOW = np.uint8(0x7F)
_SEVEN = np.uint8(7)
_COLS = np.arange(_MAX_BYTES)
_SHIFTS = np.arange(0, 7 * _MAX_BYTES, 7, dtype=np.uint64)


def _decode(buf: np.ndarray, n: int | None = None,
            ) -> tuple[np.ndarray, np.ndarray]:
    """The first `n` (None: every) LEB128 varints that end in the byte
    array `buf`, as u64, and the offset of each one's last byte.

    One scan finds every value's last byte (the continuation bit clear);
    the gaps between them are the widths. Most values on the read path
    are one or two bytes: those are computed for the whole array at
    once in 16 bits, from the last byte and, at width two, the byte
    before it. Only values of three bytes or more, a handful in a common
    word's superpost, are gathered byte by byte into u64, exactly up to
    the 10 bytes of a u64. This is the hot path of every superpost
    decode on the read path.
    """
    ends = np.flatnonzero(buf < _CONT)[:n]
    if not len(ends):
        return np.empty(0, dtype=np.uint64), ends
    width = np.empty_like(ends)
    width[0] = ends[0] + 1
    np.subtract(ends[1:], ends[:-1], out=width[1:])
    two = (width > 1).view(np.uint8)
    values = ((np.take(buf, ends).astype(np.uint16) << two * _SEVEN)
              | (np.take(buf, ends - 1) & _LOW) * two).astype(np.uint64)
    wide = np.flatnonzero(width > 2)
    if len(wide):
        w = width[wide]
        inside = _COLS < w[:, None]
        at = np.where(inside, (ends[wide] - w + 1)[:, None] + _COLS, 0)
        values[wide] = np.bitwise_or.reduce(
            (np.take(buf, at) & _LOW * inside).astype(np.uint64) << _SHIFTS,
            axis=1)
    return values, ends


def decode_varints(data: bytes, count: int) -> tuple[np.ndarray, int]:
    """Decode `count` LEB128 varints; returns (u64 values, bytes_consumed).

    Never looks past what `count` values could occupy."""
    count = int(count)
    buf = np.frombuffer(data, dtype=np.uint8)[:count * _MAX_BYTES]
    values, ends = _decode(buf, count)
    if len(values) < count:
        raise ValueError(
            f"truncated varint stream: {len(values)} values, need {count}")
    return values, int(ends[-1]) + 1 if count else 0


def _counted(data: bytes, rows: int) -> np.ndarray:
    """A block that is a count c followed by `rows` runs of c varints,
    decoded in one pass over its bytes: the (rows, c) u64 values. The
    block is all of `data` on the read path (a range read returns just
    the superpost); bytes after it are decoded and dropped."""
    values, _ = _decode(np.frombuffer(data, dtype=np.uint8))
    count = int(values[0]) if len(values) else 0
    if len(values) < 1 + rows * count:
        raise ValueError(f"truncated varint stream: {len(values)} values, "
                         f"need {1 + rows * count}")
    return values[1:1 + rows * count].reshape(rows, count)


# ---------------------------------------------------------------- superposts
# A posting is (blob_key, offset, length) — paper §III-A. We pack identity
# into a single sortable u64 key: blob_key << OFFSET_BITS | offset. That
# keeps intersection a flat u64 merge and makes delta-varint encoding of a
# sorted superpost maximally compact (the paper's string-compression idea,
# taken one step further).
OFFSET_BITS = 40                      # supports 1 TB blobs
_OFFSET_MASK = (1 << OFFSET_BITS) - 1


def posting_key(blob_key: np.ndarray, offset: np.ndarray) -> np.ndarray:
    return (np.asarray(blob_key, dtype=np.uint64) << np.uint64(OFFSET_BITS)) \
        | np.asarray(offset, dtype=np.uint64)


def split_posting_key(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    keys = np.asarray(keys, dtype=np.uint64)
    return (keys >> np.uint64(OFFSET_BITS)).astype(np.int64), \
        (keys & np.uint64(_OFFSET_MASK)).astype(np.int64)


def encode_superpost(keys: np.ndarray, lengths: np.ndarray) -> bytes:
    """Serialize one superpost: count + delta(sorted keys) + lengths."""
    keys = np.asarray(keys, dtype=np.uint64)
    lengths = np.asarray(lengths, dtype=np.uint64)
    assert keys.shape == lengths.shape
    if keys.size:
        deltas = np.empty_like(keys)
        deltas[0] = keys[0]
        deltas[1:] = keys[1:] - keys[:-1]
    else:
        deltas = keys
    return (encode_varints(np.array([keys.size], dtype=np.uint64))
            + encode_varints(deltas) + encode_varints(lengths))


def decode_superpost(data: bytes) -> tuple[np.ndarray, np.ndarray]:
    """Returns (sorted u64 posting keys, u64 lengths): the count, the
    deltas and the lengths in one pass over the superpost's bytes, the
    keys the running sum of the deltas, taken in place."""
    deltas, lengths = _counted(data, 2)
    return np.cumsum(deltas, out=deltas), lengths


# -------------------------------------------------------------------- header
@dataclass(frozen=True)
class BinPointer:
    """Locator of one superpost: (block id, byte offset, byte length)."""

    block: int
    offset: int
    length: int


def encode_header(payload: dict) -> bytes:
    return MAGIC + bytes([VERSION]) + msgpack.packb(payload, use_bin_type=True)


def decode_header(data: bytes) -> dict:
    if data[:4] != MAGIC:
        raise ValueError("not an Airphant index header")
    if data[4] != VERSION:
        raise ValueError(f"index version {data[4]} != supported {VERSION}")
    return msgpack.unpackb(data[5:], raw=False, strict_map_key=False)


def pack_pointers(ptrs: list[BinPointer]) -> bytes:
    """Columnar varint encoding of the MHT bin-pointer dictionary."""
    blocks = np.array([p.block for p in ptrs], dtype=np.uint64)
    offs = np.array([p.offset for p in ptrs], dtype=np.uint64)
    lens = np.array([p.length for p in ptrs], dtype=np.uint64)
    head = encode_varints(np.array([len(ptrs)], dtype=np.uint64))
    return head + encode_varints(blocks) + encode_varints(offs) + \
        encode_varints(lens)


def unpack_pointers(data: bytes) -> list[BinPointer]:
    blocks, offs, lens = _counted(data, 3).tolist()
    return [BinPointer(b, o, n) for b, o, n in zip(blocks, offs, lens)]
