"""A plain reader of the durability log as it lies on disk, so that the
check reads what was written and not what the program says it wrote.

On-disk framing (``utils/ingestlog.py``'s documented format): segment
files ``segment-<n>.log`` start with ``SWAL1\\n``; each record is
``u32 LE length, u32 LE CRC32, body``; a length of 0xFFFFFFFF introduces
a watermark record (``u32 length, u32 CRC32, JSON body``). A JSON wire
batch's body is ``\\x01 + tenant + \\x00 + payload``.
"""

from __future__ import annotations

import os
import struct

import numpy as np

MAGIC = b"SWAL1\n"
WATERMARK = 0xFFFFFFFF


def segments(wal_dir: str) -> list[str]:
    if not os.path.isdir(wal_dir):
        return []
    return [os.path.join(wal_dir, f) for f in sorted(os.listdir(wal_dir))
            if f.startswith("segment-") and f.endswith(".log")]


def total_bytes(wal_dir: str) -> int:
    """Bytes the log holds now, over all segments (a cheap stat: the
    probe reader calls it at every visibility observation)."""
    total = 0
    for p in segments(wal_dir):
        try:
            total += os.path.getsize(p)
        except OSError:
            pass
    return total


def read_records(paths: list[str], base_offset: int = 0) -> dict:
    """Every record of the given segments, in order: CRC32 of each JSON
    wire record (tag ``\\x01``) and the log offset at which it ends;
    counts of other records. Raises on a torn or corrupt frame."""
    crcs, ends = [], []
    other = 0
    off = base_offset
    for p in paths:
        with open(p, "rb") as f:
            buf = f.read()
        if not buf.startswith(MAGIC):
            raise ValueError(f"{p}: no segment header")
        i = len(MAGIC)
        n = len(buf)
        unpack = struct.unpack_from
        while i < n:
            (ln,) = unpack("<I", buf, i)
            if ln == WATERMARK:
                ln2, _ = unpack("<II", buf, i + 4)
                i += 12 + ln2
                other += 1
                continue
            if i + 8 + ln > n:
                raise ValueError(f"{p}: torn record at {i}")
            crc = unpack("<I", buf, i + 4)[0]
            if buf[i + 8] == 1:
                crcs.append(crc)
                ends.append(off + i + 8 + ln)
            else:
                other += 1
            i += 8 + ln
        off += n
    return {"crc": np.asarray(crcs, np.uint32),
            "end": np.asarray(ends, np.int64), "other": other}
