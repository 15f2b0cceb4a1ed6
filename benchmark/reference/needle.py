"""SeaweedFS's Haystack volume format, version 3 (weed/storage/needle/
needle_read_write.go, weed/storage/super_block/super_block.go,
weed/storage/idx/walk.go), big-endian throughout:

  .dat  superblock (8 bytes: version, replica placement, ttl(2),
        compaction revision(2), extra size(2)), then one record per blob:
        cookie(4) id(8) size(4) | dataSize(4) data flags(1) |
        crc(4) appendAtNs(8) | padding to a multiple of 8 (8 when
        already aligned), where size counts dataSize..flags and crc is
        the masked CRC-32C of data.
  .idx  key(8) offset/8 (4) size(4) per record, in append order.
  .ecx  the same entries sorted by key.
  fid   "<vid>,<hex of key||cookie with the key's leading zero bytes cut>".
"""

from __future__ import annotations

import struct

import google_crc32c
import numpy as np

VERSION = 3
SUPER_BLOCK = bytes([VERSION, 0, 0, 0, 0, 0, 0, 0])


def masked_crc(data: bytes) -> int:
    crc = google_crc32c.value(data)
    return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


def record_length(data_len: int) -> int:
    body = 16 + 4 + data_len + 1 + 4 + 8
    return body + 8 - body % 8


def build_volume(keys: np.ndarray, cookies: np.ndarray, sizes: np.ndarray,
                 content: bytes, append_ns: int
                 ) -> tuple[np.ndarray, np.ndarray]:
    """(.dat bytes, record offsets) for blobs i = 0.. in order, blob i
    being the next sizes[i] bytes of `content`."""
    lengths = np.array([record_length(int(n)) for n in sizes], np.int64)
    offsets = len(SUPER_BLOCK) + np.concatenate(
        [[0], np.cumsum(lengths)[:-1]]).astype(np.int64)
    dat = np.zeros(len(SUPER_BLOCK) + int(lengths.sum()), dtype=np.uint8)
    dat[:len(SUPER_BLOCK)] = np.frombuffer(SUPER_BLOCK, np.uint8)
    pos = 0
    for i, n in enumerate(int(x) for x in sizes):
        data = content[pos:pos + n]
        pos += n
        rec = (struct.pack(">IQII", int(cookies[i]), int(keys[i]), n + 5, n)
               + data
               + struct.pack(">BIQ", 0, masked_crc(data), append_ns + i))
        o = int(offsets[i])
        dat[o:o + len(rec)] = np.frombuffer(rec, np.uint8)
    return dat, offsets


def index_bytes(keys: np.ndarray, offsets: np.ndarray,
                body_sizes: np.ndarray) -> bytes:
    rows = np.empty(len(keys), dtype=[("k", ">u8"), ("o", ">u4"),
                                      ("s", ">u4")])
    rows["k"], rows["o"], rows["s"] = keys, offsets // 8, body_sizes
    return rows.tobytes()


def ecx_bytes(keys: np.ndarray, offsets: np.ndarray,
              body_sizes: np.ndarray) -> bytes:
    order = np.argsort(keys, kind="stable")
    return index_bytes(keys[order], offsets[order], body_sizes[order])


def fid(vid: int, key: int, cookie: int) -> str:
    raw = struct.pack(">QI", key, cookie)
    i = 0
    while i < 8 and raw[i] == 0:
        i += 1
    return f"{vid},{raw[i:].hex()}"
