"""Where one drive keeps an object's framed shard bytes — helper for
tests that read or damage them on disk.  An object above the packing
threshold has a ``part.N`` file in its data dir; one at or below it
(``commit.pack_threshold``, 1 MiB) is an extent of the drive's segment
file, found through ``fi.seg`` (storage/commit.py)."""

import os

import numpy as np

from minio_tpu.hashing import highwayhash
from minio_tpu.ops import gf8, gf8_ref


def shard_extent(disk, bucket: str, key: str, part: int = 1):
    """(path, offset, length) of part ``part``'s framed shard on
    ``disk`` (an XLStorage, or a proxy that forwards to one)."""
    fi = disk.read_version(bucket, key)
    assert fi.inline_data is None, "inline object: no shard file"
    if fi.seg:
        path = os.path.join(disk.root, ".mt.sys", "seg",
                            f"seg.{fi.seg['sid']:08x}.dat")
        return path, fi.seg["off"], fi.seg["len"]
    path = os.path.join(disk.root, bucket, key, fi.data_dir,
                        f"part.{part}")
    return path, 0, os.path.getsize(path)


def read_shard(disk, bucket: str, key: str, part: int = 1) -> bytes:
    path, off, length = shard_extent(disk, bucket, key, part)
    with open(path, "rb") as f:
        f.seek(off)
        return f.read(length)


def flip_byte(disk, bucket: str, key: str, at: int, mask: int = 0xFF,
              part: int = 1) -> None:
    """XOR ``mask`` into byte ``at`` of the framed shard (negative
    ``at`` counts from its end)."""
    path, off, length = shard_extent(disk, bucket, key, part)
    pos = off + (at if at >= 0 else length + at)
    with open(path, "r+b") as f:
        f.seek(pos)
        b = f.read(1)[0]
        f.seek(pos)
        f.write(bytes([b ^ mask]))


def reference_stripes(data: bytes, bs: int, k: int, m: int) -> list:
    """Per erasure block the (k+m, shard) stripe: reedsolomon Split +
    gf8_ref parity, the tail block at its own shard size."""
    out = []
    for off in range(0, len(data), bs):
        shards = gf8.split(data[off:off + bs], k)
        out.append(np.concatenate(
            [shards, gf8_ref.encode_parity(shards, m)]))
    return out


def reference_framed(data: bytes, bs: int, k: int, m: int) -> list[bytes]:
    """What each shard's file must hold: [32 B host HighwayHash][shard
    block] per erasure block."""
    stripes = reference_stripes(data, bs, k, m)
    return [b"".join(highwayhash.hh256(s[i].tobytes()) + s[i].tobytes()
                     for s in stripes) for i in range(k + m)]
