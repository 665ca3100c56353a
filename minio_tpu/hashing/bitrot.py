"""Bitrot protection layer — per-shard-block hash framing.

Reference behavior (cmd/bitrot.go, cmd/bitrot-streaming.go, cmd/bitrot-whole.go):

  * four algorithms: SHA256, BLAKE2b-512, HighwayHash256 (whole-file) and
    HighwayHash256S (streaming, the default) -- cmd/bitrot.go:33-38;
  * the streaming format interleaves ``hash(block) || block`` for each
    shard-size block in the shard file (cmd/bitrot-streaming.go:46-58);
  * readers verify every block hash on ReadAt and surface errFileCorrupt on
    mismatch (cmd/bitrot-streaming.go:115-158);
  * bitrotShardFileSize = ceil(size/shardSize)*hashLen + size for streaming
    algorithms, size otherwise (cmd/bitrot.go:140-145).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import BinaryIO

from .highwayhash import MAGIC_KEY, HighwayHash256, hh256
from ..ops.gf8 import ceil_frac

# algorithm ids follow the reference's iota order (cmd/bitrot-whole.go deps):
SHA256 = "sha256"
BLAKE2B512 = "blake2b"
HIGHWAYHASH256 = "highwayhash256"
HIGHWAYHASH256S = "highwayhash256S"
DEFAULT_BITROT_ALGORITHM = HIGHWAYHASH256S

_ALGORITHMS = {SHA256, BLAKE2B512, HIGHWAYHASH256, HIGHWAYHASH256S}


class BitrotError(IOError):
    """errFileCorrupt analog: stored hash does not match content."""


def is_streaming(algo: str) -> bool:
    return algo == HIGHWAYHASH256S


def available(algo: str) -> bool:
    return algo in _ALGORITHMS


def new_hash(algo: str):
    """BitrotAlgorithm.New (cmd/bitrot.go:41-58)."""
    if algo == SHA256:
        return hashlib.sha256()
    if algo == BLAKE2B512:
        return hashlib.blake2b(digest_size=64)
    if algo in (HIGHWAYHASH256, HIGHWAYHASH256S):
        return HighwayHash256(MAGIC_KEY)
    raise ValueError(f"unsupported bitrot algorithm {algo!r}")


def digest_size(algo: str) -> int:
    return new_hash(algo).digest_size


def hash_block(algo: str, block: bytes) -> bytes:
    if algo in (HIGHWAYHASH256, HIGHWAYHASH256S):
        return hh256(block)  # native one-shot fast path
    h = new_hash(algo)
    h.update(block)
    return h.digest()


def bitrot_shard_file_size(size: int, shard_size: int, algo: str) -> int:
    """On-disk size of a shard file with bitrot protection
    (cmd/bitrot.go:140-145)."""
    if not is_streaming(algo):
        return size
    return ceil_frac(size, shard_size) * digest_size(algo) + size


def bitrot_shard_file_offset(offset: int, shard_size: int, algo: str) -> int:
    """Logical shard offset -> physical offset in the framed stream
    (cmd/bitrot-streaming.go:126)."""
    if not is_streaming(algo):
        return offset
    return (offset // shard_size) * digest_size(algo) + offset


def streaming_encode(data: bytes, shard_size: int,
                     algo: str = DEFAULT_BITROT_ALGORITHM) -> bytes:
    """Frame a whole shard file: hash || block per shard_size block."""
    if not is_streaming(algo):     # only highwayhash256S streams
        # whole-file algos store the shard unframed — coerce to bytes so
        # downstream consumers (msgpack inline_data, RPC bodies) never
        # see a numpy row
        return data if isinstance(data, bytes) else \
            bytes(memoryview(data).cast("B"))
    if len(data) == 0:
        return b""
    # one GIL-free native pass: hash + interleave together
    from .highwayhash import hh256_frame
    return hh256_frame(data, shard_size)


def streaming_encode_batch(shards, shard_size: int,
                           algo: str = DEFAULT_BITROT_ALGORITHM
                           ) -> list[bytes]:
    """Frame a full stripe of equal-length shard files at once, on the
    host.  (The device form, digests from the chip, is the codec
    facade's: ``Erasure.encode_framed``.)"""
    if not is_streaming(algo):
        return [bytes(bytearray(s)) for s in shards]
    # streaming_encode takes any contiguous buffer zero-copy (numpy
    # shard rows included) — don't round-trip through bytes()
    return [streaming_encode(s, shard_size, algo) for s in shards]


def fill_framed(framed2d, shard_size: int,
                algo: str = DEFAULT_BITROT_ALGORITHM) -> bool:
    """Fill digest slots of pre-framed shard rows IN PLACE.

    framed2d: (n_shards, framed_len) uint8 laid out by
    Erasure.encode_object_framed ([32B zeroed digest][block] frames).
    Returns False when the native hash library is unavailable — the
    caller then uses the copying streaming_encode_batch path instead."""
    if algo != HIGHWAYHASH256S:
        return False
    from .highwayhash import hh256_fill
    F = 32 + shard_size
    if getattr(framed2d, "ndim", 1) == 2 and framed2d.shape[1] % F == 0 \
            and framed2d.flags["C_CONTIGUOUS"]:
        # no short tail frame: row boundaries fall on frame boundaries,
        # so the whole 2D buffer is one valid frame sequence — hash all
        # k+m rows in a single GIL-free native pass
        return hh256_fill(framed2d.reshape(-1), shard_size)
    for row in framed2d:
        if not hh256_fill(row, shard_size):
            return False
    return True


class StreamingBitrotWriter:
    """Interleaves hash||block into a file-like sink
    (cmd/bitrot-streaming.go:39-58).  Each write() must be exactly one
    shard-size block (the last may be short), as in the reference where the
    erasure encoder hands one shard-block per stripe."""

    def __init__(self, sink: BinaryIO, algo: str = DEFAULT_BITROT_ALGORITHM):
        self.sink = sink
        self.algo = algo

    def write(self, block: bytes) -> int:
        if len(block) == 0:
            return 0
        self.sink.write(hash_block(self.algo, block))
        self.sink.write(block)
        return len(block)


class StreamingBitrotReader:
    """Verified ReadAt over a framed shard stream
    (cmd/bitrot-streaming.go:92-158).

    ``read_at(offset, length)``: offset must be shard_size aligned (logical,
    unframed coordinates); every covered block's hash is verified."""

    def __init__(self, framed: bytes | memoryview, shard_size: int,
                 algo: str = DEFAULT_BITROT_ALGORITHM):
        self.data = memoryview(framed)
        self.shard_size = shard_size
        self.algo = algo
        self.hash_len = digest_size(algo)

    def read_at(self, offset: int, length: int) -> bytes:
        if not is_streaming(self.algo):
            # whole-file algorithms carry no interleaved hashes; verification
            # is done once over the full file via BitrotVerifier
            return bytes(self.data[offset:offset + length])
        if offset % self.shard_size != 0:
            raise ValueError("offset must be aligned to shard size")
        out = bytearray()
        pos = (offset // self.shard_size) * self.hash_len + offset
        remaining = length
        while remaining > 0:
            want = min(self.shard_size, remaining)
            h = bytes(self.data[pos:pos + self.hash_len])
            if len(h) < self.hash_len:
                raise BitrotError("short read: missing block hash")
            pos += self.hash_len
            block = bytes(self.data[pos:pos + want])
            if len(block) < want:
                raise BitrotError("short read: truncated block")
            pos += len(block)
            if hash_block(self.algo, block) != h:
                raise BitrotError("content hash mismatch")
            out += block
            remaining -= want
        return bytes(out)


def verify_extract(framed, shard_size: int, length: int,
                   algo: str = DEFAULT_BITROT_ALGORITHM):
    """Verify a whole framed shard and extract its payload — the GET
    hot path (cmd/bitrot-streaming.go ReadAt, whole-shard case).

    One GIL-free native digest pass over the frame plus one strided
    numpy copy for the payload, instead of per-block Python hashing
    with three intermediate copies.  Returns a uint8 array of
    ``length`` payload bytes, or None when the fast path does not
    apply (non-HH256S algo / native lib missing) — caller falls back
    to StreamingBitrotReader.
    """
    if algo != HIGHWAYHASH256S:
        return None
    from .highwayhash import hh256_verify_framed
    import numpy as np
    arr = np.frombuffer(framed, dtype=np.uint8) \
        if not isinstance(framed, np.ndarray) else framed
    bad = hh256_verify_framed(arr, shard_size)
    if bad is None:
        return None
    if bad:
        raise BitrotError(f"content hash mismatch (block {bad})")
    F = 32 + shard_size
    nfull = arr.size // F
    head = arr[:nfull * F].reshape(nfull, F)[:, 32:]   # strided view
    if nfull * shard_size >= length:
        return head.reshape(-1)[:length].copy()
    # Caller-declared length comes from xl.meta — never trust it past
    # what the digest-verified frame actually holds, or the tail copy
    # below raises a broadcast ValueError that escapes the caller's
    # BitrotError handling and surfaces as a 500 instead of FileCorrupt.
    tail = arr[nfull * F + 32:]                        # short last block
    if nfull * shard_size + tail.size < length:
        raise BitrotError(
            f"truncated frame: {nfull * shard_size + tail.size} payload "
            f"bytes present, {length} declared")
    out = np.empty(length, dtype=np.uint8)
    out[:nfull * shard_size] = head.reshape(-1)
    out[nfull * shard_size:] = tail[:length - nfull * shard_size]
    return out


@dataclass
class BitrotVerifier:
    """Whole-file verifier (cmd/bitrot.go:77-85)."""
    algorithm: str
    sum: bytes

    def verify(self, data: bytes) -> bool:
        return hash_block(self.algorithm, data) == self.sum


class WholeBitrotWriter:
    """Whole-file bitrot: raw bytes to sink, running hash kept for metadata
    (cmd/bitrot-whole.go:29-59)."""

    def __init__(self, sink: BinaryIO, algo: str):
        self.sink = sink
        self._h = new_hash(algo)

    def write(self, p: bytes) -> int:
        self._h.update(p)
        self.sink.write(p)
        return len(p)

    def sum(self) -> bytes:
        return self._h.digest()
