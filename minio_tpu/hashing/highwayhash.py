"""HighwayHash-256 — bitrot checksum (reference: cmd/bitrot.go:30-57).

The reference's default bitrot algorithm is keyed HighwayHash256 with a fixed
magic key (HH-256 of the first 100 decimals of pi under a zero key,
cmd/bitrot.go:31).  Here:

  * primary path: portable C implementation (native/highwayhash.c) compiled
    on first use and driven via ctypes -- the host-native analog of the
    reference's AVX2 assembly dependency;
  * fallback: pure-Python implementation (slow, used when no compiler).

Both are validated against the published HighwayHash64 test vectors.
"""

from __future__ import annotations

import ctypes
import os
import struct

# cmd/bitrot.go:31 — magic HH-256 key
MAGIC_KEY = (b"\x4b\xe7\x34\xfa\x8e\x23\x8a\xcd\x26\x3e\x83\xe6\xbb\x96\x85"
             b"\x52\x04\x0f\x93\x5d\xa3\x9f\x44\x14\x97\xe0\x9d\x13\x22\xde"
             b"\x36\xa0")

_NATIVE_SRC = os.path.join(os.path.dirname(__file__), "native",
                           "highwayhash.c")
# built where the other native libraries are (git-ignored native/build/)
_NATIVE_SO = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native", "build", "libmt_hash.so")
_LIB = None
_LIB_TRIED = False


def _get_lib():
    global _LIB, _LIB_TRIED
    if _LIB_TRIED:
        return _LIB
    from ..utils import nativelib
    lib = nativelib.load(_NATIVE_SRC, _NATIVE_SO)
    if lib is not None:
        try:
            lib.mt_hh256.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                     ctypes.c_size_t, ctypes.c_char_p]
            lib.mt_hh64.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                    ctypes.c_size_t]
            lib.mt_hh64.restype = ctypes.c_uint64
            lib.mt_hh256_blocks.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
                ctypes.c_size_t, ctypes.c_char_p]
            lib.mt_hh256_frame.argtypes = [
                ctypes.c_char_p, ctypes.c_void_p, ctypes.c_size_t,
                ctypes.c_size_t, ctypes.c_char_p]
            lib.mt_hh256_fill.argtypes = [
                ctypes.c_char_p, ctypes.c_void_p, ctypes.c_size_t,
                ctypes.c_size_t]
            lib.mt_hh256_verify_framed.argtypes = [
                ctypes.c_char_p, ctypes.c_void_p, ctypes.c_size_t,
                ctypes.c_size_t]
            lib.mt_hh256_verify_framed.restype = ctypes.c_int
            lib.mt_hh_stream_size.restype = ctypes.c_size_t
            lib.mt_hh_stream_init.argtypes = [ctypes.c_char_p,
                                              ctypes.c_char_p]
            lib.mt_hh_stream_update.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t]
            lib.mt_hh_stream_final256.argtypes = [ctypes.c_char_p,
                                                  ctypes.c_char_p]
        except Exception:  # noqa: BLE001
            lib = None
    _LIB = lib
    _LIB_TRIED = True
    return _LIB


# ---------------------------------------------------------------------------
# pure-Python fallback (bit-identical, slow)
# ---------------------------------------------------------------------------

_M64 = (1 << 64) - 1
_INIT_MUL0 = (0xDBE6D5D5FE4CCE2F, 0xA4093822299F31D0,
              0x13198A2E03707344, 0x243F6A8885A308D3)
_INIT_MUL1 = (0x3BD39E10CB0EF593, 0xC0ACF169B5F18A8C,
              0xBE5466CF34E90C6C, 0x452821E638D01377)


class _PyState:
    __slots__ = ("v0", "v1", "mul0", "mul1")

    def __init__(self, key: bytes):
        k = struct.unpack("<4Q", key)
        self.mul0 = list(_INIT_MUL0)
        self.mul1 = list(_INIT_MUL1)
        self.v0 = [m ^ kk for m, kk in zip(_INIT_MUL0, k)]
        self.v1 = [m ^ (((kk >> 32) | (kk << 32)) & _M64)
                   for m, kk in zip(_INIT_MUL1, k)]

    def _zipper(self, v1, v0):
        add0 = ((((v0 & 0xFF000000) | (v1 & 0xFF00000000)) >> 24)
                | (((v0 & 0xFF0000000000) | (v1 & 0xFF000000000000)) >> 16)
                | (v0 & 0xFF0000) | ((v0 & 0xFF00) << 32)
                | ((v1 & 0xFF00000000000000) >> 8) | ((v0 << 56) & _M64))
        add1 = ((((v1 & 0xFF000000) | (v0 & 0xFF00000000)) >> 24)
                | (v1 & 0xFF0000) | ((v1 & 0xFF0000000000) >> 16)
                | ((v1 & 0xFF00) << 24) | ((v0 & 0xFF000000000000) >> 8)
                | ((v1 & 0xFF) << 48) | (v0 & 0xFF00000000000000))
        return add1, add0

    def update_lanes(self, lanes):
        v0, v1, mul0, mul1 = self.v0, self.v1, self.mul0, self.mul1
        for i in range(4):
            v1[i] = (v1[i] + mul0[i] + lanes[i]) & _M64
            mul0[i] ^= ((v1[i] & 0xFFFFFFFF) * (v0[i] >> 32)) & _M64
            v0[i] = (v0[i] + mul1[i]) & _M64
            mul1[i] ^= ((v0[i] & 0xFFFFFFFF) * (v1[i] >> 32)) & _M64
        a1, a0 = self._zipper(v1[1], v1[0])
        v0[1] = (v0[1] + a1) & _M64
        v0[0] = (v0[0] + a0) & _M64
        a1, a0 = self._zipper(v1[3], v1[2])
        v0[3] = (v0[3] + a1) & _M64
        v0[2] = (v0[2] + a0) & _M64
        a1, a0 = self._zipper(v0[1], v0[0])
        v1[1] = (v1[1] + a1) & _M64
        v1[0] = (v1[0] + a0) & _M64
        a1, a0 = self._zipper(v0[3], v0[2])
        v1[3] = (v1[3] + a1) & _M64
        v1[2] = (v1[2] + a0) & _M64

    def update_packet(self, packet: bytes):
        self.update_lanes(struct.unpack("<4Q", packet))

    def update_remainder(self, tail: bytes):
        size = len(tail)
        assert 0 < size < 32
        size_mod4 = size & 3
        rem_off = size & ~3
        for i in range(4):
            self.v0[i] = (self.v0[i] + (size << 32) + size) & _M64
        # rotate each 32-bit half of v1 left by size
        for i in range(4):
            h0 = self.v1[i] & 0xFFFFFFFF
            h1 = self.v1[i] >> 32
            h0 = ((h0 << size) | (h0 >> (32 - size))) & 0xFFFFFFFF
            h1 = ((h1 << size) | (h1 >> (32 - size))) & 0xFFFFFFFF
            self.v1[i] = (h1 << 32) | h0
        packet = bytearray(32)
        packet[:rem_off] = tail[:rem_off]
        remainder = tail[rem_off:]
        if size & 16:
            for i in range(4):
                packet[28 + i] = tail[rem_off + i + size_mod4 - 4]
        elif size_mod4:
            packet[16] = remainder[0]
            packet[17] = remainder[size_mod4 >> 1]
            packet[18] = remainder[size_mod4 - 1]
        self.update_packet(bytes(packet))

    def _permute_update(self):
        v = self.v0
        self.update_lanes((
            ((v[2] >> 32) | (v[2] << 32)) & _M64,
            ((v[3] >> 32) | (v[3] << 32)) & _M64,
            ((v[0] >> 32) | (v[0] << 32)) & _M64,
            ((v[1] >> 32) | (v[1] << 32)) & _M64))

    def finalize64(self) -> int:
        for _ in range(4):
            self._permute_update()
        return (self.v0[0] + self.v1[0] + self.mul0[0] + self.mul1[0]) & _M64

    def finalize256(self) -> bytes:
        for _ in range(10):
            self._permute_update()

        def modred(a3u, a2, a1, a0):
            a3 = a3u & 0x3FFFFFFFFFFFFFFF
            m1 = a1 ^ (((a3 << 1) | (a2 >> 63)) & _M64) \
                ^ (((a3 << 2) | (a2 >> 62)) & _M64)
            m0 = a0 ^ ((a2 << 1) & _M64) ^ ((a2 << 2) & _M64)
            return m0, m1

        h0, h1 = modred((self.v1[1] + self.mul1[1]) & _M64,
                        (self.v1[0] + self.mul1[0]) & _M64,
                        (self.v0[1] + self.mul0[1]) & _M64,
                        (self.v0[0] + self.mul0[0]) & _M64)
        h2, h3 = modred((self.v1[3] + self.mul1[3]) & _M64,
                        (self.v1[2] + self.mul1[2]) & _M64,
                        (self.v0[3] + self.mul0[3]) & _M64,
                        (self.v0[2] + self.mul0[2]) & _M64)
        return struct.pack("<4Q", h0, h1, h2, h3)


def _py_process(key: bytes, data: bytes) -> _PyState:
    s = _PyState(key)
    n = len(data)
    i = 0
    while i + 32 <= n:
        s.update_packet(data[i:i + 32])
        i += 32
    if n & 31:
        s.update_remainder(data[i:])
    return s


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

DIGEST_SIZE = 32


def hh256(data, key: bytes = MAGIC_KEY) -> bytes:
    """One-shot HighwayHash-256 (the per-shard-block bitrot checksum)."""
    data = bytes(data)
    lib = _get_lib()
    if lib is not None:
        out = ctypes.create_string_buffer(32)
        lib.mt_hh256(key, data, len(data), out)
        return out.raw
    return _py_process(key, data).finalize256()


def hh64(data, key: bytes = MAGIC_KEY) -> int:
    data = bytes(data)
    lib = _get_lib()
    if lib is not None:
        return int(lib.mt_hh64(key, data, len(data)))
    return _py_process(key, data).finalize64()


def hh256_blocks(data, block_size: int, key: bytes = MAGIC_KEY) -> list[bytes]:
    """Hash consecutive blocks (last may be short): the bitrot verify sweep."""
    data = bytes(data)
    if block_size <= 0:
        raise ValueError("block_size must be positive")
    count = (len(data) + block_size - 1) // block_size
    lib = _get_lib()
    if lib is not None:
        out = ctypes.create_string_buffer(32 * count)
        lib.mt_hh256_blocks(key, data, len(data), block_size, out)
        return [out.raw[i * 32:(i + 1) * 32] for i in range(count)]
    return [hh256(data[i * block_size:(i + 1) * block_size], key)
            for i in range(count)]


def hh256_fill(framed, block_size: int, key: bytes = MAGIC_KEY) -> bool:
    """Fill digest slots of an already-framed [32B hash][block] buffer
    IN PLACE (one GIL-free native pass over a writable numpy row /
    memoryview).  The zero-copy PUT pipeline lays shard bytes straight
    into frame payloads and then calls this.  Returns False when the
    native library is unavailable (caller falls back to hh256_frame)."""
    if block_size <= 0:
        raise ValueError("block_size must be positive")
    lib = _get_lib()
    if lib is None:
        return False
    import numpy as np
    arr = np.frombuffer(framed, dtype=np.uint8) \
        if not isinstance(framed, np.ndarray) else framed
    if not (arr.flags["C_CONTIGUOUS"] and arr.flags["WRITEABLE"]):
        raise ValueError("hh256_fill needs a writable contiguous buffer")
    lib.mt_hh256_fill(key, arr.ctypes.data_as(ctypes.c_void_p),
                      arr.size, block_size)
    return True


def hh256_verify_framed(framed, block_size: int,
                        key: bytes = MAGIC_KEY) -> int | None:
    """Verify every block digest of a framed [32B hash][block] buffer
    in ONE GIL-free native pass (the GET-side dual of hh256_fill).

    Returns 0 when all blocks verify, the 1-based index of the first
    corrupt block otherwise, or None when the native library is
    unavailable (caller falls back to the per-block Python reader)."""
    if block_size <= 0:
        raise ValueError("block_size must be positive")
    lib = _get_lib()
    if lib is None:
        return None
    import numpy as np
    arr = np.frombuffer(framed, dtype=np.uint8) \
        if not isinstance(framed, np.ndarray) else framed
    if not arr.flags["C_CONTIGUOUS"]:
        arr = np.ascontiguousarray(arr)
    return int(lib.mt_hh256_verify_framed(
        key, arr.ctypes.data_as(ctypes.c_void_p), arr.size, block_size))


def verify_framed_address() -> int | None:
    """The address of the native ``mt_hh256_verify_framed`` (what
    :func:`hh256_verify_framed` calls), for a native caller that checks
    frames below the interpreter (storage/xl_storage.py
    read_shard_wave); None without the native library."""
    lib = _get_lib()
    if lib is None:
        return None
    return ctypes.cast(lib.mt_hh256_verify_framed, ctypes.c_void_p).value


def hh256_frame(data, block_size: int, key: bytes = MAGIC_KEY) -> bytes:
    """Frame a shard file (hash || block per block) in ONE native pass.

    The bitrot writer's hot path (cmd/bitrot-streaming.go:46-58): hash
    and interleave happen inside a single GIL-releasing C call, so
    concurrent PUTs scale.  Accepts any contiguous buffer (bytes,
    numpy, memoryview) without copying on the native path."""
    if block_size <= 0:
        raise ValueError("block_size must be positive")
    mv = memoryview(data).cast("B")
    size = len(mv)
    if size == 0:
        return b""
    count = (size + block_size - 1) // block_size
    lib = _get_lib()
    if lib is not None:
        import numpy as np
        arr = np.frombuffer(mv, dtype=np.uint8)     # zero-copy view
        out = ctypes.create_string_buffer(size + 32 * count)
        lib.mt_hh256_frame(key, arr.ctypes.data_as(ctypes.c_void_p),
                           size, block_size, out)
        return out.raw
    # pure-python fallback: identical framing
    b = mv.tobytes()
    parts = []
    for i in range(count):
        blk = b[i * block_size:(i + 1) * block_size]
        parts.append(hh256(blk, key))
        parts.append(blk)
    return b"".join(parts)


class HighwayHash256:
    """Streaming hash.Hash-style interface (whole-file bitrot writer)."""

    digest_size = DIGEST_SIZE
    name = "highwayhash256"

    def __init__(self, key: bytes = MAGIC_KEY):
        self._key = key
        self._lib = _get_lib()
        if self._lib is not None:
            self._st = ctypes.create_string_buffer(
                self._lib.mt_hh_stream_size())
            self._lib.mt_hh_stream_init(self._st, key)
        else:
            self._buf = bytearray()

    def update(self, data) -> None:
        data = bytes(data)
        if self._lib is not None:
            self._lib.mt_hh_stream_update(self._st, data, len(data))
        else:
            self._buf += data

    def digest(self) -> bytes:
        if self._lib is not None:
            # finalize a copy so the stream stays usable
            st_copy = ctypes.create_string_buffer(self._st.raw)
            out = ctypes.create_string_buffer(32)
            self._lib.mt_hh_stream_final256(st_copy, out)
            return out.raw
        return _py_process(self._key, bytes(self._buf)).finalize256()

    def hexdigest(self) -> str:
        return self.digest().hex()

    def reset(self) -> None:
        if self._lib is not None:
            self._lib.mt_hh_stream_init(self._st, self._key)
        else:
            self._buf = bytearray()
