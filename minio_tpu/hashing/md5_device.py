"""Device-side multi-buffer MD5 — the strict-compat ETag off the host
entirely (ISSUE 12 tentpole b).

MD5 is an irreducible serial chain per stream, but the chain step is
64 rounds of u32 add/rotate/boolean — and ``native/md5mb.cc`` already
showed the multi-buffer trick: advance N INDEPENDENT digests in
lock-step, message schedule stored word-major so every round's loads
are contiguous across lanes.  That is a batch axis, and a batch axis
is what the device is for (the same reshape that turned GF(2^8) into
matmuls, ops/gf8.py): states become an (N, 4) u32 array, one 64-byte
block becomes an (N, 16) u32 slice, and the whole block loop runs as
ONE device dispatch under ``lax.fori_loop`` — concurrent strict-ETag
streams coalesce into one launch instead of taxing host cores.

Layering (mirrors hashing/md5fast.py):

  * ``advance(states, words, nblocks)`` — the batched compress: each
    lane advances by its OWN block count (ragged batches mask with
    ``t < nblocks``), shapes bucketed to powers of two so the jit
    cache stays small;
  * ``MD5Device`` — a hashlib-compatible digest object: whole 64-byte
    blocks ride the device (through the ``md5`` combining bucket in
    parallel/batcher.py), sub-block tails and the final padding run a
    host scalar compress (≤2 blocks per digest — microseconds);
  * ``available()`` / ``unavailable_reason()`` — the degradation
    contract: no usable jax device (or import failure) yields a NAMED
    reason, and hashing/md5fast.py drops to the host lane scheduler —
    the fallback ladder is device → native lanes → hashlib;
  * ``device_rate_gibps()`` — the auto-backend calibration probe:
    ``pipeline.md5_backend=auto`` MEASURES the device rung (transfer
    included) against the native host core once and picks the winner
    instead of trusting the platform name.

Digests are bit-identical to RFC 1321 / hashlib for every lane count,
length and update split (tests/test_fused_kernel.py pins the md5fast
boundary lengths 0/1/55/56/63/64/65/4MiB±1 and split updates).
"""

from __future__ import annotations

import struct
import time

import numpy as np

# RFC 1321 tables (identical to native/md5mb.cc)
_K = [
    0xd76aa478, 0xe8c7b756, 0x242070db, 0xc1bdceee,
    0xf57c0faf, 0x4787c62a, 0xa8304613, 0xfd469501,
    0x698098d8, 0x8b44f7af, 0xffff5bb1, 0x895cd7be,
    0x6b901122, 0xfd987193, 0xa679438e, 0x49b40821,
    0xf61e2562, 0xc040b340, 0x265e5a51, 0xe9b6c7aa,
    0xd62f105d, 0x02441453, 0xd8a1e681, 0xe7d3fbc8,
    0x21e1cde6, 0xc33707d6, 0xf4d50d87, 0x455a14ed,
    0xa9e3e905, 0xfcefa3f8, 0x676f02d9, 0x8d2a4c8a,
    0xfffa3942, 0x8771f681, 0x6d9d6122, 0xfde5380c,
    0xa4beea44, 0x4bdecfa9, 0xf6bb4b60, 0xbebfbc70,
    0x289b7ec6, 0xeaa127fa, 0xd4ef3085, 0x04881d05,
    0xd9d4d039, 0xe6db99e5, 0x1fa27cf8, 0xc4ac5665,
    0xf4292244, 0x432aff97, 0xab9423a7, 0xfc93a039,
    0x655b59c3, 0x8f0ccc92, 0xffeff47d, 0x85845dd1,
    0x6fa87e4f, 0xfe2ce6e0, 0xa3014314, 0x4e0811a1,
    0xf7537e82, 0xbd3af235, 0x2ad7d2bb, 0xeb86d391,
]
_S = [
    7, 12, 17, 22, 7, 12, 17, 22, 7, 12, 17, 22, 7, 12, 17, 22,
    5, 9, 14, 20, 5, 9, 14, 20, 5, 9, 14, 20, 5, 9, 14, 20,
    4, 11, 16, 23, 4, 11, 16, 23, 4, 11, 16, 23, 4, 11, 16, 23,
    6, 10, 15, 21, 6, 10, 15, 21, 6, 10, 15, 21, 6, 10, 15, 21,
]
_INIT = (0x67452301, 0xefcdab89, 0x98badcfe, 0x10325476)


def _msg_index(i: int) -> int:
    if i < 16:
        return i
    if i < 32:
        return (5 * i + 1) % 16
    if i < 48:
        return (3 * i + 5) % 16
    return (7 * i) % 16


# -- availability -----------------------------------------------------------

_AVAIL: bool | None = None
_REASON = ""


def available() -> bool:
    """True when a jax device can run the batched compress.  The CPU
    backend COUNTS as a device (tests and virtual meshes exercise the
    exact production code path); whether it is WORTH using is the auto
    calibration's call, not this one's."""
    global _AVAIL, _REASON
    if _AVAIL is not None:
        return _AVAIL
    try:
        from ..ops import device  # noqa: F401 — compile cache set before the first jit
        import jax
        devs = jax.devices()
        if not devs:
            raise RuntimeError("jax reports zero devices")
        _AVAIL, _REASON = True, ""
    except Exception as e:  # noqa: BLE001 — the reason IS the contract
        _AVAIL = False
        _REASON = f"device MD5 unavailable: {type(e).__name__}: {e}"
    return _AVAIL


def unavailable_reason() -> str:
    """The named degradation reason (test skip messages + the
    mt_md5_device_fallback_total increment site quote this)."""
    available()
    return _REASON


def _reset_for_tests() -> None:
    global _AVAIL, _REASON, _RATE
    _AVAIL, _REASON, _RATE = None, "", None


# -- the batched compress ---------------------------------------------------


def _advance_fn():
    """Build (once) the jitted batched compress.  Shapes recompile per
    (N_pad, nb_pad) bucket; both are padded to powers of two by
    ``advance`` so the cache stays at a handful of entries."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def adv(h, words, nblocks):
        # h: (N, 4) u32; words: (N, nb, 16) u32 little-endian message
        # words; nblocks: (N,) i32 — lane l advances by nblocks[l]
        # blocks, further blocks are masked no-ops (ragged batches).
        def body(t, h):
            a = h[:, 0]
            b = h[:, 1]
            c = h[:, 2]
            d = h[:, 3]
            m = words[:, t]                      # (N, 16) word-major
            for i in range(64):
                if i < 16:
                    f = (b & c) | (~b & d)
                elif i < 32:
                    f = (d & b) | (~d & c)
                elif i < 48:
                    f = b ^ c ^ d
                else:
                    f = c ^ (b | ~d)
                f = f + a + jnp.uint32(_K[i]) + m[:, _msg_index(i)]
                a, d, c = d, c, b
                s = _S[i]
                b = b + ((f << s) | (f >> (32 - s)))
            h2 = jnp.stack([h[:, 0] + a, h[:, 1] + b,
                            h[:, 2] + c, h[:, 3] + d], axis=1)
            mask = (t < nblocks)[:, None]
            return jnp.where(mask, h2, h)

        return jax.lax.fori_loop(0, words.shape[1], body, h)

    return adv


_ADV = None


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def advance(states: np.ndarray, words: np.ndarray,
            nblocks: np.ndarray) -> np.ndarray:
    """Advance N digests by their own block counts in ONE dispatch.

    states: (N, 4) u32; words: (N, nb, 16) u32 (lane l's blocks beyond
    nblocks[l] may be garbage — they are masked); nblocks: (N,) ints.
    Returns the new (N, 4) u32 states (host numpy).
    """
    global _ADV
    if _ADV is None:
        _ADV = _advance_fn()
    import jax.numpy as jnp
    N, nb = words.shape[0], words.shape[1]
    np_, nbp = _pow2(max(1, N)), _pow2(max(1, nb))
    if np_ != N or nbp != nb:
        w = np.zeros((np_, nbp, 16), dtype=np.uint32)
        w[:N, :nb] = words
        st = np.zeros((np_, 4), dtype=np.uint32)
        st[:N] = states
        nv = np.zeros((np_,), dtype=np.int32)
        nv[:N] = nblocks
    else:
        w, st = words, np.asarray(states, np.uint32)
        nv = np.asarray(nblocks, np.int32)
    out = _ADV(jnp.asarray(st), jnp.asarray(w), jnp.asarray(nv))
    return np.asarray(out)[:N]


# -- host scalar compress (tails + finalization only) -----------------------


def _compress_host(h: list[int], block: bytes) -> list[int]:
    """One-block RFC 1321 compress in pure Python — only sub-block
    tails and the final padding ride this (≤2 blocks per digest)."""
    M = 0xFFFFFFFF
    m = struct.unpack("<16I", block)
    a, b, c, d = h
    for i in range(64):
        if i < 16:
            f = (b & c) | (~b & d & M)
        elif i < 32:
            f = (d & b) | (~d & c & M)
        elif i < 48:
            f = b ^ c ^ d
        else:
            f = c ^ ((b | (~d & M)))
        f = (f + a + _K[i] + m[_msg_index(i)]) & M
        a, d, c = d, c, b
        s = _S[i]
        b = (b + (((f << s) | (f >> (32 - s))) & M)) & M
    return [(h[0] + a) & M, (h[1] + b) & M, (h[2] + c) & M,
            (h[3] + d) & M]


class MD5Device:
    """hashlib.md5-compatible digest whose bulk blocks run on the
    device.  Whole 64-byte blocks route through the ``md5`` combining
    bucket (parallel/batcher.py) so concurrent streams coalesce into
    one dispatch; the sub-block tail and final padding run the host
    scalar compress.  ``digest`` finalizes a copy, so the stream stays
    usable (the stdlib contract)."""

    name = "md5"
    digest_size = 16
    block_size = 64

    __slots__ = ("_h", "_n", "_tail", "_dispatch")

    def __init__(self, data=b"", dispatch=None):
        self._h = list(_INIT)
        self._n = 0
        self._tail = b""
        # dispatch(h4_u32, words (nb, 16) u32) -> new h4_u32; defaults
        # to the md5 combining bucket (late import: batcher pulls the
        # codec plane in, and hashing must stay importable without it)
        self._dispatch = dispatch
        if data:
            self.update(data)

    # blocks per bucket submission: 1 MiB — the md5fast.ONESHOT_SLICE
    # discipline.  A whole 64 MiB stream-batch chunk submitted as one
    # advance would overflow the bucket's queue bound and shed every
    # time (never coalescing — the measured PR-6 failure mode of
    # whole-buffer oneshots, one level down); slab-sized submissions
    # interleave concurrent streams across batched dispatches.
    _SLAB_BLOCKS = (1 << 20) // 64

    def _advance_blocks(self, words: np.ndarray) -> None:
        if self._dispatch is None:
            from ..parallel import batcher
            self._dispatch = batcher.MD5_GLOBAL.advance
        for off in range(0, words.shape[0], self._SLAB_BLOCKS):
            self._h = list(int(x) for x in self._dispatch(
                np.asarray(self._h, np.uint32),
                words[off:off + self._SLAB_BLOCKS]))

    def update(self, data) -> None:
        mv = memoryview(data)
        if mv.ndim != 1 or mv.itemsize != 1:
            mv = mv.cast("B")
        n = len(mv)
        if n == 0:
            return
        self._n += n
        if self._tail:
            take = min(64 - len(self._tail), n)
            self._tail += bytes(mv[:take])
            mv = mv[take:]
            n -= take
            if len(self._tail) == 64:
                self._h = _compress_host(self._h, self._tail)
                self._tail = b""
            if n == 0:
                return
        nb = n // 64
        if nb:
            words = np.frombuffer(mv[:nb * 64], dtype="<u4") \
                .reshape(nb, 16)
            self._advance_blocks(words)
        if n % 64:
            self._tail = bytes(mv[nb * 64:])

    def digest(self) -> bytes:
        h = list(self._h)
        bits = self._n * 8
        pad = self._tail + b"\x80" + b"\x00" * (
            (119 - len(self._tail)) % 64) + struct.pack("<Q", bits)
        for off in range(0, len(pad), 64):
            h = _compress_host(h, pad[off:off + 64])
        return struct.pack("<4I", *h)

    def hexdigest(self) -> str:
        return self.digest().hex()

    def copy(self) -> "MD5Device":
        c = MD5Device.__new__(MD5Device)
        c._h = list(self._h)
        c._n = self._n
        c._tail = self._tail
        c._dispatch = self._dispatch
        return c


# -- auto-backend calibration ----------------------------------------------

_RATE: float | None = None


def device_rate_gibps(slices: int = 4,
                      kib_per_slice: int = 1024) -> float:
    """Measured end-to-end device MD5 rate through the PRODUCTION
    path: an ``MD5Device`` updated slice by slice through the ``md5``
    combining bucket, so the probe pays everything a real strict-ETag
    stream pays — the host->device transfer of the schedule words AND
    the bucket's combining-window wait per slice.  The slice size matches
    ``md5fast.ONESHOT_SLICE`` (1 MiB): the window tax amortizes per
    slice exactly as it does for a real solo stream — smaller probe
    slices would overweight the window and veto a fast device.  Cached
    after first call; ``pipeline.md5_backend=auto`` compares this
    against the host lane rate and picks the winner
    (hashing/md5fast.py)."""
    global _RATE
    if _RATE is not None:
        return _RATE
    if not available():
        _RATE = 0.0
        return _RATE
    buf = b"\0" * (kib_per_slice * 1024)

    def one():
        h = MD5Device()
        for _ in range(slices):
            h.update(buf)
        h.digest()

    # a probe that breaks raises: the caller (md5fast's auto probe)
    # keeps the reason instead of reading a failure as "slow"
    one()                                        # compile + warm
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        one()
    dt = time.perf_counter() - t0
    _RATE = reps * slices * len(buf) / dt / 2**30
    return _RATE
