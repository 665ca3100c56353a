"""Device-side HighwayHash-256 — bitrot verification fused on TPU.

The reference verifies every shard block with keyed HighwayHash256
(cmd/bitrot.go:30-57, AVX2 assembly in minio/highwayhash).  Here the
same hash runs ON the TPU so a batch of shard blocks can be encoded and
integrity-hashed in one device pipeline with no host round trip
(BASELINE.json config 5: "bitrot HighwayHash fused on-device").

TPU-first formulation: TPUs have no 64-bit integer units, so every u64
of HighwayHash state is a (hi, lo) uint32 pair and the 32x32->64
products are built from 16-bit partial products — the same limb trick
the reference's NEON port uses for lanes without 64-bit multiplies.
The packet loop is a lax.scan (sequential by construction: each packet
permutes the whole state); throughput comes from batching B independent
blocks per scan step, each carrying 4 hash lanes on the VPU.

Bit-identical to minio_tpu.hashing.highwayhash (and therefore to the
reference) — conformance-tested against the native C implementation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..hashing.highwayhash import MAGIC_KEY
from . import device  # noqa: F401 — compile cache set before the first jit

_U32 = jnp.uint32
_MASK16 = np.uint32(0xFFFF)

_INIT_MUL0 = np.array(
    [0xDBE6D5D5FE4CCE2F, 0xA4093822299F31D0,
     0x13198A2E03707344, 0x243F6A8885A308D3], dtype=np.uint64)
_INIT_MUL1 = np.array(
    [0x3BD39E10CB0EF593, 0xC0ACF169B5F18A8C,
     0xBE5466CF34E90C6C, 0x452821E638D01377], dtype=np.uint64)


def _split(x64: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    return ((x64 >> np.uint64(32)).astype(np.uint32),
            (x64 & np.uint64(0xFFFFFFFF)).astype(np.uint32))


# -- u64-as-pair primitives (hi, lo are uint32 arrays) ----------------------

def _add64(ah, al, bh, bl):
    lo = al + bl
    carry = (lo < al).astype(_U32)
    return ah + bh + carry, lo


def _mul32(a, b):
    """Full 32x32 -> 64 product of uint32 arrays as (hi, lo)."""
    a0 = a & _MASK16
    a1 = a >> 16
    b0 = b & _MASK16
    b1 = b >> 16
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> 16) + (p01 & _MASK16) + (p10 & _MASK16)
    lo = (p00 & _MASK16) | (mid << 16)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    return hi, lo


def _shl64(h, l, s: int):
    if s == 0:
        return h, l
    if s >= 32:
        return (l << (s - 32)) if s > 32 else l, jnp.zeros_like(l)
    return (h << s) | (l >> (32 - s)), l << s


def _shr64(h, l, s: int):
    if s == 0:
        return h, l
    if s >= 32:
        return jnp.zeros_like(h), (h >> (s - 32)) if s > 32 else h
    return h >> s, (l >> s) | (h << (32 - s))


def _and64(h, l, c: int):
    ch = np.uint32(c >> 32)
    cl = np.uint32(c & 0xFFFFFFFF)
    return h & ch, l & cl


def _or3(*pairs):
    h = pairs[0][0]
    l = pairs[0][1]
    for ph, pl in pairs[1:]:
        h = h | ph
        l = l | pl
    return h, l


def _zipper(v1h, v1l, v0h, v0l):
    """ZipperMerge (highwayhash update permutation) on u64 pairs;
    returns (add1, add0) pairs.  Direct transcription of the reference
    mask/shift formulation (hashing/highwayhash.py _zipper)."""
    add0 = _or3(
        _shr64(*_or3(_and64(v0h, v0l, 0xFF000000),
                     _and64(v1h, v1l, 0xFF00000000)), 24),
        _shr64(*_or3(_and64(v0h, v0l, 0xFF0000000000),
                     _and64(v1h, v1l, 0xFF000000000000)), 16),
        _and64(v0h, v0l, 0xFF0000),
        _shl64(*_and64(v0h, v0l, 0xFF00), 32),
        _shr64(*_and64(v1h, v1l, 0xFF00000000000000), 8),
        _shl64(v0h, v0l, 56),
    )
    add1 = _or3(
        _shr64(*_or3(_and64(v1h, v1l, 0xFF000000),
                     _and64(v0h, v0l, 0xFF00000000)), 24),
        _and64(v1h, v1l, 0xFF0000),
        _shr64(*_and64(v1h, v1l, 0xFF0000000000), 16),
        _shl64(*_and64(v1h, v1l, 0xFF00), 24),
        _shr64(*_and64(v0h, v0l, 0xFF000000000000), 8),
        _shl64(*_and64(v1h, v1l, 0xFF), 48),
        _and64(v0h, v0l, 0xFF00000000000000),
    )
    return add1, add0


def _update(state, lanes_h, lanes_l):
    """One packet update; state arrays have shape (..., 4)."""
    v0h, v0l, v1h, v1l, m0h, m0l, m1h, m1l = state
    v1h, v1l = _add64(v1h, v1l, *_add64(m0h, m0l, lanes_h, lanes_l))
    ph, pl = _mul32(v1l, v0h)
    m0h, m0l = m0h ^ ph, m0l ^ pl
    v0h, v0l = _add64(v0h, v0l, m1h, m1l)
    ph, pl = _mul32(v0l, v1h)
    m1h, m1l = m1h ^ ph, m1l ^ pl

    def zip_into(vh, vl, sh, sl):
        """v0 += zipper(v1) on lane pairs (1,0) and (3,2)."""
        (a1h, a1l), (a0h, a0l) = _zipper(
            sh[..., 1::2], sl[..., 1::2], sh[..., 0::2], sl[..., 0::2])
        oh, ol = _add64(
            vh, vl,
            jnp.stack([a0h, a1h], axis=-1).reshape(vh.shape),
            jnp.stack([a0l, a1l], axis=-1).reshape(vl.shape))
        return oh, ol

    v0h, v0l = zip_into(v0h, v0l, v1h, v1l)
    v1h, v1l = zip_into(v1h, v1l, v0h, v0l)
    return (v0h, v0l, v1h, v1l, m0h, m0l, m1h, m1l)


def _rot32(h, l):
    """(x >> 32) | (x << 32): swap halves."""
    return l, h


def _permute_update(state):
    v0h, v0l = state[0], state[1]
    # lanes (2,3,0,1) with 32-bit halves swapped
    perm = (2, 3, 0, 1)
    lh = v0l[..., perm]          # swapped: hi <- lo
    ll = v0h[..., perm]
    return _update(state, lh, ll)


def _init_state_np(key: bytes) -> tuple[np.ndarray, ...]:
    """Initial (hi, lo) state limbs, computed host-side: JAX has no
    uint64 without x64 mode, so 64-bit init math stays in numpy."""
    k = np.frombuffer(key, dtype="<u8")
    krot = (k >> np.uint64(32)) | (k << np.uint64(32))
    m0h, m0l = _split(_INIT_MUL0)
    m1h, m1l = _split(_INIT_MUL1)
    v0h, v0l = _split(_INIT_MUL0 ^ k)
    v1h, v1l = _split(_INIT_MUL1 ^ krot)
    return v0h, v0l, v1h, v1l, m0h, m0l, m1h, m1l


def _rotl32(x, s: int):
    return (x << s) | (x >> (32 - s))


def _remainder_update(state, tail, rem: int):
    """Final partial packet (update_remainder, hashing/highwayhash.py):
    `tail` is (B, rem) uint8, rem in 1..31 — static, so the packet
    construction is all fixed indexing."""
    v0h, v0l, v1h, v1l, m0h, m0l, m1h, m1l = state
    B = tail.shape[0]
    # v0 += (size << 32) + size
    v0h, v0l = _add64(v0h, v0l, jnp.full_like(v0h, np.uint32(rem)),
                      jnp.full_like(v0l, np.uint32(rem)))
    # rotate each 32-bit half of v1 left by size
    v1h = _rotl32(v1h, rem)
    v1l = _rotl32(v1l, rem)
    size_mod4 = rem & 3
    rem_off = rem & ~3
    packet = jnp.zeros((B, 32), jnp.uint8)
    if rem_off:
        packet = packet.at[:, :rem_off].set(tail[:, :rem_off])
    if rem & 16:
        packet = packet.at[:, 28:32].set(
            tail[:, rem_off + size_mod4 - 4:rem_off + size_mod4])
    elif size_mod4:
        packet = packet.at[:, 16].set(tail[:, rem_off])
        packet = packet.at[:, 17].set(tail[:, rem_off + (size_mod4 >> 1)])
        packet = packet.at[:, 18].set(tail[:, rem_off + size_mod4 - 1])
    words = jax.lax.bitcast_convert_type(
        packet.reshape(B, 8, 4), jnp.uint32).reshape(B, 8)
    lh = words[:, 1::2]
    ll = words[:, 0::2]
    return _update((v0h, v0l, v1h, v1l, m0h, m0l, m1h, m1l), lh, ll)


@functools.partial(jax.jit, static_argnames=("rem",))
def _hh256_scan(packets_h, packets_l, init, tail=None, rem=0):
    """packets_[hl]: (P, B, 4) uint32 — P sequential packets over B
    independent blocks; init: 8 x (4,) uint32 state limbs; tail: (B,
    rem) uint8 final partial packet shared-length across the batch.
    Returns (B, 8) uint32 (the 256-bit digests as LE words)."""
    B = packets_h.shape[1]
    state = tuple(jnp.broadcast_to(jnp.asarray(a, _U32), (B, 4))
                  for a in init)

    def step(st, xs):
        lh, ll = xs
        return _update(st, lh, ll), None

    state, _ = jax.lax.scan(step, state, (packets_h, packets_l))
    if rem:
        state = _remainder_update(state, tail, rem)
    for _ in range(10):
        state = _permute_update(state)
    v0h, v0l, v1h, v1l, m0h, m0l, m1h, m1l = state

    def modred(a3h, a3l, a2h, a2l, a1h, a1l, a0h, a0l):
        a3h = a3h & np.uint32(0x3FFFFFFF)
        m1h_, m1l_ = a1h, a1l
        for s in (1, 2):
            # ((a3 << s) | (a2 >> (64 - s))): the a2 spill feeds only
            # the low bits of the low word
            th, tl = _shl64(a3h, a3l, s)
            tl = tl | (a2h >> (32 - s))
            m1h_, m1l_ = m1h_ ^ th, m1l_ ^ tl
        m0h_, m0l_ = a0h, a0l
        for s in (1, 2):
            th, tl = _shl64(a2h, a2l, s)
            m0h_, m0l_ = m0h_ ^ th, m0l_ ^ tl
        return m0h_, m0l_, m1h_, m1l_

    s10h, s10l = _add64(v0h, v0l, m0h, m0l)       # v0 + mul0 per lane
    s32h, s32l = _add64(v1h, v1l, m1h, m1l)       # v1 + mul1 per lane
    h0h, h0l, h1h, h1l = modred(
        s32h[..., 1], s32l[..., 1], s32h[..., 0], s32l[..., 0],
        s10h[..., 1], s10l[..., 1], s10h[..., 0], s10l[..., 0])
    h2h, h2l, h3h, h3l = modred(
        s32h[..., 3], s32l[..., 3], s32h[..., 2], s32l[..., 2],
        s10h[..., 3], s10l[..., 3], s10h[..., 2], s10l[..., 2])
    # LE u64 words -> (B, 8) uint32 little-endian word order
    return jnp.stack([h0l, h0h, h1l, h1h, h2l, h2h, h3l, h3h], axis=-1)


def hashed_rows(B: int, n: int) -> int:
    """Rows the program hashes for (B, n) blocks: the scan carries B
    rows and pads none (hh_pallas.hashed_rows is the kernel's)."""
    return B


def hh256_batch(blocks, key: bytes = MAGIC_KEY):
    """HighwayHash-256 of B equal-sized blocks on device.

    blocks: (B, n) uint8 array (device or host), any uniform n — the
    final partial packet follows the reference's remainder rules, so
    real (non-32-aligned) shard sizes hash bit-identically.  Returns
    (B, 32) uint8 digests.
    """
    blocks = jnp.asarray(blocks, jnp.uint8)
    B, n = blocks.shape
    P, rem = n // 32, n % 32
    # (B, P, 32) bytes -> u32 lanes -> (P, B, 4) hi/lo
    words = jax.lax.bitcast_convert_type(
        blocks[:, :P * 32].reshape(B, P, 8, 4),
        jnp.uint32)                                # LE per 4 bytes
    words = words.reshape(B, P, 8)
    lo = words[..., 0::2].transpose(1, 0, 2)      # (P, B, 4)
    hi = words[..., 1::2].transpose(1, 0, 2)
    tail = blocks[:, P * 32:] if rem else None
    out = _hh256_scan(hi.astype(_U32), lo.astype(_U32),
                      _init_state_np(key), tail, rem)
    return jax.lax.bitcast_convert_type(
        out, jnp.uint8).reshape(B, 32)


def modred_reference(a3, a2, a1, a0):  # pragma: no cover - doc helper
    """The 256-bit modular reduction being mirrored (hashing/
    highwayhash.py finalize256) — kept for cross-reading."""
    M64 = (1 << 64) - 1
    a3 &= 0x3FFFFFFFFFFFFFFF
    m1 = a1 ^ (((a3 << 1) | (a2 >> 63)) & M64) ^ \
        (((a3 << 2) | (a2 >> 62)) & M64)
    m0 = a0 ^ ((a2 << 1) & M64) ^ ((a2 << 2) & M64)
    return m0, m1
