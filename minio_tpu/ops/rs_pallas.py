"""Fused GF(2^8) Reed-Solomon coding as ONE Pallas TPU kernel.

The XLA formulation (rs_kernels._gf2_apply) materialises the GF(2) bit
planes in HBM: (B, k, n) bytes inflate to (B, 8k, n) int8 on the way in
and (8r, B, n) int32 on the way out — an 8x HBM traffic tax that leaves
the kernel HBM-bound at ~5% of chip roofline (BENCH_r02).

This kernel keeps bit planes VMEM-resident for their whole life:

    bytes in  --unpack-->  bit planes  --MXU matmul-->  parity bits
                                 --pack-->  parity bytes out

HBM sees only the byte tiles: k*TN in, r*TN out per grid step — the
information-theoretic minimum for the operation.

Layout trick: the expanded GF(2) matrix's rows/cols are permuted to
BIT-MAJOR order (plane b of shard s at row b*shards+s, vs gf2_expand's
shard-major s*8+b).  Bit-major makes the in-kernel unpack a plain
concatenate of 8 shifted copies along sublanes and the pack 8 static
sublane slices — both natively supported Mosaic ops — where shard-major
would need an 8-way interleave the hardware has no vector op for.

Same kernel serves encode (parity rows) and decode (inverted survivor
rows) exactly like rs_kernels; reference semantics per
cmd/erasure-coding.go:56-143 (klauspost/reedsolomon AVX2 hot loop).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import device, gf8

_LANES = 128
# lanes per grid step: large enough that the (8r, 8k) @ (8k, TN) matmul
# amortises grid/DMA overhead, small enough that lane padding on the
# 87382-byte headline shard size stays under ~5%
_TN = 4096
# stripes per grid step, packed block-diagonally into one matmul: a lone
# (32, 96) matrix wastes the 128x128 MXU tile on padding (32->128 rows,
# 96->128 contraction).  diag(E, E, E, E) is (128, 384): M fully used,
# K = 3 exact passes — 4/3 the slot efficiency, measured MXU-bound
_GS = 4


def expand_bitmajor(M: np.ndarray) -> np.ndarray:
    """GF(2^8) coefficient matrix (r, k) -> GF(2) matrix (8r, 8k) with
    BIT-MAJOR row/col order: row b*r+i computes bit b of out shard i from
    col planes b'*k+j (bit b' of in shard j)."""
    M = np.ascontiguousarray(M, dtype=np.uint8)
    r, k = M.shape
    E = gf8.gf2_expand(M)                      # (8r, 8k) shard-major
    return np.ascontiguousarray(
        E.reshape(r, 8, k, 8).transpose(1, 0, 3, 2).reshape(8 * r, 8 * k))


def _kernel(m_ref, in_ref, out_ref, *, k: int, ro: int, gs: int):
    """One (stripe-group, lane-tile) grid step, everything VMEM-resident.

    m_ref:  (gs*8*ro, gs*8*k) int8 block-diagonal bit-major matrix
    in_ref: (gs, k, TN) uint8 data shards for gs stripes
    out_ref:(gs, ro, TN) uint8 output shards
    """
    planes = []
    for s in range(gs):
        x = in_ref[s].astype(jnp.int32)        # (k, TN)
        # unpack LSB-first into bit-major planes: rows s*8k + b*k + j.
        # No & 1 mask: (x >> b) carries bits b..7 in positions 0..7-b,
        # but every bit above position 0 contributes an EVEN multiple to
        # the matmul accumulator, so the final `acc & 1` parity is
        # unaffected (and the int8 wrap subtracts multiples of 256 —
        # also even).  Halves the VPU unpack work.
        planes.extend(x >> b for b in range(8))
    bits = jnp.concatenate(planes, axis=0).astype(jnp.int8)
    acc = jax.lax.dot_general(                 # (gs*8*ro, TN) on MXU
        m_ref[:], bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    acc = acc & 1                              # parity == XOR over GF(2)
    for s in range(gs):
        base = s * 8 * ro
        out = acc[base:base + ro]
        for b in range(1, 8):
            out = out | (acc[base + b * ro:base + (b + 1) * ro] << b)
        out_ref[s] = out.astype(jnp.uint8)


@device.named_jit("mt_rs_gf2", static_argnames=("gs", "tn"))
def _gf2_apply_bm(matrix_bd: jax.Array, data: jax.Array,
                  gs: int = _GS, tn: int = _TN) -> jax.Array:
    """matrix_bd: (gs*8r, gs*8k) int8 block-diagonal bit-major; data:
    (B, k, n) uint8 with B a multiple of gs and n a multiple of tn
    (caller pads both).  Returns (B, r, n) uint8."""
    B, k, n = data.shape
    ro = matrix_bd.shape[0] // (8 * gs)
    kernel = functools.partial(_kernel, k=k, ro=ro, gs=gs)
    return pl.pallas_call(
        kernel,
        grid=(B // gs, n // tn),
        in_specs=[
            pl.BlockSpec((gs * 8 * ro, gs * 8 * k), lambda i, j: (0, 0)),
            pl.BlockSpec((gs, k, tn), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((gs, ro, tn), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, ro, n), jnp.uint8),
        interpret=device.interpret(),
        name="mt_rs_gf2",
    )(matrix_bd, data)


@functools.lru_cache(maxsize=256)
def _device_matrix_bd(key: bytes, rows: int, cols: int,
                      gs: int) -> jax.Array:
    """Block-diagonal bit-major expanded matrix, cached on device by
    content (bounded for the same reason as rs_kernels._device_matrix:
    decode matrices vary per survivor pattern)."""
    M = np.frombuffer(key, dtype=np.uint8).reshape(rows, cols)
    E = expand_bitmajor(M)
    R, K = E.shape
    bd = np.zeros((gs * R, gs * K), dtype=np.int8)
    for s in range(gs):
        bd[s * R:(s + 1) * R, s * K:(s + 1) * K] = E
    return jnp.asarray(bd)


def lane_tile(n: int) -> int:
    """Lane tile for shards of n bytes: bucketed to ~n/4 so padding
    waste stays under ~25% at every shard width (a 5462-byte shard must
    not pad 50% to 8192, nor a 300-byte one 13x to 4096), capped at _TN
    for real widths."""
    q = max(n // 4, 1)
    tn = _LANES
    while tn * 2 <= q and tn < _TN:
        tn *= 2
    return tn


def apply_matrix(M: np.ndarray, shards) -> jax.Array:
    """out[b] = M (GF) @ shards[b], fused pallas path.

    M: (r, k) uint8 GF coefficients; shards: (B, k, n) uint8 (device or
    host).  Returns a DEVICE array (B, r, n) — callers chain further
    device work (hashing, mixing) without a host round trip; np.asarray
    the result to land it.
    """
    M = np.ascontiguousarray(M, dtype=np.uint8)
    shards = jnp.asarray(shards, jnp.uint8)
    B, k, n = shards.shape
    bpad = (-B) % _GS
    if bpad and B > 1:                 # group to keep the MXU tile full
        shards = jnp.pad(shards, ((0, bpad), (0, 0), (0, 0)))
    gs = _GS if shards.shape[0] % _GS == 0 else 1
    mb = _device_matrix_bd(M.tobytes(), M.shape[0], M.shape[1], gs)
    tn = lane_tile(n)
    pad = (-n) % tn
    if pad:
        shards = jnp.pad(shards, ((0, 0), (0, 0), (0, pad)))
    out = _gf2_apply_bm(mb, shards, gs=gs, tn=tn)
    if bpad and B > 1:
        out = out[:B]
    if pad:
        out = out[:, :, :n]
    return out
