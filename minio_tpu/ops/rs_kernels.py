"""TPU Reed-Solomon kernels: GF(2^8) coding as MXU matmuls.

TPU-first reformulation of the reference hot path (cmd/erasure-coding.go
EncodeData/DecodeDataBlocks, backed there by AVX2 assembly in
klauspost/reedsolomon):

GF(2^8) multiplication by a constant is linear over GF(2), so every
coefficient expands to an 8x8 bit matrix (gf8.gf2_expand).  A stripe of k
shards x n bytes unpacks to (8k, n) bits, and encode/decode becomes

    out_bits = M2 @ data_bits   (mod 2),   M2 in {0,1}^(8r x 8k)

i.e. an int8 matmul on the MXU followed by ``& 1``.  XOR-accumulation is
recovered from integer accumulation by parity (sum mod 2 == XOR for bits).
The same kernel serves encode (M2 = expanded parity rows) and decode
(M2 = expanded rows of the inverted survivor submatrix), so one compiled
executable per shape handles every missing-shard pattern -- no dynamic
shapes under jit.

Batching: stripes are batched on a leading axis so large objects are one
device dispatch, keeping the MXU fed (SURVEY.md section 7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as _trace
from . import device, gf8

_LANES = 128    # TPU lane width; byte axis is padded to a lane multiple
_MAX_BATCH = 64  # stripes per dispatch; batch axis is bucketed to powers of 2


@jax.jit
def _gf2_apply(matrix_bits: jax.Array, data: jax.Array) -> jax.Array:
    """Apply an expanded GF(2) matrix to batched byte shards.

    matrix_bits: (R, 8k) int8 with R = 8*out_shards
    data:        (B, k, n) uint8
    returns      (B, R//8, n) uint8
    """
    B, k, n = data.shape
    R = matrix_bits.shape[0]
    shifts = jnp.arange(8, dtype=jnp.uint8)
    # unpack LSB-first: (B, k, 8, n) -> (B, 8k, n)
    bits = ((data[:, :, None, :] >> shifts[None, None, :, None]) & 1)
    bits = bits.reshape(B, 8 * k, n).astype(jnp.int8)
    # (R, 8k) @ (B, 8k, n) -> (R, B, n) on the MXU, int32 accumulation
    acc = jax.lax.dot_general(
        matrix_bits, bits,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    par = (acc & 1).astype(jnp.uint8)              # parity == XOR reduction
    par = par.reshape(R // 8, 8, B, n)
    weights = (jnp.uint8(1) << shifts)[None, :, None, None]
    packed = (par * weights).sum(axis=1, dtype=jnp.uint8)  # (R//8, B, n)
    return packed.transpose(1, 0, 2)


@functools.lru_cache(maxsize=256)
def _device_matrix(key: bytes, rows: int, cols: int) -> jax.Array:
    """Expanded coefficient matrix, cached on device by content.

    Bounded: decode matrices vary per survivor pattern (C(n,k) of them), so
    an unbounded cache would pin device buffers forever on a healing server.
    """
    M = np.frombuffer(key, dtype=np.uint8).reshape(rows, cols)
    return jnp.asarray(gf8.gf2_expand(M), dtype=jnp.int8)


def _put_matrix(M: np.ndarray) -> jax.Array:
    M = np.ascontiguousarray(M, dtype=np.uint8)
    return _device_matrix(M.tobytes(), M.shape[0], M.shape[1])


def apply_matrix(M: np.ndarray, shards: np.ndarray | jax.Array,
                 op: str = "decode") -> np.ndarray:
    """out[b] = M (GF) @ shards[b] for a batch of stripes.

    M: (r, k) uint8 GF coefficients;  shards: (B, k, n) uint8.
    Returns (B, r, n) uint8 (numpy, host).

    ``op`` names the dispatch's legs (obs/trace.py): ``<op>.prep`` the
    host padding, ``<op>.upload`` each chunk handed to JAX,
    ``<op>.launch`` the Python dispatch of its program(s) until the
    handle is held, ``<op>.fetch`` the wait for a result and its copy
    down.  Every chunk is still dispatched before any result is pulled.
    """
    squeeze = getattr(shards, "ndim", 3) == 2
    if squeeze:
        shards = shards[None]
    # fused pallas kernel on a TPU (bit planes never touch HBM), the
    # XLA formulation elsewhere — ops/device.py decides
    pallas = device.use_pallas()
    if pallas:
        from . import rs_pallas
    else:
        mb = _put_matrix(M)
    on_device = isinstance(shards, jax.Array)
    if not on_device:
        shards = np.asarray(shards, dtype=np.uint8)
    B, k, n = shards.shape
    # Bucket both variable axes so the jit cache stays small and tiles stay
    # full: byte axis padded to a lane multiple, batch axis chunked to
    # _MAX_BATCH and padded to the next power of two.  Device-resident
    # input stays on device (no host round trip); all chunks are
    # dispatched before any result is pulled back, so XLA overlaps MXU
    # work with D2H transfer.  Both properties hold for the pallas and
    # XLA kernels alike.
    xp = jnp if on_device else np
    pad_n = (-n) % _LANES
    if pad_n:
        with _trace.span("tpu", op + ".prep", nbytes=shards.nbytes):
            shards = xp.pad(shards, ((0, 0), (0, 0), (0, pad_n)))
    handles = []
    for off in range(0, B, _MAX_BATCH):
        chunk = shards[off: off + _MAX_BATCH]
        b = chunk.shape[0]
        bb = 1 << (b - 1).bit_length()  # next power of two
        if bb != b:
            with _trace.span("tpu", op + ".prep", nbytes=chunk.nbytes):
                chunk = xp.pad(chunk, ((0, bb - b), (0, 0), (0, 0)))
        chunk = device.upload(op, chunk)
        with _trace.span("tpu", op + ".launch", nbytes=chunk.nbytes):
            out = rs_pallas.apply_matrix(M, chunk) if pallas \
                else _gf2_apply(mb, chunk)
        handles.append((out, b))
    chunks = [device.fetch(op, out, rows=b) for out, b in handles]
    res = chunks[0] if len(chunks) == 1 else np.concatenate(chunks)
    if pad_n:
        res = res[..., :n]
    return res[0] if squeeze else res


def encode_parity(data_shards: np.ndarray, parity: int,
                  matrix: np.ndarray | None = None) -> np.ndarray:
    """(B, k, n) or (k, n) data -> (B, m, n) / (m, n) parity on TPU."""
    squeeze = data_shards.ndim == 2
    if squeeze:
        data_shards = data_shards[None]
    k = data_shards.shape[1]
    if matrix is None:
        matrix = gf8.rs_matrix(k, k + parity)
    out = apply_matrix(np.asarray(matrix)[k:], data_shards, op="encode")
    return out[0] if squeeze else out


def reconstruct(shards: list[np.ndarray | None], data_blocks: int,
                parity_blocks: int, data_only: bool = False,
                matrix: np.ndarray | None = None,
                apply=None) -> list[np.ndarray]:
    """TPU-backed equivalent of gf8_ref.reconstruct (one stripe).

    ``apply`` swaps the matmul engine — rs_mesh passes its sharded
    distributed_apply so the same survivor/solve logic serves both the
    single-chip and the mesh backend."""
    if apply is None:
        apply = apply_matrix
    total = data_blocks + parity_blocks
    if len(shards) != total:
        raise ValueError("wrong shard count")
    present = [i for i, s in enumerate(shards)
               if s is not None and len(s) > 0]
    if len(present) < data_blocks:
        from .gf8_ref import ReconstructError
        raise ReconstructError(
            f"need {data_blocks} shards, have {len(present)}")
    if matrix is None:
        matrix = gf8.rs_matrix(data_blocks, total)
    limit = total if not data_only else data_blocks
    missing = [i for i in range(limit)
               if shards[i] is None or len(shards[i]) == 0]
    out = list(shards)
    if not missing:
        return out
    use = present[:data_blocks]
    rows = gf8.decode_rows(matrix, data_blocks, use, missing)
    stack = np.stack([np.asarray(shards[i], dtype=np.uint8) for i in use])
    rebuilt = apply(rows, stack[None])[0]
    for j, i in enumerate(missing):
        out[i] = np.asarray(rebuilt[j], dtype=np.uint8)
    return out


def reconstruct_batch(shards: np.ndarray, present: list[int],
                      wanted: list[int], data_blocks: int,
                      parity_blocks: int,
                      matrix: np.ndarray | None = None) -> np.ndarray:
    """Batched reconstruction: same missing pattern across B stripes.

    shards: (B, k, n) -- the k surviving shards (rows ordered by ``present``).
    Returns (B, len(wanted), n).
    """
    if matrix is None:
        matrix = gf8.rs_matrix(data_blocks, data_blocks + parity_blocks)
    rows = gf8.decode_rows(matrix, data_blocks, list(present),
                           list(wanted))
    return apply_matrix(rows, shards)
