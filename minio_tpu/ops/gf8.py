"""GF(2^8) arithmetic core for the TPU-native Reed-Solomon erasure codec.

This is the host-side (numpy) foundation of the erasure-coding hot path.  The
reference implementation is MinIO's klauspost/reedsolomon dependency
(reference: cmd/erasure-coding.go:23,56) which itself ports the Backblaze
JavaReedSolomon field:

  * field GF(2^8) defined by the primitive polynomial x^8+x^4+x^3+x^2+1
    (0x11d), generator element 2,
  * systematic encode matrix built from a Vandermonde matrix made systematic
    by multiplying with the inverse of its top k x k square,
  * ``Split`` padding semantics (zero-pad the tail shard).

Everything here is pure numpy and bit-identical to the reference semantics;
the TPU kernels in rs_kernels.py consume the tables/matrices produced here.
"""

from __future__ import annotations

import functools

import numpy as np

FIELD_SIZE = 256
_POLY = 0x11D  # x^8 + x^4 + x^3 + x^2 + 1, primitive; matches Backblaze/klauspost


def _build_tables() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """exp/log tables for GF(2^8)/0x11d with generator 2.

    exp is doubled (510 entries) so exp[log[a]+log[b]] needs no modular
    reduction during multiply.
    """
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    for i in range(255, 512):
        exp[i] = exp[i - 255]
    log[0] = -255  # sentinel; callers must special-case zero
    # full 256x256 multiplication table (64KiB) -- handy for reference code
    a = np.arange(256)
    mul = np.zeros((256, 256), dtype=np.uint8)
    la = log[a]
    for i in range(1, 256):
        mul[i, 1:] = exp[(log[i] + la[1:])]
    return exp, log, mul


GF_EXP, GF_LOG, GF_MUL = _build_tables()

# inverse: a^-1 = exp[255 - log[a]]
GF_INV = np.zeros(256, dtype=np.uint8)
GF_INV[1:] = GF_EXP[255 - GF_LOG[np.arange(1, 256)]]


def gf_mul(a, b):
    """Elementwise GF(2^8) multiply of arrays/scalars (uint8)."""
    return GF_MUL[np.asarray(a, dtype=np.uint8), np.asarray(b, dtype=np.uint8)]


def gf_exp(a: int, n: int) -> int:
    """a**n in GF(2^8) (matches klauspost galExp)."""
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(GF_EXP[(int(GF_LOG[a]) * n) % 255])


def gf_matmul_numpy(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """Pure-numpy GF matmul — the conformance oracle the native and TPU
    paths are validated against (tables built in _build_tables above)."""
    A = np.asarray(A, dtype=np.uint8)
    B = np.asarray(B, dtype=np.uint8)
    r, k = A.shape
    k2, c = B.shape
    assert k == k2
    out = np.zeros((r, c), dtype=np.uint8)
    for i in range(k):  # k <= 256; columns vectorized
        prod = GF_MUL[A[:, i][:, None], B[i][None, :]]
        out ^= prod
    return out


def gf_matmul(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """GF matrix multiply: (r,k) x (k,c) -> (r,c), XOR-accumulated.

    Dispatches to the native AVX2 kernel (native/gf8.cc — the host
    equivalent of klauspost/reedsolomon's assembly) for real shard
    widths; numpy handles tiny inputs and environments without g++.
    ctypes releases the GIL inside the native call, so concurrent PUT
    threads scale."""
    B = np.asarray(B)
    if B.ndim == 2 and B.shape[1] >= 1024:
        from . import gf8_native
        if gf8_native.available():
            return gf8_native.matmul(A, B)
    return gf_matmul_numpy(A, B)


def gf_mat_inv(M: np.ndarray) -> np.ndarray:
    """Invert a square GF(2^8) matrix via Gauss-Jordan.

    Raises ValueError on singular input (mirrors reedsolomon's
    ErrSingular -> reconstruction failure).
    """
    M = np.asarray(M, dtype=np.uint8)
    n = M.shape[0]
    assert M.shape == (n, n)
    aug = np.concatenate([M.copy(), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        # partial pivot: find a row with nonzero pivot
        pivot = None
        for r in range(col, n):
            if aug[r, col] != 0:
                pivot = r
                break
        if pivot is None:
            raise ValueError("singular matrix")
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        inv_p = GF_INV[aug[col, col]]
        aug[col] = GF_MUL[np.full(2 * n, inv_p, dtype=np.uint8), aug[col]]
        for r in range(n):
            if r != col and aug[r, col] != 0:
                f = aug[r, col]
                aug[r] ^= GF_MUL[np.full(2 * n, f, dtype=np.uint8), aug[col]]
    return aug[:, n:].copy()


@functools.lru_cache(maxsize=None)
def _vandermonde(rows: int, cols: int) -> np.ndarray:
    vm = np.zeros((rows, cols), dtype=np.uint8)
    for r in range(rows):
        for c in range(cols):
            vm[r, c] = gf_exp(r, c)
    return vm


@functools.lru_cache(maxsize=None)
def rs_matrix(data_shards: int, total_shards: int) -> np.ndarray:
    """klauspost-compatible systematic encode matrix (total x data).

    vm = Vandermonde(total, data); M = vm @ inv(vm[:data,:data]).
    Top k rows are the identity; bottom m rows are the parity coefficients.
    Mirrors reedsolomon.buildMatrix (reference dep of cmd/erasure-coding.go:56).
    """
    vm = _vandermonde(total_shards, data_shards)
    top_inv = gf_mat_inv(vm[:data_shards, :data_shards])
    M = gf_matmul(vm, top_inv)
    M.setflags(write=False)
    return M


@functools.lru_cache(maxsize=None)
def cauchy_matrix(data_shards: int, total_shards: int) -> np.ndarray:
    """Cauchy-style systematic matrix (reedsolomon WithCauchyMatrix option)."""
    parity = total_shards - data_shards
    M = np.zeros((total_shards, data_shards), dtype=np.uint8)
    M[:data_shards] = np.eye(data_shards, dtype=np.uint8)
    for r in range(parity):
        for c in range(data_shards):
            # 1 / (x_r + y_c) with x_r = data+r, y_c = c
            M[data_shards + r, c] = GF_INV[(data_shards + r) ^ c]
    M.setflags(write=False)
    return M


# ---------------------------------------------------------------------------
# GF(2) bitplane expansion: the bridge from GF(2^8) coefficients to MXU matmuls
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _companion_cols() -> np.ndarray:
    """(256, 8, 8) lookup: companion bit-matrix for every GF coefficient.

    For coefficient c, B[c] is the 8x8 GF(2) matrix with
    out_bits = B[c] @ in_bits (mod 2), bits LSB-first:
    column j of B[c] = bits of (c * x^j) = bits of gf_mul(c, 1<<j).
    """
    out = np.zeros((256, 8, 8), dtype=np.uint8)
    for c in range(256):
        for j in range(8):
            v = int(GF_MUL[c, 1 << j])
            for i in range(8):
                out[c, i, j] = (v >> i) & 1
    return out


def gf2_expand(M: np.ndarray) -> np.ndarray:
    """Expand a GF(2^8) coefficient matrix (r,k) to its GF(2) form (8r,8k).

    parity_bits = expand(M) @ data_bits (mod 2) computes the same product as
    the GF(2^8) matrix-vector multiply -- this is what runs on the MXU.
    """
    M = np.asarray(M, dtype=np.uint8)
    r, k = M.shape
    comp = _companion_cols()[M]  # (r, k, 8, 8)
    return comp.transpose(0, 2, 1, 3).reshape(8 * r, 8 * k).copy()


# ---------------------------------------------------------------------------
# Shard-size math (bit-identical with cmd/erasure-coding.go:115-143)
# ---------------------------------------------------------------------------

def ceil_frac(numerator: int, denominator: int) -> int:
    """Bit-identical port of ceilFrac (cmd/utils.go:613-628).

    Go semantics: zero denominator returns 0; division truncates toward zero
    and only positive non-exact quotients are bumped up.
    """
    if denominator == 0:
        return 0
    if denominator < 0:
        numerator = -numerator
        denominator = -denominator
    ceil = abs(numerator) // denominator
    if numerator < 0:
        ceil = -ceil  # Go int division truncates toward zero
    if numerator > 0 and numerator % denominator != 0:
        ceil += 1
    return ceil


def shard_size(block_size: int, data_blocks: int) -> int:
    """cmd/erasure-coding.go:115 ShardSize."""
    return ceil_frac(block_size, data_blocks)


def shard_file_size(block_size: int, data_blocks: int, total_length: int) -> int:
    """cmd/erasure-coding.go:120 ShardFileSize."""
    if total_length == 0:
        return 0
    if total_length == -1:
        return -1
    num_shards = total_length // block_size
    last_block_size = total_length % block_size
    last_shard_size = ceil_frac(last_block_size, data_blocks)
    return num_shards * shard_size(block_size, data_blocks) + last_shard_size


def shard_file_offset(block_size: int, data_blocks: int, start_offset: int,
                      length: int, total_length: int) -> int:
    """cmd/erasure-coding.go:134 ShardFileOffset."""
    ssize = shard_size(block_size, data_blocks)
    sfsize = shard_file_size(block_size, data_blocks, total_length)
    end_shard = (start_offset + length) // block_size
    till_offset = end_shard * ssize + ssize
    if till_offset > sfsize:
        till_offset = sfsize
    return till_offset


def framed_layout(block_size: int, data_blocks: int, total: int,
                  digest: int = 32) -> tuple[int, int, int, int]:
    """The bitrot-framed shard file of a ``total``-byte batch: per full
    erasure block one ``[digest][shard block]`` frame, then one short
    frame for a tail block (cmd/bitrot-streaming.go framing around
    cmd/erasure-encode.go blocks).  Returns (full blocks, tail bytes,
    tail shard size, framed file length) — the one place the layout's
    arithmetic lives; every route that writes it asks here."""
    nfull, tail_len = divmod(total, block_size)
    tail_ss = ceil_frac(tail_len, data_blocks)
    flen = nfull * (digest + shard_size(block_size, data_blocks)) \
        + ((digest + tail_ss) if tail_len else 0)
    return nfull, tail_len, tail_ss, flen


def decode_rows(matrix: np.ndarray, data_blocks: int,
                present: list[int], wanted: list[int]) -> np.ndarray:
    """Host-side tiny GF solve: rows mapping k survivors -> wanted shards.

    present: indices (sorted) of the k shards used for reconstruction.
    wanted:  shard indices to produce (data or parity).
    Returns (len(wanted), k) GF coefficient rows to feed apply_matrix.
    """
    assert len(present) == data_blocks
    sub = np.asarray(matrix)[present]              # (k, k)
    dec = gf_mat_inv(sub)                          # survivors -> data
    rows = []
    for w in wanted:
        if w < data_blocks:
            rows.append(dec[w])
        else:
            # parity row composed with the decode: parity_w = M[w] @ data
            rows.append(gf_matmul(np.asarray(matrix)[w][None, :], dec)[0])
    return np.stack(rows).astype(np.uint8)


def split(data: bytes | bytearray | memoryview | np.ndarray,
          data_shards: int) -> np.ndarray:
    """reedsolomon Split semantics: k equal shards, zero-padded tail.

    Returns a (data_shards, per_shard) uint8 array (data shards only).
    Raises ValueError on empty input (reedsolomon.ErrShortData).
    """
    buf = np.frombuffer(bytes(data), dtype=np.uint8) if not isinstance(data, np.ndarray) \
        else data.astype(np.uint8, copy=False).ravel()
    if buf.size == 0:
        raise ValueError("short data")
    per_shard = ceil_frac(buf.size, data_shards)
    out = np.zeros(data_shards * per_shard, dtype=np.uint8)
    out[: buf.size] = buf
    return out.reshape(data_shards, per_shard)
