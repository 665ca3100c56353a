"""The plain reference of the erasure code, kept with the benchmark.

Reed-Solomon over GF(2^8) as MinIO's klauspost/reedsolomon dependency
defines it (cmd/erasure-coding.go): field polynomial 0x11d, generator 2,
a Vandermonde matrix made systematic by its top square's inverse; parity
is the bottom m rows applied to the k data shards, and any k shards give
all k+m back through the inverse of their rows.  Straight numpy table
lookups: no kernels, no native code, nothing shared with the program
(``minio_tpu/ops/gf8_ref.py`` is the program's own copy of the same
mathematics and is not imported here).
"""

from __future__ import annotations

import functools

import numpy as np

_POLY = 0x11D


def _tables():
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    exp[255:510] = exp[:255]
    mul = np.zeros((256, 256), dtype=np.uint8)
    nz = np.arange(1, 256)
    for a in range(1, 256):
        mul[a, 1:] = exp[log[a] + log[nz]]
    return exp, log, mul


EXP, LOG, MUL = _tables()


def _pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(int(LOG[a]) * n) % 255])


def _matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(a.shape[1]):
            if a[i, j]:
                out[i] ^= MUL[a[i, j]][b[j]]
    return out


def _invert(m: np.ndarray) -> np.ndarray:
    n = m.shape[0]
    aug = np.concatenate([m.copy(), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        piv = next(r for r in range(col, n) if aug[r, col])
        if piv != col:
            aug[[col, piv]] = aug[[piv, col]]
        inv = int(EXP[255 - LOG[aug[col, col]]])
        aug[col] = MUL[inv][aug[col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= MUL[aug[r, col]][aug[col]]
    return aug[:, n:]


@functools.lru_cache(maxsize=None)
def rs_matrix(k: int, total: int) -> np.ndarray:
    vm = np.array([[_pow(r, c) for c in range(k)] for r in range(total)],
                  dtype=np.uint8)
    return _matmul(vm, _invert(vm[:k]))


def encode_parity(data: np.ndarray, m: int) -> np.ndarray:
    """(k, n) data shards -> (m, n) parity shards."""
    k = data.shape[0]
    return _matmul(rs_matrix(k, k + m)[k:], data)


def decode(rows: np.ndarray, present, k: int, m: int) -> np.ndarray:
    """(k, n) shards at the k distinct indices ``present`` of k+m -> all
    (k+m, n) shards: the data through the inverse of the matrix's present
    rows, the parity encoded again from that data."""
    present = list(present)
    if len(present) != k or len(set(present)) != k \
            or not all(0 <= i < k + m for i in present):
        raise ValueError(f"decode needs {k} distinct indices of {k + m}, "
                         f"got {present}")
    matrix = rs_matrix(k, k + m)
    return _matmul(_matmul(matrix, _invert(matrix[present])), rows)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)
