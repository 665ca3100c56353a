"""Conformance tests for the fused Pallas RS kernel (ops/rs_pallas.py).

Runs the kernel in the pallas interpreter on CPU; bit-identical
agreement with the host reference codec (gf8_ref) and the XLA
formulation (rs_kernels) is the contract — the TPU path must produce
the same shards the drives already hold (cmd/erasure-coding.go:56).
"""

import numpy as np
import pytest

from minio_tpu.ops import gf8, rs_kernels, rs_pallas


def _rand(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=shape, dtype=np.uint8)


def test_bitmajor_expansion_equivalent():
    """Bit-major permuted matrix computes the same GF product."""
    M = np.asarray(gf8.rs_matrix(4, 6))[4:]          # (2, 4) parity rows
    E = gf8.gf2_expand(M)                            # shard-major
    Ebm = rs_pallas.expand_bitmajor(M)               # bit-major
    data = _rand((4, 16))
    # shard-major product
    bits = ((data[:, None, :] >> np.arange(8)[None, :, None]) & 1)
    bits_sm = bits.reshape(32, 16)
    out_sm = (E.astype(np.int32) @ bits_sm) & 1
    # bit-major product, rows b*k+j
    bits_bm = np.concatenate([(data >> b) & 1 for b in range(8)], axis=0)
    out_bm = (Ebm.astype(np.int32) @ bits_bm) & 1
    # repack both and compare
    sm = sum(out_sm.reshape(2, 8, 16)[:, b] << b for b in range(8))
    bm = sum(out_bm.reshape(8, 2, 16)[b] << b for b in range(8))
    np.testing.assert_array_equal(sm, bm)


@pytest.mark.parametrize("k,m", [(4, 2), (12, 4)])
def test_encode_matches_reference(k, m):
    data = _rand((3, k, 300), seed=k)
    M = np.asarray(gf8.rs_matrix(k, k + m))
    got = np.asarray(rs_pallas.apply_matrix(M[k:], data))
    want = np.stack([gf8.gf_matmul(M[k:], d) for d in data])
    np.testing.assert_array_equal(got, want)


def test_matches_xla_formulation():
    k, m = 12, 4
    data = _rand((2, k, 1000), seed=7)
    M = np.asarray(gf8.rs_matrix(k, k + m))
    got = np.asarray(rs_pallas.apply_matrix(M[k:], data))
    want = rs_kernels.apply_matrix(np.asarray(M[k:]), data)
    np.testing.assert_array_equal(got, np.asarray(want))


def test_decode_roundtrip():
    k, m = 12, 4
    M = np.asarray(gf8.rs_matrix(k, k + m))
    data = _rand((2, k, 200), seed=3)
    parity = np.asarray(rs_pallas.apply_matrix(M[k:], data))
    # lose shards 0 and 1; reconstruct from 2..13
    present = list(range(2, k + 2))
    rows = gf8.decode_rows(M, k, present, [0, 1])
    full = np.concatenate([data, parity], axis=1)
    survivors = full[:, present, :]
    rebuilt = np.asarray(
        rs_pallas.apply_matrix(rows, survivors))
    np.testing.assert_array_equal(rebuilt, full[:, :2, :])


def test_rs_kernels_dispatcher_pallas_branch(monkeypatch):
    """The production dispatcher (rs_kernels.apply_matrix) must produce
    identical results when routed through the pallas kernel — this is
    the default TPU path but the CPU suite otherwise never runs it."""
    monkeypatch.setenv("MT_PALLAS", "1")
    k, m = 12, 4
    M = np.asarray(gf8.rs_matrix(k, k + m))
    for B, n in [(1, 300), (2, 128), (70, 1000)]:   # chunking + padding
        data = _rand((B, k, n), seed=B)
        got = rs_kernels.apply_matrix(np.asarray(M[k:]), data)
        monkeypatch.setenv("MT_PALLAS", "0")
        want = rs_kernels.apply_matrix(np.asarray(M[k:]), data)
        monkeypatch.setenv("MT_PALLAS", "1")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # 2-D squeeze contract
    data2 = _rand((k, 257), seed=9)
    got2 = rs_kernels.apply_matrix(np.asarray(M[k:]), data2)
    want2 = gf8.gf_matmul(M[k:], data2)
    assert got2.shape == (m, 257)
    np.testing.assert_array_equal(np.asarray(got2), want2)


def test_lane_padding_roundtrip():
    """n not a multiple of the kernel tile is padded and cropped."""
    k, m = 4, 2
    M = np.asarray(gf8.rs_matrix(k, k + m))
    for n in (1, 127, 128, 129, 4097):
        data = _rand((1, k, n), seed=n)
        got = np.asarray(
            rs_pallas.apply_matrix(M[k:], data))
        want = gf8.gf_matmul(M[k:], data[0])
        np.testing.assert_array_equal(got[0], want)
