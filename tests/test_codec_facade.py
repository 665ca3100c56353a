"""The codec facade's two entries (ops/codec.py), every route of each:
``Erasure.encode_framed`` against gf8_ref parity + host HighwayHash
frames, ``Erasure.reconstruct_files`` against gf8_ref.reconstruct —
byte for byte on the host codec (native and with the native libraries
masked), the one-chip device form and a virtual mesh (both on XLA:CPU
here: tests/conftest.py).  These pin that moving the route switch out
of the object layer changed no shard file.
"""

import numpy as np
import pytest

from minio_tpu.admin import metrics as _metrics
from minio_tpu.hashing import bitrot, highwayhash
from minio_tpu.ops import gf8, gf8_native, gf8_ref
from minio_tpu.ops.codec import Erasure

from . import shard_files

K, M = 4, 2
BS = 4096                   # shard size 1024
BS_RAGGED = 4099            # not divisible by k: per-block zero padding

BACKENDS = ["numpy", "numpy-masked", "tpu", "mesh"]


@pytest.fixture
def codec_for(monkeypatch):
    """Erasure(K, M, bs) on a named route.  ``numpy-masked`` hides both
    native libraries, so the host codec takes the copying route."""
    prev = []

    def make(backend: str, bs: int) -> Erasure:
        if backend == "numpy-masked":
            monkeypatch.setattr(gf8_native, "available", lambda: False)
            monkeypatch.setattr(highwayhash, "_get_lib", lambda: None)
            backend = "numpy"
        elif backend == "numpy" and not (
                gf8_native.available()
                and highwayhash._get_lib() is not None):
            pytest.skip("native gf8 / highwayhash unavailable")
        elif backend == "mesh":
            from minio_tpu.parallel import mesh as pmesh
            prev.append((pmesh, pmesh._ACTIVE))
            pmesh.set_active_mesh(pmesh.make_mesh(stripe=2))
        return Erasure(K, M, bs, backend=backend)

    yield make
    for pmesh, active in prev:
        pmesh.set_active_mesh(active)


def _body(n: int, seed: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _reference_stripes(data: bytes, bs: int) -> list[np.ndarray]:
    return shard_files.reference_stripes(data, bs, K, M)


def _reference_framed(data: bytes, bs: int) -> list[bytes]:
    """Built before any native library is masked."""
    return shard_files.reference_framed(data, bs, K, M)


@pytest.mark.parametrize("recycled", [False, True],
                         ids=["fresh", "dirty-out"])
@pytest.mark.parametrize("bs,total", [
    (BS, 777),                      # sub-block: one short frame
    (BS, 2 * BS),                   # whole blocks
    (BS, 2 * BS + 777),             # blocks + tail
    (BS_RAGGED, 2 * BS_RAGGED + 5),  # block size not divisible by k
], ids=["sub-block", "whole-blocks", "blocks+tail", "ragged-bs"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_encode_framed_bit_identical(codec_for, backend, bs, total,
                                     recycled):
    data = _body(total, total)
    want = _reference_framed(data, bs)
    codec = codec_for(backend, bs)
    algo = bitrot.HIGHWAYHASH256S
    shape = codec.framed_shape(total, algo)
    # only the host one-copy route fills a caller's buffer
    assert (shape is not None) == (backend == "numpy")
    *_, flen = gf8.framed_layout(bs, K, total)
    assert shape in (None, (K + M, flen))
    out = np.full((K + M, flen), 0xA5, dtype=np.uint8) \
        if recycled else None
    rows = codec.encode_framed(data, algo, out=out)
    assert len(rows) == K + M
    for i, row in enumerate(rows):
        assert bytes(row) == want[i], f"{backend}: shard {i}"
    if recycled and shape is not None:
        assert all(np.shares_memory(r, out) for r in rows)


@pytest.mark.parametrize("total,hashes", [
    (2 * BS + 777, 1),              # blocks fused, the tail's bitrot leg
    (2 * BS, 0),                    # whole blocks: the fused route alone
    (777, 1),                       # under a block: the two-dispatch route
], ids=["blocks+tail", "whole-blocks", "sub-block"])
def test_encode_framed_routes_are_counted_apart(codec_for, total, hashes):
    """What the benchmark reads: one ``op=encode`` dispatch per batch
    on the device route with the body's bytes; ``op=hash`` beside it
    only where a tail block takes the device bitrot leg (full blocks
    get their digests off the encode's own fused dispatch); the host
    one-copy route counts ``encode-framed`` and no hash."""
    def ops(backend):
        return {op: _metrics.GLOBAL.snapshot().get(
            ("mt_tpu_ops_total", (("backend", backend), ("op", op))), 0)
            for op in ("encode", "encode-framed", "hash")}

    def nbytes(backend, op):
        return _metrics.GLOBAL.snapshot().get(
            ("mt_tpu_bytes_total", (("backend", backend), ("op", op))), 0)

    data = _body(total, 3)
    tpu = codec_for("tpu", BS)
    before, b0 = ops("tpu"), nbytes("tpu", "encode")
    tpu.encode_framed(data, bitrot.HIGHWAYHASH256S)
    after = ops("tpu")
    assert {op: after[op] - before[op] for op in after} == \
        {"encode": 1, "encode-framed": 0, "hash": hashes}
    assert nbytes("tpu", "encode") - b0 == len(data)
    host = codec_for("numpy", BS)
    before = ops("numpy")
    host.encode_framed(data, bitrot.HIGHWAYHASH256S)
    after = ops("numpy")
    assert {op: after[op] - before[op] for op in after} == \
        {"encode": 0, "encode-framed": 1, "hash": 0}


def test_encode_framed_whole_file_algo_is_unframed(codec_for):
    """A non-streaming bitrot algorithm stores the shards unframed on
    every backend (no digests to take from anywhere)."""
    data = _body(BS + 100, 5)
    stripes = _reference_stripes(data, BS)
    want = [b"".join(s[i].tobytes() for s in stripes)
            for i in range(K + M)]
    for backend in ("numpy", "tpu"):
        codec = codec_for(backend, BS)
        assert codec.framed_shape(len(data), bitrot.SHA256) is None
        rows = codec.encode_framed(data, bitrot.SHA256)
        assert [bytes(r) for r in rows] == want, backend


@pytest.mark.parametrize("lost", [
    [1],                            # one data shard lost
    [0, K],                         # m lost, a parity shard among them
], ids=["1-data-lost", "m-lost-parity-wanted"])
@pytest.mark.parametrize("total", [2 * BS, 777, 2 * BS + 777],
                         ids=["full-stripes", "tail-only", "both"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_reconstruct_files_matches_reference(codec_for, backend, total,
                                             lost):
    data = _body(total, total + 1)
    stripes = _reference_stripes(data, BS)
    files = [np.concatenate([s[i] for s in stripes])
             for i in range(K + M)]
    # gf8_ref.reconstruct, stripe by stripe, is the oracle
    want = {w: [] for w in lost}
    for s in stripes:
        holed = [None if i in lost else s[i] for i in range(K + M)]
        rebuilt = gf8_ref.reconstruct(holed, K, M)
        for w in lost:
            want[w].append(rebuilt[w])
    codec = codec_for(backend, BS)
    present = [i for i in range(K + M) if i not in lost][:K]
    key = ("mt_tpu_ops_total",
           (("backend", codec.backend), ("op", "matmul")))
    n0 = _metrics.GLOBAL.snapshot().get(key, 0)
    got = codec.reconstruct_files([files[i] for i in present], present,
                                  lost, total)
    assert len(got) == len(lost)
    for w, g in zip(lost, got):
        assert np.array_equal(g, np.concatenate(want[w])), (backend, w)
        assert np.array_equal(g, files[w])
    # dispatch accounting as before the seam moved: a device dispatch
    # per matmul (full stripes, tail), the host engine uncounted
    n_dispatches = (1 if total >= BS else 0) + (1 if total % BS else 0)
    counted = _metrics.GLOBAL.snapshot().get(key, 0) - n0
    assert counted == (n_dispatches if codec.is_device else 0)


def test_reconstruct_files_honours_the_objects_block_size(codec_for):
    """An object written under another block size than the layer's
    still reads: the layout follows ``block_size=``, the matrix the
    codec's geometry."""
    other = 2 * BS
    data = _body(other + 300, 9)
    stripes = _reference_stripes(data, other)
    files = [np.concatenate([s[i] for s in stripes])
             for i in range(K + M)]
    codec = codec_for("numpy", BS)
    present = [1, 2, 3, 4]
    got = codec.reconstruct_files([files[i] for i in present], present,
                                  [0], len(data), block_size=other)
    assert np.array_equal(got[0], files[0])
