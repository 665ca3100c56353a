"""The multi-chip mesh wired INTO the object layer (VERDICT r4 #1).

backend="mesh" routes ErasureObjects' encode/reconstruct/heal matmuls
through parallel/mesh.distributed_* (via ops/rs_mesh) — these tests
prove PUT, degraded GET, and heal actually REACH the sharded kernels
on the virtual 8-device mesh and stay bit-identical with the numpy
oracle topology (cmd/erasure-encode.go:36-70 fan-out semantics).
"""

import os

import numpy as np
import pytest

from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.ops import rs_mesh
from minio_tpu.parallel import mesh as mesh_mod
from minio_tpu.storage.xl_storage import XLStorage

# slow: the full mesh dataplane (pallas interpret mode on a virtual
# 8-device CPU mesh) costs minutes of wall clock — fast-tier mesh
# coverage lives in test_mesh.py
pytestmark = pytest.mark.slow

K, M = 5, 3          # 8 drives: 5 data + 3 parity
BS = 128 * 1024


@pytest.fixture
def meshed(tmp_path):
    prev = mesh_mod._ACTIVE
    mesh_mod.set_active_mesh(mesh_mod.make_mesh(stripe=2))   # 2x4
    disks = []
    for i in range(8):
        d = tmp_path / f"d{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=M, block_size=BS,
                           backend="mesh")
    yield layer
    mesh_mod.set_active_mesh(prev)


@pytest.fixture
def counting(monkeypatch):
    """Count dispatches that reach the sharded mesh kernels — either
    engine (XLA psum path or the pallas+ppermute-ring path)."""
    calls = {"apply": 0, "fused": 0}
    real_apply = mesh_mod.distributed_apply
    real_pallas = rs_mesh._apply_pallas
    real_fused = mesh_mod._fused_encode_hash

    def apply_spy(*a, **kw):
        calls["apply"] += 1
        return real_apply(*a, **kw)

    def pallas_spy(*a, **kw):
        calls["apply"] += 1
        return real_pallas(*a, **kw)

    def fused_spy(*a, **kw):
        calls["fused"] += 1
        return real_fused(*a, **kw)

    real_fused_pallas = rs_mesh._encode_with_bitrot_pallas

    def fused_pallas_spy(*a, **kw):
        calls["fused"] += 1
        return real_fused_pallas(*a, **kw)

    monkeypatch.setattr(mesh_mod, "distributed_apply", apply_spy)
    monkeypatch.setattr(rs_mesh, "_apply_pallas", pallas_spy)
    monkeypatch.setattr(mesh_mod, "_fused_encode_hash", fused_spy)
    monkeypatch.setattr(rs_mesh, "_encode_with_bitrot_pallas",
                        fused_pallas_spy)
    # rs_mesh binds the module, not the function, so the spy is seen
    return calls


def test_put_reaches_fused_mesh_pipeline(meshed, counting):
    meshed.make_bucket("meshb")
    body = os.urandom(3 * BS + 12345)
    meshed.put_object("meshb", "obj", body)
    assert counting["fused"] >= 1, \
        "PUT did not route through the fused sharded encode"
    got = meshed.get_object("meshb", "obj")[1]
    assert bytes(got) == body


def test_degraded_get_reaches_mesh_reconstruct(meshed, counting, tmp_path):
    meshed.make_bucket("meshb")
    body = os.urandom(2 * BS + 999)
    meshed.put_object("meshb", "deg", body)
    # wipe M shard files = max erasures; GET must reconstruct via mesh
    wiped = 0
    for i in range(8):
        droot = tmp_path / f"d{i}" / "meshb" / "deg"
        if droot.exists() and wiped < M:
            import shutil
            shutil.rmtree(droot)
            wiped += 1
    assert wiped == M
    before = counting["apply"]
    got = meshed.get_object("meshb", "deg")[1]
    assert bytes(got) == body
    assert counting["apply"] > before, \
        "degraded GET did not route through the sharded reconstruct"


def test_heal_reaches_mesh_and_restores(meshed, counting, tmp_path):
    meshed.make_bucket("meshb")
    body = os.urandom(2 * BS + 31)
    meshed.put_object("meshb", "heal", body)
    import shutil
    victims = []
    for i in range(8):
        droot = tmp_path / f"d{i}" / "meshb" / "heal"
        if droot.exists() and len(victims) < 2:
            shutil.rmtree(droot)
            victims.append(i)
    assert len(victims) == 2
    before = counting["apply"]
    res = meshed.heal_object("meshb", "heal")
    assert counting["apply"] > before, \
        "heal did not route through the sharded reconstruct"
    for i in victims:
        assert (tmp_path / f"d{i}" / "meshb" / "heal").exists(), res
    # wipe DIFFERENT drives: the healed copies must decode
    for i in range(8):
        if i not in victims:
            droot = tmp_path / f"d{i}" / "meshb" / "heal"
            if droot.exists() and i < 3:
                shutil.rmtree(droot)
    got = meshed.get_object("meshb", "heal")[1]
    assert bytes(got) == body


def test_mesh_matches_numpy_oracle_on_disk(tmp_path):
    """Same object through mesh and numpy topologies -> bit-identical
    shard files (framing + digests + parity)."""
    prev = mesh_mod._ACTIVE
    mesh_mod.set_active_mesh(mesh_mod.make_mesh(stripe=2))
    try:
        rng = np.random.default_rng(7)
        body = bytes(rng.integers(0, 256, 2 * BS + 4321, dtype=np.uint8))
        layers = {}
        for be in ("mesh", "numpy"):
            disks = []
            for i in range(8):
                d = tmp_path / f"{be}{i}"
                d.mkdir()
                disks.append(XLStorage(str(d)))
            lay = ErasureObjects(disks, parity=M, block_size=BS,
                                 backend=be)
            lay.make_bucket("oraclebkt")
            lay.put_object("oraclebkt", "o", body)
            layers[be] = lay
        # compare every shard part file byte-for-byte (distribution is
        # keyed by (bucket,object) so drive order matches across layers)
        import glob
        for i in range(8):
            a = sorted(glob.glob(str(tmp_path / f"mesh{i}" / "oraclebkt" / "o" /
                                     "*" / "part.*")))
            b = sorted(glob.glob(str(tmp_path / f"numpy{i}" / "oraclebkt" / "o" /
                                     "*" / "part.*")))
            assert len(a) == len(b) == 1
            da = open(a[0], "rb").read()
            db = open(b[0], "rb").read()
            assert da == db, f"drive {i} shard file differs"
    finally:
        mesh_mod.set_active_mesh(prev)


def test_single_device_mesh_degenerate(tmp_path):
    """A 1-device mesh is the single-chip case: same code path, still
    correct (the degenerate end of SURVEY §2.3's scaling contract)."""
    import jax
    prev = mesh_mod._ACTIVE
    mesh_mod.set_active_mesh(
        mesh_mod.make_mesh(devices=jax.devices()[:1]))
    try:
        disks = []
        for i in range(4):
            d = tmp_path / f"s{i}"
            d.mkdir()
            disks.append(XLStorage(str(d)))
        lay = ErasureObjects(disks, parity=2, block_size=BS,
                             backend="mesh")
        lay.make_bucket("one")
        body = os.urandom(BS + 77)
        lay.put_object("one", "x", body)
        assert bytes(lay.get_object("one", "x")[1]) == body
    finally:
        mesh_mod.set_active_mesh(prev)


def test_rs_mesh_oracle_grid():
    """encode/reconstruct bit-identicality across geometries incl.
    k not divisible by the shard axis and B not divisible by stripe."""
    from minio_tpu.ops import gf8_ref
    prev = mesh_mod._ACTIVE
    mesh_mod.set_active_mesh(mesh_mod.make_mesh(stripe=2))
    try:
        rng = np.random.default_rng(3)
        for k, m in ((4, 2), (10, 3), (12, 4)):
            blocks = rng.integers(0, 256, (3, k, 257), dtype=np.uint8)
            want = np.stack([gf8_ref.encode_parity(b, m) for b in blocks])
            got = rs_mesh.encode_parity(blocks, m)
            assert np.array_equal(want, got), (k, m)
            # reconstruct dead data + parity (up to m erasures) via
            # the batch API
            full = np.concatenate([blocks, want], axis=1)
            dead = [0, 2, k][:m]
            present = [i for i in range(k + m) if i not in dead][:k]
            reb = rs_mesh.reconstruct_batch(
                full[:, present], present, dead, k, m)
            for j, w in enumerate(dead):
                assert np.array_equal(reb[:, j], full[:, w]), (k, m, w)
    finally:
        mesh_mod.set_active_mesh(prev)


def test_pallas_ring_engine_bit_identical(monkeypatch):
    """The TPU-default mesh engine: per-device fused pallas kernel +
    packed-byte XOR over a ppermute ring (GF(2) addition of packed
    parity IS XOR, so no int32 accumulator crosses ICI).  Forced on
    here (MT_PALLAS=1, interpret mode on CPU) and asserted
    bit-identical with the numpy oracle across geometries including
    ragged k/B/n."""
    from minio_tpu.ops import gf8_ref
    monkeypatch.setenv("MT_PALLAS", "1")
    prev = mesh_mod._ACTIVE
    mesh_mod.set_active_mesh(mesh_mod.make_mesh(stripe=2))
    try:
        rng = np.random.default_rng(11)
        for k, m, B, n in ((12, 4, 5, 1024), (10, 3, 2, 257),
                           (4, 2, 1, 640)):
            blocks = rng.integers(0, 256, (B, k, n), dtype=np.uint8)
            want = np.stack([gf8_ref.encode_parity(b, m)
                             for b in blocks])
            got = rs_mesh.encode_parity(blocks, m)
            assert np.array_equal(want, got), (k, m, B, n)
            full = np.concatenate([blocks, want], axis=1)
            dead = [0, 2, k][:m]
            present = [i for i in range(k + m)
                       if i not in dead][:k]
            reb = rs_mesh.reconstruct_batch(full[:, present], present,
                                            dead, k, m)
            for j, w in enumerate(dead):
                assert np.array_equal(reb[:, j], full[:, w]), (k, m, w)
        # fused engine: framed output vs the host oracle, bit for bit
        from minio_tpu.hashing import bitrot
        from minio_tpu.ops.codec import Erasure
        data = bytes(rng.integers(0, 256, BS + 4567, dtype=np.uint8))
        cod = Erasure(4, 2, BS, backend="numpy")
        host = cod.encode_object_framed(data)
        assert bitrot.fill_framed(host, cod.shard_size())
        got = rs_mesh.encode_object_framed_fused(4, 2, BS, data)
        assert np.array_equal(host, got), "fused pallas framed mismatch"
    finally:
        mesh_mod.set_active_mesh(prev)
