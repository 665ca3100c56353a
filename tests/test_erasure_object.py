"""Erasure object engine tests.

Mirrors the backend-generic object suite + fault-injection tiers of the
reference (SURVEY.md §4: cmd/object_api_suite_test.go,
cmd/erasure-object_test.go, cmd/erasure-healing_test.go) on tmp-dir drives.
Uses the numpy codec backend (bit-identical with the TPU path, which is
covered by tests/test_codec.py equivalence tests).
"""

import os

import numpy as np
import pytest

from minio_tpu.objectlayer import healing
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.objectlayer.interface import (BucketExists, BucketNotFound,
                                             InvalidRange, MethodNotAllowed,
                                             ObjectNotFound, ObjectOptions,
                                             PutObjectOptions,
                                             ReadQuorumError)
from minio_tpu.storage import errors as serrors
from minio_tpu.storage.faulty import BadDisk
from minio_tpu.storage.xl_storage import XLStorage

from . import shard_files

BS = 64 * 1024  # small block size so multi-stripe paths get exercised


def make_layer(tmp_path, n=6, parity=2, inline=128 * 1024, bs=BS):
    disks = []
    for i in range(n):
        d = tmp_path / f"disk{i}"
        d.mkdir(exist_ok=True)
        disks.append(XLStorage(str(d)))
    return ErasureObjects(disks, parity=parity, block_size=bs,
                          backend="numpy", inline_threshold=inline)


@pytest.fixture
def er(tmp_path):
    layer = make_layer(tmp_path)
    layer.make_bucket("bkt")
    return layer


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


# -- buckets ---------------------------------------------------------------

def test_bucket_lifecycle(tmp_path):
    er = make_layer(tmp_path)
    er.make_bucket("alpha")
    with pytest.raises(BucketExists):
        er.make_bucket("alpha")
    assert [b.name for b in er.list_buckets()] == ["alpha"]
    er.get_bucket_info("alpha")
    with pytest.raises(BucketNotFound):
        er.get_bucket_info("beta")
    er.delete_bucket("alpha")
    with pytest.raises(BucketNotFound):
        er.get_bucket_info("alpha")


# -- put/get round trips ---------------------------------------------------

@pytest.mark.parametrize("size", [0, 1, 100, BS - 1, BS, BS + 1,
                                  3 * BS + 17, 300 * 1024])
def test_put_get_roundtrip(er, size):
    data = _data(size, seed=size)
    oi = er.put_object("bkt", f"obj-{size}", data)
    assert oi.size == size
    info, got = er.get_object("bkt", f"obj-{size}")
    assert got == data
    assert info.etag == oi.etag
    assert er.get_object_info("bkt", f"obj-{size}").size == size


def test_get_range(er):
    data = _data(3 * BS + 100, seed=9)
    er.put_object("bkt", "obj", data)
    for off, ln in [(0, 10), (BS - 5, 10), (BS, BS), (2 * BS + 7, 93),
                    (0, len(data)), (len(data) - 1, 1)]:
        _, got = er.get_object("bkt", "obj", offset=off, length=ln)
        assert got == data[off:off + ln], (off, ln)
    with pytest.raises(InvalidRange):
        er.get_object("bkt", "obj", offset=len(data), length=1)


def test_get_missing(er):
    with pytest.raises(ObjectNotFound):
        er.get_object("bkt", "nope")
    with pytest.raises(BucketNotFound):
        er.get_object("missing-bucket", "obj")


def test_overwrite(er):
    er.put_object("bkt", "obj", b"first version")
    er.put_object("bkt", "obj", b"second version, longer")
    _, got = er.get_object("bkt", "obj")
    assert got == b"second version, longer"


# -- degraded reads (cmd/erasure-decode.go parallelReader semantics) -------

def test_read_with_offline_disks(tmp_path):
    er = make_layer(tmp_path, n=6, parity=2, inline=0)
    er.make_bucket("bkt")
    data = _data(2 * BS + 333, seed=1)
    er.put_object("bkt", "obj", data)
    # take 2 drives offline -> still readable (k=4 of 6)
    er.disks[1] = None
    er.disks[4] = None
    _, got = er.get_object("bkt", "obj")
    assert got == data
    # third failure exceeds parity -> read quorum error
    er.disks[2] = None
    with pytest.raises((ReadQuorumError, ObjectNotFound)):
        er.get_object("bkt", "obj")


def test_read_with_corrupt_shard(tmp_path):
    er = make_layer(tmp_path, n=4, parity=2, inline=0)
    er.make_bucket("bkt")
    data = _data(BS + 50, seed=2)
    er.put_object("bkt", "obj", data)
    # corrupt the shard on two drives, wherever each keeps it (this
    # size is packed into the drive's segment file: no part.N file)
    for disk in er.disks[:2]:
        before = shard_files.read_shard(disk, "bkt", "obj")
        shard_files.flip_byte(disk, "bkt", "obj", len(before) // 2)
        assert shard_files.read_shard(disk, "bkt", "obj") != before
    _, got = er.get_object("bkt", "obj")  # bitrot detected -> reconstruct
    assert got == data


def test_write_quorum_failure(tmp_path):
    er = make_layer(tmp_path, n=4, parity=2)
    er.make_bucket("bkt")
    # 4 drives, k=2, write quorum=2... kill 3 drives
    er.disks[0] = BadDisk()
    er.disks[1] = BadDisk()
    er.disks[2] = BadDisk()
    from minio_tpu.objectlayer.interface import WriteQuorumError
    with pytest.raises(WriteQuorumError):
        er.put_object("bkt", "obj", b"payload")


# -- delete + versioning ---------------------------------------------------

def test_delete_object(er):
    er.put_object("bkt", "obj", b"bytes")
    er.delete_object("bkt", "obj")
    with pytest.raises(ObjectNotFound):
        er.get_object("bkt", "obj")
    # idempotent
    er.delete_object("bkt", "obj")


def test_versioned_put_and_delete_marker(er):
    o1 = er.put_object("bkt", "obj", b"v1",
                       PutObjectOptions(versioned=True))
    o2 = er.put_object("bkt", "obj", b"v2",
                       PutObjectOptions(versioned=True))
    assert o1.version_id and o2.version_id and o1.version_id != o2.version_id
    _, got = er.get_object("bkt", "obj")
    assert got == b"v2"
    _, got = er.get_object("bkt", "obj",
                           opts=ObjectOptions(version_id=o1.version_id))
    assert got == b"v1"
    # delete without version -> delete marker; latest GET now fails
    dm = er.delete_object("bkt", "obj", ObjectOptions(versioned=True))
    assert dm.delete_marker and dm.version_id
    with pytest.raises(MethodNotAllowed):
        er.get_object("bkt", "obj")
    # old version still readable
    _, got = er.get_object("bkt", "obj",
                           opts=ObjectOptions(version_id=o1.version_id))
    assert got == b"v1"
    versions = er.list_object_versions("bkt", "obj")
    assert len(versions) == 3  # v1, v2, delete marker
    # remove the delete marker -> v2 is latest again
    er.delete_object("bkt", "obj", ObjectOptions(version_id=dm.version_id))
    _, got = er.get_object("bkt", "obj")
    assert got == b"v2"


# -- listing ---------------------------------------------------------------

def test_list_objects(er):
    for name in ["a/1.txt", "a/2.txt", "b/x/y.txt", "top.txt"]:
        er.put_object("bkt", name, b"c")
    out = er.list_objects("bkt")
    assert [o.name for o in out.objects] == \
        ["a/1.txt", "a/2.txt", "b/x/y.txt", "top.txt"]
    out = er.list_objects("bkt", prefix="a/")
    assert [o.name for o in out.objects] == ["a/1.txt", "a/2.txt"]
    out = er.list_objects("bkt", delimiter="/")
    assert out.prefixes == ["a/", "b/"]
    assert [o.name for o in out.objects] == ["top.txt"]
    out = er.list_objects("bkt", max_keys=2)
    assert out.is_truncated and len(out.objects) == 2


# -- healing (cmd/erasure-healing.go) --------------------------------------

def test_heal_missing_shard(tmp_path):
    er = make_layer(tmp_path, n=6, parity=2, inline=0)
    er.make_bucket("bkt")
    data = _data(2 * BS + 41, seed=3)
    er.put_object("bkt", "obj", data)
    # wipe the object from two drives entirely
    wiped = []
    for disk in er.disks[:2]:
        p = os.path.join(disk.root, "bkt", "obj")
        import shutil
        shutil.rmtree(p)
        wiped.append(disk.endpoint())
    res = healing.heal_object(er, "bkt", "obj")
    assert res.before_ok == 4 and res.after_ok == 6
    assert sorted(res.healed_disks) == sorted(wiped)
    # all drives now verify clean
    for disk in er.disks:
        fi = disk.read_version("bkt", "obj")
        disk.verify_file("bkt", "obj", fi)
    _, got = er.get_object("bkt", "obj")
    assert got == data


def test_heal_corrupt_shard_deep(tmp_path):
    er = make_layer(tmp_path, n=4, parity=2, inline=0)
    er.make_bucket("bkt")
    data = _data(BS + 5, seed=4)
    er.put_object("bkt", "obj", data)
    victim = er.disks[2]
    shard_files.flip_byte(victim, "bkt", "obj", -1, mask=1)
    with pytest.raises(serrors.FileCorrupt):
        victim.verify_file("bkt", "obj", victim.read_version("bkt", "obj"))
    res = healing.heal_object(er, "bkt", "obj", deep=True)
    assert res.after_ok == 4
    victim_fi = victim.read_version("bkt", "obj")
    victim.verify_file("bkt", "obj", victim_fi)  # healed clean


def test_heal_dangling(tmp_path):
    er = make_layer(tmp_path, n=4, parity=2, inline=0)
    er.make_bucket("bkt")
    er.put_object("bkt", "obj", _data(1000, seed=5))
    # destroy shards beyond repair (3 of 4 drives, k=2 -> 1 shard left)
    import shutil
    for disk in er.disks[:3]:
        shutil.rmtree(os.path.join(disk.root, "bkt", "obj"))
    res = healing.heal_object(er, "bkt", "obj", remove_dangling=True)
    assert res.dangling_purged
    with pytest.raises(ObjectNotFound):
        er.get_object_info("bkt", "obj")


def test_heal_inline_object(tmp_path):
    er = make_layer(tmp_path, n=4, parity=2)  # inline threshold default
    er.make_bucket("bkt")
    data = b"small inline payload"
    er.put_object("bkt", "obj", data)
    # wipe metadata from one drive
    import shutil
    shutil.rmtree(os.path.join(er.disks[1].root, "bkt", "obj"))
    res = healing.heal_object(er, "bkt", "obj")
    assert res.after_ok == 4
    _, got = er.get_object("bkt", "obj")
    assert got == data


def test_heal_delete_marker(tmp_path):
    er = make_layer(tmp_path, n=4, parity=2)
    er.make_bucket("bkt")
    er.put_object("bkt", "obj", b"x", PutObjectOptions(versioned=True))
    dm = er.delete_object("bkt", "obj", ObjectOptions(versioned=True))
    import shutil
    # drop all metadata on one disk
    shutil.rmtree(os.path.join(er.disks[0].root, "bkt", "obj"))
    res = healing.heal_object(er, "bkt", "obj", version_id=dm.version_id)
    assert res.after_ok == 4
    fi = er.disks[0].read_version("bkt", "obj", dm.version_id)
    assert fi.deleted


def test_ranged_read_fuzz_with_dead_disks(er):
    """Random offset/length reads against degraded sets — the
    cmd/erasure-decode_test.go:205 fuzz tier: every ranged read over any
    survivable failure pattern must return exactly data[off:off+ln]."""
    import numpy as np
    er.make_bucket("fuzzb")
    rng = np.random.default_rng(20260730)
    body = rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    er.put_object("fuzzb", "fz", body)
    saved = list(er.disks)
    n = len(saved)
    m = er.parity
    try:
        for trial in range(40):
            # random survivable failure pattern (0..m dead disks)
            dead = rng.choice(n, size=rng.integers(0, m + 1),
                              replace=False)
            er.disks = list(saved)
            for d in dead:
                er.disks[d] = None
            off = int(rng.integers(0, len(body)))
            ln = int(rng.integers(1, len(body) - off + 1))
            _, got = er.get_object("fuzzb", "fz", off, ln)
            assert got == body[off:off + ln], \
                f"trial {trial}: dead={dead} off={off} ln={ln}"
    finally:
        er.disks = saved


@pytest.mark.parametrize("algo", ["sha256", "blake2b",
                                  "highwayhash256"])
def test_whole_file_bitrot_algos_roundtrip(tmp_path, algo):
    """Non-streaming bitrot algorithms store shards unframed; both the
    inline (msgpack xl.meta) and striped paths must round-trip — a
    numpy row leaking out of streaming_encode_batch breaks msgpack
    serialization of inline data (regression)."""
    from minio_tpu.storage.xl_storage import XLStorage
    disks = []
    for i in range(6):
        d = tmp_path / f"wd{i}"
        d.mkdir()
        disks.append(XLStorage(str(d)))
    layer = ErasureObjects(disks, parity=2, block_size=BS,
                           backend="numpy", inline_threshold=4096,
                           bitrot_algo=algo)
    layer.make_bucket("wfb")
    small, big = _data(1000, seed=3), _data(3 * BS + 17, seed=4)
    layer.put_object("wfb", "inline-obj", small)     # inline path
    layer.put_object("wfb", "striped-obj", big)      # striped path
    _, got_small = layer.get_object("wfb", "inline-obj")
    _, got_big = layer.get_object("wfb", "striped-obj")
    assert bytes(got_small) == small
    assert bytes(got_big) == big
