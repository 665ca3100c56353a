"""Upstream's smallest erasure set through the object layer: 4 drives,
2+2, write quorum k + 1 (cmd/endpoint-ellipses.go:44,
cmd/format-erasure.go:896-906, cmd/erasure-object.go:631-642) — the
deployment of the benchmark's ``n4-ec2p2`` configuration, on the device
route (``backend="tpu"``: the XLA forms on XLA:CPU here,
tests/conftest.py).  Shard files against gf8_ref parity + the host
HighwayHash byte for byte, reads with every pair of drives gone, the
write quorum on both sides of its edge, heal of a wiped drive.

The block is 1 MiB, not the deployment's 10 MiB: the sizes keep their
places relative to it (inline, sub-block, block + 1, two blocks + tail)
and a 20 MiB body through the scan-form hash is the chip's to run.
"""

import itertools
import os
import shutil

import numpy as np
import pytest

from minio_tpu.objectlayer import healing
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.objectlayer.interface import WriteQuorumError
from minio_tpu.storage.xl_storage import XLStorage

from . import shard_files

K = M = 2
BS = 1 << 20
SIZES = [1, 3000, 131073, 1048577, 2 * BS + 7]
BUCKET = "geo"


def _body(n: int) -> bytes:
    return np.random.default_rng(n).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _reference_framed(data: bytes) -> list[bytes]:
    return shard_files.reference_framed(data, BS, K, M)


def _layer(disks) -> ErasureObjects:
    """Parity left to the drive count's default, as a server does."""
    return ErasureObjects(list(disks), block_size=BS, backend="tpu")


def _on_disk(disk, key: str) -> tuple[int, bytes]:
    """(shard index, framed shard bytes) as ``disk`` holds them."""
    fi = disk.read_version(BUCKET, key)
    framed = bytes(fi.inline_data) if fi.inline_data is not None \
        else shard_files.read_shard(disk, BUCKET, key)
    return fi.erasure.index - 1, framed


def _holders(disks, key: str) -> int:
    return sum(os.path.exists(os.path.join(d.root, BUCKET, key, "xl.meta"))
               for d in disks)


@pytest.fixture(scope="module")
def disks(tmp_path_factory):
    root = tmp_path_factory.mktemp("n4")
    out = []
    for i in range(K + M):
        d = root / f"d{i}"
        d.mkdir()
        out.append(XLStorage(str(d)))
    return out


@pytest.fixture(scope="module")
def er(disks):
    """One healthy set holding one object of every size."""
    layer = _layer(disks)
    layer.make_bucket(BUCKET)
    for n in SIZES:
        layer.put_object(BUCKET, f"obj-{n}", _body(n))
    return layer


def test_default_parity_and_write_quorum(er):
    assert (er.data_blocks, er.parity) == (K, M)
    assert er._write_quorum() == K + 1
    assert er._codec.backend == "tpu"


@pytest.mark.parametrize("size", SIZES)
def test_shard_files_equal_the_reference(er, disks, size):
    want = _reference_framed(_body(size))
    seen = set()
    for disk in disks:
        idx, framed = _on_disk(disk, f"obj-{size}")
        assert framed == want[idx], f"shard {idx} on {disk.root}"
        seen.add(idx)
    assert seen == set(range(K + M))
    fi = disks[0].read_version(BUCKET, f"obj-{size}")
    assert (fi.erasure.data_blocks, fi.erasure.parity_blocks,
            fi.erasure.block_size) == (K, M, BS)


@pytest.mark.parametrize("size", [3000, 2 * BS + 7], ids=["inline", "parts"])
@pytest.mark.parametrize("gone", list(itertools.combinations(range(4), 2)),
                         ids=lambda p: f"lost{p[0]}{p[1]}")
def test_get_survives_any_two_lost_drives(er, disks, gone, size):
    degraded = _layer(None if i in gone else d
                      for i, d in enumerate(disks))
    _, got = degraded.get_object(BUCKET, f"obj-{size}")
    assert bytes(got) == _body(size)


def test_put_with_one_drive_offline_lands_on_three(er, disks):
    data = _body(BS + 99)
    _layer([disks[0], None, disks[2], disks[3]]).put_object(
        BUCKET, "one-off", data)
    assert _holders(disks, "one-off") == K + 1
    assert bytes(er.get_object(BUCKET, "one-off")[1]) == data


def test_put_with_two_drives_offline_misses_the_write_quorum(disks):
    with pytest.raises(WriteQuorumError):
        _layer([disks[0], None, None, disks[3]]).put_object(
            BUCKET, "two-off", _body(BS + 99))


@pytest.mark.parametrize("size", SIZES)
def test_heal_restores_a_wiped_drive_byte_for_byte(er, disks, size):
    key = f"obj-{size}"
    victim = disks[size % len(disks)]
    before = _on_disk(victim, key)
    shutil.rmtree(os.path.join(victim.root, BUCKET, key))
    assert _holders(disks, key) == K + M - 1
    res = healing.heal_object(er, BUCKET, key)
    assert (res.before_ok, res.after_ok) == (K + M - 1, K + M)
    assert res.healed_disks == [victim.endpoint()]
    assert _on_disk(victim, key) == before
    assert before[1] == _reference_framed(_body(size))[before[0]]
