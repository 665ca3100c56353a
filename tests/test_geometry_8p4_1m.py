"""Upstream's default parity for a 12-drive set at its 1 MiB erasure
block: 12 drives, 8+4 (EC:4, cmd/format-erasure.go:896-906), write
quorum 8 — the deployment of the benchmark's ``n12-ec8p4-1m``
configuration, on the device route (``backend="tpu"``: the XLA forms on
XLA:CPU here, tests/conftest.py).  A body of several 1 MiB blocks goes
to the device as stripe groups (ten stripes of 8+4 per program), so the
sizes here are one block, ten (one whole group), ten with a tail, and
twenty-five (two groups and a partial one).  Shard files against the
plain reference byte for byte, read back whole, and rebuilt with four
drives' object directories gone.
"""

import os
import shutil

import numpy as np
import pytest

from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.ops import rs_fused
from minio_tpu.storage.xl_storage import XLStorage

from . import shard_files

K, M = 8, 4
BS = 1 << 20
SIZES = [BS, 10 * BS, 10 * BS + 70001, 25 * BS]
BUCKET = "geo"


def _body(n: int) -> bytes:
    return np.random.default_rng(n).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _layer(disks) -> ErasureObjects:
    """Parity left to the drive count's default, as a server does."""
    return ErasureObjects(list(disks), block_size=BS, backend="tpu")


def _on_disk(disk, key: str) -> tuple[int, bytes]:
    """(shard index, framed shard bytes) as ``disk`` holds them."""
    fi = disk.read_version(BUCKET, key)
    return fi.erasure.index - 1, shard_files.read_shard(disk, BUCKET, key)


@pytest.fixture(scope="module")
def disks(tmp_path_factory):
    root = tmp_path_factory.mktemp("n12")
    out = []
    for i in range(K + M):
        d = root / f"d{i:02d}"
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


def test_default_parity_write_quorum_and_group(er):
    assert (er.data_blocks, er.parity) == (K, M)
    assert er._write_quorum() == K
    assert er._codec.backend == "tpu"
    # a 1 MiB block is 8 shards of 131,072 B, a multiple of the lane
    # tile; ten stripes of 12 shards fill 120 of one row's 128 lanes
    assert er._codec.shard_size() == 131072
    p = rs_fused.group_plan(K, M, 131072)
    assert (p["bs"], p["S"], p["n_pad"]) == (10, 1, 131072)


@pytest.mark.parametrize("size", SIZES)
def test_shard_files_equal_the_reference(er, disks, size):
    want = shard_files.reference_framed(_body(size), BS, K, M)
    seen = set()
    for disk in disks:
        idx, framed = _on_disk(disk, f"obj-{size}")
        assert framed == want[idx], f"shard {idx} on {disk.root}"
        seen.add(idx)
    assert seen == set(range(K + M))
    fi = disks[0].read_version(BUCKET, f"obj-{size}")
    assert (fi.erasure.data_blocks, fi.erasure.parity_blocks,
            fi.erasure.block_size) == (K, M, BS)


@pytest.mark.parametrize("size", SIZES)
def test_reads_back_byte_exact(er, size):
    _, got = _layer(er.disks).get_object(BUCKET, f"obj-{size}")
    assert bytes(got) == _body(size)


@pytest.mark.parametrize("gone", [(0, 1, 2, 3), (8, 9, 10, 11),
                                  (1, 4, 7, 10)],
                         ids=lambda g: "lost" + "-".join(map(str, g)))
def test_get_rebuilds_the_body_with_four_object_dirs_gone(er, disks, gone):
    """``gone`` are shard indices: four data shards, the four parity
    shards, or two of each; the drives that hold them lose the object's
    directory and a GET through a fresh layer returns the body."""
    key = "lost-" + "-".join(map(str, gone))
    body = _body(10 * BS + 70001)
    er.put_object(BUCKET, key, body)
    holders = {_on_disk(d, key)[0]: d for d in disks}
    for idx in gone:
        shutil.rmtree(os.path.join(holders[idx].root, BUCKET, key))
    _, got = _layer(disks).get_object(BUCKET, key)
    assert bytes(got) == body
