"""A GET's local shard reads in one native wave
(``xl_storage.read_shard_wave``, ``native/syncwave.c``
``mt_read_verify_ranges``): for every drive the wave reads, the payload
or the error is the one the pool route's child gives on the same drive
(its drive call, then ``bitrot.verify_extract``), bytes, type and
message; a GET that meets a bad and a missing shard still extends into
parity and hands the object to MRF; without the library, with O_DIRECT
reads on or with a group collector armed every drive takes the pool;
``mt_read_get_drives_total{route}`` says which route read how many
drives; and each waved drive keeps its drive call observation, its span
and its ``get.queue`` / ``get.verify`` legs.
"""

import errno
import os

import numpy as np
import pytest

from minio_tpu.admin.metrics import GLOBAL, KERNEL_BUCKETS
from minio_tpu.hashing import bitrot, highwayhash
from minio_tpu.objectlayer import metadata as meta
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.obs import trace
from minio_tpu.storage import commit, errors
from minio_tpu.storage import xl_storage as xl
from minio_tpu.storage.health import HealthDisk
from minio_tpu.storage.xl_storage import XLStorage

N, PARITY, K = 16, 4, 12
BS = 64 * 1024
HLEN = 32
BKT = "getwave"
# a part file of many blocks, a packed extent of several
SIZES = {"part": 3_000_123, "packed": 400_000, "inline": 50_000}

pytestmark = pytest.mark.skipif(
    commit._wave_lib() is None
    or highwayhash.verify_framed_address() is None,
    reason="native/syncwave.c or highwayhash.c cannot be built")


def _layer(tmp_path, health: bool = True, disks=None):
    if disks is None:
        disks = []
        for i in range(N):
            d = tmp_path / f"d{i}"
            d.mkdir(parents=True)
            disk = XLStorage(str(d))
            disks.append(HealthDisk(disk, cooldown_s=60.0) if health
                         else disk)
    layer = ErasureObjects(disks, parity=PARITY, block_size=BS,
                           backend="numpy")
    layer.hotread = None    # every GET reads its shards
    layer.make_bucket(BKT)
    return layer


def _routes(fn) -> dict:
    def drives():
        s = GLOBAL.snapshot()
        return {r: s.get(("mt_read_get_drives_total", (("route", r),)), 0.0)
                for r in ("wave", "pool")}

    before = drives()
    fn()
    after = drives()
    return {r: after[r] - before[r] for r in after}


def _window(fi, b0: int, b1: int) -> tuple[int, int, int]:
    """``(framed_off, framed_len, seg_len)`` of blocks [b0, b1) of the
    object's one part, as ``_stream_range`` computes them."""
    ssize = fi.erasure.shard_size()
    sfsize = fi.erasure.shard_file_size(fi.size)
    off = b0 * ssize
    seg_len = min(b1 * ssize, sfsize) - off
    return off + b0 * HLEN, seg_len + (b1 - b0) * HLEN, seg_len


def _item(disk, fi, framed_off: int):
    """What the GET's round hands the wave for this drive's shard."""
    dfi = disk.read_version(BKT, fi.name)
    if getattr(dfi, "seg", None):
        return ("read_segment", dfi.seg["sid"], dfi.seg["off"] + framed_off)
    return ("read_file_stream", BKT,
            f"{fi.name}/{dfi.data_dir}/part.1", framed_off)


def _pool_read(disk, item, framed_len: int, seg_len: int, ssize: int):
    """The pool route's child for one drive: its drive call, then the
    verify, a ``BitrotError`` raised as ``FileCorrupt``."""
    try:
        if item[0] == "read_segment":
            framed = disk.read_segment(item[1], item[2], framed_len)
        else:
            framed = disk.read_file_stream(*item[1:], framed_len)
        return bitrot.verify_extract(framed, ssize, seg_len), None
    except bitrot.BitrotError as e:
        return None, errors.FileCorrupt(str(e))
    except Exception as e:  # noqa: BLE001 — compared below
        return None, e


def _compare(disks, fi, b0: int, b1: int) -> list:
    """The wave and the pool route on every drive for blocks [b0, b1):
    the same payload bytes or the same error, type and message."""
    framed_off, framed_len, seg_len = _window(fi, b0, b1)
    ssize = fi.erasure.shard_size()
    items = [_item(d, fi, framed_off) for d in disks]
    wave = xl.read_shard_wave(disks, items, framed_len, seg_len, ssize)
    assert len(wave) == len(disks)
    for d, item, (row, err, t0, t1) in zip(disks, items, wave):
        prow, perr = _pool_read(d, item, framed_len, seg_len, ssize)
        assert 0 < t0 <= t1
        assert type(err) is type(perr), (item, err, perr)
        assert str(err) == str(perr)
        if perr is None:
            assert row.dtype == np.uint8 and row.flags["C_CONTIGUOUS"]
            assert row.tobytes() == prow.tobytes()
        else:
            assert row is None
    return wave


def _put(layer, form: str, key: str = "obj") -> tuple[bytes, object]:
    body = os.urandom(SIZES[form])
    layer.put_object(BKT, key, body)
    fi, _ = layer._read_quorum_fileinfo(BKT, key)
    return body, fi


def _nblocks(fi) -> int:
    return -(-fi.size // fi.erasure.block_size)


# window name -> blocks [b0, b1) of an object of n blocks
WINDOWS = {
    "full": lambda n: (0, n),
    "one-frame": lambda n: (0, 1),
    "tail": lambda n: (n - 1, n),
    "ranged": lambda n: (1, n - 1),
}


@pytest.mark.parametrize("window", sorted(WINDOWS))
@pytest.mark.parametrize("form", ["part", "packed"])
def test_the_wave_reads_what_the_pool_reads(tmp_path, form, window):
    layer = _layer(tmp_path)
    _, fi = _put(layer, form)
    n = _nblocks(fi)
    assert n > 3
    b0, b1 = WINDOWS[window](n)
    wave = _compare(layer.disks, fi, b0, b1)
    assert all(err is None for _, err, *_ in wave)


def _shard_of(layer, fi, index: int):
    """The drive holding erasure shard ``index`` (1-based) of ``fi``,
    and the path of its part file (None for a packed object)."""
    for d in layer.disks:
        dfi = d.read_version(BKT, fi.name)
        if dfi.erasure.index == index:
            root = xl.wave_target(d).root
            if getattr(dfi, "seg", None):
                return d, None
            return d, os.path.join(root, BKT, fi.name, dfi.data_dir,
                                   "part.1")
    raise AssertionError(index)


def _segment_of(d, fi) -> tuple[str, int]:
    dfi = d.read_version(BKT, fi.name)
    store = xl.wave_target(d).segments
    return store.file(dfi.seg["sid"]), dfi.seg["off"]


def _flip(path: str, at: int) -> None:
    with open(path, "r+b") as f:
        f.seek(at)
        b = f.read(1)
        f.seek(at)
        f.write(bytes([b[0] ^ 0xFF]))


# what breaks one drive's shard before the read
def _break(case, layer, fi, form):
    ssize = fi.erasure.shard_size()
    d, part = _shard_of(layer, fi, 3)
    if form == "part":
        if case == "missing":
            os.remove(part)
        elif case == "short":
            os.truncate(part, (ssize + HLEN) * 2 + 100)
        elif case == "flipped":
            # a payload byte of frame 2 (1-based)
            _flip(part, (ssize + HLEN) + HLEN + 10)
        elif case == "directory":
            os.remove(part)
            os.mkdir(part)
        elif case == "not-a-directory":
            ddir = os.path.dirname(part)
            for f in os.listdir(ddir):
                os.remove(os.path.join(ddir, f))
            os.rmdir(ddir)
            open(ddir, "wb").close()
        return
    seg, off = _segment_of(d, fi)
    if case == "missing":
        os.remove(seg)
    elif case == "short":
        os.truncate(seg, off + (ssize + HLEN) * 2 + 100)
    elif case == "flipped":
        _flip(seg, off + (ssize + HLEN) + HLEN + 10)


@pytest.mark.parametrize("form,case", [
    ("part", "missing"), ("part", "short"), ("part", "flipped"),
    ("part", "directory"), ("part", "not-a-directory"),
    ("packed", "missing"), ("packed", "short"), ("packed", "flipped")])
def test_a_broken_shard_fails_as_the_pool_fails(tmp_path, form, case):
    layer = _layer(tmp_path)
    _, fi = _put(layer, form)
    _break(case, layer, fi, form)
    wave = _compare(layer.disks, fi, 0, _nblocks(fi))
    bad = [err for _, err, *_ in wave if err is not None]
    assert bad
    if case == "flipped":
        assert {str(e) for e in bad} == {"content hash mismatch (block 2)"}
        assert all(type(e) is errors.FileCorrupt for e in bad)


@pytest.mark.parametrize("op,err,step,want", [
    ("read_file_stream", errno.ENOENT, 1, errors.FileNotFound),
    ("read_file_stream", errno.EACCES, 1, errors.FileAccessDenied),
    ("read_file_stream", errno.EPERM, 1, errors.FileAccessDenied),
    ("read_file_stream", errno.EACCES, 2, errors.FileAccessDenied),
    ("read_file_stream", errno.EIO, 2, OSError),
    ("read_file_stream", errno.ENOTDIR, 1, NotADirectoryError),
    ("read_segment", errno.ENOENT, 1, errors.FileNotFound),
    ("read_segment", errno.EACCES, 1, PermissionError),
    ("read_segment", errno.EIO, 2, OSError)])
def test_an_errno_maps_as_the_drive_call_maps_it(op, err, step, want):
    """What the drive call raises for the ``OSError`` its open or read
    raised (root reads every file here, so EACCES cannot be staged)."""
    full = "/drive/bkt/obj/dd/part.1"
    item = ("read_file_stream", BKT, "obj/dd/part.1", 0) \
        if op == "read_file_stream" else ("read_segment", 7, 4096)
    got = xl._shard_read_error(err, step, op, full, item, 1000)
    assert type(got) is want
    if want is errors.FileNotFound and op == "read_segment":
        assert str(got) == "segment 7"
    elif want in (errors.FileNotFound, errors.FileAccessDenied):
        assert str(got) == item[2]
    else:
        named = step == 1
        assert str(got) == str(OSError(err, os.strerror(err), full)
                               if named else OSError(err,
                                                     os.strerror(err)))
    assert xl._shard_read_error(0, 1000, op, full, item, 1000) is None


class _MRF:
    def __init__(self):
        self.added = []

    def add(self, bucket, object_name, version_id=None):
        self.added.append((bucket, object_name))


@pytest.mark.parametrize("form", ["part", "packed"])
def test_a_bad_and_a_missing_shard_are_read_through_parity(tmp_path, form):
    layer = _layer(tmp_path)
    layer.mrf = _MRF()
    body, fi = _put(layer, form)
    _break("flipped", layer, fi, form)        # data shard 3
    d, part = _shard_of(layer, fi, 5)         # data shard 5
    if part is not None:
        os.remove(part)
    else:
        os.remove(_segment_of(d, fi)[0])
    routes = _routes(lambda: layer.get_object(BKT, "obj"))
    assert layer.get_object(BKT, "obj")[1] == body
    # the first round reads k, loses two; the second reads two parity
    assert routes == {"wave": K + 2, "pool": 0}
    assert (BKT, "obj") in layer.mrf.added
    lo, n = 70_001, 123_457
    assert layer.get_object(BKT, "obj", lo, n)[1] == body[lo:lo + n]


@pytest.mark.parametrize("form", sorted(SIZES))
def test_a_get_reads_its_local_drives_in_the_wave(tmp_path, form):
    for health in (True, False):
        layer = _layer(tmp_path / f"h{health}", health=health)
        body, _ = _put(layer, form)
        routes = _routes(lambda: layer.get_object(BKT, "obj"))
        assert routes == {"wave": K, "pool": 0}, health
        assert layer.get_object(BKT, "obj")[1] == body
        # a streamed GET reads on its readahead producer
        info, gen = layer.get_object_reader(BKT, "obj")
        assert b"".join(gen) == body


@pytest.mark.parametrize("why", ["no-library", "no-frame-check", "odirect",
                                 "collector"])
def test_every_drive_takes_the_pool_where_the_wave_cannot(tmp_path,
                                                          monkeypatch, why):
    layer = _layer(tmp_path, health=False)
    body, _ = _put(layer, "part")
    if why == "no-library":
        monkeypatch.setattr(commit, "_wave_lib", lambda: None)
    elif why == "no-frame-check":
        monkeypatch.setattr(highwayhash, "verify_framed_address",
                            lambda: None)
    elif why == "odirect":
        monkeypatch.setattr(xl, "_ODIRECT", True)
    if why == "collector":
        commit.arm(commit.GroupCollector())
    try:
        assert xl.shard_wave_positions(layer.disks) == []
        routes = _routes(lambda: layer.get_object(BKT, "obj"))
        got = layer.get_object(BKT, "obj")[1]
    finally:
        if why == "collector":
            commit.disarm()
    assert routes == {"wave": 0, "pool": K}
    assert got == body


def test_an_offline_drive_is_left_to_the_pool(tmp_path):
    layer = _layer(tmp_path)
    body, _ = _put(layer, "part")
    shuffled_first = layer.disks.index(
        next(d for d in layer.disks
             if d.read_version(BKT, "obj").erasure.index == 1))
    layer.disks[shuffled_first]._mark_offline()
    routes = _routes(lambda: layer.get_object(BKT, "obj"))
    assert layer.get_object(BKT, "obj")[1] == body
    # shard 1's drive is a pool child its breaker refuses; one parity
    # shard makes up for it, in a second round of the wave
    assert routes == {"wave": K, "pool": 1}


def test_a_mixed_set_waves_its_local_drives_and_pools_the_remote(tmp_path):
    from minio_tpu.parallel.rpc import RPCClient, RPCServer
    from minio_tpu.storage.remote import (RemoteStorage,
                                          register_storage_service)
    owned = {}
    for i in range(12):
        d = tmp_path / f"r{i}"
        d.mkdir()
        owned[f"r{i}"] = XLStorage(str(d))
    rpc = RPCServer("getwave-secret")
    register_storage_service(rpc, owned)
    rpc.start()
    try:
        local = []
        for i in range(4):
            d = tmp_path / f"l{i}"
            d.mkdir()
            local.append(HealthDisk(XLStorage(str(d))))
        remote = [HealthDisk(RemoteStorage(
            RPCClient(rpc.endpoint, "getwave-secret"), name))
            for name in owned]
        # the local drives sit among the remote ones, as node 2 of four
        layer = _layer(tmp_path, disks=remote[:4] + local + remote[4:])
        for form in ("part", "packed"):
            body, fi = _put(layer, form, key=form)
            shuffled = meta.shuffle_disks(layer.disks,
                                          fi.erasure.distribution)
            waved = sum(1 for d in shuffled[:K] if d in local)
            assert 0 < waved < K
            routes = _routes(lambda: layer.get_object(BKT, form))
            assert routes == {"wave": waved, "pool": K - waved}, form
            assert layer.get_object(BKT, form)[1] == body
    finally:
        rpc.stop()


def _hist_count(name: str, **labels) -> float:
    return sum(h[len(KERNEL_BUCKETS)] for (n, ls, _), h
               in GLOBAL.hist_snapshot().items()
               if n == name and dict(ls) == labels)


def _hist_sum(name: str, **labels) -> float:
    return sum(h[len(KERNEL_BUCKETS) + 1] for (n, ls, _), h
               in GLOBAL.hist_snapshot().items()
               if n == name and dict(ls) == labels)


@pytest.mark.parametrize("form,op", [("part", "read_file_stream"),
                                     ("packed", "read_segment")])
def test_each_waved_drive_keeps_its_call_span_and_legs(tmp_path, form, op):
    """Per waved drive: one ``mt_drive_call_seconds{op,kind=local}``
    observation, one last-minute window sample, one ``storage.<op>``
    ring tuple and one ``get.verify`` tuple under the request and its
    span parent, one ``get.queue`` sample; the sampled verify CPU never
    reads over its wall."""
    layer = _layer(tmp_path)
    body, _ = _put(layer, form)
    legs = ("mt_read_leg_seconds", "mt_read_leg_cpu_seconds")
    before = {
        "calls": _hist_count("mt_drive_call_seconds", op=op, kind="local"),
        "queue": _hist_count(legs[0], op="get", leg="queue"),
        "verify": _hist_count(legs[0], op="get", leg="verify"),
        "window": sum(xl.wave_target(d).latency.totals().get(op, (0,))[0]
                      for d in layer.disks)}
    # enough GETs that the one-in-CPU_SAMPLE_EVERY sampling reads the
    # CPU clock on some of the verifies
    gets = 3
    assert not trace.active()
    trace.set_request_id(f"getwave-{op}")
    trace.set_span_parent("getwave-parent-1")
    try:
        for _ in range(gets):
            assert layer.get_object(BKT, "obj")[1] == body
    finally:
        trace.set_request_id("")
        trace.set_span_parent("")
    assert _hist_count("mt_drive_call_seconds", op=op, kind="local") \
        - before["calls"] == gets * K
    assert _hist_count(legs[0], op="get", leg="queue") - before["queue"] \
        == gets * K
    assert _hist_count(legs[0], op="get", leg="verify") - before["verify"] \
        == gets * K
    assert sum(xl.wave_target(d).latency.totals().get(op, (0,))[0]
               for d in layer.disks) - before["window"] == gets * K
    ring = [r for r in trace.SPANS.snapshot() if r[1] == f"getwave-{op}"]
    calls = [r for r in ring if r[5] == f"storage.{op}"]
    verifies = [r for r in ring if r[5] == "get.verify"]
    assert len(calls) == len(verifies) == gets * K
    assert {r[3] for r in calls + verifies} == {"getwave-parent-1"}
    assert all(r[6] >= 0 and r[7] == "" for r in calls + verifies)
    k = _hist_count(legs[1], op="get", leg="verify", clock="cpu")
    assert k >= 1
    assert k == _hist_count(legs[1], op="get", leg="verify", clock="wall")
    cpu = _hist_sum(legs[1], op="get", leg="verify", clock="cpu")
    wall = _hist_sum(legs[1], op="get", leg="verify", clock="wall")
    assert 0 <= cpu <= wall + 0.001 * k


def test_a_corrupt_frame_is_a_verify_error_and_no_drive_error(tmp_path):
    """The drive read its window: its call records no error and its
    breaker is not consulted for it; the verify carries the error."""
    layer = _layer(tmp_path)
    _, fi = _put(layer, "part")
    _break("flipped", layer, fi, "part")
    d, _ = _shard_of(layer, fi, 3)
    trace.set_request_id("getwave-req-2")
    try:
        _compare(layer.disks, fi, 0, _nblocks(fi))
    finally:
        trace.set_request_id("")
    ring = [r for r in trace.SPANS.snapshot() if r[1] == "getwave-req-2"]
    errs = {r[5]: r[7] for r in ring if r[7]}
    assert errs == {"get.verify":
                    "BitrotError: content hash mismatch (block 2)"}
    assert not d.offline
