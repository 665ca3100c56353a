"""A quorum metadata read's local drives in one native read wave
(``xl_storage.read_version_wave``, ``native/syncwave.c``
``mt_read_files``): for every drive the wave reads, the ``FileInfo`` or
the error is the one ``read_version`` gives through today's pool path on
the same drives, type and message; the drive's ``HealthDisk`` keeps its
rules; without the library, or with a group collector armed, every drive
takes the old path; ``mt_read_meta_drives_total{route}`` says which
route read how many drives; and each drive keeps its span and its drive
call observation.
"""

import errno
import os
import shutil

import pytest

from minio_tpu.admin.metrics import GLOBAL, KERNEL_BUCKETS
from minio_tpu.objectlayer.erasure_object import ErasureObjects
from minio_tpu.objectlayer.interface import ObjectNotFound, VersionNotFound
from minio_tpu.obs import trace
from minio_tpu.storage import commit, errors
from minio_tpu.storage import xl_storage as xl
from minio_tpu.storage.health import HealthDisk
from minio_tpu.storage.xl_meta import XLMeta
from minio_tpu.storage.xl_storage import META_FILE, XLStorage

N = 16
BKT = "wavebkt"

pytestmark = pytest.mark.skipif(commit._wave_lib() is None,
                                reason="native/syncwave.c cannot be built")


def _layer(tmp_path, health: bool = True, n: int = N, parity: int = 4):
    disks = []
    for i in range(n):
        d = tmp_path / f"d{i}"
        d.mkdir(parents=True)
        disk = XLStorage(str(d))
        disks.append(HealthDisk(disk, cooldown_s=60.0) if health else disk)
    layer = ErasureObjects(disks, parity=parity, block_size=64 * 1024,
                           backend="numpy")
    layer.make_bucket(BKT)
    return layer


def _meta_file(disk, key: str) -> str:
    return os.path.join(xl.wave_target(disk).root, BKT, key, META_FILE)


def _drives(s: dict) -> dict:
    return {route: s.get(("mt_read_meta_drives_total",
                          (("route", route),)), 0.0)
            for route in ("wave", "pool")}


def _routes(layer, fn) -> dict:
    before = _drives(GLOBAL.snapshot())
    fn()
    after = _drives(GLOBAL.snapshot())
    return {r: after[r] - before[r] for r in after}


def _pool_reads(disks, key, vid=None, volume=BKT) -> list:
    """Today's path: ``read_version`` through the drive's own call."""
    out = []
    for d in disks:
        try:
            out.append((d.read_version(volume, key, vid), None))
        except Exception as e:  # noqa: BLE001 — compared below
            out.append((None, e))
    return out


def _same(wave, pool) -> None:
    assert len(wave) == len(pool)
    for (fi, err, t0, t1), (pfi, perr) in zip(wave, pool):
        assert 0 < t0 <= t1
        assert fi == pfi
        assert type(err) is type(perr)
        assert str(err) == str(perr)


# name -> (key, version id or None, volume, what breaks before the read)
def _setup(case, layer):
    if case == "present":
        layer.put_object(BKT, "obj", os.urandom(70_000))
        return "obj", None, BKT
    if case == "packed":
        layer.put_object(BKT, "obj", os.urandom(300_000))
        return "obj", None, BKT
    if case == "missing-object":
        return "nothing-here", None, BKT
    if case == "missing-version":
        layer.put_object(BKT, "obj", b"x" * 100)
        return "obj", "00000000-0000-0000-0000-000000000001", BKT
    if case == "corrupt":
        layer.put_object(BKT, "obj", b"x" * 100)
        for d in layer.disks[:5]:
            with open(_meta_file(d, "obj"), "wb") as f:
                f.write(b"MTXL2\x00 not msgpack at all \xc1")
        with open(_meta_file(layer.disks[5], "obj"), "wb") as f:
            f.write(b"short")
        return "obj", None, BKT
    if case == "missing-volume":
        return "obj", None, "no-such-bucket"
    if case == "traversal":
        return "../../etc", None, BKT
    if case == "directory":
        layer.put_object(BKT, "obj", b"x" * 100)
        for d in layer.disks[:3]:
            os.remove(_meta_file(d, "obj"))
            os.mkdir(_meta_file(d, "obj"))
        return "obj", None, BKT
    if case == "not-a-directory":
        layer.put_object(BKT, "obj", b"x" * 100)
        return "obj/xl.meta/deeper", None, BKT
    raise AssertionError(case)


CASES = ["present", "packed", "missing-object", "missing-version",
         "corrupt", "missing-volume", "traversal", "directory",
         "not-a-directory"]


@pytest.mark.parametrize("case", CASES)
def test_the_wave_reads_what_read_version_reads(tmp_path, case):
    layer = _layer(tmp_path)
    key, vid, vol = _setup(case, layer)
    assert xl.wave_positions(layer.disks) == list(range(N))
    wave = xl.read_version_wave(layer.disks, vol, key, vid)
    _same(wave, _pool_reads(layer.disks, key, vid, vol))
    if case in ("present", "packed"):
        assert all(fi is not None for fi, *_ in wave)


@pytest.mark.parametrize("err,want", [
    (errno.ENOENT, errors.FileNotFound), (errno.EISDIR, errors.FileNotFound),
    (errno.EACCES, errors.FileAccessDenied),
    (errno.EPERM, errors.FileAccessDenied),
    (errno.ENOTDIR, NotADirectoryError), (errno.EIO, OSError)])
def test_an_errno_maps_as_read_all_maps_it(err, want):
    """What ``read_all`` raises for the ``OSError`` its open or read
    raised (root reads every file here, so EACCES cannot be staged)."""
    got = xl._read_error(err, "/drive/bkt/obj/xl.meta", "obj/xl.meta")
    assert type(got) is want
    if want is NotADirectoryError or want is OSError:
        assert str(got) == str(OSError(err, os.strerror(err),
                                       "/drive/bkt/obj/xl.meta"))
    else:
        assert str(got) == str(want("obj/xl.meta"))


def test_a_file_over_its_slot_is_read_again_in_full(tmp_path,
                                                    monkeypatch):
    """A file larger than its slot comes back marked; the plain path
    reads that one again, and the thread's slot grows for the next."""
    layer = _layer(tmp_path)
    layer.put_object(BKT, "big", os.urandom(100_000))   # inline
    size = os.path.getsize(_meta_file(layer.disks[0], "big"))
    monkeypatch.setattr(xl, "_SLOT_MIN", 1024)
    monkeypatch.setattr(xl, "_SLOT_MAX", 2048)
    monkeypatch.setattr(xl, "_WAVE_TLS", type(xl._WAVE_TLS)())
    assert size > 2048
    wave = xl.read_version_wave(layer.disks, BKT, "big")
    _same(wave, _pool_reads(layer.disks, "big"))
    assert xl._WAVE_TLS.cap == 2048
    # and a file that fits its slot exactly is read whole
    monkeypatch.setattr(xl, "_WAVE_TLS", type(xl._WAVE_TLS)())
    monkeypatch.setattr(xl, "_SLOT_MIN", size)
    wave = xl.read_version_wave(layer.disks, BKT, "big")
    _same(wave, _pool_reads(layer.disks, "big"))
    assert not hasattr(xl._WAVE_TLS, "cap")     # no file over its slot


@pytest.mark.parametrize("case", ["present", "missing-object",
                                  "missing-version", "corrupt"])
def test_the_quorum_read_picks_what_the_pool_picks(tmp_path, monkeypatch,
                                                   case):
    layer = _layer(tmp_path)
    key, vid, vol = _setup(case, layer)

    def quorum():
        try:
            fi, fis = layer._read_quorum_fileinfo(vol, key, vid)
            return fi, fis, None
        except Exception as e:  # noqa: BLE001 — compared below
            return None, None, e

    routes = _routes(layer, lambda: quorum())
    assert routes == {"wave": N, "pool": 0}
    fi, fis, err = quorum()
    monkeypatch.setattr(xl, "wave_positions", lambda disks: [])
    routes = _routes(layer, lambda: quorum())
    assert routes == {"wave": 0, "pool": N}
    pfi, pfis, perr = quorum()
    assert (fi, fis) == (pfi, pfis)
    assert type(err) is type(perr) and str(err) == str(perr)
    if case == "missing-object":
        assert isinstance(err, ObjectNotFound)
    if case == "missing-version":
        assert isinstance(err, VersionNotFound)


def test_an_offline_drive_in_its_cooldown_is_not_read(tmp_path):
    """The quorum read leaves an offline drive to the pool, whose call
    the breaker refuses inside its cooldown: nothing reads its file."""
    layer = _layer(tmp_path)
    layer.put_object(BKT, "obj", b"o" * 1000)
    down = layer.disks[3]
    down._mark_offline()
    assert xl.wave_positions(layer.disks) == [i for i in range(N) if i != 3]

    def reads():
        return down.inner.latency.totals().get("read_version", (0,))[0]

    before = reads()
    routes = _routes(layer, lambda: layer.get_object_info(BKT, "obj"))
    assert routes == {"wave": N - 1, "pool": 1}
    fi, fis = layer._read_quorum_fileinfo(BKT, "obj")
    assert fis[3] is None
    assert all(f is not None for i, f in enumerate(fis) if i != 3)
    assert reads() == before and down.offline
    with pytest.raises(errors.DiskNotFound):
        down.read_version(BKT, "obj")


def test_a_drive_that_disappears_is_judged_as_guard_judges_it(tmp_path):
    layer = _layer(tmp_path)
    twin = _layer(tmp_path / "twin")
    for lay in (layer, twin):
        lay.put_object(BKT, "obj", b"g" * 1000)
    here = xl.wave_positions(layer.disks)
    assert here == list(range(N))
    for lay in (layer, twin):
        shutil.rmtree(xl.wave_target(lay.disks[7]).root)
    wave = xl.read_version_wave([layer.disks[i] for i in here], BKT, "obj")
    pool = _pool_reads(twin.disks, "obj")
    assert isinstance(pool[7][1], errors.FileNotFound)
    assert type(wave[7][1]) is type(pool[7][1])
    assert layer.disks[7].offline and twin.disks[7].offline
    # a benign per-file error trips nothing
    assert not any(d.offline for i, d in enumerate(layer.disks) if i != 7)


def test_without_the_library_every_drive_takes_the_pool(tmp_path,
                                                        monkeypatch):
    layer = _layer(tmp_path)
    layer.put_object(BKT, "obj", b"n" * 1000)
    fi, fis = layer._read_quorum_fileinfo(BKT, "obj")
    monkeypatch.setattr(commit, "_wave_lib", lambda: None)
    assert xl.wave_positions(layer.disks) == []
    routes = _routes(layer, lambda: layer.get_object_info(BKT, "obj"))
    assert routes == {"wave": 0, "pool": N}
    assert layer._read_quorum_fileinfo(BKT, "obj") == (fi, fis)


def test_an_armed_collector_sees_its_pending_content(tmp_path,
                                                      monkeypatch):
    """A group collector armed on the calling thread sends every drive
    to the pool route; where that route runs on the calling thread (a
    one-core host's serial fan-out) the read sees the pending xl.meta."""
    layer = _layer(tmp_path, n=4, parity=2)
    layer.put_object(BKT, "obj", b"c" * 1000)
    disk = layer.disks[0]
    meta = XLMeta.load(open(_meta_file(disk, "obj"), "rb").read())
    meta.versions[0]["meta"] = dict(meta.versions[0].get("meta") or {},
                                    pending="yes")
    monkeypatch.setattr(layer, "_serial_fanout", True)
    col = commit.GroupCollector()
    col.pending_put(xl.wave_target(disk)._meta_path(BKT, "obj"),
                    meta.dump())
    commit.arm(col)
    try:
        assert xl.wave_positions(layer.disks) == []
        routes = _routes(layer, lambda: layer._read_quorum_fileinfo(
            BKT, "obj"))
        _, fis = layer._read_quorum_fileinfo(BKT, "obj")
    finally:
        commit.disarm()
    assert routes == {"wave": 0, "pool": 4}
    assert fis[0].metadata.get("pending") == "yes"
    assert all(f.metadata.get("pending") is None for f in fis[1:])
    _, fis = layer._read_quorum_fileinfo(BKT, "obj")
    assert fis[0].metadata.get("pending") is None


def test_a_head_reads_sixteen_local_drives_in_the_wave(tmp_path):
    for health in (True, False):
        layer = _layer(tmp_path / f"h{health}", health=health)
        layer.put_object(BKT, "obj", os.urandom(20_000))
        routes = _routes(layer, lambda: layer.get_object_info(BKT, "obj"))
        assert routes == {"wave": N, "pool": 0}, health


def test_a_mixed_set_waves_its_local_drives_and_pools_the_remote(tmp_path):
    from minio_tpu.parallel.rpc import RPCClient, RPCServer
    from minio_tpu.storage.remote import (RemoteStorage,
                                          register_storage_service)
    owned = {}
    for i in range(12):
        d = tmp_path / f"r{i}"
        d.mkdir()
        owned[f"r{i}"] = XLStorage(str(d))
    rpc = RPCServer("wave-secret")
    register_storage_service(rpc, owned)
    rpc.start()
    try:
        local = []
        for i in range(4):
            d = tmp_path / f"l{i}"
            d.mkdir()
            local.append(HealthDisk(XLStorage(str(d))))
        remote = [HealthDisk(RemoteStorage(
            RPCClient(rpc.endpoint, "wave-secret"), name))
            for name in owned]
        # the local drives sit among the remote ones, as node 2 of four
        disks = remote[:4] + local + remote[4:]
        layer = ErasureObjects(disks, parity=4, block_size=64 * 1024,
                               backend="numpy")
        layer.make_bucket(BKT)
        layer.put_object(BKT, "obj", os.urandom(30_000))
        assert xl.wave_positions(disks) == [4, 5, 6, 7]
        routes = _routes(layer, lambda: layer.get_object_info(BKT, "obj"))
        assert routes == {"wave": 4, "pool": 12}
        fi, fis = layer._read_quorum_fileinfo(BKT, "obj")
        assert all(f is not None and f.size == 30_000 for f in fis)
        assert sorted(f.erasure.index for f in fis) == list(range(1, 17))
    finally:
        rpc.stop()


def test_each_waved_drive_keeps_its_span_and_its_call(tmp_path):
    """The idle causal ring gets one ``storage.read_version`` tuple per
    drive under the request and its span parent, and the drive-call
    family one observation per drive, as a pool child's call gives."""
    layer = _layer(tmp_path)
    layer.put_object(BKT, "obj", os.urandom(20_000))

    def calls():
        return sum(h[len(KERNEL_BUCKETS)] for (name, labels, _), h
                   in GLOBAL.hist_snapshot().items()
                   if name == "mt_drive_call_seconds"
                   and dict(labels) == {"op": "read_version",
                                        "kind": "local"})

    assert not trace.active()
    before = calls()
    trace.set_request_id("wave-req-1")
    trace.set_span_parent("wave-parent-1")
    try:
        layer.get_object_info(BKT, "obj")
    finally:
        trace.set_request_id("")
        trace.set_span_parent("")
    assert calls() - before == N
    mine = [r for r in trace.SPANS.snapshot()
            if r[1] == "wave-req-1" and r[5] == "storage.read_version"]
    assert len(mine) == N
    assert {r[3] for r in mine} == {"wave-parent-1"}
    assert sorted(r[8] for r in mine) == sorted(
        d.endpoint() for d in layer.disks)
    assert all(r[6] >= 0 and r[7] == "" for r in mine)
